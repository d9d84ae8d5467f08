"""Plain float32 reference of the decoder language model ``map_score_lm``
serves under ``mixer: sparse_mla`` (configuration ``deepseek-v3.2``): latent
attention under a learned top-k key selection, and expert layers of which one
chip's share is held. Straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``: no kernel, no cache, no segments;
the whole document's index scores a block of queries at a time, ``lax.top_k``,
a softmax over the keys it returns. It imports nothing of the program and
takes nothing the program made: the weights come from the model id by the
rule the configuration's ``assumed.weights`` states, written out again here
(layer by layer and expert by expert: 4.6 G parameters in float32 do not fit
at once), rounded once to bf16 and used in float32. What a language-model
reference needs whatever its mixer (the key from the model id, the blocked
head, the block sums and ``compare``) is ``retention_lm.py``'s.

Equations (x_t a token's residual, h = RMSNorm(x), eps ``rms_norm_eps``;
after the public inference code of the DeepSeek-V3.2-Exp release):

    cQ = RMSNorm(h W_DQ)      q_a = cQ W_UQ,a = [q_nope; RoPE(q_rope)]
    [cKV; kR] = h W_DKV       cKV <- RMSNorm(cKV)    kR <- RoPE(kR)
    qI_j = cQ W_IQ,j   kI = LayerNorm(h W_IK)   (RoPE on the FIRST rope dims)
    w = h W_IW * Hi^-0.5 * Di^-0.5
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])                   s <= t
    S_t = the index_topk keys of largest I[t, .] (all while t < index_topk;
          ties to the lower index)
    k_{s,a} = [cKV_s W_UK,a; kR_s]       v_{s,a} = cKV_s W_UV,a
    o_{t,a} = sum_{s in S_t} softmax_{S_t}(scale q_{t,a} . k_{s,a}) v_{s,a}
    u = x + concat_a(o_a) W_O

computed in the ABSORBED form over the gathered latents (q_nope W_UK^T
against cKV, the weighted sum of cKV through W_UV): equal to the above by
associativity, and the only form whose float32 intermediates fit a 32,768-
token document. RoPE is YaRN's; latent attention rotates pairs (2i, 2i + 1),
the indexer pairs (i, i + rope/2). Then, on n = RMSNorm(u): a dense layer
adds SwiGLU(n); an expert layer adds

    SwiGLU_shared(n) + sum_{e chosen, HELD HERE} g_e SwiGLU_e(n)
    s_e = sigmoid(n . W_R,e), choice by s_e + b_e over n_experts: groups of
    n_experts / n_expert_groups, a group's score the sum of its two largest,
    the n_groups_per_token best groups, the n_experts_per_token best experts
    among them; g_e = routed_scale s_e / sum_chosen s

(the experts held: ids ``expert_first`` .. ``+ n_experts_held``; what the
others would add is left out, as in the program). A final RMSNorm and an
untied head over the ``vocab_size`` rows held.

``attend``: ``"selected"`` (the model), ``"causal"`` (every causal key: what
the layer would be WITHOUT the selection, for the tests' proof that the check
sees the mechanism), or a list with one boolean [L, L] array a layer (a
selection made elsewhere: the served one, to tell how much of a gap the
selection's near-ties carry)."""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Sequence

import numpy as np

from benchmarks.harness import manifest

_lm = manifest.load_reference("retention_lm")
seed_key, head_logprobs = _lm.seed_key, _lm.head_logprobs
block_sums, block_counts = _lm.block_sums, _lm.block_counts

LEAVES = ("embed", "head", "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up",
          "w_down", "w_dq", "w_uq", "w_dkv", "w_ukv", "wi_q", "wi_k", "wi_w",
          "w_router", "ws_gate", "ws_up", "ws_down", "we_gate", "we_up",
          "we_down")
ATTENTION = ("wo", "w_dq", "w_uq", "w_dkv", "w_ukv", "wi_q", "wi_k", "wi_w")
DENSE = ("w_gate", "w_up", "w_down")
SHARED = ("w_router", "ws_gate", "ws_up", "ws_down")
EXPERT = ("we_gate", "we_up", "we_down")
LOSS_BLOCK = 1024
QUERY_BLOCK = 128
# Runs of query blocks, each held against its own prefix of the keys.
KEY_SPANS = 4
# Rows of one expert's tokens are padded to a multiple of this (few shapes).
ROW_BUCKET = 512


def leaf_shape(cfg: Mapping[str, Any], name: str):
    """(shape, fan_in) of one layer's leaf (one expert's), or of a whole
    unlayered leaf."""
    g = lambda k: int(cfg[k])  # noqa: E731
    d, h = g("d_model"), g("n_heads")
    qr, kvr = g("q_lora_rank"), g("kv_lora_rank")
    dn, dr, dv = g("qk_nope_head_dim"), g("qk_rope_head_dim"), g("v_head_dim")
    fe = g("d_expert")
    fs = fe * g("n_shared_experts")
    return {
        "embed": ((g("vocab_size"), d), 1), "head": ((g("vocab_size"), d), d),
        "wo": ((h * dv, d), h * dv), "w_dq": ((d, qr), d),
        "w_uq": ((qr, h * (dn + dr)), qr), "w_dkv": ((d, kvr + dr), d),
        "w_ukv": ((kvr, h * (dn + dv)), kvr),
        "wi_q": ((qr, g("index_n_heads") * g("index_head_dim")), qr),
        "wi_k": ((d, g("index_head_dim")), d), "wi_w": ((d, g("index_n_heads")), d),
        "w_gate": ((d, g("d_ff")), d), "w_up": ((d, g("d_ff")), d),
        "w_down": ((g("d_ff"), d), g("d_ff")),
        "w_router": ((d, g("n_experts")), d),
        "ws_gate": ((d, fs), d), "ws_up": ((d, fs), d), "ws_down": ((fs, d), fs),
        "we_gate": ((d, fe), d), "we_up": ((d, fe), d), "we_down": ((fe, d), fe),
    }[name]


_DRAW: Dict[Any, Any] = {}


def draw(cfg: Mapping[str, Any], model_id: str, name: str, layer=None,
         expert=None):
    """One leaf as the configuration defines it: normal(key) / sqrt(fan_in)
    in float32, rounded once to the stored dtype and kept in it. Key:
    fold_in(root, index of the leaf), then fold_in(., layer), then
    fold_in(., expert id among all the router's experts)."""
    import jax
    import jax.numpy as jnp

    shape, fan_in = leaf_shape(cfg, name)
    dtype = jnp.dtype(str(cfg.get("dtype", "bfloat16")))
    sig = (shape, fan_in, str(dtype))
    if sig not in _DRAW:
        _DRAW[sig] = jax.jit(lambda key: (
            jax.random.normal(key, shape, dtype=jnp.float32)
            * (1.0 / np.sqrt(max(1, fan_in)))).astype(dtype))
    key = jax.random.fold_in(seed_key(model_id), LEAVES.index(name))
    for index in (layer, expert):
        if index is not None:
            key = jax.random.fold_in(key, int(index))
    return _DRAW[sig](key)


# ---- the mathematics -----------------------------------------------------

def rms_norm(x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def layer_norm(x, eps=1e-6):
    import jax.numpy as jnp

    mean = x.mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(
        jnp.square(x - mean).mean(axis=-1, keepdims=True) + eps)


def yarn_inv_freq(cfg: Mapping[str, Any]) -> np.ndarray:
    """1 / theta^(2i/dim), and under YaRN (max_len beyond the original
    length): divided by ``rope_factor`` above the ``beta_slow`` correction
    dimension, untouched below the ``beta_fast`` one, a linear ramp between."""
    dim, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor, orig = float(cfg.get("rope_factor", 1.0)), int(
        cfg.get("rope_original_max_len", 4096))
    if factor == 1.0 or int(cfg["max_len"]) <= orig:
        return inv
    corr = lambda rot: dim * np.log(orig / (rot * 2 * np.pi)) / (  # noqa: E731
        2 * np.log(base))
    low = max(int(np.floor(corr(float(cfg.get("rope_beta_fast", 32))))), 0)
    high = min(int(np.ceil(corr(float(cfg.get("rope_beta_slow", 1))))), dim - 1)
    top = high + 0.001 if high == low else high
    ramp = np.clip((np.arange(dim // 2) - low) / (top - low), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


def softmax_scale(cfg: Mapping[str, Any]) -> float:
    scale = (int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])) ** -0.5
    factor = float(cfg.get("rope_factor", 1.0))
    if factor != 1.0 and int(cfg["max_len"]) > int(
            cfg.get("rope_original_max_len", 4096)):
        scale *= (0.1 * float(cfg.get("rope_mscale", 1.0)) * np.log(factor)
                  + 1.0) ** 2
    return float(scale)


def rotate(x, positions, inv_freq, interleaved: bool):
    """x [L, ..., D]: pairs (2i, 2i + 1) if ``interleaved`` else
    (i, i + D/2), pair i turned by position * inv_freq[i]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(x.shape)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention_layer(cfg, w, x, attend="selected", keep=None,
                    query_block=QUERY_BLOCK):
    """x [L, d] float32 → u = x + attention(RMSNorm(x)) W_O. ``keep``: a
    boolean [L, L] selection made elsewhere (``attend`` then unused)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    w = {k: jnp.asarray(a).astype(f32) for k, a in w.items()}
    L = x.shape[0]
    g = lambda k: int(cfg[k])  # noqa: E731
    h, kvr = g("n_heads"), g("kv_lora_rank")
    dn, dr, dv = g("qk_nope_head_dim"), g("qk_rope_head_dim"), g("v_head_dim")
    hi, di = g("index_n_heads"), g("index_head_dim")
    eps, scale = float(cfg["rms_norm_eps"]), softmax_scale(cfg)
    inv = yarn_inv_freq(cfg)
    n_keep = L if attend == "causal" or keep is not None else min(
        g("index_topk"), L)

    pos = jnp.arange(L)
    hn = rms_norm(x, eps)
    latent = hn @ w["w_dkv"]
    ckv = rms_norm(latent[:, :kvr], eps)                          # [L, kvr]
    k_rope = rotate(latent[:, kvr:], pos, inv, True)              # [L, dr]
    ki = layer_norm(hn @ w["wi_k"])
    ki = jnp.concatenate([rotate(ki[:, :dr], pos, inv, False), ki[:, dr:]], -1)
    w_uk = w["w_ukv"].reshape(kvr, h, dn + dv)[..., :dn]
    w_uv = w["w_ukv"].reshape(kvr, h, dn + dv)[..., dn:]

    bq = min(int(query_block), L)
    n_blocks = -(-L // bq)
    pad = n_blocks * bq - L

    def block(args, n_keys):
        """A block of queries against the document's first ``n_keys`` keys
        (none of the block's queries lies past them)."""
        xb, t, keep_b = args
        t = jnp.minimum(t, L - 1)        # padding queries: any real position
        hb = rms_norm(xb, eps)
        cq = rms_norm(hb @ w["w_dq"], eps)
        q = (cq @ w["w_uq"]).reshape(bq, h, dn + dr)
        q_rope = rotate(q[..., dn:], t, inv, True)
        causal = pos[None, :n_keys] <= t[:, None]
        if keep_b is not None:
            score = jnp.where(keep_b[:, :n_keys], 1.0, -jnp.inf)
        elif attend == "causal":
            score = jnp.where(causal, 0.0, -jnp.inf)
        else:
            qi = (cq @ w["wi_q"]).reshape(bq, hi, di)
            qi = jnp.concatenate([rotate(qi[..., :dr], t, inv, False),
                                  qi[..., dr:]], -1)
            wi = (hb @ w["wi_w"]) * (hi ** -0.5 * di ** -0.5)
            score = jnp.einsum("tj,tjs->ts", wi, jax.nn.relu(
                jnp.einsum("tjd,sd->tjs", qi, ki[:n_keys])))
            score = jnp.where(causal, score, -jnp.inf)
        top, idx = jax.lax.top_k(score, min(n_keep, n_keys))     # [bq, K]
        c_sel, r_sel = ckv[idx], k_rope[idx]                     # [bq, K, .]
        q_abs = jnp.einsum("thn,chn->thc", q[..., :dn], w_uk)
        s = scale * (jnp.einsum("thc,tkc->thk", q_abs, c_sel)
                     + jnp.einsum("thr,tkr->thk", q_rope, r_sel))
        p = jax.nn.softmax(jnp.where((top > -jnp.inf)[:, None, :], s,
                                     -jnp.inf), axis=-1)
        o = jnp.einsum("thc,chv->thv", jnp.einsum("thk,tkc->thc", p, c_sel),
                       w_uv)
        return xb + o.reshape(bq, h * dv) @ w["wo"]

    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(n_blocks, bq, -1)
    ts = jnp.arange(n_blocks * bq).reshape(n_blocks, bq)
    kp = None if keep is None else jnp.pad(
        jnp.asarray(keep), ((0, pad), (0, 0))).reshape(n_blocks, bq, L)
    # The blocks in KEY_SPANS runs: a run's queries see no key past the
    # run's end, so it is held against that prefix of the keys alone (the
    # scores of a later key would be masked anyway; this only saves work).
    out, per_run = [], -(-n_blocks // KEY_SPANS)
    for first in range(0, n_blocks, per_run):
        last = min(first + per_run, n_blocks)
        n_keys = min(last * bq, L)
        mine = (xp[first:last], ts[first:last])
        if kp is None:
            out.append(jax.lax.map(
                lambda a, n=n_keys: block((*a, None), n), mine))
        else:
            out.append(jax.lax.map(
                lambda a, n=n_keys: block(a, n), (*mine, kp[first:last])))
    return jnp.concatenate(out).reshape(n_blocks * bq, -1)[:L]


def swiglu(n, gate, up, down):
    import jax

    return (jax.nn.silu(n @ gate) * (n @ up)) @ down


def route(cfg, n, w_router):
    """n [L, d] → (experts [L, k], gates [L, k]): sigmoid scores, the
    group-limited choice (bias 0), gates normalised over the chosen."""
    import jax
    import jax.numpy as jnp

    E, G = int(cfg["n_experts"]), int(cfg["n_expert_groups"])
    k = int(cfg["n_experts_per_token"])
    s = jax.nn.sigmoid(n @ w_router)                              # [L, E]
    choice = s
    if G > 1:
        grouped = s.reshape(-1, G, E // G)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)        # [L, G]
        best = jax.lax.top_k(group_score, int(cfg["n_groups_per_token"]))[1]
        kept = jnp.zeros_like(group_score, dtype=bool).at[
            jnp.arange(s.shape[0])[:, None], best].set(True)
        choice = jnp.where(jnp.repeat(kept, E // G, axis=1), s, -jnp.inf)
    experts = jax.lax.top_k(choice, k)[1]
    picked = jnp.take_along_axis(s, experts, axis=1)
    return experts, float(cfg["routed_scale"]) * picked / picked.sum(
        -1, keepdims=True)


_JIT: Dict[Any, Any] = {}


def _jitted(name, fn, cfg, *static):
    import jax

    key = (name, static, tuple(sorted((k, str(v)) for k, v in cfg.items())))
    if key not in _JIT:
        _JIT[key] = jax.jit(fn)
    return _JIT[key]


def expert_layer_ffn(cfg, model_id, layer, u):
    """u [L, d] → u + shared expert + the held experts' gated outputs. One
    expert's weights exist at a time; an expert sees only the rows routed to
    it (padded to ``ROW_BUCKET``)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(cfg["rms_norm_eps"])
    shared = {name: draw(cfg, model_id, name, layer).astype(f32)
              for name in SHARED}
    n = rms_norm(u, eps)
    experts, gates = _jitted("route", lambda n, w: route(cfg, n, w), cfg)(
        n, shared["w_router"])
    out = u + _jitted("swiglu", swiglu, cfg)(
        n, shared["ws_gate"], shared["ws_up"], shared["ws_down"])
    del shared
    experts, gates = np.asarray(experts), np.asarray(gates)
    first = int(cfg.get("expert_first", 0))
    for e in range(first, first + int(cfg["n_experts_held"])):
        rows, slot = np.nonzero(experts == e)
        if not len(rows):
            continue
        padded = -(-len(rows) // ROW_BUCKET) * ROW_BUCKET
        take = np.zeros((padded,), np.int32)
        take[:len(rows)] = rows
        g = np.zeros((padded,), np.float32)
        g[:len(rows)] = gates[rows, slot]
        w = [draw(cfg, model_id, name, layer, e).astype(f32) for name in EXPERT]
        y = _jitted("swiglu", swiglu, cfg)(n[jnp.asarray(take)], *w)
        out = out.at[jnp.asarray(take)].add(y * jnp.asarray(g)[:, None])
    return out


def hidden_states(cfg: Mapping[str, Any], model_id: str,
                  docs: Sequence[Sequence[int]], attend="selected"):
    """The final-normed hidden states [L, d] (float32) of each document,
    layer by layer over all the documents. Call under
    ``default_matmul_precision("highest")``."""
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(cfg["rms_norm_eps"])
    n_dense = int(cfg["n_dense_layers"]) if int(cfg.get("n_experts", 0)) \
        else int(cfg["n_layers"])
    embed = draw(cfg, model_id, "embed")
    xs = [embed[jnp.asarray(d)].astype(f32) for d in docs]
    del embed
    given = isinstance(attend, (list, tuple))
    for i in range(int(cfg["n_layers"])):
        w = {name: draw(cfg, model_id, name, i) for name in ATTENTION}
        if given:
            layer = _jitted("attention_given", lambda w, x, keep:
                            attention_layer(cfg, w, x, keep=keep), cfg)
            xs = [layer(w, x, jnp.asarray(attend[i])) for x in xs]
        else:
            layer = _jitted("attention", lambda w, x: attention_layer(
                cfg, w, x, attend), cfg, attend)
            xs = [layer(w, x) for x in xs]
        del w
        if i < n_dense:
            w = [draw(cfg, model_id, name, i).astype(f32) for name in DENSE]
            ffn = _jitted("dense_ffn", lambda x, *w: x + swiglu(
                rms_norm(x, eps), *w), cfg)
            xs = [ffn(x, *w) for x in xs]
            del w
        else:
            xs = [expert_layer_ffn(cfg, model_id, i, x) for x in xs]
    return [rms_norm(x, eps) for x in xs]


def token_logprobs(cfg: Mapping[str, Any], model_id: str,
                   docs: Sequence[Sequence[int]], attend="selected"
                   ) -> List[np.ndarray]:
    """For each document (a sequence of token ids) the float32 array of
    log p(token_t | tokens before t), t = 1 .. L-1, over the rows of the
    vocabulary held."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg)
    docs = [np.asarray(d, np.int32) for d in docs]
    with jax.default_matmul_precision("highest"):
        hs = hidden_states(cfg, model_id, docs, attend)
        head = draw(cfg, model_id, "head")
        score = _jitted("head", head_logprobs, cfg)
        return [np.asarray(score(h[:-1], head, jnp.asarray(d[1:])))
                if len(d) > 1 else np.zeros((0,), np.float32)
                for h, d in zip(hs, docs)]


def logits(cfg: Mapping[str, Any], model_id: str, doc: Sequence[int],
           positions: Sequence[int], attend="selected") -> np.ndarray:
    """The logits [len(positions), vocab_size] (float32) that the given
    positions of one document give for their NEXT token."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg)
    with jax.default_matmul_precision("highest"):
        h = hidden_states(cfg, model_id, [np.asarray(doc, np.int32)],
                          attend)[0]
        head = draw(cfg, model_id, "head").astype(jnp.float32)
        return np.asarray(h[jnp.asarray(list(positions))] @ head.T)


def compare(served: Sequence[Sequence[float]],
            reference: Sequence[Sequence[float]], n_tokens: Sequence[int],
            block: int = LOSS_BLOCK) -> Dict[str, float]:
    """``retention_lm.compare``'s three numbers (bias, largest gap, slope of
    the gap e = (served - reference) / targets of a block, nats a token) and

    ``block_logprob_gap_rms``: the root mean square of e over all blocks of
    all documents. This model makes DISCRETE choices from rounded numbers
    (8 experts of 256 a token a layer, 2,048 keys a query a layer): where
    two candidates lie closer than the rounding, a program in bf16 and this
    reference choose apart, and a token that loses or gains an expert held
    here moves its log-probability by tenths. Such tokens are a few percent,
    fall anywhere, and set a floor under every block's gap that no precision
    removes; a lower precision raises every block's gap above it. The mean
    square over the blocks tells the two apart with a fifth of the scatter
    of the largest single block."""
    out = _lm.compare(served, reference, n_tokens, block)
    if out:
        gaps = np.concatenate([
            (np.asarray(s, np.float64) - np.asarray(r, np.float64))
            / np.maximum(block_counts(n, block), 1.0)
            for s, r, n in zip(served, reference, n_tokens)])
        out["block_logprob_gap_rms"] = float(np.sqrt(np.mean(gaps ** 2)))
    return out
