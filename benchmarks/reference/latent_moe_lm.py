"""Plain float32 reference of the decoder language model ``map_score_lm``
serves under ``mixer: dense_mla`` (configuration ``mistral-small-4-119b``):
dense latent attention (every causal key, a query scaled by its own position)
and softmax-routed expert layers of which one chip's share is held.
Straightforward ``jax.numpy`` under ``default_matmul_precision("highest")``:
no kernel, no cache, no segments; the whole document's keys and values are
expanded from the latents ONCE a layer (the unabsorbed form, as written
below) and a block of queries at a time meets them. It imports nothing of the
program and takes nothing the program made: the weights come from the model
id by the rule the configuration's ``assumed.weights`` states, written out
again here (layer by layer and expert by expert: 5.4 G parameters in float32
do not fit at once), rounded once to bf16 and used in float32. What a
language-model reference needs whatever its mixer (the key from the model id,
the blocked head, the block sums) is ``retention_lm.py``'s, and what latent
attention needs whatever attends its keys (YaRN, the softmax scale, the
rotation by pairs) is ``sparse_mla_lm.py``'s.

Equations (x_t a token's residual at position t, h = RMSNorm(x), eps
``rms_norm_eps``; every layer alike, no leading dense layer):

    cQ = RMSNorm(h W_DQ)      q_a = cQ W_UQ,a = [q_nope; RoPE(q_rope)]
    [cKV; kR] = h W_DKV       cKV <- RMSNorm(cKV)    kR <- RoPE(kR)
    k_{s,a} = [cKV_s W_UK,a; kR_s]       v_{s,a} = cKV_s W_UV,a
    a(t) = 1 + query_scale_beta ln(1 + floor(t / rope_original_max_len))
    o_{t,a} = sum_{s <= t} softmax_{s <= t}(scale a(t) q_{t,a} . k_{s,a}) v_{s,a}
    u = x + concat_a(o_a) W_O

RoPE is YaRN's on pairs (2i, 2i + 1); ``scale = (nope + rope)^-0.5 (0.1
rope_mscale ln rope_factor + 1)^2``. Then, on n = RMSNorm(u):

    u + SwiGLU_shared(n) + sum_{e chosen, HELD HERE} g_e SwiGLU_e(n)
    p = softmax(n W_R) over all n_experts; the n_experts_per_token largest
    (ties to the lower index); g_e = routed_scale p_e / sum_chosen p

(the experts held: ids ``expert_first`` .. ``+ n_experts_held``; what the
others would add is left out, as in the program). A final RMSNorm and an
untied head over the ``vocab_size`` rows held."""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Sequence

import numpy as np

from benchmarks.harness import manifest

_lm = manifest.load_reference("retention_lm")
seed_key, head_logprobs = _lm.seed_key, _lm.head_logprobs
block_sums, block_counts = _lm.block_sums, _lm.block_counts
_mla = manifest.load_reference("sparse_mla_lm")
rms_norm, swiglu, _jitted = _mla.rms_norm, _mla.swiglu, _mla._jitted
yarn_inv_freq, softmax_scale = _mla.yarn_inv_freq, _mla.softmax_scale

# The family's leaves in the order that keys them (the program appends to
# its list; a leaf keeps its number).
LEAVES = ("embed", "head", "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up",
          "w_down", "w_dq", "w_uq", "w_dkv", "w_ukv", "wi_q", "wi_k", "wi_w",
          "w_router", "ws_gate", "ws_up", "ws_down", "we_gate", "we_up",
          "we_down")
ATTENTION = ("wo", "w_dq", "w_uq", "w_dkv", "w_ukv")
SHARED = ("ws_gate", "ws_up", "ws_down")
EXPERT = ("we_gate", "we_up", "we_down")
LOSS_BLOCK = 1024
QUERY_BLOCK = 128
# Runs of query blocks, each held against its own prefix of the keys.
KEY_SPANS = 8
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
        "w_router": ((d, g("n_experts")), d),
        "ws_gate": ((d, fs), d), "ws_up": ((d, fs), d), "ws_down": ((fs, d), fs),
        "we_gate": ((d, fe), d), "we_up": ((d, fe), d), "we_down": ((fe, d), fe),
    }[name]


_DRAW: Dict[Any, Any] = {}


def draw(cfg: Mapping[str, Any], model_id: str, name: str, layer=None,
         expert=None):
    """One leaf as the configuration defines it: normal(key) / sqrt(fan_in)
    in float32 (embedding: fan_in 1), rounded once to the stored dtype and
    kept in it. Key: fold_in(root, the leaf's number in ``LEAVES``), then
    fold_in(., layer), then fold_in(., expert id among ALL the router's
    experts); root = the model id's key."""
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
# Latent attention's shared pieces (RMS norm, YaRN's inverse frequencies, the
# softmax scale with mscale squared, the rotation by pairs, SwiGLU) are the
# plain statements of ``sparse_mla_lm.py``: the same published family.

def query_scale(cfg: Mapping[str, Any], positions):
    """a(t) of every position, float32."""
    import jax.numpy as jnp

    steps = positions // int(cfg.get("rope_original_max_len", 4096))
    return 1.0 + float(cfg.get("query_scale_beta", 0.0)) * jnp.log(
        1.0 + steps.astype(jnp.float32))


def attention_layer(cfg, w, x, query_block=QUERY_BLOCK):
    """x [L, d] float32 → u = x + attention(RMSNorm(x)) W_O."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    w = {k: jnp.asarray(a).astype(f32) for k, a in w.items()}
    L = x.shape[0]
    g = lambda k: int(cfg[k])  # noqa: E731
    h, kvr = g("n_heads"), g("kv_lora_rank")
    dn, dr, dv = g("qk_nope_head_dim"), g("qk_rope_head_dim"), g("v_head_dim")
    eps, scale = float(cfg["rms_norm_eps"]), softmax_scale(cfg)
    inv = yarn_inv_freq(cfg)

    pos = jnp.arange(L)
    latent = rms_norm(x, eps) @ w["w_dkv"]
    ckv = rms_norm(latent[:, :kvr], eps)                          # [L, kvr]
    k_rope = _mla.rotate(latent[:, kvr:], pos, inv, True)                    # [L, dr]
    expanded = (ckv @ w["w_ukv"]).reshape(L, h, dn + dv)
    keys = jnp.concatenate([
        expanded[..., :dn],
        jnp.broadcast_to(k_rope[:, None, :], (L, h, dr))], -1)    # [L, h, dn+dr]
    values = expanded[..., dn:]                                   # [L, h, dv]

    bq = min(int(query_block), L)
    n_blocks = -(-L // bq)
    pad = n_blocks * bq - L

    def block(args, n_keys):
        """A block of queries against the document's first ``n_keys`` keys
        (none of the block's queries lies past them)."""
        xb, t = args
        t = jnp.minimum(t, L - 1)        # padding queries: any real position
        cq = rms_norm(rms_norm(xb, eps) @ w["w_dq"], eps)
        q = (cq @ w["w_uq"]).reshape(bq, h, dn + dr)
        q = jnp.concatenate([q[..., :dn], _mla.rotate(q[..., dn:], t, inv, True)], -1)
        q = q * (scale * query_scale(cfg, t))[:, None, None]
        s = jnp.einsum("thd,shd->ths", q, keys[:n_keys])
        causal = pos[None, None, :n_keys] <= t[:, None, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("ths,shv->thv", p, values[:n_keys])
        return xb + o.reshape(bq, h * dv) @ w["wo"]

    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(n_blocks, bq, -1)
    ts = jnp.arange(n_blocks * bq).reshape(n_blocks, bq)
    # The blocks in KEY_SPANS runs: a run's queries see no key past the
    # run's end, so it is held against that prefix of the keys alone (the
    # scores of a later key would be masked anyway; this only saves work).
    out, per_run = [], -(-n_blocks // KEY_SPANS)
    for first in range(0, n_blocks, per_run):
        last = min(first + per_run, n_blocks)
        n_keys = min(last * bq, L)
        out.append(jax.lax.map(lambda a, n=n_keys: block(a, n),
                               (xp[first:last], ts[first:last])))
    return jnp.concatenate(out).reshape(n_blocks * bq, -1)[:L]


def route(cfg, n, w_router):
    """n [L, d] → (experts [L, k], gates [L, k]): softmax over ALL the
    experts, the k largest (``lax.top_k``: ties to the lower index), gates
    normalised over the chosen."""
    import jax

    p = jax.nn.softmax(n @ w_router, axis=-1)                     # [L, E]
    picked, experts = jax.lax.top_k(p, int(cfg["n_experts_per_token"]))
    return experts, float(cfg["routed_scale"]) * picked / picked.sum(
        -1, keepdims=True)


def shared_expert(cfg, model_id, layer, n):
    """n [L, d] (normed) → the ungated shared expert's output."""
    import jax.numpy as jnp

    w = [draw(cfg, model_id, name, layer).astype(jnp.float32)
         for name in SHARED]
    return _jitted("swiglu", swiglu, cfg)(n, *w)


def routed_experts(cfg, model_id, layer, n):
    """n [L, d] (normed) → sum over a token's chosen experts HELD HERE of
    gate x SwiGLU_e(n). One expert's weights exist at a time; an expert sees
    only the rows routed to it (padded to ``ROW_BUCKET``)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    experts, gates = _jitted("route", lambda n, w: route(cfg, n, w), cfg)(
        n, draw(cfg, model_id, "w_router", layer).astype(f32))
    experts, gates = np.asarray(experts), np.asarray(gates)
    out = jnp.zeros_like(n)
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


def expert_layer_ffn(cfg, model_id, layer, u):
    """u [L, d] → u + shared expert + the held experts' gated outputs."""
    n = rms_norm(u, float(cfg["rms_norm_eps"]))
    return (u + shared_expert(cfg, model_id, layer, n)
            + routed_experts(cfg, model_id, layer, n))


def hidden_states(cfg: Mapping[str, Any], model_id: str,
                  docs: Sequence[Sequence[int]]):
    """The final-normed hidden states [L, d] (float32) of each document,
    layer by layer over all the documents. Call under
    ``default_matmul_precision("highest")``."""
    import jax.numpy as jnp

    if int(cfg.get("n_dense_layers", 0)) or not int(cfg.get("n_experts", 0)):
        raise ValueError("every layer of this model is an expert layer")
    embed = draw(cfg, model_id, "embed")
    xs = [embed[jnp.asarray(d)].astype(jnp.float32) for d in docs]
    del embed
    layer = _jitted("attention", lambda w, x: attention_layer(cfg, w, x), cfg)
    for i in range(int(cfg["n_layers"])):
        w = {name: draw(cfg, model_id, name, i) for name in ATTENTION}
        xs = [layer(w, x) for x in xs]
        del w
        xs = [expert_layer_ffn(cfg, model_id, i, x) for x in xs]
    return [rms_norm(x, float(cfg["rms_norm_eps"])) for x in xs]


def token_logprobs(cfg: Mapping[str, Any], model_id: str,
                   docs: Sequence[Sequence[int]]) -> List[np.ndarray]:
    """For each document (a sequence of token ids) the float32 array of
    log p(token_t | tokens before t), t = 1 .. L-1, over the rows of the
    vocabulary held."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg)
    docs = [np.asarray(d, np.int32) for d in docs]
    with jax.default_matmul_precision("highest"):
        hs = hidden_states(cfg, model_id, docs)
        head = draw(cfg, model_id, "head")
        score = _jitted("head", head_logprobs, cfg)
        return [np.asarray(score(h[:-1], head, jnp.asarray(d[1:])))
                if len(d) > 1 else np.zeros((0,), np.float32)
                for h, d in zip(hs, docs)]


def logits(cfg: Mapping[str, Any], model_id: str, doc: Sequence[int],
           positions: Sequence[int]) -> np.ndarray:
    """The logits [len(positions), vocab_size] (float32) that the given
    positions of one document give for their NEXT token."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg)
    with jax.default_matmul_precision("highest"):
        h = hidden_states(cfg, model_id, [np.asarray(doc, np.int32)])[0]
        head = draw(cfg, model_id, "head").astype(jnp.float32)
        return np.asarray(h[jnp.asarray(list(positions))] @ head.T)


def compare(served: Sequence[Sequence[float]],
            reference: Sequence[Sequence[float]], n_tokens: Sequence[int],
            block: int = LOSS_BLOCK) -> Dict[str, float]:
    """Four numbers of the gap e = (served - reference) / targets of a block,
    nats a token, over all blocks of all documents: ``block_logprob_bias``
    (|mean e|: arithmetic off the same way everywhere), ``block_logprob_gap_
    max`` (the largest |e|: a block answered from the wrong tokens),
    ``block_logprob_gap_slope`` (|least-squares slope of e against the
    block's index|, mean over documents: a cache written or read at the
    wrong place grows with position) — ``retention_lm.compare``'s three —
    and ``block_logprob_gap_rms``, the root mean square of e. This model
    makes a DISCRETE choice from rounded numbers (4 experts of 128 a token a
    layer): where two candidates lie closer than the rounding, a program in
    bf16 and this reference choose apart, and a token that gains or loses an
    expert held here moves its log-probability by tenths. Such tokens fall
    anywhere and set a floor under every block's gap that no precision
    removes; a lower precision raises every block's gap above it, and the
    mean square over the blocks tells the two apart with a fraction of the
    scatter of the largest single block."""
    out = _lm.compare(served, reference, n_tokens, block)
    if out:
        gaps = np.concatenate([
            (np.asarray(s, np.float64) - np.asarray(r, np.float64))
            / np.maximum(block_counts(n, block), 1.0)
            for s, r, n in zip(served, reference, n_tokens)])
        out["block_logprob_gap_rms"] = float(np.sqrt(np.mean(gaps ** 2)))
    return out
