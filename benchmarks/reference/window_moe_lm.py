"""Plain float32 reference of the decoder language model ``map_score_lm``
serves under ``mixer: window_gqa`` (configuration ``mellum2-12b-a2.5b``):
grouped-query softmax attention whose layers come in two KINDS, window and
full, mixed 3 : 1, over softmax-routed expert layers with no shared expert.
Straightforward ``jax.numpy`` under ``default_matmul_precision("highest")``:
no kernel, no cache, no segments, no carried tail; a block of queries meets
the document's keys under a dense ``[queries, keys]`` mask made from their
positions (a window layer's block is held against the span of keys its
window can reach: the mask is the same, the span only saves work). It
imports nothing of the program and takes nothing the program made: the
weights come from the model id by the rule the configuration's
``assumed.weights`` states, written out again here (layer by layer and expert
by expert: 5.5 G parameters in float32 do not fit at once), rounded once to
bf16 and used in float32. What a language-model reference needs whatever its
mixer (the key from the model id, the blocked head, the block sums, the
comparison's three numbers) is ``retention_lm.py``'s; YaRN's inverse
frequencies, the rotation by halves, RMS norm and SwiGLU are
``sparse_mla_lm.py``'s plain statements.

Equations of layer ``i`` of kind ``c`` (x_t a token's residual at position
t; ``c`` is ``full`` where ``(i + 1) % full_attention_every == 0``, else
``window``; eps ``rms_norm_eps``):

    h = RMSNorm(x)       q_a = h W_Q,a (a < n_heads)
    k_b = h W_K,b        v_b = h W_V,b (b < n_kv_heads)
    q_a <- QUERY_GAIN RMSNorm_head(q_a)       k_b <- RMSNorm_head(k_b)
    q_a <- m_c RoPE_c(q_a, t)                 k_b <- m_c RoPE_c(k_b, s)
        window: inv_freq theta^(-2j / d_head), m = 1
        full:   YaRN's inv_freq (rope_factor over rope_original_max_len,
                beta_fast / beta_slow), m = 0.1 rope_mscale ln rope_factor + 1
                (on cos AND sin: queries and keys both, the scores m^2)
    o_{t,a} = sum_{s in K_c(t)} softmax_s(q_{t,a} . k_{s,b} / sqrt(d_head)) v_{s,b}
        b = a div (n_heads / n_kv_heads)
        K_full(t) = {s <= t}      K_window(t) = {s : t - sliding_window < s <= t}
    u = x + concat_a(o_a) W_O        n = RMSNorm(u)
    p = softmax(n W_R) over all n_experts (float32); E = the n_experts_per_token
        largest (``lax.top_k``: ties to the lower index)
    y = u + sum_{e in E, HELD HERE} (p_e / sum_E p) W_down,e (silu(n W_gate,e) * (n W_up,e))

RoPE turns the pairs ``(j, j + d_head / 2)`` (rotation by halves). A final
RMSNorm and an untied head. The experts held are ids ``expert_first .. +
n_experts_held`` (the configuration holds all of them).

Departures from the published model, each also in the configuration's
``assumed``: the per-head RMS norm on queries and keys, the softmax router,
the rotation by halves and the window as ``s > t - sliding_window`` follow
the Qwen3-MoE family's public modelling code, whose keys the config uses;
the query norm's weight is ``QUERY_GAIN`` (2) and every other norm weight 1
(random weights of weight-1 norms give scores of unit spread, whose softmax
over thousands of keys averages them: attention would then enter the
residual at a twentieth and no mechanism of it could be told from its
absence; at 4 one key takes a query's whole weight and bf16's rounding of a
score decides which, so that no check could tell a sound program from a
broken one); the MTP head the catalog's description names is left out (the
config has no key for it); ``intermediate_size`` is unused (no dense layer)."""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Sequence

import numpy as np

from benchmarks.harness import manifest

_lm = manifest.load_reference("retention_lm")
seed_key, head_logprobs = _lm.seed_key, _lm.head_logprobs
block_sums, block_counts = _lm.block_sums, _lm.block_counts
_mla = manifest.load_reference("sparse_mla_lm")
rms_norm, swiglu, _jitted = _mla.rms_norm, _mla.swiglu, _mla._jitted
rotate = _mla.rotate

# The family's leaves in the order that keys them (the program appends to
# its list; a leaf keeps its number).
LEAVES = ("embed", "head", "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up",
          "w_down", "w_dq", "w_uq", "w_dkv", "w_ukv", "wi_q", "wi_k", "wi_w",
          "w_router", "ws_gate", "ws_up", "ws_down", "we_gate", "we_up",
          "we_down")
ATTENTION = ("wq", "wk", "wv", "wo")
EXPERT = ("we_gate", "we_up", "we_down")
# The query norm's weight (every other norm's is 1): see the docstring.
QUERY_GAIN = 2.0
LOSS_BLOCK = 1024
QUERY_BLOCK = 128
# Runs of query blocks of a full layer, each held against its own prefix of
# the keys.
KEY_SPANS = 8
# Rows of one expert's tokens are padded to a multiple of this. ONE shape in
# practice (an expert of 64 sees about 4,096 of a 32,768-token document's
# pairs): every further shape is a further compile of the step below, and
# its first 64 x 12 shapes took the reference eleven minutes where one takes
# none (PERF.md section 6, PR 42); the padding's rows are zeros times zero.
ROW_BUCKET = 8192
# ``compare`` leaves the blocks that lie furthest off out of its mean square:
# one in this many.
TRIMMED_SHARE = 16


def leaf_shape(cfg: Mapping[str, Any], name: str):
    """(shape, fan_in) of one layer's leaf (one expert's), or of a whole
    unlayered leaf."""
    g = lambda k: int(cfg[k])  # noqa: E731
    d, fe = g("d_model"), g("d_expert")
    hq, hkv = g("n_heads") * g("d_head"), g("n_kv_heads") * g("d_head")
    return {
        "embed": ((g("vocab_size"), d), 1), "head": ((g("vocab_size"), d), d),
        "wq": ((d, hq), d), "wk": ((d, hkv), d), "wv": ((d, hkv), d),
        "wo": ((hq, d), hq), "w_router": ((d, g("n_experts")), d),
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

def layer_kind(cfg: Mapping[str, Any], layer: int) -> str:
    """``full`` for the last of every ``full_attention_every`` layers."""
    every = int(cfg.get("full_attention_every", 4))
    return "full" if (int(layer) + 1) % every == 0 else "window"


def rotary(cfg: Mapping[str, Any], kind: str):
    """``(inverse frequencies [d_head / 2], factor m)`` of a kind's rotary
    positions: YaRN's on a full layer, the plain table on a window layer."""
    dim, theta = int(cfg["d_head"]), float(cfg["rope_theta"])
    if kind == "window":
        return 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim), 1.0
    inv = _mla.yarn_inv_freq({**cfg, "qk_rope_head_dim": dim})
    factor = float(cfg.get("rope_factor", 1.0))
    scaled = factor != 1.0 and int(cfg["max_len"]) > int(
        cfg.get("rope_original_max_len", 4096))
    return inv, (0.1 * float(cfg.get("rope_mscale", 1.0)) * np.log(factor)
                 + 1.0) if scaled else 1.0


def visible(cfg: Mapping[str, Any], kind: str, t, s):
    """Whether the query at position ``t`` attends the key at ``s`` (arrays
    that broadcast): every causal key on a full layer, the last
    ``sliding_window`` of them on a window layer."""
    seen = s <= t
    if kind == "window":
        seen = seen & (s > t - int(cfg["sliding_window"]))
    return seen


def attention_layer(cfg, w, x, kind, query_block=QUERY_BLOCK):
    """x [L, d] float32 → u = x + attention(RMSNorm(x)) W_O for a layer of
    ``kind``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    w = {k: jnp.asarray(a).astype(f32) for k, a in w.items()}
    L = x.shape[0]
    g = lambda k: int(cfg[k])  # noqa: E731
    hq, hkv, dh = g("n_heads"), g("n_kv_heads"), g("d_head")
    eps = float(cfg["rms_norm_eps"])
    inv, m = rotary(cfg, kind)

    pos = jnp.arange(L)
    h = rms_norm(x, eps)
    keys = m * rotate(rms_norm((h @ w["wk"]).reshape(L, hkv, dh), eps),
                      pos, inv, False)                            # [L, hkv, dh]
    values = (h @ w["wv"]).reshape(L, hkv, dh)

    bq = min(int(query_block), L)
    n_blocks = -(-L // bq)
    pad = n_blocks * bq - L

    def block(args, n_keys):
        """A block of queries against ``n_keys`` keys: the document's first
        ``n_keys`` on a full layer (none of the block's queries lies past
        them), the last ``n_keys`` up to the block's end on a window layer
        (none of its windows reaches before them)."""
        xb, t = args
        first = (jnp.clip(t[-1] + 1 - n_keys, 0, L - n_keys)
                 if kind == "window" else 0)
        ks = jax.lax.dynamic_slice_in_dim(keys, first, n_keys)
        vs = jax.lax.dynamic_slice_in_dim(values, first, n_keys)
        t = jnp.minimum(t, L - 1)        # padding queries: any real position
        q = QUERY_GAIN * rms_norm(
            (rms_norm(xb, eps) @ w["wq"]).reshape(bq, hq, dh), eps)
        q = m * rotate(q, t, inv, False) * dh ** -0.5
        q = q.reshape(bq, hkv, hq // hkv, dh)
        s = jnp.einsum("tbgd,sbd->tbgs", q, ks)
        seen = visible(cfg, kind, t[:, None], (first + jnp.arange(n_keys))[None, :])
        p = jax.nn.softmax(jnp.where(seen[:, None, None, :], s, -jnp.inf), axis=-1)
        o = jnp.einsum("tbgs,sbd->tbgd", p, vs)
        return xb + o.reshape(bq, hq * dh) @ w["wo"]

    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(n_blocks, bq, -1)
    ts = jnp.arange(n_blocks * bq).reshape(n_blocks, bq)
    if kind == "window":
        n_keys = min(L, int(cfg["sliding_window"]) + bq)
        out = [jax.lax.map(lambda a: block(a, n_keys), (xp, ts))]
    else:
        # The blocks in KEY_SPANS runs: a run's queries see no key past the
        # run's end, so it is held against that prefix of the keys alone.
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
    return experts, picked / picked.sum(-1, keepdims=True)


def routed_experts(cfg, model_id, layer, n):
    """n [L, d] (normed) → sum over a token's chosen experts HELD HERE of
    gate x SwiGLU_e(n). One expert's weights exist at a time; an expert sees
    only the rows routed to it (padded to ``ROW_BUCKET``)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    experts, gates = _jitted("route", lambda n, w: route(cfg, n, w), cfg)(
        n, draw(cfg, model_id, "w_router", layer).astype(f32))
    experts, gates = np.asarray(experts), np.asarray(gates)

    def one_expert(out, n, take, g, gate, up, down):
        """``out`` + the gated expert on its rows (padding: row 0 times 0)."""
        return out.at[take].add(swiglu(n[take], gate, up, down) * g[:, None])

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
        out = _jitted("expert", one_expert, cfg)(
            out, n, jnp.asarray(take), jnp.asarray(g), *w)
    return out


def expert_layer_ffn(cfg, model_id, layer, u):
    """u [L, d] → u + the held experts' gated outputs (no shared expert)."""
    return u + routed_experts(cfg, model_id, layer,
                              rms_norm(u, float(cfg["rms_norm_eps"])))


def hidden_states(cfg: Mapping[str, Any], model_id: str,
                  docs: Sequence[Sequence[int]]):
    """The final-normed hidden states [L, d] (float32) of each document,
    layer by layer over all the documents. Call under
    ``default_matmul_precision("highest")``."""
    import jax.numpy as jnp

    if (int(cfg.get("n_dense_layers", 0)) or int(cfg.get("n_shared_experts", 0))
            or not int(cfg.get("n_experts", 0))):
        raise ValueError("every layer of this model is an expert layer with "
                         "no shared expert")
    embed = draw(cfg, model_id, "embed")
    xs = [embed[jnp.asarray(d)].astype(jnp.float32) for d in docs]
    del embed
    for i in range(int(cfg["n_layers"])):
        kind = layer_kind(cfg, i)
        layer = _jitted("attention", lambda w, x, kind=kind: attention_layer(
            cfg, w, x, kind), cfg, kind)
        w = {name: draw(cfg, model_id, name, i) for name in ATTENTION}
        xs = [layer(w, x) for x in xs]
        del w
        xs = [expert_layer_ffn(cfg, model_id, i, x) for x in xs]
    return [rms_norm(x, float(cfg["rms_norm_eps"])) for x in xs]


def token_logprobs(cfg: Mapping[str, Any], model_id: str,
                   docs: Sequence[Sequence[int]]) -> List[np.ndarray]:
    """For each document (a sequence of token ids) the float32 array of
    log p(token_t | tokens before t), t = 1 .. L-1."""
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
    nats a token, over all blocks of all documents: ``retention_lm.compare``'s
    three (``block_logprob_bias``, ``block_logprob_gap_max``,
    ``block_logprob_gap_slope``) and ``block_logprob_gap_rms_trimmed``, the
    root mean square of e over all blocks but the sixteenth of them that lie
    furthest off (4 of 64). This model makes a DISCRETE choice from rounded
    numbers (8 experts of 64 a token a layer): where the 8th and 9th scores
    lie closer than the rounding, a program in bf16 and this reference choose
    apart and a token's log-probability moves by more than rounding alone
    moves it. Such tokens fall anywhere and set a floor under every block's
    gap; a lower precision raises every block above it, and the mean square
    over the blocks tells the two apart with a fraction of the scatter of the
    largest single block. WHY TRIMMED: a document's FIRST block scatters 2.5
    times as widely as a later one (its queries meet few keys, so nothing
    averages a key's rounding, and under Zipf ids the same few tokens repeat
    and their errors add with one sign: one sound document in nineteen read
    1.4e-2 there, eight of its own deviations), and one such block would
    carry a mean square past what int8 adds to all of them. The largest
    block keeps its own limit; this number is what every block shares."""
    out = _lm.compare(served, reference, n_tokens, block)
    if out:
        gaps = np.sort(np.abs(np.concatenate([
            (np.asarray(s, np.float64) - np.asarray(r, np.float64))
            / np.maximum(block_counts(n, block), 1.0)
            for s, r, n in zip(served, reference, n_tokens)])))
        kept = gaps[:len(gaps) - len(gaps) // TRIMMED_SHARE]
        out["block_logprob_gap_rms_trimmed"] = float(np.sqrt(np.mean(kept ** 2)))
    return out
