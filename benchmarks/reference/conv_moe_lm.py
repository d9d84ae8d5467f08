"""Plain float32 reference of the decoder language model ``map_score_lm``
serves under ``mixer: conv_gqa`` (configuration ``lfm2-24b-a2b``): layers in
two KINDS by the model's own pattern (``layer_types``): double-gated short
causal convolutions, and among them grouped-query softmax attention with a
per-head RMS norm on queries and keys; leading dense SwiGLU layers, then
sigmoid-routed expert layers (ONE group, a choice bias, no shared expert) of
which every expert is held. Straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``: no kernel, no cache, no segments, no
tail. The convolution is three shifted products over the WHOLE document; a
block of queries meets the document's keys under a dense ``[queries, keys]``
mask made from their positions (``window_moe_lm.py``'s full kind, loaded, not
written again); the experts are a loop over the held ones. It
imports nothing of the program and takes nothing the program made: the
weights come from the model id by the rule the configuration's
``assumed.weights`` states, written out again here (layer by layer and expert
by expert: 5.3 G parameters in float32 do not fit at once), rounded once to
bf16 and used in float32. What a language-model reference needs whatever its
mixer (the key from the model id, the blocked head, the block sums, the four
numbers of the comparison) is ``retention_lm.py``'s and ``latent_moe_lm.py``'s;
RMS norm and SwiGLU are ``sparse_mla_lm.py``'s plain statements.

Equations of layer ``i`` (x_t a token's residual at position t, n =
RMSNorm(x), eps ``rms_norm_eps``, d = ``d_model``; the layer's kind is
``layer_types[i]``).

``conv`` layer (K = ``conv_taps``; w a tap a channel, no bias):

    [B | C | z] = n W_in            (three equal parts of 3 d columns)
    g_t = B_t * z_t                 (g before the document's first token: 0)
    c_t = sum_{i < K} w_i * g_{t - (K - 1) + i}       (a channel)
    u = x + (C_t * c_t) W_out       (no activation anywhere in it)

``full_attention`` layer (head a of ``n_heads``, key-value head b of
``n_kv_heads``, D = ``d_head``):

    q_a = n W_Q,a        k_b = n W_K,b        v_b = n W_V,b
    q_a <- QUERY_GAIN RMSNorm_head(q_a)       k_b <- RMSNorm_head(k_b)
    q_a <- RoPE(q_a, t)  k_b <- RoPE(k_b, s)  (theta ``rope_theta``, the plain
                                               table, pairs (j, j + D / 2))
    o_{t,a} = sum_{s <= t} softmax_s(q_{t,a} . k_{s,b} / sqrt(D)) v_{s,b}
        b = a div (n_heads / n_kv_heads)
    u = x + concat_a(o_a) W_O

Then, on m = RMSNorm(u): a layer under ``n_dense_layers`` adds ``(SiLU(m W_1)
* m W_3) W_2`` at ``d_ff``; every other layer adds

    sum_{e in E, HELD HERE} (s_e / sum_E s) routed_scale (SiLU(m W_1,e) * m W_3,e) W_2,e
    s = sigmoid(m W_R) over all n_experts (float32); E = the
    n_experts_per_token largest of s + bias (``lax.top_k``: ties to the
    lower index; the bias is 0 in a model drawn from an id)

A final RMSNorm and an untied head. The experts held are ids ``expert_first
.. + n_experts_held`` (the configuration holds all of them).

Departures from the published model, each also in the configuration's
``assumed``: the query norm's weight is ``window_moe_lm.QUERY_GAIN`` (2) and
every other norm weight 1 (that file has the reason); embedding and head are
untied; the router's normaliser is the chosen scores' sum without the
modelling code's ``+ 1e-6``."""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Sequence

import numpy as np

from benchmarks.harness import manifest

_lm = manifest.load_reference("retention_lm")
seed_key, head_logprobs = _lm.seed_key, _lm.head_logprobs
block_sums, block_counts = _lm.block_sums, _lm.block_counts
_mla = manifest.load_reference("sparse_mla_lm")
rms_norm, swiglu, _jitted = _mla.rms_norm, _mla.swiglu, _mla._jitted
compare = manifest.load_reference("latent_moe_lm").compare
_window = manifest.load_reference("window_moe_lm")

# The family's leaves in the order that keys them (the program appends to
# its list; a leaf keeps its number).
LEAVES = ("embed", "head", "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up",
          "w_down", "w_dq", "w_uq", "w_dkv", "w_ukv", "wi_q", "wi_k", "wi_w",
          "w_router", "ws_gate", "ws_up", "ws_down", "we_gate", "we_up",
          "we_down", "w_ssm_in", "w_ssm_out", "conv_w", "conv_b", "w_beta",
          "w_a", "w_og", "w_conv_in")
CONV = ("w_conv_in", "wo", "conv_w")
ATTENTION = ("wq", "wk", "wv", "wo")
DENSE = ("w_gate", "w_up", "w_down")
EXPERT = ("we_gate", "we_up", "we_down")
LOSS_BLOCK = 1024
# Rows of one expert's tokens are padded to a multiple of this: ONE shape in
# practice (an expert of 64 sees about 2,048 of a 32,768-token document's
# 131,072 pairs; every further shape is a further compile of the step).
ROW_BUCKET = 4096


def layer_kind(cfg: Mapping[str, Any], layer: int) -> str:
    """``conv`` or ``full``, as the model's own pattern names the layer."""
    kind = str(cfg["layer_types"][int(layer)])
    return "full" if kind == "full_attention" else kind


def leaf_shape(cfg: Mapping[str, Any], name: str, kind: str = ""):
    """(shape, fan_in) of one layer's leaf (one expert's), or of a whole
    unlayered leaf; a mixer's leaf as a layer of ``kind`` holds it."""
    g = lambda k: int(cfg[k])  # noqa: E731
    d, fe, f = g("d_model"), g("d_expert"), g("d_ff")
    if kind == "conv":
        taps = g("conv_taps")
        return {"w_conv_in": ((d, 3 * d), d), "wo": ((d, d), d),
                "conv_w": ((taps, d), taps)}[name]
    hq, hkv = g("n_heads") * g("d_head"), g("n_kv_heads") * g("d_head")
    return {
        "embed": ((g("vocab_size"), d), 1), "head": ((g("vocab_size"), d), d),
        "wq": ((d, hq), d), "wk": ((d, hkv), d), "wv": ((d, hkv), d),
        "wo": ((hq, d), hq),
        "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f),
        "w_router": ((d, g("n_experts")), d),
        "we_gate": ((d, fe), d), "we_up": ((d, fe), d), "we_down": ((fe, d), fe),
    }[name]


_DRAW: Dict[Any, Any] = {}


def draw(cfg: Mapping[str, Any], model_id: str, name: str, layer=None,
         expert=None, kind: str = ""):
    """One leaf as the configuration defines it: normal(key) / sqrt(fan_in)
    in float32 (embedding: fan_in 1), rounded once to the stored dtype and
    kept in it. Key: fold_in(root, the leaf's number in ``LEAVES``), then
    fold_in(., layer: its number over the whole model), then fold_in(.,
    expert id among ALL the router's experts); root = the model id's key."""
    import jax
    import jax.numpy as jnp

    shape, fan_in = leaf_shape(cfg, name, kind)
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

def gated_conv(B, C, z, w):
    """``C_t * sum_i w_i (B * z)_{t - (K - 1) + i}`` a channel, zeros before
    the document: B, C, z [L, d], w [K, d]. The shifted products, each over
    the whole document."""
    import jax.numpy as jnp

    L, K = B.shape[0], w.shape[0]
    g = B * z
    c = jnp.zeros_like(g)
    for i in range(K):
        back = K - 1 - i                   # tap i reads the token ``back`` ago
        shifted = g if not back else jnp.concatenate(
            [jnp.zeros((min(back, L), g.shape[1]), g.dtype), g[:L - back]], 0)
        c = c + w[i][None, :] * shifted
    return C * c


def conv_layer(cfg, w, x):
    """x [L, d] float32 → u = x + the double-gated convolution through W_out."""
    import jax.numpy as jnp

    w = {k: jnp.asarray(a).astype(jnp.float32) for k, a in w.items()}
    d = x.shape[1]
    proj = rms_norm(x, float(cfg["rms_norm_eps"])) @ w["w_conv_in"]
    B, C, z = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    return x + gated_conv(B, C, z, w["conv_w"]) @ w["wo"]


def attention_layer(cfg, w, x):
    """x [L, d] float32 → u = x + attention(RMSNorm(x)) W_O: the FULL kind of
    ``window_moe_lm.py``'s plain statement (the same layer: a per-head RMS
    norm on queries, weight ``QUERY_GAIN``, and keys, the rotation by halves,
    every causal key under a dense mask in blocks of queries), whose rotary
    table is the plain one where the config names no scaling
    (``rope_factor`` 1)."""
    return _window.attention_layer(cfg, w, x, "full")


def route(cfg, n, w_router, bias=None):
    """n [L, d] → (experts [L, k], gates [L, k]): sigmoid scores over ALL the
    experts; the CHOICE is the k largest of score + ``bias`` (``lax.top_k``:
    ties to the lower index; ``None``: 0); the gates are the chosen experts'
    scores, WITHOUT the bias, over their sum, times ``routed_scale``."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(n @ w_router)                              # [L, E]
    choice = s if bias is None else s + jnp.asarray(bias, jnp.float32)
    experts = jax.lax.top_k(choice, int(cfg["n_experts_per_token"]))[1]
    picked = jnp.take_along_axis(s, experts, axis=1)
    return experts, float(cfg.get("routed_scale", 1.0)) * picked / picked.sum(
        -1, keepdims=True)


def dense_ffn(cfg, model_id, layer, u):
    """u [L, d] → u + SwiGLU(RMSNorm(u)) at the dense width."""
    import jax.numpy as jnp

    w = [draw(cfg, model_id, name, layer).astype(jnp.float32)
         for name in DENSE]
    return u + _jitted("swiglu", swiglu, cfg)(
        rms_norm(u, float(cfg["rms_norm_eps"])), *w)


def routed_experts(cfg, model_id, layer, n, bias=None):
    """n [L, d] (normed) → sum over a token's chosen experts HELD HERE of
    gate x SwiGLU_e(n). One expert's weights exist at a time; an expert sees
    only the rows routed to it (padded to ``ROW_BUCKET``)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    experts, gates = _jitted(
        "route", lambda n, w, b: route(cfg, n, w, b), cfg)(
        n, draw(cfg, model_id, "w_router", layer).astype(f32),
        jnp.zeros((int(cfg["n_experts"]),), f32) if bias is None
        else jnp.asarray(bias, f32))
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


def expert_layer_ffn(cfg, model_id, layer, u, bias=None):
    """u [L, d] → u + the held experts' gated outputs (no shared expert)."""
    return u + routed_experts(cfg, model_id, layer,
                              rms_norm(u, float(cfg["rms_norm_eps"])), bias)


def hidden_states(cfg: Mapping[str, Any], model_id: str,
                  docs: Sequence[Sequence[int]]):
    """The final-normed hidden states [L, d] (float32) of each document,
    layer by layer over all the documents. Call under
    ``default_matmul_precision("highest")``."""
    import jax.numpy as jnp

    if int(cfg.get("n_shared_experts", 0)):
        raise ValueError("this model's expert layers have no shared expert")
    embed = draw(cfg, model_id, "embed")
    xs = [embed[jnp.asarray(d)].astype(jnp.float32) for d in docs]
    del embed
    mixers = {
        "conv": (CONV, _jitted("conv", lambda w, x: conv_layer(cfg, w, x),
                               cfg)),
        "full": (ATTENTION, _jitted(
            "attention", lambda w, x: attention_layer(cfg, w, x), cfg))}
    routed = int(cfg.get("n_experts", 0))
    leading = int(cfg.get("n_dense_layers", 0)) if routed else int(
        cfg["n_layers"])
    for i in range(int(cfg["n_layers"])):
        kind = layer_kind(cfg, i)
        names, mixer = mixers[kind]
        w = {name: draw(cfg, model_id, name, i, kind=kind) for name in names}
        xs = [mixer(w, x) for x in xs]
        del w
        ffn = dense_ffn if i < leading else expert_layer_ffn
        xs = [ffn(cfg, model_id, i, x) for x in xs]
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
