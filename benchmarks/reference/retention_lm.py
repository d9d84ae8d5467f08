"""Plain float32 reference of the decoder language model ``map_score_lm``
serves (configuration ``brumby-14b-base``): straightforward ``jax.numpy``
under ``default_matmul_precision("highest")``, in the ATTENTION form of gated
power retention — a decayed, squared, causally masked score matrix over the
whole document; no chunks, no carried state, no kernel. It imports nothing of
the program and takes nothing the program made: the weights come from the
model id by the rule the configuration's ``assumed.weights`` states, written
out again here, rounded once to bf16 (the dtype the configuration stores
them in) and used in float32. The tier-1 tests compare with this same file.

It is blocked over queries, layers and vocabulary ONLY so that it fits beside
the served model on the chip: one layer's weights exist at a time, a block of
queries sees every key of the document, and the vocabulary is folded into a
running log-sum-exp a block at a time. The blocks change no arithmetic.

Equations (x_t in R^d; Hq query heads a, Hkv key-value heads b = a // G):

    h = RMSNorm(x)                                   eps rms_norm_eps
    q_a = RoPE(RMSNorm_head(W_q h)_a)   k_b = RoPE(RMSNorm_head(W_k h)_b)
    v_b = (W_v h)_b                     (no bias; rotary theta rope_theta)
    log g_{t,b} = log sigmoid((W_g h)_b + bias_b)
    w_{t,s} = exp(sum_{r=s+1..t} log g_{r,b}) (q_{t,a} . k_{s,b})^2   s <= t
    y_{t,a} = sum_s w_{t,s} v_{s,b} / (sum_s w_{t,s} + 1e-6)
    x' = x + W_o concat_a(y_a)
    x'' = x' + W_down(silu(W_gate n) * (W_up n)),    n = RMSNorm(x')

then a final RMSNorm and an untied head. Reported: for every position t >= 1
log p(token_t | tokens before t), natural log, over the whole vocabulary;
summed by blocks of 1,024 PREDICTING positions (block j holds the targets t
with (t - 1) // 1024 == j), which is what the op returns."""

from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Mapping, Sequence

import numpy as np

LEAVES = ("embed", "head", "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up",
          "w_down")
EPS = 1e-6
GATE_TAU0 = 16.0
LOSS_BLOCK = 1024
QUERY_BLOCK = 256
VOCAB_BLOCK = 8192


def seed_key(model_id: str):
    import jax

    digest = hashlib.sha256(model_id.encode("utf-8")).digest()
    return jax.random.PRNGKey(int.from_bytes(digest[:4], "big"))


def leaf_shape(cfg: Mapping[str, Any], name: str):
    """(shape, fan_in) of one layer's leaf, or of a whole unlayered leaf."""
    d, f = int(cfg["d_model"]), int(cfg["d_ff"])
    hq = int(cfg["n_heads"]) * int(cfg["d_head"])
    hkv = int(cfg["n_kv_heads"]) * int(cfg["d_head"])
    V = int(cfg["vocab_size"])
    return {
        "embed": ((V, d), 1), "head": ((V, d), d),
        "wq": ((d, hq), d), "wk": ((d, hkv), d), "wv": ((d, hkv), d),
        "wo": ((hq, d), hq), "wg": ((d, int(cfg["n_kv_heads"])), d),
        "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f),
    }[name]


_DRAW: Dict[Any, Any] = {}


def draw(cfg: Mapping[str, Any], model_id: str, name: str, layer=None):
    """One leaf as the configuration defines it: normal(key) / sqrt(fan_in)
    in float32, rounded once to the stored dtype, KEPT in that dtype (the
    values are exactly representable there; every use upcasts to float32).
    Key: fold_in(root, index of the leaf), then fold_in(., layer)."""
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
    if layer is not None:
        key = jax.random.fold_in(key, int(layer))
    return _DRAW[sig](key)


def gate_bias(n_kv_heads: int) -> np.ndarray:
    """log(tau_b - 1) with tau_b = 16 * 2**b: sigmoid of it is 1 - 1/tau_b,
    a memory of tau_b tokens for key-value head b."""
    return np.log(GATE_TAU0 * 2.0 ** np.arange(n_kv_heads) - 1.0).astype(
        np.float32)


# ---- the mathematics -----------------------------------------------------

def rms_norm(x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def rope(x, positions, theta):
    """x [L, H, D]; pairs (i, i + D/2), angle position / theta^(2i/D)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rot * sin


def retention_attention(q, k, v, log_g, q_positions=None):
    """The mixer in its attention form. q [Lq, Hq, D] (the queries at
    ``q_positions``, default all), k, v [L, Hkv, D], log_g [L, Hkv] → y
    [Lq, Hq, D]. All float32."""
    import jax.numpy as jnp

    L, hkv, _ = k.shape
    g = q.shape[1] // hkv
    t = jnp.arange(L) if q_positions is None else q_positions
    cum = jnp.cumsum(log_g, axis=0)                          # [L, Hkv]
    decay = cum[t][:, None, :] - cum[None, :, :]             # [Lq, L, Hkv]
    causal = (jnp.arange(L)[None, :] <= t[:, None])[:, :, None]
    decay = jnp.exp(jnp.where(causal, decay, -jnp.inf))
    q5 = q.reshape(q.shape[0], hkv, g, q.shape[-1])
    s = jnp.einsum("tbgd,sbd->tbgs", q5, k)
    w = jnp.square(s) * decay.transpose(0, 2, 1)[:, :, None, :]
    y = jnp.einsum("tbgs,sbd->tbgd", w, v) / (
        w.sum(axis=-1, keepdims=True) + EPS)
    return y.reshape(q.shape)


def layer_forward(cfg, w, x, query_block=QUERY_BLOCK):
    """One block on a whole document: x [L, d] float32 → [L, d]. ``w`` holds
    the layer's leaves (any float dtype; upcast here)."""
    import jax
    import jax.numpy as jnp

    w = {k: jnp.asarray(a).astype(jnp.float32) for k, a in w.items()}
    L, _ = x.shape
    hq, hkv, dh = int(cfg["n_heads"]), int(cfg["n_kv_heads"]), int(cfg["d_head"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    pos = jnp.arange(L)
    h = rms_norm(x, eps)
    k = rope(rms_norm((h @ w["wk"]).reshape(L, hkv, dh), eps), pos, theta)
    v = (h @ w["wv"]).reshape(L, hkv, dh)
    log_g = jax.nn.log_sigmoid(h @ w["wg"] + w["bg"])

    bq = min(int(query_block), L)
    n_blocks = -(-L // bq)
    pad = n_blocks * bq - L
    xp = jnp.pad(x, ((0, pad), (0, 0)))

    def block(args):
        xb, t = args                                          # [bq, d], [bq]
        t = jnp.minimum(t, L - 1)        # padding queries: any real position
        hb = rms_norm(xb, eps)
        q = rope(rms_norm((hb @ w["wq"]).reshape(bq, hq, dh), eps), t, theta)
        y = retention_attention(q, k, v, log_g, q_positions=t)
        xb = xb + y.reshape(bq, hq * dh) @ w["wo"]
        n = rms_norm(xb, eps)
        return xb + (jax.nn.silu(n @ w["w_gate"]) * (n @ w["w_up"])) @ w["w_down"]

    out = jax.lax.map(block, (xp.reshape(n_blocks, bq, -1),
                              jnp.arange(n_blocks * bq).reshape(n_blocks, bq)))
    return out.reshape(n_blocks * bq, -1)[:L]


def head_logprobs(h, head, targets, vocab_block=VOCAB_BLOCK):
    """log p(target) for every row of h [N, d] over the whole vocabulary
    head [V, d], the vocabulary folded in blocks; float32."""
    import jax.numpy as jnp

    V = head.shape[0]
    m = jnp.full((h.shape[0],), -jnp.inf, jnp.float32)
    l = jnp.zeros_like(m)
    hit = jnp.zeros_like(m)
    for at in range(0, V, int(vocab_block)):
        w = head[at:at + int(vocab_block)].astype(jnp.float32)
        logits = h @ w.T
        m_new = jnp.maximum(m, logits.max(axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.exp(logits - m_new[:, None]).sum(-1)
        m = m_new
        inside = (targets >= at) & (targets < at + w.shape[0])
        picked = jnp.take_along_axis(
            logits, jnp.clip(targets - at, 0, w.shape[0] - 1)[:, None], 1)[:, 0]
        hit = hit + jnp.where(inside, picked, 0.0)
    return hit - (m + jnp.log(l))


_JIT: Dict[Any, Any] = {}


def _jitted(name, fn, cfg):
    import jax

    key = (name, tuple(sorted((k, str(v)) for k, v in cfg.items())))
    if key not in _JIT:
        _JIT[key] = jax.jit(fn)
    return _JIT[key]


def hidden_states(cfg: Mapping[str, Any], model_id: str,
                  docs: Sequence[Sequence[int]]):
    """The final-normed hidden states [L, d] (float32) of each document.
    Layer by layer over all the documents, so that one layer's weights
    exist at a time. Call under ``default_matmul_precision("highest")``."""
    import jax.numpy as jnp

    n_layers, eps = int(cfg["n_layers"]), float(cfg["rms_norm_eps"])
    embed = draw(cfg, model_id, "embed")
    xs = [embed[jnp.asarray(d)].astype(jnp.float32) for d in docs]
    del embed
    layer = _jitted("layer", lambda w, x: layer_forward(cfg, w, x), cfg)
    for i in range(n_layers):
        w = {name: draw(cfg, model_id, name, layer=i) for name in LEAVES[2:]}
        w["bg"] = jnp.asarray(gate_bias(int(cfg["n_kv_heads"])))
        xs = [layer(w, x) for x in xs]
        del w
    return [rms_norm(x, eps) for x in xs]


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
    """The whole-vocabulary logits [len(positions), V] (float32) that the
    given positions of one document give for their NEXT token: what a probe
    of single positions compares, where ``token_logprobs`` folds them."""
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg)
    with jax.default_matmul_precision("highest"):
        h = hidden_states(cfg, model_id, [np.asarray(doc, np.int32)])[0]
        head = draw(cfg, model_id, "head").astype(jnp.float32)
        return np.asarray(h[jnp.asarray(list(positions))] @ head.T)


def block_sums(logprobs: np.ndarray, block: int = LOSS_BLOCK) -> np.ndarray:
    """Per-token log-probabilities (t = 1 .. L-1) → sums by blocks of
    ``block`` predicting positions, float64."""
    lp = np.asarray(logprobs, np.float64)
    n = -(-len(lp) // block) if len(lp) else 0
    return np.asarray([lp[j * block:(j + 1) * block].sum() for j in range(n)])


def block_counts(n_tokens: int, block: int = LOSS_BLOCK) -> np.ndarray:
    """Targets in each block of a document of ``n_tokens`` tokens."""
    n = max(0, int(n_tokens) - 1)
    return np.asarray([min(block, n - at) for at in range(0, n, block)],
                      np.float64)


def compare(served: Sequence[Sequence[float]], reference: Sequence[Sequence[float]],
            n_tokens: Sequence[int], block: int = LOSS_BLOCK
            ) -> Dict[str, float]:
    """The numbers ``correct`` compares, from the served and the reference's
    ``block_logprob_sums`` of the sampled documents. Every block gives one
    gap e = (served - reference) / targets in the block: a mean
    log-probability a token, nats.

    ``block_logprob_bias``: |mean of e over all blocks of all documents|.
    Arithmetic that is off the same way everywhere (weights or activations
    kept in fewer bits, a missing term, a scale) lands here; rounding noise
    averages out of it.
    ``block_logprob_gap_max``: the largest single |e|. A block answered from
    the wrong tokens, a segment boundary handled wrongly or noise of a lower
    precision lands here.
    ``block_logprob_gap_slope``: |least-squares slope of e against the
    block's index|, nats a token a block, the mean over documents. A state
    carried wrongly, or in too few bits, grows with position."""
    gaps, slopes = [], []
    for s, r, n in zip(served, reference, n_tokens):
        counts = block_counts(n, block)
        s, r = np.asarray(s, np.float64), np.asarray(r, np.float64)
        if len(s) != len(counts) or len(r) != len(counts):
            return {}
        e = (s - r) / np.maximum(counts, 1.0)
        gaps.append(e)
        if len(e) >= 2:
            idx = np.arange(len(e)) - (len(e) - 1) / 2.0
            slopes.append(float((idx * (e - e.mean())).sum() / (idx ** 2).sum()))
    if not gaps:
        return {}
    flat = np.concatenate(gaps)
    if not np.all(np.isfinite(flat)):
        return {}
    return {
        "block_logprob_bias": float(abs(flat.mean())),
        "block_logprob_gap_max": float(np.abs(flat).max()),
        "block_logprob_gap_slope": float(abs(np.mean(slopes))) if slopes else 0.0,
    }
