"""Plain float32 reference of the encoder classifier ``map_classify_tpu``
serves: straightforward ``jax.numpy``, no kernels, no batching tricks, under
``default_matmul_precision("highest")``. It imports nothing of the program
and takes nothing the program made: the weights come from the model id by
the program's PUBLISHED recipe (model id → sha256 → PRNG key → one normal
draw per leaf, scaled 1/sqrt(fan_in); embedding 0.02), written out again
here (``tests/benchmarks`` holds the two recipes to the same bits), and are
then rounded once to the configuration's stated dtype: the arithmetic is
float32 throughout, the model is the one the configuration defines.

Equations (departures from BERT as published are the program's, listed in
``benchmarks/configs/bert-base.json`` under ``assumed``): byte tokens
(id = byte + 4, no BOS/EOS, truncated to ``max_len``); x = E[ids] +
sinusoidal positions; ``n_layers`` pre-LN blocks (x += Attn(LN(x));
x += W2·gelu_tanh(W1·LN(x))); a final LN; mean over real tokens; a linear
head; softmax over classes."""

from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Mapping, Sequence

import numpy as np

N_SPECIAL = 4
NEG_INF = -1e9
LN_EPS = 1e-6


def seed_key(model_id: str):
    import jax

    digest = hashlib.sha256(model_id.encode("utf-8")).digest()
    return jax.random.PRNGKey(int.from_bytes(digest[:4], "big"))


def _normal(key, shape, fan_in):
    import jax
    import jax.numpy as jnp

    return jax.random.normal(key, shape, dtype=jnp.float32) * (
        1.0 / np.sqrt(max(1, fan_in)))


def _dense(key, d_in, d_out):
    import jax.numpy as jnp

    return {"w": _normal(key, (d_in, d_out), d_in),
            "b": jnp.zeros((d_out,), jnp.float32)}


def _layer_norm_params(d):
    import jax.numpy as jnp

    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def init_attention(key, d_model, n_heads):
    import jax

    d_head = d_model // n_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": _normal(ks[0], (d_model, n_heads, d_head), d_model),
        "wk": _normal(ks[1], (d_model, n_heads, d_head), d_model),
        "wv": _normal(ks[2], (d_model, n_heads, d_head), d_model),
        "wo": _normal(ks[3], (n_heads, d_head, d_model), d_model),
    }


def init_block(key, d_model, n_heads, d_ff, cross=False):
    import jax

    ks = jax.random.split(key, 3)
    k1, k2 = jax.random.split(ks[1])
    p = {
        "ln1": _layer_norm_params(d_model),
        "attn": init_attention(ks[0], d_model, n_heads),
        "ln2": _layer_norm_params(d_model),
        "ffn": {"wi": _dense(k1, d_model, d_ff), "wo": _dense(k2, d_ff, d_model)},
    }
    if cross:
        p["ln_x"] = _layer_norm_params(d_model)
        p["xattn"] = init_attention(ks[2], d_model, n_heads)
    return p


def sinusoidal_positions(length: int, d_model: int) -> np.ndarray:
    pos = np.arange(length)[:, None].astype(np.float64)
    dim = np.arange(0, d_model, 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, dim / d_model)
    table = np.zeros((length, d_model), dtype=np.float32)
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle)
    return table


def init_params(cfg: Mapping[str, Any], model_id: str) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    d, n = int(cfg["d_model"]), int(cfg["n_layers"])
    ks = jax.random.split(seed_key(model_id), n + 3)
    return {
        "embed": jax.random.normal(
            ks[0], (int(cfg["vocab_size"]), d), dtype=jnp.float32) * 0.02,
        "pos": jnp.asarray(sinusoidal_positions(int(cfg["max_len"]), d)),
        "blocks": [init_block(ks[i + 1], d, int(cfg["n_heads"]),
                              int(cfg["d_ff"])) for i in range(n)],
        "ln_f": _layer_norm_params(d),
        "head": _dense(ks[-1], d, int(cfg["n_classes"])),
    }


def tokenize(texts: Sequence[str], max_len: int, rows: int = 0):
    """ids [B, L] int32 and mask [B, L] int32. L is the smallest of 64, 128,
    256, ... (capped at ``max_len``) that holds the longest row, and B at
    least ``rows`` (padding rows are all mask 0), so that one cell compiles
    one reference program whatever the seed drew."""
    rows_ids = [[b + N_SPECIAL for b in t.encode("utf-8")][:max_len]
                for t in texts]
    longest = max(1, max(len(r) for r in rows_ids))
    width = 64
    while width < longest:
        width *= 2
    width = min(width, max_len)
    ids = np.zeros((max(len(rows_ids), rows), width), np.int32)
    mask = np.zeros_like(ids)
    for i, r in enumerate(rows_ids):
        ids[i, :len(r)] = r
        mask[i, :len(r)] = 1
    return ids, mask


# ---- the mathematics -----------------------------------------------------

def layer_norm(p, x):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def attention(p, x_q, x_kv, mask):
    """``mask`` broadcasts to [B, H, Lq, Lk]; 1 = attend."""
    import jax
    import jax.numpy as jnp

    q = jnp.einsum("bld,dhe->bhle", x_q, p["wq"])
    k = jnp.einsum("bld,dhe->bhle", x_kv, p["wk"])
    v = jnp.einsum("bld,dhe->bhle", x_kv, p["wv"])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(mask > 0, scores, NEG_INF)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bhle,hed->bld", out, p["wo"])


def ffn(p, x):
    h = gelu_tanh(x @ p["wi"]["w"] + p["wi"]["b"])
    return h @ p["wo"]["w"] + p["wo"]["b"]


def forward(params, ids, mask):
    """Logits [B, n_classes], float32."""
    import jax.numpy as jnp

    L = ids.shape[1]
    x = params["embed"][ids] + params["pos"][:L][None]
    attn_mask = mask[:, None, None, :]
    for blk in params["blocks"]:
        h = layer_norm(blk["ln1"], x)
        x = x + attention(blk["attn"], h, h, attn_mask)
        x = x + ffn(blk["ffn"], layer_norm(blk["ln2"], x))
    x = layer_norm(params["ln_f"], x)
    denom = jnp.maximum(mask.sum(axis=1, keepdims=True), 1).astype(jnp.float32)
    pooled = (x * mask[:, :, None]).sum(axis=1) / denom
    return pooled @ params["head"]["w"] + params["head"]["b"]


_FORWARD = None     # one jitted forward for every call of a process


def configured_params(cfg: Mapping[str, Any], model_id: str) -> Dict[str, Any]:
    """The weights the configuration defines: drawn from the model id, then
    rounded ONCE to the dtype it states (a bf16 model's weights are bf16
    values) and held in float32, so all arithmetic on them stays float32.
    Against weights left unrounded a sound bf16 run reads three times
    further from the reference, all of it this rounding (PERF.md)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(str(cfg.get("dtype", "float32")))
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype).astype(jnp.float32),
        init_params(cfg, model_id))


def logits(cfg: Mapping[str, Any], model_id: str, texts: Sequence[str],
           block: int = 32) -> np.ndarray:
    """Class logits [len(texts), n_classes] in float32 at the highest matmul
    precision, ``block`` rows at a time so it fits beside whatever else the
    device holds."""
    import jax

    global _FORWARD
    if _FORWARD is None:
        _FORWARD = jax.jit(forward)
    out: List[np.ndarray] = []
    with jax.default_matmul_precision("highest"):
        params = configured_params(cfg, model_id)
        for at in range(0, len(texts), block):
            chunk = texts[at:at + block]
            ids, mask = tokenize(chunk, int(cfg["max_len"]), rows=block)
            out.append(np.asarray(_FORWARD(params, ids, mask))[:len(chunk)])
    return (np.concatenate(out) if out
            else np.zeros((0, int(cfg["n_classes"])), np.float32))


def log_softmax(z: np.ndarray) -> np.ndarray:
    z = np.asarray(z, np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


MIN_GROUP = 4   # answers a (model, class) pair needs before its mean counts


def compare(ref_logits: np.ndarray, indices: np.ndarray, scores: np.ndarray,
            model: np.ndarray) -> Dict[str, float]:
    """The numbers ``correct`` compares, from the served top-k answers
    (``indices``, ``scores`` [N, k]) of N sampled rows, the reference's
    logits [N, classes] for the same rows and each row's tenant model.

    Every served score has an error e = log(score) - the reference's
    log-probability of that class for that row. With seeded weights all rows
    of one model are answered with nearly the same few classes, so the errors
    fall into (model, class) groups, and split into two parts that different
    faults move:

    ``top5_logprob_bias_rms``: root mean square, over the groups, of the
    group's MEAN error. Arithmetic that is off in the same way for every row
    (weights kept in fewer bits, a scale, a missing term) lands here, and the
    row-to-row rounding noise averages out of it.
    ``top5_logprob_scatter_rms``: root mean square of what is left of each
    error once its group's mean is taken off. Answers that belong to other
    rows (a shard out of order, part of a batch left out) land here.
    ``top5_prob_gap_max``: the widest single gap, as a share of the
    reference's best probability: |served score - reference probability| of
    any served class, or the reference's best over what it gives the class
    served first. A wrong class or a wild score lands here."""
    n_classes = ref_logits.shape[-1]
    logp = log_softmax(ref_logits)
    at_served = np.take_along_axis(logp, indices, axis=-1)
    err = np.log(np.maximum(scores, 1e-300)) - at_served
    key = (np.asarray(model)[:, None] * n_classes + indices).ravel()
    _, inverse, counts = np.unique(key, return_inverse=True, return_counts=True)
    means = np.bincount(inverse, weights=err.ravel()) / counts
    kept = counts[inverse] >= MIN_GROUP
    out: Dict[str, float] = {}
    if kept.any():
        out["top5_logprob_bias_rms"] = float(
            np.sqrt(np.mean(means[counts >= MIN_GROUP] ** 2)))
        out["top5_logprob_scatter_rms"] = float(
            np.sqrt(np.mean((err.ravel() - means[inverse])[kept] ** 2)))
    best = np.exp(logp.max(axis=-1))
    rel = np.abs(scores - np.exp(at_served)) / best[:, None]
    miss = (best - np.exp(at_served[:, 0])) / best
    out["top5_prob_gap_max"] = float(max(rel.max(), miss.max()))
    return out
