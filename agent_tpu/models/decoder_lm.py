"""Decoder-only language models — the family behind ``map_score_lm``.

One file for the family, not one a checkpoint (ROADMAP.md D1): a block is
described by its LAYER TYPE — pre-norm residual block, RMS norm, rotary
positions, grouped-query projections with a per-head RMS norm on q and k, a
``mixer`` that mixes along the sequence, a SwiGLU feed-forward — and the
``mixer`` key names which sequence mixer the type runs (``MIXERS``).
``power_retention`` (gated power retention of degree 2,
``kernels/power_retention.py``) is the first: it carries a fixed-size state
along the sequence, so a document longer than one program runs as
fixed-shape SEGMENTS with the state handed from one to the next
(:func:`forward_segment`). Layers are stacked and scanned; embedding and
output head are untied.

Weights are STORED in the compute dtype (bf16) and made ON THE DEVICE, leaf
by leaf, by one jitted initializer from the model id (:func:`init_params`):
at the published widths eight layers and the vocabulary are 4.2 G parameters
— 8.4 GB in bf16, and 16.8 GB (more than a chip holds, and minutes of host
time) if built on the host in float32 as the encoder families are.

The weight rule (also written, independently, in the benchmark's reference):
root key = ``layers.seed_from(model_id)``; leaf ``j`` of ``LEAVES`` draws
from ``fold_in(root, j)``, a per-layer leaf for layer ``i`` from
``fold_in(fold_in(root, j), i)``; a standard normal in float32 times
``1/sqrt(fan_in)`` (embedding: 1), rounded once to the stored dtype. Norm
weights are 1. The gate's bias gives key-value head ``b`` a memory of
``16 * 2**b`` tokens: ``log(16 * 2**b - 1)`` (see ``GATE_TAU0``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from agent_tpu.models import layers
from agent_tpu.models.layers import Params

# Leaves that draw random numbers, in the order that keys them.
LEAVES = ("embed", "head", "wq", "wk", "wv", "wo", "wg", "w_gate", "w_up",
          "w_down")
# The layer leaves a quantized mode replaces (``models.quant``).
LINEAR_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# sigmoid(log(tau - 1)) = 1 - 1/tau: head b forgets over tau0 * 2**b tokens.
GATE_TAU0 = 16.0
# Tokens a loss block: the op reports the log-probability summed a block.
LOSS_BLOCK = 1024
# Vocabulary rows a block of the loss head: [segment, VOCAB_BLOCK] float32
# logits are the only logits that ever exist.
VOCAB_BLOCK = 4096


@dataclass(frozen=True)
class DecoderLMConfig:
    """Defaults are a tiny model for tests; the published sizes come in as
    ``model_config`` (benchmarks/configs/brumby-14b-base.json)."""

    vocab_size: int = 512
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 2
    d_head: int = 32
    d_ff: int = 256
    n_layers: int = 2
    max_len: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    mixer: str = "power_retention"
    dtype: str = "bfloat16"
    quant: str = "none"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def validate(cfg: DecoderLMConfig) -> None:
    """ValueError (a caller's error) on a config no program can run."""
    if cfg.mixer not in MIXERS:
        raise ValueError(f"mixer must be one of {sorted(MIXERS)}, "
                         f"got {cfg.mixer!r}")
    if cfg.n_kv_heads <= 0 or cfg.n_heads % cfg.n_kv_heads:
        raise ValueError("n_heads must be a multiple of n_kv_heads")
    if cfg.d_head % 2:
        raise ValueError("d_head must be even (rotary pairs)")
    for name in ("vocab_size", "d_model", "d_ff", "n_layers", "max_len"):
        if int(getattr(cfg, name)) <= 0:
            raise ValueError(f"{name} must be positive")


# ---- weights --------------------------------------------------------------

def _leaf_shapes(cfg: DecoderLMConfig) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """leaf → (shape of one layer's leaf (or the whole leaf), fan_in)."""
    d, f = cfg.d_model, cfg.d_ff
    hq, hkv = cfg.n_heads * cfg.d_head, cfg.n_kv_heads * cfg.d_head
    return {
        "embed": ((cfg.vocab_size, d), 1), "head": ((cfg.vocab_size, d), d),
        "wq": ((d, hq), d), "wk": ((d, hkv), d), "wv": ((d, hkv), d),
        "wo": ((hq, d), hq), "wg": ((d, cfg.n_kv_heads), d),
        "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f),
    }


def gate_bias(n_kv_heads: int) -> np.ndarray:
    return np.log(GATE_TAU0 * 2.0 ** np.arange(n_kv_heads) - 1.0).astype(
        np.float32)


def init_params(cfg: DecoderLMConfig, model_id: str, sharding=None) -> Params:
    """The family's weights, built on the device in the stored dtype. With
    ``sharding`` the leaves come out committed to it, which is how
    ``TpuRuntime.get_params`` keeps them as built."""
    dtype = cfg.compute_dtype
    n = cfg.n_layers
    root = layers.seed_from(model_id)

    def draw(shape, fan_in, stacked):
        def fn(key):
            def one(k):
                w = jax.random.normal(k, shape, dtype=jnp.float32)
                return (w * (1.0 / np.sqrt(max(1, fan_in)))).astype(dtype)

            if not stacked:
                return one(key)
            return jax.vmap(one)(jax.vmap(
                lambda i: jax.random.fold_in(key, i))(jnp.arange(n)))

        return jax.jit(fn, out_shardings=sharding)

    def const(value, shape):
        return jax.jit(lambda: jnp.broadcast_to(
            jnp.asarray(value, jnp.float32), shape).astype(jnp.float32),
            out_shardings=sharding)()

    shapes = _leaf_shapes(cfg)
    drawn = {
        name: draw(*shapes[name], stacked=name not in ("embed", "head"))(
            jax.random.fold_in(root, j))
        for j, name in enumerate(LEAVES)
    }
    d, dh = cfg.d_model, cfg.d_head
    return {
        "embed": drawn["embed"], "head": drawn["head"],
        "final_norm": const(1.0, (d,)),
        "layers": {
            **{k: drawn[k] for k in LEAVES[2:]},
            "bg": const(gate_bias(cfg.n_kv_heads), (n, cfg.n_kv_heads)),
            "ln1": const(1.0, (n, d)), "ln2": const(1.0, (n, d)),
            "q_norm": const(1.0, (n, dh)), "k_norm": const(1.0, (n, dh)),
        },
    }


# ---- the mathematics ------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Float32 statistics, the input's dtype out."""
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary positions, half-split pairs (i, i + D/2): x [..., L, H, D],
    positions [L]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # [L, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return (xf * cos + rot * sin).astype(x.dtype)


def linear(w: Any, x: jax.Array, dtype: Any) -> jax.Array:
    """x [..., in] @ w [in, out]; dispatches on the quantized leaf shapes of
    ``models.quant`` as ``layers.dense`` does."""
    from agent_tpu.models import quant

    if quant.is_quantized(w):
        return quant.qdense(w, x, dtype)
    if quant.is_weight_only(w):
        return quant.wdense(w, x, dtype)
    return jnp.dot(x.astype(dtype), w.astype(dtype))


def _power_retention_mixer(p: Params, h: jax.Array, positions: jax.Array,
                           state, cfg: DecoderLMConfig, kernel_opts):
    """h [B, L, d] (normed) → (mixed [B, L, Hq*D], new state)."""
    from agent_tpu.kernels.power_retention import power_retention

    dtype = cfg.compute_dtype
    B, L, _ = h.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = linear(p["wq"], h, dtype).reshape(B, L, hq, dh)
    k = linear(p["wk"], h, dtype).reshape(B, L, hkv, dh)
    v = linear(p["wv"], h, dtype)
    q = rope(rms_norm(q, p["q_norm"], cfg.rms_norm_eps), positions,
             cfg.rope_theta).reshape(B, L, hq * dh)
    k = rope(rms_norm(k, p["k_norm"], cfg.rms_norm_eps), positions,
             cfg.rope_theta).reshape(B, L, hkv * dh)
    gate = jnp.dot(h.astype(dtype), p["wg"].astype(dtype),
                   preferred_element_type=jnp.float32) + p["bg"]
    log_g = jax.nn.log_sigmoid(gate)                        # [B, L, Hkv] f32
    return power_retention(q, k, v, log_g, n_kv_heads=hkv,
                           initial_state=state, **kernel_opts)


# mixer name → fn(layer params, normed h, positions, state, cfg, opts)
# → (mixed [B, L, Hq*D], new state). One entry a sequence mixer.
MIXERS: Dict[str, Callable] = {"power_retention": _power_retention_mixer}


def _layer(p: Params, x: jax.Array, positions, state, cfg, kernel_opts):
    dtype = cfg.compute_dtype
    h = rms_norm(x, p["ln1"], cfg.rms_norm_eps)
    mixed, state = MIXERS[cfg.mixer](p, h, positions, state, cfg, kernel_opts)
    x = x + linear(p["wo"], mixed, dtype)
    n = rms_norm(x, p["ln2"], cfg.rms_norm_eps)
    ff = jax.nn.silu(linear(p["w_gate"], n, dtype)) * linear(p["w_up"], n, dtype)
    return x + linear(p["w_down"], ff, dtype), state


def forward_segment(params: Params, ids: jax.Array, pos0: jax.Array,
                    state, cfg: DecoderLMConfig, **kernel_opts):
    """One fixed-shape segment of a document: ids [B, S] int32, ``pos0`` the
    position of its first token, ``state`` what the previous segment
    returned (``None``: the document starts here; a pytree with a leading
    layer axis otherwise). Returns the final-normed hidden states [B, S, d]
    and the state after the segment."""
    dtype = cfg.compute_dtype
    x = params["embed"][ids].astype(dtype)
    positions = pos0.astype(jnp.int32) + jnp.arange(ids.shape[1])

    def step(x, xs):
        p, st = xs if state is not None else (xs, None)
        x, st = _layer(p, x, positions, st, cfg, kernel_opts)
        return x, st

    xs = params["layers"] if state is None else (params["layers"], state)
    x, new_state = jax.lax.scan(step, x, xs)
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps), new_state


def blocked_logprobs(hidden: jax.Array, head: jax.Array, targets: jax.Array,
                     vocab_block: Optional[int] = None) -> jax.Array:
    """log p(target) per position, float32, never holding more than a
    [N, vocab_block] block of logits: a running log-sum-exp and the target's
    logit, vocabulary block by block. hidden [N, d], head [V, d] (rows are
    vocabulary entries), targets [N] int32."""
    V = head.shape[0]
    vb = min(int(vocab_block or VOCAB_BLOCK), V)
    n_full, tail = divmod(V, vb)
    N = hidden.shape[0]
    f32 = jnp.float32

    def fold(carry, w, offset):
        m, l, hit = carry
        logits = jax.lax.dot_general(hidden, w, (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)  # [N, rows]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.exp(
            logits - m_new[:, None]).sum(axis=-1)
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        mine = cols == (targets - offset)[:, None]
        return m_new, l, hit + jnp.where(mine, logits, 0.0).sum(axis=-1)

    carry = (jnp.full((N,), -jnp.inf, f32), jnp.zeros((N,), f32),
             jnp.zeros((N,), f32))
    carry = jax.lax.fori_loop(
        0, n_full, lambda i, c: fold(
            c, jax.lax.dynamic_slice_in_dim(head, i * vb, vb, 0), i * vb),
        carry)
    if tail:
        carry = fold(carry, head[n_full * vb:], n_full * vb)
    m, l, hit = carry
    return hit - (m + jnp.log(l))


def segment_block_sums(hidden: jax.Array, head: jax.Array,
                       targets: jax.Array, n_valid: jax.Array,
                       block: int = LOSS_BLOCK) -> jax.Array:
    """hidden [1, S, d], targets [1, S] (the NEXT token of every position),
    ``n_valid`` positions that have one → the log-probabilities summed a
    ``block`` of positions, [S / block] float32."""
    S = hidden.shape[1]
    lp = blocked_logprobs(hidden[0], head, targets[0])
    lp = jnp.where(jnp.arange(S) < n_valid, lp, 0.0)
    return lp.reshape(S // block, block).sum(axis=-1)
