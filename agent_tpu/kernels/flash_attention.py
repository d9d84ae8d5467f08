"""Fused (flash) attention as a Pallas TPU kernel.

One grid program computes one [block_q, d_head] query tile for one (batch,
head). The innermost grid axis walks K/V tiles sequentially (TPU grids are
sequential, innermost fastest), carrying the streaming-softmax state — running
row max ``m``, denominator ``l``, numerator ``acc`` — in VMEM scratch that
persists across that axis. The [Lq, Lk] score matrix therefore never exists in
HBM; each tile's QKᵀ → mask → exp → ·V chain runs entirely out of VMEM, with
the MXU doing both matmuls (``preferred_element_type=f32``) and the VPU the
elementwise tail. This is the schedule XLA cannot be relied on to find whole:
it will fuse the elementwise chain, but materializes scores for long
sequences.

Numerics match ``agent_tpu.models.layers.dot_product_attention`` (f32 softmax
accumulation, finite ``NEG_INF`` masking, zero output — not NaN — for
fully-masked rows) so the kernel is a drop-in ``attn_fn``. Unsupported shapes
(mask with a query dim, tile-indivisible lengths) fall back to the dense XLA
path; off-TPU the kernel runs in interpreter mode when asked, but the runtime
only selects it on real TPU (``TpuRuntime.attention_fn``).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from agent_tpu.models.layers import NEG_INF, dot_product_attention

_LANES = 128  # VPU lane width; scratch last dims pad to this anyway


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret=None`` → interpreter mode off-TPU. The tests' convenience
    only (the identical kernel runs on the CPU mesh): ``TpuRuntime`` decides
    from its own devices and always passes ``interpret=False`` explicitly."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret

# Below this key length the XLA dense path wins END TO END. Attention-only
# microbenchmarks on v5e show the kernel ahead already at Lk=512/d_head 64
# (1.25-1.4×), but inside the full encoder the gate at 512 measured ~13%
# SLOWER at BERT-base scale: pallas_call is a fusion barrier — XLA can no
# longer fuse the projection matmuls/softmax chain around attention — and
# the [B,L,H,D]→grid layout transitions eat the kernel's margin. The win
# is real once the dense path's [Lq, Lk] score materialization dominates.
# Measured per-call ratios vs the CURRENT dense path (which stores scores
# in bf16 — that change roughly doubled dense speed and honestly shrank
# these ratios from the old f32-score era's 3.7×/50×): 1.76× at 4k,
# 2.21× at 8k, d_head 128 (driver artifact `flash_vs_dense[_8k]`,
# BENCH_r05). The kernel's bigger win at long context is MEMORY — no
# [L, L] score tensor in HBM, so batch/length scale past where dense
# OOMs. Hence the 2048 gate; trust model-level numbers over kernel
# microbenchmarks when moving it.
FLASH_MIN_KEY_LEN = 2048

# TRAINING gates lower. Serving loses at 512 because pallas_call breaks
# XLA's fusions around a forward-only pass — but the backward-dense path
# also re-materializes and re-reads the [B, H, L, L] score tensor, which
# at BERT-base train shapes (B 256, L 512) is ~1.6 GB of HBM traffic per
# layer per direction. Measured on v5e at seq 512, remat=full: flash
# 255 ex/s vs dense 246; and because the flash backward stores NO score
# tensors, it unlocks remat-free training at batch 128 — 308 ex/s,
# 45.3% MFU vs the dense+remat baseline's 246 / 36.2% (bench `train` leg).
FLASH_TRAIN_MIN_KEY_LEN = 512

# Trace-time selection tally: ``flash_attention`` decides kernel-vs-dense while
# the surrounding jit TRACES (the gate is static shape metadata), so these
# counters tick once per compiled program, not per call. bench.py diffs them
# around a warmup to *prove* which path a compiled executable contains —
# "the bench exercises the Pallas kernel" becomes an assertion, not a belief.
SELECTION_COUNTS = {"flash": 0, "dense": 0}


def selects_flash(seq_len: int, *, block: int = 512,
                  min_key_len: Optional[int] = None) -> bool:
    """Shape-only predicate: will self-attention at ``seq_len`` (Lq == Lk,
    conforming key-padding mask, default tiles) take the Pallas path?

    Mirrors the ``supported`` gate in :func:`flash_attention` — staging code
    (``ops._model_common.split_padded_chunk``) uses it to budget dense-path
    dispatch chunks without touching device state, so a ≥2048 length that the
    kernel would still reject (not tile-divisible → dense fallback) is
    correctly treated as dense there too."""
    if min_key_len is None:
        min_key_len = FLASH_MIN_KEY_LEN
    if seq_len < min_key_len:
        return False
    return seq_len % min(block, seq_len) == 0


def selects_flash_train(seq_len: int, *, batch: int, n_heads: int,
                        mesh=None, block: int = 512,
                        min_key_len: Optional[int] = None) -> bool:
    """Shape-only predicate for the TRAINING path: will
    ``make_flash_attention_trainable(mesh)`` run the Pallas kernel for a
    [batch, n_heads, seq_len, ·] self-attention?

    Combines the trainable gate (``FLASH_TRAIN_MIN_KEY_LEN``, tile
    divisibility) with the mesh wrapper's dp/tp divisibility fallback
    (``_make_mesh_wrapper``), which otherwise silently reverts to dense.
    Code that turns OFF rematerialization on the strength of "flash is
    selected" must consult this — not the ``attn_fn`` identity, which is
    the wrapper for every shape — or a wrapper-level dense fallback would
    store [L, L] score tensors with remat disabled (bench ``train`` leg)."""
    if min_key_len is None:
        min_key_len = FLASH_TRAIN_MIN_KEY_LEN
    if not selects_flash(seq_len, block=block, min_key_len=min_key_len):
        return False
    if mesh is not None and mesh.size > 1:
        shape = dict(mesh.shape)
        if not _wrapper_shardable(batch, n_heads,
                                  shape.get("dp", 1), shape.get("tp", 1)):
            return False
    return True


def _wrapper_shardable(batch: int, n_heads: int, dp: int, tp: int) -> bool:
    """THE mesh-wrapper divisibility gate — single-sourced so
    ``_make_mesh_wrapper``'s runtime fallback and ``selects_flash_train``'s
    prediction cannot diverge (a divergence would let a caller disable
    remat while the wrapper silently runs dense)."""
    return batch % dp == 0 and n_heads % tp == 0


def _tile_softmax_update(s, keep, v_ref, m_scr, l_scr, acc_scr) -> None:
    """THE streaming-softmax tile fold: update VMEM state (m, l, acc) with
    one [bq, bk] score tile. Single-sourced for every kernel in this module
    (inference, lse-emitting trainable forward, fold, T5 bias) — and
    mirrored in ``agent_tpu.parallel.ring``'s einsum fold; keep the two in
    sync on any numerics change.

    ``s`` must already be masked to ``NEG_INF`` off-``keep``; the ``* keep``
    below makes masked entries contribute exactly 0 even in an all-masked
    tile (where s == m_new == NEG_INF would make exp() == 1).
    """
    m_prev = m_scr[:, :1]                                 # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * keep                         # [bq, bk]
    corr = jnp.exp(m_prev - m_new)                        # [bq, 1]
    l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0],          # bf16 MXU, f32 accumulate
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, n_k: int):
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Matmuls stay in the input dtype (bf16 on TPU = full MXU rate) with f32
    # accumulation; scaling after the dot is linear-equivalent to scaling q.
    s = jax.lax.dot_general(                              # [bq, bk] on the MXU
        q_ref[0, 0], k_ref[0, 0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    keep = mask_ref[0, 0, :][None, :] > 0                 # [1, bk]
    s = jnp.where(keep, s, NEG_INF)
    _tile_softmax_update(s, keep, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(kb == n_k - 1)
    def _emit():
        # Fully-padded rows have l == 0: emit 0, not NaN.
        o_ref[0, 0] = (
            acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,      # [B, H, Lq, D]
    k: jax.Array,      # [B, H, Lk, D]
    v: jax.Array,      # [B, H, Lk, D]
    mask: jax.Array,   # [B|1, 1, 1, Lk] key-padding mask (1 = attend)
    *,
    block_q: int = 512,
    block_k: int = 512,
    min_key_len: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Drop-in ``attn_fn``: fused attention, dense-XLA fallback off-contract.

    ``interpret=None`` auto-selects interpreter mode off-TPU so the identical
    kernel is testable on the CPU mesh; pass False to require Mosaic.

    Default 512×512 tiles measured best on v5e (scores tile = 1 MB VMEM).
    Measured v5e per-call ratios vs the dense XLA path: 1.33× at 4k
    context, 1.94× at 8k, at d_head 128 — see the ``FLASH_MIN_KEY_LEN``
    note (incl. why these shrank when dense went bf16-score) and
    ``bench.py``'s ``long_ctx`` leg, which records both as driver
    artifacts (``flash_vs_dense_speedup``, ``flash_vs_dense_8k``).
    """
    from agent_tpu.models.layers import is_key_padding_mask

    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    bq = min(block_q, Lq)
    bk = min(block_k, Lk)
    if min_key_len is None:
        min_key_len = FLASH_MIN_KEY_LEN
    supported = (
        is_key_padding_mask(mask, B, Lk)
        and Lk >= min_key_len
        and Lq % bq == 0
        and Lk % bk == 0
    )
    SELECTION_COUNTS["flash" if supported else "dense"] += 1
    if not supported:
        return dot_product_attention(q, k, v, mask)
    interpret = resolve_interpret(interpret)

    # [B, 1, Lk]: the singleton keeps the mask block's last-two dims legal
    # under Mosaic's (8, 128)-divisible-or-full rule (1 == full dim).
    mask3d = jnp.broadcast_to(mask[:, 0, :, :], (B, 1, Lk)).astype(jnp.int32)
    n_q, n_k = Lq // bq, Lk // bk
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / np.sqrt(D), n_k=n_k
    )
    grid = (B, H, n_q, n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running denom l
            pltpu.VMEM((bq, D), jnp.float32),        # running numerator acc
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * Lq * Lk * D,
            bytes_accessed=(2 * B * H * Lq * D + 2 * B * H * Lk * D) * q.dtype.itemsize,
            transcendentals=B * H * Lq * Lk,
        ),
        interpret=interpret,
    )(q, k, v, mask3d)


def _flash_fold_kernel(q_ref, k_ref, v_ref, mask_ref,
                       m_in_ref, l_in_ref, acc_in_ref,
                       m_out_ref, l_out_ref, acc_out_ref,
                       m_scr, l_scr, acc_scr, *, scale: float, n_k: int):
    """One flash pass over a K/V block with *carried* softmax state.

    The ring-attention hop kernel: instead of zero-initializing (m, l, acc)
    like :func:`_flash_kernel`, state streams in from the previous hop and
    streams out updated — same per-tile fold math, composable across hops.
    """
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.broadcast_to(m_in_ref[0, 0], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_in_ref[0, 0], l_scr.shape)
        acc_scr[:] = acc_in_ref[0, 0]

    s = jax.lax.dot_general(
        q_ref[0, 0], k_ref[0, 0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    keep = mask_ref[0, 0, :][None, :] > 0
    s = jnp.where(keep, s, NEG_INF)
    _tile_softmax_update(s, keep, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(kb == n_k - 1)
    def _emit():
        m_out_ref[0, 0] = m_scr[:, :1]
        l_out_ref[0, 0] = l_scr[:, :1]
        acc_out_ref[0, 0] = acc_scr[:]


def flash_fold_supported(q_shape, lk: int, *, block_q: int = 512,
                         block_k: int = 512) -> bool:
    """Static-shape gate for :func:`flash_fold` (per-hop blocks are already
    short, so no min-length heuristic here — the caller chose the ring)."""
    _, _, lq, _ = q_shape
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    return lq % bq == 0 and lk % bk == 0


def flash_fold(q, k, v, mask, m, l, acc, *, block_q: int = 512,
               block_k: int = 512, interpret: Optional[bool] = None,
               vma=None):
    """Fold K/V block ``k``/``v`` (key-padding ``mask`` [B, 1, 1, Lk]) into
    streaming-softmax state ``(m, l, acc)`` → updated state. The Pallas form
    of ``agent_tpu.parallel.ring``'s einsum fold — one fused VMEM pass.

    ``vma``: varying-mesh-axes annotation for the outputs — required when
    called inside a ``shard_map`` with vma checking (the ring passes its
    mesh axes); leave None outside shard_map.
    """
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    bq = min(block_q, Lq)
    bk = min(block_k, Lk)
    interpret = resolve_interpret(interpret)
    mask3d = jnp.broadcast_to(mask[:, 0, :, :], (B, 1, Lk)).astype(jnp.int32)
    n_q, n_k = Lq // bq, Lk // bk
    kernel = functools.partial(
        _flash_fold_kernel, scale=1.0 / np.sqrt(D), n_k=n_k
    )
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            qspec, kspec, kspec,
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
            sspec, sspec, qspec,
        ],
        out_specs=(sspec, sspec, qspec),
        out_shape=(
            jax.ShapeDtypeStruct(m.shape, jnp.float32, vma=vma),
            jax.ShapeDtypeStruct(l.shape, jnp.float32, vma=vma),
            jax.ShapeDtypeStruct(acc.shape, jnp.float32, vma=vma),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, mask3d, m.astype(jnp.float32), l.astype(jnp.float32),
      acc.astype(jnp.float32))


def _flash_t5_kernel(q_ref, k_ref, v_ref, mask_ref, bias_ref, o_ref,
                     m_scr, l_scr, acc_scr, *, scale: float, n_k: int,
                     bq: int, bk: int, num_buckets: int, max_distance: int,
                     bidirectional: bool, n_heads: int):
    """Flash attention with T5's bucketed relative-position bias computed
    PER TILE in VMEM — the [H, Lq, Lk] bias tensor never exists in HBM
    (at 16 heads × 8k² it alone would be 4 GB, defeating the kernel).

    ``bias_ref`` is this head's [num_buckets, 1] learned bias column. The
    tile's bucket map comes from absolute tile offsets (grid coords × block
    sizes + iota); the gather from the 32-entry table is an unrolled
    one-hot accumulation (Mosaic has no vectorized gather; 32 masked adds
    per tile cost ~VPU parity with the tile's MXU work).
    """
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qi = pl.program_id(2)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # The ONE bucket definition (models/t5.py, HF semantics) traces fine
    # inside the kernel — plain jnp ops; trace-time import avoids a cycle.
    from agent_tpu.models.t5 import relative_position_bucket

    bucket = relative_position_bucket(
        k_pos - q_pos, bidirectional, num_buckets, max_distance
    )

    # The whole [num_buckets, H] table rides in VMEM (tiny; Mosaic requires
    # full-dim blocks for its shape). This head's column is selected with a
    # one-hot reduction (Mosaic lowers neither dynamic_slice nor gathers):
    # cols[b, 0] = table[b, head].
    head = pl.program_id(1)
    h_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_heads), 1)
    head_1h = (h_iota == head).astype(jnp.float32)            # [1, H]
    cols = jnp.sum(bias_ref[:, :] * head_1h, axis=1, keepdims=True)
    bias = jnp.zeros((bq, bk), dtype=jnp.float32)
    for b in range(num_buckets):  # static unroll: one-hot gather
        bias += jnp.where(bucket == b, cols[b, 0], 0.0)

    s = jax.lax.dot_general(
        q_ref[0, 0], k_ref[0, 0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale + bias
    keep = mask_ref[0, 0, :][None, :] > 0
    s = jnp.where(keep, s, NEG_INF)
    _tile_softmax_update(s, keep, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(kb == n_k - 1)
    def _emit():
        o_ref[0, 0] = (
            acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention_t5(
    q: jax.Array,          # [B, H, Lq, D]
    k: jax.Array,          # [B, H, Lk, D]
    v: jax.Array,          # [B, H, Lk, D]
    mask: jax.Array,       # [B|1, 1, 1, Lk] key-padding mask (1 = attend)
    rel_bias: jax.Array,   # [num_buckets, H] learned bias table
    *,
    bidirectional: bool = True,
    max_distance: int = 128,
    scale: float = 1.0,    # T5 attention is unscaled
    block_q: int = 512,
    block_k: int = 512,
    min_key_len: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Fused T5-style attention (scores·scale + bucketed relative bias →
    masked streaming softmax → ·V). Returns the [B, H, Lq, D] context, or
    **None** for unsupported shapes — the caller keeps its own dense path
    (the trace-time None keeps selection visible to the model code instead
    of silently diverging here).
    """
    from agent_tpu.models.layers import is_key_padding_mask

    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    num_buckets = int(rel_bias.shape[0])
    bq = min(block_q, Lq)
    bk = min(block_k, Lk)
    if min_key_len is None:
        min_key_len = FLASH_MIN_KEY_LEN
    supported = (
        is_key_padding_mask(mask, B, Lk)
        and Lk >= min_key_len
        and Lq % bq == 0
        and Lk % bk == 0
        and rel_bias.ndim == 2
        and rel_bias.shape[1] == H
    )
    SELECTION_COUNTS["t5_flash" if supported else "t5_dense"] = (
        SELECTION_COUNTS.get("t5_flash" if supported else "t5_dense", 0) + 1
    )
    if not supported:
        return None
    interpret = resolve_interpret(interpret)

    mask3d = jnp.broadcast_to(mask[:, 0, :, :], (B, 1, Lk)).astype(jnp.int32)
    n_q, n_k = Lq // bq, Lk // bk
    kernel = functools.partial(
        _flash_t5_kernel, scale=scale, n_k=n_k, bq=bq, bk=bk,
        num_buckets=num_buckets, max_distance=max_distance,
        bidirectional=bidirectional, n_heads=H,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
            # The whole bias table (tiny): Mosaic requires the last two
            # block dims divisible by (8, 128) OR equal to the full array
            # dims — only the latter fits [num_buckets, H].
            pl.BlockSpec((num_buckets, H), lambda b, h, i, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * Lq * Lk * D,
            bytes_accessed=(2 * B * H * Lq * D + 2 * B * H * Lk * D)
            * q.dtype.itemsize,
            transcendentals=B * H * Lq * Lk,
        ),
        interpret=interpret,
    )(q, k, v, mask3d, rel_bias.astype(jnp.float32))


def make_flash_attention_t5(mesh, interpret: Optional[bool] = None):
    """Mesh-aware T5 kernel: ``flash_attention_t5`` wrapped in ``shard_map``
    (batch over ``dp``, heads over ``tp`` — the bias table's head dim shards
    with the heads). Same rationale as :func:`make_flash_attention`:
    ``pallas_call`` has no GSPMD partitioning rule, so the bare kernel on a
    multi-chip mesh would replicate the full batch per chip. Returns a
    callable with the kernel's signature that yields **None** (dense
    fallback) for shapes the wrapper can't shard or the kernel declines.
    ``interpret`` binds the kernel's mode for every call through the
    returned callable (the runtime passes False; see
    :func:`resolve_interpret`).
    """
    if mesh.size == 1:
        return functools.partial(flash_attention_t5, interpret=interpret)

    from jax.sharding import PartitionSpec as P

    shape = dict(mesh.shape)
    dp = shape.get("dp", 1)
    tp = shape.get("tp", 1)

    def wrapper(q, k, v, mask, rel_bias, *, bidirectional=True,
                max_distance=128, scale=1.0, block_q=512, block_k=512,
                min_key_len=None):
        from agent_tpu.models.layers import (
            is_key_padding_mask,
            materialize_key_padding_mask,
        )

        B, H, Lq, D = q.shape
        Lk = k.shape[2]
        if min_key_len is None:
            min_key_len = FLASH_MIN_KEY_LEN
        ok = (
            is_key_padding_mask(mask, B, Lk)
            and Lk >= min_key_len
            and Lq % min(block_q, Lq) == 0
            and Lk % min(block_k, Lk) == 0
            and B % dp == 0
            and H % tp == 0
            and rel_bias.shape[1] == H
        )
        SELECTION_COUNTS["t5_flash" if ok else "t5_dense"] = (
            SELECTION_COUNTS.get("t5_flash" if ok else "t5_dense", 0) + 1
        )
        if not ok:
            return None

        inner = functools.partial(
            flash_attention_t5,
            bidirectional=bidirectional, max_distance=max_distance,
            scale=scale, block_q=block_q, block_k=block_k,
            min_key_len=0,  # validated above, on the GLOBAL shapes
            interpret=interpret,
        )
        sharded = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                P("dp", "tp", None, None),
                P("dp", "tp", None, None),
                P("dp", "tp", None, None),
                P("dp", None, None, None),
                P(None, "tp"),   # bias table: head dim shards with heads
            ),
            out_specs=P("dp", "tp", None, None),
            check_vma=False,  # pallas out_shape carries no vma annotation
        )
        return sharded(
            q, k, v, materialize_key_padding_mask(mask, B, Lk), rel_bias
        )

    return wrapper


# ---------------------------------------------------------------------------
# Trainable flash attention: custom_vjp with Pallas forward AND backward.
#
# The inference kernel above is forward-only — differentiating through it
# would fail (pallas_call has no AD rule), so the training path previously
# fell back to dense attention, materializing [B, H, L, L] scores in the
# backward and capping train MFU well below serving. The trainable variant
# uses the standard recompute scheme (FlashAttention-2 backward):
#
#   forward: one extra [B, H, Lq, 1] output — the row logsumexp
#            ``lse = m + log(l)`` — saved as the only softmax residual;
#   backward: ``delta = rowsum(dO ⊙ O)`` (cheap XLA reduction), then two
#            Pallas kernels that RECOMPUTE the normalized probabilities
#            ``p = exp(s − lse)`` per tile in VMEM:
#              • dQ kernel, grid (B, H, n_q, n_k): stream K/V tiles,
#                accumulate ``dq += (p ∘ (dO·Vᵀ − delta)) · K · scale``;
#              • dK/dV kernel, grid (B, H, n_k, n_q): stream Q tiles,
#                accumulate ``dv += pᵀ·dO`` and ``dk += dsᵀ·Q · scale``.
#            The [Lq, Lk] score/probability matrices never exist in HBM in
#            either direction.
# ---------------------------------------------------------------------------


def _flash_fwd_lse_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                          m_scr, l_scr, acc_scr, *, scale: float, n_k: int):
    """:func:`_flash_kernel` + one extra output: the row logsumexp residual."""
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(
        q_ref[0, 0], k_ref[0, 0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    keep = mask_ref[0, 0, :][None, :] > 0
    s = jnp.where(keep, s, NEG_INF)
    _tile_softmax_update(s, keep, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(kb == n_k - 1)
    def _emit():
        l_fin = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l_fin, 1e-30)).astype(
            o_ref.dtype
        )
        # Fully-masked rows: m == NEG_INF, l == 0 → lse ≈ NEG_INF − 69; the
        # backward's exp(s − lse) would overflow there but is zeroed by the
        # key mask before use.
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(jnp.maximum(l_fin, 1e-30))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, *, scale: float,
                         n_k: int):
    """dQ for one query tile, streaming K/V tiles on the inner grid axis."""
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    s = jax.lax.dot_general(                                  # [bq, bk]
        q_ref[0, 0], k_ref[0, 0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    keep = mask_ref[0, 0, :][None, :] > 0
    # Normalized probabilities, recomputed from the saved logsumexp. The
    # clamp bounds exp() for fully-masked rows (lse ≈ NEG_INF) where the
    # mask zeroes p anyway — exp(80) is finite in f32, so no inf*0.
    p = jnp.where(
        keep, jnp.exp(jnp.minimum(s - lse_ref[0, 0], 80.0)), 0.0
    )
    dp = jax.lax.dot_general(                                 # dO · Vᵀ
        do_ref[0, 0], v_ref[0, 0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_ref[0, 0])                           # [bq, bk] f32
    dq_scr[:] += scale * jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[0, 0],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )

    @pl.when(kb == n_k - 1)
    def _emit():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                          scale: float, n_q: int):
    """dK and dV for one key tile, streaming Q tiles on the inner grid axis."""
    qb = pl.program_id(3)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    s = jax.lax.dot_general(                                  # [bq, bk]
        q_ref[0, 0], k_ref[0, 0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    keep = mask_ref[0, 0, :][None, :] > 0
    p = jnp.where(
        keep, jnp.exp(jnp.minimum(s - lse_ref[0, 0], 80.0)), 0.0
    )
    # dV += pᵀ · dO — explicit .T then dot: the Mosaic-supported transposed
    # contraction (same pattern as jax.experimental.pallas.ops.tpu).
    dv_scr[:] += jax.lax.dot(
        p.T.astype(do_ref.dtype), do_ref[0, 0],
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(                                 # dO · Vᵀ
        do_ref[0, 0], v_ref[0, 0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_ref[0, 0])
    dk_scr[:] += scale * jax.lax.dot(
        ds.T.astype(q_ref.dtype), q_ref[0, 0],
        preferred_element_type=jnp.float32,
    )

    @pl.when(qb == n_q - 1)
    def _emit():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_fwd_res(q, k, v, mask3d, *, block_q, block_k, interpret, scale):
    """Forward pallas_call emitting (output, [B, H, Lq, 1] logsumexp)."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    bq = min(block_q, Lq)
    bk = min(block_k, Lk)
    n_q, n_k = Lq // bq, Lk // bk
    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_flash_fwd_lse_kernel, scale=scale, n_k=n_k),
        grid=(B, H, n_q, n_k),
        in_specs=[
            qspec, kspec, kspec,
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            sspec,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, Lq, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * Lq * Lk * D,
            bytes_accessed=(2 * B * H * Lq * D + 2 * B * H * Lk * D)
            * q.dtype.itemsize,
            transcendentals=B * H * Lq * Lk,
        ),
        interpret=interpret,
    )(q, k, v, mask3d)


def _flash_bwd_res(q, k, v, mask3d, o, lse, do, *, block_q, block_k,
                   interpret, scale):
    """Backward: (dq, dk, dv) via the two streaming kernels."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    bq = min(block_q, Lq)
    bk = min(block_k, Lk)
    n_q, n_k = Lq // bq, Lk // bk
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )                                                          # [B, H, Lq, 1]

    def qtile(b, h, i, j):
        return (b, h, i, 0)

    def ktile(b, h, i, j):
        return (b, h, j, 0)

    qspec = pl.BlockSpec((1, 1, bq, D), qtile, memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, 1, bk, D), ktile, memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec((1, 1, bq, 1), qtile, memory_space=pltpu.VMEM)
    mspec = pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM)
    bwd_cost = pl.CostEstimate(
        flops=10 * B * H * Lq * Lk * D,
        bytes_accessed=(4 * B * H * Lq * D + 4 * B * H * Lk * D)
        * q.dtype.itemsize,
        transcendentals=2 * B * H * Lq * Lk,
    )
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, n_k=n_k),
        grid=(B, H, n_q, n_k),
        in_specs=[qspec, kspec, kspec, mspec, qspec, sspec, sspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        cost_estimate=bwd_cost,
        interpret=interpret,
    )(q, k, v, mask3d, do, lse, delta)

    # K-tile outer, Q-tile inner: swap the roles of the last two grid axes.
    def qtile_t(b, h, j, i):
        return (b, h, i, 0)

    def ktile_t(b, h, j, i):
        return (b, h, j, 0)

    qspec_t = pl.BlockSpec((1, 1, bq, D), qtile_t, memory_space=pltpu.VMEM)
    kspec_t = pl.BlockSpec((1, 1, bk, D), ktile_t, memory_space=pltpu.VMEM)
    sspec_t = pl.BlockSpec((1, 1, bq, 1), qtile_t, memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, n_q=n_q),
        grid=(B, H, n_k, n_q),
        in_specs=[
            qspec_t, kspec_t, kspec_t,
            pl.BlockSpec((1, 1, bk), lambda b, h, j, i: (b, 0, j),
                         memory_space=pltpu.VMEM),
            qspec_t, sspec_t, sspec_t,
        ],
        out_specs=(kspec_t, kspec_t),
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        cost_estimate=bwd_cost,
        interpret=interpret,
    )(q, k, v, mask3d, do, lse, delta)
    return dq, dk, dv


def flash_attention_trainable(
    q: jax.Array,      # [B, H, Lq, D]
    k: jax.Array,      # [B, H, Lk, D]
    v: jax.Array,      # [B, H, Lk, D]
    mask: jax.Array,   # [B|1, 1, 1, Lk] key-padding mask (1 = attend)
    *,
    block_q: int = 512,
    block_k: int = 512,
    min_key_len: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Differentiable drop-in ``attn_fn``: Pallas forward AND backward.

    Same numerics and shape rules as :func:`flash_attention`, but the
    length gate defaults to ``FLASH_TRAIN_MIN_KEY_LEN`` (512, not 2048):
    in training the kernel also eliminates the backward's score-tensor HBM
    round trip, which flips the 512 verdict — see the gate note above.
    Unsupported shapes take the dense XLA path, which autodiff handles
    natively. The
    Pallas path registers a ``custom_vjp`` whose backward runs the two
    streaming kernels above — training at long context no longer
    materializes [Lq, Lk] score matrices in either pass.

    Gradient caveat: rows whose mask keeps NO keys get zero (dq, dk, dv)
    contributions here, while the dense path backpropagates through its
    uniform-softmax-then-zero guard; with any real key present the two
    paths agree to dtype tolerance (``tests/test_flash_attention.py``).
    """
    from agent_tpu.models.layers import is_key_padding_mask

    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    bq = min(block_q, Lq)
    bk = min(block_k, Lk)
    if min_key_len is None:
        min_key_len = FLASH_TRAIN_MIN_KEY_LEN  # training gate — see note
    supported = (
        is_key_padding_mask(mask, B, Lk)
        and Lk >= min_key_len
        and Lq % bq == 0
        and Lk % bk == 0
    )
    key = "flash_train" if supported else "dense_train"
    SELECTION_COUNTS[key] = SELECTION_COUNTS.get(key, 0) + 1
    if not supported:
        return dot_product_attention(q, k, v, mask)
    interpret = resolve_interpret(interpret)
    scale = 1.0 / float(np.sqrt(D))
    mask3d = jnp.broadcast_to(mask[:, 0, :, :], (B, 1, Lk)).astype(jnp.int32)
    return _trainable_core(block_q, block_k, interpret, scale)(
        q, k, v, mask3d
    )


@functools.lru_cache(maxsize=None)
def _trainable_core(block_q: int, block_k: int, interpret: bool,
                    scale: float):
    """The custom_vjp attention for one static (tiles, interpret, scale).

    The mask rides as a PRIMAL argument (``None`` cotangent), never in a
    closure: a closed-over traced mask would leak its tracer into the
    backward trace — ``jax.checkpoint`` replays the forward under a
    different trace than the one that runs ``bwd``. The lru_cache keeps one
    function identity per static config, so jit caches see a stable callee.
    """

    @jax.custom_vjp
    def attn(q, k, v, mask3d):
        o, _ = _flash_fwd_res(q, k, v, mask3d, block_q=block_q,
                              block_k=block_k, interpret=interpret,
                              scale=scale)
        return o

    def fwd(q, k, v, mask3d):
        o, lse = _flash_fwd_res(q, k, v, mask3d, block_q=block_q,
                                block_k=block_k, interpret=interpret,
                                scale=scale)
        return o, (q, k, v, mask3d, o, lse)

    def bwd(res, do):
        q, k, v, mask3d, o, lse = res
        dq, dk, dv = _flash_bwd_res(q, k, v, mask3d, o, lse, do,
                                    block_q=block_q, block_k=block_k,
                                    interpret=interpret, scale=scale)
        return dq, dk, dv, None

    attn.defvjp(fwd, bwd)
    return attn


def _make_mesh_wrapper(mesh, inner, dense_counter_key: Optional[str]):
    """ONE shard_map wrapper for both flash kernels (batch over ``dp``,
    heads over ``tp``) — inference and trainable share the sharding layout,
    the divisibility gate, and the mask materialization, so a future spec
    change cannot silently diverge the two paths.

    ``dense_counter_key`` ticks ``SELECTION_COUNTS`` when the WRAPPER (not
    the per-shard kernel) decides on the dense fallback: inside shard_map
    the per-shard call ticks its own counter, but a wrapper-level decline
    would otherwise be invisible to the trace-time selection proof.
    """
    from jax.sharding import PartitionSpec as P

    shape = dict(mesh.shape)
    dp = shape.get("dp", 1)
    tp = shape.get("tp", 1)

    sharded = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P("dp", "tp", None, None),
            P("dp", "tp", None, None),
            P("dp", "tp", None, None),
            P("dp", None, None, None),
        ),
        out_specs=P("dp", "tp", None, None),
        # pallas_call's out_shape carries no varying-mesh-axes annotation, so
        # the vma checker can't see through it; the in/out specs above are the
        # full contract here.
        check_vma=False,
    )

    def mesh_attention(q, k, v, mask):
        from agent_tpu.models.layers import (
            is_key_padding_mask,
            materialize_key_padding_mask,
        )

        B, H, _, _ = q.shape
        Lk = k.shape[2]
        ok = is_key_padding_mask(mask, B, Lk) and _wrapper_shardable(
            B, H, dp, tp
        )
        if not ok:
            if dense_counter_key is not None:
                SELECTION_COUNTS[dense_counter_key] = (
                    SELECTION_COUNTS.get(dense_counter_key, 0) + 1
                )
            return dot_product_attention(q, k, v, mask)
        return sharded(q, k, v, materialize_key_padding_mask(mask, B, Lk))

    return mesh_attention


def make_flash_attention_trainable(mesh, interpret: Optional[bool] = None):
    """Mesh-aware trainable flash attention — :func:`make_flash_attention`
    for the training path. Batch shards over ``dp``, heads over ``tp``;
    ``shard_map`` differentiates through the per-shard ``custom_vjp``, so
    the backward kernels also run sharded. Unsupported shapes fall back to
    the dense path (GSPMD + autodiff handle it)."""
    kernel = functools.partial(flash_attention_trainable, interpret=interpret)
    if mesh.size == 1:
        return kernel
    return _make_mesh_wrapper(mesh, kernel, "dense_train")


def make_flash_attention(mesh, interpret: Optional[bool] = None):
    """Mesh-aware flash attention: the kernel wrapped in ``shard_map``.

    ``pallas_call`` has no GSPMD partitioning rule, so jitting the bare kernel
    over a dp/tp mesh silently all-gathers the batch and runs the full-batch
    kernel replicated on every chip. Wrapping in ``shard_map`` (batch over
    ``dp``, heads over ``tp``) keeps each chip on its own shard. Single-device
    meshes skip the wrapper. Shapes the wrapper can't shard (batch or heads
    indivisible) fall back to the dense XLA path, which GSPMD partitions fine.
    ``interpret`` binds the kernel's mode (the runtime passes False; see
    :func:`resolve_interpret`).
    """
    kernel = functools.partial(flash_attention, interpret=interpret)
    if mesh.size == 1:
        return kernel
    # No counter key: the wrapper-level dense fallback predates the proof
    # discipline and tests pin the "dense" counter to per-kernel decisions.
    return _make_mesh_wrapper(mesh, kernel, None)
