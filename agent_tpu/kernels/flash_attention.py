"""Fused attention as Pallas TPU kernels: streaming (flash) and whole-row.

Two schedules of one algorithm, chosen by shape and mask alone
(:func:`selects_flash`, :func:`selects_whole_row`): from
``FLASH_MIN_KEY_LEN`` keys on, the STREAMING kernel described next; below
it, for whole-sequence attention under a key-padding mask or under segment
ids (several short rows packed end to end in one program row, attention
block-diagonal), the WHOLE-ROW kernel (:func:`whole_row_attention`), whose
score row fits VMEM and whose operands are the projections' own lane-dense
[B, L, H*D]; dense XLA everywhere else.

Streaming: one grid program computes one [block_q, d_head] query tile for
one (batch, head). The innermost grid axis walks K/V tiles sequentially (TPU
grids are sequential, innermost fastest), carrying the streaming-softmax
state — running row max ``m``, denominator ``l``, numerator ``acc`` — in
VMEM scratch that persists across that axis. The [Lq, Lk] score matrix therefore never exists in
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

from agent_tpu.obs.trace import part

_LANES = 128  # VPU lane width; scratch last dims pad to this anyway


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret=None`` → interpreter mode off-TPU. The tests' convenience
    only (the identical kernel runs on the CPU mesh): ``TpuRuntime`` decides
    from its own devices and always passes ``interpret=False`` explicitly."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret

# At and above this key length the STREAMING kernel below is selected: the
# [Lq, Lk] score matrix never reaches HBM, so batch and length scale past
# where the dense path runs out of memory. Its speed against the dense path
# at 2048 and beyond: not measured on the present tree (no ledger cell runs
# such lengths). Below it the whole score row fits VMEM and the whole-row
# kernel (further down) takes over on the projections' own layout; this
# kernel's [B, H, L, D] operands (d_head 64 in the lanes, a grid step a
# (batch, head)) are what an old chip round found slower than dense inside
# BERT-base at 512 keys, before the ledger: also not measured on the present
# tree.
FLASH_MIN_KEY_LEN = 2048

# TRAINING gates lower. The backward-dense path re-materializes and re-reads
# the [B, H, L, L] score tensor, which at BERT-base train shapes (B 256,
# L 512) is ~1.6 GB of HBM traffic per layer per direction; the flash
# backward stores NO score tensors, which is what lets a training step run
# without remat at batch 128. Its speed against dense + remat: not measured
# on the present tree (PERF.md §7, rows 6-8: `train_classifier` at batch 128
# needs a `train_tokens_per_s` cell).
FLASH_TRAIN_MIN_KEY_LEN = 512

# Trace-time selection tally: ``flash_attention`` decides kernel-vs-dense while
# the surrounding jit TRACES (the gate is static shape metadata), so these
# counters tick once per compiled program, not per call. Tests and
# ``chip_smoke.py`` diff them around a compile to *prove* which path a
# compiled executable contains: an assertion, not a belief.
SELECTION_COUNTS = {"flash": 0, "dense": 0}


def _note_selection(path: str) -> None:
    """One attention block traced on ``path``: the module's tally, and
    ``attention_blocks_traced_total{path}`` in the registry of the task whose
    call traced the program (else the process's)."""
    from agent_tpu.obs.trace import record_attention_block

    SELECTION_COUNTS[path] = SELECTION_COUNTS.get(path, 0) + 1
    record_attention_block(path)


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


@part("mixer")
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

    Default 512×512 tiles (a score tile is 1 MB of VMEM). Speed against the
    dense XLA path: not measured on the present tree (see the
    ``FLASH_MIN_KEY_LEN`` note).
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
    _note_selection("flash" if supported else "dense")
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


# ---------------------------------------------------------------------------
# Whole-row attention: below the streaming gate the score row fits VMEM, and
# the operands stay in the projections' own lane-dense layout.
#
# ``flash_attention`` takes [B, H, L, D] and walks one (batch, head) a grid
# step. At d_head 64 that puts 64 in the lanes of every operand (padded to
# 128, relaid on the way in and out) and makes 3,072 steps a layer at
# BERT-base 256 x 512. Here Q, K and V are [B, L, H*D] — what a
# [B*L, d] x [d, H*D] matmul writes — and a grid step takes whole batch rows
# with all their heads: one pass, no running maximum, no correction multiply,
# no scratch carried across steps. Heads are cut from 128-lane groups inside
# the kernel: with d_head 64 two heads share a group, and zeroing the partner
# head's lanes of Q and contracting over all 128 gives the head's own sums on
# the MXU passes a 64-deep contraction takes anyway, with no unaligned lane
# slice; PV likewise yields the pair's [L, 128] and each head keeps its half.
# ---------------------------------------------------------------------------

# Key lengths at which the whole-row kernel is selected (inclusive); under
# them the dense XLA path stays, from FLASH_MIN_KEY_LEN on the streaming
# kernel. Set from chip runs of the WHOLE BERT-base encoder at 131,072 tokens
# a program (512 x 64 at 64), dense against whole-row: 1.56 x at 64, 1.07 x at
# 128, 1.19 x at 256, 1.26 x at 512, 1.31 x at 1024 (my chip run, PR 25; the
# ledger's cells are `bert-base.drain-long` = 512 and `bert-base.drain-short`
# = 64, metric `drain_rows_per_s`). 2048 and up: not measured.
WHOLE_ROW_MIN_KEY_LEN = 64
WHOLE_ROW_MAX_KEY_LEN = 1024

# A step's blocks (q, k, v, out; double-buffered by the pipeline) and its
# score temporaries must fit the scoped VMEM asked for below; v5e has 128 MiB.
_WHOLE_ROW_VMEM_LIMIT = 64 * 1024 * 1024
_WHOLE_ROW_BLOCK_BUDGET = 16 * 1024 * 1024
# Rows x length a step: enough work to bury a step's fixed cost.
_WHOLE_ROW_STEP_TOKENS = 512


def selects_whole_row(lq: int, lk: int, n_heads: int, d_head: int, *,
                      key_padding: bool, dtype, segments: bool = False) -> bool:
    """Shape-and-mask predicate: does whole-sequence attention of this kind
    take the whole-row kernel? The one place the choice is made; callers
    (``layers.attention``, ``bert.forward``) hand over the lane-dense layout
    exactly when it says yes, and every other call keeps [B, H, L, D].

    Two masks qualify: a key-padding mask (``key_padding``) and the SEGMENT
    form (``segments``: a [B, L] array of segment ids, 0 = pad, under which
    a query attends the keys of its own segment and no other: several short
    rows packed end to end in one program row). The shapes it takes are the
    same for both."""
    return bool(
        (key_padding or segments)
        and lq == lk
        and WHOLE_ROW_MIN_KEY_LEN <= lk <= WHOLE_ROW_MAX_KEY_LEN
        and lk < FLASH_MIN_KEY_LEN
        and (lk == 64 or lk % _LANES == 0)
        and d_head in (64, _LANES)
        and (n_heads * d_head) % _LANES == 0
        and jnp.dtype(dtype) == jnp.bfloat16
    )


def _whole_row_tiles(batch: int, length: int, n_groups: int):
    """(rows, lane groups) a grid step covers, from the shapes alone: all the
    groups of a row unless the blocks outgrow their budget, and as many rows
    as bring a step to ``_WHOLE_ROW_STEP_TOKENS`` tokens."""
    groups = n_groups
    while groups > 1 and (
        8 * length * groups * _LANES * 2 > _WHOLE_ROW_BLOCK_BUDGET
        or n_groups % groups
    ):
        groups -= 1
    rows = max(1, min(batch, _WHOLE_ROW_STEP_TOKENS // length))
    while batch % rows:
        rows -= 1
    return rows, groups


def _whole_row_kernel(q_ref, k_ref, v_ref, mask_ref, *rest, scale: float,
                      d_head: int, rows: int, groups: int, segments: bool):
    # Key-padding form: ``mask_ref`` [rows, 1, L] (1 = attend), rest =
    # (o_ref,). Segment form: ``mask_ref`` holds the KEYS' segment ids
    # [rows, 1, L] and rest = (the QUERIES' ids [rows, L, 1], o_ref): the
    # same ids twice, each laid out the way its broadcast wants it.
    o_ref = rest[-1]
    heads_per_group = _LANES // d_head
    # A power-of-two scale (d_head 64: 1/8) multiplies into Q exactly in
    # bf16, on [L, 128] instead of [L, L]; any other scales the f32 scores.
    fold_scale = float(np.log2(scale)).is_integer()
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def one_row(r):
        # A query with no key at all: exp(0) = 1 everywhere below, so it
        # would come out as V's mean; it is 0, as the streaming kernel has it.
        if segments:
            seg_k, seg_q = mask_ref[r], rest[0][r]            # [1, L], [L, 1]
            keep = (seg_q == seg_k) & (seg_k > 0)             # [L, L]
            # A real query is its own key; a pad slot has none.
            any_key = (seg_q > 0).astype(jnp.float32)         # [L, 1]
        else:
            keep = mask_ref[r] > 0                            # [1, L]
            any_key = jnp.max(keep.astype(jnp.float32), axis=-1,
                              keepdims=True)
        for g in range(groups):
            lanes = slice(g * _LANES, (g + 1) * _LANES)
            q_g = q_ref[r, :, lanes]                          # [L, 128]
            k_g = k_ref[r, :, lanes]
            v_g = v_ref[r, :, lanes]
            out = None
            for h in range(heads_per_group):
                q_h = q_g
                if heads_per_group > 1 or fold_scale:
                    mine = (lane >= h * d_head) & (lane < (h + 1) * d_head)
                    q_scale = jnp.where(
                        mine, scale if fold_scale else 1.0, 0.0)
                    q_h = (q_g.astype(jnp.float32) * q_scale).astype(
                        q_g.dtype)
                s = jax.lax.dot_general(                      # [L, L] f32
                    q_h, k_g, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if not fold_scale:
                    s = s * scale
                s = jnp.where(keep, s, NEG_INF)
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m)        # exactly 0 at a masked key
                l = jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(                     # [L, 128] f32
                    p.astype(v_g.dtype), v_g, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                pv = pv * (any_key / l)
                out = pv if out is None else jnp.where(mine, pv, out)
            o_ref[r, :, lanes] = out.astype(o_ref.dtype)

    # The rows of a step run as a loop, not unrolled: the kernel's text, and
    # the seconds to trace and lower it, stay those of one row.
    if rows == 1:
        one_row(0)
    else:
        jax.lax.fori_loop(0, rows, lambda r, _: one_row(r), None)


@functools.partial(
    jax.jit, static_argnames=("n_heads", "rows", "groups", "interpret"))
def _whole_row_call(q, k, v, mask3d, seg_q=None, *, n_heads: int, rows: int,
                    groups: int, interpret: bool):
    """The ``pallas_call``, under a jit of its own: an encoder calls it once
    a block and an agent builds one program a tenant, and tracing and
    lowering the kernel's body is seconds of Python each time. A jitted
    callee is traced once a shape in the process and lowered once a
    program.

    With ``k`` and ``v`` None, ``q`` is the one [B, L, 3*H*D] array a fused
    Q, K, V matmul writes, columns ``[Q | K | V]``: it goes in three times,
    and the three operands are column blocks of it (the same block shape,
    the index maps a third of the lanes apart). No copy takes it apart."""
    fused = k is None
    B, L, lanes = q.shape
    HD = lanes // 3 if fused else lanes
    d_head = HD // n_heads
    shape = (rows, L, groups * _LANES)
    steps = HD // (groups * _LANES)      # lane-group steps an operand spans

    def operand(nth):
        return pl.BlockSpec(shape, lambda b, g: (b, 0, nth * steps + g),
                            memory_space=pltpu.VMEM)

    kernel = functools.partial(
        _whole_row_kernel, scale=1.0 / float(np.sqrt(d_head)), d_head=d_head,
        rows=rows, groups=groups, segments=seg_q is not None,
    )
    masks, mask_specs = [mask3d], [
        pl.BlockSpec((rows, 1, L), lambda b, g: (b, 0, 0),
                     memory_space=pltpu.VMEM)]
    if seg_q is not None:
        masks.append(seg_q)
        mask_specs.append(pl.BlockSpec((rows, L, 1), lambda b, g: (b, 0, 0),
                                       memory_space=pltpu.VMEM))
    return pl.pallas_call(
        kernel,
        grid=(B // rows, steps),
        in_specs=[*(operand(nth if fused else 0) for nth in range(3)),
                  *mask_specs],
        out_specs=operand(0),
        out_shape=jax.ShapeDtypeStruct((B, L, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_WHOLE_ROW_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * n_heads * L * L * d_head,
            bytes_accessed=4 * B * L * HD * q.dtype.itemsize,
            transcendentals=B * n_heads * L * L,
        ),
        interpret=interpret,
    )(*((q, q, q) if fused else (q, k, v)), *masks)


@part("mixer")
def whole_row_attention(
    q: jax.Array,      # [B, L, H*D], or [B, L, 3*H*D] = [Q | K | V]
    k: Optional[jax.Array],      # [B, L, H*D]; None: ``q`` holds all three
    v: Optional[jax.Array],      # [B, L, H*D]; None with ``k``
    mask: Optional[jax.Array],   # [B|1, 1, 1, L] key-padding mask (1 = attend)
    *,
    n_heads: int,
    segment_ids: Optional[jax.Array] = None,   # [B, L] int32, 0 = pad
    rows_per_step: Optional[int] = None,
    groups_per_step: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused attention on lane-dense operands → [B, L, H*D].

    For the calls :func:`selects_whole_row` accepts, and only those: the
    caller asks it first. f32 scores and statistics, bf16 into the PV matmul,
    every key of every real row attended, 0 (not NaN) for a row with no key.

    Two operand forms, one kernel: three [B, L, H*D] arrays, or (``k`` and
    ``v`` None) the ONE [B, L, 3*H*D] array a fused Q, K, V projection
    writes, columns ``[Q | K | V]``, head-major inside each, which the call
    reads as three column blocks (:func:`_whole_row_call`).

    With ``segment_ids`` (the packed layout of ``ops/_model_common.py:
    pack_padded_chunk``: several short rows end to end in one program row)
    ``mask`` is not read and attention is block-diagonal: a query attends
    the keys that carry its own id and no other, a pad slot (id 0) attends
    nothing, is attended by nothing and comes out 0. The ids go in twice,
    as [B, 1, L] for the keys and [B, L, 1] for the queries, so the [L, L]
    compare is two broadcasts on the VPU and no transpose.

    ``rows_per_step`` / ``groups_per_step`` override the tile geometry the
    shapes give (:func:`_whole_row_tiles`) — for sweeps on the chip."""
    B, L, lanes = q.shape
    HD = lanes // 3 if k is None else lanes
    rows, groups = _whole_row_tiles(B, L, HD // _LANES)
    _note_selection("whole_row")
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)
        mask3d, seg_q = seg[:, None, :], seg[:, :, None]
    else:
        mask3d = jnp.broadcast_to(
            mask[:, 0, :, :], (B, 1, L)).astype(jnp.int32)
        seg_q = None
    return _whole_row_call(
        q, k, v, mask3d, seg_q, n_heads=n_heads, rows=rows_per_step or rows,
        groups=groups_per_step or groups,
        interpret=resolve_interpret(interpret),
    )


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


@part("mixer")
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


@part("mixer")
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
    _note_selection("t5_flash" if supported else "t5_dense")
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
        _note_selection("t5_flash" if ok else "t5_dense")
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


@part("mixer")
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
    _note_selection("flash_train" if supported else "dense_train")
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
                _note_selection(dense_counter_key)
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


class WholeRowAttention:
    """The lane-dense entry an ``attn_fn`` declares as ``attn_fn.whole_row``:
    :meth:`selects` is :func:`selects_whole_row` on what one chip of the mesh
    sees, and the call is :func:`whole_row_attention` in either operand form
    (under ``shard_map`` on a mesh: batch over ``dp``, the head-major lanes
    over ``tp``)."""

    def __init__(self, mesh, interpret: Optional[bool] = None):
        from jax.sharding import PartitionSpec as P

        shape = dict(mesh.shape)
        self.dp = shape.get("dp", 1)
        self.tp = shape.get("tp", 1)
        self._kernel = functools.partial(whole_row_attention,
                                         interpret=interpret)
        self._shard = None
        if mesh.size > 1:
            self._shard = functools.partial(
                jax.shard_map, mesh=mesh, out_specs=P("dp", None, "tp"),
                check_vma=False,  # as _make_mesh_wrapper: no vma on pallas
            )

    def selects(self, batch: int, lq: int, lk: int, n_heads: int,
                d_head: int, mask, dtype, segments: bool = False) -> bool:
        from agent_tpu.models.layers import is_key_padding_mask

        return _wrapper_shardable(batch, n_heads, self.dp, self.tp) and (
            selects_whole_row(
                lq, lk, n_heads // self.tp, d_head,
                key_padding=(not segments
                             and is_key_padding_mask(mask, batch, lk)),
                dtype=dtype, segments=segments,
            )
        )

    def __call__(self, q, k, v, mask, *, n_heads: int, segment_ids=None):
        if self._shard is None:
            return self._kernel(q, k, v, mask, n_heads=n_heads,
                                segment_ids=segment_ids)
        from jax.sharding import PartitionSpec as P

        from agent_tpu.models.layers import materialize_key_padding_mask

        B, L, _ = q.shape
        if k is None and self.tp > 1:
            # [Q | K | V] columns do not split by heads: three arrays, whose
            # head-major lanes do. (No fused leaf is built for such a mesh.)
            q, k, v = jnp.split(q, 3, axis=-1)
        operands = (q,) if k is None else (q, k, v)
        # The segment ids ride in the mask's place and its spec: [B, 1, 1, L].
        rider = (segment_ids[:, None, None, :] if segment_ids is not None
                 else materialize_key_padding_mask(mask, B, L))

        def inner(*args):
            *qkv, rider = args
            qkv += [None] * (3 - len(qkv))
            if segment_ids is not None:
                return self._kernel(*qkv, None, n_heads=n_heads // self.tp,
                                    segment_ids=rider[:, 0, 0, :])
            return self._kernel(*qkv, rider, n_heads=n_heads // self.tp)

        return self._shard(
            inner, in_specs=(P("dp", None, "tp"),) * len(operands)
            + (P("dp", None, None, None),))(*operands, rider)


def make_flash_attention(mesh, interpret: Optional[bool] = None):
    """Mesh-aware fused attention: the kernels wrapped in ``shard_map``.

    ``pallas_call`` has no GSPMD partitioning rule, so jitting the bare kernel
    over a dp/tp mesh silently all-gathers the batch and runs the full-batch
    kernel replicated on every chip. Wrapping in ``shard_map`` (batch over
    ``dp``, heads over ``tp``) keeps each chip on its own shard. Single-device
    meshes skip the wrapper. Shapes the wrapper can't shard (batch or heads
    indivisible) fall back to the dense XLA path, which GSPMD partitions fine.
    ``interpret`` binds the kernel's mode (the runtime passes False; see
    :func:`resolve_interpret`).

    The returned ``attn_fn`` takes [B, H, L, D] like every other, and
    declares ``attn_fn.whole_row`` (:class:`WholeRowAttention`): callers that
    can hand over [B, L, H*D] ask its ``selects`` first.
    """
    kernel = functools.partial(flash_attention, interpret=interpret)
    if mesh.size > 1:
        # No counter key: the wrapper-level dense fallback predates the proof
        # discipline and tests pin the "dense" counter to per-kernel decisions.
        kernel = _make_mesh_wrapper(mesh, kernel, None)
    kernel.whole_row = WholeRowAttention(mesh, interpret)
    return kernel
