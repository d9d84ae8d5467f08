"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

The reference handled long inputs by truncation only (1,024-token cap on
summarize, reference ``ops/map_summarize.py:49``; 2,048-token profile limit,
reference ``app.py:108``). The TPU-native upgrade (SURVEY.md §5.7): shard the
*sequence* axis over ``sp`` so context length scales with chips instead of
hitting one chip's HBM wall.

Mechanics (blockwise attention with a ``lax.ppermute`` ring, scaling-book
recipe): every device holds one block of Q rows and one block of K/V rows.
Each of the ``sp`` steps computes attention of the local Q block against the
currently-held K/V block while folding results into a streaming (flash-style)
softmax — running row max ``m``, running denominator ``l``, running numerator
``acc`` — then rotates the K/V block (and its key-padding mask slice) one hop
around the ring. After ``sp`` hops every Q block has seen every K/V block and
the blocks are home again. Communication is neighbor-to-neighbor only, which
is exactly what TPU ICI rings are built for; compute on block *i* overlaps
XLA-scheduled transfer of block *i+1*.

Scope: key-padding masks only (``[B, 1, 1, Lk]`` — encoder self-attention and
cross-attention). Causal decode doesn't meet this path: decode queries one
position against a full KV cache (``models/seq2seq._decode_step``), where
sequence sharding buys nothing.

Drop-in contract: :func:`make_ring_attention` returns a function with the
``attn_fn`` signature of ``agent_tpu.models.layers.attention``; shapes that
don't divide the mesh (or non-key-only masks) silently take the dense path,
so callers never need a compatibility check.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from agent_tpu.models.layers import NEG_INF, dot_product_attention


def _ring_local(q, k, v, mask, sp: int, use_flash_fold: bool = False,
                interpret: bool = False):
    """Per-device body: streaming-softmax attention over ``sp`` ring hops.

    q: [b, h, lq, d] (local Q block, f32-scaled below)
    k, v: [b, h, lk, d] (current K/V block, rotates)
    mask: [b, 1, 1, lk] key-padding block (1 = attend, rotates with K/V)

    With ``use_flash_fold`` each hop's fold runs as the fused Pallas kernel
    (``agent_tpu.kernels.flash_attention.flash_fold``) instead of einsums —
    the ring schedules communication, the kernel does the math, closing the
    sp>1-bypasses-the-kernel gap.
    """
    out_dtype = q.dtype
    scale = 1.0 / np.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32) * scale

    if use_flash_fold:
        from agent_tpu.kernels.flash_attention import (
            flash_fold,
            flash_fold_supported,
        )

        use_flash_fold = flash_fold_supported(q.shape, k.shape[2])

    b, h, lq, _ = q.shape
    # Mark the zero-init carry device-varying: shard_map requires the scan
    # carry's manual-axes type to match its (varying) outputs.
    varying = partial(lax.pcast, axis_name=("dp", "tp", "sp"), to="varying")
    m0 = varying(jnp.full((b, h, lq, 1), NEG_INF, dtype=jnp.float32))
    l0 = varying(jnp.zeros((b, h, lq, 1), dtype=jnp.float32))
    acc0 = varying(jnp.zeros(q.shape, dtype=jnp.float32))
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    def fold(k_blk, v_blk, m_blk, m, l, acc):
        """Fold one K/V block into the streaming softmax state.

        Same m/l/acc update as the Pallas flash kernel's per-tile fold
        (``agent_tpu.kernels.flash_attention._flash_fold_kernel``) — a
        numerics change there must land here too; the einsum form is the
        fallback when the kernel path is off or the shapes don't tile.
        """
        if use_flash_fold:
            return flash_fold(
                q, k_blk, v_blk, m_blk, m, l, acc,
                interpret=interpret, vma=frozenset({"dp", "tp", "sp"}),
            )
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32)
        )
        scores = jnp.where(m_blk > 0, scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        # Masked entries must contribute exactly 0 even when the whole block
        # is masked (scores == m_new == NEG_INF would make exp() == 1).
        p = jnp.exp(scores - m_new) * (m_blk > 0)
        correction = jnp.exp(m - m_new)
        l = l * correction + p.sum(axis=-1, keepdims=True)
        acc = acc * correction + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32)
        )
        return m_new, l, acc

    # Local block first, then rotate-and-fold sp-1 times: a uniform
    # fold-then-rotate scan would pay one extra (discarded) K/V rotation.
    m, l, acc = fold(k, v, mask, m0, l0, acc0)

    def hop(carry, _):
        k_blk, v_blk, m_blk, m, l, acc = carry
        k_blk = lax.ppermute(k_blk, "sp", perm)
        v_blk = lax.ppermute(v_blk, "sp", perm)
        m_blk = lax.ppermute(m_blk, "sp", perm)
        m, l, acc = fold(k_blk, v_blk, m_blk, m, l, acc)
        return (k_blk, v_blk, m_blk, m, l, acc), None

    (_, _, _, _, l, acc), _ = lax.scan(
        hop, (k, v, mask, m, l, acc), None, length=sp - 1
    )
    # Fully-padded rows have l == 0 (all-pad batch-bucket rows): emit 0, not NaN.
    return (acc / jnp.maximum(l, 1e-30)).astype(out_dtype)


def make_ring_attention(mesh: Mesh, use_flash_fold: bool = False,
                        interpret: Optional[bool] = None):
    """``attn_fn`` running ring attention over ``mesh``'s ``sp`` axis.

    With ``sp == 1`` (or shapes/mask the ring can't take) this is exactly
    :func:`~agent_tpu.models.layers.dot_product_attention` — same program,
    different mesh, preserving the framework's one-codepath rule
    (SURVEY.md §7: fallback is a backend/mesh switch, not a second model).

    ``use_flash_fold`` runs each hop's local fold as the fused Pallas
    kernel, in the mode ``interpret`` names. ``TpuRuntime.attention_fn``
    decides both from its own devices (fold on, compiled, on a TPU);
    ``interpret=None`` is the kernels' off-TPU auto-select for tests.
    """
    shape = dict(mesh.shape)
    sp = shape.get("sp", 1)
    if sp <= 1:
        return dot_product_attention
    dp = shape.get("dp", 1)
    tp = shape.get("tp", 1)
    from agent_tpu.kernels.flash_attention import resolve_interpret

    interpret = use_flash_fold and resolve_interpret(interpret)

    sharded = jax.shard_map(
        partial(_ring_local, sp=sp, use_flash_fold=use_flash_fold,
                interpret=interpret),
        mesh=mesh,
        in_specs=(
            P("dp", "tp", "sp", None),   # q: heads over tp, Lq over sp
            P("dp", "tp", "sp", None),   # k: Lk over sp (ring-rotated)
            P("dp", "tp", "sp", None),   # v
            P("dp", None, None, "sp"),   # key-padding mask: Lk over sp
        ),
        out_specs=P("dp", "tp", "sp", None),
        # The pallas INTERPRET-mode lowering emits dynamic_slices whose
        # operands confuse the vma checker inside shard_map (jax suggests
        # exactly this workaround). Scoped to interpret mode only: compiled
        # TPU runs keep full varying-mesh-axes verification (the fold's
        # outputs carry their vma annotation).
        check_vma=not (use_flash_fold and interpret),
    )

    def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       mask: jax.Array) -> jax.Array:
        from agent_tpu.models.layers import (
            is_key_padding_mask,
            materialize_key_padding_mask,
        )

        B, H, Lq, _ = q.shape
        Lk = k.shape[2]
        ring_ok = (
            is_key_padding_mask(mask, B, Lk)
            and B % dp == 0
            and H % tp == 0
            and Lq % sp == 0
            and Lk % sp == 0
        )
        if not ring_ok:
            return dot_product_attention(q, k, v, mask)
        return sharded(q, k, v, materialize_key_padding_mask(mask, B, Lk))

    return ring_attention
