"""Pipeline parallelism over a ``pp`` mesh axis — GPipe-style microbatching
as one SPMD program.

SURVEY.md §2.8 deferred pipeline parallelism ("the mesh API leaves an axis
open"); this module closes it the TPU way: no per-stage processes, no
send/recv runtime (the reference's world would use NCCL P2P here) — the whole
pipeline is a single jitted ``shard_map`` over the mesh, with
``jax.lax.ppermute`` shifting activations one stage forward per tick over ICI
and every stage running the same traced program (SPMD). XLA sees one static
loop (``lax.scan`` over ticks) and overlaps the permute with stage compute.

Layout:

- The per-layer block pytrees are **stacked**: each leaf gains a leading
  ``n_layers`` dim, reshaped to ``[pp, layers_per_stage, ...]`` and sharded
  ``P("pp")`` — so each device holds only its own stage's weights. That is
  the point of pp: a model too deep for one chip's HBM serves/trains with
  layers split across chips.
- Activations ride the schedule: microbatch ``m`` enters stage 0 at tick
  ``m``, reaches stage ``s`` at tick ``m + s``. Stage ``s`` at tick ``t``
  therefore processes microbatch ``t - s`` (bubble ticks compute on zeros and
  are discarded). After ``n_micro + pp - 1`` ticks the last stage has every
  output; a ``psum`` over ``pp`` (zeros elsewhere) hands the result to all
  stages.
- Composes with data parallelism: with a ``(dp, pp)`` mesh the microbatch
  batch dim shards over ``dp`` and each dp replica runs its own pipeline.

Bubble fraction is ``(pp - 1) / (n_micro + pp - 1)``; callers raise
``n_micro`` to amortize (default ``pp`` microbatches = the minimal schedule).
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from agent_tpu.models import layers
from agent_tpu.models.layers import dot_product_attention


def stack_blocks(blocks: List[Any]) -> Any:
    """List of per-layer block pytrees → one pytree whose leaves carry a
    leading ``n_layers`` dim (scan-ready; reshaped per-stage by the caller)."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *blocks)


def stage_blocks(stacked: Any, pp: int) -> Any:
    """[n_layers, ...] leaves → [pp, n_layers/pp, ...]; dim 0 shards over pp."""

    def split(leaf):
        n = leaf.shape[0]
        if n % pp != 0:
            raise ValueError(f"n_layers {n} not divisible by pp={pp}")
        return leaf.reshape((pp, n // pp) + leaf.shape[1:])

    return jax.tree_util.tree_map(split, stacked)


def stage_specs(staged: Any) -> Any:
    """P("pp") on every leaf's leading (stage) dim, rest replicated."""
    return jax.tree_util.tree_map(lambda _: P("pp"), staged)


def pipeline_blocks(
    mesh,
    staged: Any,          # stage_blocks() output: leaves [pp, per_stage, ...]
    x: jax.Array,         # [B, L, D] activations (B divisible by n_micro·dp)
    mask: jax.Array,      # [B, L] int padding mask (1 = real)
    dtype: Any,
    attn_fn=dot_product_attention,
    n_micro: Optional[int] = None,
) -> jax.Array:
    """Apply the stacked encoder blocks through the pp pipeline → [B, L, D].

    Numerics match running the blocks sequentially (same ops, same order);
    tests assert equality against the dense forward.
    """
    pp = mesh.shape["pp"]
    dp = mesh.shape.get("dp", 1)
    n_micro = n_micro or pp
    B, L, D = x.shape
    if B % (n_micro * dp) != 0:
        raise ValueError(f"batch {B} not divisible by n_micro*dp={n_micro * dp}")
    xm = x.reshape(n_micro, B // n_micro, L, D)
    mm = mask.reshape(n_micro, B // n_micro, L)
    ticks = n_micro + pp - 1
    fwd = [(i, (i + 1) % pp) for i in range(pp)]

    def spmd(stage_params, xm, mm):
        # stage_params leaves: [1, per_stage, ...] (this stage's slice).
        local = jax.tree_util.tree_map(lambda l: l[0], stage_params)
        stage = jax.lax.axis_index("pp")

        def apply_stage(xb, mb):
            amask = layers.pad_mask_to_attn(mb)

            def body(h, block):
                return layers.encoder_block(
                    block, h, amask, dtype, attn_fn=attn_fn
                ), None

            out, _ = jax.lax.scan(body, xb, local)
            return out

        def tick(carry, t):
            prev_out, acc = carry
            # One hop forward around the ring; stage 0's incoming edge is
            # ignored (it reads the microbatch stream instead).
            shifted = jax.lax.ppermute(prev_out, "pp", fwd)
            m_idx = jnp.clip(t - stage, 0, n_micro - 1)
            x_in = jnp.where(stage == 0, xm[m_idx], shifted)
            y = apply_stage(x_in, mm[m_idx])
            out_idx = t - (pp - 1)
            valid = jnp.logical_and(stage == pp - 1, out_idx >= 0)
            written = acc.at[jnp.clip(out_idx, 0, n_micro - 1)].set(y)
            acc = jnp.where(valid, written, acc)
            return (y, acc), None

        zero = jnp.zeros(xm.shape[1:], dtype=xm.dtype)
        acc0 = jnp.zeros_like(xm)
        (_, acc), _ = jax.lax.scan(tick, (zero, acc0), jnp.arange(ticks))
        # Only the last stage accumulated; psum over pp broadcasts it.
        return jax.lax.psum(acc, "pp")

    out = jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(stage_specs(staged), P(None, "dp"), P(None, "dp")),
        out_specs=P(None, "dp"),
        # acc mixes pp-varying writes under a pp-varying predicate before the
        # final psum makes it invariant; the in/out specs are the contract.
        check_vma=False,
    )(staged, xm.astype(dtype), mm)
    return out.reshape(B, L, D)


def encoder_forward_pp(
    params: Any,
    ids: jax.Array,       # [B, L] int32
    mask: jax.Array,      # [B, L] int32 (1 = real)
    cfg,
    mesh,
    attn_fn=dot_product_attention,
    n_micro: Optional[int] = None,
) -> jax.Array:
    """``models.encoder.forward`` with the block stack pipelined over ``pp``.

    Embedding and the pooled head run data-parallel outside the shard_map
    (they are a tiny fraction of the FLOPs); only the depth — where a
    too-deep model actually exceeds one chip — is pipelined.
    """
    pp = mesh.shape["pp"]
    dtype = cfg.compute_dtype
    L = ids.shape[1]
    x = params["embed"].astype(dtype)[ids] + params["pos"][:L].astype(dtype)[None]
    staged = stage_blocks(stack_blocks(params["blocks"]), pp)
    x = pipeline_blocks(
        mesh, staged, x, mask, dtype, attn_fn=attn_fn, n_micro=n_micro
    )
    x = layers.layer_norm(params["ln_f"], x)
    denom = jnp.maximum(mask.sum(axis=1, keepdims=True), 1).astype(jnp.float32)
    pooled = (x.astype(jnp.float32) * mask[:, :, None]).sum(axis=1) / denom
    logits = layers.dense(params["head"], pooled.astype(dtype), dtype)
    return logits.astype(jnp.float32)
