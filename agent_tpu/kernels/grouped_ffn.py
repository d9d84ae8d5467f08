"""One grouped SwiGLU feed-forward over the experts a chip holds: rows
sorted by expert, every expert's rows a whole number of ``ROW_TILE`` tiles,
and ONE ``pallas_call`` whose grid walks the tiles that hold rows — sized by
the real counts, no capacity, no one-hot dispatch (``models/moe.py:
held_experts_ffn`` sorts and combines).

Per tile of rows ``x`` of expert ``e``, the expert's width in blocks ``f``:

    y = sum_f (silu(x W_gate[e][:, f]) * (x W_up[e][:, f])) W_down[e][f, :]

``tile_expert`` (the expert of every tile) and ``n_tiles`` (how many tiles
hold rows) are prefetched scalars: a step past the last tile names that
tile's blocks again (no copy) and computes nothing, so the program is
fixed-shape at the worst case (every token routed here ``k`` times) and costs
what the routed rows cost. At about 128 rows an expert a segment the layer
is bound by reading the experts' weights once (PERF.md section 5).

The weights are read WHERE THEY LIE: the operands are the model's stacked
leaves ``[L, E, ...]`` and a third prefetched scalar, ``layer``, is the
leading block index of every weight block. A custom call wants a standalone
operand, so a layer's slice handed to it inside the layer scan is a copy of
all the layer's experts (1.41 GB a layer a segment at deepseek-v3.2's widths)
made to be read once; the whole stack is the loop's own invariant and costs
nothing. A leaf of one layer ``[E, ...]`` is the same path at ``L = 1``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from agent_tpu.obs.trace import part

# Rows a tile: an expert's rows are padded to whole tiles.
ROW_TILE = 256
# Columns of the expert's width a step.
WIDTH_TILE = 256
_VMEM_LIMIT = 100 * 1024 * 1024


def pallas_supported(d_model: int, d_expert: int, dtype) -> bool:
    return bool(jnp.dtype(dtype) == jnp.bfloat16 and d_model % 128 == 0
                and d_expert % WIDTH_TILE == 0)


def _ffn_kernel(tile_expert_ref, n_tiles_ref, layer_ref, x_ref, wg_ref, wu_ref,
                wd_ref, y_ref, acc_ref):
    del tile_expert_ref, layer_ref
    f32 = jnp.float32
    t, f = pl.program_id(0), pl.program_id(1)
    nn = (((1,), (0,)), ((), ()))

    @pl.when(t < n_tiles_ref[0])
    def _():
        @pl.when(f == 0)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        x = x_ref[...]
        gate = jax.lax.dot_general(x, wg_ref[0, 0], nn,
                                   preferred_element_type=f32)
        up = jax.lax.dot_general(x, wu_ref[0, 0], nn,
                                 preferred_element_type=f32)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        acc_ref[...] += jax.lax.dot_general(h, wd_ref[0, 0], nn,
                                            preferred_element_type=f32)

        @pl.when(f == pl.num_programs(1) - 1)
        def _():
            y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
@part("experts")
def grouped_swiglu(x, tile_expert, n_tiles, w_gate, w_up, w_down, layer=0,
                   *, interpret: bool = False):
    """x [R, d] (rows sorted by expert, ``R`` whole tiles), tile_expert
    [R / ROW_TILE] int32, n_tiles int32 scalar, w_gate, w_up [L, E, d, f],
    w_down [L, E, f, d] (the layers' stack, read in place) and ``layer`` the
    int32 scalar that says which of the ``L``; or one layer's [E, d, f],
    [E, f, d] → y [R, d]. Rows of tiles at and after ``n_tiles`` are not
    written."""
    R, d = x.shape
    fe = w_gate.shape[-1]
    tm, tf = min(ROW_TILE, R), min(WIDTH_TILE, fe)
    n_f = fe // tf
    if w_gate.ndim == 3:                   # one layer: a stack of one
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]

    def tile(t, n):
        return jnp.minimum(t, jnp.maximum(n[0] - 1, 0))

    def width(t, f, n):                    # idle steps keep the last block
        return jnp.where(t < n[0], f, n_f - 1)

    return pl.pallas_call(
        _ffn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R // tm, n_f),
            in_specs=[
                pl.BlockSpec((tm, d), lambda t, f, te, n, ly: (tile(t, n), 0)),
                pl.BlockSpec((1, 1, d, tf), lambda t, f, te, n, ly: (
                    ly[0], te[tile(t, n)], 0, width(t, f, n))),
                pl.BlockSpec((1, 1, d, tf), lambda t, f, te, n, ly: (
                    ly[0], te[tile(t, n)], 0, width(t, f, n))),
                pl.BlockSpec((1, 1, tf, d), lambda t, f, te, n, ly: (
                    ly[0], te[tile(t, n)], width(t, f, n), 0)),
            ],
            out_specs=pl.BlockSpec((tm, d),
                                   lambda t, f, te, n, ly: (tile(t, n), 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        name="moe_grouped_swiglu",
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), n_tiles.reshape(1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), x, w_gate, w_up, w_down)
