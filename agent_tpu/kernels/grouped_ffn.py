"""One grouped SwiGLU feed-forward over the experts a chip holds: the rows
routed here sorted by expert, every expert's rows a whole number of
``ROW_TILE`` tiles, and ONE ``pallas_call`` whose grid walks the tiles that
hold rows, sized by the real counts, no capacity, no one-hot dispatch
(``models/moe.py: held_experts_ffn`` sorts, counts and lays out the tiles).

Per tile of rows ``x`` of expert ``e``, the expert's width in blocks ``f``:

    y = sum_f (silu(x W_gate[e][:, f]) * (x W_up[e][:, f])) W_down[e][f, :]

**The kernel addresses its own rows** (PR 41). The tokens' rows ``[S, d]``
stay where they lie; ``token`` and ``slot`` (a prefetched table each: for
every sorted row, the token it computes on and the row of the result it
writes) and ``tile_rows`` (how many rows of a tile are real) let it fetch a
tile's REAL rows by one DMA a row into a double-buffered tile (the next
tile's rows go out a share every width step, while this tile's weights
stream) and write its real rows straight to their slots. XLA gathers nothing:
in front of a custom call a gather is materialized whole, at the fixed worst
case (every token routed here ``k`` times, plus a tile of padding an expert),
41 k and 70 k rows a call to serve 4.5 k and 2.3 k at the two cells that run
this (PERF.md section 6). The slots are ``k``-major (pair ``(token, j)`` at
row ``j * S + token``), so :func:`combine_pairs` reads them in one pass.

**A tile's row traffic stays out of the matmuls' way** (PR 45). A row's
words are ``n = d / 256`` consecutive sublane rows of a ``[rows * n, 128]``
array, in the kernel's buffers as in the passes beside it, so a row's copy
is ``n`` sublane rows and lane tile ``c`` of a whole tile is ONE strided
read (seen as ``[rows, 1, d / 2]``, a tile of one sublane a row, a tile of
256 rows was unpacked by 2,304 single-sublane loads: 4.5 us of a full
tile's 38). A DMA semaphore counts what has arrived, so a tile's landed
copies are waited for by SIZE (:func:`wait_sizes`: one wait at a full tile,
nine at most), and the descriptors go out ``_COPIES_A_TURN`` a loop turn. A
descriptor itself is NOT hidden: a DMA start is a fence in its basic block
(static descriptors laid between the matmuls ran no faster than a rolled
loop in front of them), 7-9 ns each on the scalar core, 512 a full tile.

``tile_expert`` and ``n_tiles`` are prefetched too: a step past the last
tile names that tile's blocks again (no copy) and computes nothing, so the
program is fixed-shape at the worst case and costs what the routed rows
cost. The expert's width is walked in steps of :func:`width_step` columns.

**A tile computes the rows it HOLDS** (PR 51). A tile's real rows come first
in it, so its unpack, its three matmuls and its pack run over its first
``ceil(tile_rows / SUB_ROWS)`` sub-blocks of 128 rows, in ONE body for every
such count (128 rows or all 256: a branch on ``tile_rows``, one kernel
whatever the caller's routing). The products of a row do not depend on the
rows beside it, so every real row's words are what the whole tile gave, to
the bit. What that uncovered is what bounds the kernel (PERF.md section 5,
the kernel alone on the chip with its products taken out): at about 60 or 128
rows an expert a segment (a held SHARE of a wide router: ling-3.0-flash-vl,
mistral-small-4-119b, deepseek-v3.2) the STREAM of the experts' weights, a
step's three blocks fetched one grid step ahead at 590 to 690 GB/s of the
memory's 819 (a tile of ling's 59 rows was paced by the MXU's passes over 256
before, 20.3 us against 17.2 now; deepseek's was at its weights' stream
already); at 512 (every expert held, 8 pairs a token: mellum2-12b-a2.5b) the
three matmuls as the kernel compiler lowers them (22.5 us a full tile against
the MXU's 16.1), then the rows' descriptors (about 4 us a full tile), and a
spill tile of a few dozen rows saves 3.3 us of its 11 because the NEXT
expert's weights (12.4 MB, fetched under an expert's last tile and no sooner)
take 18 us to arrive.

The weights are read WHERE THEY LIE: the operands are the model's stacked
leaves ``[L, E, ...]`` and one more prefetched scalar, ``layer``, is the
leading block index of every weight block. A custom call wants a standalone
operand, so a layer's slice handed to it inside the layer scan is a copy of
all the layer's experts (1.41 GB a layer a segment at deepseek-v3.2's widths)
made to be read once; the whole stack is the loop's own invariant and costs
nothing. A leaf of one layer ``[E, ...]`` is the same path at ``L = 1``."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from agent_tpu.obs.trace import part

# Rows a tile: an expert's rows are padded to whole tiles.
ROW_TILE = 256
# Rows a sub-block: a tile's unpack, matmuls and pack run over its sub-blocks
# that hold a real row, not over the tile. Under 128 rows the MXU's weight
# loads no longer hide (four sub-blocks of 64 read +50 % on a full tile, PR
# 45); ``models/moe.py: held_work`` counts the rows by the same number.
SUB_ROWS = 128
# Columns of the expert's width a step.
WIDTH_TILE = 256
_VMEM_LIMIT = 100 * 1024 * 1024
# The combine's block of words (every ``j`` of a run of tokens), at most.
_COMBINE_BLOCK_BYTES = 8 * 1024 * 1024


# A width that is no whole number of ``WIDTH_TILE`` is walked in ONE step
# where it is whole lane tiles and at most this wide (the three weight blocks
# of a step, double-buffered, are 12 d f bytes: 25 MB at 2,304 x 896).
_ONE_STEP_WIDTH = 1024


def width_step(d_expert: int) -> int:
    """Columns of the expert's width a grid step walks: ``WIDTH_TILE`` where
    the width is whole tiles of it (2,048: eight steps); a width of whole lane
    tiles that is not (896 = 7 x 128) in one step, its weight blocks fetched
    once an EXPERT and not once a tile (consecutive tiles of one expert name
    the same blocks); 0 where the kernel cannot walk it."""
    if d_expert % WIDTH_TILE == 0:
        return WIDTH_TILE
    if d_expert % 128 == 0 and d_expert <= _ONE_STEP_WIDTH:
        return d_expert
    return 0


def pallas_supported(d_model: int, d_expert: int, dtype) -> bool:
    """bf16 rows whose two halves are whole lane tiles (a row travels as
    words: column ``c`` beside column ``c + d / 2``), and a width the kernel's
    second grid axis can walk (:func:`width_step`)."""
    return bool(jnp.dtype(dtype) == jnp.bfloat16 and d_model % 256 == 0
                and width_step(d_expert) > 0)


# A row travels as 32-bit words that lie end to end: the DMA engine copies a
# run of sublane rows of a ``[rows * n, 128]`` array (or ONE row of a
# ``[rows, 1, words]`` array, the same bytes: what the callers see) and
# refuses one row of a tiled ``[rows, d]`` array (8 rows a tile, 16 in
# bf16). Word ``c`` of a row holds column ``c`` in its low half and column
# ``c + d / 2`` in its high half: both halves are whole lane tiles, and a
# bf16 is the high half of its float32. XLA re-lays such an array whole
# before it computes on it, so the two passes that touch it are kernels too.
_HIGH = 0xFFFF0000


def _halves(words):
    """uint32 words → (low halves, high halves) as the float32 they stand for."""
    f32 = functools.partial(jax.lax.bitcast_convert_type,
                            new_dtype=jnp.float32)
    return f32(words << 16), f32(words & jnp.uint32(_HIGH))


def _words(low, high):
    """Two float32 arrays that hold bf16 values → one of uint32 words."""
    bits = functools.partial(jax.lax.bitcast_convert_type,
                             new_dtype=jnp.uint32)
    return (bits(low) >> 16) | (bits(high) & jnp.uint32(_HIGH))


# The kernels see the words as ``[rows * n, 128]``, ``n = d / 256`` lane tiles
# a row: the same bytes as ``[rows, 1, d / 2]``, but 8 sublanes a tile, so a
# block streams at the memory's rate and lane tile ``c`` of ``bs`` rows is ONE
# strided read, ``[c :: n]``.

def _token_block(S: int) -> int:
    block = math.gcd(S, ROW_TILE)
    return block if block % 8 == 0 else S


def _pack_kernel(x_ref, words_ref):
    bs, d = x_ref.shape
    n = d // 256
    for c in range(n):
        at = c * 128
        words_ref[pl.ds(c, bs, stride=n), :] = _words(
            x_ref[:, at:at + 128].astype(jnp.float32),
            x_ref[:, d // 2 + at:d // 2 + at + 128].astype(jnp.float32))


def _pack_rows(x, interpret):
    """[S, d] bf16 → its words [S * n, 128] uint32, one pass."""
    S, d = x.shape
    bs, n = _token_block(S), d // 256
    return pl.pallas_call(
        _pack_kernel,
        grid=(S // bs,),
        in_specs=[pl.BlockSpec((bs, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bs * n, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S * n, 128), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="moe_pack_rows",
        interpret=interpret,
    )(x)


def unpack_rows(words, dtype=jnp.bfloat16):
    """[n, 1, d / 2] uint32 as :func:`grouped_swiglu` writes them → [n, d]
    (plain XLA: for a test that wants to look at rows)."""
    low, high = _halves(words[:, 0, :])
    return jnp.concatenate([low, high], axis=1).astype(dtype)


def _sum_pairs(term, js):
    """``term(j)`` summed over ``js`` in the order XLA's reduce over a token's
    ``k`` pairs had when they lay in the sublanes (stride halving: ``(a0 + a2)
    + (a1 + a3)`` at 4, ``((a0 + a4) + (a2 + a6)) + ((a1 + a5) + (a3 + a7))``
    at 8), so a document's answer is the same bits as before the kernel
    combined (PERF.md section 6, PR 41)."""
    if len(js) == 1:
        return term(js[0])
    return _sum_pairs(term, js[0::2]) + _sum_pairs(term, js[1::2])


def _combine_kernel(words_ref, gate_ref, held_ref, y_ref):
    bs, d = y_ref.shape
    k, n = words_ref.shape[0], d // 256
    gates = [jnp.broadcast_to(gate_ref[j], (bs, 128)) for j in range(k)]
    held = [jnp.broadcast_to(held_ref[j] != 0, (bs, 128)) for j in range(k)]
    for c in range(n):
        def term(j, half):
            y = _halves(words_ref[j, pl.ds(c, bs, stride=n), :])[half]
            # A pair held elsewhere was never written: select, not multiply.
            return jnp.where(held[j], y * gates[j], 0.0)

        for half in range(2):
            at = half * d // 2 + c * 128
            y_ref[:, at:at + 128] = _sum_pairs(
                functools.partial(term, half=half), list(range(k)))


@functools.partial(jax.jit, static_argnames=("interpret",))
@part("experts")
def combine_pairs(words, held, gates, *, interpret: bool = False):
    """words [k * S, 1, d / 2] uint32 (:func:`grouped_swiglu`'s, pair
    ``(token, j)`` at row ``j * S + token``); held [S, k] bool, gates [S, k]
    float32 → ``sum_j where(held[:, j], gates[:, j] * y[j], 0)`` in float32
    [S, d] (:func:`_sum_pairs`' order). Reads the words once, where they
    lie."""
    S, k = held.shape
    half = words.shape[-1]
    n = half // 128
    bs = _token_block(S)
    while bs % 16 == 0 and k * bs * half * 4 > _COMBINE_BLOCK_BYTES:
        bs //= 2
    per_pair = pl.BlockSpec((k, bs, 1), lambda i: (0, i, 0))
    return pl.pallas_call(
        _combine_kernel,
        grid=(S // bs,),
        in_specs=[pl.BlockSpec((k, bs * n, 128), lambda i: (0, i, 0)),
                  per_pair, per_pair],
        out_specs=pl.BlockSpec((bs, 2 * half), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, 2 * half), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="moe_combine_pairs",
        interpret=interpret,
    )(words.reshape(k, S * n, 128), gates.astype(jnp.float32).T[:, :, None],
      held.astype(jnp.int32).T[:, :, None])


def wait_sizes(n, tile_rows: int):
    """A DMA semaphore counts what has ARRIVED, and a wait takes off the size
    of the descriptor it is handed: ``n <= tile_rows`` landed rows are waited
    for as one descriptor of ``2^b`` rows for every set bit of ``n``, not as
    ``n`` of one row. ``[(rows, taken)]``, ``taken`` 1 where ``n`` has the
    bit (``n`` a Python int or a traced scalar): nine entries at a tile of
    256, of which a FULL tile takes one."""
    return [(1 << b, (n >> b) & 1) for b in range(tile_rows.bit_length())]


# Descriptors a turn of a rows' loop: the turn's own work (its counter, its
# branch) is paid once for them.
_COPIES_A_TURN = 8


def _clamped_ffn_kernel(token_ref, slot_ref, tile_expert_ref, tile_rows_ref,
                        n_tiles_ref, layer_ref, limit_ref, *refs):
    """:func:`_ffn_kernel` under one more prefetched scalar: the clamp on the
    SwiGLU's two inputs (float32; infinity: none)."""
    _ffn_kernel(token_ref, slot_ref, tile_expert_ref, tile_rows_ref,
                n_tiles_ref, layer_ref, *refs, limit=limit_ref[0])


def _ffn_kernel(token_ref, slot_ref, tile_expert_ref, tile_rows_ref,
                n_tiles_ref, layer_ref, x_hbm, wg_ref, wu_ref, wd_ref, y_hbm,
                rows_in, x_ref, acc_ref, rows_out, sem, limit=None):
    del tile_expert_ref, layer_ref
    f32 = jnp.float32
    t, f = pl.program_id(0), pl.program_id(1)
    n_f, n_tiles = pl.num_programs(1), n_tiles_ref[0]
    tm, d = x_ref.shape
    n = d // 256                           # sublane rows a row's words fill
    nn = (((1,), (0,)), ((), ()))
    OUT = 2                                # rows_in's two slots have sem 0, 1
    sub = min(SUB_ROWS, tm)

    def live(block):
        """``block(rows)`` on the tile's first ``rows`` rows, its sub-blocks
        that hold a real row: a body for every count of them (one body a
        sub-block loads every weight tile twice on a full tile: 0.2 us of
        its 22.5 at mellum2's widths)."""
        blocks = jax.lax.div(tile_rows_ref[t] + (sub - 1), sub)
        for i in range(1, tm // sub + 1):
            pl.when(blocks == i)(functools.partial(block, i * sub))

    def for_rows(lo, hi, one):
        """``one(j)`` for ``lo <= j < hi``, ``_COPIES_A_TURN`` a turn."""
        turns = jax.lax.div(jnp.maximum(hi - lo, 0), _COPIES_A_TURN)

        def single(j, carry):
            one(j)
            return carry

        def turn(g, carry):                # traced once, unrolled when lowered
            first = lo + g * _COPIES_A_TURN
            return jax.lax.fori_loop(
                0, _COPIES_A_TURN, lambda u, c: single(first + u, c), carry,
                unroll=True)
        jax.lax.fori_loop(0, turns, turn, 0)
        jax.lax.fori_loop(lo + turns * _COPIES_A_TURN, hi, single, 0)

    def fetch(tile, lo, hi):
        """Rows ``lo .. hi - 1`` of ``tile``: x → its slot of ``rows_in``."""
        def one(j):
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(token_ref[tile * tm + j] * n, n)],
                rows_in.at[tile % 2, pl.ds(j * n, n)],
                sem.at[tile % 2]).start()
        for_rows(lo, hi, one)

    def wait(rows, s):
        """``rows`` landed row copies on semaphore ``s``, by SIZE."""
        for size, taken in wait_sizes(rows, tm):
            @pl.when(taken == 1)
            def _(size=size):
                landed = rows_out.at[pl.ds(0, size * n)]
                pltpu.make_async_copy(landed, landed, sem.at[s]).wait()

    @pl.when(t < n_tiles)
    def _():
        @pl.when(f == 0)
        def _():
            @pl.when(t == 0)
            def _():
                fetch(0, 0, tile_rows_ref[0])

            wait(tile_rows_ref[t], t % 2)

            @live
            def _(rows):
                # Lane tile c of every row is ONE strided read; one
                # expression over them all (every traced equation is paid
                # again at every start, compile cache or not).
                low, high = _halves(jnp.concatenate(
                    [rows_in[t % 2, pl.ds(c, rows, stride=n), :]
                     for c in range(n)], axis=1))
                x_ref[:rows, :d // 2] = low.astype(x_ref.dtype)
                x_ref[:rows, d // 2:] = high.astype(x_ref.dtype)
                acc_ref[:rows, :] = jnp.zeros((rows, d), f32)

        # The next tile's rows, a share of them every width step: where the
        # weights stream, the descriptors go out while they do.
        @pl.when(t + 1 < n_tiles)
        def _():
            share = -(-tm // n_f)
            fetch(t + 1, f * share,
                  jnp.minimum((f + 1) * share, tile_rows_ref[t + 1]))

        @live
        def _(rows):
            x = x_ref[:rows, :]
            gate = jax.lax.dot_general(x, wg_ref[0, 0], nn,
                                       preferred_element_type=f32)
            up = jax.lax.dot_general(x, wu_ref[0, 0], nn,
                                     preferred_element_type=f32)
            if limit is not None:
                gate, up = (jnp.minimum(gate, limit),
                            jnp.clip(up, -limit, limit))
            h = (jax.nn.silu(gate) * up).astype(x.dtype)
            acc_ref[:rows, :] += jax.lax.dot_general(
                h, wd_ref[0, 0], nn, preferred_element_type=f32)

        @pl.when(f == n_f - 1)
        def _():
            @pl.when(t > 0)                # the tile before's rows have left
            def _():
                wait(tile_rows_ref[t - 1], OUT)

            @live
            def _(rows):
                y = acc_ref[:rows, :].astype(x_ref.dtype).astype(f32)
                words = _words(y[:, :d // 2], y[:, d // 2:])
                for c in range(n):
                    rows_out[pl.ds(c, rows, stride=n), :] = (
                        jax.lax.slice_in_dim(words, c * 128, (c + 1) * 128,
                                             axis=1))

            def one(j):
                pltpu.make_async_copy(
                    rows_out.at[pl.ds(j * n, n)],
                    y_hbm.at[pl.ds(slot_ref[t * tm + j] * n, n)],
                    sem.at[OUT]).start()
            for_rows(0, tile_rows_ref[t], one)

            @pl.when(t == n_tiles - 1)
            def _():
                wait(tile_rows_ref[t], OUT)


@functools.partial(jax.jit, static_argnames=("n_slots", "interpret"))
@part("experts")
def grouped_swiglu(x, token, slot, tile_expert, tile_rows, w_gate, w_up,
                   w_down, layer=0, limit=None, *, n_slots: int,
                   interpret: bool = False):
    """x [S, d]; for every row ``r`` of the sorted, tile-padded order (tile
    ``r // ROW_TILE``), ``token[r]`` the row of ``x`` it computes on and
    ``slot[r]`` the row of the result it writes (each slot at most once);
    tile_expert [T] int32 and tile_rows [T] int32, how many rows of the tile
    are real (they come first in it; the tiles that hold rows come first of
    the ``T``); w_gate, w_up [L, E, d, f], w_down [L, E, f, d] (the layers'
    stack, read in place) and ``layer`` the int32 scalar that says which of
    the ``L``; or one layer's [E, d, f], [E, f, d]; ``limit`` (a float32
    scalar, or ``None``: the kernel without it): the clamp on the SwiGLU's two
    inputs, ``silu(min(gate, limit)) x clip(up, -limit, limit)`` → y
    [n_slots, 1, d / 2]
    uint32, a row's two halves in a word (:func:`combine_pairs` and
    :func:`unpack_rows` read them). Only the real rows' slots are written:
    every other row of ``y`` is whatever the memory held."""
    d = x.shape[1]
    fe = w_gate.shape[-1]
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    tile_rows = i32(tile_rows)
    tm = token.shape[0] // tile_rows.shape[0]
    tf = width_step(fe) or min(WIDTH_TILE, fe)
    n_f = fe // tf
    if w_gate.ndim == 3:                   # one layer: a stack of one
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
    n_tiles = (tile_rows > 0).sum(dtype=jnp.int32)

    def tile(t, n):
        return jnp.minimum(t, jnp.maximum(n[0] - 1, 0))

    def width(t, f, n):                    # idle steps keep the last block
        return jnp.where(t < n[0], f, n_f - 1)

    def w_in(t, f, tok, sl, te, tr, n, ly, *_):
        return ly[0], te[tile(t, n)], 0, width(t, f, n)

    def w_out(t, f, tok, sl, te, tr, n, ly, *_):
        return ly[0], te[tile(t, n)], width(t, f, n), 0

    clamp = () if limit is None else (
        jnp.asarray(limit, jnp.float32).reshape(1),)

    per_row = d // 256                     # sublane rows a row's words fill
    words = pl.pallas_call(
        _clamped_ffn_kernel if clamp else _ffn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6 + len(clamp),
            grid=(tile_rows.shape[0], n_f),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, 1, d, tf), w_in),
                pl.BlockSpec((1, 1, d, tf), w_in),
                pl.BlockSpec((1, 1, tf, d), w_out),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, tm * per_row, 128), jnp.uint32),
                            pltpu.VMEM((tm, d), x.dtype),
                            pltpu.VMEM((tm, d), jnp.float32),
                            pltpu.VMEM((tm * per_row, 128), jnp.uint32),
                            pltpu.SemaphoreType.DMA((3,))],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots * per_row, 128), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        name="moe_grouped_swiglu",
        interpret=interpret,
    )(i32(token), i32(slot), i32(tile_expert), tile_rows, n_tiles.reshape(1),
      i32(layer).reshape(1), *clamp, _pack_rows(x, interpret), w_gate, w_up,
      w_down)
    return words.reshape(n_slots, 1, d // 2)
