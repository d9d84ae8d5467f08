"""Plain causal grouped-query attention of one document's segment over the
document's key and value cache: the attention half of the ``hybrid_ssm``
mixer of the decoder language-model family (``models/decoder_lm.py``).

The segment's ``S`` queries sit at positions ``pos0 .. pos0 + S`` and attend
the keys ``0 .. pos0 + t`` of a cache allocated at the document's padded
length. No mask array: causality is the positions' (``pos0`` comes in as a
prefetched scalar), so a (query tile, key tile) pair above the diagonal is
never fetched or computed, one across it is masked from two iotas, and one
below it takes no mask at all. The ``G`` query heads of a key-value head meet
ONE loaded key and value tile: their rows are stacked, ``[G * tile, D]``, so a
key tile is fetched once for five heads and every matmul has 2,560 rows.
Where a key-value head has FEWER query heads (``dense_mla``: one, over keys
expanded from latents) the query tile grows in their place
(:func:`query_tile`): a loaded key tile again meets about that many rows.

Keys and values come as one layer's ``[Hkv, Lk, D]`` or as the STACK of all
the layers' caches ``[layers, 1, Hkv, Lk, D]`` with the layer's number (the
operand's rank says which; the number is a second prefetched scalar, in
front of the tile's index): a custom call's operand sliced out of a stack
is a copy of the slice, 67 MB a layer a segment at 65,536 keys, and the
stack is the layer scan's carry (``models/decoder_lm.py: MIXER_CACHES``).

Heads of HALF a lane tile (64: ``conv_gqa``'s attention layers) lie TWO
key-value heads a cache row (:func:`cache_rows`: ``[Hkv / 2, Lk, 128]``, head
``2 p`` on lanes 0-63 and ``2 p + 1`` on 64-127), so a loaded key or value
tile is whole lanes. A grid step then serves the pair: the ``G`` query heads of
each are stacked as before, ``[2 G * tile, 128]``, a row of the first head
zero on the second's lanes and the other way round, so one product over 128
lanes gives each row its own head's scores (a matmul 64 deep fills half the
MXU's depth either way: the zeros cost nothing a 64-wide operand would not),
the value product gives each row both heads' values and the finish keeps a
row's own half. Which form runs is the SHAPES': queries 64 wide over a cache
128 wide.

A WINDOW layer (``window_gqa``'s three of four) attends the last ``window``
keys only (:func:`window_attention`): its keys are ``[the window keys before
the segment | the segment's own]``, never the document's cache, and the same
kernel body walks ``(window + query tile) / key tile`` key tiles a query tile
(three at 1,024 and 512) in place of every tile up to the diagonal: tiles
wholly behind ``t - window`` are not in the grid, the lower edge is masked from
the same two iotas as the diagonal, and what lies before the document's
first token is masked by position (a prefetched scalar). The call carries a
name of its own, ``window_gqa_attention``.

A streaming softmax with float32 scores, statistics and accumulator; the row
statistics are kept lane-replicated and the weights are rounded to bf16
before the value matmul, as ``kernels/sparse_mla.py``'s attention does
(PERF.md section 5 has that kernel's take-apart table). Which path runs is
read from shapes and platform (:func:`pallas_supported`): the kernel on a TPU
at lane-wide heads and whole tiles, the same arithmetic in ``jax.numpy``
elsewhere. No option, environment variable or ``model_config`` key chooses.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from agent_tpu.obs.trace import part

_LANES = 128
# Queries and keys a tile. A step's scores are [G * 512, 256] float32 a
# sub-block (2.6 MB at five query heads a key-value head).
QUERY_TILE = 512
KEY_TILE = 512
KEY_BLOCK = 256
_VMEM_LIMIT = 100 * 1024 * 1024
_MASKED = -1e30


def pallas_supported(seq_len: int, cache_len: int, d_head: int, dtype,
                     d_value: Optional[int] = None) -> bool:
    """Shapes the kernel takes on the chip: bf16 operands, lane-wide heads
    (or, where queries and keys are wider than the values, ``d_value`` of
    them: keys of whole lanes over lane-wide values; or heads of HALF a lane
    tile, keys and values alike, whose cache holds two a row:
    :func:`cache_rows`), a segment of whole query tiles and a cache of whole
    key tiles."""
    d_value = d_head if d_value is None else d_value
    wide = (d_head % _LANES == 0 and d_value == _LANES) or (
        d_head == d_value == _LANES // 2)
    return bool(jnp.dtype(dtype) == jnp.bfloat16 and wide
                and seq_len % QUERY_TILE == 0 and cache_len % KEY_TILE == 0)


def window_supported(seq_len: int, window: int, d_head: int, dtype) -> bool:
    """The same, for a window layer: its keys are ``window + seq_len``, and
    the window is whole key tiles (a query tile's first key tile is then a
    whole one); lane-wide heads only (its keys are no cache of paired
    rows)."""
    return bool(pallas_supported(seq_len, window + seq_len, d_head, dtype)
                and window % KEY_TILE == 0 and d_head % _LANES == 0)


def heads_a_row(n_kv_heads: int, d_head: int) -> int:
    """Key-value heads side by side on a cache row's lanes: two where a head
    is half a lane tile and the heads pair up, else one."""
    return 2 if 2 * d_head == _LANES and n_kv_heads % 2 == 0 else 1


def cache_shape(n_kv_heads: int, cache_len: int, d_head: int):
    """``(rows of heads, keys, lanes)`` of one layer's key (or value) cache
    as the kernel reads it: ``[Hkv, Lk, D]``, or two heads a row."""
    n = heads_a_row(n_kv_heads, d_head)
    return n_kv_heads // n, cache_len, n * d_head


def cache_rows(x: jax.Array) -> jax.Array:
    """A segment's keys or values ``[Hkv, S, D]`` as the cache holds them
    (:func:`cache_shape`): themselves, or ``[Hkv / 2, S, 2 D]`` with head
    ``2 p`` on a row's first ``D`` lanes and ``2 p + 1`` on its last."""
    Hkv, S, D = x.shape
    n = heads_a_row(Hkv, D)
    if n == 1:
        return x
    return x.reshape(Hkv // n, n, S, D).transpose(0, 2, 1, 3).reshape(
        Hkv // n, S, n * D)


def _heads_apart(x: jax.Array, n: int) -> jax.Array:
    """:func:`cache_rows` undone: ``[Hkv / n, Lk, n D]`` → ``[Hkv, Lk, D]``."""
    rows, Lk, lanes = x.shape
    return x.reshape(rows, Lk, n, lanes // n).transpose(0, 2, 1, 3).reshape(
        rows * n, Lk, lanes // n)


# Rows a grid step's matmuls have at most. Five stacked heads of 512 queries
# (2,560) stay as they are; ONE head a key-value head takes a whole
# 4,096-token segment a step. The kernel alone at 32 heads of 128, 4,096
# queries, 65,536 keys, a document's last segment / one in its middle / its
# first, ms a call (my chip runs, PR 40; share of the exact causal half's
# roofline): 512 queries a step 42.97 / 24.00 / 7.36 (50.3 / 43.6 / 9.5 %),
# 1,024: 32.63 / 17.57 / 4.37, 2,048: 27.61 / 14.48 / 3.00, 4,096: 25.21 /
# 13.11 / 2.44 (85.8 / 79.8 / 28.6 %): fewer steps and a key tile fetched
# once a segment outweigh the half tile more that crosses the diagonal.
MAX_STEP_ROWS = 4096


def query_tile(groups: int, seq_len: int) -> int:
    """Queries a grid step: ``QUERY_TILE``, doubled while the segment is
    whole tiles of the double and the ``groups`` stacked heads of it stay
    within ``MAX_STEP_ROWS`` (512 at five heads a key-value head, a whole
    4,096-token segment at one). A power of two: the kernel's mask takes a row's query from its low
    bits."""
    tq = QUERY_TILE
    while seq_len % (2 * tq) == 0 and groups * 2 * tq <= MAX_STEP_ROWS:
        tq *= 2
    return tq


def visited_pairs(seq_len: int, pos0: int, query_tile: int = QUERY_TILE,
                  key_tile: int = KEY_TILE) -> int:
    """(query, key) pairs the kernel's grid computes for a segment of
    ``seq_len`` queries at ``pos0``, a query head: a query tile meets the key
    tiles up to the one that holds its last query's position."""
    pairs = 0
    for i in range(-(-seq_len // query_tile)):
        last = pos0 + min(seq_len, (i + 1) * query_tile)
        pairs += query_tile * (-(-last // key_tile)) * key_tile
    return pairs


def window_visited_pairs(seq_len: int, pos0: int, window: int,
                         query_tile: int = QUERY_TILE,
                         key_tile: int = KEY_TILE) -> int:
    """The same under a window, over ``[window keys before | the segment]``:
    a query tile meets the ``(window + query_tile) / key_tile`` key tiles
    from its window's first to its diagonal's, less those that end before the
    document's first token."""
    before = max(window - pos0, 0)          # keys that lie before the document
    pairs = 0
    for i in range(-(-seq_len // query_tile)):
        tiles = range(i * query_tile // key_tile,
                      (i * query_tile + window + query_tile) // key_tile)
        pairs += query_tile * key_tile * sum(
            (kt + 1) * key_tile > before for kt in tiles)
    return pairs


def _window_jnp(q, k, v, before, window):
    """q [Hkv, G, S, D]; k, v [Hkv, window + S, D]: query ``i`` sits at key
    ``window + i`` and sees the ``window`` keys up to its own, from key
    ``before`` on (what lies before the document's first token is not a key).
    Dense float32 scores."""
    f32 = jnp.float32
    S = q.shape[2]
    at = jnp.arange(window + S)[None, :]
    t = window + jnp.arange(S)[:, None]
    seen = (at <= t) & (at > t - window) & (at >= before)
    s = jnp.einsum("hgtd,hsd->hgts", q.astype(f32), k.astype(f32))
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("hgts,hsd->hgtd", p, v.astype(f32))


def _attention_jnp(q, k, v, pos0):
    """q [Hkv, G, S, D], k [Hkv, Lk, D], v [Hkv, Lk, Dv]: dense scores, float32, a block of
    query rows at a time. Keys at and after ``pos0 + S`` are taken out of
    both products (a cache holds whatever was there)."""
    f32 = jnp.float32
    Hkv, G, S, D = q.shape
    Lk, Dv = v.shape[1:]
    seen = (jnp.arange(Lk) < pos0 + S)[None, :, None]
    kf = jnp.where(seen, k.astype(f32), 0.0)
    vf = jnp.where(seen, v.astype(f32), 0.0)
    rows = next(r for r in (256, 128, 64, 32, 16, 8, 4, 2, 1) if S % r == 0)

    def block(args):
        qb, t = args                                  # [Hkv, G, rows, D], [rows]
        causal = jnp.arange(Lk)[None, :] <= t[:, None]
        s = jnp.einsum("hgtd,hsd->hgts", qb, kf)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgts,hsd->hgtd", p, vf)

    out = jax.lax.map(block, (
        q.astype(f32).reshape(Hkv, G, S // rows, rows, D).transpose(
            2, 0, 1, 3, 4),
        (pos0 + jnp.arange(S)).reshape(S // rows, rows)))
    return out.transpose(1, 2, 0, 3, 4).reshape(Hkv, G, S, Dv)


def _attention_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                      acc_ref, *pair_ref, tq: int, tk: int, bk: int,
                      groups: int, window: Optional[int] = None):
    """One (key-value head, query tile, key tile) step. With ``window`` the
    keys are ``[window keys before the segment | the segment]``, a query
    counts from ``window`` in them, step ``j`` is key tile ``i * tq / tk + j``
    and ``pos_ref`` holds the first key that lies inside the document. With
    ``pair_ref`` (a scratch ``[2 G tq, 2 D]``) a row of the operands is TWO
    heads of ``D`` lanes: ``groups`` counts both heads' query heads, the
    first head's rows are stacked over the second's, each zero on the other
    head's lanes, and a finished row keeps its own head's lanes."""
    f32 = jnp.float32
    i, j = pl.program_id(1), pl.program_id(2)
    if window is None:
        first = pos_ref[0] + i * tq          # position of the tile's first query
        n_kv = (first + tq + tk - 1) // tk
        kt = j
    else:
        first = window + i * tq              # its index among the keys
        kt = i * (tq // tk) + j
    rows = groups * tq
    nt = (((1,), (1,)), ((), ()))           # [m, k] x [n, k]
    nn = (((1,), (0,)), ((), ()))
    lanes = bk // _LANES

    def first_heads(shape):
        """Whether a lane of ``[rows / 2, 2 D]`` is the pair's first head's."""
        return jax.lax.broadcasted_iota(jnp.int32, shape, 1) < shape[1] // 2

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)
        if pair_ref:
            both = q_ref[0].reshape(rows // 2, q_ref.shape[-1])
            mine = first_heads(both.shape)
            pair_ref[0][:rows // 2] = jnp.where(mine, both, 0)
            pair_ref[0][rows // 2:] = jnp.where(mine, 0, both)

    def tile(masked: bool):
        q = pair_ref[0][...] if pair_ref else q_ref[0].reshape(
            rows, q_ref.shape[-1])
        for c in range(tk // bk):
            keys = slice(c * bk, (c + 1) * bk)
            s = jax.lax.dot_general(q, k_ref[0, keys, :], nt,
                                    preferred_element_type=f32)  # [rows, bk]
            if masked:
                # Row r of the stack is query r % tq of head r // tq.
                t = first + jnp.bitwise_and(jax.lax.broadcasted_iota(
                    jnp.int32, (rows, bk), 0), tq - 1)
                at = kt * tk + c * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, bk), 1)
                seen = at <= t
                if window is not None:
                    seen = seen & (at > t - window) & (at >= pos_ref[0])
                s = jnp.where(seen, s, _MASKED)
            m_prev = m_ref[...]                              # [rows, 128]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - jnp.concatenate([m_new] * lanes, axis=1))
            alpha = jnp.exp(m_prev - m_new)
            part = p[:, :_LANES]
            for g in range(1, lanes):
                part = part + p[:, g * _LANES:(g + 1) * _LANES]
            l_ref[...] = alpha * l_ref[...] + part
            acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, keys, :], nn,
                preferred_element_type=f32)
            m_ref[...] = m_new

    if window is None:
        # A tile whose last key lies past the tile's first query crosses the
        # diagonal; the tiles before it are wholly below.
        crosses = (j + 1) * tk - 1 > first
        pl.when((j < n_kv) & crosses)(lambda: tile(True))
        pl.when((j < n_kv) & jnp.logical_not(crosses))(lambda: tile(False))
    else:
        # The first ``tq / tk`` tiles hold the window's lower edge, the last
        # as many the diagonal; one that starts before the document does is
        # masked too, and one that ends before it is left out. (A row whose
        # keys so far were all masked holds weights of exp(0): its first real
        # key's ``alpha`` is exp(-1e30 - m) = 0 and takes them out again; a
        # row's own key is always real.)
        edge = (j < tq // tk) | (j >= pl.num_programs(2) - tq // tk) | (
            kt * tk < pos_ref[0])
        inside = (kt + 1) * tk > pos_ref[0]
        pl.when(inside & edge)(lambda: tile(True))
        pl.when(inside & jnp.logical_not(edge))(lambda: tile(False))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        out = acc_ref[...] / l_ref[...].sum(axis=-1, keepdims=True)
        if pair_ref:
            out = jnp.where(first_heads((rows // 2, out.shape[1])),
                            out[:rows // 2], out[rows // 2:])
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _attention_call(q, k, v, pos0, layer=None, *,
                    window: Optional[int] = None, interpret: bool):
    """``pos0``: the position of the segment's first token; under ``window``
    the first of the keys that lies inside the document. ``layer``: the one
    to attend where ``k`` and ``v`` are the layers' stack (rank 5)."""
    n = k.shape[-1] // q.shape[-1]          # key-value heads a cache row
    if n > 1:
        # Query head g of the pair's two heads side by side on the lanes, as
        # the cache holds their keys: [Hkv / 2, G, S, 2 D].
        Hkv, G, S, D = q.shape
        q = q.reshape(Hkv // n, n, G, S, D).transpose(0, 2, 3, 1, 4).reshape(
            Hkv // n, G, S, n * D)
    Hkv, G, S, D = q.shape
    Lk, Dv = k.shape[-2], v.shape[-1]
    tq, tk, bk = query_tile(n * G, S), KEY_TILE, KEY_BLOCK

    if window is None:
        # Steps past a query tile's last key tile name that tile again: no
        # copy.
        def at(j, i, pos):
            return jnp.minimum(j, (pos[0] + (i + 1) * tq + tk - 1) // tk - 1)

        steps, pairs = Lk // tk, Hkv * G * S * Lk
    else:
        # A query tile's window starts ``i * tq`` keys in: tiles behind it are
        # not in the grid.
        def at(j, i, pos):
            return i * (tq // tk) + j

        steps = (window + tq) // tk
        pairs = 2 * Hkv * G * S * window

    if k.ndim == 5:
        # The kernel sees the same ``[1, tk, D]`` tile: the stack's two
        # leading axes are squeezed away at ``(layer, 0)``.
        scalars = jnp.stack([pos0, layer]).astype(jnp.int32)
        kv_block = lambda d: pl.BlockSpec(  # noqa: E731
            (None, None, 1, tk, d),
            lambda h, i, j, pos: (pos[1], 0, h, at(j, i, pos), 0))
    else:
        scalars = pos0.reshape(1).astype(jnp.int32)
        kv_block = lambda d: pl.BlockSpec(  # noqa: E731
            (1, tk, d), lambda h, i, j, pos: (h, at(j, i, pos), 0))
    q_block = lambda d: pl.BlockSpec(  # noqa: E731
        (1, G, tq, d), lambda h, i, j, pos: (h, 0, i, 0))
    out = pl.pallas_call(
        functools.partial(_attention_kernel, tq=tq, tk=tk, bk=bk,
                          groups=n * G, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Hkv, S // tq, steps),
            in_specs=[q_block(D), kv_block(D), kv_block(Dv)],
            out_specs=q_block(Dv),
            scratch_shapes=[
                pltpu.VMEM((n * G * tq, _LANES), jnp.float32),
                pltpu.VMEM((n * G * tq, _LANES), jnp.float32),
                pltpu.VMEM((n * G * tq, Dv), jnp.float32),
            ] + ([pltpu.VMEM((n * G * tq, D), q.dtype)] if n > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((Hkv, G, S, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=(D + Dv) * pairs,              # the causal half of 2 (D + Dv)
            bytes_accessed=2 * (q.size + Hkv * G * S * Dv + Hkv * Lk * (D + Dv)),
            transcendentals=pairs // 2,
        ),
        name="causal_gqa_attention" if window is None
        else "window_gqa_attention",
        interpret=interpret,
    )(scalars, q, k, v)
    if n > 1:
        out = out.reshape(Hkv, G, S, n, Dv // n).transpose(
            0, 3, 1, 2, 4).reshape(Hkv * n, G, S, Dv // n)
    return out


@part("mixer")
def causal_attention(
    q: jax.Array,          # [Hkv, G, S, D]  rotated and SCALED queries
    k: jax.Array,          # [Hkv, Lk, D]    the document's keys so far, rotated
    v: jax.Array,          # [Hkv, Lk, Dv]   and its values (Dv = D but under
                           #                 latent attention's wider keys)
    pos0: jax.Array,       # int32 scalar: position of the segment's first token
    layer: Optional[jax.Array] = None,   # int32 scalar: see below
    *,
    pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """softmax over the keys ``0 .. pos0 + t`` of ``q_t . k`` (the softmax
    scale is the caller's, folded into q before it is rounded), times ``v`` →
    ``[Hkv, G, S, Dv]``. Keys at and after ``pos0 + S`` are never read.

    ``k`` and ``v`` may be the layers' STACK of caches ``[layers, 1, Hkv, Lk,
    D]`` with ``layer`` the one to attend (the rank says which): the kernel
    then reads that layer's tiles out of the stack in place. Heads of half a
    lane tile come two a cache row, ``[.., Hkv / 2, Lk, 2 D]``
    (:func:`cache_rows`; ``q`` stays ``[Hkv, G, S, D]``)."""
    Hkv, _, S, D = q.shape
    n = k.shape[-1] // D                    # key-value heads a cache row
    if pallas is None:
        pallas = jax.default_backend() == "tpu"
    # Heads of half a lane tile run the kernel only as PAIRS: a cache row of
    # one such head is half a lane tile.
    if pallas and n == heads_a_row(Hkv, D) and k.shape[-1] % _LANES == 0 and (
            pallas_supported(S, k.shape[-2], D, q.dtype, v.shape[-1] // n)):
        from agent_tpu.kernels.flash_attention import resolve_interpret

        return _attention_call(q, k, v, pos0, layer,
                               interpret=resolve_interpret(interpret))
    if k.ndim == 5:
        k, v = k[layer, 0], v[layer, 0]
    if n > 1:
        k, v = _heads_apart(k, n), _heads_apart(v, n)
    return _attention_jnp(q, k, v, pos0).astype(q.dtype)


@part("mixer")
def window_attention(
    q: jax.Array,          # [Hkv, G, S, D]       rotated and SCALED queries
    k: jax.Array,          # [Hkv, window + S, D] the window keys before the
    v: jax.Array,          #   segment, then the segment's own; and the values
    pos0: jax.Array,       # int32 scalar: position of the segment's first token
    *,
    window: int,
    pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """softmax over the keys ``max(0, t - window + 1) .. t`` of ``q_t . k``,
    times ``v`` → ``[Hkv, G, S, D]``. Key ``j`` of ``k`` is the document's
    key ``pos0 - window + j``: where that is negative (a document's first
    segment) whatever the array holds there is never attended."""
    S, D = q.shape[2:]
    before = jnp.maximum(window - pos0, 0).astype(jnp.int32)
    if pallas is None:
        pallas = jax.default_backend() == "tpu"
    if pallas and window_supported(S, window, D, q.dtype):
        from agent_tpu.kernels.flash_attention import resolve_interpret

        return _attention_call(q, k, v, before, window=window,
                               interpret=resolve_interpret(interpret))
    return _window_jnp(q, k, v, before, window).astype(q.dtype)
