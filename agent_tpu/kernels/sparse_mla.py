"""Latent attention under a learned top-k key selection: the two kernels of
the ``sparse_mla`` mixer of the decoder language-model family
(``models/decoder_lm.py``), for one document's segment against the document's
cache.

The indexer scores every causal pair with a small many-headed dot product,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])            s <= t

and query ``t`` attends the ``topk`` keys of largest ``I[t, s]`` (all of them
while it has no more than ``topk``). :func:`index_select` never holds the
scores in HBM: a tile of queries keeps its whole score row in VMEM as
order-preserving integers, finds the ``topk``-th largest by 32 counting
passes (a bisection over the bit pattern: exact, no sort), and writes the
selection as an int8 mask, key tile by key tile. Keys that tie with the
``topk``-th are all kept (the published rule keeps the lower index; with
scores that are sums of 64 float32 products a tie is an accident).

:func:`masked_attention` is a streaming softmax over that mask with one
shared rotary key and per-head expanded keys and values (``k = [c W_UK; kR]``,
``v = c W_UV``: the expansion is the caller's, a matmul). Its key loop has
two levels: a grid step fetches a tile of 1,024 keys (the mask's layout) and
turns scores into weights a sub-block of 256 at a time, with the row
statistics lane-replicated and the next sub-block's score matmuls issued
before this one's softmax, so the MXU is not kept waiting by the VPU: the
kernel runs within 2 % of its own three matmuls alone (PERF.md section 5:
the take-apart table). Float32 scores, statistics and accumulator; the
weights are rounded to bf16 before the value matmul. Of the three forms
the layer can take (gathered and absorbed, dense with expanded keys, dense
and absorbed) this file ships the second: a TPU has no gather that feeds the
MXU 2,048 scattered rows a query, and the absorbed dense form costs 3.4 x the
FLOPs of the expanded one (PERF.md section 5; the benchmark's need counts the
cheapest).

Both kernels read ``pos0``, the position of the segment's first token, as a
prefetched scalar and bound their key loops by it: a document's first
segment does not pay for keys that are not there yet, and the programs stay
fixed-shape. Which path runs is read from the shapes
(:func:`index_supported`, :func:`attention_supported`): the kernels on a TPU at lane-wide heads, the same
arithmetic in ``jax.numpy`` elsewhere (dense scores, ``lax.top_k``). No
option, environment variable or ``model_config`` key chooses.

The mask's layout is ``[Lk / KEY_TILE, S, KEY_TILE]``: a key tile is a
leading index, which a kernel's loop may index dynamically.
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
# Keys a tile, of both kernels (the mask's last axis, and what a grid step of
# the attention fetches): 3.1 MiB of keys, values and mask a step. The
# attention works through it in sub-blocks (ATTN_KEY_BLOCK below).
KEY_TILE = 1024
# Queries a grid step of the indexer: the step's score row, [128, Lk] int32,
# is 16 MB at 32,768 keys, beside the index keys (8 MB) in VMEM.
INDEX_QUERY_TILE = 128
# Index heads a matmul: [8 x 128, 128] x [128, 1024].
INDEX_HEAD_GROUP = 8
# Queries a segment comes in (the predicates' unit), and heads a grid step
# of the attention.
ATTN_QUERY_TILE = 512
ATTN_HEAD_GROUP = 4
# Queries a grid step of the attention where the segment has that many: a
# loaded 128 x 128 block of keys then meets 1,024 query rows, not 512. The
# kernel alone at 128 heads, 4,096 queries, 32,768 keys (PERF.md section 5),
# last segment / first: 69.9 / 9.70 ms at 512, 68.3 / 8.38 at 1,024; its
# three matmuls alone, no softmax: 69.2 / 9.54 and 67.4 / 8.28. Eight heads
# a step read 67.2 at 1,024 and compile for 18 s, not 9.
ATTN_QUERY_STEP = 1024
# Keys a sub-block of the attention's inner loop: a [queries, 256] float32
# score tile is produced, masked, exponentiated, summed and fed to the value
# matmul while the next one's score matmuls are already issued. Measured as
# above at 512 queries a step: whole tiles of 1,024 with [queries, 1]
# statistics (what shipped before) 86.8 ms; the same with lane-replicated
# statistics 77.5, and lane-wise sums 75.0; sub-blocks of 512 / 256 / 128
# without the look-ahead 71.4 / 72.1 / 83.5 (the accumulator's rescale runs
# once a sub-block), with it 72.3 / 70.0 / 75.2.
ATTN_KEY_BLOCK = 256
# Heads a grid step of the expansion.
EXPAND_HEAD_GROUP = 8
# Keys the indexer kernel holds in VMEM at once (index keys + score row).
MAX_KERNEL_KEYS = 32768
_VMEM_LIMIT = 100 * 1024 * 1024
_INT_MIN = -(2 ** 31)
_MASKED = -1e30


def key_tile(cache_len: int) -> int:
    """Keys a tile for a cache of ``cache_len``: the module's tile, or the
    whole cache when that is shorter."""
    return min(KEY_TILE, int(cache_len))


def _cache_fits(cache_len: int, dtype) -> bool:
    """bf16 operands and a cache of whole key tiles that the indexer can
    hold: the indexer and the attention run on one cache and one mask, or
    neither does (the expansion has its own rule, :func:`expand_supported`)."""
    return bool(jnp.dtype(dtype) == jnp.bfloat16
                and cache_len % KEY_TILE == 0 and cache_len <= MAX_KERNEL_KEYS)


def index_supported(seq_len: int, cache_len: int, index_heads: int,
                    index_dim: int, dtype) -> bool:
    """Shapes the indexer kernel takes on the chip: bf16 operands, lane-wide
    heads in whole groups, whole tiles, and a cache whose index keys and
    score row fit VMEM."""
    return bool(_cache_fits(cache_len, dtype)
                and seq_len % ATTN_QUERY_TILE == 0 and index_dim == _LANES
                and index_heads % INDEX_HEAD_GROUP == 0)


def attention_supported(seq_len: int, cache_len: int, n_heads: int,
                        nope_dim: int, v_dim: int, dtype) -> bool:
    """Shapes the attention kernel takes on the chip (the cache's bound is
    the indexer's: the two run on one mask)."""
    return bool(_cache_fits(cache_len, dtype)
                and seq_len % ATTN_QUERY_TILE == 0
                and nope_dim == _LANES and v_dim == _LANES
                and n_heads % ATTN_HEAD_GROUP == 0)


def _on_chip(pallas: Optional[bool]) -> bool:
    return jax.default_backend() == "tpu" if pallas is None else bool(pallas)


# ---- the indexer and the selection ----------------------------------------

def _index_select_jnp(qi, w, ki, pos0, topk: int):
    """Dense scores, float32: [S, Lk], a block of query rows at a time (every
    head of a block in one product: a loop over heads runs serially on a CPU)."""
    S, hi, d = qi.shape
    Lk = ki.shape[0]
    f32 = jnp.float32
    kf = ki.astype(f32)
    rows = next(r for r in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1) if S % r == 0)

    def block(args):
        q, wb = args                                  # [rows, Hi, D], [rows, Hi]
        return jnp.einsum("tj,tjs->ts", wb, jnp.maximum(
            jnp.einsum("tjd,sd->tjs", q, kf), 0.0))

    scores = jax.lax.map(block, (
        qi.astype(f32).reshape(S // rows, rows, hi, d),
        w.astype(f32).reshape(S // rows, rows, hi))).reshape(S, Lk)
    t = pos0 + jnp.arange(S)
    causal = jnp.arange(Lk)[None, :] <= t[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, min(int(topk), Lk))[0][:, -1:]
    return (scores >= kth) & causal


def _index_kernel(pos_ref, q_ref, w_ref, k_ref, mask_ref, sc_ref, *,
                  tq: int, tk: int, hc: int, n_heads: int, n_chunks: int,
                  topk: int):
    """One tile of ``tq`` queries against every key the tile may see."""
    f32, i32 = jnp.float32, jnp.int32
    i = pl.program_id(0)
    pos0 = pos_ref[0]
    n_k = jnp.minimum((pos0 + (i + 1) * tq + tk - 1) // tk, n_chunks)
    d = q_ref.shape[-1]
    row_t = pos0 + i * tq + jax.lax.broadcasted_iota(i32, (tq, tk), 0)
    col = jax.lax.broadcasted_iota(i32, (tq, tk), 1)
    nt = (((1,), (1,)), ((), ()))           # [m, k] x [n, k]

    def score(c, _):
        kc = k_ref[pl.ds(pl.multiple_of(c * tk, tk), tk), :]     # [tk, D]

        def heads(g, acc):
            at = pl.multiple_of(g * hc, hc)
            qh = q_ref[pl.ds(at, hc)].reshape(hc * tq, d)
            s = jax.lax.dot_general(qh, kc, nt, preferred_element_type=f32)
            s = jnp.maximum(s, 0.0) * w_ref[pl.ds(at, hc)].reshape(hc * tq, 1)
            return acc + s.reshape(hc, tq, tk).sum(axis=0)

        acc = jax.lax.fori_loop(0, n_heads // hc, heads,
                                jnp.zeros((tq, tk), f32))
        # Float32 → an int32 of the same order (negative floats reversed).
        bits = pltpu.bitcast(acc, i32)
        key = bits ^ ((bits >> 31) & 0x7FFFFFFF)
        sc_ref[c] = jnp.where(col + c * tk <= row_t, key, _INT_MIN)
        return 0

    jax.lax.fori_loop(0, n_k, score, 0)

    def count_at_least(cand):
        def chunk(c, cnt):
            return cnt + jnp.sum(jnp.where(sc_ref[c] >= cand, 1.0, 0.0),
                                 axis=1, keepdims=True)

        return jax.lax.fori_loop(0, n_k, chunk, jnp.zeros((tq, 1), f32))

    # The largest threshold that still keeps ``topk`` keys, bit by bit from
    # the sign; a row with fewer keys than that keeps the lowest threshold.
    k_f = f32(topk)
    lo = jnp.where(count_at_least(jnp.zeros((tq, 1), i32)) >= k_f,
                   0, _INT_MIN).astype(i32)

    def bit(b, lo):
        cand = lo + jnp.left_shift(i32(1), i32(30) - b)
        return jnp.where(count_at_least(cand) >= k_f, cand, lo)

    lo = jax.lax.fori_loop(0, 31, bit, lo)
    thr = jnp.maximum(lo, _INT_MIN + 1)       # never a masked entry

    def write(c, _):
        keep = (sc_ref[c] >= thr) & (c < n_k)
        mask_ref[c] = jnp.where(keep, 1, 0).astype(jnp.int8)
        return 0

    jax.lax.fori_loop(0, n_chunks, write, 0)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _index_select_call(qi, w, ki, pos0, *, topk: int, interpret: bool):
    S, hi, d = qi.shape
    Lk = ki.shape[0]
    tk, tq, hc = key_tile(Lk), min(INDEX_QUERY_TILE, S), INDEX_HEAD_GROUP
    n_chunks = Lk // tk
    qh = qi.transpose(1, 0, 2)                               # [Hi, S, D]
    wh = w.astype(jnp.float32).T[:, :, None]                 # [Hi, S, 1]
    kernel = functools.partial(_index_kernel, tq=tq, tk=tk, hc=hc,
                               n_heads=hi, n_chunks=n_chunks, topk=topk)
    pairs = S * Lk
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S // tq,),
            in_specs=[
                pl.BlockSpec((hi, tq, d), lambda i, pos: (0, i, 0)),
                pl.BlockSpec((hi, tq, 1), lambda i, pos: (0, i, 0)),
                pl.BlockSpec((Lk, d), lambda i, pos: (0, 0)),
            ],
            out_specs=pl.BlockSpec((n_chunks, tq, tk),
                                   lambda i, pos: (0, i, 0)),
            scratch_shapes=[pltpu.VMEM((n_chunks, tq, tk), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_chunks, S, tk), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * hi * d * pairs,
            bytes_accessed=2 * (qi.size + ki.size) + 4 * w.size + pairs,
            transcendentals=0,
        ),
        name="sparse_index_select",
        interpret=interpret,
    )(pos0.reshape(1).astype(jnp.int32), qh, wh, ki)


@part("mixer")
def index_select(
    qi: jax.Array,         # [S, Hi, Di]  the segment's index queries, rotated
    w: jax.Array,          # [S, Hi]      their head weights, float32
    ki: jax.Array,         # [Lk, Di]     the document's index keys so far
    pos0: jax.Array,       # int32 scalar: position of the segment's first token
    topk: int,
    *,
    pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The selection as a mask ``[Lk / tile, S, tile]`` int8: 1 where query
    ``t`` of the segment attends key ``s`` (``s <= pos0 + t`` and ``I[t, s]``
    among the query's ``topk`` largest)."""
    S, hi, d = qi.shape
    Lk = ki.shape[0]
    tk = key_tile(Lk)
    if _on_chip(pallas) and index_supported(S, Lk, hi, d, qi.dtype):
        from agent_tpu.kernels.flash_attention import resolve_interpret

        return _index_select_call(qi, w, ki, pos0, topk=int(topk),
                                  interpret=resolve_interpret(interpret))
    keep = _index_select_jnp(qi, w, ki, pos0, int(topk))
    return keep.reshape(S, Lk // tk, tk).transpose(1, 0, 2).astype(jnp.int8)


# ---- per-head keys and values from the latents ----------------------------

def _expand_kernel(n_ref, c_ref, w_ref, k_ref, v_ref, *, tk: int, hb: int,
                   dn: int):
    @pl.when(pl.program_id(1) * tk < n_ref[0])
    def _():
        c = c_ref[...]
        for a in range(hb):
            e = jax.lax.dot_general(c, w_ref[a], (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            k_ref[a] = e[:, :dn].astype(k_ref.dtype)
            v_ref[a] = e[:, dn:].astype(v_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dn", "interpret"))
def _expand_call(latents, w, n_keys, *, dn: int, interpret: bool):
    Lk, kvr = latents.shape
    H, _, width = w.shape
    dv = width - dn
    tk, hb = key_tile(Lk), EXPAND_HEAD_GROUP

    # Tiles past the last key name the last tile again: no copy, no write.
    def at(i, n):
        return jnp.minimum(i, jnp.maximum((n[0] + tk - 1) // tk - 1, 0))

    out = lambda width: pl.BlockSpec(  # noqa: E731
        (hb, tk, width), lambda h, i, n: (h, at(i, n), 0))
    return pl.pallas_call(
        functools.partial(_expand_kernel, tk=tk, hb=hb, dn=dn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // hb, Lk // tk),
            in_specs=[
                pl.BlockSpec((tk, kvr), lambda h, i, n: (at(i, n), 0)),
                pl.BlockSpec((hb, kvr, width), lambda h, i, n: (h, 0, 0)),
            ],
            out_specs=[out(dn), out(dv)],
        ),
        out_shape=[jax.ShapeDtypeStruct((H, Lk, dn), latents.dtype),
                   jax.ShapeDtypeStruct((H, Lk, dv), latents.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        name="sparse_mla_expand",
        interpret=interpret,
    )(n_keys.reshape(1).astype(jnp.int32), latents, w)


def join_rotary_key(w: jax.Array, dn: int, dr: int) -> jax.Array:
    """A head's ``W_UK | W_UV`` ``[H, kvr, dn + Dv]`` → ``[H, kvr + dr, dn +
    dr + Dv]``: the weight under which the cache's WHOLE vector ``[c | kR]``
    expands to the joined key ``[c W_UK | kR]`` and the value ``c W_UV``.
    The rotary key passes through an identity block (exact: one product by
    1, float32 accumulation), so the one key all heads share is broadcast to
    every head inside the expansion's matmul and a head's key is ONE
    lane-wide row where ``dn + dr`` is 128, which plain causal attention
    takes; the price is ``(kvr + dr)(dn + dr + Dv)`` products a head where
    ``kvr (dn + Dv)`` are needed."""
    H, kvr, width = w.shape
    top = jnp.concatenate([w[..., :dn], jnp.zeros((H, kvr, dr), w.dtype),
                           w[..., dn:]], axis=-1)
    carry = jnp.concatenate([jnp.zeros((dr, dn), w.dtype),
                             jnp.eye(dr, dtype=w.dtype),
                             jnp.zeros((dr, width - dn), w.dtype)], axis=-1)
    return jnp.concatenate([top, jnp.broadcast_to(carry, (H, *carry.shape))],
                           axis=1)


def expand_supported(cache_len: int, latent_dim: int, n_heads: int, dn: int,
                     dv: int, dtype) -> bool:
    """Shapes the expansion kernel takes on the chip: bf16 operands, a cache
    of whole key tiles (of ANY length: a step holds one tile, so the
    indexer's ``MAX_KERNEL_KEYS`` is not its bound), lane-wide keys and
    values, whole head groups, latents in whole sublane groups."""
    return bool(jnp.dtype(dtype) == jnp.bfloat16 and cache_len % KEY_TILE == 0
                and dn == _LANES and dv == _LANES and latent_dim % 64 == 0
                and n_heads % EXPAND_HEAD_GROUP == 0)


@part("project")
def expand_latents(
    latents: jax.Array,    # [Lk, kvr]  the document's normed latents
    w: jax.Array,          # [H, kvr, Dn + Dv]  a head's W_UK | W_UV
    n_keys: jax.Array,     # int32 scalar: keys the segment can see
    dn: int,
    *,
    pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
):
    """Per-head keys ``[H, Lk, Dn]`` and values ``[H, Lk, Dv]`` of the first
    ``n_keys`` latents, head-major as the attention reads them (as one plain
    matmul XLA relaid the result out three times over: PERF.md section 5). On
    the chip the rest is NOT written (and never read); elsewhere every key
    is expanded. With :func:`join_rotary_key`'s weight the latents are the
    cache's whole vectors and the keys come out joined with the rotary key."""
    Lk, kvr = latents.shape
    H, _, width = w.shape
    if _on_chip(pallas) and expand_supported(Lk, kvr, H, dn, width - dn,
                                             latents.dtype):
        from agent_tpu.kernels.flash_attention import resolve_interpret

        return _expand_call(latents, w, n_keys, dn=dn,
                            interpret=resolve_interpret(interpret))
    e = jnp.einsum("sc,hcd->hsd", latents, w)
    return e[..., :dn], e[..., dn:]


# ---- attention over the selected keys -------------------------------------

def _masked_attention_jnp(q_nope, q_rope, k_nope, k_rope, v, mask):
    """One head at a time, float32; mask [S, Lk] bool."""
    f32 = jnp.float32
    kr = k_rope.astype(f32)

    def head(args):
        qn, qr, kn, vh = (a.astype(f32) for a in args)
        s = qn @ kn.T + qr @ kr.T
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return p @ vh

    return jax.lax.map(head, (q_nope, q_rope, k_nope, v))    # [H, S, Dv]


def _attention_kernel(pos_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                      mask_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      tq: int, tk: int, hb: int, bk: int):
    """One (head group, query tile, key tile) step of the streaming softmax:
    the fetched key tile in sub-blocks of ``bk`` keys, a head after another.
    ``m`` and ``l`` are kept ``[tq, 128]``: ``m`` the row's maximum in every
    lane, ``l`` the row's sum spread over the lanes (summed at the end)."""
    f32 = jnp.float32
    i, j = pl.program_id(1), pl.program_id(2)
    n_kv = (pos_ref[0] + (i + 1) * tq + tk - 1) // tk
    nt = (((1,), (1,)), ((), ()))           # [m, k] x [n, k]
    nn = (((1,), (0,)), ((), ()))
    groups = bk // _LANES

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def block(c):
        return slice(c * bk, (c + 1) * bk)

    def scores(a, c):
        return jax.lax.dot_general(
            qn_ref[a], kn_ref[a, block(c), :], nt, preferred_element_type=f32
        ) + jax.lax.dot_general(
            qr_ref[a], kr_ref[block(c), :], nt, preferred_element_type=f32)

    @pl.when(j < n_kv)
    def _():
        steps = [(a, c) for a in range(hb) for c in range(tk // bk)]
        keep = {}
        ahead = scores(*steps[0])
        for n, (a, c) in enumerate(steps):
            s = ahead
            # The next sub-block's score matmuls are issued before this
            # one's softmax: the MXU has work while the VPU does its part.
            if n + 1 < len(steps):
                ahead = scores(*steps[n + 1])
            if c not in keep:                 # widened once for the heads
                keep[c] = mask_ref[0, :, block(c)].astype(jnp.int32) != 0
            # A row whose keys so far are all masked holds exp(0) = 1 a key
            # until its first kept key arrives; that key's alpha is 0.
            s = jnp.where(keep[c], s, _MASKED)
            m_prev = m_ref[a]                                   # [tq, 128]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - jnp.concatenate([m_new] * groups, axis=1))
            alpha = jnp.exp(m_prev - m_new)
            lanes = p[:, :_LANES]
            for g in range(1, groups):
                lanes = lanes + p[:, g * _LANES:(g + 1) * _LANES]
            l_ref[a] = alpha * l_ref[a] + lanes
            acc_ref[a] = alpha * acc_ref[a] + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[a, block(c), :], nn,
                preferred_element_type=f32)
            m_ref[a] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...].sum(
            axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _masked_attention_call(q_nope, q_rope, k_nope, k_rope, v, mask, pos0, *,
                           interpret: bool):
    H, S, dn = q_nope.shape
    Lk, dr = k_rope.shape
    dv = v.shape[-1]
    tk = mask.shape[-1]
    tq = ATTN_QUERY_STEP if S % ATTN_QUERY_STEP == 0 else ATTN_QUERY_TILE
    hb, bk = ATTN_HEAD_GROUP, ATTN_KEY_BLOCK
    n_tiles = Lk // tk

    def last(i, pos):
        return (pos[0] + (i + 1) * tq + tk - 1) // tk - 1

    # Steps past a query tile's last key tile name that tile again: no copy.
    def at(j, i, pos):
        return jnp.minimum(j, last(i, pos))

    kernel = functools.partial(_attention_kernel, tq=tq, tk=tk, hb=hb, bk=bk)
    pairs = H * S * Lk
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // hb, S // tq, n_tiles),
            in_specs=[
                pl.BlockSpec((hb, tq, dn), lambda h, i, j, pos: (h, i, 0)),
                pl.BlockSpec((hb, tq, dr), lambda h, i, j, pos: (h, i, 0)),
                pl.BlockSpec((hb, tk, dn),
                             lambda h, i, j, pos: (h, at(j, i, pos), 0)),
                pl.BlockSpec((tk, dr),
                             lambda h, i, j, pos: (at(j, i, pos), 0)),
                pl.BlockSpec((hb, tk, dv),
                             lambda h, i, j, pos: (h, at(j, i, pos), 0)),
                pl.BlockSpec((1, tq, tk),
                             lambda h, i, j, pos: (at(j, i, pos), i, 0)),
            ],
            out_specs=pl.BlockSpec((hb, tq, dv),
                                   lambda h, i, j, pos: (h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((hb, tq, _LANES), jnp.float32),
                pltpu.VMEM((hb, tq, _LANES), jnp.float32),
                pltpu.VMEM((hb, tq, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((H, S, dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * (dn + dr + dv) * pairs,
            bytes_accessed=2 * (2 * q_nope.size + k_nope.size + v.size)
            + mask.size * (H // hb),
            transcendentals=pairs,
        ),
        name="sparse_mla_attention",
        interpret=interpret,
    )(pos0.reshape(1).astype(jnp.int32), q_nope, q_rope, k_nope, k_rope, v,
      mask)


@part("mixer")
def masked_attention(
    q_nope: jax.Array,     # [H, S, Dn]   per-head queries, the part no RoPE,
    q_rope: jax.Array,     # [H, S, Dr]   and their rotary part: both SCALED
    k_nope: jax.Array,     # [H, Lk, Dn]  expanded keys (valid below pos0 + S)
    k_rope: jax.Array,     # [Lk, Dr]     the one rotary key all heads share
    v: jax.Array,          # [H, Lk, Dv]  expanded values
    mask: jax.Array,       # [Lk / tile, S, tile] int8 of ``index_select``
    pos0: jax.Array,
    *,
    pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """softmax over each query's selected keys of ``q . k`` (the softmax
    scale is the caller's, folded into q before it is rounded: a multiply
    less on every score), times ``v`` → ``[H, S, Dv]``. Keys at and after
    ``pos0 + S`` are never read."""
    H, S, dn = q_nope.shape
    Lk = k_rope.shape[0]
    if _on_chip(pallas) and attention_supported(
            S, Lk, H, dn, v.shape[-1], q_nope.dtype):
        from agent_tpu.kernels.flash_attention import resolve_interpret

        return _masked_attention_call(
            q_nope, q_rope, k_nope, k_rope, v, mask, pos0,
            interpret=resolve_interpret(interpret))
    dense = mask.transpose(1, 0, 2).reshape(S, Lk) != 0
    return _masked_attention_jnp(q_nope, q_rope, k_nope, k_rope, v,
                                 dense).astype(q_nope.dtype)
