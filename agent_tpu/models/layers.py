"""Pure-JAX transformer building blocks shared by every model in the framework.

Design stance (SURVEY.md §7): models are *functions over param pytrees*, not
classes — the idiomatic JAX shape. Parameters are plain nested dicts of
``jnp.float32`` arrays; compute casts to the runtime's compute dtype (bf16 on
TPU — the MXU-native choice) and accumulates softmax/logits in f32.

Determinism: all init goes through :func:`seed_from` + ``jax.random.fold_in``,
so a model id string fully determines the weights (zero egress — no hub
downloads, reference ``ops/map_summarize.py:29-30`` pulled from HF instead).

Sharding: these functions are GSPMD-friendly — no data-dependent shapes, heads
and ffn hidden kept as separate, shardable axes. Explicit tp/sp placement is
applied by callers (op executors / the train step) via in_shardings and
``with_sharding_constraint``; the ring-attention sp path lives in
``agent_tpu.parallel.ring`` and slots in behind :func:`attention`'s interface.
"""

from __future__ import annotations

import hashlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from agent_tpu.obs.trace import part

Params = Dict[str, Any]

NEG_INF = -1e9  # additive mask value; finite so bf16 stays NaN-free


def seed_from(name: str) -> jax.Array:
    """A PRNG key fully determined by ``name`` (model id → weights)."""
    h = hashlib.sha256(name.encode("utf-8")).digest()
    return jax.random.PRNGKey(int.from_bytes(h[:4], "big"))


def _dense_init(key: jax.Array, shape: Tuple[int, ...], fan_in: int) -> jax.Array:
    scale = 1.0 / np.sqrt(max(1, fan_in))
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


def init_dense(key: jax.Array, d_in: int, d_out: int) -> Params:
    return {
        "w": _dense_init(key, (d_in, d_out), d_in),
        "b": jnp.zeros((d_out,), dtype=jnp.float32),
    }


def dense(p: Params, x: jax.Array, dtype: Any) -> jax.Array:
    from agent_tpu.models import quant

    if quant.is_quantized(p):  # int8 leaf (models.quant leaf convention)
        return quant.qdense(p, x, dtype)
    if quant.is_weight_only(p):  # W8A16 leaf: int8 table, dtype activations
        return quant.wdense(p, x, dtype)
    return jnp.dot(x.astype(dtype), p["w"].astype(dtype)) + p["b"].astype(dtype)


def init_layer_norm(d: int) -> Params:
    return {
        "scale": jnp.ones((d,), dtype=jnp.float32),
        "bias": jnp.zeros((d,), dtype=jnp.float32),
    }


@part("norm")
def layer_norm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    # Normalize in f32 regardless of compute dtype: variance in bf16 is lossy.
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def init_attention(key: jax.Array, d_model: int, n_heads: int) -> Params:
    """QKV/out projections with an explicit head axis (shardable over tp).

    Shapes: wq/wk/wv ``[d_model, n_heads, d_head]``, wo ``[n_heads, d_head,
    d_model]`` — the head axis stays a named dimension so a tp sharding rule
    can split it without reshapes.
    """
    d_head = d_model // n_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (d_model, n_heads, d_head), d_model),
        "wk": _dense_init(ks[1], (d_model, n_heads, d_head), d_model),
        "wv": _dense_init(ks[2], (d_model, n_heads, d_head), d_model),
        "wo": _dense_init(ks[3], (n_heads, d_head, d_model), d_model),
    }


@part("mixer")
def dot_product_attention(
    q: jax.Array,       # [B, H, Lq, D]
    k: jax.Array,       # [B, H, Lk, D]
    v: jax.Array,       # [B, H, Lk, D]
    mask: jax.Array,    # [B, 1|H, Lq|1, Lk] additive-mask source (1 = attend)
) -> jax.Array:
    """Masked softmax(QKᵀ)V → [B, H, Lq, D].

    Numerics/traffic contract: QKᵀ accumulates in f32 (MXU native), but the
    materialized [B, H, Lq, Lk] score array is stored in the **compute
    dtype** (bf16 on TPU), which halves the layer's dominant HBM traffic at
    seq 512 / BERT-base shapes (its speed against f32 scores: not measured
    on the present tree). Softmax statistics (exp, sum, divide) still run in
    f32; with f32 inputs the whole path is f32 and matches the old
    ``jax.nn.softmax`` form exactly.
    """
    d = q.shape[-1]
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    scores = (scores / np.sqrt(d)).astype(q.dtype)
    scores = jnp.where(mask > 0, scores, jnp.asarray(NEG_INF, q.dtype))
    m = scores.max(axis=-1, keepdims=True)
    p = jnp.exp((scores - m).astype(jnp.float32))
    z = p.sum(axis=-1, keepdims=True)
    probs = (p / z).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@part("project")
def _proj_in(leaf: Any, x: jax.Array, dtype: Any) -> jax.Array:
    """x [B, L, d] @ leaf [d, H, E] → [B, H, L, E]; int8 (W8A8) and W8A16
    paths for quantized leaves (``models.quant`` leaf conventions)."""
    from agent_tpu.models import quant

    if quant.is_quantized(leaf):
        return quant.qproj_in(leaf, x, dtype)
    if quant.is_weight_only(leaf):
        return quant.wproj_in(leaf, x, dtype)
    return jnp.einsum("bld,dhe->bhle", x.astype(dtype), leaf.astype(dtype))


@part("project")
def _proj_out(leaf: Any, x: jax.Array, dtype: Any) -> jax.Array:
    """x [B, H, L, E] @ leaf [H, E, d] → [B, L, d]; int8 (W8A8) and W8A16
    paths for quantized leaves."""
    from agent_tpu.models import quant

    if quant.is_quantized(leaf):
        return quant.qproj_out(leaf, x, dtype)
    if quant.is_weight_only(leaf):
        return quant.wproj_out(leaf, x, dtype)
    return jnp.einsum("bhle,hed->bld", x, leaf.astype(dtype))


def whole_row_entry(attn_fn, batch: int, lq: int, lk: int, n_heads: int,
                    d_head: int, mask: jax.Array, dtype: Any,
                    segments: bool = False):
    """``attn_fn``'s lane-dense entry (``attn_fn.whole_row``, see
    ``kernels.flash_attention.WholeRowAttention``) if it declares one AND its
    shape-and-mask predicate takes this call (``segments``: under segment
    ids, not under ``mask``); else None, and the caller keeps the
    [B, H, L, D] path."""
    entry = getattr(attn_fn, "whole_row", None)
    if entry is not None and entry.selects(
        batch, lq, lk, n_heads, d_head, mask, dtype, segments=segments
    ):
        return entry
    return None


def fuse_qkv(p: Params) -> Params:
    """An attention subtree with its ``wq``, ``wk``, ``wv`` ([d, H, E] each)
    as ONE leaf ``wqkv`` [d, 3 * H * E]: columns ``[Q | K | V]``, head-major
    inside each as the three were, same dtype, same bytes. A SERVING layout,
    made once a model where its weights are built
    (``ops/_model_common.py: maybe_fuse_qkv_params``); ``init_attention``,
    checkpoints and the ``tp`` specs keep the three."""
    d_model = p["wq"].shape[0]
    fused = {k: w for k, w in p.items() if k not in ("wq", "wk", "wv")}
    fused["wqkv"] = jnp.concatenate(
        [p[k].reshape(d_model, -1) for k in ("wq", "wk", "wv")], axis=1)
    return fused


@part("project")
def qkv_leaves(p: Params) -> Tuple[Any, Any, Any]:
    """``(wq, wk, wv)`` of an attention subtree in either layout: its own
    three leaves, or the column blocks of ``wqkv`` as [d, H, E] views (H and
    E are ``wo``'s). Slices of the WEIGHT: where a fused subtree meets a call
    that wants the three (a cache, cross-attention, a length the whole-row
    predicate leaves dense)."""
    if "wqkv" not in p:
        return p["wq"], p["wk"], p["wv"]
    H, E, _ = p["wo"].shape
    w = p["wqkv"]
    return tuple(
        w[:, i * H * E:(i + 1) * H * E].reshape(w.shape[0], H, E)
        for i in range(3))


def _note_qkv(form: str) -> None:
    from agent_tpu.obs.trace import record_attention_qkv

    record_attention_qkv(form)


def _attention_lane_dense(p: Params, x_q, x_kv, mask, dtype, attn_fn,
                          segment_ids=None):
    """:func:`attention` without a cache on [B, L, H*D] operands — the
    projections' own layout, H*D in the lanes — where ``attn_fn`` takes them;
    None where it does not (or a leaf is quantized: those projections write
    [B, H, L, E]). With ``segment_ids`` the entry is asked for its segment
    form and reads the ids, not the mask.

    A fused subtree (``"wqkv" in p``, :func:`fuse_qkv`) in self-attention
    runs Q, K and V as ONE matmul, and the entry takes its [B, L, 3*H*D]
    result whole, as column blocks: the block's activations are read once
    and nothing copies the result apart."""
    from agent_tpu.models import quant

    if any(quant.is_quantized(w) or quant.is_weight_only(w)
           for w in p.values()):
        return None
    wo = p["wo"]
    H, E, d_model = wo.shape
    B, Lq, _ = x_q.shape
    entry = whole_row_entry(attn_fn, B, Lq, x_kv.shape[1], H, E, mask, dtype,
                            segments=segment_ids is not None)
    if entry is None:
        return None

    @part("project")
    def proj(w, x):
        # "bld,dhe->blhe" as the 2-D matmul it is: XLA lays a 4-D result out
        # with L in the lanes and copies it back; a [B*L, H*E] one stays put.
        y = jnp.dot(x.astype(dtype).reshape(-1, d_model),
                    w.astype(dtype).reshape(d_model, -1))
        return y.reshape(B, x.shape[1], -1)

    if "wqkv" in p and x_q is x_kv:
        _note_qkv("fused")
        operands = (proj(p["wqkv"], x_q), None, None)
    else:
        _note_qkv("separate")
        wq, wk, wv = qkv_leaves(p)
        operands = (proj(wq, x_q), proj(wk, x_kv), proj(wv, x_kv))
    with part("mixer"):
        out = entry(*operands, mask, n_heads=H, segment_ids=segment_ids)
    with part("project"):
        y = jnp.dot(out.reshape(-1, H * E),
                    wo.astype(dtype).reshape(H * E, d_model))
        return y.reshape(B, Lq, d_model)


def attention(
    p: Params,
    x_q: jax.Array,                 # [B, Lq, d_model]
    x_kv: jax.Array,                # [B, Lk, d_model] (== x_q for self-attn)
    mask: jax.Array,                # [B, 1, Lq|1, Lk] (1 = attend)
    dtype: Any,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
    attn_fn=dot_product_attention,
    segment_ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Multi-head attention; optional KV cache for autoregressive decode.

    ``segment_ids`` ([B, L] int32, 0 = pad; self-attention without a cache)
    says that ``mask`` is the block-diagonal [B, 1, L, L] mask of those ids
    (:func:`segment_mask_to_attn`): a fused path that takes the ids reads
    them and not the mask; every other path reads the mask, so both give
    the same answers.

    With ``cache`` (arrays ``k``/``v`` of shape [B, H, Lmax, D]) and a scalar
    ``cache_index``, the new K/V rows are written at ``cache_index`` via
    ``dynamic_update_slice`` and attention runs over the full cache — the
    static-shape decode pattern that keeps ``lax.scan`` from retracing
    (SURVEY.md §7 "hard parts": decode doesn't retrace per step).

    A **vector** ``cache_index`` ([B] int32) writes each row's K/V at its own
    position — the continuous-batching decode case (ISSUE 15), where slots in
    a running batch sit at different decode depths. The written values are
    identical to the scalar path's; only the addressing generalizes.

    With ``block_table`` ([B, MAXB] int32, ISSUE 16) the cache is a **paged
    pool**: ``k``/``v`` are ``[NB, H, BS, D]`` fixed-size blocks shared by
    every row, and row ``b``'s logical position ``p`` lives in pool block
    ``block_table[b, p // BS]`` at offset ``p % BS``. Pool block 0 is the
    trash block: unallocated/released table entries point there, so a frozen
    row's steady rewrite at its frozen position can never corrupt a block
    that was reallocated to a live request. The read view gathers the row's
    blocks and slices to the mask's key length, so the attention shapes —
    and therefore the reduction trees and the bits — match the dense path
    exactly; positions past a row's write point are masked, and
    ``exp(NEG_INF - m)`` is exactly 0.0 in f32, so trash/garbage content
    never contributes. Requires a vector ``cache_index``.

    ``attn_fn`` is the inner attention kernel — the sp ring path
    (``agent_tpu.parallel.ring.ring_attention``) substitutes here.
    """
    if cache is None:
        lane_dense = _attention_lane_dense(p, x_q, x_kv, mask, dtype, attn_fn,
                                           segment_ids)
        if lane_dense is not None:
            return lane_dense, None
    _note_qkv("separate")
    wq, wk, wv = qkv_leaves(p)
    q = _proj_in(wq, x_q, dtype)
    k = _proj_in(wk, x_kv, dtype)
    v = _proj_in(wv, x_kv, dtype)

    if cache is not None:
        assert cache_index is not None
        if block_table is not None:
            if getattr(cache_index, "ndim", 0) != 1:
                raise ValueError(
                    "paged KV (block_table) requires a per-row vector "
                    "cache_index"
                )
            bsz = block_table.shape[0]
            maxb = block_table.shape[1]
            bs = cache["k"].shape[2]                  # pool block size
            lk = mask.shape[-1]

            def view(pool):
                x = pool[block_table]                 # [B, MAXB, H, BS, D]
                x = x.transpose(0, 2, 1, 3, 4)
                x = x.reshape(bsz, pool.shape[1], maxb * bs, pool.shape[3])
                return x[:, :, :lk]                   # dense-shape view

            with part("around"):
                ji = cache_index // bs                # [B] logical block
                off = cache_index % bs                # [B] offset in block
                # Rows whose position ran past table coverage (frozen at
                # the engine's max) write to the trash block, not a clamped
                # real one.
                blk = jnp.where(
                    ji < maxb,
                    jnp.take_along_axis(
                        block_table, jnp.minimum(ji, maxb - 1)[:, None],
                        axis=1
                    )[:, 0],
                    0,
                )
                # Scatter one K/V row per batch row: pool[blk[b], :,
                # off[b]] = new_kv[b]. Duplicate (blk, off) pairs only ever
                # collide at the trash block (allocated blocks are
                # row-exclusive) — harmless.
                pk = cache["k"].astype(dtype).at[blk, :, off].set(k[:, :, 0])
                pv = cache["v"].astype(dtype).at[blk, :, off].set(v[:, :, 0])
                k, v = view(pk), view(pv)
            with part("mixer"):
                out = attn_fn(q, k, v, mask)
            y = _proj_out(p["wo"], out, dtype)
            return y, {"k": pk, "v": pv}
        if getattr(cache_index, "ndim", 0) == 1:
            # Per-row positions: one decode step (Lk == 1) written to each
            # row's own cache slot. Formulated as a one-hot select, NOT a
            # gather/scatter — XLA lowers scatters to element loops on some
            # backends, while the dense where is a single vectorized pass
            # over the cache. Which form the chip's decode step runs faster
            # in: not measured on the present tree (PERF.md §7, row 1: the
            # `/v1/infer` kind that was built and not shipped).
            with part("around"):
                sel = (
                    jnp.arange(cache["k"].shape[2])[None, :]
                    == cache_index[:, None]
                )[:, None, :, None]                   # [B, 1, Lmax, 1]
                k = jnp.where(sel, k, cache["k"].astype(dtype))
                v = jnp.where(sel, v, cache["v"].astype(dtype))
        else:
            with part("around"):
                zero = jnp.zeros((), dtype=jnp.int32)
                k = jax.lax.dynamic_update_slice(
                    cache["k"].astype(dtype), k,
                    (zero, zero, cache_index, zero)
                )
                v = jax.lax.dynamic_update_slice(
                    cache["v"].astype(dtype), v,
                    (zero, zero, cache_index, zero)
                )
        cache = {"k": k, "v": v}

    with part("mixer"):
        out = attn_fn(q, k, v, mask)
    y = _proj_out(p["wo"], out, dtype)
    return y, cache


def init_ffn(key: jax.Array, d_model: int, d_ff: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {"wi": init_dense(k1, d_model, d_ff), "wo": init_dense(k2, d_ff, d_model)}


@part("ffn")
def ffn(p: Params, x: jax.Array, dtype: Any) -> jax.Array:
    h = jax.nn.gelu(dense(p["wi"], x, dtype))
    return dense(p["wo"], h, dtype)


def init_block(key: jax.Array, d_model: int, n_heads: int, d_ff: int,
               cross: bool = False) -> Params:
    ks = jax.random.split(key, 3)
    p: Params = {
        "ln1": init_layer_norm(d_model),
        "attn": init_attention(ks[0], d_model, n_heads),
        "ln2": init_layer_norm(d_model),
        "ffn": init_ffn(ks[1], d_model, d_ff),
    }
    if cross:
        p["ln_x"] = init_layer_norm(d_model)
        p["xattn"] = init_attention(ks[2], d_model, n_heads)
    return p


def encoder_block(
    p: Params, x: jax.Array, mask: jax.Array, dtype: Any,
    attn_fn=dot_product_attention, moe_ctx=None, with_aux: bool = False,
    segment_ids: Optional[jax.Array] = None,
):
    """Pre-LN transformer block: x + Attn(LN(x)); x + FFN(LN(x)).

    ``segment_ids``: see :func:`attention` (packed rows; ``mask`` is then
    their block-diagonal mask).

    A block carrying a ``moe`` subtree (``encoder.init_params`` with
    ``moe_experts > 0``) routes its FFN sublayer through the Switch MoE
    layer; ``moe_ctx`` is the ``(MoeConfig, mesh-or-None)`` pair the caller
    (``encoder.forward``) resolved once for the whole stack.

    ``with_aux=True`` returns ``(x, aux)`` where ``aux`` is the block's
    Switch load-balancing auxiliary loss (0.0 for dense blocks) — the
    training path MUST use it for MoE configs (a router trained without
    the aux term collapses onto one expert); serving ignores it.
    """
    h = layer_norm(p["ln1"], x)
    a, _ = attention(p["attn"], h, h, mask, dtype, attn_fn=attn_fn,
                     segment_ids=segment_ids)
    with part("around"):
        x = x + a
    h = layer_norm(p["ln2"], x)
    if "moe" in p:
        from agent_tpu.models import moe as moe_mod

        if moe_ctx is None:
            # Fail with the contract, not an unpack TypeError deep inside a
            # traced shard_map: every MoE-capable entry point must resolve
            # the (MoeConfig, mesh) pair (encoder.forward does; the pp
            # pipeline intentionally does not — pp+MoE is unsupported).
            raise ValueError(
                "encoder block has a 'moe' subtree but no moe_ctx was "
                "threaded — this forward path does not support MoE configs"
            )
        mcfg, mesh = moe_ctx
        B, L, d = h.shape
        with part("experts"):
            y, aux = moe_mod.moe_ffn(
                p["moe"], h.astype(dtype).reshape(B * L, d), mcfg, mesh=mesh
            )
        with part("around"):
            out = x + y.reshape(B, L, d).astype(x.dtype)
        return (out, aux) if with_aux else out
    y = ffn(p["ffn"], h, dtype)
    with part("around"):
        out = x + y
    return (out, jnp.float32(0.0)) if with_aux else out


def decoder_block(
    p: Params,
    x: jax.Array,                    # [B, Lq, d_model]
    self_mask: jax.Array,            # [B, 1, Lq|1, Lself]
    enc_out: jax.Array,              # [B, Lsrc, d_model]
    enc_mask: jax.Array,             # [B, 1, 1, Lsrc]
    dtype: Any,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    h = layer_norm(p["ln1"], x)
    a, cache = attention(
        p["attn"], h, h, self_mask, dtype, cache=cache,
        cache_index=cache_index, block_table=block_table,
    )
    with part("around"):
        x = x + a
    h = layer_norm(p["ln_x"], x)
    a, _ = attention(p["xattn"], h, enc_out, enc_mask, dtype)
    with part("around"):
        x = x + a
    h = layer_norm(p["ln2"], x)
    y = ffn(p["ffn"], h, dtype)
    with part("around"):
        return x + y, cache


def sinusoidal_positions(length: int, d_model: int) -> np.ndarray:
    """Classic fixed sinusoidal position table [length, d_model] (f32)."""
    pos = np.arange(length)[:, None].astype(np.float64)
    dim = np.arange(0, d_model, 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, dim / d_model)
    table = np.zeros((length, d_model), dtype=np.float32)
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle)
    return table


def causal_mask(length: int) -> np.ndarray:
    """[1, 1, L, L] lower-triangular attend mask."""
    return np.tril(np.ones((length, length), dtype=np.int32))[None, None]


def pad_mask_to_attn(mask: jax.Array) -> jax.Array:
    """[B, L] padding mask (1 = real token) → [B, 1, 1, L] broadcastable."""
    return mask[:, None, None, :]


@part("around")
def segment_mask_to_attn(segment_ids: jax.Array) -> jax.Array:
    """[B, L] segment ids (0 = pad) → the block-diagonal [B, 1, L, L] attend
    mask: a query attends the keys of its own segment and no other, a pad
    slot attends nothing and is attended by nothing."""
    seg_q, seg_k = segment_ids[:, :, None], segment_ids[:, None, :]
    return ((seg_q == seg_k) & (seg_k > 0)).astype(jnp.int32)[:, None]


def is_key_padding_mask(mask: jax.Array, batch: int, lk: int) -> bool:
    """True iff ``mask`` is a key-padding attention mask ``[B|1, 1, 1, Lk]``.

    The shared contract gate of the fast attention paths (ring in
    ``agent_tpu.parallel.ring``, Pallas flash in ``agent_tpu.kernels``):
    shapes that fail it take the dense path. A contract change here changes
    every fast path at once.
    """
    return (
        mask.ndim == 4
        and mask.shape[1] == 1
        and mask.shape[2] == 1              # no causal / per-query dim
        and mask.shape[0] in (1, batch)
        and mask.shape[3] == lk
    )


def materialize_key_padding_mask(mask: jax.Array, batch: int, lk: int) -> jax.Array:
    """Broadcast a shared ``[1, 1, 1, Lk]`` mask to ``[B, 1, 1, Lk]`` — the
    sharded fast paths partition the batch dim, which a size-1 dim cannot
    satisfy."""
    if mask.shape[0] == 1 and batch > 1:
        return jnp.broadcast_to(mask, (batch, 1, 1, lk))
    return mask


def count_params(params: Params) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(params)))


def assign_from_npz(params: Params, path: str) -> Params:
    """Overlay a flat ``.npz`` checkpoint onto an init'd param pytree.

    Keys are dotted paths (``blocks.0.attn.wq``); leaves absent from the file
    keep their initialized values, so partial checkpoints compose with
    deterministic init. Shared by encoder and seq2seq loaders.
    """
    flat = dict(np.load(path))

    def assign(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: assign(v, f"{prefix}{k}.") for k, v in tree.items()}
        if isinstance(tree, list):
            return [assign(v, f"{prefix}{i}.") for i, v in enumerate(tree)]
        key = prefix[:-1]
        return jnp.asarray(flat[key]) if key in flat else tree

    return assign(params)
