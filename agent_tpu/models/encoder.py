"""Transformer encoder classifier — the model behind ``map_classify_tpu``.

The reference classified with an INT8 TFLite CNN on a Coral Edge TPU, one row
per ``interpreter.invoke()`` (reference ``ops/map_classify_tpu.py:71-74``,
``CONTRACT.md:24`` "No batching"). The TPU-native successor is a BERT-class
token encoder compiled once per shape bucket and run *batched* with the batch
dim sharded over the mesh ``dp`` axis (SURVEY.md §2.8) — the MXU wants large
batched matmuls, not row-at-a-time invokes.

Weights are deterministic from the model id (:func:`agent_tpu.models.layers.seed_from`)
or loaded from an ``.npz`` checkpoint path — the generalization of the
reference's immutable model artifact at ``/models/model_edgetpu.tflite``
(reference ``ops/_tpu_runtime.py:23-31``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from agent_tpu.models import layers
from agent_tpu.models.layers import Params
from agent_tpu.obs.trace import part


@dataclass(frozen=True)
class EncoderConfig:
    """Model hyperparameters. Defaults give a ~7M-param encoder whose dims are
    multiples of the MXU tile (128) where it matters (d_model, d_ff)."""

    vocab_size: int = 260          # ByteTokenizer vocab (256 bytes + specials)
    d_model: int = 256
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 1024
    max_len: int = 2048            # reference profile max_tokens (app.py:108)
    n_classes: int = 1000
    dtype: str = "bfloat16"
    # "int8" runs the hot matmuls W8A8 on the MXU (models.quant) — the
    # TPU-native successor of the reference's INT8 TFLite execution
    # (reference ops/_tpu_runtime.py:23-31); "w8a16" keeps the int8 weight
    # tables but leaves activations at dtype (the memory-bound recipe).
    quant: str = "none"
    # Serving-strategy fields (payload model_config may set them, SURVEY
    # §2.8 "strategies usable by the workload"):
    # pp > 1 pipelines the block stack over a ``pp`` mesh axis
    # (parallel.pipeline.encoder_forward_pp); n_layers must divide by pp.
    pp: int = 1
    # moe_experts > 0 replaces each block's dense FFN with a Switch MoE
    # layer (models.moe) — experts shard over an ``ep`` mesh axis when the
    # serving mesh has one, else run unsharded.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def scaled(self, **overrides) -> "EncoderConfig":
        return replace(self, **overrides)


def moe_cfg_of(cfg: EncoderConfig):
    """The block-level MoE config for an ``moe_experts > 0`` encoder."""
    from agent_tpu.models.moe import MoeConfig

    return MoeConfig(
        d_model=cfg.d_model, d_ff=cfg.d_ff, n_experts=cfg.moe_experts,
        capacity_factor=cfg.moe_capacity_factor, dtype=cfg.dtype,
    )


def init_params(cfg: EncoderConfig, model_id: str = "classify-default") -> Params:
    """Deterministic param pytree for ``model_id`` (same id ⇒ same weights).

    ``moe_experts > 0``: each block's dense ``ffn`` subtree is replaced by a
    ``moe`` subtree (router + expert-stacked FFN, ``models.moe``); attention
    and norms are unchanged, so the MoE encoder serves through the same
    forward and op contract.
    """
    key = layers.seed_from(model_id)
    ks = jax.random.split(key, cfg.n_layers + 3)
    blocks = [
        layers.init_block(ks[i + 1], cfg.d_model, cfg.n_heads, cfg.d_ff)
        for i in range(cfg.n_layers)
    ]
    if cfg.moe_experts > 0:
        from agent_tpu.models import moe

        mcfg = moe_cfg_of(cfg)
        for i, blk in enumerate(blocks):
            del blk["ffn"]
            # ks[i + 1] already differs per layer; the fold_in decorrelates
            # the MoE init from init_block's split of the SAME per-layer key
            # (the attention weights above consumed splits of ks[i + 1]).
            blk["moe"] = moe.init_moe_ffn(
                jax.random.fold_in(ks[i + 1], 0x40E), mcfg
            )
    params: Params = {
        "embed": jax.random.normal(
            ks[0], (cfg.vocab_size, cfg.d_model), dtype=jnp.float32
        ) * 0.02,
        "pos": jnp.asarray(layers.sinusoidal_positions(cfg.max_len, cfg.d_model)),
        "blocks": blocks,
        "ln_f": layers.init_layer_norm(cfg.d_model),
        "head": layers.init_dense(ks[-1], cfg.d_model, cfg.n_classes),
    }
    return params


def load_npz(path: str, cfg: EncoderConfig) -> Params:
    """Load params from a flat ``.npz`` (keys like ``blocks.0.attn.wq``)."""
    return layers.assign_from_npz(init_params(cfg, model_id=path), path)


@part("around")
def segment_layout(segment_lengths: jax.Array, length: int):
    """The packed layout's slot arrays from its wire form. ``segment_lengths``
    [P, G] int32: the token counts of the rows laid end to end in each
    program row, in order (0 = no such segment, or a row with no token).
    Returns ``(segment_ids [P, L] int32, positions [P, L] int32)``: a slot's
    id is 1 + the index of the segment it falls in and 0 past the last real
    token; its position counts from the start of its own segment."""
    ends = jnp.cumsum(segment_lengths.astype(jnp.int32), axis=1)   # [P, G]
    slot = jnp.arange(length, dtype=jnp.int32)[None, :]            # [1, L]
    index = (slot[:, :, None] >= ends[:, None, :]).sum(-1)         # [P, L]
    real = slot < ends[:, -1:]
    starts = jnp.concatenate(
        [jnp.zeros_like(ends[:, :1]), ends[:, :-1]], axis=1)
    start = jnp.take_along_axis(
        starts, jnp.minimum(index, ends.shape[1] - 1), axis=1)
    segment_ids = jnp.where(real, index + 1, 0).astype(jnp.int32)
    positions = jnp.where(real, slot - start, 0).astype(jnp.int32)
    return segment_ids, positions


def _run_blocks(params, x, attn_mask, cfg, attn_fn, remat, mesh,
                segment_ids=None):
    """The block stack and the final LayerNorm → (x [B, L, d], summed aux)."""
    dtype = cfg.compute_dtype
    moe_ctx = None
    if cfg.moe_experts > 0:
        moe_ctx = (
            moe_cfg_of(cfg),
            mesh if mesh is not None and "ep" in mesh.shape else None,
        )
    block_fn = lambda p, h, m, s: layers.encoder_block(  # noqa: E731
        p, h, m, dtype, attn_fn=attn_fn, moe_ctx=moe_ctx, with_aux=True,
        segment_ids=s,
    )
    if remat:
        # Full-block recompute (minimum memory): the one policy kept.
        # Selective policies (dots-saveable) against it, and no remat at
        # all on the flash-train kernel: not measured on the present tree
        # (PERF.md §7, rows 6-8: `train_classifier` needs a cell).
        block_fn = jax.checkpoint(block_fn)
    aux_total = jnp.float32(0.0)
    for block in params["blocks"]:
        x, aux = block_fn(block, x, attn_mask, segment_ids)
        aux_total = aux_total + aux
    return layers.layer_norm(params["ln_f"], x), aux_total


@part("head")
def classify_head(params: Params, pooled: jax.Array, cfg: EncoderConfig):
    """Pooled rows [R, d_model] (f32) → logits [R, n_classes] (f32)."""
    dtype = cfg.compute_dtype
    logits = layers.dense(params["head"], pooled.astype(dtype), dtype)
    return logits.astype(jnp.float32)


def pooled_segments(
    params: Params,
    ids: jax.Array,               # [P, L] int32: rows packed end to end
    segment_lengths: jax.Array,   # [P, G] int32 (:func:`segment_layout`)
    cfg: EncoderConfig,
    attn_fn=layers.dot_product_attention,
    remat: bool = False,
    mesh=None,
) -> jax.Array:
    """The encoder on PACKED rows → the mean of every segment's real tokens,
    [P, G, d_model] f32 (0 for a segment with no token): what
    :func:`forward` computes for a row alone in its program row, for each of
    the rows that share one. Positions restart at every segment, attention is
    block-diagonal (``layers.segment_mask_to_attn``), pad slots take part in
    nothing."""
    dtype = cfg.compute_dtype
    L, G = ids.shape[1], segment_lengths.shape[1]
    segment_ids, positions = segment_layout(segment_lengths, L)
    with part("embed"):
        x = (params["embed"].astype(dtype)[ids]
             + params["pos"][:L].astype(dtype)[positions])
    x, _ = _run_blocks(
        params, x, layers.segment_mask_to_attn(segment_ids), cfg, attn_fn,
        remat, mesh, segment_ids=segment_ids)
    with part("head"):
        member = (segment_ids[:, None, :]
                  == jnp.arange(1, G + 1, dtype=jnp.int32)[None, :, None])
        # 0/1 weights: at full precision the sums are the tokens' own f32
        # sums.
        sums = jnp.einsum("pgl,pld->pgd", member.astype(jnp.float32),
                          x.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
        denom = jnp.maximum(segment_lengths, 1).astype(jnp.float32)
        return sums / denom[:, :, None]


def forward(
    params: Params,
    ids: jax.Array,      # [B, L] int32 token ids
    mask: Optional[jax.Array],   # [B, L] int32 padding mask (1 = real)
    cfg: EncoderConfig,
    attn_fn=layers.dot_product_attention,
    remat: bool = False,
    mesh=None,
    with_aux: bool = False,
    segment_lengths: Optional[jax.Array] = None,
    row_slots: Optional[jax.Array] = None,
):
    """Logits [B, n_classes] (f32). Mean-pool over real tokens, linear head.

    ``remat=True`` wraps each block in ``jax.checkpoint`` so the backward
    pass recomputes block activations instead of storing them — at training
    scale the stored [B, H, L, L] attention scores otherwise exceed HBM
    (BERT-base, batch 256, seq 512: ~39 GB saved for ~33% more FLOPs).

    ``mesh`` matters only for MoE configs (``moe_experts > 0``): when it
    carries an ``ep`` axis the expert batches get explicit sharding
    constraints so the experts provably land on ``ep``.

    ``with_aux=True`` returns ``(logits, aux)`` — the mean Switch
    load-balancing loss over blocks (0.0 for dense configs). Blocks return
    their aux through the (possibly checkpointed) block_fn, never via
    side-channel closures: a Python-list accumulator would leak tracers
    out of ``jax.checkpoint``'s inner trace.

    The SEGMENT form — ``segment_lengths`` [P, G] given, ``mask`` not read:
    ``ids`` [P, L] holds several rows end to end in each program row
    (:func:`pooled_segments`). Logits come back one row a segment slot,
    [P * G, n_classes], or, with ``row_slots`` [R] (the flat slot
    ``program row * G + segment`` of each caller's row), gathered into the
    caller's row order BEFORE the head: [R, n_classes]. Without
    ``segment_lengths`` the traced program is what it was before the form
    existed.
    """
    if segment_lengths is not None:
        if with_aux:
            raise ValueError("the segment form returns no auxiliary loss")
        pooled = pooled_segments(
            params, ids, segment_lengths, cfg, attn_fn=attn_fn, remat=remat,
            mesh=mesh).reshape(-1, cfg.d_model)
        if row_slots is not None:
            pooled = pooled[row_slots]
        return classify_head(params, pooled, cfg)
    dtype = cfg.compute_dtype
    L = ids.shape[1]
    with part("embed"):
        x = (params["embed"].astype(dtype)[ids]
             + params["pos"][:L].astype(dtype)[None])
    x, aux_total = _run_blocks(
        params, x, layers.pad_mask_to_attn(mask), cfg, attn_fn, remat, mesh)
    with part("head"):
        denom = jnp.maximum(
            mask.sum(axis=1, keepdims=True), 1).astype(jnp.float32)
        pooled = (x.astype(jnp.float32) * mask[:, :, None]).sum(axis=1) / denom
    logits = classify_head(params, pooled, cfg)
    if with_aux:
        return logits, aux_total / max(1, cfg.n_layers)
    return logits


@part("head")
def topk_probs(logits: jax.Array, k: int):
    """On-device top-k over softmax probabilities → (values, indices), both
    ``[B, k]`` — the host fetches k numbers per row instead of the full
    ``[B, n_classes]`` logits; the device→host transfer is the expensive hop
    (SURVEY.md §3.2 rebuild mapping)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jax.lax.top_k(probs, k)


def topk_rows(values: np.ndarray, indices: np.ndarray) -> list:
    """Device (values, indices) → per-row [{"index", "score"}] result shape
    (reference ``ops/map_classify_tpu.py:76-82``). lax.top_k returns sorted
    descending already. ``tolist()`` first: it converts to native Python
    numbers in C, where per-element indexing makes a numpy scalar each."""
    return [
        [{"index": i, "score": s} for i, s in zip(idx_row, val_row)]
        for idx_row, val_row in zip(
            np.asarray(indices).tolist(), np.asarray(values).tolist()
        )
    ]


