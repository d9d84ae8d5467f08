"""Encoder-decoder (T5-class) seq2seq with scan-based decode — behind
``map_summarize``.

The reference summarized with torch BART ``model.generate(num_beams=4)`` on the
host CPU (reference ``ops/map_summarize.py:52-59``, ``SUMMARIZE_FORCE_CPU``
default on, ``:10``) — the "zero CPU-side model execution" target of
BASELINE.json. Here generation is a single jit-compiled program: the encoder
runs once, then ``lax.scan`` steps the decoder over a **static** number of
positions with a preallocated KV cache updated via ``dynamic_update_slice`` —
no per-step retrace, no host round-trips inside the decode loop (SURVEY.md §7
"hard parts": autoregressive decode under pjit).

Greedy decode is the default; beam search stays optional per VERDICT item 7.
Weights are deterministic from the model id or loaded from ``.npz``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from agent_tpu.models import layers
from agent_tpu.models.layers import Params
from agent_tpu.models.tokenizer import BOS_ID, EOS_ID, PAD_ID


@dataclass(frozen=True)
class Seq2SeqConfig:
    vocab_size: int = 260
    d_model: int = 256
    n_heads: int = 8
    n_enc_layers: int = 4
    n_dec_layers: int = 4
    d_ff: int = 1024
    max_src_len: int = 1024       # reference truncates input at 1024 (:49)
    max_tgt_len: int = 130        # reference generate max_length default (:46)
    dtype: str = "bfloat16"
    # "int8": W8A8 quantized matmuls (models.quant) in encode AND decode —
    # the reference's INT8 device execution, TPU-native. "w8a16": weight-only
    # int8 (activations stay at dtype) — the decode-mode recipe for
    # HBM-bound thin matmuls.
    quant: str = "none"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def init_params(cfg: Seq2SeqConfig, model_id: str = "summarize-default") -> Params:
    key = layers.seed_from(model_id)
    n = cfg.n_enc_layers + cfg.n_dec_layers
    ks = jax.random.split(key, n + 3)
    max_len = max(cfg.max_src_len, cfg.max_tgt_len)
    return {
        "embed": jax.random.normal(
            ks[0], (cfg.vocab_size, cfg.d_model), dtype=jnp.float32
        ) * 0.02,
        "pos": jnp.asarray(layers.sinusoidal_positions(max_len, cfg.d_model)),
        "enc": [
            layers.init_block(ks[1 + i], cfg.d_model, cfg.n_heads, cfg.d_ff)
            for i in range(cfg.n_enc_layers)
        ],
        "dec": [
            layers.init_block(
                ks[1 + cfg.n_enc_layers + i], cfg.d_model, cfg.n_heads, cfg.d_ff,
                cross=True,
            )
            for i in range(cfg.n_dec_layers)
        ],
        "ln_enc": layers.init_layer_norm(cfg.d_model),
        "ln_dec": layers.init_layer_norm(cfg.d_model),
        # Output projection ties to the embedding (transposed) — standard and
        # halves the param count; no separate head matrix.
    }


def encode(params: Params, src_ids: jax.Array, src_mask: jax.Array,
           cfg: Seq2SeqConfig,
           attn_fn=layers.dot_product_attention) -> jax.Array:
    dtype = cfg.compute_dtype
    L = src_ids.shape[1]
    x = params["embed"].astype(dtype)[src_ids] + params["pos"][:L].astype(dtype)[None]
    attn_mask = layers.pad_mask_to_attn(src_mask)
    for block in params["enc"]:
        x = layers.encoder_block(block, x, attn_mask, dtype, attn_fn=attn_fn)
    return layers.layer_norm(params["ln_enc"], x)


def _empty_cache(cfg: Seq2SeqConfig, batch: int) -> list:
    d_head = cfg.d_model // cfg.n_heads
    shape = (batch, cfg.n_heads, cfg.max_tgt_len, d_head)
    return [
        {
            "k": jnp.zeros(shape, dtype=cfg.compute_dtype),
            "v": jnp.zeros(shape, dtype=cfg.compute_dtype),
        }
        for _ in range(cfg.n_dec_layers)
    ]


def _decode_step(
    params: Params,
    tok: jax.Array,           # [B] current input token
    step: jax.Array,          # scalar int32 position, or [B] per-row positions
    enc_out: jax.Array,       # [B, Ls, d]
    enc_mask: jax.Array,      # [B, Ls]
    caches: list,
    cfg: Seq2SeqConfig,
) -> Tuple[jax.Array, list]:
    """One decoder step over the KV cache; returns (logits [B, V], caches).

    ``step`` may be a **[B] vector** of per-row positions — the continuous-
    batching case (ISSUE 15), where each running-batch slot sits at its own
    decode depth. The per-row math (position embedding gather, per-row
    causal mask, per-row cache scatter) computes exactly the values the
    scalar path computes for a batch whose rows all share one position, so
    a slot's step stream is bit-identical to a solo scalar-step decode.

    ``caches`` may be the **paged** pytree ``{"table": [B, MAXB] int32,
    "layers": [{"k","v"}: [NB, H, BS, D]]}`` (``make_paged_cache_factory``,
    ISSUE 16): layer caches become shared block pools indexed through the
    per-row block table, detected structurally so the step signature — and
    every caller — is unchanged. Paged decode requires the vector-``step``
    form; the mask math is identical, and the attention layer slices its
    paged view back to ``max_tgt_len`` so the emitted logits stay
    bit-identical to a dense-cache decode.
    """
    dtype = cfg.compute_dtype
    paged = isinstance(caches, dict) and "table" in caches
    if paged and getattr(step, "ndim", 0) != 1:
        raise ValueError(
            "paged KV caches require per-row vector positions (the "
            "continuous-batching step); scan decode uses dense caches"
        )
    table = caches["table"] if paged else None
    layer_caches = caches["layers"] if paged else caches
    x = params["embed"].astype(dtype)[tok][:, None, :]  # [B, 1, d]
    positions = jnp.arange(cfg.max_tgt_len)
    if getattr(step, "ndim", 0) == 1:
        x = x + params["pos"].astype(dtype)[step][:, None, :]
        # Per-row causal mask: row b attends to cache positions <= step[b].
        self_mask = (
            positions[None, :] <= step[:, None]
        ).astype(jnp.int32)[:, None, None, :]
    else:
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos"].astype(dtype), step, 1, axis=0
        )[None]
        # Self-attention mask: attend to cache positions <= step.
        self_mask = (positions <= step).astype(jnp.int32)[None, None, None, :]
    enc_attn_mask = enc_mask[:, None, None, :]
    new_layers = []
    for block, cache in zip(params["dec"], layer_caches):
        x, cache = layers.decoder_block(
            block, x, self_mask, enc_out, enc_attn_mask, dtype,
            cache=cache, cache_index=step, block_table=table,
        )
        new_layers.append(cache)
    x = layers.layer_norm(params["ln_dec"], x)[:, 0]  # [B, d]
    logits = jnp.dot(x.astype(dtype), params["embed"].astype(dtype).T)
    new_caches = {"table": table, "layers": new_layers} if paged else new_layers
    return logits.astype(jnp.float32), new_caches


def greedy_generate(
    params: Params,
    src_ids: jax.Array,    # [B, Ls] int32
    src_mask: jax.Array,   # [B, Ls] int32
    cfg: Seq2SeqConfig,
    max_new_tokens: int,
    min_length: int = 0,
    attn_fn=layers.dot_product_attention,
) -> Tuple[jax.Array, jax.Array]:
    """Greedy decode under one jit trace: ``lax.scan`` over static steps.

    Returns (tokens [B, max_new_tokens], lengths [B]) — generation stops
    contributing after EOS per row (tokens after EOS are PAD), but the scan
    always runs the static step count so the executable is shape-stable.

    ``attn_fn`` applies to the *encoder* (where the long context lives — the
    ring/sp path, SURVEY.md §5.7); decode steps query one position against the
    KV cache, where sequence sharding buys nothing.
    """
    from agent_tpu.models.decoding import greedy_scan

    B = src_ids.shape[0]
    enc_out = encode(params, src_ids, src_mask, cfg, attn_fn=attn_fn)

    def step_fn(tok, step, caches):
        return _decode_step(params, tok, step, enc_out, src_mask, caches, cfg)

    return greedy_scan(
        step_fn, _empty_cache(cfg, B), B, max_new_tokens,
        start_id=BOS_ID, eos_id=EOS_ID, pad_id=PAD_ID,
        min_length=min_length,
    )


def greedy_generate_from_encoded(
    params: Params,
    enc_out: jax.Array,    # [B, Ls, d] encoder output (cfg.compute_dtype)
    src_mask: jax.Array,   # [B, Ls] int32
    cfg: Seq2SeqConfig,
    max_new_tokens: int,
    min_length: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Greedy decode from a PRE-COMPUTED encoder output — the decoder half
    of the MPMD pipeline split (ISSUE 7 stretch, arXiv 2412.14374): an
    encode-stage agent ships ``enc_out`` through the controller and a
    decode-stage agent resumes here. Same scan/caches/EOS semantics as
    :func:`greedy_generate`, which is exactly ``encode(...)`` composed with
    this function."""
    from agent_tpu.models.decoding import greedy_scan

    B = enc_out.shape[0]
    enc_out = enc_out.astype(cfg.compute_dtype)

    def step_fn(tok, step, caches):
        return _decode_step(params, tok, step, enc_out, src_mask, caches, cfg)

    return greedy_scan(
        step_fn, _empty_cache(cfg, B), B, max_new_tokens,
        start_id=BOS_ID, eos_id=EOS_ID, pad_id=PAD_ID,
        min_length=min_length,
    )


def beam_generate(
    params: Params,
    src_ids: jax.Array,    # [B, Ls] int32
    src_mask: jax.Array,   # [B, Ls] int32
    cfg: Seq2SeqConfig,
    max_new_tokens: int,
    num_beams: int = 4,
    length_penalty: float = 1.0,
    early_stopping: bool = False,
    min_length: int = 0,
    attn_fn=layers.dot_product_attention,
) -> Tuple[jax.Array, jax.Array]:
    """Beam-search decode under one jit trace — static shapes throughout.

    The reference decoded with torch ``generate(num_beams=4)`` on the host
    CPU (reference ``ops/map_summarize.py:52-59``). Here beams flatten into
    the batch dim (``B*K`` rows share the decode-step executable with greedy),
    every step does one top-K over ``[B, K*V]`` joint scores, and beam
    reordering gathers the KV caches along the beam axis — all inside
    ``lax.scan``, so the program never retraces per step.

    Semantics are HF ``BeamSearchScorer``-exact (see ``decoding.beam_scan``):
    EOS hypotheses bank into a K-slot finished store normalized by
    ``generated_length ** length_penalty``; ``early_stopping=True`` closes a
    row as soon as the store fills (HF's generic default is False;
    bart-large-cnn — the reference's model — generated with True).

    Returns (tokens [B, max_new_tokens], lengths [B]) like
    :func:`greedy_generate` (``num_beams=1`` reduces to exactly greedy).
    """
    from agent_tpu.models.decoding import beam_scan

    B, K = src_ids.shape[0], num_beams
    enc_out = encode(params, src_ids, src_mask, cfg, attn_fn=attn_fn)
    enc_out = jnp.repeat(enc_out, K, axis=0)            # [B*K, Ls, d]
    enc_mask = jnp.repeat(src_mask, K, axis=0)          # [B*K, Ls]

    def step_fn(tok, step, caches):
        return _decode_step(params, tok, step, enc_out, enc_mask, caches, cfg)

    return beam_scan(
        step_fn, _empty_cache(cfg, B * K), B, cfg.vocab_size, max_new_tokens,
        num_beams=K, start_id=BOS_ID, eos_id=EOS_ID, pad_id=PAD_ID,
        length_penalty=length_penalty, early_stopping=early_stopping,
        min_length=min_length,
    )


def make_positional_step(cfg: Seq2SeqConfig):
    """The per-row-position decode step the continuous-batching engine
    (``models.decoding.ContinuousBatcher``) drives: unlike the scan engines'
    closures, the encoder state is an ARGUMENT, because slots join a running
    batch with their own encoder output (the prefill/decode split — prefill
    produced ``enc_out`` earlier, possibly on another agent, cf.
    ``greedy_generate_from_encoded``). The parameters are an argument too
    (see ``decoding.PositionalStepFn``)."""

    def step_fn(params, tok, pos_rows, caches, enc_out, enc_mask):
        return _decode_step(
            params, tok, pos_rows, enc_out.astype(cfg.compute_dtype),
            enc_mask, caches, cfg,
        )

    return step_fn


def make_cache_factory(cfg: Seq2SeqConfig):
    """``rows -> empty KV caches`` for the continuous engine's slot store."""

    def factory(rows: int) -> list:
        return _empty_cache(cfg, rows)

    return factory


def make_paged_cache_factory(
    cfg: Seq2SeqConfig, block_size: int = 16, pool_blocks: int = 0
):
    """``rows -> paged KV caches`` for the continuous engine (ISSUE 16).

    Instead of ``rows × max_tgt_len`` dense reservation, each decoder layer
    holds ONE shared pool of ``pool_blocks`` fixed-size KV blocks
    ``[NB, H, block_size, d_head]`` plus a per-row block table
    ``[rows, ceil(max_tgt_len / block_size)]`` mapping logical block →
    pool block. Pool block 0 is reserved as the trash block (the engine
    points unallocated/released entries there), so ``pool_blocks`` counts
    one unusable block. ``pool_blocks=0`` auto-sizes to dense parity
    (``rows * MAXB + 1``) — same worst-case HBM, no admission stalls; shrink
    it to trade admission headroom for resident-memory savings, since live
    requests only hold ``ceil(limit / block_size)`` blocks per row.
    """
    bs = int(block_size)
    if bs < 1:
        raise ValueError("block_size must be >= 1")
    maxb = -(-cfg.max_tgt_len // bs)
    d_head = cfg.d_model // cfg.n_heads

    def factory(rows: int) -> dict:
        nb = int(pool_blocks) or rows * maxb + 1
        if nb < maxb + 1:
            raise ValueError(
                f"pool_blocks={nb} cannot seat one max-length row "
                f"({maxb} blocks + trash)"
            )
        return {
            "table": jnp.zeros((rows, maxb), dtype=jnp.int32),
            "layers": [
                {
                    "k": jnp.zeros(
                        (nb, cfg.n_heads, bs, d_head),
                        dtype=cfg.compute_dtype,
                    ),
                    "v": jnp.zeros(
                        (nb, cfg.n_heads, bs, d_head),
                        dtype=cfg.compute_dtype,
                    ),
                }
                for _ in range(cfg.n_dec_layers)
            ],
        }

    return factory


def load_npz(path: str, cfg: Seq2SeqConfig) -> Params:
    """Load params from a flat ``.npz`` (keys like ``dec.0.xattn.wq``)."""
    return layers.assign_from_npz(init_params(cfg, model_id=path), path)
