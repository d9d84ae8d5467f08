"""INT8 quantized execution (W8A8, dynamic activation scales).

The reference's entire device story was INT8: the Edge-TPU ran an
INT8-compiled TFLite artifact with an int8 input contract (reference
``ops/map_classify_tpu.py:53,58-69``, ``ops/_tpu_runtime.py:23-31``, the
Coral toolchain in ``Dockerfile:9-30``). The TPU-native successor is not a
quantized *artifact* but a quantized *execution mode*: the same checkpoint /
deterministic params, with the hot matmuls running ``int8 × int8 → int32``
on the MXU (a v5e's published peaks: 393 TOP/s in int8, 197 TFLOP/s in
bf16) and dequantizing into the f32 residual stream. Serving contract, tokenization, and result shapes are
unchanged; ``model_config: {"quant": "int8"}`` (or ``TPU_QUANT=int8``)
flips the mode per task.

Scheme (the standard dynamic W8A8 recipe, AQT-style but hand-rolled):

- **Weights**: symmetric per-output-channel int8, quantized once at build
  time on the host (``w_q = round(w / s)``, ``s = amax/127`` over the
  contracting axes). Host-side quantization also shrinks the host→HBM
  transfer 4× vs f32 leaves.
- **Activations**: symmetric per-row dynamic int8 at trace time — abs-max
  over the contracting axes, fused by XLA into the preceding elementwise op
  (LN / GELU). No calibration pass, no clipping tuning.
- **Matmul**: ``lax.dot_general(x_q, w_q, preferred_element_type=int32)``;
  the int32 product dequantizes as ``y · s_x · s_w`` in f32.
- **What stays high-precision**: embeddings, LayerNorms, softmax, residual
  adds, the attention score/context matmuls (QKᵀ, PV — both activations,
  dynamic-range-fragile), and the tiny classifier/pooler heads. FFN + QKVO
  projections carry ~90% of encoder FLOPs: that share is all the int8 rate
  can act on.

Leaf convention: a quantized projection replaces the f32 array (or
``{"w", "b"}`` dense dict) with ``{"w_q": int8, "w_scale": f32[out-dims]}``
(+ ``"b"``). ``layers.dense`` / ``layers.attention`` / the model-local dense
helpers dispatch on that structure, so every family (encoder, BERT, BART,
T5) serves quantized through its unmodified forward.

A second execution mode, **W8A16 weight-only** (``quant: "w8a16"``), keeps
the same int8 weight tables but leaves activations in the compute dtype —
no dynamic quantization pass at all. Its leaf convention is ``{"w8": int8,
"w_scale"}`` (+ ``"b"``), and the same dispatch sites route it through
:func:`wdense` / :func:`wproj_in` / :func:`wproj_out` / :func:`wmoe_expert`.
W8A8 is the big-matmul *encoder* mode (MXU rate); W8A16 is the thin-matmul
*decode* mode (HBM weight bandwidth) — see the section comments below.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]

_QMAX = 127.0
# Floor for dynamic scales: an all-zero row would otherwise divide by zero.
# 1e-8/127 keeps true zeros exact (0/s = 0) without NaN.
_EPS = 1e-8


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and "w_q" in leaf


def is_weight_only(leaf: Any) -> bool:
    """W8A16 leaf (``{"w8": int8, "w_scale"}``): int8 weight table, but
    activations stay in the compute dtype — no dynamic quantization."""
    return isinstance(leaf, dict) and "w8" in leaf


# ---- weight quantization (host, build-time) ----


def quantize_weight(w: Any, reduce_axes: Tuple[int, ...]) -> Params:
    """Symmetric per-channel int8: scale over the contracting ``reduce_axes``.

    Runs on host numpy (``np.asarray`` fetches device leaves once) so the
    int8 table — not the f32 original — is what ships to HBM.
    """
    w = np.asarray(w, dtype=np.float32)
    amax = np.max(np.abs(w), axis=reduce_axes, keepdims=True)
    scale = np.maximum(amax, _EPS) / _QMAX
    w_q = np.clip(np.rint(w / scale), -_QMAX, _QMAX).astype(np.int8)
    return {
        "w_q": w_q,
        "w_scale": np.squeeze(scale, axis=reduce_axes).astype(np.float32),
    }


def quantize_dense(p: Params) -> Params:
    """``{"w": [in, out], "b"}`` → ``{"w_q", "w_scale": [out], "b"}``."""
    out = quantize_weight(p["w"], (0,))
    out["b"] = np.asarray(p["b"], dtype=np.float32)
    return out


def quantize_weight_w8a16(w: Any, reduce_axes: Tuple[int, ...]) -> Params:
    """W8A16 twin of :func:`quantize_weight`: the SAME int8 table and scale,
    stored under the weight-only leaf key ``w8`` so the dispatch sites pick
    the activation-passthrough matmuls instead of the W8A8 ones."""
    q = quantize_weight(w, reduce_axes)
    return {"w8": q["w_q"], "w_scale": q["w_scale"]}


def quantize_dense_w8a16(p: Params) -> Params:
    """``{"w": [in, out], "b"}`` → ``{"w8", "w_scale": [out], "b"}``."""
    out = quantize_weight_w8a16(p["w"], (0,))
    out["b"] = np.asarray(p["b"], dtype=np.float32)
    return out


# ---- activation quantization (device, trace-time) ----


def quantize_act(x: jax.Array, axes: Tuple[int, ...] = (-1,)):
    """Dynamic symmetric int8 over ``axes`` → (x_q int8, scale f32 keepdims).

    The abs-max reduce runs in the *input* dtype (bf16 on TPU) so no f32
    copy of the activation ever materializes — the quantize chain is two
    fused passes over x (reduce, then scale/round/cast). The clip stays:
    a bf16-rounded amax can undershoot the true max by up to 2⁻⁸ relative,
    putting |x/s| at ~127.5 in the worst case.
    """
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True).astype(jnp.float32)
    scale = jnp.maximum(amax, _EPS) / _QMAX
    x_q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale), -_QMAX, _QMAX
    ).astype(jnp.int8)
    return x_q, scale


# ---- quantized matmuls ----
#
# These stay on XLA's ``dot_general(int8, int8 → int32)`` ON PURPOSE: a
# ``pallas_call`` with the dequant epilogue fused in VMEM is a fusion
# barrier (activation quantization can no longer fuse into the preceding
# LN/GELU) and re-reads x per N-tile, while XLA already fuses
# int32→f32·sx·sw+b into the dot's output pass and CSEs the identical
# Q/K/V quantizations to one. What is left to pay is the dynamic activation
# quantization and everything that is not a quantized matmul (LN, GELU,
# softmax, residuals, the attention score/context matmuls, which stay in
# the compute dtype by choice). How much int8 gains end to end over bf16:
# not measured on the present tree as a cell (PERF.md §7, rows 6-8: it
# needs a reference that quantizes as the configuration states; the int8
# CONTROL runs of the ledger's cells read `correct: false`, PERF.md §6).


def qdense(p: Params, x: jax.Array, dtype: Any) -> jax.Array:
    """int8 path of ``layers.dense``: x [..., in] @ w [in, out] + b."""
    x_q, sx = quantize_act(x)                       # sx [..., 1]
    y = lax.dot_general(
        x_q, p["w_q"],
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)
    y = y * (sx * p["w_scale"])                     # [..., out]
    if "b" in p:
        y = y + p["b"]
    return y.astype(dtype)


def qproj_in(p: Params, x: jax.Array, dtype: Any) -> jax.Array:
    """int8 path of the head-axis input projection:
    x [B, L, d] @ w [d, H, E] → [B, H, L, E] (the ``bld,dhe->bhle`` einsum)."""
    x_q, sx = quantize_act(x)                       # sx [B, L, 1]
    y = lax.dot_general(
        x_q, p["w_q"],
        (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)                           # [B, L, H, E]
    y = y * (sx[..., None] * p["w_scale"][None, None])
    return y.astype(dtype).transpose(0, 2, 1, 3)


def qproj_out(p: Params, x: jax.Array, dtype: Any) -> jax.Array:
    """int8 path of the head-axis output projection:
    x [B, H, L, E] @ w [H, E, d] → [B, L, d] (the ``bhle,hed->bld`` einsum)."""
    xt = x.transpose(0, 2, 1, 3)                    # [B, L, H, E]
    x_q, sx = quantize_act(xt, axes=(2, 3))         # sx [B, L, 1, 1]
    y = lax.dot_general(
        x_q, p["w_q"],
        (((2, 3), (0, 1)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)                           # [B, L, d]
    y = y * (sx[..., 0] * p["w_scale"])
    return y.astype(dtype)


# ---- weight-only (W8A16) matmuls ----
#
# The memory-bound recipe for DECODE: the per-step matmuls are [rows, d]-thin
# (rows ≤ batch, d = d_model), so the MXU is idle waiting on HBM and the
# W8A8 activation-quant pass is overhead with nothing to win (decode speed
# by mode: not measured on the present tree; PERF.md §7, rows 6-8, scan
# decode in `map_summarize`). Weight-only keeps
# activations in the compute dtype and ships/reads the int8 table (half the
# bf16 bytes, a quarter of f32), dequantizing by a per-output-channel scale
# on the dot's OUTPUT — the epilogue fuses, and there is no quantize pass
# at all. Same int8 tables as W8A8 (quantize_weight), different execution.


def wdense(p: Params, x: jax.Array, dtype: Any) -> jax.Array:
    """W8A16 path of ``layers.dense``: x [..., in] @ w8 [in, out] + b."""
    y = jnp.dot(x.astype(dtype), p["w8"].astype(dtype))
    y = y.astype(jnp.float32) * p["w_scale"]
    if "b" in p:
        y = y + p["b"]
    return y.astype(dtype)


def wproj_in(p: Params, x: jax.Array, dtype: Any) -> jax.Array:
    """W8A16 path of the head-axis input projection:
    x [B, L, d] @ w8 [d, H, E] → [B, H, L, E]."""
    y = lax.dot_general(
        x.astype(dtype), p["w8"].astype(dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
    )                                               # [B, L, H, E]
    y = (y.astype(jnp.float32) * p["w_scale"][None, None]).astype(dtype)
    return y.transpose(0, 2, 1, 3)


def wproj_out(p: Params, x: jax.Array, dtype: Any) -> jax.Array:
    """W8A16 path of the head-axis output projection:
    x [B, H, L, E] @ w8 [H, E, d] → [B, L, d]."""
    xt = x.transpose(0, 2, 1, 3)                    # [B, L, H, E]
    y = lax.dot_general(
        xt, p["w8"].astype(dtype),
        (((2, 3), (0, 1)), ((), ())),
    )                                               # [B, L, d]
    return (y.astype(jnp.float32) * p["w_scale"]).astype(dtype)


def wmoe_expert(p: Params, x: jax.Array, dtype: Any) -> jax.Array:
    """W8A16 path of the grouped expert matmul (layout as
    :func:`qmoe_expert`): x [G, E, C, d_in] @ w8 [E, d_in, d_out]."""
    y = lax.dot_general(
        x.astype(dtype), p["w8"].astype(dtype),
        (((3,), (1,)), ((1,), (0,))),               # contract d; batch E
    )                                               # [E, G, C, d_out]
    y = y.transpose(1, 0, 2, 3).astype(jnp.float32) \
        * p["w_scale"][None, :, None, :]
    return y.astype(dtype)


def qmoe_expert(p: Params, x: jax.Array, dtype: Any) -> jax.Array:
    """int8 path of the grouped expert matmul (``models.moe``):
    x [G, E, C, d_in] @ w [E, d_in, d_out] → [G, E, C, d_out] (the
    ``gecd,edf->gecf`` / ``gecf,efd->gecd`` einsums, expert dim batched).

    Per-slot dynamic activation scales (each [g, e, c] capacity row
    quantizes over its feature axis) and per-expert-per-channel weight
    scales (``quantize_weight(w, (1,))`` → [E, d_out]), so each expert's
    matmul is the same W8A8 recipe as :func:`qdense`. Capacity-padding rows
    are all-zero → scale floors at ``_EPS`` → exact zeros, same as dense."""
    x_q, sx = quantize_act(x)                       # sx [G, E, C, 1]
    y = lax.dot_general(
        x_q, p["w_q"],
        (((3,), (1,)), ((1,), (0,))),               # contract d; batch E
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)                           # [E, G, C, d_out]
    y = y.transpose(1, 0, 2, 3) * (sx * p["w_scale"][None, :, None, :])
    return y.astype(dtype)


# ---- family param-tree transformers (+ matching spec transformers) ----
#
# Each quantize_* below has a *_specs twin transforming the same paths of the
# shardings.* spec tree; they live side by side so the structures cannot
# drift. Scale specs keep the non-contracted entries of the weight spec
# (e.g. wq [d, H, E] P(None, "tp", None) → scale [H, E] P("tp", None)).
#
# Every transformer is parameterized by ``mode`` ("int8" W8A8 / "w8a16"
# weight-only): the two modes quantize the SAME tree paths with the SAME
# reduce axes and differ only in the leaf convention (``w_q`` vs ``w8``),
# so one traversal serves both and the modes cannot drift structurally.


def _qw_spec(spec: P, reduce_axes: Sequence[int], wkey: str = "w_q") -> Params:
    keep = [s for i, s in enumerate(spec) if i not in reduce_axes]
    return {wkey: spec, "w_scale": P(*keep)}


def _qdense_spec(spec: Params, wkey: str = "w_q") -> Params:
    out = _qw_spec(spec["w"], (0,), wkey)
    out["b"] = spec["b"]
    return out


# mode → (weight quantizer, dense quantizer, weight-spec fn, dense-spec fn).
_MODES = {
    "int8": (
        quantize_weight,
        quantize_dense,
        lambda s, ax: _qw_spec(s, ax, "w_q"),
        lambda s: _qdense_spec(s, "w_q"),
    ),
    "w8a16": (
        quantize_weight_w8a16,
        quantize_dense_w8a16,
        lambda s, ax: _qw_spec(s, ax, "w8"),
        lambda s: _qdense_spec(s, "w8"),
    ),
}


def _quantize_attn(a: Params, mode: str = "int8") -> Params:
    qw = _MODES[mode][0]
    return {
        "wq": qw(a["wq"], (0,)),
        "wk": qw(a["wk"], (0,)),
        "wv": qw(a["wv"], (0,)),
        "wo": qw(a["wo"], (0, 1)),
    }


def _quantize_attn_specs(a: Params, mode: str = "int8") -> Params:
    ws = _MODES[mode][2]
    return {
        "wq": ws(a["wq"], (0,)),
        "wk": ws(a["wk"], (0,)),
        "wv": ws(a["wv"], (0,)),
        "wo": ws(a["wo"], (0, 1)),
    }


def _quantize_block(b: Params, mode: str = "int8") -> Params:
    qw, qd = _MODES[mode][0], _MODES[mode][1]
    nb = dict(b)
    nb["attn"] = _quantize_attn(b["attn"], mode)
    if "ffn" in b:
        nb["ffn"] = {
            "wi": qd(b["ffn"]["wi"]),
            "wo": qd(b["ffn"]["wo"]),
        }
    if "moe" in b:
        # Switch MoE FFN: expert-stacked weights take per-expert-per-channel
        # int8 (scale over each expert's contracting dim); the router stays
        # f32 — it is tiny and its softmax/argmax routing decisions are
        # dynamic-range-fragile (same exclusion rule as attention scores).
        m = b["moe"]
        nb["moe"] = {
            "router": m["router"],
            "wi": qw(m["wi"], (1,)),
            "wo": qw(m["wo"], (1,)),
        }
    if "xattn" in b:
        nb["xattn"] = _quantize_attn(b["xattn"], mode)
    return nb


def _quantize_block_specs(b: Params, mode: str = "int8") -> Params:
    ws = _MODES[mode][2]
    ds = _MODES[mode][3]
    nb = dict(b)
    nb["attn"] = _quantize_attn_specs(b["attn"], mode)
    if "ffn" in b:
        nb["ffn"] = {
            "wi": ds(b["ffn"]["wi"]),
            "wo": ds(b["ffn"]["wo"]),
        }
    if "moe" in b:
        m = b["moe"]
        nb["moe"] = {
            "router": m["router"],
            "wi": ws(m["wi"], (1,)),   # scale [E, d_out] → P("ep", ·)
            "wo": ws(m["wo"], (1,)),
        }
    if "xattn" in b:
        nb["xattn"] = _quantize_attn_specs(b["xattn"], mode)
    return nb


def quantize_encoder(params: Params, mode: str = "int8") -> Params:
    """In-house encoder tree (``models.encoder.init_params``): quantize every
    block's QKVO + FFN; embeddings, LNs, and the head stay f32."""
    out = dict(params)
    out["blocks"] = [_quantize_block(b, mode) for b in params["blocks"]]
    return out


def quantize_encoder_specs(specs: Params, mode: str = "int8") -> Params:
    out = dict(specs)
    out["blocks"] = [_quantize_block_specs(b, mode) for b in specs["blocks"]]
    return out


def quantize_bert(params: Params, mode: str = "int8") -> Params:
    """HF-BERT tree (``models.bert.from_state_dict``): per-layer QKVO + FFN
    dense dicts; embeddings, LNs, pooler, and head stay f32."""
    qd = _MODES[mode][1]
    out = dict(params)
    out["layers"] = []
    for blk in params["layers"]:
        a, f = blk["attn"], blk["ffn"]
        out["layers"].append({
            "attn": {
                "q": qd(a["q"]),
                "k": qd(a["k"]),
                "v": qd(a["v"]),
                "o": qd(a["o"]),
                "ln": a["ln"],
            },
            "ffn": {
                "i": qd(f["i"]),
                "o": qd(f["o"]),
                "ln": f["ln"],
            },
        })
    return out


def quantize_bert_specs(specs: Params, mode: str = "int8") -> Params:
    ds = _MODES[mode][3]
    out = dict(specs)
    out["layers"] = []
    for blk in specs["layers"]:
        a, f = blk["attn"], blk["ffn"]
        out["layers"].append({
            "attn": {
                "q": ds(a["q"]),
                "k": ds(a["k"]),
                "v": ds(a["v"]),
                "o": ds(a["o"]),
                "ln": a["ln"],
            },
            "ffn": {
                "i": ds(f["i"]),
                "o": ds(f["o"]),
                "ln": f["ln"],
            },
        })
    return out


def quantize_seq2seq(params: Params, mode: str = "int8") -> Params:
    """In-house seq2seq tree (``models.seq2seq.init_params``): quantize every
    encoder/decoder block (incl. cross-attention); embeddings and final LNs
    stay f32 (the lm head is the tied embedding — unquantized)."""
    out = dict(params)
    out["enc"] = [_quantize_block(b, mode) for b in params["enc"]]
    out["dec"] = [_quantize_block(b, mode) for b in params["dec"]]
    return out


def quantize_seq2seq_specs(specs: Params, mode: str = "int8") -> Params:
    out = dict(specs)
    out["enc"] = [_quantize_block_specs(b, mode) for b in specs["enc"]]
    out["dec"] = [_quantize_block_specs(b, mode) for b in specs["dec"]]
    return out


def _quantize_bart_block(blk: Params, mode: str = "int8") -> Params:
    qd = _MODES[mode][1]
    nb = dict(blk)
    nb["self"] = {k: qd(v) for k, v in blk["self"].items()}
    if "cross" in blk:
        nb["cross"] = {k: qd(v) for k, v in blk["cross"].items()}
    nb["fc1"] = qd(blk["fc1"])
    nb["fc2"] = qd(blk["fc2"])
    return nb


def _quantize_bart_block_specs(blk: Params, mode: str = "int8") -> Params:
    ds = _MODES[mode][3]
    nb = dict(blk)
    nb["self"] = {k: ds(v) for k, v in blk["self"].items()}
    if "cross" in blk:
        nb["cross"] = {k: ds(v) for k, v in blk["cross"].items()}
    nb["fc1"] = ds(blk["fc1"])
    nb["fc2"] = ds(blk["fc2"])
    return nb


def quantize_bart(params: Params, mode: str = "int8") -> Params:
    """HF-BART tree (``models.bart.from_state_dict``): QKVO + FFN dense dicts
    per layer; embeddings / position tables / LNs / final_logits_bias stay
    f32 (the lm head is the tied embedding)."""
    out = dict(params)
    for branch in ("enc", "dec"):
        br = dict(params[branch])
        br["layers"] = [
            _quantize_bart_block(b, mode) for b in params[branch]["layers"]
        ]
        out[branch] = br
    return out


def quantize_bart_specs(specs: Params, mode: str = "int8") -> Params:
    out = dict(specs)
    for branch in ("enc", "dec"):
        br = dict(specs[branch])
        br["layers"] = [
            _quantize_bart_block_specs(b, mode)
            for b in specs[branch]["layers"]
        ]
        out[branch] = br
    return out


def _quantize_t5_block(blk: Params, mode: str = "int8") -> Params:
    qw = _MODES[mode][0]
    nb = dict(blk)
    nb["attn"] = {
        k: qw(w, (0,)) for k, w in blk["attn"].items()
    }
    if "cross" in blk:
        nb["cross"] = {
            k: qw(w, (0,)) for k, w in blk["cross"].items()
        }
    nb["ffn"] = {
        k: qw(w, (0,)) for k, w in blk["ffn"].items()
    }
    return nb


def _quantize_t5_block_specs(blk: Params, mode: str = "int8") -> Params:
    ws = _MODES[mode][2]
    nb = dict(blk)
    nb["attn"] = {k: ws(s, (0,)) for k, s in blk["attn"].items()}
    if "cross" in blk:
        nb["cross"] = {k: ws(s, (0,)) for k, s in blk["cross"].items()}
    nb["ffn"] = {k: ws(s, (0,)) for k, s in blk["ffn"].items()}
    return nb


def quantize_t5(params: Params, mode: str = "int8") -> Params:
    """HF-T5 tree (``models.t5.from_state_dict``): bias-free QKVO + FFN bare
    matrices per layer; embeddings, RMSNorm scales, relative-bias tables, and
    the (possibly untied) lm head stay f32."""
    out = dict(params)
    for branch in ("enc", "dec"):
        br = dict(params[branch])
        br["layers"] = [
            _quantize_t5_block(b, mode) for b in params[branch]["layers"]
        ]
        out[branch] = br
    return out


def quantize_t5_specs(specs: Params, mode: str = "int8") -> Params:
    out = dict(specs)
    for branch in ("enc", "dec"):
        br = dict(specs[branch])
        br["layers"] = [
            _quantize_t5_block_specs(b, mode)
            for b in specs[branch]["layers"]
        ]
        out[branch] = br
    return out


def _quantize_stacked(w: jax.Array, wkey: str) -> Params:
    """Symmetric per-output-channel int8 of a layer-stacked ``[n, in, out]``
    leaf (or ``[n, experts, in, out]``), ON THE DEVICE (the decoder family
    builds its weights there: 2.6 G of them through host numpy would be
    minutes): the same scale and rounding rule as :func:`quantize_weight`,
    contracting the axis before the last."""
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-2, keepdims=True),
                        _EPS) / _QMAX
    w_q = jnp.clip(jnp.round(wf / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return {wkey: w_q, "w_scale": scale[..., 0, :]}


def quantize_decoder_lm(params: Params, mode: str = "int8") -> Params:
    """The decoder language-model family (``models/decoder_lm.py``): the
    projection, FFN and expert leaves (``LINEAR_LEAVES``) of every scanned
    layer group become int8 tables (W8A8 or W8A16 by ``mode``; the held
    experts' tables are multiplied out again where the grouped matmul takes
    them: their weights are rounded, their activations not); embedding,
    head, norms, the retention gate, the indexer's head weights and the
    state-space scan's convolution and constants stay.
    ``params`` is CONSUMED: each bf16 leaf is dropped as its table is made,
    so the peak is one leaf's float32 copy over the stored model."""
    from agent_tpu.models.decoder_lm import LINEAR_LEAVES

    wkey = "w_q" if mode == "int8" else "w8"
    fn = jax.jit(_quantize_stacked, static_argnames="wkey")
    for group in ("layers", "expert_layers"):
        layer_leaves = params.get(group, {})   # consumed: leaf by leaf
        for name in LINEAR_LEAVES:
            if name in layer_leaves:
                layer_leaves[name] = fn(layer_leaves.pop(name), wkey=wkey)
    return params


# Family name (the ops' model-family strings) → (params, specs) transformer
# pair. Single dispatch point so the two model ops cannot drift (the same
# anti-drift rule as ops/_model_common.py).
_FAMILY_QUANTIZERS = {
    "encoder": lambda: (quantize_encoder, quantize_encoder_specs),
    "bert": lambda: (quantize_bert, quantize_bert_specs),
    "seq2seq": lambda: (quantize_seq2seq, quantize_seq2seq_specs),
    "bart": lambda: (quantize_bart, quantize_bart_specs),
    "t5": lambda: (quantize_t5, quantize_t5_specs),
    # No tp specs for this family yet: weights are made replicated.
    "decoder_lm": lambda: (quantize_decoder_lm, lambda specs, mode: specs),
}


def quantize_for_family(family: str, params: Params,
                        mode: str = "int8") -> Params:
    return _FAMILY_QUANTIZERS[family]()[0](params, mode)


def quantize_specs_for_family(family: str, specs: Params,
                              mode: str = "int8") -> Params:
    return _FAMILY_QUANTIZERS[family]()[1](specs, mode)


# quant values that trigger the build-time tree transform (everything but
# "none"); _model_common.maybe_quantize_params gates on membership here so a
# new mode needs exactly one registration (this tuple + _MODES).
QUANTIZED_MODES = ("int8", "w8a16")
VALID_QUANT = ("none",) + QUANTIZED_MODES


def validate_quant(value: str) -> str:
    """Payload/env ``quant`` value → validated, or ValueError (soft error)."""
    if value not in VALID_QUANT:
        raise ValueError(
            f"quant must be one of {VALID_QUANT}, got {value!r}"
        )
    return value
