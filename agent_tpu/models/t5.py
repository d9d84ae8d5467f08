"""HF-T5-compatible seq2seq: the checkpoint family BASELINE.json names for
the summarize slot ("map_summarize.py T5-large seq2seq").

Faithful to ``transformers``' T5: RMSNorm (no mean subtraction, no bias),
pre-LN residual blocks, **relative position biases** (bucketed, learned in
the first block of each stack and shared by the rest, bidirectional for the
encoder / causal for the decoder), unscaled attention (the 1/√d is folded
into T5's init), ReLU or gated-GELU FFN per ``feed_forward_proj``, and a
lm_head tied to the embedding with the ``d_model**-0.5`` output scale (or an
untied head when the checkpoint has one). Differential-tested against
``transformers`` (logits and generated tokens) in ``tests/test_t5.py``.

Generation runs on the shared scan engines (``models/decoding.py``) with KV
caches; the decoder's causal relative bias is precomputed for the static
decode length and sliced per step.

Serving text through ``map_summarize`` additionally needs the checkpoint's
SentencePiece tokenizer: gated on the ``sentencepiece`` package
(:func:`hf_spm`), with a clear error when absent — the model/ids path works
without it.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from agent_tpu.models.layers import NEG_INF, Params


@dataclass(frozen=True)
class T5Config:
    """Mirror of the HF T5 ``config.json`` fields the forward needs."""

    vocab_size: int = 32128
    d_model: int = 512
    d_kv: int = 64              # per-head dim (decoupled from d_model in T5)
    n_heads: int = 8
    n_enc_layers: int = 6
    n_dec_layers: int = 6
    d_ff: int = 2048
    rel_buckets: int = 32
    rel_max_distance: int = 128
    gated_ffn: bool = False     # v1.1 "gated-gelu"; v1.0 is plain relu
    tie_word_embeddings: bool = True
    pad_id: int = 0
    eos_id: int = 1
    decoder_start_id: int = 0   # T5 starts decode from pad
    layer_norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # "int8": serve with W8A8 quantized matmuls (models.quant); "w8a16":
    # weight-only int8 — the decode-mode recipe (int8-resident weights
    # dequantized in-register, activations stay at dtype).
    quant: str = "none"

    # Uniform serving-config view (map_summarize reads these off any family).
    # T5 has no position table — length is bounded by memory, not params;
    # 1024 mirrors the reference's input truncation.
    max_src_len: int = 1024
    max_tgt_len: int = 1024

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @classmethod
    def from_hf_json(cls, path: str, **overrides) -> "T5Config":
        try:
            with open(path) as f:
                hf = json.load(f)
        except json.JSONDecodeError as exc:
            raise RuntimeError(
                f"unreadable checkpoint config.json at {path}: {exc}"
            ) from exc
        if hf.get("model_type") not in (None, "t5"):
            raise RuntimeError(
                f"not a T5 checkpoint (model_type={hf.get('model_type')!r})"
            )
        proj = hf.get("feed_forward_proj", "relu")
        # Whitelist, don't approximate: a 'gelu' or 'gated-silu' checkpoint
        # served through the wrong activation would return ok=true with wrong
        # numerics — fail loudly as a retryable integrity error instead (same
        # contract as the model_type check above).
        if proj not in ("relu", "gated-gelu"):
            raise RuntimeError(
                f"unsupported T5 feed_forward_proj={proj!r} "
                "(supported: 'relu', 'gated-gelu')"
            )
        fields = dict(
            vocab_size=hf["vocab_size"],
            d_model=hf["d_model"],
            d_kv=hf["d_kv"],
            n_heads=hf["num_heads"],
            n_enc_layers=hf["num_layers"],
            n_dec_layers=hf.get("num_decoder_layers", hf["num_layers"]),
            d_ff=hf["d_ff"],
            rel_buckets=hf.get("relative_attention_num_buckets", 32),
            rel_max_distance=hf.get("relative_attention_max_distance", 128),
            gated_ffn=proj.startswith("gated"),
            tie_word_embeddings=hf.get("tie_word_embeddings", True),
            pad_id=hf.get("pad_token_id", 0),
            eos_id=hf.get("eos_token_id", 1),
            decoder_start_id=hf.get(
                "decoder_start_token_id", hf.get("pad_token_id", 0)
            ),
            layer_norm_eps=hf.get("layer_norm_epsilon", 1e-6),
        )
        fields.update(overrides)
        return cls(**fields)


def _rms(p: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """T5LayerNorm: scale / rms, no mean subtraction, no bias; f32 stats."""
    x32 = x.astype(jnp.float32)
    var = (x32 * x32).mean(axis=-1, keepdims=True)
    return (p * (x32 * jax.lax.rsqrt(var + eps))).astype(x.dtype)


def _dense(w: jax.Array, x: jax.Array, dtype) -> jax.Array:
    """Bias-free linear (T5 has no biases anywhere); w is [in, out]."""
    from agent_tpu.models import quant

    if quant.is_quantized(w):  # int8 leaf (models.quant convention)
        return quant.qdense(w, x, dtype)
    if quant.is_weight_only(w):  # W8A16 leaf: decode-mode weight-only int8
        return quant.wdense(w, x, dtype)
    return jnp.dot(x.astype(dtype), w.astype(dtype))


def relative_position_bucket(
    relative_position: jax.Array, bidirectional: bool,
    num_buckets: int, max_distance: int,
) -> jax.Array:
    """HF ``_relative_position_bucket``, verbatim semantics.

    ``relative_position`` = key_pos − query_pos (any int array).
    """
    rel = relative_position
    bucket = jnp.zeros_like(rel)
    if bidirectional:
        num_buckets //= 2
        bucket = bucket + (rel > 0).astype(rel.dtype) * num_buckets
        rel = jnp.abs(rel)
    else:
        rel = -jnp.minimum(rel, 0)
    max_exact = num_buckets // 2
    is_small = rel < max_exact
    rel_f = jnp.maximum(rel.astype(jnp.float32), 1.0)
    large = max_exact + (
        jnp.log(rel_f / max_exact) / np.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(rel.dtype)
    large = jnp.minimum(large, num_buckets - 1)
    return bucket + jnp.where(is_small, rel, large)


def _position_bias(
    rel_bias: jax.Array,       # [num_buckets, H]
    q_pos: jax.Array,          # [Lq] int32 absolute query positions
    k_pos: jax.Array,          # [Lk] int32 absolute key positions
    bidirectional: bool,
    cfg: T5Config,
) -> jax.Array:
    """[1, H, Lq, Lk] additive attention bias (f32)."""
    rel = k_pos[None, :] - q_pos[:, None]                  # [Lq, Lk]
    buckets = relative_position_bucket(
        rel, bidirectional, cfg.rel_buckets, cfg.rel_max_distance
    )
    bias = rel_bias.astype(jnp.float32)[buckets]           # [Lq, Lk, H]
    return bias.transpose(2, 0, 1)[None]                   # [1, H, Lq, Lk]


def _attn(blk: Params, q_in, kv_in, bias, cfg, *, Lq: int, Lk: int):
    """T5 attention: UNSCALED scores + additive ``bias`` (position bias and
    padding mask pre-combined, f32), softmax in f32. blk = {q, k, v, o}."""
    dtype = cfg.compute_dtype
    B = q_in.shape[0]

    def heads(t, L):
        return t.reshape(B, L, cfg.n_heads, cfg.d_kv).transpose(0, 2, 1, 3)

    q = heads(_dense(blk["q"], q_in, dtype), Lq)
    k = heads(_dense(blk["k"], kv_in, dtype), Lk)
    v = heads(_dense(blk["v"], kv_in, dtype), Lk)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, Lq, cfg.n_heads * cfg.d_kv)
    return _dense(blk["o"], ctx, dtype)


def _ffn(blk: Params, x, cfg) -> jax.Array:
    dtype = cfg.compute_dtype
    if cfg.gated_ffn:
        h = jax.nn.gelu(
            _dense(blk["wi_0"], x, dtype).astype(jnp.float32),
            approximate=True,  # HF gated-gelu uses the tanh approximation
        ).astype(dtype) * _dense(blk["wi_1"], x, dtype)
    else:
        h = jax.nn.relu(_dense(blk["wi"], x, dtype))
    return _dense(blk["wo"], h, cfg.compute_dtype)


def _pad_bias(mask: jax.Array) -> jax.Array:
    """[B, Lk] padding mask → additive [B, 1, 1, Lk] f32 bias."""
    return jnp.where(mask[:, None, None, :] > 0, 0.0, NEG_INF).astype(
        jnp.float32
    )


def encode(params: Params, src_ids: jax.Array, src_mask: jax.Array,
           cfg: T5Config, kernel=None) -> jax.Array:
    """Encoder stack → [B, Ls, d].

    Long-context path: with a ``kernel``, self-attention routes through the
    fused Pallas T5 kernel, which computes the bucketed relative-position
    bias per tile in VMEM instead of materializing the [H, Ls, Ls] bias in
    HBM. The kernel comes from ``TpuRuntime.t5_attention_kernel()`` — the
    runtime decides from its own devices and hands over the mesh-aware
    wrapper (batch over dp, heads over tp) — or not at all: ``kernel=None``
    is the dense path. The kernel declines unsupported shapes at trace time
    (returns None) and the layer falls back to the dense path with a lazily
    built dense bias; kernel == dense is asserted in tests.
    """
    dtype = cfg.compute_dtype
    B, L = src_ids.shape
    x = jnp.asarray(params["embed"]).astype(dtype)[src_ids]
    rel_bias = jnp.asarray(params["enc"]["rel_bias"])
    mask4 = src_mask[:, None, None, :].astype(jnp.int32)
    dense_bias = None  # built only when the dense path is taken

    def heads(t):
        return t.reshape(B, L, cfg.n_heads, cfg.d_kv).transpose(0, 2, 1, 3)

    for i, blk in enumerate(params["enc"]["layers"]):
        h = _rms(blk["ln1"], x, cfg.layer_norm_eps)
        a = blk["attn"]
        q = heads(_dense(a["q"], h, dtype))
        k = heads(_dense(a["k"], h, dtype))
        v = heads(_dense(a["v"], h, dtype))
        ctx = None
        if kernel is not None:
            ctx = kernel(
                q, k, v, mask4, rel_bias,
                bidirectional=True, max_distance=cfg.rel_max_distance,
                scale=1.0,
            )
            if i == 0 and ctx is None:
                # The gate is shape-static and identical for every layer:
                # decide once so fallback traces don't re-attempt per layer
                # (and the selection counter ticks once per program).
                kernel = None
        if ctx is None:
            if dense_bias is None:
                pos = jnp.arange(L, dtype=jnp.int32)
                dense_bias = _position_bias(
                    rel_bias, pos, pos, True, cfg
                ) + _pad_bias(src_mask)
            # Dense path on the SAME q/k/v (T5: unscaled scores + bias).
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(
                jnp.float32
            ) + dense_bias
            probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, L, cfg.n_heads * cfg.d_kv)
        x = x + _dense(a["o"], ctx, dtype)
        h = _rms(blk["ln2"], x, cfg.layer_norm_eps)
        x = x + _ffn(blk["ffn"], h, cfg)
    return _rms(params["enc"]["ln_f"], x, cfg.layer_norm_eps)


def _lm_logits(params: Params, x: jax.Array, cfg: T5Config) -> jax.Array:
    dtype = cfg.compute_dtype
    if cfg.tie_word_embeddings:
        x = x * (cfg.d_model ** -0.5)
        w = jnp.asarray(params["embed"]).astype(dtype).T
    else:
        w = jnp.asarray(params["lm_head"]).astype(dtype)
    return jnp.dot(x.astype(dtype), w).astype(jnp.float32)


def decode_full(params: Params, tgt_ids: jax.Array, enc_out: jax.Array,
                enc_mask: jax.Array, cfg: T5Config) -> jax.Array:
    """Teacher-forced decoder → lm logits [B, Lt, V] — the differential-test
    surface vs HF ``T5ForConditionalGeneration`` logits."""
    dtype = cfg.compute_dtype
    B, Lt = tgt_ids.shape
    Ls = enc_out.shape[1]
    x = jnp.asarray(params["embed"]).astype(dtype)[tgt_ids]
    pos = jnp.arange(Lt, dtype=jnp.int32)
    causal = jnp.where(
        pos[None, :] <= pos[:, None], 0.0, NEG_INF
    ).astype(jnp.float32)[None, None]
    self_bias = _position_bias(
        params["dec"]["rel_bias"], pos, pos, False, cfg
    ) + causal
    cross_bias = _pad_bias(enc_mask)  # no positional bias on cross-attn
    for blk in params["dec"]["layers"]:
        h = _rms(blk["ln1"], x, cfg.layer_norm_eps)
        x = x + _attn(blk["attn"], h, h, self_bias, cfg, Lq=Lt, Lk=Lt)
        h = _rms(blk["ln_x"], x, cfg.layer_norm_eps)
        x = x + _attn(blk["cross"], h, enc_out, cross_bias, cfg,
                      Lq=Lt, Lk=Ls)
        h = _rms(blk["ln2"], x, cfg.layer_norm_eps)
        x = x + _ffn(blk["ffn"], h, cfg)
    x = _rms(params["dec"]["ln_f"], x, cfg.layer_norm_eps)
    return _lm_logits(params, x, cfg)


# ---- cached single-step decode (generation) ----


def _init_self_caches(cfg: T5Config, batch: int, max_new: int) -> list:
    dtype = cfg.compute_dtype
    return [
        {
            "k": jnp.zeros((batch, cfg.n_heads, max_new, cfg.d_kv), dtype=dtype),
            "v": jnp.zeros((batch, cfg.n_heads, max_new, cfg.d_kv), dtype=dtype),
        }
        for _ in range(cfg.n_dec_layers)
    ]


def _init_cross_kv(params: Params, enc_out: jax.Array, cfg: T5Config) -> list:
    """Cross-attention K/V computed once (loop-invariant; closed over by the
    step function, NOT carried through the scan — see models/bart.py)."""
    B, Ls, _ = enc_out.shape
    dtype = cfg.compute_dtype

    def heads(t):
        return t.reshape(B, Ls, cfg.n_heads, cfg.d_kv).transpose(0, 2, 1, 3)

    return [
        {
            "k": heads(_dense(blk["cross"]["k"], enc_out, dtype)),
            "v": heads(_dense(blk["cross"]["v"], enc_out, dtype)),
        }
        for blk in params["dec"]["layers"]
    ]


def decode_step(params: Params, tok: jax.Array, step: jax.Array,
                self_caches: list, cross_kv: list, dec_bias: jax.Array,
                enc_mask_bias: jax.Array, cfg: T5Config,
                max_new: int) -> Tuple[jax.Array, list]:
    """One cached decoder step → (logits [B, V] f32, self_caches).

    ``dec_bias`` is the precomputed causal relative bias [1, H, T, T] for the
    static decode length; row ``step`` is sliced per step."""
    dtype = cfg.compute_dtype
    B = tok.shape[0]
    x = jnp.asarray(params["embed"]).astype(dtype)[tok][:, None]  # [B, 1, d]
    # [1, H, 1, T]: this step's row of the causal+relative bias. Positions
    # > step already carry NEG_INF from the causal term.
    bias_row = jax.lax.dynamic_slice_in_dim(dec_bias, step, 1, axis=2)
    new_self = []
    for blk, s_kv, x_kv in zip(
        params["dec"]["layers"], self_caches, cross_kv
    ):
        h = _rms(blk["ln1"], x, cfg.layer_norm_eps)
        a = blk["attn"]
        q = _dense(a["q"], h, dtype).reshape(B, 1, cfg.n_heads, cfg.d_kv)
        q = q.transpose(0, 2, 1, 3)
        k1 = _dense(a["k"], h, dtype).reshape(B, 1, cfg.n_heads, cfg.d_kv)
        v1 = _dense(a["v"], h, dtype).reshape(B, 1, cfg.n_heads, cfg.d_kv)
        k = jax.lax.dynamic_update_slice(
            s_kv["k"], k1.transpose(0, 2, 1, 3), (0, 0, step, 0)
        )
        v = jax.lax.dynamic_update_slice(
            s_kv["v"], v1.transpose(0, 2, 1, 3), (0, 0, step, 0)
        )
        new_self.append({"k": k, "v": v})
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
        probs = jax.nn.softmax(scores + bias_row, axis=-1).astype(dtype)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, 1, cfg.n_heads * cfg.d_kv)
        x = x + _dense(a["o"], ctx, dtype)

        h = _rms(blk["ln_x"], x, cfg.layer_norm_eps)
        c = blk["cross"]
        qx = _dense(c["q"], h, dtype).reshape(B, 1, cfg.n_heads, cfg.d_kv)
        qx = qx.transpose(0, 2, 1, 3)
        xs = jnp.einsum("bhqd,bhkd->bhqk", qx, x_kv["k"]).astype(jnp.float32)
        xp = jax.nn.softmax(xs + enc_mask_bias, axis=-1).astype(dtype)
        cctx = jnp.einsum("bhqk,bhkd->bhqd", xp, x_kv["v"])
        cctx = cctx.transpose(0, 2, 1, 3).reshape(
            B, 1, cfg.n_heads * cfg.d_kv
        )
        x = x + _dense(c["o"], cctx, dtype)

        h = _rms(blk["ln2"], x, cfg.layer_norm_eps)
        x = x + _ffn(blk["ffn"], h, cfg)
    x = _rms(params["dec"]["ln_f"], x, cfg.layer_norm_eps)
    return _lm_logits(params, x, cfg)[:, 0], new_self


def generate(
    params: Params,
    src_ids: jax.Array,
    src_mask: jax.Array,
    cfg: T5Config,
    max_new_tokens: int,
    num_beams: int = 1,
    length_penalty: float = 1.0,
    early_stopping: bool = False,
    min_length: int = 0,
    kernel=None,
) -> Tuple[jax.Array, jax.Array]:
    """Greedy (or beam) generation via the shared scan engines. Returns
    (tokens [B, T], lengths [B]); tokens after EOS are the pad id.

    ``kernel`` routes the encoder pass through a fused T5 attention kernel
    (see :func:`encode` — pass ``runtime.t5_attention_kernel()`` for the
    mesh-aware wrapper); the decoder's incremental steps keep the dense
    bias path (per-step Lq == 1 is outside the kernel's contract)."""
    from agent_tpu.models.decoding import beam_scan, greedy_scan

    B = src_ids.shape[0]
    T = max_new_tokens
    enc_out = encode(params, src_ids, src_mask, cfg, kernel=kernel)
    pos = jnp.arange(T, dtype=jnp.int32)
    causal = jnp.where(
        pos[None, :] <= pos[:, None], 0.0, NEG_INF
    ).astype(jnp.float32)[None, None]
    dec_bias = _position_bias(
        params["dec"]["rel_bias"], pos, pos, False, cfg
    ) + causal

    def run(enc_out, enc_mask, batch):
        cross_kv = _init_cross_kv(params, enc_out, cfg)
        mask_bias = _pad_bias(enc_mask)

        def step_fn(tok, step, caches):
            return decode_step(
                params, tok, step, caches, cross_kv, dec_bias, mask_bias,
                cfg, T,
            )

        return step_fn, _init_self_caches(cfg, batch, T)

    if num_beams <= 1:
        step_fn, caches = run(enc_out, src_mask, B)
        return greedy_scan(
            step_fn, caches, B, T,
            start_id=cfg.decoder_start_id, eos_id=cfg.eos_id,
            pad_id=cfg.pad_id, min_length=min_length,
        )
    K = num_beams
    step_fn, caches = run(
        jnp.repeat(enc_out, K, axis=0), jnp.repeat(src_mask, K, axis=0),
        B * K,
    )
    return beam_scan(
        step_fn, caches, B, cfg.vocab_size, T,
        num_beams=K, length_penalty=length_penalty,
        early_stopping=early_stopping, min_length=min_length,
        start_id=cfg.decoder_start_id, eos_id=cfg.eos_id,
        pad_id=cfg.pad_id,
    )


# ---- weight import ----


def _w(sd, key: str) -> np.ndarray:
    """HF Linear weight [out, in] → ours [in, out]."""
    return np.ascontiguousarray(sd[key].T)


def _attn_from(sd, prefix: str) -> Params:
    return {
        "q": _w(sd, f"{prefix}.q.weight"),
        "k": _w(sd, f"{prefix}.k.weight"),
        "v": _w(sd, f"{prefix}.v.weight"),
        "o": _w(sd, f"{prefix}.o.weight"),
    }


def _ffn_from(sd, prefix: str, gated: bool) -> Params:
    if gated:
        return {
            "wi_0": _w(sd, f"{prefix}.wi_0.weight"),
            "wi_1": _w(sd, f"{prefix}.wi_1.weight"),
            "wo": _w(sd, f"{prefix}.wo.weight"),
        }
    return {
        "wi": _w(sd, f"{prefix}.wi.weight"),
        "wo": _w(sd, f"{prefix}.wo.weight"),
    }


def from_state_dict(sd: Dict[str, np.ndarray], cfg: T5Config) -> Params:
    """HF T5 state dict → our param pytree (``T5Model`` /
    ``T5ForConditionalGeneration`` naming)."""
    sd = {k: np.asarray(v) for k, v in sd.items()}

    def branch(name: str, n_layers: int, cross: bool) -> Params:
        out: Params = {
            "rel_bias": sd[
                f"{name}.block.0.layer.0.SelfAttention"
                ".relative_attention_bias.weight"
            ],
            "layers": [],
            "ln_f": sd[f"{name}.final_layer_norm.weight"],
        }
        ff_idx = 2 if cross else 1
        for i in range(n_layers):
            p = f"{name}.block.{i}"
            blk: Params = {
                "attn": _attn_from(sd, f"{p}.layer.0.SelfAttention"),
                "ln1": sd[f"{p}.layer.0.layer_norm.weight"],
                "ffn": _ffn_from(
                    sd, f"{p}.layer.{ff_idx}.DenseReluDense", cfg.gated_ffn
                ),
                "ln2": sd[f"{p}.layer.{ff_idx}.layer_norm.weight"],
            }
            if cross:
                blk["cross"] = _attn_from(sd, f"{p}.layer.1.EncDecAttention")
                blk["ln_x"] = sd[f"{p}.layer.1.layer_norm.weight"]
            out["layers"].append(blk)
        return out

    params: Params = {
        "embed": sd["shared.weight"],
        "enc": branch("encoder", cfg.n_enc_layers, cross=False),
        "dec": branch("decoder", cfg.n_dec_layers, cross=True),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = _w(sd, "lm_head.weight")
    return params


def is_hf_t5_dir(path: str) -> bool:
    cfg_path = os.path.join(path, "config.json")
    if not os.path.isdir(path) or not os.path.exists(cfg_path):
        return False
    try:
        with open(cfg_path) as f:
            return json.load(f).get("model_type") == "t5"
    except Exception:  # noqa: BLE001 — unreadable json resolves at load time
        return True  # claim it; load_hf_dir surfaces the real error


def load_hf_dir(path: str, **config_overrides) -> Tuple[T5Config, Params]:
    """Load (config, params) from a local HF T5 checkpoint directory."""
    cfg = T5Config.from_hf_json(
        os.path.join(path, "config.json"), **config_overrides
    )
    st_path = os.path.join(path, "model.safetensors")
    bin_path = os.path.join(path, "pytorch_model.bin")
    if os.path.exists(st_path):
        try:
            from safetensors.numpy import load_file

            return cfg, from_state_dict(load_file(st_path), cfg)
        except ImportError:
            pass
    if not os.path.exists(bin_path):
        raise FileNotFoundError(
            f"no model.safetensors or pytorch_model.bin under {path}"
        )
    import torch

    raw = torch.load(bin_path, map_location="cpu", weights_only=True)
    return cfg, from_state_dict({k: v.numpy() for k, v in raw.items()}, cfg)


# ---- tokenizer (gated on sentencepiece) ----

# Same bounded mtime-keyed cache discipline as the BPE loader (models/bpe.py):
# a pipelined drain calls the tokenizer per shard in both stage and finalize,
# and re-parsing an ~800 KB spiece.model on the host hot path is pure waste.
_SPM_CACHE_MAX = 8
_spm_cache: Dict[tuple, object] = {}
_spm_order: List[tuple] = []
_spm_lock = threading.Lock()


def hf_spm(path: str):
    """The checkpoint's SentencePiece tokenizer (``spiece.model``), cached
    per (directory, mtime). Needs the ``sentencepiece`` package — a clear,
    actionable error when absent (this environment does not bundle it)."""
    try:
        import sentencepiece as spm
    except ImportError as exc:
        raise RuntimeError(
            "serving a T5 checkpoint's text requires the sentencepiece "
            "package (pip install sentencepiece); the ids-level model path "
            "works without it"
        ) from exc
    model_path = os.path.join(path, "spiece.model")
    if not os.path.exists(model_path):
        raise ValueError(f"T5 checkpoint {path} has no spiece.model")
    key = (os.path.abspath(path), os.path.getmtime(model_path))
    with _spm_lock:
        hit = _spm_cache.get(key)
        if hit is not None:
            return hit
    sp = spm.SentencePieceProcessor()
    sp.Load(model_path)
    with _spm_lock:
        _spm_cache[key] = sp
        _spm_order.append(key)
        while len(_spm_order) > _SPM_CACHE_MAX:
            _spm_cache.pop(_spm_order.pop(0), None)
    return sp


def encode_pad_batch(
    sp, texts, cfg: T5Config, batch_buckets, length_buckets
) -> Tuple[np.ndarray, np.ndarray]:
    """``pieces </s>`` per row (the HF T5 tokenizer's convention) →
    (ids [B, L] int32, lengths [B] int32) with bucketed static shapes;
    bucket truncation keeps the trailing ``</s>`` (same semantics as
    ``models.bart.encode_pad_batch``)."""
    from agent_tpu.models.tokenizer import bucket_length

    max_len = cfg.max_src_len
    rows: List[List[int]] = [
        sp.EncodeAsIds(t)[: max_len - 1] + [cfg.eos_id] for t in texts
    ]
    L = bucket_length(min(max(len(r) for r in rows), max_len), length_buckets)
    B = bucket_length(len(rows), batch_buckets)
    ids = np.full((B, L), cfg.pad_id, dtype=np.int32)
    lengths = np.zeros(B, dtype=np.int32)
    for r, row in enumerate(rows):
        if len(row) > L:
            row = row[: L - 1] + [cfg.eos_id]
        ids[r, : len(row)] = row
        lengths[r] = len(row)
    return ids, lengths
