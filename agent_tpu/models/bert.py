"""HF-BERT-compatible encoder: serve *pretrained* checkpoints TPU-side.

The reference's capability story was serving pretrained weights — a compiled
artifact at a well-known path (reference ``ops/_tpu_runtime.py:23-31``) and a
hub model for summarize (``ops/map_summarize.py:29-32``). This module is that
story for the classify family: a user points ``model_path`` at a standard
Hugging Face BERT checkpoint **directory** (``config.json`` +
``pytorch_model.bin`` / ``model.safetensors`` + ``vocab.txt``) and the op
serves it — same weights, same numerics (differential-tested against
``transformers``' reference implementation), but batched, jitted, and sharded
on the mesh instead of row-at-a-time on host torch.

Architecture notes (faithful to BERT, deliberately NOT our pre-LN encoder):
post-LN residuals, learned position + token-type embeddings, erf-exact GELU,
tanh pooler over [CLS], optional sequence-classification head. The attention
core goes through the same injectable ``attn_fn`` contract as the in-house
models, so the Pallas flash kernel and ring attention compose unchanged.

No network access is assumed anywhere: checkpoints load from local disk only.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from agent_tpu.models import layers
from agent_tpu.models.layers import Params, dot_product_attention


@dataclass(frozen=True)
class BertConfig:
    """Mirror of the HF ``config.json`` fields the forward needs."""

    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab: int = 2
    layer_norm_eps: float = 1e-12
    num_labels: int = 1000
    dtype: str = "bfloat16"
    # "int8": serve with W8A8 quantized matmuls (models.quant) — execution
    # mode, not a different artifact; the checkpoint weights are quantized
    # per-channel at load. "w8a16": weight-only int8, activations at dtype.
    quant: str = "none"

    # Uniform serving-config view (the classify op reads these off any family).
    @property
    def max_len(self) -> int:
        return self.max_position

    @property
    def n_classes(self) -> int:
        return self.num_labels

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @classmethod
    def from_hf_json(cls, path: str, **overrides) -> "BertConfig":
        try:
            with open(path) as f:
                hf = json.load(f)
        except json.JSONDecodeError as exc:
            # NOT a ValueError to callers: JSONDecodeError subclasses it, and
            # the op's soft-error handler would silently drop the shard as
            # caller bad_input. A corrupt checkpoint is a retryable
            # integrity failure, not a payload problem.
            raise RuntimeError(
                f"unreadable checkpoint config.json at {path}: {exc}"
            ) from exc
        if hf.get("model_type") not in (None, "bert"):
            raise RuntimeError(
                f"not a BERT checkpoint (model_type={hf.get('model_type')!r}"
                " — map_classify_tpu serves model_type=bert; map_summarize "
                "serves BART)"
            )
        fields = dict(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            intermediate_size=hf["intermediate_size"],
            max_position=hf["max_position_embeddings"],
            type_vocab=hf.get("type_vocab_size", 2),
            layer_norm_eps=hf.get("layer_norm_eps", 1e-12),
        )
        if "num_labels" in hf:
            fields["num_labels"] = hf["num_labels"]
        elif hf.get("id2label"):
            fields["num_labels"] = len(hf["id2label"])
        fields.update(overrides)
        return cls(**fields)


def _ln(params: Params, x: jax.Array, eps: float) -> jax.Array:
    """LayerNorm in f32 (BERT's eps differs from our in-house default)."""
    x32 = x.astype(jnp.float32)
    mu = x32.mean(axis=-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(axis=-1, keepdims=True)
    out = (x32 - mu) / jnp.sqrt(var + eps)
    return (out * params["scale"] + params["bias"]).astype(x.dtype)


def forward(
    params: Params,
    ids: jax.Array,        # [B, L] int32 token ids (wordpiece)
    mask: jax.Array,       # [B, L] int32 padding mask (1 = real)
    cfg: BertConfig,
    attn_fn=dot_product_attention,
) -> jax.Array:
    """Sequence-classification logits [B, num_labels] (f32).

    Matches ``transformers.BertModel`` + pooler + linear head: embeddings
    (word + learned position + token type 0) → post-LN transformer stack →
    tanh pooler over [CLS] → head. Softmax accumulation and LayerNorms run
    in f32 regardless of compute dtype.
    """
    dtype = cfg.compute_dtype
    B, L = ids.shape
    emb = params["embed"]
    x = (
        emb["word"].astype(dtype)[ids]
        + emb["pos"][:L].astype(dtype)[None]
        + emb["type"][0].astype(dtype)[None, None]
    )
    x = _ln(emb["ln"], x, cfg.layer_norm_eps)

    attn_mask = layers.pad_mask_to_attn(mask)
    d_head = cfg.hidden_size // cfg.num_heads

    def split_heads(t):
        return t.reshape(B, L, cfg.num_heads, d_head).transpose(0, 2, 1, 3)

    whole_row = layers.whole_row_entry(
        attn_fn, B, L, L, cfg.num_heads, d_head, attn_mask, dtype
    )
    for blk in params["layers"]:
        a = blk["attn"]
        if whole_row is not None:
            # [B, L, hidden] is what the projections write: no head split.
            ctx = whole_row(
                *(layers.dense(a[n], x, dtype) for n in ("q", "k", "v")),
                attn_mask, n_heads=cfg.num_heads,
            )
        else:
            q = split_heads(layers.dense(a["q"], x, dtype))
            k = split_heads(layers.dense(a["k"], x, dtype))
            v = split_heads(layers.dense(a["v"], x, dtype))
            ctx = attn_fn(q, k, v, attn_mask)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(B, L, cfg.hidden_size)
        x = _ln(a["ln"], x + layers.dense(a["o"], ctx, dtype),
                cfg.layer_norm_eps)
        f = blk["ffn"]
        h = jax.nn.gelu(
            layers.dense(f["i"], x, dtype).astype(jnp.float32),
            approximate=False,
        ).astype(dtype)
        x = _ln(f["ln"], x + layers.dense(f["o"], h, dtype),
                cfg.layer_norm_eps)

    pooled = jnp.tanh(
        layers.dense(params["pooler"], x[:, 0], dtype).astype(jnp.float32)
    ).astype(dtype)
    logits = layers.dense(params["head"], pooled, dtype)
    return logits.astype(jnp.float32)


# ---- weight import ----


def _dense_from(sd: Dict[str, np.ndarray], prefix: str) -> Params:
    """HF ``nn.Linear`` ([out, in] weight) → our ``{"w": [in, out], "b"}``."""
    return {
        "w": np.ascontiguousarray(sd[f"{prefix}.weight"].T),
        "b": sd[f"{prefix}.bias"],
    }


def _ln_from(sd: Dict[str, np.ndarray], prefix: str) -> Params:
    return {"scale": sd[f"{prefix}.weight"], "bias": sd[f"{prefix}.bias"]}


def from_state_dict(
    sd: Dict[str, np.ndarray], cfg: BertConfig, head_seed: str = "bert-head"
) -> Params:
    """HF BERT state dict (``BertModel`` or ``BertForSequenceClassification``
    naming — the ``bert.`` prefix is stripped) → our param pytree. A missing
    classification head gets deterministic random init seeded by
    ``head_seed`` (same contract as the in-house models: same id ⇒ same
    weights)."""
    sd = {
        (k[5:] if k.startswith("bert.") else k): np.asarray(v)
        for k, v in sd.items()
    }
    params: Params = {
        "embed": {
            "word": sd["embeddings.word_embeddings.weight"],
            "pos": sd["embeddings.position_embeddings.weight"],
            "type": sd["embeddings.token_type_embeddings.weight"],
            "ln": _ln_from(sd, "embeddings.LayerNorm"),
        },
        "layers": [],
        "pooler": _dense_from(sd, "pooler.dense"),
    }
    for i in range(cfg.num_layers):
        p = f"encoder.layer.{i}"
        params["layers"].append(
            {
                "attn": {
                    "q": _dense_from(sd, f"{p}.attention.self.query"),
                    "k": _dense_from(sd, f"{p}.attention.self.key"),
                    "v": _dense_from(sd, f"{p}.attention.self.value"),
                    "o": _dense_from(sd, f"{p}.attention.output.dense"),
                    "ln": _ln_from(sd, f"{p}.attention.output.LayerNorm"),
                },
                "ffn": {
                    "i": _dense_from(sd, f"{p}.intermediate.dense"),
                    "o": _dense_from(sd, f"{p}.output.dense"),
                    "ln": _ln_from(sd, f"{p}.output.LayerNorm"),
                },
            }
        )
    # The checkpoint's trained head is used only when it matches
    # cfg.num_labels (config.json's own num_labels always does — HF writes
    # them consistently). An explicit payload override to a different label
    # space gets a fresh seeded head instead: mixing a k-clamp from the
    # override with a differently-sized trained head would crash top_k on
    # device.
    cls_w = sd.get("classifier.weight")
    if cls_w is not None and cls_w.shape[0] == cfg.num_labels:
        params["head"] = _dense_from(sd, "classifier")
    else:
        key = layers.seed_from(head_seed)
        params["head"] = layers.init_dense(
            key, cfg.hidden_size, cfg.num_labels
        )
    return params


def is_hf_dir(path: str) -> bool:
    """A local HF checkpoint directory: has ``config.json``."""
    return os.path.isdir(path) and os.path.exists(
        os.path.join(path, "config.json")
    )


def load_hf_dir(path: str, **config_overrides) -> Tuple[BertConfig, Params]:
    """Load (config, params) from a local HF BERT checkpoint directory.

    Weights: ``model.safetensors`` if present (and the safetensors package
    is importable), else ``pytorch_model.bin`` via torch (CPU map). torch
    imports lazily — only checkpoints pay its import cost.
    """
    cfg = BertConfig.from_hf_json(
        os.path.join(path, "config.json"), **config_overrides
    )
    st_path = os.path.join(path, "model.safetensors")
    bin_path = os.path.join(path, "pytorch_model.bin")
    if os.path.exists(st_path):
        try:
            from safetensors.numpy import load_file

            sd = load_file(st_path)
            return cfg, from_state_dict(sd, cfg, head_seed=path)
        except ImportError:
            pass
    if not os.path.exists(bin_path):
        raise FileNotFoundError(
            f"no model.safetensors or pytorch_model.bin under {path}"
        )
    import torch

    raw = torch.load(bin_path, map_location="cpu", weights_only=True)
    sd = {k: v.numpy() for k, v in raw.items()}
    return cfg, from_state_dict(sd, cfg, head_seed=path)


# ---- tokenizer ----

_tok_cache: Dict[str, Any] = {}
_tok_lock = threading.Lock()


def hf_wordpiece(path: str):
    """The checkpoint's wordpiece tokenizer (``vocab.txt``), with the HF
    special ids resolved from the vocab itself ([CLS]/[SEP]/[PAD]/[UNK] live
    at whatever line the file puts them). Cached per directory."""
    with _tok_lock:
        tok = _tok_cache.get(path)
        if tok is not None:
            return tok
    from agent_tpu.models.tokenizer import WordPieceTokenizer

    vocab_path = os.path.join(path, "vocab.txt")
    if not os.path.exists(vocab_path):
        raise ValueError(f"HF checkpoint {path} has no vocab.txt")
    lowercase = True
    tcfg_path = os.path.join(path, "tokenizer_config.json")
    if os.path.exists(tcfg_path):
        with open(tcfg_path) as f:
            lowercase = bool(json.load(f).get("do_lower_case", True))
    tok = WordPieceTokenizer.from_file(vocab_path, lowercase=lowercase)
    # The class-level unk_id (3) is the in-house vocab's; remap it to the
    # checkpoint's own [UNK] line so OOV words don't encode as whatever
    # token happens to sit at line 3 (bert-base: '[unused2]').
    if "[UNK]" in tok.vocab:
        tok.unk_id = tok.vocab["[UNK]"]
    with _tok_lock:
        _tok_cache[path] = tok
    return tok


def _is_cjk(cp: int) -> bool:
    """HF BasicTokenizer's CJK ranges (each char becomes its own word)."""
    return (
        0x4E00 <= cp <= 0x9FFF or 0x3400 <= cp <= 0x4DBF
        or 0x20000 <= cp <= 0x2A6DF or 0x2A700 <= cp <= 0x2B73F
        or 0x2B740 <= cp <= 0x2B81F or 0x2B820 <= cp <= 0x2CEAF
        or 0xF900 <= cp <= 0xFAFF or 0x2F800 <= cp <= 0x2FA1F
    )


def basic_normalize(text: str, strip_accents: bool) -> str:
    """HF ``BasicTokenizer`` text normalization: accent stripping (NFD +
    drop combining marks — on by default when ``do_lower_case``) and CJK
    characters spaced out so each is one word. Without this, 'café' would
    miss the vocab and encode as [UNK] where transformers finds 'cafe'."""
    import unicodedata

    if strip_accents:
        text = "".join(
            c for c in unicodedata.normalize("NFD", text)
            if unicodedata.category(c) != "Mn"
        )
    if any(_is_cjk(ord(c)) for c in text):
        text = "".join(
            f" {c} " if _is_cjk(ord(c)) else c for c in text
        )
    return text


def encode_pad_batch(
    tok, texts, max_len: int, batch_buckets, length_buckets
) -> Tuple[np.ndarray, np.ndarray]:
    """[CLS] pieces [SEP] per row → (ids [B, L] int32, lengths [B] int32)
    with bucketed static shapes (same shape discipline as ``byte_encode_pad``;
    wordpiece is a Python loop — slower per row than the byte path, priced in
    by serving real vocab)."""
    from agent_tpu.models.tokenizer import bucket_length

    cls_id = tok.vocab.get("[CLS]")
    sep_id = tok.vocab.get("[SEP]")
    pad_id = tok.vocab.get("[PAD]", 0)
    if cls_id is None or sep_id is None:
        raise ValueError("vocab.txt lacks [CLS]/[SEP] tokens")
    rows = [
        [cls_id]
        + tok.encode(basic_normalize(t, tok.lowercase))[: max_len - 2]
        + [sep_id]
        for t in texts
    ]
    longest = max(len(r) for r in rows)
    L = bucket_length(min(longest, max_len), length_buckets)
    B = bucket_length(len(rows), batch_buckets)
    ids = np.full((B, L), pad_id, dtype=np.int32)
    lengths = np.zeros(B, dtype=np.int32)
    for r, row in enumerate(rows):
        if len(row) > L:
            # Bucket truncation keeps the trailing [SEP] (transformers'
            # truncation semantics), not a mid-word cut.
            row = row[: L - 1] + [sep_id]
        ids[r, : len(row)] = row
        lengths[r] = len(row)
    return ids, lengths
