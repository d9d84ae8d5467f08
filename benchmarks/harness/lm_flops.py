"""Operations and bytes the decoder language model NEEDS, from shapes alone,
for the ``brumby-14b-base`` cells' roofline shares. Conservative on purpose,
as ``flops.py``: needed work only, matmul terms only (2 M N K a matmul), each
document at its real length and not at the padded segments it ran as — so a
share computed from these cannot pass 100 % unless the time leaves out part
of the work. ``model`` is the configuration file's ``model`` group.

Hand arithmetic at the published widths (hidden 5,120, 40 query and 8
key-value heads of 128, FFN 17,408, vocabulary 151,936, 8 layers), one
16,384-token document (``tests/benchmarks`` holds the functions to it):

- a layer's matmuls: Q 26.21 M + K 5.24 M + V 5.24 M + O 26.21 M + gate
  0.04 M + 3 x 89.13 M FFN = 330.34 M parameters, 660.7 MFLOP a token;
  eight layers, 16,384 tokens: 86.60 TFLOP;
- the head: 2 x 5,120 x 151,936 = 1.556 GFLOP a token, 25.49 TFLOP;
- retention, a query-head token: quadratic 256 L (the causal half of QK^T and
  PV over L keys) = 4.19 MFLOP at 16,384; chunked at c = 1,024: 256 c =
  0.262 M inside the chunk + 2 x 8,256 x 128 = 2.114 M to read the state (not
  in a document's first chunk) + a fifth of that, 0.423 M, for the update a
  key-value head shares among five query heads = 2.667 M on average; the
  cheaper form (chunked) over 40 heads, 16,384 tokens, 8 layers: 13.98 TFLOP
  (ISSUE 27 reckons "about 14.4" with every chunk reading a state);
- 126.1 TFLOP a document in all: 0.640 s at 197 TFLOP/s."""

from __future__ import annotations

from typing import Iterable, Mapping

CHUNK = 1024        # the program's chunk (kernels/power_retention.py)


def distinct_products(d_head: int) -> int:
    """Rows of the symmetric degree-2 expansion: D (D + 1) / 2."""
    return d_head * (d_head + 1) // 2


def layer_matmul_params(model: Mapping[str, int]) -> int:
    d, f = int(model["d_model"]), int(model["d_ff"])
    hq = int(model["n_heads"]) * int(model["d_head"])
    hkv = int(model["n_kv_heads"]) * int(model["d_head"])
    return d * (2 * hq + 2 * hkv + int(model["n_kv_heads"])) + 3 * d * f


def layers_flops(model: Mapping[str, int], n_tokens: int) -> int:
    return 2 * layer_matmul_params(model) * int(model["n_layers"]) * int(n_tokens)


def head_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """One logit row a token that has a successor would do; every token is
    counted (L against L - 1: the difference is below any share's digits)."""
    return 2 * int(model["d_model"]) * int(model["vocab_size"]) * int(n_tokens)


def retention_quadratic_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """All layers and query heads, pure quadratic, causal half: token t needs
    2 x 2 x D x (t + 1) FLOPs a head; summed, 2 D L (L + 1)."""
    d, L = int(model["d_head"]), int(n_tokens)
    return int(model["n_layers"]) * int(model["n_heads"]) * 2 * d * L * (L + 1)


def retention_chunked_flops(model: Mapping[str, int], n_tokens: int,
                            chunk: int = CHUNK) -> int:
    """All layers: inside every chunk the causal half of its block; the state
    read for every query-head token outside the document's first chunk; the
    state update once a key-value-head token (the last chunk's is needed by
    nobody and not counted)."""
    d, L = int(model["d_head"]), int(n_tokens)
    hq, hkv = int(model["n_heads"]), int(model["n_kv_heads"])
    per_read = 2 * distinct_products(d) * d
    n_full, rest = divmod(L, chunk)
    inside = 2 * d * (n_full * chunk * (chunk + 1) + rest * (rest + 1))
    after_first = max(0, L - chunk)
    updated = (n_full - (0 if rest else 1)) * chunk if L > chunk else 0
    return int(model["n_layers"]) * (
        hq * (inside + per_read * after_first) + hkv * per_read * updated)


def retention_flops_needed(model: Mapping[str, int], n_tokens: int) -> int:
    """The cheaper of the two forms."""
    return min(retention_quadratic_flops(model, n_tokens),
               retention_chunked_flops(model, n_tokens))


def retention_bytes_needed(model: Mapping[str, int], n_tokens: int) -> int:
    """HBM traffic the mixer cannot avoid: q and y (bf16) of every query
    head, k and v of every key-value head, the gate (f32), every layer."""
    d, L = int(model["d_head"]), int(n_tokens)
    hq, hkv = int(model["n_heads"]), int(model["n_kv_heads"])
    return int(model["n_layers"]) * L * (2 * 2 * hq * d + 2 * 2 * hkv * d
                                         + 4 * hkv)


def head_bytes_needed(model: Mapping[str, int], n_tokens: int) -> int:
    """The head's weights once (bf16) and the hidden states once."""
    d = int(model["d_model"])
    return 2 * d * int(model["vocab_size"]) + 2 * d * int(n_tokens)


def document_flops_needed(model: Mapping[str, int], n_tokens: int) -> int:
    return (layers_flops(model, n_tokens) + head_flops(model, n_tokens)
            + retention_flops_needed(model, n_tokens))


def mean_needed(model: Mapping[str, int], lengths: Iterable[int]):
    """Per-document means over ``lengths`` of everything the readers use."""
    lengths = [int(n) for n in lengths]
    n = max(1, len(lengths))
    total = lambda fn: sum(fn(model, L) for L in lengths) / n  # noqa: E731
    return {
        "flops": total(document_flops_needed),
        "retention_flops": total(retention_flops_needed),
        "retention_bytes": total(retention_bytes_needed),
        "head_flops": total(head_flops),
        "head_bytes": total(head_bytes_needed),
    }
