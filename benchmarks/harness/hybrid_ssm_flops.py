"""Operations and bytes the decoder language model NEEDS under ``mixer:
hybrid_ssm`` (a Mamba-2 state-space scan and causal grouped-query attention
side by side in one block), from shapes alone, for the ``falcon-h1-34b``
cells' roofline shares. Conservative on purpose, as ``lm_flops.py``: needed
work only, matmul terms only (2 M N K a matmul), each document at its real
length, every kernel at the CHEAPEST form that computes it and not at the
form the program ships — so a share computed from these cannot pass 100 %
unless the time leaves out part of the work. ``model`` is the configuration
file's ``model`` group.

Hand arithmetic at the published widths (hidden 5,120; 20 query and 4
key-value heads of 128; 32 scan heads of 128 in 2 groups, state 256, chunks of
128; FFN 21,504; vocabulary 261,120; 6 layers), one 65,536-token document
(``tests/benchmarks`` holds the functions to it):

- a layer's matmuls: Q 13.107 M + K 2.621 M + V 2.621 M + O 13.107 M = 31.457
  M of attention; the scan's in-projection 5,120 x 9,248 = 47.350 M and
  out-projection 20.972 M; 3 x 110.100 M FFN = 330.301 M: 430.080 M
  parameters, 860.2 MFLOP a token; six layers, 65,536 tokens: 338.22 TFLOP;
- the head: 2 x 5,120 x 261,120 = 2.674 GFLOP a token, 175.24 TFLOP;
- attention, the exact causal half: token t needs 4 x 128 x (t + 1) FLOPs a
  query head; 4 x 20 x 128 x L (L + 1) / 2 = 21.99 TFLOP a layer, 131.94 in all;
- the scan, a head-token: token by token 4 x 128 x 256 = 131,072 FLOPs (the
  state's update and its read); chunked at c = 128 the causal half of the
  chunk's block, 128 x 128 = 16,384, the same 131,072 outside a document's
  first chunk (read) and last (update), and C B^T once a GROUP (its causal
  half: 128 x 256 a group-token): 147,456 + 2 x 32,768 / 32: the token-by-token
  form is the cheaper at this length, 32 x 131,072 = 4.194 MFLOP a token a
  layer, 1.649 TFLOP (ISSUE 35 reckons 1.9 with the chunked form);
- the scan's bytes, a token a layer: x in and y out 2 x 8,192, B and C 2 x
  1,024, dt 128: 18,560 B, 7.298 GB: 8.91 ms at 819 GB/s against 8.37 ms of
  FLOPs, so bytes bound it (ISSUE 35 reckons 10.5 GB with the gate z, which
  the scan's kernel never reads: counted, it would let the share pass 100);
- 647.05 TFLOP a document in all: 3.285 s at 197 TFLOP/s."""

from __future__ import annotations

from typing import Iterable, Mapping


def _g(model: Mapping[str, int], key: str) -> int:
    return int(model[key])


def in_projection_columns(model: Mapping[str, int]) -> int:
    """[z | x | B | C | dt]."""
    d_ssm = _g(model, "ssm_n_heads") * _g(model, "ssm_d_head")
    return (2 * d_ssm + 2 * _g(model, "ssm_n_groups") * _g(model, "ssm_d_state")
            + _g(model, "ssm_n_heads"))


def layer_matmul_params(model: Mapping[str, int]) -> int:
    d, f = _g(model, "d_model"), _g(model, "d_ff")
    hq = _g(model, "n_heads") * _g(model, "d_head")
    hkv = _g(model, "n_kv_heads") * _g(model, "d_head")
    d_ssm = _g(model, "ssm_n_heads") * _g(model, "ssm_d_head")
    return (d * (2 * hq + 2 * hkv) + d * in_projection_columns(model)
            + d_ssm * d + 3 * d * f)


def layers_flops(model: Mapping[str, int], n_tokens: int) -> int:
    return 2 * layer_matmul_params(model) * _g(model, "n_layers") * int(n_tokens)


def head_flops(model: Mapping[str, int], n_tokens: int) -> int:
    return 2 * _g(model, "d_model") * _g(model, "vocab_size") * int(n_tokens)


def head_bytes_needed(model: Mapping[str, int], n_tokens: int) -> int:
    """The head's weights once (bf16) and the hidden states once."""
    d = _g(model, "d_model")
    return 2 * d * _g(model, "vocab_size") + 2 * d * int(n_tokens)


def causal_pairs(n_tokens: int) -> int:
    return int(n_tokens) * (int(n_tokens) + 1) // 2


def attention_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """Every layer and query head, the exact causal half: a pair's score and
    its value product, 4 D."""
    return (_g(model, "n_layers") * 4 * _g(model, "n_heads")
            * _g(model, "d_head") * causal_pairs(n_tokens))


def attention_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    """q in and o out of every query head, k and v of every key-value head
    once (bf16), every layer."""
    d = _g(model, "d_head")
    return _g(model, "n_layers") * int(n_tokens) * 2 * d * (
        2 * _g(model, "n_heads") + 2 * _g(model, "n_kv_heads"))


def ssd_recurrent_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """Token by token: the state's update and its read, 2 P N each a head."""
    return (_g(model, "n_layers") * _g(model, "ssm_n_heads") * 4
            * _g(model, "ssm_d_head") * _g(model, "ssm_d_state") * int(n_tokens))


def ssd_chunked_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """Chunked: the causal half of every chunk's block a head and of C B^T a
    group; the state read outside the document's first chunk, updated
    outside its last."""
    L, c = int(n_tokens), _g(model, "ssm_chunk")
    H, P, N = (_g(model, "ssm_n_heads"), _g(model, "ssm_d_head"),
               _g(model, "ssm_d_state"))
    n_full, rest = divmod(L, c)
    inside = n_full * c * (c + 1) // 2 + rest * (rest + 1) // 2   # pairs
    read = max(0, L - c)
    updated = (n_full - (0 if rest else 1)) * c if L > c else 0
    return _g(model, "n_layers") * (
        H * (2 * P * inside + 2 * P * N * (read + updated))
        + _g(model, "ssm_n_groups") * 2 * N * inside)


def ssd_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """The cheaper of the two forms."""
    return min(ssd_recurrent_flops(model, n_tokens),
               ssd_chunked_flops(model, n_tokens))


def ssd_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    """HBM traffic the scan cannot avoid: x in and y out (bf16) of every
    head, B and C of every group, the step (f32), every layer."""
    H, P = _g(model, "ssm_n_heads"), _g(model, "ssm_d_head")
    bc = _g(model, "ssm_n_groups") * _g(model, "ssm_d_state")
    return _g(model, "n_layers") * int(n_tokens) * (
        2 * 2 * H * P + 2 * 2 * bc + 4 * H)


def document_flops_needed(model: Mapping[str, int], n_tokens: int) -> int:
    return (layers_flops(model, n_tokens) + head_flops(model, n_tokens)
            + attention_flops(model, n_tokens) + ssd_flops(model, n_tokens))


def mean_needed(model: Mapping[str, int], lengths: Iterable[int]):
    """Per-document means over ``lengths`` of everything the readers use."""
    lengths = [int(n) for n in lengths]
    n = max(1, len(lengths))
    total = lambda fn: sum(fn(model, L) for L in lengths) / n  # noqa: E731
    return {
        "flops": total(document_flops_needed),
        "head_flops": total(head_flops),
        "head_bytes": total(head_bytes_needed),
        "ssd_flops": total(ssd_flops),
        "ssd_bytes": total(ssd_bytes),
        "attention_flops": total(attention_flops),
        "attention_bytes": total(attention_bytes),
    }
