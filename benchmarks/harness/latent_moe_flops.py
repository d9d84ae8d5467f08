"""Operations and bytes the decoder language model NEEDS under ``mixer:
dense_mla`` (dense latent attention: every causal key; softmax-routed expert
layers of which one chip's share is held), from shapes alone, for the
``mistral-small-4-119b`` cell's roofline shares. Conservative on purpose, as
``lm_flops.py``: needed work only, matmul terms only (2 M N K a matmul), each
document at its real length, every kernel at the CHEAPEST form that computes
it and not at the form the program ships — so a share computed from these
cannot pass 100 % unless the time leaves out part of the work, and what a
version of the program leaves on the table is on the record: a latent is
counted expanded ONCE (the program expands it again in every later segment),
attention at the exact causal half on expanded keys (512 FLOPs a pair a
head; the absorbed form would be 1,152), the experts HELD. ``model`` is the
configuration file's ``model`` group.

Hand arithmetic at the published widths (hidden 4,096; 32 heads of 64 + 64
query / key and 128 value dimensions; ranks 1,024 and 256; experts 2,048
wide, 4 of 128 a token, 32 held, 1 shared; vocabulary 32,768 rows held; 6
layers, every one an expert layer), one 65,536-token document
(``tests/benchmarks`` holds the functions to it):

- a layer's per-token matmuls: q down 4,194,304 + q up 4,194,304 + kv down
  1,310,720 + out 16,777,216 = 26,476,544 parameters of latent attention
  (the kv up-projection, 1,572,864, is the expansion below), router 524,288,
  shared expert 25,165,824, and the routed pairs HELD HERE, 4 x 32 / 128 =
  1.0 a token of 25,165,824 each if routing is even: 77,332,480, 154.7 MFLOP
  a token; six layers, 65,536 tokens: 60.82 TFLOP;
- the expansion, once a token a layer: 2 x 256 x 32 x (64 + 128) = 3.146
  MFLOP, 1.237 TFLOP in all; its bytes: the latent read (320 x 2 B) and a
  head's key and value written (32 x 256 x 2 B): 17,024 B a token a layer,
  6.694 GB: 8.17 ms at 819 GB/s against 6.28 ms of FLOPs, so bytes bound it;
- attention, the exact causal half: a pair's score and value product, 4 x 128
  a head: 16,384 x L (L + 1) / 2 = 35.18 TFLOP a layer, 211.11 in all;
- the head: 2 x 4,096 x 32,768 = 268.4 MFLOP a token, 17.59 TFLOP;
- a document: 60.82 + 1.24 + 211.11 + 17.59 = 290.76 TFLOP: 1.476 s at 197
  TFLOP/s, 0.6775 documents a second; attention is 72.6 % of it (57.0 % at
  32,768 tokens), the routed experts held 6.8 %, the shared expert 6.8 %, the
  head 6.0 % (3.4 % in the whole model: 36 layers, 4 pairs a token, the
  whole vocabulary)."""

from __future__ import annotations

from typing import Iterable, Mapping


def _g(model: Mapping[str, int], key: str) -> int:
    return int(model[key])


def projection_params(model: Mapping[str, int]) -> int:
    """Latent attention's per-token projections, a layer: query down and up,
    key-value down, out (the key-value up-projection is the expansion)."""
    d, h = _g(model, "d_model"), _g(model, "n_heads")
    qr, kvr = _g(model, "q_lora_rank"), _g(model, "kv_lora_rank")
    dn, dr = _g(model, "qk_nope_head_dim"), _g(model, "qk_rope_head_dim")
    return d * qr + qr * h * (dn + dr) + d * (kvr + dr) + h * _g(
        model, "v_head_dim") * d


def expansion_params(model: Mapping[str, int]) -> int:
    """The key-value up-projection: a head's W_UK | W_UV."""
    return _g(model, "kv_lora_rank") * _g(model, "n_heads") * (
        _g(model, "qk_nope_head_dim") + _g(model, "v_head_dim"))


def expert_params(model: Mapping[str, int]) -> int:
    """One expert: a SwiGLU of the experts' width."""
    return 3 * _g(model, "d_model") * _g(model, "d_expert")


def pairs_per_token(model: Mapping[str, int]) -> float:
    """(token, expert) pairs a token routed to the experts held, if routing
    is even."""
    return (_g(model, "n_experts_per_token") * _g(model, "n_experts_held")
            / _g(model, "n_experts"))


def layer_flops_per_token(model: Mapping[str, int]) -> float:
    """Projections, router, shared experts, and the routed pairs held."""
    fixed = (projection_params(model)
             + _g(model, "d_model") * _g(model, "n_experts")
             + _g(model, "n_shared_experts") * expert_params(model))
    return 2.0 * (fixed + pairs_per_token(model) * expert_params(model))


def causal_pairs(n_tokens: int) -> int:
    return int(n_tokens) * (int(n_tokens) + 1) // 2


def attention_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """Every layer and head, the exact causal half on expanded keys: a
    pair's score over nope + rope and its value product."""
    per_pair = 2 * _g(model, "n_heads") * (
        _g(model, "qk_nope_head_dim") + _g(model, "qk_rope_head_dim")
        + _g(model, "v_head_dim"))
    return _g(model, "n_layers") * per_pair * causal_pairs(n_tokens)


def attention_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    """q in and o out of every head, its expanded key and value once (bf16),
    every layer."""
    h = _g(model, "n_heads")
    dk = _g(model, "qk_nope_head_dim") + _g(model, "qk_rope_head_dim")
    dv = _g(model, "v_head_dim")
    return _g(model, "n_layers") * int(n_tokens) * 2 * h * (2 * dk + 2 * dv)


def expand_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """Every latent through the key-value up-projection ONCE, every layer."""
    return 2 * expansion_params(model) * _g(model, "n_layers") * int(n_tokens)


def expand_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    """The cached vector read and every head's key and value written once
    (bf16), every layer."""
    h = _g(model, "n_heads")
    dk = _g(model, "qk_nope_head_dim") + _g(model, "qk_rope_head_dim")
    cached = _g(model, "kv_lora_rank") + _g(model, "qk_rope_head_dim")
    return _g(model, "n_layers") * int(n_tokens) * 2 * (
        cached + h * (dk + _g(model, "v_head_dim")))


def expert_flops(model: Mapping[str, int], n_tokens: int) -> float:
    """The routed pairs held here if routing is even, every layer."""
    return (2.0 * pairs_per_token(model) * expert_params(model)
            * _g(model, "n_layers") * int(n_tokens))


def expert_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    """The held experts' weights once a document (bf16), every layer, and a
    routed row in and out."""
    d = _g(model, "d_model")
    rows = pairs_per_token(model) * int(n_tokens)
    return int(_g(model, "n_layers") * (
        2 * _g(model, "n_experts_held") * expert_params(model)
        + 2 * 2 * d * rows))


def head_flops(model: Mapping[str, int], n_tokens: int) -> int:
    return 2 * _g(model, "d_model") * _g(model, "vocab_size") * int(n_tokens)


def head_bytes_needed(model: Mapping[str, int], n_tokens: int) -> int:
    """The head's rows held once (bf16) and the hidden states once."""
    d = _g(model, "d_model")
    return 2 * d * _g(model, "vocab_size") + 2 * d * int(n_tokens)


def document_flops_needed(model: Mapping[str, int], n_tokens: int) -> float:
    return (layer_flops_per_token(model) * _g(model, "n_layers")
            * int(n_tokens) + expand_flops(model, n_tokens)
            + attention_flops(model, n_tokens) + head_flops(model, n_tokens))


def mean_needed(model: Mapping[str, int], lengths: Iterable[int]):
    """Per-document means over ``lengths`` of everything the readers use."""
    lengths = [int(n) for n in lengths]
    n = max(1, len(lengths))
    total = lambda fn: sum(fn(model, L) for L in lengths) / n  # noqa: E731
    return {
        "flops": total(document_flops_needed),
        "head_flops": total(head_flops),
        "head_bytes": total(head_bytes_needed),
        "attention_flops": total(attention_flops),
        "attention_bytes": total(attention_bytes),
        "expert_flops": total(expert_flops),
        "expert_bytes": total(expert_bytes),
        "expand_flops": total(expand_flops),
        "expand_bytes": total(expand_bytes),
    }
