"""Operations and bytes the decoder language model NEEDS under ``mixer:
conv_gqa`` (double-gated short convolutions and, by the model's own pattern,
grouped-query attention layers at heads of 64; leading dense layers, then
sigmoid-routed expert layers of which every expert is held), from shapes
alone, for the ``lfm2-24b-a2b`` cell's roofline shares. Conservative on
purpose, as ``lm_flops.py``: needed work only, matmul terms only (2 M N K a
matmul), each document at its real length, every kernel at the CHEAPEST form
that computes it and not at the form the program ships — so a share computed
from these cannot pass 100 % unless the time leaves out part of the work:
attention at the exact causal half at the heads' OWN size (``4 x 64`` a pair
a query head: the kernel's matrix products run 64 deep and 64 wide on a
128 x 128 unit, half its depth, so its share of THIS roofline reads at most
about 50 % however well it runs), the experts at the pairs routed and their
weights read once a DOCUMENT, the gates-and-convolution pass at one read of
the in-projection's columns and one write of its product in bf16.
``attention_*`` and ``expert_*`` are under the names the accepted readers
read; ``conv_gate_bytes`` is this family's own. ``model`` is the configuration
file's ``model`` group.

Hand arithmetic at the published widths (hidden 2,048; 32 query over 8
key-value heads of 64; experts 1,536 wide, 4 of 64 a token, all held, none
shared; dense width 11,776; vocabulary 65,536; 9 layers = 1 leading dense
``conv`` layer + two periods of ``full_attention, conv, conv, conv``), one
32,768-token document (``tests/benchmarks`` holds the functions to it):

- a ``conv`` layer's projections: in 2,048 x 6,144 + out 2,048 x 2,048 =
  16,777,216 parameters, 33.55 MFLOP a token; seven of them, 32,768 tokens:
  7.70 TFLOP; its gates and taps are elementwise (no matmul term): 6,144
  columns read and 2,048 written a token in bf16, 16,384 B, 3.76 GB in seven
  layers: 4.6 ms at 819 GB/s;
- an attention layer's projections: q and out 2 x 4,194,304 + k and v 2 x
  1,048,576 = 10,485,760, 20.97 MFLOP a token; two layers: 1.37 TFLOP;
  attention, the exact causal half: 4 x 32 x 64 = 8,192 a pair x L (L + 1) / 2
  = 4.398 TFLOP a layer, 8.80 in two;
- the leading layer's dense FFN: 3 x 2,048 x 11,776 = 72,351,744 parameters,
  144.7 MFLOP a token, 4.74 TFLOP; an expert layer's router 131,072 and 4
  routed pairs a token of 9,437,184 each: 37,879,808 parameters, 75.76 MFLOP
  a token; eight layers: 19.86 TFLOP (of which the routed pairs 19.79);
- the head: 2 x 2,048 x 65,536 = 268.4 MFLOP a token, 8.80 TFLOP;
- a document: 7.70 + 1.37 + 8.80 + 4.74 + 19.86 + 8.80 = 51.27 TFLOP: 0.260 s
  at 197 TFLOP/s, 3.84 documents a second; the experts 38.7 %, attention
  17.2 %, the head 17.2 % (4.5 % at the published 40 layers), the seven ``conv``
  mixers 15.0 %, the dense FFN 9.2 %, the attention projections 2.7 %."""

from __future__ import annotations

from typing import Iterable, Mapping


def _g(model: Mapping[str, int], key: str) -> int:
    return int(model[key])


def layers_of(model: Mapping[str, int]) -> Mapping[str, int]:
    """How many of the layers are ``conv``, ``full`` (attention), ``dense``
    (their feed-forward) and ``experts``."""
    kinds = [str(kind) for kind in model["layer_types"]]
    routed = _g(model, "n_experts") > 0
    dense = _g(model, "n_dense_layers") if routed else _g(model, "n_layers")
    return {"conv": kinds.count("conv"),
            "full": len(kinds) - kinds.count("conv"),
            "dense": dense, "experts": _g(model, "n_layers") - dense}


def conv_projection_params(model: Mapping[str, int]) -> int:
    """A ``conv`` layer: the in-projection ``[B | C | z]`` and out."""
    d = _g(model, "d_model")
    return 4 * d * d


def attention_projection_params(model: Mapping[str, int]) -> int:
    d, dh = _g(model, "d_model"), _g(model, "d_head")
    return 2 * d * dh * (_g(model, "n_heads") + _g(model, "n_kv_heads"))


def expert_params(model: Mapping[str, int]) -> int:
    return 3 * _g(model, "d_model") * _g(model, "d_expert")


def pairs_per_token(model: Mapping[str, int]) -> float:
    return (_g(model, "n_experts_per_token") * _g(model, "n_experts_held")
            / _g(model, "n_experts"))


def conv_gate_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    """The gates-and-convolution pass, every ``conv`` layer: the
    in-projection's three column blocks read and the gated product written
    once, in bf16."""
    return layers_of(model)["conv"] * int(n_tokens) * 2 * 4 * _g(
        model, "d_model")


def causal_pairs(n_tokens: int) -> int:
    return int(n_tokens) * (int(n_tokens) + 1) // 2


def attention_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """The attention layers, every query head, the exact causal half at the
    heads' own size: ``4 x d_head`` a pair."""
    per_pair = 4 * _g(model, "n_heads") * _g(model, "d_head")
    return layers_of(model)["full"] * per_pair * causal_pairs(n_tokens)


def attention_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    """q in and o out of every query head, k and v of every key-value head,
    once, in bf16."""
    dh = _g(model, "d_head")
    return layers_of(model)["full"] * int(n_tokens) * 2 * dh * 2 * (
        _g(model, "n_heads") + _g(model, "n_kv_heads"))


def expert_flops(model: Mapping[str, int], n_tokens: int) -> float:
    return (2.0 * pairs_per_token(model) * expert_params(model)
            * layers_of(model)["experts"] * int(n_tokens))


def expert_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    """The held experts' weights once a document (bf16), every expert
    layer, and a routed row in and out."""
    d = _g(model, "d_model")
    rows = pairs_per_token(model) * int(n_tokens)
    return int(layers_of(model)["experts"] * (
        2 * _g(model, "n_experts_held") * expert_params(model)
        + 2 * 2 * d * rows))


def head_flops(model: Mapping[str, int], n_tokens: int) -> int:
    return 2 * _g(model, "d_model") * _g(model, "vocab_size") * int(n_tokens)


def head_bytes_needed(model: Mapping[str, int], n_tokens: int) -> int:
    d = _g(model, "d_model")
    return 2 * d * _g(model, "vocab_size") + 2 * d * int(n_tokens)


def per_token_flops(model: Mapping[str, int]) -> float:
    """Every layer's matmuls that cost the same at any position."""
    n, d = layers_of(model), _g(model, "d_model")
    experts = d * _g(model, "n_experts") + (
        _g(model, "n_shared_experts") + pairs_per_token(model)
    ) * expert_params(model)
    return 2.0 * (n["conv"] * conv_projection_params(model)
                  + n["full"] * attention_projection_params(model)
                  + n["dense"] * 3 * d * _g(model, "d_ff")
                  + n["experts"] * experts)


def document_flops_needed(model: Mapping[str, int], n_tokens: int) -> float:
    return (per_token_flops(model) * int(n_tokens)
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
        "conv_gate_bytes": total(conv_gate_bytes),
    }
