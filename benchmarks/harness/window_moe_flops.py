"""Operations and bytes the decoder language model NEEDS under ``mixer:
window_gqa`` (grouped-query attention whose layers come in two kinds, window
and full, mixed ``full_attention_every - 1`` : 1; softmax-routed expert
layers with no shared expert), from shapes alone, for the
``mellum2-12b-a2.5b`` cell's roofline shares. Conservative on purpose, as
``lm_flops.py``: needed work only, matmul terms only (2 M N K a matmul), each
document at its real length, every kernel at the CHEAPEST form that computes
it and not at the form the program ships — so a share computed from these
cannot pass 100 % unless the time leaves out part of the work: a full layer
at the exact causal half, a window layer at the exact pairs inside the
windows (``sum over t of min(t + 1, sliding_window)``, not the key tiles a
grid visits), the experts HELD at even routing, their weights read once a
DOCUMENT. ``attention_*`` are the FULL layers' (the accepted
``causal_attention_roofline`` reads the kernel of that name, which only they
run), ``window_*`` the window layers', ``expert_*`` the routed experts' under
the names the accepted readers read. ``model`` is the configuration file's
``model`` group.

Hand arithmetic at the published widths (hidden 2,304; 32 query over 4
key-value heads of 128; window 1,024; experts 896 wide, 8 of 64 a token, all
64 held, none shared; vocabulary 98,304; 12 layers = 3 periods of 3 window +
1 full), one 32,768-token document (``tests/benchmarks`` holds the functions
to it):

- a layer's per-token projections: q 9,437,184 + k 1,179,648 + v 1,179,648 +
  out 9,437,184 = 21,233,664 parameters, the router 147,456: 42.76 MFLOP a
  token; twelve layers, 32,768 tokens: 16.81 TFLOP;
- the routed pairs, 8 a token of 6,193,152 parameters each: 99.09 MFLOP a
  token a layer, 38.96 TFLOP in all; their bytes: 64 experts' weights once a
  layer (792.7 MB) and a routed row in and out: 12 x (792.7 MB + 2.4 GB) =
  38.5 GB, 47 ms at 819 GB/s against 198 ms of FLOPs: the MXU bounds them;
- full attention, the exact causal half: 4 x 128 a pair a head, 16,384 x
  L (L + 1) / 2 = 8.796 TFLOP a layer, 26.39 in three;
- window attention: 16,384 x (1,024 x 1,025 / 2 + 31,744 x 1,024) = 0.5412
  TFLOP a layer, 4.87 in nine (every causal key would be 79.2: 97 % of the
  causal tiles are never fetched); its bytes, q, o, k, v once: 18,432 B a
  token a layer, 5.4 GB: 6.6 ms against 24.7 ms of FLOPs;
- the head: 2 x 2,304 x 98,304 = 453.0 MFLOP a token, 14.84 TFLOP;
- a document: 16.81 + 38.96 + 26.39 + 4.87 + 14.84 = 101.88 TFLOP: 0.517 s
  at 197 TFLOP/s, 1.934 documents a second; experts 38.2 %, full attention
  25.9 %, projections and router 16.5 %, head 14.6 % (6.8 % at 28 layers),
  window attention 4.8 %."""

from __future__ import annotations

from typing import Iterable, Mapping


def _g(model: Mapping[str, int], key: str) -> int:
    return int(model[key])


def layers_of(model: Mapping[str, int]) -> Mapping[str, int]:
    """How many of the layers are ``full`` and how many ``window``."""
    full = _g(model, "n_layers") // _g(model, "full_attention_every")
    return {"full": full, "window": _g(model, "n_layers") - full}


def projection_params(model: Mapping[str, int]) -> int:
    """Query, key, value and out projections, a layer."""
    d, dh = _g(model, "d_model"), _g(model, "d_head")
    return 2 * d * dh * (_g(model, "n_heads") + _g(model, "n_kv_heads"))


def expert_params(model: Mapping[str, int]) -> int:
    """One expert: a SwiGLU of the experts' width."""
    return 3 * _g(model, "d_model") * _g(model, "d_expert")


def pairs_per_token(model: Mapping[str, int]) -> float:
    """(token, expert) pairs a token routed to the experts held, if routing
    is even."""
    return (_g(model, "n_experts_per_token") * _g(model, "n_experts_held")
            / _g(model, "n_experts"))


def layer_flops_per_token(model: Mapping[str, int]) -> float:
    """Projections, router, and the routed pairs held."""
    fixed = projection_params(model) + _g(model, "d_model") * _g(
        model, "n_experts")
    return 2.0 * (fixed + pairs_per_token(model) * expert_params(model))


def causal_pairs(n_tokens: int) -> int:
    return int(n_tokens) * (int(n_tokens) + 1) // 2


def window_pairs(model: Mapping[str, int], n_tokens: int) -> int:
    """``sum over t of min(t + 1, sliding_window)``."""
    n = int(n_tokens)
    ramp = min(n, _g(model, "sliding_window"))
    return ramp * (ramp + 1) // 2 + (n - ramp) * _g(model, "sliding_window")


def _pair_flops(model: Mapping[str, int]) -> int:
    """A pair's score and value product, every query head."""
    return 4 * _g(model, "n_heads") * _g(model, "d_head")


def _qokv_bytes(model: Mapping[str, int]) -> int:
    """q in and o out of every query head, k and v of every key-value head
    once (bf16), a token a layer."""
    return 2 * _g(model, "d_head") * (2 * _g(model, "n_heads")
                                      + 2 * _g(model, "n_kv_heads"))


def attention_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """The FULL layers, the exact causal half."""
    return layers_of(model)["full"] * _pair_flops(model) * causal_pairs(n_tokens)


def attention_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    return layers_of(model)["full"] * int(n_tokens) * _qokv_bytes(model)


def window_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """The WINDOW layers, the exact pairs inside the windows."""
    return layers_of(model)["window"] * _pair_flops(model) * window_pairs(
        model, n_tokens)


def window_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    return layers_of(model)["window"] * int(n_tokens) * _qokv_bytes(model)


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
    """The head's rows once (bf16) and the hidden states once."""
    d = _g(model, "d_model")
    return 2 * d * _g(model, "vocab_size") + 2 * d * int(n_tokens)


def document_flops_needed(model: Mapping[str, int], n_tokens: int) -> float:
    return (layer_flops_per_token(model) * _g(model, "n_layers")
            * int(n_tokens) + attention_flops(model, n_tokens)
            + window_flops(model, n_tokens) + head_flops(model, n_tokens))


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
        "window_flops": total(window_flops),
        "window_bytes": total(window_bytes),
    }
