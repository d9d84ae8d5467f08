"""Operations and bytes the decoder language model NEEDS under ``mixer:
sparse_mla`` (latent attention under a learned top-k key selection, expert
layers of which one chip's share is held), from shapes alone, for the
``deepseek-v3.2`` cells' roofline shares. Conservative on purpose, as
``lm_flops.py``: needed work only, matmul terms only (2 M N K a matmul), each
document at its real length, every kernel at the CHEAPEST form that computes
it and not at the form the program ships — so a share computed from these
cannot pass 100 % unless the time leaves out part of the work, and what a
version of the program leaves on the table is on the record. ``model`` is the
configuration file's ``model`` group.

Hand arithmetic at the published widths (hidden 7,168; 128 heads of 128 + 64
query / key and 128 value dimensions; ranks 1,536 and 512; indexer 64 heads
of 128, top 2,048; dense FFN 18,432; experts 2,048 wide, 8 of 256 a token, 16
held, 1 shared; vocabulary 16,160 rows held; 1 dense + 4 expert layers), one
32,768-token document (``tests/benchmarks`` holds the functions to it):

- latent attention's projections: q down 11.01 M + q up 37.75 M + kv down
  4.13 M + kv up 16.78 M + out 117.44 M = 187.11 M parameters; the indexer's
  12.58 M + 0.92 M + 0.46 M = 13.96 M;
- a dense layer: 187.11 + 13.96 + 3 x 132.12 M FFN = 597.43 M, 1,194.9 MFLOP
  a token; an expert layer: 187.11 + 13.96 + 44.04 M shared + 1.84 M router =
  246.95 M, plus the routed pairs HELD HERE, 8 x 16 / 256 = 0.5 a token of
  44.04 M each if routing is even = 268.97 M, 537.9 MFLOP a token;
- index scores: 2 x 64 x 128 = 16,384 FLOPs a causal pair, (L + 1) / 2 =
  16,384.5 pairs a token on average: 268.4 MFLOP a token a layer;
- attention over the selected keys, absorbed (W_UK into q, W_UV after): 2 x
  128 x (576 + 512) = 278,528 FLOPs a selected key; sum_t min(t + 1, 2,048) /
  L = 1,984.03 keys a token: 552.6 MFLOP a token a layer (dense over every
  causal key with expanded keys: 2 x 128 x 320 x 16,384.5 + the expansion
  33.6 M = 1,375.8 M; dense is the cheaper below about 13 k tokens), and
  1,984.03 x 576 x 2 B = 2.29 MB of latents gathered a token a layer;
- the head: 2 x 7,168 x 16,160 = 231.7 MFLOP a token;
- a token: 4 x 537.9 + 1,194.9 + 5 x (268.4 + 552.6) + 231.7 = 7,683.2
  MFLOP; the document 251.8 TFLOP: 1.278 s at 197 TFLOP/s."""

from __future__ import annotations

from typing import Iterable, Mapping


def _g(model: Mapping[str, int], key: str) -> int:
    return int(model[key])


def attention_params(model: Mapping[str, int]) -> int:
    """Latent attention's projections and the indexer's, a layer."""
    d, h = _g(model, "d_model"), _g(model, "n_heads")
    qr, kvr = _g(model, "q_lora_rank"), _g(model, "kv_lora_rank")
    dn, dr = _g(model, "qk_nope_head_dim"), _g(model, "qk_rope_head_dim")
    dv = _g(model, "v_head_dim")
    hi, di = _g(model, "index_n_heads"), _g(model, "index_head_dim")
    mla = (d * qr + qr * h * (dn + dr) + d * (kvr + dr) + kvr * h * (dn + dv)
           + h * dv * d)
    return mla + qr * hi * di + d * di + d * hi


def expert_params(model: Mapping[str, int]) -> int:
    """One expert: a SwiGLU of the experts' width."""
    return 3 * _g(model, "d_model") * _g(model, "d_expert")


def pairs_per_token(model: Mapping[str, int]) -> float:
    """(token, expert) pairs a token routed to the experts held, if routing
    is even."""
    return (_g(model, "n_experts_per_token") * _g(model, "n_experts_held")
            / _g(model, "n_experts"))


def dense_layer_flops_per_token(model: Mapping[str, int]) -> float:
    return 2.0 * (attention_params(model)
                  + 3 * _g(model, "d_model") * _g(model, "d_ff"))


def expert_layer_flops_per_token(model: Mapping[str, int]) -> float:
    """Projections, router, shared experts, and the routed pairs held."""
    fixed = (attention_params(model)
             + _g(model, "d_model") * _g(model, "n_experts")
             + _g(model, "n_shared_experts") * expert_params(model))
    return 2.0 * (fixed + pairs_per_token(model) * expert_params(model))


def layer_counts(model: Mapping[str, int]):
    """(dense layers, expert layers)."""
    n = _g(model, "n_layers")
    if not int(model.get("n_experts", 0)):
        return n, 0
    return _g(model, "n_dense_layers"), n - _g(model, "n_dense_layers")


def causal_pairs(n_tokens: int) -> int:
    return int(n_tokens) * (int(n_tokens) + 1) // 2


def selected_pairs(model: Mapping[str, int], n_tokens: int) -> int:
    """sum over t of min(t + 1, index_topk)."""
    L, k = int(n_tokens), min(int(n_tokens), _g(model, "index_topk"))
    return k * (k + 1) // 2 + (L - k) * _g(model, "index_topk")


def indexer_flops(model: Mapping[str, int], n_tokens: int) -> int:
    """Every causal pair's score, every layer."""
    return (_g(model, "n_layers") * 2 * _g(model, "index_n_heads")
            * _g(model, "index_head_dim") * causal_pairs(n_tokens))


def _attention_forms(model: Mapping[str, int], n_tokens: int):
    """((FLOPs, bytes) over the selected keys, absorbed; (FLOPs, bytes) dense
    over every causal key with keys and values expanded once a key), a layer."""
    h, kvr = _g(model, "n_heads"), _g(model, "kv_lora_rank")
    dn, dr = _g(model, "qk_nope_head_dim"), _g(model, "qk_rope_head_dim")
    dv, L = _g(model, "v_head_dim"), int(n_tokens)
    latent = kvr + dr
    q_and_o = 2 * L * h * (latent + kvr)
    sparse = (2 * h * (latent + kvr) * selected_pairs(model, L),
              2 * latent * selected_pairs(model, L) + q_and_o)
    dense = (2 * h * (dn + dr + dv) * causal_pairs(L)
             + 2 * kvr * h * (dn + dv) * L,
             2 * latent * L + 2 * L * h * (dn + dr + dv))
    return sparse, dense


def sparse_attention_needed(model: Mapping[str, int], n_tokens: int):
    """(FLOPs, bytes) of the form with fewer FLOPs, every layer."""
    flops, nbytes = min(_attention_forms(model, n_tokens))
    n = _g(model, "n_layers")
    return n * flops, n * nbytes


def expert_flops(model: Mapping[str, int], n_tokens: int) -> float:
    """The routed pairs held here if routing is even, every expert layer."""
    return (2.0 * pairs_per_token(model) * expert_params(model)
            * layer_counts(model)[1] * int(n_tokens))


def expert_bytes(model: Mapping[str, int], n_tokens: int) -> int:
    """The held experts' weights once a document (bf16), every expert
    layer, and a routed row in and out."""
    d = _g(model, "d_model")
    rows = pairs_per_token(model) * int(n_tokens)
    return int(layer_counts(model)[1] * (
        2 * _g(model, "n_experts_held") * expert_params(model)
        + 2 * 2 * d * rows))


def head_flops(model: Mapping[str, int], n_tokens: int) -> int:
    return 2 * _g(model, "d_model") * _g(model, "vocab_size") * int(n_tokens)


def head_bytes_needed(model: Mapping[str, int], n_tokens: int) -> int:
    """The head's rows held once (bf16) and the hidden states once."""
    d = _g(model, "d_model")
    return 2 * d * _g(model, "vocab_size") + 2 * d * int(n_tokens)


def document_flops_needed(model: Mapping[str, int], n_tokens: int) -> float:
    dense, experts = layer_counts(model)
    per_token = (dense * dense_layer_flops_per_token(model)
                 + experts * expert_layer_flops_per_token(model))
    return (per_token * int(n_tokens) + head_flops(model, n_tokens)
            + indexer_flops(model, n_tokens)
            + sparse_attention_needed(model, n_tokens)[0])


def mean_needed(model: Mapping[str, int], lengths: Iterable[int]):
    """Per-document means over ``lengths`` of everything the readers use."""
    lengths = [int(n) for n in lengths]
    n = max(1, len(lengths))
    total = lambda fn: sum(fn(model, L) for L in lengths) / n  # noqa: E731
    return {
        "flops": total(document_flops_needed),
        "head_flops": total(head_flops),
        "head_bytes": total(head_bytes_needed),
        "indexer_flops": total(indexer_flops),
        "sparse_attention_flops": total(
            lambda m, L: sparse_attention_needed(m, L)[0]),
        "sparse_attention_bytes": total(
            lambda m, L: sparse_attention_needed(m, L)[1]),
        "expert_flops": total(expert_flops),
        "expert_bytes": total(expert_bytes),
    }
