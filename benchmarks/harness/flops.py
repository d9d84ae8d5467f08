"""Operations the algorithm needs, from shapes alone. Conservative on
purpose — needed work only, matmul terms only — so a share of a peak computed
from these cannot pass 100% unless the time leaves out part of the work."""

from __future__ import annotations

from typing import Iterable, Mapping


def encoder_flops_per_row(cfg: Mapping[str, int], seq_len: int,
                          with_head: bool = False) -> int:
    """Forward FLOPs of one row of ``seq_len`` tokens through the encoder
    (2·M·N·K per matmul): QKVO projections, QKᵀ and P·V, the FFN, summed
    over layers. Copy of ``bench.encoder_flops_per_row`` (sound arithmetic in
    a file the benchmark does not read); the head is left out unless asked.
    BERT-base: 11,022,630,912 at 64 tokens, 96,636,764,160 at 512."""
    d, f, L = int(cfg["d_model"]), int(cfg["d_ff"]), int(seq_len)
    attn_proj = 8 * L * d * d
    attn_sdpa = 4 * L * L * d
    ffn = 4 * L * d * f
    total = int(cfg["n_layers"]) * (attn_proj + attn_sdpa + ffn)
    if with_head:
        total += 2 * d * int(cfg.get("n_classes", 0))
    return total


def encoder_flops_needed(cfg: Mapping[str, int],
                         real_tokens: Iterable[int]) -> int:
    """What a set of rows NEEDS: each row at its own real length (capped at
    the model's positions), not at the padded length it was run at."""
    cap = int(cfg["max_len"])
    return sum(encoder_flops_per_row(cfg, min(int(n), cap))
               for n in real_tokens)
