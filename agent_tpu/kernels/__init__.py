"""Hand-written Pallas (Mosaic) TPU kernels for the hot ops.

The reference's only "kernel" was the opaque Edge-TPU interpreter invoke
(reference ``ops/map_classify_tpu.py:72``). Here XLA compiles almost
everything well on its own (SURVEY.md §7: "let XLA fuse — don't hand-schedule
what the compiler already does"), so this package holds only kernels where a
hand schedule beats XLA's: fused attention, which runs the QKᵀ → mask →
softmax → ·V chain as one VMEM-resident pass and never materializes the
[Lq, Lk] score matrix in HBM — streaming over key tiles from 2048 keys, a
whole score row at a time on lane-dense [B, L, H*D] operands below that.

Every kernel ships with an XLA fallback and an interpret-mode path so the CPU
test mesh exercises identical code (same-program-different-backend rule,
SURVEY.md §7).
"""

from agent_tpu.kernels.flash_attention import (
    flash_attention,
    flash_attention_trainable,
    make_flash_attention,
    make_flash_attention_trainable,
)

__all__ = [
    "flash_attention",
    "flash_attention_trainable",
    "make_flash_attention",
    "make_flash_attention_trainable",
]
