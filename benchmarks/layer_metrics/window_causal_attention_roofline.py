"""Layer: Kernels (kernels/causal_attention.py at eight query heads a key head:
the FULL layers of the ``window_gqa`` mixer). The accepted
``causal_attention_roofline`` read in the ``mellum2-12b-a2.5b`` cell: the kernel named ``causal_gqa_attention``, which only
the full layers run (a window layer's call is ``window_gqa_attention``),
against this family's ``attention_flops`` / ``attention_bytes`` (the full
layers' exact causal half). An
entry of its own because the accepted entry's list of cells is held to one
cell by a test no PR may edit (``tests/benchmarks/test_bench_hybrid_ssm.py``); the reader is that entry's,
not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

_accepted = manifest.load_layer_metric("causal_attention_roofline")
OP_PATTERNS = _accepted.OP_PATTERNS
read = _accepted.read
