"""Layer: Kernels (kernels/causal_attention.py at heads of HALF a lane tile,
two key-value heads a cache row: the attention layers of the ``conv_gqa``
mixer). The accepted ``causal_attention_roofline`` read in the
``lfm2-24b-a2b`` cell: the kernel named ``causal_gqa_attention`` against
this family's ``attention_flops`` / ``attention_bytes`` (the two attention
layers' exact causal half at ``4 x 64`` a pair a query head). The kernel's
products run 64 deep and 64 wide on a 128 x 128 matrix unit, so this share
reads at most about 50 % however well the kernel runs. An entry of its own
because the accepted entry's list of cells is held by a test no PR may edit
(``tests/benchmarks/test_bench_hybrid_ssm.py``); the reader is that entry's,
not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

_accepted = manifest.load_layer_metric("causal_attention_roofline")
OP_PATTERNS = _accepted.OP_PATTERNS
read = _accepted.read
