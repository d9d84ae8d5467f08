"""Layer: Kernels (kernels/grouped_ffn.py). The accepted
``expert_ffn_roofline`` read in the ``lfm2-24b-a2b`` cell: the grouped
expert matmul by its name, against this family's ``expert_flops`` /
``expert_bytes`` (4 routed pairs a token over ALL 64 experts of 2,048 x
1,536: 256 rows an expert a 4,096-token segment, one row tile on average, so
every expert over the mean spills into a second; products and the weights'
stream take about as long). An entry of its own because the accepted entry's
list of cells is held by a test no PR may edit
(``tests/benchmarks/test_bench_sparse_mla.py``); the reader is that entry's,
not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

_accepted = manifest.load_layer_metric("expert_ffn_roofline")
OP_PATTERNS = _accepted.OP_PATTERNS
read = _accepted.read
