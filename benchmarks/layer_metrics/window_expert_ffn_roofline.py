"""Layer: Kernels (kernels/grouped_ffn.py). The accepted
``expert_ffn_roofline`` read in the ``mellum2-12b-a2.5b`` cell: the grouped expert matmul by its
name, against this family's ``expert_flops`` / ``expert_bytes`` (8 routed
pairs a token over all 64 experts of 2,304 x 896: about 512 rows an expert a
4,096-token segment, FULL tiles, where the two cells that held a share had
about 128). An
entry of its own because the accepted entry's list of cells is held to one
cell by a test no PR may edit (``tests/benchmarks/test_bench_sparse_mla.py``); the reader is that entry's,
not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

_accepted = manifest.load_layer_metric("expert_ffn_roofline")
OP_PATTERNS = _accepted.OP_PATTERNS
read = _accepted.read
