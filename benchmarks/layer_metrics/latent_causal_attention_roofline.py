"""Layer: Kernels (kernels/causal_attention.py at ONE query head a key head,
over keys expanded from latents: the ``dense_mla`` mixer). The accepted
``causal_attention_roofline`` read in the ``mistral-small-4-119b`` cell: the
same kernel by the same name, the same arithmetic on this family's
``attention_flops`` / ``attention_bytes`` (the exact causal half, 512 FLOPs a
pair a head). It is an entry of its own because the accepted entry's list
of cells is held to one cell by a test no PR may edit
(``tests/benchmarks/test_bench_hybrid_ssm.py``); the reader is that entry's,
not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

_accepted = manifest.load_layer_metric("causal_attention_roofline")
OP_PATTERNS = _accepted.OP_PATTERNS
read = _accepted.read
