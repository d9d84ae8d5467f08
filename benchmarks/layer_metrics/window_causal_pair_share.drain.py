"""Layer: Ops (ops/map_score_lm.py). The accepted
``causal_attention_pair_share.drain`` read in the ``mellum2-12b-a2.5b`` cell: 100 x causal / computed of ``causal_attention_pairs_total{kind}``,
which the ``window_gqa`` mixer ticks for its FULL layers at the query tile
eight heads a key head take (512: 98.5 at 32,768 tokens). An
entry of its own because the accepted entry's list of cells is held to one
cell by a test no PR may edit (``tests/benchmarks/test_bench_hybrid_ssm.py``); the reader is that entry's,
not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

read = manifest.load_layer_metric("causal_attention_pair_share.drain").read
