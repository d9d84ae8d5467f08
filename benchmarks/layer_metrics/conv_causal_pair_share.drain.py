"""Layer: Ops (ops/map_score_lm.py). The accepted
``causal_attention_pair_share.drain`` read in the ``lfm2-24b-a2b`` cell: 100
x causal / computed of ``causal_attention_pairs_total{kind}``, which the
``conv_gqa`` mixer ticks for its attention layers at the query tile the
stacked heads of a cache row's PAIR take (8 x 512 rows a step). An entry of
its own because the accepted entry's list of cells is held by a test no PR
may edit (``tests/benchmarks/test_bench_hybrid_ssm.py``); the reader is that
entry's, not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

read = manifest.load_layer_metric("causal_attention_pair_share.drain").read
