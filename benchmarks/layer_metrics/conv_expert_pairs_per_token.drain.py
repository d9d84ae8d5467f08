"""Layer: Ops (ops/map_score_lm.py; models/moe.py). The accepted
``expert_pairs_per_token.drain`` read in the ``lfm2-24b-a2b`` cell:
``moe_expert_pairs_total`` over ``moe_tokens_total``; exactly 4.0 (4 a
token, every expert held: no pair is routed elsewhere). An entry of its own
because the accepted entry's list of cells is held by a test no PR may edit
(``tests/benchmarks/test_bench_sparse_mla.py``); the reader is that entry's,
not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

read = manifest.load_layer_metric("expert_pairs_per_token.drain").read
