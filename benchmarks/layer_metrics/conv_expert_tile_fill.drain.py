"""Layer: Ops (ops/map_score_lm.py; models/moe.py; kernels/grouped_ffn.py). The
accepted ``expert_tile_fill.drain`` read in the ``lfm2-24b-a2b`` cell: 100 x
``moe_expert_pairs_total`` over (``moe_tiles_total`` x the rows a tile): 256
rows an expert a segment on average in tiles of 256, so about every second
expert spills into a second tile. An entry of its own because the accepted
entry's list of cells is held by a test no PR may edit
(``tests/benchmarks/test_bench_window_moe.py``); the reader is that entry's,
not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

read = manifest.load_layer_metric("expert_tile_fill.drain").read
