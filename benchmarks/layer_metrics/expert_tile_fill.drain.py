"""Layer: Ops (ops/map_score_lm.py; models/moe.py; kernels/grouped_ffn.py).
How full the grouped expert matmul's row tiles were inside the window, in
percent: 100 x ``moe_expert_pairs_total`` over (``moe_tiles_total`` x the
rows a tile). Every held expert's rows are padded to whole tiles of
``ROW_TILE`` rows, so an expert with 512 rows a segment fills two tiles and
one with 130 fills half of its second: the kernel's MXU time goes with the
tiles, its useful work with the pairs. Both are counted on the device and
fetched with a shard's answer; a program that does not count its tiles has
nothing to read. Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta

# Rows a tile: ``kernels/grouped_ffn.py: ROW_TILE`` (a test holds the two
# equal; a reader imports nothing of the program).
ROW_TILE = 256


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    pairs = counter_delta(before, after, "moe_expert_pairs_total")
    tiles = counter_delta(before, after, "moe_tiles_total")
    if pairs is None or tiles is None or tiles <= 0:
        return None
    return 100.0 * pairs / (tiles * ROW_TILE)
