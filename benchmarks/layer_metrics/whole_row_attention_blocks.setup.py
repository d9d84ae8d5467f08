"""Layer: Kernels (kernels/flash_attention.py). Attention blocks the agent's
tasks traced onto the whole-row kernel by the window's end:
``attention_blocks_traced_total{path="whole_row"}``. It ticks while a program
is TRACED, once a block (12 a BERT-base program), so every tick falls in
set-up and the count says how many of the cell's programs hold the kernel:
12 tenants x 12 blocks where the predicate takes the cell's length, 0 where
it leaves it dense. A program without the counter has no such kernel:
nothing to read. Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_sum


def read(run):
    if run["kind"] != "drain":
        return None
    return counter_sum(run["agent_metrics"][1],
                       "attention_blocks_traced_total", path="whole_row")
