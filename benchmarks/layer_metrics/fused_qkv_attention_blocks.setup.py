"""Layer: Kernels (models/layers.py, kernels/flash_attention.py). Attention
blocks the agent's tasks traced with their Q, K, V projections as ONE matmul
on a fused weight leaf by the window's end:
``attention_qkv_traced_total{form="fused"}``. It ticks while a program is
TRACED, once a block (12 a BERT-base program), so every tick falls in set-up
and the count says how many of the cell's programs read a block's
activations once and hand the whole-row kernel the one result as column
blocks: 12 tenants x 12 blocks where the model's leaves were fused when its
weights were built, 0 where they stayed three. A program without the counter
has no such matmul: nothing to read. Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_sum


def read(run):
    if run["kind"] != "drain":
        return None
    return counter_sum(run["agent_metrics"][1],
                       "attention_qkv_traced_total", form="fused")
