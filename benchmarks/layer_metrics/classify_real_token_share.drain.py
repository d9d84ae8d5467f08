"""Layer: Ops (ops/map_classify_tpu.py, ops/_model_common.py). Of the token
slots the classify programs dispatched inside the window (program rows x
length: what the device computes on), the percentage that held a real token:
100 x real / dispatched of ``classify_token_slots_total{kind}``, which the op
ticks per shard at dispatch from the staged lengths and shapes. Says how
often, and how well, staging packs a shard's short rows several to a program
row (``pack_padded_chunk``): rows of 8-64 bytes padded to 64 read 49, packed
near 90; rows that fill their bucket read 100 and are not packed. A program
without the counter dispatches only padded chunks and says nothing about
them: nothing to read. Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    real = counter_delta(before, after, "classify_token_slots_total",
                         kind="real")
    slots = counter_delta(before, after, "classify_token_slots_total",
                          kind="dispatched")
    if real is None or slots is None or slots <= 0:
        return None
    return 100.0 * real / slots
