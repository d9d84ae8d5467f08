"""Layer: Ops (ops/map_classify_tpu.py). Milliseconds the poster thread WAITS
for the device before it can read a shard's answers (the deferred
device-to-host fetch in ``finalize``): device time seen from the host, not
host work. Mean of ``task_phase_seconds{op, phase="fetch"}`` over what the
histogram gained inside the window. Moves ``drain_rows_per_s``."""

from benchmarks.harness.stack import histogram_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    total, count = histogram_delta(before, after, "task_phase_seconds",
                                   op=run["op"], phase="fetch")
    return total * 1e3 / count if count else None
