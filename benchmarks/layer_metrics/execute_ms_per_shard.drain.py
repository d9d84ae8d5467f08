"""Layer: Ops (ops/map_classify_tpu.py). Milliseconds the device thread spends dispatching a shard's programs (dispatch only in the no-fallback drain mode; the fetch is in the finalize phase). Mean of ``task_phase_seconds{op, phase="execute"}`` over
what the histogram gained inside the window. Moves ``drain_rows_per_s``."""

from benchmarks.harness.stack import histogram_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    total, count = histogram_delta(before, after, "task_phase_seconds",
                                   op=run["op"], phase="execute")
    return total * 1e3 / count if count else None
