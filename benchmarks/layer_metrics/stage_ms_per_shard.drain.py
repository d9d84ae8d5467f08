"""Layer: Agent loop and pipeline (agent/app.py, agent/pipeline.py, data/staging.py). Host milliseconds a shard spends in the staging pool: CSV scan, tokenize, pad. Mean of ``task_phase_seconds{op, phase="stage"}`` over
what the histogram gained inside the window. Moves ``drain_rows_per_s``."""

from benchmarks.harness.stack import histogram_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    total, count = histogram_delta(before, after, "task_phase_seconds",
                                   op=run["op"], phase="stage")
    return total * 1e3 / count if count else None
