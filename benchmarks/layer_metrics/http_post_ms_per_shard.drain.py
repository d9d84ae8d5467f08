"""Layer: Agent loop and pipeline. Milliseconds of one ``/v1/results`` round
trip as the poster thread pays it (serialize, send, the controller's apply,
the answer), alone: mean of ``task_phase_seconds{op, phase="post_http"}`` over
what the histogram gained inside the window. The part of
``post_ms_per_shard.drain`` that is the wire and the controller, measured at
its own boundary and not by subtraction. Moves ``drain_rows_per_s``."""

from benchmarks.harness.stack import histogram_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    total, count = histogram_delta(before, after, "task_phase_seconds",
                                   op=run["op"], phase="post_http")
    return total * 1e3 / count if count else None
