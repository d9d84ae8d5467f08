"""Layer: Agent loop and pipeline. HOST milliseconds the poster thread spends
on a shard: shaping the result and the HTTP post of it, WITHOUT the wait for
the device. The agent's ``post`` span covers finalize (the deferred
device-to-host fetch, then shaping) and the post; the op stamps the fetch's
wait as ``task_phase_seconds{op, phase="fetch"}``. Mean span of the shards
posted in the window less the mean fetch wait the histogram gained in it.
Moves ``drain_rows_per_s``."""

from benchmarks.harness.stack import histogram_delta


def read(run):
    spans = run.get("post_span_s") if run["kind"] == "drain" else None
    if not spans:
        return None
    before, after = run["agent_metrics"]
    wait, count = histogram_delta(before, after, "task_phase_seconds",
                                  op=run["op"], phase="fetch")
    if not count:
        return None
    return (sum(spans) / len(spans) - wait / count) * 1e3
