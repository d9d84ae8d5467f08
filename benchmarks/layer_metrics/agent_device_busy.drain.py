"""Layer: Device. The agent's OWN account of the chip's time, in percent:
``device_busy_seconds_total`` (all ops) over busy plus
``device_idle_seconds_total``, of what both counters gained inside the
window. Busy is counted from completion events (dispatch, or the previous
program's completion, to the result seen ready on the host:
``Agent.note_device_interval``), so on a chip the trace shows busy this reads
near 100 and agrees with ``device.busy_s / device.window_s``. A program
without ``device_thread_seconds_total`` predates that accounting (its two
counters were dispatch seconds and a host thread's queue wait): nothing to
read. Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta, counter_sum


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    if counter_sum(after, "device_thread_seconds_total") is None:
        return None
    busy = counter_delta(before, after, "device_busy_seconds_total")
    idle = counter_delta(before, after, "device_idle_seconds_total")
    if busy is None or idle is None or busy + idle <= 0:
        return None
    return 100.0 * busy / (busy + idle)
