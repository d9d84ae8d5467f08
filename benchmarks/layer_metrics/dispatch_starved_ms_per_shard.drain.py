"""Layer: Agent loop and pipeline. Milliseconds a shard the device-owning
thread spent blocked on the staged queue, with nothing to dispatch:
``device_thread_seconds_total{state="wait_staged"}`` gained inside the window
over the shards accepted in it. The thread's side of starvation (the chip may
still be busy with what is in flight: ``agent_device_busy.drain`` says).
Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta


def read(run):
    if run["kind"] != "drain" or not run["shards"]:
        return None
    before, after = run["agent_metrics"]
    waited = counter_delta(before, after, "device_thread_seconds_total",
                           state="wait_staged")
    return None if waited is None else waited * 1e3 / run["shards"]
