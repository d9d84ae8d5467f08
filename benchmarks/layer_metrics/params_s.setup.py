"""Layer: Runtime (runtime/). Seconds of set-up spent building the tenants'
weights and placing them on the device: ``runtime_params_seconds_total``
(``TpuRuntime.get_params`` misses) as it stands in the snapshot taken at the
window's first instant. Contains the small executables each build obtains,
so it overlaps ``xla_compile_s.setup``. Moves ``setup_s``."""

from benchmarks.harness.counters import counter_sum


def read(run):
    if run["kind"] != "drain":
        return None
    return counter_sum(run["agent_metrics"][0], "runtime_params_seconds_total")
