"""Layer: Runtime (runtime/). Executables the PROGRAM counted inside the
window: what ``runtime_xla_executables_total`` gained (the runtime's own
``jax.monitoring`` listener: every executable obtained, compiled or loaded
from the persistent cache). Should be 0, and equal to
``compiles_in_window.drain``, which the benchmark counts itself. Moves
``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    return counter_delta(before, after, "runtime_xla_executables_total")
