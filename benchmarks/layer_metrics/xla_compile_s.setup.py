"""Layer: Runtime (runtime/). Seconds of set-up the agent's tasks spent
waiting for XLA to hand over executables (backend compiles and persistent-
cache loads): ``runtime_compile_seconds_total``, all ops, as it stands in the
snapshot taken at the window's first instant. A program without
``runtime_xla_executables_total`` timed the building of jit wrappers under
that name: nothing to read. Overlaps ``params_s.setup`` (a params build
obtains small executables of its own); the two are not to be added. Moves
``setup_s``."""

from benchmarks.harness.counters import counter_sum


def read(run):
    if run["kind"] != "drain":
        return None
    at_open = run["agent_metrics"][0]
    if counter_sum(at_open, "runtime_xla_executables_total") is None:
        return None
    return counter_sum(at_open, "runtime_compile_seconds_total")
