"""Layer: Runtime (runtime/). Seconds of set-up that the first calls of the
agent's programs took beside XLA's own (``xla_compile_s.setup``): Python
tracing, lowering, the compile cache's key, dispatch:
``runtime_trace_lower_seconds_total``, all ops, as it stands in the snapshot
taken at the window's first instant (``runtime/executor.py: Program``: a first
call's wall time less the compile seconds the listener recorded on that
thread during it). A program without the counter has nothing to read. Moves
``setup_s``."""

from benchmarks.harness.counters import counter_sum


def read(run):
    if run["kind"] != "drain":
        return None
    return counter_sum(run["agent_metrics"][0],
                       "runtime_trace_lower_seconds_total")
