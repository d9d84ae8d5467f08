"""Layer: Ops (ops/map_score_lm.py). Of the tokens dispatched to a retention
mixer inside the window, the percentage whose chunk read a carried state:
100 x state / (state + quadratic) of ``retention_tokens_total{path}``. A
document's first chunk is the quadratic form alone; every later chunk reads
the state, across chunks and across segment programs. Says that the state
path ran at all, and how much of the traffic it carried. A program without
the counter has no such mixer: nothing to read. Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    state = counter_delta(before, after, "retention_tokens_total", path="state")
    alone = counter_delta(before, after, "retention_tokens_total",
                          path="quadratic")
    if state is None or alone is None or state + alone <= 0:
        return None
    return 100.0 * state / (state + alone)
