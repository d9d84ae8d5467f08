"""Layer: Ops (ops/map_score_lm.py). Of the tokens dispatched to the
double-gated short convolutions inside the window, the percentage whose
segment program read a tail the segment before handed on: 100 x carried /
(carried + first_segment) of ``conv_tail_tokens_total{path}``. A document's
first segment starts from the zeros before the document; every later one
reads the last ``conv_taps - 1`` rows of the gated input across a program
boundary. 87.5 at 32,768 tokens in eight segments. Says that the carried
path ran at all, and how much of the traffic it carried. A program without
the counter has no such mixer: nothing to read. Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    carried = counter_delta(before, after, "conv_tail_tokens_total",
                            path="carried")
    first = counter_delta(before, after, "conv_tail_tokens_total",
                          path="first_segment")
    if carried is None or first is None or carried + first <= 0:
        return None
    return 100.0 * carried / (carried + first)
