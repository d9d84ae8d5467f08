"""Layer: Ops (ops/map_score_lm.py). Of the causal keys the window's tokens
could attend, the percentage the learned selection keeps: 100 x selected /
causal of ``sparse_attention_keys_total{kind}`` (counted at dispatch from the
documents' lengths: token t keeps min(t + 1, index_topk) of t + 1). 12.1 at
32,768 tokens and 2,048 kept; 100 for documents no longer than the selection.
Says how sparse the traffic makes the mechanism. A program without the
counter has no such mixer: nothing to read. Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    selected = counter_delta(before, after, "sparse_attention_keys_total",
                             kind="selected")
    causal = counter_delta(before, after, "sparse_attention_keys_total",
                           kind="causal")
    if selected is None or causal is None or causal <= 0:
        return None
    return 100.0 * selected / causal
