"""Layer: Ops (ops/map_score_lm.py). Of the (query, key) pairs in the key
tiles the WINDOW kernel's grid visits for the window's shards, the percentage
that lie inside their real tokens' windows: 100 x window / computed of
``window_attention_pairs_total{kind}`` (counted at dispatch from the
documents' lengths, the window and the kernel's tile sizes: token t needs
``min(t + 1, sliding_window)`` pairs, a query tile meets the whole key tiles
from its window's lower edge to its diagonal). 65.9 at 32,768 tokens, a
window of 1,024 and 512-wide tiles (three tiles visited for two needed); a
program that ran the window layers over every causal key would read 6. A
program without the counter has no window layer: nothing to read. Moves
``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    window = counter_delta(before, after, "window_attention_pairs_total",
                           kind="window")
    computed = counter_delta(before, after, "window_attention_pairs_total",
                             kind="computed")
    if window is None or computed is None or computed <= 0:
        return None
    return 100.0 * window / computed
