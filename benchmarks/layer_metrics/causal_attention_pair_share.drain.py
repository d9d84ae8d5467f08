"""Layer: Ops (ops/map_score_lm.py). Of the (query, key) pairs in the key
tiles the attention kernel's grid visits for the window's shards, the
percentage their real tokens need: 100 x causal / computed of
``causal_attention_pairs_total{kind}`` (counted at dispatch from the
documents' lengths and the kernel's tile sizes: token t needs t + 1 pairs, a
query tile meets whole key tiles up to the one that holds its last query).
99.2 at 65,536 tokens under 512-wide tiles; a kernel that visited the tiles
above the diagonal would read 50. A program without the counter has no such
mixer: nothing to read. Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    causal = counter_delta(before, after, "causal_attention_pairs_total",
                           kind="causal")
    computed = counter_delta(before, after, "causal_attention_pairs_total",
                             kind="computed")
    if causal is None or computed is None or computed <= 0:
        return None
    return 100.0 * causal / computed
