"""Layer: Ops (ops/map_score_lm.py). Cached latents the window's segment
programs expand to keys and values, a token whose latent the cache holds, a
layer: expanded / cached of ``latent_keys_expanded_total{kind}`` (counted at
dispatch from the documents' lengths and the segments they ran as: every
segment expands all the latents it can see, ``pos0 + its tokens``). 1 would
be every latent expanded once; 8.5 at 16 segments of 4,096 a 65,536-token
document. The price of carrying latents and not keys between programs; what
moves it is the segment's size or the attention's form. A program without
the counter has no such mixer: nothing to read. Moves ``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    expanded = counter_delta(before, after, "latent_keys_expanded_total",
                             kind="expanded")
    cached = counter_delta(before, after, "latent_keys_expanded_total",
                           kind="cached")
    if expanded is None or cached is None or cached <= 0:
        return None
    return expanded / cached
