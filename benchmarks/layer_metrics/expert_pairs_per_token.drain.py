"""Layer: Ops (ops/map_score_lm.py; models/moe.py). (token, expert) pairs
routed to the experts this chip holds, a token a expert layer, inside the
window: ``moe_expert_pairs_total`` over ``moe_tokens_total`` (the pairs are
counted on the device by the expert layers and come back with a shard's
answer; the tokens are the dispatched slots times the expert layers). 0.5 if
the router spreads its 8 choices evenly over 256 experts of which 16 are
held; what it reads says how even the routing is and sizes the grouped
matmul. A program without the counters routes nothing: nothing to read. Moves
``drain_rows_per_s``."""

from benchmarks.harness.counters import counter_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    pairs = counter_delta(before, after, "moe_expert_pairs_total")
    tokens = counter_delta(before, after, "moe_tokens_total")
    if pairs is None or tokens is None or tokens <= 0:
        return None
    return pairs / tokens
