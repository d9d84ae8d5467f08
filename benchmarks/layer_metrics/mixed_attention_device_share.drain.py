"""Layer: Kernels (kernels/causal_attention.py: causal_attention and
window_attention). Percent of the device's busy time in the traced interval
that lies inside the two attention kernels of a model whose layers mix window
and full attention (the ``XLA Ops`` events named after them,
``harness/op_times.py``): the full layers' grows with the square of a
document's length, the window layers' with the length. Only a program that
runs BOTH has something to read: the causal kernel alone is another mixer's.
Moves ``drain_rows_per_s``."""

OP_PATTERNS = {"window_attention": r"^%?window_gqa_attention",
               "causal_attention": r"^%?causal_gqa_attention"}


def read(run):
    trace, times = run.get("trace"), run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or trace["busy_s"] <= 0:
        return None
    seconds = [(times.get(label) or {}).get("seconds", 0.0)
               for label in OP_PATTERNS]
    if min(seconds) <= 0:
        return None
    return 100.0 * sum(seconds) / trace["busy_s"]
