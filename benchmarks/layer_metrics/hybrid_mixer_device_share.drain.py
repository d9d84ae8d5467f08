"""Layer: Kernels (kernels/ssd.py, kernels/causal_attention.py). Percent of
the device's busy time in the traced interval that lies inside the mixer's
two kernels: the state-space scan and the causal attention beside it (the
``XLA Ops`` events named after them, ``harness/op_times.py``; the
convolution is XLA's, fused into the pass that reads its input, and has no
event of its own). The attention grows with the square of a document's
length where everything else grows with the length: the longer the
documents, the larger this share, and what lowers it at given traffic is the
kernels. Moves ``drain_rows_per_s``."""

OP_PATTERNS = {"ssd_scan": r"^%?ssd_scan",
               "causal_attention": r"^%?causal_gqa_attention"}


def read(run):
    trace, times = run.get("trace"), run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or trace["busy_s"] <= 0:
        return None
    seconds = sum((times.get(label) or {}).get("seconds", 0.0)
                  for label in OP_PATTERNS)
    if seconds <= 0:
        return None
    return 100.0 * seconds / trace["busy_s"]
