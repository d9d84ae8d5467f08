"""Layer: Kernels (kernels/sparse_mla.py). Percent of the device's busy time
in the traced interval that lies inside the mechanism's two kernels: the
indexer with its selection, and the attention under it (the ``XLA Ops`` events
named after them, ``harness/op_times.py``). Both grow with the square of a
document's length where everything else grows with the length: the longer the
documents, the larger this share, and what lowers it at given traffic is the
kernels. Moves ``drain_rows_per_s``."""

OP_PATTERNS = {"sparse_index": r"^%?sparse_index_select",
               "sparse_attention": r"^%?sparse_mla_attention"}


def read(run):
    trace, times = run.get("trace"), run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or trace["busy_s"] <= 0:
        return None
    seconds = sum((times.get(label) or {}).get("seconds", 0.0)
                  for label in OP_PATTERNS)
    if seconds <= 0:
        return None
    return 100.0 * seconds / trace["busy_s"]
