"""Layer: Kernels (kernels/sparse_mla.py: index_select). The indexer-and-
selection kernel's share of its roofline, in percent: the least time the chip
could take for the index scores the window's documents NEED a second (2 x
heads x dimension FLOPs a causal pair, every layer, over the bf16 peak; the
scores are never in HBM, so no bytes bound it) over the share of the traced
interval the kernel ran. The kernel's time also holds the selection (the
counting passes that find each query's threshold), which needs no matmul: what
they cost shows here as a lower share. Moves ``drain_rows_per_s``.

``OP_PATTERNS`` is data: the kernel's ``XLA Ops`` events carry the name given
to ``pallas_call``; ``harness/op_times.py`` sums them. An event's name is the
whole instruction, ``%<name>.<n> = ... custom-call(operands)``, and the
attention's operands name this kernel's result: the pattern is held to the
START of the name (unanchored it read the attention's time too: PR 33's first
traced run)."""

OP_PATTERNS = {"sparse_index": r"^%?sparse_index_select"}


def read(run):
    trace, peaks, needed = run.get("trace"), run.get("peaks"), run.get("lm_needed")
    times = run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or not peaks or not needed:
        return None
    if "indexer_flops" not in needed:       # another family's counter
        return None
    seconds = (times.get("sparse_index") or {}).get("seconds", 0.0)
    if seconds <= 0:
        return None
    least = needed["indexer_flops"] / peaks["bf16_flops_per_s"]
    rate = run["end_to_end"]["drain_rows_per_s"] * least
    return 100.0 * rate / (seconds / trace["window_s"])
