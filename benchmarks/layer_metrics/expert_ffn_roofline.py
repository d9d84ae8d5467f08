"""Layer: Kernels (kernels/grouped_ffn.py). The grouped expert matmul's share
of its roofline, in percent: the least time the chip could take for the
routed experts the window's documents NEED a second — the larger of the FLOPs
of the pairs routed to the experts held (0.5 a token if routing is even) over
the bf16 peak and the held experts' weights once a document over the HBM
bandwidth — over the share of the traced interval the kernel ran. The
program reads the weights once a 4,096-token SEGMENT, where a tile of about
128 rows an expert is bound by that read: the share says what batching more
rows an expert would win. Moves ``drain_rows_per_s``.

``OP_PATTERNS`` is data: the kernel's name as given to ``pallas_call``."""

OP_PATTERNS = {"expert_ffn": r"^%?moe_grouped_swiglu"}


def read(run):
    trace, peaks, needed = run.get("trace"), run.get("peaks"), run.get("lm_needed")
    times = run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or not peaks or not needed:
        return None
    if "expert_flops" not in needed:
        return None
    seconds = (times.get("expert_ffn") or {}).get("seconds", 0.0)
    if seconds <= 0:
        return None
    least = max(needed["expert_flops"] / peaks["bf16_flops_per_s"],
                needed["expert_bytes"] / peaks["hbm_bytes_per_s"])
    rate = run["end_to_end"]["drain_rows_per_s"] * least
    return 100.0 * rate / (seconds / trace["window_s"])
