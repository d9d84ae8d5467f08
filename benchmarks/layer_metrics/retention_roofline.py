"""Layer: Kernels (kernels/power_retention.py). The retention kernels' share
of their own roofline, in percent: the least time the chip could take for
what the window's documents NEED of the mixer a second — the larger of FLOPs
over the bf16 peak (``lm_flops.retention_flops_needed``: the cheaper of the
two forms) and bytes over the HBM bandwidth (q, k, v, y and the gate once) —
over the share of the traced interval the kernels ran. At the published
widths the FLOPs bound it. Moves ``drain_rows_per_s``.

``OP_PATTERNS`` is data: the kernel's ``XLA Ops`` events carry the name given
to ``pallas_call`` (``power_retention``); ``harness/op_times.py`` sums them."""

OP_PATTERNS = {"retention": r"power_retention"}


def read(run):
    trace, peaks, needed = run.get("trace"), run.get("peaks"), run.get("lm_needed")
    times = run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or not peaks or not needed:
        return None
    if "retention_flops" not in needed:     # another family's counter
        return None
    seconds = (times.get("retention") or {}).get("seconds", 0.0)
    if seconds <= 0:
        return None
    least = max(needed["retention_flops"] / peaks["bf16_flops_per_s"],
                needed["retention_bytes"] / peaks["hbm_bytes_per_s"])
    rate = run["end_to_end"]["drain_rows_per_s"] * least
    return 100.0 * rate / (seconds / trace["window_s"])
