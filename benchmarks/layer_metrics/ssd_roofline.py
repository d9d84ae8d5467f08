"""Layer: Kernels (kernels/ssd.py: ssd_scan). The state-space scan kernel's
share of its roofline, in percent: the least time the chip could take for
the scan the window's documents NEED a second — the larger of its FLOPs
(``hybrid_ssm_flops.ssd_flops``: the cheaper of the token-by-token and the
chunked form, C B^T once a group) over the bf16 peak and its bytes (x, y, B,
C and the step once) over the HBM bandwidth — over the share of the traced
interval the kernel ran. At the published widths the bytes bound it, by
little. A program without the kernel has nothing to read. Moves
``drain_rows_per_s``.

``OP_PATTERNS`` is data: the kernel's name as given to ``pallas_call``, held
to the START of the event's name (an event is named by its whole instruction,
operands and all)."""

OP_PATTERNS = {"ssd_scan": r"^%?ssd_scan"}


def read(run):
    trace, peaks, needed = run.get("trace"), run.get("peaks"), run.get("lm_needed")
    times = run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or not peaks or not needed:
        return None
    if "ssd_flops" not in needed:           # another family's counter
        return None
    seconds = (times.get("ssd_scan") or {}).get("seconds", 0.0)
    if seconds <= 0:
        return None
    least = max(needed["ssd_flops"] / peaks["bf16_flops_per_s"],
                needed["ssd_bytes"] / peaks["hbm_bytes_per_s"])
    rate = run["end_to_end"]["drain_rows_per_s"] * least
    return 100.0 * rate / (seconds / trace["window_s"])
