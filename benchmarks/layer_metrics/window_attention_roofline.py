"""Layer: Kernels (kernels/causal_attention.py: window_attention). The
windowed attention kernel's share of its roofline, in percent: the least time
the chip could take for the window layers' attention the window's documents
NEED a second — the exact pairs inside the windows, ``4 x heads x 128 x sum
over t of min(t + 1, sliding_window)`` FLOPs a layer over the bf16 peak, or
q, o, k and v once over the HBM bandwidth if that is more (it is not) — over
the share of the traced interval the kernel ran. The kernel's grid visits
whole key tiles from a query tile's window's lower edge to its diagonal
(three 512-key tiles a 512-query tile at a window of 1,024: two thirds of
what it computes is needed, ``window_attention_pair_share.drain``), so the
share reads under that by construction. A program without the kernel, or
another family's needed-work counter, has nothing to read. Moves
``drain_rows_per_s``.

``OP_PATTERNS`` is data: the kernel's name as given to ``pallas_call``, held
to the START of the event's name (the full layers' kernel is
``causal_gqa_attention``)."""

OP_PATTERNS = {"window_attention": r"^%?window_gqa_attention"}


def read(run):
    trace, peaks, needed = run.get("trace"), run.get("peaks"), run.get("lm_needed")
    times = run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or not peaks or not needed:
        return None
    if "window_flops" not in needed:        # another family's counter
        return None
    seconds = (times.get("window_attention") or {}).get("seconds", 0.0)
    if seconds <= 0:
        return None
    least = max(needed["window_flops"] / peaks["bf16_flops_per_s"],
                needed["window_bytes"] / peaks["hbm_bytes_per_s"])
    rate = run["end_to_end"]["drain_rows_per_s"] * least
    return 100.0 * rate / (seconds / trace["window_s"])
