"""Layer: Kernels (kernels/causal_attention.py: causal_attention). The causal
grouped-query attention kernel's share of its roofline, in percent: the least
time the chip could take for the attention the window's documents NEED a
second — the exact causal half, ``4 x heads x 128 x L (L + 1) / 2`` FLOPs a
layer over the bf16 peak, or q, o, k and v once over the HBM bandwidth if
that is more (it is not, past a few hundred tokens) — over the share of the
traced interval the kernel ran. A program without the kernel has nothing to
read. Moves ``drain_rows_per_s``.

``OP_PATTERNS`` is data: the kernel's name as given to ``pallas_call``, held
to the START of the event's name."""

OP_PATTERNS = {"causal_attention": r"^%?causal_gqa_attention"}


def read(run):
    trace, peaks, needed = run.get("trace"), run.get("peaks"), run.get("lm_needed")
    times = run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or not peaks or not needed:
        return None
    if "attention_flops" not in needed:     # another family's counter
        return None
    seconds = (times.get("causal_attention") or {}).get("seconds", 0.0)
    if seconds <= 0:
        return None
    least = max(needed["attention_flops"] / peaks["bf16_flops_per_s"],
                needed["attention_bytes"] / peaks["hbm_bytes_per_s"])
    rate = run["end_to_end"]["drain_rows_per_s"] * least
    return 100.0 * rate / (seconds / trace["window_s"])
