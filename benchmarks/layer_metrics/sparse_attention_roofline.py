"""Layer: Kernels (kernels/sparse_mla.py: masked_attention). The attention
kernel's share of its roofline, in percent: the least time the chip could
take for the attention the window's documents NEED a second — at the form
with the fewest FLOPs (``sparse_mla_flops.sparse_attention_needed``: over the
selected keys and absorbed at long documents, dense on expanded keys at short
ones), the larger of its FLOPs over the bf16 peak and its bytes (the latents
gathered a query) over the HBM bandwidth — over the share of the traced
interval the kernel ran. The program ships the dense form, so at 32,768
tokens the share is bounded near the two forms' ratio: what a gathered form
would win is on the record here. Moves ``drain_rows_per_s``.

``OP_PATTERNS`` is data: the kernel's name as given to ``pallas_call``."""

OP_PATTERNS = {"sparse_attention": r"^%?sparse_mla_attention"}


def read(run):
    trace, peaks, needed = run.get("trace"), run.get("peaks"), run.get("lm_needed")
    times = run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or not peaks or not needed:
        return None
    if "sparse_attention_flops" not in needed:
        return None
    seconds = (times.get("sparse_attention") or {}).get("seconds", 0.0)
    if seconds <= 0:
        return None
    least = max(needed["sparse_attention_flops"] / peaks["bf16_flops_per_s"],
                needed["sparse_attention_bytes"] / peaks["hbm_bytes_per_s"])
    rate = run["end_to_end"]["drain_rows_per_s"] * least
    return 100.0 * rate / (seconds / trace["window_s"])
