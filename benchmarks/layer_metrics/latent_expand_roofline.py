"""Layer: Kernels (kernels/sparse_mla.py: expand_latents, under ``dense_mla``
with ``join_rotary_key``'s weight). The latents' expansion kernel's share of
its roofline, in percent: the least time the chip could take to expand the
window's documents' latents ONCE a second — the larger of ``2 x kv_lora_rank
x heads x (nope + v)`` FLOPs a token a layer over the bf16 peak and the
cached vector read plus every head's key and value written once over the HBM
bandwidth (the bytes bound it) — over the share of the traced interval the
kernel ran. The program expands a cached latent again in every later segment
(8.5 x a token at 16 segments a document: ``latent_expansions_per_token.
drain``) and carries the rotary key through an identity block of the weight,
so the share reads LOW, and by how much is the price of carrying latents
between segment programs. A program without the kernel, or another
family's needed-work counter, has nothing to read. Moves
``drain_rows_per_s``.

``OP_PATTERNS`` is data: the kernel's name as given to ``pallas_call``, held
to the START of the event's name."""

OP_PATTERNS = {"latent_expand": r"^%?sparse_mla_expand"}


def read(run):
    trace, peaks, needed = run.get("trace"), run.get("peaks"), run.get("lm_needed")
    times = run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or not peaks or not needed:
        return None
    if "expand_flops" not in needed:        # another family's counter
        return None
    seconds = (times.get("latent_expand") or {}).get("seconds", 0.0)
    if seconds <= 0:
        return None
    least = max(needed["expand_flops"] / peaks["bf16_flops_per_s"],
                needed["expand_bytes"] / peaks["hbm_bytes_per_s"])
    rate = run["end_to_end"]["drain_rows_per_s"] * least
    return 100.0 * rate / (seconds / trace["window_s"])
