"""Layer: Kernels / device programs (models/decoder_lm.py: blocked_logprobs).
The blocked loss head's share of its roofline, in percent: the least time
the chip could take for the head of the window's documents a second — the
larger of 2 x d x V FLOPs a token over the bf16 peak and the head's weights
and the hidden states once over the HBM bandwidth — over the share of the
traced interval in which the head's program (``jit_lm_loss_head``: the
vocabulary-blocked logits, the running log-sum-exp, the target's logit)
ran. Compute-bound at 16,384-token documents. Moves ``drain_rows_per_s``."""

PROGRAM_PATTERNS = {"lm_loss_head": r"^jit_lm_loss_head\("}


def read(run):
    trace, peaks, needed = run.get("trace"), run.get("peaks"), run.get("lm_needed")
    if run["kind"] != "drain" or not trace or not peaks or not needed:
        return None
    program = trace["programs"].get("lm_loss_head")
    if not program or program["clipped_seconds"] <= 0:
        return None
    least = max(needed["head_flops"] / peaks["bf16_flops_per_s"],
                needed["head_bytes"] / peaks["hbm_bytes_per_s"])
    rate = run["end_to_end"]["drain_rows_per_s"] * least
    return 100.0 * rate / (program["clipped_seconds"] / trace["window_s"])
