"""Layer: Kernels / device programs. The scoring programs' share of the
chip's bf16 peak, in percent: the FLOPs a second the window's documents
NEEDED at their real lengths (``lm_flops.document_flops_needed``: matmul
terms only, the layers, the head, and for retention the cheaper of the
quadratic and the chunked form; times ``drain_rows_per_s``) over the share of
the traced interval in which the scoring programs ran on the device. Moves
``drain_rows_per_s``.

``PROGRAM_PATTERNS`` is data: XLA's module names, ``jit_<function>``, of the
functions ``ops/map_score_lm._programs`` jits (a document's segments, first
and later, are both ``lm_segment``; the blocked head is ``lm_loss_head``)."""

PROGRAM_PATTERNS = {"lm_segment": r"^jit_lm_segment\(",
                    "lm_loss_head": r"^jit_lm_loss_head\("}


def read(run):
    trace, peaks, needed = run.get("trace"), run.get("peaks"), run.get("lm_needed")
    if run["kind"] != "drain" or not trace or not peaks or not needed:
        return None
    seconds = sum((trace["programs"].get(label) or {}).get("clipped_seconds", 0.0)
                  for label in PROGRAM_PATTERNS)
    if seconds <= 0:
        return None
    on_device = seconds / trace["window_s"]
    rate = run["end_to_end"]["drain_rows_per_s"] * needed["flops"]
    return 100.0 * rate / on_device / peaks["bf16_flops_per_s"]
