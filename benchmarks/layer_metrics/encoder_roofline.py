"""Layer: Kernels / device programs. The classify program's share of its
roofline, in percent, compute-bound: the FLOPs a second the window's rows
NEEDED (``flops.encoder_flops_needed``: each row at its real token count, not
the padded one, head left out; times ``drain_rows_per_s``) over the share of
the traced interval in which the program ran on the device, over the chip's
bf16 peak. Moves ``drain_rows_per_s``.

``PROGRAM_PATTERNS`` is data: XLA's own module names as one v5e trace showed
them (``jit_<python function>(<fingerprint>)``); ``run_fwd`` is the function
``ops/map_classify_tpu._execute_chunks`` jits. Stable ``jax.named_scope``
names are the tracing issue's."""

PROGRAM_PATTERNS = {"classify": r"^jit_run_fwd\("}


def read(run):
    trace, peaks = run.get("trace"), run.get("peaks")
    if run["kind"] != "drain" or not trace or not peaks:
        return None
    program = trace["programs"].get("classify")
    if not program or program["clipped_seconds"] <= 0:
        return None
    on_device = program["clipped_seconds"] / trace["window_s"]
    needed = run["end_to_end"]["drain_rows_per_s"] * run["mean_flops_per_row"]
    return 100.0 * needed / on_device / peaks["bf16_flops_per_s"]
