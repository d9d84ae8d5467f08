"""Layer: Kernels (kernels/power_retention.py). Percent of the device's busy
time in the traced interval that lies inside the retention kernels (the
``XLA Ops`` events named ``power_retention``, ``harness/op_times.py``). A
linear layer's cost a token does not grow with length, so no traffic raises
this share; what moves it is the kernel. Moves ``drain_rows_per_s``."""

OP_PATTERNS = {"retention": r"power_retention"}


def read(run):
    trace, times = run.get("trace"), run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or trace["busy_s"] <= 0:
        return None
    seconds = (times.get("retention") or {}).get("seconds", 0.0)
    if seconds <= 0:
        return None
    return 100.0 * seconds / trace["busy_s"]
