"""Layer: Kernels (device programs, by the model's own parts). The guard on
the instrument itself: percent of the device's busy time in the traced
interval that lies in ``XLA Ops`` events NO part names
(``harness/part_times.py``): programs the runtime's keyed cache does not hold
(a concatenate, a transfer), copies and a loop's own bookkeeping, what an op
writes outside ``models/``. Near 100 with ``named_share`` 0 in the ``parts``
line: the executables came out of a compile cache older than the scopes
(metadata is no part of the cache's key). ``None`` without a trace or a part
map. Moves ``drain_rows_per_s``."""

from benchmarks.harness import part_times


def read(run):
    parts = part_times.of_run(run)
    if not parts:
        return None
    return 100.0 * parts["parts"].get(part_times.UNNAMED, 0.0) \
        / run["trace"]["busy_s"]
