"""Layer: Kernels (device programs, by the model's own parts). Milliseconds of a
shard's wall time the device spent in the part ``experts`` (router, sort,
gathers, the grouped matmul, the combine (``expert_ffn_roofline`` sees the
grouped kernel alone)): the part's share of the traced interval
(``harness/part_times.py``: the ``XLA Ops`` events' self time under the name
the program entered with ``obs.trace.part``, read back by
``TpuRuntime.program_parts``) times the window's seconds a shard. ``None``
without a trace, a part map or time in the part. Moves ``drain_rows_per_s``."""

from benchmarks.harness import part_times


def read(run):
    return part_times.part_ms_per_shard(run, "experts")
