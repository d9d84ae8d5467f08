"""Layer: Kernels (device programs, by the model's own parts). The accepted
``experts_device_ms_per_shard.drain`` read in the ``mellum2-12b-a2.5b`` cell
(``harness/part_times.py`` over the traced interval; under ``window_gqa``
both attention kernels are ``mixer`` and their names tell them apart, the
four projections ``project``, router, index work and the three expert kernels
``experts``; the model has no dense FFN and no shared expert, so no ``ffn``).
An entry of its own because the accepted entry's list of cells is held to a
literal list by a test no PR may edit (``tests/benchmarks/test_bench_parts.py``);
the reader is that entry's, not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

read = manifest.load_layer_metric("experts_device_ms_per_shard.drain").read
