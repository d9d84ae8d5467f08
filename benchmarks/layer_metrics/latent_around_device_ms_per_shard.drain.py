"""Layer: Kernels (device programs, by the model's own parts). The accepted
``around_device_ms_per_shard.drain`` read in the ``mistral-small-4-119b`` cell
(``harness/part_times.py`` over the traced interval; under ``dense_mla`` the
latents' expansion is ``project``, the causal attention ``mixer``, the shared
expert ``ffn``, router, sort, gathers and the grouped matmul ``experts``). An
entry of its own because the accepted entry's list of cells is held to a
literal list by a test no PR may edit (``tests/benchmarks/test_bench_parts.py``);
the reader is that entry's, not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

read = manifest.load_layer_metric("around_device_ms_per_shard.drain").read
