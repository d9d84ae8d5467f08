"""Layer: Kernels (device programs, by the model's own parts). The accepted
``project_device_ms_per_shard.drain`` read in the ``lfm2-24b-a2b`` cell:
``harness/part_times.py`` over the traced interval; under ``conv_gqa`` the
attention kernel and the two passes of a ``conv`` layer's gates and taps
(``B x z``; the taps and ``C x``) are ``mixer``, the in- and out-projections
of both kinds of layer and the attention's q, k and v ``project``, the
leading layer's dense FFN ``ffn``, router and the three expert kernels
``experts``, rotary, the tails' handling and the layer scan's own work
``around``. An entry of its own because the accepted entry's list of cells
is held by a test no PR may edit (``tests/benchmarks/test_bench_parts.py``);
the reader is that entry's, not a copy. Moves ``drain_rows_per_s``."""

from benchmarks.harness import manifest

read = manifest.load_layer_metric("project_device_ms_per_shard.drain").read
