"""The benchmark's harness: everything ``benchmarks/run.py`` needs that is
not one configuration's, one traffic mix's or one per-layer metric's own
file. Later PRs add files beside these and entries to ``BENCHMARK.json``;
they never edit what is here."""
