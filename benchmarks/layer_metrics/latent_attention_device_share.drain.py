"""Layer: Kernels (kernels/sparse_mla.py: expand_latents;
kernels/causal_attention.py). Percent of the device's busy time in the traced
interval that lies inside dense latent attention's two kernels: the latents'
expansion and the causal attention over the expanded keys (the ``XLA Ops``
events named after them, ``harness/op_times.py``). Both grow with the square
of a document's length (the expansion because every segment expands all it
can see) where everything else grows with the length. Only a program whose
mixer runs BOTH has something to read: the attention kernel alone is another
mixer's. Moves ``drain_rows_per_s``."""

OP_PATTERNS = {"latent_expand": r"^%?sparse_mla_expand",
               "causal_attention": r"^%?causal_gqa_attention"}


def read(run):
    trace, times = run.get("trace"), run.get("op_times") or {}
    if run["kind"] != "drain" or not trace or trace["busy_s"] <= 0:
        return None
    seconds = [(times.get(label) or {}).get("seconds", 0.0)
               for label in OP_PATTERNS]
    if min(seconds) <= 0:
        return None
    return 100.0 * sum(seconds) / trace["busy_s"]
