"""Layer: Runtime (runtime/). Executables JAX obtained inside the window —
compiled or loaded from the persistent cache, either stalls the caller —
counted by the benchmark's own ``jax.monitoring`` listener in the agent's
process (not ``runtime_compile_cache_total``, which counts builds of a jit
wrapper). Should be 0. Moves ``drain_rows_per_s``."""


def read(run):
    return float(run["compiles_in_window"]) if run["kind"] == "drain" else None
