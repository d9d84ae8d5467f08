"""Layer: Runtime (runtime/, with every layer above it on the path). Seconds
from the agent's start to the last warm-up shard's acceptance: one shard of
every tenant through the normal path, so the tenants' weights
(``params_s.setup``), the executables read from the compile cache or compiled
(``xla_compile_s.setup``) and the Python tracing and lowering that no counter
holds. The part of ``setup_s`` the program decides. Moves ``setup_s``."""


def read(run):
    return (run.get("setup_phases") or {}).get("warm_up_s")
