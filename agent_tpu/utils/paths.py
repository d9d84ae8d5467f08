"""Where this checkout keeps what it builds at run time.

One fixed directory, resolved from the package's own location and listed in
``.gitignore``: the XLA compile cache (``TpuRuntime``) and the native CSV
scanner's shared object (``data/native/build.py``) both live under it. The
path is part of the compile cache's key, so it never depends on the working
directory, a pid or a clock; and because it sits inside the checkout, only
what git would commit decides what a fresh copy of the tree runs.
"""

from __future__ import annotations

import os

# The directory that holds the ``agent_tpu`` package — the checkout root.
# Fleet children put it on PYTHONPATH so they import the tree the parent did.
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

CACHE_ROOT = os.path.join(REPO_ROOT, ".cache")


def cache_dir(name: str) -> str:
    """``<checkout>/.cache/<name>`` (not created here — the writer does)."""
    return os.path.join(CACHE_ROOT, name)
