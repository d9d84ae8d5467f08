"""Backend compiles counted by the benchmark itself, in the process that
holds the chip. ``runtime_compile_cache_total`` counts builds of a jit
wrapper, not XLA compiles, so a program whose input types flip would
recompile unseen; this listener sees every executable JAX obtains, compiled
or loaded from the persistent cache (either stalls the caller)."""

from __future__ import annotations

import threading
import time
from typing import List, Tuple

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Wall-clock stamped record of backend compiles and cache hits."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.compiles: List[Tuple[float, float]] = []   # (wall_end, seconds)
        self.cache_hits: List[float] = []               # wall

    def install(self) -> "CompileCounter":
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event: str, seconds: float, **_: object) -> None:
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.compiles.append((time.time(), float(seconds)))

    def _on_event(self, event: str, **_: object) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits.append(time.time())

    def between(self, t0: float, t1: float) -> int:
        """Executables obtained (compiled or loaded) with ``t0 <= end < t1``."""
        with self._lock:
            return sum(1 for end, _ in self.compiles if t0 <= end < t1)

    def totals(self) -> dict:
        with self._lock:
            return {
                "executables": len(self.compiles),
                "cache_hits": len(self.cache_hits),
                "seconds": sum(s for _, s in self.compiles),
            }
