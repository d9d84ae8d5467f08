"""Compiled-executable cache — the successor of the interpreter singleton.

The reference cached one native interpreter per model path so every task after
the first skipped model load (reference ``ops/_tpu_runtime.py:8-13,42-43``).
Under XLA the expensive artifact is the *compiled executable*: a traced +
compiled jit program for one (op, shape-bucket, dtype, sharding) combination.
This cache makes compilation a once-per-bucket cost, which is why ops feed it
bucketed static shapes (``agent_tpu.models.tokenizer.pad_batch``) — the cache
stays small and stops missing once the buckets are warm.

Keys are caller-built tuples of hashables (op name, shape tuple, dtype string,
mesh axis sizes). A key holds what the traced function CLOSES OVER (family,
shapes, static values such as a fused top-k, the config's fingerprint) and
nothing else; never whose weights are passed: parameters are arguments, so
every model of a config shares one wrapper, one trace and one executable
(``ops/map_classify_tpu.py``; the mesh and the attention function are the
runtime's, as this cache is). A field the program does not depend on costs a
trace, a lowering and a load for every value it takes. Stats are exported for
the metrics channel (SURVEY.md §5.5).

What the cache holds for ``runtime.compiled(key, build)`` is a ``jax.jit``
WRAPPER: XLA compiles (or loads from the persistent cache) at the wrapper's
first call, inside the op's dispatch. So executables are counted where JAX
obtains them, by :func:`install_xla_listener`, not where the wrapper is built.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Hashable, Tuple

from agent_tpu.obs import trace as obs_trace

# JAX's own monitoring events (jax/_src/dispatch.py, jax/_src/compilation_cache.py):
# the duration event wraps ``compile_or_get_cached``, so it fires once per
# executable obtained, compiled or loaded; the plain event marks a load.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_listener_lock = threading.Lock()
_listener_installed = False


def _on_duration(event: str, seconds: float, **kwargs: Any) -> None:
    if event == BACKEND_COMPILE_EVENT:
        obs_trace.record_compile(seconds, program=kwargs.get("fun_name", ""))


def _on_event(event: str, **_kwargs: Any) -> None:
    if event == CACHE_HIT_EVENT:
        obs_trace.record_xla_cache_hit()


def install_xla_listener() -> None:
    """Register the process's ONE ``jax.monitoring`` listener (idempotent).
    JAX calls listeners on the thread that needed the executable, so
    ``obs.trace.record_compile`` finds the task's ambient context there."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _listener_installed = True


class ExecutableCache:
    """Thread-safe build-once cache: key → built value (a compiled callable for
    executables; any expensive device-resident object in general — the runtime
    also uses it for HBM params, where double-build means double transfer).

    A single lock guards the map; the build itself runs outside the lock so a
    slow XLA compile does not serialize unrelated ops, with a per-key event so
    concurrent builders of the same key trigger exactly one build.

    With ``count_lookups`` (the default) every lookup ticks
    ``runtime_compile_cache_total{op, outcome}`` in the ambient task's
    registry. The params store passes ``False``: a weights transfer is not a
    program lookup.
    """

    def __init__(self, count_lookups: bool = True) -> None:
        self._lock = threading.Lock()
        self._cache: Dict[Tuple[Hashable, ...], Any] = {}
        self._building: Dict[Tuple[Hashable, ...], threading.Event] = {}
        self._generation = 0  # bumped by clear(); fences in-flight builds
        self._count_lookups = count_lookups
        self.hits = 0
        self.misses = 0

    def get_or_build(
        self, key: Tuple[Hashable, ...], build: Callable[[], Any]
    ) -> Any:
        while True:
            with self._lock:
                fn = self._cache.get(key)
                if fn is not None:
                    self.hits += 1
                    if self._count_lookups:
                        obs_trace.record_cache_event(key, hit=True)
                    return fn
                ev = self._building.get(key)
                if ev is None:
                    self._building[key] = threading.Event()
                    self.misses += 1
                    gen = self._generation
                    break
            ev.wait()  # someone else is compiling this key
        if self._count_lookups:
            obs_trace.record_cache_event(key, hit=False)
        try:
            fn = build()
            with self._lock:
                # A clear() that raced this build wins: return the value to
                # the caller but do NOT cache it, so a post-clear store is
                # actually empty (for params, the HBM is released as soon as
                # the caller drops the tree — the point of clear_params).
                if gen == self._generation:
                    self._cache[key] = fn
            return fn
        finally:
            with self._lock:
                self._building.pop(key).set()

    def evict(self, key: Tuple[Hashable, ...]) -> None:
        with self._lock:
            self._cache.pop(key, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"entries": len(self._cache), "hits": self.hits, "misses": self.misses}

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()
            self._generation += 1
