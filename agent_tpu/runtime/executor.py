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
The wrapper sits behind ONE thin first-call layer (:class:`Program`): it
notes the abstract arguments of the first call, so the compiled text can be
asked for later (:func:`parts_of_text`, ``TpuRuntime.program_parts``), and
what that call cost beside XLA's own seconds.
"""

from __future__ import annotations

import re
import threading
import time
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

from agent_tpu.obs import trace as obs_trace

# JAX's own monitoring events (jax/_src/dispatch.py, jax/_src/compilation_cache.py):
# the duration event wraps ``compile_or_get_cached``, so it fires once per
# executable obtained, compiled or loaded; the plain event marks a load.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_listener_lock = threading.Lock()
_listener_installed = False
# Seconds the listener has counted ON THIS THREAD: what a first call reads
# before and after itself to know XLA's share of its wall time.
_on_thread = threading.local()


def _on_duration(event: str, seconds: float, **kwargs: Any) -> None:
    if event == BACKEND_COMPILE_EVENT:
        _on_thread.compile_s = compile_seconds_on_thread() + seconds
        obs_trace.record_compile(seconds, program=kwargs.get("fun_name", ""))


def compile_seconds_on_thread() -> float:
    return getattr(_on_thread, "compile_s", 0.0)


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


def _abstract(x: Any) -> Any:
    """Shape, dtype, weak type and (where the caller placed it) sharding of
    one argument leaf: what ``jit(f).lower`` needs to find the executable
    the call itself obtained."""
    import jax

    aval = jax.typeof(x)
    sharding = getattr(x, "sharding", None)
    if not getattr(x, "committed", True):
        sharding = None
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=sharding,
                                weak_type=aval.weak_type)


class Program:
    """The first-call layer around one cached ``jax.jit`` wrapper. The FIRST
    call notes the abstract arguments (a few hundred bytes a key) and ticks
    ``runtime_trace_lower_seconds_total`` with the call's wall time less the
    compile seconds the listener recorded on this thread during it: tracing,
    lowering, the persistent cache's key and the dispatch, nested jits
    counted once because it is the caller's clock (JAX's own
    ``jaxpr_trace_duration`` fires for every jit inside a jit). Every later
    call goes straight to the wrapper, and ``TpuRuntime.compiled`` hands the
    wrapper itself out once the first call is over."""

    __slots__ = ("wrapper", "noted")

    def __init__(self, wrapper: Callable) -> None:
        self.wrapper = wrapper
        self.noted: Optional[Tuple[tuple, dict]] = None   # (args, kwargs)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if self.noted is not None:
            return self.wrapper(*args, **kwargs)
        import jax

        # Before the call: a donated argument is gone after it.
        noted = jax.tree_util.tree_map(_abstract, (args, kwargs))
        t0, compiled0 = time.perf_counter(), compile_seconds_on_thread()
        out = self.wrapper(*args, **kwargs)
        obs_trace.record_trace_lower(
            time.perf_counter() - t0
            - (compile_seconds_on_thread() - compiled0))
        self.noted = noted
        return out

    def compiled_text(self) -> Optional[str]:
        """The optimized HLO of the executable the first call obtained, or
        ``None`` before it. After the call ``lower(...).compile()`` is a
        lookup in JAX's own caches, not a compile."""
        if self.noted is None:
            return None
        args, kwargs = self.noted
        return self.wrapper.lower(*args, **kwargs).compile().as_text()


# ---- a program's parts, out of its compiled text ---------------------------

_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^  (?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"[\])}] ([a-z][\w\-]*)\(")
_PART = re.compile(r"part:([a-z]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
# Instructions that are never a device event of their own.
_NO_WORK = frozenset({"parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast"})
_MATMULS = frozenset({"dot", "convolution"})


def parts_of_text(text: str) -> Tuple[str, Dict[str, Any]]:
    """``(module name, {"instructions": {name: part or None}, "mixed":
    {fusion: [parts inside]}, "named_share": share})`` of one executable's
    optimized HLO text. An instruction's part is the innermost ``part:<name>``
    scope of its ``op_name`` (``obs.trace.part``); instruction names are
    unique in a module, so one flat dictionary holds every computation's. A
    fusion takes the part its own ``op_name`` gives it (XLA writes the
    root's) UNLESS its fused computation holds matmuls (``dot``,
    ``convolution``) that all lie in ONE other part: then the matmuls', since
    that is where the fusion's time goes (an out-projection fused with the
    residual add and the next norm's mean is the projection); a fusion with
    no ``op_name`` of its own takes the part most of its inside has. ``mixed`` lists
    the fusions whose fused computation spans two parts or more, whichever
    part got them. ``named_share``: the share with a part of the
    instructions that can be a device event (those of the entry, loop and
    branch computations; not a fusion's or a reduction's inside, not
    parameters, constants, tuples and bitcasts)."""
    found = _MODULE.search(text)
    module = found.group(1) if found else "?"
    instructions: Dict[str, Optional[str]] = {}
    opcode: Dict[str, str] = {}
    where: Dict[str, str] = {}                 # instruction -> computation
    members: Dict[str, List[str]] = {}         # computation -> instructions
    fused: Dict[str, str] = {}                 # fusion -> fused computation
    inner = set()      # computations whose instructions are never events
    computation = ""
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        found = _INSTRUCTION.match(line)
        if not found or not computation:
            continue
        name = found.group(1)
        rest = line[found.end():]
        op = _OPCODE.search(rest)
        opcode[name] = op.group(1) if op else "?"
        op_name = _OP_NAME.search(rest)
        scopes = _PART.findall(op_name.group(1)) if op_name else []
        instructions[name] = scopes[-1] if scopes else None
        where[name] = computation
        members.setdefault(computation, []).append(name)
        if opcode[name] == "fusion":
            calls = _CALLS.search(rest)
            if calls:
                fused[name] = calls.group(1)
                inner.add(calls.group(1))
        elif opcode[name] != "call":
            applied = _TO_APPLY.search(rest)
            if applied:
                inner.add(applied.group(1))

    def within(body: str) -> List[str]:
        """A fused computation's instructions, those of the fusions nested
        in it (the chip's compiler nests them) included."""
        out = list(members.get(body, []))
        for i in members.get(body, []):
            if i in fused:
                out.extend(within(fused[i]))
        return out

    mixed: Dict[str, List[str]] = {}
    for fusion, body in fused.items():
        inside = within(body)
        theirs = [instructions[i] for i in inside]
        parts = sorted({p for p in theirs if p is not None})
        if len(parts) > 1:
            mixed[fusion] = parts
        matmuls = {p for i, p in zip(inside, theirs)
                   if opcode[i] in _MATMULS}
        if len(matmuls) == 1 and None not in matmuls:
            instructions[fusion] = matmuls.pop()
        elif instructions[fusion] is None and parts:
            # The compiler made it (a multi-output fusion of clones keeps
            # no metadata of its own): what most of its inside is.
            instructions[fusion] = max(parts, key=theirs.count)
    events = [i for i in instructions
              if where[i] not in inner and opcode[i] not in _NO_WORK]
    named = sum(1 for i in events if instructions[i] is not None)
    return module, {
        "instructions": instructions, "mixed": mixed,
        "named_share": named / len(events) if events else 0.0,
    }


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

    def values(self) -> List[Any]:
        with self._lock:
            return list(self._cache.values())

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
