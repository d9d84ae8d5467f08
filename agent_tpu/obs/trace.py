"""End-to-end distributed tracing — causal spans from submit to result-apply.

PR 2 gave the swarm aggregate metrics and a flight recorder; this module
(ISSUE 5 tentpole) assembles the ``trace={job_id, attempt, lease_id}`` tags
those layers already stamp into a *causal timeline*: one span tree per job,
``trace_id = job_id``, covering controller ``submit`` (the root), scheduler
decisions, the lease window, the agent-side ``stage``/``queue``/``execute``/
``post`` phases (each measured ONCE, by :class:`phase`, which feeds the
histogram, the span and a profiler annotation from the same two clock
reads), XLA compile cost (``xla.compile`` spans emitted from the runtime's
``jax.monitoring`` listener, one per executable obtained), spool
redeliveries, and controller ``apply``.

Dependency-free by the same rule as ``obs.metrics``: stdlib only.

Shapes:

- **Span** — ``trace_id``/``span_id``/``parent_span_id`` plus a
  monotonic-start + duration pair for exact intra-process math and a
  wall-clock anchor (``start_wall``) for cross-process ordering. The wire
  format is the plain dict (``Span.to_wire`` / any dict with the same keys).
  A span may additionally carry ``links`` — causal references to spans in
  *other* traces (ISSUE 17: a coalesced serving batch job links back to
  each rider request's trace). Links never replace the single parent; the
  key is emitted only when non-empty, so legacy span bytes are unchanged
  when no links exist.
- **SpanBuffer** — the per-process bounded ring agents record into
  (O(capacity) like the flight recorder). ``drain()`` pops everything
  pending so the agent can piggyback spans onto ``POST /v1/results`` and
  the metrics-only flush lease the same way metric snapshots ship;
  ``requeue`` puts them back when the post fails.
- **TraceContext** (a contextvar) — the ambient ``(trace_id,
  parent_span_id, tracer, registry, op, …)`` the agent sets around a task's
  phases so deep layers (an op's own ``fetch`` phase, the runtime's compile
  listener) can attribute what they measure to the task that triggered it
  without plumbing arguments through jax.
- **phase** — the one phase boundary: a context manager that observes
  ``task_phase_seconds{op,phase}``, buffers the job's span, notes the
  flight recorder and, for its whole extent, holds a
  ``jax.profiler.TraceAnnotation("agent.<phase>")`` so any profiler capture
  shows the agent's phases on the trace's own clock.
- **TraceStore** — the controller-side assembly point: bounded per-trace
  span maps (dedup by ``span_id``, so redelivered piggybacks are
  idempotent), ``assemble()`` returning sorted spans with orphans flagged.
- **Exporters** — Chrome-trace/Perfetto JSON (``to_chrome_trace`` +
  ``validate_chrome_trace``) and JSONL round-trip.

``TRACE_ENABLED=0`` short-circuits every record path to a no-op (ISSUE 5
satellite): ``SpanBuffer.add``/``TraceStore.open`` return immediately, so a
tracing-off drain pays only the env check.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import json
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import (
    Any,
    Dict,
    Iterable,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
)

from agent_tpu.config import TRUTHY_TOKENS

DEFAULT_BUFFER_CAPACITY = 4096
DEFAULT_MAX_TRACES = 512
DEFAULT_MAX_SPANS_PER_TRACE = 1024

# ---- global enable switch (TRACE_ENABLED, default on) ----

_forced_enabled: Optional[bool] = None
_env_enabled: Optional[bool] = None  # memoized env read (hot path)


def set_enabled(value: Optional[bool]) -> None:
    """Override the TRACE_ENABLED env check (tests); ``None`` restores it
    (and re-reads the env on the next :func:`enabled` call)."""
    global _forced_enabled, _env_enabled
    _forced_enabled = value
    _env_enabled = None


def enabled() -> bool:
    if _forced_enabled is not None:
        return _forced_enabled
    # enabled() runs several times per task; memoize the env read (an
    # os.environ hit per call is measurable). set_enabled(None) re-arms it.
    global _env_enabled
    if _env_enabled is None:
        v = os.environ.get("TRACE_ENABLED")
        _env_enabled = (
            True if v is None or v == ""
            else v.strip().lower() in TRUTHY_TOKENS
        )
    return _env_enabled


def new_span_id() -> str:
    # os.urandom is cheaper than uuid4 and this runs several times per
    # task on the drain hot path; 64 random bits is the OTel span-id width.
    return os.urandom(8).hex()


# ---- the span model ----

@dataclass
class Span:
    """One timed operation. ``start_mono``/``duration_ms`` are the exact
    measurement (monotonic clock, immune to wall adjustments);
    ``start_wall`` anchors the span on the shared wall clock so spans from
    different processes sort into one timeline. ``duration_ms=None`` means
    the span is still open (assembly flags the trace incomplete)."""

    trace_id: str
    span_id: str
    name: str
    parent_span_id: Optional[str] = None
    start_wall: float = 0.0
    start_mono: float = 0.0
    duration_ms: Optional[float] = None
    process: str = ""
    attributes: Dict[str, Any] = field(default_factory=dict)
    # Cross-trace causal references (ISSUE 17): each entry is
    # {"trace_id": ..., "span_id": ...?, "attributes": {...}?}. Links do
    # NOT participate in the parent/child tree — assembly ignores them —
    # and the wire key is omitted entirely when the list is empty so a
    # link-free span serializes byte-identically to the pre-links schema.
    links: List[Dict[str, Any]] = field(default_factory=list)

    def to_wire(self) -> Dict[str, Any]:
        wire = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "name": self.name,
            "start_wall": self.start_wall,
            "start_mono": self.start_mono,
            "duration_ms": self.duration_ms,
            "process": self.process,
            "attributes": dict(self.attributes),
        }
        if self.links:
            wire["links"] = [dict(link) for link in self.links]
        return wire


def make_span(
    name: str,
    trace_id: str,
    parent_span_id: Optional[str] = None,
    *,
    start_mono: Optional[float] = None,
    duration_s: Optional[float] = None,
    process: str = "",
    span_id: Optional[str] = None,
    attributes: Optional[Mapping[str, Any]] = None,
    links: Optional[Sequence[Mapping[str, Any]]] = None,
) -> Dict[str, Any]:
    """A closed span wire dict from a measured ``(start_mono, duration)``
    pair, back-deriving the wall anchor from the current clocks so callers
    never run two clocks for one measurement. Builds the wire dict directly
    (no ``Span`` round-trip): this runs several times per task on the drain
    hot path. ``links`` is emitted only when non-empty (legacy bytes)."""
    now_mono = time.monotonic()
    start_mono = now_mono if start_mono is None else float(start_mono)
    span = {
        "trace_id": trace_id,
        "span_id": span_id or new_span_id(),
        "parent_span_id": parent_span_id,
        "name": name,
        "start_wall": time.time() - max(0.0, now_mono - start_mono),
        "start_mono": start_mono,
        "duration_ms": (
            None if duration_s is None else round(float(duration_s) * 1e3, 3)
        ),
        "process": process,
        "attributes": dict(attributes or {}),
    }
    if links:
        span["links"] = [dict(link) for link in links]
    return span


def span_link(
    trace_id: str,
    span_id: Optional[str] = None,
    **attributes: Any,
) -> Dict[str, Any]:
    """One link entry for a span's ``links`` list: a causal reference into
    ANOTHER trace (the serving batch job ↔ rider request association).
    ``span_id``/``attributes`` are optional and omitted when empty."""
    link: Dict[str, Any] = {"trace_id": str(trace_id)}
    if span_id:
        link["span_id"] = str(span_id)
    if attributes:
        link["attributes"] = dict(attributes)
    return link


def _valid_span(span: Any) -> bool:
    # dict first: the typing.Mapping ABC check is the dear one and every
    # span on the wire is a plain dict; the ABC path survives only for odd
    # callers.
    if type(span) is not dict and not isinstance(span, Mapping):
        return False
    return (
        isinstance(span.get("trace_id"), str)
        and span["trace_id"] != ""
        and isinstance(span.get("span_id"), str)
        and span["span_id"] != ""
        and isinstance(span.get("name"), str)
        and span["name"] != ""
    )


# ---- per-process span ring (the agent side) ----

class SpanBuffer:
    """Thread-safe bounded ring of span wire dicts. ``add`` is on hot paths:
    it must never raise, never block beyond the lock, and stay O(1)."""

    def __init__(self, capacity: int = DEFAULT_BUFFER_CAPACITY) -> None:
        self.capacity = max(1, int(capacity))
        self._lock = threading.Lock()
        self._spans: "collections.deque" = collections.deque(
            maxlen=self.capacity
        )
        self._dropped = 0

    def add(self, span: Any) -> None:
        """Buffer one span. Ownership transfers: a plain dict is stored
        as-is (``make_span`` hands over fresh dicts on the hot path);
        callers that keep a reference must not mutate it after ``add``."""
        if not enabled():
            return
        if isinstance(span, Span):
            span = span.to_wire()
        if not _valid_span(span):
            return
        if type(span) is not dict:
            span = dict(span)
        with self._lock:
            if len(self._spans) == self.capacity:
                self._dropped += 1
            self._spans.append(span)

    def drain(self) -> List[Dict[str, Any]]:
        """Pop everything pending (the piggyback ship). Callers that fail to
        deliver must ``requeue`` what they took."""
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
        return out

    def requeue(self, spans: Iterable[Mapping[str, Any]]) -> None:
        """Put undelivered spans back (order within the ring is irrelevant —
        assembly sorts by time). Ring bound still applies."""
        with self._lock:
            for s in spans:
                if len(self._spans) == self.capacity:
                    self._dropped += 1
                self._spans.append(dict(s))

    def spans(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._spans)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


_default_tracer = SpanBuffer()


def get_tracer() -> SpanBuffer:
    return _default_tracer


# ---- ambient trace context (compile-cost attribution) ----

@dataclass(frozen=True)
class TraceContext:
    """What a deep layer needs to attribute a measurement to the current
    task: where to record (``tracer``/``registry``/``recorder``), what to
    parent to, and which op and job it belongs to. ``trace_id`` is empty for
    a task the controller stamped no trace on: its phases are still timed
    and annotated, but emit no span and no exemplar."""

    trace_id: str = ""
    parent_span_id: Optional[str] = None
    tracer: Optional[SpanBuffer] = None
    registry: Any = None
    process: str = ""
    op: str = ""
    recorder: Any = None   # FlightRecorder: one "phase" event per span
    # The task's identity as the flight recorder carries it
    # (job_id / lease_id / attempt).
    job: Mapping[str, Any] = field(default_factory=dict)


_current: "contextvars.ContextVar[Optional[TraceContext]]" = (
    contextvars.ContextVar("agent_tpu_trace_ctx", default=None)
)


def current() -> Optional[TraceContext]:
    return _current.get()


@contextlib.contextmanager
def use_context(ctx: Optional[TraceContext]):
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)


# ---- the phase boundary (histogram + span + profiler annotation) ----

PHASE_HISTOGRAM = "task_phase_seconds"
_NO_CONTEXT = TraceContext()


def annotate(name: str) -> Any:
    """An entered ``jax.profiler.TraceAnnotation(name)``, or None on a
    process that never imported jax (a host-only agent must not pay the
    import for a profiler it cannot have). With no profiler session open an
    annotation is one atomic load; the caller exits what it gets."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


# ---- the device side of the same idea: a model's parts, by name ----

# The closed vocabulary of :func:`part`, the same for every model family so
# that one reduction serves every program: ``embed``; ``norm``; ``project``
# (a mixer's in- and out-projections, the latents' expansion); ``mixer`` (the
# token-mixing computation itself: everything inside a ``kernels/`` entry
# function, or the ``jax.numpy`` path that stands in for it); ``around``
# (rotary, convolution, gate, SiLU, multipliers, group norm's scaling, cache
# and state writes, residual adds, relayouts written in the model); ``ffn``
# (dense and shared-expert feed-forwards); ``experts`` (router, sort, gathers,
# grouped matmul, combine); ``head``.
PARTS = ("embed", "norm", "project", "mixer", "around", "ffn", "experts",
         "head")


def part(name: str) -> Any:
    """``jax.named_scope("part:" + name)`` and nothing else: what
    :func:`annotate` is to a host span, for the DEVICE's time. Entered where
    the work is written (``models/``, ``kernels/``), the name rides XLA's
    ``op_name`` metadata into every executable, the runtime reads it back out
    of the compiled text (``TpuRuntime.program_parts``) and a reduction lays
    it over a device trace. Nested, the innermost part is the instruction's.
    A scope is metadata: the optimized program is the same with and without.
    A name outside :data:`PARTS` raises while the program is traced."""
    if name not in PARTS:
        raise ValueError(f"unknown part {name!r}: one of {PARTS}")
    import jax

    return jax.named_scope("part:" + name)


class phase:
    """One phase boundary, measured once: ``with phase("stage", ctx) as ph``
    reads the clock on entry and exit and feeds every sink from that pair.

    - ``histogram``: ``task_phase_seconds{op, phase}`` in ``ctx.registry``
      (with the trace id as exemplar);
    - ``span``: the job's span ``name`` in ``ctx.tracer``, parented to
      ``ctx.parent_span_id``, plus one ``phase`` event in ``ctx.recorder``;
      while the phase is open the AMBIENT context's parent is this span, so
      what runs inside (an op's ``fetch``, an ``xla.compile``) nests under
      it;
    - ``annotation``: ``agent.<name>`` (or the string given) on the calling
      thread's line of any profiler capture.

    ``ctx`` defaults to the ambient context — an op calls ``phase("fetch")``
    and lands in the task the agent set around it; with no context at all
    only the clock and the annotation remain. A phase that itself finds out
    which task it serves (``stage`` resolves the task) assigns ``ph.ctx``
    inside the block. ``ph.t0``/``ph.t1``/``ph.seconds`` are the
    measurement; ``ph.attributes`` may be filled before exit; an exception
    passing through marks ``status="failed"``. Never raises from a sink."""

    __slots__ = ("name", "ctx", "histogram", "span", "annotation",
                 "attributes", "span_id", "t0", "t1", "_ann", "_token")

    def __init__(self, name: str, ctx: Optional[TraceContext] = None, *,
                 histogram: bool = True, span: bool = True,
                 annotation: str = "", **attributes: Any) -> None:
        self.name = name
        self.ctx = ctx
        self.histogram = histogram
        self.span = span
        self.annotation = annotation or f"agent.{name}"
        self.attributes = attributes
        self.span_id: Optional[str] = None
        self.t0 = self.t1 = 0.0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "phase":
        ctx = self.ctx
        if ctx is None:
            ctx = self.ctx = _current.get() or _NO_CONTEXT
        if self.span and enabled():
            # What runs inside sees this span as its parent, and no flight
            # recorder: the ring notes a task's top-level phases only.
            self.span_id = new_span_id()
            ctx = dataclasses.replace(
                ctx, parent_span_id=self.span_id, recorder=None)
        self._token = _current.set(ctx)
        self._ann = annotate(self.annotation)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _current.reset(self._token)
        if exc_type is not None:
            self.attributes["status"] = "failed"
        record_phase(
            self.name, self.ctx, self.t0, self.t1, histogram=self.histogram,
            span=self.span, span_id=self.span_id, **self.attributes)
        return False


def record_phase(
    name: str, ctx: TraceContext, t0: float, t1: float, *,
    histogram: bool = True, span: bool = True,
    span_id: Optional[str] = None, **attributes: Any,
) -> None:
    """The sinks of :class:`phase` for an extent that is no ``with`` block
    (a serving job's execute runs across many passes of the device loop):
    ``task_phase_seconds{op, phase}``, the job's span, the flight recorder.
    ``t0``/``t1`` are ``perf_counter`` reads. The recorder's event carries
    the job's keys and the attributes under ``phase`` and ``duration_ms`` of
    this extent (an attribute of either name loses: the poster's task
    duration travels as ``task_duration_ms``). The sinks are this package's
    own and raise on nothing well-formed, so nothing here is swallowed."""
    seconds = t1 - t0
    if histogram and ctx.registry is not None:
        ctx.registry.histogram(
            PHASE_HISTOGRAM, "", ("op", "phase")
        ).observe(
            seconds,
            exemplar={"trace_id": ctx.trace_id} if ctx.trace_id else None,
            op=ctx.op or "?", phase=name,
        )
    if not span:
        return
    attributes = {k: v for k, v in attributes.items() if v is not None}
    if ctx.op:
        attributes.setdefault("op", ctx.op)
    if ctx.trace_id and ctx.tracer is not None and enabled():
        ctx.tracer.add(make_span(
            name, ctx.trace_id, ctx.parent_span_id,
            start_mono=t0, duration_s=seconds, span_id=span_id,
            process=ctx.process, attributes=attributes,
        ))
    if ctx.recorder is not None:
        ctx.recorder.record("phase", **{
            **ctx.job, **attributes,
            "phase": name, "duration_ms": round(seconds * 1e3, 3),
        })


# ---- XLA executables, counted where they are obtained ----

def _ambient_registry(ctx: Optional[TraceContext]) -> Any:
    registry = getattr(ctx, "registry", None)
    if registry is None:
        from agent_tpu.obs.metrics import get_registry

        registry = get_registry()
    return registry


def _count(name: str, help: str, amount: float = 1.0,
           **labels: str) -> None:
    """Tick a counter in the ambient task's registry, else the process's.
    Must never raise: a broken metrics path must not fail what it counts."""
    try:
        _ambient_registry(current()).counter(
            name, help, tuple(labels)
        ).inc(amount, **labels)
    except Exception:  # noqa: BLE001
        pass


def record_compile(seconds: float, program: str = "") -> None:
    """One executable obtained from XLA — compiled, or loaded from the
    persistent cache; either stalls the caller for ``seconds``. Called by
    the runtime's ``jax.monitoring`` listener (``runtime/executor.py``) ON
    the compiling thread, so the ambient context is the task whose call
    needed the program: ticks ``runtime_xla_executables_total`` and
    ``runtime_compile_seconds_total{op}`` (``op="?"`` outside a task) in that
    task's registry, else the process registry, and emits the
    ``xla.compile`` span at the event's own length. Must never raise — a
    broken trace path must not fail a compile that already succeeded."""
    try:
        ctx = current()
        seconds = max(0.0, float(seconds))
        op = (ctx.op if ctx else "") or "?"
        _count("runtime_xla_executables_total",
               "Executables obtained from XLA: compiled, or loaded from the "
               "persistent compile cache (either stalls the caller)")
        _count("runtime_compile_seconds_total",
               "Seconds callers waited for XLA to hand over an executable "
               "(backend compile or persistent-cache load), by the op whose "
               "task was running; contains the small programs a params build "
               "obtains, so it overlaps runtime_params_seconds_total",
               seconds, op=op)
        if not enabled():
            return
        tracer = (ctx.tracer if ctx and ctx.tracer is not None
                  else get_tracer())
        tracer.add(make_span(
            "xla.compile",
            trace_id=ctx.trace_id if ctx else "",
            parent_span_id=ctx.parent_span_id if ctx else None,
            start_mono=time.monotonic() - seconds,
            duration_s=seconds,
            process=ctx.process if ctx else "",
            attributes={"op": op, "program": str(program)},
        ))
    except Exception:  # noqa: BLE001 — tracing must never break a compile
        pass


def record_xla_cache_hit() -> None:
    """An executable came out of the persistent compile cache; the
    executable itself is counted by :func:`record_compile` when the load
    returns."""
    _count("runtime_xla_cache_hits_total",
           "Executables loaded from the persistent compile cache "
           "(a subset of runtime_xla_executables_total)")


def record_trace_lower(seconds: float) -> None:
    """What a program's FIRST call cost its caller beside XLA's own seconds:
    Python tracing, lowering to MLIR, the persistent cache's key, dispatch
    (``runtime/executor.py: Program``: the call's wall time less the compile
    seconds the listener recorded on that thread during it). Ticks
    ``runtime_trace_lower_seconds_total{op}`` in the ambient task's
    registry, else the process's."""
    ctx = current()
    _count("runtime_trace_lower_seconds_total",
           "Seconds the first call of a program of the runtime's keyed cache "
           "took beside what XLA's listener counted for it "
           "(runtime_compile_seconds_total): tracing, lowering, the cache "
           "key, dispatch; by the op whose task was running",
           max(0.0, float(seconds)), op=(ctx.op if ctx else "") or "?")


def record_params_build(seconds: float) -> None:
    """One model's weights built and placed on the device (a params-store
    miss in ``TpuRuntime.get_params``)."""
    _count("runtime_params_seconds_total",
           "Seconds spent building one model's weights and placing them on "
           "the device (params-store misses); contains the executables the "
           "build obtains, so it overlaps runtime_compile_seconds_total",
           max(0.0, float(seconds)))


def record_attention_block(path: str) -> None:
    """One attention block TRACED into an XLA program on ``path``
    (``whole_row``, ``flash``, ``dense``, ...): the kernels decide from static
    shapes while the surrounding jit traces, so this ticks once a block of a
    program (twelve for a 12-layer encoder) and never again when the program
    runs."""
    _count("attention_blocks_traced_total",
           "Attention blocks traced into XLA programs, by the path the "
           "kernels' shape-and-mask predicates selected (ticks while a "
           "program is traced, not when it runs)",
           path=path)


def record_attention_qkv(form: str) -> None:
    """One attention block TRACED with its Q, K, V projections in ``form``:
    ``fused`` (one matmul on the block's ``wqkv`` leaf, its result handed to
    the whole-row kernel as column blocks: ``models/layers.py:
    _attention_lane_dense``) or ``separate`` (three, every other call).
    Beside :func:`record_attention_block`: ticks while a program is traced,
    once an attention call, never when it runs."""
    _count("attention_qkv_traced_total",
           "Attention blocks traced into XLA programs, by the form of their "
           "Q, K, V projections: one matmul on a fused weight leaf (fused) "
           "or three (separate); ticks while a program is traced, not when "
           "it runs",
           form=form)


def record_caches_in_place(mixer: str, leaves: int) -> None:
    """A layer scan TRACED with ``leaves`` state leaves a layer as its CARRY
    (12: six layers' keys and values): the stacks of the layers' caches that
    the mixer writes and the attention kernel reads where they lie
    (``models/decoder_lm.py: MIXER_CACHES``). Beside
    :func:`record_attention_block`: ticks while a program is traced, never
    when it runs; a model whose caches are stepped over, a layer's slice
    copied out and back, ticks nothing."""
    if leaves:
        _count("state_caches_in_place_traced_total",
               "State leaves a layer that a traced layer scan carries whole "
               "and a mixer writes and reads in place (a growing key or "
               "value cache that is never sliced out of its stack), by "
               "mixer; ticks while a program is traced, not when it runs",
               float(leaves), mixer=mixer)


def record_classify_shard(real_tokens: int, token_slots: int,
                          packed: bool) -> None:
    """One classify shard DISPATCHED: the tokens its rows hold, the token
    slots of the programs it goes out as (program rows x length: what the
    device computes on), and whether staging packed its short rows several
    to a program row (``ops/_model_common.py: pack_padded_chunk``) or left
    them padded. From the staged lengths and shapes, packed or not."""
    for kind, amount in (("real", real_tokens), ("dispatched", token_slots)):
        _count("classify_token_slots_total",
               "Token slots of the classify programs dispatched "
               "(kind=dispatched: program rows x length, padding included) "
               "and the real tokens in them (kind=real)",
               float(amount), kind=kind)
    _count("classify_shards_total",
           "Classify shards dispatched, by whether staging packed their rows "
           "several to a program row (packed) or padded each to the length "
           "bucket (padded)", layout="packed" if packed else "padded")


def record_retention_tokens(path: str, tokens: int) -> None:
    """Real tokens DISPATCHED whose mixer did (``state``) or did not
    (``quadratic``) read a carried state: counted by the op at dispatch,
    from the chunk each token falls in."""
    if tokens > 0:
        _count("retention_tokens_total",
               "Tokens dispatched to a retention mixer, by whether the "
               "token's chunk read a carried state (state) or was the "
               "quadratic form alone (quadratic)",
               float(tokens), path=path)


def record_sparse_attention_keys(kind: str, keys: int) -> None:
    """Keys the real tokens of a DISPATCHED shard attend under a learned
    top-k selection (``selected``) and the causal keys they could
    (``causal``), a layer: counted by the op from the documents' lengths."""
    if keys > 0:
        _count("sparse_attention_keys_total",
               "Keys attended by the tokens dispatched to a sparse-attention "
               "mixer, a layer: under the selection (selected) and all "
               "causal keys (causal)",
               float(keys), kind=kind)


def record_ssm_tokens(path: str, tokens: int) -> None:
    """Real tokens DISPATCHED to a state-space scan whose chunk did
    (``state``) or did not (``first_chunk``: a document's first chunk, whose
    state is the empty one) read a carried state: counted by the op at
    dispatch, from the chunk each token falls in."""
    if tokens > 0:
        _count("ssm_tokens_total",
               "Tokens dispatched to a state-space scan, by whether the "
               "token's chunk read a carried state (state) or was a "
               "document's first (first_chunk)",
               float(tokens), path=path)


def record_kda_tokens(path: str, tokens: int) -> None:
    """Real tokens DISPATCHED to the delta-rule linear layers whose chunk did
    (``state``) or did not (``first_chunk``: a document's first chunk, whose
    state is the empty one) read a carried state: counted by the op at
    dispatch, from the chunk each token falls in."""
    if tokens > 0:
        _count("kda_tokens_total",
               "Tokens dispatched to a delta-rule linear-attention layer, by "
               "whether the token's chunk read a carried state (state) or "
               "was a document's first (first_chunk)",
               float(tokens), path=path)


def record_conv_tail_tokens(path: str, tokens: int) -> None:
    """Real tokens DISPATCHED to the double-gated short convolutions whose
    segment program did (``carried``) or did not (``first_segment``: a
    document's first, whose tail is the zeros before the document) read a
    tail handed on by the segment before: counted by the op at dispatch,
    from the segment each token falls in."""
    if tokens > 0:
        _count("conv_tail_tokens_total",
               "Tokens dispatched to a gated short-convolution layer, by "
               "whether the token's segment read a carried tail (carried) or "
               "was a document's first (first_segment)",
               float(tokens), path=path)


def record_kda_chunks(chunks: int) -> None:
    """Chunks the delta-rule kernel walks for a DISPATCHED shard, a head a
    linear layer (its segments' padding included): counted by the op from
    the segments its documents ran as."""
    if chunks > 0:
        _count("kda_chunks_total",
               "Chunks the delta-rule kernel walks for the segments "
               "dispatched, a head a linear layer", float(chunks))


def record_causal_attention_pairs(kind: str, pairs: int) -> None:
    """(query, key) pairs of a DISPATCHED shard under plain causal
    attention, a query head a layer: those its real tokens need (``causal``:
    ``t + 1`` for token ``t``) and those in the key tiles the kernel's grid
    visits (``computed``: whole tiles up to the diagonal's, padding
    included). Counted by the op from the documents' lengths and the
    kernel's tile sizes."""
    if pairs > 0:
        _count("causal_attention_pairs_total",
               "(query, key) pairs of the tokens dispatched to a causal "
               "attention mixer, a query head a layer: needed by the real "
               "tokens (causal) and in the key tiles the kernel's grid "
               "visits (computed)",
               float(pairs), kind=kind)


def record_window_attention_pairs(kind: str, pairs: int) -> None:
    """(query, key) pairs of a DISPATCHED shard under windowed causal
    attention, a query head a window layer: those its real tokens need
    (``window``: ``min(t + 1, window)`` for token ``t``) and those in the key
    tiles the window kernel's grid visits (``computed``: whole tiles from
    the window's lower edge to the diagonal, padding included). Counted by
    the op from the documents' lengths, the window and the kernel's tile
    sizes."""
    if pairs > 0:
        _count("window_attention_pairs_total",
               "(query, key) pairs of the tokens dispatched to a windowed "
               "attention layer, a query head a layer: inside the real "
               "tokens' windows (window) and in the key tiles the kernel's "
               "grid visits (computed)",
               float(pairs), kind=kind)


def record_latent_keys(kind: str, keys: int) -> None:
    """Latents of a DISPATCHED shard under a mixer that caches latents and
    expands them to keys and values inside its programs, a layer: the real
    tokens whose latents the cache holds (``cached``) and the latents its
    segment programs expand (``expanded``: a cached latent once for its own
    and every later segment). Counted by the op from the documents' lengths
    and the segments they ran as."""
    if keys > 0:
        _count("latent_keys_expanded_total",
               "Cached latents of the tokens dispatched to a latent-"
               "attention mixer, a layer: held by the cache (cached) and "
               "expanded to keys and values by its segment programs "
               "(expanded)",
               float(keys), kind=kind)


def record_moe_routing(pairs: float, tokens: int) -> None:
    """What a FETCHED shard's expert layers routed: (token, expert) pairs
    that went to the experts held here (counted on the device, fetched with
    the shard's answer) and the token slots the expert layers saw (dispatched
    tokens, padding included, x expert layers)."""
    if tokens > 0:
        _count("moe_expert_pairs_total",
               "(token, expert) pairs routed to the experts this process "
               "holds, over all expert layers", float(pairs))
        _count("moe_tokens_total",
               "Token slots dispatched to expert layers (tokens x expert "
               "layers, padding included)", float(tokens))


def record_moe_tiles(tiles: float, rows_computed: float) -> None:
    """Row tiles the grouped expert matmul visited for a FETCHED shard (an
    expert's rows padded to whole tiles; counted on the device beside the
    pairs, fetched with the shard's answer): ``moe_expert_pairs_total`` over
    this times the rows a tile is how full the tiles were. And the rows its
    matmuls took (a tile's real rows rounded up to whole sub-blocks, counted
    beside the tiles): ``moe_expert_pairs_total`` over this is the share of
    what the MXU computed that was a real row."""
    if tiles > 0:
        _count("moe_tiles_total",
               "Row tiles the grouped expert matmul visited (every held "
               "expert's rows padded to whole tiles), over all expert "
               "layers", float(tiles))
        _count("moe_rows_computed_total",
               "Rows the grouped expert matmul's products took (every "
               "visited tile's real rows rounded up to whole sub-blocks), "
               "over all expert layers", float(rows_computed))


def record_lm_segments(op: str, segments: int) -> None:
    """Fixed-shape segment programs an op dispatched (a document longer
    than one program runs as several, the state handed on on the device)."""
    if segments > 0:
        _count("lm_segments_total",
               "Fixed-shape segment programs dispatched by the "
               "language-model ops (a long document is several)",
               float(segments), op=op)


def record_cache_event(key: Sequence[Any], hit: bool) -> None:
    """One lookup of the runtime's keyed cache of jit wrappers."""
    _count("runtime_compile_cache_total",
           "Lookups of the runtime's keyed cache of jit WRAPPERS by op and "
           "outcome (a miss builds a wrapper, not an executable: see "
           "runtime_xla_executables_total for those)",
           op=str(key[0]) if key else "?",
           outcome="hit" if hit else "miss")


# ---- controller-side assembly ----

class TraceStore:
    """Bounded per-trace span store — the controller's assembly point.

    Traces evict oldest-first past ``max_traces`` (same O(capacity) deal as
    the flight recorder: a 10M-shard drain keeps the newest window, not the
    whole history). Spans dedup by ``span_id``, so a piggyback redelivered
    after a lost response re-ingests idempotently.
    """

    def __init__(
        self,
        max_traces: int = DEFAULT_MAX_TRACES,
        max_spans_per_trace: int = DEFAULT_MAX_SPANS_PER_TRACE,
    ) -> None:
        self.max_traces = max(1, int(max_traces))
        self.max_spans_per_trace = max(1, int(max_spans_per_trace))
        self._lock = threading.Lock()
        # trace_id -> {span_id: span dict}; OrderedDict for FIFO eviction.
        self._traces: "collections.OrderedDict[str, Dict[str, Dict[str, Any]]]" = (
            collections.OrderedDict()
        )
        self.dropped_traces = 0
        self.dropped_spans = 0

    def add(self, span: Any) -> bool:
        """Ingest one span wire dict; False = rejected (malformed/bounds).
        Ownership transfers like :meth:`SpanBuffer.add`: a plain dict is
        stored without copying (``finish`` mutates it in place)."""
        if not enabled():
            return False
        if isinstance(span, Span):
            span = span.to_wire()
        if not _valid_span(span):
            return False
        if type(span) is not dict:
            span = dict(span)
        with self._lock:
            spans = self._traces.get(span["trace_id"])
            if spans is None:
                while len(self._traces) >= self.max_traces:
                    self._traces.popitem(last=False)
                    self.dropped_traces += 1
                spans = {}
                self._traces[span["trace_id"]] = spans
            if (
                span["span_id"] not in spans
                and len(spans) >= self.max_spans_per_trace
            ):
                self.dropped_spans += 1
                return False
            spans[span["span_id"]] = span
        return True

    def ingest(self, spans: Any) -> int:
        """Bulk ``add`` for a piggybacked batch; returns spans accepted."""
        if not isinstance(spans, (list, tuple)):
            return 0
        return sum(1 for s in spans if self.add(s))

    def open(
        self,
        trace_id: str,
        name: str,
        parent_span_id: Optional[str] = None,
        *,
        start_clock: float = 0.0,
        process: str = "controller",
        attributes: Optional[Mapping[str, Any]] = None,
        span_id: Optional[str] = None,
        links: Optional[Sequence[Mapping[str, Any]]] = None,
    ) -> Optional[str]:
        """Record an OPEN span (duration unknown yet) and return its id, or
        None when tracing is disabled. ``start_clock`` is whatever monotonic
        clock the caller will later pass to :meth:`finish` — the controller
        uses its own (injectable) clock."""
        if not enabled():
            return None
        sid = span_id or new_span_id()
        span: Dict[str, Any] = {
            "trace_id": trace_id,
            "span_id": sid,
            "parent_span_id": parent_span_id,
            "name": name,
            "start_wall": time.time(),
            "start_mono": float(start_clock),
            "duration_ms": None,
            "process": process,
            "attributes": dict(attributes or {}),
        }
        if links:
            span["links"] = [dict(link) for link in links]
        ok = self.add(span)
        return sid if ok else None

    def add_links(
        self,
        trace_id: str,
        span_id: Optional[str],
        links: Sequence[Mapping[str, Any]],
    ) -> None:
        """Append cross-trace links to a stored span (the serving batch
        job's root learns its riders only after the job is submitted, so
        links land post-``open``). No-op when the span is absent."""
        if span_id is None or not links:
            return
        with self._lock:
            span = self._traces.get(trace_id, {}).get(span_id)
            if span is None:
                return
            span.setdefault("links", []).extend(dict(link) for link in links)

    def links(self, trace_id: str, span_id: str) -> List[Dict[str, Any]]:
        """The stored links of one span (empty when absent/link-free)."""
        with self._lock:
            span = self._traces.get(trace_id, {}).get(span_id)
            return [dict(link) for link in span.get("links", [])] \
                if span else []

    def finish(
        self,
        trace_id: str,
        span_id: Optional[str],
        end_clock: float,
        attributes: Optional[Mapping[str, Any]] = None,
    ) -> None:
        """Close an open span: duration = ``end_clock`` − its
        ``start_mono`` (same clock as :meth:`open`'s ``start_clock``)."""
        if span_id is None:
            return
        with self._lock:
            span = self._traces.get(trace_id, {}).get(span_id)
            if span is None:
                return
            span["duration_ms"] = round(
                max(0.0, float(end_clock) - float(span.get("start_mono", 0.0)))
                * 1e3, 3,
            )
            if attributes:
                span.setdefault("attributes", {}).update(attributes)

    def spans(self, trace_id: str) -> Optional[List[Dict[str, Any]]]:
        with self._lock:
            spans = self._traces.get(trace_id)
            if spans is None:
                return None
            return [dict(s) for s in spans.values()]

    def trace_ids(self) -> List[str]:
        with self._lock:
            return list(self._traces)

    def assemble(self, trace_id: str) -> Optional[Dict[str, Any]]:
        """The ``GET /v1/trace/{job_id}`` body: spans sorted by wall start,
        orphans (dangling ``parent_span_id``) flagged, completeness = one
        root + no orphans + every span closed."""
        spans = self.spans(trace_id)
        if spans is None:
            return None
        return assemble(trace_id, spans)

    def summaries(self, limit: int = 20) -> List[Dict[str, Any]]:
        """Newest-first trace listing for ``GET /v1/traces``."""
        with self._lock:
            items = [
                (tid, [dict(s) for s in spans.values()])
                for tid, spans in self._traces.items()
            ]
        out: List[Dict[str, Any]] = []
        for tid, spans in reversed(items):
            roots = [s for s in spans if s.get("parent_span_id") is None]
            root = min(
                roots, key=lambda s: s.get("start_wall", 0.0)
            ) if roots else None
            out.append({
                "trace_id": tid,
                "n_spans": len(spans),
                "root_name": root.get("name") if root else None,
                "root_duration_ms": root.get("duration_ms") if root else None,
                "complete": _complete(spans),
            })
            if len(out) >= max(1, int(limit)):
                break
        return out


def _complete(spans: Sequence[Mapping[str, Any]]) -> bool:
    ids = {s["span_id"] for s in spans}
    roots = [s for s in spans if s.get("parent_span_id") is None]
    orphans = [
        s for s in spans
        if s.get("parent_span_id") is not None
        and s["parent_span_id"] not in ids
    ]
    open_spans = [s for s in spans if s.get("duration_ms") is None]
    return len(roots) == 1 and not orphans and not open_spans


def assemble(
    trace_id: str, spans: Sequence[Mapping[str, Any]]
) -> Dict[str, Any]:
    ids = {s["span_id"] for s in spans}
    ordered = sorted(
        (dict(s) for s in spans),
        key=lambda s: (s.get("start_wall", 0.0), s.get("start_mono", 0.0)),
    )
    roots = [s["span_id"] for s in ordered
             if s.get("parent_span_id") is None]
    orphans = [
        s["span_id"] for s in ordered
        if s.get("parent_span_id") is not None
        and s["parent_span_id"] not in ids
    ]
    open_ids = [s["span_id"] for s in ordered if s.get("duration_ms") is None]
    return {
        "trace_id": trace_id,
        "spans": ordered,
        "root_span_id": roots[0] if len(roots) == 1 else None,
        "roots": roots,
        "orphans": orphans,
        "open_spans": open_ids,
        "complete": len(roots) == 1 and not orphans and not open_ids,
    }


# ---- exporters ----

def to_jsonl(spans: Iterable[Mapping[str, Any]]) -> str:
    return "".join(
        json.dumps(dict(s), sort_keys=True, default=str) + "\n"
        for s in spans
    )


def from_jsonl(text: str) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        span = json.loads(line)
        if _valid_span(span):
            out.append(span)
    return out


def to_chrome_trace(spans: Iterable[Mapping[str, Any]]) -> Dict[str, Any]:
    """Chrome-trace / Perfetto JSON object format: complete ("X") events in
    microseconds on the wall clock, one pid per producing process plus the
    ``process_name`` metadata events Perfetto uses for track labels. Open
    spans export with ``dur=0`` and ``args.incomplete`` so a live trace
    still loads."""
    pids: Dict[str, int] = {}
    events: List[Dict[str, Any]] = []
    for s in spans:
        proc = str(s.get("process") or "unknown")
        pid = pids.get(proc)
        if pid is None:
            pid = len(pids) + 1
            pids[proc] = pid
            events.append({
                "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {"name": proc},
            })
        dur_ms = s.get("duration_ms")
        ev: Dict[str, Any] = {
            "ph": "X",
            "name": str(s.get("name", "?")),
            "cat": "agent-tpu",
            "ts": float(s.get("start_wall", 0.0)) * 1e6,
            "dur": max(0.0, float(dur_ms or 0.0)) * 1e3,
            "pid": pid,
            "tid": 0,
            "args": {
                "trace_id": s.get("trace_id"),
                "span_id": s.get("span_id"),
                "parent_span_id": s.get("parent_span_id"),
                **(s.get("attributes") or {}),
            },
        }
        if dur_ms is None:
            ev["args"]["incomplete"] = True
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def validate_chrome_trace(obj: Any) -> List[str]:
    """Structural check of a Chrome-trace export (the schema Perfetto's
    legacy JSON importer requires); returns problems, empty = loads."""
    problems: List[str] = []
    if not isinstance(obj, Mapping):
        return ["trace is not a JSON object"]
    events = obj.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents is not a list"]
    for i, ev in enumerate(events):
        if not isinstance(ev, Mapping):
            problems.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "M"):
            problems.append(f"event {i}: unsupported ph {ph!r}")
            continue
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            problems.append(f"event {i}: missing name")
        if not isinstance(ev.get("pid"), int):
            problems.append(f"event {i}: missing int pid")
        if ph == "X":
            for key in ("ts", "dur"):
                v = ev.get(key)
                if not isinstance(v, (int, float)) or isinstance(v, bool):
                    problems.append(f"event {i}: missing numeric {key}")
                elif key == "dur" and v < 0:
                    problems.append(f"event {i}: negative dur")
    return problems


def phase_breakdown(assembled: Mapping[str, Any]) -> str:
    """One-line per-phase attribution of an assembled trace — the bench/
    drain report line ("where did this job's seconds go")."""
    spans = assembled.get("spans") or []
    totals: Dict[str, float] = {}
    order: List[str] = []
    for s in spans:
        dur = s.get("duration_ms")
        if dur is None:
            continue
        name = str(s.get("name", "?"))
        if name not in totals:
            order.append(name)
        totals[name] = totals.get(name, 0.0) + float(dur)
    root_id = assembled.get("root_span_id")
    root = next(
        (s for s in spans if s.get("span_id") == root_id), None
    )
    total = (root or {}).get("duration_ms")
    parts = " | ".join(
        f"{name} {totals[name]:.1f}ms"
        for name in order if name != (root or {}).get("name")
    )
    head = f"trace {assembled.get('trace_id')}"
    if total is not None:
        head += f": total {float(total):.1f}ms"
    return f"{head} = {parts}" if parts else head
