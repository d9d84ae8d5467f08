"""Pipelined drain: host-side double buffering around the device loop.

The serial agent loop pays, per task: lease RTT → CSV read + tokenize/pad →
device compute → serialize + result RTT, all on one thread — so the device
idles while the host stages and posts (the round-2 gap: drain < pure-op
throughput). This runner overlaps them (BASELINE.json north star: "streams
shards straight into HBM with host-side double buffering"):

- **staging pool** (ISSUE 6, ``data/staging.py``): a feeder thread owns the
  lease loop and N autotuned workers run op ``stage`` phases (payload
  validation, shard read, fused tokenize+pad → numpy) *concurrently* into a
  bounded queue of depth ``pipeline_depth`` (the autotuner may widen it);
  the bound is the backpressure that keeps staging ~one shard ahead of the
  device instead of reading the whole dataset into RAM. ``STAGE_WORKERS=1``
  reproduces the old single-stager pipeline exactly.
- **device (calling) thread**: pops staged work and runs the op's ``execute``
  phase — every device touch stays on this one thread, preserving the
  single-owner invariant the reference called the "TPU RULE" (reference
  ``app.py:286``; SURVEY.md §5.2). No forks, no process pools. With
  ``FEED_DOUBLE_BUFFER`` (default on) it also *pre-feeds* the next staged
  item's host→device transfer (``jax.device_put`` is async and this is the
  owning thread) before dispatching the current item, so the device never
  waits on a transfer between shards.
- **poster thread**: runs ``finalize`` — which for the model ops also pays
  the deferred device→host result fetch (reading a ``jax.Array`` is
  thread-safe; only dispatch is owner-bound), then numpy → JSON shapes —
  and posts the result over its own HTTP session. Deferring the fetch here
  is what lets the device thread dispatch shard i+1 while shard i's
  round trip is in flight; the bounded post queue caps how many unfetched
  shards may be pinned at once.

Ops advertise phases as attributes on their registered handler
(``fn.stage/.execute/.finalize``, see ``ops/map_classify_tpu.py``); ops
without them run monolithically on the device thread, so the pipeline is
safe for every op.

Every phase boundary is measured once, by ``obs.trace.phase`` (histogram,
span, flight recorder, profiler annotation from one pair of clock reads).
Device time comes from completion events (``Agent.note_device_interval``).
An op whose ``execute`` may return with the device still working DECLARES it
(``fn.deferred = True`` beside the phase hooks) and stamps ``t_ready``, the
instant its results were on the host, into its state from whichever phase
fetched them; the device thread numbers the dispatch and the poster accounts
the interval once ``finalize`` has returned. Any other execute is taken to
have blocked until its result was on the host, and the device thread accounts
it as it returns — so an op that defers without declaring it is billed its
dispatch microseconds: the declaration is the contract. Intervals are
accounted in dispatch order whichever thread reports them. The device
thread's own wall time is split into the exclusive states of
``device_thread_seconds_total{state}``.

Wire-protocol semantics are unchanged: same lease/result bodies, same
structured errors, same epoch fencing. Results may post out of task order —
the protocol never required ordering (results are keyed by job_id).
Multi-host slices don't use this runner: leader/follower lockstep broadcast
serializes by design (``agent/app.py``).
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional

from agent_tpu.obs import trace as obs_trace
from agent_tpu.obs.usage import stamp_usage
from agent_tpu.utils.errors import structured_error
from agent_tpu.utils.logging import log


@dataclass
class _Item:
    """One leased task moving through the pipeline."""

    lease_id: str
    job_id: str
    epoch: Any
    op: str
    payload: Dict[str, Any]
    ctx: Any
    t_start: float
    fn: Any = None
    staged: Any = None            # op state between stage and execute
    executed: Any = None          # op state between execute and finalize
    result: Any = None            # terminal result (skips later phases)
    status: str = "succeeded"
    error: Any = None
    monolithic: bool = False      # op has no phase hooks
    # What every phase of this task is measured under (Agent.task_context:
    # trace_id = job_id, parent = the controller's lease span), and the
    # boundaries later phases measure from.
    trace: Any = None
    t_staged: float = 0.0         # when staging finished (queue-span start)
    t_exec0: float = 0.0          # execute entered (the dispatch instant)
    # The op declares a deferred fetch (fn.deferred): the poster accounts
    # the device interval, under the number this dispatch took.
    deferred: bool = False
    seq: Optional[int] = None
    accounted: bool = False       # its device interval has been reported
    # Continuous serving (ISSUE 15): the engine handle while this item's
    # requests ride the running batch (its execute span runs from t_exec0,
    # the admit instant, to the collect).
    serve_handle: Any = None


_STOP = object()


class _ThreadClock:
    """The device-owning thread's wall time as exclusive states: every
    ``switch`` books the seconds since the last one to the state that was
    current (``device_thread_seconds_total{state}``), so the states sum to
    the loop's wall time by construction. Each state is also an
    ``agent.<state>`` annotation on the thread's profiler line, except where
    the caller's own phase annotates the same extent."""

    def __init__(self, counter: Any, state: str) -> None:
        self._counter = counter
        self._state: Optional[str] = None
        self._t = time.perf_counter()
        self._ann: Any = None
        self.switch(state)

    def switch(self, state: Optional[str], annotate: bool = True
               ) -> Optional[str]:
        """Enter ``state`` (None: stop the clock); returns the state left."""
        now = time.perf_counter()
        left = self._state
        if left is not None:
            self._counter.inc(now - self._t, state=left)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._ann = (obs_trace.annotate(f"agent.{state}")
                     if state is not None and annotate else None)
        self._state, self._t = state, now
        return left

# How long a shutting-down device thread keeps waiting for the poster to free
# a post-queue slot before giving up (wedged-poster escape; see _put_post).
SHUTDOWN_GRACE_SEC = 30.0


class PipelineRunner:
    """Owns the staging pool + poster thread around the caller's device loop.

    ``runner.run()`` blocks on the device loop until ``agent.running`` flips
    false (signal handler or test), then drains both queues so no leased task
    is dropped on shutdown — same graceful-drain contract as the serial loop.
    """

    def __init__(
        self,
        agent,
        depth: int = 2,
        workers: Optional[int] = None,
        autotune: Optional[bool] = None,
        double_buffer: Optional[bool] = None,
    ) -> None:
        self.agent = agent
        self.depth = max(1, depth)
        self.staged_q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        # Bounded like staged_q: with deferred fetch (ops returning
        # unfetched device arrays from execute), this bound is what caps
        # in-flight shards — an unbounded post queue would pin device
        # output buffers without limit when the poster falls behind.
        self.post_q: "queue.Queue" = queue.Queue(maxsize=self.depth + 1)
        # Staging pool (ISSUE 6): the feeder thread owns the lease loop and
        # N autotuned workers run stage() concurrently; workers/autotune
        # default from config (STAGE_WORKERS / STAGE_AUTOTUNE).
        from agent_tpu.data.staging import StagingPool

        self._pool = StagingPool(
            agent, self.staged_q, self._stage_one, _STOP,
            max_workers=workers, autotune=autotune, base_depth=self.depth,
        )
        # Double-buffered device feed (FEED_DOUBLE_BUFFER): pre-issue the
        # next item's host→device transfer while the current one executes.
        self.double_buffer = (
            agent.config.agent.feed_double_buffer
            if double_buffer is None else bool(double_buffer)
        )
        # Live load advertisement (ISSUE 4): lease polls ship the CURRENT
        # leased-but-unexecuted backlog (staged + queued-for-staging) in
        # capabilities.queue_depth, so the controller's fair scheduler can
        # shrink this agent's grants and steer bulk shards to idler agents
        # while we're backed up. (The obs gauge lags a queue transition;
        # the qsize read does not.)
        agent.staged_depth_fn = self._pool.backlog
        self.tasks_posted = 0
        self.m_thread = agent.obs.counter(
            "device_thread_seconds_total",
            "Wall seconds of the device-owning thread by what it was doing: "
            "wait_staged (blocked on the staged queue), prefeed, dispatch "
            "(inside op execute), wait_post (blocked on the post queue), "
            "serve_pump; exclusive, they sum to the loop's wall time",
            ("state",))
        self._poster = threading.Thread(
            target=self._post_loop, name="agent-poster", daemon=True
        )

    # ---- staging (run on the pool's worker threads) ----

    def _stage_one(self, lease_id: str, task: Any) -> Optional[_Item]:
        agent = self.agent
        attempt = task.get("attempt") if isinstance(task, dict) else None
        # The stage phase resolves the task, so it learns its own context
        # inside the block; a task that stops before that records nothing.
        with obs_trace.phase("stage") as ph:
            # Shared resolution (Agent.resolve_task): malformed-task salvage
            # and the UnknownOp shape are single-sourced with the serial
            # loop.
            job_id, op, payload, epoch, fn, resolve_error = \
                agent.resolve_task(task)
            if job_id is None:
                return None
            trace_id, span_parent = agent.task_trace(task)
            trace = agent.task_context(
                op, job_id, trace_id, span_parent,
                lease_id=lease_id, attempt=attempt)
            if resolve_error is not None:
                return _Item(
                    lease_id, job_id, epoch, op, {}, None, ph.t0,
                    status="failed", error=resolve_error, trace=trace,
                )
            item = _Item(
                lease_id, job_id, epoch, op, payload,
                agent._op_context(job_id, lease_id=lease_id, attempt=attempt,
                                  parent_span_id=span_parent,
                                  tenant=task.get("tenant")
                                  if isinstance(task, dict) else None),
                ph.t0, fn=fn, trace=trace,
            )
            stage = getattr(fn, "stage", None)
            if stage is None:
                item.monolithic = True
                item.t_staged = time.perf_counter()
                return item
            ph.ctx = trace
            try:
                phase, value = stage(payload, item.ctx)
            except Exception as exc:  # noqa: BLE001 — same contract as run_task
                item.status = ph.attributes["status"] = "failed"
                item.error = structured_error(exc)
                agent.rate.log("exec", "stage raised", op=op,
                               type=type(exc).__name__)
                agent.recorder.record(
                    "error", phase="stage", job_id=job_id, op=op,
                    lease_id=lease_id, attempt=attempt,
                    type=type(exc).__name__, message=str(exc)[:200],
                )
                return item
            if phase == "done":
                item.result = value
            else:
                item.staged = value
        item.t_staged = ph.t1
        # Host-side usage attribution (ISSUE 9): stage seconds ride the
        # result's usage block next to the device seconds the completion
        # accounting stamps.
        stamp_usage(item.ctx.tags, host_s=ph.seconds)
        return item

    # ---- device (calling) thread ----

    def _put_post(self, item: Any) -> bool:
        """Blocking put into the bounded post queue. Blocking here is the
        backpressure that caps in-flight shards (ops defer their device→host
        fetch to the poster, so every queued item pins device buffers).

        Escapes: a dead poster (blocking would deadlock), or shutdown with a
        poster that has stopped draining (e.g. wedged in a fetch on a hung
        device) — a graceful drain keeps consuming and frees a slot well
        inside the grace window, so normal shutdown still posts everything."""
        clock = self._clock
        left = clock.switch("wait_post") if clock is not None else None
        try:
            return self._put_post_blocking(item)
        finally:
            if clock is not None:
                clock.switch(left, annotate=False)

    def _put_post_blocking(self, item: Any) -> bool:
        waited = 0.0
        while True:
            try:
                self.post_q.put(item, timeout=0.5)
                self.agent.m_queue.set(self.post_q.qsize(), queue="post")
                return True
            except queue.Full:
                if not self._poster.is_alive():
                    return False  # lease TTL re-queues the task
                if self.agent.running:
                    # Normal backpressure: only POST-shutdown waiting counts
                    # against the grace window, else a slow-but-draining
                    # poster could have an item dropped the instant
                    # shutdown begins.
                    waited = 0.0
                    continue
                waited += 0.5
                if waited >= SHUTDOWN_GRACE_SEC:
                    return False  # wedged poster during shutdown

    def _prefeed(self, item: Any) -> None:
        """Double-buffered device feed (ISSUE 6): start the NEXT item's
        host→device transfer before the current item's execute dispatch.
        ``jax.device_put`` is async and this is the owning thread, so the
        transfer overlaps the in-flight compute and the op's own
        ``put_batch`` later passes the already-placed arrays through without
        a copy. Only the well-known staged-chunk layout
        (``state["chunks"] = [(ids, lengths, n), …]`` of numpy arrays) is
        pre-fed; anything else stays untouched — this is purely an
        optimization and must never fail an item."""
        import numpy as np

        runtime = self.agent.runtime
        if (
            runtime is None or item.monolithic or item.staged is None
            or item.result is not None or item.status == "failed"
        ):
            return
        state = item.staged
        chunks = state.get("chunks") if isinstance(state, dict) else None
        if not isinstance(chunks, list):
            return
        try:
            fed = []
            for chunk in chunks:
                if (
                    isinstance(chunk, (tuple, list)) and len(chunk) == 3
                    and isinstance(chunk[0], np.ndarray)
                    and isinstance(chunk[1], np.ndarray)
                ):
                    fed.append((
                        runtime.put_batch(chunk[0]),
                        runtime.put_batch(chunk[1]),
                        chunk[2],
                    ))
                else:
                    fed.append(chunk)
            state["chunks"] = fed
        except Exception:  # noqa: BLE001 — the op re-puts on execute anyway
            pass

    def _serve_admit(self, item: Any, serving: list) -> None:
        """Join a serving item's requests to the continuous decode engine:
        prefill runs now (a batched compiled step on this, the device
        thread), the decode iterations run in :meth:`_serve_pump_once`
        interleaved with everything else the loop does."""
        agent = self.agent
        self._clock.switch("dispatch")
        t0 = time.perf_counter()
        item.t_exec0 = t0
        try:
            with obs_trace.use_context(item.trace):
                item.serve_handle = item.fn.serve_admit(item.staged, item.ctx)
        except Exception as exc:  # noqa: BLE001 — op error → failed
            item.status = "failed"
            item.error = structured_error(exc)
            agent.rate.log("exec", "serve admit raised", op=item.op,
                           type=type(exc).__name__)
            agent.recorder.record(
                "error", phase="execute", job_id=item.job_id, op=item.op,
                lease_id=item.lease_id, type=type(exc).__name__,
                message=str(exc)[:200],
            )
            self._put_post(item)
            return
        # Prefill blocks until the engine holds the rows: device time from
        # its own start and end; the decode iterations bill per pump.
        agent.note_device_interval(
            item.op, t0, time.perf_counter(),
            item.ctx.tags if item.ctx is not None else None,
        )
        agent.recorder.record(
            "phase", phase="serve_admitted", job_id=item.job_id, op=item.op,
            lease_id=item.lease_id,
        )
        serving.append(item)

    def _serve_pump_once(self, serving: list) -> None:
        """One decode iteration for every distinct engine with items in
        flight (several leased jobs share one engine — pumping it once
        advances all their slots), then post the items whose requests all
        finished. Finished sequences freed their slots inside the engine
        step, so backlogged requests joined BETWEEN iterations."""
        agent = self.agent
        self._clock.switch("serve_pump")
        engines: Dict[int, Any] = {}
        for item in serving:
            engines.setdefault(id(item.serve_handle["engine"]), item)
        t0 = time.perf_counter()
        occupancy = 0
        for item in engines.values():
            with obs_trace.use_context(item.trace):
                occupancy = max(
                    occupancy, item.fn.serve_pump(item.serve_handle))
        if engines:
            first = next(iter(engines.values()))
            # Decode-iteration device time, attributed once per pump (the
            # overlapped items share the very same dispatch); the step reads
            # its tokens back, so its return is its completion.
            agent.note_device_interval(
                first.op, t0, time.perf_counter(), None)
            agent.m_serve_occupancy.set(occupancy)
        for item in [
            it for it in serving if it.fn.serve_done(it.serve_handle)
        ]:
            serving.remove(item)
            try:
                item.executed = item.fn.serve_collect(item.serve_handle)
            except Exception as exc:  # noqa: BLE001
                item.status = "failed"
                item.error = structured_error(exc)
                agent.recorder.record(
                    "error", phase="execute", job_id=item.job_id,
                    op=item.op, lease_id=item.lease_id,
                    type=type(exc).__name__, message=str(exc)[:200],
                )
            item.serve_handle = None
            # Admit to collect, across many passes of the loop: no with
            # block can hold it, so the phase's sinks are fed directly.
            obs_trace.record_phase(
                "execute", item.trace, item.t_exec0, time.perf_counter(),
                status=item.status,
            )
            self._put_post(item)
        if not serving:
            agent.m_serve_occupancy.set(0)

    def _execute_loop(self) -> None:
        pending: Any = None
        # Continuous-serving items currently riding a decode engine
        # (ISSUE 15): the loop interleaves one engine iteration per pass
        # with ordinary staged work, so interactive decode keeps stepping
        # while bulk shards stage and new serving jobs join between steps.
        serving: list = []
        stopping = False
        self._clock = clock = _ThreadClock(self.m_thread, "wait_staged")
        try:
            while True:
                item = None
                if pending is not None:
                    item, pending = pending, None
                elif not stopping:
                    if serving:
                        # Decode in flight: never block on the queue — an
                        # empty poll just means this pass is pure decode.
                        try:
                            item = self.staged_q.get_nowait()
                        except queue.Empty:
                            item = None
                    else:
                        # The tf.data question — is the input stage or the
                        # accelerator the limiter? — from this thread's
                        # side: blocked here it is starved of staged work.
                        # (Whether the DEVICE idles meanwhile is
                        # note_device_interval's to say.)
                        clock.switch("wait_staged")
                        item = self.staged_q.get()
                if item is _STOP:
                    # Keep pumping until in-flight serving work posts —
                    # a leased request must answer even through shutdown.
                    stopping = True
                    item = None
                if item is not None:
                    self._execute_item(item, serving)
                    pending = self._peeked
                    self._peeked = None
                if serving:
                    self._serve_pump_once(serving)
                if stopping and not serving and pending is None:
                    break
        finally:
            self._put_post(_STOP)  # same lost-sentinel guard as the stager
            clock.switch(None)

    _peeked: Any = None
    # The device thread's state clock, alive while _execute_loop runs.
    _clock: Optional[_ThreadClock] = None

    def _execute_item(self, item: Any, serving: list) -> None:
        agent = self.agent
        agent.m_queue.set(self.staged_q.qsize(), queue="staged")
        if item.result is not None or item.status == "failed":
            self._put_post(item)
            return
        if getattr(item.fn, "serve_admit", None) is not None \
                and not item.monolithic:
            self._serve_admit(item, serving)
            return
        if self.double_buffer:
            # Peek-ahead: grab the next staged item (if any) and issue its
            # transfers now, so they run under the current item's execute.
            # The popped item is handed back to the loop via _peeked and
            # consumed on the next iteration — never lost.
            self._clock.switch("prefeed")
            try:
                peeked = self.staged_q.get_nowait()
            except queue.Empty:
                peeked = None
            if peeked is not None and peeked is not _STOP:
                self._prefeed(peeked)
            self._peeked = peeked
        # The execute phase below annotates this extent itself.
        self._clock.switch("dispatch", annotate=False)
        item.deferred = (not item.monolithic
                         and bool(getattr(item.fn, "deferred", False)))
        if item.deferred:
            item.seq = agent.device_dispatched()
        # What the dispatch compiles (the first call of a program) parents
        # to the execute span, at the compile's own length.
        with obs_trace.phase("execute", item.trace,
                             annotation="agent.dispatch") as ph:
            item.t_exec0 = ph.t0
            if item.t_staged:
                # Time spent waiting in the staged queue — the
                # backpressure gap between host staging and the device.
                # An item's wait, not this thread's work: span and
                # flight-recorder event, no annotation (the histogram is
                # fed from the op's own queue_ms at finalize).
                obs_trace.record_phase(
                    "queue", item.trace, item.t_staged, ph.t0,
                    histogram=False)
            try:
                # profiled_call covers phased ops too — PROFILE_DIR
                # traces capture the device phase either way (§5.1).
                if item.monolithic:
                    item.result = agent.profiled_call(
                        item.op,
                        lambda i=item: i.fn(i.payload, i.ctx),
                    )
                else:
                    item.executed = agent.profiled_call(
                        item.op,
                        lambda i=item: i.fn.execute(i.staged, i.ctx),
                    )
            except Exception as exc:  # noqa: BLE001 — op error → failed
                item.status = "failed"
                item.error = structured_error(exc)
                agent.rate.log("exec", "op raised", op=item.op,
                               type=type(exc).__name__)
                agent.recorder.record(
                    "error", phase="execute", job_id=item.job_id,
                    op=item.op, lease_id=item.lease_id,
                    type=type(exc).__name__, message=str(exc)[:200],
                )
            ph.attributes["status"] = item.status
        if not item.deferred or item.status == "failed":
            # The execute blocked until its result was on the host, or
            # raised: its own start and end are the dispatch and the
            # completion.
            self._account(item, ph.t1)
        if not self._put_post(item):
            # Nobody will finalize it: its number must still come in.
            self._account(item, time.perf_counter())

    def _account(self, item: Any, t_ready: float) -> None:
        """Report the item's device interval, once (either thread)."""
        if item.accounted:
            return
        item.accounted = True
        self.agent.note_device_interval(
            item.op, item.t_exec0, t_ready,
            item.ctx.tags if item.ctx is not None else None, seq=item.seq,
        )

    # ---- poster thread ----

    def _post_loop(self) -> None:
        agent = self.agent
        # Own HTTP session: requests.Session is not thread-safe, and the
        # feeder is concurrently POSTing leases on the agent's session.
        # ``post_session_factory`` overrides (bench wire-byte counting,
        # loopback soaks) — it must return a session safe for THIS thread.
        session = None
        factory = getattr(agent, "post_session_factory", None)
        if factory is not None:
            session = factory()
        else:
            try:
                import requests

                session = requests.Session()
            except Exception:  # noqa: BLE001 — stub sessions in tests
                pass
        while True:
            item = self.post_q.get()
            if item is _STOP:
                # Shutdown: force one last redelivery pass past the backoff
                # window; what stays undeliverable survives in the on-disk
                # spool (when configured) for the next incarnation.
                agent.flush_spool(session=session, force=True)
                break
            agent.m_queue.set(self.post_q.qsize(), queue="post")
            # Poster-thread cost as one span: finalize (incl. the deferred
            # device→host fetch, which the op measures as its own ``fetch``
            # phase inside) + the result post (``post_http`` inside). Ships
            # on the NEXT post or the final metrics-only flush.
            with obs_trace.phase("post", item.trace,
                                 histogram=False) as posted:
                self._finalize_and_post(item, session, posted)
            # Spooled redelivery rides the poster cadence (backoff-gated
            # inside flush_spool) — the pipelined drain heals from a
            # controller blip the same way the serial loop does.
            agent.flush_spool(session=session)
            self.tasks_posted += 1
            agent.tasks_done += 1
            agent.m_tasks.inc(op=item.op, status=item.status)
            agent.note_progress(queues={
                "staged_q": self.staged_q.qsize(),
                "post_q": self.post_q.qsize(),
            })

    def _finalize_and_post(self, item: Any, session: Any,
                           posted: Any) -> None:
        agent = self.agent
        with obs_trace.phase("finalize", span=False) as fin:
            try:
                if item.executed is not None:
                    item.result = item.fn.finalize(item.executed, item.ctx)
            except Exception as exc:  # noqa: BLE001
                item.status = "failed"
                item.error = structured_error(exc)
                item.result = None
                agent.recorder.record(
                    "error", phase="finalize", job_id=item.job_id,
                    op=item.op, lease_id=item.lease_id,
                    type=type(exc).__name__, message=str(exc)[:200],
                )
        if item.deferred:
            # The completion of an execute that may only have dispatched:
            # the instant the op says its results were on the host (where it
            # says none — the fetch itself failed — the device had nothing
            # more to give by the time finalize gave up). BEFORE the usage
            # block is read below: device_s is this interval.
            state = item.executed
            stamped = state.get("t_ready") if isinstance(state, dict) else None
            self._account(item, stamped or fin.t1)
        finalize_s = fin.seconds
        duration_ms = (fin.t1 - item.t_start) * 1000.0
        if item.ctx is not None:
            # Poster-thread host seconds join the stage stamp (ISSUE 9).
            stamp_usage(item.ctx.tags, host_s=finalize_s)
            timings = item.ctx.tags.setdefault("timings", {})
            # Stamped here because finalize cannot time its own return;
            # rides the result body so scrape-side attribution sees the
            # poster-thread cost too.
            timings["finalize_ms"] = round(finalize_s * 1000.0, 3)
            # The queue wait comes from the op's own timings; stage/execute/
            # fetch/finalize were measured at their boundaries (observing
            # both views would double-count those phases).
            agent.record_phase_timings(
                item.op, timings, keys=("queue_ms",), trace_id=item.job_id,
            )
        if isinstance(item.result, dict):
            item.result.setdefault("duration_ms", duration_ms)
            if item.ctx is not None:
                if item.ctx.tags.get("timings"):
                    item.result.setdefault(
                        "timings", item.ctx.tags["timings"]
                    )
                item.result.setdefault(
                    "trace", item.ctx.tags.get("trace")
                )
                if item.ctx.tags.get("usage"):
                    # Usage block (ISSUE 9): what the controller's
                    # showback ledger bills for this task.
                    item.result.setdefault(
                        "usage", item.ctx.tags["usage"]
                    )
        agent.post_result(
            item.lease_id, item.job_id, item.epoch, item.status,
            result=item.result, error=item.error, session=session,
            op=item.op,
        )
        # The span's own duration_ms is the poster's extent; the task's
        # (stage entered to finalize returned) rides beside it.
        posted.attributes.update(
            status=item.status, finalize_ms=round(finalize_s * 1e3, 3),
            task_duration_ms=round(duration_ms, 3),
        )

    # ---- lifecycle ----

    def run(self) -> None:
        # The runtime must exist before the stager reads mesh metadata, and
        # it must be built HERE: this is the device-owning thread.
        if self.agent.runtime is None:
            from agent_tpu.runtime.runtime import get_runtime

            self.agent.runtime = get_runtime(self.agent.config.device)
        log(
            "pipelined drain up", depth=self.depth,
            stage_workers=self._pool.max_workers,
            autotune=self._pool.autotune,
            double_buffer=self.double_buffer,
        )
        self._pool.start()
        self._poster.start()
        try:
            self._execute_loop()   # device work stays on the caller's thread
        finally:
            self.agent.running = False
            self._pool.join(timeout=30)
            # Graceful drain (ISSUE 10): tasks still queued for staging
            # after the workers exited are handed back (released) instead
            # of stranding the lease until the TTL; release_pending no-ops
            # unless the agent is draining.
            self._pool.release_pending()
            self._poster.join(timeout=30)
            # Final telemetry flush (metrics-only lease): the last shard's
            # finalize postdates the stager's last real poll, so without
            # this the fleet view would miss the drain's tail. A draining
            # agent's flush carries the `draining` mark — the controller
            # half of the drain handshake.
            self.agent.push_metrics()
        log("pipelined drain stopped", tasks_posted=self.tasks_posted)
