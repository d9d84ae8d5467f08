"""The agent control loop (successor of reference ``app.py:143-316``).

Wire protocol (the compatibility contract, SURVEY.md §2.9):

- ``POST /v1/leases`` body ``{agent, capabilities: {ops}, max_tasks, timeout_ms,
  labels, worker_profile, metrics}``; response 204 (or empty tasks) = idle,
  else ``{lease_id, tasks: [{id|job_id, op, payload, job_epoch}]}``.
- ``POST /v1/results`` body ``{lease_id, job_id, job_epoch,
  status: "succeeded"|"failed", result, error}``; the echoed ``job_epoch`` is
  the fencing token that lets the controller discard stale retries.

Behavioral contract kept from the reference:

- Ops run **inline** on the main thread — "TPU RULE: no fork / no process
  pool" (reference ``app.py:286``). The device mesh has exactly one owner; a
  forked child would wedge the TPU runtime. Parallelism lives *inside* the op
  (batched SPMD over the mesh), not in host processes.
- status 0 = transport error (reference ``app.py:146-148``); lease errors back
  off with capped exponential backoff + decorrelated jitter (ISSUE 3 —
  ``error_backoff_sec`` is the base; the reference slept it flat) with
  per-key rate-limited logging; empty lease sleeps ``idle_sleep_sec`` ±25%
  jitter so a restarted fleet doesn't poll in lockstep.
- SIGINT/SIGTERM flip a running flag → graceful drain after the in-flight task.
- Exit code 2 when TASKS resolves to no ops.

New here: per-task phase timings (lease wait / execute / report) embedded in
the result for tracing (SURVEY.md §5.1), device telemetry from
``TpuRuntime.describe()`` shipped in the lease ``metrics`` channel alongside
host cpu/ram (reference ``app.py:74-83``), and the **result spool** (ISSUE 3):
a completed result whose post fails transiently is spooled (bounded ring +
optional ``RESULT_SPOOL_PATH`` JSONL) and redelivered with backoff on later
loop iterations instead of dropped — a controller restart inside the lease
window no longer re-executes finished shards; epoch fencing makes the
redelivery idempotent.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from agent_tpu.agent.spool import ResultSpool
from agent_tpu.config import Config
from agent_tpu.data import wire
from agent_tpu.obs.health import RollingWindow, resolve_peak_flops
from agent_tpu.obs.metrics import MetricsRegistry
from agent_tpu.obs.profile import device_memory_stats
from agent_tpu.obs.recorder import FlightRecorder, default_dump_path
from agent_tpu.obs.usage import stamp_usage
from agent_tpu.obs.trace import SpanBuffer, TraceContext, make_span
from agent_tpu.obs import trace as obs_trace
from agent_tpu.ops import OpFn, load_ops
from agent_tpu.utils.errors import structured_error
from agent_tpu.utils.logging import RateLimiter, log
from agent_tpu.utils.retry import (
    PERMANENT,
    RetryPolicy,
    classify_http,
    jittered,
)

# result-timings key → task_phase_seconds phase label. The ops stamp
# milliseconds into ctx.tags["timings"] (see map_classify_tpu.finalize);
# the loops turn them into histogram observations in seconds. The fetch wait
# is not here: the op measures it itself, through obs.trace.phase("fetch").
PHASE_KEYS = (
    ("stage_ms", "stage"),
    ("queue_ms", "queue"),
    ("device_ms", "execute"),
    ("finalize_ms", "finalize"),
)

STATUS_TRANSPORT_ERROR = 0  # "could not reach the controller at all"

# Rolling duty-cycle window (ISSUE 8): 60s matches the "is the device busy
# RIGHT NOW" question the autoscaler asks; the cumulative busy/idle
# counters remain the long-horizon view.
DUTY_WINDOW_SEC = 60.0


def collect_host_metrics() -> Dict[str, Any]:
    """``{cpu_util: 0..1, ram_mb}`` via psutil; empty when psutil is missing
    (reference ``app.py:74-83``)."""
    try:
        import psutil  # type: ignore

        return {
            "cpu_util": psutil.cpu_percent(interval=None) / 100.0,
            "ram_mb": int(psutil.virtual_memory().used / (1024 * 1024)),
        }
    except Exception:  # noqa: BLE001 — psutil optional
        return {}


class Agent:
    """One agent process: leases tasks, executes them on the mesh, reports.

    ``session`` is any object with ``post(url, json=, timeout=) -> response``
    (a ``requests.Session`` in production, a stub in tests). ``runtime`` is the
    ``TpuRuntime`` handed to ops via ``OpContext``; left None it is built
    lazily by the first op that needs the device, so pure-host agents never
    touch jax.
    """

    def __init__(
        self,
        config: Optional[Config] = None,
        session: Any = None,
        runtime: Any = None,
        registry: Any = None,
        recorder: Any = None,
        tracer: Any = None,
    ) -> None:
        self.config = config or Config.from_env()
        if session is None:
            import requests

            session = requests.Session()
        self.session = session
        self.runtime = runtime
        self.running = True
        # Graceful retirement (ISSUE 10): set by request_drain (SIGTERM,
        # autoscaler scale-down, spot reclaim). A draining agent stops
        # leasing new work, finishes the in-flight task, RELEASES the
        # unstarted remainder of its lease (status="released" — instant
        # requeue, no TTL wait, no attempt burned), flushes its spool and
        # final metrics (the flush poll carries draining=true so
        # /v1/status marks it), then exits clean.
        self.draining = False
        self.rate = RateLimiter(self.config.agent.error_log_every_sec)
        # Observability (ISSUE 2): an OWN registry/recorder per agent — the
        # controller often shares the process (tests, bench) and the fleet
        # merge must not double-count series. The snapshot ships to the
        # controller inside every lease's ``metrics`` dict.
        self.obs: MetricsRegistry = (
            registry if registry is not None else MetricsRegistry()
        )
        self.recorder: FlightRecorder = (
            recorder if recorder is not None else FlightRecorder()
        )
        # Distributed tracing (ISSUE 5): agent-side spans (stage/queue/
        # execute/post, xla.compile, spool redeliveries) buffer here and
        # piggyback onto /v1/results bodies and the lease metrics channel;
        # the controller assembles them into per-job trees. Bounded ring;
        # TRACE_ENABLED=0 makes every add a no-op.
        self.tracer: SpanBuffer = (
            tracer if tracer is not None else SpanBuffer()
        )
        self.m_tasks = self.obs.counter(
            "tasks_total", "Tasks completed by op and status",
            ("op", "status"))
        self.m_phase = self.obs.histogram(
            "task_phase_seconds",
            "Per-task phase latency (stage/queue/execute/fetch/finalize)",
            ("op", "phase"))
        self.m_lease = self.obs.counter(
            "lease_requests_total", "Lease polls by outcome", ("outcome",))
        self.m_lease_seconds = self.obs.histogram(
            "agent_lease_seconds",
            "One lease poll as the agent pays it: telemetry snapshot, the "
            "/v1/leases round trip, response handling", ("outcome",))
        self.m_queue = self.obs.gauge(
            "queue_depth", "Pipeline queue occupancy (staged/post)",
            ("queue",))
        self.m_device_idle = self.obs.counter(
            "device_idle_seconds_total",
            "Seconds with nothing in flight on the device: from the last "
            "program seen ready (or the agent's start) to the next dispatch")
        # Serving (ISSUE 15): live occupancy of the continuous-batching
        # decode engine's running batch — the "is iteration-level batching
        # actually batching" signal swarmtop's serving row shows.
        self.m_serve_occupancy = self.obs.gauge(
            "serve_batch_occupancy",
            "Continuous-batching running batch: requests currently seated "
            "(0 when no serving work is in flight)")
        # Per-op device attribution (ISSUE 8): busy seconds carry the op so
        # /v1/health can say WHICH workload owns the device, not just that
        # it is busy. Fleet-merge/scrape consumers that sum the family are
        # unaffected (labels sum); value() readers must now pass op=.
        self.m_device_busy = self.obs.counter(
            "device_busy_seconds_total",
            "Seconds the device had this op's work in flight, per op: from "
            "dispatch (or the previous program's completion, the device "
            "runs them in order) to the result seen ready on the host",
            ("op",))
        self.m_duty = self.obs.gauge(
            "device_duty_cycle",
            "Rolling duty cycle: device_busy_seconds_total gained inside "
            f"the last {int(DUTY_WINDOW_SEC)}s window / window span")
        self.m_flops = self.obs.counter(
            "device_flops_total",
            "Analytic model FLOPs dispatched, per op and shape bucket "
            "(matmul terms only — the ops' own estimate)",
            ("op", "shape"))
        self.m_mfu = self.obs.gauge(
            "device_mfu",
            "Model FLOPs utilization per op: analytic FLOPs / "
            "device_busy_seconds_total / peak dense-bf16 FLOP/s (absent "
            "when the peak is unknown — PEAK_TFLOPS overrides)", ("op",))
        self.m_hbm = self.obs.gauge(
            "device_hbm_bytes",
            "Per-device accelerator memory from memory_stats(), across ALL "
            "local devices (absent on backends that report none — CPU)",
            ("device", "kind"))
        self.m_failover = self.obs.counter(
            "controller_failovers_total",
            "Active-controller rotations after transport errors "
            "(CONTROLLER_URLS failover list)")
        self.m_post_fail = self.obs.counter(
            "result_post_failures_total",
            "Result posts that failed (then spooled, or dropped if the "
            "failure was permanent)", ("op",))
        self.m_redeliveries = self.obs.counter(
            "result_redeliveries_total",
            "Spooled-result redelivery outcomes (delivered/"
            "dropped_permanent/dropped_overflow/expired)", ("outcome",))
        self.m_spool_depth = self.obs.gauge(
            "result_spool_depth", "Completed results awaiting redelivery")
        # Fault tolerance (ISSUE 3): undelivered results spool here and
        # redeliver with decorrelated backoff; lease errors share the same
        # policy. error_backoff_sec stays the lease-retry base so the legacy
        # knob keeps meaning what it meant.
        a = self.config.agent
        self.spool = ResultSpool(
            capacity=a.result_spool_max, path=a.result_spool_path or None
        )
        self._retry_policy = RetryPolicy(
            base_sec=a.retry_base_sec, max_sec=a.retry_max_sec
        )
        self._lease_retry = RetryPolicy(
            base_sec=a.error_backoff_sec, max_sec=a.retry_max_sec
        ).start()
        self._spool_retry = self._retry_policy.start()
        self._spool_next_try = 0.0
        self.m_spool_depth.set(len(self.spool))  # disk-loaded backlog
        # Periodic progress-summary state (the per-task "task done" line is
        # rate-limited away: one line per task floods stdout at drain scale).
        self._progress = {"t": time.monotonic(), "n": 0}
        # Multi-host: join the coordination service BEFORE anything touches a
        # jax backend (sizing probes jax.devices()); jax.distributed must be
        # first or it refuses and the slice desyncs.
        self.dist = self._dist_info()
        # Resolve the full op table at startup — unknown/disabled names fail
        # fast here, not mid-lease (the intended design the reference's dead
        # ops_loader.py:8-19 sketched).
        self.handlers: Dict[str, OpFn] = load_ops(list(self.config.agent.tasks))
        self._profile: Optional[Dict[str, Any]] = None
        self.tasks_done = 0
        # Live staged-queue depth source (set by PipelineRunner); the serial
        # loop has no staging queue, so it falls back to the obs gauge
        # (which is 0 unless a pipeline ever ran). Shipped in the lease
        # ``capabilities`` so the controller's scheduler can steer bulk work
        # away from backed-up agents and shrink grants (ISSUE 4).
        self.staged_depth_fn: Optional[Any] = None
        # Binary shard wire (ISSUE 6): the format the controller negotiated
        # on the last granted lease (``wire: "b1"`` in the response body),
        # None against a JSON-only controller. Read at op-context build time
        # so finalize knows whether to emit binary result columns.
        self.wire_format: Optional[str] = None
        # Staging-pool grant ask (data/staging.py): when set, lease polls
        # request max(MAX_TASKS, hint) tasks so N stage workers have work in
        # flight; the controller's grant stays advisory downward.
        self.lease_batch_hint: Optional[int] = None
        # Poster-thread session override (PipelineRunner._post_loop):
        # callable returning a session; None = a fresh requests.Session.
        self.post_session_factory: Optional[Any] = None
        # Fleet health (ISSUE 8): rolling duty window + cumulative per-op
        # busy/FLOPs for the MFU gauge, and the in-order queue model's
        # state: when the device was last seen done (it is idle from the
        # agent's start), and the intervals reported ahead of an earlier
        # dispatch's, held until their turn. Fed by note_device_interval
        # from the poster thread (deferred fetches) and the dispatch thread,
        # and read by the lease thread's snapshot: all under _device_lock.
        self._device_lock = threading.Lock()
        self._t_ready_prev = time.perf_counter()
        self._dispatch_seq = 0          # dispatches numbered so far
        self._accounted_seq = 0         # ... and accounted, in order
        self._held_intervals: Dict[int, Tuple[Any, ...]] = {}
        self._duty = RollingWindow(DUTY_WINDOW_SEC)
        self._busy_by_op: Dict[str, float] = {}
        self._flops_by_op: Dict[str, float] = {}
        self._peak_flops: Optional[float] = None
        # SLO page alerts piggybacked on granted leases: objectives whose
        # page episode this agent already dumped its ring for (one dump per
        # episode; clearing re-arms).
        self._page_dumped: set = set()
        self.slo_dump_paths: List[str] = []
        # On-demand deep captures (ISSUE 9): requests arrive as
        # `profile_capture` lease alerts, wrap the next matching op
        # execution in jax.profiler.trace, and the completion records ship
        # back on the lease metrics channel. Touched only by the dispatch
        # thread (captures) and the lease loop (completions).
        self._pending_captures: List[Dict[str, Any]] = []
        self._captures_seen: set = set()
        self._capture_done: List[Dict[str, Any]] = []
        # Mesh width for chip-seconds attribution: device_s × chips is what
        # the ledger turns into chip-seconds (a dp=8 dispatch second spans
        # 8 chips). Cached on first use; 1 without a runtime.
        self._usage_chips: Optional[float] = None
        # Controller failover list (ISSUE 14): CONTROLLER_URLS candidates,
        # primary first. A transport error rotates the active index
        # (sticky on success), so spool redelivery and the lease loop
        # follow a promoted hot standby without restarting the agent.
        # Index updates race benignly across the lease/poster threads.
        urls = list(a.controller_urls) or [a.controller_url]
        if a.controller_url not in urls:
            urls.insert(0, a.controller_url)
        self._controller_urls = urls
        self._url_index = 0
        # Partitioned control plane (ISSUE 18): with an explicit partition
        # map the agent wraps its session in the in-process router shim —
        # home-first leases, depth-based stealing, tagged lease ids — and
        # the whole loop above this line stays topology-blind. The spool
        # stores the TAGGED lease id, so redelivery follows the stolen
        # job's applying partition through the shim with no new spool
        # machinery. (With a router URL in CONTROLLER_URLS the router does
        # all of this server-side and this branch never runs.)
        if a.controller_partition_map:
            from agent_tpu.controller.partition import (
                PartitionMap,
                PartitionSession,
            )
            from agent_tpu.sched.steal import StealPolicy

            pmap = PartitionMap.parse(a.controller_partition_map)
            steal = StealPolicy.from_env()
            self.session = PartitionSession(
                self.session, pmap, steal=steal,
                timeout_sec=a.http_timeout_sec,
            )
            # The pipelined poster thread builds its own session
            # (requests.Session is not thread-safe) — give it the same
            # shim, or results would bypass the partition map entirely.
            if getattr(self, "post_session_factory", None) is None:
                def _partition_post_session() -> PartitionSession:
                    import requests

                    return PartitionSession(
                        requests.Session(), pmap, steal=steal,
                        timeout_sec=a.http_timeout_sec,
                    )

                self.post_session_factory = _partition_post_session

    # ---- controller I/O ----

    def active_controller_url(self) -> str:
        """The controller currently targeted — rotates through the
        CONTROLLER_URLS failover list on transport errors (ISSUE 14)."""
        urls = self._controller_urls
        return urls[self._url_index % len(urls)]

    def _note_transport_error(self, url: str) -> None:
        """Rotate to the next failover candidate. Only meaningful with
        ≥ 2 URLs; self-correcting — if the next candidate is also down,
        the following error rotates again, and a success pins the index
        wherever it landed."""
        urls = self._controller_urls
        if len(urls) < 2:
            return
        # Another thread may have rotated already; only advance past the
        # URL that actually failed so concurrent errors rotate once.
        if urls[self._url_index % len(urls)] == url:
            self._url_index = (self._url_index + 1) % len(urls)
            self.m_failover.inc()
            self.recorder.record(
                "controller_failover", failed=url,
                active=urls[self._url_index],
            )
            log(
                "controller unreachable — failing over",
                failed=url, active=urls[self._url_index],
            )

    def _post_json(
        self, path: str, body: Dict[str, Any], session: Any = None
    ) -> Tuple[int, Any]:
        """POST JSON → (status, parsed body). Status 0 = transport error; JSON
        parse falls back to raw text (reference ``app.py:143-158``).
        ``session`` overrides the agent's session — the pipelined poster
        thread brings its own (requests.Session is not thread-safe)."""
        base = self.active_controller_url()
        url = f"{base}{path}"
        try:
            resp = (session or self.session).post(
                url, json=body, timeout=self.config.agent.http_timeout_sec
            )
        except Exception as exc:  # noqa: BLE001 — any transport failure
            # Failover (ISSUE 14): the retry/spool machinery redelivers —
            # to the NEXT candidate once the list rotates.
            self._note_transport_error(base)
            return STATUS_TRANSPORT_ERROR, repr(exc)
        if resp.status_code == 204:
            return 204, None
        try:
            return resp.status_code, resp.json()
        except ValueError:
            return resp.status_code, getattr(resp, "text", None)

    def worker_profile(self) -> Dict[str, Any]:
        """Dynamic profile, built once per process (probing is not free)."""
        if self._profile is None:
            from agent_tpu.sizing import build_worker_profile

            self._profile = build_worker_profile(self.config)
        return self._profile

    def _staged_depth(self) -> int:
        if self.staged_depth_fn is not None:
            try:
                return max(0, int(self.staged_depth_fn()))
            except Exception:  # noqa: BLE001 — telemetry must never kill a lease
                return 0
        try:
            return max(0, int(self.m_queue.value(queue="staged")))
        except Exception:  # noqa: BLE001
            return 0

    def capabilities(self) -> Dict[str, Any]:
        """The lease ``capabilities`` body: ops plus the scheduler-facing
        enrichment (ISSUE 4) — ``device_kind``/``mesh_devices`` from
        ``TpuRuntime.describe()`` and the current staged ``queue_depth``.
        Shipped regardless of the controller's SCHED_POLICY (fifo ignores
        it; fair uses it for placement and grant sizing). A runtime that
        hasn't been built yet is NOT forced into existence here — pure-host
        agents never touch jax, so the device fields are simply absent."""
        caps: Dict[str, Any] = {
            "ops": sorted(self.handlers),
            "queue_depth": self._staged_depth(),
        }
        if self.config.agent.wire_binary:
            # Binary shard wire offer (ISSUE 6): a capable controller
            # answers with ``wire: "b1"``; a legacy one ignores the key and
            # the whole exchange stays plain JSON.
            caps["wire_formats"] = list(wire.FORMATS)
        if self.config.device.chip_slice:
            # Device-pinned fleet member (ISSUE 7): which slice of the
            # host's chips this agent owns. Informational for operators and
            # the fleet view; placement keeps reading device_kind/
            # mesh_devices/queue_depth.
            caps["chip_slice"] = self.config.device.chip_slice
        if self.runtime is not None:
            try:
                desc = self.runtime.describe()
                caps["device_kind"] = desc.get("platform")
                caps["mesh_devices"] = desc.get("n_devices")
            except Exception:  # noqa: BLE001 — telemetry must never kill a lease
                pass
        return caps

    def device_dispatched(self) -> int:
        """The device thread, as it dispatches work whose completion ANOTHER
        thread will report: the work's place in the device's queue, to hand
        to :meth:`note_device_interval` as ``seq``. Whoever takes a number
        reports it, whatever becomes of the work: later intervals wait for
        it."""
        with self._device_lock:
            self._dispatch_seq += 1
            return self._dispatch_seq

    def note_device_interval(
        self, op: str, t_dispatch: float, t_ready: float,
        tags: Optional[Dict[str, Any]] = None, seq: Optional[int] = None,
    ) -> None:
        """Per-op device attribution from completion events (the in-order
        queue model): one thread dispatches and the device runs programs in
        dispatch order, so work dispatched at ``t_dispatch`` and seen ready
        on the host at ``t_ready`` (both ``perf_counter``) kept the device
        busy from ``max(t_dispatch, previous ready)`` to ``t_ready``, and
        the device idled from the previous ready to ``t_dispatch`` when
        nothing was in flight. That SAME float feeds
        ``device_busy_seconds_total{op}``, the rolling duty cycle,
        ``device_mfu{op}`` (with the FLOPs the op stamped into
        ``tags["device_attr"]``) and the task's ``usage.device_s``, so the
        showback ledger reconciles with the counter exactly.

        Callers: the pipeline's poster, after ``finalize`` returned, with
        the instant the op's deferred fetch came back and the ``seq`` the
        device thread took at dispatch (:meth:`device_dispatched`); and
        whoever ran an execute that blocks until the result is on the host
        (the serial loop, the serve pump), with its own start and end and no
        ``seq``: it is numbered here, as dispatched just now. Intervals are
        accounted in that order whatever order they are reported in: one
        reported ahead of an earlier dispatch's (a decode step run while a
        drain shard still waits for the poster) is held until the earlier
        ones are in, or the earlier one would find its seconds already taken
        and an idle gap booked that never was. The sum telescopes: a ready
        seen late moves seconds between neighbouring tasks, never into or
        out of the total, and can only hide an idle gap shorter than the
        lateness."""
        with self._device_lock:
            if seq is None:
                self._dispatch_seq += 1
                seq = self._dispatch_seq
            self._held_intervals[seq] = (op, t_dispatch, t_ready, tags)
            while self._accounted_seq + 1 in self._held_intervals:
                self._accounted_seq += 1
                self._account_interval(
                    *self._held_intervals.pop(self._accounted_seq))

    def _account_interval(
        self, op: str, t_dispatch: float, t_ready: float,
        tags: Optional[Dict[str, Any]],
    ) -> None:
        """One interval, in its turn (under ``_device_lock``)."""
        prev = self._t_ready_prev
        seconds = max(0.0, t_ready - max(t_dispatch, prev))
        idle = max(0.0, t_dispatch - prev)
        self._t_ready_prev = max(prev, t_ready)
        self._duty.add(seconds)
        busy = self._busy_by_op[op] = self._busy_by_op.get(op, 0.0) + seconds
        task_flops = 0.0
        attr = (tags or {}).get("device_attr")
        if isinstance(attr, dict):
            flops = attr.get("flops")
            if isinstance(flops, (int, float)) and flops > 0:
                task_flops = float(flops)
        flops_total = self._flops_by_op[op] = (
            self._flops_by_op.get(op, 0.0) + task_flops
        )
        self.m_device_busy.inc(seconds, op=op)
        if idle:
            self.m_device_idle.inc(idle)
        self.m_duty.set(round(self._duty.fraction(), 4))
        if task_flops:
            self.m_flops.inc(
                task_flops, op=op, shape=str(attr.get("shape", "?"))
            )
        if self._usage_chips is None:
            try:
                self._usage_chips = (
                    float(self.runtime.n_devices)
                    if self.runtime is not None else 1.0
                )
            except Exception:  # noqa: BLE001 — telemetry must never raise
                self._usage_chips = 1.0
        stamp_usage(
            tags, device_s=seconds, chips=self._usage_chips,
            flops=task_flops or None,
        )
        if self._peak_flops is None:
            self._peak_flops = resolve_peak_flops(self.runtime)
        if self._peak_flops and busy > 0 and flops_total > 0:
            self.m_mfu.set(
                round(flops_total / busy / self._peak_flops, 6), op=op
            )

    def note_alerts(self, alerts: Any) -> None:
        """React to SLO page alerts piggybacked on a granted lease (ISSUE 8
        satellite): entering ``page`` dumps THIS agent's flight-recorder
        ring, tagged with the breaching objective's ``{tier, op}`` — the
        agent half of the evidence pair (the controller dumps its own ring
        at the transition). One dump per objective per page episode; an
        objective that recovers re-arms."""
        active: set = set()
        for a in alerts or []:
            if not isinstance(a, dict):
                continue
            if a.get("kind") == "profile_capture":
                # On-demand deep capture (ISSUE 9): arm one jax.profiler
                # trace around the next matching op execution. Deduped by
                # capture id — the alerts channel may redeliver.
                cid = a.get("capture_id")
                if isinstance(cid, str) and cid \
                        and cid not in self._captures_seen:
                    self._captures_seen.add(cid)
                    self._pending_captures.append({
                        "capture_id": cid,
                        "op": a.get("op"),
                        "duration_ms": a.get("duration_ms"),
                    })
                continue
            if a.get("state") != "page":
                continue
            objective = a.get("objective")
            if not objective:
                continue
            active.add(objective)
            if objective in self._page_dumped:
                continue
            self._page_dumped.add(objective)
            bits = "-".join(
                f"{k}{a[k]}" for k in ("tier", "tenant", "op") if a.get(k)
            ) or "all"
            path = default_dump_path(
                f"agent-{self.config.agent.agent_name}-slo-{objective}-{bits}"
            )
            self.recorder.record(
                "slo_page", objective=objective, path=path,
                **{k: a[k] for k in ("tier", "tenant", "op") if a.get(k)},
            )
            try:
                n = self.recorder.dump(path)
                self.slo_dump_paths.append(path)
                log("slo page — agent flight recorder dumped",
                    objective=objective, path=path, events=n)
            except OSError:
                pass  # a failing dump must not stop the drain
        self._page_dumped &= active

    def _refresh_hbm_gauges(self) -> None:
        """``device_hbm_bytes{device,kind}`` from ``memory_stats()`` across
        ALL local devices (ISSUE 9) — refreshed at snapshot time like the
        duty gauge. Backends without stats (CPU) export nothing: the family
        is cleanly absent, never zero-filled."""
        if self.runtime is None:
            return
        try:
            for entry in device_memory_stats(self.runtime.devices):
                for kind in ("used", "limit", "peak"):
                    if kind in entry:
                        self.m_hbm.set(
                            entry[kind], device=entry["device"], kind=kind
                        )
        except Exception:  # noqa: BLE001 — telemetry must never kill a lease
            pass

    def _metrics(self) -> Dict[str, Any]:
        m = collect_host_metrics()
        # Duty decays while idle: refresh at snapshot time so a quiet agent
        # reads 0, not its last busy moment.
        with self._device_lock:
            duty = self._duty.fraction()
        self.m_duty.set(round(duty, 4))
        self._refresh_hbm_gauges()
        if self.runtime is not None:
            try:
                m["device"] = self.runtime.describe()
            except Exception:  # noqa: BLE001 — telemetry must never kill a lease
                pass
        try:
            # The fleet channel: the controller keys this snapshot by agent
            # id and merges the fleet into GET /v1/metrics.
            m["obs"] = self.obs.snapshot()
        except Exception:  # noqa: BLE001 — telemetry must never kill a lease
            pass
        return m

    def push_metrics(self, session: Any = None) -> bool:
        """Metrics-only lease poll (``max_tasks=0`` — the controller records
        telemetry and leases nothing). Drain loops call this after the last
        result posts so the final counters reach the fleet view; best-effort
        by contract."""
        spans: List[Dict[str, Any]] = []
        captures: List[Dict[str, Any]] = []
        try:
            a = self.config.agent
            metrics = self._metrics()
            spans = self._drain_spans()
            if spans:
                # Final span ship (ISSUE 5): the drain-tail spans (last
                # post/redeliver) postdate the last result post, so the
                # flush lease is what completes the last jobs' trees.
                metrics["spans"] = spans
            captures = self._drain_capture_results()
            if captures:
                metrics["profile_captures"] = captures
            body: Dict[str, Any] = {
                "agent": a.agent_name,
                # queue_depth sampled at request-BUILD time (ISSUE 6
                # satellite): the flush postdates the last real poll, so
                # without this the advertised depth would lag reality by
                # a whole poll cycle on every channel but the lease.
                "capabilities": {
                    "ops": [],
                    "queue_depth": self._staged_depth(),
                },
                "max_tasks": 0,
                "labels": a.labels,
                "metrics": metrics,
            }
            if self.draining:
                # Drain handshake (ISSUE 10): the final flush is what tells
                # the controller this member is retiring — /v1/status and
                # /v1/health mark it `draining`. Absent otherwise, keeping
                # the steady-state wire byte-identical.
                body["draining"] = True
            status, _ = self._post_json("/v1/leases", body, session=session)
            if status not in (200, 204):
                if spans:
                    self.tracer.requeue(spans)
                self._requeue_capture_results(captures)
            return status in (200, 204)
        except Exception:  # noqa: BLE001 — flush must never fail a drain
            if spans:
                self.tracer.requeue(spans)
            self._requeue_capture_results(captures)
            return False

    def record_phase_timings(
        self, op: str, timings: Optional[Dict[str, Any]],
        keys: Optional[Tuple[str, ...]] = None,
        trace_id: Optional[str] = None,
    ) -> None:
        """ctx.tags["timings"] (milliseconds) → ``task_phase_seconds``
        observations. ``keys`` restricts which timing keys count — the
        pipelined runner measures stage/execute/finalize wall-clock itself
        and only takes queue/fetch from the op timings (observing both would
        double-count). ``trace_id`` (the job id) rides along as an
        OpenMetrics exemplar, linking the histogram bucket to the trace
        that produced the sample (ISSUE 5)."""
        exemplar = (
            {"trace_id": trace_id}
            if trace_id and obs_trace.enabled() else None
        )
        for key, phase in PHASE_KEYS:
            if keys is not None and key not in keys:
                continue
            v = (timings or {}).get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                self.m_phase.observe(
                    float(v) / 1000.0, exemplar=exemplar, op=op, phase=phase
                )

    # ---- distributed tracing (ISSUE 5) ----

    @staticmethod
    def task_trace(task: Any) -> Tuple[Optional[str], Optional[str]]:
        """``(trace_id, parent_span_id)`` from the controller-stamped task
        trace context; ``(None, None)`` for legacy tasks or a tracing-off
        controller (agent spans are then skipped entirely)."""
        if isinstance(task, dict) and isinstance(task.get("trace"), dict):
            t = task["trace"]
            tid, sid = t.get("trace_id"), t.get("span_id")
            if isinstance(tid, str) and tid:
                return tid, sid if isinstance(sid, str) and sid else None
        return None, None

    def _process_name(self) -> str:
        return f"agent:{self.config.agent.agent_name}"

    def task_context(
        self, op: str, job_id: Optional[str] = None,
        trace_id: Optional[str] = None, span_parent: Optional[str] = None,
        lease_id: Optional[str] = None, attempt: Any = None,
    ) -> TraceContext:
        """The context every phase of one task is measured under
        (``obs.trace.phase``): this agent's registry, span ring and flight
        recorder, the op, and the controller's lease span as the parent.
        Without a job it is the agent's own context (a lease poll, a
        release): metrics and annotations, no span."""
        job = {} if job_id is None else {
            "job_id": job_id, "lease_id": lease_id, "attempt": attempt}
        return TraceContext(
            trace_id=trace_id or "", parent_span_id=span_parent,
            tracer=self.tracer, registry=self.obs,
            process=self._process_name(), op=op,
            recorder=self.recorder if job else None, job=job,
        )

    def trace_span(
        self,
        name: str,
        trace_id: Optional[str],
        parent_span_id: Optional[str],
        start_mono: float,
        duration_s: float,
        span_id: Optional[str] = None,
        **attributes: Any,
    ) -> None:
        """Buffer one closed agent-side span; no-op without a trace id or
        with tracing disabled (the SpanBuffer short-circuits too)."""
        if not trace_id or not obs_trace.enabled():
            return
        self.tracer.add(make_span(
            name, trace_id, parent_span_id,
            start_mono=start_mono, duration_s=duration_s, span_id=span_id,
            process=self._process_name(),
            attributes={k: v for k, v in attributes.items() if v is not None},
        ))

    def _drain_spans(self) -> List[Dict[str, Any]]:
        """Pending spans for a piggyback ship ([] when tracing is off —
        nothing accumulates then either)."""
        return self.tracer.drain()

    def note_progress(self, queues: Optional[Dict[str, int]] = None) -> None:
        """Periodic progress summary (tasks/sec over the window, queue
        depths), rate-limited on the shared ``RateLimiter`` — the drain-scale
        replacement for one log line per task."""
        if not self.rate.ready("progress"):
            return
        now = time.monotonic()
        dt = now - self._progress["t"]
        dn = self.tasks_done - self._progress["n"]
        self._progress = {"t": now, "n": self.tasks_done}
        fields: Dict[str, Any] = {"tasks_done": self.tasks_done}
        if dt > 0:
            fields["tasks_per_sec"] = round(dn / dt, 3)
        if queues:
            fields.update(queues)
        log("progress", **fields)

    def lease_once(self) -> Optional[Tuple[str, List[Dict[str, Any]]]]:
        """One ``/v1/leases`` round-trip → ``(lease_id, tasks)`` or None when
        idle. Raises RuntimeError on transport/protocol errors so the caller
        applies backoff (reference ``app.py:161-195``). Timed whole, as the
        polling thread pays it: ``agent_lease_seconds{outcome}`` and an
        ``agent.lease`` annotation on that thread's profiler line."""
        outcome = "error"
        polled = obs_trace.phase("lease", histogram=False, span=False)
        try:
            with polled:
                leased = self._lease_once()
            outcome = "idle" if leased is None else "tasks"
            return leased
        finally:
            self.m_lease.inc(outcome=outcome)
            self.m_lease_seconds.observe(polled.seconds, outcome=outcome)

    def _lease_once(self) -> Optional[Tuple[str, List[Dict[str, Any]]]]:
        a = self.config.agent
        metrics = self._metrics()
        spans = self._drain_spans()
        if spans:
            # Spans piggyback on the lease metrics channel (keyed by agent
            # like the obs snapshot); undelivered batches requeue below.
            metrics["spans"] = spans
        captures = self._drain_capture_results()
        if captures:
            # Deep-capture completions ride the same channel (ISSUE 9).
            metrics["profile_captures"] = captures
        # Staging-pool grant ask: never below the configured MAX_TASKS, and
        # absent a pool hint exactly MAX_TASKS (the legacy wire).
        hint = self.lease_batch_hint
        max_tasks = (
            a.max_tasks if hint is None else max(a.max_tasks, int(hint))
        )
        status, body = self._post_json(
            "/v1/leases",
            {
                "agent": a.agent_name,
                "capabilities": self.capabilities(),
                "max_tasks": max_tasks,
                "timeout_ms": a.lease_timeout_ms,
                "labels": a.labels,
                "worker_profile": self.worker_profile(),
                "metrics": metrics,
            },
        )
        if status not in (200, 204):
            if spans:
                self.tracer.requeue(spans)
            self._requeue_capture_results(captures)
        if status == STATUS_TRANSPORT_ERROR:
            raise RuntimeError(f"lease transport error: {body}")
        if status == 204:
            return None
        if status != 200 or not isinstance(body, dict):
            raise RuntimeError(f"lease HTTP {status}: {str(body)[:200]}")
        tasks = body.get("tasks")
        lease_id = body.get("lease_id")
        if not tasks:
            return None
        if not isinstance(lease_id, str) or not isinstance(tasks, list):
            raise RuntimeError(f"malformed lease response: {str(body)[:200]}")
        # Binary-wire negotiation (ISSUE 6): the controller stamps every
        # granted lease it negotiated, so re-deriving here self-corrects if
        # the controller changed its mind (e.g. restarted without binary).
        fmt = body.get("wire")
        self.wire_format = fmt if fmt in wire.FORMATS else None
        # SLO page alerts ride granted leases (absent in steady state);
        # entering page auto-dumps this agent's flight recorder.
        self.note_alerts(body.get("alerts"))
        self.recorder.record(
            "lease", lease_id=lease_id, n_tasks=len(tasks),
            job_ids=[
                t.get("id") for t in tasks if isinstance(t, dict)
            ],
        )
        return lease_id, tasks

    def post_result(
        self,
        lease_id: str,
        job_id: str,
        job_epoch: Any,
        status: str,
        result: Any = None,
        error: Any = None,
        session: Any = None,
        op: str = "?",
    ) -> bool:
        """Post one result; on transient failure the completed result is
        SPOOLED for redelivery (never silently dropped — the reference's
        behavior this replaces, ref ``app.py:307-312``). Permanent failures
        (the controller rejected the request itself) are counted and dropped:
        resending identical bytes cannot succeed."""
        wire: Dict[str, Any] = {
            "lease_id": lease_id,
            "job_id": job_id,
            "job_epoch": job_epoch,
            "status": status,
            "result": result,
            "error": error,
        }
        spans = self._drain_spans()
        if spans:
            # Spans ride the result post (ISSUE 5) — the same piggyback the
            # metrics snapshot uses on leases. NOT stored in the spool: a
            # failed batch requeues and ships on the next post or lease.
            wire["spans"] = spans
        # The /v1/results round trip alone (the enclosing ``post`` span also
        # holds finalize): in the task's context when the caller set one.
        with obs_trace.phase(
            "post_http", obs_trace.current() or self.task_context(op),
        ) as ph:
            http_status, body = self._post_json(
                "/v1/results", wire, session=session,
            )
            ph.attributes["http_status"] = http_status
        if http_status in (200, 204):
            return True
        if spans:
            self.tracer.requeue(spans)
        self.m_post_fail.inc(op=op)
        failure_class = classify_http(http_status)
        self.recorder.record(
            "result_post_failed", job_id=job_id, op=op, lease_id=lease_id,
            status=http_status, **{"class": failure_class},
        )
        self.rate.log(
            "result", "post failed", status=http_status,
            failure_class=failure_class, body=str(body)[:200],
        )
        if failure_class == PERMANENT:
            return False
        evicted = self.spool.put(
            lease_id, job_id, job_epoch, status,
            result=result, error=error, op=op,
        )
        if evicted is not None:
            # Ring overflow: the OLDEST spooled result is gone for good —
            # make the loss visible (pre-spool it was every failed post).
            self.m_redeliveries.inc(outcome="dropped_overflow")
            self.recorder.record(
                "spool_overflow", job_id=evicted.get("job_id"),
                op=evicted.get("op"),
            )
        self.m_spool_depth.set(len(self.spool))
        return False

    def release_job(
        self, lease_id: str, job_id: str, job_epoch: Any, op: str = "?",
        session: Any = None,
    ) -> bool:
        """Hand one unstarted leased task back to the controller (the drain
        protocol, ISSUE 10): a ``status="released"`` result makes the job
        instantly leasable again at a bumped epoch without burning the
        attempt — scale-down never strands a lease waiting out the TTL.
        A failed post spools and redelivers like any result; if the TTL
        beats the redelivery the epoch fence discards it harmlessly."""
        self.m_tasks.inc(op=op, status="released")
        self.recorder.record(
            "task_released", job_id=job_id, op=op, lease_id=lease_id,
        )
        return self.post_result(
            lease_id, job_id, job_epoch, "released", op=op, session=session,
        )

    def release_task(
        self, lease_id: str, task: Any, session: Any = None
    ) -> bool:
        """:meth:`release_job` from a raw task dict (no payload decode —
        a release needs only the identity triple)."""
        if not isinstance(task, dict):
            return False
        job_id = task.get("id", task.get("job_id"))
        if not isinstance(job_id, str) or not job_id:
            return False
        op = task.get("op") if isinstance(task.get("op"), str) else "?"
        return self.release_job(
            lease_id, job_id, task.get("job_epoch"), op=op, session=session,
        )

    def flush_spool(self, session: Any = None, force: bool = False) -> int:
        """Redeliver spooled results, oldest first, honoring the backoff
        window between attempts (``force`` ignores it — drain shutdown).
        Stops at the first transient failure (the controller is still down);
        drops entries the controller rejects permanently or that outlived
        ``retry_deadline_sec``. Epoch fencing makes redelivery of an
        already-applied result a counted no-op, so this can never
        double-apply. Returns the number delivered."""
        if not len(self.spool):
            return 0
        now = time.monotonic()
        if not force and now < self._spool_next_try:
            return 0
        deadline = self.config.agent.retry_deadline_sec
        delivered = 0
        while len(self.spool):
            if deadline > 0 and self.spool.age_of_head() >= deadline:
                entry = self.spool.pop_head()
                self.m_redeliveries.inc(outcome="expired")
                self.recorder.record(
                    "spool_expired", job_id=(entry or {}).get("job_id"),
                    op=(entry or {}).get("op"),
                )
                continue
            entry = self.spool.head()
            t_try = time.perf_counter()
            status, _body = self._post_json(
                "/v1/results", ResultSpool.wire_body(entry), session=session
            )
            if status in (200, 204):
                self.spool.pop_head()
                delivered += 1
                self.m_redeliveries.inc(outcome="delivered")
                self.recorder.record(
                    "result_redelivered", job_id=entry.get("job_id"),
                    op=entry.get("op"),
                )
                self._trace_redelivery(entry, t_try, "delivered")
                self._spool_retry.reset()
                self._spool_next_try = 0.0
            elif classify_http(status) == PERMANENT:
                self.spool.pop_head()
                self.m_redeliveries.inc(outcome="dropped_permanent")
                self.recorder.record(
                    "spool_dropped_permanent", job_id=entry.get("job_id"),
                    op=entry.get("op"), status=status,
                )
                self._trace_redelivery(entry, t_try, "dropped_permanent")
            else:
                # Still unreachable: back off before the next redelivery
                # attempt so a down controller isn't hammered by the loop.
                self._spool_next_try = (
                    time.monotonic() + self._spool_retry.next_backoff()
                )
                break
        self.m_spool_depth.set(len(self.spool))
        return delivered

    def _trace_redelivery(
        self, entry: Dict[str, Any], t_start: float, outcome: str
    ) -> None:
        """Span for one spool redelivery attempt (ISSUE 5): parents to the
        job's lease span when the spooled result body carried the trace
        context, so a controller blip's recovery shows on the timeline."""
        job_id = entry.get("job_id")
        if not isinstance(job_id, str) or not job_id:
            return
        parent = None
        res = entry.get("result")
        if isinstance(res, dict) and isinstance(res.get("trace"), dict):
            sid = res["trace"].get("span_id")
            parent = sid if isinstance(sid, str) and sid else None
        self.trace_span(
            "result.redeliver", job_id, parent,
            start_mono=t_start,
            duration_s=time.perf_counter() - t_start,
            op=entry.get("op"), outcome=outcome,
        )

    # ---- task execution ----

    @staticmethod
    def extract_task(task: Any) -> Tuple[str, str, Dict[str, Any], Any]:
        """Task dict → ``(job_id, op, payload, job_epoch)``; accepts ``id`` or
        ``job_id``, strict types (reference ``app.py:221-234``)."""
        if not isinstance(task, dict):
            raise ValueError(f"task must be a dict, got {type(task).__name__}")
        job_id = task.get("id", task.get("job_id"))
        op = task.get("op")
        payload = task.get("payload", {})
        epoch = task.get("job_epoch")
        if not isinstance(job_id, str) or not job_id:
            raise ValueError("task missing string id/job_id")
        if not isinstance(op, str) or not op:
            raise ValueError("task missing string op")
        if payload is None:
            payload = {}
        if not isinstance(payload, dict):
            raise ValueError("task payload must be a dict")
        return job_id, op, payload, epoch

    def _op_context(self, job_id: str, lease_id: Optional[str] = None,
                    attempt: Any = None, parent_span_id: Any = None,
                    tenant: Any = None):
        from agent_tpu.runtime.context import OpContext

        # The trace triple stamped at lease time (ISSUE 2 tentpole 5): it
        # rides ctx.tags into op timings/logs and is copied into the result
        # body, so one job's life greps across controller journal, agent
        # logs, and both flight recorders. `span_id` (ISSUE 5) is the
        # controller's lease span — the parent of the agent-side spans.
        # `tenant` (ISSUE 9) rides only when the controller stamped one on
        # the task, so multi-tenant attribution greps agent-side too.
        trace = {"job_id": job_id, "attempt": attempt, "lease_id": lease_id}
        if isinstance(tenant, str) and tenant:
            trace["tenant"] = tenant
        if parent_span_id:
            trace["span_id"] = parent_span_id
        tags: Dict[str, Any] = {"job_id": job_id, "trace": trace}
        if self.wire_format:
            # Negotiated wire format (ISSUE 6): finalize reads this to emit
            # binary result columns instead of tolist()-ed JSON.
            tags["wire"] = self.wire_format
        return OpContext(
            runtime=self.runtime, config=self.config, tags=tags,
        )

    def _drain_capture_results(self) -> List[Dict[str, Any]]:
        """Completed deep-capture records awaiting their piggyback ship."""
        out, self._capture_done = self._capture_done, []
        return out

    def _requeue_capture_results(
        self, batch: List[Dict[str, Any]]
    ) -> None:
        """Undelivered completion batch goes back to the head — a capture
        completion must survive a lost lease round like spans do."""
        if batch:
            self._capture_done = batch + self._capture_done

    def _take_capture(self, op: str) -> Optional[Dict[str, Any]]:
        """Pop the first pending capture matching ``op`` (a request without
        an op matches the next task of any op)."""
        for i, cap in enumerate(self._pending_captures):
            want = cap.get("op")
            if not want or want == op:
                return self._pending_captures.pop(i)
        return None

    def _captured_call(
        self, op: str, thunk: Any, cap: Dict[str, Any]
    ) -> Any:
        """One on-demand deep capture (ISSUE 9): wrap this op execution in
        ``jax.profiler.trace`` writing into a per-capture artifact dir, and
        queue the completion record (artifact path + summary) for the next
        lease's metrics channel. A profiler that cannot start degrades to a
        plain call with an ``error`` completion — diagnostics must never
        fail the task they observe."""
        import tempfile

        record: Dict[str, Any] = {
            "capture_id": cap.get("capture_id"),
            "agent": self.config.agent.agent_name,
            "op": op,
            "status": "done",
        }
        try:
            base = os.environ.get("PROFILE_CAPTURE_DIR", "").strip()
            if base:
                artifact = os.path.join(
                    base, f"capture-{cap.get('capture_id')}"
                )
                os.makedirs(artifact, exist_ok=True)
            else:
                artifact = tempfile.mkdtemp(
                    prefix=f"agent_tpu_capture_{cap.get('capture_id')}_"
                )
            import jax

            prof = jax.profiler.trace(artifact)
            prof.__enter__()
        except Exception as exc:  # noqa: BLE001 — profiler failed to start:
            # plain call, error completion; diagnostics never fail the task.
            record.update(status="error", error=str(exc)[:300])
            self._capture_done.append(record)
            return thunk()
        t0 = time.perf_counter()
        try:
            # annotated by the caller's obs.trace.phase
            return self._device_done(thunk())
        except Exception:
            record["status"] = "op_failed"  # trace still captured; op raised
            raise
        finally:
            try:
                prof.__exit__(None, None, None)
            except Exception:  # noqa: BLE001 — a torn trace close is not
                pass            # worth failing the op over
            dt_ms = round((time.perf_counter() - t0) * 1e3, 3)
            parts_file = self._write_program_parts(artifact)
            n_files = sum(
                len(files) for _, _, files in os.walk(artifact)
            )
            record.update(
                artifact=artifact,
                actual_duration_ms=dt_ms,
                summary={"op": op, "n_trace_files": n_files,
                         "duration_ms": dt_ms, "parts_file": parts_file},
            )
            self._capture_done.append(record)
            self.recorder.record(
                "profile_capture", capture_id=record["capture_id"],
                op=op, artifact=artifact, status=record["status"],
            )
            log("deep capture complete", op=op, artifact=artifact,
                capture_id=record["capture_id"])

    @staticmethod
    def _device_done(result: Any) -> Any:
        """``result`` once the device work behind it is over: a capture that
        closed when the op's DISPATCH returned held none of it. Blocks on
        every device array in what the thunk returned, which for an op that
        declares ``deferred = True`` is the state its fetch will read."""
        import jax

        return jax.block_until_ready(result)

    def _write_program_parts(self, directory: str) -> Optional[str]:
        """``program_parts.json`` (``TpuRuntime.program_parts``: which part
        of a model every instruction of every program that has run belongs
        to) beside the newest ``.xplane.pb`` under ``directory``; what
        ``scripts/capture_parts.py`` lays over that trace. ``None`` where
        there is no runtime or no trace; diagnostics never fail the task."""
        try:
            traces = [os.path.join(root, name)
                      for root, _, names in os.walk(directory)
                      for name in names if name.endswith(".xplane.pb")]
            if self.runtime is None or not traces:
                return None
            path = os.path.join(
                os.path.dirname(max(traces, key=os.path.getmtime)),
                "program_parts.json")
            with open(path, "w", encoding="utf-8") as f:
                json.dump(self.runtime.program_parts(), f)
            return path
        except Exception as exc:  # noqa: BLE001 — see the docstring
            log("program parts not written", error=str(exc)[:300])
            return None

    def profiled_call(self, op: str, thunk: Any) -> Any:
        """Run ``thunk`` capturing an XProf trace for the first
        ``profile_tasks`` tasks when PROFILE_DIR is set (SURVEY.md §5.1 —
        result-embedded wall-clock timings flow regardless; traces are the
        deep-dive channel), or under an on-demand deep capture when one is
        pending for this op (ISSUE 9). Shared by the serial loop and the
        pipelined device loop so both cover phased ops too."""
        if self._pending_captures:
            cap = self._take_capture(op)
            if cap is not None:
                return self._captured_call(op, thunk, cap)
        dev = self.config.device
        if dev.profile_dir and self.tasks_done < dev.profile_tasks:
            import jax

            try:
                with jax.profiler.trace(dev.profile_dir):
                    return self._device_done(thunk())
            finally:
                self._write_program_parts(dev.profile_dir)
        return thunk()

    def _maybe_profiled(self, op: str, fn: OpFn, payload: Dict[str, Any],
                        ctx: Any) -> Any:
        return self.profiled_call(op, lambda: fn(payload, ctx))

    def resolve_task(
        self, task: Any
    ) -> Tuple[Optional[str], str, Dict[str, Any], Any, Optional[OpFn],
               Optional[Dict[str, Any]]]:
        """Task dict → ``(job_id, op, payload, epoch, handler, error)``.

        The single definition of malformed-task salvage and the UnknownOp
        error shape, shared by the serial loop and the pipeline so the two
        paths can never drift in what they report. ``handler`` is None iff
        ``error`` is set; a malformed task with no salvageable id returns
        ``job_id=None`` (nothing to report against — drop it).
        """
        try:
            job_id, op, payload, epoch = self.extract_task(task)
            if wire.is_binary_payload(payload):
                # Binary shard wire (ISSUE 6): the controller encoded the
                # bulk columns; ops see the decoded plain payload. A
                # malformed envelope raises ValueError and reports exactly
                # like any other malformed task.
                payload = wire.decode_task_payload(payload)
        except ValueError as exc:
            self.rate.log("task:bad", "malformed task", error=str(exc))
            jid = task.get("id") if isinstance(task, dict) else None
            jid = jid if isinstance(jid, str) and jid else None
            return jid, "?", {}, None, None, structured_error(exc)
        fn = self.handlers.get(op)
        if fn is None:
            return job_id, op, payload, epoch, None, {
                "type": "UnknownOp",
                "message": f"op {op!r} not in capabilities {sorted(self.handlers)}",
                "trace": "",
            }
        return job_id, op, payload, epoch, fn, None

    def run_task(self, lease_id: str, task: Any) -> None:
        """Execute one leased task inline and report its result.

        Any raised exception becomes a ``failed`` result with the structured
        ``{type, message, trace}`` error (reference ``app.py:288-294``); a
        single-host agent never dies on an op error. Multi-host slices fail
        in lockstep instead: leader and followers all re-raise (see
        ``run_follower``), because continuing past a diverged collective
        program would wedge the slice silently.

        The loop's three phases (``stage``: resolution + broadcast,
        ``execute``: the monolithic op call, ``post``) are spans and
        annotations only: this loop's ``task_phase_seconds`` come from the
        op's own timings (``record_phase_timings``).
        """
        t0 = time.perf_counter()
        attempt = task.get("attempt") if isinstance(task, dict) else None
        status, error, result = "succeeded", None, None
        job_id = None
        try:
            with obs_trace.phase("stage", histogram=False) as staged:
                job_id, op, payload, epoch, fn, resolve_error = \
                    self.resolve_task(task)
                if resolve_error is not None:
                    if job_id is not None:
                        self.m_tasks.inc(op=op, status="failed")
                        self.recorder.record(
                            "task", job_id=job_id, op=op, status="failed",
                            lease_id=lease_id, attempt=attempt,
                            error_type=resolve_error.get("type"),
                        )
                        self.post_result(
                            lease_id, job_id, epoch, "failed",
                            error=resolve_error, op=op,
                        )
                    return
                trace_id, span_parent = self.task_trace(task)
                ctx = self._op_context(
                    job_id, lease_id=lease_id, attempt=attempt,
                    parent_span_id=span_parent,
                    tenant=task.get("tenant")
                    if isinstance(task, dict) else None)
                staged.ctx = tctx = self.task_context(
                    op, job_id, trace_id, span_parent,
                    lease_id=lease_id, attempt=attempt)
                # Multi-host: every host must enter the same SPMD program
                # in lockstep — the leader publishes the task before
                # executing it (no-op on a single host). SURVEY.md §7.
                self._broadcast_to_followers(op, payload)
            stamp_usage(ctx.tags, host_s=staged.seconds)
            # What the op compiles inside parents to this span.
            with obs_trace.phase("execute", tctx, histogram=False,
                                 annotation="agent.dispatch",
                                 status=status) as executed:
                try:
                    result = self._maybe_profiled(op, fn, payload, ctx)
                finally:
                    # The monolithic call blocks until the result is on the
                    # host: its own start and end are dispatch and ready.
                    self.note_device_interval(
                        op, executed.t0, time.perf_counter(), ctx.tags)
        except Exception as exc:  # noqa: BLE001 — every op error → failed result
            if job_id is None:
                raise   # not an op error: the task was never resolved
            result = None
            status = "failed"
            error = structured_error(exc)
            self.rate.log("exec", "op raised", op=op, type=type(exc).__name__)
            if self.dist.process_count > 1:
                # Multi-host, ops are collective programs: followers that hit
                # the same exception crash (run_follower); a leader that
                # caught it and moved on would re-enter the broadcast
                # collective against dead or desynced peers — a silent slice
                # hang. Post the structured failure (so the controller can
                # stick the job failed after its one retry), then die in
                # lockstep with the followers; the slice restarts clean.
                self.post_result(
                    lease_id, job_id, epoch, status, result=None, error=error,
                    op=op,
                )
                raise
        duration_ms = (time.perf_counter() - t0) * 1000.0
        if isinstance(result, dict):
            result.setdefault("duration_ms", duration_ms)
            if ctx.tags.get("timings"):
                result.setdefault("timings", ctx.tags["timings"])
            result.setdefault("trace", ctx.tags.get("trace"))
            if ctx.tags.get("usage"):
                # Usage block (ISSUE 9): device/host seconds, chips, FLOPs,
                # rows — what the controller's showback ledger bills.
                result.setdefault("usage", ctx.tags["usage"])
        # Closed after the post (a span cannot include its own ship); it
        # rides the NEXT post or the final metrics-only flush.
        with obs_trace.phase("post", tctx, histogram=False, status=status):
            self.post_result(
                lease_id, job_id, epoch, status, result=result, error=error,
                op=op,
            )
        self.tasks_done += 1
        self.m_tasks.inc(op=op, status=status)
        # Serial phases come from the op's own timings (the monolithic call
        # gives this loop no phase boundaries of its own to measure).
        self.record_phase_timings(op, ctx.tags.get("timings"),
                                  trace_id=job_id)
        self.recorder.record(
            "task", job_id=job_id, op=op, status=status, lease_id=lease_id,
            attempt=attempt, duration_ms=round(duration_ms, 3),
            error_type=(error or {}).get("type") if error else None,
        )
        self.note_progress()

    # ---- main loop ----

    def step(self) -> bool:
        """One loop iteration. Returns True if a task was executed (so callers
        and tests can drive the loop deterministically)."""
        # Redelivery rides the loop cadence: each iteration gives spooled
        # results one (backoff-gated) chance before new work leases.
        self.flush_spool()
        try:
            leased = self.lease_once()
        except RuntimeError as exc:
            self.rate.log("lease", str(exc))
            # Decorrelated jittered backoff (base = error_backoff_sec): a
            # fleet that lost its controller must not retry in lockstep.
            time.sleep(self._lease_retry.next_backoff())
            return False
        self._lease_retry.reset()
        if leased is None:
            # ±25% jitter: a fleet restarted together must not long-poll in
            # lockstep forever (ISSUE 3 satellite).
            time.sleep(jittered(self.config.agent.idle_sleep_sec))
            return False
        lease_id, tasks = leased
        for task in tasks:
            if self.running:
                self.run_task(lease_id, task)
            elif self.draining:
                # Drain (ISSUE 10): the in-flight task above finished and
                # posted; the unstarted remainder of the lease is handed
                # back instead of abandoned to the TTL.
                self.release_task(lease_id, task)
            # else: hard stop — abandoned, the lease TTL re-queues.
        return True

    # ---- multi-host (leader/follower, SURVEY.md §5.8) ----

    def _dist_info(self):
        """Process topology; import-light so pure-host agents never touch jax
        unless multi-host env vars are actually set."""
        cfg = self.config.device
        if cfg.coordinator_address is None:
            from agent_tpu.runtime.distributed import DistInfo

            return DistInfo(process_index=0, process_count=1)
        from agent_tpu.runtime.distributed import maybe_initialize

        return maybe_initialize(
            cfg.coordinator_address, cfg.num_processes, cfg.process_id
        )

    def _broadcast_to_followers(self, op: str, payload: Dict[str, Any]) -> None:
        if self.dist.process_count == 1:
            return
        from agent_tpu.runtime.distributed import broadcast_task

        broadcast_task({"op": op, "payload": payload})

    def run_follower(self) -> None:
        """Non-leader hosts: execute every task the leader broadcasts, in
        lockstep, discarding results (the leader posts them). Blocks in the
        broadcast collective between tasks; exits on the shutdown sentinel.

        Drain-mode ops (``source_uri`` payloads) require the dataset path
        readable on **every** host of the slice — a follower that fails to
        read it host-locally never enters the SPMD program the leader is
        already inside, which would wedge the whole slice in that collective.
        """
        from agent_tpu.runtime.distributed import broadcast_task, is_shutdown

        log("follower up", process=self.dist.process_index)
        while self.running:
            task = broadcast_task(None)
            if task is None or is_shutdown(task):
                break
            fn = self.handlers.get(task.get("op"))
            if fn is None:
                # The leader only broadcasts ops it resolved — so it is
                # already inside the SPMD program waiting for our devices.
                # Skipping would wedge the whole slice in that collective;
                # failing fast turns a silent hang into a visible crash.
                raise RuntimeError(
                    f"follower has no handler for broadcast op "
                    f"{task.get('op')!r}: TASKS must be identical on every "
                    f"host of a slice (have {sorted(self.handlers)})"
                )
            try:
                fn(task.get("payload") or {}, self._op_context("follower"))
            except Exception as exc:  # noqa: BLE001 — re-raised below
                # Same reasoning as the missing-handler branch: a follower
                # that raised host-locally (e.g. a drain CSV readable only on
                # host 0) never reached the SPMD program, and the leader is
                # already blocked in it spanning our devices. Log-and-continue
                # would loop us back into the *broadcast* collective — two
                # processes in different collectives, a silent slice-wide
                # hang. Crash instead: the coordination service's heartbeat
                # then tears the slice down visibly and the controller
                # re-leases the task.
                log(
                    "follower op raised — crashing to avoid a slice hang",
                    op=task.get("op"),
                    type=type(exc).__name__,
                    error=str(exc)[:200],
                )
                raise
            self.tasks_done += 1
        log("follower drained", tasks_done=self.tasks_done)

    def run(self, max_steps: Optional[int] = None) -> None:
        info = self.dist
        if info.process_count > 1 and not info.is_leader:
            self.run_follower()
            return
        if (
            max_steps is None
            and info.process_count == 1
            and self.config.agent.pipeline_depth > 0
        ):
            # Host-side double buffering: stage/post on worker threads,
            # device dispatch stays here on the owning thread. Multi-host
            # keeps the serial lockstep loop (broadcast must serialize);
            # max_steps callers (tests) drive the deterministic serial loop.
            from agent_tpu.agent.pipeline import PipelineRunner

            PipelineRunner(self, depth=self.config.agent.pipeline_depth).run()
            return
        steps = 0
        while self.running:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        # Last chance for spooled results before exit: force past the
        # backoff window — anything still undeliverable stays in the on-disk
        # spool (if configured) for the next incarnation.
        self.flush_spool(force=True)
        # Final telemetry flush: the last task's counters postdate the last
        # real lease poll, so without this the fleet view would always lag
        # one snapshot behind a finished drain.
        self.push_metrics()
        # Clean exit only: after an op exception the followers are desynced
        # or dead, and the shutdown broadcast is itself a collective —
        # entering it would recreate the silent slice hang the lockstep
        # crash exists to avoid. On the error path the exception propagates,
        # the leader dies, and the coordination heartbeat tears down the rest.
        if info.process_count > 1:
            from agent_tpu.runtime.distributed import broadcast_shutdown

            broadcast_shutdown()

    def request_drain(self, reason: str = "drain") -> None:
        """Begin graceful retirement (ISSUE 10) — the ONE drain path shared
        by the SIGTERM handler, autoscaler scale-down, and spot reclaims:
        stop leasing, finish the in-flight task, release the unstarted
        remainder of the lease, flush spool + final metrics (tagged
        ``draining``), exit clean."""
        if not self.draining:
            self.draining = True
            log("drain requested", reason=reason)
        self.running = False

    def shutdown(self, *_args: Any) -> None:
        """Signal handler (SIGINT/SIGTERM): the drain path — a SIGTERM from
        ``Fleet.stop`` or a spot reclaim retires exactly like an autoscaler
        scale-down (reference ``app.py:239-249`` only stopped the loop)."""
        self.request_drain(reason="signal")


def main(argv: Optional[List[str]] = None) -> int:
    config = Config.from_env()
    if not config.agent.tasks:
        print("[agent-tpu] no TASKS configured; refusing to start", flush=True)
        return 2
    try:
        agent = Agent(config)
    except KeyError as exc:
        # load_ops raised on an unknown/disabled op name — same startup-fail
        # semantics as an empty TASKS list.
        print(f"[agent-tpu] bad TASKS: {exc}", flush=True)
        return 2
    signal.signal(signal.SIGINT, agent.shutdown)
    signal.signal(signal.SIGTERM, agent.shutdown)
    # Flight recorder taps: SIGUSR1 dumps the ring on demand; a fatal error
    # dumps it before the process dies — a wedged drain is diagnosable after
    # the fact without re-running it under extra logging.
    from agent_tpu.obs.recorder import default_dump_path, install_sigusr1_dump

    dump_path = default_dump_path(f"agent-{config.agent.agent_name}")
    if install_sigusr1_dump(agent.recorder, dump_path):
        log("flight recorder armed", signal="SIGUSR1", path=dump_path)
    log(
        "agent up",
        agent=config.agent.agent_name,
        controller=config.agent.controller_url,
        ops=sorted(agent.handlers),
    )
    try:
        agent.run()
    except BaseException:
        try:
            n = agent.recorder.dump(dump_path)
            log("fatal error — flight recorder dumped",
                path=dump_path, events=n)
        except OSError:
            pass
        raise
    log("agent drained", tasks_done=agent.tasks_done)
    return 0


if __name__ == "__main__":
    sys.exit(main())
