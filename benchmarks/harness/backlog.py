"""The measurement the backlog kinds (``drain``, ``score``) share: CSV jobs
over ``POST /v1/jobs`` whose backlog outlasts the window, drained by the
in-process agent while the controller runs as a child process. A kind brings
its rows, its CSV writer, the keys of a shard's answer and its check; backlog
size, submission, lead-in, window edges, ``setup_s`` and the ``window`` record
are decided here, once.

The end-to-end metric is ``drain_rows_per_s``: rows of the shards whose
result the controller accepted inside the window, over the window's length.
The benchmark clocks each acceptance itself, on the poster thread's own HTTP
session (``Agent.post_session_factory``, the program's hook for it), when
the controller's 200 comes back. A shard is up to 0.8 s of work, so a window
cut at arbitrary instants would count ±1 shard (up to ±8 %); the window
therefore opens AT an acceptance and closes at the first acceptance at or
after ``--seconds`` later, and the rate is all the rows over all that time.

The opening acceptance is a REGULAR one: the first at or after
``lead_in_shards`` whose gap to the one before is within ``REGULAR`` x the
median gap of the lead-in, either way (a held-up post reads long, the burst
that follows it short; opened on either, the window holds shards the device
did before it). A stall inside the window or at its close is the system's
time and stays in the number.

``setup_s`` is process start to the window's opening LESS two things that
are clocked and printed in the ``setup`` line beside it. The seconds the
benchmark spent on its own inputs while the system waited for them: drawing
the rows, writing the CSV files, posting the backlog's jobs. And
``backend_s``, the accelerator runtime's own start (``jax.devices()``): 6 to
14 s from one run to the next on one machine, nearly all of the spread
``setup_s`` had, and no module of the program is in it (PERF.md section 2).
What the program decides stays in: imports, controller child, agent, the
tenants' weights, warm-up shards, cache loads, the lead-in."""

from __future__ import annotations

import gc
import math
import os
import statistics
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import procs, stack, stats
from benchmarks.harness.compile_count import CompileCounter
from benchmarks.harness.stack import check, emit

JOB_TIMEOUT_S = 400.0
REGULAR = 1.25


class PostClock:
    """Wraps the poster thread's session: notes the wall clock of every
    ``/v1/results`` answer. Reads, never changes, what is posted."""

    def __init__(self) -> None:
        self.cond = threading.Condition()
        self.posts: List[Tuple[float, str, str, int]] = []
        # How many answers were in when the backlog's last job was posted:
        # the lead-in counts from there.
        self.lead_in_from: Optional[int] = None

    def factory(self):
        import requests

        clock, inner = self, requests.Session()

        class Session:
            def post(self, url, *args, **kwargs):
                resp = inner.post(url, *args, **kwargs)
                if url.endswith("/v1/results"):
                    body = kwargs.get("json") or {}
                    with clock.cond:
                        clock.posts.append((
                            time.time(), str(body.get("job_id")),
                            str(body.get("status")), int(resp.status_code)))
                        clock.cond.notify_all()
                return resp

            def __getattr__(self, name):
                return getattr(inner, name)

        return Session()

    def wait_for(self, predicate, timeout: float, alive) -> None:
        deadline = time.monotonic() + timeout
        with self.cond:
            while not predicate():
                check(alive(), "the agent's runner thread died")
                check(time.monotonic() < deadline,
                      f"no progress in {timeout:.0f} s: {len(self.posts)} "
                      f"results posted")
                self.cond.wait(timeout=0.25)


def post_csv_job(url: str, csv_path: str, total_rows: int, shard_rows: int,
                 map_op: str, extra: Dict[str, Any], tenant: str) -> List[str]:
    status, body = procs.http_json(url + "/v1/jobs", {
        "source_uri": csv_path, "total_rows": total_rows,
        "shard_size": shard_rows, "map_op": map_op, "extra_payload": extra,
        "tenant": tenant,
    })
    check(status == 200, f"POST /v1/jobs → {status} {body}")
    return list(body["job_ids"])


def job_snapshot(url: str, job_id: str) -> Dict[str, Any]:
    status, snap = procs.http_json(f"{url}/v1/jobs/{job_id}")
    check(status == 200 and isinstance(snap, dict) and "state" in snap,
          f"GET /v1/jobs/{job_id} → {status} {str(snap)[:200]}")
    return snap


def model_payload(config: Dict[str, Any], seed: int, tenant: int
                  ) -> Tuple[str, Dict[str, Any]]:
    """(model id, the op's extra payload) of one tenant. Weights come from
    the id, on the device, by the program's own initializer."""
    model_id = f"bench-{config['name']}-{seed}-t{tenant}"
    extra = {**config["op"]["extra_payload"],
             "model_config": dict(config["model"]), "model_path": model_id}
    return model_id, extra


def plan(traffic: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """The backlog a traffic file asks for over a window of ``seconds``:
    whole datasets, every tenant as often, and the rate at which the window
    would use them up (``ceiling_rows_per_s``: the lead-in and the two
    acceptances at the window's edges come off; so do the shards accepted
    while the jobs are being posted, about a second's worth, which no
    traffic file knows)."""
    shard, tenants = int(traffic["shard_rows"]), int(traffic["tenants"])
    job_rows, lead_in = int(traffic["job_rows"]), int(traffic["lead_in_shards"])
    check(job_rows % shard == 0, "job_rows must be whole shards")
    n_jobs = math.ceil((float(traffic["backlog_rows_per_s"]) * seconds
                        + (lead_in + 2) * shard) / job_rows)
    n_jobs = tenants * math.ceil(n_jobs / tenants)
    shards = n_jobs * job_rows // shard
    return {
        "n_jobs": n_jobs, "shards": shards, "rows": n_jobs * job_rows,
        "warm_rows": tenants * shard,
        "ceiling_rows_per_s": (shards - lead_in - 2) * shard / seconds,
    }


def opening(stamps: Sequence[float], lead_in: int
            ) -> Optional[Tuple[int, float, bool]]:
    """``stamps``: the acceptances since the backlog was posted. Returns
    ``(index of the opening acceptance, the lead-in's median gap, whether
    that acceptance is regular)``, or ``None`` while more acceptances are
    needed. Number ``2 x lead_in`` opens the window regular or not: the
    backlog is sized for a lead-in, not for a search."""
    if len(stamps) <= lead_in:
        return None
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    median = statistics.median(gaps[:lead_in])
    for i in range(lead_in, min(len(stamps), 2 * lead_in + 1)):
        if median / REGULAR <= gaps[i - 1] <= median * REGULAR:
            return i, median, True
        if i == 2 * lead_in:
            return i, median, False
    return None


def run(ctx: Dict[str, Any], *, make_rows: Callable[[int], List[Any]],
        write_csv: Callable[[str, List[Any]], None],
        answer_keys: Sequence[str],
        snapshot: Callable[[str, str], Dict[str, Any]] = job_snapshot
        ) -> Dict[str, Any]:
    """Drive one cell up to the window's close and stop the system. Returns
    the ``run`` record the ``.drain`` and ``.setup`` readers read, with what
    the kind still needs under ``backlog`` (the rows), ``accepted`` (``(first
    row, model id, result body)`` per shard of the window), ``window_record``
    (for the kind to emit), ``agent`` and ``tracer``: ``finish`` takes those
    out again. ``answer_keys``: the lists of a result body that hold one
    entry a row."""
    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    seed, seconds = int(ctx["seed"]), float(ctx["seconds"])
    out = stack.output_dir(cell["name"], seed, int(ctx["trace"]))
    t_enter = time.time()
    import jax  # noqa: F401 — clocked apart from the runtime's start

    t_jax = time.time()
    device = stack.init_device(int(cell["chips"]))
    counter = CompileCounter().install()
    t_backend = time.time()

    shard, tenants = int(traffic["shard_rows"]), int(traffic["tenants"])
    job_rows, lead_in = int(traffic["job_rows"]), int(traffic["lead_in_shards"])
    size = plan(traffic, seconds)
    payloads = [model_payload(config, seed, k) for k in range(tenants)]
    map_op = config["op"]["map_op"]

    # ---- the benchmark's own inputs: drawn and written before the system
    # starts, clocked, and no part of setup_s ---------------------------
    rows = make_rows(size["rows"] + size["warm_rows"])
    warm, rows = rows[:size["warm_rows"]], rows[size["warm_rows"]:]
    csvs: List[str] = []

    def csv_of(name: str, part: List[Any]) -> str:
        csvs.append(os.path.join(out, name))
        write_csv(csvs[-1], part)
        return csvs[-1]

    def submit(path: str, n: int, tenant: int) -> List[str]:
        return post_csv_job(url, path, n, shard, map_op, payloads[tenant][1],
                            f"tenant-{tenant}")

    # A read-only tap on the interpreter's collector: a collection holds
    # every thread of the agent's process, and one of the oldest generation
    # lasts as long as two short shards. (generation, wall start, seconds)
    # of each that took 5 ms or more.
    pauses: List[Tuple[int, float, float]] = []
    began = 0.0

    def on_gc(phase: str, info: Dict[str, Any]) -> None:
        nonlocal began
        now = time.time()
        if phase == "start":
            began = now
        elif now - began >= 0.005:
            pauses.append((int(info["generation"]), began, now - began))

    controller: Optional[procs.ControllerProcess] = None
    agent: Optional[stack.AgentStack] = None
    gc.callbacks.append(on_gc)
    try:
        warm_csvs = [csv_of(f"warm-{k}.csv", warm[k * shard:(k + 1) * shard])
                     for k in range(tenants)]
        job_csvs = [csv_of(f"job-{j}.csv",
                           rows[j * job_rows:(j + 1) * job_rows])
                    for j in range(size["n_jobs"])]
        t_inputs = time.time()
        controller = procs.ControllerProcess(os.path.join(out, "controller.log"))
        url = controller.url
        clock = PostClock()
        agent = stack.AgentStack(controller.url, config["op"]["tasks"],
                                 traffic.get("agent"))
        agent.agent.post_session_factory = clock.factory
        t_stack = time.time()

        # ---- warm-up: one shard of every tenant's model, the cell's own
        # shapes, through the normal path -----------------------------------
        warm_ids = [jid for k, path in enumerate(warm_csvs)
                    for jid in submit(path, shard, k)]
        clock.wait_for(lambda: len(clock.posts) >= len(warm_ids),
                       JOB_TIMEOUT_S, agent.alive)
        for jid in warm_ids:
            snap = snapshot(url, jid)
            check(snap["state"] == "succeeded",
                  f"warm-up shard {jid} {snap['state']}: {snap.get('error')}")
        warm_totals = counter.totals()
        n_warm = len(clock.posts)
        t_warm = time.time()

        # ---- the backlog (the agent starts on it while it is posted: those
        # seconds are the benchmark's), then the lead-in ---------------------
        where: Dict[str, Tuple[int, str]] = {}   # shard job id -> first row, model
        for j, path in enumerate(job_csvs):
            k = j % tenants
            for i, jid in enumerate(submit(path, job_rows, k)):
                where[jid] = (j * job_rows + i * shard, payloads[k][0])
        with clock.cond:
            n0 = clock.lead_in_from = len(clock.posts)
        t_posted = time.time()

        def lead_in_over():
            return opening([p[0] for p in clock.posts[n0:]], lead_in)

        clock.wait_for(lambda: lead_in_over() is not None,
                       JOB_TIMEOUT_S, agent.alive)
        opened_after, lead_in_gap_s, open_regular = lead_in_over()
        open_at = n0 + opened_after
        t_open = clock.posts[open_at][0]
        phases = {
            "imports_s": t_enter - ctx["t_start"], "jax_import_s": t_jax - t_enter,
            "controller_agent_s": t_stack - t_inputs,
            "warm_up_s": t_warm - t_stack, "lead_in_s": t_open - t_posted,
        }
        left_out = {
            "backend_s": t_backend - t_jax, "own_inputs_s": t_inputs - t_backend,
            "own_submit_s": t_posted - t_warm,
        }
        setup_s = t_open - ctx["t_start"] - sum(left_out.values())
        emit("setup", setup_s=setup_s, wall_to_open_s=t_open - ctx["t_start"],
             **phases, **left_out)
        m0, cpu0 = agent.metrics(), controller.cpu_seconds()
        tracer = None
        if ctx["trace"]:
            tracer = stack.Tracer(os.path.join(out, "trace"))
            tracer.capture(t_open, traffic)
        clock.wait_for(
            lambda: (clock.posts[-1][0] >= t_open + seconds
                     or len(clock.posts) >= n_warm + size["shards"]),
            seconds + 120.0, agent.alive)
        with clock.cond:
            posts = list(clock.posts)
        close_at = next((i for i, p in enumerate(posts)
                         if i > open_at and p[0] >= t_open + seconds), None)
        check(close_at is not None,
              f"the backlog ran empty inside the window: all {size['shards']} "
              f"shards accepted {posts[-1][0] - t_open:.2f} s after it opened, "
              f"ceiling_rows_per_s {size['ceiling_rows_per_s']:.0f}: raise "
              f"backlog_rows_per_s")
        t_close = posts[close_at][0]
        m1, cpu1 = agent.metrics(), controller.cpu_seconds()
        compiles = counter.between(t_open, t_close)
        agent.close()
        post_spans = [(b - a) / 1e9 for name, a, b in
                      agent.host_spans(t_open, t_close) if name == "post"
                      and t_open <= b / 1e9 <= t_close]
        peak = stack.memory_peak_bytes()
        emit("memory", **stack.memory_stats())

        # ---- what the window answered -----------------------------------
        window = posts[open_at + 1:close_at + 1]
        accepted, failed = [], 0
        for _, jid, status, code in window:
            snap = snapshot(url, jid) if jid in where else None
            if (snap is None or status != "succeeded" or code != 200
                    or snap["state"] != "succeeded"):
                failed += 1
                continue
            body = snap["result"]
            good = (isinstance(body, dict) and body.get("ok") is True
                    and body.get("device") == stack.REQUIRED_PLATFORM
                    and "fallback" not in body
                    and all(len(body.get(key) or []) == shard
                            for key in answer_keys))
            if good:
                accepted.append((*where[jid], body))
            else:
                failed += 1
    finally:
        if agent is not None:
            agent.close()
        if controller is not None:
            controller.stop()
        remove(csvs)                          # tens of MB a run: not kept
        gc.callbacks.remove(on_gc)

    window_s = t_close - t_open
    n_rows = shard * len(accepted)
    check(len(accepted) > 0, f"no shard succeeded in the window: {window}")
    backlog_left = size["shards"] - (close_at + 1 - n_warm)
    check(backlog_left > 0,
          f"the backlog ran empty inside the window: backlog_left "
          f"{backlog_left} of {size['shards']} shards, ceiling_rows_per_s "
          f"{size['ceiling_rows_per_s']:.0f}: raise backlog_rows_per_s")
    stamps = [p[0] for p in posts[open_at:close_at + 1]]
    in_window = [d for _, t, d in pauses if t_open <= t <= t_close]
    return {
        "kind": "drain", "cell": cell, "config": config, "traffic": traffic,
        "device": device, "window_s": window_s, "t_open": t_open,
        "t_close": t_close, "shards": len(accepted), "rows": n_rows,
        "agent_metrics": (m0, m1), "controller_cpu_s": cpu1 - cpu0,
        "post_span_s": post_spans, "compiles_in_window": compiles,
        "op": map_op, "trace": None, "memory_peak_bytes": peak,
        "attempted": len(window), "failed": failed,
        "end_to_end": {
            "drain_rows_per_s": stats.rate(n_rows, window_s),
            "setup_s": setup_s,
        },
        # The system's phases of set-up: they tile ``setup_s``.
        "setup_phases": phases,
        # Every acceptance from the opening one to the closing one.
        "acceptances": stamps,
        "backlog": rows, "accepted": accepted, "agent": agent,
        "tracer": tracer,
        "window_record": {
            "window_s": window_s, "shards": len(accepted), "rows": n_rows,
            "models_in_window": len({m for _, m, _ in accepted}),
            "longest_silence_s": max(b - a for a, b in zip(stamps, stamps[1:])),
            "gc_pauses_in_window": len(in_window),
            "gc_pause_s": sum(in_window), "failed": failed,
            "setup_s": setup_s,
            "compiles_in_window": compiles, "warm_up": warm_totals,
            "all_compiles": counter.totals(), "backlog_left": backlog_left,
            "backlog_shards": size["shards"],
            "ceiling_rows_per_s": size["ceiling_rows_per_s"],
            "opened_after": opened_after, "open_regular": open_regular,
            "lead_in_gap_s": lead_in_gap_s,
        },
    }


def remove(paths: List[str]) -> None:
    for path in paths:
        if os.path.exists(path):
            os.remove(path)


def finish(run: Dict[str, Any], ctx: Dict[str, Any],
           checks: List[Dict[str, Any]]) -> Optional[stack.Tracer]:
    """The kind's verdicts into the ``run`` record, the traced run's
    reduction, and the helper's own keys out of it. Returns the tracer of a
    traced run (its directory holds the profile)."""
    for verdict in checks:
        emit("check", **verdict)
    run["checks"], run["check_data"] = checks, ctx.get("check_data")
    run["correct"] = bool(all(c["ok"] for c in checks) and run["failed"] == 0)
    for key in ("backlog", "accepted", "window_record"):
        del run[key]
    agent, tracer = run.pop("agent"), run.pop("tracer")
    if tracer is not None:
        run["trace"] = tracer.reduce(agent, ctx["program_patterns"])
    return tracer
