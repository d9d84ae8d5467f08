"""Traffic kind ``drain``: CSV jobs over ``POST /v1/jobs`` whose backlog
outlasts the window, drained by the in-process agent; the controller is a
child process. The traffic file's ``tenants`` take turns to submit a dataset
of ``job_rows`` rows, each tenant for its own model (``<model id>-t<k>``), so
the agent holds that many models and every one of them answers inside the
window.

The end-to-end metric is ``drain_rows_per_s``: rows of the shards whose
result the controller accepted inside the window, over the window's length.
The benchmark clocks each acceptance itself, on the poster thread's own HTTP
session (``Agent.post_session_factory``, the program's hook for it), when the
controller's 200 comes back. A shard is up to half a second of work, so a
window cut at arbitrary instants would count ±1 shard (up to ±4%); the window
therefore opens AT an acceptance and closes at the first acceptance at or after
``--seconds`` later, and the rate is all the rows over all that time."""

from __future__ import annotations

import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.harness import flops, manifest, procs, schedule, stack, stats
from benchmarks.harness.compile_count import CompileCounter
from benchmarks.harness.stack import check, emit

JOB_TIMEOUT_S = 400.0


class PostClock:
    """Wraps the poster thread's session: notes the wall clock of every
    ``/v1/results`` answer. Reads, never changes, what is posted."""

    def __init__(self) -> None:
        self.cond = threading.Condition()
        self.posts: List[Tuple[float, str, str, int]] = []

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


def check_rows(ctx: Dict[str, Any], rows: List[str],
               accepted: List[Tuple[int, str, Dict[str, Any]]]
               ) -> List[Dict[str, Any]]:
    """A seeded sample of the rows the window answered against the plain
    float32 reference, which makes each tenant's weights itself from the
    model id. ``accepted`` is ``(first row, model id, result body)`` per
    shard. Returns one record per number compared (value, limit, verdict);
    ``ctx["check_data"]`` keeps what was compared."""
    import numpy as np

    config = ctx["config"]
    ref = manifest.load_reference(config["reference"])
    spec = config["check"]
    pool = [(start + j, model_id, body, j) for start, model_id, body in accepted
            for j in range(len(body["indices"]))]
    rng = schedule.rng_of(ctx["seed"], "check")
    take = rng.choice(len(pool), size=min(int(spec["rows"]), len(pool)),
                      replace=False)
    # Sorted by model, then row: the reference's rows, model after model,
    # come out in the sample's own order.
    sample = sorted((pool[int(i)] for i in take), key=lambda s: (s[1], s[0]))
    models = sorted({m for _, m, _, _ in sample})
    data = {
        "ref_logits": np.concatenate([
            ref.logits(config["model"], model_id,
                       [rows[r] for r, m, _, _ in sample if m == model_id])
            for model_id in models]) if sample else np.zeros((0, 1), np.float32),
        "indices": np.asarray([b["indices"][j] for _, _, b, j in sample], np.int64),
        "scores": np.asarray([b["scores"][j] for _, _, b, j in sample], np.float64),
        "model": np.asarray([models.index(m) for _, m, _, _ in sample], np.int64),
    }
    ctx["check_data"] = data
    values = ref.compare(**data) if len(sample) else {}
    return [{
        "number": number, "value": float(values.get(number, float("inf"))),
        "limit": float(limit), "rows": len(sample), "models": len(models),
        "ok": bool(number in values and values[number] <= limit),
    } for number, limit in spec["limits"].items()]


def run_cell(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    seed, seconds = int(ctx["seed"]), float(ctx["seconds"])
    out = stack.output_dir(cell["name"], seed, int(ctx["trace"]))
    device = stack.init_device(int(cell["chips"]))
    counter = CompileCounter().install()

    shard, tenants = int(traffic["shard_rows"]), int(traffic["tenants"])
    job_rows = int(traffic["job_rows"])
    check(job_rows % shard == 0, "job_rows must be whole shards")
    lead_in = int(traffic["lead_in_shards"])
    n_jobs = math.ceil((float(traffic["backlog_rows_per_s"]) * seconds
                        + (lead_in + 2) * shard) / job_rows)
    n_jobs = tenants * math.ceil(n_jobs / tenants)   # every tenant as often
    warm_rows = tenants * shard
    rows = schedule.drain_rows(traffic, seed, n_jobs * job_rows + warm_rows)
    warm, rows = rows[:warm_rows], rows[warm_rows:]
    payloads = [model_payload(config, seed, k) for k in range(tenants)]
    map_op = config["op"]["map_op"]
    csvs: List[str] = []

    def submit(name: str, texts: List[str], tenant: int) -> List[str]:
        path = os.path.join(out, name)
        schedule.write_csv(path, texts)
        csvs.append(path)
        return post_csv_job(url, path, len(texts), shard, map_op,
                            payloads[tenant][1], f"tenant-{tenant}")

    controller = procs.ControllerProcess(os.path.join(out, "controller.log"))
    url = controller.url
    agent: Optional[stack.AgentStack] = None
    try:
        clock = PostClock()
        agent = stack.AgentStack(controller.url, config["op"]["tasks"])
        agent.agent.post_session_factory = clock.factory

        # ---- warm-up: one shard of every tenant's model, the cell's own
        # shapes, through the normal path -----------------------------------
        warm_ids = [jid for k in range(tenants) for jid in submit(
            f"warm-{k}.csv", warm[k * shard:(k + 1) * shard], k)]
        clock.wait_for(lambda: len(clock.posts) >= len(warm_ids),
                       JOB_TIMEOUT_S, agent.alive)
        for jid in warm_ids:
            snap = job_snapshot(url, jid)
            check(snap["state"] == "succeeded",
                  f"warm-up shard {jid} {snap['state']}: {snap.get('error')}")
        warm_totals = counter.totals()

        # ---- the backlog; the window opens at an acceptance --------------
        where: Dict[str, Tuple[int, str]] = {}   # shard job id -> first row, model
        for j in range(n_jobs):
            k = j % tenants
            ids = submit(f"job-{j}.csv", rows[j * job_rows:(j + 1) * job_rows], k)
            for i, jid in enumerate(ids):
                where[jid] = (j * job_rows + i * shard, payloads[k][0])
        n0 = len(clock.posts)
        clock.wait_for(lambda: len(clock.posts) >= n0 + lead_in + 1,
                       JOB_TIMEOUT_S, agent.alive)
        open_at = n0 + lead_in
        t_open = clock.posts[open_at][0]
        setup_s = t_open - ctx["t_start"]
        m0, cpu0 = agent.metrics(), controller.cpu_seconds()
        tracer = None
        if ctx["trace"]:
            tracer = stack.Tracer(os.path.join(out, "trace"))
            tracer.capture(t_open, traffic)
        clock.wait_for(
            lambda: clock.posts[-1][0] >= t_open + seconds,
            seconds + 120.0, agent.alive)
        with clock.cond:
            posts = list(clock.posts)
        close_at = next(i for i, p in enumerate(posts)
                        if i > open_at and p[0] >= t_open + seconds)
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
            snap = job_snapshot(url, jid) if jid in where else None
            if (snap is None or status != "succeeded" or code != 200
                    or snap["state"] != "succeeded"):
                failed += 1
                continue
            body = snap["result"]
            good = (isinstance(body, dict) and body.get("ok") is True
                    and body.get("device") == stack.REQUIRED_PLATFORM
                    and "fallback" not in body
                    and len(body.get("indices") or []) == shard
                    and len(body.get("scores") or []) == shard)
            if good:
                accepted.append((*where[jid], body))
            else:
                failed += 1
        window_s = t_close - t_open
        n_rows = sum(len(b["indices"]) for _, _, b in accepted)
        check(len(accepted) > 0, f"no shard succeeded in the window: {window}")
        backlog_left = len(where) - (close_at - n0 + 1)
        check(backlog_left > 0, "the backlog ran empty inside the window: "
              f"{len(where)} shards, raise backlog_rows_per_s")
        models_in_window = len({m for _, m, _ in accepted})
        checks = check_rows(ctx, rows, accepted)
        for verdict in checks:
            emit("check", **verdict)
    finally:
        if agent is not None:
            agent.close()
        controller.stop()
        for path in csvs:                     # tens of MB a run: not kept
            if os.path.exists(path):
                os.remove(path)

    real_tokens = [min(len(r), int(config["model"]["max_len"]))
                   for start, _, _ in accepted for r in rows[start:start + shard]]
    run: Dict[str, Any] = {
        "kind": "drain", "cell": cell, "config": config, "traffic": traffic,
        "device": device, "window_s": window_s, "t_open": t_open,
        "t_close": t_close, "shards": len(accepted), "rows": n_rows,
        "agent_metrics": (m0, m1), "controller_cpu_s": cpu1 - cpu0,
        "post_span_s": post_spans,
        "compiles_in_window": compiles, "op": map_op,
        "mean_flops_per_row": flops.encoder_flops_needed(
            config["model"], real_tokens) / max(1, len(real_tokens)),
        "trace": None, "memory_peak_bytes": peak,
        "attempted": len(window), "failed": failed,
        "correct": bool(all(c["ok"] for c in checks) and failed == 0),
        "checks": checks, "check_data": ctx.get("check_data"),
        "end_to_end": {
            "drain_rows_per_s": stats.rate(n_rows, window_s),
            "setup_s": setup_s,
        },
    }
    stamps = [p[0] for p in posts[open_at:close_at + 1]]
    emit("window", window_s=window_s, shards=len(accepted), rows=n_rows,
         models_in_window=models_in_window,
         longest_silence_s=max(b - a for a, b in zip(stamps, stamps[1:])),
         failed=failed, setup_s=setup_s, compiles_in_window=compiles,
         warm_up=warm_totals, all_compiles=counter.totals(),
         backlog_left=backlog_left)
    if tracer is not None:
        run["trace"] = tracer.reduce(agent, ctx["program_patterns"])
    return run
