#!/usr/bin/env python3
"""CI smoke for the distributed-tracing pipeline (ISSUE 5).

Drains a small multi-shard CSV map-reduce plus two ``compile_probe`` jobs
(a smoke-local plugin op whose first call of a fresh ``jax.jit`` program
makes the runtime's compile listener emit an ``xla.compile`` span) through
the real ``Agent`` loop over
``chaos.LoopbackSession``, then asserts the acceptance criteria end to end:

1. every terminal job's ``GET /v1/trace/{job_id}`` is a single-rooted,
   causally consistent (gap-free: no orphans, no open spans) tree covering
   submit → sched.decide → lease → stage → execute → post → apply;
2. the Perfetto export (``?format=perfetto``) round-trips through JSON and
   passes ``validate_chrome_trace`` — the schema the legacy Perfetto
   importer requires;
3. exactly one ``xla.compile`` span lands on the cold-cache probe run, and
   the warm re-run obtains no executable (counters prove it);
4. the ``/v1/metrics`` exposition validates and its ``task_phase_seconds``
   buckets carry OpenMetrics exemplars whose trace_ids all resolve to jobs
   this smoke actually submitted;
5. tracing is pay-for-what-you-use: rows/sec over a CSV map-reduce drain
   (1024-row shards — 8x smaller than the 8192-row shards real drains
   use, so the bound is conservative) with tracing on stays within 10% of
   tracing off (best-of-5 each way, interleaved — best-of damps the
   scheduler noise that dwarfs the ~2% true overhead on shared runners);
6. ``scripts/chaos_soak.py --quick`` still reconciles with tracing enabled
   (subprocess, ``TRACE_ENABLED=1``).

Exit 0 = clean; 1 = problems (one per line). Style sibling of
``scripts/check_metrics_endpoint.py``: repo-rooted, zero external deps
beyond jax (the compile span comes from JAX's own compile event).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request
from typing import List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from agent_tpu.agent.app import Agent
from agent_tpu.chaos import LoopbackSession
from agent_tpu.config import AgentConfig, Config
from agent_tpu.controller.core import Controller
from agent_tpu.controller.server import ControllerServer
from agent_tpu.obs import trace as obs_trace
from agent_tpu.obs.metrics import parse_exemplars, validate_exposition
from agent_tpu.obs.trace import validate_chrome_trace

SHARDS = 8
ROWS_PER_SHARD = 10
# submit → … → apply: the causal chain every drained job must show.
REQUIRED_SPANS = (
    "submit", "sched.decide", "lease", "stage", "execute", "post", "apply",
)
BENCH_SHARDS = 24
BENCH_ROWS_PER_SHARD = 1024
BENCH_ROUNDS = 5
BENCH_TOLERANCE = 0.90  # tracing-on rows/sec must stay within 10% of off

# The probe op ships through the designed extension point (OPS_PLUGIN_PATH
# / load_plugins) rather than monkey-patching the registry. Its first call
# runs inside the agent's ambient TraceContext, so the span the runtime's
# compile listener emits parents to the triggering job's execute span — the
# same path the first call of a real op's runtime.compiled() program takes.
# Inputs are numpy arrays: a jnp constructor would be a program (and a
# span) of its own.
PLUGIN_SRC = '''\
"""Smoke-only op: one fresh jit program per distinct payload n."""
import time

import jax
import numpy as np

from agent_tpu.ops import register_op
from agent_tpu.runtime.executor import ExecutableCache

_CACHE = ExecutableCache()


@register_op("compile_probe")
def run(payload, ctx=None):
    t0 = time.perf_counter()
    n = int(payload.get("n", 8))
    t1 = time.perf_counter()
    fn = _CACHE.get_or_build(
        ("compile_probe", n),
        lambda: jax.jit(lambda x: (x * 2.0 + 1.0).sum()),
    )
    value = float(fn(np.arange(n, dtype=np.float32)))  # first call compiles
    t2 = time.perf_counter()
    if ctx is not None:
        # Stamp phase timings per the op contract (see
        # map_classify_tpu.CONTRACT.md): the serial loop turns these into
        # task_phase_seconds observations carrying the job exemplar.
        ctx.tags.setdefault("timings", {}).update({
            "stage_ms": (t1 - t0) * 1000.0,
            "device_ms": (t2 - t1) * 1000.0,
        })
    return {
        "ok": True,
        "value": value,
        "compute_time_ms": (time.perf_counter() - t0) * 1000.0,
    }
'''


def build_csv(path: str, rows: int) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write("id,text,risk\n")
        for i in range(rows):
            f.write(f'{i},"record {i}",{(i % 13) * 0.5}\n')


def make_agent(
    controller: Controller, tasks: Tuple[str, ...], max_tasks: int = 4
) -> Agent:
    cfg = Config(agent=AgentConfig(
        controller_url="http://loopback", agent_name="trace-smoke",
        tasks=tasks, max_tasks=max_tasks,
        idle_sleep_sec=0.0, error_backoff_sec=0.0,
    ))
    agent = Agent(config=cfg, session=LoopbackSession(controller))
    agent._profile = {"tier": "trace-smoke"}  # skip hardware probing
    return agent


def drain(controller: Controller, agent: Agent, deadline_s: float = 60.0
          ) -> bool:
    """Run the real lease/execute/post loop until drained; sweeps on idle
    so dep-gated reduce jobs release. Final metrics-only flush ships the
    tail spans (the last post span postdates its own post)."""
    deadline = time.monotonic() + deadline_s
    while not controller.drained() and time.monotonic() < deadline:
        leased = agent.lease_once()
        if leased is None:
            controller.sweep()
            continue
        lease_id, tasks = leased
        for task in tasks:
            agent.run_task(lease_id, task)
    agent.push_metrics()
    return controller.drained()


def check_trace_trees(controller: Controller, job_ids: List[str],
                      problems: List[str]) -> None:
    for jid in job_ids:
        t = controller.trace_json(jid)
        if t is None:
            problems.append(f"job {jid}: no trace assembled")
            continue
        if not t["complete"]:
            problems.append(
                f"job {jid}: trace not gap-free (roots={t['roots']}, "
                f"orphans={t['orphans']}, open={t['open_spans']})"
            )
        names = {s["name"] for s in t["spans"]}
        missing = [n for n in REQUIRED_SPANS if n not in names]
        if missing:
            problems.append(f"job {jid}: missing spans {missing}")
        # causal consistency: every non-root parent id resolves in-trace
        ids = {s["span_id"] for s in t["spans"]}
        for s in t["spans"]:
            p = s.get("parent_span_id")
            if p is not None and p not in ids:
                problems.append(
                    f"job {jid}: span {s['name']} dangles from {p}"
                )


def check_http_surface(controller: Controller, job_id: str,
                       problems: List[str]) -> None:
    with ControllerServer(controller) as server:
        with urllib.request.urlopen(
            f"{server.url}/v1/trace/{job_id}"
        ) as r:
            body = json.load(r)
        if not body.get("complete"):
            problems.append("/v1/trace over HTTP lost completeness")
        with urllib.request.urlopen(
            f"{server.url}/v1/trace/{job_id}?format=perfetto"
        ) as r:
            raw = r.read().decode()
        perfetto = json.loads(raw)  # "the export loads": JSON round-trip
        schema = validate_chrome_trace(perfetto)
        if schema:
            problems.append(f"perfetto export schema problems: {schema}")
        if not any(
            e.get("ph") == "X" for e in perfetto.get("traceEvents", [])
        ):
            problems.append("perfetto export carries no X events")
        with urllib.request.urlopen(f"{server.url}/v1/traces?limit=4") as r:
            listing = json.load(r).get("traces", [])
        if len(listing) != 4:
            problems.append(f"/v1/traces?limit=4 returned {len(listing)}")


def check_exemplars(controller: Controller, job_ids: List[str],
                    problems: List[str]) -> None:
    text = controller.metrics_text()
    problems += validate_exposition(text)
    exemplars = parse_exemplars(text)
    phase_ex = exemplars.get("task_phase_seconds_bucket", [])
    if not phase_ex:
        problems.append("task_phase_seconds buckets carry no exemplars")
    known = set(job_ids)
    for _labels, ex_labels, _v in (
        e for samples in exemplars.values() for e in samples
    ):
        jid = ex_labels.get("trace_id")
        if jid not in known:
            problems.append(f"exemplar references unknown job {jid!r}")


def drain_rows_per_sec(csv_path: str) -> float:
    rows = BENCH_SHARDS * BENCH_ROWS_PER_SHARD
    controller = Controller(lease_ttl_sec=30.0)
    controller.submit_csv_job(
        csv_path, total_rows=rows, shard_size=BENCH_ROWS_PER_SHARD,
        map_op="risk_accumulate", extra_payload={"field": "risk"},
        reduce_op="risk_accumulate", collect_partials=True,
    )
    agent = make_agent(controller, tasks=("risk_accumulate",), max_tasks=8)
    t0 = time.perf_counter()
    while not controller.drained():
        leased = agent.lease_once()
        if leased is None:
            controller.sweep()
            continue
        lease_id, tasks = leased
        for task in tasks:
            agent.run_task(lease_id, task)
    return rows / (time.perf_counter() - t0)


def main() -> int:
    problems: List[str] = []
    obs_trace.set_enabled(True)  # host env must not decide phase 1
    with tempfile.TemporaryDirectory(prefix="trace_smoke_") as tmp:
        plugin_path = os.path.join(tmp, "compile_probe_plugin.py")
        with open(plugin_path, "w", encoding="utf-8") as f:
            f.write(PLUGIN_SRC)
        from agent_tpu.ops import load_plugins

        if "compile_probe" not in load_plugins(plugin_path):
            from agent_tpu.ops import OPS_LOAD_ERRORS

            print(f"compile_probe plugin failed to load: {OPS_LOAD_ERRORS}")
            return 1

        csv_path = os.path.join(tmp, "rows.csv")
        build_csv(csv_path, SHARDS * ROWS_PER_SHARD)
        controller = Controller(lease_ttl_sec=30.0)
        shard_ids, reduce_id = controller.submit_csv_job(
            csv_path,
            total_rows=SHARDS * ROWS_PER_SHARD,
            shard_size=ROWS_PER_SHARD,
            map_op="risk_accumulate",
            extra_payload={"field": "risk"},
            reduce_op="risk_accumulate",
            collect_partials=True,
        )
        cold_probe = controller.submit("compile_probe", {"n": 16})
        warm_probe = controller.submit("compile_probe", {"n": 16})
        job_ids = list(shard_ids) + [reduce_id, cold_probe, warm_probe]

        agent = make_agent(
            controller, tasks=("risk_accumulate", "compile_probe")
        )
        if not drain(controller, agent):
            print(f"drain did not complete (counts {controller.counts()})")
            return 1
        counts = controller.counts()
        if counts.get("failed") or counts.get("dead"):
            problems.append(f"failed/dead jobs in the smoke drain: {counts}")

        check_trace_trees(controller, job_ids, problems)

        # Cold cache ⇒ exactly one xla.compile span, on the first probe.
        compile_spans = [
            s for jid in (cold_probe, warm_probe)
            for s in (controller.traces.spans(jid) or [])
            if s["name"] == "xla.compile"
        ]
        if len(compile_spans) != 1:
            problems.append(
                f"{len(compile_spans)} xla.compile spans on the cold-cache "
                f"run, want exactly one"
            )
        elif compile_spans[0]["trace_id"] != cold_probe:
            problems.append("xla.compile span attributed to the wrong job")
        else:
            execute = [
                s for s in controller.traces.spans(cold_probe)
                if s["name"] == "execute"
            ]
            if compile_spans[0]["parent_span_id"] != execute[0]["span_id"]:
                problems.append("xla.compile span not under execute")
        if any(s["trace_id"] == warm_probe for s in compile_spans):
            problems.append("warm probe re-compiled (cache hit expected)")
        if agent.obs.counter("runtime_xla_executables_total").value() != 1:
            problems.append("runtime_xla_executables_total != 1")
        cache = agent.obs.counter(
            "runtime_compile_cache_total", "", ("op", "outcome")
        )
        if cache.value(op="compile_probe", outcome="miss") != 1:
            problems.append("compile cache miss counter != 1")
        if cache.value(op="compile_probe", outcome="hit") != 1:
            problems.append("compile cache hit counter != 1")

        check_http_surface(controller, reduce_id, problems)
        check_exemplars(controller, job_ids, problems)

    # 5. overhead bound: best-of-N rows/sec over the CSV drain, tracing
    # off vs on, interleaved so machine drift hits both modes alike.
    with tempfile.TemporaryDirectory(prefix="trace_bench_") as tmp:
        bench_csv = os.path.join(tmp, "bench.csv")
        build_csv(bench_csv, BENCH_SHARDS * BENCH_ROWS_PER_SHARD)
        best = {False: 0.0, True: 0.0}
        for _ in range(BENCH_ROUNDS):
            for mode in (False, True):
                obs_trace.set_enabled(mode)
                best[mode] = max(best[mode], drain_rows_per_sec(bench_csv))
    obs_trace.set_enabled(None)  # restore the env-driven default
    ratio = best[True] / best[False] if best[False] else 0.0
    print(
        f"tracing overhead: off {best[False]:.0f} rows/s, "
        f"on {best[True]:.0f} rows/s (ratio {ratio:.3f})"
    )
    if ratio < BENCH_TOLERANCE:
        problems.append(
            f"tracing-on drain rate {best[True]:.0f} rows/s is below "
            f"{BENCH_TOLERANCE:.0%} of tracing-off {best[False]:.0f} rows/s"
        )

    # 6. the chaos soak still reconciles with tracing forced on.
    env = dict(os.environ, TRACE_ENABLED="1")
    soak = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "chaos_soak.py"),
         "--seed", "7", "--shards", "8", "--quick"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    if soak.returncode != 0:
        tail = (soak.stdout + soak.stderr).strip().splitlines()[-8:]
        problems.append(
            "chaos_soak --quick failed with TRACE_ENABLED=1: "
            + " | ".join(tail)
        )

    if problems:
        for p in problems:
            print(p)
        print(f"FAILED: {len(problems)} problem(s)")
        return 1
    print("trace pipeline smoke check: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
