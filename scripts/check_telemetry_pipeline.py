#!/usr/bin/env python3
"""CI smoke for durable fleet telemetry & incident forensics (ISSUE 20).

Four phases, each over the real surfaces:

1. **Durability** — a real ``agent_tpu.controller.server`` subprocess
   persists sweep samples into ``TSDB_DIR``; it is SIGKILLed mid-write and
   restarted on the same directory. Every sample the first incarnation
   served over ``GET /v1/timeseries?since=`` must still be served by the
   second, from disk (``source == "tsdb"``).
2. **Fleet history** — two partitioned controllers behind a
   ``RouterServer`` collector: the router's ``/v1/timeseries?since=``
   answers one fleet-wide query with both ``partition`` labels present.
3. **Forensics** — a calm warmup then a queue-depth burst on a live
   controller: the detector must confirm exactly ONE anomaly, ``/v1/health``
   must carry it as a warn reason, and ``/v1/incidents`` must hold exactly
   ONE correlated bundle (timeseries + flight recorder + status + health)
   fetchable by id.
4. **Overhead** — the same drain with the durable store on vs off:
   rows/sec with telemetry on must stay >=90% of off in CI (a guard on the
   CPU runner, which prints the ratio it read; the cost on a chip is not
   measured on the present tree).

Exit 0 = clean; 1 = problems (one per line). Style sibling of
``scripts/check_profile_pipeline.py``: repo-rooted, stdlib-only driver.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
from typing import List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from agent_tpu.agent.app import Agent
from agent_tpu.chaos import LoopbackSession
from agent_tpu.config import AgentConfig, Config, ObsConfig
from agent_tpu.controller.core import Controller
from agent_tpu.controller.server import ControllerServer
from agent_tpu.controller.router import PartitionMap, RouterServer

SHARD_ROWS = 1024
SHARDS = 8
BENCH_ROUNDS = 3
# The CI bar absorbs shared-runner noise; the ratio the runner read
# prints either way.
BENCH_TOLERANCE = 0.90


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.load(r)


def wait_http(url: str, deadline_s: float = 20.0) -> bool:
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            http_json(url, timeout=2)
            return True
        except Exception:  # noqa: BLE001 — still starting
            time.sleep(0.1)
    return False


def build_csv(path: str, rows: int) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write("id,text,risk\n")
        for i in range(rows):
            f.write(f'{i},"record {i}",{(i % 13) * 0.5}\n')


def make_agent(controller: Controller, name: str) -> Agent:
    cfg = Config(agent=AgentConfig(
        controller_url="http://loopback", agent_name=name,
        tasks=("risk_accumulate",), max_tasks=4, idle_sleep_sec=0.0,
        error_backoff_sec=0.0,
    ))
    agent = Agent(config=cfg, session=LoopbackSession(controller))
    agent._profile = {"tier": "telemetry-smoke"}
    return agent


def drain(controller: Controller, agent: Agent,
          deadline_s: float = 120.0) -> bool:
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


def spawn_server(port: int, tsdb_dir: str, incident_dir: str,
                 journal: str) -> subprocess.Popen:
    env = dict(os.environ)
    env.update(
        PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
        CONTROLLER_HOST="127.0.0.1",
        CONTROLLER_PORT=str(port),
        CONTROLLER_JOURNAL=journal,
        CONTROLLER_SWEEP_SEC="0.1",
        TSDB_DIR=tsdb_dir,
        TSDB_INTERVAL="0.1",
        INCIDENT_DIR=incident_dir,
    )
    return subprocess.Popen(
        [sys.executable, "-m", "agent_tpu.controller.server"],
        env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def phase_durability(tmp: str, problems: List[str]) -> None:
    """SIGKILL + restart: pre-kill samples stay queryable over HTTP."""
    port = free_port()
    url = f"http://127.0.0.1:{port}"
    tsdb_dir = os.path.join(tmp, "tsdb")
    incident_dir = os.path.join(tmp, "incidents")
    journal = os.path.join(tmp, "journal.jsonl")
    proc = spawn_server(port, tsdb_dir, incident_dir, journal)
    proc2: Optional[subprocess.Popen] = None
    try:
        if not wait_http(url + "/v1/status"):
            problems.append("durability: server never became healthy")
            return
        # Let the sweeper persist a few samples, then capture them.
        prekill: List[float] = []
        deadline = time.monotonic() + 15.0
        while len(prekill) < 5 and time.monotonic() < deadline:
            time.sleep(0.3)
            body = http_json(
                url + "/v1/timeseries?name=controller_queue_depth&since=600"
            )
            prekill = [
                w for s in body.get("series", [])
                for w, _v in s.get("points", [])
            ]
        if len(prekill) < 5:
            problems.append(
                f"durability: only {len(prekill)} pre-kill samples landed"
            )
            return
        if body.get("source") != "tsdb":
            problems.append(
                f"durability: live history source {body.get('source')!r}, "
                "want 'tsdb'"
            )
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)

        proc2 = spawn_server(port, tsdb_dir, incident_dir, journal)
        if not wait_http(url + "/v1/status"):
            problems.append("durability: restarted server never healthy")
            return
        body = http_json(
            url + "/v1/timeseries?name=controller_queue_depth&since=600"
        )
        post = {
            w for s in body.get("series", [])
            for w, _v in s.get("points", [])
        }
        missing = [w for w in prekill if w not in post]
        if body.get("source") != "tsdb":
            problems.append(
                f"durability: post-restart source {body.get('source')!r}"
            )
        if missing:
            problems.append(
                f"durability: {len(missing)}/{len(prekill)} pre-kill "
                f"samples lost across SIGKILL+restart (e.g. {missing[0]})"
            )
        print(f"durability: {len(prekill)} pre-kill samples intact "
              "across SIGKILL+restart")
    finally:
        for p in (proc, proc2):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=10)


def phase_fleet(tmp: str, problems: List[str]) -> None:
    """Router collector: one query answers across both partitions."""
    ctrls, srvs = [], []
    router = None
    try:
        for i in range(2):
            obs = ObsConfig(
                tsdb_dir=os.path.join(tmp, f"tsdb-p{i}"),
                tsdb_interval_sec=0.05,
            )
            c = Controller(journal_path=None, obs=obs,
                           sweep_interval_sec=0.05, partition=f"p{i}")
            c.start_sweeper()
            c.submit("risk_accumulate", {"values": [1.0, float(i)]})
            s = ControllerServer(c, host="127.0.0.1", port=0)
            s.start()
            ctrls.append(c)
            srvs.append(s)
        pmap = PartitionMap({"p0": [srvs[0].url], "p1": [srvs[1].url]})
        router = RouterServer(
            pmap, host="127.0.0.1", port=0, collect_interval_sec=0.1,
            fleet_tsdb_dir=os.path.join(tmp, "fleet-tsdb"),
        )
        router.start()
        deadline = time.monotonic() + 15.0
        parts: set = set()
        while parts != {"p0", "p1"} and time.monotonic() < deadline:
            time.sleep(0.3)
            body = http_json(
                router.url
                + "/v1/timeseries?name=controller_queue_depth&since=600"
            )
            parts = {
                s.get("labels", {}).get("partition")
                for s in body.get("series", [])
            }
        if parts != {"p0", "p1"}:
            problems.append(
                f"fleet: router history covered partitions {parts}, "
                "want both p0 and p1"
            )
        else:
            stats = router.collector.stats()
            if stats.get("scrape_errors", 0) > 0:
                problems.append(
                    f"fleet: collector scrape errors {stats}"
                )
            print(f"fleet: one router query spans {sorted(parts)} "
                  f"({stats.get('samples_collected', 0)} samples collected)")
    finally:
        if router is not None:
            router.stop()
        for s in srvs:
            s.stop()
        for c in ctrls:
            c.close()


def phase_forensics(tmp: str, problems: List[str]) -> None:
    """Calm warmup then a queue burst: exactly one anomaly, one bundle."""
    obs = ObsConfig(
        tsdb_dir=os.path.join(tmp, "tsdb-forensics"),
        tsdb_interval_sec=0.03,
        anomaly_window=60, anomaly_warmup=10, anomaly_confirm=2,
        incident_dir=os.path.join(tmp, "incidents-forensics"),
    )
    c = Controller(journal_path=None, obs=obs)
    srv = ControllerServer(c, host="127.0.0.1", port=0)
    srv.start()
    try:
        # Calm baseline: empty queue, sampled well past warmup.
        for _ in range(20):
            c.sweep()
            time.sleep(0.035)
        # The burst: 100 jobs land with no agent draining them.
        for i in range(100):
            c.submit("risk_accumulate", {"values": [1.0]},
                     job_id=f"burst-{i}")
        for _ in range(10):
            c.sweep()
            time.sleep(0.035)

        health = http_json(srv.url + "/v1/health")
        anomaly_reasons = [
            r for r in health.get("reasons", [])
            if r.get("kind") == "anomaly"
        ]
        if health.get("verdict") not in ("warn", "page") \
                or not anomaly_reasons:
            problems.append(
                f"forensics: /v1/health verdict {health.get('verdict')!r} "
                f"reasons {health.get('reasons')} — no anomaly warn"
            )
        listing = http_json(srv.url + "/v1/incidents")
        bundles = [
            h for h in listing.get("incidents", [])
            if h.get("kind") == "anomaly"
        ]
        if len(bundles) != 1:
            problems.append(
                f"forensics: {len(bundles)} anomaly bundles, want exactly 1"
            )
            return
        head = bundles[0]
        if head.get("key") != "queue_depth":
            problems.append(
                f"forensics: bundle watched {head.get('key')!r}, "
                "want queue_depth"
            )
        body = http_json(srv.url + "/v1/incidents/" + head["id"])
        sections = (body.get("incident") or {}).get("sections", {})
        for section in ("timeseries", "flight_recorder", "status", "health"):
            if section not in sections:
                problems.append(
                    f"forensics: bundle missing section {section!r}"
                )
        print(f"forensics: one anomaly -> one bundle {head['id']} "
              f"(z={head.get('reason', {}).get('z')})")
    finally:
        srv.stop()
        c.close()


def drain_rows_per_sec(tmp: str, csv_path: str, enabled: bool,
                       round_i: int) -> float:
    rows = SHARDS * SHARD_ROWS
    obs = ObsConfig(
        tsdb_dir=os.path.join(tmp, f"bench-tsdb-{round_i}")
        if enabled else "",
        tsdb_interval_sec=0.1,
        anomaly_enabled=enabled,
        incident_enabled=enabled,
    )
    controller = Controller(journal_path=None, obs=obs)
    controller.submit_csv_job(
        csv_path, total_rows=rows, shard_size=SHARD_ROWS,
        map_op="risk_accumulate", extra_payload={"field": "risk"},
    )
    agent = make_agent(controller, name=f"bench-{round_i}")
    t0 = time.perf_counter()
    if not drain(controller, agent):
        raise RuntimeError(f"bench drain wedged: {controller.counts()}")
    dt = time.perf_counter() - t0
    controller.close()
    return rows / dt


def phase_overhead(tmp: str, problems: List[str]) -> None:
    csv_path = os.path.join(tmp, "rows.csv")
    build_csv(csv_path, SHARDS * SHARD_ROWS)
    best_on = best_off = 0.0
    for i in range(BENCH_ROUNDS):  # interleaved best-of-N
        best_off = max(best_off, drain_rows_per_sec(tmp, csv_path, False, i))
        best_on = max(best_on, drain_rows_per_sec(tmp, csv_path, True, i))
    ratio = best_on / best_off if best_off > 0 else 0.0
    print(f"overhead: telemetry-on {best_on:,.0f} rows/s vs off "
          f"{best_off:,.0f} rows/s — ratio {ratio:.3f}")
    if ratio < BENCH_TOLERANCE:
        problems.append(
            f"overhead: tsdb-on throughput ratio {ratio:.3f} < "
            f"{BENCH_TOLERANCE} of tsdb-off"
        )


def main() -> int:
    problems: List[str] = []
    with tempfile.TemporaryDirectory(prefix="telemetry_smoke_") as tmp:
        phase_durability(tmp, problems)
        phase_fleet(tmp, problems)
        phase_forensics(tmp, problems)
        phase_overhead(tmp, problems)
    if problems:
        for p in problems:
            print(p)
        print(f"FAILED: {len(problems)} problem(s)")
        return 1
    print("telemetry pipeline smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
