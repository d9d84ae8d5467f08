#!/usr/bin/env python3
"""CI smoke for resource accounting & continuous profiling (ISSUE 9).

Drives a mixed TWO-TENANT drain through the real ``Agent`` loop over
``chaos.LoopbackSession`` against a controller serving the new surfaces
over real HTTP, then asserts the acceptance bar end to end:

1. **Usage reconciliation** — ``GET /v1/usage`` per-tenant
   ``device_seconds`` totals sum to the fleet-merged
   ``device_busy_seconds_total{op}`` within 1% on a two-tenant
   1024-row-shard drain, both tenants appear with correct row counts, and
   the per-tenant split is disjoint (billed tasks == accepted results).
2. **Host flamegraph** — ``GET /v1/profile/host`` returns collapsed-stack
   text with ≥1 real frame (``a;b;c count`` lines, positive counts).
3. **On-demand deep capture** — ``POST /v1/profile/capture`` round-trips
   through the lease ``alerts`` channel: the agent wraps one matching op
   execution in ``jax.profiler.trace`` and the artifact path + summary land
   back at ``GET /v1/profile/captures`` with ≥1 trace file on disk.
4. **HBM telemetry** — ``device_hbm_bytes{device,kind}`` gauges appear
   when ``memory_stats()`` reports (TPU), or are CLEANLY absent (CPU CI:
   no zero-filled series, no errors).
5. **Time-series ring** — ``GET /v1/timeseries?name=tasks_total`` serves
   ≥2 samples with non-negative rates; unknown names and pre-sample reads
   return empty series, never errors.
6. **Overhead** — enabling usage+tsdb+host-profiling keeps >=90% of the
   all-disabled rows/sec on the same drain (best-of-N interleaved; the bar
   absorbs shared-runner noise, and the ratio the runner read is printed).
   A guard on the CPU runner: the cost on a chip is not measured on the
   present tree.

Exit 0 = clean; 1 = problems (one per line). Style sibling of
``scripts/check_slo_pipeline.py``: repo-rooted, stdlib-only driver.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import urllib.error
import urllib.request
from typing import List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from agent_tpu.agent.app import Agent
from agent_tpu.chaos import LoopbackSession
from agent_tpu.config import AgentConfig, Config, ObsConfig
from agent_tpu.controller.core import Controller
from agent_tpu.controller.server import ControllerServer

SHARD_ROWS = 1024          # the acceptance bar's shard size
SHARDS_PER_TENANT = 8
TENANTS = ("tenant-a", "tenant-b")

BENCH_ROUNDS = 3
# The CI bar absorbs shared-runner noise; the ratio the runner read
# prints either way.
BENCH_TOLERANCE = 0.90


def build_csv(path: str, rows: int) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write("id,text,risk\n")
        for i in range(rows):
            f.write(f'{i},"record {i}",{(i % 13) * 0.5}\n')


def make_controller(enabled: bool = True,
                    tsdb_interval: float = 0.1) -> Controller:
    return Controller(
        lease_ttl_sec=30.0,
        obs=ObsConfig(
            usage_enabled=enabled,
            tsdb_enabled=enabled,
            tsdb_interval_sec=tsdb_interval,
            profile_host_enabled=enabled,
        ),
    )


def make_agent(controller: Controller, name: str = "profile-smoke") -> Agent:
    cfg = Config(agent=AgentConfig(
        controller_url="http://loopback", agent_name=name,
        tasks=("risk_accumulate",), max_tasks=4, idle_sleep_sec=0.0,
        error_backoff_sec=0.0,
    ))
    agent = Agent(config=cfg, session=LoopbackSession(controller))
    agent._profile = {"tier": "profile-smoke"}  # skip hardware probing
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


def fleet_busy_seconds(controller: Controller) -> float:
    fleet = controller.fleet_snapshot()
    return sum(
        float(s.get("value", 0.0))
        for s in fleet.get("device_busy_seconds_total", {}).get("series", [])
    )


def http_json(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.load(r)


def http_text(url: str) -> str:
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode("utf-8", errors="replace")


def submit_two_tenants(controller: Controller, csv_path: str) -> None:
    for tenant in TENANTS:
        controller.submit_csv_job(
            csv_path, total_rows=SHARDS_PER_TENANT * SHARD_ROWS,
            shard_size=SHARD_ROWS, map_op="risk_accumulate",
            extra_payload={"field": "risk"}, tenant=tenant,
        )


def drain_rows_per_sec(csv_path: str, enabled: bool) -> float:
    rows = SHARDS_PER_TENANT * SHARD_ROWS * len(TENANTS)
    controller = make_controller(enabled=enabled)
    submit_two_tenants(controller, csv_path)
    if enabled:
        # The overhead leg measures the FULL feature set: profiler sampling
        # included (started eagerly here; production starts it lazily).
        controller.host_profile_text()
    agent = make_agent(controller, name="bench")
    t0 = time.perf_counter()
    if not drain(controller, agent):
        raise RuntimeError(f"bench drain wedged: {controller.counts()}")
    dt = time.perf_counter() - t0
    controller.close()
    return rows / dt


def main() -> int:
    problems: List[str] = []
    tmp = tempfile.mkdtemp(prefix="profile_smoke_")
    os.environ["PROFILE_CAPTURE_DIR"] = os.path.join(tmp, "captures")

    csv_path = os.path.join(tmp, "rows.csv")
    build_csv(csv_path, SHARDS_PER_TENANT * SHARD_ROWS)

    controller = make_controller()
    agent = make_agent(controller)

    with ControllerServer(controller) as server:
        # ---- phase 1+3: two-tenant drain with an armed deep capture ----
        submit_two_tenants(controller, csv_path)
        cap = http_json_post(
            server.url + "/v1/profile/capture",
            {"agent": "profile-smoke", "op": "risk_accumulate"},
        )
        if "capture_id" not in cap:
            problems.append(f"capture request got no id: {cap}")
        if not drain(controller, agent):
            print(f"two-tenant drain wedged: {controller.counts()}")
            return 1

        usage = http_json(server.url + "/v1/usage")
        busy = fleet_busy_seconds(controller)
        ledger = usage.get("totals", {}).get("device_seconds", 0.0)
        if busy <= 0:
            problems.append("fleet device_busy_seconds_total is zero")
        elif abs(ledger - busy) > 0.01 * busy:
            problems.append(
                f"usage device_seconds {ledger} vs fleet busy {busy} — "
                f"off by {abs(ledger - busy) / busy:.2%}, want <1%"
            )
        print(f"usage reconciliation: ledger {ledger:.4f}s vs fleet busy "
              f"{busy:.4f}s")
        by_tenant = usage.get("by_tenant", {})
        for tenant in TENANTS:
            t = by_tenant.get(tenant)
            if t is None:
                problems.append(f"/v1/usage missing tenant {tenant!r}")
                continue
            if t["rows"] != SHARDS_PER_TENANT * SHARD_ROWS:
                problems.append(
                    f"{tenant} rows {t['rows']} != "
                    f"{SHARDS_PER_TENANT * SHARD_ROWS}"
                )
            if t["tasks"] != SHARDS_PER_TENANT:
                problems.append(
                    f"{tenant} tasks {t['tasks']} != {SHARDS_PER_TENANT}"
                )
        n_jobs = SHARDS_PER_TENANT * len(TENANTS)
        if usage.get("billed_tasks") != n_jobs:
            problems.append(
                f"billed_tasks {usage.get('billed_tasks')} != jobs {n_jobs} "
                "(a result went unbilled or billed twice)"
            )
        if not usage.get("top_jobs"):
            problems.append("/v1/usage top_jobs empty after a drain")

        # ---- phase 2: host flamegraph over real HTTP ----
        flame = http_text(server.url + "/v1/profile/host")
        frames = [
            line for line in flame.splitlines()
            if line.strip() and ";" in line
            and line.rsplit(" ", 1)[-1].isdigit()
            and int(line.rsplit(" ", 1)[-1]) >= 1
        ]
        if not frames:
            problems.append(
                f"host flamegraph has no real frames: {flame[:200]!r}"
            )
        else:
            print(f"host flamegraph: {len(frames)} collapsed stack(s)")

        # ---- phase 3 (cont): capture completion round-tripped ----
        captures = http_json(server.url + "/v1/profile/captures")["captures"]
        done = [c for c in captures
                if c.get("capture_id") == cap.get("capture_id")]
        if not done:
            problems.append("capture never round-tripped to /v1/profile/"
                            f"captures: {captures}")
        else:
            c = done[0]
            if c.get("status") != "done":
                problems.append(f"capture status {c.get('status')!r}: {c}")
            elif not (c.get("artifact") and os.path.isdir(c["artifact"])
                      and (c.get("summary") or {}).get("n_trace_files", 0)
                      >= 1):
                problems.append(f"capture artifact missing on disk: {c}")
            else:
                print(f"deep capture: {c['summary']['n_trace_files']} trace "
                      f"file(s) at {c['artifact']}")

        # ---- phase 4: HBM gauges present or cleanly absent ----
        snap = agent.obs.snapshot()
        hbm = snap.get("device_hbm_bytes", {}).get("series", [])
        reports_stats = False
        if agent.runtime is not None:
            from agent_tpu.obs.profile import device_memory_stats

            reports_stats = bool(device_memory_stats(agent.runtime.devices))
        if reports_stats and not hbm:
            problems.append("backend reports memory_stats but no "
                            "device_hbm_bytes gauges were exported")
        if not reports_stats and hbm:
            problems.append(
                f"device_hbm_bytes zero-filled on a statless backend: {hbm}"
            )
        if hbm and any(s.get("value", 0) <= 0
                       for s in hbm if s["labels"]["kind"] == "limit"):
            problems.append(f"nonsense HBM limit gauge: {hbm}")
        print(f"HBM gauges: {len(hbm)} series "
              f"({'backend reports stats' if reports_stats else 'cleanly absent on this backend'})")

        # ---- phase 5: time-series ring over real HTTP ----
        ts = http_json(server.url + "/v1/timeseries?name=tasks_total&rate=1")
        if ts.get("n_samples", 0) < 2:
            problems.append(f"time-series ring has {ts.get('n_samples')} "
                            "samples, want >=2")
        if not ts.get("series"):
            problems.append("tasks_total absent from the time-series ring")
        elif any(v < 0 for s in ts["series"] for _t, v in s["points"]):
            problems.append("negative rate in tasks_total series")
        empty = http_json(server.url + "/v1/timeseries?name=no_such_series")
        if empty.get("series") != []:
            problems.append(f"unknown series name not empty: {empty}")
        missing_name = urllib.request.Request(
            server.url + "/v1/timeseries")
        try:
            urllib.request.urlopen(missing_name, timeout=10)
            problems.append("nameless /v1/timeseries did not 400")
        except urllib.error.HTTPError as exc:
            if exc.code != 400:
                problems.append(f"nameless /v1/timeseries: HTTP {exc.code}")

    controller.close()

    # ---- phase 6: overhead of the full feature set ----
    best = {False: 0.0, True: 0.0}
    for _ in range(BENCH_ROUNDS):
        for mode in (False, True):
            best[mode] = max(best[mode], drain_rows_per_sec(csv_path, mode))
    ratio = best[True] / best[False] if best[False] else 0.0
    print(
        f"usage+tsdb+profiling overhead: off {best[False]:.0f} rows/s, on "
        f"{best[True]:.0f} rows/s (ratio {ratio:.3f}; acceptance wants "
        f">0.97 true cost, CI asserts >{BENCH_TOLERANCE})"
    )
    if ratio < BENCH_TOLERANCE:
        problems.append(
            f"accounting-on drain rate {best[True]:.0f} rows/s is below "
            f"{BENCH_TOLERANCE:.0%} of off {best[False]:.0f} rows/s"
        )

    # ---- disabled path: everything off is cleanly off ----
    off = make_controller(enabled=False)
    if off.usage_json() != {"enabled": False}:
        problems.append("USAGE_ENABLED=0 still reports usage")
    if off.timeseries_json("tasks_total").get("enabled", True):
        problems.append("TSDB_ENABLED=0 still serves series")
    if off.host_profile_text() is not None:
        problems.append("PROFILE_HOST_ENABLED=0 still serves a flamegraph")
    usage_fams = [k for k in off.metrics.snapshot()
                  if k.startswith("usage_")]
    if usage_fams:
        problems.append(f"disabled controller registered {usage_fams}")
    off.close()

    if problems:
        for p in problems:
            print(p)
        print(f"FAILED: {len(problems)} problem(s)")
        return 1
    print("profile pipeline smoke check: OK")
    return 0


def http_json_post(url: str, body: dict):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.load(r)


if __name__ == "__main__":
    sys.exit(main())
