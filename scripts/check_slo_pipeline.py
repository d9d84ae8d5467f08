#!/usr/bin/env python3
"""CI smoke for the fleet health & SLO engine (ISSUE 8).

Drives a mixed interactive+bulk drain through the real ``Agent`` loop over
``chaos.LoopbackSession`` against a controller with CI-shrunk SLO windows,
then asserts the acceptance bar end to end:

1. healthy traffic (1ms interactive ops on tier 8 + bulk risk_accumulate
   shards) → ``/v1/health`` verdict ``ok``, interactive attainment ≈ 1;
2. an injected latency regression (the probe op sleeps past the p99
   target) drops attainment, drives the burn rate through ``warn`` into
   ``page``, and flips the verdict within one short window — served over
   real HTTP, not just in-process;
3. entering ``page`` auto-dumps BOTH flight-recorder rings (controller at
   the transition, agent on the next granted lease via the piggybacked
   alert), tagged with the breaching objective;
4. clean traffic recovers the verdict to ``ok`` through the hysteresis
   exit (short-window burn below exit_frac × threshold);
5. ``SLO_ENABLED=0`` no-ops the whole path: no tracker, no ``slo_*``
   metric families, health still serves fleet/queue signals;
6. steady-state overhead: rows/sec over a 1024-row-shard drain with the
   SLO engine on stays within 10% of off (best-of-3 interleaved; the bar
   absorbs shared-runner noise. A guard on the CPU runner, not a figure).

Exit 0 = clean; 1 = problems (one per line). Style sibling of
``scripts/check_trace_pipeline.py``: repo-rooted, stdlib-only driver.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile
import time
import urllib.request
from typing import List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from agent_tpu.agent.app import Agent
from agent_tpu.chaos import LoopbackSession
from agent_tpu.config import AgentConfig, Config, SloConfig
from agent_tpu.controller.core import Controller
from agent_tpu.controller.server import ControllerServer

# CI-shrunk windows: the production shape is 5m/1h; the MATH is identical
# (cell width = short/5), so seconds-scale windows pin the same behavior.
WINDOW_SHORT = 2.0
WINDOW_LONG = 8.0
BURN_WARN = 2.0
BURN_PAGE = 6.0

SLO_SPEC = json.dumps([
    {"name": "interactive", "tier": 8, "p99_ms": 150, "availability": 0.9},
    {"name": "bulk", "op": "risk_accumulate", "p99_ms": 60000,
     "availability": 0.9},
])

BULK_SHARDS = 8
BULK_ROWS_PER_SHARD = 16

BENCH_SHARDS = 16
BENCH_ROWS_PER_SHARD = 1024
BENCH_ROUNDS = 3
BENCH_TOLERANCE = 0.90

# The injected-latency probe ships through the designed extension point
# (OPS_PLUGIN_PATH / load_plugins), not a registry monkey-patch.
PLUGIN_SRC = '''\
"""Smoke-only op: payload-controlled latency (the injected regression)."""
import time

from agent_tpu.ops import register_op


@register_op("interactive_probe")
def run(payload, ctx=None):
    time.sleep(float(payload.get("sleep_ms", 1.0)) / 1e3)
    return {"ok": True}
'''


def build_csv(path: str, rows: int) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write("id,text,risk\n")
        for i in range(rows):
            f.write(f'{i},"record {i}",{(i % 13) * 0.5}\n')


def make_controller(enabled: bool = True) -> Controller:
    return Controller(
        lease_ttl_sec=30.0,
        slo=SloConfig(
            enabled=enabled, spec=SLO_SPEC,
            window_short_sec=WINDOW_SHORT, window_long_sec=WINDOW_LONG,
            burn_warn=BURN_WARN, burn_page=BURN_PAGE, burn_exit_frac=0.5,
        ),
    )


def make_agent(controller: Controller, tasks: Tuple[str, ...],
               name: str = "slo-smoke") -> Agent:
    cfg = Config(agent=AgentConfig(
        controller_url="http://loopback", agent_name=name,
        tasks=tasks, max_tasks=4, idle_sleep_sec=0.0, error_backoff_sec=0.0,
    ))
    agent = Agent(config=cfg, session=LoopbackSession(controller))
    agent._profile = {"tier": "slo-smoke"}  # skip hardware probing
    return agent


def drain(controller: Controller, agent: Agent, deadline_s: float = 60.0
          ) -> bool:
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


def interactive_attainment(controller: Controller) -> Optional[float]:
    for obj in controller.slo.evaluate():
        if obj["objective"] == "interactive":
            return obj["attainment"]
    return None


def interactive_state(controller: Controller) -> str:
    return controller.slo.states()["interactive"]


def http_health(server_url: str) -> dict:
    with urllib.request.urlopen(server_url + "/v1/health", timeout=10) as r:
        return json.load(r)


def drain_rows_per_sec(csv_path: str, enabled: bool) -> float:
    rows = BENCH_SHARDS * BENCH_ROWS_PER_SHARD
    controller = make_controller(enabled=enabled)
    controller.submit_csv_job(
        csv_path, total_rows=rows, shard_size=BENCH_ROWS_PER_SHARD,
        map_op="risk_accumulate", extra_payload={"field": "risk"},
        reduce_op="risk_accumulate", collect_partials=True,
    )
    agent = make_agent(controller, tasks=("risk_accumulate",), name="bench")
    t0 = time.perf_counter()
    if not drain(controller, agent, deadline_s=120.0):
        raise RuntimeError(f"bench drain wedged: {controller.counts()}")
    return rows / (time.perf_counter() - t0)


def main() -> int:
    problems: List[str] = []
    tmp = tempfile.mkdtemp(prefix="slo_smoke_")
    os.environ["FLIGHT_RECORDER_DIR"] = tmp

    plugin_path = os.path.join(tmp, "interactive_probe_plugin.py")
    with open(plugin_path, "w", encoding="utf-8") as f:
        f.write(PLUGIN_SRC)
    from agent_tpu.ops import load_plugins

    if "interactive_probe" not in load_plugins(plugin_path):
        from agent_tpu.ops import OPS_LOAD_ERRORS

        print(f"interactive_probe plugin failed to load: {OPS_LOAD_ERRORS}")
        return 1

    csv_path = os.path.join(tmp, "bulk.csv")
    build_csv(csv_path, BULK_SHARDS * BULK_ROWS_PER_SHARD)

    controller = make_controller()
    agent = make_agent(
        controller, tasks=("risk_accumulate", "interactive_probe")
    )

    with ControllerServer(controller) as server:
        # ---- phase 1: healthy mixed traffic ----
        controller.submit_csv_job(
            csv_path, total_rows=BULK_SHARDS * BULK_ROWS_PER_SHARD,
            shard_size=BULK_ROWS_PER_SHARD, map_op="risk_accumulate",
            extra_payload={"field": "risk"},
        )
        for _ in range(12):
            controller.submit(
                "interactive_probe", {"sleep_ms": 1.0}, priority=8,
            )
        if not drain(controller, agent):
            print(f"healthy drain wedged: {controller.counts()}")
            return 1
        attain_healthy = interactive_attainment(controller)
        health = http_health(server.url)
        if health["verdict"] != "ok":
            problems.append(
                f"healthy phase verdict {health['verdict']!r}, want ok "
                f"(reasons={health['reasons']})"
            )
        if attain_healthy is None or attain_healthy < 0.99:
            problems.append(
                f"healthy interactive attainment {attain_healthy}, want ≈1"
            )
        agents_row = health["agents"].get("slo-smoke") or {}
        if agents_row.get("duty_cycle") is None:
            problems.append("health carries no agent duty cycle")

        # ---- phase 2: injected latency regression ----
        t_regress = time.monotonic()
        for _ in range(12):
            controller.submit(
                "interactive_probe", {"sleep_ms": 300.0}, priority=8,
            )
        if not drain(controller, agent):
            print(f"regression drain wedged: {controller.counts()}")
            return 1
        results = controller.slo.evaluate()
        inter = next(
            o for o in results if o["objective"] == "interactive"
        )
        flip_s = time.monotonic() - t_regress
        if inter["attainment"] is None or inter["attainment"] >= 0.5:
            problems.append(
                f"regression did not drop attainment: {inter['attainment']}"
            )
        if inter["burn_rate_short"] < BURN_WARN:
            problems.append(
                f"short burn {inter['burn_rate_short']} never reached the "
                f"warn threshold {BURN_WARN}"
            )
        if inter["state"] != "page":
            problems.append(
                f"regression state {inter['state']!r}, want page "
                f"(burn short={inter['burn_rate_short']}, "
                f"long={inter['burn_rate_long']})"
            )
        health = http_health(server.url)
        if health["verdict"] != "page":
            problems.append(
                f"/v1/health verdict {health['verdict']!r} under "
                "regression, want page"
            )
        elif flip_s > WINDOW_SHORT + 10.0:
            problems.append(
                f"verdict flip took {flip_s:.1f}s — not within one short "
                "window of the regression"
            )
        bulk = next(o for o in results if o["objective"] == "bulk")
        if bulk["state"] != "ok":
            problems.append(
                f"bulk objective collaterally {bulk['state']!r} — "
                "selectors must isolate the breaching class"
            )

        # ---- phase 3: both flight recorders auto-dumped, tagged ----
        if len(controller.slo_dump_paths) != 1:
            problems.append(
                f"controller page dumps: {controller.slo_dump_paths} "
                "(want exactly one)"
            )
        else:
            dump = controller.slo_dump_paths[0]
            if "slo-interactive" not in dump or "tier8" not in dump:
                problems.append(f"controller dump path untagged: {dump}")
            kinds = {
                json.loads(line)["kind"] for line in open(dump)
            }
            if "slo_alert" not in kinds:
                problems.append("controller dump lacks the slo_alert event")
        # The agent dumps on the next granted lease carrying the alert —
        # the regression drain already leased while paging, so the dump
        # must exist by now.
        if len(agent.slo_dump_paths) != 1:
            problems.append(
                f"agent page dumps: {agent.slo_dump_paths} (want exactly "
                "one — the piggybacked alert should have fired it)"
            )
        elif "agent-slo-smoke-slo-interactive" not in agent.slo_dump_paths[0]:
            problems.append(
                f"agent dump path untagged: {agent.slo_dump_paths[0]}"
            )
        stray = [
            p for p in glob.glob(os.path.join(tmp, "agent_tpu_flight_*"))
            if p not in controller.slo_dump_paths
            and p not in agent.slo_dump_paths
        ]
        if stray:
            problems.append(f"unexpected extra dumps: {stray}")

        # ---- phase 4: recovery with hysteresis ----
        recovered = False
        deadline = time.monotonic() + 6.0 * WINDOW_LONG
        while time.monotonic() < deadline:
            for _ in range(4):
                controller.submit(
                    "interactive_probe", {"sleep_ms": 1.0}, priority=8,
                )
            drain(controller, agent, deadline_s=30.0)
            controller.sweep()
            if interactive_state(controller) == "ok":
                recovered = True
                break
            time.sleep(WINDOW_SHORT / 4.0)
        if not recovered:
            problems.append(
                f"verdict never recovered to ok "
                f"(state={interactive_state(controller)})"
            )
        else:
            health = http_health(server.url)
            if health["verdict"] != "ok":
                problems.append(
                    f"post-recovery /v1/health verdict "
                    f"{health['verdict']!r}, want ok"
                )

    # ---- phase 5: SLO_ENABLED=0 no-ops the path ----
    off = make_controller(enabled=False)
    off.submit("interactive_probe", {"sleep_ms": 300.0}, priority=8)
    off_agent = make_agent(off, tasks=("interactive_probe",), name="off")
    if not drain(off, off_agent):
        problems.append("SLO-disabled drain wedged")
    h = off.health_json()
    if h["slo"] != {"enabled": False, "objectives": []}:
        problems.append(f"disabled health still judges: {h['slo']}")
    if h["verdict"] != "ok":
        problems.append(f"disabled verdict {h['verdict']!r}, want ok")
    slo_fams = [k for k in off.metrics.snapshot() if k.startswith("slo_")]
    if slo_fams:
        problems.append(f"disabled controller registered {slo_fams}")

    # ---- phase 6: steady-state overhead on the 1024-row-shard drain ----
    bench_csv = os.path.join(tmp, "bench.csv")
    build_csv(bench_csv, BENCH_SHARDS * BENCH_ROWS_PER_SHARD)
    best = {False: 0.0, True: 0.0}
    for _ in range(BENCH_ROUNDS):
        for mode in (False, True):
            best[mode] = max(best[mode], drain_rows_per_sec(bench_csv, mode))
    ratio = best[True] / best[False] if best[False] else 0.0
    print(
        f"slo overhead: off {best[False]:.0f} rows/s, on "
        f"{best[True]:.0f} rows/s (ratio {ratio:.3f})"
    )
    if ratio < BENCH_TOLERANCE:
        problems.append(
            f"SLO-on drain rate {best[True]:.0f} rows/s is below "
            f"{BENCH_TOLERANCE:.0%} of SLO-off {best[False]:.0f} rows/s"
        )

    if problems:
        for p in problems:
            print(p)
        print(f"FAILED: {len(problems)} problem(s)")
        return 1
    print("slo pipeline smoke check: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
