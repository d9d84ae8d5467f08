#!/usr/bin/env python3
"""Multi-chip drain smoke (ISSUE 7) — the CI gate for fleet and mesh mode.

Four checks on the forced-host CPU shape (4 virtual devices):

1. **Fleet-of-2 is bit-identical**: two agent subprocesses, each pinned to a
   disjoint 2-device slice (``CHIP_SLICE``), drain a sharded classify job
   from one fair-scheduled controller over real HTTP; per-shard
   indices/scores equal the 1-chip reference drain exactly, and EVERY fleet
   member executed at least one shard (the fair scheduler's idle-preference
   spreading, not one agent hoovering the queue).
2. **dp=4 mesh is bit-identical**: one agent whose runtime owns all 4
   devices as a ``dp=4`` mesh executes the same shards dp-sharded
   (``runtime.put_batch`` → ``NamedSharding(P("dp"))`` end-to-end, double-
   buffered feed and binary wire intact) with identical results.
3. **Scaling sanity floor**: fleet-of-2 rows/sec ÷ (2 × 1-chip rows/sec)
   is recorded and must clear a floor — 0.45 with ≥3 host cores (CI), 0.15
   on starved single-core boxes (throughput must at least be conserved).
   A sanity floor on virtual devices, not a speed figure: how a fleet of
   four pinned agents scales on real chips is not measured on the present
   tree (PERF.md §7, row 2).
4. **MPMD pipeline chain**: summarize's encoder and decoder run as separate
   ops on DIFFERENT agents (``summarize_encode`` / ``summarize_decode``)
   chained through controller dependency gating (``after`` +
   ``collect_partials``) — the stretch leg of arXiv 2412.14374 over the
   existing lease protocol — and the chained summaries equal the monolithic
   ``map_summarize`` output.

Exit 0 = all clean; 1 = problems (listed one per line).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from typing import Any, Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = {
    "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
    "max_len": 64, "dtype": "float32", "n_classes": 16,
}
TINY_S2S = {
    "d_model": 32, "n_heads": 4, "n_enc_layers": 1, "n_dec_layers": 1,
    "d_ff": 64, "max_src_len": 64, "max_tgt_len": 16, "dtype": "float32",
}
ROWS, SHARD = 2048, 64          # 32 shards per drain
DRAIN_DEADLINE_SEC = 420.0
READY_TIMEOUT_SEC = 300.0

# (mode, n_agents, devices_per_agent, MESH_SHAPE)
MODES: Tuple[Tuple[str, int, int, str], ...] = (
    ("chip1", 1, 1, ""),
    ("fleet2", 2, 2, ""),
    ("mesh4", 1, 4, "dp=4"),
)


def build_csv(path: str, rows: int) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write("id,text\n")
        for i in range(rows):
            f.write(f'{i},"multichip smoke row {i} with a text payload"\n')


def _tail_logs(log_dir: str, n: int = 1500) -> List[str]:
    out = []
    try:
        for name in sorted(os.listdir(log_dir)):
            with open(os.path.join(log_dir, name), "rb") as f:
                data = f.read()[-n:]
            out.append(f"--- {name} ---\n{data.decode(errors='replace')}")
    except OSError:
        pass
    return out


def run_mode(
    mode: str, n_agents: int, devices_per_agent: int, mesh_shape: str,
    csv: str, extra: Dict[str, Any], tmp: str,
) -> Tuple[List[str], Dict[str, Any]]:
    """One drain in one mode → (problems, record)."""
    from agent_tpu.agent import fleet
    from agent_tpu.config import SchedConfig
    from agent_tpu.controller.core import Controller
    from agent_tpu.controller.server import ControllerServer

    problems: List[str] = []
    record: Dict[str, Any] = {
        "mode": mode, "n_agents": n_agents,
        "n_chips": n_agents * devices_per_agent,
    }
    warm_file = os.path.join(tmp, f"warm_{mode}.json")
    with open(warm_file, "w", encoding="utf-8") as f:
        json.dump([{
            "op": "map_classify_tpu",
            "payload": {**extra, "source_uri": csv, "start_row": 0,
                        "shard_size": SHARD},
        }], f)
    log_dir = os.path.join(tmp, f"logs_{mode}")
    # The fair policy is the one under test: idle-preference and
    # queue_depth-aware grants are what spread shards across the fleet.
    controller = Controller(
        lease_ttl_sec=600.0, sched=SchedConfig(policy="fair")
    )
    server = ControllerServer(controller).start()
    handle = fleet.spawn_fleet(
        n_agents, devices_per_agent,
        controller_url=server.url, tasks="map_classify_tpu",
        platform="cpu", name_prefix=mode, mesh_shape=mesh_shape,
        warm_file=warm_file, log_dir=log_dir,
        extra_env={
            "IDLE_SLEEP_SEC": "0.02",
            # One "chip" must not borrow the whole host's BLAS pool, or the
            # 1-chip reference silently uses N cores and every scaling
            # ratio deflates.
            "OMP_NUM_THREADS": "1",
            "OPENBLAS_NUM_THREADS": "1",
        },
    )
    try:
        if not fleet.wait_for_agents(
            controller.agents_summary, handle.names,
            timeout=READY_TIMEOUT_SEC, fleet=handle,
        ):
            return (
                [f"{mode}: fleet not ready (alive={handle.alive()}, "
                 f"failures={handle.poll_failures()})"] + _tail_logs(log_dir),
                record,
            )
        t0 = time.perf_counter()
        shard_ids, _ = controller.submit_csv_job(
            csv, total_rows=ROWS, shard_size=SHARD,
            map_op="map_classify_tpu", extra_payload=extra,
        )
        deadline = time.monotonic() + DRAIN_DEADLINE_SEC
        while not controller.drained():
            if time.monotonic() > deadline:
                return (
                    [f"{mode}: drain did not finish: {controller.counts()}"]
                    + _tail_logs(log_dir),
                    record,
                )
            if handle.poll_failures():
                return (
                    [f"{mode}: fleet member died mid-drain: "
                     f"{handle.poll_failures()}"] + _tail_logs(log_dir),
                    record,
                )
            time.sleep(0.02)
        wall = time.perf_counter() - t0
        counts = controller.counts()
        if counts != {"succeeded": ROWS // SHARD}:
            problems.append(f"{mode}: bad terminal counts {counts}")
        per_agent: Dict[str, int] = {name: 0 for name in handle.names}
        results: Dict[int, Any] = {}
        for jid in shard_ids:
            snap = controller.job_snapshot(jid)
            r = snap["result"]
            if not (isinstance(r, dict) and r.get("ok") is True):
                problems.append(f"{mode}: shard {jid} non-ok result")
                continue
            results[snap_start(controller, jid)] = (
                r.get("indices"), r.get("scores")
            )
            if snap["agent"] in per_agent:
                per_agent[snap["agent"]] += 1
        record.update(
            rows_per_sec=round(ROWS / wall, 1),
            wall_s=round(wall, 2),
            per_agent_shards=per_agent,
        )
        zero = [a for a, n in per_agent.items() if n == 0]
        if zero:
            problems.append(
                f"{mode}: agent(s) got ZERO shards: {zero} "
                f"(per-agent {per_agent})"
            )
        record["results"] = results
    finally:
        handle.stop()
        server.stop()
    return problems, record


def snap_start(controller, job_id: str) -> int:
    return int(controller.job(job_id).payload["start_row"])


def check_fleet_and_mesh(tmp: str) -> Tuple[List[str], Dict[str, Any]]:
    problems: List[str] = []
    extra = {"text_field": "text", "allow_fallback": False,
             "result_format": "columnar", "model_config": dict(TINY),
             "topk": 3}
    csv = os.path.join(tmp, "rows.csv")
    build_csv(csv, ROWS)
    records: Dict[str, Dict[str, Any]] = {}
    for mode, n_agents, dev_per, mesh in MODES:
        mode_problems, record = run_mode(
            mode, n_agents, dev_per, mesh, csv, extra, tmp
        )
        problems += mode_problems
        records[mode] = record
        if mode_problems:
            return problems, records  # later checks compare against chip1

    ref = records["chip1"].pop("results")
    for mode in ("fleet2", "mesh4"):
        got = records[mode].pop("results")
        if got != ref:
            diverged = sorted(
                start for start in ref
                if got.get(start) != ref[start]
            )[:5]
            problems.append(
                f"{mode}: NOT bit-identical to the 1-chip reference "
                f"(first diverging shards at start_row {diverged})"
            )
        else:
            records[mode]["bit_identical"] = True

    r1 = records["chip1"].get("rows_per_sec") or 0.0
    r2 = records["fleet2"].get("rows_per_sec") or 0.0
    eff = r2 / (2 * r1) if r1 else 0.0
    records["fleet2"]["scaling_efficiency"] = round(eff, 3)
    floor = 0.45 if (os.cpu_count() or 1) >= 3 else 0.15
    if eff < floor:
        problems.append(
            f"fleet2 scaling_efficiency {eff:.3f} below the sanity floor "
            f"{floor} (chip1 {r1} vs fleet2 {r2} rows/s, "
            f"{os.cpu_count()} cores)"
        )
    if not problems:
        print(json.dumps({
            "check": "fleet_and_mesh", "ok": True,
            "modes": {
                m: {k: v for k, v in rec.items() if k != "results"}
                for m, rec in records.items()
            },
        }, sort_keys=True))
    return problems, records


def check_mpmd_pipeline() -> List[str]:
    """Encoder and decoder stages on DIFFERENT agents, chained through
    controller dep-gating; output equals the monolithic op."""
    from agent_tpu.agent.app import Agent
    from agent_tpu.chaos import LoopbackSession
    from agent_tpu.config import AgentConfig, Config
    from agent_tpu.controller.core import Controller
    from agent_tpu.ops import get_op
    from agent_tpu.runtime.context import OpContext
    from agent_tpu.runtime.runtime import get_runtime

    problems: List[str] = []
    texts = [f"mpmd pipeline row {i} with text to summarize"
             for i in range(96)]
    shards = [texts[i:i + 32] for i in range(0, len(texts), 32)]
    runtime = get_runtime()

    # Monolithic reference: the fused map_summarize drain of the same rows.
    reference: List[str] = []
    for shard in shards:
        out = get_op("map_summarize")(
            {"texts": shard, "max_length": 8,
             "model_config": dict(TINY_S2S)},
            OpContext(runtime=runtime),
        )
        if not out.get("ok"):
            return [f"mpmd: monolithic reference failed: {str(out)[:200]}"]
        reference.extend(out["summaries"])

    controller = Controller()
    decode_ids = []
    for i, shard in enumerate(shards):
        enc_id = controller.submit(
            "summarize_encode",
            {"texts": shard, "model_config": dict(TINY_S2S)},
            job_id=f"enc-{i}",
        )
        decode_ids.append(controller.submit(
            "summarize_decode",
            {"max_length": 8, "model_config": dict(TINY_S2S),
             "__collect_partials__": True},
            job_id=f"dec-{i}",
            after=[enc_id],
        ))

    def stage_agent(name: str, tasks: Tuple[str, ...]) -> Agent:
        agent = Agent(
            config=Config(agent=AgentConfig(
                controller_url="http://loopback", agent_name=name,
                tasks=tasks, idle_sleep_sec=0.0,
            )),
            session=LoopbackSession(controller), runtime=runtime,
        )
        agent._profile = {"tier": "smoke"}
        return agent

    enc_agent = stage_agent("mpmd-enc", ("summarize_encode",))
    dec_agent = stage_agent("mpmd-dec", ("summarize_decode",))
    deadline = time.monotonic() + 240.0
    while not controller.drained():
        if time.monotonic() > deadline:
            return [f"mpmd: chain did not drain: {controller.counts()}"]
        enc_agent.step()
        dec_agent.step()

    chained: List[str] = []
    for jid in decode_ids:
        snap = controller.job_snapshot(jid)
        if snap["agent"] != "mpmd-dec":
            problems.append(
                f"mpmd: decode job {jid} ran on {snap['agent']!r}, "
                "not the decode-stage agent"
            )
        r = snap["result"]
        if not (isinstance(r, dict) and r.get("ok") is True):
            return [f"mpmd: decode job {jid} failed: {str(r)[:200]}"]
        chained.extend(r["summaries"])
    for i in range(len(shards)):
        if controller.job_snapshot(f"enc-{i}")["agent"] != "mpmd-enc":
            problems.append(f"mpmd: encode job enc-{i} ran on the wrong agent")
    if chained != reference:
        n_diff = sum(1 for a, b in zip(chained, reference) if a != b)
        problems.append(
            f"mpmd: chained summaries diverged from monolithic "
            f"({n_diff}/{len(reference)} rows differ)"
        )
    if not problems:
        print(json.dumps({
            "check": "mpmd_pipeline", "ok": True, "rows": len(reference),
            "stages": {"encode": "mpmd-enc", "decode": "mpmd-dec"},
            "identical_to_monolithic": True,
        }, sort_keys=True))
    return problems


def main() -> int:
    t0 = time.monotonic()
    problems: List[str] = []
    with tempfile.TemporaryDirectory(prefix="multichip_") as tmp:
        mode_problems, _records = check_fleet_and_mesh(tmp)
        problems += mode_problems
    problems += check_mpmd_pipeline()
    elapsed = round(time.monotonic() - t0, 1)
    if problems:
        for p in problems:
            print(p)
        print(f"check_multichip_drain: FAILED ({len(problems)} problem(s), "
              f"{elapsed}s)")
        return 1
    print(f"check_multichip_drain: OK ({elapsed}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
