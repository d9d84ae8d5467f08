"""At-scale mixed drain: the literal BASELINE.json north-star job shape.

Drains an N-row (default 10M) CSV through BOTH model ops — every row
classified AND summarized — via the real controller/HTTP/agent/pipeline
path, with per-row results streaming to JSONL sinks (``output_uri``) so the
controller carries receipts, not payloads.

Run on the TPU host:

    python scripts/drain_at_scale.py --rows 10000000 \
        --workdir /tmp/drain10m --report DRAIN_AT_SCALE.json

Multi-chip legs (ISSUE 7): ``--agents N`` drains through a fleet of N
device-pinned agent subprocesses (``agent_tpu/agent/fleet.py``; on TPU
hardware pass ``--fleet-platform tpu`` so each member owns disjoint chips
via ``fleet.tpu_process_env``); ``--mesh-dp N`` drains through ONE agent whose
runtime executes dp-sharded over an N-device mesh. Both record per-agent
shard counts and the trace-derived stage/execute overlap per agent, and
exit nonzero if any agent got zero shards.

The report JSON records wall time, per-op rows/sec and device-busy seconds,
shard counts, retry/failure counts, n_chips, and sink row totals — the
artifact PARITY.md cites for the "drains a 10M-row classify+summarize job"
sentence.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CLASSIFY_SHARD = 8192
# SLO objectives for the drain (ISSUE 8): op-keyed, generous p99 (bulk
# shards legitimately run seconds) — the point is recording attainment and
# the verdict in the artifact, not paging a healthy drain.
SLO_SPEC = (
    '[{"name": "classify", "op": "map_classify_tpu",'
    ' "p99_ms": 600000, "availability": 0.999},'
    ' {"name": "summarize", "op": "map_summarize",'
    ' "p99_ms": 600000, "availability": 0.999}]'
)
# Summarize throughput scales with decode rows in flight (measured on v5e,
# payload-size sweep: 4,980 → 8,093 rows/s from 1k → 8k rows, dispatched
# as chained ≤MAX_DECODE_ROWS programs; one single B=8192 program measured
# 9,132 — see ops/map_summarize.MAX_DECODE_ROWS). One shard = one op call.
SUMMARIZE_SHARD = 8192
SUMMARIZE_MAX_NEW = 32


def build_csv(path: str, n_rows: int) -> None:
    if os.path.exists(path):
        return
    tmp = path + ".tmp"
    t0 = time.perf_counter()
    with open(tmp, "w") as f:
        f.write("id,text,risk\n")
        for i in range(n_rows):
            f.write(
                f'{i},"drain record {i} with a payload of text to classify '
                f'and summarize",{i % 89}\n'
            )
    os.replace(tmp, path)
    print(f"csv built: {n_rows} rows, "
          f"{os.path.getsize(path) / 1e6:.0f} MB, "
          f"{time.perf_counter() - t0:.0f}s", flush=True)


def warm_payload_specs(csv_path, n_rows, classify_extra, summarize_extra,
                       warm_out):
    """``[{op, payload}]`` covering BOTH length buckets of BOTH ops (row ids
    grow 1→7 digits across the dataset, crossing a bucket boundary) — the
    single warm-shape definition shared by the in-process warm submissions
    and the fleet members' local pre-lease warmup."""
    specs = []
    for op_name, shard, extra in (
        ("map_classify_tpu", CLASSIFY_SHARD, classify_extra),
        ("map_summarize", SUMMARIZE_SHARD, summarize_extra),
    ):
        starts = [0]
        tail = max(0, n_rows - min(shard, n_rows))
        if tail > 0:
            starts.append(tail)
        for start in starts:
            specs.append({"op": op_name, "payload": {
                **extra,
                "source_uri": csv_path,
                "start_row": start,
                "shard_size": min(shard, n_rows - start),
                "output_uri": warm_out,
            }})
    return specs


def per_agent_shards(controller, job_ids):
    """{agent: executed shard count} over ``job_ids`` (succeeded jobs)."""
    counts = {}
    for jid in job_ids:
        agent = controller.job_snapshot(jid)["agent"]
        if agent:
            counts[agent] = counts.get(agent, 0) + 1
    return counts


def health_report(server_url):
    """Flat per-op SLO attainment / MFU + the verdict off ``GET
    /v1/health`` (ISSUE 8 satellite). None when unreachable — callers FAIL
    the drain on that (the fields were promised, silence is rot)."""
    from agent_tpu.obs.scrape import fetch_health

    health = fetch_health(server_url)
    if health is None:
        return None
    attain = {
        o.get("op", o["objective"]): o.get("attainment")
        for o in health["slo"]["objectives"]
    }
    mfu: dict = {}
    duty: dict = {}
    for name, row in (health.get("agents") or {}).items():
        duty[name] = row.get("duty_cycle")
        for op, v in (row.get("mfu") or {}).items():
            mfu.setdefault(op, []).append(v)
    return {
        "verdict": health["verdict"],
        "attain": attain,
        # Fleet MFU per op = mean across reporting agents.
        "mfu": {
            op: round(sum(vs) / len(vs), 4) for op, vs in mfu.items()
        },
        "duty": duty,
    }


def health_fields(hf):
    """The flat report fields both drain modes record."""
    return {
        "health_verdict": hf["verdict"],
        "slo_attainment_classify": hf["attain"].get("map_classify_tpu"),
        "slo_attainment_summarize": hf["attain"].get("map_summarize"),
        "mfu_classify": hf["mfu"].get("map_classify_tpu"),
        "mfu_summarize": hf["mfu"].get("map_summarize"),
        "duty_cycle_by_agent": hf["duty"],
    }


def overlap_report(server_url):
    """(fleet overlap, per-agent overlap) from the trace window; either may
    be None when tracing is off — callers decide how loud to be."""
    from agent_tpu.obs.scrape import (
        collect_trace_spans,
        overlap_by_process,
        overlap_from_spans,
    )

    spans = collect_trace_spans(server_url)
    if spans is None:
        return None, None
    return overlap_from_spans(spans), overlap_by_process(spans)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--workdir", default="/tmp/drain_at_scale")
    ap.add_argument("--report", default="DRAIN_AT_SCALE.json")
    ap.add_argument("--progress-sec", type=float, default=60.0)
    # Multi-chip legs (ISSUE 7): a fleet of N pinned agent processes, or
    # one dp=N mesh agent. Default (1, 0) keeps the classic in-process leg.
    ap.add_argument("--agents", type=int, default=1)
    ap.add_argument("--devices-per-agent", type=int, default=1)
    ap.add_argument("--mesh-dp", type=int, default=0,
                    help="run ONE agent with MESH_SHAPE=dp=N (N devices)")
    ap.add_argument("--fleet-platform", choices=("cpu", "tpu"),
                    default="cpu",
                    help="fleet pinning mode: cpu = forced-host virtual "
                         "devices; tpu = hardware chips")
    # bf16 is the default: W8A8's dynamic activation quantization costs
    # more than the MXU saves on [B, 256]-thin decode matmuls (measured
    # 3,983 int8 vs 4,980 bf16 rows/s at B=1024); int8 pays off on
    # big-matmul encoders (BERT-base leg 1.21×), not this decode.
    ap.add_argument("--summarize-quant", default="none",
                    choices=("int8", "none"))
    args = ap.parse_args()

    if args.agents > 1 or args.mesh_dp > 1 or args.devices_per_agent > 1:
        return main_fleet(args)

    import requests

    from agent_tpu.agent.app import Agent
    from agent_tpu.agent.pipeline import PipelineRunner
    from agent_tpu.config import AgentConfig, Config
    from agent_tpu.controller.core import Controller
    from agent_tpu.controller.server import ControllerServer
    from agent_tpu.runtime.runtime import get_runtime

    os.makedirs(args.workdir, exist_ok=True)
    csv_path = os.path.join(args.workdir, f"drain_{args.rows}.csv")
    classify_out = os.path.join(args.workdir, "classify_out")
    summarize_out = os.path.join(args.workdir, "summarize_out")
    build_csv(csv_path, args.rows)

    from agent_tpu.config import SloConfig

    runtime = get_runtime()
    controller = Controller(
        lease_ttl_sec=600.0, slo=SloConfig(spec=SLO_SPEC)
    )
    t_start = time.perf_counter()
    with ControllerServer(controller) as server:
        cfg = Config(
            agent=AgentConfig(
                controller_url=server.url,
                agent_name="drain-at-scale",
                tasks=("map_classify_tpu", "map_summarize"),
                idle_sleep_sec=0.0,
            )
        )
        agent = Agent(config=cfg, session=requests.Session(), runtime=runtime)
        agent._profile = {"tier": "at-scale"}

        # ONE payload definition per op, shared verbatim by the warm
        # submissions and the timed submit_csv_job below — a drifted copy
        # would warm a different executable than the drain uses.
        classify_extra = {
            "text_field": "text", "allow_fallback": False,
            "output_uri": classify_out,
        }
        summarize_extra = {
            "text_field": "text", "allow_fallback": False,
            "max_length": SUMMARIZE_MAX_NEW, "output_uri": summarize_out,
            **(
                {"model_config": {"quant": args.summarize_quant}}
                if args.summarize_quant != "none" else {}
            ),
        }

        # Warm the executable cache OUTSIDE the timed window (same
        # methodology as bench.py's drain leg: compile is a once-per-process
        # cost — reference handle-singleton semantics — and a cold ~2-7 min
        # XLA compile mid-drain is compiler time, not drain time). Row ids
        # grow 1→7 digits across the dataset, crossing a length-bucket
        # boundary, so warm shards come from BOTH ends of the CSV — per-op
        # tail positions, so each op warms its own full shard shape.
        # Warm results go to a scratch sink dir: the real sinks must contain
        # EXACTLY the timed job's shards for the post-run validation.
        warm_out = os.path.join(args.workdir, "warm_out")
        warm_specs = warm_payload_specs(
            csv_path, args.rows, classify_extra, summarize_extra, warm_out
        )
        for spec in warm_specs:
            controller.submit(spec["op"], spec["payload"])
        n_warm = len(warm_specs)
        agent.running = True
        warm_done = {}

        def warm_watch():
            while not controller.drained():
                time.sleep(0.05)
            warm_done["ok"] = True
            agent.running = False

        threading.Thread(target=warm_watch, daemon=True).start()
        t_warm = time.perf_counter()
        PipelineRunner(agent, depth=2).run()
        assert warm_done.get("ok"), "warmup drain did not complete"
        # Every warm shard must have SUCCEEDED — a failed warm shard means
        # a cold cache (compile lands in the timed window) and corrupts the
        # warm-exclusion arithmetic in the report.
        warm_results = controller.results()
        warm_bad = [
            j for j, r in warm_results.items()
            if not (isinstance(r, dict) and r.get("ok") is True)
        ]
        assert len(warm_results) == n_warm and not warm_bad, (
            f"warmup failed: {len(warm_results)}/{n_warm} results, "
            f"bad={warm_bad}"
        )
        print(f"warmup done ({time.perf_counter() - t_warm:.0f}s, "
              f"{n_warm} shards, both buckets x both ops)", flush=True)
        agent.running = True
        warm_jobs = set(warm_results)
        # Per-op attribution now scrapes /v1/metrics (fleet task_phase
        # series); the warm shards already counted, so the timed numbers
        # are the scrape delta across the timed window.
        from agent_tpu.obs.scrape import fetch_metrics_text, op_phase_seconds

        drain_ops = ("map_classify_tpu", "map_summarize")
        pre_text = fetch_metrics_text(server.url)
        span_pre = (
            op_phase_seconds(pre_text, drain_ops)
            if pre_text is not None else None
        )
        t_start = time.perf_counter()  # the timed window starts POST-warmup

        controller.submit_csv_job(
            csv_path, total_rows=args.rows, shard_size=CLASSIFY_SHARD,
            map_op="map_classify_tpu", extra_payload=classify_extra,
        )
        controller.submit_csv_job(
            csv_path, total_rows=args.rows, shard_size=SUMMARIZE_SHARD,
            map_op="map_summarize", extra_payload=summarize_extra,
        )
        # Timed-drain shard count and progress EXCLUDE the warm shards
        # (already succeeded in the controller's cumulative counts).
        n_shards = sum(controller.counts().values()) - n_warm
        print(f"submitted {n_shards} shards "
              f"({args.rows} rows x 2 ops)", flush=True)

        done = {}

        def watch():
            last = 0.0
            while not controller.drained():
                time.sleep(1.0)
                now = time.perf_counter()
                c = controller.counts()
                done_n = c.get("succeeded", 0) + c.get("failed", 0) - n_warm
                if now - last >= args.progress_sec:
                    last = now
                    print(
                        f"[{now - t_start:7.0f}s] {json.dumps(c)} "
                        f"({done_n}/{n_shards} shards)",
                        flush=True,
                    )
            done["wall"] = time.perf_counter() - t_start
            agent.running = False

        threading.Thread(target=watch, daemon=True).start()
        PipelineRunner(agent, depth=2).run()
        wall = done.get("wall", time.perf_counter() - t_start)

        from agent_tpu.utils.spans import op_span_ms, result_op

        counts = dict(controller.counts())
        if counts.get("succeeded"):
            counts["succeeded"] -= n_warm  # warm shards are untimed
        ok_results = []
        rows_written = {"map_classify_tpu": 0, "map_summarize": 0}
        not_ok = 0
        for job_id, r in controller.results().items():
            if job_id in warm_jobs:
                continue  # warm shards ran outside the timed window
            if not isinstance(r, dict) or r.get("ok") is not True:
                not_ok += 1
                continue
            ok_results.append(r)
            op = result_op(r)
            if op in rows_written:
                rows_written[op] += int(r.get("rows_written", 0))
        # Per-shard device-side span = dispatch + deferred fetch. Primary
        # source: scraped /v1/metrics fleet series (execute+fetch sums,
        # delta vs the post-warmup scrape); fallback: result-body summing
        # (agent_tpu.utils.spans, shared with bench.py) when scraping is
        # unavailable.
        post_text = fetch_metrics_text(server.url)
        busy_s = {}
        span_source = "scrape"
        if span_pre is not None and post_text is not None:
            span_post = op_phase_seconds(post_text, drain_ops)
            busy_s = {op: span_post[op] - span_pre[op] for op in drain_ops}
        if not any(busy_s.values()):
            span_source = "result_bodies"
            busy_ms = op_span_ms(ok_results, drain_ops)
            busy_s = {op: busy_ms[op] / 1e3 for op in drain_ops}

        # Slowest-job trace (ISSUE 5 satellite) + stage/execute overlap
        # (ISSUE 6 satellite): per-phase attribution and the cross-job
        # concurrency ratio, both from GET /v1/trace/*. A broken trace path
        # FAILS the drain (nonzero exit) rather than silently omitting the
        # breakdown.
        from agent_tpu.obs import trace as obs_trace
        from agent_tpu.obs.scrape import slowest_trace, stage_execute_overlap
        from agent_tpu.obs.trace import phase_breakdown

        trace_line = None
        overlap = None
        if obs_trace.enabled():
            worst = slowest_trace(server.url)
            if worst is None:
                print(
                    "DRAIN FAILED: trace path broken — /v1/traces or "
                    "/v1/trace/{job_id} returned nothing for a drained run",
                    flush=True,
                )
                return 1
            trace_line = phase_breakdown(worst)
            print(f"[slowest shard] {trace_line}", flush=True)
            overlap = stage_execute_overlap(server.url)
            if overlap is None:
                print(
                    "DRAIN FAILED: no closed stage/execute spans in the "
                    "trace window — overlap breakdown unavailable",
                    flush=True,
                )
                return 1
            print(
                f"[overlap] {overlap['overlap_ratio']:.3f} of stage wall "
                f"time hidden behind execute (stage p50 "
                f"{overlap['stage_p50_ms']:.1f} ms vs execute p50 "
                f"{overlap['execute_p50_ms']:.1f} ms)",
                flush=True,
            )
            # Per-agent attribution (ISSUE 7 satellite): trivially one
            # entry here; the fleet leg reports one per member.
            from agent_tpu.obs.scrape import stage_execute_overlap_by_agent

            overlap_by_agent = stage_execute_overlap_by_agent(server.url)
        else:
            overlap_by_agent = None
        agent_shards = per_agent_shards(
            controller,
            [j for j in controller.results() if j not in warm_jobs],
        )
        # Fleet health rollup (ISSUE 8 satellite): verdict + flat per-op
        # attainment/MFU in the artifact; an unreachable /v1/health FAILS
        # the drain instead of silently omitting the promised fields.
        hf = health_report(server.url)
        if hf is None:
            print("DRAIN FAILED: GET /v1/health unreachable", flush=True)
            return 1
        print(f"[health] verdict={hf['verdict']} "
              f"attainment={hf['attain']} mfu={hf['mfu']}", flush=True)

    report = {
        **health_fields(hf),
        "rows": args.rows,
        "ops": ["map_classify_tpu", "map_summarize"],
        "wall_s": round(wall, 1),
        "shards": n_shards,
        "counts": counts,
        "non_ok_results": not_ok,
        "total_rows_per_sec": round(2 * args.rows / wall, 1),
        # "span" = per-shard dispatch + deferred-fetch wait summed per op.
        # Under pipeline overlap this can over- or under-count true device
        # busy time; wall_s / total_rows_per_sec are the primary metrics.
        # (Renamed from the pre-deferred-fetch "device_busy_s" so old
        # reports aren't compared against a different quantity.)
        "span_source": span_source,
        # Per-phase breakdown of the slowest job's assembled trace
        # (GET /v1/trace/{job_id}); None only with TRACE_ENABLED=0.
        "slowest_trace": trace_line,
        # Stage/execute concurrency over the trace window (ISSUE 6): the
        # fraction of stage wall time the staging pool hid behind device
        # execute, with per-phase p50s; None only with TRACE_ENABLED=0.
        "stage_execute_overlap": overlap,
        # Multi-chip accounting (ISSUE 7): who executed what, and each
        # member's own overlap picture.
        "mode": "single",
        "per_agent_shards": agent_shards,
        "stage_execute_overlap_by_agent": overlap_by_agent,
        "classify": {
            "shard_size": CLASSIFY_SHARD,
            "rows_written": rows_written["map_classify_tpu"],
            "device_span_s": round(busy_s["map_classify_tpu"], 1),
            "rows_per_span_sec": round(
                args.rows / busy_s["map_classify_tpu"], 1
            ) if busy_s["map_classify_tpu"] else None,
        },
        "summarize": {
            "shard_size": SUMMARIZE_SHARD,
            "max_new_tokens": SUMMARIZE_MAX_NEW,
            "quant": args.summarize_quant,
            "rows_written": rows_written["map_summarize"],
            "device_span_s": round(busy_s["map_summarize"], 1),
            "rows_per_span_sec": round(
                args.rows / busy_s["map_summarize"], 1
            ) if busy_s["map_summarize"] else None,
        },
        "platform": runtime.platform,
        "n_chips": runtime.n_devices,
    }
    with open(args.report, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report), flush=True)

    ok = (
        counts.get("failed", 0) == 0
        and not_ok == 0
        and rows_written["map_classify_tpu"] == args.rows
        and rows_written["map_summarize"] == args.rows
        # Zero-shard agents fail the drain (ISSUE 7): an idle member means
        # placement is broken even when the rows all landed.
        and bool(agent_shards)
        and all(v > 0 for v in agent_shards.values())
    )
    print("DRAIN", "OK" if ok else "FAILED", flush=True)
    return 0 if ok else 1


def main_fleet(args) -> int:
    """Multi-chip leg: the same classify+summarize drain executed by a
    fleet of pinned agent subprocesses (``--agents N``) or one dp=N mesh
    agent (``--mesh-dp N``), timed post-warmup like the in-process leg."""
    from agent_tpu.agent import fleet as fleet_mod
    from agent_tpu.config import SchedConfig
    from agent_tpu.controller.core import Controller
    from agent_tpu.controller.server import ControllerServer
    from agent_tpu.obs.scrape import (
        fetch_metrics_text,
        op_phase_seconds,
        slowest_trace,
    )
    from agent_tpu.obs import trace as obs_trace
    from agent_tpu.obs.trace import phase_breakdown

    if args.mesh_dp > 1 and args.agents > 1:
        print("--agents and --mesh-dp are alternative modes; pick one",
              flush=True)
        return 2
    mode = "mesh" if args.mesh_dp > 1 else "fleet"
    n_agents = 1 if mode == "mesh" else args.agents
    dev_per = args.mesh_dp if mode == "mesh" else args.devices_per_agent
    mesh_shape = f"dp={args.mesh_dp}" if mode == "mesh" else ""

    os.makedirs(args.workdir, exist_ok=True)
    csv_path = os.path.join(args.workdir, f"drain_{args.rows}.csv")
    classify_out = os.path.join(args.workdir, "classify_out")
    summarize_out = os.path.join(args.workdir, "summarize_out")
    build_csv(csv_path, args.rows)

    classify_extra = {
        "text_field": "text", "allow_fallback": False,
        "output_uri": classify_out,
    }
    summarize_extra = {
        "text_field": "text", "allow_fallback": False,
        "max_length": SUMMARIZE_MAX_NEW, "output_uri": summarize_out,
        **(
            {"model_config": {"quant": args.summarize_quant}}
            if args.summarize_quant != "none" else {}
        ),
    }
    # Fleet members warm LOCALLY (pre-lease, both ops × both length
    # buckets) — compile is per-process and must stay out of the window.
    warm_file = os.path.join(args.workdir, "fleet_warm.json")
    with open(warm_file, "w") as f:
        json.dump(warm_payload_specs(
            csv_path, args.rows, classify_extra, summarize_extra,
            os.path.join(args.workdir, "warm_out"),
        ), f)

    from agent_tpu.config import SloConfig

    controller = Controller(
        lease_ttl_sec=600.0, sched=SchedConfig(policy="fair"),
        slo=SloConfig(spec=SLO_SPEC),
    )
    drain_ops = ("map_classify_tpu", "map_summarize")
    with ControllerServer(controller) as server:
        handle = fleet_mod.spawn_fleet(
            n_agents, dev_per,
            controller_url=server.url,
            tasks="map_classify_tpu,map_summarize",
            platform=args.fleet_platform, name_prefix="drain",
            mesh_shape=mesh_shape, warm_file=warm_file,
            log_dir=os.path.join(args.workdir, "fleet_logs"),
            extra_env={"IDLE_SLEEP_SEC": "0.02"},
        )
        try:
            if not fleet_mod.wait_for_agents(
                controller.agents_summary, handle.names, timeout=1800.0,
                fleet=handle,
            ):
                print(
                    f"DRAIN FAILED: fleet not ready (failures="
                    f"{handle.poll_failures()}); see "
                    f"{args.workdir}/fleet_logs", flush=True,
                )
                return 1
            print(f"fleet ready: {handle.names} "
                  f"({mode}, {dev_per} device(s) each)", flush=True)
            pre_text = fetch_metrics_text(server.url)
            span_pre = (
                op_phase_seconds(pre_text, drain_ops)
                if pre_text is not None else None
            )
            t_start = time.perf_counter()
            shard_ids = []
            for op_name, shard, extra in (
                ("map_classify_tpu", CLASSIFY_SHARD, classify_extra),
                ("map_summarize", SUMMARIZE_SHARD, summarize_extra),
            ):
                ids, _ = controller.submit_csv_job(
                    csv_path, total_rows=args.rows, shard_size=shard,
                    map_op=op_name, extra_payload=extra,
                )
                shard_ids.extend(ids)
            n_shards = len(shard_ids)
            print(f"submitted {n_shards} shards "
                  f"({args.rows} rows x 2 ops)", flush=True)
            last = 0.0
            while not controller.drained():
                time.sleep(1.0)
                if handle.poll_failures():
                    print(
                        f"DRAIN FAILED: fleet member died "
                        f"({handle.poll_failures()})", flush=True,
                    )
                    return 1
                now = time.perf_counter()
                if now - last >= args.progress_sec:
                    last = now
                    print(
                        f"[{now - t_start:7.0f}s] "
                        f"{json.dumps(controller.counts())}", flush=True,
                    )
            wall = time.perf_counter() - t_start

            counts = dict(controller.counts())
            rows_written = {"map_classify_tpu": 0, "map_summarize": 0}
            not_ok = 0
            from agent_tpu.utils.spans import result_op

            for jid in shard_ids:
                r = controller.job_snapshot(jid)["result"]
                if not isinstance(r, dict) or r.get("ok") is not True:
                    not_ok += 1
                    continue
                op = result_op(r)
                if op in rows_written:
                    rows_written[op] += int(r.get("rows_written", 0))
            post_text = fetch_metrics_text(server.url)
            busy_s = {}
            if span_pre is not None and post_text is not None:
                span_post = op_phase_seconds(post_text, drain_ops)
                busy_s = {
                    op: span_post[op] - span_pre[op] for op in drain_ops
                }
            agent_shards = per_agent_shards(controller, shard_ids)
            # Fleet chip accounting: every member pushed its runtime
            # describe() through the lease metrics channel.
            n_chips = 0
            platform = None
            for entry in controller.agents_summary().values():
                dev = (entry.get("metrics") or {}).get("device") or {}
                n_chips += int(dev.get("n_devices") or 0)
                platform = dev.get("platform") or platform
            trace_line = None
            overlap = None
            overlap_by_agent = None
            if obs_trace.enabled():
                worst = slowest_trace(server.url)
                if worst is None:
                    print("DRAIN FAILED: trace path broken for the fleet "
                          "drain", flush=True)
                    return 1
                trace_line = phase_breakdown(worst)
                print(f"[slowest shard] {trace_line}", flush=True)
                overlap, overlap_by_agent = overlap_report(server.url)
                if not overlap_by_agent:
                    print("DRAIN FAILED: no per-agent stage/execute "
                          "overlap assembled", flush=True)
                    return 1
                for name, o in sorted(overlap_by_agent.items()):
                    print(
                        f"[overlap {name}] {o['overlap_ratio']:.3f} hidden "
                        f"(stage p50 {o['stage_p50_ms']:.1f} ms, execute "
                        f"p50 {o['execute_p50_ms']:.1f} ms)", flush=True,
                    )
            # Fleet health rollup (ISSUE 8): same contract as the single
            # leg — the promised fields or a loud failure.
            hf = health_report(server.url)
            if hf is None:
                print("DRAIN FAILED: GET /v1/health unreachable",
                      flush=True)
                return 1
            print(f"[health] verdict={hf['verdict']} "
                  f"attainment={hf['attain']} mfu={hf['mfu']}", flush=True)
        finally:
            handle.stop()

    report = {
        **health_fields(hf),
        "rows": args.rows,
        "ops": list(drain_ops),
        "mode": mode,
        "agents": n_agents,
        "devices_per_agent": dev_per,
        "wall_s": round(wall, 1),
        "shards": n_shards,
        "counts": counts,
        "non_ok_results": not_ok,
        "total_rows_per_sec": round(2 * args.rows / wall, 1),
        "per_agent_shards": agent_shards,
        "slowest_trace": trace_line,
        "stage_execute_overlap": overlap,
        "stage_execute_overlap_by_agent": overlap_by_agent,
        "classify": {
            "shard_size": CLASSIFY_SHARD,
            "rows_written": rows_written["map_classify_tpu"],
            "device_span_s": round(busy_s.get("map_classify_tpu", 0.0), 1),
        },
        "summarize": {
            "shard_size": SUMMARIZE_SHARD,
            "max_new_tokens": SUMMARIZE_MAX_NEW,
            "quant": args.summarize_quant,
            "rows_written": rows_written["map_summarize"],
            "device_span_s": round(busy_s.get("map_summarize", 0.0), 1),
        },
        "platform": platform,
        "n_chips": n_chips,
    }
    with open(args.report, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report), flush=True)

    zero = [a for a, v in agent_shards.items() if v == 0]
    # An agent that executed nothing never appears in the per-job agent
    # fields at all — the absent members are the real zero-shard signal.
    missing = [a for a in handle.names if a not in agent_shards]
    ok = (
        counts.get("failed", 0) == 0
        and not_ok == 0
        and rows_written["map_classify_tpu"] == args.rows
        and rows_written["map_summarize"] == args.rows
        and n_chips >= n_agents * dev_per
        and not zero
        and not missing  # an agent that executed nothing never appears
    )
    if zero or missing:
        print(f"ZERO-SHARD AGENTS: {zero + missing}", flush=True)
    print("DRAIN", "OK" if ok else "FAILED", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
