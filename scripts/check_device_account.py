#!/usr/bin/env python3
"""On-chip check of the agent's own account of the chip's time (ISSUE 24).

    python3 scripts/check_device_account.py --workload bert-base.drain-long \
        --seed 123 [--seconds 10] [--overhead]

Runs ONE traced cell of the benchmark (``benchmarks/run.py``'s own
``run_cell``, unchanged: this script only taps what the run produces) and
holds the program's counters against the benchmark's trace of the same run:

- ``agent_device_busy.drain`` against ``100 * device.busy_s / window_s``;
- ``xla_executables_in_window.drain`` against ``compiles_in_window.drain``,
  ``xla_compile_s.setup`` against what the benchmark's own listener held at
  the warm-up's end (``warm_up.seconds`` of the ``window`` line: the same
  events; ``all_compiles`` is read at the run's end and also holds the
  benchmark's float32 reference, compiled after the window outside any task);
- the busy seconds the window gained over the window's length; and the
  ``device_duty_cycle`` gauge at the window's end where ``--seconds`` covers
  the gauge's own 60 s (a shorter window leaves set-up, where the chip waits
  between warm shards, inside the gauge's reach: reported, not judged);
- ``usage.device_s`` of the window's accepted shards against the window;
- the ``agent.*`` annotations on every host thread's profiler line, and for
  the 10 longest device gaps the annotation open on the device thread,
  beside the span name ``trace_reduce.attribute`` gives;
- bytes the new spans add to a result post;
- the stats one event of the device's ``XLA Ops`` line carries (whether a
  ``jax.named_scope`` could reach the reduction: ROADMAP S2).

``--overhead`` times the phase helper alone (no profiler session, then one).
Prints one JSON object per line; the last is ``{"account": ...}``. Exit 1
when a check above is missed. There is no CPU path: like the benchmark it
fails without the chip. The harness hands out neither its agent nor its
``window`` line, and its files are not this script's to edit: three taps
wrap what the run calls and pass everything through unchanged."""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time
from typing import Any, Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from agent_tpu.agent.app import DUTY_WINDOW_SEC    # noqa: E402
from agent_tpu.obs.metrics import get_registry     # noqa: E402
from benchmarks import run as bench_run            # noqa: E402
from benchmarks.harness import manifest as mf      # noqa: E402
from benchmarks.harness import stack, trace_reduce  # noqa: E402


def emit(what: str, **fields: Any) -> None:
    print(json.dumps({what: fields}, sort_keys=True, default=str), flush=True)


def series_sum(snap: Dict[str, Any], name: str, **labels: str) -> float:
    return sum(float(s["value"]) for s in (snap.get(name) or {}).get("series", [])
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def helper_overhead() -> Dict[str, float]:
    """Nanoseconds per enter+exit of ``obs.trace.phase``: the annotation
    alone and every sink, without a profiler session and inside one."""
    import jax

    from agent_tpu.obs import trace as obs_trace
    from agent_tpu.obs.metrics import MetricsRegistry

    jax.devices()
    ctx = obs_trace.TraceContext(
        trace_id="job-o", tracer=obs_trace.SpanBuffer(),
        registry=MetricsRegistry(), op="op_o")

    def loop(n: int, **kw: Any) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with obs_trace.phase("fetch", ctx, **kw):
                pass
        return (time.perf_counter() - t0) / n * 1e9

    out = {"annotation_only_ns": loop(200_000, histogram=False, span=False),
           "all_sinks_ns": loop(100_000)}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["annotation_only_in_session_ns"] = loop(
                20_000, histogram=False, span=False)
            out["all_sinks_in_session_ns"] = loop(20_000)
        finally:
            jax.profiler.stop_trace()
    return out


def host_lines(pd) -> List[Tuple[str, List[Tuple[str, float, float]]]]:
    """``(line name, [(agent.* event, start, end)])`` per host thread."""
    out = []
    for plane in pd.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns))
                      for e in line.events if e.name.startswith("agent.")]
            if events:
                out.append((line.name, events))
    return out


def device_ops_line(pd):
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    return line
    return None


def device_gaps(pd, begin: float, end: float) -> List[Tuple[float, float]]:
    line = device_ops_line(pd)
    if line is None:
        return []
    busy = trace_reduce.union(trace_reduce.clip(
        ((float(e.start_ns), float(e.start_ns + e.duration_ns))
         for e in line.events), begin, end))
    return trace_reduce.gaps(busy, begin, end)


def op_event_stats(pd) -> Dict[str, List[str]]:
    """Stat names of the longest event of the ``XLA Ops`` line."""
    line = device_ops_line(pd)
    events = list(line.events) if line is not None else []
    if not events:
        return {}
    longest = max(events, key=lambda e: e.duration_ns)
    return {longest.name[:80]: sorted(str(k) for k, _ in longest.stats)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--overhead", action="store_true")
    args = ap.parse_args()

    # ---- taps (read, never change) ------------------------------------
    usage: List[Tuple[float, str, Dict[str, Any]]] = []
    tapped: Dict[str, Any] = {}
    load_kind = mf.load_kind

    def load_kind_tapped(kind: str):
        module = load_kind(kind)
        inner = module.job_snapshot

        def job_snapshot(url: str, job_id: str):
            snap = inner(url, job_id)
            body = snap.get("result") if isinstance(snap, dict) else None
            if isinstance(body, dict):
                usage.append((time.time(), job_id, body.get("usage") or {}))
            return snap

        module.job_snapshot = job_snapshot
        emit_inner = module.emit

        def emit_tapped(what: str, **fields: Any) -> None:
            if what == "window":
                tapped["window_line"] = fields
            emit_inner(what, **fields)

        module.emit = emit_tapped
        return module

    mf.load_kind = load_kind_tapped
    reduce_inner = stack.Tracer.reduce

    def reduce_tapped(self, agent, program_patterns):
        tapped["tracer"], tapped["agent"] = self, agent
        return reduce_inner(self, agent, program_patterns)

    stack.Tracer.reduce = reduce_tapped

    manifest = mf.load_manifest()
    run = bench_run.run_cell(manifest, args.workload, args.seed,
                             args.seconds, 1)
    metrics = {k: v["value"] for k, v in run["metrics"].items()}
    emit("metrics", **metrics)
    m0, m1 = run["agent_metrics"]
    trace = run["trace"]
    problems: List[str] = []

    # ---- device busy: the agent's account against the trace ------------
    trace_busy = 100.0 * trace["busy_s"] / trace["window_s"]
    agent_busy = metrics.get("agent_device_busy.drain")
    if agent_busy is None or abs(agent_busy - trace_busy) > 1.0 \
            or agent_busy > 100.0:
        problems.append(f"agent_device_busy {agent_busy} vs trace {trace_busy}")
    busy_gain = (series_sum(m1, "device_busy_seconds_total")
                 - series_sum(m0, "device_busy_seconds_total"))
    if busy_gain <= 0.95 * run["window_s"]:
        problems.append(
            f"busy seconds gained {busy_gain} in a window of {run['window_s']}")
    duty = series_sum(m1, "device_duty_cycle")
    duty_judged = args.seconds >= DUTY_WINDOW_SEC + 5.0
    if duty_judged and duty <= 0.95:
        problems.append(f"device_duty_cycle at the window's end {duty}")
    in_window = [u for t, _, u in usage if t >= run["t_close"]]
    billed = sum(float(u.get("device_s", 0.0)) for u in in_window)
    if abs(billed - run["window_s"]) > 0.02 * run["window_s"]:
        problems.append(f"usage.device_s sums to {billed} of {run['window_s']}")
    states = {
        s["labels"]["state"]: s["value"] - series_sum(
            m0, "device_thread_seconds_total", state=s["labels"]["state"])
        for s in (m1.get("device_thread_seconds_total") or {}).get("series", [])}

    # ---- executables: the program's count against the benchmark's ------
    if metrics.get("xla_executables_in_window.drain") != \
            metrics.get("compiles_in_window.drain"):
        problems.append("xla_executables_in_window != compiles_in_window")
    warm_up_s = float(tapped["window_line"]["warm_up"]["seconds"])
    compile_s = metrics.get("xla_compile_s.setup")
    if compile_s is None or abs(compile_s - warm_up_s) > 0.01 * warm_up_s:
        problems.append(
            f"xla_compile_s.setup {compile_s} vs warm_up.seconds {warm_up_s}")
    process_compile_s = series_sum(
        get_registry().snapshot(), "runtime_compile_seconds_total")

    # ---- the annotations, and the ten longest device gaps --------------
    tracer, agent = tapped["tracer"], tapped["agent"]
    paths = sorted(glob.glob(os.path.join(
        tracer.directory, "plugins", "profile", "*", "*.xplane.pb")))
    pd = trace_reduce.load(paths[-1])
    begin, end = trace_reduce.find_markers(pd)
    lines = host_lines(pd)
    per_line = [sorted({n for n, _, _ in ev}) for _, ev in lines]
    device_line = next((ev for _, ev in lines
                        if any(n == "agent.dispatch" for n, _, _ in ev)), [])
    offset = begin - float(tracer.begin_wall_ns)
    spans = [(n, a + offset, b + offset) for n, a, b in agent.host_spans(
        tracer.begin_wall_ns / 1e9, tracer.end_wall_ns / 1e9)]
    table = []
    for gap in sorted(device_gaps(pd, begin, end),
                      key=lambda g: g[0] - g[1])[:10]:
        table.append({
            "gap_us": (gap[1] - gap[0]) / 1e3,
            "at_ms": (gap[0] - begin) / 1e6,
            "device_thread": trace_reduce.attribute(gap, device_line),
            "idle_gaps_name": trace_reduce.attribute(gap, spans),
        })
    wanted = {"agent.stage", "agent.dispatch", "agent.fetch",
              "agent.post_http", "agent.lease", "agent.wait_post"}
    seen = {n for names in per_line for n in names}
    if not wanted <= seen:
        problems.append(f"annotations missing: {sorted(wanted - seen)}")

    # ---- what the two new spans add to a result post -------------------
    kept = [s for s in agent.tracer.kept
            if run["t_open"] <= float(s.get("start_wall") or 0) <= run["t_close"]]
    grown = sum(len(json.dumps(s)) + 2 for s in kept
                if s.get("name") in ("fetch", "post_http"))
    posted = sum(len(json.dumps(s)) + 2 for s in kept)

    account = {
        "workload": args.workload, "seed": args.seed,
        "window_s": run["window_s"], "shards": run["shards"],
        "trace_busy_pct": trace_busy, "agent_busy_pct": agent_busy,
        "idle_share_trace": trace["idle_share"],
        "device_duty_cycle_at_close": duty,
        "device_duty_cycle_judged": duty_judged,
        "busy_gain_over_window": busy_gain / run["window_s"],
        "benchmark_listener_warm_up": tapped["window_line"]["warm_up"],
        "benchmark_listener_all_compiles":
            tapped["window_line"]["all_compiles"],
        "device_mfu_at_close": series_sum(m1, "device_mfu"),
        # The gauge is cumulative from the agent's start (set-up's blocking
        # work dilutes it); the window alone:
        "device_mfu_in_window": (
            (series_sum(m1, "device_flops_total")
             - series_sum(m0, "device_flops_total"))
            / busy_gain / run["peaks"]["bf16_flops_per_s"]
            if busy_gain > 0 and run.get("peaks") else None),
        "usage_device_s_sum": billed, "usage_shards": len(in_window),
        "busy_counter_gain_s": busy_gain,
        "idle_counter_gain_s": (
            series_sum(m1, "device_idle_seconds_total")
            - series_sum(m0, "device_idle_seconds_total")),
        "device_thread_states_gain_s": states,
        "agent_compile_s_at_open": series_sum(
            m0, "runtime_compile_seconds_total"),
        "agent_compile_s_at_close": series_sum(
            m1, "runtime_compile_seconds_total"),
        "agent_executables_at_open": series_sum(
            m0, "runtime_xla_executables_total"),
        "agent_cache_hits_at_open": series_sum(
            m0, "runtime_xla_cache_hits_total"),
        "process_registry_compile_s_at_end": process_compile_s,
        "params_s_at_open": series_sum(m0, "runtime_params_seconds_total"),
        "annotations_per_host_line": per_line,
        "longest_device_gaps": table,
        "xla_ops_event_stats": op_event_stats(pd),
        "span_bytes_per_shard": {
            "fetch_and_post_http": grown / max(1, run["shards"]),
            "all_spans": posted / max(1, run["shards"])},
        "correct": run["correct"], "failed": run["failed"],
        "problems": problems,
    }
    if args.overhead:
        account["phase_helper_ns"] = helper_overhead()
    emit("account", **account)
    return 1 if problems or not run["correct"] else 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
