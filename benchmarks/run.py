#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs ONE cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and,
traced, ``breakdown``. Everything that belongs to one configuration, traffic
mix or per-layer metric is a file found by the name in the manifest
(``benchmarks/harness/manifest.py``); this file dispatches on the traffic
file's ``kind`` and knows no cell by name.

There is no CPU path: without the accelerator the cell asks for, or on a
device the peaks table does not know, the run exits non-zero."""

from __future__ import annotations

import time

T_START = time.time()   # set-up counts from here: process start

import argparse        # noqa: E402
import os              # noqa: E402
import sys             # noqa: E402
import traceback       # noqa: E402
from typing import Any, Dict, Optional, Sequence  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest as mf   # noqa: E402
from benchmarks.harness import peaks, stack     # noqa: E402


def layer_metrics(manifest: Dict[str, Any], cell_name: str):
    """``(entry, module)`` per per-layer metric of the cell."""
    return [(m, mf.load_layer_metric(m["name"]))
            for m in mf.metrics_of_cell(manifest, cell_name, "per_layer")]


def run_cell(manifest: Dict[str, Any], workload: str, seed: int,
             seconds: float, trace: int,
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """Drive one cell; returns the kind's ``run`` record with ``metrics``
    (name → {value, unit}) filled for the requested mode."""
    cell = mf.find_cell(manifest, workload)
    config = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    readers = layer_metrics(manifest, workload)
    patterns: Dict[str, str] = {}
    for _, module in readers:
        patterns.update(getattr(module, "PROGRAM_PATTERNS", {}))
    ctx = {
        "manifest": manifest, "cell": cell, "config": config,
        "traffic": traffic, "seed": seed, "seconds": seconds,
        "trace": trace, "program_patterns": patterns,
        "t_start": T_START if t_start is None else t_start,
    }
    run = mf.load_kind(traffic["kind"]).run_cell(ctx)
    run["peaks"] = (peaks.lookup(run["device"]["kind"])
                    if run["device"]["platform"] == "tpu" else None)
    # What the per-layer readers of set-up read in this run, traced or not:
    # the parts of ``setup_s`` are told apart from the runs that time it.
    stack.emit("setup_layers", **{
        entry["name"]: module.read(run) for entry, module in readers
        if entry["moves"] == "setup_s"})
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for entry, module in readers:
            value = module.read(run)
            if value is not None:
                metrics[entry["name"]] = {
                    "value": float(value), "unit": entry["unit"]}
    else:
        for entry in mf.metrics_of_cell(manifest, workload, "end_to_end"):
            metrics[entry["name"]] = {
                "value": float(run["end_to_end"][entry["name"]]),
                "unit": entry["unit"]}
    run["metrics"] = metrics
    return run


def device_block(run: Dict[str, Any], trace: int) -> Dict[str, Any]:
    device = dict(run["device"], memory_peak_bytes=run["memory_peak_bytes"])
    if trace and run.get("trace"):
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
    return device


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        manifest = mf.load_manifest()
        run = run_cell(manifest, args.workload, args.seed, args.seconds,
                       args.trace)
    except BaseException as exc:  # noqa: BLE001 — reported, then non-zero
        traceback.print_exc()
        stack.emit("failed", error=f"{type(exc).__name__}: {exc}"[:2000],
                   correct=False)
        return 1
    breakdown = None
    if args.trace and run.get("trace"):
        breakdown = {"device_ops": run["trace"]["device_ops"],
                     "idle_gaps": run["trace"]["idle_gaps"]}
    # Every number compared beside its limit: the last lines of stderr, and
    # the last key of the result line.
    for c in run["checks"]:
        stack.emit("compared", number=c["number"], value=c["value"],
                   limit=c["limit"], ok=c["ok"])
        print(f"compared {c['number']} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'NOT ok'}", file=sys.stderr, flush=True)
    stack.print_result(run["correct"], run["attempted"], run["failed"],
                       run["metrics"], device_block(run, args.trace),
                       breakdown, compared={
                           c["number"]: {"value": c["value"], "limit": c["limit"]}
                           for c in run["checks"]})
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # Daemon threads of the program (HTTP keep-alives, samplers) must not
    # hold the process: every child is already stopped and waited for.
    os._exit(code)
