#!/usr/bin/env python3
"""Builder-run, never run by the benchmark itself: read, on the chip and at
the cell's own size, the numbers that ``correct`` compares — for sound runs
of the program over many seeds and for the control (the program with the
configuration's lower-precision path switched on: ``control.model_config``
of the configuration file laid over its ``model`` group) — in ONE process,
so the backend starts once.

    python3 benchmarks/control.py --workload <name> --seconds <s> \
        --sound 11,12,13 --control 21,22,23 [--dump <directory>]

Prints one JSON line per run with every number compared, its limit and the
verdict, then a summary: the sound runs' largest and the control's smallest
reading of each number. A limit is set between the two (PERF.md). With
``--dump`` what each run compared (served top-k, reference logits) is kept
as ``<workload>-<side>-<seed>.npz`` for a statistic to be tried on."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run                  # noqa: E402
from benchmarks.harness import manifest as mf            # noqa: E402


def seeds(text: str) -> List[int]:
    return [int(s) for s in text.split(",") if s.strip()]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sound", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()
    manifest = mf.load_manifest()
    name = mf.find_cell(manifest, args.workload)["config"]
    lower = mf.load_config(manifest, name)["control"]["model_config"]
    readings: Dict[str, Dict[str, List[float]]] = {}
    for side, seed_list in (("sound", args.sound), ("control", args.control)):
        if side == "control":
            mf.MODEL_OVERRIDES[name] = {**mf.MODEL_OVERRIDES.get(name, {}), **lower}
        for seed in seed_list:
            t0 = time.time()
            try:
                run = bench_run.run_cell(manifest, args.workload, seed,
                                         args.seconds, 0, t_start=t0)
                checks, correct = run["checks"], run["correct"]
                e2e = run["end_to_end"]
                if args.dump and run.get("check_data"):
                    import numpy as np

                    os.makedirs(args.dump, exist_ok=True)
                    np.savez_compressed(os.path.join(
                        args.dump, f"{args.workload}-{side}-{seed}.npz"),
                        **run["check_data"])
            except Exception as exc:  # noqa: BLE001 — a control may crash
                checks, correct, e2e = [], False, {}
                print(json.dumps({"side": side, "seed": seed,
                                  "error": f"{type(exc).__name__}: {exc}"[:500]}),
                      flush=True)
            finally:
                # The params store never evicts: give the tenants' models
                # back before the next run builds its own.
                from agent_tpu.runtime.runtime import get_runtime

                get_runtime().clear_params()
            for c in checks:
                readings.setdefault(c["number"], {}).setdefault(
                    side, []).append(float(c["value"]))
            print(json.dumps({"side": side, "seed": seed, "correct": correct,
                              "checks": checks, "end_to_end": e2e,
                              "took_s": time.time() - t0}), flush=True)
    summary = {
        number: {
            "sound_max": max(sides["sound"]) if sides.get("sound") else None,
            "sound_all": sides.get("sound"),
            "control_min": min(sides["control"]) if sides.get("control") else None,
            "control_all": sides.get("control"),
        } for number, sides in readings.items()}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
