#!/usr/bin/env python3
"""Where an on-demand capture's device time went, by the model's own parts.

    python3 scripts/capture_parts.py <artifact dir>

``<artifact dir>`` is what ``POST /v1/profile/capture`` (or ``PROFILE_DIR``)
left behind: a profiler trace (``*.xplane.pb``) and, beside it,
``program_parts.json``: which part of a model (``agent_tpu/obs/trace.py:
PARTS``) every instruction of every program the agent had run belongs to,
as the runtime read it out of the compiled text. Prints device seconds by
part, by program and part, and the heaviest instructions, with the
benchmark's own reduction (``benchmarks/harness/part_times.py``: self time,
so a loop is not counted beside its body). Needs no chip: it reads two
files. Exit 1 where either is missing."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import part_times, trace_reduce   # noqa: E402


def main(argv) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    trace = part_times.newest_trace(argv[0])
    if trace is None:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    parts_file = os.path.join(os.path.dirname(trace), "program_parts.json")
    if not os.path.exists(parts_file):
        print(f"no program_parts.json beside {trace}", file=sys.stderr)
        return 1
    with open(parts_file, "r", encoding="utf-8") as f:
        parts_map = json.load(f)
    # A capture has no marker annotations: the whole trace is the window.
    out = part_times.reduce_parts(trace_reduce.load(trace), parts_map,
                                  window=(0.0, float("inf")))
    busy = out["busy_s"] or float("nan")
    print(f"{trace}\ndevice busy {out['busy_s']:.6f} s on {out['chips']} "
          f"chip(s); in fusions that span parts {out['mixed_s']:.6f} s")
    print(f"\n{'part':<10}{'seconds':>12}{'share %':>10}")
    for part, seconds in sorted(out["parts"].items(), key=lambda kv: -kv[1]):
        print(f"{part:<10}{seconds:>12.6f}{100 * seconds / busy:>10.2f}")
    print(f"\n{'program':<28}{'part':<10}{'seconds':>12}")
    for module, by_part in sorted(out["programs"].items()):
        for part, seconds in sorted(by_part.items(), key=lambda kv: -kv[1]):
            print(f"{module:<28}{part:<10}{seconds:>12.6f}")
    print(f"\n{'program':<28}{'part':<10}{'instruction':<40}{'seconds':>12}")
    for module, part, name, seconds in out["rows"]:
        print(f"{module:<28}{part:<10}{name:<40}{seconds:>12.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
