"""Device time of single operations in a profiler trace, by name: what
``trace_reduce`` (per program, and the ten heaviest operations) does not
give. A Pallas kernel is one ``XLA Ops`` event a call inside its program,
named after the kernel (``pallas_call(name=...)``); a reader that wants a
kernel's time lists a pattern for it under ``OP_PATTERNS`` (data, like
``PROGRAM_PATTERNS``) and the kind that traces hands them here.

Same clock, markers and clipping as ``trace_reduce.reduce``. A trace with
no markers, no device plane or no matching event gives zeros, never an
error: a program without the kernel has nothing to read."""

from __future__ import annotations

import re
from typing import Any, Dict, Mapping

from benchmarks.harness import trace_reduce


def reduce_ops(pd, patterns: Mapping[str, str]) -> Dict[str, Dict[str, Any]]:
    """label → ``{"seconds", "count"}``: the time inside the markers of the
    ``XLA Ops`` events whose name the label's regex finds (clipped to the
    markers, averaged over chips), and how many such events lie wholly
    inside them."""
    out = {label: {"seconds": 0.0, "count": 0} for label in patterns}
    begin, end = trace_reduce.find_markers(pd)
    if begin is None or end is None or end <= begin or not patterns:
        return out
    compiled = {label: re.compile(rx) for label, rx in patterns.items()}
    chips = 0
    for plane in pd.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if trace_reduce.OPS_LINE not in lines:
            continue
        chips += 1
        for e in lines[trace_reduce.OPS_LINE].events:
            a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
            if b <= begin or a >= end:
                continue
            for label, rx in compiled.items():
                if rx.search(e.name):
                    out[label]["seconds"] += (min(b, end) - max(a, begin)) / 1e9
                    out[label]["count"] += int(a >= begin and b <= end)
    for entry in out.values():
        entry["seconds"] /= max(1, chips)
    return out
