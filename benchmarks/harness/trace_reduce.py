"""From a profiler trace (``.xplane.pb``) to device busy/idle, per-program
time, the heaviest device operations and the idle gaps laid against what the
host was doing. Kept with the benchmark so every PR computes the same number
the same way; checked on ``benchmarks/fixtures/fixture.xplane.pb.gz``.

What one v5e trace looks like (looked at by hand, PR 23): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<function>(<fingerprint>)``) and ``XLA Ops``
(one event per HLO operation); a plane ``/host:CPU`` with one line per host
thread, where ``jax.profiler.TraceAnnotation`` events appear under their own
name. All planes share one clock, nanoseconds from the start of the session.
The benchmark writes two marker annotations around the traced interval and
notes the wall clock inside each, which puts wall-clock host spans on the
trace's clock."""

from __future__ import annotations

import gzip
import re
from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
MARKER_BEGIN = "bench_marker_begin"
MARKER_END = "bench_marker_end"
SHORT_GAP_NS = 20_000.0     # under this a gap is a bubble inside a program

Interval = Tuple[float, float]


def load(path: str):
    """``jax.profiler.ProfileData`` of an ``.xplane.pb`` or ``.xplane.pb.gz``."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` inside ``[lo, hi]``."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def find_markers(pd) -> Tuple[Optional[float], Optional[float]]:
    """Trace-clock start of the begin marker and end of the end marker."""
    begin = end = None
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == MARKER_BEGIN and begin is None:
                    begin = float(e.start_ns)
                elif e.name == MARKER_END:
                    end = float(e.start_ns + e.duration_ns)
    return begin, end


def short_op(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` → ``%fusion.3``."""
    return name.split(" = ", 1)[0][:60]


def short_module(name: str) -> str:
    """``jit_run_fwd(1234)`` → ``jit_run_fwd``."""
    return name.split("(", 1)[0][:60]


def attribute(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The host span name that covers most of ``gap``; spans are
    ``(name, start, end)`` on the trace's clock."""
    cover: Dict[str, float] = {}
    for name, a, b in spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > 0:
            cover[name] = cover.get(name, 0.0) + ov
    if not cover:
        return "no_host_span"
    return max(sorted(cover), key=lambda k: cover[k])


def reduce(
    pd,
    *,
    begin_wall_ns: Optional[int] = None,
    host_spans: Sequence[Tuple[str, int, int]] = (),
    program_patterns: Optional[Mapping[str, str]] = None,
    top: int = 10,
) -> Dict[str, Any]:
    """The whole reduction.

    ``begin_wall_ns`` is the wall clock noted inside the begin marker;
    ``host_spans`` are ``(name, start_wall_ns, end_wall_ns)``;
    ``program_patterns`` maps a label to a regex over ``XLA Modules`` event
    names. Returns ``window_s``, ``busy_s`` (union of device-operation
    intervals inside the markers, averaged over chips), ``idle_share``,
    ``chips``, ``programs`` (label → seconds and count over the events that
    lie wholly inside the markers, ``clipped_seconds`` over all of them cut
    to the markers), ``modules`` (every program seen →
    seconds clipped to the markers, count), ``device_ops`` and ``idle_gaps`` (each at
    most ``top`` ``[name, seconds]`` pairs, largest first). With no device
    plane or no markers ``busy_s`` is 0.0 and the lists are empty."""
    begin, end = find_markers(pd)
    out: Dict[str, Any] = {
        "window_s": 0.0, "busy_s": 0.0, "idle_share": None, "chips": 0,
        "programs": {}, "modules": {}, "device_ops": [], "idle_gaps": [],
    }
    if begin is None or end is None or end <= begin:
        return out
    out["window_s"] = (end - begin) / 1e9
    patterns = {k: re.compile(v) for k, v in (program_patterns or {}).items()}
    programs = {k: [0.0, 0, 0.0] for k in patterns}
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    busy_total = 0.0
    idle: Dict[str, float] = {}
    offset = None if begin_wall_ns is None else begin - float(begin_wall_ns)
    spans = [] if offset is None else [
        (name, a + offset, b + offset) for name, a, b in host_spans]
    chips = 0
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        chips += 1
        mods = [m for m in _events(lines[MODULES_LINE])
                if m[2] > begin and m[1] < end] \
            if MODULES_LINE in lines else []
        mods.sort(key=lambda m: m[1])
        for name, a, b in mods:
            secs = (min(b, end) - max(a, begin)) / 1e9
            m = modules.setdefault(short_module(name), [0.0, 0])
            m[0] += secs
            m[1] += 1
            whole = a >= begin and b <= end
            for label, rx in patterns.items():
                if rx.search(name):
                    programs[label][2] += secs
                    if whole:   # counted whole or not at all
                        programs[label][0] += secs
                        programs[label][1] += 1
        op_events = [o for o in _events(lines[OPS_LINE])
                     if o[2] > begin and o[1] < end]
        op_events.sort(key=lambda o: o[1])
        mi = 0
        for name, a, b in op_events:
            while mi < len(mods) and mods[mi][2] <= a:
                mi += 1
            owner = short_module(mods[mi][0]) \
                if mi < len(mods) and mods[mi][1] <= a else "?"
            key = f"{owner}/{short_op(name)}"
            ops[key] = ops.get(key, 0.0) + (min(b, end) - max(a, begin)) / 1e9
        busy = union(clip(((a, b) for _, a, b in op_events), begin, end))
        busy_total += total(busy) / 1e9
        for gap in gaps(busy, begin, end):
            length = gap[1] - gap[0]
            name = ("inside_program_under_20us" if length < SHORT_GAP_NS
                    else attribute(gap, spans))
            idle[name] = idle.get(name, 0.0) + length / 1e9
    out["chips"] = chips
    if chips:
        out["busy_s"] = busy_total / chips
        out["idle_share"] = 1.0 - out["busy_s"] / out["window_s"]
        out["idle_gaps"] = [
            [k, v / chips] for k, v in
            sorted(idle.items(), key=lambda kv: -kv[1])[:top]]
    out["programs"] = {k: {"seconds": v[0], "count": v[1],
                           "clipped_seconds": v[2]}
                       for k, v in programs.items()}
    out["modules"] = {k: {"seconds": v[0], "count": v[1]}
                      for k, v in modules.items()}
    out["device_ops"] = [
        [k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]]
    return out
