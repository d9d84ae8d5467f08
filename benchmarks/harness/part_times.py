"""The device's time by the MODEL'S OWN PARTS: what ``trace_reduce`` (per
program, and the ten heaviest operations by XLA's names) and ``op_times``
(single kernels) do not give. The program enters a part's name where the work
is written (``agent_tpu/obs/trace.py: part``, a ``jax.named_scope``), XLA
carries it into every executable, and the runtime reads it back out of the
compiled text (``TpuRuntime.program_parts``): ``{module: [{"instructions":
{name: part or None}, "mixed": {fusion: [parts]}, "named_share"}, ...]}``,
one map an executable. :func:`reduce_parts` lays such a map over a trace.

Same markers, clock and clipping as ``trace_reduce.reduce``. An ``XLA Ops``
event is charged its SELF time: an event that contains later events of the
line (a ``while``, a ``conditional``, a ``call``) is charged only what they do
not cover, so the parts add up to ``busy_s`` and a loop is not counted beside
its own body. A trace with no markers or no device plane gives zeros; a
program without a map is all ``unnamed``; nothing here raises on a program
that has no ``program_parts`` at all (the parent of the PR that brought it)."""

from __future__ import annotations

import glob
import json
import os
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

from benchmarks.harness import stack, trace_reduce

UNNAMED = "unnamed"
COUNTERS = ("runtime_xla_executables_total", "runtime_xla_cache_hits_total",
            "runtime_compile_seconds_total")


def self_times(spans: Sequence[Tuple[float, float]]) -> List[float]:
    """``spans`` sorted by (start, -end) → what of each no LATER-started span
    covers: every instant goes to the innermost (latest started) span that
    holds it, so the self times add up to the union."""
    out = [0.0] * len(spans)
    open_: List[int] = []
    at = 0.0

    def close(j: int) -> None:
        nonlocal at
        if spans[j][1] > at:
            out[j] += spans[j][1] - at
            at = spans[j][1]

    for i, (a, _) in enumerate(spans):
        while open_ and spans[open_[-1]][1] <= a:
            close(open_.pop())
        if open_ and a > at:
            out[open_[-1]] += a - at
        at = max(at, a)
        open_.append(i)
    while open_:
        close(open_.pop())
    return out


def _key(instructions: Mapping[str, Any], name: str) -> str:
    """An event's instruction name as the map has it: a trace's events carry
    a leading ``%``, a compiled text's names may or may not."""
    return name if name in instructions else name.lstrip("%")


def _lookup(candidates: Sequence[Mapping[str, Any]], names: Sequence[str]
            ) -> Optional[Mapping[str, Any]]:
    """Of the maps that share a module name, the one that holds the most of
    the instruction names a module event shows (two shapes of one function
    are two executables under one name)."""
    if len(candidates) <= 1:
        return candidates[0] if candidates else None
    return max(candidates, key=lambda c: sum(
        1 for n in names if _key(c["instructions"], n) in c["instructions"]))


def reduce_parts(pd, parts_map: Mapping[str, Sequence[Mapping[str, Any]]],
                 top: int = 20,
                 window: Optional[Tuple[float, float]] = None
                 ) -> Dict[str, Any]:
    """Seconds of device self time inside the markers (``window``: inside
    that interval of the trace's clock instead, for a capture that has no
    markers), averaged over chips:
    ``busy_s`` (their sum), ``parts`` (part → seconds, :data:`UNNAMED` for
    what no map names), ``programs`` (module → part → seconds), ``rows`` (the
    ``top`` heaviest ``[module, part, instruction, seconds]``), ``mixed_s``
    (in fusions whose fused computation spans two parts or more) with
    ``mixed_rows`` (``[module, instruction, part it got, parts inside,
    seconds]``) and ``unnamed_rows``."""
    out: Dict[str, Any] = {
        "busy_s": 0.0, "chips": 0, "parts": {}, "programs": {}, "rows": [],
        "mixed_s": 0.0, "mixed_rows": [], "unnamed_rows": [],
    }
    begin, end = window or trace_reduce.find_markers(pd)
    if begin is None or end is None or end <= begin:
        return out
    rows: Dict[Tuple[str, str, str], float] = {}
    mixed: Dict[Tuple[str, str, str], Any] = {}
    for plane in pd.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if trace_reduce.OPS_LINE not in lines:
            continue
        out["chips"] += 1
        mods = sorted((m for m in trace_reduce._events(
            lines[trace_reduce.MODULES_LINE]) if m[2] > begin and m[1] < end),
            key=lambda m: m[1]) if trace_reduce.MODULES_LINE in lines else []
        ops = sorted(((max(a, begin), min(b, end), trace_reduce.short_op(name))
                      for name, a, b in trace_reduce._events(
                          lines[trace_reduce.OPS_LINE])
                      if min(b, end) > max(a, begin)),
                     key=lambda o: (o[0], -o[1]))
        # Owner: the module event that holds the operation's start, as
        # ``trace_reduce.reduce`` finds it.
        owner: List[int] = []
        mi = 0
        for a, _, _ in ops:
            while mi < len(mods) and mods[mi][2] <= a:
                mi += 1
            owner.append(mi if mi < len(mods) and mods[mi][1] <= a else -1)
        under: Dict[int, List[str]] = {}
        for o, (_, _, name) in zip(owner, ops):
            under.setdefault(o, []).append(name)
        chosen = {o: _lookup(parts_map.get(
            trace_reduce.short_module(mods[o][0]), ()), names)
            for o, names in under.items() if o >= 0}
        for o, (_, _, name), seconds in zip(
                owner, ops, self_times([(a, b) for a, b, _ in ops])):
            if seconds <= 0:
                continue
            module = trace_reduce.short_module(mods[o][0]) if o >= 0 else "?"
            found = chosen.get(o)
            key = _key(found["instructions"], name) if found else name
            part = (found["instructions"].get(key) if found else None) \
                or UNNAMED
            row = (module, part, name.lstrip("%"))
            rows[row] = rows.get(row, 0.0) + seconds / 1e9
            if found and key in found.get("mixed", ()):
                mixed[row] = found["mixed"][key]
    chips = max(1, out["chips"])
    for (module, part, name), seconds in rows.items():
        seconds /= chips
        out["busy_s"] += seconds
        out["parts"][part] = out["parts"].get(part, 0.0) + seconds
        by_part = out["programs"].setdefault(module, {})
        by_part[part] = by_part.get(part, 0.0) + seconds
    heaviest = sorted(rows.items(), key=lambda kv: -kv[1])
    out["rows"] = [[*row, s / chips] for row, s in heaviest[:top]]
    out["unnamed_rows"] = [[m, n, s / chips] for (m, p, n), s in heaviest
                           if p == UNNAMED][:top // 2]
    out["mixed_s"] = sum(rows[row] for row in mixed) / chips
    out["mixed_rows"] = [[m, n, p, mixed[m, p, n], s / chips]
                         for (m, p, n), s in heaviest
                         if (m, p, n) in mixed][:top // 2]
    return out


def newest_trace(pattern: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under the directories ``pattern`` globs."""
    paths = glob.glob(os.path.join(pattern, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def capture_path(cell: str) -> Optional[str]:
    """The capture a traced run of ``cell`` left in its scratch. NOT through
    ``stack.output_dir``, which empties it."""
    return newest_trace(os.path.join(
        stack.ROOT, ".cache", "bench_runs", f"{cell}-*-t1"))


def ask_runtime():
    """``program_parts`` of the process's runtime (the one the in-process
    agent uses), or ``None`` where the program has no such method."""
    from agent_tpu.runtime.runtime import get_runtime

    return getattr(get_runtime(), "program_parts", None)


def _counters() -> Dict[str, float]:
    """What the process's own registry holds of the runtime's XLA counters:
    the agent is closed by now, so whatever ``program_parts`` obtains ticks
    here."""
    from agent_tpu.obs.metrics import get_registry
    from benchmarks.harness.counters import counter_sum

    snap = get_registry().snapshot()
    return {name: counter_sum(snap, name) or 0.0 for name in COUNTERS}


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The run's capture reduced by part, ONCE a run (kept in the record);
    emits the ``parts`` line the first time. ``None`` for an untraced run, a
    trace with no device time, a missing capture, or a program that has no
    ``program_parts``: the runtime is not asked in any of those."""
    trace = run.get("trace")
    if run.get("kind") != "drain" or not trace or trace["busy_s"] <= 0:
        return None
    if "parts" not in run:
        run["parts"] = _reduce_run(run)
    return run["parts"]


def _reduce_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    path, ask = capture_path(run["cell"]["name"]), ask_runtime()
    if path is None or ask is None:
        return None
    before, t0 = _counters(), time.time()
    parts_map = ask()
    after, t1 = _counters(), time.time()
    # Beside the capture, as an operator's capture has it: the two files
    # ``scripts/capture_parts.py`` reads.
    with open(os.path.join(os.path.dirname(path), "program_parts.json"), "w",
              encoding="utf-8") as f:
        json.dump(parts_map, f)
    pd = trace_reduce.load(path)
    t2 = time.time()
    out = reduce_parts(pd, parts_map)
    # What the instrument costs when it is on, and that asking compiled
    # nothing: executables obtained while asking, of which loads.
    out["cost"] = {
        "program_parts_s": t1 - t0, "load_s": t2 - t1,
        "reduce_s": time.time() - t2,
        "programs": sum(len(maps) for maps in parts_map.values()),
        "instructions": sum(len(m["instructions"]) for maps in
                            parts_map.values() for m in maps),
        **{name.replace("runtime_", "").replace("_total", ""):
           after[name] - before[name] for name in COUNTERS},
    }
    out["named_share"] = {
        module: [round(m["named_share"], 4) for m in maps]
        for module, maps in parts_map.items()}
    stack.emit("parts", **out)
    return out


def part_ms_per_shard(run: Dict[str, Any], part: str) -> Optional[float]:
    """The part's share of the traced interval times the window's seconds a
    shard, in milliseconds: what of a shard's wall time the device spent in
    that part. ``None`` where the part has no time."""
    parts = of_run(run)
    if not parts or not run.get("shards"):
        return None
    seconds = parts["parts"].get(part, 0.0)
    if seconds <= 0:
        return None
    share = seconds / run["trace"]["window_s"]
    return 1e3 * share * run["window_s"] / run["shards"]
