"""The system under test, as the benchmark holds it: the run's main process
owns the chip and one in-process ``Agent`` on the pipelined runner (the
program's own defaults), the compile listener, the profiler and the taps that
read — never change — what the program produces."""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import peaks as peaks_mod
from benchmarks.harness import trace_reduce
from benchmarks.harness.manifest import ROOT

# Module constant, not an option: tests rehearse a cell on the CPU by
# patching it (the pattern of tests/test_chip_smoke.py).
REQUIRED_PLATFORM = "tpu"


class BenchFailure(Exception):
    """The run cannot give a result; it ends non-zero."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise BenchFailure(message)


def emit(what: str, **fields: Any) -> None:
    """One JSON line on stdout, before the result line."""
    print(json.dumps({"bench": what, **fields}, sort_keys=True, default=str),
          flush=True)


def output_dir(cell: str, seed: int, trace: int) -> str:
    """Per-run scratch inside the checkout (git-ignored), emptied first."""
    path = os.path.join(ROOT, ".cache", "bench_runs", f"{cell}-{seed}-t{trace}")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def init_device(chips: int) -> Dict[str, Any]:
    """``jax.devices()`` as the result line reports them; fails when the
    platform is not the required one, the chips are fewer than the cell
    asks for, or the kind has no peaks."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    check(device["platform"] == REQUIRED_PLATFORM,
          f"no accelerator: jax.devices() is {devices}, "
          f"want platform {REQUIRED_PLATFORM!r}")
    check(len(devices) >= chips,
          f"the cell asks for {chips} chips, jax.devices() has {len(devices)}")
    if REQUIRED_PLATFORM == "tpu":
        peaks_mod.lookup(device["kind"])    # raises UnknownDevice
    return device


def memory_peak_bytes() -> int:
    """Peak bytes on the fullest chip. This runtime keeps two pools that do
    not overlap: ordinary buffers (``peak_bytes_in_use``: parameters, inputs,
    results) and the space reserved for a running program's temporaries
    (``peak_bytes_reserved``; PERF.md, PR 21 and PR 23), and the free block
    it reports is the limit less BOTH. The peak is therefore their sum; 0
    where the backend reports none (CPU)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_stats() -> Dict[str, Any]:
    """The first device's allocator statistics, as the backend gives them."""
    import jax

    return dict(jax.devices()[0].memory_stats() or {})


class AgentStack:
    """One in-process ``Agent`` leasing from ``controller_url`` on the
    pipelined runner, every knob at the program's default but those the
    cell's traffic file sets under ``agent`` (fields of the program's
    ``AgentConfig``, as the deployment's operator would set them for that
    traffic; an unknown one is a ``TypeError``)."""

    def __init__(self, controller_url: str, tasks: Sequence[str],
                 knobs: Optional[Dict[str, Any]] = None) -> None:
        import requests

        from agent_tpu.agent.app import Agent
        from agent_tpu.config import Config
        from agent_tpu.obs.trace import SpanBuffer
        from agent_tpu.runtime.runtime import get_runtime

        class TeeSpanBuffer(SpanBuffer):
            """The agent's span ring, with a copy kept for the benchmark
            (the ring itself is drained onto every result post)."""

            def __init__(self) -> None:
                super().__init__()
                self.kept: List[Dict[str, Any]] = []

            def add(self, span: Any) -> None:
                super().add(span)
                if isinstance(span, dict) and len(self.kept) < 200_000:
                    self.kept.append(span)

        config = Config.from_env()
        config = dataclasses.replace(config, agent=dataclasses.replace(
            config.agent, **dict(knobs or {}), controller_url=controller_url,
            agent_name="bench-agent", tasks=tuple(tasks)))
        self.runtime = get_runtime(config.device)
        check(self.runtime.platform == REQUIRED_PLATFORM,
              f"runtime.platform is {self.runtime.platform!r}")
        self.tracer = TeeSpanBuffer()
        self.agent = Agent(config=config, session=requests.Session(),
                           runtime=self.runtime, tracer=self.tracer)
        self._thread = threading.Thread(target=self.agent.run, daemon=True)
        self._thread.start()

    def alive(self) -> bool:
        return self._thread.is_alive()

    def metrics(self) -> Dict[str, Any]:
        return self.agent.obs.snapshot()

    def host_spans(self, t0_wall: float, t1_wall: float
                   ) -> List[Tuple[str, int, int]]:
        """The agent's own task-phase spans that touch ``[t0, t1]`` as
        ``(name, start_wall_ns, end_wall_ns)``."""
        out = []
        for s in list(self.tracer.kept):
            a = float(s.get("start_wall") or 0.0)
            b = a + float(s.get("duration_ms") or 0.0) / 1e3
            if b >= t0_wall and a <= t1_wall:
                out.append((str(s.get("name")), int(a * 1e9), int(b * 1e9)))
        return out

    def close(self) -> None:
        self.agent.running = False
        self._thread.join(timeout=120)


def histogram_delta(before: Dict[str, Any], after: Dict[str, Any], name: str,
                    **labels: str) -> Tuple[float, int]:
    """(sum, count) a histogram series of a registry snapshot gained."""
    def find(snap):
        for s in (snap.get(name) or {}).get("series", []):
            if all(s["labels"].get(k) == v for k, v in labels.items()):
                return float(s["sum"]), int(s["count"])
        return 0.0, 0

    (s0, c0), (s1, c1) = find(before), find(after)
    return s1 - s0, c1 - c0


class Tracer:
    """A profiler capture of a few seconds, bracketed by the two marker
    annotations ``trace_reduce`` looks for."""

    def __init__(self, directory: str) -> None:
        self.directory = directory
        self.begin_wall_ns: Optional[int] = None
        self.end_wall_ns: Optional[int] = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host python frames: not needed
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        with jax.profiler.TraceAnnotation(trace_reduce.MARKER_BEGIN):
            self.begin_wall_ns = time.time_ns()

    def stop(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation(trace_reduce.MARKER_END):
            self.end_wall_ns = time.time_ns()
        jax.profiler.stop_trace()

    def capture(self, t_open: float, traffic: Dict[str, Any]) -> None:
        """Trace ``trace_seconds`` of the window, ``trace_start_s`` in."""
        time.sleep(max(0.0, t_open + float(traffic["trace_start_s"])
                       - time.time()))
        self.start()
        time.sleep(float(traffic["trace_seconds"]))
        self.stop()

    def reduce(self, agent: "AgentStack", program_patterns) -> Dict[str, Any]:
        """The reduction of the captured trace, with the agent's own spans
        laid on its clock; also printed (without the two breakdown lists)."""
        paths = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        check(bool(paths), f"the profiler wrote no trace under {self.directory}")
        out = trace_reduce.reduce(
            trace_reduce.load(paths[-1]), begin_wall_ns=self.begin_wall_ns,
            host_spans=agent.host_spans(self.begin_wall_ns / 1e9,
                                        self.end_wall_ns / 1e9),
            program_patterns=program_patterns)
        emit("trace", **{k: v for k, v in out.items()
                         if k not in ("device_ops", "idle_gaps")})
        return out


def print_result(correct: bool, attempted: int, failed: int,
                 metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                 breakdown: Optional[Dict[str, Any]] = None,
                 compared: Optional[Dict[str, Any]] = None) -> None:
    """The contract's result object, as the LAST line of stdout; the numbers
    compared, each with its limit, are its last key."""
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = compared or {}
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
