"""Staging pool + autotuner (ISSUE 6): regulator math, the adjustable
gate, multi-worker drains bit-identical to single-worker, the sentinel
contract, and the double-buffered device feed."""

import queue
import threading
import time

import jax
import numpy as np
import pytest
import requests

from agent_tpu.agent.app import Agent
from agent_tpu.config import AgentConfig, Config, DeviceConfig
from agent_tpu.controller.core import Controller
from agent_tpu.controller.server import ControllerServer
from agent_tpu.data.staging import (
    AdjustableGate,
    PhaseRatioSampler,
    default_workers,
    desired_workers,
)
from agent_tpu.obs.metrics import MetricsRegistry
from agent_tpu.runtime.runtime import TpuRuntime

TINY = {
    "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
    "max_len": 64, "dtype": "float32", "n_classes": 16,
}


@pytest.fixture(scope="module")
def runtime():
    return TpuRuntime(
        config=DeviceConfig(tpu_disabled=True, mesh_shape={"dp": 8}),
        devices=jax.devices("cpu"),
    )


# ---------------------------------------------------------------------------
# Regulator math + primitives
# ---------------------------------------------------------------------------


def test_desired_workers_tracks_the_stage_execute_ratio():
    # Stage cheaper than execute → one worker suffices.
    assert desired_workers(0.01, 0.05, 4) == 1
    # Stage 2.5× execute → 3 workers to hide it.
    assert desired_workers(0.25, 0.10, 8) == 3
    # Clamped at the pool size.
    assert desired_workers(1.0, 0.01, 4) == 4
    # Device starving with no execute signal → saturate.
    assert desired_workers(0.2, 0.0, 4) == 4
    # Nothing measured → stay serial.
    assert desired_workers(0.0, 0.0, 4) == 1
    assert 1 <= default_workers() <= 4


def test_adjustable_gate_limits_and_retunes():
    gate = AdjustableGate(2)
    assert gate.acquire(0.01) and gate.acquire(0.01)
    assert not gate.acquire(0.01)  # at the limit
    gate.set_limit(3)
    assert gate.acquire(0.01)      # widened live
    gate.release()
    gate.set_limit(1)
    assert not gate.acquire(0.01)  # narrowed below the active count
    gate.release()
    gate.release()
    assert gate.acquire(0.01)


def test_phase_ratio_sampler_windows_the_registry():
    reg = MetricsRegistry()
    hist = reg.histogram("task_phase_seconds", "t", ("op", "phase"))
    sampler = PhaseRatioSampler(reg)
    assert sampler.sample() is None  # nothing recorded yet
    for _ in range(4):
        hist.observe(0.2, op="a", phase="stage")
        hist.observe(0.05, op="a", phase="execute")
    stage_s, exec_s = sampler.sample()
    assert stage_s == pytest.approx(0.2)
    assert exec_s == pytest.approx(0.05)
    # The next window is a DELTA: two fresh samples are below the minimum.
    hist.observe(0.3, op="a", phase="stage")
    hist.observe(0.3, op="a", phase="execute")
    assert sampler.sample() is None


# ---------------------------------------------------------------------------
# Drains through the real pipeline
# ---------------------------------------------------------------------------


def _csv(tmp_path, n=96):
    path = tmp_path / "rows.csv"
    lines = ["id,text"]
    for i in range(n):
        lines.append(f'{i},"staging pool row {i} with text"')
    path.write_text("\n".join(lines) + "\n", encoding="utf-8")
    return str(path)


def _drain(controller, server, runtime, workers, autotune=False,
           double_buffer=True, depth=2, name=""):
    from agent_tpu.agent.pipeline import PipelineRunner

    cfg = Config(agent=AgentConfig(
        controller_url=server.url, agent_name=name or f"pool-{workers}",
        tasks=("map_classify_tpu",), idle_sleep_sec=0.0,
    ))
    agent = Agent(config=cfg, session=requests.Session(), runtime=runtime)
    agent._profile = {"tier": "test"}
    agent.running = True

    def watch():
        deadline = time.time() + 120
        while not controller.drained() and time.time() < deadline:
            time.sleep(0.02)
        agent.running = False

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    PipelineRunner(
        agent, depth=depth, workers=workers, autotune=autotune,
        double_buffer=double_buffer,
    ).run()
    watcher.join(timeout=5)
    return agent


# The other side of the comparison, as (chip slice or "" for the module's
# dp=8 mesh, stage workers, autotune) per agent on the one queue.
DRAIN_VARIANTS = {
    "stage_workers_4": [("", 4, True)],
    "fleet_of_two_one_device_agents": [("0:1", 1, False), ("1:1", 1, False)],
}


def _pinned_runtime(chips):
    return TpuRuntime(config=DeviceConfig(tpu_disabled=True, chip_slice=chips))


@pytest.mark.parametrize("members", list(DRAIN_VARIANTS.values()),
                         ids=list(DRAIN_VARIANTS))
def test_multi_worker_drain_bit_identical_to_single(runtime, tmp_path, members):
    """The CI acceptance bar in miniature: 4 stage workers + autotune +
    double buffering, and a fleet of two pinned one-device agents sharing
    the queue, each produce exactly the results of one single-worker agent
    on the dp=8 mesh."""
    csv = _csv(tmp_path)
    extra = {"text_field": "text", "allow_fallback": False,
             "result_format": "columnar", "model_config": dict(TINY),
             "topk": 3}

    results = {}
    for side, fleet in (("single", [("", 1, False)]), ("other", members)):
        controller = Controller()
        controller.submit_csv_job(csv, total_rows=96, shard_size=12,
                                  map_op="map_classify_tpu",
                                  extra_payload=extra)
        with ControllerServer(controller) as server:
            threads = [
                threading.Thread(
                    target=_drain,
                    args=(controller, server,
                          _pinned_runtime(chips) if chips else runtime,
                          workers),
                    kwargs={"autotune": autotune, "name": f"{side}-{i}"})
                for i, (chips, workers, autotune) in enumerate(fleet)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=150)
        assert controller.counts() == {"succeeded": 8}
        results[side] = {
            controller.job(j).payload["start_row"]: r
            for j, r in controller.results().items()
        }
    assert set(results["single"]) == set(results["other"])
    for start, want in results["single"].items():
        got = results["other"][start]
        assert got["indices"] == want["indices"], f"shard @{start}"
        assert got["scores"] == want["scores"], f"shard @{start}"


def test_pool_gauges_and_backlog_advertisement(runtime, tmp_path):
    """The pool exports its knob positions and feeds the scheduler-facing
    queue_depth from the live backlog (staged + awaiting a worker)."""
    csv = _csv(tmp_path, n=48)
    controller = Controller()
    controller.submit_csv_job(csv, total_rows=48, shard_size=12,
                              map_op="map_classify_tpu",
                              extra_payload={"text_field": "text",
                                             "allow_fallback": False,
                                             "model_config": dict(TINY)})
    with ControllerServer(controller) as server:
        agent = _drain(controller, server, runtime, workers=3)
    snap = agent.obs.snapshot()
    assert snap["stage_pool_workers"]["series"][0]["value"] == 3
    assert snap["stage_prefetch_depth"]["series"][0]["value"] >= 2
    assert agent.staged_depth_fn is not None
    assert agent.staged_depth_fn() == 0  # drained


def test_last_worker_owns_the_stop_sentinel():
    """However many workers die in whatever order, the device loop gets
    EXACTLY one stop token — a lost sentinel would hang the device thread,
    a duplicate would kill a later incarnation's loop early."""
    from agent_tpu.data.staging import StagingPool

    class StubAgent:
        running = False  # feeder exits immediately

        class config:
            class agent:
                stage_workers = 3
                stage_autotune = False
                idle_sleep_sec = 0.0

        obs = MetricsRegistry()

    stop = object()
    staged_q = queue.Queue(maxsize=4)
    pool = StagingPool(
        StubAgent(), staged_q, lambda lease_id, task: None, stop,
        max_workers=3, autotune=False,
    )
    pool.start()
    pool.join(timeout=10)
    assert staged_q.get(timeout=1) is stop
    assert staged_q.qsize() == 0


def test_prefeed_places_chunks_on_device(runtime):
    """The double-buffered feed replaces staged numpy chunks with device
    arrays ahead of execute; the op's own put_batch then passes them
    through, and values survive exactly."""
    from agent_tpu.agent.pipeline import PipelineRunner, _Item

    cfg = Config(agent=AgentConfig(tasks=("echo",)))
    agent = Agent.__new__(Agent)
    agent.config = cfg
    agent.runtime = runtime
    runner = PipelineRunner.__new__(PipelineRunner)
    runner.agent = agent

    ids = np.arange(64, dtype=np.uint16).reshape(8, 8)
    lengths = np.full(8, 8, dtype=np.int32)
    item = _Item("l1", "j1", 0, "map_classify_tpu", {}, None, 0.0,
                 staged={"chunks": [(ids, lengths, 8)], "other": "kept"})
    runner._prefeed(item)
    fed_ids, fed_lengths, n = item.staged["chunks"][0]
    assert isinstance(fed_ids, jax.Array) and isinstance(fed_lengths, jax.Array)
    assert n == 8 and item.staged["other"] == "kept"
    np.testing.assert_array_equal(np.asarray(fed_ids), ids)
    # Re-putting an already-placed array is the op's execute path — no-op.
    again = runtime.put_batch(fed_ids)
    np.testing.assert_array_equal(np.asarray(again), ids)

    # Monolithic / failed / resultful items are left alone.
    mono = _Item("l1", "j2", 0, "echo", {}, None, 0.0, monolithic=True)
    runner._prefeed(mono)
    assert mono.staged is None


# ---------------------------------------------------------------------------
# Stage/execute overlap (ISSUE 6 satellite: the per-drain breakdown)
# ---------------------------------------------------------------------------


def test_overlap_from_spans_math():
    from agent_tpu.obs.scrape import overlap_from_spans

    def span(name, start, dur_s):
        return {"name": name, "start_wall": start,
                "duration_ms": dur_s * 1e3}

    # Job B's stage [1, 3) fully inside job A's execute [0, 4): hidden.
    # Job C's stage [5, 7) overlaps execute [6, 8) for half its span.
    spans = [
        span("execute", 0.0, 4.0), span("execute", 6.0, 2.0),
        span("stage", 1.0, 2.0), span("stage", 5.0, 2.0),
        span("post", 0.0, 1.0),          # other phases ignored
        {"name": "stage", "start_wall": 9.0, "duration_ms": None},  # open
    ]
    out = overlap_from_spans(spans)
    assert out["n_stage_spans"] == 2 and out["n_execute_spans"] == 2
    assert out["stage_total_s"] == pytest.approx(4.0)
    assert out["overlap_ratio"] == pytest.approx(3.0 / 4.0)
    assert out["stage_p50_ms"] == pytest.approx(2000.0)
    # No closed spans of both kinds → None (a caller must fail loudly).
    assert overlap_from_spans([span("stage", 0, 1)]) is None
    assert overlap_from_spans([]) is None


def test_stage_execute_overlap_from_a_real_drain(runtime, tmp_path):
    """End-to-end: a pipelined drain's trace window yields an overlap
    breakdown via the HTTP trace endpoints (None means the trace path is
    down, and a caller that promised the breakdown fails loudly on it)."""
    from agent_tpu.obs.scrape import stage_execute_overlap

    csv = _csv(tmp_path, n=48)
    controller = Controller()
    controller.submit_csv_job(csv, total_rows=48, shard_size=12,
                              map_op="map_classify_tpu",
                              extra_payload={"text_field": "text",
                                             "allow_fallback": False,
                                             "model_config": dict(TINY)})
    with ControllerServer(controller) as server:
        _drain(controller, server, runtime, workers=2)
        out = stage_execute_overlap(server.url)
    assert out is not None, "trace window yielded no overlap breakdown"
    assert out["n_stage_spans"] == 4 and out["n_execute_spans"] == 4
    assert 0.0 <= out["overlap_ratio"] <= 1.0
    assert out["stage_p50_ms"] > 0 and out["execute_p50_ms"] > 0
