"""``map_score_lm`` on the CPU at tiny widths: through ``run()`` and through
the pipelined ``stage`` / ``execute`` / ``finalize`` (a real ``Agent`` on the
pipelined runner against a real controller), against the log-probabilities
of the benchmark's plain reference; the registry's invariants; soft errors;
and that a process which never leases the op never imports its modules.

Tolerance: ``dtype: float32`` here, so the op computes what the reference
computes in another order: 2e-5 nats a token (float32 reordering), that is
0.02 on a block sum of 1,024 tokens."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest
import requests

from agent_tpu import ops as ops_pkg
from agent_tpu.ops import get_op
from benchmarks.harness import manifest

ref = manifest.load_reference("retention_lm")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {"vocab_size": 3000, "d_model": 64, "n_heads": 10, "n_kv_heads": 2,
        "d_head": 16, "d_ff": 96, "n_layers": 2, "dtype": "float32"}
REF_CFG = {**TINY, "rms_norm_eps": 1e-6, "rope_theta": 1e6}
TOKEN_TOL = 2e-5
LENGTHS = (5000, 700, 1025, 1)      # two segments; one; a block and a token; no target


def _docs(seed=0, lengths=LENGTHS):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
            for n in lengths]


def _csv(path, docs):
    with open(path, "w", encoding="ascii") as f:
        f.write("id,ids\n")
        for i, d in enumerate(docs):
            f.write(f"{i},{' '.join(map(str, d.tolist()))}\n")
    return str(path)


def _assert_matches_reference(result, docs, model_id):
    want = ref.token_logprobs(REF_CFG, model_id, docs)
    assert result["n_tokens"] == [len(d) for d in docs]
    for d, lp, blocks, total in zip(docs, want, result["block_logprob_sums"],
                                    result["logprob_sum"]):
        sums = ref.block_sums(lp)
        assert len(blocks) == len(sums) == -(-(len(d) - 1) // 1024)
        np.testing.assert_allclose(blocks, sums, atol=TOKEN_TOL * 1024)
        assert total == pytest.approx(float(sums.sum()), abs=TOKEN_TOL * len(d))
    assert ref.compare(result["block_logprob_sums"],
                       [ref.block_sums(lp) for lp in want[:3]] + [[]],
                       result["n_tokens"])["block_logprob_gap_max"] < TOKEN_TOL


def test_run_matches_the_reference_from_ids_and_from_csv(tmp_path):
    docs = _docs()
    op = get_op("map_score_lm")
    out = op({"ids": [d.tolist() for d in docs], "model_config": TINY,
              "model_path": "score-a"})
    assert out["ok"] is True and out["op"] == "map_score_lm"
    assert out["device"] == "cpu" and out["n_rows"] == 4
    _assert_matches_reference(out, docs, "score-a")
    path = _csv(tmp_path / "docs.csv", docs)
    shard = op({"source_uri": path, "start_row": 1, "shard_size": 2,
                "ids_field": "ids", "model_config": TINY,
                "model_path": "score-a", "allow_fallback": False})
    assert shard["n_tokens"] == [700, 1025]
    np.testing.assert_allclose(shard["logprob_sum"], out["logprob_sum"][1:3],
                               rtol=1e-6)


def test_pipelined_agent_matches_the_reference(tmp_path):
    from agent_tpu.agent.app import Agent
    from agent_tpu.config import AgentConfig, Config, DeviceConfig
    from agent_tpu.controller.core import Controller
    from agent_tpu.controller.server import ControllerServer
    from agent_tpu.runtime.runtime import TpuRuntime

    runtime = TpuRuntime(
        config=DeviceConfig(tpu_disabled=True, mesh_shape={"dp": 8}),
        devices=jax.devices("cpu"))
    docs = _docs(seed=4, lengths=(4500, 900, 1300, 60))
    controller = Controller()
    controller.submit_csv_job(
        _csv(tmp_path / "docs.csv", docs), total_rows=4, shard_size=1,
        map_op="map_score_lm", extra_payload={
            "ids_field": "ids", "allow_fallback": False,
            "model_config": dict(TINY), "model_path": "score-p"})
    with ControllerServer(controller) as server:
        agent = Agent(config=Config(agent=AgentConfig(
            controller_url=server.url, agent_name="pipe",
            tasks=("map_score_lm",), idle_sleep_sec=0.0, pipeline_depth=2)),
            session=requests.Session(), runtime=runtime)
        agent._profile = {"tier": "test"}

        def watch():
            deadline = time.time() + 300
            while not controller.drained() and time.time() < deadline:
                time.sleep(0.02)
            agent.shutdown()

        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()
        agent.run()
        watcher.join(timeout=5)
    assert controller.counts() == {"succeeded": 4}
    by_row = {controller.job(j).payload["start_row"]: r
              for j, r in controller.results().items()}
    merged = {"n_tokens": [], "logprob_sum": [], "block_logprob_sums": []}
    for row in range(4):
        r = by_row[row]
        assert r["ok"] is True and r["device"] == "cpu"
        assert r["timings"]["device_ms"] > 0 and "fetch_ms" in r["timings"]
        assert r["usage"]["device_s"] > 0
        for key in merged:
            merged[key] += r[key]
    want = ref.token_logprobs(REF_CFG, "score-p", docs)
    for lp, blocks in zip(want, merged["block_logprob_sums"]):
        np.testing.assert_allclose(blocks, ref.block_sums(lp),
                                   atol=TOKEN_TOL * 1024)
    snap = agent.obs.snapshot()
    series = {tuple(sorted(s["labels"].items())): s["value"]
              for s in snap["retention_tokens_total"]["series"]}
    # a document's first chunk (1,024 tokens, or all of a shorter one) is
    # the quadratic form alone; every other token reads a carried state
    assert series[(("path", "quadratic"),)] == 1024 + 900 + 1024 + 60
    assert series[(("path", "state"),)] == (4500 - 1024) + (1300 - 1024)
    segments = snap["lm_segments_total"]["series"]
    assert [s["value"] for s in segments] == [5.0]
    assert segments[0]["labels"] == {"op": "map_score_lm"}
    flops = snap["device_flops_total"]["series"]
    assert any(s["labels"].get("op") == "map_score_lm" and s["value"] > 0
               for s in flops)


@pytest.mark.parametrize("payload, message", [
    ("not a dict", "dict"),
    ({"model_config": TINY}, "requires"),
    ({"ids": [], "model_config": TINY}, "requires"),
    ({"ids": [[]], "model_config": TINY}, "non-empty"),
    ({"ids": [[1, 2.5]], "model_config": TINY}, "ints"),
    ({"ids": [[1, True]], "model_config": TINY}, "ints"),
    ({"ids": [[1, 3000]], "model_config": TINY}, "out of range"),
    ({"ids": [[-1, 5]], "model_config": TINY}, "out of range"),
    ({"ids": [[1, 2]], "model_config": {**TINY, "mixer": "softmax"}}, "mixer"),
    ({"ids": [[1, 2]], "model_config": {**TINY, "quant": "int4"}}, "quant"),
    ({"ids": "1 2 3", "model_config": TINY}, "requires"),
])
def test_bad_payloads_are_soft_errors(payload, message):
    out = get_op("map_score_lm")(payload)
    assert out["ok"] is False and message in out["error"]


@pytest.mark.parametrize("row, message", [
    ("12 x7 9", "whole numbers"), ("1 2.5 3", "whole numbers"),
    ("1 2 3000", "out of range"), ("4 -2 7", "out of range"),
    ("", "empty"), ("99999999999999999999999 1", "whole numbers"),
])
def test_malformed_and_out_of_range_csv_ids_are_bad_input(tmp_path, row, message):
    path = tmp_path / "bad.csv"
    path.write_text(f'id,ids\n0,"{row}"\n', encoding="ascii")
    out = get_op("map_score_lm")({
        "source_uri": str(path), "start_row": 0, "shard_size": 1,
        "model_config": TINY})
    assert out["ok"] is False and message in out["error"]


def test_shard_integrity_errors_fail_the_shard(tmp_path):
    path = tmp_path / "other.csv"
    path.write_text("id,text\n0,hello\n", encoding="ascii")
    with pytest.raises(RuntimeError, match="missing"):
        get_op("map_score_lm")({"source_uri": str(path), "start_row": 0,
                                "shard_size": 1, "model_config": TINY})


def test_rows_longer_than_the_csv_modules_default_field_limit(tmp_path):
    from agent_tpu.data.csv_index import read_shard_token_ids

    doc = np.full(40000, 2999, np.int32)            # 200 kB in one field
    path = _csv(tmp_path / "long.csv", [doc])
    got = read_shard_token_ids({"source_uri": path, "start_row": 0,
                                "shard_size": 1}, 3000)
    assert len(got) == 1 and np.array_equal(got[0], doc)


def test_registry_invariants():
    assert ops_pkg.OP_TO_MODULE["map_score_lm"] == "map_score_lm"
    fn = get_op("map_score_lm")
    assert ops_pkg.OPS_REGISTRY["map_score_lm"] is fn
    assert fn.deferred is True
    assert all(callable(getattr(fn, p)) for p in ("stage", "execute", "finalize"))
    assert "map_score_lm" in ops_pkg.list_ops()
    assert os.path.exists(os.path.join(
        ROOT, "agent_tpu", "ops", "map_score_lm.CONTRACT.md"))


def test_segment_plan():
    from agent_tpu.ops.map_score_lm import SEGMENT_BUCKETS, segment_plan

    assert SEGMENT_BUCKETS == (1024, 4096)
    assert segment_plan(1) == [(0, 1024)]
    assert segment_plan(1024) == [(0, 1024)]
    assert segment_plan(1025) == [(0, 4096)]
    assert segment_plan(4096) == [(0, 4096)]
    assert segment_plan(5000) == [(0, 4096), (4096, 1024)]
    assert segment_plan(16384) == [(i * 4096, 4096) for i in range(4)]
    assert segment_plan(9000) == [(0, 4096), (4096, 4096), (8192, 1024)]


ISOLATION = r"""
import json, os, sys, threading, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {root!r})
import requests
from agent_tpu.agent.app import Agent
from agent_tpu.config import AgentConfig, Config
from agent_tpu.controller.core import Controller
from agent_tpu.controller.server import ControllerServer
from agent_tpu.runtime.runtime import get_runtime

NEW = ["agent_tpu.models.decoder_lm", "agent_tpu.kernels.power_retention",
       "agent_tpu.ops.map_score_lm"]
with open({csv!r}, "w") as f:
    f.write("id,text\n" + "".join(f'{{i}},"row {{i}}"\n' for i in range(8)))
controller = Controller()
controller.submit_csv_job({csv!r}, total_rows=8, shard_size=4,
    map_op="map_classify_tpu", extra_payload={{
        "text_field": "text", "allow_fallback": False,
        "result_format": "columnar", "model_config": {{
            "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
            "max_len": 64, "dtype": "float32", "n_classes": 16}}}})
with ControllerServer(controller) as server:
    agent = Agent(config=Config(agent=AgentConfig(
        controller_url=server.url, agent_name="iso",
        tasks=("map_classify_tpu",), idle_sleep_sec=0.0, pipeline_depth=2)),
        session=requests.Session(), runtime=get_runtime())
    def watch():
        deadline = time.time() + 120
        while not controller.drained() and time.time() < deadline:
            time.sleep(0.02)
        agent.shutdown()
    threading.Thread(target=watch, daemon=True).start()
    agent.run()
out = {{"drained": controller.counts(),
       "after_drain": [m for m in NEW if m in sys.modules]}}
from agent_tpu.ops import get_op
fn = get_op("map_score_lm")
out["after_get_op"] = [m for m in NEW if m in sys.modules]
fn({{"ids": [[1, 2, 3]], "model_config": {{"vocab_size": 64, "d_model": 32,
    "n_heads": 2, "n_kv_heads": 1, "d_head": 16, "d_ff": 32, "n_layers": 1,
    "dtype": "float32"}}}})
out["after_first_task"] = [m for m in NEW if m in sys.modules]
print("ISOLATION " + json.dumps(out))
"""


def test_the_encoders_agent_never_imports_the_new_family(tmp_path):
    """An agent started as the benchmark's ``drain`` kind starts it
    (registry, runtime, pipelined runner; tasks: the classify op) drains a
    job without any of the new modules in ``sys.modules``; resolving the op
    imports its own module only, and the model and kernel files come with
    its first task."""
    script = ISOLATION.format(root=ROOT, csv=str(tmp_path / "rows.csv"))
    env = {k: v for k, v in os.environ.items() if k != "TASKS"}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("ISOLATION "))
    out = json.loads(line[len("ISOLATION "):])
    assert out["drained"] == {"succeeded": 2}
    assert out["after_drain"] == []
    assert out["after_get_op"] == ["agent_tpu.ops.map_score_lm"]
    assert sorted(out["after_first_task"]) == sorted([
        "agent_tpu.models.decoder_lm", "agent_tpu.kernels.power_retention",
        "agent_tpu.ops.map_score_lm"])
