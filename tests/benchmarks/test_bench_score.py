"""The ``brumby-14b-base.score-long`` cell off the chip: its CPU rehearsal
through ``MODEL_OVERRIDES`` / ``TRAFFIC_OVERRIDES`` (as
``test_bench_rehearsal.py``), the counting functions against hand arithmetic,
each new reader on a recorded ``run``, and a broken path (a state dropped
between segments has to print ``correct: false``). No number printed here is
a device number."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from agent_tpu.runtime.runtime import reset_runtime  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import lm_flops, manifest, op_times, stack  # noqa: E402

CELL = "brumby-14b-base.score-long"
TINY_LM = {
    "vocab_size": 2048, "d_model": 64, "n_heads": 10, "n_kv_heads": 2,
    "d_head": 16, "d_ff": 128, "n_layers": 2, "dtype": "float32",
}
# 5,000 tokens: a 4,096-token segment and a 1,024-token one, so the state
# crosses a program boundary in every document.
TINY_SCORE = {
    "doc_tokens": {"dist": "fixed", "value": 5000}, "job_rows": 4,
    "backlog_rows_per_s": 6, "lead_in_shards": 1, "trace_start_s": 0.2,
    "trace_seconds": 0.5,
}
PUBLISHED = manifest.load_config(manifest.load_manifest(),
                                 "brumby-14b-base")["model"]


@pytest.fixture()
def tiny(monkeypatch):
    monkeypatch.setattr(stack, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(manifest, "MODEL_OVERRIDES",
                        {"brumby-14b-base": TINY_LM})
    monkeypatch.setattr(manifest, "TRAFFIC_OVERRIDES",
                        {"score-long": dict(TINY_SCORE)})
    reset_runtime()
    yield monkeypatch
    reset_runtime()


def lines_of(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(tiny, capsys, trace):
    code = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 27),
                           "--seconds", "2", "--trace", str(trace)])
    result, lines = lines_of(capsys)
    assert code == 0, lines[-5:]
    # float32 against the float32 reference: the same arithmetic.
    assert result["correct"] is True, lines[-8:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    if trace == 0:
        assert set(result["metrics"]) == {"drain_rows_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in result["metrics"].values())
    else:
        names = {m["name"] for m in manifest.metrics_of_cell(
            manifest.load_manifest(), CELL, "per_layer")}
        assert set(result["metrics"]) <= names
        # The counter-based reader reads; no device plane in a CPU trace, so
        # the device_trace readers are left out, never printed as a number.
        share = result["metrics"]["retention_state_token_share.drain"]["value"]
        assert share == pytest.approx(100.0 * (5000 - 1024) / 5000)
        assert result["metrics"]["compiles_in_window.drain"]["value"] == 0
        assert not any("roofline" in n or "device_share" in n
                       for n in result["metrics"])
    compared = {c["number"]: c for c in map(json.loads, (
        ln for ln in lines if ln.startswith('{"bench": "compared"')))}
    assert set(compared) == {"block_logprob_bias", "block_logprob_gap_max",
                             "block_logprob_gap_slope"}
    assert all(c["value"] < 1e-4 for c in compared.values()), compared


def test_a_state_dropped_between_segments_is_not_correct(tiny, capsys):
    """Every later segment starts from an empty state (the hand-over lost):
    the documents' second segments are scored without their first 4,096
    tokens, which the largest single-block gap and the slope show against
    the configuration's own limits."""
    from agent_tpu.models import decoder_lm

    real = decoder_lm.forward_segment

    def forgetful(params, ids, pos0, state, cfg, **kw):
        if state is not None:
            state = tuple(s * 0 for s in state)
        return real(params, ids, pos0, state, cfg, **kw)

    tiny.setattr(decoder_lm, "forward_segment", forgetful)
    # 4,160 tokens: the second segment holds 64, all within the memory (16
    # and 32 tokens at these two heads) of what the first segment left, so
    # the last block's gap is whole, not diluted over a thousand tokens
    # (read on the CPU over 4 seeds: gap 0.012-0.077 against the limit
    # 0.002, slope 2.4e-3 to 1.5e-2 against 8e-5).
    tiny.setitem(manifest.TRAFFIC_OVERRIDES, "score-long", dict(
        TINY_SCORE, doc_tokens={"dist": "fixed", "value": 4160}))
    code = bench_run.main(["--workload", CELL, "--seed", "8",
                           "--seconds", "1", "--trace", "0"])
    last, lines = lines_of(capsys)
    compared = {c["number"]: c for c in map(json.loads, (
        ln for ln in lines if ln.startswith('{"bench": "compared"')))}
    assert code == 0 and last["correct"] is False, lines[-6:]
    assert compared["block_logprob_gap_max"]["ok"] is False
    assert compared["block_logprob_gap_slope"]["ok"] is False


def test_a_second_familys_needed_work_counter_is_found_by_name(
        tiny, appended, capsys):
    """A later PR's decoder family on the ``score`` kind: its configuration
    names its own needed-work counter (``conftest.py`` writes the file and
    appends the entries; nothing under ``harness/`` is edited), the kind
    finds it by that name and the two shared readers read its three keys."""
    cell = appended["workloads"][-1]
    assert cell["config"] == "appended-lm" and cell["chips"] == 4
    tiny.setitem(manifest.MODEL_OVERRIDES, "appended-lm", TINY_LM)
    tiny.setitem(manifest.TRAFFIC_OVERRIDES, "appended-mix", dict(TINY_SCORE))
    run = bench_run.run_cell(appended, cell["name"], 2 ** 31 + 32, 2.0, 1)
    capsys.readouterr()
    assert run["correct"] is True and run["failed"] == 0 and run["shards"] > 0
    assert run["config"]["needed_work"] == "appended_needed"
    assert run["lm_needed"] == {"flops": 35000.0, "head_flops": 10000.0,
                                "head_bytes": 15000.0}
    assert run["mean_flops_per_row"] == 35000.0
    # The shared ``.drain`` readers read its cell; its own reader finds
    # nothing in this run and is left out.
    assert run["metrics"]["compiles_in_window.drain"]["value"] == 0
    assert "appended_ms.drain" not in run["metrics"]
    # No device plane in a CPU trace; on a recorded one the shared readers
    # read this counter's keys, and brumby's own reader finds nothing.
    recorded = dict(recorded_run(), lm_needed=run["lm_needed"])
    read = lambda name: manifest.load_layer_metric(name).read(recorded)  # noqa: E731
    assert read("lm_roofline") == pytest.approx(
        100 * 1.25 * 35000.0 / (2.9 / 3.0) / 197e12)
    assert read("loss_head_roofline") == pytest.approx(
        100 * 1.25 * (15000.0 / 819e9) / (0.5 / 3.0))
    assert read("retention_roofline") is None
    # The committed cell still counts with the file it names.
    brumby = manifest.load_config(appended, "brumby-14b-base")
    assert manifest.load_needed_work(brumby["needed_work"]).mean_needed(
        PUBLISHED, [16384]) == lm_flops.mean_needed(PUBLISHED, [16384])


# ---- the counting functions against hand arithmetic ----------------------

def test_counts_of_a_16384_token_document_at_the_published_widths():
    """ISSUE 27's figures: a layer 330.3 M parameters; 86.6 TFLOP in the
    eight layers' matmuls, 25.5 in the head, retention about 14.4 (here
    13.84: a document's first chunk reads no state and its last chunk's
    update is needed by nobody), 126.5 in all (here 125.9): 0.64 s at 197
    TF/s."""
    m, L = PUBLISHED, 16384
    d, f, V = 5120, 17408, 151936
    params = d * (2 * 40 * 128 + 2 * 8 * 128 + 8) + 3 * d * f
    assert params == 330_342_400 == lm_flops.layer_matmul_params(m)
    assert lm_flops.layers_flops(m, L) == 2 * params * 8 * L
    assert lm_flops.layers_flops(m, L) / 1e12 == pytest.approx(86.6, abs=0.05)
    assert lm_flops.head_flops(m, L) == 2 * d * V * L
    assert lm_flops.head_flops(m, L) / 1e12 == pytest.approx(25.5, abs=0.05)
    assert lm_flops.distinct_products(128) == 8256
    # a query-head token: quadratic 256 L; chunked 256 c + the state
    quadratic = lm_flops.retention_quadratic_flops(m, L) / (8 * 40 * L)
    assert quadratic == pytest.approx(256 * L, rel=1e-3)
    read = 2 * 8256 * 128
    chunked = (256 * 1025 + read * 15 / 16 + read / 5 * 15 / 16)
    assert lm_flops.retention_chunked_flops(m, L) / (8 * 40 * L) == \
        pytest.approx(chunked, rel=1e-9)
    assert chunked == pytest.approx(2.64e6, rel=2e-3)
    assert lm_flops.retention_flops_needed(m, L) == \
        lm_flops.retention_chunked_flops(m, L)
    assert lm_flops.retention_flops_needed(m, L) / 1e12 == \
        pytest.approx(14.4, rel=0.05)
    total = lm_flops.document_flops_needed(m, L)
    assert total / 1e12 == pytest.approx(126.5, rel=0.01)
    assert total / 197e12 == pytest.approx(0.64, abs=0.005)


@pytest.mark.parametrize("L, cheaper", [
    (512, "quadratic"), (1024, "quadratic"), (4096, "quadratic"),
    (8192, "quadratic"), (12288, "chunked"), (16384, "chunked"),
    (32768, "chunked"),
])
def test_the_cheaper_form_switches_near_10k_tokens(L, cheaper):
    q = lm_flops.retention_quadratic_flops(PUBLISHED, L)
    c = lm_flops.retention_chunked_flops(PUBLISHED, L)
    assert (q <= c) == (cheaper == "quadratic"), (q, c)
    assert lm_flops.retention_flops_needed(PUBLISHED, L) == min(q, c)


def test_bytes_and_means():
    m = PUBLISHED
    assert lm_flops.retention_bytes_needed(m, 1000) == 8 * 1000 * (
        4 * 40 * 128 + 4 * 8 * 128 + 32)
    assert lm_flops.head_bytes_needed(m, 1000) == 2 * 5120 * (151936 + 1000)
    mean = lm_flops.mean_needed(m, [16384, 16384])
    assert mean["flops"] == lm_flops.document_flops_needed(m, 16384)
    assert mean["head_flops"] == lm_flops.head_flops(m, 16384)


# ---- each new reader on a recorded run ----------------------------------

def recorded_run():
    """What a traced run of the cell records, with round numbers: 1.25
    documents a second, a 3 s traced interval all busy, the segment programs
    2.4 s of it, the head 0.5 s, the retention kernels 0.45 s."""
    needed = lm_flops.mean_needed(PUBLISHED, [16384])
    counters = lambda s, q: {"retention_tokens_total": {"series": [  # noqa: E731
        {"labels": {"path": "state"}, "value": s},
        {"labels": {"path": "quadratic"}, "value": q}]}}
    return {
        "kind": "drain", "lm_needed": needed,
        "end_to_end": {"drain_rows_per_s": 1.25},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "agent_metrics": (counters(15360.0, 1024.0),
                          counters(15360.0 * 11, 1024.0 * 11)),
        "trace": {"window_s": 3.0, "busy_s": 3.0, "programs": {
            "lm_segment": {"clipped_seconds": 2.4, "seconds": 2.2, "count": 14},
            "lm_loss_head": {"clipped_seconds": 0.5, "seconds": 0.5, "count": 15}}},
        "op_times": {"retention": {"seconds": 0.45, "count": 120}},
    }


@pytest.mark.parametrize("name, want", [
    ("lm_roofline", 100 * 1.25 * 125.929783296e12 / (2.9 / 3.0) / 197e12),
    ("retention_roofline", 100 * 1.25 * (13.84187428864e12 / 197e12) / 0.15),
    ("loss_head_roofline",
     100 * 1.25 * (25.49063090176e12 / 197e12) / (0.5 / 3.0)),
    ("retention_device_share.drain", 15.0),
    ("retention_state_token_share.drain", 93.75),
])
def test_reader_on_a_recorded_run(name, want):
    reader = manifest.load_layer_metric(name)
    assert reader.read(recorded_run()) == pytest.approx(want, rel=1e-9)
    assert 0 < want < 100


@pytest.mark.parametrize("name", [
    "lm_roofline", "retention_roofline", "loss_head_roofline",
    "retention_device_share.drain", "retention_state_token_share.drain"])
def test_reader_reads_nothing_where_the_program_has_nothing(name):
    """On the parent (no such program, kernel or counter) and untraced."""
    reader = manifest.load_layer_metric(name)
    bare = {"kind": "drain", "end_to_end": {"drain_rows_per_s": 1000.0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "agent_metrics": ({}, {}), "trace": {
                "window_s": 3.0, "busy_s": 3.0, "programs": {
                    "classify": {"clipped_seconds": 3.0}}}}
    assert reader.read(bare) is None
    assert reader.read(dict(bare, trace=None)) is None


def test_op_times_on_the_recorded_fixture():
    """The reduction by operation name on the repo's recorded v5e trace:
    the classify program's fusions are there, a retention kernel is not."""
    pd = __import__("benchmarks.harness.trace_reduce", fromlist=["x"]).load(
        os.path.join(ROOT, "benchmarks", "fixtures", "fixture.xplane.pb.gz"))
    out = op_times.reduce_ops(pd, {"retention": r"power_retention",
                                   "fusions": r"^%fusion"})
    assert out["retention"] == {"seconds": 0.0, "count": 0}
    assert out["fusions"]["seconds"] > 0 and out["fusions"]["count"] > 0
    assert op_times.reduce_ops(pd, {}) == {}


def test_documents_are_seeded_zipf_over_the_whole_vocabulary():
    score = manifest.load_kind("score")
    traffic = manifest.load_traffic("score-long")
    a = score.documents(traffic, 151936, 2 ** 31 + 5, 3)
    b = score.documents(traffic, 151936, 2 ** 31 + 5, 3)
    c = score.documents(traffic, 151936, 7, 3)
    assert [len(d) for d in a] == [16384] * 3
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    ids = np.concatenate(a)
    assert 0 <= ids.min() and ids.max() < 151936
    # Zipf 1.1: the most frequent id is several percent of the tokens.
    assert 0.03 < np.bincount(ids).max() / len(ids) < 0.25


def test_manifest_entries_of_the_cell(manifests):
    m = manifests
    cell = manifest.find_cell(m, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    cfg = manifest.load_config(m, cell["config"])
    assert cfg["reduced"] == ["num_hidden_layers"] == [
        k for k, v in cfg["published"].items() if cfg[k] != v]
    assert cfg["model"]["n_layers"] == cfg["num_hidden_layers"] == 8
    assert cfg["model"]["d_model"] == cfg["hidden_size"]
    assert cfg["model"]["vocab_size"] == cfg["vocab_size"] == 151936
    traffic = manifest.load_traffic(cell["traffic"])
    assert traffic["shard_rows"] == 1 and traffic["job_rows"] == 8
    assert traffic["doc_tokens"] == {"dist": "fixed", "value": 16384}
    e2e = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "end_to_end")}
    assert e2e == {"drain_rows_per_s", "setup_s"}
    per_layer = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "per_layer")}
    assert {"lm_roofline", "retention_roofline", "loss_head_roofline",
            "retention_device_share.drain",
            "retention_state_token_share.drain",
            "compiles_in_window.drain", "fetch_wait_ms_per_shard.drain",
            "stage_ms_per_shard.drain"} <= per_layer
    # Six of PR 24's seven read this cell too (PR 28); a window of 13 shards
    # need not hold one lease poll that came back with tasks, so
    # ``lease_rtt_ms.drain`` is not listed. The encoder's two have nothing
    # to read here.
    assert {"agent_device_busy.drain", "dispatch_starved_ms_per_shard.drain",
            "http_post_ms_per_shard.drain", "xla_executables_in_window.drain",
            "xla_compile_s.setup", "params_s.setup"} <= per_layer
    assert not {"encoder_roofline", "whole_row_attention_blocks.setup",
                "fused_qkv_attention_blocks.setup",
                "lease_rtt_ms.drain"} & per_layer
    # The work a document needs is counted by the file the configuration
    # names, found by that name as its reference is.
    assert cfg["needed_work"] == "lm_flops" and cfg["reference"] == "retention_lm"
