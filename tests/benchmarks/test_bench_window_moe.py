"""The ``mellum2-12b-a2.5b.score-32k-window`` cell off the chip: its CPU
rehearsal through ``MODEL_OVERRIDES`` / ``TRAFFIC_OVERRIDES`` (as
``test_bench_latent_moe.py``), the needed-work functions against the hand
arithmetic of their docstring, the configuration's parameter count by a
count of the leaves' shapes, each new reader on a recorded ``run``, the
configuration file against the catalog's rules, and the manifest's entries. No
number printed here is a device number."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from agent_tpu.ops import map_score_lm  # noqa: E402
from agent_tpu.runtime.runtime import reset_runtime  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import manifest, stack  # noqa: E402

import test_bench_backlog  # noqa: E402

CELL = "mellum2-12b-a2.5b.score-32k-window"
CONFIG = "mellum2-12b-a2.5b"
TRAFFIC = "score-32k-window"
# ``test_bench_backlog.py`` holds every cell's backlog over the cell's rate at
# 100 % of its roofline and asks a new cell to bring that rate; its table may
# not be edited by a PR that adds a cell, so the entry comes from here (as
# ``test_bench_latent_moe.py`` brings its own): a document needs 101.88
# TFLOP, 0.5172 s at 197 TFLOP/s, 1.9336 rows/s.
test_bench_backlog.AT_THE_ROOFLINE.setdefault(CELL, 1.94)
# Two periods of (window, window, full) at a window of 300; 16 experts, all
# held, 4 chosen, none shared; an original length inside the document.
TINY_LM = {
    "vocab_size": 2048, "d_model": 64, "n_heads": 8, "n_kv_heads": 2,
    "d_head": 16, "n_layers": 6, "full_attention_every": 3,
    "sliding_window": 300, "rope_original_max_len": 700, "max_len": 16384,
    "n_experts": 16, "n_experts_held": 16, "n_experts_per_token": 4,
    "d_expert": 32, "dtype": "float32",
}
# 2,600 tokens under segments of 2,048 and 1,024 (the op's sizes halved for
# the CPU): both kinds of state cross a program boundary in every document.
DOC_TOKENS = 2600
SEGMENT_BUCKETS = (1024, 2048)
TINY_SCORE = {
    "doc_tokens": {"dist": "fixed", "value": DOC_TOKENS}, "job_rows": 4,
    "backlog_rows_per_s": 2, "lead_in_shards": 1, "trace_start_s": 0.2,
    "trace_seconds": 0.5,
}
PUBLISHED = manifest.load_config(manifest.load_manifest(), CONFIG)["model"]
needed = manifest.load_needed_work("window_moe_flops")


@pytest.fixture()
def tiny(monkeypatch):
    monkeypatch.setattr(stack, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(manifest, "MODEL_OVERRIDES", {CONFIG: TINY_LM})
    monkeypatch.setattr(manifest, "TRAFFIC_OVERRIDES",
                        {TRAFFIC: dict(TINY_SCORE)})
    monkeypatch.setattr(map_score_lm, "SEGMENT_BUCKETS", SEGMENT_BUCKETS)
    reset_runtime()
    yield monkeypatch
    reset_runtime()


def lines_of(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(tiny, capsys, trace):
    code = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 42),
                           "--seconds", "2", "--trace", str(trace)])
    result, lines = lines_of(capsys)
    assert code == 0, lines[-5:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    if trace == 0:
        assert set(result["metrics"]) == {"drain_rows_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in result["metrics"].values())
    else:
        names = {m["name"] for m in manifest.metrics_of_cell(
            manifest.load_manifest(), CELL, "per_layer")}
        assert set(result["metrics"]) <= names
        # The counter-based readers read; no device plane in a CPU trace, so
        # the device_trace readers are left out, never printed as a number.
        from agent_tpu.kernels.causal_attention import (
            query_tile, visited_pairs, window_visited_pairs)

        n, w = DOC_TOKENS, 300
        segments = [(2048, 0), (1024, 2048)]
        computed = sum(visited_pairs(s, p, query_tile(4, s)) for s, p in segments)
        share = result["metrics"]["window_causal_pair_share.drain"]["value"]
        assert share == pytest.approx(100.0 * (n * (n + 1) / 2) / computed)
        visited = sum(window_visited_pairs(s, p, w, query_tile(4, s))
                      for s, p in segments)
        share = result["metrics"]["window_attention_pair_share.drain"]["value"]
        assert share == pytest.approx(
            100.0 * sum(min(t + 1, w) for t in range(n)) / visited)
        pairs = result["metrics"]["window_expert_pairs_per_token.drain"]["value"]
        assert pairs == 4.0                    # every expert is held
        fill = result["metrics"]["expert_tile_fill.drain"]["value"]
        assert 50.0 < fill <= 100.0
        assert result["metrics"]["compiles_in_window.drain"]["value"] == 0
        assert not any("roofline" in n or "device_share" in n or "device_ms" in n
                       for n in result["metrics"])
    compared = {c["number"]: c for c in map(json.loads, (
        ln for ln in lines if ln.startswith('{"bench": "compared"')))}
    assert set(compared) == set(manifest.load_config(
        manifest.load_manifest(), CONFIG)["check"]["limits"])
    # float32 against the float32 reference: rounding in another order.
    assert result["correct"] is True, lines[-8:]
    assert compared["block_logprob_gap_max"]["value"] < 1e-4, compared


# ---- the counting functions against hand arithmetic ----------------------

def test_counts_of_a_32768_token_document_at_the_published_widths():
    """ISSUE 42's figures: 101.88 TFLOP a document: experts 38.96, full
    attention 26.39, projections and router 16.81, head 14.84, window
    attention 4.87; 38.2 / 25.9 / 16.5 / 14.6 / 4.8 %."""
    m, L = PUBLISHED, 32768
    d = 2304
    assert needed.layers_of(m) == {"full": 3, "window": 9}
    assert needed.projection_params(m) == (
        2 * d * 32 * 128 + 2 * d * 4 * 128) == 21_233_664
    assert needed.expert_params(m) == 3 * d * 896 == 6_193_152
    assert needed.pairs_per_token(m) == 8.0
    per_layer = 21_233_664 + d * 64 + 8 * 6_193_152
    assert needed.layer_flops_per_token(m) == 2.0 * per_layer
    assert 2.0 * (21_233_664 + d * 64) * 12 * L / 1e12 == pytest.approx(
        16.81, abs=0.005)
    assert needed.expert_flops(m, L) == 2.0 * 8 * 6_193_152 * 12 * L
    assert needed.expert_flops(m, L) / 1e12 == pytest.approx(38.96, abs=0.005)
    assert needed.expert_bytes(m, L) == 12 * (2 * 64 * 6_193_152 + 4 * d * 8 * L)
    # The MXU bounds the full tiles, not the weights' read.
    assert needed.expert_flops(m, L) / 197e12 > 4 * needed.expert_bytes(m, L) / 819e9
    assert needed.causal_pairs(L) == L * (L + 1) // 2
    assert needed.attention_flops(m, L) == 3 * 16384 * (L * (L + 1) // 2)
    assert needed.attention_flops(m, L) / 1e12 == pytest.approx(26.39, abs=0.005)
    assert needed.window_pairs(m, L) == 1024 * 1025 // 2 + (L - 1024) * 1024
    assert needed.window_pairs(m, 700) == 700 * 701 // 2
    assert needed.window_flops(m, L) == 9 * 16384 * needed.window_pairs(m, L)
    assert needed.window_flops(m, L) / 1e12 == pytest.approx(4.87, abs=0.005)
    # Every causal key on the nine window layers: 1.73 x the document's need.
    every_key = 9 * 16384 * needed.causal_pairs(L)
    total = needed.document_flops_needed(m, L)
    assert (total - needed.window_flops(m, L) + every_key) / total == (
        pytest.approx(1.73, abs=0.005))
    assert needed.attention_bytes(m, L) == 3 * L * 2 * 128 * (64 + 8)
    assert needed.window_bytes(m, L) == 9 * L * 18_432
    assert needed.window_flops(m, L) / 197e12 > needed.window_bytes(m, L) / 819e9
    assert needed.head_flops(m, L) == 2 * d * 98304 * L
    assert needed.head_flops(m, L) / 1e12 == pytest.approx(14.84, abs=0.005)
    assert needed.head_bytes_needed(m, L) == 2 * d * (98304 + L)
    assert total / 1e12 == pytest.approx(101.88, abs=0.005)
    assert total / 197e12 == pytest.approx(0.5172, abs=0.0005)
    # The cell's entry of the backlog's table: the rate at the roofline,
    # under the backlog's ceiling.
    assert 197e12 / total == pytest.approx(1.9336, abs=0.0005)
    assert test_bench_backlog.AT_THE_ROOFLINE[CELL] == 1.94 >= 197e12 / total
    from benchmarks.harness import backlog

    size = backlog.plan(manifest.load_traffic(TRAFFIC), 10.0)
    assert (size["n_jobs"], size["shards"]) == (4, 32)
    assert size["ceiling_rows_per_s"] == 2.7 > 1.94
    for fn, share in ((needed.expert_flops, 0.382), (needed.attention_flops, 0.259),
                      (needed.head_flops, 0.146), (needed.window_flops, 0.048)):
        assert fn(m, L) / total == pytest.approx(share, abs=0.001)
    whole = dict(m, n_layers=28)
    assert needed.head_flops(whole, L) / needed.document_flops_needed(
        whole, L) == pytest.approx(0.068, abs=0.001)
    assert needed.attention_flops(m, 65536) / needed.document_flops_needed(
        m, 65536) == pytest.approx(0.41, abs=0.005)


def test_the_parameters_by_a_count_of_the_leaves_shapes():
    """5,465,959,680 parameters with the final norm, 10.93 GB in bf16: the
    configuration file's arithmetic, from the shapes the program would
    build; the state a document carries, by kind."""
    import jax
    import numpy as np

    from agent_tpu.models.decoder_lm import (DecoderLMConfig, init_params,
                                             init_state)

    cfg = DecoderLMConfig(**PUBLISHED)
    shapes = jax.eval_shape(lambda: init_params(cfg, "count"))
    assert set(shapes) == {"embed", "head", "final_norm", "expert_layers"}
    layers = shapes["expert_layers"]
    count = lambda tree: sum(int(np.prod(a.shape))  # noqa: E731
                             for a in jax.tree_util.tree_leaves(tree))
    assert layers["we_gate"].shape == (12, 64, 2304, 896)
    assert layers["wq"].shape == (12, 2304, 4096)
    assert layers["wk"].shape == (12, 2304, 512)
    assert not {"router_bias", "ws_gate", "wg", "bg"} & set(layers)
    assert sum(count(layers[k]) for k in ("wq", "wk", "wv", "wo")
               ) == 12 * 21_233_664
    assert count(layers["w_router"]) == 12 * 147_456
    assert sum(count(layers[k]) for k in ("we_gate", "we_up", "we_down")
               ) == 12 * 396_361_728
    assert count(layers) == 12 * (417_742_848 + 4_864) == 12 * 417_747_712
    assert count(shapes) == 12 * 417_747_712 + 2 * 98304 * 2304 + 2304
    assert count(shapes) == 5_465_959_680
    text = manifest.load_config(manifest.load_manifest(), CONFIG)["deployment"]
    assert "5,465,959,680 parameters (5,466.0 M), 10.93 GB" in text
    # A full layer's keys and values at the document's length, a window
    # layer's last 1,024: 0.22 GB at 32,768 tokens (0.81 if every layer kept
    # every key).
    state = jax.eval_shape(lambda: init_state(cfg, 1, 32768))
    assert set(state) == {"mixer", "pairs", "tiles"}
    assert state["mixer"]["full"]["k"].shape == (3, 1, 4, 32768, 128)
    assert state["mixer"]["window"]["v"].shape == (9, 1, 4, 1024, 128)
    assert 2 * count(state["mixer"]) == 3 * 67_108_864 + 9 * 2_097_152
    assert 12 * 67_108_864 / 1e9 == pytest.approx(0.81, abs=0.01)


def test_the_programs_own_count_covers_the_need():
    """``segment_flops`` (the ``device_mfu{op}`` numerator) counts the exact
    pairs of either kind: the need, to the rounding of the causal half."""
    from agent_tpu.models.decoder_lm import DecoderLMConfig, segment_flops

    cfg = DecoderLMConfig(**PUBLISHED)
    done = sum(segment_flops(cfg, 4096, pos0) for pos0 in range(0, 32768, 4096))
    need = needed.document_flops_needed(PUBLISHED, 32768)
    assert done == pytest.approx(need, rel=2e-4)


def test_means_over_documents():
    mean = needed.mean_needed(PUBLISHED, [32768, 8192])
    assert set(mean) == {"flops", "head_flops", "head_bytes", "attention_flops",
                         "attention_bytes", "expert_flops", "expert_bytes",
                         "window_flops", "window_bytes"}
    assert mean["flops"] == (needed.document_flops_needed(PUBLISHED, 32768)
                             + needed.document_flops_needed(PUBLISHED, 8192)) / 2
    assert mean["head_bytes"] == 2 * 2304 * (98304 + (32768 + 8192) / 2)


def test_the_counters_of_visited_tiles():
    """Eight query heads a key head take 512 queries a step: at 32,768 tokens
    98.5 % of the full layers' visited pairs are causal ones; a window layer
    visits three 512-key tiles a query tile for the two its window needs."""
    from agent_tpu.kernels.causal_attention import (
        query_tile, visited_pairs, window_visited_pairs)

    assert query_tile(8, 4096) == 512
    visited = sum(visited_pairs(4096, p, 512) for p in range(0, 32768, 4096))
    assert 100.0 * needed.causal_pairs(32768) / visited == pytest.approx(
        98.46, abs=0.01)
    window = sum(window_visited_pairs(4096, p, 1024, 512)
                 for p in range(0, 32768, 4096))
    assert window == 32768 * 1536 - 3 * 512 * 512
    assert 100.0 * needed.window_pairs(PUBLISHED, 32768) / window == (
        pytest.approx(66.67, abs=0.05))
    # Every causal key would have been eleven times the tiles.
    assert visited / window == pytest.approx(11.0, abs=0.1)


# ---- each new reader on a recorded run ----------------------------------

def recorded_run():
    """What a traced run of the cell records, with round numbers: 1.0
    document a second, a 2 s traced interval all busy, the segment programs
    1.8 s of it and the head 0.2 s; the window kernel 0.1 s, the full layers'
    0.4 s, the grouped matmul 0.6 s."""
    def counters(window, visited, causal, computed, pairs, tokens, tiles):
        return {
            "window_attention_pairs_total": {"series": [
                {"labels": {"kind": "window"}, "value": window},
                {"labels": {"kind": "computed"}, "value": visited}]},
            "causal_attention_pairs_total": {"series": [
                {"labels": {"kind": "causal"}, "value": causal},
                {"labels": {"kind": "computed"}, "value": computed}]},
            "moe_expert_pairs_total": {"series": [{"labels": {}, "value": pairs}]},
            "moe_tokens_total": {"series": [{"labels": {}, "value": tokens}]},
            "moe_tiles_total": {"series": [{"labels": {}, "value": tiles}]}}
    return {
        "kind": "drain", "lm_needed": needed.mean_needed(PUBLISHED, [32768]),
        "end_to_end": {"drain_rows_per_s": 1.0},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "agent_metrics": (counters(1e6, 2e6, 1e6, 2e6, 10.0, 10.0, 5.0),
                          counters(1e6 + 660.0, 2e6 + 1000.0, 1e6 + 985.0,
                                   2e6 + 1000.0, 10.0 + 8192.0, 10.0 + 1024.0,
                                   5.0 + 40.0)),
        "trace": {"window_s": 2.0, "busy_s": 2.0, "programs": {
            "lm_segment": {"clipped_seconds": 1.8, "seconds": 1.8, "count": 16},
            "lm_loss_head": {"clipped_seconds": 0.2, "seconds": 0.2,
                             "count": 16}}},
        "op_times": {"window_attention": {"seconds": 0.1, "count": 144},
                     "causal_attention": {"seconds": 0.4, "count": 48},
                     "expert_ffn": {"seconds": 0.6, "count": 192}},
    }


@pytest.mark.parametrize("name, want", [
    ("lm_roofline", 100 * 1.0 * 101.881800032256e12 / 1.0 / 197e12),
    ("loss_head_roofline", 100 * 1.0 * (14.843406974976e12 / 197e12) / (0.2 / 2)),
    ("window_attention_roofline",
     100 * 1.0 * (4.870568411136e12 / 197e12) / 0.05),
    ("window_causal_attention_roofline",
     100 * 1.0 * (26.389084372992e12 / 197e12) / 0.2),
    ("window_expert_ffn_roofline",
     100 * 1.0 * (38.963943309312e12 / 197e12) / 0.3),
    ("mixed_attention_device_share.drain", 25.0),
    ("window_attention_pair_share.drain", 66.0),
    ("window_causal_pair_share.drain", 98.5),
    ("window_expert_pairs_per_token.drain", 8.0),
    ("expert_tile_fill.drain", 80.0),
])
def test_reader_on_a_recorded_run(name, want):
    reader = manifest.load_layer_metric(name)
    assert reader.read(recorded_run()) == pytest.approx(want, rel=1e-9)
    assert 0 < want < 100


NEW_READERS = ["window_attention_roofline", "window_attention_pair_share.drain",
               "mixed_attention_device_share.drain", "expert_tile_fill.drain"]
# The accepted readers this cell reads under entries of its own: the
# accepted entries' lists of cells are held to literal lists by
# ``test_bench_sparse_mla.py``, ``test_bench_hybrid_ssm.py`` and
# ``test_bench_parts.py``, which no PR but a ``benchmark`` PR may edit.
ACCEPTED = {
    "window_causal_attention_roofline": "causal_attention_roofline",
    "window_causal_pair_share.drain": "causal_attention_pair_share.drain",
    "window_expert_ffn_roofline": "expert_ffn_roofline",
    "window_expert_pairs_per_token.drain": "expert_pairs_per_token.drain",
    **{"window_" + name: name for name in (
        "unnamed_device_share.drain", "norm_device_ms_per_shard.drain",
        "project_device_ms_per_shard.drain", "mixer_device_ms_per_shard.drain",
        "around_device_ms_per_shard.drain",
        "experts_device_ms_per_shard.drain")},
}


@pytest.mark.parametrize("name", NEW_READERS + sorted(ACCEPTED))
def test_reader_reads_nothing_where_the_program_has_nothing(name):
    """On the parent (no such kernel or counter), under another family's
    needed-work counter, and untraced."""
    reader = manifest.load_layer_metric(name)
    bare = {"kind": "drain", "end_to_end": {"drain_rows_per_s": 1.3},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "lm_needed": {"flops": 1e14, "head_flops": 1e13, "head_bytes": 1e9,
                          "retention_flops": 1e13, "retention_bytes": 1e9},
            "agent_metrics": ({}, {}), "op_times": {
                "retention": {"seconds": 0.4, "count": 10},
                "window_attention": {"seconds": 0.0, "count": 0},
                "expert_ffn": {"seconds": 0.0, "count": 0},
                "causal_attention": {"seconds": 0.0, "count": 0}},
            "trace": {"window_s": 3.0, "busy_s": 3.0, "programs": {
                "lm_segment": {"clipped_seconds": 2.5}}},
            # What ``part_times.of_run`` keeps of a program with no part map.
            "parts": None}
    assert reader.read(bare) is None
    assert reader.read(dict(bare, trace=None, op_times=None)) is None


def test_the_device_share_wants_both_kernels_and_the_fill_wants_tiles():
    """The causal kernel alone is falcon's or mistral's mixer; pairs without
    tiles are deepseek's or mistral's count: nothing to read."""
    reader = manifest.load_layer_metric("mixed_attention_device_share.drain")
    run = recorded_run()
    run["op_times"]["window_attention"] = {"seconds": 0.0, "count": 0}
    assert reader.read(run) is None
    fill = manifest.load_layer_metric("expert_tile_fill.drain")
    run = recorded_run()
    for snap in run["agent_metrics"]:
        del snap["moe_tiles_total"]
    assert fill.read(run) is None
    from agent_tpu.kernels import grouped_ffn

    assert fill.ROW_TILE == grouped_ffn.ROW_TILE


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_an_accepted_reader_is_read_not_copied(name):
    mine = manifest.load_layer_metric(name)
    accepted = manifest.load_layer_metric(ACCEPTED[name])
    assert mine.read.__code__.co_filename == accepted.read.__code__.co_filename
    assert mine.read.__code__.co_filename.endswith(ACCEPTED[name] + ".py")
    assert getattr(mine, "OP_PATTERNS", None) == getattr(
        accepted, "OP_PATTERNS", None)
    entries = {e["name"]: e for e in manifest.load_manifest()["per_layer"]}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entries[name][key] == entries[ACCEPTED[name]][key]


def test_op_patterns_tell_the_two_attention_kernels_apart():
    """An ``XLA Ops`` event is named by its whole instruction, operands and
    all: a fusion that reads a kernel's result names it. Each pattern takes
    its own kernel only, and the window layers' is not the full layers'."""
    import inspect
    import re

    from agent_tpu.kernels import causal_attention

    events = {
        "window_attention": "%window_gqa_attention.38 = bf16[4,8,4096,128]{3,2,1,0} "
        "custom-call(s32[1]{0} %reshape.9, bf16[4,8,4096,128]{3,2,1,0} %fusion.5, "
        "bf16[4,5120,128]{2,1,0} %concatenate.2)",
        "causal_attention": "%causal_gqa_attention.12 = bf16[4,8,4096,128]{3,2,1,0} "
        "custom-call(s32[1]{0} %reshape.3, bf16[4,8,4096,128]{3,2,1,0} %fusion.6, "
        "bf16[4,32768,128]{2,1,0} %copy-done)",
        None: "%fusion.77 = bf16[4096,2304]{1,0} fusion(bf16[4,8,4096,128]{3,2,1,0} "
        "%window_gqa_attention.38, bf16[4,8,4096,128]{3,2,1,0} "
        "%causal_gqa_attention.12), kind=kLoop",
    }
    patterns = {}
    for name in ("window_attention_roofline",
                 "mixed_attention_device_share.drain",
                 "window_causal_attention_roofline"):
        patterns.update(manifest.load_layer_metric(name).OP_PATTERNS)
    assert set(patterns) == {"window_attention", "causal_attention"}
    for label, rx in patterns.items():
        assert [k for k, text in events.items() if re.search(rx, text)] == [label]
    source = inspect.getsource(causal_attention)
    assert '"window_gqa_attention"' in source and '"causal_gqa_attention"' in source


def test_documents_draw_their_ids_from_the_whole_vocabulary():
    score = manifest.load_kind("score")
    traffic = manifest.load_traffic(TRAFFIC)
    docs = score.documents(traffic, PUBLISHED["vocab_size"], 2 ** 31 + 5, 2)
    assert [len(d) for d in docs] == [32768, 32768]
    assert 0 <= min(d.min() for d in docs) and max(
        d.max() for d in docs) < 98304
    assert max(d.max() for d in docs) > 90000


# ---- the configuration file and the manifest's entries -------------------

def test_the_configuration_file_keeps_the_catalogs_rules():
    m = manifest.load_manifest()
    cfg = manifest.load_config(m, CONFIG)
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and "Mellum2-12B-A2.5B-Instruct" in cfg[
        "source"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers"}
    assert set(cfg["published"]) <= set(cfg)
    model, pub = cfg["model"], cfg["published"]
    for ours, theirs in {
            "d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "d_head": "head_dim",
            "d_ff": "intermediate_size", "max_len": "max_position_embeddings",
            "rms_norm_eps": "rms_norm_eps", "vocab_size": "vocab_size",
            "d_expert": "moe_intermediate_size", "n_experts": "num_experts",
            "n_experts_held": "num_experts",
            "n_experts_per_token": "num_experts_per_tok",
            "sliding_window": "sliding_window"}.items():
        assert model[ours] == pub[theirs], (ours, theirs)
    # The layer pattern: a period of three window layers and a full one, as
    # often as the depth allows; the cut keeps three whole periods.
    kinds = pub["layer_types"]
    assert len(kinds) == pub["num_hidden_layers"] == 28 == 7 * 4
    assert kinds == (["sliding_attention"] * 3 + ["full_attention"]) * 7
    assert model["full_attention_every"] == 4
    assert model["n_layers"] == cfg["num_hidden_layers"] == 12 == 3 * 4 >= 4 + 4
    assert set(pub["mlp_layer_types"]) == {"sparse"} and model["n_dense_layers"] == 0
    rope = pub["rope_parameters"]
    assert cfg["rope_parameters"] == rope
    full, window = rope["full_attention"], rope["sliding_attention"]
    assert window == {"rope_type": "default", "rope_theta": 500000}
    for ours, theirs in {
            "rope_theta": "rope_theta", "rope_factor": "factor",
            "rope_original_max_len": "original_max_position_embeddings",
            "rope_beta_fast": "beta_fast", "rope_beta_slow": "beta_slow"}.items():
        assert model[ours] == full[theirs], (ours, theirs)
    import math

    assert full["attention_factor"] == pytest.approx(
        0.1 * model["rope_mscale"] * math.log(model["rope_factor"]) + 1.0,
        rel=1e-12)
    assert pub["norm_topk_prob"] is True and model["routed_scale"] == 1.0
    assert model["n_shared_experts"] == 0 and model["scoring_func"] == "softmax"
    assert model["mixer"] == "window_gqa" and model["dtype"] == "bfloat16"
    for key in ("qk_norm", "rotation", "window", "router", "dense_width", "mtp",
                "weights"):
        assert len(cfg["assumed"][key]) > 80, key
    assert cfg["control"]["model_config"] == {"quant": "int8"}
    assert cfg["check"]["docs"] in (1, 2) and set(cfg["check"]["limits"]) <= set(
        cfg["check"]["why"])
    # The op takes every key of the model group.
    from agent_tpu.models.decoder_lm import DecoderLMConfig, validate
    from agent_tpu.ops._model_common import cfg_key

    assert set(model) <= set(DecoderLMConfig.__dataclass_fields__)
    validate(DecoderLMConfig(**model))
    hash(cfg_key(DecoderLMConfig(**model)))


@pytest.mark.parametrize("first_block, trimmed_moves", [(0.0, False),
                                                        (14.0, False),
                                                        (14.0, True)])
def test_the_mean_square_leaves_the_furthest_sixteenth_out(first_block,
                                                           trimmed_moves):
    """One block far off (a document's first: the chip read 1.4e-2 in one
    sound document of nineteen) moves the largest block's number and not
    the trimmed mean square; every block off (what a lower precision does)
    moves it."""
    ref = manifest.load_reference("window_moe_lm")
    rng = np.random.default_rng(7)
    want = [rng.normal(size=32) * 100.0 for _ in range(2)]
    noise = [rng.normal(size=32) * 1.8 for _ in range(2)]
    n_tokens = [32768, 32768]
    sound = ref.compare([w + e for w, e in zip(want, noise)], want, n_tokens)
    scale = 1.7 if trimmed_moves else 1.0
    served = [w + scale * e for w, e in zip(want, noise)]
    served[0][0] += first_block
    got = ref.compare(served, want, n_tokens)
    assert set(got) == {"block_logprob_bias", "block_logprob_gap_max",
                        "block_logprob_gap_slope",
                        "block_logprob_gap_rms_trimmed"}
    kept = 64 - 64 // ref.TRIMMED_SHARE
    assert kept == 60
    if first_block:
        assert got["block_logprob_gap_max"] > 0.01
    if trimmed_moves:
        assert got["block_logprob_gap_rms_trimmed"] == pytest.approx(
            1.7 * sound["block_logprob_gap_rms_trimmed"], rel=0.08)
    else:
        assert got["block_logprob_gap_rms_trimmed"] == pytest.approx(
            sound["block_logprob_gap_rms_trimmed"], rel=0.08)
    # Fewer blocks than the share: nothing is left out.
    few = ref.compare([[1.0, 3.0]], [[0.0, 0.0]], [2049])
    assert few["block_logprob_gap_rms_trimmed"] == pytest.approx(
        np.sqrt((1.0 / 1024) ** 2 / 2 + (3.0 / 1024) ** 2 / 2))


def test_manifest_entries_of_the_cell(manifests):
    m = manifests
    cell = manifest.find_cell(m, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    cfg = manifest.load_config(m, cell["config"])
    assert cfg["needed_work"] == "window_moe_flops"
    assert cfg["reference"] == "window_moe_lm"
    traffic = manifest.load_traffic(cell["traffic"])
    assert traffic["kind"] == "score" and traffic["shard_rows"] == 1
    assert traffic["doc_tokens"] == {"dist": "fixed", "value": 32768}
    assert traffic["token_ids"] == {"dist": "zipf", "exponent": 1.1}
    assert traffic["backlog_rows_per_s"] == 2.0
    assert (traffic["job_rows"], traffic["tenants"], traffic["order_seed"],
            traffic["lead_in_shards"], traffic["agent"]) == (8, 1, 0, 3, {})
    e2e = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "end_to_end")}
    assert e2e == {"drain_rows_per_s", "setup_s"}
    per_layer = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "per_layer")}
    # Everything the score cells share (the by-part readers under entries of
    # this cell's own; no ``ffn``: the model has no dense FFN and no shared
    # expert), and what this one brings.
    shared = {e["name"] for e in manifest.metrics_of_cell(
        m, "brumby-14b-base.score-long", "per_layer")} & {
        e["name"] for e in manifest.metrics_of_cell(
            m, "deepseek-v3.2.score-32k", "per_layer")} & {
        e["name"] for e in manifest.metrics_of_cell(
            m, "falcon-h1-34b.score-64k", "per_layer")}
    mine = set(NEW_READERS) | set(ACCEPTED)
    assert {("window_" + n if "window_" + n in ACCEPTED else n)
            for n in shared if not n.startswith("ffn_")} | mine <= per_layer
    assert "trace_lower_s.setup" in per_layer
    assert not {n for n in per_layer if n.startswith(
        ("retention_", "sparse_", "indexer_", "ssd_", "hybrid_", "latent_",
         "ffn_", "window_ffn_"))}
    for entry in m["per_layer"]:
        if entry["name"] in mine:
            assert CELL in entry["workloads"]
            assert entry["moves"] == "drain_rows_per_s"
