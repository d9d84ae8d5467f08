"""The ``falcon-h1-34b.score-64k`` cell off the chip: its CPU rehearsal
through ``MODEL_OVERRIDES`` / ``TRAFFIC_OVERRIDES`` (as
``test_bench_sparse_mla.py``), the needed-work functions against the hand
arithmetic of their docstring, each new reader on a recorded ``run``, the
configuration file against the catalog's rules, and the manifest's entries. No
number printed here is a device number."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from agent_tpu.ops import map_score_lm  # noqa: E402
from agent_tpu.runtime.runtime import reset_runtime  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import manifest, stack  # noqa: E402

import test_bench_backlog  # noqa: E402

CELL = "falcon-h1-34b.score-64k"
CONFIG = "falcon-h1-34b"
# ``test_bench_backlog.py`` holds every cell's backlog over the cell's rate at
# 100 % of its roofline and asks a new cell to bring that rate; its table may
# not be edited by a PR that adds a cell, so the entry comes from here (as
# ``test_bench_sparse_mla.py`` brings its own): a document needs 647.06
# TFLOP, 3.2845 s at 197 TFLOP/s, 0.3045 rows/s.
test_bench_backlog.AT_THE_ROOFLINE.setdefault(CELL, 0.31)
# Head counts that are no powers of two: 15 query over 3 key-value heads (five
# a head, as published), 6 scan heads in 2 groups.
TINY_LM = {
    "vocab_size": 2048, "d_model": 64, "n_heads": 15, "n_kv_heads": 3,
    "d_head": 16, "d_ff": 96, "n_layers": 2, "ssm_n_heads": 6,
    "ssm_d_head": 16, "ssm_d_state": 24, "ssm_n_groups": 2, "dtype": "float32",
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
needed = manifest.load_needed_work("hybrid_ssm_flops")


@pytest.fixture()
def tiny(monkeypatch):
    monkeypatch.setattr(stack, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(manifest, "MODEL_OVERRIDES", {CONFIG: TINY_LM})
    monkeypatch.setattr(manifest, "TRAFFIC_OVERRIDES",
                        {"score-64k": dict(TINY_SCORE)})
    monkeypatch.setattr(map_score_lm, "SEGMENT_BUCKETS", SEGMENT_BUCKETS)
    reset_runtime()
    yield monkeypatch
    reset_runtime()


def lines_of(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(tiny, capsys, trace):
    code = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 35),
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
        # The counter-based reader reads; no device plane in a CPU trace, so
        # the device_trace readers are left out, never printed as a number.
        from agent_tpu.kernels.causal_attention import visited_pairs

        n = DOC_TOKENS
        computed = visited_pairs(2048, 0) + visited_pairs(1024, 2048)
        share = result["metrics"]["causal_attention_pair_share.drain"]["value"]
        assert share == pytest.approx(100.0 * (n * (n + 1) / 2) / computed)
        assert result["metrics"]["compiles_in_window.drain"]["value"] == 0
        assert not any("roofline" in n or "device_share" in n or
                       n.startswith(("retention_", "sparse_", "expert_"))
                       for n in result["metrics"])
    compared = {c["number"]: c for c in map(json.loads, (
        ln for ln in lines if ln.startswith('{"bench": "compared"')))}
    assert set(compared) == set(manifest.load_config(
        manifest.load_manifest(), CONFIG)["check"]["limits"])
    # float32 against the float32 reference: rounding in another order.
    assert result["correct"] is True, lines[-8:]
    assert compared["block_logprob_gap_max"]["value"] < 1e-4, compared


# ---- the counting functions against hand arithmetic ----------------------

def test_counts_of_a_65536_token_document_at_the_published_widths():
    """The docstring's figures (ISSUE 35): 430.08 M matmul parameters a
    layer, 338.2 TFLOP in six layers, 175.2 in the head, 131.9 of causal
    attention, 1.65 in the scan, 647.06 a document: 3.285 s at the peak."""
    m, L = PUBLISHED, 65536
    d = 5120
    attention = d * (2560 + 512 + 512) + 2560 * d
    in_proj = 2 * 4096 + 2 * 2 * 256 + 32
    assert attention == 31_457_280 and in_proj == 9248
    assert needed.in_projection_columns(m) == in_proj
    per_layer = attention + d * in_proj + 4096 * d + 3 * d * 21504
    assert needed.layer_matmul_params(m) == per_layer == 430_080_000
    assert needed.layers_flops(m, L) == 2 * per_layer * 6 * L
    assert needed.layers_flops(m, L) / 1e12 == pytest.approx(338.23, abs=0.01)
    assert needed.head_flops(m, L) == 2 * d * 261120 * L
    assert needed.head_flops(m, L) / 1e12 == pytest.approx(175.23, abs=0.01)
    assert needed.head_bytes_needed(m, L) == 2 * d * (261120 + L)
    assert needed.causal_pairs(L) == L * (L + 1) // 2
    assert needed.attention_flops(m, L) == 6 * 4 * 20 * 128 * (L * (L + 1) // 2)
    assert needed.attention_flops(m, L) / 1e12 == pytest.approx(131.94, abs=0.01)
    assert needed.attention_bytes(m, L) == 6 * L * 2 * 128 * (2 * 20 + 2 * 4)
    # The scan: token by token 4 P N a head-token; chunked, the causal half of
    # a chunk's block a head and of C B^T a group, the state read in 511 of
    # 512 chunks and updated in 511.
    assert needed.ssd_recurrent_flops(m, L) == 6 * 32 * 4 * 128 * 256 * L
    inside = 512 * (128 * 129 // 2)
    assert needed.ssd_chunked_flops(m, L) == 6 * (
        32 * (2 * 128 * inside + 2 * 128 * 256 * 2 * (L - 128))
        + 2 * 2 * 256 * inside)
    assert needed.ssd_flops(m, L) == needed.ssd_recurrent_flops(m, L)
    assert needed.ssd_flops(m, L) / 1e12 == pytest.approx(1.649, abs=0.001)
    assert needed.ssd_chunked_flops(m, L) / 1e12 == pytest.approx(1.880, abs=0.001)
    # A document of one chunk reads no state and updates none.
    assert needed.ssd_flops(m, 100) == needed.ssd_chunked_flops(m, 100) == 6 * (
        32 * 2 * 128 * 5050 + 2 * 2 * 256 * 5050)
    assert needed.ssd_bytes(m, L) == 6 * L * 18560
    assert needed.ssd_bytes(m, L) / 819e9 > needed.ssd_flops(m, L) / 197e12
    total = needed.document_flops_needed(m, L)
    assert total / 1e12 == pytest.approx(647.06, abs=0.01)
    assert total / 197e12 == pytest.approx(3.2845, abs=0.0005)
    # The shares the cell's ``why`` quotes, here and at the published depth.
    assert needed.attention_flops(m, L) / total == pytest.approx(0.204, abs=0.001)
    assert needed.head_flops(m, L) / total == pytest.approx(0.271, abs=0.001)
    whole = dict(m, n_layers=72)
    total72 = needed.document_flops_needed(whole, L)
    assert needed.head_flops(whole, L) / total72 == pytest.approx(0.030, abs=0.001)
    assert needed.attention_flops(whole, L) / total72 == pytest.approx(
        0.271, abs=0.001)
    assert needed.attention_flops(m, 32768) / needed.document_flops_needed(
        m, 32768) == pytest.approx(0.114, abs=0.001)


def test_the_programs_own_count_covers_the_need():
    """``segment_flops`` (the ``device_mfu{op}`` numerator: what the program
    does, padded tiles and whole chunk blocks) is never under the need."""
    from agent_tpu.models.decoder_lm import DecoderLMConfig, segment_flops

    cfg = DecoderLMConfig(**PUBLISHED)
    done = sum(segment_flops(cfg, 4096, pos0) for pos0 in range(0, 65536, 4096))
    need = needed.document_flops_needed(PUBLISHED, 65536)
    assert need <= done <= 1.01 * need
    # The parameters the configuration file's arithmetic states.
    small = 4 * 5120 + 5120 + 2 * 5120 + 4096 + 3 * 32
    assert small == 40_032
    assert 6 * (430_080_000 + small) + 2 * 261120 * 5120 + 5120 == 5_254_594_112


def test_means_over_documents():
    mean = needed.mean_needed(PUBLISHED, [65536, 8192])
    assert set(mean) == {"flops", "head_flops", "head_bytes", "ssd_flops",
                         "ssd_bytes", "attention_flops", "attention_bytes"}
    assert mean["flops"] == (needed.document_flops_needed(PUBLISHED, 65536)
                             + needed.document_flops_needed(PUBLISHED, 8192)) / 2
    assert mean["head_bytes"] == 2 * 5120 * (261120 + (65536 + 8192) / 2)


def test_the_counter_of_visited_tiles():
    """A query tile meets whole key tiles up to the one with its last query:
    at 65,536 tokens 99.2 % of the visited pairs are causal ones."""
    from agent_tpu.kernels.causal_attention import visited_pairs

    assert visited_pairs(512, 0) == 512 * 512
    assert visited_pairs(1024, 4096) == 512 * (4096 + 512) + 512 * (4096 + 1024)
    visited = sum(visited_pairs(4096, p) for p in range(0, 65536, 4096))
    assert visited == 512 * 512 * (128 * 129 // 2)
    assert 100.0 * needed.causal_pairs(65536) / visited == pytest.approx(
        99.23, abs=0.01)


# ---- each new reader on a recorded run ----------------------------------

def recorded_run():
    """What a traced run of the cell records, with round numbers: 0.2
    documents a second, an 8 s traced interval all busy, the segment programs
    6.5 s of it and the head 1.5 s; the scan 0.08 s, the attention 2.0 s."""
    counters = lambda causal, computed: {  # noqa: E731
        "causal_attention_pairs_total": {"series": [
            {"labels": {"kind": "causal"}, "value": causal},
            {"labels": {"kind": "computed"}, "value": computed}]}}
    return {
        "kind": "drain", "lm_needed": needed.mean_needed(PUBLISHED, [65536]),
        "end_to_end": {"drain_rows_per_s": 0.2},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "agent_metrics": (counters(1e6, 2e6),
                          counters(1e6 + 992.0, 2e6 + 1000.0)),
        "trace": {"window_s": 8.0, "busy_s": 8.0, "programs": {
            "lm_segment": {"clipped_seconds": 6.5, "seconds": 6.5, "count": 26},
            "lm_loss_head": {"clipped_seconds": 1.5, "seconds": 1.5,
                             "count": 26}}},
        "op_times": {"ssd_scan": {"seconds": 0.08, "count": 156},
                     "causal_attention": {"seconds": 2.0, "count": 156}},
    }


@pytest.mark.parametrize("name, want", [
    ("lm_roofline", 100 * 0.2 * 647.056016277504e12 / 1.0 / 197e12),
    ("loss_head_roofline", 100 * 0.2 * (175.2346656768e12 / 197e12) / (1.5 / 8)),
    # bytes bound the scan: 7.298 GB against 1.649 TFLOP.
    ("ssd_roofline", 100 * 0.2 * (7.29808896e9 / 819e9) / 0.01),
    ("causal_attention_roofline",
     100 * 0.2 * (131.94340859904e12 / 197e12) / 0.25),
    ("hybrid_mixer_device_share.drain", 26.0),
    ("causal_attention_pair_share.drain", 99.2),
])
def test_reader_on_a_recorded_run(name, want):
    reader = manifest.load_layer_metric(name)
    assert reader.read(recorded_run()) == pytest.approx(want, rel=1e-9)
    assert 0 < want < 100


NEW_READERS = ["ssd_roofline", "causal_attention_roofline",
               "hybrid_mixer_device_share.drain",
               "causal_attention_pair_share.drain"]


@pytest.mark.parametrize("name", NEW_READERS)
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
                "ssd_scan": {"seconds": 0.0, "count": 0},
                "causal_attention": {"seconds": 0.0, "count": 0}},
            "trace": {"window_s": 3.0, "busy_s": 3.0, "programs": {
                "lm_segment": {"clipped_seconds": 2.5}}}}
    assert reader.read(bare) is None
    assert reader.read(dict(bare, trace=None, op_times=None)) is None


def test_op_patterns_match_the_start_of_an_events_name():
    """An ``XLA Ops`` event is named by its whole instruction, operands and
    all: a fusion that reads the scan's result names it. Each reader's
    pattern takes its own kernel only."""
    import re

    events = {
        "ssd_scan": "%ssd_scan.7 = (bf16[4096,4096]{1,0}, f32[32,256,128]{2,1,0}) "
        "custom-call(f32[1024]{0} %fusion.12, bf16[4096,4096]{1,0} %fusion.9), "
        "custom_call_target=\"tpu_custom_call\"",
        "causal_attention": "%causal_gqa_attention.7 = bf16[4,5,4096,128]{3,2,1,0} "
        "custom-call(s32[1]{0} %reshape.3, bf16[4,5,4096,128]{3,2,1,0} %fusion.5)",
        None: "%fusion.77 = f32[4096,4096]{1,0} fusion(bf16[4096,4096]{1,0} "
        "%get-tuple-element.4, f32[4096,4096]{1,0} %ssd_scan.7), kind=kLoop",
    }
    patterns = {}
    for name in NEW_READERS[:3]:
        patterns.update(manifest.load_layer_metric(name).OP_PATTERNS)
    assert set(patterns) == {"ssd_scan", "causal_attention"}
    for label, rx in patterns.items():
        assert [k for k, text in events.items() if re.search(rx, text)] == [label]
    # And the kernels carry those names.
    from agent_tpu.kernels import causal_attention, ssd
    import inspect

    assert 'name="ssd_scan"' in inspect.getsource(ssd)
    assert 'name="causal_gqa_attention"' in inspect.getsource(causal_attention)


def test_documents_draw_their_ids_from_the_whole_vocabulary():
    score = manifest.load_kind("score")
    traffic = manifest.load_traffic("score-64k")
    docs = score.documents(traffic, PUBLISHED["vocab_size"], 2 ** 31 + 5, 2)
    assert [len(d) for d in docs] == [65536, 65536]
    assert 0 <= min(d.min() for d in docs) and max(
        d.max() for d in docs) < 261120
    assert max(d.max() for d in docs) > 200000


# ---- the configuration file and the manifest's entries -------------------

def test_the_configuration_file_keeps_the_catalogs_rules():
    m = manifest.load_manifest()
    cfg = manifest.load_config(m, CONFIG)
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    changed = [k for k, v in cfg["published"].items() if cfg[k] != v]
    assert changed == cfg["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    model, pub = cfg["model"], cfg["published"]
    for ours, theirs in {
            "d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "d_head": "head_dim",
            "d_ff": "intermediate_size", "vocab_size": "vocab_size",
            "max_len": "max_position_embeddings", "rms_norm_eps": "rms_norm_eps",
            "rope_theta": "rope_theta", "ssm_n_heads": "mamba_n_heads",
            "ssm_d_head": "mamba_d_head", "ssm_d_state": "mamba_d_state",
            "ssm_n_groups": "mamba_n_groups", "ssm_d_conv": "mamba_d_conv",
            "ssm_chunk": "mamba_chunk_size",
            "embedding_multiplier": "embedding_multiplier",
            "lm_head_multiplier": "lm_head_multiplier",
            "attention_in_multiplier": "attention_in_multiplier",
            "attention_out_multiplier": "attention_out_multiplier",
            "key_multiplier": "key_multiplier",
            "ssm_in_multiplier": "ssm_in_multiplier",
            "ssm_out_multiplier": "ssm_out_multiplier"}.items():
        assert model[ours] == pub[theirs], (ours, theirs)
    assert [model[f"ssm_{part}_multiplier"] for part in "zxbc"] + [
        model["ssm_dt_multiplier"]] == pub["ssm_multipliers"]
    assert [model["mlp_gate_multiplier"], model["mlp_down_multiplier"]] == pub[
        "mlp_multipliers"]
    assert model["ssm_n_heads"] * model["ssm_d_head"] == pub["mamba_d_ssm"]
    # The cut: depth alone, a whole stage of 6 (the floor is 4 layers).
    assert model["n_layers"] == cfg["num_hidden_layers"] == 6
    assert pub["num_hidden_layers"] == 72 == 12 * 6
    assert "5,254.6 M parameters, 10.51 GB" in cfg["deployment"]
    assert cfg["control"]["model_config"] == {"quant": "int8"}
    assert cfg["check"]["docs"] in (1, 2) and set(cfg["check"]["limits"]) <= set(
        cfg["check"]["why"])
    # The op takes every key of the model group, lists and all left out.
    from agent_tpu.models.decoder_lm import DecoderLMConfig, validate
    from agent_tpu.ops._model_common import cfg_key

    assert set(model) <= set(DecoderLMConfig.__dataclass_fields__)
    validate(DecoderLMConfig(**model))
    hash(cfg_key(DecoderLMConfig(**model)))


def test_manifest_entries_of_the_cell(manifests):
    m = manifests
    cell = manifest.find_cell(m, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    cfg = manifest.load_config(m, cell["config"])
    assert cfg["needed_work"] == "hybrid_ssm_flops"
    assert cfg["reference"] == "hybrid_ssm_lm"
    traffic = manifest.load_traffic(cell["traffic"])
    assert traffic["kind"] == "score" and traffic["shard_rows"] == 1
    assert traffic["doc_tokens"] == {"dist": "fixed", "value": 65536}
    assert traffic["token_ids"] == {"dist": "zipf", "exponent": 1.1}
    assert (traffic["job_rows"], traffic["tenants"], traffic["order_seed"],
            traffic["lead_in_shards"], traffic["agent"]) == (8, 1, 0, 3, {})
    e2e = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "end_to_end")}
    assert e2e == {"drain_rows_per_s", "setup_s"}
    per_layer = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "per_layer")}
    # Everything the other score cells share, and the four this one brings.
    shared = {e["name"] for e in manifest.metrics_of_cell(
        m, "brumby-14b-base.score-long", "per_layer")} & {
        e["name"] for e in manifest.metrics_of_cell(
            m, "deepseek-v3.2.score-32k", "per_layer")}
    mine = set(NEW_READERS)
    assert shared | mine <= per_layer
    assert not {n for n in per_layer if n.startswith(
        ("retention_", "sparse_", "expert_", "indexer_"))}
    for entry in m["per_layer"]:
        if entry["name"] in mine:
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "drain_rows_per_s"
