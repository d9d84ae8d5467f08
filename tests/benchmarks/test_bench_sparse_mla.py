"""The ``deepseek-v3.2.score-32k`` cell off the chip: its CPU rehearsal
through ``MODEL_OVERRIDES`` / ``TRAFFIC_OVERRIDES`` (as
``test_bench_score.py``), the needed-work functions against the hand
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

CELL = "deepseek-v3.2.score-32k"
CONFIG = "deepseek-v3.2"
# ``test_bench_backlog.py`` holds every cell's backlog over the cell's rate at
# 100 % of its roofline and asks a new cell to bring that rate. Its table is a
# dict in that file, which a PR that adds a cell may not edit, so the entry
# comes from here (pytest imports every test file before it runs a test): a
# document needs 251.8 TFLOP, 1.278 s at 197 TFLOP/s, 0.7825 rows/s.
test_bench_backlog.AT_THE_ROOFLINE.setdefault(CELL, 0.79)
# 16 index heads: a score is a sum of 16 rectified products, so an exact 0
# (every head negative), the one tie a tiny model can make, is a 2^-16 event.
TINY_LM = {
    "vocab_size": 2048, "d_model": 64, "n_heads": 4, "d_ff": 96,
    "n_layers": 2, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "index_n_heads": 16, "index_head_dim": 16, "index_topk": 64,
    "n_dense_layers": 1, "n_experts": 16, "n_experts_held": 4,
    "n_experts_per_token": 4, "n_expert_groups": 4, "n_groups_per_token": 2,
    "d_expert": 32, "dtype": "float32",
}
# 2,600 tokens under segments of 2,048 and 1,024 (the op's sizes halved for
# the CPU: a third of the causal pairs; ``brumby-14b-base``'s rehearsal runs
# the 4,096-token segment): the cache crosses a program boundary in every
# document and most queries select.
DOC_TOKENS = 2600
SEGMENT_BUCKETS = (1024, 2048)
TINY_SCORE = {
    "doc_tokens": {"dist": "fixed", "value": DOC_TOKENS}, "job_rows": 4,
    "backlog_rows_per_s": 2, "lead_in_shards": 1, "trace_start_s": 0.2,
    "trace_seconds": 0.5,
}
PUBLISHED = manifest.load_config(manifest.load_manifest(), CONFIG)["model"]
needed = manifest.load_needed_work("sparse_mla_flops")


@pytest.fixture()
def tiny(monkeypatch):
    monkeypatch.setattr(stack, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(manifest, "MODEL_OVERRIDES", {CONFIG: TINY_LM})
    monkeypatch.setattr(manifest, "TRAFFIC_OVERRIDES",
                        {"score-32k": dict(TINY_SCORE)})
    monkeypatch.setattr(map_score_lm, "SEGMENT_BUCKETS", SEGMENT_BUCKETS)
    reset_runtime()
    yield monkeypatch
    reset_runtime()


def lines_of(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(tiny, capsys, trace):
    code = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 33),
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
        k, n = 64, DOC_TOKENS
        share = result["metrics"]["sparse_selected_key_share.drain"]["value"]
        assert share == pytest.approx(
            100.0 * (k * (k + 1) / 2 + (n - k) * k) / (n * (n + 1) / 2))
        # 4 of 16 experts held, 4 chosen a token: 1 pair a token if even,
        # over token SLOTS (a sixth of them padding, all of one id).
        pairs = result["metrics"]["expert_pairs_per_token.drain"]["value"]
        assert 0.3 < pairs < 1.5
        assert result["metrics"]["compiles_in_window.drain"]["value"] == 0
        assert not any("roofline" in n or "device_share" in n or
                       n.startswith("retention_") for n in result["metrics"])
    compared = {c["number"]: c for c in map(json.loads, (
        ln for ln in lines if ln.startswith('{"bench": "compared"')))}
    assert set(compared) == set(manifest.load_config(
        manifest.load_manifest(), CONFIG)["check"]["limits"])
    # float32 against the float32 reference, the same selection but where a
    # near-tie falls the other way in another order of sums: a flipped key
    # moves one token's log-probability, not a block's mean by a limit.
    assert result["correct"] is True, lines[-8:]
    assert compared["block_logprob_bias"]["value"] < 1e-3, compared


# ---- the counting functions against hand arithmetic ----------------------

def test_counts_of_a_32768_token_document_at_the_published_widths():
    """The docstring's figures (ISSUE 33): 537.9 / 1,194.9 MFLOP a token in
    an expert / the dense layer's matmuls, 268.4 in a layer's index scores,
    552.6 in its attention over the selected keys, 251.8 TFLOP a document."""
    m, L = PUBLISHED, 32768
    d = 7168
    mla = (d * 1536 + 1536 * 128 * 192 + d * 576 + 512 * 128 * 256
           + 128 * 128 * d)
    indexer = 1536 * 64 * 128 + d * 128 + d * 64
    assert mla == 187_105_280 and indexer == 13_959_168
    assert needed.attention_params(m) == mla + indexer
    assert needed.expert_params(m) == 3 * d * 2048 == 44_040_192
    assert needed.pairs_per_token(m) == 0.5
    assert needed.layer_counts(m) == (1, 4)
    assert needed.dense_layer_flops_per_token(m) == 2 * (
        mla + indexer + 3 * d * 18432)
    assert needed.dense_layer_flops_per_token(m) / 1e6 == pytest.approx(
        1194.9, abs=0.05)
    assert needed.expert_layer_flops_per_token(m) == 2 * (
        mla + indexer + d * 256 + 44_040_192 + 0.5 * 44_040_192)
    assert needed.expert_layer_flops_per_token(m) / 1e6 == pytest.approx(
        537.9, abs=0.05)
    assert needed.causal_pairs(L) == L * (L + 1) // 2
    assert needed.selected_pairs(m, L) == 2048 * 2049 // 2 + (L - 2048) * 2048
    assert needed.selected_pairs(m, 100) == needed.causal_pairs(100)
    assert needed.indexer_flops(m, L) == 5 * 2 * 64 * 128 * needed.causal_pairs(L)
    assert needed.indexer_flops(m, L) / (5 * L) / 1e6 == pytest.approx(
        268.4, abs=0.05)
    flops, nbytes = needed.sparse_attention_needed(m, L)
    assert flops == 5 * 2 * 128 * (576 + 512) * needed.selected_pairs(m, L)
    assert flops / (5 * L) / 1e6 == pytest.approx(552.6, abs=0.05)
    assert nbytes == 5 * (2 * 576 * needed.selected_pairs(m, L)
                          + 2 * L * 128 * (576 + 512))
    assert needed.head_flops(m, L) == 2 * d * 16160 * L
    assert needed.expert_flops(m, L) == 2 * 0.5 * 44_040_192 * 4 * L
    assert needed.expert_bytes(m, L) == 4 * (2 * 16 * 44_040_192
                                             + 4 * d * 0.5 * L)
    total = needed.document_flops_needed(m, L)
    assert total / 1e12 == pytest.approx(251.8, abs=0.05)
    assert total / 197e12 == pytest.approx(1.278, abs=0.001)
    per_token = 4 * 537.919488 + 1194.852352 + 5 * (268.443648 + 552.608256) \
        + 2 * d * 16160 / 1e6
    assert total / L / 1e6 == pytest.approx(per_token, rel=1e-9)
    # The mechanism and its projections against the whole.
    mechanism = needed.indexer_flops(m, L) + flops
    assert mechanism / total == pytest.approx(0.534, abs=0.002)
    assert needed.expert_flops(m, L) / total == pytest.approx(0.023, abs=0.001)
    assert needed.head_flops(m, L) / total == pytest.approx(0.030, abs=0.001)


@pytest.mark.parametrize("L, cheaper", [
    (1024, "dense"), (4096, "dense"), (8192, "dense"), (12288, "sparse"),
    (16384, "sparse"), (32768, "sparse"), (65536, "sparse"),
])
def test_the_cheaper_attention_switches_near_12k_tokens(L, cheaper):
    sparse, dense = needed._attention_forms(PUBLISHED, L)
    assert (sparse[0] < dense[0]) == (cheaper == "sparse"), (sparse, dense)
    assert needed.sparse_attention_needed(PUBLISHED, L)[0] == 5 * min(
        sparse[0], dense[0])


def test_means_over_documents():
    mean = needed.mean_needed(PUBLISHED, [32768, 8192])
    assert set(mean) == {
        "flops", "head_flops", "head_bytes", "indexer_flops",
        "sparse_attention_flops", "sparse_attention_bytes", "expert_flops",
        "expert_bytes"}
    assert mean["flops"] == (needed.document_flops_needed(PUBLISHED, 32768)
                             + needed.document_flops_needed(PUBLISHED, 8192)) / 2
    assert mean["head_bytes"] == 2 * 7168 * (16160 + (32768 + 8192) / 2)


# ---- each new reader on a recorded run ----------------------------------

def recorded_run():
    """What a traced run of the cell records, with round numbers: 0.3
    documents a second, a 5 s traced interval all busy, the segment programs
    4.5 s of it and the head 0.25 s; the indexer 1.0 s, the attention 2.0 s,
    the grouped expert matmul 0.25 s."""
    counters = lambda sel, cau, pairs, tokens: {  # noqa: E731
        "sparse_attention_keys_total": {"series": [
            {"labels": {"kind": "selected"}, "value": sel},
            {"labels": {"kind": "causal"}, "value": cau}]},
        "moe_expert_pairs_total": {"series": [{"labels": {}, "value": pairs}]},
        "moe_tokens_total": {"series": [{"labels": {}, "value": tokens}]}}
    return {
        "kind": "drain", "lm_needed": needed.mean_needed(PUBLISHED, [32768]),
        "end_to_end": {"drain_rows_per_s": 0.3},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "agent_metrics": (counters(1e6, 1e7, 1000.0, 4000.0),
                          counters(1e6 + 121.0, 1e7 + 1000.0, 1000.0 + 65.0,
                                   4000.0 + 128.0)),
        "trace": {"window_s": 5.0, "busy_s": 5.0, "programs": {
            "lm_segment": {"clipped_seconds": 4.5, "seconds": 4.5, "count": 12},
            "lm_loss_head": {"clipped_seconds": 0.25, "seconds": 0.25,
                             "count": 12}}},
        "op_times": {"sparse_index": {"seconds": 1.0, "count": 60},
                     "sparse_attention": {"seconds": 2.0, "count": 60},
                     "expert_ffn": {"seconds": 0.25, "count": 48}},
    }


@pytest.mark.parametrize("name, want", [
    ("lm_roofline", 100 * 0.3 * 251.771603648512e12 / (4.75 / 5.0) / 197e12),
    ("loss_head_roofline",
     100 * 0.3 * (7.59135469568e12 / 197e12) / (0.25 / 5.0)),
    ("indexer_roofline", 100 * 0.3 * (43.98180728832e12 / 197e12) / 0.2),
    # bytes bound it: 420.1 GB of gathered latents against 90.5 TFLOP.
    ("sparse_attention_roofline",
     100 * 0.3 * (420.10738688e9 / 819e9) / 0.4),
    ("expert_ffn_roofline", 100 * 0.3 * (5.772436045824e12 / 197e12) / 0.05),
    ("sparse_attention_device_share.drain", 60.0),
    ("sparse_selected_key_share.drain", 12.1),
    ("expert_pairs_per_token.drain", 65.0 / 128.0),
])
def test_reader_on_a_recorded_run(name, want):
    reader = manifest.load_layer_metric(name)
    assert reader.read(recorded_run()) == pytest.approx(want, rel=1e-9)
    assert 0 < want < 100


@pytest.mark.parametrize("name", [
    "indexer_roofline", "sparse_attention_roofline", "expert_ffn_roofline",
    "sparse_attention_device_share.drain", "sparse_selected_key_share.drain",
    "expert_pairs_per_token.drain"])
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
                "sparse_index": {"seconds": 0.0, "count": 0},
                "sparse_attention": {"seconds": 0.0, "count": 0},
                "expert_ffn": {"seconds": 0.0, "count": 0}},
            "trace": {"window_s": 3.0, "busy_s": 3.0, "programs": {
                "lm_segment": {"clipped_seconds": 2.5}}}}
    assert reader.read(bare) is None
    assert reader.read(dict(bare, trace=None, op_times=None)) is None


def test_op_patterns_match_the_start_of_an_events_name():
    """An ``XLA Ops`` event is named by its whole instruction, operands and
    all: the attention's names the indexer's result and a gather's names the
    grouped matmul's. Each reader's pattern takes its own kernel only."""
    import re

    events = {
        "sparse_attention": "%sparse_mla_attention.16 = bf16[128,4096,128]{2,1,0} "
        "custom-call(s32[1]{0} %slice.943, s8[32,4096,1024]{2,1,0} "
        "%sparse_index_select.16), custom_call_target=\"tpu_custom_call\"",
        "sparse_index": "%sparse_index_select.16 = s8[32,4096,1024]{2,1,0} "
        "custom-call(s32[1]{0} %slice.943, bf16[64,4096,128]{2,1,0} %fusion.3)",
        "expert_ffn": "%moe_grouped_swiglu.12 = bf16[36864,7168]{1,0} "
        "custom-call(s32[144]{0} %copy-done.3)",
        None: "%fusion.355 = bf16[32768,7168]{1,0} fusion(bf16[36864,7168]{1,0} "
        "%moe_grouped_swiglu.12, s32[32768]{0} %copy-done.31), kind=kCustom",
    }
    patterns = {}
    for name in ("indexer_roofline", "sparse_attention_roofline",
                 "expert_ffn_roofline", "sparse_attention_device_share.drain"):
        patterns.update(manifest.load_layer_metric(name).OP_PATTERNS)
    assert set(patterns) == {"sparse_index", "sparse_attention", "expert_ffn"}
    for label, rx in patterns.items():
        assert [k for k, text in events.items() if re.search(rx, text)] == [label]


def test_documents_draw_their_ids_from_the_slice():
    score = manifest.load_kind("score")
    traffic = manifest.load_traffic("score-32k")
    docs = score.documents(traffic, PUBLISHED["vocab_size"], 2 ** 31 + 5, 2)
    assert [len(d) for d in docs] == [32768, 32768]
    assert 0 <= min(d.min() for d in docs) and max(
        d.max() for d in docs) < 16160


# ---- the configuration file and the manifest's entries -------------------

def test_the_configuration_file_keeps_the_catalogs_rules():
    m = manifest.load_manifest()
    cfg = manifest.load_config(m, CONFIG)
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    changed = [k for k, v in cfg["published"].items() if cfg[k] != v]
    assert sorted(changed) == sorted(cfg["reduced"]) == sorted(entry["reduced"])
    # No width among them: layers, leading dense layers, experts HELD, rows
    # of the vocabulary held, the prediction module.
    assert set(changed) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert cfg["rope_scaling"] == cfg["published"]["rope_scaling"]
    model, pub = cfg["model"], cfg["published"]
    for ours, theirs in {
            "d_model": "hidden_size", "n_heads": "num_attention_heads",
            "d_ff": "intermediate_size", "q_lora_rank": "q_lora_rank",
            "kv_lora_rank": "kv_lora_rank", "v_head_dim": "v_head_dim",
            "qk_nope_head_dim": "qk_nope_head_dim",
            "qk_rope_head_dim": "qk_rope_head_dim",
            "index_n_heads": "index_n_heads", "index_topk": "index_topk",
            "index_head_dim": "index_head_dim", "n_experts": "n_routed_experts",
            "n_experts_per_token": "num_experts_per_tok",
            "n_expert_groups": "n_group", "n_groups_per_token": "topk_group",
            "d_expert": "moe_intermediate_size",
            "n_shared_experts": "n_shared_experts",
            "routed_scale": "routed_scaling_factor", "rope_theta": "rope_theta",
            "max_len": "max_position_embeddings",
            "rms_norm_eps": "rms_norm_eps"}.items():
        assert model[ours] == pub[theirs], (ours, theirs)
    assert model["rope_factor"] == pub["rope_scaling"]["factor"]
    assert model["rope_original_max_len"] == pub["rope_scaling"][
        "original_max_position_embeddings"]
    # The cut, and its floors: 4 expert layers after the dense one, 16 >= 8
    # experts, an eighth of the vocabulary.
    assert model["n_layers"] == cfg["num_hidden_layers"] == 5
    assert model["n_dense_layers"] == cfg["first_k_dense_replace"] == 1
    assert model["n_experts_held"] == cfg["n_routed_experts"] == 16 >= 8
    assert model["vocab_size"] == cfg["vocab_size"] == 129280 // 8
    assert cfg["control"]["model_config"] == {"quant": "int8"}
    assert cfg["check"]["docs"] == 2 and set(cfg["check"]["limits"]) <= set(
        cfg["check"]["why"])
    # The op takes every key of the model group.
    from agent_tpu.models.decoder_lm import DecoderLMConfig, validate

    assert set(model) <= set(DecoderLMConfig.__dataclass_fields__)
    validate(DecoderLMConfig(**model))


def test_manifest_entries_of_the_cell(manifests):
    m = manifests
    cell = manifest.find_cell(m, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    cfg = manifest.load_config(m, cell["config"])
    assert cfg["needed_work"] == "sparse_mla_flops"
    assert cfg["reference"] == "sparse_mla_lm"
    traffic = manifest.load_traffic(cell["traffic"])
    assert traffic["kind"] == "score" and traffic["shard_rows"] == 1
    assert traffic["doc_tokens"] == {"dist": "fixed", "value": 32768}
    e2e = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "end_to_end")}
    assert e2e == {"drain_rows_per_s", "setup_s"}
    per_layer = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "per_layer")}
    # Everything the other score cell reads but its mixer's three, and the
    # six this configuration brings.
    other = {e["name"] for e in manifest.metrics_of_cell(
        m, "brumby-14b-base.score-long", "per_layer")}
    mine = {"indexer_roofline", "sparse_attention_roofline",
            "expert_ffn_roofline", "sparse_attention_device_share.drain",
            "sparse_selected_key_share.drain", "expert_pairs_per_token.drain"}
    assert {n for n in other if not n.startswith("retention_")} | mine \
        <= per_layer
    assert not {n for n in per_layer if n.startswith("retention_")}
    assert not mine & other
    for entry in m["per_layer"]:
        if entry["name"] in mine:
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "drain_rows_per_s"
