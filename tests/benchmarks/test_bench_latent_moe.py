"""The ``mistral-small-4-119b.score-64k-latent`` cell off the chip: its CPU
rehearsal through ``MODEL_OVERRIDES`` / ``TRAFFIC_OVERRIDES`` (as
``test_bench_hybrid_ssm.py``), the needed-work functions against the hand
arithmetic of their docstring, the configuration's parameter count by a
count of the leaves' shapes, each new reader on a recorded ``run``, the
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

CELL = "mistral-small-4-119b.score-64k-latent"
CONFIG = "mistral-small-4-119b"
TRAFFIC = "score-64k-latent"
# ``test_bench_backlog.py`` holds every cell's backlog over the cell's rate at
# 100 % of its roofline and asks a new cell to bring that rate; its table may
# not be edited by a PR that adds a cell, so the entry comes from here (as
# ``test_bench_hybrid_ssm.py`` brings its own): a document needs 290.76
# TFLOP, 1.4759 s at 197 TFLOP/s, 0.6775 rows/s.
test_bench_backlog.AT_THE_ROOFLINE.setdefault(CELL, 0.68)
# The published head split's ratio (nope = rope, v twice either), 16 experts
# of which 4 are held and 4 chosen, and an original length so short that the
# query's scale steps inside every segment below.
TINY_LM = {
    "vocab_size": 2048, "d_model": 64, "n_heads": 4, "n_layers": 2,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_original_max_len": 700,
    "max_len": 16384, "n_experts": 16, "n_experts_held": 4, "d_expert": 32,
    "dtype": "float32",
}
# 2,600 tokens under segments of 2,048 and 1,024 (the op's sizes halved for
# the CPU): the latents cross a program boundary in every document.
DOC_TOKENS = 2600
SEGMENT_BUCKETS = (1024, 2048)
TINY_SCORE = {
    "doc_tokens": {"dist": "fixed", "value": DOC_TOKENS}, "job_rows": 4,
    "backlog_rows_per_s": 2, "lead_in_shards": 1, "trace_start_s": 0.2,
    "trace_seconds": 0.5,
}
PUBLISHED = manifest.load_config(manifest.load_manifest(), CONFIG)["model"]
needed = manifest.load_needed_work("latent_moe_flops")


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
    code = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 40),
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
        from agent_tpu.kernels.causal_attention import visited_pairs

        n = DOC_TOKENS
        computed = visited_pairs(2048, 0, 2048) + visited_pairs(1024, 2048, 1024)
        share = result["metrics"]["latent_causal_pair_share.drain"]["value"]
        assert share == pytest.approx(100.0 * (n * (n + 1) / 2) / computed)
        per_token = result["metrics"]["latent_expansions_per_token.drain"]
        assert per_token["value"] == pytest.approx((2048 + 3072) / n)
        pairs = result["metrics"]["latent_expert_pairs_per_token.drain"]["value"]
        assert 0.5 < pairs < 1.5                  # 4 x 4 / 16 if even
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

def test_counts_of_a_65536_token_document_at_the_published_widths():
    """The docstring's figures (ISSUE 40): 77.33 M per-token matmul parameters
    a layer, 60.8 TFLOP in six layers, 1.24 of expansion, 211.1 of causal
    attention, 17.6 in the head, 290.76 a document: 1.476 s at the peak."""
    m, L = PUBLISHED, 65536
    d = 4096
    assert needed.projection_params(m) == (
        d * 1024 + 1024 * 32 * 128 + d * 320 + 32 * 128 * d) == 26_476_544
    assert needed.expansion_params(m) == 256 * 32 * 192 == 1_572_864
    assert needed.projection_params(m) + needed.expansion_params(m) == 28_049_408
    assert needed.expert_params(m) == 3 * d * 2048 == 25_165_824
    assert needed.pairs_per_token(m) == 1.0
    per_layer = 26_476_544 + d * 128 + 25_165_824 + 25_165_824
    assert per_layer == 77_332_480
    assert needed.layer_flops_per_token(m) == 2.0 * per_layer
    assert needed.layer_flops_per_token(m) * 6 * L / 1e12 == pytest.approx(
        60.82, abs=0.01)
    assert needed.expand_flops(m, L) == 2 * 256 * 6144 * 6 * L
    assert needed.expand_flops(m, L) / 1e12 == pytest.approx(1.237, abs=0.001)
    assert needed.expand_bytes(m, L) == 6 * L * (2 * 320 + 32 * 256 * 2)
    assert needed.expand_bytes(m, L) / 819e9 > needed.expand_flops(m, L) / 197e12
    assert needed.causal_pairs(L) == L * (L + 1) // 2
    assert needed.attention_flops(m, L) == 6 * 16384 * (L * (L + 1) // 2)
    assert needed.attention_flops(m, L) / 1e12 == pytest.approx(211.11, abs=0.01)
    assert needed.attention_bytes(m, L) == 6 * L * 2 * 32 * 512
    assert needed.expert_flops(m, L) == 2.0 * 25_165_824 * 6 * L
    assert needed.expert_bytes(m, L) == 6 * (2 * 32 * 25_165_824 + 4 * d * L)
    assert needed.head_flops(m, L) == 2 * d * 32768 * L
    assert needed.head_flops(m, L) / 1e12 == pytest.approx(17.59, abs=0.01)
    assert needed.head_bytes_needed(m, L) == 2 * d * (32768 + L)
    total = needed.document_flops_needed(m, L)
    assert total / 1e12 == pytest.approx(290.76, abs=0.01)
    assert total / 197e12 == pytest.approx(1.4759, abs=0.0005)
    # The cell's entry of the backlog's table: the rate at the roofline.
    assert 197e12 / total == pytest.approx(0.6775, abs=0.0005)
    assert test_bench_backlog.AT_THE_ROOFLINE[CELL] == 0.68 >= 197e12 / total
    # The shares the cell's ``why`` quotes, here and in the whole model.
    assert needed.attention_flops(m, L) / total == pytest.approx(0.726, abs=0.001)
    assert needed.expert_flops(m, L) / total == pytest.approx(0.068, abs=0.001)
    assert needed.head_flops(m, L) / total == pytest.approx(0.0605, abs=0.001)
    whole = dict(m, n_layers=36, n_experts_held=128, vocab_size=131072)
    assert needed.head_flops(whole, L) / needed.document_flops_needed(
        whole, L) == pytest.approx(0.034, abs=0.001)
    assert needed.attention_flops(m, 32768) / needed.document_flops_needed(
        m, 32768) == pytest.approx(0.570, abs=0.001)


def test_the_parameters_by_a_count_of_the_leaves_shapes():
    """5,422.8 M parameters, 10.85 GB in bf16: the configuration file's
    arithmetic, from the shapes the program would build."""
    import jax
    import numpy as np

    from agent_tpu.models.decoder_lm import DecoderLMConfig, init_params

    cfg = DecoderLMConfig(**PUBLISHED)
    shapes = jax.eval_shape(lambda: init_params(cfg, "count"))
    assert set(shapes) == {"embed", "head", "final_norm", "expert_layers"}
    layers = shapes["expert_layers"]
    count = lambda tree: sum(int(np.prod(a.shape))  # noqa: E731
                             for a in jax.tree_util.tree_leaves(tree))
    assert layers["we_gate"].shape == (6, 32, 4096, 2048)
    assert layers["w_ukv"].shape == (6, 256, 32 * 192)
    assert layers["w_dkv"].shape == (6, 4096, 320)
    assert "router_bias" not in layers and "wi_k" not in layers
    mixer = sum(count(layers[k]) for k in ("w_dq", "w_uq", "w_dkv", "w_ukv", "wo"))
    assert mixer == 6 * 28_049_408
    assert count(layers["w_router"]) + sum(
        count(layers[k]) for k in ("ws_gate", "ws_up", "ws_down")) == 6 * 25_690_112
    assert sum(count(layers[k]) for k in ("we_gate", "we_up", "we_down")
               ) == 6 * 805_306_368
    norms = 2 * 4096 + 1024 + 256
    assert count(layers) == 6 * (859_045_888 + norms) == 5_154_332_160
    assert count(shapes) == 5_154_332_160 + 2 * 32768 * 4096 + 4096
    assert count(shapes) == 5_422_771_712
    text = manifest.load_config(manifest.load_manifest(), CONFIG)["deployment"]
    assert "5,422.8 M parameters, 10.85 GB" in text
    # The state a document carries: latents only, 252 MB at 65,536 tokens.
    from agent_tpu.models.decoder_lm import init_state

    state = jax.eval_shape(lambda: init_state(cfg, 1, 65536))
    assert set(state["mixer"]) == {"kv"}
    assert state["mixer"]["kv"].shape == (6, 1, 65536, 320)
    assert 2 * count(state["mixer"]) == 251_658_240


def test_the_programs_own_count_covers_the_need():
    """``segment_flops`` (the ``device_mfu{op}`` numerator: what the program
    does: every segment's re-expansion, whole key tiles) is never under the
    need, and over it by the re-expansion."""
    from agent_tpu.models.decoder_lm import DecoderLMConfig, segment_flops

    cfg = DecoderLMConfig(**PUBLISHED)
    done = sum(segment_flops(cfg, 4096, pos0) for pos0 in range(0, 65536, 4096))
    need = needed.document_flops_needed(PUBLISHED, 65536)
    again = 7.5 * needed.expand_flops(PUBLISHED, 65536)
    assert need <= done <= 1.001 * (need + again)
    assert done - need == pytest.approx(again, rel=0.05)


def test_means_over_documents():
    mean = needed.mean_needed(PUBLISHED, [65536, 8192])
    assert set(mean) == {"flops", "head_flops", "head_bytes", "attention_flops",
                         "attention_bytes", "expert_flops", "expert_bytes",
                         "expand_flops", "expand_bytes"}
    assert mean["flops"] == (needed.document_flops_needed(PUBLISHED, 65536)
                             + needed.document_flops_needed(PUBLISHED, 8192)) / 2
    assert mean["head_bytes"] == 2 * 4096 * (32768 + (65536 + 8192) / 2)


def test_the_counters_of_visited_tiles_and_expansions():
    """One query head a key head takes a whole 4,096-token segment a step:
    at 65,536 tokens 94.1 % of the visited pairs are causal ones (99.2 at
    512); 16 segments expand 8.5 latents a token."""
    from agent_tpu.kernels.causal_attention import query_tile, visited_pairs

    assert query_tile(1, 4096) == 4096 and query_tile(5, 4096) == 512
    visited = sum(visited_pairs(4096, p, 4096) for p in range(0, 65536, 4096))
    assert 100.0 * needed.causal_pairs(65536) / visited == pytest.approx(
        94.12, abs=0.01)
    assert sum(p + 4096 for p in range(0, 65536, 4096)) / 65536 == 8.5


# ---- each new reader on a recorded run ----------------------------------

def recorded_run():
    """What a traced run of the cell records, with round numbers: 0.4
    documents a second, a 5 s traced interval all busy, the segment programs
    4.6 s of it and the head 0.4 s; the expansion 0.2 s, the attention 3.0 s,
    the grouped matmul 0.4 s."""
    def counters(expanded, cached, causal, computed, pairs, tokens):
        return {
            "latent_keys_expanded_total": {"series": [
                {"labels": {"kind": "expanded"}, "value": expanded},
                {"labels": {"kind": "cached"}, "value": cached}]},
            "causal_attention_pairs_total": {"series": [
                {"labels": {"kind": "causal"}, "value": causal},
                {"labels": {"kind": "computed"}, "value": computed}]},
            "moe_expert_pairs_total": {"series": [{"labels": {}, "value": pairs}]},
            "moe_tokens_total": {"series": [{"labels": {}, "value": tokens}]}}
    return {
        "kind": "drain", "lm_needed": needed.mean_needed(PUBLISHED, [65536]),
        "end_to_end": {"drain_rows_per_s": 0.4},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "agent_metrics": (counters(1e6, 1e5, 1e6, 2e6, 10.0, 10.0),
                          counters(1e6 + 850.0, 1e5 + 100.0, 1e6 + 940.0,
                                   2e6 + 1000.0, 10.0 + 98.0, 10.0 + 100.0)),
        "trace": {"window_s": 5.0, "busy_s": 5.0, "programs": {
            "lm_segment": {"clipped_seconds": 4.6, "seconds": 4.6, "count": 32},
            "lm_loss_head": {"clipped_seconds": 0.4, "seconds": 0.4,
                             "count": 32}}},
        "op_times": {"latent_expand": {"seconds": 0.2, "count": 192},
                     "causal_attention": {"seconds": 3.0, "count": 192},
                     "expert_ffn": {"seconds": 0.4, "count": 192}},
    }


@pytest.mark.parametrize("name, want", [
    ("lm_roofline", 100 * 0.4 * 290.755327295488e12 / 1.0 / 197e12),
    ("loss_head_roofline", 100 * 0.4 * (17.592186044416e12 / 197e12) / (0.4 / 5)),
    # bytes bound the expansion: 6.694 GB against 1.237 TFLOP.
    ("latent_expand_roofline", 100 * 0.4 * (6.694109184e9 / 819e9) / 0.04),
    ("latent_causal_attention_roofline",
     100 * 0.4 * (211.109453758464e12 / 197e12) / 0.6),
    ("latent_expert_ffn_roofline",
     100 * 0.4 * (19.791209299968e12 / 197e12) / 0.08),
    ("latent_attention_device_share.drain", 64.0),
    ("latent_expansions_per_token.drain", 8.5),
    ("latent_causal_pair_share.drain", 94.0),
    ("latent_expert_pairs_per_token.drain", 0.98),
])
def test_reader_on_a_recorded_run(name, want):
    reader = manifest.load_layer_metric(name)
    assert reader.read(recorded_run()) == pytest.approx(want, rel=1e-9)
    assert 0 < want < 100


NEW_READERS = ["latent_expand_roofline", "latent_expansions_per_token.drain",
               "latent_attention_device_share.drain"]
# The accepted readers this cell reads under entries of its own: the
# accepted entries' lists of cells are held to literal lists by
# ``test_bench_sparse_mla.py``, ``test_bench_hybrid_ssm.py`` and
# ``test_bench_parts.py``, which no PR but a ``benchmark`` PR may edit.
ACCEPTED = {
    "latent_causal_attention_roofline": "causal_attention_roofline",
    "latent_causal_pair_share.drain": "causal_attention_pair_share.drain",
    "latent_expert_ffn_roofline": "expert_ffn_roofline",
    "latent_expert_pairs_per_token.drain": "expert_pairs_per_token.drain",
    **{"latent_" + name: name for name in (
        "unnamed_device_share.drain", "norm_device_ms_per_shard.drain",
        "project_device_ms_per_shard.drain", "mixer_device_ms_per_shard.drain",
        "around_device_ms_per_shard.drain", "ffn_device_ms_per_shard.drain",
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
                "latent_expand": {"seconds": 0.0, "count": 0},
                "expert_ffn": {"seconds": 0.0, "count": 0},
                "causal_attention": {"seconds": 0.0, "count": 0}},
            "trace": {"window_s": 3.0, "busy_s": 3.0, "programs": {
                "lm_segment": {"clipped_seconds": 2.5}}},
            # What ``part_times.of_run`` keeps of a program with no part map.
            "parts": None}
    assert reader.read(bare) is None
    assert reader.read(dict(bare, trace=None, op_times=None)) is None


def test_the_device_share_wants_both_kernels():
    """The attention kernel alone is falcon's mixer: nothing to read."""
    reader = manifest.load_layer_metric("latent_attention_device_share.drain")
    run = recorded_run()
    run["op_times"]["latent_expand"] = {"seconds": 0.0, "count": 0}
    assert reader.read(run) is None


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


def test_op_patterns_match_the_start_of_an_events_name():
    """An ``XLA Ops`` event is named by its whole instruction, operands and
    all: a fusion that reads the expansion's result names it. The pattern
    takes its own kernel only."""
    import inspect
    import re

    from agent_tpu.kernels import sparse_mla

    events = {
        "latent_expand": "%sparse_mla_expand.3 = (bf16[32,65536,128]{2,1,0}, "
        "bf16[32,65536,128]{2,1,0}) custom-call(s32[1]{0} %reshape.9, "
        "bf16[65536,320]{1,0} %dynamic-update-slice.2)",
        "causal_attention": "%causal_gqa_attention.7 = bf16[32,1,4096,128]{3,2,1,0} "
        "custom-call(s32[1]{0} %reshape.3, bf16[32,1,4096,128]{3,2,1,0} %fusion.5)",
        None: "%fusion.77 = bf16[4096,4096]{1,0} fusion(bf16[32,65536,128]{2,1,0} "
        "%sparse_mla_expand.3, bf16[32,1,4096,128]{3,2,1,0} "
        "%causal_gqa_attention.7), kind=kLoop",
    }
    patterns = {}
    for name in ("latent_expand_roofline", "latent_attention_device_share.drain"):
        patterns.update(manifest.load_layer_metric(name).OP_PATTERNS)
    assert set(patterns) == {"latent_expand", "causal_attention"}
    for label, rx in patterns.items():
        assert [k for k, text in events.items() if re.search(rx, text)] == [label]
    assert 'name="sparse_mla_expand"' in inspect.getsource(sparse_mla)


def test_documents_draw_their_ids_from_the_slice():
    score = manifest.load_kind("score")
    traffic = manifest.load_traffic(TRAFFIC)
    docs = score.documents(traffic, PUBLISHED["vocab_size"], 2 ** 31 + 5, 2)
    assert [len(d) for d in docs] == [65536, 65536]
    assert 0 <= min(d.min() for d in docs) and max(
        d.max() for d in docs) < 32768
    assert max(d.max() for d in docs) > 30000


# ---- the configuration file and the manifest's entries -------------------

def test_the_configuration_file_keeps_the_catalogs_rules():
    m = manifest.load_manifest()
    cfg = manifest.load_config(m, CONFIG)
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and "Mistral-Small-4-119B-2603" in cfg[
        "source"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["published"]) <= set(cfg)
    model, pub = cfg["model"], cfg["published"]
    for ours, theirs in {
            "d_model": "hidden_size", "n_heads": "num_attention_heads",
            "d_ff": "intermediate_size", "max_len": "max_position_embeddings",
            "rms_norm_eps": "rms_norm_eps", "q_lora_rank": "q_lora_rank",
            "kv_lora_rank": "kv_lora_rank",
            "qk_nope_head_dim": "qk_nope_head_dim",
            "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
            "d_expert": "moe_intermediate_size",
            "n_experts_per_token": "num_experts_per_tok",
            "n_shared_experts": "n_shared_experts",
            "n_expert_groups": "n_group", "n_groups_per_token": "topk_group",
            "routed_scale": "routed_scaling_factor",
            "n_dense_layers": "first_k_dense_replace"}.items():
        assert model[ours] == pub[theirs], (ours, theirs)
    rope = pub["rope_parameters"]
    assert cfg["rope_parameters"] == rope
    for ours, theirs in {
            "rope_theta": "rope_theta", "rope_factor": "factor",
            "rope_original_max_len": "original_max_position_embeddings",
            "rope_beta_fast": "beta_fast", "rope_beta_slow": "beta_slow",
            "rope_mscale": "mscale_all_dim",
            "query_scale_beta": "llama_4_scaling_beta"}.items():
        assert model[ours] == rope[theirs], (ours, theirs)
    assert pub["qk_head_dim"] == pub["head_dim"] == 128 == (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"])
    assert pub["rope_interleave"] is True and pub["norm_topk_prob"] is True
    assert model["scoring_func"] == "softmax" and "scoring_func" not in pub
    assert model["mixer"] == "dense_mla" and model["dtype"] == "bfloat16"
    # The cut: a stage of 6 of 36 layers, a chip's 32 of 128 experts, a
    # quarter of the vocabulary; the floors are 4 layers, 8 experts, an eighth.
    assert model["n_layers"] == cfg["num_hidden_layers"] == 6
    assert pub["num_hidden_layers"] == 36 == 6 * 6
    assert model["n_experts"] == pub["n_routed_experts"] == 128
    assert model["n_experts_held"] == cfg["n_routed_experts"] == 32 >= 8
    assert model["vocab_size"] == cfg["vocab_size"] == 131072 // 4
    for key in ("scoring_func", "mscale", "query_scale", "vision", "weights"):
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


def test_manifest_entries_of_the_cell(manifests):
    m = manifests
    cell = manifest.find_cell(m, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    cfg = manifest.load_config(m, cell["config"])
    assert cfg["needed_work"] == "latent_moe_flops"
    assert cfg["reference"] == "latent_moe_lm"
    traffic = manifest.load_traffic(cell["traffic"])
    assert traffic["kind"] == "score" and traffic["shard_rows"] == 1
    assert traffic["doc_tokens"] == {"dist": "fixed", "value": 65536}
    assert traffic["token_ids"] == {"dist": "zipf", "exponent": 1.1}
    assert (traffic["job_rows"], traffic["tenants"], traffic["order_seed"],
            traffic["lead_in_shards"], traffic["agent"]) == (8, 1, 0, 3, {})
    e2e = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "end_to_end")}
    assert e2e == {"drain_rows_per_s", "setup_s"}
    per_layer = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "per_layer")}
    # Everything the three score cells share (the by-part readers under
    # entries of this cell's own), and what this one brings.
    shared = {e["name"] for e in manifest.metrics_of_cell(
        m, "brumby-14b-base.score-long", "per_layer")} & {
        e["name"] for e in manifest.metrics_of_cell(
            m, "deepseek-v3.2.score-32k", "per_layer")} & {
        e["name"] for e in manifest.metrics_of_cell(
            m, "falcon-h1-34b.score-64k", "per_layer")}
    mine = set(NEW_READERS) | set(ACCEPTED)
    assert {("latent_" + n if "latent_" + n in ACCEPTED else n)
            for n in shared} | mine <= per_layer
    assert "trace_lower_s.setup" in per_layer
    assert not {n for n in per_layer if n.startswith(
        ("retention_", "sparse_", "indexer_", "ssd_", "hybrid_"))}
    for entry in m["per_layer"]:
        if entry["name"] in mine:
            assert CELL in entry["workloads"]
            assert entry["moves"] == "drain_rows_per_s"
