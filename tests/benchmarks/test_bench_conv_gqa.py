"""The ``lfm2-24b-a2b.score-32k-conv`` cell off the chip: its CPU rehearsal
through ``MODEL_OVERRIDES`` / ``TRAFFIC_OVERRIDES`` (as
``test_bench_hybrid_kda.py``), the needed-work functions against the hand
arithmetic of their docstring, the configuration's parameter count by a
count of the leaves' shapes, the broken forms against the configuration's own
limits, each new reader on a recorded ``run``, the configuration file against
the catalog's rules, and the manifest's entries. No number printed here is a
device number.

The cell's OWN per-layer readers (``benchmarks/layer_metrics/conv_*``) have no
entry in ``BENCHMARK.json`` yet: ``test_bench_hybrid_kda.py`` holds ITS
entries to the END of ``per_layer``, so nothing can be appended behind them
until a ``benchmark`` PR takes that line out (PERF.md section 7). ``ENTRIES``
below is what that PR appends; here the rehearsal runs under a manifest that
has them (``benchmarks/run.py: run_cell`` takes the manifest it is given)."""

from __future__ import annotations

import copy
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

CELL = "lfm2-24b-a2b.score-32k-conv"
CONFIG = "lfm2-24b-a2b"
TRAFFIC = "score-32k-conv"
# ``test_bench_backlog.py`` holds every cell's backlog over the cell's rate at
# 100 % of its roofline and asks a new cell to bring that rate; its table may
# not be edited by a PR that adds a cell, so the entry comes from here: a
# document needs 51.265 TFLOP, 0.2602 s at 197 TFLOP/s, 3.8428 rows/s.
test_bench_backlog.AT_THE_ROOFLINE.setdefault(CELL, 3.85)
# The published pattern cut as the cell's is (a leading dense ``conv`` layer,
# then two periods that BEGIN with their attention layer) at heads of 64, two
# key-value heads a cache row; 16 experts, all held, 4 a token, none shared.
TINY_LM = {
    "vocab_size": 2048, "d_model": 64, "n_heads": 8, "n_kv_heads": 2,
    "d_head": 64, "d_ff": 96, "n_layers": 9, "max_len": 16384,
    "n_experts": 16, "n_experts_held": 16, "d_expert": 32,
    "dtype": "float32",
}
# 2,600 tokens under segments of 2,048 and 1,024 (the op's sizes halved for
# the CPU): both kinds of state cross a program boundary in every document.
DOC_TOKENS = 2600
SEGMENT_BUCKETS = (1024, 2048)
TINY_SCORE = {
    "doc_tokens": {"dist": "fixed", "value": DOC_TOKENS}, "job_rows": 4,
    "backlog_rows_per_s": 10, "lead_in_shards": 1, "trace_start_s": 0.2,
    "trace_seconds": 3.0,
}
PUBLISHED = manifest.load_config(manifest.load_manifest(), CONFIG)["model"]
needed = manifest.load_needed_work("conv_moe_flops")

NEW_READERS = ["conv_tail_token_share.drain"]
# The accepted readers this cell reads under names of its own: the accepted
# entries' lists of cells are held to literal lists by
# ``test_bench_sparse_mla.py``, ``test_bench_hybrid_ssm.py``,
# ``test_bench_window_moe.py`` and ``test_bench_parts.py``.
ACCEPTED = {
    "conv_causal_attention_roofline": "causal_attention_roofline",
    "conv_causal_pair_share.drain": "causal_attention_pair_share.drain",
    "conv_expert_ffn_roofline": "expert_ffn_roofline",
    "conv_expert_pairs_per_token.drain": "expert_pairs_per_token.drain",
    "conv_expert_tile_fill.drain": "expert_tile_fill.drain",
    **{"conv_" + name: name for name in (
        "unnamed_device_share.drain", "norm_device_ms_per_shard.drain",
        "project_device_ms_per_shard.drain", "mixer_device_ms_per_shard.drain",
        "around_device_ms_per_shard.drain", "ffn_device_ms_per_shard.drain",
        "experts_device_ms_per_shard.drain")},
}


def entries():
    """The per-layer entries of the cell's own readers, as a ``benchmark`` PR
    appends them: an accepted reader's under its own unit, direction, source
    and layer."""
    accepted = {e["name"]: e for e in manifest.load_manifest()["per_layer"]}
    keys = ("unit", "better", "source", "layer")
    own = {"conv_tail_token_share.drain": ("%", "higher", "program_counter",
                                           "Ops")}
    return [{"name": name,
             **(dict(zip(keys, own[name])) if name in own else {
                 k: accepted[ACCEPTED[name]][k] for k in keys}),
             "moves": "drain_rows_per_s", "workloads": [CELL]}
            for name in NEW_READERS + list(ACCEPTED)]


def with_entries(m):
    m = copy.deepcopy(m)
    m["per_layer"] += entries()
    return m


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


def compared_of(lines):
    return {c["number"]: c for c in map(json.loads, (
        ln for ln in lines if ln.startswith('{"bench": "compared"')))}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(tiny, capsys, trace):
    code = bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 52),
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
        assert result["metrics"]["compiles_in_window.drain"]["value"] == 0
        assert not any("roofline" in n or "device_share" in n or "device_ms" in n
                       for n in result["metrics"])
    compared = compared_of(lines)
    assert set(compared) == set(manifest.load_config(
        manifest.load_manifest(), CONFIG)["check"]["limits"])
    # float32 against the float32 reference: rounding in another order, and
    # at most a token whose 4th and 5th expert scores tie to float32's last
    # bits and fall apart (tenths of a nat over a block of 1,024).
    assert result["correct"] is True, lines[-8:]
    assert compared["block_logprob_gap_max"]["value"] < 1e-3, compared


def test_cell_rehearsal_with_its_own_readers(tiny, capsys):
    """The traced rehearsal under a manifest that has the cell's own entries:
    the counter-based readers read what the op ticked; no device plane in a
    CPU trace, so the device_trace readers are left out, never printed as a
    number."""
    from agent_tpu.kernels.causal_attention import query_tile, visited_pairs

    run = bench_run.run_cell(with_entries(manifest.load_manifest()), CELL,
                             2 ** 31 + 53, 2.0, 1)
    capsys.readouterr()
    assert run["correct"] is True and run["failed"] == 0
    metrics = run["metrics"]
    n = DOC_TOKENS
    assert metrics["conv_tail_token_share.drain"]["value"] == pytest.approx(
        100.0 * (n - 2048) / n)
    # 4 query heads a key-value head, two heads a cache row: 8 stacked.
    computed = sum(visited_pairs(s, p, query_tile(8, s))
                   for s, p in [(2048, 0), (1024, 2048)])
    assert metrics["conv_causal_pair_share.drain"]["value"] == pytest.approx(
        100.0 * (n * (n + 1) / 2) / computed)
    # Every expert is held: a token's 4 pairs are all routed here.
    assert metrics["conv_expert_pairs_per_token.drain"]["value"] == 4.0
    assert 0.0 < metrics["conv_expert_tile_fill.drain"]["value"] <= 100.0
    assert not any("roofline" in n or "device_share" in n or "device_ms" in n
                   for n in metrics)


# ---- the broken forms against the configuration's own limits --------------

def _conv_kind(form):
    """``conv_gqa``'s mixer with its ``conv`` kind replaced by ``form(p, h,
    state, cfg) -> (what enters the out-projection, the new tail)``."""
    from agent_tpu.models import decoder_lm

    real = decoder_lm._conv_gqa_mixer

    def mixer(p, h, positions, state, cfg, kernel_opts, kind):
        if kind != "conv":
            return real(p, h, positions, state, cfg, kernel_opts, kind)
        y, tail = form(p, h[0], state["tail"][0], cfg)
        return decoder_lm.linear(p["wo"], y[None], cfg.compute_dtype), {
            "tail": tail[None]}
    return mixer


def _gates(p, h, cfg):
    from agent_tpu.models import decoder_lm

    d = cfg.d_model
    proj = decoder_lm.linear(p["w_conv_in"], h, cfg.compute_dtype)
    return proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]


def _second_gate_dropped():
    """``c`` in place of ``C x c``."""
    from agent_tpu.kernels import ssd

    def form(p, h, tail, cfg):
        B, _, z = _gates(p, h, cfg)
        return ssd.causal_conv(B * z, tail, p["conv_w"])
    return _conv_kind(form)


def _taps_reversed():
    """The newest token under the oldest tap."""
    from agent_tpu.kernels import ssd

    def form(p, h, tail, cfg):
        B, C, z = _gates(p, h, cfg)
        c, tail = ssd.causal_conv(B * z, tail, p["conv_w"][::-1])
        return C * c, tail
    return _conv_kind(form)


def _cache_not_carried():
    """An attention layer attends its own segment's keys over an EMPTY cache:
    what the segments before wrote is gone at every boundary."""
    import jax.numpy as jnp

    from agent_tpu.models import decoder_lm

    real = decoder_lm._conv_gqa_mixer

    def mixer(p, h, positions, state, cfg, kernel_opts, kind):
        if kind == "full":
            state = {**state, "k": jnp.zeros_like(state["k"]),
                     "v": jnp.zeros_like(state["v"])}
        return real(p, h, positions, state, cfg, kernel_opts, kind)
    return mixer


_SOUND = {}       # the sound program's readings: compiled and run once


@pytest.mark.parametrize("broken", [
    _cache_not_carried, _second_gate_dropped, _taps_reversed])
def test_a_broken_form_fails_the_configurations_limits(tiny, broken):
    """The cell's own check (``kinds/score.py: check_documents``: its sample,
    the reference, ``compare`` and the configuration's limits) on what the
    op answers for two FIXED documents when a mixer is wrong: not ``correct``
    at a tiny width in float32, where the sound program reads under 1e-4
    (by WHICH limit a form fails follows the weights at this width; PERF.md
    section 6 has the chip's readings, among them a tail zeroed at every
    boundary, which the cell does NOT catch: ``tests/test_conv_gqa.py`` holds
    one segment equal to four to the bit)."""
    from agent_tpu.models import decoder_lm
    from agent_tpu.ops import get_op

    score = manifest.load_kind("score")
    cfg = manifest.load_config(manifest.load_manifest(), CONFIG)
    docs = score.documents(
        {**manifest.load_traffic(TRAFFIC),
         "doc_tokens": {"dist": "fixed", "value": DOC_TOKENS}},
        cfg["model"]["vocab_size"], 5252, 2)
    # Segments of 1,024 (a loss block): two boundaries a document.
    tiny.setattr(map_score_lm, "SEGMENT_BUCKETS", (1024,))

    def compared(mixer):
        tiny.setitem(decoder_lm.MIXERS, "conv_gqa", mixer)
        reset_runtime()
        out = get_op("map_score_lm")({
            "ids": [d.tolist() for d in docs], "model_config": cfg["model"],
            "model_path": "conv-broken", "allow_fallback": False})
        assert out.get("ok", True), out
        return {c["number"]: c for c in score.check_documents(
            {"config": cfg, "seed": 5252}, docs, [(0, "conv-broken", out)])}

    if not _SOUND:
        _SOUND.update(compared(decoder_lm._conv_gqa_mixer))
    sound = _SOUND
    assert all(c["ok"] for c in sound.values()), sound
    assert sound["block_logprob_gap_max"]["value"] < 1e-3
    wrong = compared(broken())
    assert not all(c["ok"] for c in wrong.values()), wrong
    assert wrong["block_logprob_gap_rms"]["value"] > 30 * max(
        1e-4, sound["block_logprob_gap_rms"]["value"]), wrong


# ---- the counting functions against hand arithmetic ----------------------

def test_counts_of_a_32768_token_document_at_the_published_widths():
    """ISSUE 52's figures by the leaves' shapes: 1,564 MFLOP a token, 51.27
    TFLOP a document: the experts 38.7 %, attention 17.2 %, the head 17.2 %,
    the seven ``conv`` mixers 15.0 %, the dense FFN 9.2 %, the attention
    projections 2.7 %."""
    m, L = PUBLISHED, 32768
    d = 2048
    assert needed.layers_of(m) == {"conv": 7, "full": 2, "dense": 1,
                                   "experts": 8}
    assert needed.conv_projection_params(m) == d * 6144 + d * d == 16_777_216
    assert needed.attention_projection_params(m) == (
        2 * d * 2048 + 2 * d * 512) == 10_485_760
    assert needed.expert_params(m) == 3 * d * 1536 == 9_437_184
    assert needed.pairs_per_token(m) == 4.0
    assert needed.conv_gate_bytes(m, L) == 7 * L * 16_384
    assert needed.conv_gate_bytes(m, L) / 819e9 == pytest.approx(4.59e-3, abs=1e-5)
    assert needed.attention_flops(m, L) == 2 * 8_192 * (L * (L + 1) // 2)
    assert needed.attention_flops(m, L) / 1e12 == pytest.approx(8.796, abs=0.001)
    assert needed.attention_bytes(m, L) == 2 * L * 2 * 64 * 2 * 40
    assert needed.expert_flops(m, L) == 2.0 * 4.0 * 9_437_184 * 8 * L
    assert needed.expert_flops(m, L) / 1e12 == pytest.approx(19.79, abs=0.005)
    assert needed.expert_bytes(m, L) == 8 * (2 * 64 * 9_437_184 + 4 * d * 4 * L)
    # 256 rows an expert a segment: a layer's weights read once a SEGMENT
    # take 1.47 ms where its pairs' products take 1.57 at the peak: on the
    # line between the two.
    assert 2 * 64 * 9_437_184 / 819e9 == pytest.approx(1.475e-3, abs=1e-6)
    assert 2.0 * 4.0 * 9_437_184 * 4096 / 197e12 == pytest.approx(
        1.570e-3, abs=1e-6)
    assert needed.head_flops(m, L) == 2 * d * 65536 * L
    assert needed.head_flops(m, L) / 1e12 == pytest.approx(8.796, abs=0.001)
    assert needed.head_bytes_needed(m, L) == 2 * d * (65536 + L)
    per_token = 2.0 * (7 * 16_777_216 + 2 * 10_485_760 + 3 * d * 11776 + 8 * (
        d * 64 + 4 * 9_437_184))
    assert needed.per_token_flops(m) == per_token
    total = needed.document_flops_needed(m, L)
    assert total / L / 1e6 == pytest.approx(1564.5, abs=0.05)
    assert total / 1e12 == pytest.approx(51.265, abs=0.005)
    assert total / 197e12 == pytest.approx(0.2602, abs=0.0005)
    # The cell's entry of the backlog's table: the rate at the roofline,
    # under the backlog's ceiling.
    assert 197e12 / total == pytest.approx(3.8428, abs=0.0005)
    assert test_bench_backlog.AT_THE_ROOFLINE[CELL] == 3.85 >= 197e12 / total
    from benchmarks.harness import backlog

    size = backlog.plan(manifest.load_traffic(TRAFFIC), 10.0)
    assert (size["n_jobs"], size["shards"]) == (6, 48) and size["shards"] >= 24
    assert size["ceiling_rows_per_s"] == 4.3 > 3.85
    experts = 2.0 * 8 * (d * 64 + 4 * 9_437_184) * L
    for part, share in (
            (experts, 0.387), (needed.attention_flops(m, L), 0.172),
            (needed.head_flops(m, L), 0.172),
            (2.0 * 7 * 16_777_216 * L, 0.150),
            (2.0 * 3 * d * 11776 * L, 0.092), (2.0 * 2 * 10_485_760 * L, 0.027)):
        assert part / total == pytest.approx(share, abs=0.001)
    # The head at the published depth (2 dense + 38 layers), and attention's
    # share at 65,536 and at 8,192 tokens: why 32,768.
    deep = dict(m, n_layers=40, n_dense_layers=2, layer_types=(
        ["conv", "conv", "full_attention", "conv"] * 10))
    assert needed.head_flops(deep, L) / needed.document_flops_needed(
        deep, L) == pytest.approx(0.045, abs=0.001)
    assert needed.attention_flops(m, 65536) / needed.document_flops_needed(
        m, 65536) == pytest.approx(0.29, abs=0.005)
    assert needed.attention_flops(m, 8192) / needed.document_flops_needed(
        m, 8192) == pytest.approx(0.05, abs=0.005)


def test_the_parameters_by_a_count_of_the_leaves_shapes():
    """5,312,168,704 parameters, 10.62 GB in bf16: the configuration file's
    arithmetic, from the shapes the program would build; the state a
    document carries, by kind."""
    import jax

    from agent_tpu.models.decoder_lm import (DecoderLMConfig, init_params,
                                             init_state)

    cfg = DecoderLMConfig(**PUBLISHED)
    shapes = jax.eval_shape(lambda: init_params(cfg, "count"))
    assert set(shapes) == {"embed", "head", "final_norm", "layers",
                           "expert_layers"}
    count = lambda tree: sum(int(np.prod(a.shape))  # noqa: E731
                             for a in jax.tree_util.tree_leaves(tree))
    matrices = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree)
        if a.dtype == jax.numpy.bfloat16)
    dense, experts = shapes["layers"], shapes["expert_layers"]
    assert experts["we_gate"].shape == (8, 64, 2048, 1536)
    conv, full = experts["mixers"]["conv"], experts["mixers"]["full"]
    assert conv["w_conv_in"].shape == (6, 2048, 6144)
    assert conv["wo"].shape == (6, 2048, 2048)
    assert conv["conv_w"].shape == (6, 3, 2048)
    assert full["wq"].shape == (2, 2048, 2048)
    assert full["wk"].shape == full["wv"].shape == (2, 2048, 512)
    assert full["q_norm"].shape == full["k_norm"].shape == (2, 64)
    assert set(dense["mixers"]) == {"conv"}          # the pattern's own kind
    assert dense["mixers"]["conv"]["w_conv_in"].shape == (1, 2048, 6144)
    assert count(dense) == 89_139_200
    assert matrices(conv) == 6 * 16_783_360
    assert matrices(full) == 2 * 10_485_760
    assert sum(count(experts[k]) for k in ("we_gate", "we_up", "we_down")
               ) == 8 * 603_979_776
    assert count(experts["w_router"]) == 8 * 131_072
    assert count(experts["router_bias"]) == 8 * 64
    assert count(experts) == 6 * 620_898_368 + 2 * 614_600_896
    assert count(shapes) == 5_312_168_704
    assert 2 * matrices(shapes) / 1e9 == pytest.approx(10.62, abs=0.005)
    text = manifest.load_config(manifest.load_manifest(), CONFIG)["deployment"]
    assert "5,312,168,704 parameters, 10.62 GB" in text
    assert "620,898,368" in text and "614,600,896" in text
    # Over the floor of a quarter of the chip's 16 GiB, by a factor of 2.4.
    assert 2 * matrices(shapes) > 2.4 * 0.25 * 16 * 2 ** 30
    state = jax.eval_shape(lambda: init_state(cfg, 1, 32768))
    assert set(state) == {"mixer", "pairs", "tiles"}
    # Heads of 64 lie two a row of 128 lanes: 4 rows for 8 key-value heads.
    assert state["mixer"]["full"]["k"].shape == (2, 1, 4, 32768, 128)
    assert state["mixer"]["conv"]["tail"].shape == (7, 1, 2, 2048)
    assert 2 * count(state["mixer"]["full"]) == 2 * 67_108_864
    assert 4 * count(state["mixer"]["conv"]) == 7 * 16_384


def test_the_programs_own_count_covers_the_need():
    """``segment_flops`` (the ``device_mfu{op}`` numerator) counts what the
    program DOES: the need over the exact causal pairs, and the gates' and
    taps' elementwise terms, which the need (matmul terms only) leaves out."""
    from agent_tpu.models.decoder_lm import DecoderLMConfig, segment_flops

    cfg = DecoderLMConfig(**PUBLISHED)
    L = 32768
    done = sum(segment_flops(cfg, 4096, pos0) for pos0 in range(0, L, 4096))
    need = needed.document_flops_needed(PUBLISHED, L)
    elementwise = 7 * 8 * 2048 * L
    assert done == pytest.approx(need + elementwise, rel=2e-4)
    assert 1.0 <= done / need < 1.001


def test_means_over_documents():
    mean = needed.mean_needed(PUBLISHED, [32768, 8192])
    assert set(mean) == {"flops", "head_flops", "head_bytes",
                         "attention_flops", "attention_bytes", "expert_flops",
                         "expert_bytes", "conv_gate_bytes"}
    assert mean["flops"] == (needed.document_flops_needed(PUBLISHED, 32768)
                             + needed.document_flops_needed(PUBLISHED, 8192)) / 2
    assert mean["head_bytes"] == 2 * 2048 * (65536 + (32768 + 8192) / 2)


# ---- each new reader on a recorded run ----------------------------------

def recorded_run():
    """What a traced run of the cell records, with round numbers: 2.0
    documents a second, a 2 s traced interval all busy, the segment programs
    1.7 s of it and the head 0.3 s; the attention kernel 0.4 s, the grouped
    matmul 0.6 s."""
    def counters(carried, first, causal, computed, pairs, tokens, tiles):
        one = lambda v: {"series": [{"labels": {}, "value": v}]}  # noqa: E731
        two = lambda key, a, b: {"series": [  # noqa: E731
            {"labels": {key: a[0]}, "value": a[1]},
            {"labels": {key: b[0]}, "value": b[1]}]}
        return {
            "conv_tail_tokens_total": two("path", ("carried", carried),
                                          ("first_segment", first)),
            "causal_attention_pairs_total": two(
                "kind", ("causal", causal), ("computed", computed)),
            "moe_expert_pairs_total": one(pairs),
            "moe_tokens_total": one(tokens), "moe_tiles_total": one(tiles)}
    return {
        "kind": "drain", "lm_needed": needed.mean_needed(PUBLISHED, [32768]),
        "end_to_end": {"drain_rows_per_s": 2.0},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "agent_metrics": (
            counters(1e6, 1e3, 1e6, 2e6, 10.0, 10.0, 5.0),
            counters(1e6 + 875.0, 1e3 + 125.0, 1e6 + 940.0, 2e6 + 1000.0,
                     10.0 + 4096.0, 10.0 + 1024.0, 5.0 + 25.0)),
        "trace": {"window_s": 2.0, "busy_s": 2.0, "programs": {
            "lm_segment": {"clipped_seconds": 1.7, "seconds": 1.7, "count": 32},
            "lm_loss_head": {"clipped_seconds": 0.3, "seconds": 0.3,
                             "count": 32}}},
        "op_times": {"causal_attention": {"seconds": 0.4, "count": 64},
                     "expert_ffn": {"seconds": 0.6, "count": 256}},
    }


@pytest.mark.parametrize("name, want", [
    ("lm_roofline", 100 * 2.0 * 51.264998080512e12 / 197e12),
    ("loss_head_roofline", 100 * 2.0 * (8.796093022208e12 / 197e12) / (0.3 / 2)),
    ("conv_tail_token_share.drain", 87.5),
    # Under a half: the kernel's products run at half the unit's depth.
    ("conv_causal_attention_roofline",
     100 * 2.0 * (8.796361457664e12 / 197e12) / 0.2),
    ("conv_causal_pair_share.drain", 94.0),
    ("conv_expert_ffn_roofline", 100 * 2.0 * (19.791209299968e12 / 197e12) / 0.3),
    ("conv_expert_pairs_per_token.drain", 4.0),
    ("conv_expert_tile_fill.drain", 64.0),
])
def test_reader_on_a_recorded_run(name, want):
    reader = manifest.load_layer_metric(name)
    assert reader.read(recorded_run()) == pytest.approx(want, rel=1e-9)
    assert 0 < want < 100


@pytest.mark.parametrize("name", NEW_READERS + sorted(ACCEPTED))
def test_reader_reads_nothing_where_the_program_has_nothing(name):
    """On the parent (no such scope or counter), under another family's
    needed-work counter, and untraced."""
    reader = manifest.load_layer_metric(name)
    bare = {"kind": "drain", "end_to_end": {"drain_rows_per_s": 1.3},
            "cell": {"name": "no-such-cell"},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "lm_needed": {"flops": 1e14, "head_flops": 1e13, "head_bytes": 1e9,
                          "retention_flops": 1e13, "retention_bytes": 1e9},
            "agent_metrics": ({}, {}), "op_times": {
                "retention": {"seconds": 0.4, "count": 10},
                "expert_ffn": {"seconds": 0.0, "count": 0},
                "causal_attention": {"seconds": 0.0, "count": 0}},
            "trace": {"window_s": 3.0, "busy_s": 3.0, "programs": {
                "lm_segment": {"clipped_seconds": 2.5}}},
            # What ``part_times.of_run`` keeps of a program with no part map.
            "parts": None}
    assert reader.read(bare) is None
    assert reader.read(dict(bare, trace=None, op_times=None)) is None


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_an_accepted_reader_is_read_not_copied(name):
    mine = manifest.load_layer_metric(name)
    accepted = manifest.load_layer_metric(ACCEPTED[name])
    assert mine.read.__code__.co_filename == accepted.read.__code__.co_filename
    assert mine.read.__code__.co_filename.endswith(ACCEPTED[name] + ".py")
    assert getattr(mine, "OP_PATTERNS", None) == getattr(
        accepted, "OP_PATTERNS", None)


def test_the_gate_pass_has_a_scope_and_no_reader():
    """ISSUE 52 asked for ``conv_gate_roofline`` unless XLA fuses the pass
    into the projections: it does (PERF.md section 5: the in-projection stays
    in the core's memory, ``B x z`` is one small fusion, the taps and ``C x``
    ride inside the out-projection's fusion), so there is NO such reader; the
    scope stays, because it is how the compiled text shows where the pass
    went, and the bytes the pass would need from HBM stay in the counter."""
    import inspect

    from agent_tpu.models import decoder_lm

    assert 'jax.named_scope("conv_gate")' in inspect.getsource(
        decoder_lm._conv_gqa_mixer)
    assert not os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", "conv_gate_roofline.py"))
    assert "conv_gate_bytes" in needed.mean_needed(PUBLISHED, [32768])


def test_documents_draw_their_ids_from_the_whole_vocabulary():
    score = manifest.load_kind("score")
    traffic = manifest.load_traffic(TRAFFIC)
    docs = score.documents(traffic, PUBLISHED["vocab_size"], 2 ** 31 + 5, 2)
    assert [len(d) for d in docs] == [32768, 32768]
    assert 0 <= min(d.min() for d in docs) and max(
        d.max() for d in docs) < 65536
    assert max(d.max() for d in docs) > 60000


# ---- the configuration file and the manifest's entries -------------------

def test_the_configuration_file_keeps_the_catalogs_rules():
    m = manifest.load_manifest()
    cfg = manifest.load_config(m, CONFIG)
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and "LFM2-24B-A2B" in cfg["source"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types"}
    # No width among them.
    assert not any(k.endswith(("_dim", "_rank", "_size")) or "per_tok" in k
                   for k in changed)
    assert set(cfg["published"]) <= set(cfg)
    model, pub = cfg["model"], cfg["published"]
    for ours, theirs in {
            "d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
            "max_len": "max_position_embeddings", "rms_norm_eps": "norm_eps",
            "d_expert": "moe_intermediate_size", "n_experts": "num_experts",
            "n_experts_per_token": "num_experts_per_tok",
            "routed_scale": "routed_scaling_factor",
            "conv_taps": "conv_L_cache", "vocab_size": "vocab_size"}.items():
        assert model[ours] == pub[theirs], (ours, theirs)
    assert model["d_head"] == pub["hidden_size"] // pub[
        "num_attention_heads"] == 64
    assert model["rope_theta"] == pub["rope_parameters"]["rope_theta"]
    assert pub["rope_parameters"]["rope_type"] == "default"
    assert cfg["rope_parameters"] == pub["rope_parameters"]
    assert (pub["conv_bias"], pub["use_expert_bias"], pub["norm_topk_prob"]
            ) == (False, True, True)
    # The layer pattern: ten periods of (conv, conv, attention, conv), the
    # first two layers' FFN dense. The cut keeps published layers 1-9: one
    # leading dense layer and the first two whole periods behind the dense
    # ones, which BEGIN with their attention layer.
    assert pub["layer_types"] == ["conv", "conv", "full_attention",
                                  "conv"] * 10
    assert pub["num_hidden_layers"] == 40 and pub["num_dense_layers"] == 2
    assert cfg["layer_types"] == pub["layer_types"][1:10] == model[
        "layer_types"]
    assert model["n_layers"] == cfg["num_hidden_layers"] == 9 == 1 + 2 * 4
    assert model["n_dense_layers"] == cfg["num_dense_layers"] == 1
    assert model["layer_types"][1:5] == ["full_attention", "conv", "conv",
                                         "conv"] == model["layer_types"][5:]
    # Every expert, none shared, one group.
    assert model["n_experts_held"] == model["n_experts"] == 64
    assert (model["expert_first"], model["n_shared_experts"],
            model["n_expert_groups"], model["n_groups_per_token"]) == (0, 0, 1, 1)
    assert model["scoring_func"] == "sigmoid"
    assert model["mixer"] == "conv_gqa" and model["dtype"] == "bfloat16"
    for key in ("conv_layer", "attention_layer", "experts", "layer_pattern",
                "tied_head", "weights", "segments"):
        assert len(cfg["assumed"][key]) > 80, key
    assert cfg["control"]["model_config"] == {"quant": "int8"}
    assert cfg["check"]["docs"] in (1, 2) and set(cfg["check"]["limits"]) <= set(
        cfg["check"]["why"])
    # The op takes every key of the model group.
    from agent_tpu.models.decoder_lm import (DecoderLMConfig, kinds_by_layer,
                                             validate)
    from agent_tpu.ops._model_common import cfg_key

    assert set(model) <= set(DecoderLMConfig.__dataclass_fields__)
    validate(DecoderLMConfig(**model))
    hash(cfg_key(DecoderLMConfig(**model)))
    assert kinds_by_layer(DecoderLMConfig(**model)) == (
        "conv", "full", "conv", "conv", "conv", "full", "conv", "conv", "conv")


def test_manifest_entries_of_the_cell(manifests):
    m = manifests
    cell = manifest.find_cell(m, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200
    cfg = manifest.load_config(m, cell["config"])
    assert cfg["needed_work"] == "conv_moe_flops"
    assert cfg["reference"] == "conv_moe_lm"
    traffic = manifest.load_traffic(cell["traffic"])
    assert traffic["kind"] == "score" and traffic["shard_rows"] == 1
    assert traffic["doc_tokens"] == {"dist": "fixed", "value": 32768}
    assert traffic["token_ids"] == {"dist": "zipf", "exponent": 1.1}
    assert (traffic["job_rows"], traffic["tenants"], traffic["order_seed"],
            traffic["lead_in_shards"], traffic["agent"]) == (8, 1, 0, 3, {})
    e2e = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "end_to_end")}
    assert e2e == {"drain_rows_per_s", "setup_s"}
    per_layer = {e["name"] for e in manifest.metrics_of_cell(m, CELL, "per_layer")}
    # Everything the score cells share.
    shared = {e["name"] for e in manifest.metrics_of_cell(
        m, "brumby-14b-base.score-long", "per_layer")} & {
        e["name"] for e in manifest.metrics_of_cell(
            m, "ling-3.0-flash-vl.score-32k-hybrid", "per_layer")}
    assert shared <= per_layer
    assert {"lm_roofline", "loss_head_roofline", "trace_lower_s.setup"} <= per_layer
    assert not {n for n in per_layer if n.startswith(
        ("retention_", "sparse_", "indexer_", "ssd_", "hybrid_", "latent_",
         "window_", "kda_", "ffn_"))}
    # The cell's own readers wait for a ``benchmark`` PR (this file's
    # docstring); appended, the manifest keeps the manifest's rules.
    names = {e["name"] for e in m["per_layer"]}
    mine = entries()
    assert not {e["name"] for e in mine} & names
    for e in mine:
        assert manifest.NAME.match(e["name"]) and manifest.UNIT.match(e["unit"])
        assert e["source"] in manifest.SOURCES
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", e["name"] + ".py"))
    assert {e["name"] for e in manifest.metrics_of_cell(
        with_entries(m), CELL, "per_layer")} == per_layer | {
        e["name"] for e in mine}
    assert CELL in [w["name"] for w in m["workloads"][8:]]
    assert CONFIG in [c["name"] for c in m["configs"][7:]]
