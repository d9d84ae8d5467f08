"""The ``window_gqa`` mixer (grouped-query attention whose layers come in two
kinds, window and full, mixed in periods; YaRN on the full layers only) over
softmax-routed expert layers with no shared expert, on the CPU at tiny
widths: the family through ``map_score_lm`` in several segment programs
against the benchmark's plain reference's one forward pass, at a window
shorter than a segment and at one that spans a program boundary; the window's
lower edge to the key; the rotary table by kind; a reference without the
window, the carried tail, YaRN or the gates' renormalisation; the shares of an
expert layer against the uncut layer; what the carried state holds; the window
kernel and the grouped matmul at width 896 in interpret mode against the same
arithmetic in ``jax.numpy``; what the op counts.

Tolerances, each with its reason:

- ``TOKEN_TOL`` 2e-5 nats a token (0.02 on a block sum of 1,024 tokens):
  ``dtype: float32`` here, so the op computes what the reference computes in
  another order (segments, a cache, a carried tail), and float32 reordering
  is all that may differ. A token whose 4th and 5th router scores lie closer
  than that reordering may choose the other expert: none does in these
  documents (the test would say so by tenths);
- the kernels: 2e-2 absolute on bf16 outputs of unit-variance values (bf16's
  own rounding of a weighted mean, as ``test_latent_mla.py``);
- the control: int8 must lie at least 1.5 x further from the reference than
  bf16 does: the check's limits sit between them."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import lm_once
import numpy as np
import pytest

from agent_tpu.kernels import causal_attention, grouped_ffn
from agent_tpu.models import decoder_lm, moe
from agent_tpu.obs.metrics import get_registry
from agent_tpu.ops import get_op
from agent_tpu.runtime.runtime import reset_runtime
from benchmarks.harness import manifest

ref = manifest.load_reference("window_moe_lm")

# Two periods of (window, full); YaRN is on (max_len past the original
# length); 16 experts, all held, 4 a token, none shared.
TINY = {"vocab_size": 3000, "d_model": 64, "n_heads": 8, "n_kv_heads": 2,
        "d_head": 16, "n_layers": 4, "max_len": 16384, "mixer": "window_gqa",
        "dtype": "float32", "rope_theta": 10000.0, "rope_factor": 8.0,
        "rope_original_max_len": 1500, "rope_beta_fast": 32.0,
        "rope_beta_slow": 1.0, "rope_mscale": 1.0, "sliding_window": 300,
        "full_attention_every": 2, "n_dense_layers": 0, "n_experts": 16,
        "n_experts_held": 16, "expert_first": 0, "n_experts_per_token": 4,
        "n_expert_groups": 1, "n_groups_per_token": 1, "d_expert": 32,
        "n_shared_experts": 0, "routed_scale": 1.0, "scoring_func": "softmax"}
TOKEN_TOL = 2e-5
BF16 = jnp.bfloat16
LONG = 4200             # 2,048 + 2,048 + 1,024 program tokens under BUCKETS
BUCKETS = (1024, 2048)  # the op's segment sizes, halved for the CPU
# A window inside a segment, and one LONGER than the last segment's bucket:
# its tail spans two program boundaries.
WINDOWS = {"inside_a_segment": 300, "across_a_boundary": 1500}


def _ref_cfg(window):
    return {**TINY, "sliding_window": window, "rms_norm_eps": 1e-6}


def _short_segments() -> pytest.MonkeyPatch:
    from agent_tpu.ops import map_score_lm

    mp = pytest.MonkeyPatch()
    mp.setattr(map_score_lm, "SEGMENT_BUCKETS", BUCKETS)
    return mp


# ---- (a) the family in segments against the reference's one pass ----------

@pytest.fixture(scope="module", params=list(WINDOWS))
def served(request):
    """One document of three segments and a short one through
    ``map_score_lm``: ``(window, documents, result, counters gained)``."""
    window = WINDOWS[request.param]
    reset_runtime()
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
            for n in (LONG, 37)]
    before = get_registry().snapshot()
    mp = _short_segments()
    try:
        out = get_op("map_score_lm")({
            "ids": [d.tolist() for d in docs],
            "model_config": {**TINY, "sliding_window": window},
            "model_path": "window-a"})
    finally:
        mp.undo()
    after = get_registry().snapshot()
    reset_runtime()
    assert out["ok"] is True, out
    return window, docs, out, (before, after)


def _gaps(result, logprobs):
    return [np.abs(np.asarray(blocks) - ref.block_sums(lp))
            for blocks, lp in zip(result["block_logprob_sums"], logprobs)]


def test_segments_with_a_carried_tail_match_one_forward_pass(served):
    window, docs, out, _ = served
    assert out["n_tokens"] == [LONG, 37]
    assert [len(b) for b in out["block_logprob_sums"]] == [5, 1]
    want = ref.token_logprobs(_ref_cfg(window), "window-a", docs)
    long_gap, short_gap = _gaps(out, want)
    assert short_gap.max() < TOKEN_TOL * 37
    assert long_gap.max() < TOKEN_TOL * 1024, long_gap


def _no_tail(cfg, kind, t, s):
    """A window layer that sees its own segment only (2,048-token segments)."""
    seen = s <= t
    if kind == "window":
        seen = seen & (s > t - int(cfg["sliding_window"])) & (
            s >= (t // BUCKETS[-1]) * BUCKETS[-1])
    return seen


def _gates_of_all(cfg, n, w_router):
    """The chosen experts' softmax scores as they are: not renormalised."""
    p = jax.nn.softmax(n @ w_router, axis=-1)
    picked, experts = jax.lax.top_k(p, int(cfg["n_experts_per_token"]))
    return experts, picked


@pytest.mark.parametrize("what", ["no window", "no carried tail", "no YaRN",
                                  "YaRN on the window layers too",
                                  "gates not renormalised"])
def test_a_reference_without_a_mechanism_misses_by_ten_tolerances(
        served, what, monkeypatch):
    """The check sees each mechanism: a reference that leaves it out is off by
    far more than the tolerance in every later block of the LONG document."""
    window, docs, out, _ = served
    cfg = _ref_cfg(window)
    if what == "no window":
        cfg = {**cfg, "sliding_window": 10 ** 6}
    elif what == "no carried tail":
        monkeypatch.setattr(ref, "visible", _no_tail)
    elif what == "no YaRN":
        cfg = {**cfg, "rope_factor": 1.0}
    elif what == "YaRN on the window layers too":
        monkeypatch.setattr(ref, "layer_kind", lambda cfg, layer: "full"
                            if (layer + 1) % 2 == 0 else "window-yarn")
        plain = ref.rotary
        monkeypatch.setattr(ref, "rotary", lambda cfg, kind: plain(
            cfg, "full" if kind == "window-yarn" else kind))
        monkeypatch.setattr(ref, "visible", lambda cfg, kind, t, s: (s <= t) & (
            (s > t - int(cfg["sliding_window"])) | (kind == "full")))
    else:
        monkeypatch.setattr(ref, "route", _gates_of_all)
    # A changed function is traced anew: the reference's jit cache is keyed by
    # the config alone.
    monkeypatch.setattr(ref._mla, "_JIT", {})
    other = ref.token_logprobs(cfg, "window-a", docs[:1])
    gap = _gaps(out, other)[0]
    counts = ref.block_counts(LONG)
    # The window and its tail matter past the first segment; YaRN's slow
    # pairs past the first thousand positions.
    later = slice(2, None) if what in ("no window", "no carried tail") else \
        slice(1, None)
    assert (gap[later] > 10 * TOKEN_TOL * counts[later]).all(), (what, gap)


def test_the_op_counts_pairs_by_kind_and_tiles(served):
    """``causal_attention_pairs_total`` for the full layers at the query tile
    four heads a key head take, ``window_attention_pairs_total`` for the
    window layers (``min(t + 1, window)`` a token beside the tiles the window
    kernel's grid visits), ``moe_tiles_total`` beside the pairs."""
    window, _, _, (before, after) = served

    def gained(name, **labels):
        def value(snap):
            return sum(s["value"] for s in snap.get(name, {}).get("series", [])
                       if all(s["labels"].get(k) == v for k, v in labels.items()))
        return value(after) - value(before)

    n = LONG
    assert gained("causal_attention_pairs_total", kind="causal") == (
        n * (n + 1) // 2 + 37 * 38 // 2)
    tile = causal_attention.query_tile(4, 2048)
    assert tile == 1024 and causal_attention.query_tile(8, 4096) == 512
    segments = [(2048, 0), (2048, 2048), (1024, 4096), (1024, 0)]
    assert gained("causal_attention_pairs_total", kind="computed") == sum(
        causal_attention.visited_pairs(s, p, causal_attention.query_tile(4, s))
        for s, p in segments)
    assert gained("window_attention_pairs_total", kind="window") == sum(
        min(t + 1, window) for t in range(n)) + 37 * 38 // 2
    assert gained("window_attention_pairs_total", kind="computed") == sum(
        causal_attention.window_visited_pairs(
            s, p, window, causal_attention.query_tile(4, s))
        for s, p in segments)
    assert gained("moe_tokens_total") == (5120 + 1024) * 4
    # Every expert is held: every choice is a pair, padding's too.
    pairs = gained("moe_expert_pairs_total")
    assert pairs == (5120 + 1024) * 4 * 4
    tiles = gained("moe_tiles_total")
    assert pairs / grouped_ffn.ROW_TILE <= tiles <= (
        pairs / grouped_ffn.ROW_TILE + 16 * 4 * 4)     # < a tile an expert a call
    # The rows the matmuls took: whole sub-blocks, under a sub-block an
    # expert a call over the pairs and never more than the tiles hold.
    rows = gained("moe_rows_computed_total")
    assert rows % grouped_ffn.SUB_ROWS == 0
    assert pairs <= rows <= min(tiles * grouped_ffn.ROW_TILE,
                                pairs + grouped_ffn.SUB_ROWS * 16 * 4 * 4)


# ---- (b) the window's edge, to the key ------------------------------------

@pytest.mark.parametrize("path", ["jax.numpy", "kernel"])
def test_the_windows_lower_edge_is_exact(path):
    """A key no query may miss: at ``t - window`` it is out, at ``t - window +
    1`` it is in. 512 queries at position 1,024 under a window of 512."""
    W, S, pos0, D = 512, 512, 1024, 128
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((1, 2, S, D)) * 0.1, BF16)
    k = np.asarray(rng.standard_normal((1, W + S, D)) * 0.1, np.float32)
    v = rng.standard_normal((1, W + S, D)).astype(np.float32)
    t = 300                                   # the query, in the segment
    loud = np.asarray(q[0, 0, t], np.float32) * 400.0        # score ~ +50
    opts = {"pallas": False} if path == "jax.numpy" else {
        "pallas": True, "interpret": True}
    seen = {}
    for name, at in (("out", W + t - W), ("in", W + t - W + 1)):
        keys = k.copy()
        keys[0, at] = loud
        o = causal_attention.window_attention(
            q, jnp.asarray(keys, BF16), jnp.asarray(v, BF16),
            jnp.int32(pos0), window=W, **opts)
        seen[name] = np.abs(np.asarray(o[0, 0, t], np.float32) - v[0, at]).max()
    assert seen["in"] < 5e-2            # the loud key takes all the weight
    assert seen["out"] > 0.5            # one key further back is not attended


def test_keys_before_the_document_are_never_attended():
    """A document's first segment: the carried tail is what lay before the
    document, whatever it holds; and the kernel agrees with the plain path at
    a tail that is partly real."""
    W, S, D = 512, 512, 128
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, 4, S, D)) * 0.3, BF16)
    k = jnp.asarray(rng.standard_normal((2, W + S, D)), BF16)
    v = jnp.asarray(rng.standard_normal((2, W + S, D)), BF16)
    for pos0 in (0, 200, 4096):
        plain = causal_attention.window_attention(
            q, k, v, jnp.int32(pos0), window=W, pallas=False)
        kernel = causal_attention.window_attention(
            q, k, v, jnp.int32(pos0), window=W, pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(kernel, np.float32),
                                   np.asarray(plain, np.float32), atol=2e-2)
        if pos0 < W:
            junk = k.at[:, :W - pos0].set(50.0)
            again = causal_attention.window_attention(
                q, junk, v, jnp.int32(pos0), window=W, pallas=False)
            np.testing.assert_array_equal(np.asarray(again), np.asarray(plain))
    assert causal_attention.window_supported(4096, 1024, 128, BF16)
    assert not causal_attention.window_supported(4096, 1000, 128, BF16)
    assert not causal_attention.window_supported(4096, 1024, 128, jnp.float32)
    # Three 512-key tiles a 512-query tile; the first segment's tiles before
    # the document are left out.
    assert causal_attention.window_visited_pairs(4096, 4096, 1024) == (
        4096 * 1536)
    assert causal_attention.window_visited_pairs(4096, 0, 1024) == (
        4096 * 1536 - 512 * 512 * 3)


# ---- (c) the rotary table by kind -----------------------------------------

def test_yarn_is_on_the_full_layers_only():
    published = manifest.load_config(manifest.load_manifest(),
                                     "mellum2-12b-a2.5b")
    cfg = decoder_lm.DecoderLMConfig(**published["model"])
    assert decoder_lm.layer_kinds(cfg) == ("window", "window", "window", "full")
    plain, one = decoder_lm.kind_rotary(cfg, "window")
    np.testing.assert_allclose(
        plain, 500000.0 ** (-np.arange(0, 128, 2) / 128.0), rtol=1e-6)
    assert one == 1.0
    yarn, factor = decoder_lm.kind_rotary(cfg, "full")
    rope = published["rope_parameters"]["full_attention"]
    assert factor == pytest.approx(rope["attention_factor"], rel=1e-12)
    assert factor == pytest.approx(0.1 * np.log(16.0) + 1.0)
    # Fast pairs untouched, slow pairs divided by the factor, a ramp between.
    np.testing.assert_allclose(yarn[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(yarn[-8:], plain[-8:] / 16.0, rtol=1e-6)
    assert ((yarn <= plain * (1 + 1e-6)) & (yarn >= plain / 16.0 * (1 - 1e-6))).all()
    # The reference's own statement of both.
    for kind in ("window", "full"):
        inv, m = ref.rotary(published["model"], kind)
        np.testing.assert_allclose(inv, decoder_lm.kind_rotary(cfg, kind)[0],
                                   rtol=1e-6)
        assert m == pytest.approx(decoder_lm.kind_rotary(cfg, kind)[1])
    # The other mixers' table is what it was.
    latent = decoder_lm.DecoderLMConfig(mixer="dense_mla", rope_factor=8.0,
                                        rope_original_max_len=1500)
    assert decoder_lm.yarn_inv_freq(latent).shape == (4,)


# ---- (d) an expert layer and its shares -----------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips of sixteen experts each, and one that holds all sixty-four:
    the routed parts of the shares ARE the uncut layer (no shared expert to
    count once); an expert's weights are the same wherever it is held; the
    uncut layer is the reference's; the tiles of the shares cover the uncut
    layer's."""
    wide = {**TINY, "n_experts": 64, "n_experts_per_token": 8}
    whole = decoder_lm.DecoderLMConfig(**{**wide, "n_experts_held": 64})
    n = jax.random.normal(jax.random.PRNGKey(5), (1, 300, 64), jnp.float32)
    layer = lambda cfg: lm_once.first_layer(  # noqa: E731
        lm_once.params(cfg, "window-c")["expert_layers"])
    p_whole = layer(whole)
    assert not {"router_bias", "ws_gate", "ws_up", "ws_down"} & set(p_whole)
    y_whole, counted = lm_once.experts_program(whole)(p_whole, n)
    assert set(counted) == {"pairs", "tiles"}
    down_whole = np.asarray(p_whole["we_down"])
    assert set(counted["tiles"]) == {"visited", "rows"}
    total, pairs, tiles, rows = 0.0, 0.0, 0.0, 0.0
    for first in (0, 16, 32, 48):
        cfg = decoder_lm.DecoderLMConfig(**{**wide, "n_experts_held": 16,
                                            "expert_first": first})
        p = layer(cfg)
        np.testing.assert_array_equal(np.asarray(p["we_down"]),
                                      down_whole[first:first + 16])
        y, held = lm_once.experts_program(cfg)(p, n)
        total = total + y
        pairs += float(held["pairs"])
        tiles += float(held["tiles"]["visited"])
        rows += float(held["tiles"]["rows"])
    assert pairs == float(counted["pairs"]) == 300 * 8    # every choice, once
    assert tiles == float(counted["tiles"]["visited"]) <= 64
    assert rows == float(counted["tiles"]["rows"]) <= 64 * 128  # a sub-block
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_whole),
                               atol=1e-5)
    u = n[0] * 3.0
    cfg64 = {**_ref_cfg(300), "n_experts": 64, "n_experts_per_token": 8,
             "n_experts_held": 64}
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer_ffn(cfg64, "window-c", 0, u)
        normed = ref.rms_norm(u, 1e-6)
        parts = sum(ref.routed_experts(
            {**cfg64, "n_experts_held": 16, "expert_first": first},
            "window-c", 0, normed) for first in (0, 16, 32, 48))
    np.testing.assert_allclose(np.asarray(u + parts), np.asarray(want),
                               atol=1e-5)
    got = lm_once.expert_layer_program(whole)(p_whole, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_held_tiles_counts_whole_tiles_an_expert():
    experts = jnp.asarray([[0, 1]] * 300 + [[2, 5]] * 3, jnp.int32)
    # Experts 0 and 1: 300 rows = 2 tiles each; 2: 3 rows = 1; 3: none; 5 is
    # held elsewhere.
    assert int(moe.held_work(experts, 0, 4)["visited"]) == 5
    assert int(moe.held_work(experts, 4, 4)["visited"]) == 1


@pytest.mark.parametrize("held, tiles, computed", [
    (0, 0, 0), (1, 1, 128), (59, 1, 128), (128, 1, 128), (129, 1, 256),
    (130, 1, 256), (256, 1, 256), (257, 2, 384), (512, 2, 512),
    (540, 3, 640)])
def test_held_work_counts_the_rows_the_matmuls_take(held, tiles, computed):
    """``moe_rows_computed_total``'s arithmetic: an expert's rows rounded up
    to whole ``SUB_ROWS`` sub-blocks (a tile's real rows come first in it, and
    the kernel computes the sub-blocks that hold one), beside the whole tiles
    they are padded to; an expert held elsewhere counts nothing."""
    assert (grouped_ffn.ROW_TILE, grouped_ffn.SUB_ROWS) == (256, 128)
    experts = jnp.asarray([[1, 6]] * held + [[2, 7]] * 3, jnp.int32)
    work = moe.held_work(experts, 0, 4)       # expert 2: 3 rows, a sub-block
    assert int(work["visited"]) == tiles + 1
    assert int(work["rows"]) == computed + 128


# ---- (e) what the carried state holds -------------------------------------

def test_the_carried_state_has_two_shapes_side_by_side():
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    params = lm_once.params(cfg, "window-b")
    assert set(params) == {"embed", "head", "final_norm", "expert_layers"}
    assert set(params["expert_layers"]) == {
        "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln1", "ln2", "w_router",
        "we_gate", "we_up", "we_down"}
    assert params["expert_layers"]["wq"].shape == (4, 64, 128)
    np.testing.assert_array_equal(
        np.asarray(params["expert_layers"]["q_norm"]),
        np.full((4, 16), decoder_lm.QUERY_NORM_GAIN, np.float32))
    ids = np.random.default_rng(3).integers(0, 3000, (1, 512)).astype(np.int32)
    state = lm_once.state(cfg, 1, 1024)
    assert set(state) == {"mixer", "pairs", "tiles"}
    assert set(state["mixer"]) == {"window", "full"}
    step = lm_once.segment_program(cfg)
    for pos0 in (0, 512):
        hidden, state = step(params, ids, jnp.int32(pos0), state)
        # Two window layers keep their last 300 keys, whatever the document's
        # length; two full layers keep every key.
        for leaf in ("k", "v"):
            assert state["mixer"]["window"][leaf].shape == (2, 1, 2, 300, 16)
            assert state["mixer"]["full"][leaf].shape == (2, 1, 2, 1024, 16)
    assert hidden.shape == (1, 512, 64)
    assert (np.asarray(state["mixer"]["full"]["k"]) != 0).any(axis=-1).all()
    assert float(state["pairs"]) == 1024 * 4 * 4 and float(
        state["tiles"]["visited"]) > 0
    with pytest.raises(ValueError, match="init_state"):
        decoder_lm.forward_segment(params, jnp.asarray(ids), jnp.int32(0),
                                   None, cfg)
    assert not decoder_lm.starts_from_nothing(cfg)


def _sliced_out_and_stacked(params, ids, pos0, state, cfg):
    """``forward_segment`` of a model by kind as the parent of PR 46 ran its
    caches: layer after layer, a full layer's cache SLICED out of its kind's
    stack (and handed to the mixer as a stack of that one layer), a window
    layer's tail beside it, the results stacked again a kind."""
    kinds = decoder_lm.layer_kinds(cfg)
    (group, ffn, _, n_layers), = cfg.layer_groups
    x = decoder_lm._times(params["embed"][ids], cfg.embedding_multiplier,
                          cfg.compute_dtype)
    positions = pos0 + jnp.arange(ids.shape[1])
    counted = {k: v for k, v in state.items() if k != "mixer"}
    new = {kind: [] for kind in set(kinds)}
    for layer in range(n_layers):
        kind = kinds[layer % len(kinds)]
        p = jax.tree_util.tree_map(lambda a, at=layer: a[at], params[group])
        mine = jax.tree_util.tree_map(lambda a, at=len(new[kind]): a[at],
                                      state["mixer"][kind])
        if kind == "full":
            mine = {"k": mine["k"][None], "v": mine["v"][None],
                    "layer": jnp.int32(0)}
        x, mine, more = decoder_lm._layer(p, x, positions, mine, cfg, {}, ffn,
                                          kind)
        counted = jax.tree_util.tree_map(jnp.add, counted, more)
        new[kind].append(jax.tree_util.tree_map(lambda a: a[0], mine)
                         if kind == "full" else mine)
    hidden = decoder_lm._times(
        decoder_lm.rms_norm(x, params["final_norm"], cfg.rms_norm_eps),
        cfg.lm_head_multiplier, cfg.compute_dtype)
    return hidden, {"mixer": {kind: jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *sts) for kind, sts in new.items()},
        **counted}


def test_carried_caches_answer_as_the_parents_form_bit_for_bit():
    """Two periods, three segments of one document: the full layers' caches
    are the period scan's CARRY, written at ``(layer, 0, 0, pos0, 0)`` and
    attended through the layer's number; hidden states and every leaf of the
    state EQUAL the parent's form's, in which a layer's cache was sliced out
    of its stack and stacked again. Operation by operation: what XLA fuses
    on this host, and so how it orders a float32 sum, follows the program
    around the arithmetic."""
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    params = lm_once.params(cfg, "window-b")
    ids = np.random.default_rng(11).integers(0, 3000, (1, 768)).astype(np.int32)
    mine = theirs = lm_once.state(cfg, 1, 768)
    for pos0 in (0, 256, 512):
        segment, at = ids[:, pos0:pos0 + 256], jnp.int32(pos0)
        with jax.disable_jit():
            hidden, mine = decoder_lm.forward_segment(params, segment, at,
                                                      mine, cfg)
            want, theirs = _sliced_out_and_stacked(params, segment, at,
                                                   theirs, cfg)
        np.testing.assert_array_equal(np.asarray(hidden), np.asarray(want))
        got, held = (jax.tree_util.tree_leaves_with_path(t)
                     for t in (mine, theirs))
        assert [path for path, _ in got] == [path for path, _ in held]
        for (path, a), (_, b) in zip(got, held):
            assert a.shape == b.shape, path
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=str(path))
    assert mine["mixer"]["full"]["k"].shape == (2, 1, 2, 768, 16)
    assert (np.asarray(mine["mixer"]["full"]["k"]) != 0).any(axis=-1).all()


def test_the_tables_have_the_fifth_mixer():
    assert set(decoder_lm.MIXERS) == set(decoder_lm.MIXER_LEAVES) == set(
        decoder_lm.MIXER_FLOPS) >= {"window_gqa"}
    assert "window_gqa" in decoder_lm.MIXER_STATES
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    assert decoder_lm.layer_kinds(cfg) == ("window", "full")
    assert decoder_lm.layer_kinds(decoder_lm.DecoderLMConfig()) == ()
    # What the program does of a segment, by kind: a full layer every causal
    # pair, a window layer the window's.
    flops = decoder_lm.segment_flops(cfg, 2048, 2048)
    pair = 4.0 * 8 * 16
    mixers = 2048 * 2 * pair * ((2048 + 1024) + 300)
    dense = decoder_lm.segment_flops(
        decoder_lm.DecoderLMConfig(**{**TINY, "sliding_window": 10 ** 6}),
        2048, 2048)
    assert dense - flops == pytest.approx(2048 * 2 * pair * (2048 + 1024.5 - 300))
    assert flops > mixers


# ---- (f) the grouped matmul at a width of seven lane tiles -----------------

def test_the_grouped_matmul_walks_a_width_of_896_in_one_step():
    assert grouped_ffn.width_step(2048) == grouped_ffn.WIDTH_TILE == 256
    assert grouped_ffn.width_step(896) == 896
    assert grouped_ffn.width_step(900) == 0 == grouped_ffn.width_step(1152)
    assert grouped_ffn.pallas_supported(2304, 896, BF16)
    assert grouped_ffn.pallas_supported(4096, 2048, BF16)
    assert not grouped_ffn.pallas_supported(2304, 900, BF16)
    assert not grouped_ffn.pallas_supported(2304, 896, jnp.float32)
    S, d, fe, E, k = 192, 256, 896, 6, 3
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    x = jax.random.normal(ks[0], (S, d)).astype(BF16)
    experts, gates = moe.route_softmax(
        jax.random.normal(ks[1], (S, 8), jnp.float32), top_k=k, scale=1.0)
    w = [(jax.random.normal(key, shape) * shape[1] ** -0.5).astype(BF16)
         for key, shape in zip(ks[2:5], [(E, d, fe), (E, d, fe), (E, fe, d)])]
    got, pairs = moe.held_experts_ffn(x, experts, gates, *w, 0, pallas=True,
                                      interpret=True)
    local = jnp.where(experts < E, experts, -1)
    want = moe._held_dense(x, local, gates, *w)
    assert int(pairs) == int((experts < E).sum())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)


@pytest.mark.parametrize("n", [0, 1, 2, 3, 127, 128, 255, 256])
def test_a_tiles_landed_rows_are_waited_for_by_size(n):
    """``n`` landed rows are waited for as descriptors of ``2^b`` rows, one a
    set bit of ``n``: the sizes add up to ``n``, nine at most at a tile of
    256, ONE where the tile is full; a traced ``n`` takes the same ones."""
    table = grouped_ffn.wait_sizes(n, grouped_ffn.ROW_TILE)
    assert [rows for rows, _ in table] == [1, 2, 4, 8, 16, 32, 64, 128, 256]
    sizes = [rows for rows, taken in table if taken]
    assert sum(sizes) == n and len(sizes) <= 9
    assert len(sizes) == bin(n).count("1")
    if n == grouped_ffn.ROW_TILE:
        assert sizes == [grouped_ffn.ROW_TILE]
    traced = jax.jit(lambda m: jnp.stack([taken for _, taken in
                                          grouped_ffn.wait_sizes(m, 256)]))
    assert [int(t) for t in traced(jnp.int32(n))] == [t for _, t in table]


# ---- (g) the lower precision, and what no program can run ------------------

def test_bf16_is_near_the_reference_and_the_int8_control_further_off():
    rng = np.random.default_rng(1)
    doc = rng.integers(0, TINY["vocab_size"], 2500).astype(np.int32)
    want = ref.block_sums(ref.token_logprobs(
        {**_ref_cfg(300), "dtype": "bfloat16"}, "window-q", [doc])[0])
    gap = {}
    mp = _short_segments()
    try:
        for name, over in (("bf16", {}), ("int8", {"quant": "int8"})):
            reset_runtime()
            out = get_op("map_score_lm")({
                "ids": [doc.tolist()],
                "model_config": {**TINY, "dtype": "bfloat16", **over},
                "model_path": "window-q"})
            assert out["ok"] is True, out
            gap[name] = np.abs(np.asarray(out["block_logprob_sums"][0]) - want
                               ) / ref.block_counts(2500)
    finally:
        mp.undo()
        reset_runtime()
    assert gap["bf16"].max() < 0.05, gap
    assert np.sqrt((gap["int8"] ** 2).mean()) > 1.5 * np.sqrt(
        (gap["bf16"] ** 2).mean()), gap


@pytest.mark.parametrize("over, message", [
    ({"n_layers": 3}, "whole periods"),
    ({"sliding_window": 0}, "sliding_window"),
    ({"full_attention_every": 0}, "full_attention_every"),
    ({"n_dense_layers": 2}, "one group"),
    ({"n_kv_heads": 3}, "multiple of n_kv_heads"),
    ({"d_head": 15}, "d_head must be even"),
    ({"n_shared_experts": -1}, "n_shared_experts"),
])
def test_validate_rejects_what_no_program_can_run(over, message):
    with pytest.raises(ValueError, match=message):
        decoder_lm.validate(decoder_lm.DecoderLMConfig(**{**TINY, **over}))


def test_a_model_by_kind_runs_with_a_dense_ffn_too():
    """No expert layer: the state is the mixer's alone, nothing is counted."""
    cfg = decoder_lm.DecoderLMConfig(**{**TINY, "n_experts": 0, "d_ff": 96})
    decoder_lm.validate(cfg)
    params = lm_once.params(cfg, "window-d")
    state = lm_once.state(cfg, 1, 512)
    assert set(state) == {"window", "full"}
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 3000, (1, 512)),
                      jnp.int32)
    hidden, state = lm_once.segment_program(cfg)(params, ids, jnp.int32(0),
                                                 state)
    assert hidden.shape == (1, 512, 64) and set(state) == {"window", "full"}


_LOWER = """
import hashlib, json, sys
import jax, jax.numpy as jnp
from agent_tpu.models import decoder_lm
cfg = decoder_lm.DecoderLMConfig(**json.loads(sys.argv[1]))
params = jax.eval_shape(lambda: decoder_lm.init_params(cfg, "x"))
state = jax.eval_shape(lambda: decoder_lm.init_state(cfg, 1, 2048))
ids = jax.ShapeDtypeStruct((1, 1024), jnp.int32)
pos = jax.ShapeDtypeStruct((), jnp.int32)
text = jax.jit(lambda p, i, a, s: decoder_lm.forward_segment(
    p, i, a, s, cfg)).lower(params, ids, pos, state).as_text()
print(hashlib.sha256(text.encode()).hexdigest())
"""


def test_the_program_has_one_text_whatever_the_process_hashes_to():
    """The kinds are walked in ONE order: a set of their names iterates by
    the process's string hashes, and two orders of the same reshapes are two
    program texts, two entries of the compile cache and a compile in every
    other run's set-up (on the chip 37 s of ``setup_s`` against 19; seeds 1
    and 2 lowered apart before the order was fixed)."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digests = set()
    for seed in ("1", "2"):
        env = {**os.environ, "PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": root}
        done = subprocess.run([sys.executable, "-c", _LOWER, json.dumps(TINY)],
                              env=env, capture_output=True, text=True,
                              timeout=300, cwd=root)
        assert done.returncode == 0, done.stderr[-2000:]
        digests.add(done.stdout.strip().splitlines()[-1])
    assert len(digests) == 1
