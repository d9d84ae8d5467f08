"""The ``dense_mla`` mixer (dense latent attention: every causal key, a query
scaled by its own position, a cache of latents only) and a softmax-routed
expert layer of which a share is held, on the CPU at tiny widths: the family
through ``map_score_lm`` in several segment programs against the benchmark's
plain reference's one forward pass; the shares of an expert layer against the
uncut layer; what the carried state holds; the two kernels the mixer runs on
the chip (the latents' expansion at a 64 + 64 head split, causal attention at
one query head a key head) in interpret mode against the same arithmetic in
``jax.numpy``; the router; the int8 control; and that the three mixers that
were there lower to the programs they were.

Tolerances, each with its reason:

- ``TOKEN_TOL`` 2e-5 nats a token (0.02 on a block sum of 1,024 tokens):
  ``dtype: float32`` here, so the op computes what the reference computes in
  another order (segments, a cache, expanded keys joined through an identity
  block), and float32 reordering is all that may differ. A token whose 4th
  and 5th router scores lie closer than that reordering may choose the other
  expert: none does in these documents (the test would say so by tenths);
- the kernels: 2e-2 absolute on bf16 outputs of unit-variance values (bf16's
  own rounding of a weighted mean, as ``test_sparse_mla.py``); the expansion
  is exact but for the order of a float32 sum, 1 bf16 ulp;
- the control: int8 must lie at least 1.5 x further from the reference than
  bf16 does (it reads 3 x and more): the check's limits sit between them."""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import lm_once
import numpy as np
import pytest

from agent_tpu.kernels import causal_attention, sparse_mla
from agent_tpu.models import decoder_lm, moe
from agent_tpu.obs.metrics import get_registry
from agent_tpu.ops import get_op
from agent_tpu.runtime.runtime import reset_runtime
from benchmarks.harness import manifest

ref = manifest.load_reference("latent_moe_lm")

# YaRN is on (max_len past the original length) and the original length is
# tiny: a(t) steps at 1,500, 3,000 and 4,500, INSIDE the segments below.
TINY = {"vocab_size": 3000, "d_model": 64, "n_heads": 4, "d_ff": 96,
        "n_layers": 2, "max_len": 16384, "mixer": "dense_mla",
        "dtype": "float32", "q_lora_rank": 48, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_theta": 10000.0, "rope_factor": 8.0,
        "rope_original_max_len": 1500, "query_scale_beta": 0.1,
        "n_dense_layers": 0, "n_experts": 16, "n_experts_held": 4,
        "expert_first": 0, "n_experts_per_token": 4, "n_expert_groups": 1,
        "n_groups_per_token": 1, "d_expert": 32, "n_shared_experts": 1,
        "routed_scale": 1.0, "scoring_func": "softmax"}
REF_CFG = {**TINY, "rms_norm_eps": 1e-6}
TOKEN_TOL = 2e-5
BF16 = jnp.bfloat16
LONG = 4200             # 2,048 + 2,048 + 1,024 program tokens under BUCKETS
BUCKETS = (1024, 2048)  # the op's segment sizes, halved for the CPU


def _short_segments() -> pytest.MonkeyPatch:
    from agent_tpu.ops import map_score_lm

    mp = pytest.MonkeyPatch()
    mp.setattr(map_score_lm, "SEGMENT_BUCKETS", BUCKETS)
    return mp


# ---- (a) the family in segments against the reference's one pass ----------

@pytest.fixture(scope="module")
def served():
    """One document of three segments and a short one through
    ``map_score_lm``: ``(documents, result, counters gained)``."""
    reset_runtime()
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
            for n in (LONG, 37)]
    before = get_registry().snapshot()
    mp = _short_segments()
    try:
        out = get_op("map_score_lm")({
            "ids": [d.tolist() for d in docs], "model_config": TINY,
            "model_path": "latent-a"})
    finally:
        mp.undo()
    after = get_registry().snapshot()
    reset_runtime()
    assert out["ok"] is True, out
    return docs, out, (before, after)


def _gaps(result, logprobs):
    return [np.abs(np.asarray(blocks) - ref.block_sums(lp))
            for blocks, lp in zip(result["block_logprob_sums"], logprobs)]


def test_segments_across_a_step_of_the_query_scale_match_one_forward_pass(
        served):
    docs, out, _ = served
    assert out["n_tokens"] == [LONG, 37]
    assert [len(b) for b in out["block_logprob_sums"]] == [5, 1]
    # The scale steps inside a segment, and YaRN is on.
    assert 0 < 1500 < 2048 < 3000 < 4096
    assert ref.softmax_scale(REF_CFG) > 24 ** -0.5
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    a = np.asarray(decoder_lm.query_position_scale(
        cfg, jnp.asarray([0, 1499, 1500, 2999, 3000, 4199])))[:, 0]
    np.testing.assert_allclose(a, 1 + 0.1 * np.log([1, 1, 2, 2, 3, 3]),
                               rtol=1e-6)
    want = ref.token_logprobs(REF_CFG, "latent-a", docs)
    long_gap, short_gap = _gaps(out, want)
    assert short_gap.max() < TOKEN_TOL * 37
    assert long_gap.max() < TOKEN_TOL * 1024, long_gap


@pytest.mark.parametrize("over, what", [
    ({"query_scale_beta": 0.0}, "no scale by position"),
    ({"rope_factor": 1.0}, "no YaRN: plain frequencies, no mscale"),
    ({"routed_scale": 0.5}, "the routed experts at half their weight"),
])
def test_a_reference_of_another_model_misses_by_ten_tolerances(served, over,
                                                               what):
    """The check sees each mechanism: without it the LONG document's blocks
    past the mechanism's onset are off by far more than the tolerance."""
    docs, out, _ = served
    other = ref.token_logprobs({**REF_CFG, **over}, "latent-a", docs[:1])
    gap = _gaps(out, other)[0]
    counts = ref.block_counts(LONG)
    assert (gap[2:] > 10 * TOKEN_TOL * counts[2:]).all(), (what, gap)


def test_the_op_counts_pairs_and_expansions_from_the_segments(served):
    """``causal_attention_pairs_total`` as the hybrid mixer ticks it, at the
    query tile one head a key head takes; ``latent_keys_expanded_total``:
    every segment expands all it can see."""
    _, _, (before, after) = served

    def gained(name, **labels):
        def value(snap):
            return sum(s["value"] for s in snap.get(name, {}).get("series", [])
                       if all(s["labels"].get(k) == v for k, v in labels.items()))
        return value(after) - value(before)

    n = LONG
    assert gained("latent_keys_expanded_total", kind="cached") == n + 37
    assert gained("latent_keys_expanded_total", kind="expanded") == (
        2048 + 4096 + 5120) + 1024
    assert gained("causal_attention_pairs_total", kind="causal") == (
        n * (n + 1) // 2 + 37 * 38 // 2)
    tile = causal_attention.query_tile(1, 2048)
    assert tile == 2048 and causal_attention.query_tile(5, 4096) == 512
    assert causal_attention.query_tile(1, 1024) == 1024
    assert causal_attention.query_tile(1, 4096) == 4096
    assert causal_attention.query_tile(1, 8192) == 4096
    assert gained("causal_attention_pairs_total", kind="computed") == (
        causal_attention.visited_pairs(2048, 0, 2048)
        + causal_attention.visited_pairs(2048, 2048, 2048)
        + causal_attention.visited_pairs(1024, 4096, 1024)
        + causal_attention.visited_pairs(1024, 0, 1024))
    assert gained("moe_tokens_total") == (5120 + 1024) * 2
    assert 0 < gained("moe_expert_pairs_total") <= (5120 + 1024) * 2 * 4


# ---- (b) an expert layer and its shares -----------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips of four experts each, and one that holds all sixteen: the
    routed parts of the shares, with the shared expert counted once, are the
    uncut layer; an expert's weights are the same wherever it is held; the
    uncut layer is the reference's."""
    whole = decoder_lm.DecoderLMConfig(**{**TINY, "n_experts_held": 16})
    n = jax.random.normal(jax.random.PRNGKey(5), (1, 300, 64), jnp.float32)
    layer = lambda cfg: lm_once.first_layer(  # noqa: E731
        lm_once.params(cfg, "latent-c")["expert_layers"])
    p_whole = layer(whole)
    assert "router_bias" not in p_whole          # a softmax router has none
    y_whole, pairs_whole = lm_once.experts_program(whole)(p_whole, n)
    shared = lm_once.shared_expert(p_whole, n)
    down_whole = np.asarray(p_whole["we_down"])
    total, pairs = shared, 0.0
    for first in (0, 4, 8, 12):
        cfg = decoder_lm.DecoderLMConfig(**{**TINY, "expert_first": first})
        p = layer(cfg)
        np.testing.assert_array_equal(np.asarray(p["we_down"]),
                                      down_whole[first:first + 4])
        y, held_pairs = lm_once.experts_program(cfg)(p, n)
        total = total + (y - shared)
        pairs += float(held_pairs)
    assert pairs == float(pairs_whole) == 300 * 4     # every choice, once
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_whole),
                               atol=1e-5)
    # The reference's layer, uncut (it adds the residual; n is normed there),
    # and its own four shares. The reference stays as it is written: its
    # parts are jitted inside, an expert's rows are found on the host.
    u = n[0] * 3.0
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer_ffn({**REF_CFG, "n_experts_held": 16},
                                    "latent-c", 0, u)
        normed = ref.rms_norm(u, 1e-6)
        parts = sum(ref.routed_experts({**REF_CFG, "expert_first": first},
                                       "latent-c", 0, normed)
                    for first in (0, 4, 8, 12))
        once = ref.shared_expert(REF_CFG, "latent-c", 0, normed)
    np.testing.assert_allclose(np.asarray(u + once + parts), np.asarray(want),
                               atol=1e-5)
    got = lm_once.expert_layer_program(whole)(p_whole, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---- (c) what the carried state holds -------------------------------------

def test_the_carried_state_holds_latents_and_nothing_expanded():
    cfg = decoder_lm.DecoderLMConfig(**TINY)
    params = lm_once.params(cfg, "latent-c")
    assert set(params) == {"embed", "head", "final_norm", "expert_layers"}
    assert set(params["expert_layers"]) == {
        "wo", "w_dq", "w_uq", "w_dkv", "w_ukv", "q_norm", "kv_norm", "ln1",
        "ln2", "w_router", "ws_gate", "ws_up", "ws_down", "we_gate", "we_up",
        "we_down"}
    ids = np.random.default_rng(3).integers(0, 3000, (1, 256)).astype(np.int32)
    state = lm_once.state(cfg, 1, 512)
    assert set(state) == {"mixer", "pairs"} and set(state["mixer"]) == {"kv"}
    step = lm_once.segment_program(cfg)
    for pos0 in (0, 256):
        hidden, state = step(params, ids, jnp.int32(pos0), state)
        assert set(state["mixer"]) == {"kv"}
        # [layers, 1, Lk, kv_lora_rank + rope]: 40 numbers a token a layer.
        assert state["mixer"]["kv"].shape == (2, 1, 512, 32 + 8)
    assert hidden.shape == (1, 256, 64)
    kv = np.asarray(state["mixer"]["kv"])
    assert (kv[:, 0, :512] != 0).any(axis=-1).all()       # both segments wrote
    assert 0 < float(state["pairs"]) <= 512 * 2 * 4


# ---- (d) the kernels in interpret mode against the plain arithmetic -------

def test_the_expansion_at_a_64_64_split_joins_the_rotary_key():
    """The cache's whole vectors [c 256 | kR 64] under the joined weight: a
    head's key is ``[c W_UK | kR]`` (the rotary key exact), its value ``c
    W_UV``; tiles past the segment's last key are not written."""
    H, kvr, dn, dr, dv, Lk = 8, 256, 64, 64, 128, 2048
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    latents = jax.random.normal(ks[0], (Lk, kvr + dr), BF16)
    w = (jax.random.normal(ks[1], (H, kvr, dn + dv)) / 16.0).astype(BF16)
    joined = sparse_mla.join_rotary_key(w, dn, dr)
    assert joined.shape == (H, kvr + dr, dn + dr + dv)
    assert sparse_mla.expand_supported(Lk, kvr + dr, H, dn + dr, dv, BF16)
    # Longer than the indexer can hold: still the kernel's.
    assert sparse_mla.expand_supported(65536, kvr + dr, 32, 128, 128, BF16)
    assert not sparse_mla.expand_supported(65536, kvr + dr, 32, 64, 128, BF16)
    n_keys = jnp.int32(1024 + 5)
    k, v = sparse_mla.expand_latents(latents, joined, n_keys, dn + dr,
                                     pallas=True, interpret=True)
    k0, v0 = sparse_mla.expand_latents(latents, joined, n_keys, dn + dr,
                                       pallas=False)
    assert k.shape == (H, Lk, 128) and v.shape == (H, Lk, 128)
    f32 = np.float32
    # Rounded once from a float32 sum in another order: an ulp of bf16 on a
    # few values in a million; the carried rotary key is exact in both.
    seen = 2048                       # both tiles hold keys below n_keys
    np.testing.assert_allclose(np.asarray(k, f32)[:, :seen],
                               np.asarray(k0, f32)[:, :seen], atol=2e-2)
    np.testing.assert_allclose(np.asarray(v, f32)[:, :seen],
                               np.asarray(v0, f32)[:, :seen], atol=2e-2)
    np.testing.assert_array_equal(np.asarray(k0[:, :, dn:], f32),
                                  np.asarray(k[:, :, dn:], f32))
    want = np.einsum("sc,hcd->hsd", np.asarray(latents[:, :kvr], f32),
                     np.asarray(w, f32))
    np.testing.assert_array_equal(
        np.asarray(k[:, :, dn:], f32),
        np.broadcast_to(np.asarray(latents[:, kvr:], f32), (H, Lk, dr)))
    np.testing.assert_allclose(np.asarray(k[:, :, :dn], f32), want[..., :dn],
                               atol=2e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(v, f32), want[..., dn:],
                               atol=2e-2, rtol=1e-2)


# Queries, pos0, cache keys: a document's first, a middle and its last
# segment, at the query tile one head a key head takes (the whole segment
# where it is whole tiles of the double: 4,096, 2,048, 1,024, 512).
ATTENTION_CASES = {
    "a_whole_4096_token_segment": (4096, 4096, 8192),
    "first_segment": (2048, 0, 4096),
    "middle_segment": (1024, 1024, 4096),
    "last_segment": (2048, 2048, 4096),
    "one_tile_of_512": (512, 1536, 2560),
}


@pytest.mark.parametrize("case", list(ATTENTION_CASES))
def test_causal_attention_at_one_query_head_a_key_head(case):
    S, pos0, Lk = ATTENTION_CASES[case]
    H, D = 2, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (H, 1, S, D), BF16) * 0.1
    k = jax.random.normal(ks[1], (H, Lk, D), BF16)
    v = jax.random.normal(ks[2], (H, Lk, D), BF16)
    # Keys past the segment's last hold NaN: never read.
    seen = (np.arange(Lk) < pos0 + S)[None, :, None]
    k, v = jnp.where(seen, k, jnp.nan), jnp.where(seen, v, jnp.nan)
    assert causal_attention.pallas_supported(S, Lk, D, BF16)
    got = causal_attention.causal_attention(q, k, v, jnp.int32(pos0),
                                            pallas=True, interpret=True)
    want = causal_attention.causal_attention(q, k, v, jnp.int32(pos0),
                                             pallas=False)
    assert got.shape == (H, 1, S, D) and np.isfinite(
        np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_the_mixer_on_its_kernels_equals_the_plain_path():
    """One layer's mixer at the published head split (64 + 64 / 128, rank 256)
    on both kernels in interpret mode, a second segment of a 2,048-key
    cache, against the ``jax.numpy`` path: bf16 rounding apart."""
    cfg = decoder_lm.DecoderLMConfig(**{
        **TINY, "dtype": "bfloat16", "d_model": 128, "n_heads": 8,
        "q_lora_rank": 64, "kv_lora_rank": 256, "qk_nope_head_dim": 64,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "n_layers": 1})
    p = lm_once.first_layer(lm_once.params(cfg, "latent-k")["expert_layers"])
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 1024, 128), BF16)
    cache = jax.random.normal(jax.random.PRNGKey(8), (1, 2048, 320), BF16)
    positions = 1024 + jnp.arange(1024)
    # Two programs, one a path, each built and run once.
    run = lambda **opts: jax.jit(  # noqa: E731
        lambda p, h, at, kv: decoder_lm._dense_mla_mixer(
            p, h, at, {"kv": kv}, cfg, opts))(p, h, positions, cache)
    got, state = run(pallas=True, interpret=True)
    want, state0 = run(pallas=False)
    np.testing.assert_array_equal(np.asarray(state["kv"], np.float32),
                                  np.asarray(state0["kv"], np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


# ---- (e) the router -------------------------------------------------------

def test_softmax_routing_ties_to_the_lower_index_and_gates_sum_to_one():
    logits = jnp.asarray([
        [0.0] * 8,                                    # all tie: 0, 1, 2
        [1.0, 3.0, 3.0, 2.0, 3.0, 0.0, 3.0, -1.0],    # four tie for three
        [5.0, -2.0, 0.5, 0.25, 7.0, 7.5, 0.0, 0.0],
    ], jnp.float32)
    experts, gates = moe.route_softmax(logits, top_k=3, scale=1.0)
    assert experts.dtype == jnp.int32 and gates.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(experts),
                                  [[0, 1, 2], [1, 2, 4], [5, 4, 0]])
    np.testing.assert_allclose(np.asarray(gates).sum(axis=-1), 1.0, rtol=1e-6)
    p = np.exp([7.5, 7.0, 5.0])
    np.testing.assert_allclose(np.asarray(gates[2]), p / p.sum(), rtol=1e-5)
    _, scaled = moe.route_softmax(logits, top_k=3, scale=2.5)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(gates),
                               rtol=1e-6)
    # The reference's own router makes the same choice.
    mine, mine_gates = ref.route(
        {"n_experts_per_token": 3, "routed_scale": 1.0}, logits,
        jnp.eye(8, dtype=jnp.float32))
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(experts))
    np.testing.assert_allclose(np.asarray(mine_gates), np.asarray(gates),
                               rtol=1e-6)


def test_the_config_says_which_router_runs(monkeypatch):
    seen = []
    for name in ("route_softmax", "route_sigmoid_grouped"):
        real = getattr(moe, name)
        monkeypatch.setattr(moe, name, lambda *a, _n=name, _f=real, **k: (
            seen.append(_n), _f(*a, **k))[1])
    n = jax.ShapeDtypeStruct((1, 16, 64), jnp.float32)
    for func, over in (("softmax", {}), ("sigmoid", {"scoring_func": "sigmoid"})):
        cfg = decoder_lm.DecoderLMConfig(**{**TINY, **over})
        group = lm_once.param_shapes(cfg)["expert_layers"]
        assert ("router_bias" in group) == (func == "sigmoid")
        # Which router a config calls is decided while the layer is TRACED:
        # nothing has to be drawn, compiled or run to see it.
        jax.eval_shape(lambda group, n, cfg=cfg: decoder_lm._experts_ffn(
            jax.tree_util.tree_map(lambda a: a[0], group), n, cfg, {}),
            group, n)
    assert seen == ["route_softmax", "route_sigmoid_grouped"]


# ---- (f) the int8 control -------------------------------------------------

def test_bf16_is_near_the_reference_and_the_int8_control_further_off():
    """The control's tables reach every leaf of the mixer and of the expert
    layer (the router's too); its block sums lie further from the reference
    than the bf16 program's by more than the check needs between them."""
    from agent_tpu.models.quant import quantize_for_family

    cfg = decoder_lm.DecoderLMConfig(**{**TINY, "dtype": "bfloat16"})
    ids = np.random.default_rng(4).integers(0, 3000, 1024).astype(np.int32)
    want = ref.token_logprobs({**REF_CFG, "dtype": "bfloat16"}, "latent-q",
                              [ids])[0]

    step = lm_once.segment_program(cfg)     # traced a tree: bf16, int8

    def gap(params):
        hidden, _ = step(params, ids[None], jnp.int32(0),
                         lm_once.state(cfg, 1, 1024))
        lp = lm_once.blocked_logprobs(hidden[0, :-1], params["head"],
                                      jnp.asarray(ids[1:]))
        return float(np.sqrt(np.mean((np.asarray(lp) - want) ** 2)))

    sound = gap(lm_once.params(cfg, "latent-q"))
    q = quantize_for_family("decoder_lm", lm_once.params(cfg, "latent-q"),
                            "int8")
    layers = q["expert_layers"]
    for name in ("wo", "w_dq", "w_uq", "w_dkv", "w_ukv", "w_router",
                 "ws_gate", "ws_up", "ws_down", "we_gate", "we_up", "we_down"):
        assert layers[name]["w_q"].dtype == jnp.int8, name
    assert layers["we_up"]["w_q"].shape == (2, 4, 64, 32)
    assert layers["w_ukv"]["w_q"].shape == (2, 32, 4 * 32)
    assert layers["q_norm"].dtype == jnp.float32
    control = gap(q)
    assert sound < 0.1 and control > 1.5 * sound, (sound, control)


# ---- (g) the mixers that were there ---------------------------------------

SPARSE = {"vocab_size": 3000, "d_model": 64, "n_heads": 4, "d_ff": 96,
          "n_layers": 2, "max_len": 163840, "mixer": "sparse_mla",
          "dtype": "float32", "q_lora_rank": 48, "kv_lora_rank": 32,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "index_n_heads": 16, "index_head_dim": 16, "index_topk": 16,
          "rope_theta": 10000.0, "rope_factor": 40.0,
          "rope_original_max_len": 4096, "n_dense_layers": 1, "n_experts": 16,
          "n_experts_held": 4, "expert_first": 0, "n_experts_per_token": 4,
          "n_expert_groups": 4, "n_groups_per_token": 2, "d_expert": 32,
          "n_shared_experts": 1, "routed_scale": 2.5}
HYBRID = {"vocab_size": 2048, "d_model": 64, "n_heads": 15, "n_kv_heads": 3,
          "d_head": 16, "d_ff": 96, "n_layers": 2, "ssm_n_heads": 6,
          "ssm_d_head": 16, "ssm_d_state": 24, "ssm_n_groups": 2,
          "dtype": "float32", "mixer": "hybrid_ssm",
          "embedding_multiplier": 5.656854249492381,
          "lm_head_multiplier": 0.0078125, "key_multiplier": 0.39,
          "ssm_z_multiplier": 0.35}
# sha256 of ``jit(forward_segment).lower(...).as_text()`` (a 256-token
# segment, a 512-token cache) as the PARENT of this change lowers it
# (commit 2b86bad, this container's JAX): brumby's mixer first and later
# segment, deepseek's in float32 and bf16, falcon's.
PARENT_PROGRAMS = {
    "power_retention-first": (
        {}, "2f18bd1be3d95440e0b5c2abae0e0ec116db5ce528b9bb7b8e3fc33ba7f67b20"),
    "power_retention-later": (
        {}, "03b9e32638440a521c998ec7b2a4b07c24691177a4135429438c19986d0ce918"),
    "sparse_mla": (
        SPARSE, "d28dc3af1e17d4ce2c3f1c1e75a397a701f1d796ca0cce8412b6c80718e09c25"),
    "sparse_mla-bf16": (
        {**SPARSE, "dtype": "bfloat16"},
        "57f286c3a302cb3cc40f9b77be5d3bf8ec02e829ae0d999dd60c8c2ffc7a2b95"),
    # Its caches are the layer scan's carry since PR 46: no longer the
    # parent's text, and held to the parent's NUMBERS instead (below).
    "hybrid_ssm": (HYBRID, None),
}


def _sliced_out_and_stacked(params, ids, pos0, state, cfg):
    """``forward_segment`` of a model of one dense group as the parent of
    PR 46 ran its caches: a layer's slice of EVERY state leaf scanned out of
    the stack, the mixer's arithmetic on it (its caches handed over as a
    stack of that one layer), the results stacked again."""
    caches = decoder_lm.MIXER_CACHES[cfg.mixer]
    x = decoder_lm._times(params["embed"][ids], cfg.embedding_multiplier,
                          cfg.compute_dtype)
    positions = pos0 + jnp.arange(ids.shape[1])

    def step(x, xs):
        p, mine = xs
        mine = {name: leaf[None] if caches[name] else leaf
                for name, leaf in mine.items()}
        x, mine, _ = decoder_lm._layer(p, x, positions,
                                       {**mine, "layer": jnp.int32(0)}, cfg, {})
        return x, {name: leaf[0] if caches[name] else leaf
                   for name, leaf in mine.items()}

    x, state = jax.lax.scan(step, x, (params["layers"], state))
    return decoder_lm._times(
        decoder_lm.rms_norm(x, params["final_norm"], cfg.rms_norm_eps),
        cfg.lm_head_multiplier, cfg.compute_dtype), state


def _carried_caches_answer_as_the_parents_form(cfg):
    """Three segments of one document: hidden states and state of
    ``forward_segment`` (caches carried, written and read in place) EQUAL
    those of the parent's form, to the bit."""
    params = lm_once.params(cfg, "carried")
    ids = np.random.default_rng(7).integers(0, cfg.vocab_size,
                                            (1, 768)).astype(np.int32)
    served = lambda p, i, a, s: decoder_lm.forward_segment(  # noqa: E731
        p, i, a, s, cfg)
    parents = lambda p, i, a, s: _sliced_out_and_stacked(  # noqa: E731
        p, i, a, s, cfg)
    mine = theirs = lm_once.state(cfg, 1, 768)
    for pos0 in (0, 256, 512):
        segment, at = ids[:, pos0:pos0 + 256], jnp.int32(pos0)
        # Operation by operation: what XLA fuses on this host, and so how it
        # orders a float32 sum, follows the program around the arithmetic.
        with jax.disable_jit():
            hidden, mine = served(params, segment, at, mine)
            want, theirs = parents(params, segment, at, theirs)
        np.testing.assert_array_equal(np.asarray(hidden), np.asarray(want))
        assert set(mine) == set(theirs) == {"k", "v", "ssm", "conv"}
        for name in theirs:
            assert mine[name].shape == theirs[name].shape
            np.testing.assert_array_equal(np.asarray(mine[name]),
                                          np.asarray(theirs[name]))
    assert (np.asarray(mine["k"]) != 0).any(axis=-1).all()   # every position


@pytest.mark.parametrize("case", list(PARENT_PROGRAMS))
def test_the_other_mixers_lower_to_the_parents_text(case):
    """The shared latent projections, the softmax router's branch, the
    attention kernel's query tile, the expansion's own rule and the layer
    scan's carried caches leave ``jit_lm_segment`` of the mixers that have no
    such cache as it was, on these tiny configs, letter for letter; the one
    that has (``hybrid_ssm``) answers as the parent's form does, bit for
    bit."""
    over, digest = PARENT_PROGRAMS[case]
    cfg = decoder_lm.DecoderLMConfig(**over)
    if digest is None:
        return _carried_caches_answer_as_the_parents_form(cfg)
    params = lm_once.param_shapes(cfg)
    state = lm_once.state_shapes(cfg, 1, 512)
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    first = jax.jit(lambda p, i, a: decoder_lm.forward_segment(
        p, i, a, None, cfg))
    later = jax.jit(lambda p, i, a, s: decoder_lm.forward_segment(
        p, i, a, s, cfg))
    if case.endswith("-first"):
        text = first.lower(params, ids, pos).as_text()
    else:
        if state is None:
            state = jax.eval_shape(first, params, ids, pos)[1]
        text = later.lower(params, ids, pos, state).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest


WINDOW = {"vocab_size": 3000, "d_model": 64, "n_heads": 8, "n_kv_heads": 2,
          "d_head": 16, "d_ff": 96, "n_layers": 6, "max_len": 16384,
          "mixer": "window_gqa", "dtype": "float32", "sliding_window": 128,
          "full_attention_every": 2}


@pytest.mark.parametrize("over, leaves", [
    ({**HYBRID, "n_layers": 6}, 12),     # falcon: six layers' keys and values
    (WINDOW, 6),                         # mellum2: three full layers of six
    (SPARSE, 0), (TINY, 0), ({}, 0),     # latent caches, a fixed-size state
], ids=["hybrid_ssm", "window_gqa", "sparse_mla", "dense_mla",
        "power_retention"])
def test_a_traced_scan_counts_the_caches_it_carries_in_place(over, leaves):
    """``state_caches_in_place_traced_total{mixer}``: ticks while a segment
    program is TRACED, once a state leaf a layer that the layer scan carries
    whole (``decoder_lm.MIXER_CACHES``); a mixer whose caches are stepped
    over a layer's slice at a time ticks nothing."""
    def ticks():
        family = get_registry().snapshot().get(
            "state_caches_in_place_traced_total") or {"series": []}
        return {s["labels"]["mixer"]: s["value"] for s in family["series"]}

    cfg = decoder_lm.DecoderLMConfig(**over)
    params = lm_once.param_shapes(cfg)
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    state = lm_once.state_shapes(cfg, 1, 512)
    if state is None:
        state = jax.eval_shape(lambda p, i, a: decoder_lm.forward_segment(
            p, i, a, None, cfg), params, ids, pos)[1]
    before = ticks()
    jax.jit(lambda p, i, a, s: decoder_lm.forward_segment(
        p, i, a, s, cfg)).lower(params, ids, pos, state)
    after = ticks()
    gained = {m: after[m] - before.get(m, 0.0) for m in after
              if after[m] != before.get(m, 0.0)}
    assert gained == ({cfg.mixer: float(leaves)} if leaves else {})


# ---- what no program can run ----------------------------------------------

@pytest.mark.parametrize("over, message", [
    ({"qk_rope_head_dim": 7}, "qk_rope_head_dim"),
    ({"kv_lora_rank": 0}, "kv_lora_rank"),
    ({"query_scale_beta": -0.1}, "query_scale_beta"),
    ({"scoring_func": "tanh"}, "scoring_func"),
    ({"n_expert_groups": 4, "n_groups_per_token": 2}, "no groups"),
    ({"expert_first": 14}, "experts held"),
    ({"n_experts_per_token": 17}, "cannot choose"),
])
def test_validate_rejects_what_no_program_can_run(over, message):
    with pytest.raises(ValueError, match=message):
        decoder_lm.validate(decoder_lm.DecoderLMConfig(**{**TINY, **over}))


def test_validate_takes_no_leading_dense_layer_and_asks_no_indexer():
    cfg = decoder_lm.DecoderLMConfig(**{**TINY, "index_topk": 0,
                                        "index_n_heads": 0})
    decoder_lm.validate(cfg)
    assert cfg.layer_groups == (("expert_layers", "experts", 0, 2),)
    assert not decoder_lm.starts_from_nothing(cfg)
