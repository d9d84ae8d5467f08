"""The ``conv_gqa`` mixer (double-gated short convolutions and, by the model's
own pattern of kinds, grouped-query attention at heads of HALF a lane tile, two
key-value heads a cache row) over sigmoid-routed expert layers, on the CPU at
tiny widths: the ``conv`` mixer against three shifted products; the half-lane
attention (the ``jax.numpy`` form and the Pallas kernel in interpret mode,
with the layers' stacked cache and a layer's number) against dense masked
softmax; the router under a NONZERO choice bias; the family through
``map_score_lm`` in several segment programs against the reference's one
forward pass; a document in one segment against the same in four; the kinds
of a model whose stacked group begins in the middle of the model's period;
the older mixers' lowered programs against their parent's text.

Tolerances, each with its reason:

- ``TOKEN_TOL`` 2e-5 nats a token (0.02 on a block sum of 1,024 tokens):
  ``dtype: float32`` here, so the op computes what the reference computes in
  another order (segments, a carried tail, a cache in rows of two heads), and
  float32 reordering is all that may differ;
- one segment against four: EQUAL, to the bit. A tail carries the very
  float32 rows the one-segment program holds at that place, and a segment's
  queries meet the keys in the same key tiles of the same cache;
- the attention kernel in interpret mode against dense float32 softmax: 1e-2
  on outputs of order 1 (bf16 operands and weights rounded to bf16 before the
  value product, through a softmax over up to 2,048 keys)."""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import lm_once
import numpy as np
import pytest

from agent_tpu.kernels import causal_attention as ca
from agent_tpu.kernels import ssd
from agent_tpu.models import decoder_lm, moe
from agent_tpu.obs.metrics import get_registry
from agent_tpu.ops import get_op
from agent_tpu.runtime.runtime import reset_runtime
from benchmarks.harness import manifest

ref = manifest.load_reference("conv_moe_lm")

# The cell's pattern at tiny widths: a leading dense ``conv`` layer, then two
# periods that BEGIN with their attention layer; heads of 64 (two key-value
# heads a cache row), 4 query heads a key-value head; 16 experts, all held,
# 4 a token in one group, none shared.
PATTERN = ("conv", "full_attention", "conv", "conv", "conv",
           "full_attention", "conv", "conv", "conv")
TINY = {"vocab_size": 3000, "d_model": 64, "n_heads": 8, "n_kv_heads": 2,
        "d_head": 64, "d_ff": 96, "n_layers": 9, "max_len": 16384,
        "mixer": "conv_gqa", "dtype": "float32", "rms_norm_eps": 1e-5,
        "rope_theta": 1e6, "layer_types": list(PATTERN), "conv_taps": 3,
        "n_dense_layers": 1, "n_experts": 16, "n_experts_held": 16,
        "expert_first": 0, "n_experts_per_token": 4, "n_expert_groups": 1,
        "n_groups_per_token": 1, "d_expert": 32, "n_shared_experts": 0,
        "routed_scale": 1.0, "scoring_func": "sigmoid"}
CFG = decoder_lm.DecoderLMConfig(**TINY)
TOKEN_TOL = 2e-5
BF16 = jnp.bfloat16
LONG = 4200             # 2,048 + 2,048 + 1,024 program tokens under BUCKETS
BUCKETS = (1024, 2048)  # the op's segment sizes, halved for the CPU


def ids_of(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (n,)).astype(np.int32)


# ---- (a) the conv mixer against three shifted products --------------------

_conv_layer = jax.jit(lambda p, h, tail: decoder_lm._conv_gqa_mixer(
    p, h, None, {"tail": tail}, CFG, {}, kind="conv"))


def test_the_conv_mixer_is_three_shifted_products():
    """``C_t x sum_i w_i (B x z)_{t-2+i}`` through the out-projection, the
    document's first rows over zeros, against the reference's statement of
    it; and the tail it hands on is the last two rows of ``B x z``."""
    rng = np.random.default_rng(1)
    d, S = 64, 96
    p = {"w_conv_in": jnp.asarray(rng.standard_normal((d, 3 * d)) / 8, jnp.float32),
         "wo": jnp.asarray(rng.standard_normal((d, d)) / 8, jnp.float32),
         "conv_w": jnp.asarray(rng.standard_normal((3, d)), jnp.float32)}
    h = jnp.asarray(rng.standard_normal((1, S, d)), jnp.float32)
    y, state = _conv_layer(p, h, jnp.zeros((1, 2, d), jnp.float32))
    proj = np.asarray(h[0]) @ np.asarray(p["w_conv_in"])
    B, C, z = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    g = B * z
    w = np.asarray(p["conv_w"])
    c = w[2] * g
    c[1:] += w[1] * g[:-1]
    c[2:] += w[0] * g[:-2]
    np.testing.assert_allclose(np.asarray(y[0]), (C * c) @ np.asarray(p["wo"]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(state["tail"][0]), g[-2:], atol=1e-6)
    want = ref.gated_conv(jnp.asarray(B), jnp.asarray(C), jnp.asarray(z),
                          p["conv_w"])
    np.testing.assert_allclose(np.asarray(want), C * c, atol=2e-5)
    # A second segment over the first's tail is the one pass over both.
    both = jnp.concatenate([h, h[:, ::-1]], axis=1)
    whole, _ = _conv_layer(p, both, jnp.zeros((1, 2, d), jnp.float32))
    later, _ = _conv_layer(p, h[:, ::-1], state["tail"])
    np.testing.assert_array_equal(np.asarray(whole[0, S:]), np.asarray(later[0]))


def test_the_convolution_without_a_bias_starts_at_its_first_tap():
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((40, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
    tail = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)
    plain, t0 = ssd.causal_conv(u, tail, w)
    zeros, t1 = ssd.causal_conv(u, tail, w, jnp.zeros((8,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(zeros))
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))


# ---- (b) the half-lane attention against dense masked softmax -------------

def _dense(q, k, v, pos0):
    """q [Hkv, G, S, D], k, v [Hkv, Lk, D]: float64 softmax under the
    causal mask of the queries' positions."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    S, Lk = q.shape[2], k.shape[1]
    s = np.einsum("hgtd,hsd->hgts", q, k)
    seen = np.arange(Lk)[None, :] <= (pos0 + np.arange(S))[:, None]
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hgts,hsd->hgtd", p / p.sum(-1, keepdims=True), v)


_attend = jax.jit(ca.causal_attention, static_argnames=("pallas", "interpret"))


@pytest.mark.parametrize("pos0", [0, 1024])
def test_half_lane_attention_against_dense_softmax(pos0):
    """4 key-value heads of 64 two a cache row, 4 query heads each, the cache
    the second layer of a stack of three: the ``jax.numpy`` form and the
    kernel in interpret mode, a document's first segment and a later one
    (keys past the segment hold noise and are never read)."""
    rng = np.random.default_rng(3)
    Hkv, G, S, D, Lk = 4, 4, 1024, 64, 2048
    assert ca.heads_a_row(Hkv, D) == 2 and ca.heads_a_row(3, D) == 1
    assert ca.cache_shape(Hkv, Lk, D) == (2, Lk, 128)
    assert ca.cache_shape(Hkv, Lk, 128) == (Hkv, Lk, 128)
    assert ca.pallas_supported(S, Lk, D, BF16, D)
    assert not ca.pallas_supported(S, Lk, 32, BF16, 32)
    assert not ca.window_supported(S, 512, D, BF16)
    assert ca.query_tile(2 * G, S) == 512
    q = jnp.asarray(rng.standard_normal((Hkv, G, S, D)) * 0.3, BF16)
    k = jnp.asarray(rng.standard_normal((Hkv, Lk, D)), BF16)
    v = jnp.asarray(rng.standard_normal((Hkv, Lk, D)), BF16)
    rows = ca.cache_rows(k)
    assert rows.shape == (2, Lk, 128)
    np.testing.assert_array_equal(np.asarray(rows[1, :, 64:]), np.asarray(k[3]))
    np.testing.assert_array_equal(np.asarray(ca._heads_apart(rows, 2)),
                                  np.asarray(k))
    noise = jnp.asarray(rng.standard_normal((2, Lk, 128)), BF16)
    stack = lambda a: jnp.stack([noise, ca.cache_rows(a), noise])[:, None]  # noqa: E731
    want = _dense(q, k[:, :pos0 + S], v[:, :pos0 + S], pos0)
    at, layer = jnp.int32(pos0), jnp.int32(1)
    plain = _attend(q, stack(k), stack(v), at, layer, pallas=False)
    kernel = _attend(q, stack(k), stack(v), at, layer, pallas=True,
                     interpret=True)
    assert plain.shape == kernel.shape == (Hkv, G, S, D)
    np.testing.assert_allclose(np.asarray(plain, np.float64), want, atol=1e-2)
    np.testing.assert_allclose(np.asarray(kernel, np.float64), want, atol=1e-2)


# ---- (c) the router with a nonzero bias -----------------------------------

def test_the_router_chooses_by_score_plus_bias_and_gates_by_score():
    """One group: the 4 largest of ``s + bias`` (ties to the lower index),
    gated by ``s`` WITHOUT the bias over their sum; the reference's router
    states the same."""
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.standard_normal((200, 16)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((16,)) * 0.5, jnp.float32)
    experts, gates = jax.jit(lambda l, b: moe.route_sigmoid_grouped(
        l, b, n_groups=1, groups_kept=1, top_k=4, scale=1.0))(logits, bias)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    order = np.argsort(-(s + np.asarray(bias, np.float64)), axis=1,
                       kind="stable")[:, :4]
    np.testing.assert_array_equal(np.asarray(experts), order)
    picked = np.take_along_axis(s, order, axis=1)
    np.testing.assert_allclose(np.asarray(gates),
                               picked / picked.sum(-1, keepdims=True), atol=1e-6)
    # The bias moved the choice (else the case says nothing), never a gate.
    plain = np.argsort(-s, axis=1, kind="stable")[:, :4]
    assert (np.sort(plain, 1) != np.sort(order, 1)).any()
    w = jnp.asarray(np.eye(16), jnp.float32)
    theirs, their_gates = ref.route(TINY, logits, w, bias)
    np.testing.assert_array_equal(np.asarray(theirs), order)
    np.testing.assert_allclose(np.asarray(their_gates), np.asarray(gates),
                               atol=1e-6)


# ---- (d) the kinds of a model whose group begins inside a period ----------

def test_the_kinds_come_from_the_models_own_pattern():
    """1 dense + 8 layers of the published pattern cut at layer 1: the dense
    layer has the kind the pattern gives it (``conv``: not the period's
    first), the expert group's period begins with its attention layer, and
    a kind's layers are numbered over both groups."""
    decoder_lm.validate(CFG)
    assert CFG.layer_types[1] == "full"          # the published name, mapped
    assert decoder_lm.kinds_by_layer(CFG) == tuple(
        "full" if k == "full_attention" else k for k in PATTERN)
    assert decoder_lm.layer_kinds(CFG) == ("full", "conv", "conv", "conv")
    assert decoder_lm.group_kinds(CFG, "dense") == ("conv",)
    assert decoder_lm.group_kinds(CFG, "experts") == (
        "full", "conv", "conv", "conv")
    assert decoder_lm.layers_of_kinds(CFG) == {
        "layers": {"conv": (0,)},
        "expert_layers": {"conv": (2, 3, 4, 6, 7, 8), "full": (1, 5)}}
    # The published model whole: two leading dense conv layers, then
    # (attention, conv, conv, conv) nine times and a half: one period of 38.
    whole = decoder_lm.DecoderLMConfig(**{
        **TINY, "n_layers": 40, "n_dense_layers": 2,
        "layer_types": ["conv", "conv", "full_attention", "conv"] * 10})
    decoder_lm.validate(whole)
    assert decoder_lm.group_kinds(whole, "dense") == ("conv",)
    assert len(decoder_lm.group_kinds(whole, "experts")) == 38
    # The mixers whose kinds follow from their own keys keep theirs.
    window = decoder_lm.DecoderLMConfig(
        mixer="window_gqa", n_layers=8, full_attention_every=4, n_experts=0)
    assert decoder_lm.kinds_by_layer(window) == (
        "window", "window", "window", "full") * 2
    kda = decoder_lm.DecoderLMConfig(
        mixer="hybrid_kda", n_layers=8, layer_group_size=3, n_dense_layers=2,
        n_experts=16, n_experts_held=16)
    assert decoder_lm.kinds_by_layer(kda) == ("linear", "linear") + (
        "linear", "linear", "latent") * 2
    assert decoder_lm.group_kinds(kda, "dense") == ("linear",)
    assert decoder_lm.layers_of_kinds(kda)["expert_layers"] == {
        "latent": (4, 7), "linear": (2, 3, 5, 6)}


@pytest.mark.parametrize("over, message", [
    ({"layer_types": list(PATTERN[:8])}, "layer_types"),
    ({"layer_types": ["window"] + list(PATTERN[1:])}, "layer_types"),
    ({"conv_taps": 0}, "conv_taps"),
    ({"n_kv_heads": 3}, "multiple of n_kv_heads"),
    ({"mixer": "window_gqa", "n_dense_layers": 0, "n_layers": 8},
     "layer_types is conv_gqa's"),
])
def test_validate_rejects_what_no_program_can_run(over, message):
    with pytest.raises(ValueError, match=message):
        decoder_lm.validate(decoder_lm.DecoderLMConfig(**{**TINY, **over}))


# ---- (e) the family against the reference; one segment against four -------

def test_forward_segment_agrees_with_the_reference_across_boundaries():
    """A document of 1,536 tokens in three segments of 512: both kinds of
    state cross two program boundaries; per-token log-probabilities against
    the reference's one pass over the whole document."""
    ids = ids_of(1536, 5)
    params = lm_once.params(CFG, "conv-a")
    step = lm_once.segment_program(CFG)
    state, hidden = lm_once.state(CFG, 1, 1536), []
    for pos0 in range(0, 1536, 512):
        h, state = step(params, ids[None, pos0:pos0 + 512], jnp.int32(pos0),
                        state)
        hidden.append(h[0])
    lp = lm_once.blocked_logprobs(jnp.concatenate(hidden)[:-1], params["head"],
                                  jnp.asarray(ids[1:]))
    want = ref.token_logprobs(TINY, "conv-a", [ids])[0]
    assert np.abs(np.asarray(lp) - want).max() < TOKEN_TOL
    assert set(state) == {"mixer", "pairs", "tiles"}
    assert set(state["mixer"]) == {"conv", "full"}
    # Two key-value heads of 64 a row of 128 lanes, every position written.
    assert state["mixer"]["full"]["k"].shape == (2, 1, 1, 1536, 128)
    assert (np.asarray(state["mixer"]["full"]["k"]) != 0).any(axis=-1).all()
    assert state["mixer"]["conv"]["tail"].shape == (7, 1, 2, 64)
    # Every expert is held: 4 pairs a token a sparse layer.
    assert float(state["pairs"]) == 4 * 8 * 1536


def test_one_segment_equals_four_to_the_bit():
    """The same document as ONE segment of 1,024 and as four of 256: hidden
    states and state EQUAL. A tail zeroed at a boundary, or a cache that
    lost a segment, fails it."""
    ids = ids_of(1024, 6)
    params = lm_once.params(CFG, "conv-a")
    step = lm_once.segment_program(CFG)

    def run(segment, carry=lambda s: s):
        state, hidden = lm_once.state(CFG, 1, 1024), []
        for pos0 in range(0, 1024, segment):
            h, state = step(params, ids[None, pos0:pos0 + segment],
                            jnp.int32(pos0), carry(state))
            hidden.append(h[0])
        return np.asarray(jnp.concatenate(hidden)), state

    one, state1 = run(1024)
    four, state4 = run(256)
    np.testing.assert_array_equal(one, four)
    # (The tiles the grouped matmul visits follow the segments: not held.)
    for a, b in zip(jax.tree_util.tree_leaves(state1["mixer"]),
                    jax.tree_util.tree_leaves(state4["mixer"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(state1["pairs"]) == float(state4["pairs"])

    def tail_zeroed(state):
        mixer = state["mixer"]
        return {**state, "mixer": {**mixer, "conv": {
            "tail": jnp.zeros_like(mixer["conv"]["tail"])}}}

    broken, _ = run(256, tail_zeroed)
    assert np.abs(broken - one)[:256].max() == 0          # before a boundary
    assert np.abs(broken - one)[256:].max() > 1e-3


def test_the_op_scores_long_documents_as_the_reference_does(monkeypatch):
    """Through ``map_score_lm`` under segment buckets of 2,048 and 1,024: a
    4,200-token document in three segment programs and a short one, block
    sums against the reference; the counters the op ticks."""
    from agent_tpu.ops import map_score_lm

    monkeypatch.setattr(map_score_lm, "SEGMENT_BUCKETS", BUCKETS)
    reset_runtime()
    docs = [ids_of(LONG, 7), ids_of(700, 8)]
    before = get_registry().snapshot()
    out = get_op("map_score_lm")({
        "ids": [d.tolist() for d in docs], "model_config": TINY,
        "model_path": "conv-op", "allow_fallback": False})
    reset_runtime()
    assert out["ok"] and out["n_tokens"] == [LONG, 700]
    want = ref.token_logprobs(TINY, "conv-op", docs)
    for got, lp in zip(out["block_logprob_sums"], want):
        sums = ref.block_sums(lp)
        assert len(got) == len(sums)
        assert np.abs(np.asarray(got) - sums).max() < TOKEN_TOL * 1024
    from benchmarks.harness.counters import counter_delta

    after = get_registry().snapshot()
    delta = lambda name, **labels: counter_delta(  # noqa: E731
        before, after, name, **labels)
    assert delta("conv_tail_tokens_total", path="first_segment") == 2048 + 700
    assert delta("conv_tail_tokens_total", path="carried") == LONG - 2048
    assert delta("causal_attention_pairs_total", kind="causal") == sum(
        n * (n + 1) // 2 for n in (LONG, 700))
    computed = sum(ca.visited_pairs(s, p, ca.query_tile(8, s)) for s, p in
                   [(2048, 0), (2048, 2048), (1024, 4096), (1024, 0)])
    assert delta("causal_attention_pairs_total", kind="computed") == computed
    assert delta("moe_expert_pairs_total") == 4 * 8 * (5120 + 1024)
    assert delta("state_caches_in_place_traced_total", mixer="conv_gqa") > 0


def test_all_experts_held_the_held_share_is_the_layer():
    """Guide section 4's share test where every expert is held: the one
    share IS the layer. The program's expert layer (router with its bias
    leaf, the held experts) against the reference's loop over the 16."""
    params = lm_once.params(CFG, "conv-a")
    p = lm_once.first_layer({k: v for k, v in params["expert_layers"].items()
                             if k != "mixers"})
    u = jnp.asarray(np.random.default_rng(9).standard_normal((300, 64)),
                    jnp.float32)
    got = lm_once.expert_layer_program(CFG)(p, u)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer_ffn(TINY, "conv-a", 1, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_int8_quantizes_both_kinds_projections():
    from agent_tpu.models.quant import quantize_for_family

    q = quantize_for_family("decoder_lm", lm_once.params(CFG, "conv-a"),
                            "int8")
    mixers = q["expert_layers"]["mixers"]
    for kind, names in (("conv", ("w_conv_in", "wo")),
                        ("full", ("wq", "wk", "wv", "wo"))):
        for name in names:
            assert mixers[kind][name]["w_q"].dtype == jnp.int8, (kind, name)
    assert mixers["conv"]["conv_w"].dtype == jnp.float32
    assert q["layers"]["mixers"]["conv"]["w_conv_in"]["w_q"].shape == (
        1, 64, 192)
    ids = ids_of(512, 10)
    hidden, _ = lm_once.segment_program(CFG)(
        q, ids[None], jnp.int32(0), lm_once.state(CFG, 1, 512))
    sound, _ = lm_once.segment_program(CFG)(
        lm_once.params(CFG, "conv-a"), ids[None], jnp.int32(0),
        lm_once.state(CFG, 1, 512))
    # Unit-rms hidden states: int8 on every projection of nine layers of 64
    # (and the expert choices it flips) moves them by a fifth, not by all.
    gap = float(jnp.sqrt(jnp.mean((hidden - sound) ** 2)))
    assert 1e-3 < gap < 0.5, gap


# ---- (f) the older mixers that share this code keep their programs --------

WINDOW = {"vocab_size": 3000, "d_model": 64, "n_heads": 8, "n_kv_heads": 2,
          "d_head": 16, "d_ff": 96, "n_layers": 6, "max_len": 16384,
          "mixer": "window_gqa", "dtype": "float32", "sliding_window": 128,
          "full_attention_every": 2}
KDA = {"vocab_size": 3000, "d_model": 64, "n_heads": 4, "d_head": 16,
       "d_ff": 96, "n_layers": 7, "max_len": 16384, "mixer": "hybrid_kda",
       "dtype": "float32", "rope_theta": 6e6, "kv_lora_rank": 32,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "layer_group_size": 3, "kda_conv": 4, "kda_lower_bound": -5.0,
       "n_dense_layers": 1, "n_experts": 16, "n_experts_held": 8,
       "expert_first": 4, "n_experts_per_token": 4, "n_expert_groups": 4,
       "n_groups_per_token": 2, "d_expert": 32, "n_shared_experts": 1,
       "routed_scale": 2.5, "scoring_func": "sigmoid",
       "expert_swiglu_limits": [0, 0.5, 0.5, 0.5, 0.5, 0, 0.5],
       "shared_swiglu_limits": [0, 0.7, 0, 0.7, 0.7, 0.7, 0.7]}
HYBRID = {"vocab_size": 2048, "d_model": 64, "n_heads": 15, "n_kv_heads": 3,
          "d_head": 16, "d_ff": 96, "n_layers": 2, "ssm_n_heads": 6,
          "ssm_d_head": 16, "ssm_d_state": 24, "ssm_n_groups": 2,
          "dtype": "float32", "mixer": "hybrid_ssm"}
# sha256 of ``jit(forward_segment).lower(...).as_text()`` (a 256-token
# segment, a 512-token cache) as the PARENT of this change lowers it (commit
# 7a944b9, this container's JAX; ``tests/test_latent_mla.py`` holds
# ``power_retention``'s and ``sparse_mla``'s the same way): the mixers that
# share the kinds machinery (``kinds_by_layer``, ``group_kinds``), the full
# kind's cache write (``cache_rows``), the attention kernel's entry and the
# convolution (its bias made optional) with ``conv_gqa``.
PARENT_PROGRAMS = {
    "window_gqa": (
        WINDOW, "cd4d798c375baabbd091d7246ab4f3937685b2622fdd916f87ee6b82d308ac75"),
    "hybrid_kda": (
        KDA, "9b85bd5cbabe1f8325926561c133e255b9f2537822f02308094e174b3cbe8b1e"),
    "hybrid_ssm": (
        HYBRID, "a5455fe0e8a05bd91ef7122475108958f1612675c7d5dda708e7538d198a19bf"),
}


@pytest.mark.parametrize("case", list(PARENT_PROGRAMS))
def test_the_mixers_that_share_this_code_lower_to_the_parents_text(case):
    over, digest = PARENT_PROGRAMS[case]
    cfg = decoder_lm.DecoderLMConfig(**over)
    params = lm_once.param_shapes(cfg)
    state = lm_once.state_shapes(cfg, 1, 512)
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    text = jax.jit(lambda p, i, a, s: decoder_lm.forward_segment(
        p, i, a, s, cfg)).lower(params, ids, pos, state).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest
