"""``models/decoder_lm.py`` at tiny widths on the CPU, against the
benchmark's plain reference (``benchmarks/reference/retention_lm.py``: one
reference, the file the chip check uses).

Tolerances, and why. With ``dtype: float32`` the family computes what the
reference computes in another order (chunks and a state against one score
matrix; a blocked log-sum-exp against a whole one): float32 reordering,
below 2e-5 nats a token. In bf16 (the stored and compute dtype of the
published configuration) every matmul operand is rounded to 2^-9: about
1e-2 nats a token at these widths; the bf16 test holds it under 5e-2, which
int8 projections (``test_int8_control_is_further_off``) exceed threefold."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import lm_once
import numpy as np
import pytest

from agent_tpu.models import decoder_lm as lm
from benchmarks.harness import manifest

ref = manifest.load_reference("retention_lm")

TINY = dict(vocab_size=1000, d_model=64, n_heads=10, n_kv_heads=2, d_head=16,
            d_ff=96, n_layers=2)
F32_TOL = 2e-5      # nats a token, float32 reordering
BF16_TOL = 5e-2     # nats a token, bf16 operands at width 64


def _cfg(**over):
    return lm.DecoderLMConfig(**{**TINY, "dtype": "float32", **over})


def _ref_cfg(cfg):
    return {k: getattr(cfg, k) for k in (
        "vocab_size", "d_model", "n_heads", "n_kv_heads", "d_head", "d_ff",
        "n_layers", "rms_norm_eps", "rope_theta", "dtype")}


def _score(cfg, params, doc, cuts=(), **opts):
    """Per-token log-probabilities of ``doc`` run as segments cut at
    ``cuts`` (none: one program): the config's one jitted segment program,
    traced a segment LENGTH and a kind of state (none, carried)."""
    step = lm_once.segment_program(cfg, pallas=False, **opts)
    state, out, at = None, [], 0
    for cut in (*cuts, len(doc)):
        ids = jnp.asarray(doc[None, at:cut])
        hidden, state = step(params, ids, jnp.int32(at), state)
        targets = jnp.asarray(doc[at + 1:cut + 1])
        out.append(np.asarray(lm_once.blocked_logprobs(
            hidden[0, :len(targets)], params["head"], targets)))
        at = cut
    return np.concatenate(out)


@pytest.fixture(scope="module")
def doc():
    return np.random.default_rng(3).integers(0, 1000, 300).astype(np.int32)


def test_weights_follow_the_published_rule_and_are_stored_in_the_dtype():
    cfg = _cfg(dtype="bfloat16")
    # About the draw itself: fresh ones, not ``lm_once``'s.
    params = lm.init_params(cfg, "rule-check")
    rc = _ref_cfg(cfg)
    assert params["embed"].dtype == jnp.bfloat16
    assert params["layers"]["w_up"].shape == (2, 64, 96)
    for name in ("embed", "head"):
        np.testing.assert_array_equal(
            np.asarray(params[name], np.float32),
            np.asarray(ref.draw(rc, "rule-check", name), np.float32))
    for name in ("wq", "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down"):
        for i in range(2):
            np.testing.assert_array_equal(
                np.asarray(params["layers"][name][i], np.float32),
                np.asarray(ref.draw(rc, "rule-check", name, layer=i),
                           np.float32))
    np.testing.assert_allclose(np.asarray(params["layers"]["bg"][0]),
                               ref.gate_bias(2))
    np.testing.assert_allclose(
        1.0 / (1.0 + np.exp(-lm.gate_bias(8))), 1.0 - 1.0 / (16 * 2.0 ** np.arange(8)),
        rtol=1e-6)
    other = lm.init_params(cfg, "another-id")
    assert not np.array_equal(np.asarray(other["head"], np.float32),
                              np.asarray(params["head"], np.float32))


# One small config a mixer: what ``lm_once`` is held to below.
_MLA = dict(vocab_size=500, d_model=64, n_heads=4, d_ff=96, n_layers=2,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, n_experts=8, n_experts_held=4,
            expert_first=4, n_experts_per_token=2, d_expert=32,
            n_shared_experts=1)
ONE_OF_EACH_MIXER = {
    "power_retention": dict(TINY, vocab_size=500),
    "sparse_mla": dict(_MLA, mixer="sparse_mla", n_dense_layers=1,
                       index_n_heads=4, index_head_dim=16, index_topk=16,
                       n_expert_groups=4, n_groups_per_token=2),
    "hybrid_ssm": dict(vocab_size=500, d_model=64, n_heads=6, n_kv_heads=3,
                       d_head=16, d_ff=96, n_layers=2, mixer="hybrid_ssm",
                       ssm_n_heads=6, ssm_d_head=16, ssm_d_state=24,
                       ssm_n_groups=2, key_multiplier=0.39),
    "dense_mla": dict(_MLA, mixer="dense_mla", n_dense_layers=0,
                      scoring_func="softmax", n_expert_groups=1,
                      n_groups_per_token=1),
    "window_gqa": dict(vocab_size=500, d_model=64, n_heads=8, n_kv_heads=2,
                       d_head=16, d_ff=96, n_layers=2, mixer="window_gqa",
                       sliding_window=128, full_attention_every=2),
    "hybrid_kda": dict(_MLA, mixer="hybrid_kda", d_head=16, n_layers=3,
                       n_dense_layers=1, layer_group_size=2,
                       n_expert_groups=4, n_groups_per_token=2,
                       expert_swiglu_limits=(0.0, 4.0, 4.0),
                       shared_swiglu_limits=(0.0, 5.0, 7.0)),
    "conv_gqa": dict(vocab_size=500, d_model=64, n_heads=4, n_kv_heads=2,
                     d_head=64, d_ff=96, n_layers=5, mixer="conv_gqa",
                     layer_types=("conv", "full_attention", "conv",
                                  "full_attention", "conv"),
                     n_dense_layers=1, n_experts=8, n_experts_held=8,
                     n_experts_per_token=2, n_expert_groups=1,
                     n_groups_per_token=1, d_expert=32, n_shared_experts=0),
}


def _bytes_of(tree):
    return {jax.tree_util.keystr(path): (leaf.dtype, np.asarray(leaf).tobytes())
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("mixer", list(lm.MIXER_LEAVES))
def test_the_weights_drawn_once_are_a_fresh_draws_to_the_byte(mixer):
    """``lm_once.params`` (what the family's test files take their weights
    from) against ``init_params`` itself, for a config of every mixer: the
    same tree, the same bytes, so the cache (keyed by the config's items and
    the id) serves no other config's weights; an equal config gets the same
    leaves in containers of its own; the shapes are the drawn tree's."""
    cfg = lm.DecoderLMConfig(**ONE_OF_EACH_MIXER[mixer], dtype="bfloat16")
    lm.validate(cfg)
    fresh = lm.init_params(cfg, "drawn-once")
    cached = lm_once.params(cfg, "drawn-once")
    group = cached.get("layers") or cached["expert_layers"]
    held = lm.MIXER_LEAVES[mixer]
    if isinstance(held, dict):          # leaves by kind, a stack a kind
        assert all(set(held[kind]) <= set(leaves)
                   for kind, leaves in group["mixers"].items())
    else:
        assert set(held) & set(group)
    assert _bytes_of(cached) == _bytes_of(fresh)
    again = lm_once.params(dataclasses.replace(cfg), "drawn-once")
    assert again is not cached and again["embed"] is cached["embed"]
    group = "expert_layers" if "expert_layers" in cached else "layers"
    assert again[group] is not cached[group]
    assert jax.tree_util.tree_structure(lm_once.param_shapes(cfg)) == (
        jax.tree_util.tree_structure(fresh))
    assert [(s.shape, s.dtype) for s in jax.tree_util.tree_leaves(
        lm_once.param_shapes(cfg))] == [
            (a.shape, a.dtype) for a in jax.tree_util.tree_leaves(fresh)]


def test_float32_forward_matches_the_reference(doc):
    cfg = _cfg()
    params = lm_once.params(cfg, "m-f32")
    want = ref.token_logprobs(_ref_cfg(cfg), "m-f32", [doc])[0]
    got = _score(cfg, params, doc)
    assert np.abs(got - want).max() < F32_TOL


@pytest.mark.parametrize("cuts, chunk", [((128,), 32), ((64, 200), 64),
                                         ((100, 101, 250), 16)])
def test_segments_with_the_state_handed_on_equal_one_program(doc, cuts, chunk):
    cfg = _cfg()
    params = lm_once.params(cfg, "m-f32")
    whole = _score(cfg, params, doc, chunk=chunk)
    parts = _score(cfg, params, doc, cuts=cuts, chunk=chunk)
    assert np.abs(parts - whole).max() < F32_TOL
    want = ref.token_logprobs(_ref_cfg(cfg), "m-f32", [doc])[0]
    assert np.abs(parts - want).max() < F32_TOL


def test_bf16_forward_is_near_the_reference_and_int8_control_is_further_off(doc):
    cfg = _cfg(dtype="bfloat16")
    params = lm_once.params(cfg, "m-bf16")
    want = ref.token_logprobs(_ref_cfg(cfg), "m-bf16", [doc])[0]
    sound = np.abs(_score(cfg, params, doc, cuts=(128,), chunk=64) - want)
    assert sound.mean() < BF16_TOL / 3 and sound.max() < BF16_TOL * 4
    from agent_tpu.models.quant import quantize_for_family

    q = quantize_for_family("decoder_lm", lm_once.params(cfg, "m-bf16"), "int8")
    assert q["layers"]["w_up"]["w_q"].dtype == jnp.int8
    assert q["layers"]["w_up"]["w_scale"].shape == (2, 96)
    assert q["embed"].dtype == jnp.bfloat16
    control = np.abs(_score(cfg, q, doc, cuts=(128,), chunk=64) - want)
    assert control.mean() > 2 * sound.mean(), (control.mean(), sound.mean())
    w8 = quantize_for_family("decoder_lm", lm_once.params(cfg, "m-bf16"), "w8a16")
    assert "w8" in w8["layers"]["wq"]
    assert np.isfinite(_score(cfg, w8, doc)).all()


@pytest.mark.parametrize("vocab, block", [(1000, 256), (1000, 1000),
                                          (777, 128), (512, 4096)])
def test_blocked_head_equals_a_full_log_softmax(vocab, block):
    rng = np.random.default_rng(vocab + block)
    h = jnp.asarray(rng.standard_normal((50, 32)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((vocab, 32)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, vocab, 50), jnp.int32)
    want = jnp.take_along_axis(
        jax.nn.log_softmax(h @ head.T, axis=-1), targets[:, None], 1)[:, 0]
    got = jax.jit(lm.blocked_logprobs, static_argnames="vocab_block")(
        h, head, targets, vocab_block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_blocked_head_against_the_references_own_logits(doc):
    """The reference's whole-vocabulary logits at a few positions, through a
    plain log-softmax, are its folded log-probabilities and the model's."""
    cfg = _cfg()
    params = lm_once.params(cfg, "m-f32")
    at = [0, 7, 150, 298]
    z = ref.logits(_ref_cfg(cfg), "m-f32", doc, at)
    assert z.shape == (4, cfg.vocab_size)
    want = np.asarray(jax.nn.log_softmax(jnp.asarray(z), axis=-1))[
        np.arange(4), doc[np.asarray(at) + 1]]
    folded = ref.token_logprobs(_ref_cfg(cfg), "m-f32", [doc])[0][at]
    np.testing.assert_allclose(folded, want, atol=F32_TOL)
    np.testing.assert_allclose(_score(cfg, params, doc)[at], want, atol=F32_TOL)


def test_segment_block_sums_mask_positions_without_a_target():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((1, 2048, 16)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    t = jnp.asarray(rng.integers(0, 64, (1, 2048)), jnp.int32)
    lp = np.asarray(lm_once.blocked_logprobs(h[0], head, t[0]))
    got = np.asarray(lm_once.segment_block_sums(h, head, t, jnp.int32(1500)))
    np.testing.assert_allclose(
        got, [lp[:1024].sum(), lp[1024:1500].sum()], rtol=1e-5)


@pytest.mark.parametrize("over, message", [
    ({"mixer": "softmax"}, "mixer"), ({"n_kv_heads": 3}, "n_kv_heads"),
    ({"d_head": 15}, "d_head"), ({"n_layers": 0}, "n_layers"),
])
def test_validate_rejects_what_no_program_can_run(over, message):
    with pytest.raises(ValueError, match=message):
        lm.validate(_cfg(**over))
    lm.validate(_cfg())


def test_rope_rotates_pairs_and_keeps_norms():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((5, 2, 16)),
                    jnp.float32)
    pos = jnp.arange(5) + 7
    got = lm.rope(x, pos, 1e6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        ref.rope(x, pos, 1e6)), atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(got), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
