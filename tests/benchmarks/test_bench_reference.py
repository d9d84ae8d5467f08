"""The output check's two links, guarded on every PR without a chip: the
plain float32 reference against the program's own model functions on seeded
weights at a small size, and the arithmetic of the numbers compared. In
float32 program and reference agree to rounding; in bf16 (what the
configuration states) they agree within the written tolerance; with the
program's int8 path switched on (the control: the nearest precision below
bf16) they do not. The limits the CHIP runs are held to are set from chip
readings (PERF.md); the ones here are for this size on the CPU."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from agent_tpu.models import encoder  # noqa: E402
from agent_tpu.ops._model_common import maybe_quantize_params  # noqa: E402
from benchmarks.harness import schedule  # noqa: E402
from benchmarks.reference import encoder as ref_enc  # noqa: E402

ENC = {"d_model": 256, "n_heads": 4, "n_layers": 4, "d_ff": 1024,
       "max_len": 128, "vocab_size": 260, "n_classes": 64}
MODEL_IDS = tuple(f"bench-test-{k}" for k in range(6))
ROWS_PER_MODEL = 24

# At this size on the CPU, six models a reading: bf16 leaves a bias of 0.0009
# in the served log-probabilities (the reference computes on the bf16 values
# of the weights, as the configuration defines the model); the program's int8
# path (weights kept in 8 bits move every row of a model the same way) one of
# 0.0137. The tolerance lies between, with room on both sides.
BIAS_TOLERANCE = 0.004


def leaves_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


@pytest.mark.parametrize("model_id", MODEL_IDS[:2])
def test_reference_weights_follow_the_programs_recipe(model_id):
    """The reference makes its own weights from the model id; they are the
    program's to the bit, leaf for leaf, or nothing downstream means much."""
    assert leaves_equal(
        ref_enc.init_params(ENC, model_id),
        encoder.init_params(encoder.EncoderConfig(**ENC), model_id))


TEXTS = schedule.drain_rows(
    {"row_bytes": {"dist": "uniform", "min": 64, "max": 148}, "order_seed": 5}, 5,
    ROWS_PER_MODEL * len(MODEL_IDS))


def served_against_reference(dtype: str, quant: str):
    """What ``correct`` compares, with the program's model functions in the
    agent's place: top-5 of every row, six tenant models."""
    data = {"ref_logits": [], "indices": [], "scores": [], "model": []}
    for k, model_id in enumerate(MODEL_IDS):
        texts = TEXTS[k * ROWS_PER_MODEL:(k + 1) * ROWS_PER_MODEL]
        cfg = encoder.EncoderConfig(**ENC, dtype=dtype, quant=quant)
        params = maybe_quantize_params(
            encoder.init_params(cfg, model_id), "encoder", cfg)
        ids, mask = ref_enc.tokenize(texts, ENC["max_len"])
        vals, idx = encoder.topk_probs(encoder.forward(
            params, jnp.asarray(ids), jnp.asarray(mask), cfg), 5)
        data["ref_logits"].append(
            ref_enc.logits(dict(ENC, dtype=dtype), model_id, texts))
        data["indices"].append(np.asarray(idx, np.int64))
        data["scores"].append(np.asarray(vals, np.float64))
        data["model"].append(np.full(len(texts), k))
    return ref_enc.compare(**{k: np.concatenate(v) for k, v in data.items()})


def test_float32_program_and_reference_agree_to_rounding():
    got = served_against_reference("float32", "none")
    assert got["top5_logprob_bias_rms"] < 1e-5
    assert got["top5_logprob_scatter_rms"] < 1e-5
    assert got["top5_prob_gap_max"] < 1e-4


def test_bf16_is_inside_the_tolerance_and_the_int8_control_outside():
    sound = served_against_reference("bfloat16", "none")
    control = served_against_reference("bfloat16", "int8")
    assert sound["top5_logprob_bias_rms"] <= BIAS_TOLERANCE / 2, sound
    assert control["top5_logprob_bias_rms"] >= 1.5 * BIAS_TOLERANCE, control
    # int8 moves all rows of a model alike: the scatter stays where it was.
    assert control["top5_logprob_scatter_rms"] < \
        2 * sound["top5_logprob_scatter_rms"] + 1e-4


# ---- the arithmetic of the numbers compared ------------------------------

def synthetic(n_models=3, rows=40, classes=50, k=5, seed=0):
    rng = np.random.default_rng(seed)
    base = rng.normal(size=(n_models, classes))
    model = np.repeat(np.arange(n_models), rows)
    logits = base[model] + 0.01 * rng.normal(size=(len(model), classes))
    logp = ref_enc.log_softmax(logits)
    indices = np.argsort(-logp, axis=-1)[:, :k]
    scores = np.exp(np.take_along_axis(logp, indices, axis=-1))
    return {"ref_logits": logits.astype(np.float32), "indices": indices,
            "scores": scores, "model": model}


def test_compare_is_zero_on_the_references_own_answers():
    got = ref_enc.compare(**synthetic())
    assert got["top5_logprob_bias_rms"] < 1e-6
    assert got["top5_logprob_scatter_rms"] < 1e-6
    assert got["top5_prob_gap_max"] < 1e-6


def test_scores_off_alike_for_every_row_are_bias():
    data = synthetic()
    got = ref_enc.compare(**dict(data, scores=data["scores"] * 0.9))
    assert got["top5_logprob_bias_rms"] == pytest.approx(-np.log(0.9), rel=1e-3)
    assert got["top5_logprob_scatter_rms"] < 1e-6
    assert got["top5_prob_gap_max"] == pytest.approx(0.1, rel=1e-3)


def test_answers_of_other_rows_are_scatter():
    data = synthetic()
    order = np.arange(len(data["model"]))
    for m in np.unique(data["model"]):        # shuffle inside each model
        sel = np.where(data["model"] == m)[0]
        order[sel] = np.random.default_rng(1).permutation(sel)
    got = ref_enc.compare(**dict(data, indices=data["indices"][order],
                                 scores=data["scores"][order]))
    assert got["top5_logprob_scatter_rms"] > 0.008
    assert got["top5_logprob_bias_rms"] < 0.004


def test_a_wrong_class_is_the_widest_gap():
    data = synthetic()
    worst = np.argmin(data["ref_logits"][0])
    indices = data["indices"].copy()
    indices[0, 0] = worst
    got = ref_enc.compare(**dict(data, indices=indices))
    assert got["top5_prob_gap_max"] > 0.5
