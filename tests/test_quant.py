"""INT8 quantized execution (models.quant) — the TPU-native successor of the
reference's INT8 TFLite device story (reference ``ops/_tpu_runtime.py:23-31``,
``ops/map_classify_tpu.py:53-74``): same serving contract, W8A8 matmuls.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from agent_tpu.config import DeviceConfig
from agent_tpu.models import encoder, layers, quant
from agent_tpu.runtime.context import OpContext
from agent_tpu.runtime.runtime import TpuRuntime


def _runtime(mesh_shape):
    return TpuRuntime(
        config=DeviceConfig(tpu_disabled=True, mesh_shape=mesh_shape),
        devices=jax.devices("cpu")[:8],
    )


@pytest.fixture(scope="module")
def rt():
    return _runtime({"dp": 8, "tp": 1, "sp": 1})


@pytest.fixture(scope="module")
def rt_tp():
    return _runtime({"dp": 4, "tp": 2, "sp": 1})


# ---- kernel-level numerics ----


def test_qdense_close_to_dense():
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w": jax.random.normal(k1, (64, 96), dtype=jnp.float32) * 0.1,
        "b": jax.random.normal(k2, (96,), dtype=jnp.float32) * 0.01,
    }
    x = jax.random.normal(k3, (8, 64), dtype=jnp.float32)
    want = layers.dense(p, x, jnp.float32)
    got = quant.qdense(quant.quantize_dense(p), x, jnp.float32)
    # W8A8 relative error budget: ~1% of the output scale.
    err = np.abs(np.asarray(got - want))
    assert err.max() <= 0.02 * np.abs(np.asarray(want)).max() + 1e-6


def test_qproj_in_out_close_to_einsum():
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    B, L, d, H, E = 2, 16, 32, 4, 8
    w_in = jax.random.normal(k1, (d, H, E), dtype=jnp.float32) * 0.1
    w_out = jax.random.normal(k2, (H, E, d), dtype=jnp.float32) * 0.1
    x = jax.random.normal(k3, (B, L, d), dtype=jnp.float32)

    want_in = jnp.einsum("bld,dhe->bhle", x, w_in)
    got_in = quant.qproj_in(quant.quantize_weight(w_in, (0,)), x, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got_in), np.asarray(want_in),
        atol=0.02 * float(jnp.abs(want_in).max()),
    )

    h = jnp.asarray(want_in)  # [B, H, L, E]
    want_out = jnp.einsum("bhle,hed->bld", h, w_out)
    got_out = quant.qproj_out(
        quant.quantize_weight(w_out, (0, 1)), h, jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(got_out), np.asarray(want_out),
        atol=0.02 * float(jnp.abs(want_out).max()),
    )


def test_weight_roundtrip_exact_for_representable():
    """Weights already on the int8 grid must survive quantization exactly."""
    scale = 0.5 / 127.0
    w = (np.arange(-127, 128, dtype=np.float32) * scale).reshape(1, -1)
    w = np.repeat(w, 4, axis=0)
    q = quant.quantize_weight(w, (0,))
    back = q["w_q"].astype(np.float32) * q["w_scale"]
    np.testing.assert_allclose(back, w, rtol=1e-6)


def test_validate_quant():
    assert quant.validate_quant("int8") == "int8"
    assert quant.validate_quant("w8a16") == "w8a16"
    assert quant.validate_quant("none") == "none"
    with pytest.raises(ValueError, match="quant"):
        quant.validate_quant("int4")


# ---- W8A16 weight-only kernels ----


def test_wdense_matches_dequantized_dense():
    """wdense must equal the plain dense over the DEQUANTIZED table — the
    only approximation in W8A16 is the weight rounding itself (activations
    are untouched), so against w8·scale the match is float-exact-ish."""
    key = jax.random.PRNGKey(2)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w": jax.random.normal(k1, (64, 96), dtype=jnp.float32) * 0.1,
        "b": jax.random.normal(k2, (96,), dtype=jnp.float32) * 0.01,
    }
    x = jax.random.normal(k3, (8, 64), dtype=jnp.float32)
    q = quant.quantize_dense_w8a16(p)
    assert q["w8"].dtype == np.int8
    deq = {"w": q["w8"].astype(np.float32) * q["w_scale"], "b": q["b"]}
    want = layers.dense(deq, x, jnp.float32)
    got = quant.wdense(q, x, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
    # And it tracks the ORIGINAL weights within the int8 rounding budget.
    orig = layers.dense(p, x, jnp.float32)
    err = np.abs(np.asarray(got - orig))
    assert err.max() <= 0.02 * np.abs(np.asarray(orig)).max() + 1e-6


def test_wproj_in_out_close_to_einsum():
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    B, L, d, H, E = 2, 16, 32, 4, 8
    w_in = jax.random.normal(k1, (d, H, E), dtype=jnp.float32) * 0.1
    w_out = jax.random.normal(k2, (H, E, d), dtype=jnp.float32) * 0.1
    x = jax.random.normal(k3, (B, L, d), dtype=jnp.float32)

    want_in = jnp.einsum("bld,dhe->bhle", x, w_in)
    got_in = quant.wproj_in(
        quant.quantize_weight_w8a16(w_in, (0,)), x, jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(got_in), np.asarray(want_in),
        atol=0.02 * float(jnp.abs(want_in).max()),
    )

    h = jnp.asarray(want_in)  # [B, H, L, E]
    want_out = jnp.einsum("bhle,hed->bld", h, w_out)
    got_out = quant.wproj_out(
        quant.quantize_weight_w8a16(w_out, (0, 1)), h, jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(got_out), np.asarray(want_out),
        atol=0.02 * float(jnp.abs(want_out).max()),
    )


def test_w8a16_leaf_conventions_are_disjoint():
    """The two leaf predicates must never both claim a leaf — dispatch in
    layers.dense/_proj_* relies on it."""
    w = np.ones((4, 8), np.float32)
    q8 = quant.quantize_weight(w, (0,))
    w8 = quant.quantize_weight_w8a16(w, (0,))
    assert quant.is_quantized(q8) and not quant.is_weight_only(q8)
    assert quant.is_weight_only(w8) and not quant.is_quantized(w8)
    # Same int8 table, same scale — only the leaf key differs.
    np.testing.assert_array_equal(q8["w_q"], w8["w8"])
    np.testing.assert_array_equal(q8["w_scale"], w8["w_scale"])


# ---- model-level numerics ----


@pytest.mark.parametrize("mode", ["int8", "w8a16"])
def test_encoder_forward_quantized_tracks_f32(mode):
    cfg = encoder.EncoderConfig(
        d_model=64, n_heads=4, n_layers=3, d_ff=128, max_len=64,
        n_classes=50, dtype="float32",
    )
    params = encoder.init_params(cfg, model_id="quant-numerics")
    qparams = quant.quantize_encoder(params, mode)
    rng = np.random.default_rng(0)
    B, L = 16, 32
    ids = rng.integers(4, 200, size=(B, L)).astype(np.int32)
    mask = np.ones((B, L), dtype=np.int32)
    # ONE program, traced a tree of weights (eagerly, a compile a primitive).
    forward = jax.jit(lambda p, i, m: encoder.forward(p, i, m, cfg))
    want = np.asarray(forward(params, ids, mask))
    got = np.asarray(forward(qparams, ids, mask))
    # Per-row cosine similarity of the logit vectors stays ~1 through the
    # whole quantized stack.
    cos = (want * got).sum(-1) / (
        np.linalg.norm(want, axis=-1) * np.linalg.norm(got, axis=-1)
    )
    assert cos.min() > 0.999
    # And the decision (top-1 over 50 classes) agrees for most rows.
    agree = (want.argmax(-1) == got.argmax(-1)).mean()
    assert agree >= 0.9


# (mode, how far its logits may stray, in units of the bf16 control's own
# distance from float32)
@pytest.mark.parametrize("mode,control_multiple", [("int8", 3.0), ("w8a16", 1.0)])
def test_seq2seq_decode_step_quantized_within_the_dtype_control(
        mode, control_multiple):
    """Two decode steps through the KV cache (the continuous engine's own
    step): the quantized logits track float32, pick its tokens, and stray
    from it by no more than a small multiple of what computing the SAME
    weights in bfloat16 does. Weight-only int8 stays under the control
    itself: it adds no more than compute-dtype noise to a decode."""
    from dataclasses import replace

    from agent_tpu.models import seq2seq
    from agent_tpu.models.tokenizer import BOS_ID

    cfg = seq2seq.Seq2SeqConfig(
        d_model=64, n_heads=4, n_enc_layers=2, n_dec_layers=2, d_ff=128,
        max_src_len=64, max_tgt_len=16, dtype="float32",
    )
    params = seq2seq.init_params(cfg, model_id="quant-decode-step")
    rng = np.random.default_rng(3)
    B, Ls = 16, 32
    ids = rng.integers(4, cfg.vocab_size, size=(B, Ls)).astype(np.int32)
    mask = np.ones((B, Ls), dtype=np.int32)

    def second_step_logits(p, c):
        step = seq2seq.make_positional_step(c)
        caches = seq2seq.make_cache_factory(c)(B)
        enc = seq2seq.encode(p, ids, mask, c)
        bos = jnp.full((B,), BOS_ID, jnp.int32)
        first, caches = step(p, bos, jnp.zeros((B,), jnp.int32), caches,
                             enc, mask)
        tok = jnp.argmax(first, -1).astype(jnp.int32)
        logits, _ = step(p, tok, jnp.ones((B,), jnp.int32), caches, enc, mask)
        return np.asarray(logits, np.float32)

    want = second_step_logits(params, cfg)
    control = second_step_logits(params, replace(cfg, dtype="bfloat16"))
    got = second_step_logits(
        quant.quantize_for_family("seq2seq", params, mode), cfg)
    cos = (want * got).sum(-1) / (
        np.linalg.norm(want, axis=-1) * np.linalg.norm(got, axis=-1)
    )
    assert cos.min() > 0.999
    assert (want.argmax(-1) == got.argmax(-1)).all()
    assert np.abs(got - want).max() <= (
        control_multiple * np.abs(control - want).max())


# ---- op contract ----


QCFG = {
    "d_model": 64, "n_heads": 4, "n_layers": 2, "d_ff": 128,
    "max_len": 64, "n_classes": 32, "dtype": "float32",
}


def test_classify_int8_through_op(rt):
    from agent_tpu.ops import get_op

    classify = get_op("map_classify_tpu")
    texts = [f"int8 contract row {i}" for i in range(8)]
    base = {
        "texts": texts, "topk": 3, "model_path": "quant-op",
        "allow_fallback": False, "result_format": "columnar",
    }
    a = classify(
        {**base, "model_config": QCFG}, OpContext(runtime=rt)
    )
    b = classify(
        {**base, "model_config": {**QCFG, "quant": "int8"}},
        OpContext(runtime=rt),
    )
    assert a["ok"] and b["ok"]
    assert len(b["indices"]) == len(texts) and len(b["indices"][0]) == 3
    # int8 compiles/caches under its own key (distinct cfg fingerprint).
    keys = list(rt.cache._cache.keys())
    quant_keys = [
        k for k in keys
        if k[0] == "map_classify_tpu" and ("quant", "int8") in k[-1]
    ]
    assert quant_keys, f"no int8-keyed executable in {keys}"
    # Decisions track the f32 run on a comfortable majority of rows.
    top1_a = [row[0] for row in a["indices"]]
    top1_b = [row[0] for row in b["indices"]]
    agree = np.mean([x == y for x, y in zip(top1_a, top1_b)])
    assert agree >= 0.75


def test_classify_int8_bad_value_soft_error(rt):
    from agent_tpu.ops import get_op

    out = get_op("map_classify_tpu")(
        {"texts": ["x"], "model_config": {**QCFG, "quant": "fp4"}},
        OpContext(runtime=rt),
    )
    assert out["ok"] is False and "quant" in out["error"]


def _classify_keys_of_call(rt, payload):
    """The ``map_classify_tpu`` executable keys one call looks up. No key
    holds a model id (the weights are arguments of the program), so a call's
    own keys are read off its lookups, not picked out of the cache by name."""
    from agent_tpu.ops import get_op

    keys, real = [], rt.compiled
    rt.compiled = lambda key, build: (keys.append(key), real(key, build))[1]
    try:
        out = get_op("map_classify_tpu")(payload, OpContext(runtime=rt))
    finally:
        del rt.compiled
    assert out["ok"] is True
    return [k for k in keys if k[0] == "map_classify_tpu"]


def test_classify_int8_env_switch(rt, monkeypatch):
    """TPU_QUANT=int8 turns quantized serving on without payload changes."""
    monkeypatch.setenv("TPU_QUANT", "int8")
    keys = _classify_keys_of_call(
        rt, {"texts": ["env switch row"], "topk": 3, "model_config": QCFG,
             "model_path": "quant-env", "allow_fallback": False})
    assert keys and all(
        k[1] == "encoder" and ("quant", "int8") in k[-1] for k in keys)


def test_classify_int8_tp_matches_replicated(rt, rt_tp):
    """Quantized serving on a tp=2 mesh: the int8 tables shard per the
    transformed spec tree and the decisions match the replicated int8 run."""
    from agent_tpu.ops import get_op

    classify = get_op("map_classify_tpu")
    payload = {
        "texts": [f"int8 tp row {i}" for i in range(16)],
        "topk": 5,
        "model_config": {**QCFG, "n_heads": 8, "quant": "int8"},
        "model_path": "quant-tp",
        "allow_fallback": False,
        "result_format": "columnar",
    }
    a = classify(dict(payload), OpContext(runtime=rt))
    b = classify(dict(payload), OpContext(runtime=rt_tp))
    assert a["ok"] and b["ok"]
    assert a["indices"] == b["indices"]
    np.testing.assert_allclose(a["scores"], b["scores"], rtol=1e-4, atol=1e-6)


def test_int8_params_actually_sharded_and_int8(rt_tp):
    """On the tp mesh the resident tables are int8 dtype AND head-sharded —
    the transfer/HBM win and the tp win must compose, not exclude."""
    from agent_tpu.models.encoder import EncoderConfig
    from agent_tpu.ops import get_op
    from agent_tpu.ops._model_common import cfg_key

    cfg_dict = {**QCFG, "n_heads": 8, "quant": "int8"}
    get_op("map_classify_tpu")(
        {"texts": ["shard check"], "model_config": cfg_dict,
         "model_path": "quant-shardcheck", "allow_fallback": False},
        OpContext(runtime=rt_tp),
    )
    cfg = EncoderConfig(**cfg_dict)
    key = (
        "params",
        f"quant-shardcheck#encoder#{hash(cfg_key(cfg)) & 0xFFFFFFFF:08x}",
        "tp",
    )
    params = rt_tp._params.get_or_build(
        key, lambda: pytest.fail("int8 params not cached under the tp key")
    )
    wq = params["blocks"][0]["attn"]["wq"]
    assert wq["w_q"].dtype == jnp.int8
    shard = wq["w_q"].sharding.shard_shape(wq["w_q"].shape)
    assert shard[1] == wq["w_q"].shape[1] // 2      # heads over tp=2
    scale_shard = wq["w_scale"].sharding.shard_shape(wq["w_scale"].shape)
    assert scale_shard[0] == wq["w_scale"].shape[0] // 2  # scales follow


# ---- summarize families ----


def test_summarize_int8_through_op(rt):
    from agent_tpu.ops import get_op

    summarize = get_op("map_summarize")
    cfg = {
        "d_model": 32, "n_heads": 4, "n_enc_layers": 1, "n_dec_layers": 1,
        "d_ff": 64, "max_src_len": 64, "max_tgt_len": 16, "dtype": "float32",
    }
    payload = {
        "texts": ["an int8 document about quantized decoding " * 3] * 4,
        "max_length": 8,
        "model_config": {**cfg, "quant": "int8"},
        "model_path": "quant-sum",
    }
    out = summarize(dict(payload), OpContext(runtime=rt))
    assert out["ok"] is True
    assert len(out["summaries"]) == 4
    assert all(isinstance(s, str) for s in out["summaries"])
    keys = [
        k for k in rt.cache._cache.keys()
        if k[0] == "map_summarize" and k[1] == "quant-sum"
    ]
    assert keys and all(("quant", "int8") in k[-1] for k in keys)


def test_summarize_int8_tp_matches_replicated(rt, rt_tp):
    from agent_tpu.ops import get_op

    summarize = get_op("map_summarize")
    cfg = {
        "d_model": 32, "n_heads": 4, "n_enc_layers": 1, "n_dec_layers": 1,
        "d_ff": 64, "max_src_len": 64, "max_tgt_len": 16, "dtype": "float32",
        "quant": "int8",
    }
    payload = {
        "texts": ["a long document about int8 tensor parallel " * 3] * 4,
        "max_length": 8,
        "model_config": cfg,
        "model_path": "quant-sum-tp",
    }
    a = summarize(dict(payload), OpContext(runtime=rt))
    b = summarize(dict(payload), OpContext(runtime=rt_tp))
    assert a["ok"] and b["ok"]
    assert a["summaries"] == b["summaries"]


def test_t5_bart_quantize_trees_close():
    """Quantized BART/T5 teacher-forced logits track the f32 forward — the
    whole-tree transformers hit every hot matmul without breaking shapes."""
    from agent_tpu.models import bart as bart_mod
    from agent_tpu.models import layers as L

    cfg = bart_mod.BartConfig(
        vocab_size=64, d_model=32, n_heads=4, n_enc_layers=1, n_dec_layers=1,
        d_ff=64, max_position=64, dtype="float32",
    )
    rng = np.random.default_rng(1)

    def dense(i, o):
        return {
            "w": rng.normal(size=(i, o), scale=0.1).astype(np.float32),
            "b": rng.normal(size=(o,), scale=0.01).astype(np.float32),
        }

    def ln(d):
        return {
            "scale": np.ones(d, np.float32), "bias": np.zeros(d, np.float32)
        }

    def attn():
        d = cfg.d_model
        return {"q": dense(d, d), "k": dense(d, d), "v": dense(d, d),
                "o": dense(d, d)}

    def blk(cross):
        p = {"self": attn(), "ln1": ln(cfg.d_model),
             "fc1": dense(cfg.d_model, cfg.d_ff),
             "fc2": dense(cfg.d_ff, cfg.d_model), "ln2": ln(cfg.d_model)}
        if cross:
            p["cross"] = attn()
            p["ln_x"] = ln(cfg.d_model)
        return p

    params = {
        "embed": rng.normal(size=(cfg.vocab_size, cfg.d_model), scale=0.1)
        .astype(np.float32),
        "final_logits_bias": np.zeros(cfg.vocab_size, np.float32),
        "enc": {
            "pos": rng.normal(
                size=(cfg.max_position + 2, cfg.d_model), scale=0.02
            ).astype(np.float32),
            "ln_emb": ln(cfg.d_model),
            "layers": [blk(False)],
        },
        "dec": {
            "pos": rng.normal(
                size=(cfg.max_position + 2, cfg.d_model), scale=0.02
            ).astype(np.float32),
            "ln_emb": ln(cfg.d_model),
            "layers": [blk(True)],
        },
    }
    src = rng.integers(4, 60, size=(2, 10)).astype(np.int32)
    mask = np.ones((2, 10), np.int32)
    tgt = rng.integers(4, 60, size=(2, 6)).astype(np.int32)
    enc = bart_mod.encode(params, src, mask, cfg)
    want = np.asarray(bart_mod.decode_full(params, tgt, enc, mask, cfg))
    qp = quant.quantize_bart(params)
    enc_q = bart_mod.encode(qp, src, mask, cfg)
    got = np.asarray(bart_mod.decode_full(qp, tgt, enc_q, mask, cfg))
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max() + 1e-3
    # Unquantized leaves pass through untouched.
    assert qp["embed"] is params["embed"]
    assert L.count_params(params) > 0  # tree still walkable


# ---- W8A16 op contract ----


def test_classify_w8a16_through_op(rt):
    from agent_tpu.ops import get_op

    classify = get_op("map_classify_tpu")
    texts = [f"w8a16 contract row {i}" for i in range(8)]
    base = {
        "texts": texts, "topk": 3, "model_path": "w8a16-op",
        "allow_fallback": False, "result_format": "columnar",
    }
    a = classify({**base, "model_config": QCFG}, OpContext(runtime=rt))
    b = classify(
        {**base, "model_config": {**QCFG, "quant": "w8a16"}},
        OpContext(runtime=rt),
    )
    assert a["ok"] and b["ok"]
    assert len(b["indices"]) == len(texts) and len(b["indices"][0]) == 3
    # w8a16 compiles/caches under its own key (distinct cfg fingerprint).
    keys = list(rt.cache._cache.keys())
    w_keys = [
        k for k in keys
        if k[0] == "map_classify_tpu" and ("quant", "w8a16") in k[-1]
    ]
    assert w_keys, f"no w8a16-keyed executable in {keys}"
    top1_a = [row[0] for row in a["indices"]]
    top1_b = [row[0] for row in b["indices"]]
    agree = np.mean([x == y for x, y in zip(top1_a, top1_b)])
    assert agree >= 0.75


def test_summarize_w8a16_through_op(rt):
    from agent_tpu.ops import get_op

    summarize = get_op("map_summarize")
    cfg = {
        "d_model": 32, "n_heads": 4, "n_enc_layers": 1, "n_dec_layers": 1,
        "d_ff": 64, "max_src_len": 64, "max_tgt_len": 16, "dtype": "float32",
    }
    payload = {
        "texts": ["a w8a16 document about weight-only decoding " * 3] * 4,
        "max_length": 8,
        "num_beams": 4,  # the decode mode the W8A16 path targets
        "model_config": {**cfg, "quant": "w8a16"},
        "model_path": "w8a16-sum",
    }
    out = summarize(dict(payload), OpContext(runtime=rt))
    assert out["ok"] is True
    assert len(out["summaries"]) == 4
    assert all(isinstance(s, str) for s in out["summaries"])
    keys = [
        k for k in rt.cache._cache.keys()
        if k[0] == "map_summarize" and k[1] == "w8a16-sum"
    ]
    assert keys and all(("quant", "w8a16") in k[-1] for k in keys)


def test_summarize_w8a16_tp_matches_replicated(rt, rt_tp):
    from agent_tpu.ops import get_op

    summarize = get_op("map_summarize")
    cfg = {
        "d_model": 32, "n_heads": 4, "n_enc_layers": 1, "n_dec_layers": 1,
        "d_ff": 64, "max_src_len": 64, "max_tgt_len": 16, "dtype": "float32",
        "quant": "w8a16",
    }
    payload = {
        "texts": ["a long document about w8a16 tensor parallel " * 3] * 4,
        "max_length": 8,
        "model_config": cfg,
        "model_path": "w8a16-sum-tp",
    }
    a = summarize(dict(payload), OpContext(runtime=rt))
    b = summarize(dict(payload), OpContext(runtime=rt_tp))
    assert a["ok"] and b["ok"]
    assert a["summaries"] == b["summaries"]


def test_w8a16_params_actually_sharded_and_int8(rt_tp):
    """On the tp mesh the resident W8A16 tables are int8 dtype AND
    head-sharded — the spec-tree twin transforms the same paths as int8's,
    so the HBM-bytes win and the tp win compose."""
    from agent_tpu.models.encoder import EncoderConfig
    from agent_tpu.ops import get_op
    from agent_tpu.ops._model_common import cfg_key

    cfg_dict = {**QCFG, "n_heads": 8, "quant": "w8a16"}
    get_op("map_classify_tpu")(
        {"texts": ["w8a16 shard check"], "model_config": cfg_dict,
         "model_path": "w8a16-shardcheck", "allow_fallback": False},
        OpContext(runtime=rt_tp),
    )
    cfg = EncoderConfig(**cfg_dict)
    key = (
        "params",
        f"w8a16-shardcheck#encoder#{hash(cfg_key(cfg)) & 0xFFFFFFFF:08x}",
        "tp",
    )
    params = rt_tp._params.get_or_build(
        key, lambda: pytest.fail("w8a16 params not cached under the tp key")
    )
    wq = params["blocks"][0]["attn"]["wq"]
    assert set(wq) == {"w8", "w_scale"}
    assert wq["w8"].dtype == jnp.int8
    shard = wq["w8"].sharding.shard_shape(wq["w8"].shape)
    assert shard[1] == wq["w8"].shape[1] // 2        # heads over tp=2
    scale_shard = wq["w_scale"].sharding.shard_shape(wq["w_scale"].shape)
    assert scale_shard[0] == wq["w_scale"].shape[0] // 2  # scales follow


def test_w8a16_env_switch(rt, monkeypatch):
    """TPU_QUANT=w8a16 turns weight-only serving on without payload
    changes — the same env path as int8."""
    monkeypatch.setenv("TPU_QUANT", "w8a16")
    keys = _classify_keys_of_call(
        rt, {"texts": ["w8a16 env switch row"], "topk": 3,
             "model_config": QCFG, "model_path": "w8a16-env",
             "allow_fallback": False})
    assert keys and all(
        k[1] == "encoder" and ("quant", "w8a16") in k[-1] for k in keys)


def test_bad_env_quant_fails_shard_not_soft(rt, monkeypatch):
    """A TPU_QUANT typo is a worker deployment misconfig: the shard must FAIL
    (→ controller retry / visible error), not soft-drop as caller bad_input."""
    from agent_tpu.ops import get_op

    monkeypatch.setenv("TPU_QUANT", "int8x")
    with pytest.raises(RuntimeError, match="TPU_QUANT"):
        get_op("map_classify_tpu")(
            {"texts": ["x"], "model_config": QCFG},
            OpContext(runtime=rt),
        )
    with pytest.raises(RuntimeError, match="TPU_QUANT"):
        get_op("map_summarize")(
            {"texts": ["y"], "max_length": 4,
             "model_config": {"d_model": 32, "n_heads": 4, "n_enc_layers": 1,
                              "n_dec_layers": 1, "d_ff": 64,
                              "max_src_len": 64, "max_tgt_len": 8}},
            OpContext(runtime=rt),
        )
