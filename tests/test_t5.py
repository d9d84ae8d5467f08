"""HF-T5 family (``models/t5.py``): relative-position-bias attention,
RMSNorm, and tied-head logits must reproduce ``transformers``' reference
outputs — the checkpoint family BASELINE.json names for summarize."""

import numpy as np
import pytest

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

import jax  # noqa: E402

from agent_tpu.models import t5  # noqa: E402

TINY = dict(
    vocab_size=64, d_model=32, d_kv=8, num_heads=4, d_ff=64,
    num_layers=2, num_decoder_layers=2, feed_forward_proj="relu",
)


def _torch_model(**overrides):
    torch.manual_seed(0)
    cfg = transformers.T5Config(**{**TINY, **overrides})
    return transformers.T5ForConditionalGeneration(cfg).eval()


def _import(model, tmp_path, name):
    d = tmp_path / name
    model.save_pretrained(str(d), safe_serialization=False)
    return t5.load_hf_dir(str(d), dtype="float32")


def test_bucket_function_matches_transformers():
    from transformers.models.t5.modeling_t5 import T5Attention

    rel = np.arange(-40, 41).reshape(1, -1).repeat(3, axis=0)
    rel = rel + np.array([[-5], [0], [7]])
    for bidir in (True, False):
        want = T5Attention._relative_position_bucket(
            torch.tensor(rel), bidirectional=bidir, num_buckets=32,
            max_distance=128,
        ).numpy()
        got = np.asarray(
            t5.relative_position_bucket(np.asarray(rel), bidir, 32, 128)
        )
        np.testing.assert_array_equal(got, want)


def test_forward_matches_transformers(tmp_path):
    model = _torch_model()
    cfg, params = _import(model, tmp_path, "relu_tied")
    assert cfg.tie_word_embeddings and not cfg.gated_ffn

    rng = np.random.default_rng(0)
    src = rng.integers(2, cfg.vocab_size, (3, 9)).astype(np.int32)
    mask = np.ones((3, 9), dtype=np.int32)
    mask[1, 6:] = 0
    src[1, 6:] = cfg.pad_id
    tgt = rng.integers(2, cfg.vocab_size, (3, 5)).astype(np.int32)
    tgt[:, 0] = cfg.decoder_start_id

    with torch.no_grad():
        want = model(
            input_ids=torch.tensor(src, dtype=torch.long),
            attention_mask=torch.tensor(mask, dtype=torch.long),
            decoder_input_ids=torch.tensor(tgt, dtype=torch.long),
        ).logits.numpy()
    enc = t5.encode(params, src, mask, cfg)
    got = np.asarray(
        jax.jit(lambda p, t, e, m: t5.decode_full(p, t, e, m, cfg))(
            params, tgt, enc, mask
        )
    )
    np.testing.assert_allclose(got, want, atol=3e-4)


def test_gated_untied_variant_matches(tmp_path):
    model = _torch_model(
        feed_forward_proj="gated-gelu", tie_word_embeddings=False
    )
    cfg, params = _import(model, tmp_path, "gated_untied")
    assert cfg.gated_ffn and not cfg.tie_word_embeddings
    assert "lm_head" in params

    rng = np.random.default_rng(1)
    src = rng.integers(2, cfg.vocab_size, (2, 7)).astype(np.int32)
    mask = np.ones((2, 7), dtype=np.int32)
    tgt = np.full((2, 4), cfg.decoder_start_id, dtype=np.int32)
    tgt[:, 1:] = rng.integers(2, cfg.vocab_size, (2, 3))
    with torch.no_grad():
        want = model(
            input_ids=torch.tensor(src, dtype=torch.long),
            attention_mask=torch.tensor(mask, dtype=torch.long),
            decoder_input_ids=torch.tensor(tgt, dtype=torch.long),
        ).logits.numpy()
    enc = t5.encode(params, src, mask, cfg)
    got = np.asarray(t5.decode_full(params, tgt, enc, mask, cfg))
    np.testing.assert_allclose(got, want, atol=3e-4)


def test_greedy_generation_matches_transformers(tmp_path):
    model = _torch_model()
    cfg, params = _import(model, tmp_path, "gen")
    rng = np.random.default_rng(2)
    src = rng.integers(2, cfg.vocab_size, (2, 6)).astype(np.int32)
    mask = np.ones((2, 6), dtype=np.int32)
    T = 7
    with torch.no_grad():
        want = model.generate(
            input_ids=torch.tensor(src, dtype=torch.long),
            attention_mask=torch.tensor(mask, dtype=torch.long),
            max_new_tokens=T, num_beams=1, do_sample=False, min_length=0,
            decoder_start_token_id=cfg.decoder_start_id,  # this transformers
            # version requires it explicitly for a from-config T5
        ).numpy()
    toks, _ = jax.jit(
        lambda p, i, m: t5.generate(p, i, m, cfg, T)
    )(params, src, mask)
    toks = np.asarray(toks)
    want_gen = want[:, 1:]  # HF row = [decoder_start, generated...]
    n = min(want_gen.shape[1], T)
    np.testing.assert_array_equal(toks[:, :n], want_gen[:, :n])


def test_beam_runs_and_returns_shapes(tmp_path):
    model = _torch_model()
    cfg, params = _import(model, tmp_path, "beam")
    src = np.full((2, 5), 9, dtype=np.int32)
    mask = np.ones((2, 5), dtype=np.int32)
    toks, lengths = t5.generate(params, src, mask, cfg, 5, num_beams=3)
    assert np.asarray(toks).shape == (2, 5)
    assert np.asarray(lengths).shape == (2,)


def test_spm_gate_gives_actionable_error(tmp_path):
    with pytest.raises((RuntimeError, ValueError),
                       match="sentencepiece|spiece"):
        t5.hf_spm(str(tmp_path))


def test_t5_dir_through_op_gives_sentencepiece_gate(tmp_path):
    """Without the sentencepiece package, a T5 checkpoint through
    map_summarize must fail with the actionable gate error (not serve
    random weights, not crash obscurely)."""
    pytest.importorskip("agent_tpu.ops")
    try:
        import sentencepiece  # noqa: F401

        pytest.skip("sentencepiece installed; gate not reachable")
    except ImportError:
        pass

    from agent_tpu.ops import get_op
    from agent_tpu.runtime.context import OpContext
    from agent_tpu.runtime.runtime import get_runtime

    model = _torch_model()
    d = tmp_path / "t5_ckpt"
    model.save_pretrained(str(d), safe_serialization=False)
    with pytest.raises(RuntimeError, match="sentencepiece"):
        get_op("map_summarize")(
            {"texts": ["row text"], "model_path": str(d), "max_length": 4},
            OpContext(runtime=get_runtime()),
        )


def test_flash_t5_kernel_matches_dense(tmp_path):
    """The fused T5 kernel (bias computed per tile in VMEM, interpret mode
    on CPU) must equal the dense bias-attention path, padding included."""
    import jax.numpy as jnp

    from agent_tpu.kernels.flash_attention import flash_attention_t5

    model = _torch_model()
    d = tmp_path / "flash_ckpt"
    model.save_pretrained(str(d), safe_serialization=False)
    cfg, params = t5.load_hf_dir(str(d), dtype="float32")

    rng = np.random.default_rng(3)
    B, H, L, D = 2, cfg.n_heads, 16, cfg.d_kv
    q = jnp.asarray(rng.standard_normal((B, H, L, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, L, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, L, D)), dtype=jnp.float32)
    mask = np.ones((B, L), dtype=np.int32)
    mask[1, 10:] = 0
    rel_bias = jnp.asarray(params["enc"]["rel_bias"])

    got = flash_attention_t5(
        q, k, v, jnp.asarray(mask)[:, None, None, :], rel_bias,
        bidirectional=True, max_distance=cfg.rel_max_distance,
        scale=1.0, min_key_len=0, block_q=8, block_k=8, interpret=True,
    )
    assert got is not None

    pos = jnp.arange(L, dtype=jnp.int32)
    bias = t5._position_bias(rel_bias, pos, pos, True, cfg) \
        + t5._pad_bias(jnp.asarray(mask))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) + bias
    probs = jax.nn.softmax(scores, axis=-1)
    want = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5
    )


def test_encode_flash_equals_dense(tmp_path, monkeypatch):
    """t5.encode with the kernel routed in (gate lowered for the test) must
    reproduce the dense encoder exactly — logits-level equivalence."""
    import importlib

    # The kernels package re-exports the flash_attention FUNCTION, which
    # shadows the submodule attribute — resolve the module itself.
    fa = importlib.import_module("agent_tpu.kernels.flash_attention")

    model = _torch_model()
    d = tmp_path / "flash_enc_ckpt"
    model.save_pretrained(str(d), safe_serialization=False)
    cfg, params = t5.load_hf_dir(str(d), dtype="float32")

    monkeypatch.setattr(fa, "FLASH_MIN_KEY_LEN", 8)
    rng = np.random.default_rng(4)
    src = rng.integers(2, cfg.vocab_size, (2, 16)).astype(np.int32)
    mask = np.ones((2, 16), dtype=np.int32)
    mask[0, 12:] = 0

    before = dict(fa.SELECTION_COUNTS)
    flash = np.asarray(
        t5.encode(params, src, mask, cfg, kernel=fa.flash_attention_t5)
    )
    assert fa.SELECTION_COUNTS.get("t5_flash", 0) > before.get("t5_flash", 0)
    dense = np.asarray(t5.encode(params, src, mask, cfg))
    np.testing.assert_allclose(flash, dense, atol=3e-5)


def test_unsupported_feed_forward_proj_fails_loudly(tmp_path):
    """A checkpoint whose activation we can't honor must FAIL, not silently
    serve a different activation with ok=true (advisor r3, medium)."""
    import json

    cfg = dict(
        model_type="t5", vocab_size=32, d_model=8, d_kv=4, num_heads=2,
        num_layers=1, d_ff=16, feed_forward_proj="gelu",
    )
    p = tmp_path / "config.json"
    p.write_text(json.dumps(cfg))
    with pytest.raises(RuntimeError, match="feed_forward_proj"):
        t5.T5Config.from_hf_json(str(p))
    cfg["feed_forward_proj"] = "gated-silu"
    p.write_text(json.dumps(cfg))
    with pytest.raises(RuntimeError, match="feed_forward_proj"):
        t5.T5Config.from_hf_json(str(p))
    # The two supported values still load.
    cfg["feed_forward_proj"] = "gated-gelu"
    p.write_text(json.dumps(cfg))
    assert t5.T5Config.from_hf_json(str(p)).gated_ffn is True
    cfg["feed_forward_proj"] = "relu"
    p.write_text(json.dumps(cfg))
    assert t5.T5Config.from_hf_json(str(p)).gated_ffn is False


def test_encode_mesh_kernel_on_dp_tp_mesh(tmp_path, monkeypatch):
    """The mesh-aware T5 kernel wrapper (shard_map: batch over dp, heads
    over tp) routed through t5.encode — the PRODUCT wiring
    (``runtime.t5_attention_kernel()`` → ``map_summarize`` → ``generate``)
    — must equal the dense encoder and tick the t5_flash counter."""
    import importlib

    from jax.sharding import NamedSharding, PartitionSpec as P

    from agent_tpu.kernels.flash_attention import make_flash_attention_t5
    from agent_tpu.runtime.mesh import build_mesh

    fa = importlib.import_module("agent_tpu.kernels.flash_attention")
    model = _torch_model()
    d = tmp_path / "mesh_enc_ckpt"
    model.save_pretrained(str(d), safe_serialization=False)
    cfg, params = t5.load_hf_dir(str(d), dtype="float32")

    monkeypatch.setattr(fa, "FLASH_MIN_KEY_LEN", 8)
    mesh = build_mesh(jax.devices()[:8], {"dp": 4, "tp": 2})
    kernel = make_flash_attention_t5(mesh)

    rng = np.random.default_rng(5)
    src = rng.integers(2, cfg.vocab_size, (4, 16)).astype(np.int32)
    mask = np.ones((4, 16), dtype=np.int32)
    mask[0, 12:] = 0

    before = dict(fa.SELECTION_COUNTS)
    flash = np.asarray(t5.encode(params, src, mask, cfg, kernel=kernel))
    assert fa.SELECTION_COUNTS.get("t5_flash", 0) > before.get("t5_flash", 0)
    dense = np.asarray(t5.encode(params, src, mask, cfg))
    np.testing.assert_allclose(flash, dense, atol=3e-5)

    # generate() threads the kernel through its encoder pass.
    before = dict(fa.SELECTION_COUNTS)
    toks_k, lens_k = t5.generate(params, src, mask, cfg, 4, kernel=kernel)
    assert fa.SELECTION_COUNTS.get("t5_flash", 0) > before.get("t5_flash", 0)
    toks_d, lens_d = t5.generate(params, src, mask, cfg, 4)
    np.testing.assert_array_equal(np.asarray(toks_k), np.asarray(toks_d))
    np.testing.assert_array_equal(np.asarray(lens_k), np.asarray(lens_d))


def test_beam4_generation_matches_transformers(tmp_path):
    """Beam decode through the T5 plumbing (no forced BOS/EOS — T5's
    natural ending, the finalize-normalization path) must be token-exact
    vs transformers, like the BART twin in tests/test_bart.py."""
    model = _torch_model()
    cfg, params = _import(model, tmp_path, "beam4")
    rng = np.random.default_rng(6)
    src = rng.integers(2, cfg.vocab_size, (3, 7)).astype(np.int32)
    mask = np.ones((3, 7), dtype=np.int32)
    mask[1, 5:] = 0
    for lp, T in ((1.0, 8), (2.0, 6)):
        with torch.no_grad():
            want = model.generate(
                input_ids=torch.tensor(src, dtype=torch.long),
                attention_mask=torch.tensor(mask, dtype=torch.long),
                max_new_tokens=T, num_beams=4, do_sample=False,
                min_length=0, length_penalty=lp, early_stopping=False,
                decoder_start_token_id=cfg.decoder_start_id,
            ).numpy()[:, 1:]
        toks, _ = jax.jit(
            lambda p, i, m, T=T, lp=lp: t5.generate(
                p, i, m, cfg, T, num_beams=4, length_penalty=lp
            )
        )(params, src, mask)
        toks = np.asarray(toks)
        n = min(want.shape[1], T)
        np.testing.assert_array_equal(toks[:, :n], want[:, :n])
