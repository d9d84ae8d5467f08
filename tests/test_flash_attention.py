"""The Pallas flash-attention kernel must agree with dense attention.

Runs in interpreter mode on the CPU test mesh (the identical kernel compiles
via Mosaic on real TPU — same-program-different-backend). Covers multi-tile
streaming (Lk > block_k), padded keys, broadcast masks, fully-masked rows, and
the dense fallback for off-contract shapes.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from agent_tpu.kernels import flash_attention
from agent_tpu.models import layers


def _qkvm(B=2, H=2, Lq=16, Lk=16, D=8, pad_tail=0, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, H, Lq, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, Lk, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, Lk, D)), dtype=jnp.float32)
    mask_1d = np.ones((B, Lk), dtype=np.int32)
    if pad_tail:
        mask_1d[:, -pad_tail:] = 0
    mask = jnp.asarray(mask_1d)[:, None, None, :]
    return q, k, v, mask


def _check(got, q, k, v, mask, rtol=2e-5, atol=2e-5):
    want = np.asarray(layers.dot_product_attention(q, k, v, mask))
    np.testing.assert_allclose(np.asarray(got), want, rtol=rtol, atol=atol)


def test_flash_matches_dense_single_tile():
    q, k, v, mask = _qkvm(pad_tail=3)
    _check(flash_attention(q, k, v, mask, min_key_len=0, interpret=True), q, k, v, mask)


def test_flash_matches_dense_multi_tile_streaming():
    """Lq and Lk both larger than the tile → real streaming-softmax carry."""
    q, k, v, mask = _qkvm(Lq=32, Lk=48, D=8, pad_tail=5, seed=1)
    got = flash_attention(q, k, v, mask, block_q=16, block_k=16, min_key_len=0, interpret=True)
    _check(got, q, k, v, mask)


def test_flash_broadcast_mask_and_cross_lengths():
    q, k, v, _ = _qkvm(Lq=16, Lk=32, seed=2)
    shared = np.ones((1, 1, 1, 32), dtype=np.int32)
    shared[..., -7:] = 0
    shared = jnp.asarray(shared)
    got = flash_attention(q, k, v, shared, block_q=16, block_k=16,
                          min_key_len=0, interpret=True)
    _check(got, q, k, v, shared)


def test_flash_fully_masked_row_is_zero_not_nan():
    q, k, v, mask = _qkvm(seed=3)
    mask = mask.at[1].set(0)
    got = np.asarray(flash_attention(q, k, v, mask, min_key_len=0, interpret=True))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[1], np.zeros_like(got[1]))
    _check(flash_attention(q, k, v, mask, min_key_len=0, interpret=True)[0][None],
           q[0][None], k[0][None], v[0][None], mask[0][None])


def test_flash_falls_back_on_causal_mask():
    q, k, v, _ = _qkvm()
    causal = jnp.asarray(layers.causal_mask(16))
    got = np.asarray(flash_attention(q, k, v, causal, min_key_len=0, interpret=True))
    want = np.asarray(layers.dot_product_attention(q, k, v, causal))
    np.testing.assert_array_equal(got, want)


def test_flash_falls_back_on_indivisible_lengths():
    q, k, v, mask = _qkvm(Lq=10, Lk=10)  # 10 % 16 != 0 after min() → bq=10 ok
    # Make it actually indivisible: force tile 16 on Lk=10 via explicit blocks.
    got = np.asarray(
        flash_attention(q[:, :, :7], k, v, mask, block_q=4, min_key_len=0, interpret=True)
    )
    want = np.asarray(layers.dot_product_attention(q[:, :, :7], k, v, mask))
    np.testing.assert_array_equal(got, want)


def test_flash_bfloat16_inputs():
    q, k, v, mask = _qkvm(pad_tail=2, seed=4)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = np.asarray(
        flash_attention(qb, kb, vb, mask, min_key_len=0, interpret=True)
    ).astype(np.float32)
    want = np.asarray(
        layers.dot_product_attention(qb, kb, vb, mask)
    ).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_mesh_flash_preserves_dp_sharding():
    """shard_map-wrapped kernel must keep the batch dp-sharded (the bare
    pallas_call has no GSPMD rule and would replicate the full batch)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agent_tpu.kernels import make_flash_attention
    from agent_tpu.runtime.mesh import build_mesh

    mesh = build_mesh(jax.devices()[:8], {"dp": 4, "tp": 2})
    fn = make_flash_attention(mesh)
    q, k, v, mask = _qkvm(B=8, H=4, Lq=16, Lk=16, D=8, pad_tail=3)
    shard = NamedSharding(mesh, P("dp", "tp", None, None))
    qs = jax.device_put(q, shard)
    ks = jax.device_put(k, shard)
    vs = jax.device_put(v, shard)
    ms = jax.device_put(mask, NamedSharding(mesh, P("dp", None, None, None)))
    out = jax.jit(fn)(qs, ks, vs, ms)
    assert out.sharding.spec == P("dp", "tp", None, None), out.sharding
    _check(out, q, k, v, mask)
    # Indivisible heads (H=3 over tp=2) → dense fallback, still correct.
    got = fn(q[:, :3], k[:, :3], v[:, :3], mask)
    _check(got, q[:, :3], k[:, :3], v[:, :3], mask)


def _encoder_program(cfg, attn_fn=None):
    """``encoder.forward`` of a config as ONE program: ``(params, ids,
    mask)``; eagerly it is a compile a primitive a shape (tests/README.md).
    Traced a shape and a tree of weights."""
    import jax

    from agent_tpu.models import encoder

    kw = {} if attn_fn is None else {"attn_fn": attn_fn}
    return jax.jit(lambda p, ids, mask: encoder.forward(p, ids, mask, cfg,
                                                        **kw))


def test_encoder_forward_with_flash_matches_dense():
    from agent_tpu.models import encoder

    cfg = encoder.EncoderConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=16, n_classes=10, dtype="float32",
    )
    params = encoder.init_params(cfg, model_id="flash-test")
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(0, 64, size=(4, 16)), dtype=jnp.int32)
    mask = np.ones((4, 16), dtype=np.int32)
    mask[:, 12:] = 0
    mask = jnp.asarray(mask)

    def attn(q, k, v, m):
        return flash_attention(q, k, v, m, min_key_len=0, interpret=True)

    dense_logits = _encoder_program(cfg)(params, ids, mask)
    flash_logits = _encoder_program(cfg, attn)(params, ids, mask)
    np.testing.assert_allclose(
        np.asarray(flash_logits), np.asarray(dense_logits),
        rtol=5e-5, atol=5e-5,
    )


# ---------------------------------------------------------------------------
# Trainable kernel (custom_vjp: Pallas forward AND backward)
# ---------------------------------------------------------------------------

import functools

import jax

from agent_tpu.kernels import flash_attention_trainable


def _train_attn(**kw):
    return functools.partial(
        flash_attention_trainable, min_key_len=0, interpret=True, **kw
    )


def _grads(attn_fn, q, k, v, mask, g):
    def loss(q, k, v):
        return jnp.sum(attn_fn(q, k, v, mask) * g)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def test_trainable_forward_equals_inference_kernel():
    """Same streaming-softmax math → bit-identical forward outputs."""
    q, k, v, mask = _qkvm(Lq=32, Lk=48, pad_tail=5, seed=6)
    got = flash_attention_trainable(
        q, k, v, mask, block_q=16, block_k=16, min_key_len=0, interpret=True
    )
    want = flash_attention(
        q, k, v, mask, block_q=16, block_k=16, min_key_len=0, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_trainable_grads_match_dense_multi_tile():
    """dq/dk/dv from the streaming backward kernels == autodiff through the
    dense path, with real tile streaming (Lq, Lk > blocks) and padded keys."""
    q, k, v, mask = _qkvm(Lq=32, Lk=48, D=8, pad_tail=5, seed=7)
    g = jnp.asarray(
        np.random.default_rng(8).normal(size=q.shape), dtype=jnp.float32
    )
    flash = _grads(_train_attn(block_q=16, block_k=16), q, k, v, mask, g)
    dense = _grads(layers.dot_product_attention, q, k, v, mask, g)
    for got, want in zip(flash, dense):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )


def test_trainable_grads_bfloat16():
    q, k, v, mask = _qkvm(Lq=32, Lk=32, pad_tail=3, seed=9)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    g = jnp.asarray(
        np.random.default_rng(10).normal(size=q.shape), dtype=jnp.bfloat16
    )
    flash = _grads(_train_attn(block_q=16, block_k=16), qb, kb, vb, mask, g)
    dense = _grads(layers.dot_product_attention, qb, kb, vb, mask, g)
    for got, want in zip(flash, dense):
        np.testing.assert_allclose(
            np.asarray(got).astype(np.float32),
            np.asarray(want).astype(np.float32),
            rtol=5e-2, atol=5e-2,
        )


def test_trainable_fully_masked_row_grads_finite():
    """Documented divergence: a no-keys row contributes ZERO gradient on the
    flash path (dense backpropagates through its uniform-softmax guard);
    gradients must stay finite, never NaN."""
    q, k, v, mask = _qkvm(seed=11)
    mask = mask.at[1].set(0)
    g = jnp.ones_like(q)
    dq, dk, dv = _grads(_train_attn(), q, k, v, mask, g)
    for a in (dq, dk, dv):
        assert np.isfinite(np.asarray(a)).all()
    np.testing.assert_array_equal(np.asarray(dq[1]), 0.0)


def test_trainable_off_contract_falls_back_differentiable():
    """Causal mask → dense fallback; autodiff must flow through it."""
    q, k, v, _ = _qkvm()
    causal = jnp.asarray(layers.causal_mask(16))
    g = jnp.ones_like(q)
    flash = _grads(_train_attn(), q, k, v, causal, g)
    dense = _grads(layers.dot_product_attention, q, k, v, causal, g)
    for got, want in zip(flash, dense):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_trainable_selection_counter_ticks():
    import importlib

    fa_mod = importlib.import_module("agent_tpu.kernels.flash_attention")
    q, k, v, mask = _qkvm()
    before = fa_mod.SELECTION_COUNTS.get("flash_train", 0)
    flash_attention_trainable(q, k, v, mask, min_key_len=0, interpret=True)
    assert fa_mod.SELECTION_COUNTS["flash_train"] == before + 1


def test_trainable_under_remat_and_train_step():
    """The custom_vjp must compose with jax.checkpoint and the full train
    step: one flash-attn SGD step == one dense SGD step (loss and params)."""
    from agent_tpu.models import encoder
    from agent_tpu.models.train import make_train_step

    cfg = encoder.EncoderConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=16, n_classes=10, dtype="float32",
    )
    rng = np.random.default_rng(12)
    ids = jnp.asarray(rng.integers(0, 64, size=(4, 16)), dtype=jnp.int32)
    mask = np.ones((4, 16), dtype=np.int32)
    mask[:, 12:] = 0
    mask = jnp.asarray(mask)
    labels = jnp.asarray(rng.integers(0, 10, size=(4,)), dtype=jnp.int32)

    losses, states = [], []
    for attn_fn in (layers.dot_product_attention, _train_attn()):
        params = encoder.init_params(cfg, model_id="trainable-flash")
        init_state, step = make_train_step(cfg, remat=True, attn_fn=attn_fn)
        opt_state = init_state(params)
        params, opt_state, loss = step(params, opt_state, ids, mask, labels)
        losses.append(float(loss))
        states.append(params)
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
    flat_d = jax.tree_util.tree_leaves(states[0])
    flat_f = jax.tree_util.tree_leaves(states[1])
    for a, b in zip(flat_d, flat_f):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_mesh_trainable_grads_on_dp_tp_mesh():
    """shard_map + custom_vjp: sharded backward == dense autodiff."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from agent_tpu.kernels import make_flash_attention_trainable
    from agent_tpu.runtime.mesh import build_mesh

    mesh = build_mesh(jax.devices()[:8], {"dp": 4, "tp": 2})
    fn = make_flash_attention_trainable(mesh)
    q, k, v, mask = _qkvm(B=8, H=4, Lq=16, Lk=16, D=8, pad_tail=3, seed=13)
    g = jnp.asarray(
        np.random.default_rng(14).normal(size=q.shape), dtype=jnp.float32
    )
    shard = NamedSharding(mesh, P("dp", "tp", None, None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    ms = jax.device_put(mask, NamedSharding(mesh, P("dp", None, None, None)))

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v, ms) * g)

    flash = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(qs, ks, vs)
    dense = _grads(layers.dot_product_attention, q, k, v, mask, g)
    for got, want in zip(flash, dense):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )


def test_selects_flash_train_gate_and_mesh_divisibility():
    """The training-path predicate: 512 gate (below serving's 2048) AND the
    mesh wrapper's dp/tp divisibility fallback — the remat-off decision in
    bench's train leg rides on exactly this logic."""
    import importlib

    from agent_tpu.runtime.mesh import build_mesh

    fa_mod = importlib.import_module("agent_tpu.kernels.flash_attention")
    sel = fa_mod.selects_flash_train
    assert sel(512, batch=128, n_heads=12)
    assert not sel(256, batch=128, n_heads=12)        # below training gate
    assert not sel(520, batch=128, n_heads=12)        # tile-indivisible
    assert fa_mod.selects_flash(512, min_key_len=None) is False  # serving: 2048

    mesh = build_mesh(jax.devices("cpu")[:8], {"dp": 4, "tp": 2})
    assert sel(512, batch=128, n_heads=12, mesh=mesh)
    assert not sel(512, batch=126, n_heads=12, mesh=mesh)  # B % dp != 0
    assert not sel(512, batch=128, n_heads=11, mesh=mesh)  # H % tp != 0
    one = build_mesh(jax.devices("cpu")[:1], {"dp": 1})
    assert sel(512, batch=1, n_heads=3, mesh=one)     # size-1 mesh: no wrapper


# ---------------------------------------------------------------------------
# Whole-row kernel (lane-dense [B, L, H*D] operands, below the flash gate)
# ---------------------------------------------------------------------------

import importlib

fa_mod = importlib.import_module("agent_tpu.kernels.flash_attention")


def _lane_dense_case(B, L, H, D, seed):
    """bf16 [B, L, H*D] operands; ragged key padding, row 1 with no key at
    all, the last row full."""
    rng = np.random.default_rng(seed)
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H * D)), dtype=jnp.bfloat16)
        for _ in range(3)
    )
    lens = rng.integers(1, L + 1, size=B)
    lens[1], lens[-1] = 0, L
    mask = (np.arange(L)[None, :] < lens[:, None]).astype(np.int32)
    return q, k, v, jnp.asarray(mask)[:, None, None, :], lens


def _heads(t, H):
    B, L, HD = t.shape
    return t.reshape(B, L, H, HD // H).transpose(0, 2, 1, 3)


def _unheads(t):
    B, H, L, D = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B, L, H * D)


@pytest.mark.parametrize("L,H,D", [
    (64, 12, 64), (128, 12, 64), (256, 12, 64), (512, 12, 64),
    (128, 3, 128),      # an odd head count: one head a 128-lane group
    (64, 2, 64),
])
def test_whole_row_matches_dense_and_float32(L, H, D):
    B = 3 if L >= 256 else 4
    q, k, v, mask, lens = _lane_dense_case(B, L, H, D, seed=L + H)
    got = np.asarray(fa_mod.whole_row_attention(
        q, k, v, mask, n_heads=H, interpret=True)).astype(np.float32)
    assert got.shape == (B, L, H * D) and np.isfinite(got).all()
    # A row with no key: 0, as the streaming kernel has it (dense gives V's
    # mean there; the encoder pools such a row to nothing either way).
    np.testing.assert_array_equal(got[1], np.zeros_like(got[1]))
    real = lens > 0
    dense = np.asarray(_unheads(layers.dot_product_attention(
        _heads(q, H), _heads(k, H), _heads(v, H), mask))).astype(np.float32)
    np.testing.assert_allclose(got[real], dense[real], rtol=2e-2, atol=2e-2)
    f32 = np.asarray(_unheads(layers.dot_product_attention(
        *(_heads(t.astype(jnp.float32), H) for t in (q, k, v)), mask)))
    # bf16 result of f32 statistics: within a bf16 ulp or two of the float32
    # answer, and no further from it than the dense bf16 path is.
    err = np.abs(got[real] - f32[real]).max()
    assert err <= 2e-2
    assert err <= np.abs(dense[real] - f32[real]).max() * 1.5 + 1e-3


@pytest.mark.parametrize("operands", ["three", "column_blocks"])
@pytest.mark.parametrize("rows,groups", [(1, 1), (2, 3), (4, 6)])
def test_whole_row_tile_geometry_does_not_change_the_answer(rows, groups,
                                                            operands):
    """``column_blocks``: the ONE [B, L, 3*H*D] array ``[Q | K | V]`` in the
    three operands' place; the index maps find each a third of the lanes on,
    whatever the lane groups a step takes."""
    q, k, v, mask, _ = _lane_dense_case(4, 64, 12, 64, seed=9)
    want = fa_mod.whole_row_attention(q, k, v, mask, n_heads=12,
                                      interpret=True)
    if operands == "column_blocks":
        q, k, v = jnp.concatenate([q, k, v], axis=-1), None, None
    got = fa_mod.whole_row_attention(
        q, k, v, mask, n_heads=12, rows_per_step=rows,
        groups_per_step=groups, interpret=True)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("batch,length,groups,want", [
    (256, 512, 6, (1, 6)), (512, 64, 6, (8, 6)), (1024, 128, 6, (4, 6)),
    (6, 64, 6, (6, 6)), (7, 64, 6, (7, 6)), (128, 1024, 6, (1, 6)),
    (1, 64, 1, (1, 1)),
])
def test_whole_row_tiles_come_from_the_shapes(batch, length, groups, want):
    rows, g = fa_mod._whole_row_tiles(batch, length, groups)
    assert (rows, g) == want
    assert batch % rows == 0 and groups % g == 0


_KP = "key_padding"
_BF16 = jnp.bfloat16


@pytest.mark.parametrize("lq,lk,H,D,mask_kind,dtype,want", [
    (512, 512, 12, 64, _KP, _BF16, "whole_row"),
    (64, 64, 12, 64, _KP, _BF16, "whole_row"),
    (128, 128, 3, 128, _KP, _BF16, "whole_row"),
    (1, 512, 12, 64, _KP, _BF16, "dense"),          # Lq != Lk: a decode step
    (512, 512, 12, 64, "causal", _BF16, "dense"),
    (512, 512, 12, 32, _KP, _BF16, "dense"),        # D neither 64 nor 128
    (512, 512, 3, 64, _KP, _BF16, "dense"),         # odd heads of 64: a half group
    (512, 512, 12, 64, _KP, jnp.float32, "dense"),
    (32, 32, 12, 64, _KP, _BF16, "dense"),          # under the kernel's range
    (192, 192, 12, 64, _KP, _BF16, "dense"),        # not whole 128-lane score tiles
    (2048, 2048, 12, 64, _KP, _BF16, "flash"),      # the streaming kernel's
    (4096, 4096, 12, 128, _KP, _BF16, "flash"),
])
def test_attention_path_predicate(lq, lk, H, D, mask_kind, dtype, want):
    B = 2
    shape = (B, 1, 1, lk) if mask_kind == _KP else (1, 1, lq, lk)
    mask = jax.ShapeDtypeStruct(shape, jnp.int32)
    whole = fa_mod.selects_whole_row(
        lq, lk, H, D,
        key_padding=layers.is_key_padding_mask(mask, B, lk), dtype=dtype)
    flash = (lq == lk and mask_kind == _KP and fa_mod.selects_flash(lk))
    got = "whole_row" if whole else "flash" if flash else "dense"
    assert got == want
    assert not (whole and flash)        # one path a call


def _fused_attn_fn(dp=1, tp=1):
    """What ``TpuRuntime.attention_fn()`` builds on a chip, in interpret
    mode: the [B, H, L, D] kernel that also declares ``whole_row``."""
    from agent_tpu.kernels import make_flash_attention
    from agent_tpu.runtime.mesh import build_mesh

    mesh = build_mesh(jax.devices()[:dp * tp], {"dp": dp, "tp": tp})
    return make_flash_attention(mesh, interpret=True)


def _parent_attn_fn():
    """The same without the declaration: the parent commit's ``attn_fn``."""
    return functools.partial(fa_mod.flash_attention, interpret=True)


def _check_limits(got_logits, want_logits):
    """The benchmark check's three numbers (``benchmarks/reference``), on
    top-5 log-probabilities: bias 0.006, scatter 0.012, gap 0.07."""
    got = jax.nn.log_softmax(jnp.asarray(got_logits, jnp.float32))
    want = jax.nn.log_softmax(jnp.asarray(want_logits, jnp.float32))
    _, top = jax.lax.top_k(want, 5)
    e = np.asarray(jnp.take_along_axis(got, top, 1)
                   - jnp.take_along_axis(want, top, 1))
    assert abs(e.mean()) <= 0.006
    assert np.sqrt(np.mean((e - e.mean()) ** 2)) <= 0.012
    gap = np.abs(np.exp(np.asarray(jnp.take_along_axis(got, top, 1)))
                 - np.exp(np.asarray(jnp.take_along_axis(want, top, 1))))
    assert gap.max() <= 0.07


def _ids_mask(B, L, vocab, seed):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(4, vocab, size=(B, L)), dtype=jnp.int32)
    lens = rng.integers(L // 4, L + 1, size=B)
    lens[0] = L
    mask = (np.arange(L)[None, :] < lens[:, None]).astype(np.int32)
    return ids, jnp.asarray(mask)


@pytest.mark.parametrize("tree", ["three_leaf", "fused_qkv"])
@pytest.mark.parametrize("L", [64, 128])
def test_encoder_forward_whole_row_within_the_benchmark_limits(L, tree):
    """``fused_qkv``: the serving layout of the same weights (one ``wqkv``
    leaf a block): one Q, K, V matmul, its result into the kernel as column
    blocks. Every output column is the dot product it was, so the kernel's
    answers are the three-leaf tree's, well inside the bf16 tolerance."""
    from agent_tpu.models import encoder
    from agent_tpu.ops._model_common import maybe_fuse_qkv_params

    cfg = encoder.EncoderConfig(
        vocab_size=260, d_model=256, n_heads=4, n_layers=2, d_ff=512,
        max_len=128, n_classes=50,
    )
    three_leaf = params = encoder.init_params(cfg, model_id="whole-row-test")
    if tree == "fused_qkv":     # fused in place: a tree of its own
        params = maybe_fuse_qkv_params(
            encoder.init_params(cfg, model_id="whole-row-test"), "encoder",
            cfg, 1)
    ids, mask = _ids_mask(4, L, 260, seed=L)
    before = dict(fa_mod.SELECTION_COUNTS)
    on_the_kernel = _encoder_program(cfg, _fused_attn_fn())
    in_float32 = _encoder_program(cfg.scaled(dtype="float32"))
    fused = on_the_kernel(params, ids, mask)
    assert (fa_mod.SELECTION_COUNTS["whole_row"]
            - before.get("whole_row", 0)) == cfg.n_layers
    if tree == "fused_qkv":
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(on_the_kernel(three_leaf, ids, mask)),
            atol=2e-2)
        # The XLA path in float32: bit for bit.
        np.testing.assert_array_equal(
            np.asarray(in_float32(params, ids, mask)),
            np.asarray(in_float32(three_leaf, ids, mask)))
        params = three_leaf     # the controls below: the canonical tree
    dense = _encoder_program(cfg)(params, ids, mask)
    f32 = in_float32(params, ids, mask)
    _check_limits(fused, dense)
    _check_limits(fused, f32)


def _random_bert_params(cfg, seed):
    """The pytree ``bert.from_state_dict`` builds, from a seed."""
    rng = np.random.default_rng(seed)
    d, ff = cfg.hidden_size, cfg.intermediate_size

    def dense(d_in, d_out):
        return {"w": rng.normal(0, d_in ** -0.5, (d_in, d_out)).astype(
            np.float32), "b": np.zeros((d_out,), np.float32)}

    def ln():
        return {"scale": np.ones((d,), np.float32),
                "bias": np.zeros((d,), np.float32)}

    return {
        "embed": {
            "word": rng.normal(0, 0.5, (cfg.vocab_size, d)).astype(np.float32),
            "pos": rng.normal(0, 0.5, (cfg.max_position, d)).astype(np.float32),
            "type": rng.normal(0, 0.5, (cfg.type_vocab, d)).astype(np.float32),
            "ln": ln(),
        },
        "layers": [
            {"attn": {"q": dense(d, d), "k": dense(d, d), "v": dense(d, d),
                      "o": dense(d, d), "ln": ln()},
             "ffn": {"i": dense(d, ff), "o": dense(ff, d), "ln": ln()}}
            for _ in range(cfg.num_layers)
        ],
        "pooler": dense(d, d),
        "head": dense(d, cfg.num_labels),
    }


def test_bert_forward_whole_row_within_the_benchmark_limits():
    from agent_tpu.models import bert

    cfg = bert.BertConfig(
        vocab_size=120, hidden_size=128, num_layers=2, num_heads=2,
        intermediate_size=256, max_position=64, num_labels=20,
    )
    params = _random_bert_params(cfg, seed=3)
    ids, mask = _ids_mask(4, 64, 120, seed=11)
    before = fa_mod.SELECTION_COUNTS.get("whole_row", 0)
    fused = bert.forward(params, ids, mask, cfg, attn_fn=_fused_attn_fn())
    assert fa_mod.SELECTION_COUNTS["whole_row"] - before == cfg.num_layers
    _check_limits(fused, bert.forward(params, ids, mask, cfg))


def test_whole_row_on_a_dp_tp_mesh_keeps_its_shards():
    """Batch over dp, the head-major lanes over tp: each chip runs the kernel
    on its own rows and heads (a bare pallas_call would replicate both)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    attn_fn = _fused_attn_fn(dp=2, tp=2)
    entry = attn_fn.whole_row
    B, L, H, D = 4, 64, 4, 64
    q, k, v, mask, lens = _lane_dense_case(B, L, H, D, seed=21)
    assert entry.selects(B, L, L, H, D, mask, jnp.bfloat16)
    assert not entry.selects(3, L, L, H, D, mask[:3], jnp.bfloat16)  # 3 % dp
    assert not entry.selects(B, L, L, 2, D, mask, jnp.bfloat16)      # half a group a chip
    mesh = entry._shard.keywords["mesh"]
    shard = NamedSharding(mesh, P("dp", None, "tp"))
    out = jax.jit(functools.partial(entry, n_heads=H))(
        *(jax.device_put(t, shard) for t in (q, k, v)), mask)
    assert out.sharding.spec == P("dp", None, "tp"), out.sharding
    want = fa_mod.whole_row_attention(q, k, v, mask, n_heads=H,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def _attention_params(d_model=128, H=2, seed=0):
    return layers.init_attention(jax.random.PRNGKey(seed), d_model, H)


def _decode_step_call(attn_fn):
    """One self-attention decode step over a KV cache (Lq 1, Lk 64)."""
    p = _attention_params()
    x = jnp.ones((2, 1, 128), jnp.bfloat16)
    cache = {"k": jnp.zeros((2, 2, 64, 64), jnp.bfloat16),
             "v": jnp.zeros((2, 2, 64, 64), jnp.bfloat16)}
    mask = (jnp.arange(64) <= 5).astype(jnp.int32)[None, None, None, :]

    def f(p, x, cache):
        return layers.attention(p, x, x, mask, jnp.bfloat16, cache=cache,
                                cache_index=jnp.int32(5), attn_fn=attn_fn)
    return f, (p, x, cache)


def _cross_attention_call(attn_fn):
    """Cross-attention of 16 target positions over 64 source positions."""
    p = _attention_params(seed=1)
    x_q = jnp.ones((2, 16, 128), jnp.bfloat16)
    x_kv = jnp.ones((2, 64, 128), jnp.bfloat16)
    mask = jnp.ones((2, 1, 1, 64), jnp.int32)

    def f(p, x_q, x_kv):
        return layers.attention(p, x_q, x_kv, mask, jnp.bfloat16,
                                attn_fn=attn_fn)
    return f, (p, x_q, x_kv)


def _causal_self_attention_call(attn_fn):
    p = _attention_params(seed=2)
    x = jnp.ones((2, 64, 128), jnp.bfloat16)
    mask = jnp.asarray(layers.causal_mask(64))

    def f(p, x):
        return layers.attention(p, x, x, mask, jnp.bfloat16, attn_fn=attn_fn)
    return f, (p, x)


def _quantized_self_attention_call(attn_fn):
    from agent_tpu.models import quant

    p = quant._quantize_attn(_attention_params(seed=3))     # int8 leaves
    x = jnp.ones((2, 64, 128), jnp.bfloat16)
    mask = jnp.ones((2, 1, 1, 64), jnp.int32)

    def f(p, x):
        return layers.attention(p, x, x, mask, jnp.bfloat16, attn_fn=attn_fn)
    return f, (p, x)


@pytest.mark.parametrize("call", [
    _decode_step_call, _cross_attention_call, _causal_self_attention_call,
    _quantized_self_attention_call,
])
def test_calls_outside_the_predicate_lower_to_unchanged_hlo(call):
    """An ``attn_fn`` that declares ``whole_row`` changes nothing for a call
    the predicate does not take: the lowered program is, text for text, what
    the parent's ``attn_fn`` (no declaration) lowers to, and no block of it
    is counted as ``whole_row``."""
    before = fa_mod.SELECTION_COUNTS.get("whole_row", 0)
    f, args = call(_fused_attn_fn())
    declared = jax.jit(f).lower(*args).as_text()
    f, args = call(_parent_attn_fn())
    parent = jax.jit(f).lower(*args).as_text()
    assert declared == parent
    assert "tpu_custom_call" not in declared and "pallas" not in declared
    assert fa_mod.SELECTION_COUNTS.get("whole_row", 0) == before


def test_self_attention_inside_the_predicate_lowers_to_the_kernel():
    """The control of the test above: the same comparison DOES tell a call
    the predicate takes."""
    p = _attention_params(seed=4)
    x = jnp.ones((2, 64, 128), jnp.bfloat16)
    mask = jnp.ones((2, 1, 1, 64), jnp.int32)

    def lowered(attn_fn):
        return jax.jit(lambda p, x: layers.attention(
            p, x, x, mask, jnp.bfloat16, attn_fn=attn_fn)).lower(p, x).as_text()

    assert lowered(_fused_attn_fn()) != lowered(_parent_attn_fn())


def test_attention_blocks_counter_whole_row_and_dense():
    """``attention_blocks_traced_total{path}``: a traced 256 x 512 BERT-base
    program ticks ``whole_row`` once a block (12) and ``dense`` not at all; a
    decode step ticks ``dense``. Tracing only: nothing is compiled or run."""
    from agent_tpu.models import encoder
    from agent_tpu.obs import trace as obs_trace
    from agent_tpu.obs.metrics import MetricsRegistry

    def ticks(registry, path):
        fam = registry.snapshot().get("attention_blocks_traced_total")
        return sum(s["value"] for s in (fam or {"series": []})["series"]
                   if s["labels"].get("path") == path)

    cfg = encoder.EncoderConfig(d_model=768, n_heads=12, n_layers=12,
                                d_ff=3072, max_len=512, n_classes=1000)
    params = jax.eval_shape(lambda: encoder.init_params(cfg, "bert-base"))
    ids = jax.ShapeDtypeStruct((256, 512), jnp.int32)
    attn_fn = _fused_attn_fn()
    registry = MetricsRegistry()
    with obs_trace.use_context(obs_trace.TraceContext(registry=registry)):
        jax.eval_shape(
            lambda p, i, m: encoder.forward(p, i, m, cfg, attn_fn=attn_fn),
            params, ids, ids)
        assert ticks(registry, "whole_row") == cfg.n_layers
        assert ticks(registry, "dense") == 0
        f, args = _decode_step_call(attn_fn)
        jax.eval_shape(f, *args)
        assert ticks(registry, "whole_row") == cfg.n_layers
        assert ticks(registry, "dense") == 1
