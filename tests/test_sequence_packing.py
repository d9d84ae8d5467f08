"""Sequence packing in the classify program (PR 29): a shard's short rows
packed several to a program row under a segment mask. The packer as a pure
function, ``encoder.forward``'s segment form against the padded one, the
whole-row kernel with segment ids (interpret mode), the op end to end on a
512-row shard of ``drain-short``'s distribution, the predicate, the counters,
and that a window's shards build no executable warm-up did not."""

import functools
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agent_tpu.models import encoder, layers
from agent_tpu.obs import trace as obs_trace
from agent_tpu.obs.metrics import MetricsRegistry
from agent_tpu.ops import _model_common as mc
from agent_tpu.ops import get_op
from agent_tpu.ops import map_classify_tpu as op
from agent_tpu.runtime.context import OpContext
from agent_tpu.runtime.runtime import get_runtime

fa = importlib.import_module("agent_tpu.kernels.flash_attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- (a) the packer, a pure function -------------------------------------

def _unpack(chunk: mc.PackedChunk, n: int):
    """Every chunk row's tokens, read back out of the packed layout."""
    G = chunk.segment_lengths.shape[1]
    prow, seg = chunk.row_slots[:n] // G, chunk.row_slots[:n] % G
    starts = np.cumsum(chunk.segment_lengths, axis=1) - chunk.segment_lengths
    return [chunk.ids[p, starts[p, s]:starts[p, s] + chunk.segment_lengths[p, s]]
            for p, s in zip(prow, seg)]


def _padded(lengths, L, B=None, seed=0):
    rng = np.random.default_rng(seed)
    n = len(lengths)
    ids = np.zeros((B or n, L), dtype=np.uint8)
    for r, ln in enumerate(lengths):
        ids[r, :ln] = rng.integers(1, 250, size=ln)
    full = np.zeros(B or n, dtype=np.int32)
    full[:n] = lengths
    return ids, full


PACK_CASES = {
    "random_short": (np.random.default_rng(1).integers(1, 40, size=512), 64, 512),
    "zero_one_and_exactly_L": (
        np.array([0, 1, 64, 0, 1, 64] + [9] * 250, dtype=np.int64), 64, 256),
    "padding_rows_past_n": (np.random.default_rng(2).integers(8, 30, size=300),
                            64, 512),
    "long_bucket_mixed": (np.random.default_rng(3).integers(1, 200, size=256),
                          512, 256),
}


@pytest.mark.parametrize("case", list(PACK_CASES))
def test_pack_places_every_row_once_and_restores_order(case):
    lengths, L, B = PACK_CASES[case]
    n = len(lengths)
    ids, full = _padded(lengths, L, B)
    chunk = mc.pack_padded_chunk(ids, full, n, dp=1)
    assert chunk is not None
    P, G = chunk.segment_lengths.shape
    assert chunk.ids.shape == (P, L) and chunk.ids.dtype == ids.dtype
    assert chunk.slice_rows == mc.packed_slice_rows(L, 1) and P % chunk.slice_rows == 0
    assert P < B and chunk.n == n and len(chunk.row_slots) == B
    # No program row over L; every row exactly once, whole, in its own slot.
    assert (chunk.segment_lengths.sum(axis=1) <= L).all()
    assert len(set(chunk.row_slots[:n].tolist())) == n
    assert chunk.segment_lengths.sum() == lengths.sum()
    for r, tokens in enumerate(_unpack(chunk, n)):
        np.testing.assert_array_equal(tokens, ids[r, :lengths[r]])
    # Slots past the last real token carry nothing.
    past = np.arange(L)[None, :] >= chunk.segment_lengths.sum(axis=1)[:, None]
    assert not chunk.ids[past].any()
    # The same pack for the same input.
    again = mc.pack_padded_chunk(ids.copy(), full.copy(), n, dp=1)
    for a, b in zip(chunk[:3], again[:3]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("lengths, capacity, segments", [
    ([5, 5, 5, 5], 10, 8), ([10, 10], 10, 8), ([0, 0, 0], 10, 2),
    ([1] * 40, 16, 4), ([7], 16, 2), ([], 16, 2),
    (list(np.random.default_rng(4).integers(0, 65, size=500)), 64, 8),
])
def test_pack_rows_is_a_bin_packing(lengths, capacity, segments):
    where, segment, used = mc.pack_rows(lengths, capacity, segments)
    fill, count = [0] * used, [0] * used
    for r, (b, s) in enumerate(zip(where, segment)):
        fill[b] += lengths[r]
        count[b] += 1
    assert all(f <= capacity for f in fill) and all(1 <= c <= segments for c in count)
    assert sorted(zip(where, segment)) == sorted(
        (b, s) for b in range(used) for s in range(count[b]))
    # Best-fit-decreasing stays within 11/9 of the least any pack needs (+1).
    least = max(-(-sum(lengths) // capacity), -(-len(lengths) // segments))
    assert used <= least * 11 // 9 + 1
    assert mc.pack_rows(lengths, capacity, segments) == (where, segment, used)


def test_pack_rows_refuses_a_row_longer_than_a_program_row():
    with pytest.raises(ValueError):
        mc.pack_rows([4, 17], 16, 4)


@pytest.mark.parametrize("lengths, L, B, why", [
    (np.full(512, 512), 512, 512, "every row fills its bucket"),
    (np.full(64, 20), 64, 64, "a chunk no larger than one slice"),
    (np.array([9]), 64, 1, "a shard of one row"),
    (np.full(8, 100), 2048, 8, "a streaming (flash) length"),
    (np.full(256, 40), 64, 256, "two rows never share 64 slots: 256 program rows"),
])
def test_chunks_that_stay_padded(lengths, L, B, why):
    ids, full = _padded(lengths, L, B)
    assert mc.pack_padded_chunk(ids, full, len(lengths), dp=1) is None, why


def test_slice_rows_divide_the_mesh():
    assert [mc.packed_slice_rows(L, 1) for L in (32, 64, 128, 512, 1024)] == [
        128, 64, 32, 8, 4]
    assert mc.packed_slice_rows(64, 8) == 64 and mc.packed_slice_rows(1024, 8) == 8
    lengths = np.random.default_rng(5).integers(8, 30, size=512)
    chunk = mc.pack_padded_chunk(*_padded(lengths, 1024, 512), 512, dp=8)
    assert chunk.slice_rows == 8 and chunk.ids.shape[0] % 8 == 0


# ---- (b) encoder.forward, packed against padded ----------------------------

CFG = encoder.EncoderConfig(d_model=128, n_heads=2, n_layers=2, d_ff=256,
                            max_len=64, n_classes=24, dtype="float32")


@functools.lru_cache(maxsize=None)
def _forward_programs(cfg, attn_fn):
    """``encoder.forward`` in its padded and its segment form, each ONE
    program a config and attention function (eagerly, a compile a primitive
    a shape: tests/README.md); traced a shape and a tree of weights."""
    return (jax.jit(lambda p, ids, mask: encoder.forward(
                p, ids, mask, cfg, attn_fn=attn_fn)),
            jax.jit(lambda p, ids, seg_lengths, slots: encoder.forward(
                p, ids, None, cfg, attn_fn=attn_fn,
                segment_lengths=seg_lengths, row_slots=slots)))


def _forward_pair(cfg, lengths, L, seed=0, attn_fn=layers.dot_product_attention,
                  edit=None, tree="three_leaf"):
    """(padded logits, packed logits in row order) of the same rows; with
    ``tree="fused"`` on the serving layout of the same weights (one ``wqkv``
    leaf a block, ``_model_common.maybe_fuse_qkv_params``)."""
    params = encoder.init_params(cfg, model_id=f"pack-{seed}")
    if tree == "fused":
        params = mc.maybe_fuse_qkv_params(params, "encoder", cfg, 1)
        assert "wqkv" in params["blocks"][0]["attn"]
    ids, full = _padded(lengths, L, seed=seed)
    if edit is not None:
        edit(ids)
    ids = ids.astype(np.int32) % cfg.vocab_size
    mask = (np.arange(L)[None, :] < full[:, None]).astype(np.int32)
    forward_padded, forward_packed = _forward_programs(cfg, attn_fn)
    padded = forward_padded(params, jnp.asarray(ids), jnp.asarray(mask))
    where, seg, used = mc.pack_rows(list(lengths), L, L // mc.PACKED_MIN_SEGMENT)
    G = L // mc.PACKED_MIN_SEGMENT
    seg_lengths = np.zeros((used, G), np.int32)
    seg_lengths[where, seg] = lengths
    starts = np.cumsum(seg_lengths, axis=1) - seg_lengths
    packed_ids = np.zeros((used, L), np.int32)
    for r, ln in enumerate(lengths):
        a = starts[where[r], seg[r]]
        packed_ids[where[r], a:a + ln] = ids[r, :ln]
    slots = np.asarray(where) * G + np.asarray(seg)
    packed = forward_packed(params, jnp.asarray(packed_ids),
                            jnp.asarray(seg_lengths),
                            jnp.asarray(slots, dtype=jnp.int32))
    return np.asarray(padded), np.asarray(packed), used


@pytest.mark.parametrize("tree", ["three_leaf", "fused"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_packed_equals_padded_float32(seed, tree):
    lengths = np.random.default_rng(seed).integers(0, 65, size=40)
    lengths[:3] = (0, 1, 64)
    padded, packed, used = _forward_pair(CFG, lengths, 64, seed=seed, tree=tree)
    assert used < len(lengths)
    np.testing.assert_allclose(packed, padded, atol=1e-5, rtol=1e-5)
    if tree == "fused":     # the XLA path: bit for bit the three-leaf answers
        for got, want in zip((padded, packed),
                             _forward_pair(CFG, lengths, 64, seed=seed)):
            np.testing.assert_array_equal(got, want)


def _fused_attn_fn():
    """What ``TpuRuntime.attention_fn()`` builds on a chip, interpreted."""
    from agent_tpu.kernels import make_flash_attention
    from agent_tpu.runtime.mesh import build_mesh

    return make_flash_attention(build_mesh(jax.devices()[:1], {"dp": 1}),
                                interpret=True)


@pytest.mark.parametrize("attn, tree", [
    ("dense", "three_leaf"), ("whole_row", "three_leaf"),
    ("dense", "fused"), ("whole_row", "fused"),
])
def test_forward_packed_equals_padded_bfloat16(attn, tree):
    cfg = CFG.scaled(dtype="bfloat16")
    lengths = np.random.default_rng(7).integers(1, 65, size=24)
    before = fa.SELECTION_COUNTS.get("whole_row", 0)
    padded, packed, _ = _forward_pair(
        cfg, lengths, 64, seed=7, tree=tree,
        attn_fn=_fused_attn_fn() if attn == "whole_row"
        else layers.dot_product_attention)
    if attn == "whole_row":     # both programs, every block
        assert fa.SELECTION_COUNTS["whole_row"] - before == 2 * cfg.n_layers
    # The tolerance tests/test_map_classify.py holds two programs of one
    # model to (scores within 1e-3): bf16 rounds differently by layout.
    probs = lambda z: np.asarray(jax.nn.softmax(jnp.asarray(z), axis=-1))  # noqa: E731
    np.testing.assert_allclose(probs(packed), probs(padded), atol=1e-3)
    np.testing.assert_allclose(packed, padded, atol=5e-2)


def test_segment_layout_restarts_positions():
    seg_lengths = jnp.asarray([[3, 0, 2, 0], [8, 0, 0, 0], [0, 0, 0, 0]], jnp.int32)
    ids, pos = encoder.segment_layout(seg_lengths, 8)
    np.testing.assert_array_equal(np.asarray(ids), [
        [1, 1, 1, 3, 3, 0, 0, 0], [1] * 8, [0] * 8])
    np.testing.assert_array_equal(np.asarray(pos), [
        [0, 1, 2, 0, 1, 0, 0, 0], list(range(8)), [0] * 8])
    mask = np.asarray(layers.segment_mask_to_attn(ids))[:, 0]
    assert mask[0, 0, :3].all() and not mask[0, 0, 3:].any()
    assert mask[0, 4, 3:5].all() and not mask[0, 4, :3].any()
    assert not mask[0, 5:].any() and not mask[0, :, 5:].any() and not mask[2].any()


def test_a_neighbours_tokens_change_nothing():
    """Perturb one segment: every other row's logits are bit-equal."""
    lengths = np.array([20, 20, 20, 30, 30, 9, 9, 9], dtype=np.int64)
    _, base, used = _forward_pair(CFG, lengths, 64, seed=3)

    def edit(ids):
        ids[0, :20] = (ids[0, :20].astype(np.int32) * 7 + 3) % 250

    _, moved, _ = _forward_pair(CFG, lengths, 64, seed=3, edit=edit)
    assert used < len(lengths)
    assert not np.array_equal(base[0], moved[0])
    np.testing.assert_array_equal(base[1:], moved[1:])


def _forward_before(params, ids, mask, cfg):
    """``encoder.forward`` as it was before the segment form existed."""
    dtype = cfg.compute_dtype
    L = ids.shape[1]
    x = params["embed"].astype(dtype)[ids] + params["pos"][:L].astype(dtype)[None]
    attn_mask = layers.pad_mask_to_attn(mask)
    aux_total = jnp.float32(0.0)
    for block in params["blocks"]:
        x, aux = layers.encoder_block(block, x, attn_mask, dtype, with_aux=True)
        aux_total = aux_total + aux
    x = layers.layer_norm(params["ln_f"], x)
    denom = jnp.maximum(mask.sum(axis=1, keepdims=True), 1).astype(jnp.float32)
    pooled = (x.astype(jnp.float32) * mask[:, :, None]).sum(axis=1) / denom
    logits = layers.dense(params["head"], pooled.astype(dtype), dtype)
    return logits.astype(jnp.float32)


def test_without_segments_the_traced_program_is_unchanged():
    cfg = CFG.scaled(dtype="bfloat16")
    params = jax.eval_shape(lambda: encoder.init_params(cfg))
    ids = jax.ShapeDtypeStruct((4, 64), jnp.int32)
    now = jax.make_jaxpr(lambda p, i, m: encoder.forward(p, i, m, cfg))(
        params, ids, ids)
    was = jax.make_jaxpr(lambda p, i, m: _forward_before(p, i, m, cfg))(
        params, ids, ids)
    assert str(now) == str(was)


# ---- (c) the whole-row kernel under segment ids ---------------------------

def _heads(t, H):
    B, L, HD = t.shape
    return t.reshape(B, L, H, HD // H).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("L", [64, 128, 512])
def test_whole_row_kernel_with_segment_ids(L):
    B, H, D = 4, 4, 64
    rng = np.random.default_rng(L)
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H * D)), dtype=jnp.bfloat16)
               for _ in range(3))
    G = L // 8
    seg_lengths = np.zeros((B, G), np.int32)
    seg_lengths[0, :3] = (L // 2, L // 4, L // 4)          # full
    seg_lengths[1, :4] = (9, 0, 17, 8)                     # an all-pad tail
    seg_lengths[3, :2] = (L - 8, 1)                        # row 2: empty
    seg_ids, _ = encoder.segment_layout(jnp.asarray(seg_lengths), L)
    assert selects(L, H, D, segments=True)
    got = np.asarray(fa.whole_row_attention(
        q, k, v, None, n_heads=H, segment_ids=seg_ids, interpret=True,
    )).astype(np.float32)
    want = layers.dot_product_attention(
        _heads(q, H), _heads(k, H), _heads(v, H),
        layers.segment_mask_to_attn(seg_ids))
    want = np.asarray(want.transpose(0, 2, 1, 3).reshape(B, L, H * D)
                      ).astype(np.float32)
    real = np.asarray(seg_ids) > 0
    assert real[0].all() and not real[2].any() and not real[1, 34:].any()
    np.testing.assert_allclose(got[real], want[real], rtol=2e-2, atol=2e-2)
    # Pad slots attend nothing: 0, not V's mean and not NaN.
    assert np.isfinite(got).all() and not got[~real].any()


def selects(L, H, D, **kw):
    return fa.selects_whole_row(L, L, H, D, key_padding=False,
                                dtype=jnp.bfloat16, **kw)


def test_selects_whole_row_learns_the_segment_form():
    assert selects(64, 12, 64, segments=True) and selects(512, 12, 64, segments=True)
    assert not selects(64, 12, 64)                     # neither mask kind
    assert not selects(96, 12, 64, segments=True)      # the same shape rules
    assert not selects(2048, 12, 64, segments=True)
    assert not fa.selects_whole_row(64, 64, 12, 64, key_padding=False,
                                    dtype=jnp.float32, segments=True)
    entry = _fused_attn_fn().whole_row
    block = jnp.ones((4, 1, 64, 64), jnp.int32)        # what rides as `mask`
    assert entry.selects(4, 64, 64, 12, 64, block, jnp.bfloat16, segments=True)
    assert not entry.selects(4, 64, 64, 12, 64, block, jnp.bfloat16)


def test_whole_row_segments_on_a_dp_mesh_keep_their_shards():
    from agent_tpu.kernels import make_flash_attention
    from agent_tpu.runtime.mesh import build_mesh

    mesh = build_mesh(jax.devices()[:4], {"dp": 2, "tp": 2})
    entry = make_flash_attention(mesh, interpret=True).whole_row
    B, L, H, D = 4, 64, 4, 64
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H * D)), dtype=jnp.bfloat16)
               for _ in range(3))
    seg_lengths = np.zeros((B, 8), np.int32)
    seg_lengths[:, :3] = rng.integers(8, 20, size=(B, 3))
    seg_ids, _ = encoder.segment_layout(jnp.asarray(seg_lengths), L)
    out = jax.jit(functools.partial(entry, n_heads=H))(
        q, k, v, None, segment_ids=seg_ids)
    want = fa.whole_row_attention(q, k, v, None, n_heads=H,
                                  segment_ids=seg_ids, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ---- (d) the op on a shard of drain-short's distribution ------------------

TINY = {"d_model": 32, "n_heads": 4, "n_layers": 2, "d_ff": 64, "max_len": 64,
        "n_classes": 16, "dtype": "float32"}


def _traffic_rows(n, seed=5):
    from benchmarks.harness import schedule

    with open(os.path.join(ROOT, "benchmarks/traffic/drain-short.json")) as f:
        traffic = json.load(f)
    return schedule.drain_rows(traffic, seed, n)


@pytest.fixture(scope="module")
def ctx():
    return OpContext(runtime=get_runtime())


def _payload(rows, model="pack-op", **over):
    return {"texts": rows, "model_config": dict(TINY), "model_path": model,
            "result_format": "columnar", "topk": 5, "allow_fallback": False,
            **over}


def test_run_on_a_512_row_shard_matches_the_padded_program(ctx, monkeypatch):
    rows = _traffic_rows(512)
    _, state = op.stage(_payload(rows), ctx)
    (chunk,) = state["chunks"]
    assert isinstance(chunk, mc.PackedChunk)
    assert chunk.ids.dtype == np.uint8             # the raw-byte wire, kept
    assert chunk.ids.shape[0] in (256, 320)        # 4 or 5 slices of 64
    state = op.execute(state, ctx)
    ((result, n),) = state["pending_dev"]          # one array, one fetch
    assert n == 512 and result.shape == (512, 5, 2) and result.dtype == np.int32
    got = op.finalize(state, ctx)
    monkeypatch.setattr(op, "_takes_packed_rows", lambda *a: False)
    _, padded = op.stage(_payload(rows), ctx)
    assert not isinstance(padded["chunks"][0], mc.PackedChunk)
    want = op.finalize(op.execute(padded, ctx), ctx)
    assert got["n_rows"] == want["n_rows"] == 512
    assert got["indices"] == want["indices"]       # rows in order, same top-k
    np.testing.assert_allclose(got["scores"], want["scores"], atol=1e-5)


def test_rows_past_a_bucket_and_fallback_fetch(ctx):
    """300 rows in a bucket of 512; the fetching (fallback-allowed) mode."""
    rows = _traffic_rows(300, seed=6)
    out = get_op("map_classify_tpu")(_payload(rows, allow_fallback=True), ctx)
    one = get_op("map_classify_tpu")(
        _payload([rows[7]], allow_fallback=True), ctx)   # a row alone: padded
    assert out["ok"] and out["n_rows"] == 300 and len(out["indices"]) == 300
    assert out["indices"][7] == one["indices"][0]
    np.testing.assert_allclose(out["scores"][7], one["scores"][0], atol=1e-5)


# ---- (e) the predicate ------------------------------------------------------

def test_a_full_length_shard_runs_todays_program_under_todays_key(ctx):
    rows = ["x" * 80] * 128                           # fills max_len 64
    _, state = op.stage(_payload(rows, model="pack-full"), ctx)
    (chunk,) = state["chunks"]
    assert not isinstance(chunk, mc.PackedChunk) and chunk[0].shape == (128, 64)
    assert state["token_slots"] == (128 * 64, 128 * 64, False)
    keys = []
    real_compiled = ctx.runtime.compiled
    ctx.runtime.compiled = lambda key, build: (
        keys.append(key), real_compiled(key, build))[1]
    try:
        op.finalize(op.execute(state, ctx), ctx)
    finally:
        del ctx.runtime.compiled
    cfg = state["cfg"]
    assert keys == [("map_classify_tpu", "encoder", 128, 64, 5,
                     mc.cfg_key(cfg))]


@pytest.mark.parametrize("cfg, family, why", [
    (encoder.EncoderConfig(moe_experts=4), "encoder", "an expert layer"),
    (encoder.EncoderConfig(pp=2), "encoder", "a pipeline schedule"),
    (encoder.EncoderConfig(), "bert", "bert.forward has no segment form"),
])
def test_programs_that_stay_padded(cfg, family, why, ctx):
    assert op._takes_packed_rows(encoder.EncoderConfig(), "encoder", ctx.runtime)
    assert op._takes_packed_rows(encoder.EncoderConfig(quant="int8"), "encoder",
                                 None)
    assert not op._takes_packed_rows(cfg, family, ctx.runtime), why


class _Mesh:
    def __init__(self, **axes):
        self.axes = axes

    def axis_size(self, name):
        return self.axes.get(name, 1)


def test_pp_and_sp_meshes_stay_padded():
    cfg = encoder.EncoderConfig()
    assert op._takes_packed_rows(cfg, "encoder", _Mesh(dp=4, tp=2))
    assert not op._takes_packed_rows(cfg, "encoder", _Mesh(pp=2))
    assert not op._takes_packed_rows(cfg, "encoder", _Mesh(sp=4))


def test_moe_shard_of_short_rows_is_staged_padded(ctx):
    rows = _traffic_rows(256, seed=8)
    moe = dict(TINY, moe_experts=2)
    _, state = op.stage(_payload(rows, model_config=moe), ctx)
    assert not any(isinstance(c, mc.PackedChunk) for c in state["chunks"])


def test_summarize_staging_is_untouched():
    rows = _traffic_rows(256, seed=9)
    chunks = mc.stage_text_chunks(1, rows, max_len=64, vocab_size=260,
                                  max_batch=8192)
    assert [type(c) for c in chunks] == [tuple]
    packed = mc.stage_text_chunks(1, rows, max_len=64, vocab_size=260,
                                  max_batch=8192, pack_short_rows=True)
    assert [type(c) for c in packed] == [mc.PackedChunk]
    import inspect

    from agent_tpu.ops import map_summarize

    assert "pack_short_rows" not in inspect.getsource(map_summarize)


def test_int8_packs_with_its_padded_answers(ctx, monkeypatch):
    """W8A8 scales an activation by its own token's maximum, so a token's
    values do not depend on what shares its program row."""
    rows = _traffic_rows(256, seed=10)
    cfg8 = dict(TINY, quant="int8")
    _, state = op.stage(_payload(rows, model="pack-q", model_config=cfg8), ctx)
    assert isinstance(state["chunks"][0], mc.PackedChunk)
    got = op.finalize(op.execute(state, ctx), ctx)
    monkeypatch.setattr(op, "_takes_packed_rows", lambda *a: False)
    want = get_op("map_classify_tpu")(
        _payload(rows, model="pack-q", model_config=cfg8), ctx)
    np.testing.assert_allclose(got["scores"], want["scores"], atol=1e-4)
    flips = sum(g != w for gi, wi in zip(got["indices"], want["indices"])
                for g, w in zip(gi, wi))
    assert flips <= 256 * 5 // 50


# ---- (f) the counters -----------------------------------------------------

def _value(reg, name, **labels):
    return reg.counter(name, "", tuple(labels)).value(**labels)


def test_counters_tick_what_the_packer_says(ctx):
    reg = MetricsRegistry()
    short, full = _traffic_rows(512, seed=12), ["y" * 90] * 128
    with obs_trace.use_context(obs_trace.TraceContext(
            trace_id="t", registry=reg, op="map_classify_tpu")):
        _, s1 = op.stage(_payload(short), ctx)
        op.finalize(op.execute(s1, ctx), ctx)
        _, s2 = op.stage(_payload(full), ctx)
        op.finalize(op.execute(s2, ctx), ctx)
    (chunk,) = s1["chunks"]
    real = sum(min(len(r), 64) for r in short)
    assert s1["token_slots"] == (real, chunk.ids.shape[0] * 64, True)
    assert _value(reg, "classify_token_slots_total", kind="real") == real + 128 * 64
    assert _value(reg, "classify_token_slots_total", kind="dispatched") == (
        chunk.ids.shape[0] * 64 + 128 * 64)
    assert _value(reg, "classify_shards_total", layout="packed") == 1
    assert _value(reg, "classify_shards_total", layout="padded") == 1
    assert real / (chunk.ids.shape[0] * 64) > 0.8


# ---- no executable is built inside a window -------------------------------

def _shard_with_slices(slices, rows=512):
    """``rows`` texts that pack into exactly ``slices`` slices of 64 program
    rows of length 64: one row that fills a program row (so the bucket is
    64), the others equal rows, ``m`` or ``m + 1`` to a program row."""
    bins, rest = 64 * slices - 1, rows - 1
    m = rest // bins
    more = rest - bins * m                    # program rows that hold m + 1
    lengths = ([64] + [64 // (m + 1)] * (more * (m + 1))
               + [64 // m] * ((bins - more) * m))
    chunk = mc.pack_padded_chunk(*_padded(lengths, 64), rows, dp=1)
    assert len(lengths) == rows and chunk.ids.shape[0] == bins + 1
    return ["z" * n for n in lengths]


def test_no_shard_of_a_window_builds_an_executable(ctx):
    """After ONE warm-up shard a tenant, whatever number of slices it had,
    shards with every number of slices a 512-row shard of this length can
    have (2 to 7: eight rows to every program row would be a shorter bucket)
    obtain nothing from XLA: one slice program and one head program for
    every tenant of the config, no gather per arity."""
    tenants = ["pack-t0", "pack-t1"]
    # A config no other test of this runtime runs: the programs are every
    # tenant's of a config, so only then does warm-up obtain them itself.
    own = {"model_config": {**TINY, "n_classes": 17}}
    reg = MetricsRegistry()
    with obs_trace.use_context(obs_trace.TraceContext(
            trace_id="w", registry=reg, op="map_classify_tpu")):
        for slices, tenant in zip((4, 5), tenants):      # warm-up
            out = get_op("map_classify_tpu")(_payload(
                _shard_with_slices(slices), model=tenant, **own), ctx)
            assert out["ok"]
        warm = _value(reg, "runtime_xla_executables_total")
        assert warm > 0
        for slices in range(2, 8):                       # the window
            for tenant in tenants:
                _, state = op.stage(_payload(
                    _shard_with_slices(slices), model=tenant, **own), ctx)
                assert state["chunks"][0].ids.shape[0] == 64 * slices
                assert op.finalize(op.execute(state, ctx), ctx)["ok"]
        assert _value(reg, "runtime_xla_executables_total") == warm
        assert _value(reg, "classify_shards_total", layout="packed") == 14
