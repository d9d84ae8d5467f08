"""One Q, K, V matmul a block (PR 31): the serving layout ``wqkv`` of a
block's self-attention projections. The build-time transform and where it is
NOT applied, ``layers.attention`` on a fused subtree at every kind of call,
the traced program's shape (one dot, no slice of the projected activations),
the two trace-time counters, and the classify op end to end."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agent_tpu.config import DeviceConfig
from agent_tpu.models import encoder, layers
from agent_tpu.obs import trace as obs_trace
from agent_tpu.obs.metrics import MetricsRegistry
from agent_tpu.ops import _model_common as mc
from agent_tpu.ops import map_classify_tpu as op
from agent_tpu.runtime.context import OpContext
from agent_tpu.runtime.runtime import TpuRuntime

fa = importlib.import_module("agent_tpu.kernels.flash_attention")

CFG = encoder.EncoderConfig(d_model=128, n_heads=2, n_layers=2, d_ff=256,
                            max_len=128, n_classes=24, dtype="float32")
BF16 = jnp.bfloat16


def _whole_row_attn_fn(dp=1, tp=1):
    """What ``TpuRuntime.attention_fn()`` builds on a chip, interpreted."""
    from agent_tpu.kernels import make_flash_attention
    from agent_tpu.runtime.mesh import build_mesh

    return make_flash_attention(
        build_mesh(jax.devices()[:dp * tp], {"dp": dp, "tp": tp}),
        interpret=True)


def _attn_pair(d_model=128, H=2, seed=0):
    """(three-leaf attention subtree, its fused layout)."""
    p = layers.init_attention(jax.random.PRNGKey(seed), d_model, H)
    return p, layers.fuse_qkv(p)


# ---- (b) the transform ------------------------------------------------------

def test_fused_leaf_is_q_k_v_columns_head_major_and_slices_back_exactly():
    p, fused = _attn_pair(d_model=96, H=3, seed=5)
    assert sorted(fused) == ["wo", "wqkv"] and fused["wo"] is p["wo"]
    w = fused["wqkv"]
    assert w.shape == (96, 3 * 96) and w.dtype == p["wq"].dtype == jnp.float32
    for i, name in enumerate(("wq", "wk", "wv")):
        for h in range(3):      # head h of operand i: columns i*H*E + h*E ...
            lo = i * 96 + h * 32
            np.testing.assert_array_equal(np.asarray(w[:, lo:lo + 32]),
                                          np.asarray(p[name][:, h, :]))
    for got, name in zip(layers.qkv_leaves(fused), ("wq", "wk", "wv")):
        assert got.shape == p[name].shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(p[name]))
    assert all(a is b for a, b in zip(
        layers.qkv_leaves(p), (p["wq"], p["wk"], p["wv"])))
    # Same bytes resident: the three leaves' parameters, no more.
    assert layers.count_params(fused) == layers.count_params(p)


def test_transform_fuses_every_block_and_touches_nothing_else():
    params = encoder.init_params(CFG, "fuse-me")
    before = jax.tree_util.tree_map(lambda leaf: leaf, params)   # same leaves
    fused = mc.maybe_fuse_qkv_params(params, "encoder", CFG, 1)
    # In place: the build's own tree, a block's three leaves let go as its
    # fused leaf is made.
    assert fused is params
    assert all(sorted(b["attn"]) == ["wo", "wqkv"] for b in fused["blocks"])
    assert layers.count_params(fused) == layers.count_params(before)
    for name in ("embed", "pos", "ln_f", "head"):
        assert jax.tree_util.tree_leaves(fused[name])[0] is (
            jax.tree_util.tree_leaves(before[name])[0])
    for got, was in zip(fused["blocks"], before["blocks"]):
        assert got["ffn"]["wi"]["w"] is was["ffn"]["wi"]["w"]
        assert got["attn"]["wo"] is was["attn"]["wo"]
        for leaf, name in zip(layers.qkv_leaves(got["attn"]), ("wq", "wk", "wv")):
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(was["attn"][name]))


@pytest.mark.parametrize("family, cfg, tp, why", [
    ("encoder", CFG.scaled(quant="int8"), 1, "quantized leaves keep [d, H, E] tables"),
    ("encoder", CFG.scaled(quant="w8a16"), 1, "weight-only leaves too"),
    ("bert", CFG, 1, "the pretrained family has biased projections of its own"),
    ("encoder", CFG, 2, "[Q | K | V] columns do not split by heads over tp"),
])
def test_transform_is_not_applied(family, cfg, tp, why):
    params = {"blocks": [{"attn": {"wq": 1, "wk": 2, "wv": 3, "wo": 4}}]}
    assert mc.maybe_fuse_qkv_params(params, family, cfg, tp) is params, why
    specs = {"blocks": [{"attn": {"wq": 1}}]}
    assert mc.maybe_fuse_qkv_specs(specs, family, cfg, tp) is specs, why


def test_spec_twin_is_congruent_with_the_fused_tree():
    from agent_tpu.parallel.shardings import encoder_param_specs
    from jax.sharding import PartitionSpec as P

    for cfg in (CFG, CFG.scaled(moe_experts=2)):
        params = jax.eval_shape(lambda: mc.maybe_fuse_qkv_params(
            encoder.init_params(cfg), "encoder", cfg, 1))
        specs = mc.maybe_fuse_qkv_specs(
            encoder_param_specs(cfg), "encoder", cfg, 1)
        is_spec = lambda x: isinstance(x, P)  # noqa: E731
        assert (jax.tree_util.tree_structure(params)
                == jax.tree_util.tree_structure(
                    jax.tree_util.tree_map(lambda s: 0, specs, is_leaf=is_spec)))
        assert specs["blocks"][0]["attn"]["wqkv"] == P()
        assert specs["blocks"][0]["attn"]["wo"] == P("tp", None, None)


def _runtime(mesh_shape):
    return TpuRuntime(
        config=DeviceConfig(tpu_disabled=True, mesh_shape=mesh_shape),
        devices=jax.devices("cpu")[:8],
    )


TINY = {"d_model": 32, "n_heads": 4, "n_layers": 2, "d_ff": 64, "max_len": 64,
        "n_classes": 16, "dtype": "float32"}
ROWS = ["a row of text", "another", "x" * 70, ""] * 4


def _classify(rt, monkeypatch, model="fuse-op", **model_config):
    """Run the op on ``rt``; returns (result, the tree the build returned)."""
    built = []
    real = op._build_params

    def spy(*args):
        built.append(real(*args))
        return built[-1]

    monkeypatch.setattr(op, "_build_params", spy)
    out = op.run({"texts": ROWS, "model_path": model, "topk": 3,
                  "model_config": {**TINY, **model_config},
                  "result_format": "columnar", "allow_fallback": False},
                 OpContext(runtime=rt))
    assert out["ok"] and out["n_rows"] == len(ROWS), out
    (tree,) = built
    return out, tree


@pytest.mark.parametrize("mesh_shape, model_config, fused", [
    ({"dp": 8}, {}, True),
    ({"dp": 4, "tp": 2}, {}, False),                    # 8-device CPU mesh
    ({"dp": 4, "ep": 2}, {"moe_experts": 2}, True),     # placed by specs
    ({"dp": 8}, {"quant": "int8"}, False),
])
def test_op_holds_the_fused_tree_only_where_the_placement_allows(
        mesh_shape, model_config, fused, monkeypatch):
    out, tree = _classify(_runtime(mesh_shape), monkeypatch, **model_config)
    attn = tree["blocks"][0]["attn"]
    assert ("wqkv" in attn) == fused and ("wq" in attn) != fused
    if fused:
        assert attn["wqkv"].dtype == jnp.float32        # the stored dtype
    if not model_config:
        # The same model on either tree, either mesh: the same answers.
        want = encoder.forward(
            encoder.init_params(encoder.EncoderConfig(**TINY), "fuse-op"),
            *_ids_mask(ROWS), encoder.EncoderConfig(**TINY))
        probs, idx = jax.lax.top_k(jax.nn.softmax(want), 3)
        assert out["indices"] == np.asarray(idx).tolist()
        np.testing.assert_allclose(out["scores"], np.asarray(probs), atol=1e-5)


def _ids_mask(rows, L=64):
    from agent_tpu.models.tokenizer import N_SPECIAL

    ids = np.zeros((len(rows), L), np.int32)
    mask = np.zeros((len(rows), L), np.int32)
    for r, text in enumerate(rows):
        b = text.encode()[:L]
        ids[r, :len(b)] = np.frombuffer(b, np.uint8).astype(np.int32) + N_SPECIAL
        mask[r, :len(b)] = 1
    return jnp.asarray(ids), jnp.asarray(mask)


# ---- (c) every kind of call on a fused subtree -----------------------------

def _self_attention(L, attn_fn, dtype):
    def call(p):
        x = jnp.asarray(np.random.default_rng(L).normal(size=(2, L, 128)), dtype)
        mask = jnp.asarray(np.arange(L)[None, :] < np.array([L, L // 3])[:, None]
                           )[:, None, None, :].astype(jnp.int32)
        return layers.attention(p, x, x, mask, dtype, attn_fn=attn_fn)[0]
    return call


def _decode_step(attn_fn, index):
    def call(p):
        x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 1, 128)), BF16)
        cache = {n: jnp.asarray(np.random.default_rng(i).normal(size=(2, 2, 64, 64)),
                                BF16) for i, n in enumerate(("k", "v"))}
        mask = (jnp.arange(64) <= 5).astype(jnp.int32)[None, None, None, :]
        y, new = layers.attention(p, x, x, mask, BF16, cache=cache,
                                  cache_index=index, attn_fn=attn_fn)
        return jnp.concatenate([y.reshape(-1), new["k"].reshape(-1),
                                new["v"].reshape(-1)])
    return call


def _cross_attention(attn_fn):
    def call(p):
        rng = np.random.default_rng(4)
        x_q = jnp.asarray(rng.normal(size=(2, 64, 128)), BF16)
        x_kv = jnp.asarray(rng.normal(size=(2, 64, 128)), BF16)
        mask = jnp.ones((2, 1, 1, 64), jnp.int32)
        return layers.attention(p, x_q, x_kv, mask, BF16, attn_fn=attn_fn)[0]
    return call


CALLS = {
    # name: (call builder, the Q, K, V form a fused subtree runs in)
    "whole_row_64": (lambda: _self_attention(64, _whole_row_attn_fn(), BF16), "fused"),
    "whole_row_128": (lambda: _self_attention(128, _whole_row_attn_fn(), BF16), "fused"),
    "dense_length_96": (lambda: _self_attention(96, _whole_row_attn_fn(), BF16), "separate"),
    "float32_xla": (lambda: _self_attention(64, _whole_row_attn_fn(), jnp.float32), "separate"),
    "plain_attn_fn": (lambda: _self_attention(64, layers.dot_product_attention, BF16), "separate"),
    "decode_step_scalar_index": (
        lambda: _decode_step(_whole_row_attn_fn(), jnp.int32(5)), "separate"),
    "decode_step_row_indices": (
        lambda: _decode_step(_whole_row_attn_fn(), jnp.asarray([5, 2], jnp.int32)), "separate"),
    # Two arrays of one shape under a key-padding mask: the whole-row entry
    # takes the call, but Q reads other activations than K and V do.
    "cross_attention_64_over_64": (lambda: _cross_attention(_whole_row_attn_fn()), "separate"),
}


def _ticks(registry, name, **labels):
    fam = registry.snapshot().get(name) or {"series": []}
    return sum(s["value"] for s in fam["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _qkv_ticks(registry, form):
    return _ticks(registry, "attention_qkv_traced_total", form=form)


@pytest.mark.parametrize("name", list(CALLS))
def test_fused_subtree_gives_the_three_leaf_answers(name):
    build, form = CALLS[name]
    call = build()
    p, fused = _attn_pair(seed=7)
    want = np.asarray(call(p).astype(jnp.float32))
    registry = MetricsRegistry()
    with obs_trace.use_context(obs_trace.TraceContext(registry=registry)):
        got = np.asarray(call(fused).astype(jnp.float32))
    np.testing.assert_array_equal(got, want)
    other = "separate" if form == "fused" else "fused"
    assert _qkv_ticks(registry, form) == 1 and _qkv_ticks(registry, other) == 0


def test_seq2seq_decoder_block_on_fused_subtrees():
    """A decoder block (self-attention with a cache, cross-attention) whose
    two attention subtrees are fused by hand: no serving path builds such a
    tree today, and ``layers.attention`` still reads what it is given."""
    block = layers.init_block(jax.random.PRNGKey(2), 128, 2, 256, cross=True)
    fused = {**block, "attn": layers.fuse_qkv(block["attn"]),
             "xattn": layers.fuse_qkv(block["xattn"])}
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 1, 128)), jnp.float32)
    enc = jnp.asarray(rng.normal(size=(2, 16, 128)), jnp.float32)
    cache = {n: jnp.zeros((2, 2, 32, 64), jnp.float32) for n in ("k", "v")}
    self_mask = (jnp.arange(32) <= 3).astype(jnp.int32)[None, None, None, :]
    enc_mask = jnp.ones((2, 1, 1, 16), jnp.int32)

    def run(p):
        return layers.decoder_block(p, x, self_mask, enc, enc_mask, jnp.float32,
                                    cache=cache, cache_index=jnp.int32(3))

    (y, c), (y0, c0) = run(fused), run(block)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    np.testing.assert_array_equal(np.asarray(c["k"]), np.asarray(c0["k"]))


def test_fused_operand_on_a_dp_mesh_keeps_its_shards_and_tp_splits_it():
    """Batch over dp with the one [B, L, 3*H*D] array; on a tp mesh (for
    which no fused leaf is ever built) the entry still answers right."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    B, L, H, D = 4, 64, 4, 64
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H * D)), BF16) for _ in range(3))
    mask = jnp.asarray(np.arange(L)[None, :] < np.array([64, 9, 33, 1])[:, None]
                       )[:, None, None, :].astype(jnp.int32)
    want = np.asarray(fa.whole_row_attention(q, k, v, mask, n_heads=H, interpret=True))
    qkv = jnp.concatenate([q, k, v], axis=-1)
    for dp, tp in ((4, 1), (2, 2)):
        entry = _whole_row_attn_fn(dp=dp, tp=tp).whole_row
        out = jax.jit(functools.partial(entry, n_heads=H))(qkv, None, None, mask)
        assert out.sharding.is_equivalent_to(NamedSharding(
            entry._shard.keywords["mesh"], P("dp", None, "tp")), 3), out.sharding
        np.testing.assert_array_equal(np.asarray(out), want)


# ---- (d) the traced program -------------------------------------------------

def _top_level(jaxpr):
    """The equations of a traced function, through ``jit`` calls but not
    into a kernel's body."""
    for eqn in jaxpr.eqns:
        inner = eqn.params.get("jaxpr") if eqn.primitive.name in ("pjit", "jit") else None
        if inner is not None:
            yield from _top_level(getattr(inner, "jaxpr", inner))
        else:
            yield eqn


@pytest.mark.parametrize("segments", [False, True], ids=["key_padding", "segment_ids"])
def test_a_fused_block_traces_one_qkv_dot_and_no_slice_of_its_result(segments):
    B, L, d, H = 4, 64, 128, 2
    block = layers.init_block(jax.random.PRNGKey(0), d, H, 256)
    fused = {**block, "attn": layers.fuse_qkv(block["attn"])}
    attn_fn = _whole_row_attn_fn()
    x = jax.ShapeDtypeStruct((B, L, d), BF16)
    seg = jnp.ones((B, L), jnp.int32)
    mask = layers.segment_mask_to_attn(seg) if segments else jnp.ones((B, 1, 1, L), jnp.int32)

    def eqns(p):
        return list(_top_level(jax.make_jaxpr(lambda p, x: layers.encoder_block(
            p, x, mask, BF16, attn_fn=attn_fn,
            segment_ids=seg if segments else None))(p, x).jaxpr))

    def dots(es):
        return [tuple(e.outvars[0].aval.shape) for e in es
                if e.primitive.name == "dot_general"]

    # Q, K, V as one [B*L, d] x [d, 3*H*E] dot; out-projection; the FFN's two.
    assert dots(eqns(fused)) == [(B * L, 3 * d), (B * L, d), (B, L, 256), (B, L, d)]
    assert dots(eqns(block))[:3] == [(B * L, d)] * 3 and len(dots(eqns(block))) == 6
    # Nothing takes the projected activations apart: they go into the kernel
    # whole, three times.
    wide = [e for e in eqns(fused)
            if any(getattr(v.aval, "shape", ())[-1:] == (3 * d,)
                   and getattr(v.aval, "shape", ())[:1] in ((B,), (B * L,))
                   for v in e.invars if hasattr(v, "aval"))]
    assert [e.primitive.name for e in wide] == ["reshape", "pallas_call"]
    kernel = wide[-1]
    assert [v is kernel.invars[0] for v in kernel.invars[:3]] == [True] * 3


# ---- (e) the counters -------------------------------------------------------

def test_both_counters_tick_once_a_block_of_a_traced_program():
    """A traced 256 x 512 BERT-base program on the fused tree:
    ``attention_blocks_traced_total{path="whole_row"}`` 12 as before, and
    ``attention_qkv_traced_total{form="fused"}`` 12 beside it; on the
    canonical tree the same 12 and ``form="separate"`` 12. Tracing only."""
    cfg = encoder.EncoderConfig(d_model=768, n_heads=12, n_layers=12,
                                d_ff=3072, max_len=512, n_classes=1000)
    canonical = jax.eval_shape(lambda: encoder.init_params(cfg, "bert-base"))
    fused = jax.eval_shape(lambda: mc.maybe_fuse_qkv_params(
        encoder.init_params(cfg, "bert-base"), "encoder", cfg, 1))
    ids = jax.ShapeDtypeStruct((256, 512), jnp.int32)
    attn_fn = _whole_row_attn_fn()

    def blocks(registry, path):
        return _ticks(registry, "attention_blocks_traced_total", path=path)

    for tree, form, other in ((fused, "fused", "separate"),
                              (canonical, "separate", "fused")):
        registry = MetricsRegistry()
        with obs_trace.use_context(obs_trace.TraceContext(registry=registry)):
            jax.eval_shape(
                lambda p, i, m: encoder.forward(p, i, m, cfg, attn_fn=attn_fn),
                tree, ids, ids)
        assert blocks(registry, "whole_row") == cfg.n_layers
        assert blocks(registry, "dense") == 0
        assert _qkv_ticks(registry, form) == cfg.n_layers
        assert _qkv_ticks(registry, other) == 0
