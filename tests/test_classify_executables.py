"""The classify op's forward executables are filed under what the traced
program depends on (family, shapes, ``k``, ``cfg_key``), never under whose
weights it runs on: every model of one configuration shares one ``jax.jit``
wrapper, one trace and one executable, on the padded path and on the packed
one, and is still answered by its own weights."""

import jax
import numpy as np
import pytest

from agent_tpu.config import DeviceConfig
from agent_tpu.obs import trace as obs_trace
from agent_tpu.obs.metrics import MetricsRegistry
from agent_tpu.ops import _model_common as mc
from agent_tpu.ops import get_op
from agent_tpu.ops import map_classify_tpu as op
from agent_tpu.runtime.context import OpContext
from agent_tpu.runtime.runtime import TpuRuntime

TINY = {"d_model": 32, "n_heads": 4, "n_layers": 2, "d_ff": 64, "max_len": 64,
        "n_classes": 16, "dtype": "float32"}

# A shard whose rows fill their bucket stays padded (``pack_padded_chunk``
# says no); 256 short rows beside one that fills the bucket are packed.
ROWS = {
    "padded": ["x" * 80] * 32,
    "packed": ["ab" * (4 + i % 12) for i in range(255)] + ["z" * 80],
}
# Lookups of the runtime's cache a shard: the padded program is one, the
# packed path a slice program and a head program.
LOOKUPS = {"padded": 1, "packed": 2}


def _fresh_ctx():
    """A runtime of its own, with the attention function a chip's runtime
    builds (interpreted here): it is what ticks
    ``attention_blocks_traced_total`` while a program is traced."""
    from agent_tpu.kernels import make_flash_attention

    runtime = TpuRuntime(
        config=DeviceConfig(tpu_disabled=True,
                            mesh_shape={"dp": 1, "tp": 1, "sp": 1}),
        devices=jax.devices("cpu")[:1])
    runtime._attention_fn = make_flash_attention(runtime.mesh, interpret=True)
    return OpContext(runtime=runtime)


def _payload(path, model, **config):
    return {"texts": ROWS[path], "model_config": {**TINY, **config},
            "model_path": model, "result_format": "columnar", "topk": 5,
            "allow_fallback": False}


def _classify(ctx, payload):
    out = get_op("map_classify_tpu")(payload, ctx)
    assert out["ok"] and "fallback" not in out
    return np.asarray(out["indices"]), np.asarray(out["scores"], np.float32)


def _sum(reg, name, **labels):
    family = reg.snapshot().get(name) or {"series": []}
    return sum(s["value"] for s in family["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _account(ctx, reg):
    """What a tenant may not add to, once a tenant of its configuration has
    run: entries of the runtime's cache, executables obtained from XLA,
    attention blocks traced, and misses of the op's lookups."""
    return {
        "entries": len(ctx.runtime.cache),
        "executables": _sum(reg, "runtime_xla_executables_total"),
        "blocks": _sum(reg, "attention_blocks_traced_total"),
        "qkv": _sum(reg, "attention_qkv_traced_total"),
        "misses": _sum(reg, "runtime_compile_cache_total",
                       op="map_classify_tpu", outcome="miss"),
    }


def _hits(reg):
    return _sum(reg, "runtime_compile_cache_total",
                op="map_classify_tpu", outcome="hit")


def _traced(reg):
    return obs_trace.use_context(obs_trace.TraceContext(
        trace_id="t", registry=reg, op="map_classify_tpu"))


@pytest.mark.parametrize("path", ["padded", "packed"])
def test_the_staged_chunk_takes_the_path_it_is_named_for(path):
    _, state = op.stage(_payload(path, "exe-a"), _fresh_ctx())
    (chunk,) = state["chunks"]
    assert isinstance(chunk, mc.PackedChunk) == (path == "packed")


@pytest.mark.parametrize("path", ["padded", "packed"])
@pytest.mark.parametrize("tenants", [2, 12])
def test_every_tenant_after_the_first_obtains_nothing(path, tenants):
    """A second model of a config, and an agent's warm-up of twelve tenants
    of one architecture: one entry a program in ``runtime.cache``,
    ``runtime_compile_cache_total`` one miss for each and a hit a tenant
    after, and every executable, every traced block the first tenant's."""
    ctx, reg = _fresh_ctx(), MetricsRegistry()
    with _traced(reg):
        _classify(ctx, _payload(path, "tenant-0"))
        first, hits = _account(ctx, reg), _hits(reg)
        for t in range(1, tenants):
            _classify(ctx, _payload(path, f"tenant-{t}"))
        assert _account(ctx, reg) == first
    assert first["entries"] == first["misses"] == LOOKUPS[path]
    assert first["executables"] > 0
    assert first["blocks"] == first["qkv"] == TINY["n_layers"]
    assert _hits(reg) == hits + (tenants - 1) * LOOKUPS[path]
    forward = [k for k in ctx.runtime.cache._cache
               if k[0] == "map_classify_tpu" and k[1] != "packed_head"]
    assert len(forward) == 1 and not any(
        isinstance(part, str) and "tenant" in part for part in forward[0])
    # The weights stay a tenant's: every tree resident under the one program.
    assert len(ctx.runtime.describe()["models_resident"]) == tenants


@pytest.mark.parametrize("path", ["padded", "packed"])
def test_each_model_is_answered_by_its_own_weights(path):
    """The weights are arguments, not captured: under the shared executable
    two models' answers differ, and each equals, bit for bit, what the same
    model answers on a runtime where it is the only tenant."""
    shared = _fresh_ctx()
    a = _classify(shared, _payload(path, "exe-a"))
    b = _classify(shared, _payload(path, "exe-b"))
    again = _classify(shared, _payload(path, "exe-a"))
    assert not np.array_equal(a[0], b[0])
    assert not np.array_equal(a[1], b[1])
    for got, model in ((a, "exe-a"), (b, "exe-b"), (again, "exe-a")):
        alone = _classify(_fresh_ctx(), _payload(path, model))
        np.testing.assert_array_equal(got[0], alone[0])
        np.testing.assert_array_equal(got[1].view(np.int32),
                                      alone[1].view(np.int32))


@pytest.mark.parametrize("path", ["padded", "packed"])
@pytest.mark.parametrize("other", [{"quant": "int8"}, {"n_classes": 12}],
                         ids=["int8", "other_num_labels"])
def test_a_tenant_of_another_config_gets_its_own_executable(path, other):
    ctx, reg = _fresh_ctx(), MetricsRegistry()
    with _traced(reg):
        plain = _classify(ctx, _payload(path, "exe-a"))
        first = _account(ctx, reg)
        got = _classify(ctx, _payload(path, "exe-a", **other))
        second = _account(ctx, reg)
    # Both of the packed path's programs depend on the config.
    assert second["entries"] == second["misses"] == 2 * LOOKUPS[path]
    assert second["executables"] > first["executables"]
    assert second["blocks"] == 2 * TINY["n_layers"]
    alone = _classify(_fresh_ctx(), _payload(path, "exe-a", **other))
    np.testing.assert_array_equal(got[0], alone[0])
    np.testing.assert_array_equal(got[1], alone[1])
    assert not np.array_equal(got[1], plain[1])
    if "n_classes" in other:
        assert got[0].max() < 12 <= plain[0].max()


def test_a_tenant_whose_parameter_tree_differs_retraces_by_itself(tmp_path):
    """A checkpoint that holds a leaf in another dtype: the key is the same,
    so the wrapper is shared, and ``jax.jit``'s own cache traces again for
    the other tree. The tenant is answered by its own weights."""
    from agent_tpu.models import encoder

    cfg = encoder.EncoderConfig(**TINY)
    head = encoder.init_params(cfg, model_id="exe-npz")["head"]["w"]
    path = str(tmp_path / "half.npz")
    np.savez(path, **{"head.w": np.asarray(head, np.float16)})

    ctx, reg = _fresh_ctx(), MetricsRegistry()
    with _traced(reg):
        _classify(ctx, _payload("padded", "exe-a"))
        first = _account(ctx, reg)
        got = _classify(ctx, _payload("padded", path))
        second = _account(ctx, reg)
    assert second["entries"] == first["entries"]          # the shared wrapper
    assert second["misses"] == first["misses"]
    assert second["blocks"] == 2 * TINY["n_layers"]       # traced again
    alone = _classify(_fresh_ctx(), _payload("padded", path))
    np.testing.assert_array_equal(got[0], alone[0])
    np.testing.assert_array_equal(got[1], alone[1])
