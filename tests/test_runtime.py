"""Device runtime tests — run on the 8-device virtual CPU mesh (conftest)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from agent_tpu.config import DeviceConfig
from agent_tpu.runtime import MeshSpec, TpuRuntime, build_mesh
from agent_tpu.runtime.executor import ExecutableCache
from agent_tpu.runtime.runtime import detect_platform, get_runtime, reset_runtime


def test_virtual_mesh_has_8_devices():
    assert len(jax.devices()) == 8  # conftest flag took effect


def test_meshspec_defaults_all_to_dp():
    spec = MeshSpec.resolve(8)
    assert dict(spec.axes) == {"dp": 8, "tp": 1, "sp": 1}


def test_meshspec_partial_shape():
    spec = MeshSpec.resolve(8, {"tp": 2})
    assert dict(spec.axes) == {"dp": 4, "tp": 2, "sp": 1}
    spec = MeshSpec.resolve(8, {"tp": 2, "sp": 2})
    assert dict(spec.axes) == {"dp": 2, "tp": 2, "sp": 2}


def test_meshspec_rejects_indivisible():
    with pytest.raises(ValueError):
        MeshSpec.resolve(8, {"tp": 3})
    with pytest.raises(ValueError):
        MeshSpec.resolve(8, {"dp": 16})
    with pytest.raises(ValueError):
        MeshSpec.resolve(8, {"tp": 0})


def test_build_mesh_axes():
    mesh = build_mesh(shape={"dp": 2, "tp": 2, "sp": 2})
    assert dict(mesh.shape) == {"dp": 2, "tp": 2, "sp": 2}


def test_runtime_shards_batch_over_dp():
    rt = TpuRuntime(DeviceConfig())
    assert rt.n_devices == 8
    batch = np.arange(16 * 3, dtype=np.float32).reshape(16, 3)
    arr = rt.put_batch(batch)
    assert arr.sharding.spec == jax.sharding.PartitionSpec("dp")
    # Each of the 8 devices holds 2 of the 16 rows.
    assert arr.addressable_shards[0].data.shape == (2, 3)
    np.testing.assert_array_equal(np.asarray(arr), batch)


def test_params_store_builds_once():
    rt = TpuRuntime(DeviceConfig())
    calls = []

    def build():
        calls.append(1)
        return {"w": np.ones((4, 4), dtype=np.float32)}

    p1 = rt.get_params("m", build)
    p2 = rt.get_params("m", build)
    assert len(calls) == 1
    assert p1 is p2


def test_executable_cache_counts():
    cache = ExecutableCache()
    fn1 = cache.get_or_build(("k", 1), lambda: (lambda x: x + 1))
    fn2 = cache.get_or_build(("k", 1), lambda: (lambda x: x + 2))
    assert fn1 is fn2
    assert cache.stats() == {"entries": 1, "hits": 1, "misses": 1}


def test_detect_platform_cpu_here():
    assert detect_platform() == "cpu"  # conftest forces JAX_PLATFORMS=cpu


def test_detect_platform_raises_when_no_backend_initializes(monkeypatch):
    """A backend that cannot initialize is an error, never a quiet 'cpu':
    an agent meant for a chip must not carry on on the wrong device."""
    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        detect_platform()
    # The kill-switch answers without asking JAX at all.
    assert detect_platform(tpu_disabled=True) == "cpu"


@pytest.fixture()
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_one_dir_inside_the_checkout(
    restore_cache_dir, monkeypatch, tmp_path
):
    """Nothing placed the cache from outside → the checkout's own fixed
    directory, whatever the working directory (the path is part of the
    cache's key: a directory that moves never hits)."""
    from agent_tpu.utils.paths import REPO_ROOT

    seen = []
    for name in ("here", "there"):
        (tmp_path / name).mkdir()
        monkeypatch.chdir(tmp_path / name)
        jax.config.update("jax_compilation_cache_dir", None)
        TpuRuntime(DeviceConfig())
        seen.append(jax.config.jax_compilation_cache_dir)
    assert seen[0] == seen[1] == os.path.join(REPO_ROOT, ".cache", "xla")
    assert os.path.isdir(os.path.join(REPO_ROOT, "agent_tpu"))  # the checkout


def test_compile_cache_dir_set_from_outside_is_left_alone(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set → JAX uses it and the runtime sets
    no other directory in code (a real process: the variable is read when
    jax is imported)."""
    outside = str(tmp_path / "placed_from_outside")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from agent_tpu.runtime.runtime import TpuRuntime\n"
         "TpuRuntime()\n"
         "print(jax.config.jax_compilation_cache_dir)"],
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": outside,
             "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == outside


def test_singleton_reset():
    reset_runtime()
    rt1 = get_runtime()
    assert get_runtime() is rt1
    reset_runtime()
    assert get_runtime() is not rt1


def test_describe_telemetry_shape():
    rt = TpuRuntime(DeviceConfig())
    d = rt.describe()
    assert d["platform"] == "cpu"
    assert d["n_devices"] == 8
    assert d["mesh"] == {"dp": 8, "tp": 1, "sp": 1}


def test_clear_params_empties_store_and_rebuilds():
    """clear_params drops every resident model (HBM give-back for
    many-model workloads — see the r4 bench RESOURCE_EXHAUSTED note) and
    the next get_params rebuilds from scratch."""
    import numpy as np

    from agent_tpu.config import DeviceConfig
    from agent_tpu.runtime.runtime import TpuRuntime

    rt = TpuRuntime(config=DeviceConfig(tpu_disabled=True),
                    devices=jax.devices("cpu")[:2])
    builds = []

    def build(tag):
        def f():
            builds.append(tag)
            return {"w": np.ones((4, 4), np.float32)}
        return f

    rt.get_params("m-a", build("a"))
    rt.get_params("m-b", build("b"))
    rt.get_params("m-a", build("a2"))     # cached — no rebuild
    assert builds == ["a", "b"]
    assert len(rt._params) == 2
    rt.clear_params()
    assert len(rt._params) == 0
    rt.get_params("m-a", build("a3"))
    assert builds == ["a", "b", "a3"]


def test_clear_params_fences_in_flight_build():
    """A clear() racing an in-flight build must win: the late insert is
    dropped so a post-clear store is actually empty (the HBM give-back
    contract of clear_params)."""
    import threading

    import numpy as np

    from agent_tpu.config import DeviceConfig
    from agent_tpu.runtime.runtime import TpuRuntime

    rt = TpuRuntime(config=DeviceConfig(tpu_disabled=True),
                    devices=jax.devices("cpu")[:2])
    build_started = threading.Event()
    release_build = threading.Event()

    def slow_build():
        build_started.set()
        release_build.wait(5)
        return {"w": np.ones((2, 2), np.float32)}

    out = {}
    t = threading.Thread(
        target=lambda: out.setdefault(
            "tree", rt.get_params("raced-model", slow_build)
        )
    )
    t.start()
    assert build_started.wait(5)
    rt.clear_params()            # races the in-flight build
    release_build.set()
    t.join(5)
    assert "tree" in out         # the caller still gets its params
    assert len(rt._params) == 0  # ...but the cleared store stays empty
    assert rt.describe()["models_resident"] == []
