"""Test environment: force the CPU backend with 8 virtual devices.

Per SURVEY.md §4.3, all mesh/sharding/collective logic is exercised hermetically
on a virtual multi-chip mesh (``--xla_force_host_platform_device_count=8``) so CI
needs no TPU; TPU is a backend switch. This must run before anything imports
jax, hence module-level in conftest.
"""

import os

# Overwrite, not setdefault: a machine with a chip defaults to it, and tests
# must be hermetic on the virtual CPU mesh. The installed JAX honours both
# variables on its own — set before jax is imported, nothing else is needed.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# If something imported jax before this file (a pytest plugin), it read the
# variable too early; the config update pins the CPU backend regardless.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# The files of this directory that take a worker longest, slowest first
# (worker-seconds of the whole run, PR 47's CHANGES.md). ``--dist loadfile``
# hands files out in the order they are collected, which is by name:
# ``test_window_moe.py`` and ``test_tpu_compile.py`` came last and ran on while
# five workers stood idle. Handed out right after ``tests/benchmarks/`` (which
# keeps its place at the front, in fresh workers, as it always ran), the long
# files overlap and the short ones fill the end; no id changes, as it would by
# splitting a file. A file that grows past the last one here (``--durations``,
# see tests/README.md) is added.
LONGEST_FIRST = (
    "test_sparse_mla.py", "test_window_moe.py", "test_tpu_compile.py",
    "test_hybrid_ssm.py", "test_decoder_lm.py", "test_latent_mla.py",
    "test_hybrid_kda.py",
    "test_flash_attention.py", "test_parts.py", "test_conv_gqa.py",
)


def pytest_collection_modifyitems(items):
    """``tests/benchmarks/`` as collected, then the files of
    ``LONGEST_FIRST`` in that order, then the rest as collected; a file's
    cases stay together and in their order (the sort is stable), and every
    worker collects the same list."""
    rank = {name: at for at, name in enumerate(LONGEST_FIRST)}

    def place(item):
        if item.path.parent.name == "benchmarks":
            return -1
        return rank.get(item.path.name, len(rank))

    items.sort(key=place)


@pytest.fixture()
def tmp_csv(tmp_path):
    """A small CSV with quoted commas and a quoted embedded newline."""
    path = tmp_path / "data.csv"
    rows = ['id,text,risk']
    for i in range(25):
        rows.append(f'{i},"row {i}, text",{i * 0.5}')
    # Row with an embedded newline inside quotes (index 25).
    rows.append('25,"line one\nline two",12.5')
    path.write_text("\n".join(rows) + "\n", encoding="utf-8")
    return str(path)
