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
