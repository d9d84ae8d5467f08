"""CPU rehearsal of ``benchmarks/run.py`` end to end, one test per cell, at
a tiny width and a 2-second window: the same two processes, taps, window
arithmetic, output check and result line the chip run uses.

``run.py`` has no option for any of this: the tests patch the harness's
module constants (required platform, model and traffic overrides), as
``tests/test_chip_smoke.py`` patches ``chip_smoke``'s. No number printed
here is a device number."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from agent_tpu.runtime.runtime import reset_runtime  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import manifest, stack  # noqa: E402

TINY_BERT = {
    "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64, "max_len": 64,
    "n_classes": 16, "dtype": "float32",
}
# ``agent``: a rehearsal keeps the program's own pipeline depth. The tests'
# platform is 8 virtual CPU devices, and their in-process collectives stop
# in a rendezvous once a dozen shards' programs are in flight at a time
# (``drain-short`` sets 12 for the chip, where a cell has one device).
TINY_DRAIN = {
    "shard_rows": 16, "tenants": 3, "job_rows": 32,
    "backlog_rows_per_s": 4000, "lead_in_shards": 2, "trace_start_s": 0.2,
    "trace_seconds": 0.5, "agent": {},
}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture()
def tiny(monkeypatch):
    monkeypatch.setattr(stack, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(manifest, "MODEL_OVERRIDES", {"bert-base": TINY_BERT})
    monkeypatch.setattr(manifest, "TRAFFIC_OVERRIDES", {
        "drain-long": dict(TINY_DRAIN,
                           row_bytes={"dist": "fixed", "value": 80}),
        "drain-short": dict(TINY_DRAIN),
    })
    reset_runtime()
    yield monkeypatch
    reset_runtime()


def last_line(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("workload, e2e", [
    ("bert-base.drain-long", {"drain_rows_per_s", "setup_s"}),
    ("bert-base.drain-short", {"drain_rows_per_s", "setup_s"}),
])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(tiny, capsys, workload, e2e, trace):
    code = bench_run.main(["--workload", workload, "--seed", str(2 ** 31 + 11),
                           "--seconds", "2", "--trace", str(trace)])
    result, lines = last_line(capsys)
    assert code == 0, lines[-5:]
    assert RESULT_KEYS <= set(result)
    # float32 against the float32 reference: exact work, so it agrees.
    assert result["correct"] is True, lines[-8:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(result["device"])
    if trace == 0:
        assert set(result["metrics"]) == e2e
        assert all(m["value"] > 0 for m in result["metrics"].values())
    else:
        names = {m["name"] for m in manifest.metrics_of_cell(
            manifest.load_manifest(), workload, "per_layer")}
        assert set(result["metrics"]) <= names
        # No device plane in a CPU trace: the device_trace readers find
        # nothing and are left out, never printed as a CPU number.
        assert not any("roofline" in n for n in result["metrics"])
    assert any('"compared"' in ln for ln in lines)


def test_unpatched_run_fails_off_tpu(capsys):
    """No accelerator → non-zero exit and no result line."""
    code = bench_run.main(["--workload", "bert-base.drain-long", "--seed", "3",
                           "--seconds", "2", "--trace", "0"])
    last, _ = last_line(capsys)
    assert code != 0
    assert last.get("correct") is False and "metrics" not in last
    assert "no accelerator" in last["error"]
