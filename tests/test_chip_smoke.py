"""CPU rehearsal of ``chip_smoke.py`` (the script that proves the system
starts on the chip): the ``drain`` and ``infer`` phases at a tiny width
through the same HTTP controller + pipelined agent the chip run uses, and
the guarantee that the script itself never passes on a CPU backend.

The script has no option for any of this: the tests patch its module
constants (widths, row counts, the required platform)."""

from __future__ import annotations

import json

import pytest

import chip_smoke
from agent_tpu.ops.serve_infer import reset_engines
from agent_tpu.runtime.runtime import get_runtime, reset_runtime

TINY_CLASSIFY = {
    "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64, "max_len": 64,
    "n_classes": 16, "dtype": "float32",
}
TINY_SEQ2SEQ = {
    "d_model": 32, "n_heads": 4, "n_enc_layers": 1, "n_dec_layers": 1,
    "d_ff": 64, "max_src_len": 128, "max_tgt_len": 16, "dtype": "float32",
}


@pytest.fixture()
def tiny(monkeypatch):
    """The smoke at a width the CPU runs in seconds, device check off."""
    for name, value in dict(
        REQUIRED_PLATFORM="cpu",
        CLASSIFY_MODEL=TINY_CLASSIFY, SEQ2SEQ_MODEL=TINY_SEQ2SEQ,
        ROW_BYTES=80, DRAIN_ROWS=64, DRAIN_SHARD=16, REFERENCE_ROWS=4,
        SUMMARIZE_ROWS=4, SUMMARIZE_LONG_BYTES=100, SUMMARIZE_MAX_NEW=12,
        TRAIN_ROWS=40, TRAIN_BATCH=8, TRAIN_EPOCHS=2, JOB_TIMEOUT_S=300.0,
    ).items():
        monkeypatch.setattr(chip_smoke, name, value)
    reset_runtime()
    reset_engines()
    yield
    reset_engines()
    reset_runtime()


def _phases(capsys):
    """The phases' JSON records (the agent's own log lines share stdout
    when the phase functions run outside ``main``)."""
    lines = capsys.readouterr().out.splitlines()
    records = [json.loads(ln) for ln in lines if ln.startswith("{")]
    return {rec["phase"]: rec for rec in records}


def test_drain_infer_train_rehearsal(tiny, tmp_path, capsys):
    data = chip_smoke.build_data(str(tmp_path), seed=7)
    stack = chip_smoke.Stack(get_runtime(), chip_smoke.SMOKE_TASKS)
    try:
        drained = chip_smoke.phase_drain(stack, data)
        chip_smoke.phase_infer(stack, data, drained)
        chip_smoke.phase_train(stack, data, str(tmp_path))
    finally:
        stack.close()
    out = _phases(capsys)
    assert out["drain"]["ok"] and out["drain"]["failed_shards"] == 0
    assert out["drain"]["rows"] == 64 and out["drain"]["shards"] == 4
    # float32 against float32: the drained top-1 IS the reference's.
    assert out["drain"]["reference"]["top1_exact"] == 4
    assert out["drain"]["csv_scanner"] in ("native", "python")
    assert len(drained["summaries"]) == 4
    reqs = out["infer"]["requests"]
    assert [r["op"] for r in reqs] == ["summarize"] * 4 + ["classify"] * 2
    # Engine against scan is bit-identical in float32 on the CPU.
    assert all(r["first_difference"] is None for r in reqs[:4])
    assert out["infer"]["kv_layout"] == "paged"
    # Rows 2/3 joined warm engines (phase_infer fails the run otherwise).
    assert sorted(e["bucket"] for e in out["infer"]["engines"]) == [64, 128]
    assert all(e["step"] == e["insert"] == 1 for e in out["infer"]["engines"])
    assert out["train"]["last_epoch_loss"] <= out["train"]["first_epoch_loss"]
    assert out["train"]["served_rows"] == 16


def test_result_on_another_device_fails(tiny, monkeypatch):
    """Every result body is held to the required device and to no
    fallback — the checks the rehearsal above runs with 'cpu'."""
    body = {"ok": True, "device": "cpu"}
    chip_smoke.check_result_body(body, "shard")
    with pytest.raises(chip_smoke.SmokeFailure, match="fell back"):
        chip_smoke.check_result_body({**body, "fallback": "cpu"}, "shard")
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "tpu")
    with pytest.raises(chip_smoke.SmokeFailure, match="device 'cpu'"):
        chip_smoke.check_result_body(body, "shard")


def test_main_fails_on_a_cpu_backend(capsys):
    """No accelerator → non-zero exit, ``ok: false`` on the last line, and
    nothing ran on the CPU."""
    assert chip_smoke.main([]) != 0
    lines = capsys.readouterr().out.splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert "no accelerator" in last["error"]
    assert not any(json.loads(ln).get("phase") for ln in lines[:-1])
