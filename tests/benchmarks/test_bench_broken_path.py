"""``correct`` has to come out false when the timed path is broken. These
tests skip the harness's look for a chip (the rehearsal patch) and drive the
rest of a run with a fault planted UNDER the timed path, where an answer is
produced, or with the program's lower-precision path switched on (the
control, at a size a test run can hold)."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from agent_tpu.ops import map_classify_tpu  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import manifest  # noqa: E402

from test_bench_rehearsal import TINY_DRAIN, tiny  # noqa: E402,F401


def result(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return json.loads(lines[-1]), {
        c["number"]: c for c in map(json.loads, (
            ln for ln in lines if ln.startswith('{"bench": "compared"')))}


def test_scores_altered_where_they_are_produced_are_not_correct(tiny, capsys):
    """Every shard's scores come back 10 % low (a faulty fetch): off alike
    for every row, so it is the bias that shows it."""
    real = map_classify_tpu._fetch_pending

    def faulty(pending):
        vals, idx = real(pending)
        return vals * np.float32(0.9), idx

    tiny.setattr(map_classify_tpu, "_fetch_pending", faulty)
    code = bench_run.main(["--workload", "bert-base.drain-long", "--seed", "5",
                           "--seconds", "1", "--trace", "0"])
    last, compared = result(capsys)
    assert code == 0 and last["correct"] is False
    bias = compared["top5_logprob_bias_rms"]
    assert bias["ok"] is False
    assert bias["value"] == pytest.approx(-np.log(0.9), rel=0.02)


def test_answers_given_to_other_rows_are_not_correct(tiny, capsys):
    """Every shard answers its rows in reverse order (a batch put back
    together wrongly): each model's mean is untouched, the scatter is not."""
    real = map_classify_tpu._fetch_pending

    def faulty(pending):
        vals, idx = real(pending)
        return vals[::-1].copy(), idx[::-1].copy()

    tiny.setattr(map_classify_tpu, "_fetch_pending", faulty)
    code = bench_run.main(["--workload", "bert-base.drain-long", "--seed", "6",
                           "--seconds", "1", "--trace", "0"])
    last, compared = result(capsys)
    assert code == 0 and last["correct"] is False
    assert compared["top5_logprob_scatter_rms"]["ok"] is False


SMALL_BERT = {"d_model": 256, "n_heads": 4, "n_layers": 4, "d_ff": 1024,
              "max_len": 128, "n_classes": 64, "dtype": "bfloat16"}


def test_the_int8_control_at_a_size_a_test_run_can_hold(tiny):
    """The program with its int8 path switched on, as
    ``benchmarks/control.py`` switches it on the chip at the cell's own
    size: the bias it leaves in the served log-probabilities reads several
    times the sound run's."""
    tiny.setitem(manifest.TRAFFIC_OVERRIDES, "drain-long", dict(
        TINY_DRAIN, tenants=6, job_rows=48,
        row_bytes={"dist": "fixed", "value": 150}))
    m = manifest.load_manifest()
    value = {}
    for side, extra in (("sound", {}), ("control", {"quant": "int8"})):
        tiny.setitem(manifest.MODEL_OVERRIDES, "bert-base",
                     dict(SMALL_BERT, **extra))
        run = bench_run.run_cell(m, "bert-base.drain-long", 9, 1.0, 0)
        assert run["failed"] == 0
        value[side] = {c["number"]: c["value"] for c in run["checks"]}
    sound, control = (value[s]["top5_logprob_bias_rms"]
                      for s in ("sound", "control"))
    assert control > 2.5 * sound, value
