"""``classify_real_token_share.drain`` (PR 29): the reader on hand-made runs,
its entry in the manifest, and a CPU rehearsal of both ``bert-base`` cells
with shards large enough for staging to pack their short rows: the share it
reads, and that no shard of the window obtained an executable, whatever
number of slices the warm-up shards happened to have."""

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

NAME = "classify_real_token_share.drain"
SLOTS = "classify_token_slots_total"


def counter(*series):
    return {"type": "counter", "series": [
        {"labels": labels, "value": value} for labels, value in series]}


def slots(real, dispatched):
    return {SLOTS: counter(({"kind": "real"}, real),
                           ({"kind": "dispatched"}, dispatched))}


def run_of(before, after, kind="drain"):
    return {"kind": kind, "op": "map_classify_tpu", "shards": 20,
            "agent_metrics": (before, after)}


@pytest.mark.parametrize("before, after, want", [
    (slots(1e6, 2e6), slots(1e6 + 16_181 * 20, 2e6 + 284 * 64 * 20), 89.03),
    (slots(1e6, 2e6), slots(1e6 + 16_181 * 20, 2e6 + 512 * 64 * 20), 49.38),
    (slots(0, 0), slots(262_144.0, 262_144.0), 100.0),
    ({}, slots(900.0, 1000.0), 90.0),        # the series began in the window
])
def test_reader_reads_the_window(before, after, want):
    read = manifest.load_layer_metric(NAME).read
    assert read(run_of(before, after)) == pytest.approx(want, abs=0.01)


@pytest.mark.parametrize("before, after, kind", [
    ({}, {}, "drain"),                                  # the parent commit
    (slots(5.0, 9.0), slots(5.0, 9.0), "drain"),        # nothing dispatched
    ({}, {"classify_shards_total": counter(({"layout": "padded"}, 3.0))},
     "drain"),
    (slots(0, 0), slots(9.0, 10.0), "infer"),
])
def test_reader_gives_nothing_where_there_is_nothing_to_read(before, after, kind):
    read = manifest.load_layer_metric(NAME).read
    assert read(run_of(before, after, kind)) is None


def test_manifest_entry(manifests):
    m = manifests
    # THERE, once; later PRs append after it (``conftest.py`` appends two).
    (entry,) = [e for e in m["per_layer"] if e["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Ops",
        "moves": "drain_rows_per_s",
        "workloads": ["bert-base.drain-long", "bert-base.drain-short"]}
    for cell in m["workloads"]:
        names = {e["name"] for e in manifest.metrics_of_cell(
            m, cell["name"], "per_layer")}
        assert (NAME in names) == cell["name"].startswith("bert-base.")


# ---- CPU rehearsal: shards that pack ----------------------------------------

TINY_BERT = {
    "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64, "max_len": 64,
    "n_classes": 16, "dtype": "float32",
}
# 256-row shards: 104-130 program rows of 64 when packed, so two or three
# slices of 64, by the shard; three tenants, one warm-up shard each.
PACKING_DRAIN = {
    "shard_rows": 256, "tenants": 3, "job_rows": 1024,
    "backlog_rows_per_s": 60000, "lead_in_shards": 2, "trace_start_s": 0.2,
    "trace_seconds": 0.5, "agent": {},   # as TINY_DRAIN: the default depth
}


@pytest.fixture()
def tiny(monkeypatch):
    monkeypatch.setattr(stack, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(manifest, "MODEL_OVERRIDES", {"bert-base": TINY_BERT})
    monkeypatch.setattr(manifest, "TRAFFIC_OVERRIDES", {
        "drain-long": dict(PACKING_DRAIN, backlog_rows_per_s=30000,
                           row_bytes={"dist": "fixed", "value": 80}),
        "drain-short": dict(PACKING_DRAIN),
    })
    reset_runtime()
    yield monkeypatch
    reset_runtime()


@pytest.mark.parametrize("workload, share", [
    # Packed: two slices of 64 program rows hold a 256-row shard at 88 %,
    # three at 59 %; padded it would read 49.
    ("bert-base.drain-short", (60.0, 99.0)),
    ("bert-base.drain-long", (100.0, 100.0)),   # every row fills its bucket
])
def test_rehearsal_reads_the_share_and_builds_nothing_in_the_window(
        tiny, capsys, workload, share):
    code = bench_run.main(["--workload", workload, "--seed", str(2 ** 31 + 29),
                           "--seconds", "2", "--trace", "1"])
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    result = json.loads(lines[-1])
    assert code == 0 and result["correct"] is True, lines[-8:]
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert share[0] <= metrics[NAME] <= share[1], metrics
    assert metrics["compiles_in_window.drain"] == 0
    assert metrics["xla_executables_in_window.drain"] == 0
    # PR 32's appended reader, found by its name in the manifest: on the CPU
    # the program ticks every attention call as ``separate`` (the whole-row
    # kernel does not take it there), so there is a count to read and it is 0.
    assert metrics["fused_qkv_attention_blocks.setup"] == 0
