"""Tier-1 tests of the benchmark's yardstick: the trace reduction on the
recorded fixture, the schedule, the arithmetic, the peaks and FLOP tables,
and the manifest — including that a new configuration, traffic mix, cell and
per-layer metric are added as files plus entries, with no edit to
``run.py`` or the harness."""

from __future__ import annotations

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import flops, manifest, peaks, schedule, stack, stats  # noqa: E402
from benchmarks.harness import trace_reduce as tr  # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmarks", "fixtures")


# ---- trace reduction on the recorded fixture ------------------------------

@pytest.fixture(scope="module")
def fixture_reduction():
    with open(os.path.join(FIXTURES, "fixture_expected.json")) as f:
        expected = json.load(f)
    pd = tr.load(os.path.join(FIXTURES, "fixture.xplane.pb.gz"))
    got = tr.reduce(
        pd, begin_wall_ns=expected["begin_wall_ns"],
        host_spans=[tuple(s) for s in expected["host_spans"]],
        program_patterns=expected["program_patterns"])
    return got, expected


def test_fixture_busy_union_and_idle_share(fixture_reduction):
    got, expected = fixture_reduction
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert got["idle_share"] == pytest.approx(
        1.0 - expected["busy_s"] / expected["window_s"], rel=1e-9)
    # Busy is a union: never more than the summed operation time.
    assert got["busy_s"] <= sum(s for _, s in got["device_ops"]) + 1e-4


def test_fixture_per_program_time(fixture_reduction):
    got, expected = fixture_reduction
    for label, want in expected["programs"].items():
        assert got["programs"][label]["count"] == want["count"]
        assert got["programs"][label]["seconds"] == pytest.approx(
            want["seconds"], rel=1e-9)
        # Every fixture program lies wholly inside the markers.
        assert got["programs"][label]["clipped_seconds"] == pytest.approx(
            want["seconds"], rel=1e-9)
    assert set(got["modules"]) == {"jit_bench_fixture_step",
                                   "jit_bench_fixture_other"}
    assert got["device_ops"][0][0].startswith("jit_bench_fixture_step/%fusion")
    assert len(got["device_ops"]) <= 10


def test_fixture_gap_attribution_through_the_markers(fixture_reduction):
    got, expected = fixture_reduction
    gaps = dict(got["idle_gaps"])
    # The host slept 20 ms in a "post" span after each of three steps: the
    # device's idle time lies under those spans once the wall-clock spans
    # are laid on the trace's clock through the begin marker.
    assert gaps["post"] == pytest.approx(expected["idle_gaps"]["post"], rel=1e-9)
    assert gaps["post"] > 0.055
    assert sum(gaps.values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    # Without the wall-clock anchor nothing can be attributed.
    pd = tr.load(os.path.join(FIXTURES, "fixture.xplane.pb.gz"))
    blind = tr.reduce(pd, host_spans=[tuple(s) for s in expected["host_spans"]])
    assert "post" not in dict(blind["idle_gaps"])


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.total(tr.union([(0, 1), (0.5, 2)])) == 2
    assert tr.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tr.clip([(0, 10)], 2, 3) == [(2, 3)]
    assert tr.attribute((0, 10), [("a", 0, 3), ("b", 2, 9)]) == "b"
    assert tr.attribute((0, 1), []) == "no_host_span"
    assert tr.short_op("%fusion.3 = bf16[2,2]{1,0} fusion(...)") == "%fusion.3"
    assert tr.short_module("jit_run_fwd(123)") == "jit_run_fwd"


# ---- the schedule ----------------------------------------------------------

SHORT = {"row_bytes": {"dist": "lognormal", "median": 28, "sigma": 0.6,
                       "min": 8, "max": 64}, "order_seed": 107}


def test_rows_are_byte_identical_for_one_seed():
    a = schedule.drain_rows(SHORT, 2 ** 31 + 5, 2048)
    assert a == schedule.drain_rows(SHORT, 2 ** 31 + 5, 2048)
    assert a != schedule.drain_rows(SHORT, 2 ** 31 + 6, 2048)


def test_every_seed_offers_the_same_work_in_another_order():
    """The same sizes in the same sequence (the traffic file's
    ``order_seed`` shuffles them, PR 32), said in other bytes: what a seed
    orders anew is the letters of every row, not which shard a row falls
    into."""
    a, b = schedule.drain_rows(SHORT, 1, 1024), schedule.drain_rows(SHORT, 2, 1024)
    assert list(map(len, a)) == list(map(len, b))
    assert list(map(len, a)) != sorted(map(len, a))
    assert not set(a) & set(b)
    c = schedule.drain_rows(dict(SHORT, order_seed=108), 1, 1024)
    assert sorted(map(len, c)) == sorted(map(len, a))
    assert list(map(len, c)) != list(map(len, a))
    assert len(set(a)) == len(a)                      # no row twice
    lengths = sorted(map(len, a))
    assert lengths[0] == 8 and lengths[-1] == 64
    assert 26 <= lengths[len(lengths) // 2] <= 30


def test_drain_rows():
    long_rows = schedule.drain_rows(
        {"row_bytes": {"dist": "fixed", "value": 600}, "order_seed": 0}, 7, 64)
    assert {len(r) for r in long_rows} == {600} and len(set(long_rows)) == 64
    a = schedule.drain_rows(SHORT, 1, 1024)
    assert all(not r.startswith(" ") and not r.endswith(" ") and '"' not in r
               for r in a)


def test_csv_round_trip(tmp_path):
    import csv

    rows = schedule.drain_rows(
        {"row_bytes": {"dist": "uniform", "min": 5, "max": 40}, "order_seed": 1},
        9, 50)
    path = str(tmp_path / "job.csv")
    schedule.write_csv(path, rows)
    with open(path, newline="") as f:
        got = list(csv.DictReader(f))
    assert [r["text"] for r in got] == rows
    assert [int(r["id"]) for r in got] == list(range(50))


# ---- arithmetic -----------------------------------------------------------

def test_rate():
    assert stats.rate(1024, 2.0) == 512.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_quartile_spread_is_the_contracts():
    import statistics

    values = [100, 101, 99, 102, 98, 100.5]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartile_spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


def snapshot(**phases):
    """A registry snapshot with ``task_phase_seconds`` series (sum, count)."""
    return {"task_phase_seconds": {"series": [
        {"labels": {"op": "map_classify_tpu", "phase": k}, "sum": v[0],
         "count": v[1]} for k, v in phases.items()]}}


def test_histogram_delta():
    before, after = snapshot(fetch=(1.0, 2)), snapshot(fetch=(3.5, 7))
    assert stack.histogram_delta(before, after, "task_phase_seconds",
                                 op="map_classify_tpu", phase="fetch") == (2.5, 5)
    assert stack.histogram_delta(before, after, "task_phase_seconds",
                                 op="map_classify_tpu", phase="stage") == (0.0, 0)


def test_post_reader_leaves_the_wait_for_the_device_out():
    """``post_ms_per_shard.drain`` is host work: the poster's span (fetch
    wait + shaping + HTTP post) less the fetch wait."""
    run = {"kind": "drain", "op": "map_classify_tpu",
           "post_span_s": [0.410, 0.412, 0.408],
           "agent_metrics": (snapshot(fetch=(0.0, 0)),
                             snapshot(fetch=(1.2, 3)))}
    read = manifest.load_layer_metric("post_ms_per_shard.drain").read
    assert read(run) == pytest.approx(10.0)
    wait = manifest.load_layer_metric("fetch_wait_ms_per_shard.drain").read
    assert wait(run) == pytest.approx(400.0)
    assert read(dict(run, post_span_s=[])) is None


def test_roofline_reader_is_needed_flops_over_the_programs_time():
    """1,000 rows/s that need 98.5 GFLOP each while the program holds the
    device for 90 % of the traced interval: 98.5 TF/s / 0.9 / 197 TF/s."""
    run = {"kind": "drain", "end_to_end": {"drain_rows_per_s": 1000.0},
           "mean_flops_per_row": 98.5e9, "peaks": peaks.lookup("TPU v5 lite"),
           "trace": {"window_s": 3.0, "programs": {"classify": {
               "seconds": 2.4, "count": 12, "clipped_seconds": 2.7}}}}
    read = manifest.load_layer_metric("encoder_roofline").read
    assert read(run) == pytest.approx(100.0 * 98.5e12 / 0.9 / 197e12)
    assert read(dict(run, trace=None)) is None
    assert read(dict(run, peaks=None)) is None


def test_peaks_raise_on_an_unknown_device():
    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("TPU v9")


def test_flops_hand_worked_bert_base():
    cfg = manifest.load_config(manifest.load_manifest(), "bert-base")["model"]
    assert flops.encoder_flops_per_row(cfg, 64) == 11_022_630_912
    assert flops.encoder_flops_per_row(cfg, 512) == 96_636_764_160
    assert flops.encoder_flops_per_row(cfg, 512, with_head=True) == \
        96_636_764_160 + 2 * 768 * 1000
    # Needed work counts real tokens, capped at the model's positions.
    assert flops.encoder_flops_needed(cfg, [600, 64]) == \
        96_636_764_160 + 11_022_630_912


# ---- the manifest ---------------------------------------------------------

def test_every_entry_resolves_and_every_name_passes_the_rules(manifests):
    """Over the committed manifest, and over a copy with a configuration, a
    four-chip cell and two per-layer entries APPENDED (``conftest.py``): what
    a later PR may do passes every rule, so no rule pins an order or a
    count."""
    m = manifests
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks", "tests/benchmarks"]
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in m["workloads"]}
    assert len(cells) == len(m["workloads"]) <= 24
    # The driver's rule: 1 chip or 4; of the cells at most a quarter, rounded
    # down, may ask for 4, and one always may.
    assert all(w["chips"] in (1, 4) for w in m["workloads"])
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)
    for w in m["workloads"]:
        assert 1 <= len(w["why"]) <= 200
        for key in ("name", "config", "traffic"):
            assert manifest.NAME.match(w[key]), w[key]
        config = manifest.load_config(m, w["config"])
        traffic = manifest.load_traffic(w["traffic"])
        assert hasattr(manifest.load_kind(traffic["kind"]), "run_cell")
        ref = manifest.load_reference(config["reference"])
        assert callable(ref.logits) and callable(ref.compare)
        assert config["check"]["limits"] and traffic["tenants"] >= 1
        # The order of a backlog's sizes is the traffic file's, never --seed's.
        assert isinstance(traffic["order_seed"], int)
        if "needed_work" in config:
            assert callable(manifest.load_needed_work(
                config["needed_work"]).mean_needed)
        reported = {x["name"] for x in manifest.metrics_of_cell(
            m, w["name"], "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layer = manifest.metrics_of_cell(m, w["name"], "per_layer")
        assert layer
        for entry in layer:
            assert entry["moves"] in reported
            assert callable(manifest.load_layer_metric(entry["name"]).read)
    for c in m["configs"]:
        assert manifest.NAME.match(c["name"])
        assert c["file"].startswith("benchmarks/configs/")
        assert c["reduced"] == manifest.load_config(m, c["name"])["reduced"]
        assert any(w["config"] == c["name"] for w in m["workloads"])
    for x in m["end_to_end"] + m["per_layer"]:
        assert manifest.NAME.match(x["name"]) and manifest.UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert x["source"] in manifest.SOURCES
        assert set(x.get("workloads", cells)) <= cells
    for x in m["end_to_end"]:
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
        assert set(x) <= {"name", "unit", "better", "bound", "source", "workloads"}
    for x in m["per_layer"]:
        assert x["moves"] in e2e and len(x["layer"]) <= 200
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if "roofline" in x["name"]:
            assert x["unit"] == "%" and x["source"] == "device_trace"
    for name in ("end_to_end", "per_layer", "configs"):
        names = [x["name"] for x in m[name]]
        assert len(set(names)) == len(names)
    for root, _, files in os.walk(manifest.BENCH_DIR):
        for name in files:
            if "__pycache__" not in root:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


def test_new_config_traffic_cell_metric_and_kind_are_files_plus_entries(
        tmp_path, monkeypatch):
    """A later PR adds each by adding files and entries: the harness finds a
    dummy of every kind with no edit to ``run.py`` or ``harness/``."""
    bench = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics", "harness/kinds",
                "reference"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs" / "dummy.json").write_text(json.dumps({
        "model": {"d_model": 8}, "reference": "dummyref", "reduced": []}))
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps({
        "kind": "dummykind", "rate": 3}))
    (bench / "layer_metrics" / "dummy_ms.x.py").write_text(
        "def read(run):\n    return run['answer'] * 2\n")
    (bench / "layer_metrics" / "dummy_nothing.py").write_text(
        "def read(run):\n    return None\n")
    (bench / "harness" / "kinds" / "dummykind.py").write_text(
        "def run_cell(ctx):\n"
        "    return {'answer': ctx['traffic']['rate'] + ctx['config']['model']['d_model'],\n"
        "            'device': {'platform': 'cpu', 'kind': 'cpu', 'count': 1},\n"
        "            'end_to_end': {'setup_s': 1.0, 'dummy_per_s': 5.0}}\n")
    (bench / "reference" / "dummyref.py").write_text("def init_params():\n    return {}\n")
    m = {
        "configs": [{"name": "dummy", "file": "benchmarks/configs/dummy.json"}],
        "workloads": [{"name": "dummy.mix", "config": "dummy",
                       "traffic": "dummy-mix", "chips": 1, "why": "x"}],
        "end_to_end": [
            {"name": "dummy_per_s", "unit": "1/s", "workloads": ["dummy.mix"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "dummy_ms.x", "unit": "ms", "moves": "dummy_per_s",
             "workloads": ["dummy.mix"]},
            {"name": "dummy_nothing", "unit": "ms", "moves": "dummy_per_s"}],
    }
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    monkeypatch.setattr(manifest, "BENCH_DIR", str(bench))
    from benchmarks import run as bench_run

    run = bench_run.run_cell(m, "dummy.mix", 1, 1.0, 0)
    assert run["metrics"] == {"dummy_per_s": {"value": 5.0, "unit": "1/s"},
                              "setup_s": {"value": 1.0, "unit": "s"}}
    run = bench_run.run_cell(m, "dummy.mix", 1, 1.0, 1)
    # A reader that finds nothing returns nothing and is left out.
    assert run["metrics"] == {"dummy_ms.x": {"value": 22.0, "unit": "ms"}}
    with pytest.raises(KeyError):
        manifest.find_cell(m, "no.such.cell")
