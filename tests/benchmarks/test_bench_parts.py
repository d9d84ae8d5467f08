"""``benchmarks/harness/part_times.py`` and the eight readers PR 38 appended:
the reduction on the recorded fixture under a hand-written part map (self
time of a container on a hand-made line: the fixture has no loop), the
readers with and without a trace, the ``parts`` line, and that an untraced
run never asks the runtime for its part map. CPU only; the fixture's numbers
are the recorded chip trace's, every other number is a count."""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from agent_tpu.runtime.runtime import TpuRuntime, reset_runtime  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import manifest, part_times, stack, trace_reduce  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures", "fixture.xplane.pb.gz")
with open(os.path.join(ROOT, "benchmarks", "fixtures",
                       "fixture_expected.json")) as _f:
    EXPECTED = json.load(_f)

CELLS = ["bert-base.drain-long", "bert-base.drain-short",
         "brumby-14b-base.score-long", "deepseek-v3.2.score-32k",
         "falcon-h1-34b.score-64k"]
PART_READERS = {f"{p}_device_ms_per_shard.drain": p
                for p in ("norm", "project", "mixer", "around", "ffn",
                          "experts")}
# The fixture's step program is eight chained matmul fusions behind a copy:
# a hand-written map, one name WITH the ``%`` the trace's events carry.
HAND_MAP = {"jit_bench_fixture_step": [{
    "instructions": {"fusion.7": "ffn", "fusion.6": "ffn",
                     "%fusion.5": "mixer", "fusion.4": "norm",
                     "fusion.3": "project", "fusion.2": "around",
                     "fusion.1": "experts", "fusion": "head",
                     "copy-done": None},
    "mixed": {"fusion.6": ["ffn", "norm"]}, "named_share": 0.8}]}


@pytest.fixture(scope="module")
def fixture_pd():
    return trace_reduce.load(FIXTURE)


def test_self_time_goes_to_the_innermost_event():
    # A loop that holds two of its body's events, then one after it.
    assert part_times.self_times([(0, 20), (5, 8), (10, 12), (20, 30)]) == [
        15.0, 3.0, 2.0, 10.0]
    # Events that only overlap: every instant counted once.
    assert part_times.self_times([(0, 10), (5, 15)]) == [5.0, 10.0]
    assert part_times.self_times([]) == []


def hand_made_trace():
    """One chip, one program event, a ``while`` that contains two body
    events and leaves 2 us of its own; markers around it all."""
    def event(name, start, duration):
        return SimpleNamespace(name=name, start_ns=start, duration_ns=duration)

    device = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name=trace_reduce.MODULES_LINE, events=[
            event("jit_lm_segment(123)", 1000, 11_000)]),
        SimpleNamespace(name=trace_reduce.OPS_LINE, events=[
            event("%while.65 = (s32[], f32[8]) while(...)", 1000, 10_000),
            event("%fusion.321 = f32[8] fusion(...)", 2000, 5000),
            event("%sparse_mla_attention.16 = f32[8] custom-call(...)",
                  7000, 3000),
            event("%copy.9 = f32[8] copy(...)", 11_000, 1000)])])
    host = SimpleNamespace(name=trace_reduce.HOST_PLANE, lines=[
        SimpleNamespace(name="main", events=[
            event(trace_reduce.MARKER_BEGIN, 0, 10),
            event(trace_reduce.MARKER_END, 20_000, 10)])])
    return SimpleNamespace(planes=[device, host])


def test_a_container_is_charged_what_its_children_do_not_cover():
    parts_map = {"jit_lm_segment": [{
        "instructions": {"while.65": None, "fusion.321": "ffn",
                         "sparse_mla_attention.16": "mixer"},
        "mixed": {}, "named_share": 0.5}]}
    out = part_times.reduce_parts(hand_made_trace(), parts_map)
    by_name = {name: (part, s) for _, part, name, s in out["rows"]}
    assert by_name["while.65"] == ("unnamed", pytest.approx(2e-6))
    assert by_name["fusion.321"] == ("ffn", pytest.approx(5e-6))
    assert out["rows"][0][2] == "fusion.321"       # not the loop
    assert out["busy_s"] == pytest.approx(11e-6)
    assert out["parts"] == {"ffn": pytest.approx(5e-6),
                            "mixer": pytest.approx(3e-6),
                            "unnamed": pytest.approx(3e-6)}
    # The same trace under ``trace_reduce``: the busy union agrees, and the
    # loop leads its ten heaviest.
    plain = trace_reduce.reduce(hand_made_trace())
    assert plain["busy_s"] == pytest.approx(out["busy_s"])
    assert plain["device_ops"][0][0] == "jit_lm_segment/%while.65"


def test_two_maps_under_one_module_name_the_reader_takes_the_one_that_fits():
    other = {"instructions": {"fusion.999": "head"}, "mixed": {},
             "named_share": 1.0}
    fits = {"instructions": {"while.65": None, "fusion.321": "ffn",
                             "sparse_mla_attention.16": "mixer",
                             "copy.9": "around"},
            "mixed": {}, "named_share": 0.75}
    out = part_times.reduce_parts(hand_made_trace(),
                                  {"jit_lm_segment": [other, fits]})
    assert out["parts"]["ffn"] == pytest.approx(5e-6)
    assert out["parts"]["around"] == pytest.approx(1e-6)
    assert "head" not in out["parts"]


def test_fixture_parts_add_up_to_the_busy_time(fixture_pd):
    out = part_times.reduce_parts(fixture_pd, HAND_MAP)
    assert out["chips"] == 1
    assert out["busy_s"] == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    assert sum(out["parts"].values()) == pytest.approx(EXPECTED["busy_s"],
                                                       rel=1e-9)
    # Three runs of the step program: 0.27 ms a fusion; the ``%`` either way.
    for part in ("mixer", "norm", "project", "around", "experts", "head"):
        assert out["parts"][part] == pytest.approx(0.00027, rel=0.02), part
    assert out["parts"]["ffn"] == pytest.approx(0.00054, rel=0.02)
    # What the map lacks (the copy, the whole second program) is unnamed.
    unnamed = {(m, n) for m, n, _ in out["unnamed_rows"]}
    assert unnamed == {("jit_bench_fixture_step", "copy-done"),
                       ("jit_bench_fixture_step", "copy-start"),
                       ("jit_bench_fixture_other", "add_reduce_fusion")}
    assert out["programs"]["jit_bench_fixture_other"] == {
        "unnamed": pytest.approx(EXPECTED["programs"]["other"]["seconds"],
                                 rel=1e-3)}
    assert out["mixed_s"] == pytest.approx(0.00027, rel=0.02)
    assert out["mixed_rows"][0][:4] == [
        "jit_bench_fixture_step", "fusion.6", "ffn", ["ffn", "norm"]]


def test_no_map_no_markers_no_device_plane(fixture_pd):
    bare = part_times.reduce_parts(fixture_pd, {})
    assert set(bare["parts"]) == {"unnamed"}
    assert bare["busy_s"] == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    host_only = SimpleNamespace(planes=[hand_made_trace().planes[1]])
    assert part_times.reduce_parts(host_only, HAND_MAP)["busy_s"] == 0.0
    no_markers = SimpleNamespace(planes=[hand_made_trace().planes[0]])
    assert part_times.reduce_parts(no_markers, HAND_MAP)["parts"] == {}
    # A capture has no markers: the caller names the interval.
    whole = part_times.reduce_parts(no_markers, {}, window=(0.0, 1e12))
    assert whole["busy_s"] == pytest.approx(11e-6)


def traced_run():
    return {"kind": "drain", "cell": {"name": "fixture.cell"},
            "trace": {"busy_s": EXPECTED["busy_s"],
                      "window_s": EXPECTED["window_s"]},
            "window_s": 10.0, "shards": 4, "agent_metrics": ({}, {})}


@pytest.fixture()
def rehearsed(monkeypatch, tmp_path):
    """A copy of the fixture as the run's capture (the part map is written
    beside it), the hand map as the runtime's."""
    import shutil

    asked = []
    capture = str(shutil.copy(FIXTURE, tmp_path / "fixture.xplane.pb.gz"))
    monkeypatch.setattr(part_times, "capture_path", lambda cell: capture)
    monkeypatch.setattr(part_times, "ask_runtime",
                        lambda: lambda: asked.append(1) or HAND_MAP)
    return asked


@pytest.mark.parametrize("name", sorted(PART_READERS)
                         + ["unnamed_device_share.drain"])
def test_reader_is_none_without_a_trace_and_a_number_with_one(
        rehearsed, capsys, tmp_path, name):
    read = manifest.load_layer_metric(name).read
    run = traced_run()
    for bare in (dict(run, trace=None), dict(run, kind="infer"),
                 dict(run, trace=dict(run["trace"], busy_s=0.0))):
        assert read(bare) is None
    assert rehearsed == []            # the runtime was never asked
    value = read(run)
    if name in PART_READERS:
        seconds = 0.00054 if PART_READERS[name] == "ffn" else 0.00027
        assert value == pytest.approx(
            1e3 * seconds / EXPECTED["window_s"] * 10.0 / 4, rel=0.02)
    else:
        assert value == pytest.approx(
            100 * (3 * 12.7e-6 + 39.7e-6) / EXPECTED["busy_s"], rel=0.02)
    # Reduced ONCE a run, whatever reads it next; one ``parts`` line.
    for other in list(PART_READERS) + ["unnamed_device_share.drain"]:
        manifest.load_layer_metric(other).read(run)
    assert rehearsed == [1]
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    (line,) = [ln for ln in lines if ln.get("bench") == "parts"]
    assert line["busy_s"] == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    assert sum(line["parts"].values()) == pytest.approx(line["busy_s"])
    assert {"program_parts_s", "load_s", "reduce_s", "xla_executables",
            "xla_cache_hits", "compile_seconds"} <= set(line["cost"])
    assert line["named_share"] == {"jit_bench_fixture_step": [0.8]}
    with open(tmp_path / "program_parts.json") as f:
        assert json.load(f) == HAND_MAP


def test_readers_are_none_where_the_program_has_no_part_map(monkeypatch):
    """The parent of PR 38: a runtime without ``program_parts``; and a run
    whose capture is gone."""
    run = traced_run()
    monkeypatch.setattr(part_times, "capture_path", lambda cell: FIXTURE)
    monkeypatch.setattr(part_times, "ask_runtime", lambda: None)
    for name in list(PART_READERS) + ["unnamed_device_share.drain"]:
        assert manifest.load_layer_metric(name).read(run) is None
    run = traced_run()
    monkeypatch.setattr(part_times, "capture_path", lambda cell: None)
    monkeypatch.setattr(part_times, "ask_runtime", lambda: lambda: HAND_MAP)
    assert manifest.load_layer_metric("ffn_device_ms_per_shard.drain").read(
        run) is None
    assert part_times.capture_path("no-such-cell") is None


def counter(value):
    return {"series": [{"labels": {"op": "map_score_lm"}, "value": value},
                       {"labels": {"op": "?"}, "value": 0.25}]}


def test_trace_lower_reader_reads_the_counter_at_the_windows_opening():
    read = manifest.load_layer_metric("trace_lower_s.setup").read
    run = {"kind": "drain", "agent_metrics": (
        {"runtime_trace_lower_seconds_total": counter(2.5)},
        {"runtime_trace_lower_seconds_total": counter(9.0)})}
    assert read(run) == 2.75
    assert read(dict(run, agent_metrics=({}, {}))) is None    # the parent
    assert read(dict(run, kind="infer")) is None


def test_the_eight_entries_are_appended_with_their_cells():
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in list(PART_READERS) + ["unnamed_device_share.drain"]:
        entry = entries[name]
        assert (entry["source"], entry["layer"], entry["moves"],
                entry["better"]) == ("device_trace", "Kernels",
                                     "drain_rows_per_s", "lower")
        # A metric lists the cells in which its reader finds something to
        # read: ``experts`` exists in one model, and the long classify
        # cell's program has NO ``around`` time (its residual adds live in
        # the matmul fusions; the chip's reading, PERF.md section 5).
        cells = (["deepseek-v3.2.score-32k"]
                 if name.startswith("experts_") else
                 [c for c in CELLS if c != "bert-base.drain-long"]
                 if name.startswith("around_") else CELLS)
        assert set(cells) == set(entry["workloads"])
        assert entry["unit"] == ("%" if name.startswith("unnamed") else "ms")
    setup = entries["trace_lower_s.setup"]
    assert (setup["source"], setup["layer"], setup["moves"], setup["unit"]
            ) == ("program_counter", "Runtime", "setup_s", "s")
    # The two cells whose ``setup_layers`` line no accepted test pins: for
    # the three others ``test_bench_backlog.py:
    # test_both_kinds_print_the_one_window_record`` holds the line's keys
    # to a literal set, which only a ``benchmark`` PR may edit.
    assert {"deepseek-v3.2.score-32k", "falcon-h1-34b.score-64k"} <= set(
        setup["workloads"])


def test_an_untraced_run_never_asks_for_the_part_map(monkeypatch, capsys):
    """A rehearsed cell, untraced, with ``program_parts`` patched to raise:
    the run is sound, and its ``setup_layers`` line holds the seconds the
    programs' first calls took beside XLA's. (The tiny widths and halved
    segments of ``test_bench_hybrid_ssm.py``'s rehearsal.)"""
    from agent_tpu.ops import map_score_lm

    if not hasattr(TpuRuntime, "program_parts"):
        pytest.skip("a program older than its part map (PR 38's parent)")

    def never(self):
        raise AssertionError("program_parts() in an untraced run")

    monkeypatch.setattr(TpuRuntime, "program_parts", never)
    monkeypatch.setattr(stack, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(manifest, "MODEL_OVERRIDES", {"falcon-h1-34b": {
        "vocab_size": 2048, "d_model": 64, "n_heads": 15, "n_kv_heads": 3,
        "d_head": 16, "d_ff": 96, "n_layers": 2, "ssm_n_heads": 6,
        "ssm_d_head": 16, "ssm_d_state": 24, "ssm_n_groups": 2,
        "dtype": "float32"}})
    monkeypatch.setattr(manifest, "TRAFFIC_OVERRIDES", {"score-64k": {
        "doc_tokens": {"dist": "fixed", "value": 2600}, "job_rows": 4,
        "backlog_rows_per_s": 2, "lead_in_shards": 1, "trace_start_s": 0.2,
        "trace_seconds": 0.5}})
    monkeypatch.setattr(map_score_lm, "SEGMENT_BUCKETS", (1024, 2048))
    reset_runtime()
    try:
        code = bench_run.main(["--workload", "falcon-h1-34b.score-64k",
                               "--seed", str(2 ** 31 + 38), "--seconds", "2",
                               "--trace", "0"])
    finally:
        reset_runtime()
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert code == 0 and lines[-1]["failed"] == 0, lines[-3:]
    (layers,) = [ln for ln in lines if ln.get("bench") == "setup_layers"]
    assert layers["trace_lower_s.setup"] > 0
    assert layers["xla_compile_s.setup"] is not None
    assert not [ln for ln in lines if ln.get("bench") == "parts"]
