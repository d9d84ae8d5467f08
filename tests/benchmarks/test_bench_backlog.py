"""``benchmarks/harness/backlog.py``, the one helper behind both backlog
kinds: the ceiling every cell's backlog gives its window, the opening on a
regular acceptance (on hand-made stamps, and in a rehearsal whose poster is
held up), a ``setup_s`` that leaves the benchmark's own seconds out, and the
``window`` record both kinds print. CPU rehearsals as in
``test_bench_rehearsal.py``: no number printed here is a device number."""

from __future__ import annotations

import json
import math
import os
import statistics
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from agent_tpu.ops import map_classify_tpu  # noqa: E402
from agent_tpu.runtime.runtime import reset_runtime  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import backlog, manifest, schedule, stack  # noqa: E402

from test_bench_rehearsal import TINY_BERT, TINY_DRAIN  # noqa: E402
from test_bench_score import TINY_LM, TINY_SCORE  # noqa: E402

MANIFEST = manifest.load_manifest()

# The newest accepted reading of each cell, scaled to 100 % of its roofline
# and rounded up (ledger, PR 27: ``drain_rows_per_s`` 1,592.2 at
# ``encoder_roofline`` 78.16; 13,363 at 36.792; 1.2954 at ``lm_roofline``
# 82.862): a window must not be the ceiling a kernel PR runs into.
AT_THE_ROOFLINE = {
    "bert-base.drain-long": 2040.0,
    "bert-base.drain-short": 36300.0,
    "brumby-14b-base.score-long": 1.57,
}


# ---- the ceiling -----------------------------------------------------------

@pytest.mark.parametrize("cell", [c["name"] for c in MANIFEST["workloads"]])
def test_the_backlog_outlasts_a_program_at_its_roofline(cell):
    assert cell in AT_THE_ROOFLINE, "a new cell brings its level and roofline"
    traffic = manifest.load_traffic(manifest.find_cell(MANIFEST, cell)["traffic"])
    size = backlog.plan(traffic, MANIFEST["run_seconds"])
    assert size["ceiling_rows_per_s"] >= AT_THE_ROOFLINE[cell]
    assert size["n_jobs"] % traffic["tenants"] == 0
    assert size["shards"] * traffic["shard_rows"] == size["rows"]


def test_the_short_cells_ceiling_is_the_issues():
    size = backlog.plan(manifest.load_traffic("drain-short"), 10)
    assert (size["n_jobs"], size["shards"]) == (108, 864)
    assert size["ceiling_rows_per_s"] == pytest.approx(43929.6)


# ---- the opening acceptance, on hand-made stamps --------------------------

def stamps_of(gaps):
    out = [100.0]
    for g in gaps:
        out.append(out[-1] + g)
    return out


@pytest.mark.parametrize("gaps, lead_in, want", [
    # regular all along: number lead_in opens
    ([1.0] * 6, 4, (4, True)),
    ([1.0, 1.1, 0.9, 1.2], 4, (4, True)),
    # too few acceptances yet
    ([1.0] * 3, 4, None),
    # the lead-in's last post held up for three shards, then the burst of
    # what the device did meanwhile, then regular again
    ([1.0, 1.0, 1.0, 4.0, 0.01, 0.01, 1.0], 4, (7, True)),
    # the same, the regular one not in yet
    ([1.0, 1.0, 1.0, 4.0, 0.01, 0.01], 4, None),
    # a stall early in the lead-in does not move the median
    ([3.0, 1.0, 1.0, 1.0], 4, (4, True)),
    ([1.0, 1.0, 3.0, 0.01], 3, None),
    ([1.0, 1.0, 3.0, 0.01, 1.0], 3, (5, True)),
    # nothing regular: number 2 x lead_in opens the window all the same
    ([1.0, 1.0, 5.0, 5.0, 5.0, 5.0], 3, (6, False)),
    ([1.0, 1.0, 5.0, 5.0, 5.0], 3, None),
    # just inside and just outside 1.25 x, either way
    ([1.0, 1.0, 1.24], 3, (3, True)),
    ([1.0, 1.0, 1.26], 3, None),
    ([1.0, 1.0, 1.26, 1.0], 3, (4, True)),
    ([1.0, 1.0, 0.81], 3, (3, True)),
    ([1.0, 1.0, 0.79], 3, None),
])
def test_the_window_opens_on_a_regular_acceptance(gaps, lead_in, want):
    got = backlog.opening(stamps_of(gaps), lead_in)
    assert (got if got is None else got[::2]) == want
    if got is not None:
        assert got[1] == pytest.approx(statistics.median(gaps[:lead_in]))


# ---- the agent's knobs: the traffic file's --------------------------------

@pytest.mark.parametrize("traffic, knobs", [
    ("drain-long", {}), ("drain-short", {"pipeline_depth": 12}),
    ("score-long", {}),
])
def test_a_traffic_file_sets_only_knobs_the_program_has(traffic, knobs):
    """Short shards are 21 ms of device work, so the short cell's deployment
    stages and posts 12 ahead (PR 32); the other two run every default."""
    import dataclasses

    from agent_tpu.config import AgentConfig

    got = manifest.load_traffic(traffic).get("agent", {})
    assert got == knobs
    assert set(got) <= {f.name for f in dataclasses.fields(AgentConfig)}


@pytest.mark.parametrize("knobs, depth", [(None, 2), ({"pipeline_depth": 12}, 12)])
def test_the_agent_gets_the_traffic_files_knobs(monkeypatch, knobs, depth):
    """``AgentStack`` hands them to the program's own configuration, over
    what ``Config.from_env`` read, and refuses a knob the program does not
    have."""
    import agent_tpu.agent.app as app

    seen = {}

    class Agent:
        def __init__(self, config, **_):
            seen["agent"] = config.agent

        def run(self):
            pass

    monkeypatch.setattr(stack, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(app, "Agent", Agent)
    monkeypatch.setenv("PIPELINE_DEPTH", "5" if knobs is None else "7")
    reset_runtime()
    try:
        stack.AgentStack("http://127.0.0.1:9", ["map_classify_tpu"], knobs).close()
        assert seen["agent"].pipeline_depth == (5 if knobs is None else depth)
        assert seen["agent"].controller_url == "http://127.0.0.1:9"
        with pytest.raises(TypeError):
            stack.AgentStack("http://127.0.0.1:9", [], {"no_such_knob": 1})
    finally:
        reset_runtime()


# ---- rehearsals ------------------------------------------------------------

@pytest.fixture()
def tiny(monkeypatch):
    monkeypatch.setattr(stack, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(manifest, "MODEL_OVERRIDES", {
        "bert-base": TINY_BERT, "brumby-14b-base": TINY_LM})
    monkeypatch.setattr(manifest, "TRAFFIC_OVERRIDES", {
        "drain-long": dict(TINY_DRAIN, row_bytes={"dist": "fixed", "value": 80}),
        "drain-short": dict(TINY_DRAIN),
        "score-long": dict(TINY_SCORE),
    })
    reset_runtime()
    yield monkeypatch
    reset_runtime()


def bench_lines(capsys):
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return lines[-1], {ln["bench"]: ln for ln in lines if "bench" in ln}


WINDOW_KEYS = {
    "bench", "window_s", "shards", "rows", "models_in_window",
    "longest_silence_s", "gc_pauses_in_window", "gc_pause_s", "failed",
    "setup_s", "compiles_in_window", "warm_up",
    "all_compiles", "backlog_left", "backlog_shards", "ceiling_rows_per_s",
    "opened_after", "open_regular", "lead_in_gap_s",
}
SETUP_PHASES = ("imports_s", "jax_import_s", "controller_agent_s", "warm_up_s",
                "lead_in_s")
LEFT_OUT = ("backend_s", "own_inputs_s", "own_submit_s")


@pytest.mark.parametrize("cell", sorted(AT_THE_ROOFLINE))
def test_both_kinds_print_the_one_window_record(tiny, capsys, cell):
    code = bench_run.main(["--workload", cell, "--seed", "28", "--seconds",
                           "1", "--trace", "0"])
    result, bench = bench_lines(capsys)
    assert code == 0 and result["correct"] is True
    window, setup = bench["window"], bench["setup"]
    assert set(window) == WINDOW_KEYS
    traffic = manifest.load_traffic(manifest.find_cell(MANIFEST, cell)["traffic"])
    size = backlog.plan(traffic, 1.0)
    assert window["ceiling_rows_per_s"] == size["ceiling_rows_per_s"]
    assert window["backlog_shards"] == size["shards"]
    assert 0 < window["backlog_left"] < size["shards"] - window["shards"]
    lead_in = traffic["lead_in_shards"]
    assert lead_in <= window["opened_after"] <= 2 * lead_in
    # setup_s is the wall clock to the opening less the benchmark's own
    # seconds and the runtime's start, and the program's phases tile it.
    assert result["metrics"]["setup_s"]["value"] == setup["setup_s"]
    assert all(setup[k] > 0 for k in LEFT_OUT)
    assert setup["setup_s"] == pytest.approx(
        setup["wall_to_open_s"] - sum(setup[k] for k in LEFT_OUT))
    assert sum(setup[k] for k in SETUP_PHASES) == pytest.approx(
        setup["setup_s"], abs=0.05)
    # The per-layer readers of set-up read in an untraced run too.
    assert set(bench["setup_layers"]) == {
        "bench", "xla_compile_s.setup", "params_s.setup", "warm_up_s.setup"}
    assert bench["setup_layers"]["warm_up_s.setup"] == setup["warm_up_s"]
    # Every number compared is the result line's last key.
    assert list(result)[-1] == "compared"
    assert all({"value", "limit"} == set(v) for v in result["compared"].values())
    assert result["compared"]


def test_setup_s_leaves_out_a_sleep_in_the_row_generator(tiny, capsys):
    """The benchmark's own inputs are not the system's set-up."""
    nap, real = 1.5, schedule.drain_rows

    def slow(traffic, seed, n):
        time.sleep(nap)
        return real(traffic, seed, n)

    tiny.setattr(schedule, "drain_rows", slow)
    code = bench_run.main(["--workload", "bert-base.drain-short", "--seed",
                           "29", "--seconds", "1", "--trace", "0"])
    result, bench = bench_lines(capsys)
    assert code == 0 and result["correct"] is True
    setup = bench["setup"]
    assert setup["own_inputs_s"] >= nap
    assert (setup["wall_to_open_s"] - result["metrics"]["setup_s"]["value"]
            >= nap)


SHARD_S = 0.1     # the rehearsal's "device": a dispatch that takes this long
LEAD_IN = 4


def slow_device(monkeypatch):
    real = map_classify_tpu._execute_chunks

    def execute_chunks(*args, **kwargs):
        time.sleep(SHARD_S)
        return real(*args, **kwargs)

    monkeypatch.setattr(map_classify_tpu, "_execute_chunks", execute_chunks)
    monkeypatch.setitem(manifest.TRAFFIC_OVERRIDES, "drain-long", dict(
        TINY_DRAIN, row_bytes={"dist": "fixed", "value": 80},
        backlog_rows_per_s=400, lead_in_shards=LEAD_IN))


class HeldClock(backlog.PostClock):
    """The poster is held for three shard times before it posts acceptance
    number ``LEAD_IN`` of the lead-in: the one the window used to open at."""

    def factory(self):
        session, clock, held = super().factory(), self, []
        post = session.post

        def held_post(url, *args, **kwargs):
            if (url.endswith("/v1/results") and not held
                    and clock.lead_in_from is not None
                    and len(clock.posts) == clock.lead_in_from + LEAD_IN):
                held.append(True)
                time.sleep(3 * SHARD_S)
            return post(url, *args, **kwargs)

        session.post = held_post
        self.held = held
        return session


@pytest.mark.parametrize("held", [False, True])
def test_a_held_up_poster_does_not_put_its_burst_into_the_window(
        tiny, capsys, held):
    slow_device(tiny)
    if held:
        tiny.setattr(backlog, "PostClock", HeldClock)
    run = bench_run.run_cell(MANIFEST, "bert-base.drain-long", 30, 2.0, 0)
    _, bench = bench_lines(capsys)
    window = bench["window"]
    assert run["correct"] is True and run["failed"] == 0
    # The device does one shard every SHARD_S and a bit: a window that holds
    # a burst holds two or three shards more than its length allows.
    stamps = run["acceptances"]
    assert len(stamps) == run["shards"] + 1
    gap = statistics.median(b - a for a, b in zip(stamps, stamps[1:]))
    assert SHARD_S <= gap < 1.5 * SHARD_S
    assert run["shards"] <= math.floor(run["window_s"] / gap) + 1
    rate = run["end_to_end"]["drain_rows_per_s"]
    one_shard = TINY_DRAIN["shard_rows"] / run["window_s"]
    assert abs(rate - TINY_DRAIN["shard_rows"] / gap) <= one_shard
    if held:
        # It opened after the held post and the burst behind it.
        assert window["opened_after"] > LEAD_IN
        assert window["open_regular"] is True
    else:
        assert window["opened_after"] == LEAD_IN
