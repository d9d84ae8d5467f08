"""The seven per-layer readers that read the agent's own account (ISSUE 24)
on hand-made runs: the value where the series is there, ``None`` where the
program has no such series (the parent commit); and the name of the function
the classify op jits, held against the roofline reader's pattern."""

from __future__ import annotations

import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest  # noqa: E402

OP = "map_classify_tpu"


def counter(*series):
    return {"type": "counter", "series": [
        {"labels": labels, "value": value} for labels, value in series]}


def histogram(*series):
    return {"type": "histogram", "series": [
        {"labels": labels, "sum": total, "count": count}
        for labels, total, count in series]}


def run_of(before, after, **extra):
    return {"kind": "drain", "op": OP, "shards": 20,
            "agent_metrics": (before, after), **extra}


# What the counters read at the window's two ends, 10 s apart, on a chip
# that waited 50 ms in all; the thread's states tile those 10 s.
BEFORE = {
    "device_busy_seconds_total": counter(({"op": OP}, 30.0)),
    "device_idle_seconds_total": counter(({}, 2.0)),
    "device_thread_seconds_total": counter(
        ({"state": "wait_staged"}, 1.0), ({"state": "dispatch"}, 3.0),
        ({"state": "wait_post"}, 28.0)),
    "task_phase_seconds": histogram(
        ({"op": OP, "phase": "post_http"}, 0.30, 60),
        ({"op": OP, "phase": "fetch"}, 20.0, 60)),
    "agent_lease_seconds": histogram(
        ({"outcome": "tasks"}, 0.20, 40), ({"outcome": "idle"}, 9.0, 3)),
    "runtime_xla_executables_total": counter(({}, 90.0)),
    "runtime_compile_seconds_total": counter(
        ({"op": OP}, 17.25), ({"op": "?"}, 0.5)),
    "runtime_params_seconds_total": counter(({}, 18.5)),
}
AFTER = {
    "device_busy_seconds_total": counter(
        ({"op": OP}, 39.5), ({"op": "echo"}, 0.45)),
    "device_idle_seconds_total": counter(({}, 2.05)),
    "device_thread_seconds_total": counter(
        ({"state": "wait_staged"}, 1.4), ({"state": "dispatch"}, 3.2),
        ({"state": "wait_post"}, 37.4)),
    "task_phase_seconds": histogram(
        ({"op": OP, "phase": "post_http"}, 0.42, 80),
        ({"op": OP, "phase": "fetch"}, 28.0, 80)),
    "agent_lease_seconds": histogram(
        ({"outcome": "tasks"}, 0.26, 50), ({"outcome": "idle"}, 9.0, 3)),
    "runtime_xla_executables_total": counter(({}, 92.0)),
    "runtime_compile_seconds_total": counter(
        ({"op": OP}, 19.0), ({"op": "?"}, 0.5)),
    "runtime_params_seconds_total": counter(({}, 18.5)),
}

# name -> (value on the run above, the series whose absence means "nothing
# to read": the program predates the counter or span).
READERS = {
    "agent_device_busy.drain": (
        100.0 * 9.95 / 10.0, "device_thread_seconds_total"),
    "dispatch_starved_ms_per_shard.drain": (
        0.4 * 1e3 / 20, "device_thread_seconds_total"),
    "http_post_ms_per_shard.drain": (0.12 * 1e3 / 20, "task_phase_seconds"),
    "lease_rtt_ms.drain": (0.06 * 1e3 / 10, "agent_lease_seconds"),
    "xla_executables_in_window.drain": (2.0, "runtime_xla_executables_total"),
    "xla_compile_s.setup": (17.75, "runtime_xla_executables_total"),
    "params_s.setup": (18.5, "runtime_params_seconds_total"),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_value(name):
    want, _ = READERS[name]
    read = manifest.load_layer_metric(name).read
    assert read(run_of(BEFORE, AFTER)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_nothing_where_the_series_is_absent(name):
    _, series = READERS[name]
    read = manifest.load_layer_metric(name).read
    before = {k: v for k, v in BEFORE.items() if k != series}
    after = {k: v for k, v in AFTER.items() if k != series}
    assert read(run_of(before, after)) is None
    # Another traffic kind has none of it either, and nothing raises.
    assert read(dict(run_of(BEFORE, AFTER), kind="infer")) is None


def test_parent_commits_counters_are_not_read_under_the_new_names():
    """The parent has ``device_busy/idle_seconds_total`` (dispatch seconds,
    a host thread's queue wait) and ``runtime_compile_seconds_total`` (jit
    wrapper builds): the readers must not print those as the new metrics."""
    parent = {k: v for k, v in AFTER.items() if k in (
        "device_busy_seconds_total", "device_idle_seconds_total",
        "runtime_compile_seconds_total", "task_phase_seconds")}
    run = run_of(parent, parent)
    for name in ("agent_device_busy.drain", "xla_compile_s.setup",
                 "xla_executables_in_window.drain", "params_s.setup",
                 "dispatch_starved_ms_per_shard.drain"):
        assert manifest.load_layer_metric(name).read(run) is None, name


def test_every_new_metric_is_in_the_manifest_for_both_drain_cells():
    """Contains both: a later cell that the readers can read lists itself."""
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in READERS:
        assert {"bert-base.drain-long", "bert-base.drain-short"} <= set(
            entries[name]["workloads"]), name
    assert entries["xla_compile_s.setup"]["moves"] == "setup_s"
    assert entries["params_s.setup"]["moves"] == "setup_s"


def test_classify_program_is_still_named_for_the_roofline_reader():
    """``encoder_roofline`` finds the classify program in a device trace by
    XLA's module name ``jit_run_fwd(<fingerprint>)``: the function
    ``_execute_chunks`` jits must keep the name ``run_fwd``. The runtime's
    compile listener reports it as ``jit(run_fwd)``."""
    import jax

    from agent_tpu.config import DeviceConfig
    from agent_tpu.obs import trace as obs_trace
    from agent_tpu.ops import get_op
    from agent_tpu.runtime.context import OpContext
    from agent_tpu.runtime.runtime import TpuRuntime

    runtime = TpuRuntime(
        config=DeviceConfig(tpu_disabled=True, mesh_shape={"dp": 1}),
        devices=jax.devices("cpu")[:1])
    buf = obs_trace.SpanBuffer()
    obs_trace.set_enabled(True)
    try:
        with obs_trace.use_context(obs_trace.TraceContext(
                trace_id="roofline-name", tracer=buf, op=OP)):
            out = get_op(OP)({
                "texts": ["a row", "another row"], "allow_fallback": False,
                "result_format": "columnar", "topk": 2,
                "model_path": "roofline-name-probe",
                "model_config": {
                    "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
                    "max_len": 32, "dtype": "float32", "n_classes": 8},
            }, OpContext(runtime=runtime))
    finally:
        obs_trace.set_enabled(None)
    assert out["ok"] is True, out
    programs = [s["attributes"]["program"] for s in buf.spans()
                if s["name"] == "xla.compile"]
    modules = [re.sub(r"^jit\((.*)\)$", r"jit_\1(0)", p) for p in programs]
    pattern = manifest.load_layer_metric(
        "encoder_roofline").PROGRAM_PATTERNS["classify"]
    assert any(re.search(pattern, m) for m in modules), programs
