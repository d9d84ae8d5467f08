"""The agent's own account of the chip's time (ISSUE 24): device busy/idle
from completion events (the in-order queue model), the device thread's
exclusive states, XLA executables counted where a task obtains them, and the
``agent.*`` profiler annotations of every pipeline phase."""

import glob
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from agent_tpu.agent.app import Agent
from agent_tpu.agent.pipeline import PipelineRunner
from agent_tpu.chaos import LoopbackSession
from agent_tpu.config import AgentConfig, Config, DeviceConfig
from agent_tpu.controller.core import Controller
from agent_tpu.obs import trace as obs_trace
from agent_tpu.runtime.runtime import TpuRuntime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {
    "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
    "max_len": 64, "dtype": "float32", "n_classes": 16,
}


@pytest.fixture(autouse=True)
def _tracing_on():
    obs_trace.set_enabled(True)
    yield
    obs_trace.set_enabled(None)


@pytest.fixture(scope="module")
def runtime():
    return TpuRuntime(
        config=DeviceConfig(tpu_disabled=True, mesh_shape={"dp": 8}),
        devices=jax.devices("cpu"),
    )


def _agent(controller, tasks=("echo",), runtime=None, depth=0, name="acct"):
    cfg = Config(agent=AgentConfig(
        controller_url="http://loopback", agent_name=name, tasks=tasks,
        idle_sleep_sec=0.0, pipeline_depth=depth,
    ))
    agent = Agent(config=cfg, session=LoopbackSession(controller),
                  runtime=runtime)
    agent._profile = {"tier": "test"}
    agent.post_session_factory = lambda: LoopbackSession(controller)
    return agent


def _counter(agent, name, **labels):
    total = 0.0
    for s in (agent.obs.snapshot().get(name) or {}).get("series", []):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            total += s["value"]
    return total


def _run_until_drained(agent, controller, timeout=60.0):
    def watch():
        deadline = time.time() + timeout
        while not controller.drained() and time.time() < deadline:
            time.sleep(0.005)
        agent.shutdown()

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    agent.run()
    watcher.join(timeout=5)
    assert controller.drained(), controller.counts()


# ---- the interval function on synthetic sequences ----

# (dispatch, ready) pairs in seconds after the agent's start, as the
# function is told them; expected busy per item, expected idle in all.
SEQUENCES = {
    # Every dispatch precedes the previous completion: the chip never waits.
    # Busy sums to last ready less first dispatch.
    "saturated": ([(0.0, 1.0), (0.1, 2.0), (1.1, 3.0), (2.1, 4.0)],
                  [1.0, 1.0, 1.0, 1.0], 0.0),
    # Nothing in flight between the first completion and the next dispatch.
    "gapped": ([(0.0, 1.0), (1.5, 2.5), (2.5, 3.0)],
               [1.0, 1.0, 0.5], 0.5),
    # The saturated run with the second ready SEEN 0.4 s late (the poster
    # was busy posting): seconds move between neighbours, the total stays.
    "ready_seen_late": ([(0.0, 1.0), (0.1, 2.4), (1.1, 3.0), (2.1, 4.0)],
                        [1.0, 1.4, 0.6, 1.0], 0.0),
    # Executes that block until the result is on the host (serial loop,
    # fallback mode): dispatch and ready are the call's own start and end.
    "blocking": ([(0.0, 1.0), (1.0, 2.0), (2.2, 3.0)],
                 [1.0, 1.0, 0.8], 0.2),
}


@pytest.mark.parametrize("name", sorted(SEQUENCES))
def test_device_interval_sequences(name):
    intervals, want_busy, want_idle = SEQUENCES[name]
    agent = _agent(Controller())
    base = agent._t_ready_prev   # the device idles from the agent's start
    billed = []
    for t_dispatch, t_ready in intervals:
        tags = {}
        before = _counter(agent, "device_busy_seconds_total", op="op_x")
        agent.note_device_interval(
            "op_x", base + t_dispatch, base + t_ready, tags)
        # The ledger's float IS the counter's float.
        assert _counter(agent, "device_busy_seconds_total", op="op_x") \
            == pytest.approx(before + tags["usage"]["device_s"], abs=1e-12)
        billed.append(tags["usage"]["device_s"])
    assert billed == pytest.approx(want_busy, abs=1e-9)
    busy = _counter(agent, "device_busy_seconds_total", op="op_x")
    assert busy == pytest.approx(sum(want_busy), abs=1e-9)
    assert busy == pytest.approx(sum(billed), abs=1e-12)
    assert _counter(agent, "device_idle_seconds_total") == pytest.approx(
        want_idle, abs=1e-9)
    # Busy and idle tile the time from the agent's start to the last ready.
    assert busy + want_idle == pytest.approx(intervals[-1][1], abs=1e-9)


def test_intervals_are_accounted_in_dispatch_order():
    """A blocking interval reported by the device thread AHEAD of an earlier
    deferred dispatch's (a decode step while a drain shard waits for the
    poster) is held until the earlier one is in: the shard keeps its
    seconds, and no idle is booked that never was."""
    agent = _agent(Controller())
    base = agent._t_ready_prev
    shard_tags, step_tags = {}, {}
    seq = agent.device_dispatched()                 # the shard, at 0.0
    agent.note_device_interval(                     # the step: 0.1 .. 1.5
        "serve", base + 0.1, base + 1.5, step_tags)
    assert "usage" not in step_tags                 # held
    assert _counter(agent, "device_busy_seconds_total") == 0.0
    agent.note_device_interval(                     # the shard: ready at 1.0
        "drain", base + 0.0, base + 1.0, shard_tags, seq=seq)
    assert shard_tags["usage"]["device_s"] == pytest.approx(1.0)
    assert step_tags["usage"]["device_s"] == pytest.approx(0.5)
    assert _counter(agent, "device_busy_seconds_total", op="drain") == \
        pytest.approx(1.0)
    assert _counter(agent, "device_busy_seconds_total", op="serve") == \
        pytest.approx(0.5)
    assert _counter(agent, "device_idle_seconds_total") == 0.0
    assert not agent._held_intervals


def test_device_mfu_is_flops_over_completion_seconds(monkeypatch):
    monkeypatch.setenv("PEAK_TFLOPS", "1")
    agent = _agent(Controller())
    base = agent._t_ready_prev
    tags = {"device_attr": {"flops": 2e11, "shape": "B8xL64"}}
    agent.note_device_interval("op_x", base, base + 0.5, tags)
    assert agent.m_mfu.value(op="op_x") == pytest.approx(0.4)
    assert tags["usage"]["flops"] == 2e11


# ---- a pipelined run with a fake deferred op ----

class FakeChip:
    """An in-order device: work dispatched at t runs from max(t, the end of
    what was dispatched before) for ``work_s``; ``finalize`` (the poster)
    waits for it, as a deferred fetch waits for the real chip."""

    def __init__(self, work_s, faulty=False, poster_lag_s=0.0):
        self.work_s = work_s
        self.faulty = faulty     # every 4th execute raises, every 4th fetch
        self.poster_lag_s = poster_lag_s   # host work after each fetch
        self.free_at = 0.0
        self.first_dispatch = None
        self.last_done = 0.0
        self.last_seen = 0.0     # the last ready a finalize stamped

    def op(self):
        def run(payload, ctx=None):
            raise AssertionError("the pipeline runs the phases")

        def stage(payload, ctx=None):
            return "staged", {"n": payload.get("n", 0)}

        def execute(state, ctx=None):
            if state["n"] % 4 == 1 and self.faulty:
                raise RuntimeError("dispatch refused")
            now = time.perf_counter()
            if self.first_dispatch is None:
                self.first_dispatch = now
            self.free_at = max(now, self.free_at) + self.work_s
            state["done_at"] = self.last_done = self.free_at
            return state                 # dispatch only

        def finalize(state, ctx=None):
            with obs_trace.phase("fetch") as fetched:
                time.sleep(max(0.0, state["done_at"] - time.perf_counter()))
                if state["n"] % 4 == 3 and self.faulty:
                    raise RuntimeError("fetch failed")   # stamps no t_ready
            state["t_ready"] = self.last_seen = fetched.t1
            time.sleep(self.poster_lag_s)
            return {"ok": True, "n": state["n"]}

        run.stage, run.execute, run.finalize = stage, execute, finalize
        run.deferred = True              # the contract: declared, not guessed
        return run

    def block(self, work_s):
        """Work the caller waits for (a decode step reads its tokens back):
        queued behind what was dispatched before, like everything else."""
        now = time.perf_counter()
        if self.first_dispatch is None:
            self.first_dispatch = now
        self.free_at = self.last_done = max(now, self.free_at) + work_s
        time.sleep(max(0.0, self.free_at - time.perf_counter()))

    def serving_op(self, steps, step_s):
        """A continuous-serving op on the same chip: admit and every pump
        block on it (the pipeline's serve hooks)."""
        engine = object()

        def run(payload, ctx=None):
            raise AssertionError("the pipeline runs the serve hooks")

        def stage(payload, ctx=None):
            return "staged", {"left": steps}

        def admit(state, ctx=None):
            self.block(step_s)
            return {"engine": engine, "state": state}

        def pump(handle):
            if handle["state"]["left"] > 0:
                self.block(step_s)
                handle["state"]["left"] -= 1
            return 1

        run.stage, run.execute = stage, lambda state, ctx=None: state
        run.finalize = lambda state, ctx=None: {"ok": True}
        run.serve_admit, run.serve_pump = admit, pump
        run.serve_done = lambda handle: handle["state"]["left"] <= 0
        run.serve_collect = lambda handle: handle["state"]
        return run


def test_pipelined_deferred_op_busy_is_wall_and_states_tile_the_loop():
    n_jobs, work_s = 12, 0.03
    controller = Controller()
    chip = FakeChip(work_s)
    agent = _agent(controller, depth=2)
    started = agent._t_ready_prev    # the device idles from the agent's start
    agent.handlers["fake_deferred"] = chip.op()
    job_ids = [controller.submit("fake_deferred", {"n": i}, tenant="t")
               for i in range(n_jobs)]

    loop_wall = {}
    inner = PipelineRunner._execute_loop

    def timed(self):
        t0 = time.perf_counter()
        try:
            inner(self)
        finally:
            loop_wall["s"] = time.perf_counter() - t0

    PipelineRunner._execute_loop = timed
    try:
        _run_until_drained(agent, controller)
    finally:
        PipelineRunner._execute_loop = inner

    busy = _counter(agent, "device_busy_seconds_total", op="fake_deferred")
    # Completion seconds, not the microseconds execute took to dispatch: at
    # least the chip's own work (a ready seen late only adds), and with the
    # idle booked since, all the time from the first dispatch to the last
    # ready seen.
    assert busy >= n_jobs * work_s - 1e-6
    idle = _counter(agent, "device_idle_seconds_total") \
        - (chip.first_dispatch - started)
    assert busy + idle == pytest.approx(
        chip.last_seen - chip.first_dispatch, abs=1e-6)
    # What the tenants are billed sums to the counter (same floats).
    billed = sum(
        controller.job(j).result["usage"]["device_s"] for j in job_ids)
    assert billed == pytest.approx(busy, rel=1e-9)
    assert controller.usage_json()["totals"]["device_seconds"] == \
        pytest.approx(busy, abs=1e-6)      # the ledger rounds to 6 places
    # The duty gauge agrees with a chip that hardly waited.
    assert agent.m_duty.value() > 0.5
    # The device thread's states are exclusive and tile the loop.
    states = {
        s["labels"]["state"]: s["value"] for s in
        agent.obs.snapshot()["device_thread_seconds_total"]["series"]}
    assert set(states) <= {"wait_staged", "prefeed", "dispatch",
                           "wait_post", "serve_pump"}
    assert sum(states.values()) == pytest.approx(loop_wall["s"], abs=0.02)
    # With depth 2 and a 30 ms chip the thread mostly waits for the poster.
    assert states["wait_post"] > states["dispatch"]
    # The fetch is a phase of its own: histogram and span, under `post`.
    fam = agent.obs.snapshot()["task_phase_seconds"]["series"]
    counts = {s["labels"]["phase"]: s["count"] for s in fam
              if s["labels"]["op"] == "fake_deferred"}
    assert counts == {"stage": n_jobs, "execute": n_jobs, "fetch": n_jobs,
                      "finalize": n_jobs, "post_http": n_jobs}
    spans = {s["span_id"]: s for s in controller.traces.spans(job_ids[0])}
    by_name = {s["name"]: s for s in spans.values()}
    assert by_name["fetch"]["parent_span_id"] == by_name["post"]["span_id"]
    assert by_name["post_http"]["parent_span_id"] == by_name["post"]["span_id"]
    assert by_name["execute"]["parent_span_id"] == \
        by_name["stage"]["parent_span_id"]          # the lease span
    # The flight recorder holds every top-level phase of every task, the
    # terminal one with the task's status and duration (what an SLO-page
    # dump and an incident bundle read).
    for job_id in job_ids:
        events = {e["phase"]: e for e in agent.recorder.events(job_id=job_id)
                  if e["kind"] == "phase"}
        assert set(events) == {"stage", "queue", "execute", "post"}, events
        post = events["post"]
        assert post["status"] == "succeeded" and post["op"] == "fake_deferred"
        assert post["duration_ms"] > 0            # the poster's extent
        assert post["task_duration_ms"] > 0       # stage in to finalize out
        assert post["lease_id"]


def test_drain_shards_and_decode_steps_mixed_keep_every_second():
    """One agent draining deferred shards while a serving job decodes: the
    device thread reports each blocking decode step as it returns, AHEAD of
    the shards still waiting for the poster. Accounted in dispatch order,
    every op keeps its own seconds and the chip's time is all there."""
    n_shards, work_s, steps, step_s = 10, 0.03, 40, 0.004
    controller = Controller()
    # The poster falls behind the chip, so it sees the shards' readies late.
    chip = FakeChip(work_s, poster_lag_s=0.02)
    agent = _agent(controller, depth=2)
    agent.handlers["fake_deferred"] = chip.op()
    agent.handlers["fake_serving"] = chip.serving_op(steps, step_s)
    started = agent._t_ready_prev    # the device idles from the agent's start
    shard_ids = [controller.submit("fake_deferred", {"n": i})
                 for i in range(n_shards // 2)]
    controller.submit("fake_serving", {})
    shard_ids += [controller.submit("fake_deferred", {"n": i})
                  for i in range(n_shards // 2, n_shards)]
    _run_until_drained(agent, controller)
    assert not agent._held_intervals
    drain = _counter(agent, "device_busy_seconds_total", op="fake_deferred")
    serve = _counter(agent, "device_busy_seconds_total", op="fake_serving")
    idle = _counter(agent, "device_idle_seconds_total")
    # Every op keeps its own work (told in call order, the shards overtaken
    # by a decode step would be left with a third of theirs). A ready seen
    # late moves seconds between neighbours, so the bounds leave room for a
    # loaded host; the total does not move.
    assert drain >= 0.8 * n_shards * work_s
    assert serve >= 0.5 * (steps + 1) * step_s
    # Busy and idle tile the time from the agent's start to the last ready.
    assert drain + serve + idle == pytest.approx(
        chip.last_done - started, abs=0.05)
    billed = sum(controller.job(j).result["usage"]["device_s"]
                 for j in shard_ids)
    assert billed == pytest.approx(drain, rel=1e-9)


def test_failed_dispatches_and_fetches_still_report_their_interval():
    """A number taken at dispatch comes in whatever becomes of the work (an
    execute that raises, a fetch that fails): nothing stays held, and what
    was billed still sums to the counter."""
    n_jobs = 8
    controller = Controller()
    agent = _agent(controller, depth=2)
    agent.handlers["fake_deferred"] = FakeChip(0.01, faulty=True).op()
    job_ids = [controller.submit("fake_deferred", {"n": i}, max_attempts=1)
               for i in range(n_jobs)]
    _run_until_drained(agent, controller)
    states = [controller.job(j).state for j in job_ids]
    assert states.count("dead") == 4, states
    assert not agent._held_intervals
    assert agent._accounted_seq == agent._dispatch_seq == n_jobs
    busy = _counter(agent, "device_busy_seconds_total", op="fake_deferred")
    assert busy >= 6 * 0.01 - 1e-6       # the two refused ran nothing
    billed = sum((controller.job(j).result or {}).get(
        "usage", {}).get("device_s", 0.0) for j in job_ids
        if controller.job(j).state == "succeeded")
    assert 0 < billed <= busy + 1e-9


# ---- a fresh jit compile inside a task ----

def test_compile_inside_a_task_is_counted_and_spanned():
    controller = Controller()
    agent = _agent(controller)
    fn = jax.jit(lambda x: (x * 5.0 - 2.0).sum())

    def compile_probe(payload, ctx=None):
        return {"ok": True, "value": float(fn(np.arange(7, dtype=np.float32)))}

    agent.handlers["compile_probe"] = compile_probe
    cold = controller.submit("compile_probe", {})
    warm = controller.submit("compile_probe", {})
    for _ in range(2):
        assert agent.step()
    agent.push_metrics()

    assert _counter(agent, "runtime_xla_executables_total") == 1
    seconds = _counter(agent, "runtime_compile_seconds_total",
                       op="compile_probe")
    assert seconds > 0
    spans = {s["name"]: s for s in controller.traces.spans(cold)}
    compile_span, execute = spans["xla.compile"], spans["execute"]
    assert compile_span["parent_span_id"] == execute["span_id"]
    assert compile_span["duration_ms"] == pytest.approx(seconds * 1e3,
                                                        abs=1e-3)
    assert execute["duration_ms"] >= compile_span["duration_ms"]
    # The second task's call obtained nothing.
    assert "xla.compile" not in {
        s["name"] for s in controller.traces.spans(warm)}


def test_params_build_is_timed_on_a_miss(runtime):
    from agent_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    build = lambda: {"w": np.ones((4, 4), np.float32)}   # noqa: E731
    with obs_trace.use_context(obs_trace.TraceContext(registry=reg)):
        runtime.get_params("acct-params-model", build)
        first = reg.counter("runtime_params_seconds_total").value()
        runtime.get_params("acct-params-model", build)
    assert first > 0
    assert reg.counter("runtime_params_seconds_total").value() == first


# ---- the annotations, in a real profiler capture ----

def test_profiler_capture_shows_every_phase_on_host_lines(runtime, tmp_path):
    from benchmarks.harness import trace_reduce

    csv = tmp_path / "rows.csv"
    csv.write_text(
        "id,text\n" + "".join(f'{i},"annotated row {i}"\n' for i in range(16)),
        encoding="utf-8")
    controller = Controller()
    controller.submit_csv_job(
        str(csv), total_rows=16, shard_size=16, map_op="map_classify_tpu",
        extra_payload={"text_field": "text", "allow_fallback": False,
                       "result_format": "columnar",
                       "model_config": dict(TINY), "topk": 3},
    )
    agent = _agent(controller, tasks=("map_classify_tpu",), runtime=runtime,
                   depth=2)
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        _run_until_drained(agent, controller)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    pd = trace_reduce.load(path)
    # One line per host thread (their names may repeat: the OS names every
    # Python thread alike).
    per_line = [
        {e.name for e in line.events if e.name.startswith("agent.")}
        for plane in pd.planes if plane.name == trace_reduce.HOST_PLANE
        for line in plane.lines]
    seen = set().union(*per_line)
    assert {"agent.stage", "agent.dispatch", "agent.fetch", "agent.finalize",
            "agent.post_http", "agent.lease", "agent.wait_staged"} <= seen
    # The poster's phases are not on the line that dispatches, nor the
    # feeder's lease on either.
    (device_line,) = [n for n in per_line if "agent.dispatch" in n]
    (poster_line,) = [n for n in per_line if "agent.post_http" in n]
    assert device_line.isdisjoint(poster_line)
    assert "agent.wait_staged" in device_line
    assert "agent.fetch" in poster_line
    assert all("agent.lease" not in n for n in (device_line, poster_line))
    assert "op:map_classify_tpu" not in {
        e.name for plane in pd.planes for line in plane.lines
        for e in line.events}


# ---- a host-only agent never pays for jax ----

HOST_ONLY = """
import sys
from agent_tpu.agent.app import Agent          # the agent's own imports
from agent_tpu.obs import trace as obs_trace
from agent_tpu.obs.metrics import MetricsRegistry
from agent_tpu.ops import get_op

reg, buf = MetricsRegistry(), obs_trace.SpanBuffer()
ctx = obs_trace.TraceContext(trace_id="job-h", tracer=buf, registry=reg,
                             op="echo")
with obs_trace.phase("execute", ctx, annotation="agent.dispatch") as ph:
    out = get_op("echo")({"v": 1})
    with obs_trace.phase("fetch"):
        pass
assert out["ok"] is True and ph.seconds > 0
assert obs_trace.annotate("agent.x") is None
assert [s["name"] for s in buf.spans()] == ["fetch", "execute"]
assert {s["labels"]["phase"] for s in
        reg.snapshot()["task_phase_seconds"]["series"]} == {"execute", "fetch"}
assert "jax" not in sys.modules, "the phase helper imported jax"
print("host-only ok")
"""


def test_phase_helper_never_imports_jax_on_a_host_only_agent():
    env = {k: v for k, v in os.environ.items() if k != "TASKS"}
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, "-c", HOST_ONLY], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "host-only ok" in out.stdout
