"""Distributed tracing (ISSUE 5): span model, ring bounding, exporters,
controller-side assembly, compile-cost attribution, exemplar round-trip,
and the end-to-end acceptance path — a LoopbackSession drain yielding one
causally consistent span tree per job, served on ``GET /v1/trace``."""

import json
import urllib.request

import pytest

from agent_tpu.agent.app import Agent
from agent_tpu.chaos import LoopbackSession
from agent_tpu.config import AgentConfig, Config
from agent_tpu.controller.core import Controller
from agent_tpu.controller.server import ControllerServer
from agent_tpu.obs import trace as obs_trace
from agent_tpu.obs.metrics import (
    MetricsRegistry,
    parse_exemplars,
    parse_exposition,
    render_snapshots,
    validate_exposition,
)
from agent_tpu.obs.trace import (
    SpanBuffer,
    TraceContext,
    TraceStore,
    from_jsonl,
    make_span,
    new_span_id,
    phase_breakdown,
    to_chrome_trace,
    to_jsonl,
    use_context,
    validate_chrome_trace,
)
from agent_tpu.runtime.executor import ExecutableCache


@pytest.fixture(autouse=True)
def _tracing_on():
    """Pin tracing ON for every test here (host env must not flip it), and
    restore the env-driven default afterwards."""
    obs_trace.set_enabled(True)
    yield
    obs_trace.set_enabled(None)


def _span(trace_id="t1", span_id=None, parent=None, name="x", **kw):
    return make_span(
        name, trace_id, parent, span_id=span_id or new_span_id(),
        start_mono=0.0, duration_s=kw.pop("duration_s", 0.001), **kw,
    )


# ---- unit: buffer, store, exporters ----

class TestSpanBuffer:
    def test_ring_is_bounded_and_counts_drops(self):
        buf = SpanBuffer(capacity=8)
        for i in range(100):
            buf.add(_span(span_id=f"s{i}"))
        assert len(buf) == 8
        assert buf.dropped == 92
        assert [s["span_id"] for s in buf.spans()] == \
            [f"s{i}" for i in range(92, 100)]

    def test_drain_and_requeue(self):
        buf = SpanBuffer(capacity=8)
        buf.add(_span(span_id="a"))
        buf.add(_span(span_id="b"))
        taken = buf.drain()
        assert [s["span_id"] for s in taken] == ["a", "b"]
        assert len(buf) == 0
        buf.requeue(taken)  # failed ship puts them back
        assert len(buf) == 2

    def test_disabled_short_circuits(self):
        obs_trace.set_enabled(False)
        buf = SpanBuffer()
        buf.add(_span())
        assert len(buf) == 0

    def test_malformed_spans_rejected(self):
        buf = SpanBuffer()
        buf.add({"span_id": "x"})          # no trace_id
        buf.add({"trace_id": "t"})         # no span_id
        buf.add("not a span")
        assert len(buf) == 0


class TestTraceStore:
    def test_dedup_by_span_id(self):
        store = TraceStore()
        s = _span(span_id="dup")
        assert store.add(s)
        assert store.add(dict(s, name="updated"))
        spans = store.spans("t1")
        assert len(spans) == 1 and spans[0]["name"] == "updated"

    def test_trace_eviction_oldest_first(self):
        store = TraceStore(max_traces=3)
        for i in range(5):
            store.add(_span(trace_id=f"t{i}"))
        assert store.trace_ids() == ["t2", "t3", "t4"]
        assert store.dropped_traces == 2
        assert store.spans("t0") is None

    def test_span_cap_per_trace(self):
        store = TraceStore(max_spans_per_trace=4)
        for i in range(10):
            store.add(_span(span_id=f"s{i}"))
        assert len(store.spans("t1")) == 4
        assert store.dropped_spans == 6

    def test_open_finish_and_assembly(self):
        store = TraceStore()
        root = store.open("t1", "submit", start_clock=10.0)
        child = store.open("t1", "lease", parent_span_id=root,
                           start_clock=11.0)
        out = store.assemble("t1")
        assert out["root_span_id"] == root
        assert out["open_spans"] == sorted([root, child]) or \
            set(out["open_spans"]) == {root, child}
        assert not out["complete"]
        store.finish("t1", child, 12.5, attributes={"outcome": "succeeded"})
        store.finish("t1", root, 13.0)
        out = store.assemble("t1")
        assert out["complete"] and not out["orphans"]
        by_id = {s["span_id"]: s for s in out["spans"]}
        assert by_id[child]["duration_ms"] == pytest.approx(1500.0)
        assert by_id[child]["attributes"]["outcome"] == "succeeded"
        assert by_id[root]["duration_ms"] == pytest.approx(3000.0)

    def test_orphans_flagged(self):
        store = TraceStore()
        store.add(_span(span_id="root"))
        store.add(_span(span_id="kid", parent="root"))
        store.add(_span(span_id="lost", parent="never-existed"))
        out = store.assemble("t1")
        assert out["orphans"] == ["lost"]
        assert not out["complete"]

    def test_assemble_unknown_trace_is_none(self):
        assert TraceStore().assemble("nope") is None

    def test_disabled_store_is_noop(self):
        obs_trace.set_enabled(False)
        store = TraceStore()
        assert store.open("t1", "submit") is None
        assert not store.add(_span())


class TestSpanLinks:
    """Cross-trace span links (ISSUE 17): additive-only — a link-free span
    serializes byte-identically to the pre-links schema, and links never
    participate in parent/child assembly."""

    def test_link_free_wire_bytes_unchanged(self):
        wire = make_span("x", "t1", start_mono=0.0, duration_s=0.001)
        assert "links" not in wire
        assert "links" not in obs_trace.Span(
            trace_id="t1", span_id="s1", name="x"
        ).to_wire()

    def test_make_span_emits_links(self):
        link = obs_trace.span_link("other-trace", "s9", kind="serve_request")
        assert link == {
            "trace_id": "other-trace", "span_id": "s9",
            "attributes": {"kind": "serve_request"},
        }
        wire = make_span("x", "t1", start_mono=0.0, duration_s=0.001,
                         links=[link])
        assert wire["links"] == [link]

    def test_span_link_omits_empty_fields(self):
        assert obs_trace.span_link("t2") == {"trace_id": "t2"}

    def test_store_add_links_post_open_and_read_back(self):
        store = TraceStore()
        root = store.open("job-1", "submit", start_clock=0.0)
        assert store.links("job-1", root) == []
        store.add_links("job-1", root, [obs_trace.span_link("req-a", "s1")])
        store.add_links("job-1", root, [obs_trace.span_link("req-b")])
        assert store.links("job-1", root) == [
            {"trace_id": "req-a", "span_id": "s1"},
            {"trace_id": "req-b"},
        ]
        # Absent span / trace: silent no-op, empty read.
        store.add_links("job-1", "nope", [obs_trace.span_link("x")])
        store.add_links("no-trace", root, [obs_trace.span_link("x")])
        assert store.links("no-trace", root) == []

    def test_links_do_not_affect_assembly(self):
        store = TraceStore()
        root = store.open("t1", "root", start_clock=0.0)
        store.add_links(
            "t1", root, [obs_trace.span_link("elsewhere", "dangling")]
        )
        store.finish("t1", root, 1.0)
        out = store.assemble("t1")
        assert out["complete"] and not out["orphans"]
        (span,) = out["spans"]
        assert span["links"] == [
            {"trace_id": "elsewhere", "span_id": "dangling"}
        ]

    def test_links_survive_jsonl_round_trip(self):
        wire = make_span("x", "t1", start_mono=0.0, duration_s=0.001,
                         links=[obs_trace.span_link("t2", "s2")])
        (back,) = from_jsonl(to_jsonl([wire]))
        assert back["links"] == [{"trace_id": "t2", "span_id": "s2"}]


class TestExporters:
    def test_jsonl_round_trip(self):
        spans = [_span(span_id="a"), _span(span_id="b", parent="a")]
        back = from_jsonl(to_jsonl(spans))
        assert back == [json.loads(json.dumps(s)) for s in spans]

    def test_chrome_trace_schema_valid(self):
        spans = [
            _span(span_id="a", process="controller"),
            _span(span_id="b", parent="a", process="agent:w1"),
        ]
        ct = to_chrome_trace(spans)
        assert validate_chrome_trace(ct) == []
        xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
        ms = [e for e in ct["traceEvents"] if e["ph"] == "M"]
        # one pid per process + process_name metadata for each
        assert len(xs) == 2 and len(ms) == 2
        assert xs[0]["pid"] != xs[1]["pid"]
        assert all(e["dur"] >= 0 and e["ts"] > 0 for e in xs)
        assert xs[1]["args"]["parent_span_id"] == "a"

    def test_chrome_trace_open_span_exports_incomplete(self):
        store = TraceStore()
        store.open("t1", "submit", start_clock=0.0)
        ct = to_chrome_trace(store.spans("t1"))
        assert validate_chrome_trace(ct) == []
        (x,) = [e for e in ct["traceEvents"] if e["ph"] == "X"]
        assert x["dur"] == 0 and x["args"]["incomplete"] is True

    def test_validate_chrome_trace_catches_garbage(self):
        assert validate_chrome_trace([]) != []
        assert validate_chrome_trace({"traceEvents": "nope"}) != []
        assert validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "x", "pid": 1}]}
        ) != []  # missing ts/dur

    def test_phase_breakdown_line(self):
        store = TraceStore()
        root = store.open("job-1", "submit", start_clock=0.0)
        store.add(_span(trace_id="job-1", parent=root, name="execute",
                        duration_s=0.2))
        store.finish("job-1", root, 0.5)
        line = phase_breakdown(store.assemble("job-1"))
        assert "job-1" in line and "execute 200.0ms" in line
        assert "total 500.0ms" in line


class TestCompileAttribution:
    """Executables are counted where JAX obtains them (the runtime's
    ``jax.monitoring`` listener), not where a jit wrapper is built."""

    @staticmethod
    def _fresh_jit():
        import jax

        return jax.jit(lambda x: (x * 3.0 + 1.0).sum())

    @staticmethod
    def _counter(reg, name, **labels):
        return reg.counter(name, "", tuple(labels)).value(**labels)

    def test_first_call_emits_span_and_counters(self):
        import numpy as np

        buf = SpanBuffer()
        reg = MetricsRegistry()
        cache = ExecutableCache()
        ctx = TraceContext(trace_id="job-c", parent_span_id="exec-span",
                           tracer=buf, registry=reg, process="agent:t",
                           op="my_op")
        key = ("my_op", 8, 128, "f32")
        with use_context(ctx):
            fn = cache.get_or_build(key, self._fresh_jit)
            # Building the wrapper obtains nothing from XLA ...
            assert buf.spans() == []
            assert "runtime_xla_executables_total" not in reg.snapshot()
            # ... the first call does, the second does not.
            fn(np.zeros((8, 128), np.float32))
            assert cache.get_or_build(key, self._fresh_jit) is fn
            fn(np.ones((8, 128), np.float32))
        (span,) = buf.spans()
        assert span["name"] == "xla.compile"
        assert span["trace_id"] == "job-c"
        assert span["parent_span_id"] == "exec-span"
        assert span["attributes"]["op"] == "my_op"
        assert span["attributes"]["program"].startswith("jit(")
        assert self._counter(reg, "runtime_xla_executables_total") == 1
        seconds = self._counter(
            reg, "runtime_compile_seconds_total", op="my_op")
        assert seconds > 0
        # The span is the event's own duration, not a second clock.
        assert span["duration_ms"] == pytest.approx(seconds * 1e3, abs=1e-3)
        hits = reg.counter("runtime_compile_cache_total", "",
                           ("op", "outcome"))
        assert hits.value(op="my_op", outcome="miss") == 1
        assert hits.value(op="my_op", outcome="hit") == 1

    def test_params_cache_stays_out_of_lookup_series(self):
        buf = SpanBuffer()
        reg = MetricsRegistry()
        cache = ExecutableCache(count_lookups=False)
        with use_context(TraceContext(trace_id="j", tracer=buf, registry=reg)):
            cache.get_or_build(("params", "m1", "rep"), lambda: object())
        assert len(buf) == 0
        assert reg.snapshot() == {}

    def test_disabled_tracing_skips_span_keeps_counter(self):
        import numpy as np

        obs_trace.set_enabled(False)
        buf = SpanBuffer()
        reg = MetricsRegistry()
        with use_context(TraceContext(trace_id="j", tracer=buf, registry=reg,
                                      op="op2")):
            self._fresh_jit()(np.zeros((3,), np.float32))
        assert len(buf) == 0  # span skipped
        # compile cost still counted — it's a metric
        assert self._counter(
            reg, "runtime_compile_seconds_total", op="op2") > 0
        assert self._counter(reg, "runtime_xla_executables_total") == 1

    def test_outside_a_task_counts_in_the_process_registry(self):
        import numpy as np

        from agent_tpu.obs.metrics import get_registry

        reg = get_registry()
        before = self._counter(reg, "runtime_compile_seconds_total", op="?")
        n0 = self._counter(reg, "runtime_xla_executables_total")
        self._fresh_jit()(np.zeros((5,), np.float32))
        assert self._counter(reg, "runtime_xla_executables_total") == n0 + 1
        assert self._counter(
            reg, "runtime_compile_seconds_total", op="?") > before


class TestExemplars:
    def test_render_parse_round_trip(self):
        r = MetricsRegistry()
        h = r.histogram("task_phase_seconds", "p", ("op", "phase"),
                        buckets=(0.1, 1.0))
        h.observe(0.05, exemplar={"trace_id": "job-x"},
                  op="echo", phase="execute")
        h.observe(5.0, exemplar={"trace_id": "job-y"},
                  op="echo", phase="execute")
        text = r.render()
        assert validate_exposition(text) == []
        ex = parse_exemplars(text)["task_phase_seconds_bucket"]
        got = {e[1]["trace_id"]: e[2] for e in ex}
        assert got == {"job-x": pytest.approx(0.05),
                       "job-y": pytest.approx(5.0)}
        # plain parsing still works on exemplar-carrying lines
        parsed = parse_exposition(text)
        assert any(lbl.get("le") == "0.1"
                   for lbl, _ in parsed["task_phase_seconds_bucket"])

    def test_exemplars_survive_fleet_merge_latest_wins(self):
        from agent_tpu.obs.metrics import merge_snapshots

        def snap(job, v):
            r = MetricsRegistry()
            r.histogram("h", "", ("op",), buckets=(1.0,)).observe(
                v, exemplar={"trace_id": job}, op="x")
            return r.snapshot()

        first, second = snap("job-old", 0.5), snap("job-new", 0.6)
        merged = merge_snapshots([first, second])
        (series,) = merged["h"]["series"]
        assert series["exemplars"]["0"]["labels"]["trace_id"] == "job-new"
        assert series["count"] == 2
        text = render_snapshots([(merged, {})])
        assert validate_exposition(text) == []
        assert 'trace_id="job-new"' in text

    def test_snapshot_without_exemplars_keeps_legacy_shape(self):
        r = MetricsRegistry()
        r.histogram("h", "", ("op",)).observe(0.1, op="x")
        (series,) = r.snapshot()["h"]["series"]
        assert set(series) == {"labels", "counts", "sum", "count"}


# ---- end-to-end: LoopbackSession drain → causal span tree ----

def _drain_serial(controller, n_steps=10, tasks=("echo",), max_tasks=2):
    cfg = Config(agent=AgentConfig(
        controller_url="http://loopback", agent_name="trace-agent",
        tasks=tasks, max_tasks=max_tasks, idle_sleep_sec=0.0,
    ))
    agent = Agent(config=cfg, session=LoopbackSession(controller))
    agent._profile = {"tier": "test"}
    agent.run(max_steps=n_steps)
    return agent


def test_loopback_drain_yields_causal_span_tree():
    """The acceptance path: submit → drained job has ONE root span with
    sched/lease children, and stage/execute/post parented to the lease —
    every parent id resolves, every span closed."""
    c = Controller()
    jids = [c.submit("echo", {"i": i}) for i in range(3)]
    _drain_serial(c)
    assert c.drained()
    for jid in jids:
        t = c.trace_json(jid)
        assert t is not None and t["complete"], t
        assert t["orphans"] == [] and t["open_spans"] == []
        by_name = {}
        for s in t["spans"]:
            by_name.setdefault(s["name"], []).append(s)
        for name in ("submit", "sched.decide", "lease", "stage",
                     "execute", "post", "apply"):
            assert name in by_name, (name, sorted(by_name))
        root = by_name["submit"][0]
        assert root["span_id"] == t["root_span_id"]
        assert root["parent_span_id"] is None
        lease = by_name["lease"][0]
        assert lease["parent_span_id"] == root["span_id"]
        assert by_name["sched.decide"][0]["parent_span_id"] == \
            root["span_id"]
        assert by_name["apply"][0]["parent_span_id"] == root["span_id"]
        for phase in ("stage", "execute", "post"):
            assert by_name[phase][0]["parent_span_id"] == lease["span_id"]
            assert by_name[phase][0]["process"] == "agent:trace-agent"
        # execute precedes post on the assembled (sorted) timeline
        names = [s["name"] for s in t["spans"]]
        assert names.index("execute") < names.index("post")


def test_retried_job_trace_shows_both_lease_windows():
    """A transient failure retries: the trace carries one lease span per
    attempt, both closed, and the root closes on the terminal state."""
    c = Controller(max_attempts=2)
    jid = c.submit("boom_transient", {})
    lease = c.lease("a1", {"ops": ["boom_transient"]})
    c.report(lease["lease_id"], jid, 0, "failed",
             error={"type": "RuntimeError", "message": "x", "trace": ""})
    lease2 = c.lease("a1", {"ops": ["boom_transient"]})
    task = lease2["tasks"][0]
    c.report(lease2["lease_id"], jid, task["job_epoch"], "succeeded",
             {"ok": True})
    t = c.trace_json(jid)
    leases = [s for s in t["spans"] if s["name"] == "lease"]
    assert len(leases) == 2
    assert [s["attributes"]["attempt"] for s in leases] == [1, 2]
    assert all(s["duration_ms"] is not None for s in leases)
    assert leases[0]["attributes"]["outcome"] == "pending"  # retried
    assert leases[1]["attributes"]["outcome"] == "succeeded"
    assert t["complete"]


def test_lease_expiry_closes_lease_span_as_expired():
    clock = {"t": 0.0}
    c = Controller(lease_ttl_sec=5.0, clock=lambda: clock["t"])
    jid = c.submit("echo", {})
    c.lease("a1", {"ops": ["echo"]})
    clock["t"] = 10.0
    c.sweep()
    t = c.trace_json(jid)
    (lease,) = [s for s in t["spans"] if s["name"] == "lease"]
    assert lease["attributes"]["outcome"] == "expired"
    # closed at the sweep that noticed the expiry (t=10), not the TTL edge
    assert lease["duration_ms"] == pytest.approx(10000.0)


def test_task_wire_carries_trace_context_only_when_enabled():
    c = Controller()
    c.submit("echo", {})
    lease = c.lease("a1", {"ops": ["echo"]})
    task = lease["tasks"][0]
    assert task["trace"]["trace_id"] == task["id"]
    assert isinstance(task["trace"]["span_id"], str)

    obs_trace.set_enabled(False)
    c2 = Controller()
    jid = c2.submit("echo", {})
    lease2 = c2.lease("a1", {"ops": ["echo"]})
    assert "trace" not in lease2["tasks"][0]
    c2.report(lease2["lease_id"], jid, 0, "succeeded", {"ok": True})
    assert c2.trace_json(jid) is None  # nothing recorded at all


def test_trace_disabled_drain_still_clean():
    """TRACE_ENABLED=0 short-circuit: the drain completes, no spans
    anywhere, result bodies carry no span ids."""
    obs_trace.set_enabled(False)
    c = Controller()
    jid = c.submit("echo", {"x": 1})
    agent = _drain_serial(c, n_steps=4)
    assert c.drained()
    assert len(agent.tracer) == 0
    assert c.trace_json(jid) is None
    assert c.traces_json() == []
    trace = c.job_snapshot(jid)["result"]["trace"]
    assert "span_id" not in trace  # ISSUE-2 triple intact, no span leak
    assert trace["job_id"] == jid


def test_fenced_result_spans_still_ingested():
    """A stale-epoch (fenced) result's agent spans still land on the
    timeline — the execution happened; only the application was refused."""
    c = Controller()
    c.inject("stale_epoch")
    jid = c.submit("echo", {})
    agent = _drain_serial(c, n_steps=1)
    # fenced: the bumped-epoch job is still leased; the result was rejected
    assert c.job_snapshot(jid)["state"] != "succeeded"
    assert c.stale_results == 1
    agent.push_metrics()  # ship any spans still buffered
    spans = c.traces.spans(jid) or []
    agent_spans = [s for s in spans if s["process"].startswith("agent:")]
    assert any(s["name"] == "execute" for s in agent_spans)


# ---- HTTP surface ----

def test_trace_endpoints_over_http():
    c = Controller()
    jid = c.submit("echo", {"i": 1})
    _drain_serial(c, n_steps=4)
    with ControllerServer(c) as server:
        with urllib.request.urlopen(f"{server.url}/v1/trace/{jid}") as r:
            body = json.load(r)
        assert body["trace_id"] == jid and body["complete"]

        with urllib.request.urlopen(
            f"{server.url}/v1/trace/{jid}?format=perfetto"
        ) as r:
            perfetto = json.load(r)
        assert validate_chrome_trace(perfetto) == []

        with urllib.request.urlopen(
            f"{server.url}/v1/trace/{jid}?format=jsonl"
        ) as r:
            spans = from_jsonl(r.read().decode())
        assert {s["span_id"] for s in spans} == \
            {s["span_id"] for s in body["spans"]}

        with urllib.request.urlopen(
            f"{server.url}/v1/traces?limit=5"
        ) as r:
            listing = json.load(r)["traces"]
        assert listing and listing[0]["trace_id"] == jid
        assert listing[0]["complete"] is True

        try:
            urllib.request.urlopen(f"{server.url}/v1/trace/unknown-job")
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as exc:
            assert exc.code == 404


def test_debug_events_job_id_filter_and_seq():
    """ISSUE 5 satellites: events carry (ts, mono, seq) so dumps interleave
    deterministically, and /v1/debug/events?job_id= filters server-side."""
    c = Controller()
    jid = c.submit("echo", {"i": 1})
    c.submit("echo", {"i": 2})
    _drain_serial(c, n_steps=4)
    events = c.recorder.events()
    assert all(
        {"ts", "mono", "seq", "kind"} <= set(e) for e in events
    )
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    with ControllerServer(c) as server:
        with urllib.request.urlopen(
            f"{server.url}/v1/debug/events?job_id={jid}"
        ) as r:
            mine = json.load(r)["events"]
    assert mine and all(e.get("job_id") == jid for e in mine)
    assert {"submit", "lease", "result"} <= {e["kind"] for e in mine}


def test_exposition_carries_queue_wait_exemplars_end_to_end():
    c = Controller()
    jid = c.submit("echo", {})
    _drain_serial(c, n_steps=4)
    text = c.metrics_text()
    assert validate_exposition(text) == []
    ex = parse_exemplars(text)
    refs = {
        e[1].get("trace_id")
        for samples in ex.values() for e in samples
    }
    assert jid in refs
