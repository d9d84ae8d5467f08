"""Online serving front door (ISSUE 15): batch bucketer, continuous-batching
decode engine, controller /v1/infer path, HTTP routes.

The engine correctness tests pin the acceptance bar: tokens emitted per
request through the continuous engine — with early joins and exits, beam
included — are BIT-IDENTICAL to a solo static-batch decode of the same
request.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from agent_tpu.config import ServeConfig
from agent_tpu.controller.core import Controller
from agent_tpu.controller.serving import ServeFrontDoor
from agent_tpu.sched import AdmissionError

TINY_S2S = {
    "d_model": 32, "n_heads": 4, "n_enc_layers": 1, "n_dec_layers": 1,
    "d_ff": 64, "max_src_len": 32, "max_tgt_len": 20, "dtype": "float32",
}
TINY_CLS = {
    "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
    "max_len": 64, "dtype": "float32", "n_classes": 8,
}


class FakeClock:
    def __init__(self, t: float = 100.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# batch bucketer
# ---------------------------------------------------------------------------

class TestServeBatcher:
    def make(self, **kw):
        clock = FakeClock()
        defaults = dict(max_wait_ms=50.0, max_batch=4, max_pending=0)
        defaults.update(kw)
        return ServeFrontDoor(ServeConfig(**defaults), clock=clock), clock

    def test_bucket_overflow_flushes_immediately(self):
        door, _ = self.make(max_batch=3)
        flushed = []
        for _ in range(7):
            _req, full = door.submit("summarize", "same length text")
            flushed.extend(full)
        # 7 same-bucket requests at max_batch 3 → two full flushes, one
        # request still waiting on the deadline.
        assert [len(b.requests) for b in flushed] == [3, 3]
        assert all(b.reason == "full" for b in flushed)
        assert door.stats()["bucketed"] == 1

    def test_deadline_flush(self):
        door, clock = self.make(max_wait_ms=50.0, max_batch=16)
        door.submit("summarize", "a text")
        clock.advance(0.02)
        assert door.pop_due() == []          # oldest has waited only 20ms
        clock.advance(0.04)
        due = door.pop_due()
        assert len(due) == 1 and due[0].reason == "deadline"
        assert len(due[0].requests) == 1

    def test_empty_queue_stays_idle(self):
        door, clock = self.make()
        clock.advance(10.0)
        assert door.pop_due() == []
        assert door.stats()["open_buckets"] == 0

    def test_buckets_split_by_op_params_tenant_and_length(self):
        door, _ = self.make(max_batch=16)
        door.submit("summarize", "short")
        door.submit("summarize", "x" * 500)                  # other length
        door.submit("summarize", "short", params={"num_beams": 4})
        door.submit("summarize", "short", tenant="acme")
        door.submit("classify", "short")
        assert door.stats()["open_buckets"] == 5

    def test_max_length_is_per_request_not_bucket(self):
        door, _ = self.make(max_batch=2)
        door.submit("summarize", "text a", params={"max_length": 4})
        _, full = door.submit("summarize", "text b",
                              params={"max_length": 9})
        (batch,) = full  # same bucket despite different budgets
        payload = batch.job_payload()
        assert [r["max_length"] for r in payload["requests"]] == [4, 9]

    def test_admission_budget_429(self):
        door, _ = self.make(max_pending=2, max_batch=16)
        door.submit("classify", "one")
        door.submit("classify", "two")
        with pytest.raises(AdmissionError):
            door.submit("classify", "three")
        assert door.rejected == 1

    def test_malformed_requests_raise(self):
        door, _ = self.make()
        with pytest.raises(ValueError):
            door.submit("transcribe", "text")        # unknown op
        with pytest.raises(ValueError):
            door.submit("classify", "")              # empty text
        with pytest.raises(ValueError):
            door.submit("classify", "x", params={"bogus": 1})
        with pytest.raises(ValueError):
            door.submit("classify", "x", priority=99)

    def test_completion_fan_out_and_wait(self):
        door, _ = self.make(max_batch=2)
        r1, _ = door.submit("summarize", "text a")
        r2, full = door.submit("summarize", "text b")
        (batch,) = full
        door.mark_batched(batch, "job-1")
        assert door.get(r1.req_id).state == "batched"
        done = door.complete_job("job-1", True, result={"results": [
            {"req_id": r1.req_id, "summary": "s1", "tokens": 3,
             "ttft_ms": 12.0},
            {"req_id": r2.req_id, "summary": "s2", "tokens": 5,
             "ttft_ms": 15.0},
        ]})
        assert {d.req_id for d in done} == {r1.req_id, r2.req_id}
        snap = door.snapshot(r1.req_id)
        assert snap["state"] == "done"
        assert snap["result"]["summary"] == "s1"
        assert snap["ttft_ms"] == 12.0
        # waiting on an already-terminal request returns immediately
        assert door.wait(r2.req_id, 0.0)["state"] == "done"
        # unknown job fan-out is a no-op
        assert door.complete_job("job-1", True, result={}) == []

    def test_failed_job_fails_riders(self):
        door, _ = self.make(max_batch=1)
        req, full = door.submit("summarize", "text")
        door.mark_batched(full[0], "job-f")
        (done,) = door.complete_job(
            "job-f", False, error={"type": "Boom", "message": "x"}
        )
        assert done.state == "failed"
        assert door.snapshot(req.req_id)["error"]["type"] == "Boom"

    def test_missing_result_entry_fails_that_rider(self):
        door, _ = self.make(max_batch=2)
        r1, _ = door.submit("summarize", "a")
        r2, full = door.submit("summarize", "b")
        door.mark_batched(full[0], "job-m")
        door.complete_job("job-m", True, result={"results": [
            {"req_id": r1.req_id, "summary": "s", "tokens": 1},
        ]})
        assert door.snapshot(r1.req_id)["state"] == "done"
        assert door.snapshot(r2.req_id)["state"] == "failed"


# ---------------------------------------------------------------------------
# continuous-batching engine correctness
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def s2s():
    from agent_tpu.models import seq2seq

    cfg = seq2seq.Seq2SeqConfig(**TINY_S2S)
    params = seq2seq.init_params(cfg, model_id="serving-test")
    return cfg, params


def _requests(cfg, n, seed=0, src_len=16):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        real = int(rng.integers(4, src_len))
        ids = rng.integers(4, cfg.vocab_size, (1, src_len)).astype(np.int32)
        mask = np.zeros((1, src_len), np.int32)
        mask[0, :real] = 1
        limit = int(rng.integers(2, cfg.max_tgt_len))
        out.append((ids, mask, limit))
    return out


def _solo(cfg, params, ids, mask, limit, num_beams):
    import jax.numpy as jnp

    from agent_tpu.models import seq2seq

    if num_beams == 1:
        toks, _ = seq2seq.greedy_generate(
            params, jnp.asarray(ids), jnp.asarray(mask), cfg, limit
        )
    else:
        toks, _ = seq2seq.beam_generate(
            params, jnp.asarray(ids), jnp.asarray(mask), cfg, limit,
            num_beams=num_beams,
        )
    return np.asarray(toks)[0]


def _engine(cfg, params, num_beams, slots=3, src_len=16, **kw):
    from agent_tpu.models import seq2seq
    from agent_tpu.models.decoding import ContinuousBatcher
    from agent_tpu.models.tokenizer import BOS_ID, EOS_ID, PAD_ID

    return ContinuousBatcher(
        seq2seq.make_positional_step(cfg),
        seq2seq.make_cache_factory(cfg),
        params=params,
        slots=slots, vocab_size=cfg.vocab_size, max_tokens=cfg.max_tgt_len,
        enc_len=src_len, d_model=cfg.d_model,
        start_id=BOS_ID, eos_id=EOS_ID, pad_id=PAD_ID,
        num_beams=num_beams, **kw,
    )


def _encode(cfg, params, ids, mask):
    import jax
    import jax.numpy as jnp

    from agent_tpu.models import seq2seq

    return np.asarray(jax.jit(
        lambda p, i, m: seq2seq.encode(p, i, m, cfg).astype(jnp.float32)
    )(params, jnp.asarray(ids), jnp.asarray(mask)))


@pytest.mark.parametrize("num_beams", [1, 3])
def test_continuous_engine_bit_identical_with_joins_and_exits(
    s2s, num_beams
):
    """The acceptance bar: staggered joins (mid-decode, via the backlog)
    and early exits (per-request limits freeing slots) leave every
    request's emitted tokens EXACTLY equal to its solo decode."""
    cfg, params = s2s
    reqs = _requests(cfg, 7, seed=num_beams)
    solos = [
        _solo(cfg, params, ids, mask, limit, num_beams)
        for ids, mask, limit in reqs
    ]
    engine = _engine(cfg, params, num_beams, slots=3)
    done = []
    # 4 requests up front (one exceeds capacity → backlog), the rest join
    # mid-flight every other step.
    for i in range(4):
        ids, mask, limit = reqs[i]
        engine.admit(_encode(cfg, params, ids, mask)[0], mask[0], limit,
                     data=i)
    pending = list(range(4, len(reqs)))
    while engine.has_work():
        done.extend(engine.step())
        if pending and engine.steps_run % 2 == 0:
            i = pending.pop(0)
            ids, mask, limit = reqs[i]
            engine.admit(_encode(cfg, params, ids, mask)[0], mask[0],
                         limit, data=i)
    assert len(done) == len(reqs)
    assert engine.max_occupancy == 3           # capacity actually shared
    for ticket in done:
        i = ticket.data
        limit = reqs[i][2]
        assert np.array_equal(ticket.tokens[:limit], solos[i][:limit]), (
            f"request {i} (beams={num_beams}) diverged from solo decode"
        )
        assert ticket.first_token_wall is not None
        assert ticket.steps <= limit


def test_engine_backlog_joins_between_steps(s2s):
    cfg, params = s2s
    reqs = _requests(cfg, 5, seed=9)
    engine = _engine(cfg, params, 1, slots=2)
    for i, (ids, mask, limit) in enumerate(reqs):
        engine.admit(_encode(cfg, params, ids, mask)[0], mask[0], limit,
                     data=i)
    assert engine.occupancy == 2 and engine.backlog == 3
    finished = 0
    while engine.has_work():
        finished += len(engine.step())
        assert engine.occupancy <= 2
    assert finished == 5
    assert engine.mean_occupancy() > 1.0       # the batch stayed shared


def test_engine_per_slot_limits_exit_early(s2s):
    cfg, params = s2s
    ids = np.full((1, 16), 7, np.int32)
    mask = np.ones((1, 16), np.int32)
    engine = _engine(cfg, params, 1, slots=2)
    enc = _encode(cfg, params, ids, mask)[0]
    short = engine.admit(enc, mask[0], 2, data="short")
    long_ = engine.admit(enc, mask[0], 12, data="long")
    order = []
    while engine.has_work():
        order.extend(t.data for t in engine.step())
    assert order[0] == "short"                 # exited at its own limit
    assert short.steps <= 2 and long_.steps <= 12


def test_engine_run_monolithic(s2s):
    cfg, params = s2s
    reqs = _requests(cfg, 3, seed=3)
    engine = _engine(cfg, params, 2, slots=2)
    tickets = [
        engine.admit(_encode(cfg, params, ids, mask)[0], mask[0], limit,
                     data=i)
        for i, (ids, mask, limit) in enumerate(reqs)
    ]
    engine.run(tickets)
    assert all(t.done_wall is not None for t in tickets)


# ---------------------------------------------------------------------------
# controller front door (in-process)
# ---------------------------------------------------------------------------

def _drain_serving(controller, tasks=("serve_classify", "serve_summarize")):
    """Lease + execute serving jobs inline until the queue drains — a
    minimal in-process agent for controller-level tests."""
    from agent_tpu.ops import load_ops
    from agent_tpu.runtime.context import OpContext

    handlers = load_ops(list(tasks))
    for _ in range(50):
        lease = controller.lease(
            agent="test", capabilities={"ops": sorted(handlers)},
            max_tasks=4,
        )
        if lease is None:
            if controller.serve_door.stats()["bucketed"] == 0 \
                    and not controller.serve_door.job_ids():
                return
            time.sleep(0.01)
            continue
        for task in lease["tasks"]:
            fn = handlers[task["op"]]
            result = fn(task["payload"], OpContext())
            controller.report(
                lease_id=lease["lease_id"], job_id=task["id"],
                job_epoch=task["job_epoch"],
                status="succeeded" if result.get("ok") else "failed",
                result=result,
            )


class TestControllerInfer:
    def make(self, **kw):
        defaults = dict(max_wait_ms=0.0, max_batch=4)  # 0ms: flush on pump
        defaults.update(kw)
        return Controller(serve=ServeConfig(**defaults))

    def test_infer_end_to_end_classify_and_summarize(self):
        c = self.make()
        rid_c = c.submit_infer(
            "classify", "classify this text",
            params={"model_config": TINY_CLS, "topk": 2},
        )
        rid_s = c.submit_infer(
            "summarize", "summarize this text",
            params={"model_config": TINY_S2S, "max_length": 4,
                    "num_beams": 2},
        )
        c._serve_pump()
        _drain_serving(c)
        c._serve_reap()
        snap_c = c.infer_snapshot(rid_c)
        snap_s = c.infer_snapshot(rid_s)
        assert snap_c["state"] == "done", snap_c
        assert len(snap_c["result"]["indices"]) == 2
        assert snap_s["state"] == "done", snap_s
        assert isinstance(snap_s["result"]["summary"], str)
        assert snap_s["result"]["tokens"] <= 4
        assert snap_s["ttft_ms"] is not None
        # metrics observed the completions
        snap = c.metrics.snapshot()
        outcomes = {
            (s["labels"]["op"], s["labels"]["outcome"]): s["value"]
            for s in snap["serve_requests_total"]["series"]
        }
        assert outcomes[("classify", "completed")] == 1
        assert outcomes[("summarize", "completed")] == 1

    @pytest.mark.parametrize("num_beams", [1, 2])
    def test_second_same_bucket_request_is_served_warm(self, num_beams):
        """The runtime places params on its mesh, and what a program over
        them returns is typed with that mesh: engine state, block table and
        admitted rows must be too, or step and insert retrace at every
        join. Sequential same-bucket requests leave ONE executable each."""
        from agent_tpu.ops.serve_infer import engine_executables, reset_engines

        reset_engines()
        c = self.make()
        for text in ("first request", "second one", "and a third"):
            rid = c.submit_infer(
                "summarize", text,
                params={"model_config": TINY_S2S, "max_length": 4,
                        "num_beams": num_beams},
            )
            c._serve_pump()
            _drain_serving(c)
            c._serve_reap()
            assert c.infer_snapshot(rid)["state"] == "done"
        (engine,) = engine_executables()
        assert engine["step"] == 1 and engine["insert"] == 1, engine
        reset_engines()

    def test_serve_jobs_ride_interactive_tier_and_tenant(self):
        c = self.make(priority=8)
        c.submit_infer("classify", "text", tenant="acme",
                       params={"model_config": TINY_CLS})
        c._serve_pump()
        (job_id,) = c.serve_door.job_ids()
        job = c.job(job_id)
        assert job.priority == 8
        assert job.tenant == "acme"
        assert job.op == "serve_classify"

    def test_infer_disabled_raises(self):
        c = Controller(serve=ServeConfig(enabled=False))
        with pytest.raises(RuntimeError):
            c.submit_infer("classify", "text")
        assert c.serve_status() == {"enabled": False}

    def test_wait_infer_pumps_the_deadline_flush(self):
        c = self.make(max_wait_ms=10.0)
        done = {}

        def agent_loop():
            deadline = time.monotonic() + 30.0
            while "rid" not in done and time.monotonic() < deadline:
                time.sleep(0.005)
            _drain_serving(c)

        t = threading.Thread(target=agent_loop, daemon=True)
        t.start()
        rid = c.submit_infer("classify", "text",
                             params={"model_config": TINY_CLS})
        done["rid"] = rid
        snap = c.wait_infer(rid, 30.0)
        t.join(timeout=30)
        assert snap["state"] == "done", snap

    def test_slo_ttft_objective_fed(self):
        c = self.make()
        c.submit_infer("summarize", "text",
                       params={"model_config": TINY_S2S, "max_length": 3})
        c._serve_pump()
        _drain_serving(c)
        c._serve_reap()
        results = c.slo.evaluate()
        by_name = {r["objective"]: r for r in results}
        short = by_name["interactive_ttft"]["windows"]["short"]
        assert short["requests"] == 1


# ---------------------------------------------------------------------------
# HTTP routes
# ---------------------------------------------------------------------------

@pytest.fixture()
def http_server():
    requests = pytest.importorskip("requests")
    from agent_tpu.controller.server import ControllerServer

    controller = Controller(
        serve=ServeConfig(max_wait_ms=10.0, max_batch=4)
    )
    server = ControllerServer(controller).start()
    stop = threading.Event()

    def loop():
        from agent_tpu.ops import load_ops
        from agent_tpu.runtime.context import OpContext

        handlers = load_ops(["serve_classify", "serve_summarize"])
        session = requests.Session()
        while not stop.is_set():
            lease = controller.lease(
                agent="http-test", capabilities={"ops": sorted(handlers)},
                max_tasks=4,
            )
            if lease is None:
                time.sleep(0.005)
                continue
            for task in lease["tasks"]:
                fn = handlers[task["op"]]
                out = fn(task["payload"], OpContext())
                controller.report(
                    lease_id=lease["lease_id"], job_id=task["id"],
                    job_epoch=task["job_epoch"],
                    status="succeeded" if out.get("ok") else "failed",
                    result=out,
                )

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    yield server, requests.Session()
    stop.set()
    t.join(timeout=10)
    server.stop()


class TestInferHttp:
    def test_blocking_post(self, http_server):
        server, session = http_server
        r = session.post(server.url + "/v1/infer", json={
            "op": "summarize", "text": "please summarize",
            "params": {"model_config": TINY_S2S, "max_length": 4},
        }, timeout=120)
        assert r.status_code == 200
        body = r.json()
        assert body["state"] == "done", body
        assert isinstance(body["result"]["summary"], str)

    def test_nonblocking_then_get(self, http_server):
        server, session = http_server
        r = session.post(server.url + "/v1/infer", json={
            "op": "classify", "text": "route me", "wait": False,
            "params": {"model_config": TINY_CLS},
        }, timeout=30)
        rid = r.json()["req_id"]
        assert r.json()["state"] == "queued"
        r2 = session.get(
            server.url + f"/v1/infer/{rid}?wait_ms=60000", timeout=120
        )
        assert r2.json()["state"] == "done", r2.json()

    def test_stream_frames_lifecycle(self, http_server):
        import json as _json

        server, session = http_server
        r = session.post(server.url + "/v1/infer", json={
            "op": "summarize", "text": "stream me", "stream": True,
            "params": {"model_config": TINY_S2S, "max_length": 3},
        }, stream=True, timeout=120)
        events = [_json.loads(line) for line in r.iter_lines() if line]
        states = [e["state"] for e in events]
        assert states[0] == "queued"
        assert states[-1] == "done"
        assert "result" in events[-1]

    def test_bad_request_400_and_unknown_404(self, http_server):
        server, session = http_server
        r = session.post(server.url + "/v1/infer", json={
            "op": "transcribe", "text": "x",
        }, timeout=10)
        assert r.status_code == 400
        r2 = session.get(server.url + "/v1/infer/req-nope", timeout=10)
        assert r2.status_code == 404

    def test_admission_429(self):
        requests = pytest.importorskip("requests")
        from agent_tpu.controller.server import ControllerServer

        controller = Controller(serve=ServeConfig(
            max_wait_ms=10_000.0, max_batch=64, max_pending=1,
        ))
        with ControllerServer(controller) as server:
            s = requests.Session()
            r1 = s.post(server.url + "/v1/infer", json={
                "op": "classify", "text": "one", "wait": False,
            }, timeout=10)
            assert r1.status_code == 200
            r2 = s.post(server.url + "/v1/infer", json={
                "op": "classify", "text": "two", "wait": False,
            }, timeout=10)
            assert r2.status_code == 429
            assert "retry_after_ms" in r2.json()
            assert r2.headers.get("Retry-After")

    def test_status_serving_block(self, http_server):
        server, session = http_server
        st = session.get(server.url + "/v1/status", timeout=10).json()
        assert st["serving"]["enabled"] is True


# ---------------------------------------------------------------------------
# request-level observability (ISSUE 17)
# ---------------------------------------------------------------------------

def _hist_count(controller, family, **labels):
    for s in controller.metrics.snapshot().get(family, {}).get("series", []):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["count"]
    return 0


class TestRequestObservability:
    def test_flush_reasons_counted_and_bucket_wait_component_fed(self):
        """ISSUE 17 satellite: full and deadline flushes count distinctly
        in serve_batches_total, and BOTH paths feed the bucket_wait
        component histogram once their riders complete."""
        c = Controller(serve=ServeConfig(max_wait_ms=0.0, max_batch=2))
        # Same bucket twice -> the second submit fills it: reason "full".
        for text in ("classify this", "classify that!"):
            c.submit_infer("classify", text,
                           params={"model_config": TINY_CLS})
        assert c._m_serve_batches.value(op="classify", reason="full") == 1
        # A lone rider flushes on the pump cadence: reason "deadline".
        c.submit_infer("classify", "straggler text",
                       params={"model_config": TINY_CLS})
        c._serve_pump()
        assert c._m_serve_batches.value(
            op="classify", reason="deadline"
        ) == 1
        _drain_serving(c)
        c._serve_reap()
        assert _hist_count(
            c, "serve_ttft_component_seconds", component="bucket_wait"
        ) == 3
        reasons = {
            r["flush_reason"] for r in c.requests_json()["requests"]
        }
        assert reasons == {"full", "deadline"}

    def test_stitched_request_trace_and_wide_record(self):
        """Tentpole acceptance (colocated): a completed request resolves to
        ONE complete trace linked into its batch job, its TTFT components
        telescope to the measured TTFT, and the wide-event record carries
        the full schema."""
        c = Controller(serve=ServeConfig(max_wait_ms=0.0, max_batch=4))
        rid = c.submit_infer(
            "summarize", "summarize this text",
            params={"model_config": TINY_S2S, "max_length": 5},
        )
        c._serve_pump()
        _drain_serving(c)
        c._serve_reap()
        (rec,) = c.requests_json()["requests"]
        assert rec["req_id"] == rid and rec["outcome"] == "completed"
        assert rec["path"] == "colocated"
        for key in ("tenant", "op", "bucket", "priority", "ttft_ms",
                    "tpot_ms", "tokens", "steps", "prefix_hit",
                    "kv_wait_ms", "occupancy", "components",
                    "dominant_component", "trace_id", "job_id"):
            assert key in rec, key
        comps = rec["components"]
        assert set(comps) == {"bucket_wait", "queue_wait", "prefill",
                              "handoff", "kv_wait", "first_decode"}
        assert abs(sum(comps.values()) - rec["ttft_ms"]) <= \
            max(1.0, 0.1 * rec["ttft_ms"])
        tr = c.trace_json(rid)
        assert tr is not None and tr["complete"], tr
        names = {s["name"] for s in tr["spans"]}
        assert {"infer", "bucket.wait", "ttft.first_decode",
                "decode"} <= names
        linked = {lt["trace_id"] for lt in tr.get("linked_traces", [])}
        assert rec["job_id"] in linked
        # And the batch job's trace names this rider back.
        job_tr = c.trace_json(rec["job_id"])
        assert rid in {
            lt["trace_id"] for lt in job_tr.get("linked_traces", [])
        }

    def test_disagg_dep_failed_emits_dep_failed_record(self):
        """ISSUE 17 satellite: riders of a serve_decode job killed by a
        dead prefill dependency get outcome=dep_failed in the request
        log (not a generic failure)."""
        c = Controller(serve=ServeConfig(
            max_wait_ms=0.0, max_batch=4, disaggregated=True,
        ))
        rid = c.submit_infer(
            "summarize", "doomed request",
            params={"model_config": TINY_S2S, "max_length": 4},
        )
        c._serve_pump()
        # The front door watches the DECODE job; its dependency is the
        # prefill leg. Fail that leg to death: permanent error, retries
        # exhausted.
        (decode_id,) = c.serve_door.job_ids()
        (pf_id,) = c.job(decode_id).after
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if c.job(pf_id).state in ("failed", "dead"):
                break
            lease = c.lease(
                agent="t", capabilities={"ops": ["serve_prefill"]},
                max_tasks=1,
            )
            if lease is None:
                time.sleep(0.005)
                continue
            for task in lease["tasks"]:
                c.report(
                    lease_id=lease["lease_id"], job_id=task["id"],
                    job_epoch=task["job_epoch"], status="failed",
                    error={"type": "ValueError", "message": "boom"},
                )
        c._serve_pump()
        snap = c.infer_snapshot(rid)
        assert snap["state"] == "failed", snap
        (rec,) = c.requests_json()["requests"]
        assert rec["outcome"] == "dep_failed", rec
        assert rec["error"] == "DependencyFailed"
        # The request's root span closed with the verdict.
        tr = c.trace_json(rid)
        root = next(s for s in tr["spans"] if s["name"] == "infer")
        assert root["attributes"]["outcome"] == "dep_failed"
        assert root["duration_ms"] is not None

    def test_debug_requests_http_filters_and_jsonl(self, http_server):
        """GET /v1/debug/requests: tenant/outcome/slow filters + JSONL
        export; GET /v1/debug/events?req_id= narrows to one request."""
        import json as _json

        server, session = http_server
        r = session.post(server.url + "/v1/infer", json={
            "op": "classify", "text": "observable request",
            "tenant": "acme", "params": {"model_config": TINY_CLS},
        }, timeout=120)
        assert r.json()["state"] == "done", r.json()
        rid = r.json()["req_id"]
        # The reap wakes the request's waiters first and writes the wide
        # event right after: the record can trail ``done`` by a moment.
        deadline = time.monotonic() + 10.0
        while True:
            body = session.get(
                server.url + "/v1/debug/requests?tenant=acme", timeout=10
            ).json()
            if body["requests"] or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert body["enabled"] and body["requests"]
        assert all(rec["tenant"] == "acme" for rec in body["requests"])
        assert body["stats"]["seen"] >= 1
        none = session.get(
            server.url + "/v1/debug/requests?tenant=nobody", timeout=10
        ).json()
        assert none["requests"] == []
        jl = session.get(
            server.url + "/v1/debug/requests?format=jsonl", timeout=10
        )
        assert jl.headers["Content-Type"].startswith("application/jsonl")
        recs = [_json.loads(line) for line in jl.text.splitlines() if line]
        assert any(rec["req_id"] == rid for rec in recs)
        ev = session.get(
            server.url + f"/v1/debug/events?req_id={rid}", timeout=10
        ).json()["events"]
        assert ev and all(e.get("req_id") == rid for e in ev)
        # The stitched trace resolves over HTTP for a req_id too.
        tr = session.get(
            server.url + f"/v1/trace/{rid}", timeout=10
        ).json()
        assert tr["trace_id"] == rid and tr.get("linked_traces")

    def test_usage_surfaces_prefix_dedupe_ratio(self):
        """ISSUE 17 satellite: /v1/usage exposes the per-tenant share of
        prefill rows the prefix cache absorbed."""
        from agent_tpu.obs.usage import UsageLedger

        ledger = UsageLedger()
        ledger.bill(
            job_id="j1", tenant="acme", tier=5, op="serve_summarize",
            attempt=1,
            usage={"device_s": 1.0, "rows": 6, "cache_hit_rows": 2},
        )
        report = ledger.report()
        assert report["by_tenant"]["acme"]["prefix_dedupe_ratio"] == \
            pytest.approx(0.25)
        assert report["totals"]["prefix_dedupe_ratio"] == \
            pytest.approx(0.25)
