"""Decode-path raw speed (ISSUE 16): paged KV cache, cross-request prefix
reuse, and the disaggregated prefill/decode chain.

The engine tests pin the same acceptance bar as ``test_serving.py`` — tokens
through the continuous engine are BIT-IDENTICAL to a solo static decode —
but on the PAGED cache layout, including pool-constrained admission (a full
pool delays a request, it never changes its tokens). The controller tests
pin the serving-level bars: a prefix-cache hit is bit-identical to the cold
prefill that populated it, and the disaggregated prefill→decode chain is
bit-identical to the colocated path (JSON and b1 wire both).
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from agent_tpu.config import ServeConfig
from agent_tpu.controller.core import Controller
from agent_tpu.models.decoding import KVPoolExhausted
from agent_tpu.ops.prefix_cache import PrefixCache, prefix_key

TINY_S2S = {
    "d_model": 32, "n_heads": 4, "n_enc_layers": 1, "n_dec_layers": 1,
    "d_ff": 64, "max_src_len": 32, "max_tgt_len": 20, "dtype": "float32",
}

# block_size 4 at max_tgt_len 20 → 5 blocks per max-length row: small enough
# that a handful of requests exercises allocate/release/trash-block paths.
BLOCK_SIZE = 4
BLOCKS_PER_ROW = 5


@pytest.fixture(scope="module")
def s2s():
    from agent_tpu.models import seq2seq

    cfg = seq2seq.Seq2SeqConfig(**TINY_S2S)
    params = seq2seq.init_params(cfg, model_id="paged-test")
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


def _encode(cfg, params, ids, mask):
    import jax
    import jax.numpy as jnp

    from agent_tpu.models import seq2seq

    return np.asarray(jax.jit(
        lambda p, i, m: seq2seq.encode(p, i, m, cfg).astype(jnp.float32)
    )(params, jnp.asarray(ids), jnp.asarray(mask)))


def _paged_engine(
    cfg, params, num_beams, slots=3, src_len=16, pool_blocks=0, **kw
):
    from agent_tpu.models import seq2seq
    from agent_tpu.models.decoding import ContinuousBatcher
    from agent_tpu.models.tokenizer import BOS_ID, EOS_ID, PAD_ID

    return ContinuousBatcher(
        seq2seq.make_positional_step(cfg),
        seq2seq.make_paged_cache_factory(
            cfg, block_size=BLOCK_SIZE, pool_blocks=pool_blocks
        ),
        params=params,
        slots=slots, vocab_size=cfg.vocab_size, max_tokens=cfg.max_tgt_len,
        enc_len=src_len, d_model=cfg.d_model,
        start_id=BOS_ID, eos_id=EOS_ID, pad_id=PAD_ID,
        num_beams=num_beams, **kw,
    )


# ---------------------------------------------------------------------------
# paged engine correctness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_beams", [1, 3])
def test_paged_engine_bit_identical_with_joins_and_exits(s2s, num_beams):
    """The flagship bar on the paged layout: staggered joins and early
    exits over a shared block pool leave every request's tokens EXACTLY
    equal to its solo (dense-cache) decode."""
    cfg, params = s2s
    reqs = _requests(cfg, 7, seed=num_beams)
    solos = [
        _solo(cfg, params, ids, mask, limit, num_beams)
        for ids, mask, limit in reqs
    ]
    engine = _paged_engine(cfg, params, num_beams, slots=3)
    assert engine.paged
    total = engine.kv_blocks_total
    assert total == 3 * num_beams * BLOCKS_PER_ROW  # auto-sized dense parity
    done = []
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
    assert engine.max_occupancy == 3
    for ticket in done:
        i = ticket.data
        limit = reqs[i][2]
        assert np.array_equal(ticket.tokens[:limit], solos[i][:limit]), (
            f"request {i} (beams={num_beams}) diverged from solo decode "
            "on the paged cache"
        )
    # Every block came back to the free list; none leaked into the trash.
    assert engine.kv_blocks_free == total


def test_paged_slot_reuse_returns_blocks(s2s):
    """Churn through more requests than slots: released blocks are reused
    by later seats, the free count never goes negative, and the pool is
    whole after the drain."""
    cfg, params = s2s
    reqs = _requests(cfg, 6, seed=11)
    solos = [_solo(cfg, params, i, m, l, 1) for i, m, l in reqs]
    engine = _paged_engine(cfg, params, 1, slots=2)
    total = engine.kv_blocks_total
    for i, (ids, mask, limit) in enumerate(reqs):
        engine.admit(_encode(cfg, params, ids, mask)[0], mask[0], limit,
                     data=i)
    done = []
    while engine.has_work():
        done.extend(engine.step())
        assert 0 <= engine.kv_blocks_free <= total
    assert len(done) == len(reqs)
    assert engine.max_occupancy == 2
    for t in done:
        limit = reqs[t.data][2]
        assert np.array_equal(t.tokens[:limit], solos[t.data][:limit])
    assert engine.kv_blocks_free == total


def test_paged_never_seatable_request_raises(s2s):
    """A request whose worst-case reservation exceeds the WHOLE pool can
    never run — admit refuses it up front instead of wedging the queue."""
    cfg, params = s2s
    # Minimum legal pool: one max-length row + trash. At 2 beams, a
    # max-length request needs 2 rows' worth — never seatable.
    engine = _paged_engine(
        cfg, params, 2, slots=2, pool_blocks=BLOCKS_PER_ROW + 1
    )
    ids = np.full((1, 16), 7, np.int32)
    mask = np.ones((1, 16), np.int32)
    enc = _encode(cfg, params, ids, mask)[0]
    with pytest.raises(KVPoolExhausted):
        engine.admit(enc, mask[0], cfg.max_tgt_len, data="too-big")
    # A request that fits the pool still seats and completes.
    t = engine.admit(enc, mask[0], BLOCK_SIZE, data="fits")
    while engine.has_work():
        engine.step()
    assert t.done_wall is not None
    assert engine.kv_blocks_free == engine.kv_blocks_total


def test_paged_full_pool_waits_fifo_and_stays_exact(s2s):
    """Pool exhaustion is backpressure, not corruption: with free slots but
    no free blocks, requests wait in FIFO order (no small-request overtake)
    and every one still decodes bit-identically."""
    cfg, params = s2s
    # Usable pool = exactly one max-length greedy row: requests 0 and 2
    # (5 blocks each) serialize the pool even though 3 slots are free.
    engine = _paged_engine(
        cfg, params, 1, slots=3, pool_blocks=BLOCKS_PER_ROW + 1
    )
    limits = [cfg.max_tgt_len - 1, 2, cfg.max_tgt_len - 1]
    reqs = []
    rng = np.random.default_rng(21)
    for limit in limits:
        ids = rng.integers(4, cfg.vocab_size, (1, 16)).astype(np.int32)
        mask = np.ones((1, 16), np.int32)
        reqs.append((ids, mask, limit))
    solos = [_solo(cfg, params, i, m, l, 1) for i, m, l in reqs]
    for i, (ids, mask, limit) in enumerate(reqs):
        engine.admit(_encode(cfg, params, ids, mask)[0], mask[0], limit,
                     data=i)
    # Only the head seats: request 1 needs one block but must not overtake.
    assert engine.occupancy == 1 and engine.backlog == 2
    order = []
    while engine.has_work():
        order.extend(t.data for t in engine.step())
        assert engine.occupancy <= 1   # the pool, not the slots, gates
    assert order == [0, 1, 2]
    # Same workload again, keeping ticket handles for the token checks.
    engine2 = _paged_engine(
        cfg, params, 1, slots=3, pool_blocks=BLOCKS_PER_ROW + 1
    )
    tickets = [
        engine2.admit(_encode(cfg, params, ids, mask)[0], mask[0], limit,
                      data=i)
        for i, (ids, mask, limit) in enumerate(reqs)
    ]
    while engine2.has_work():
        engine2.step()
    for i, t in enumerate(tickets):
        limit = reqs[i][2]
        assert np.array_equal(t.tokens[:limit], solos[i][:limit]), (
            f"request {i} diverged after waiting on the full pool"
        )
    assert engine2.kv_blocks_free == engine2.kv_blocks_total


# ---------------------------------------------------------------------------
# prefix cache unit
# ---------------------------------------------------------------------------

class TestPrefixCache:
    def test_key_separates_model_version_and_length(self):
        row = np.arange(16, dtype=np.int32)
        k = prefix_key("m1", row)
        assert k == prefix_key("m1", row.copy())          # content-stable
        assert k != prefix_key("m2", row)                 # model in the seed
        assert k != prefix_key("m1", row[:8])             # length in the seed
        longer = np.concatenate([row, np.zeros(64, np.int32)])
        assert k != prefix_key("m1", longer)              # pad bucket too
        row2 = row.copy()
        row2[3] += 1
        assert k != prefix_key("m1", row2)                # content-sensitive

    def test_hit_is_bit_exact_and_counted(self):
        cache = PrefixCache(max_entries=4)
        row = np.random.default_rng(0).normal(size=(8, 16)).astype(np.float32)
        key = prefix_key("m", np.arange(8, dtype=np.int32))
        assert cache.get(key) is None                     # cold miss
        cache.put(key, row)
        hit = cache.get(key)
        assert hit is not None and np.array_equal(hit, row)
        assert hit.dtype == np.float32
        s = cache.stats()
        assert s["hits"] == 1 and s["misses"] == 1 and s["hit_rate"] == 0.5

    def test_lru_eviction_order_and_counters(self):
        cache = PrefixCache(max_entries=2)
        rows = {k: np.full((2, 2), i, np.float32)
                for i, k in enumerate("abc")}
        cache.put("a", rows["a"])
        cache.put("b", rows["b"])
        assert cache.get("a") is not None                 # refresh "a"
        cache.put("c", rows["c"])                         # evicts LRU = "b"
        assert cache.get("b") is None
        assert cache.get("a") is not None
        assert cache.get("c") is not None
        assert cache.stats()["evictions"] == 1
        assert len(cache) == 2

    def test_byte_budget_bounds_and_oversized_row(self):
        one_kb = np.zeros(256, np.float32)                # 1024 bytes
        cache = PrefixCache(max_entries=64, max_bytes=2048)
        cache.put("a", one_kb)
        cache.put("b", one_kb)
        cache.put("c", one_kb)                            # over budget → evict
        assert len(cache) == 2 and cache.bytes_used <= 2048
        assert cache.stats()["evictions"] == 1
        cache.put("huge", np.zeros(4096, np.float32))     # > whole budget
        assert cache.get("huge") is None                  # never cached
        assert cache.bytes_used <= 2048


# ---------------------------------------------------------------------------
# controller-level: colocated prefix reuse + disaggregated chain
# ---------------------------------------------------------------------------

SERVE_TASKS = ("serve_summarize", "serve_prefill", "serve_decode")

TEXTS = [
    "shared prefix context document alpha for the serving tests",
    "shared prefix context document alpha for the serving tests",
    "a different text to summarize entirely",
    "shared prefix context document alpha for the serving tests",
]


def _serve_drain(controller, ctx=None):
    """Minimal in-process agent: lease + execute + report until the serving
    door is empty (mirrors ``test_serving._drain_serving``, with the op
    context injectable so the b1-wire test can tag the agent side)."""
    from agent_tpu.ops import load_ops
    from agent_tpu.runtime.context import OpContext

    handlers = load_ops(list(SERVE_TASKS))
    ctx = ctx if ctx is not None else OpContext()
    for _ in range(200):
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
            result = handlers[task["op"]](task["payload"], ctx)
            controller.report(
                lease_id=lease["lease_id"], job_id=task["id"],
                job_epoch=task["job_epoch"],
                status="succeeded" if result.get("ok") else "failed",
                result=result,
            )
    raise AssertionError("serve drain did not converge")


class TestDisaggServing:
    def _round(self, controller, ctx=None):
        rids = [
            controller.submit_infer("summarize", t, params={
                "model_config": TINY_S2S, "max_length": 8, "num_beams": 2,
            })
            for t in TEXTS
        ]
        controller._serve_pump()
        _serve_drain(controller, ctx=ctx)
        controller._serve_reap()
        out = []
        for rid in rids:
            snap = controller.infer_snapshot(rid)
            assert snap["state"] == "done", snap
            assert snap["ttft_ms"] is not None
            out.append(snap["result"]["summary"])
        return out

    def _controller(self, **kw):
        from agent_tpu.ops.serve_infer import reset_engines

        reset_engines()   # fresh engine store + prefix cache per test
        defaults = dict(max_wait_ms=0.0, max_batch=4)
        defaults.update(kw)
        # Result cache off (ISSUE 19): these tests exercise the KV-layer
        # prefix cache, which the front-door result cache would mask on
        # repeated identical requests.
        from agent_tpu.config import FlowConfig

        return Controller(
            serve=ServeConfig(**defaults),
            flow=FlowConfig(cache_enabled=False),
        )

    def test_colocated_prefix_cache_hit_bit_identical(self):
        """The satellite bar: a prefix-cache hit returns output
        bit-identical to the cold prefill, and the controller gauges see
        the paged pool come back whole."""
        c = self._controller()
        first = self._round(c)
        hits_after_cold = c._m_serve_prefix.value(event="hits")
        second = self._round(c)
        assert second == first                       # cached == cold
        hits = c._m_serve_prefix.value(event="hits")
        misses = c._m_serve_prefix.value(event="misses")
        assert hits - hits_after_cold >= len(TEXTS)  # every repeat hit
        assert misses >= 2.0                         # 2 distinct cold texts
        assert c._m_serve_kv_total.value() > 0       # paged is the default
        assert c._m_serve_kv_free.value() == c._m_serve_kv_total.value()

    def test_disagg_chain_bit_identical_to_colocated(self):
        colo = self._round(self._controller())
        c = self._controller(disaggregated=True)
        dis = self._round(c)
        assert dis == colo
        ops = {
            r.get("op") for r in c.results().values() if isinstance(r, dict)
        }
        assert {"serve_prefill", "serve_decode"} <= ops
        # Forwarded prefix/KV stats reached the reap from the decode leg.
        assert c._m_serve_kv_total.value() > 0
        assert c._m_serve_prefix.value(event="misses") >= 1.0

    def test_disagg_b1_wire_handoff_round_trip(self):
        """The KV-handoff envelope survives the binary wire: a disagg run
        whose agent speaks b1 (encoded rows attached as binary columns,
        decoded at report time) equals the JSON-wire run bit-for-bit."""
        from agent_tpu.runtime.context import OpContext

        json_out = self._round(self._controller(disaggregated=True))
        c = self._controller(disaggregated=True)
        b1_out = self._round(c, ctx=OpContext(tags={"wire": "b1"}))
        assert b1_out == json_out

    def test_prefill_failure_cascades_to_decode_rider(self):
        """A dead prefill must not strand its decode job (dep gating only
        releases on success): the reap fails the decode the deadline-death
        way and the rider's wait resolves failed, not hung."""
        c = self._controller(disaggregated=True)
        rid = c.submit_infer("summarize", "text that will not prefill",
                             params={"model_config": TINY_S2S,
                                     "max_length": 4})
        c._serve_pump()
        lease = c.lease(
            agent="test", capabilities={"ops": ["serve_prefill"]},
            max_tasks=4,
        )
        assert lease is not None
        (task,) = lease["tasks"]
        assert task["op"] == "serve_prefill"
        # ValueError is a PERMANENT type: the job sticks FAILED on the
        # first report instead of burning the retry budget.
        c.report(
            lease_id=lease["lease_id"], job_id=task["id"],
            job_epoch=task["job_epoch"], status="failed",
            error={"type": "ValueError", "message": "injected prefill fault"},
        )
        c._serve_pump()
        c._serve_reap()
        snap = c.infer_snapshot(rid)
        assert snap["state"] == "failed", snap
        assert snap["error"]["type"] in ("DependencyFailed", "ValueError")
