"""Request-serving ops: the agent half of the ``POST /v1/infer`` path.

The controller's front door (``controller/serving.py``) coalesces single
requests into length-bucketed batch jobs; these ops execute them:

- ``serve_classify`` — one batched encoder forward through the existing
  ``map_classify_tpu`` guts, fanned back out per request. Monolithic: a
  classify is a single dispatch, there is nothing to batch continuously.
- ``serve_summarize`` — the decode path, split prefill/decode (ISSUE 15):
  **prefill** runs as its own batched compiled step (``seq2seq.encode`` —
  the ``summarize_mpmd`` encoded-handoff shape), then the requests join a
  process-persistent :class:`~agent_tpu.models.decoding.ContinuousBatcher`
  whose fixed-capacity running batch decodes ``SERVE_DECODE_SLOTS``
  requests × ``num_beams`` beam rows per step, finished sequences exiting
  and queued ones joining *between steps*. Each request carries its own
  ``max_length`` as the per-slot token limit — short answers free their
  slot early instead of riding the batch to the longest request's length,
  which is the whole throughput story vs. the static-batch decode.

Phase contract for the pipelined drain: ``stage``/``finalize`` as usual,
plus the serving hooks the runner's continuous loop drives —
``serve_admit`` (prefill + join), ``serve_pump`` (one engine iteration),
``serve_done``/``serve_collect``. Monolithic callers (serial agent loop,
tests) get the composed ``run`` which pumps to completion inline.

Scenario ops for the in-house seq2seq family (like ``summarize_mpmd``);
checkpoint families keep the batch ``map_summarize`` path.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from agent_tpu.ops import register_op
from agent_tpu.utils.errors import bad_input

# Process-wide engine store, keyed by (runtime identity, model/config/shape
# signature). Device-thread only (engines are created and stepped inside op
# execute paths — the TPU single-owner rule), so no lock.
_ENGINES: Dict[Tuple, Any] = {}

# Process-wide prefix cache (ISSUE 16), rebuilt when its knobs change.
_PREFIX_CACHE: Any = None
_PREFIX_KNOBS: Optional[Tuple] = None


def reset_engines() -> None:
    """Drop every cached engine (tests; a fresh runtime invalidates them)."""
    global _PREFIX_CACHE, _PREFIX_KNOBS
    _ENGINES.clear()
    _PREFIX_CACHE = None
    _PREFIX_KNOBS = None


def engine_executables() -> List[Dict[str, int]]:
    """Per cached engine: its source bucket and how many executables its
    step and insert programs hold (``ContinuousBatcher.executables``). One
    of each is a warm engine; more is a retrace at every join."""
    return [
        {"bucket": int(key[3]), **engine.executables()}
        for key, engine in list(_ENGINES.items())
    ]


def _get_prefix_cache(serve):
    """The process prefix cache per the active knobs, or ``None`` when
    disabled."""
    global _PREFIX_CACHE, _PREFIX_KNOBS
    if not serve.prefix_cache_enabled or serve.prefix_cache_entries < 1 \
            or serve.prefix_cache_mb <= 0:
        return None
    knobs = (serve.prefix_cache_entries, serve.prefix_cache_mb)
    if _PREFIX_CACHE is None or _PREFIX_KNOBS != knobs:
        from agent_tpu.ops.prefix_cache import PrefixCache

        _PREFIX_CACHE = PrefixCache(
            max_entries=serve.prefix_cache_entries,
            max_bytes=int(serve.prefix_cache_mb * 2 ** 20),
        )
        _PREFIX_KNOBS = knobs
    return _PREFIX_CACHE


def _clamp_ttft(first_wall: Optional[float], arrived: Any) -> Optional[float]:
    """first-token wall − controller arrival wall, in ms, clamped at 0
    (the two clocks are different hosts' ``time.time()``; sub-ms skew must
    not produce negative TTFT)."""
    if first_wall is None or not isinstance(arrived, (int, float)):
        return None
    return round(max(0.0, (first_wall - float(arrived)) * 1e3), 3)


def _validate_requests(payload: Dict[str, Any]):
    reqs = payload.get("requests")
    if not isinstance(reqs, list) or not reqs:
        raise ValueError("payload requires a non-empty 'requests' list")
    for r in reqs:
        if not (
            isinstance(r, dict)
            and isinstance(r.get("req_id"), str) and r["req_id"]
            and isinstance(r.get("text"), str) and r["text"]
        ):
            raise ValueError(
                "each request needs a string req_id and a non-empty text"
            )
    return reqs


# ---------------------------------------------------------------------------
# serve_classify
# ---------------------------------------------------------------------------

@register_op("serve_classify")
def run_classify(payload: Any, ctx: Optional[object] = None) -> Dict[str, Any]:
    """Batched interactive classify: requests in, per-request top-k out."""
    t0 = time.perf_counter()
    t0_wall = time.time()
    if not isinstance(payload, dict):
        return bad_input("payload must be a dict")
    try:
        reqs = _validate_requests(payload)
    except ValueError as exc:
        return bad_input(str(exc))
    topk = payload.get("topk", 1)
    if isinstance(topk, bool) or not isinstance(topk, int) or topk < 1:
        return bad_input("topk must be a positive int")

    from agent_tpu.ops import get_op

    sub: Dict[str, Any] = {
        "texts": [r["text"] for r in reqs],
        "topk": topk,
        "allow_fallback": False,
        "result_format": "columnar",
    }
    if isinstance(payload.get("model_config"), dict):
        sub["model_config"] = payload["model_config"]
    # The negotiated binary wire ("b1" in ctx.tags) would make classify
    # emit deflated result columns — this op fans the columns out PER
    # REQUEST, so it needs them plain; pop the tag for the delegated call
    # (everything else — timings, usage, FLOPs stamps — keeps flowing).
    tags = getattr(ctx, "tags", None) if ctx is not None else None
    wire_fmt = tags.pop("wire", None) if isinstance(tags, dict) else None
    try:
        out = get_op("map_classify_tpu")(sub, ctx)
    finally:
        if wire_fmt is not None:
            tags["wire"] = wire_fmt
    if not (isinstance(out, dict) and out.get("ok") is True):
        return out  # soft error shape propagates as this op's result
    now = time.time()
    results = [
        {
            "req_id": r["req_id"],
            "indices": out["indices"][i],
            "scores": out["scores"][i],
            # No decode stream: the first answer byte IS the whole answer.
            "ttft_ms": _clamp_ttft(now, r.get("arrived_wall")),
            "tokens": 0,
            # Per-request telemetry (ISSUE 17): classify is one forward —
            # the whole device window is "prefill", first token == done.
            "telemetry": {
                "path": "colocated",
                "prefill_t0_wall": t0_wall,
                "prefill_t1_wall": now,
                "admitted_wall": now,
                "joined_wall": now,
                "first_token_wall": now,
                "done_wall": now,
                "kv_wait_ms": 0.0,
                "occupancy_at_join": len(reqs),
                "cache_hit": False,
                "steps": 0,
            },
        }
        for i, r in enumerate(reqs)
    ]
    return {
        "ok": True,
        "op": "serve_classify",
        "device": out.get("device"),
        "model": out.get("model"),
        "n_requests": len(reqs),
        "results": results,
        "occupancy": float(len(reqs)),
        "max_occupancy": len(reqs),
        "elapsed_ms": (time.perf_counter() - t0) * 1000.0,
    }


# ---------------------------------------------------------------------------
# serve_summarize
# ---------------------------------------------------------------------------

def _resolve(payload: Dict[str, Any]):
    from agent_tpu.models import bert
    from agent_tpu.models.seq2seq import Seq2SeqConfig
    from agent_tpu.ops._model_common import (
        config_from_payload,
        resolve_model_id,
    )

    model_id = resolve_model_id(payload, "BART_MODEL", "summarize-default")
    if bert.is_hf_dir(model_id):
        raise ValueError(
            "serve_summarize serves the in-house seq2seq family; checkpoint "
            "directories stay on the batch map_summarize path"
        )
    cfg = config_from_payload(payload, Seq2SeqConfig)
    return model_id, cfg


def _runtime(ctx):
    if ctx is not None and getattr(ctx, "require_runtime", None):
        return ctx.require_runtime()
    from agent_tpu.runtime.runtime import get_runtime

    return get_runtime()


def _serve_knobs(ctx):
    """The agent's :class:`~agent_tpu.config.ServeConfig` (SERVE_* env)."""
    cfg = getattr(ctx, "config", None) if ctx is not None else None
    serve = getattr(cfg, "serve", None) if cfg is not None else None
    if serve is None:
        from agent_tpu.config import ServeConfig

        serve = ServeConfig.from_env()
    return serve


def stage(payload: Any, ctx: Optional[object] = None):
    """Host phase: validate the batch, fused byte-tokenize+pad every request
    to the bucket length the controller coalesced on."""
    t0 = time.perf_counter()
    if not isinstance(payload, dict):
        return "done", bad_input("payload must be a dict")
    try:
        reqs = _validate_requests(payload)
        model_id, cfg = _resolve(payload)
    except ValueError as exc:
        return "done", bad_input(str(exc))

    num_beams = payload.get("num_beams", 1)
    if isinstance(num_beams, bool) or not isinstance(num_beams, int) or \
            not 1 <= num_beams <= 16:
        return "done", bad_input("num_beams must be an int in [1, 16]")
    length_penalty = payload.get("length_penalty", 1.0)
    if isinstance(length_penalty, bool) or \
            not isinstance(length_penalty, (int, float)) or \
            not -4.0 <= float(length_penalty) <= 4.0:
        return "done", bad_input("length_penalty must be a number in [-4, 4]")
    early_stopping = payload.get("early_stopping", False)
    if not isinstance(early_stopping, bool):
        return "done", bad_input("early_stopping must be a bool")
    min_length = payload.get("min_length", 0)
    if isinstance(min_length, bool) or not isinstance(min_length, int) or \
            min_length < 0:
        return "done", bad_input("min_length must be a non-negative int")
    bucket = payload.get("bucket", cfg.max_src_len)
    if isinstance(bucket, bool) or not isinstance(bucket, int) or bucket < 1:
        return "done", bad_input("bucket must be a positive int")
    bucket = min(bucket, cfg.max_src_len)

    from agent_tpu.models.tokenizer import byte_encode_pad

    # One fixed padded length per batch (the controller's length bucket):
    # the prefill program and the engine's encoder block key on it.
    ids, lengths = byte_encode_pad(
        [r["text"] for r in reqs], buckets=(bucket,), max_len_cap=bucket,
        add_bos=True, add_eos=True,
    )
    limits = []
    for r in reqs:
        lim = r.get("max_length")
        if lim is None:
            lim = cfg.max_tgt_len
        if isinstance(lim, bool) or not isinstance(lim, int) or lim < 1:
            return "done", bad_input("max_length must be a positive int")
        limits.append(min(lim, cfg.max_tgt_len))
    state = {
        "t0": t0,
        "reqs": reqs,
        "ids": ids.astype(np.int32),
        "lengths": np.asarray(lengths, dtype=np.int32),
        "limits": limits,
        "bucket": int(ids.shape[1]),
        "model_id": model_id,
        "cfg": cfg,
        "num_beams": num_beams,
        "length_penalty": float(length_penalty),
        "early_stopping": early_stopping,
        "min_length": min_length,
        "t_staged": time.perf_counter(),
    }
    return "staged", state


def _params_key(model_id: str, cfg) -> str:
    """EXACTLY ``map_summarize``'s params-store key for the seq2seq family,
    so colocated serving + batch ops share one HBM weight copy."""
    from agent_tpu.ops._model_common import cfg_key

    return f"{model_id}#seq2seq#{hash(cfg_key(cfg)) & 0xFFFFFFFF:08x}"


def _get_params(runtime, model_id: str, cfg):
    from agent_tpu.ops._model_common import maybe_quantize_specs
    from agent_tpu.ops.map_summarize import _build_params
    from agent_tpu.parallel.shardings import seq2seq_param_specs

    specs = maybe_quantize_specs(seq2seq_param_specs(cfg), "seq2seq", cfg)
    return runtime.get_params(
        _params_key(model_id, cfg),
        lambda: _build_params(model_id, cfg, "seq2seq"),
        specs=specs,
    )


def _get_engine(runtime, params, state, serve):
    from agent_tpu.models import seq2seq
    from agent_tpu.models.decoding import ContinuousBatcher
    from agent_tpu.models.tokenizer import BOS_ID, EOS_ID, PAD_ID
    from agent_tpu.ops._model_common import cfg_key

    cfg = state["cfg"]
    slots = int(serve.decode_slots)
    micro_steps = int(serve.decode_micro_steps)
    paged = serve.kv_layout == "paged"
    key = (
        id(runtime), state["model_id"], cfg_key(cfg), state["bucket"],
        state["num_beams"], state["min_length"], state["length_penalty"],
        state["early_stopping"], slots, micro_steps,
        serve.kv_layout, serve.kv_block_size, serve.kv_pool_blocks,
    )
    engine = _ENGINES.get(key)
    if engine is None:
        if paged:
            cache_factory = seq2seq.make_paged_cache_factory(
                cfg, block_size=serve.kv_block_size,
                pool_blocks=serve.kv_pool_blocks,
            )
        else:
            cache_factory = seq2seq.make_cache_factory(cfg)
        engine = ContinuousBatcher(
            seq2seq.make_positional_step(cfg),
            cache_factory,
            params=params,
            slots=slots,
            vocab_size=cfg.vocab_size,
            max_tokens=cfg.max_tgt_len,
            enc_len=state["bucket"],
            d_model=cfg.d_model,
            start_id=BOS_ID, eos_id=EOS_ID, pad_id=PAD_ID,
            num_beams=state["num_beams"],
            min_length=state["min_length"],
            length_penalty=state["length_penalty"],
            early_stopping=state["early_stopping"],
            micro_steps=micro_steps,
        )
        _ENGINES[key] = engine
    return engine


def _prefill_rows(runtime, params, state, serve):
    """Prefill this batch: prefix-cache hits come back from host RAM, only
    the MISS rows run the compiled encoder. Returns
    ``(enc f32 [B, Ls, d_model], prefix delta dict)``.

    A hit row is the exact ``float32`` array the cold prefill produced
    when it populated the cache — bit-identical by construction. The miss
    rows compile per distinct miss count (like the batch dim already did);
    length buckets keep that key space small.
    """
    import jax

    ids, lengths = state["ids"], state["lengths"]
    B, Ls = ids.shape
    cfg, model_id = state["cfg"], state["model_id"]
    cache = _get_prefix_cache(serve)
    enc = np.zeros((B, Ls, cfg.d_model), dtype=np.float32)
    hit = np.zeros((B,), dtype=bool)
    keys: List[Optional[str]] = [None] * B
    if cache is not None:
        from agent_tpu.ops.prefix_cache import prefix_key

        version = _params_key(model_id, cfg)
        for i in range(B):
            keys[i] = prefix_key(version, ids[i])
            row = cache.get(keys[i])
            if row is not None:
                enc[i] = row
                hit[i] = True
    miss = np.nonzero(~hit)[0]
    ev0 = cache.evictions if cache is not None else 0
    t_pf0 = time.time()
    if miss.size:

        def build(Ls=Ls, n=int(miss.size)):
            import jax.numpy as jnp

            from agent_tpu.models import seq2seq

            def run_enc(p, i, nlen):
                mask = (
                    jnp.arange(Ls)[None, :] < nlen[:, None]
                ).astype(jnp.int32)
                out = seq2seq.encode(p, i.astype(jnp.int32), mask, cfg)
                # f32 handoff like summarize_mpmd: a bf16→f32 widening is
                # lossless and the engine re-casts to its compute dtype.
                return out.astype(jnp.float32)

            return jax.jit(run_enc)

        from agent_tpu.ops._model_common import cfg_key

        fn = runtime.compiled(
            ("serve_prefill", model_id, int(miss.size), Ls, cfg_key(cfg)),
            build,
        )
        got = np.asarray(
            fn(params, ids[miss], lengths[miss])
        )
        enc[miss] = got
        if cache is not None:
            for j, i in enumerate(miss):
                cache.put(keys[i], got[j])
    return enc, {
        "hits": int(hit.sum()),
        "misses": int(miss.size),
        "evictions": int(
            (cache.evictions - ev0) if cache is not None else 0
        ),
        # Per-row hit flags + the encoder-forward wall window (ISSUE 17):
        # the telemetry side channel — finalize pops them out of the
        # controller-visible prefix counters.
        "row_hits": hit.tolist(),
        "prefill_t0_wall": t_pf0,
        "prefill_t1_wall": time.time(),
    }


def serve_admit(state: Dict[str, Any], ctx: Optional[object] = None
                ) -> Dict[str, Any]:
    """Device phase, part 1 — prefill as its own batched step (prefix-cache
    hits skip it, ISSUE 16), then join the continuous engine (between
    decode iterations, never inside one). Returns the handle the runner
    pumps. Disaggregated decode jobs arrive with ``enc_rows`` already in
    the state (the serve_prefill agent's b1-wire handoff) and skip prefill
    entirely."""
    runtime = _runtime(ctx)
    cfg, model_id = state["cfg"], state["model_id"]
    params = _get_params(runtime, model_id, cfg)
    serve = _serve_knobs(ctx)
    engine = _get_engine(runtime, params, state, serve)
    if state.get("enc_rows") is not None:
        enc = np.asarray(state.pop("enc_rows"), dtype=np.float32)
        prefix = state.pop("prefix", None) or {
            "hits": 0, "misses": 0, "evictions": 0,
        }
    else:
        enc, prefix = _prefill_rows(runtime, params, state, serve)
    Ls = state["ids"].shape[1]
    masks = (
        np.arange(Ls)[None, :] < state["lengths"][:, None]
    ).astype(np.int32)
    t_admit = time.perf_counter()
    steps0, occ0 = engine.steps_run, engine.occupancy_sum
    tickets = []
    for i, r in enumerate(state["reqs"][: len(state["limits"])]):
        tickets.append(
            engine.admit(
                enc[i], masks[i], state["limits"][i],
                data={"req_id": r["req_id"],
                      "arrived_wall": r.get("arrived_wall")},
            )
        )
    return {
        "engine": engine,
        "tickets": tickets,
        "state": state,
        "prefix": prefix,
        "t_admit": t_admit,
        "steps0": steps0,
        "occ0": occ0,
        "device": runtime.platform,
    }


def serve_pump(handle: Dict[str, Any]) -> int:
    """One decode iteration of the handle's engine (finished sequences exit,
    backlog joins). Returns the live occupancy after the step."""
    engine = handle["engine"]
    engine.step()
    return engine.occupancy


def serve_done(handle: Dict[str, Any]) -> bool:
    return all(t.done_wall is not None for t in handle["tickets"])


def serve_collect(handle: Dict[str, Any]) -> Dict[str, Any]:
    """Handle → executed-state (the poster thread's finalize input)."""
    engine, state = handle["engine"], handle["state"]
    d_steps = max(1, engine.steps_run - handle["steps0"])
    d_occ = engine.occupancy_sum - handle["occ0"]
    return {
        "state": state,
        "tickets": handle["tickets"],
        "device": handle["device"],
        "occupancy": round(d_occ / d_steps, 3),
        "max_occupancy": engine.max_occupancy,
        "prefix": handle.get("prefix"),
        "kv_blocks_total": engine.kv_blocks_total,
        "kv_blocks_free": engine.kv_blocks_free,
        "t_admit": handle["t_admit"],
        "t_device": time.perf_counter(),
    }


def execute(state: Dict[str, Any], ctx: Optional[object] = None
            ) -> Dict[str, Any]:
    """Monolithic device phase: admit, pump this job's tickets to
    completion inline (the pipelined runner interleaves instead)."""
    handle = serve_admit(state, ctx)
    handle["engine"].run(handle["tickets"])
    return serve_collect(handle)


def finalize(executed: Dict[str, Any], ctx: Optional[object] = None
             ) -> Dict[str, Any]:
    """Host phase: detokenize each ticket's emitted tokens, shape the
    per-request fan-out entries the controller's front door expects."""
    from agent_tpu.models.tokenizer import ByteTokenizer

    state = executed["state"]
    tok = ByteTokenizer()
    prefix = dict(executed.get("prefix") or {
        "hits": 0, "misses": 0, "evictions": 0,
    })
    # Telemetry side channel riding the prefix dict (ISSUE 17): per-row
    # cache-hit flags + the prefill wall window — popped here so the
    # controller-facing prefix counters stay {hits, misses, evictions}.
    row_hits = prefix.pop("row_hits", None)
    pf_t0 = prefix.pop("prefill_t0_wall", None)
    pf_t1 = prefix.pop("prefill_t1_wall", None)
    path = "disagg" if state.get("op_name") == "serve_decode" \
        else "colocated"
    results: List[Dict[str, Any]] = []
    for i, ticket in enumerate(executed["tickets"]):
        row = ticket.tokens if ticket.tokens is not None else np.array([], int)
        results.append({
            "req_id": ticket.data["req_id"],
            "summary": tok.decode([t for t in row if t > 0]),
            "tokens": int(ticket.length),
            "steps": int(ticket.steps),
            "ttft_ms": _clamp_ttft(
                ticket.first_token_wall, ticket.data.get("arrived_wall")
            ),
            # Raw decomposition material for the controller's
            # serve_ttft_component_seconds / serve_tpot_seconds feeds and
            # the synthesized request-trace spans: lifecycle walls stamped
            # by the continuous engine + the prefill window above. Walls
            # on either side of a process boundary telescope — the
            # component sum equals first_token − arrival exactly.
            "telemetry": {
                "path": path,
                "prefill_t0_wall": pf_t0,
                "prefill_t1_wall": pf_t1,
                "admitted_wall": ticket.admitted_wall,
                "joined_wall": ticket.joined_wall,
                "first_token_wall": ticket.first_token_wall,
                "done_wall": ticket.done_wall,
                "kv_wait_ms": round(ticket.kv_wait_s * 1e3, 3),
                "join_step": int(ticket.join_step),
                "occupancy_at_join": int(ticket.occupancy_at_join),
                "cache_hit": bool(row_hits[i]) if (
                    isinstance(row_hits, list) and i < len(row_hits)
                ) else False,
                "steps": int(ticket.steps),
                "events": [
                    [name, wall] for name, wall in ticket.events
                ],
            },
        })
    if ctx is not None and hasattr(ctx, "tags"):
        ctx.tags.setdefault("timings", {}).update(
            stage_ms=round((state["t_staged"] - state["t0"]) * 1e3, 3),
            device_ms=round(
                (executed["t_device"] - executed["t_admit"]) * 1e3, 3
            ),
        )
    from agent_tpu.ops._model_common import stamp_rows

    stamp_rows(ctx, len(results))
    # A disaggregated decode job carries the PREFILL agent's counters
    # forward (so the controller's reap sees them on the one job it
    # watches) — but that agent already billed the cache hits; billing
    # again here would double-count the saved prefill.
    forwarded = bool(prefix.pop("forwarded", False))
    if prefix.get("hits") and not forwarded and ctx is not None \
            and hasattr(ctx, "tags"):
        from agent_tpu.obs.usage import stamp_usage

        # Saved prefill bills as cache hits — the showback line that says
        # what a tenant's repeated prefixes DIDN'T cost (ISSUE 16).
        stamp_usage(ctx.tags, cache_hit_rows=float(prefix["hits"]))
    return {
        "ok": True,
        "op": state.get("op_name", "serve_summarize"),
        "device": executed["device"],
        "model": state["model_id"],
        "num_beams": state["num_beams"],
        "n_requests": len(results),
        "results": results,
        "occupancy": executed["occupancy"],
        "max_occupancy": executed["max_occupancy"],
        "prefix_cache": prefix,
        "kv_blocks_total": executed.get("kv_blocks_total", 0),
        "kv_blocks_free": executed.get("kv_blocks_free", 0),
        "elapsed_ms": (time.perf_counter() - state["t0"]) * 1000.0,
    }


@register_op("serve_summarize")
def run_summarize(payload: Any, ctx: Optional[object] = None
                  ) -> Dict[str, Any]:
    """Classic monolithic entry: stage → execute → finalize inline."""
    phase, value = stage(payload, ctx)
    if phase == "done":
        return value
    return finalize(execute(value, ctx), ctx)


# Phase hooks for the pipelined drain, plus the serving hooks its
# continuous loop drives (agent_tpu.agent.pipeline).
run_summarize.stage = stage
run_summarize.execute = execute
run_summarize.finalize = finalize
run_summarize.serve_admit = serve_admit
run_summarize.serve_pump = serve_pump
run_summarize.serve_done = serve_done
run_summarize.serve_collect = serve_collect


# ---------------------------------------------------------------------------
# disaggregated prefill/decode pools (ISSUE 16)
# ---------------------------------------------------------------------------

@register_op("serve_prefill")
def run_prefill(payload: Any, ctx: Optional[object] = None) -> Dict[str, Any]:
    """Prefill half of the disaggregated pool split (``SERVE_DISAGG=1``):
    tokenize the batch and run the prefix-cached encoder forward, posting
    the encoded rows as this job's RESULT — binary (b1) columns to a
    negotiated controller, plain JSON floats otherwise. Both decode to the
    identical f32 rows (exact bit patterns on b1; exact float→double→float
    round trip on JSON, the ``summarize_mpmd`` argument), so the decode
    pool resumes bit-identically either way. The dep-gated ``serve_decode``
    job receives this result as its ``partials``."""
    phase, state = stage(payload, ctx)
    if phase == "done":
        return state
    runtime = _runtime(ctx)
    params = _get_params(runtime, state["model_id"], state["cfg"])
    serve = _serve_knobs(ctx)
    enc, prefix = _prefill_rows(runtime, params, state, serve)
    if ctx is not None and hasattr(ctx, "tags"):
        ctx.tags.setdefault("timings", {}).update(
            stage_ms=round((state["t_staged"] - state["t0"]) * 1e3, 3),
        )
        if prefix.get("hits"):
            from agent_tpu.obs.usage import stamp_usage

            # The prefill agent is where the saved work lives in disagg
            # mode, so cache hits bill HERE (the decode job forwards the
            # counters for metrics only — see finalize).
            stamp_usage(ctx.tags, cache_hit_rows=float(prefix["hits"]))
    out: Dict[str, Any] = {
        "ok": True,
        "op": "serve_prefill",
        "device": runtime.platform,
        "model": state["model_id"],
        "n_requests": len(state["reqs"]),
        "bucket": state["bucket"],
        "prefix_cache": prefix,
        "elapsed_ms": (time.perf_counter() - state["t0"]) * 1000.0,
    }
    tags = getattr(ctx, "tags", None) if ctx is not None else None
    if isinstance(tags, dict) and tags.get("wire") == "b1":
        from agent_tpu.data import wire

        return wire.attach_result_columns(out, {
            "enc_rows": np.ascontiguousarray(enc),
            "lengths": np.ascontiguousarray(state["lengths"]),
        })
    out["enc_rows"] = enc.tolist()
    out["lengths"] = state["lengths"].astype(int).tolist()
    return out


def _handoff_rows(
    payload: Dict[str, Any], state: Dict[str, Any]
) -> Tuple[np.ndarray, Dict[str, Any]]:
    """The serve_prefill result riding this decode job: ``encoded`` (one
    result object — tests, manual chains) or dep-gated ``partials`` (the
    controller's lease-time materialization). Returns the f32 encoded rows
    and the prefill agent's prefix-cache delta, marked ``forwarded`` so the
    decode side reports it without re-billing it."""
    if "encoded" in payload:
        sources: Any = [payload["encoded"]]
    elif "partials" in payload:
        sources = payload["partials"]
    else:
        raise ValueError(
            "serve_decode requires 'encoded' (one serve_prefill result) or "
            "dep-gated 'partials'"
        )
    if not isinstance(sources, list) or len(sources) != 1:
        raise ValueError(
            "serve_decode expects exactly one prefill result to resume from"
        )
    src = sources[0]
    if not (
        isinstance(src, dict) and src.get("ok") is True
        and src.get("op") == "serve_prefill"
    ):
        raise ValueError("handoff is not an ok serve_prefill result")
    enc = np.asarray(src.get("enc_rows"), dtype=np.float32)
    B, Ls = state["ids"].shape
    d_model = state["cfg"].d_model
    if enc.ndim != 3 or enc.shape != (B, Ls, d_model):
        raise ValueError(
            f"handoff enc_rows shape {enc.shape} does not match the batch "
            f"({B}, {Ls}, {d_model}) — prefill and decode saw different "
            f"payloads?"
        )
    prefix = dict(src.get("prefix_cache") or {})
    prefix["forwarded"] = True
    return enc, prefix


def _decode_stage(payload: Any, ctx: Optional[object] = None):
    """serve_decode's stage: the ordinary serving stage plus the prefill
    handoff — the encoded rows land in the state, so ``serve_admit`` skips
    the encoder entirely (the whole point of the split pool). The byte
    tokenizer is deterministic, so re-tokenizing the same texts here yields
    the very ids/lengths the prefill stage hashed and encoded."""
    phase, state = stage(payload, ctx)
    if phase == "done":
        return phase, state
    try:
        enc, prefix = _handoff_rows(payload, state)
    except ValueError as exc:
        return "done", bad_input(str(exc))
    state["enc_rows"] = enc
    state["prefix"] = prefix
    state["op_name"] = "serve_decode"
    return "staged", state


@register_op("serve_decode")
def run_decode(payload: Any, ctx: Optional[object] = None) -> Dict[str, Any]:
    """Decode half of the disaggregated pool split: resume from the
    serve_prefill result's encoded rows and run ONLY the continuous decode
    engine — bit-identical to the colocated serve_summarize path, because
    the engine is handed the very same f32 rows either way."""
    phase, value = _decode_stage(payload, ctx)
    if phase == "done":
        return value
    return finalize(execute(value, ctx), ctx)


run_decode.stage = _decode_stage
run_decode.execute = execute
run_decode.finalize = finalize
run_decode.serve_admit = serve_admit
run_decode.serve_pump = serve_pump
run_decode.serve_done = serve_done
run_decode.serve_collect = serve_collect
