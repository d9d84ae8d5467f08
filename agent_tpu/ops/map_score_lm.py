"""Score pre-tokenized documents with a base language model, in bulk.

A drain op like ``map_classify_tpu`` (``stage`` / ``execute`` / ``finalize``,
``run.deferred = True``): ``POST /v1/jobs`` with ``map_op: "map_score_lm"``
and CSV shard addressing, ``ids_field`` naming a column of space-separated
token ids; ``model_config`` and ``model_path`` as the other model ops take
them (weights from the model id). Full contract:
``map_score_lm.CONTRACT.md``. Result, columnar, one entry a document:

- ``n_tokens``: tokens scored (a longer row is cut at ``max_len``);
- ``logprob_sum``: sum over t = 1 .. L-1 of log p(token_t | tokens before
  t), natural log, over the whole vocabulary;
- ``block_logprob_sums``: the same sum by blocks of 1,024 PREDICTING
  positions (block j holds the targets t with (t - 1) // 1024 == j): what a
  filtering pipeline thresholds.

New to the op layer: a ROW LONGER THAN ONE PROGRAM. ``_model_common``
budgets a batch of short rows; here a document runs as fixed-shape SEGMENTS
(``SEGMENT_BUCKETS`` tokens, one document a program) and the mixer's state
goes from one segment program to the next as device arrays, never through
the host. What that state is depends on the mixer (``models/decoder_lm.py``):

- ``power_retention``: a FIXED-SIZE retention state a layer; nothing comes
  into a document's first segment (its first chunk is the quadratic form
  alone), so per segment bucket there is one program for a first segment
  and one for every later one;
- ``sparse_mla``: a CACHE that grows with position (a latent vector and an
  index key a token a layer), allocated on the device at the document's
  PADDED length (the sum of its segments: not ``max_len``), donated from
  program to program and written in place; every segment runs the one
  program that takes a state, keyed by that length, and the kernels bound
  their key loops by the segment's first position;
- ``hybrid_ssm``: TWO kinds of state a layer, side by side: a key and value
  CACHE that grows with position (allocated, donated and keyed as
  ``sparse_mla``'s is) and a FIXED-SIZE float32 scan state with the
  convolution's last three inputs; the empty ones are zeros, so every
  segment runs the one program that takes a state;
- ``dense_mla``: a CACHE of latents only (``sparse_mla``'s without the index
  keys), allocated, donated and keyed the same way; keys and values are
  expanded from it inside a segment program, a layer at a time, and never
  leave it;
- ``window_gqa``: a state in TWO SHAPES, by the layer's kind: a full
  layer's key and value cache at the document's padded length (allocated,
  donated and keyed as ``hybrid_ssm``'s), a window layer's LAST
  ``sliding_window`` keys and values only, whatever the document's length;
  such a model also counts the tiles its grouped expert matmul visits and
  the rows it computes;
- ``hybrid_kda``: two UNLIKE kinds of state by the layer's kind: a linear
  layer's FIXED-SIZE float32 state (``[heads, 128, 128]``) and its
  convolution's last three inputs, a latent layer's CACHE of latents at the
  document's padded length (``dense_mla``'s); the empty ones are zeros,
  allocated, donated and keyed as the other caches are;
- ``conv_gqa``: two kinds of state by the layer's kind, one of them next to
  nothing: a ``conv`` layer's TAIL, the last ``conv_taps - 1`` rows of its
  gated input (two rows of ``d_model`` float32, whatever the document's
  length), and an attention layer's key and value cache at the document's
  padded length, heads of 64 two a row of 128 lanes (allocated, donated and
  keyed as ``hybrid_ssm``'s).
  Where the model has
  expert layers the state also carries the count of (token, expert) pairs
  routed to the experts held here; it comes back with the block sums in the
  one fetch.

The loss head is a program of its own (``lm_loss_head``) that never holds
more than a [segment, 4,096] block of logits (the full [L, 151,936] float32
logits of a 32 k document would be 19.9 GB). Programs have fixed shapes.
There is no CPU retry: a device failure fails the shard, as
``allow_fallback: false`` does for the classify op.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from agent_tpu.obs import trace as obs_trace
from agent_tpu.ops import register_op
from agent_tpu.utils.errors import bad_input

OP = "map_score_lm"
FAMILY = "decoder_lm"
DEFAULT_MODEL_ID = "score-lm-default"
# Tokens a segment program. A document is whole 4,096-token segments and a
# last one in the smallest bucket that holds the rest. 4,096 x 5,120 keeps
# every matmul of the published widths MXU-bound (PERF.md §5, the ledger's
# `brumby-14b-base.score-long` lines since PR 27) and the
# MLP's [4096, 17408] intermediates at 143 MB ([4096, 21504] under
# `falcon-h1-34b`: 176 MB); one document a program.
SEGMENT_BUCKETS = (1024, 4096)


def _get_cfg(payload: Dict[str, Any]):
    from agent_tpu.models.decoder_lm import DecoderLMConfig, validate
    from agent_tpu.ops._model_common import apply_quant_env, config_from_payload

    cfg = apply_quant_env(payload, config_from_payload(payload, DecoderLMConfig))
    validate(cfg)
    return cfg


def _collect_documents(payload: Dict[str, Any], cfg) -> List[np.ndarray]:
    """Payload → one int32 id array a document: ``ids`` (a list of id lists)
    or CSV shard addressing with ``ids_field``. ValueError → soft
    ``bad_input``; shard I/O and integrity errors propagate so the shard
    fails and is retried."""
    from agent_tpu.data.csv_index import check_token_ids, read_shard_token_ids

    if "source_uri" in payload and "ids" not in payload:
        docs = read_shard_token_ids(payload, cfg.vocab_size)
    else:
        raw = payload.get("ids")
        if not isinstance(raw, list) or not raw:
            raise ValueError(
                "payload requires 'ids' (a list of token-id lists) or "
                "'source_uri' CSV shard addressing")
        docs = []
        for row in raw:
            if not isinstance(row, list) or not row or any(
                    isinstance(v, bool) or not isinstance(v, int) for v in row):
                raise ValueError(
                    "every document must be a non-empty list of ints")
            docs.append(check_token_ids(np.asarray(row, dtype=np.int64),
                                        cfg.vocab_size))
    return [d[: cfg.max_len] for d in docs]


def segment_plan(n_tokens: int) -> List[Tuple[int, int]]:
    """``(first token, bucket)`` of each segment of a document."""
    top = SEGMENT_BUCKETS[-1]
    plan, at = [], 0
    while n_tokens - at > top:
        plan.append((at, top))
        at += top
    rest = n_tokens - at
    plan.append((at, next(b for b in SEGMENT_BUCKETS if b >= rest)))
    return plan


def _stage_document(ids: np.ndarray) -> Dict[str, Any]:
    """Pad a document into its segments: ids, the NEXT token of every
    position, and how many positions of the segment have one."""
    n = len(ids)
    segments = []
    for at, bucket in segment_plan(n):
        seg = np.zeros((1, bucket), np.int32)
        nxt = np.zeros((1, bucket), np.int32)
        here = ids[at:at + bucket]
        seg[0, :len(here)] = here
        targets = ids[at + 1:at + bucket + 1]
        nxt[0, :len(targets)] = targets
        segments.append((seg, nxt, len(targets), at))
    return {"n_tokens": n, "segments": segments}


def stage(payload: Any, ctx: Optional[object] = None):
    """Host-only phase: validation, the shard read, the parse, padding into
    segments. ``("done", soft result)`` or ``("staged", state)``."""
    t0 = time.perf_counter()
    if not isinstance(payload, dict):
        return "done", bad_input("payload must be a dict")
    from agent_tpu.ops._model_common import resolve_model_id

    try:
        cfg = _get_cfg(payload)
        docs = _collect_documents(payload, cfg)
    except ValueError as exc:
        return "done", bad_input(str(exc))
    return "staged", {
        "t0": t0,
        "docs": [_stage_document(d) for d in docs],
        "n_rows": len(docs),
        "cfg": cfg,
        "model_id": resolve_model_id(payload, "TPU_LM_MODEL_PATH",
                                     DEFAULT_MODEL_ID),
        "t_staged": time.perf_counter(),
    }


def _build_params(runtime, model_id: str, cfg):
    from agent_tpu.models import decoder_lm
    from agent_tpu.ops._model_common import maybe_quantize_params

    params = decoder_lm.init_params(cfg, model_id,
                                    sharding=runtime.replicated())
    return maybe_quantize_params(params, FAMILY, cfg)


def _programs(runtime, cfg, bucket: int, cache_len: int):
    """(first-segment program, later-segment program, loss head) of one
    segment bucket; jit wrappers from the runtime's keyed cache.
    ``cache_len``: the document's padded length where the mixer's state is
    allocated at it (a shape of the later-segment program), else 0. The XLA
    module names (``jit_lm_segment``, ``jit_lm_loss_head``) are what a
    trace shows and what the benchmark's readers match."""
    import jax

    from agent_tpu.models import decoder_lm
    from agent_tpu.ops._model_common import cfg_key

    opts = {"pallas": bool(runtime.pallas), "interpret": False}

    def build_segment(carried: bool):
        def build():
            if carried:
                def lm_segment(p, ids, pos0, state):
                    return decoder_lm.forward_segment(
                        p, ids, pos0, state, cfg, **opts)

                # The incoming state's buffers become the outgoing state's.
                donate = (3,) if runtime.platform != "cpu" else ()
                return jax.jit(lm_segment, donate_argnums=donate)

            def lm_segment(p, ids, pos0):
                return decoder_lm.forward_segment(p, ids, pos0, None, cfg,
                                                  **opts)

            return jax.jit(lm_segment)

        return build

    def build_head():
        def lm_loss_head(hidden, head, targets, n_valid):
            return decoder_lm.segment_block_sums(hidden, head, targets,
                                                 n_valid)

        return jax.jit(lm_loss_head)

    key = (bucket, cfg_key(cfg))
    return (
        runtime.compiled((OP, "segment", False, *key), build_segment(False)),
        runtime.compiled((OP, "segment", True, cache_len, *key),
                         build_segment(True)),
        runtime.compiled((OP, "loss_head", *key), build_head),
    )


def _empty_state(runtime, cfg, cache_len: int):
    """The state before a document's first segment, made on the device
    (``None``: the mixer starts from nothing)."""
    import jax

    from agent_tpu.models import decoder_lm
    from agent_tpu.ops._model_common import cfg_key

    if decoder_lm.starts_from_nothing(cfg):
        return None

    def build():
        def lm_state():
            return decoder_lm.init_state(cfg, 1, cache_len)

        return jax.jit(lm_state, out_shardings=runtime.replicated())

    return runtime.compiled((OP, "state", cache_len, cfg_key(cfg)), build)()


def _record_retention(state: Dict[str, Any]) -> None:
    """Real tokens whose chunk read a carried state, and those whose chunk
    was the quadratic form alone (a document's first chunk)."""
    from agent_tpu.kernels.power_retention import retention_chunk

    carried = alone = 0
    for doc in state["docs"]:
        first = doc["segments"][0]
        quadratic = min(doc["n_tokens"], retention_chunk(first[0].shape[1]))
        alone += quadratic
        carried += doc["n_tokens"] - quadratic
    obs_trace.record_retention_tokens("state", carried)
    obs_trace.record_retention_tokens("quadratic", alone)


def _record_sparse_keys(state: Dict[str, Any]) -> None:
    """Keys the shard's real tokens attend under the selection, and the
    causal keys they could: token t of a document sees ``t + 1`` keys and
    keeps ``min(t + 1, index_topk)`` (a layer; from lengths alone)."""
    topk = int(state["cfg"].index_topk)
    selected = causal = 0
    for doc in state["docs"]:
        n, k = doc["n_tokens"], min(doc["n_tokens"], topk)
        causal += n * (n + 1) // 2
        selected += k * (k + 1) // 2 + (n - k) * topk
    obs_trace.record_sparse_attention_keys("selected", selected)
    obs_trace.record_sparse_attention_keys("causal", causal)


def _record_hybrid(state: Dict[str, Any]) -> None:
    """Of a shard's real tokens, those whose scan chunk read a carried state
    and those in a document's first chunk; and, a query head a layer, the
    causal (query, key) pairs they need (``t + 1`` for token ``t``) beside
    the pairs in the key tiles the attention kernel's grid visits for the
    segments they ran as."""
    from agent_tpu.kernels.causal_attention import visited_pairs

    chunk = int(state["cfg"].ssm_chunk)
    first = carried = causal = computed = 0
    for doc in state["docs"]:
        n = doc["n_tokens"]
        first += min(n, chunk)
        carried += n - min(n, chunk)
        causal += n * (n + 1) // 2
        computed += sum(visited_pairs(ids.shape[1], pos0)
                        for ids, _, _, pos0 in doc["segments"])
    obs_trace.record_ssm_tokens("state", carried)
    obs_trace.record_ssm_tokens("first_chunk", first)
    obs_trace.record_causal_attention_pairs("causal", causal)
    obs_trace.record_causal_attention_pairs("computed", computed)


def _record_dense_latent(state: Dict[str, Any]) -> None:
    """A query head a layer, the causal pairs a shard's real tokens need
    beside those in the key tiles the attention kernel's grid visits at ONE
    query head a key head; and, a layer, the cached latents its segment
    programs expand (every segment expands all it can see: ``pos0 +
    bucket``) beside the real tokens whose latents the cache holds."""
    from agent_tpu.kernels.causal_attention import query_tile, visited_pairs

    cached = expanded = causal = computed = 0
    for doc in state["docs"]:
        n = doc["n_tokens"]
        cached += n
        causal += n * (n + 1) // 2
        for ids, _, _, pos0 in doc["segments"]:
            bucket = ids.shape[1]
            expanded += pos0 + bucket
            computed += visited_pairs(bucket, pos0, query_tile(1, bucket))
    obs_trace.record_causal_attention_pairs("causal", causal)
    obs_trace.record_causal_attention_pairs("computed", computed)
    obs_trace.record_latent_keys("expanded", expanded)
    obs_trace.record_latent_keys("cached", cached)


def _full_layer_pairs(state: Dict[str, Any]) -> Tuple[int, int]:
    """A query head a layer that attends every causal key through the layers'
    stacked cache (``window_gqa``'s full kind, which ``conv_gqa`` runs too):
    the causal pairs a shard's real tokens need, and those in the key tiles
    the attention kernel's grid visits at the query tile the stacked heads of
    a cache row take (the query heads of one key-value head, or of the PAIR
    that heads of half a lane tile lie as)."""
    from agent_tpu.kernels.causal_attention import (
        heads_a_row, query_tile, visited_pairs)

    cfg = state["cfg"]
    stacked = cfg.n_heads // cfg.n_kv_heads * heads_a_row(cfg.n_kv_heads,
                                                         cfg.d_head)
    causal = computed = 0
    for doc in state["docs"]:
        causal += doc["n_tokens"] * (doc["n_tokens"] + 1) // 2
        computed += sum(
            visited_pairs(ids.shape[1], pos0, query_tile(stacked, ids.shape[1]))
            for ids, _, _, pos0 in doc["segments"])
    return causal, computed


def _record_window_gqa(state: Dict[str, Any]) -> None:
    """A query head a layer of each kind: on a full layer
    :func:`_full_layer_pairs`; on a window layer the pairs inside their
    windows (``min(t + 1, sliding_window)`` for token ``t``) beside those in
    the tiles the window kernel's grid visits."""
    from agent_tpu.kernels.causal_attention import (
        query_tile, window_visited_pairs)

    cfg = state["cfg"]
    w, groups = int(cfg.sliding_window), cfg.n_heads // cfg.n_kv_heads
    in_window = window_computed = 0
    for doc in state["docs"]:
        n, ramp = doc["n_tokens"], min(doc["n_tokens"], w)
        in_window += ramp * (ramp + 1) // 2 + (n - ramp) * w
        window_computed += sum(
            window_visited_pairs(ids.shape[1], pos0, w,
                                 query_tile(groups, ids.shape[1]))
            for ids, _, _, pos0 in doc["segments"])
    causal, computed = _full_layer_pairs(state)
    obs_trace.record_causal_attention_pairs("causal", causal)
    obs_trace.record_causal_attention_pairs("computed", computed)
    obs_trace.record_window_attention_pairs("window", in_window)
    obs_trace.record_window_attention_pairs("computed", window_computed)


def _record_hybrid_kda(state: Dict[str, Any]) -> None:
    """Of a shard's real tokens, those whose delta-rule chunk read a carried
    state and those in a document's first chunk, and the chunks the kernel
    walks for the segments they ran as (a head a linear layer); and what
    ``dense_mla`` counts of its latent layer: the causal pairs and the cached
    latents expanded (:func:`_record_dense_latent`)."""
    from agent_tpu.kernels.kda import CHUNK, chunks_of

    first = carried = chunks = 0
    for doc in state["docs"]:
        n = doc["n_tokens"]
        first += min(n, CHUNK)
        carried += n - min(n, CHUNK)
        chunks += sum(chunks_of(ids.shape[1])
                      for ids, _, _, _ in doc["segments"])
    obs_trace.record_kda_tokens("state", carried)
    obs_trace.record_kda_tokens("first_chunk", first)
    obs_trace.record_kda_chunks(chunks)
    _record_dense_latent(state)


def _record_conv_gqa(state: Dict[str, Any]) -> None:
    """Of a shard's real tokens, those whose ``conv`` layers read a carried
    tail (every segment program but a document's first starts from the rows
    the one before handed on) and those of a document's first segment, whose
    tail is the zeros before the document; and what its attention layers
    need and visit (:func:`_full_layer_pairs`)."""
    first = carried = 0
    for doc in state["docs"]:
        head = min(doc["n_tokens"], doc["segments"][0][0].shape[1])
        first += head
        carried += doc["n_tokens"] - head
    causal, computed = _full_layer_pairs(state)
    obs_trace.record_conv_tail_tokens("carried", carried)
    obs_trace.record_conv_tail_tokens("first_segment", first)
    obs_trace.record_causal_attention_pairs("causal", causal)
    obs_trace.record_causal_attention_pairs("computed", computed)


# mixer → what the op counts of a shard at dispatch, from its lengths.
_MIXER_COUNTERS = {"power_retention": _record_retention,
                   "sparse_mla": _record_sparse_keys,
                   "hybrid_ssm": _record_hybrid,
                   "dense_mla": _record_dense_latent,
                   "window_gqa": _record_window_gqa,
                   "hybrid_kda": _record_hybrid_kda,
                   "conv_gqa": _record_conv_gqa}


def execute(state: Dict[str, Any], ctx: Optional[object] = None) -> Dict[str, Any]:
    """Device phase (owning thread only): every segment of every document is
    dispatched, the state going from program to program on the device; the
    block sums stay unfetched for :func:`finalize`."""
    import jax
    import jax.numpy as jnp

    from agent_tpu.models.decoder_lm import segment_flops
    from agent_tpu.ops._model_common import cfg_key, stamp_device_flops

    state["t_exec0"] = time.perf_counter()
    cfg, model_id = state["cfg"], state["model_id"]
    if ctx is not None and getattr(ctx, "require_runtime", None):
        runtime = ctx.require_runtime()
    else:
        from agent_tpu.runtime.runtime import get_runtime

        runtime = get_runtime()
    params = runtime.get_params(
        f"{model_id}#{FAMILY}#{hash(cfg_key(cfg)) & 0xFFFFFFFF:08x}",
        lambda: _build_params(runtime, model_id, cfg),
    )
    put = lambda a: jax.device_put(a, runtime.replicated())  # noqa: E731
    programs: Dict[Tuple[int, int], Tuple] = {}   # (bucket, cache) -> programs
    parts, routed, tiles, rows, layout = [], [], [], [], []
    flops = 0.0
    for doc in state["docs"]:
        padded = sum(seg[0].shape[1] for seg in doc["segments"])
        carried = _empty_state(runtime, cfg, padded)
        cache_len = padded if carried is not None else 0
        for ids, targets, n_valid, pos0 in doc["segments"]:
            bucket = ids.shape[1]
            if (bucket, cache_len) not in programs:
                programs[bucket, cache_len] = _programs(runtime, cfg, bucket,
                                                        cache_len)
            first, later, head = programs[bucket, cache_len]
            pos = put(np.int32(pos0))
            if carried is None:
                hidden, carried = first(params, put(ids), pos)
            else:
                hidden, carried = later(params, put(ids), pos, carried)
            parts.append(head(hidden, params["head"], put(targets),
                              put(np.int32(n_valid))))
            flops += segment_flops(cfg, bucket, pos0)
        if cfg.n_experts:
            routed.append(carried["pairs"].reshape(1))
            if "tiles" in carried:
                tiles.append(carried["tiles"]["visited"].reshape(1))
                rows.append(carried["tiles"]["rows"].reshape(1))
        layout.append((padded, doc["n_tokens"]))
    dispatched = sum(padded for padded, _ in layout)
    obs_trace.record_lm_segments(
        OP, sum(len(doc["segments"]) for doc in state["docs"]))
    _MIXER_COUNTERS[cfg.mixer](state)
    stamp_device_flops(
        ctx, flops,
        f"B1xS{max(s[0].shape[1] for d in state['docs'] for s in d['segments'])}")
    parts += routed + tiles + rows
    state.update(
        # One array a shard, gathered on the device by the owner thread:
        # one fetch, not one a segment. Behind the block sums, where the
        # model routes: a document's (token, expert) pairs held here, then
        # (a model that counts them) the tiles its grouped matmul visited
        # and then the rows it computed.
        pending_dev=jnp.concatenate(parts) if len(parts) > 1 else parts[0],
        layout=layout, device=runtime.platform, n_routed=len(routed),
        n_tiles=len(tiles),
        moe_tokens=dispatched * sum(
            n for _, kind, _, n in cfg.layer_groups if kind == "experts"),
        t_device=time.perf_counter(),
    )
    return state


def finalize(state: Dict[str, Any], ctx: Optional[object] = None) -> Dict[str, Any]:
    """Host phase: the deferred fetch (a READ of device arrays: safe on the
    poster thread) and the result's shape."""
    from agent_tpu.models.decoder_lm import LOSS_BLOCK
    from agent_tpu.ops._model_common import stamp_rows

    t0 = state["t0"]
    with obs_trace.phase("fetch") as fetched:
        sums = np.asarray(state["pending_dev"], dtype=np.float64)
    state["t_ready"] = fetched.t1
    if state["n_tiles"]:
        n = state["n_tiles"]
        obs_trace.record_moe_tiles(float(sums[-2 * n:-n].sum()),
                                   float(sums[-n:].sum()))
        sums = sums[:-2 * n]
    if state["n_routed"]:
        obs_trace.record_moe_routing(float(sums[-state["n_routed"]:].sum()),
                                     state["moe_tokens"])
    if ctx is not None and hasattr(ctx, "tags"):
        ctx.tags.setdefault("timings", {}).update(
            stage_ms=round((state["t_staged"] - t0) * 1000.0, 3),
            queue_ms=round((state["t_exec0"] - state["t_staged"]) * 1000.0, 3),
            device_ms=round((state["t_device"] - state["t_exec0"]) * 1000.0, 3),
            fetch_ms=round(fetched.seconds * 1000.0, 3),
        )
    stamp_rows(ctx, state["n_rows"])
    n_tokens, totals, blocks, at = [], [], [], 0
    for padded, n in state["layout"]:
        real = -(-max(0, n - 1) // LOSS_BLOCK)
        mine = sums[at:at + real]
        at += padded // LOSS_BLOCK
        n_tokens.append(int(n))
        blocks.append([float(x) for x in mine])
        totals.append(float(mine.sum()))
    out: Dict[str, Any] = {
        "ok": True,
        "op": OP,
        "model_path": state["model_id"],
        "device": state["device"],
        "n_rows": state["n_rows"],
        "elapsed_ms": (time.perf_counter() - t0) * 1000.0,
    }
    out["n_tokens"] = n_tokens
    out["logprob_sum"] = totals
    out["block_logprob_sums"] = blocks
    return out


@register_op(OP)
def run(payload: Any, ctx: Optional[object] = None) -> Dict[str, Any]:
    """Monolithic entry: stage → execute → finalize inline."""
    phase, value = stage(payload, ctx)
    if phase == "done":
        return value
    return finalize(execute(value, ctx), ctx)


run.stage = stage
run.execute = execute
run.finalize = finalize
# execute returns with the device still working; finalize stamps ``t_ready``.
run.deferred = True
