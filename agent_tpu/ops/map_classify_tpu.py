"""Classification on the TPU mesh — successor of the reference's Edge-TPU op.

Capability parity with reference ``ops/map_classify_tpu.py:31-90`` +
``CONTRACT.md:1-27`` (full contract: ``map_classify_tpu.CONTRACT.md`` here):

- Payload: required input (``input`` flat numeric list — now token ids — or the
  batched upgrades ``text``/``texts``), optional ``model_path``, ``topk``
  (default 5), ``allow_fallback`` (default True).
- Result: ``{op, model_path, topk: [{index, score}], elapsed_ms}`` (ref
  ``:76-82``); degraded shape ``{fallback: "cpu", reason, topk: []}`` on
  failure with ``allow_fallback`` (ref ``:22-28, 84-90``).
- Input-size validation errors raise (→ structured ``failed`` result at the
  agent) unless fallback is allowed, matching ref ``:58-69``.

The TPU-native inversion: instead of one ``interpreter.invoke()`` per row, rows
batch into bucketed static shapes (``pad_batch``), the batch dim shards over
the mesh ``dp`` axis, and a jit-compiled executable is cached per
(model, batch-bucket, length-bucket) — reference handle-singleton semantics
(``ops/_tpu_runtime.py:34-63``) generalized to a compiled-op cache.

The op is **phase-split** for the pipelined drain (BASELINE.json "host-side
double buffering"): :func:`stage` (pure host — payload validation, CSV shard
read, fused tokenize+pad), :func:`execute` (device — params, compiled
dispatch; with ``allow_fallback`` also the result fetch), :func:`finalize`
(host — result shaping; in the no-fallback drain mode it also pays the
deferred device→host fetch, which is a thread-safe READ of device arrays).
``run`` composes all three, so monolithic callers see the classic contract;
the agent's pipeline runs stage/finalize on worker threads and keeps every
device *dispatch* in ``execute`` on the owning thread (single-owner
invariant, SURVEY.md §5.2 — ownership governs dispatch/mesh mutation, not
reads of results).

Degraded mode is *better* than the reference's: the reference's fallback never
computes (empty topk, ``CONTRACT.md:26`` "fallback handled elsewhere"); ours
retries the identical JAX program on the CPU backend and only returns the empty
shape if that fails too — same program, different backend (SURVEY.md §7).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from agent_tpu.obs import trace as obs_trace
from agent_tpu.ops import register_op
from agent_tpu.utils.errors import bad_input

DEFAULT_TOPK = 5
DEFAULT_MODEL_ID = "classify-default"

# Lazy module state: config + CPU fallback runtime, built on first use so the
# op module imports cleanly on hosts without a working jax backend.
_cpu_runtime = None


def _get_cfg(payload: Dict[str, Any]):
    from agent_tpu.models.encoder import EncoderConfig
    from agent_tpu.ops._model_common import config_from_payload

    return config_from_payload(payload, EncoderConfig)


def _resolve_family(model_id: str) -> str:
    """``model_path`` pointing at a local HF checkpoint directory serves the
    pretrained-BERT family; anything else is the in-house encoder (model id
    or ``.npz`` artifact). The pretrained serving story of the reference
    (``ops/_tpu_runtime.py:23-31``), TPU-native."""
    from agent_tpu.models import bert

    return "bert" if bert.is_hf_dir(model_id) else "encoder"


# The only model_config fields a payload may override for a checkpoint
# model: serving controls. Structural fields (num_layers, hidden_size, ...)
# are the checkpoint's — an override there would desync the staged config
# from the actual weights.
_BERT_SERVING_OVERRIDES = ("dtype", "num_labels", "quant")


def _get_bert_cfg(model_id: str, payload: Dict[str, Any]):
    """BertConfig from the checkpoint's config.json; payload ``model_config``
    may override only the serving controls (``_BERT_SERVING_OVERRIDES``:
    ``dtype``, ``num_labels``, ``quant``)."""
    import os as _os

    from agent_tpu.models.bert import BertConfig

    overrides = payload.get("model_config")
    allowed = {}
    if isinstance(overrides, dict):
        allowed = {
            k: v for k, v in overrides.items()
            if k in _BERT_SERVING_OVERRIDES
        }
    return BertConfig.from_hf_json(
        _os.path.join(model_id, "config.json"), **allowed
    )


def _resolve_model_id(payload: Dict[str, Any]) -> str:
    from agent_tpu.ops._model_common import resolve_model_id

    return resolve_model_id(payload, "TPU_MODEL_PATH", DEFAULT_MODEL_ID)


def _build_params(model_id: str, cfg, family: str = "encoder", tp: int = 1):
    """A model's weights as the agent holds them: the canonical tree of its
    family, then the build-time serving transforms (quantized tables; the
    fused Q, K, V leaf where a mesh whose ``tp`` axis is ``tp`` allows)."""
    import os

    if family == "bert":
        from agent_tpu.models import bert

        # Same overrides as the staged cfg so the head matches num_labels.
        _, params = bert.load_hf_dir(
            model_id, dtype=cfg.dtype, num_labels=cfg.num_labels
        )
    else:
        from agent_tpu.models import encoder

        if model_id.endswith(".npz") and os.path.exists(model_id):
            params = encoder.load_npz(model_id, cfg)
        else:
            params = encoder.init_params(cfg, model_id=model_id)
    from agent_tpu.ops._model_common import (
        maybe_fuse_qkv_params,
        maybe_quantize_params,
    )

    return maybe_fuse_qkv_params(
        maybe_quantize_params(params, family, cfg), family, cfg, tp)


def _collect_sequences(payload: Dict[str, Any], cfg) -> Tuple[List, str, bool]:
    """Payload → (items, kind, was_single_input); kind is ``"ids"`` (items =
    token-id lists) or ``"texts"`` (raw strings — tokenization fuses with
    padding on the hot path, ``byte_encode_pad``).

    Accepts, in precedence order: ``input`` (flat token ids, reference
    contract), ``text``/``texts``, or CSV shard addressing (``source_uri`` +
    ``start_row``/``shard_size`` + optional ``text_field``) — the last makes
    a classify task *itself* shard-addressable, so the controller's
    ``submit_csv_job(map_op="map_classify_tpu")`` drains a dataset without a
    separate read stage (BASELINE.json 10M-row drain shape).
    """
    if "input" in payload:
        raw = payload["input"]
        if not isinstance(raw, list) or not raw:
            raise ValueError("input must be a non-empty flat list of ints")
        ids = []
        for v in raw:
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise ValueError("input values must be numeric")
            iv = int(v)
            if not 0 <= iv < cfg.vocab_size:
                # Validate-and-reject like the reference's size/shape checks
                # (ref ops/map_classify_tpu.py:58-69) — silently wrapping
                # out-of-range ids would hide caller bugs.
                raise ValueError(
                    f"input id {iv} out of range [0, {cfg.vocab_size})"
                )
            ids.append(iv)
        return [ids[: cfg.max_len]], "ids", True
    texts = payload.get("texts")
    single = False
    if texts is None and "text" in payload:
        texts = [payload["text"]]
        single = True  # single iff the row came from 'text'; 'texts' wins
    if texts is None and "source_uri" in payload:
        from agent_tpu.data.csv_index import read_shard_texts

        # Shared drain-mode contract (also map_summarize's): ValueError →
        # soft bad_input; RuntimeError/OSError propagate so the shard FAILS
        # and the controller retries instead of silently dropping its rows.
        texts = read_shard_texts(payload)
    if texts is not None:
        if not isinstance(texts, list) or not texts or not all(
            isinstance(t, str) for t in texts
        ):
            raise ValueError("texts must be a non-empty list of strings")
        return texts, "texts", single
    raise ValueError(
        "payload requires 'input' (token ids), 'text'/'texts', or "
        "'source_uri' CSV shard addressing"
    )


MAX_BATCH = 8192


def _model_module(family: str):
    """The module whose ``forward`` serves ``family``."""
    if family == "bert":
        from agent_tpu.models import bert

        return bert
    from agent_tpu.models import encoder

    return encoder


def _takes_packed_rows(cfg, family: str, rt) -> bool:
    """May this program's short rows share a program row? By what the code
    can observe of the program that will run, never by a model's name: its
    ``forward`` has the segment form (``models/encoder.py``; the pretrained
    BERT family, with learned positions, token types and its own pooling,
    has not); no expert layer (capacity is counted in token slots, so fewer
    slots change which tokens an expert drops: a different result); no
    pipeline schedule (``encoder_forward_pp`` repeats the embedding and the
    pooling) and no ``sp`` ring (it takes key-padding masks alone). Which
    chunks of such a program ARE packed is staging's own predicate,
    ``_model_common.pack_padded_chunk``."""
    import inspect

    forward = _model_module(family).forward
    if "segment_lengths" not in inspect.signature(forward).parameters:
        return False
    if getattr(cfg, "moe_experts", 0) > 0 or getattr(cfg, "pp", 1) > 1:
        return False
    return rt is None or (rt.axis_size("pp") <= 1 and rt.axis_size("sp") <= 1)


def _stage_chunks(dp: int, items: List, kind: str, cfg,
                  family: str = "encoder", model_id: str = "",
                  pack: bool = False) -> List[Tuple]:
    """Pure host: tokenize+pad ``items`` into device-ready
    ``[(ids[B, L] wire-dtype, lengths[B] int32, n_real_rows), ...]``; with
    ``pack`` a chunk of short text rows comes back as a
    ``_model_common.PackedChunk`` instead.

    Text rows go through the shared fused tokenize+pad hot path
    (``_model_common.stage_text_chunks`` — wire format documented there) for
    the byte-vocab encoder family, or the checkpoint's wordpiece vocab for
    the BERT family; pre-tokenized ``input`` rows (v0 contract) pad here.
    """
    from agent_tpu.models.tokenizer import pad_batch
    from agent_tpu.ops._model_common import (
        batch_buckets,
        iter_chunks,
        length_buckets_for,
        stage_text_chunks,
    )

    if kind == "texts":
        encode_pad = None
        if family == "bert":
            from agent_tpu.models import bert

            tok = bert.hf_wordpiece(model_id)

            def encode_pad(chunk, lb, bb):
                return bert.encode_pad_batch(tok, chunk, cfg.max_len, bb, lb)

        return stage_text_chunks(
            dp, items, max_len=cfg.max_len, vocab_size=cfg.vocab_size,
            max_batch=MAX_BATCH, encode_pad=encode_pad,
            split_for_dispatch=True, pack_short_rows=pack,
        )
    # Length buckets must not exceed the position table (max_len).
    buckets = length_buckets_for(cfg.max_len)
    bbuckets = batch_buckets(dp, MAX_BATCH)
    wire_dtype = np.uint16 if cfg.vocab_size <= (1 << 16) else np.int32
    chunks: List[Tuple] = []
    from agent_tpu.ops._model_common import split_padded_chunk

    for chunk in iter_chunks(items, bbuckets[-1]):
        ids, _ = pad_batch(chunk, buckets=buckets, batch_buckets=bbuckets)
        B, L = ids.shape
        lengths = np.zeros(B, dtype=np.int32)
        lengths[: len(chunk)] = [min(len(s), L) for s in chunk]
        chunks.extend(
            split_padded_chunk(ids.astype(wire_dtype), lengths, len(chunk), dp)
        )
    return chunks


def _execute_chunks(
    runtime, chunks: List[Tuple], model_id: str, cfg, k: int,
    family: str = "encoder", fetch: bool = True,
):
    """Device phase: classify staged chunks.

    ``fetch=True`` → (topk values [N, k] numpy, indices numpy), synced here.
    ``fetch=False`` → the pending device arrays, unfetched — one
    ``(vals_dev, idx_dev, n)`` entry, or ``("cat", vals_dev, idx_dev,
    layout)`` when several dispatch chunks were gathered on device: the pipelined drain's finalize (poster thread) syncs
    them instead, so the device thread can dispatch the NEXT shard while
    this one's device→host round trip is in flight (reading a jax.Array is
    thread-safe; only dispatch is owner-bound).

    Top-k runs on device, fused into the forward executable: the host fetches
    k probabilities per row, not [B, n_classes] logits (5 of 1,000 a row in
    the benchmark's cells). Chunks dispatch asynchronously
    and are fetched after the loop, so host staging of chunk i+1 overlaps
    device compute of chunk i even without the pipeline.
    """
    import jax
    import jax.numpy as jnp

    from agent_tpu.models import encoder, tokenizer
    from agent_tpu.ops._model_common import cfg_key
    from agent_tpu.parallel.shardings import bert_param_specs, encoder_param_specs

    model_mod = _model_module(family)
    specs = (bert_param_specs if family == "bert"
             else encoder_param_specs)(cfg)
    from agent_tpu.ops._model_common import (
        PackedChunk,
        maybe_fuse_qkv_specs,
        maybe_quantize_specs,
    )

    tp = runtime.axis_size("tp")
    specs = maybe_fuse_qkv_specs(
        maybe_quantize_specs(specs, family, cfg), family, cfg, tp)

    # On a tp>1 mesh the weights land sharded (Megatron-style specs) and XLA
    # inserts the tp collectives in the forward — the serving path for models
    # that exceed one chip's HBM, not just the train path.
    params = runtime.get_params(
        f"{model_id}#{family}#{hash(cfg_key(cfg)) & 0xFFFFFFFF:08x}",
        lambda: _build_params(model_id, cfg, family, tp),
        specs=specs,
    )
    attn_fn = runtime.attention_fn()  # ring over sp when the mesh has one

    # Pipeline-parallel routing (SURVEY §2.8 "strategies usable by the
    # workload"): a pp axis on the serving mesh, or model_config {"pp": N},
    # sends the encoder's block stack through the GPipe shard_map schedule.
    # With a derived mesh (same devices, dp×pp layout) XLA reshards the
    # dp-placed inputs at the jit boundary; workers that serve pp-heavy
    # models full-time should put the pp axis in MESH_SHAPE instead.
    pp_mesh = None
    if family == "encoder":
        if runtime.axis_size("pp") > 1:
            pp_mesh = runtime.mesh
        elif getattr(cfg, "pp", 1) > 1:
            from agent_tpu.runtime.mesh import build_mesh

            pp = cfg.pp
            n_dev = runtime.n_devices
            if n_dev % pp != 0:
                raise ValueError(
                    f"pp={pp} does not divide the {n_dev}-device mesh"
                )
            pp_mesh = build_mesh(
                runtime.devices, {"dp": n_dev // pp, "pp": pp}
            )
    if pp_mesh is not None:
        from agent_tpu.parallel.pipeline import encoder_forward_pp

        # Inside the pp shard_map the per-stage attention must be a plain
        # per-shard function (a nested mesh wrapper would shard_map twice):
        # the bare flash kernel, compiled, on TPU; dense elsewhere.
        if runtime.pallas:
            import functools

            from agent_tpu.kernels.flash_attention import flash_attention

            pp_attn = functools.partial(flash_attention, interpret=False)
        else:
            from agent_tpu.models.layers import (
                dot_product_attention as pp_attn,
            )

    # The three programs below take the weights as ARGUMENTS, so each is
    # filed under what its traced function closes over and nothing else: the
    # family, the shapes, ``k`` where top-k is fused in, and ``cfg_key(cfg)``
    # (which tells quantized from plain, ``pp`` and the rest of a config
    # apart). The segment slots of a program row follow from ``L``; the
    # attention function and the mesh are the runtime's, as the cache is.
    # Never a model id: one wrapper, one trace and one executable for every
    # model of a config, and a tenant whose parameter TREE differs (another
    # dtype, other leaves) retraces under the shared wrapper by ``jax.jit``'s
    # own cache and is still answered by its own weights.

    def rebuild_ids(i, real):
        ids = i.astype(jnp.int32)
        if i.dtype == jnp.uint8:
            # Raw-byte wire (stage_text_chunks): unshifted bytes on the
            # wire, ids rebuilt on device. Trace-time branch — jit
            # specializes per input dtype, so the uint16/int32 wires trace
            # without it.
            ids = (ids + tokenizer.N_SPECIAL) * real
        return ids

    def pack_result(logits):
        vals, idx = encoder.topk_probs(logits, k)
        # One fused [B, k, 2] int32 result: a device→host read costs a full
        # round trip regardless of size (its cost is not measured on a
        # directly attached chip), so vals+idx fetch as ONE array. The
        # SCORES ride as their exact float32 bit patterns in an integer
        # array, not the indices in a float one: a small index is a
        # denormal float, and the chip flushes denormals to zero wherever
        # the packing fuses with float arithmetic (seen on v5e: every index
        # of a 1-row batch came back 0). Integer lanes are never flushed.
        return jnp.stack(
            [jax.lax.bitcast_convert_type(vals, jnp.int32), idx], axis=-1,
        )

    def dispatch_packed(chunk):
        """A packed chunk: its slices through ONE slice program, each to the
        mean of every segment slot; then one head program gathers the
        chunk's rows out of the slots, in the chunk's own order, and runs
        head and top-k on those. Both take what a chunk of these shapes can
        have at most, whatever this chunk has: the slice program the
        chunk's ids and lengths as ``most`` slices (two transfers a chunk,
        the missing slices empty) and the index of the one to run, the head
        program ``most`` slot arrays (the first stands in for the rest; no
        row reads them). Every packed chunk of a shape runs the same two
        executables."""
        rows, L = chunk.slice_rows, chunk.ids.shape[1]
        B, G = len(chunk.row_slots), chunk.segment_lengths.shape[1]
        most = B // rows - 1    # pack_padded_chunk packs under B rows only

        def build_slice():
            def run_fwd(p, ids_all, seg_all, s):
                i, seg = (jax.lax.dynamic_index_in_dim(a, s, keepdims=False)
                          for a in (ids_all, seg_all))
                real = (jnp.arange(L)[None, :]
                        < seg.sum(axis=1, keepdims=True)).astype(jnp.int32)
                return model_mod.pooled_segments(
                    p, rebuild_ids(i, real), seg, cfg, attn_fn=attn_fn,
                    mesh=runtime.mesh,
                ).reshape(rows * G, -1)

            return jax.jit(run_fwd)

        def build_head():
            def run_head(p, slots, row_slots):
                pooled = jnp.concatenate(slots, axis=0)[row_slots]
                return pack_result(model_mod.classify_head(p, pooled, cfg))

            return jax.jit(run_head)

        fwd = runtime.compiled(
            ("map_classify_tpu", family, rows, L, ("packed", most),
             cfg_key(cfg)), build_slice)
        head = runtime.compiled(
            ("map_classify_tpu", "packed_head", family, B, rows * G, most, k,
             cfg_key(cfg)), build_head)

        def put_slices(a):
            """[P, w] → [most, rows, w] on the device, a slice's rows over
            ``dp``."""
            out = np.zeros((most * rows, a.shape[1]), a.dtype)
            out[:len(a)] = a
            return jax.device_put(out.reshape(most, rows, -1),
                                  runtime.sharding(None, "dp"))

        ids_all, seg_all = put_slices(chunk.ids), put_slices(
            chunk.segment_lengths)
        slots = [fwd(params, ids_all, seg_all, np.int32(s))
                 for s in range(chunk.ids.shape[0] // rows)]
        slots += [slots[0]] * (most - len(slots))
        return head({"head": params["head"]}, slots,
                    runtime.put_batch(chunk.row_slots))

    pending: List[Tuple[Any, Any, int]] = []
    for chunk in chunks:
        if isinstance(chunk, PackedChunk):
            pending.append((dispatch_packed(chunk), chunk.n))
            continue
        ids, lengths, n = chunk
        B, L = ids.shape

        def build(L=L):
            def run_fwd(p, i, nlen):
                mask = (jnp.arange(L)[None, :] < nlen[:, None]).astype(jnp.int32)
                ids = rebuild_ids(i, mask)
                if pp_mesh is not None:
                    logits = encoder_forward_pp(
                        p, ids, mask, cfg, pp_mesh,
                        attn_fn=pp_attn,
                    )
                elif family == "encoder":
                    logits = model_mod.forward(
                        p, ids, mask, cfg, attn_fn=attn_fn,
                        mesh=runtime.mesh,  # ep expert sharding for MoE cfgs
                    )
                else:
                    logits = model_mod.forward(
                        p, ids, mask, cfg, attn_fn=attn_fn
                    )
                return pack_result(logits)

            return jax.jit(run_fwd)

        # k is fused into the executable, so a task stream alternating topk
        # values recompiles per (shape, k). Splitting top-k into its own
        # jit avoids that but costs an extra dispatch every call (what it
        # costs in rows/s: not measured on the present tree; either
        # `bert-base` cell would show it); jobs use one topk, so the fused
        # form stays.
        fn = runtime.compiled(
            ("map_classify_tpu", family, B, L, k, cfg_key(cfg)),
            build,
        )
        packed = fn(
            params, runtime.put_batch(ids), runtime.put_batch(lengths)
        )
        pending.append((packed, n))
    if len(pending) > 1:
        # Gather the chunk results on DEVICE here, on the dispatching
        # (owner) thread: each host read of a device array is a full round
        # trip (not measured on a directly attached chip), so fetching 16
        # chunks separately would pay 16 where one suffices — and in
        # pipelined no-fallback mode the fetch happens on the poster
        # thread, which must only ever READ device arrays (single-owner
        # dispatch invariant, agent/pipeline.py).
        packed_d = _concat_pending()([p for p, _ in pending])
        pending = [("cat", packed_d, [(p.shape[0], n) for p, n in pending])]
    if not fetch:
        return pending
    return _fetch_pending(pending)


_concat_fn = None


def _concat_pending():
    """Module-cached jitted device concat (jit reuses its own executable
    cache per chunk-shape signature). Called from the dispatching thread
    ONLY — see the single-owner note in :func:`_execute_chunks`."""
    global _concat_fn
    if _concat_fn is None:
        import jax
        import jax.numpy as jnp

        _concat_fn = jax.jit(lambda ps: jnp.concatenate(ps, axis=0))
    return _concat_fn


def _fetch_pending(pending) -> Tuple[np.ndarray, np.ndarray]:
    """Sync pending device results → (vals [N, k], idx [N, k]) numpy,
    trimming padding rows — ONE ``np.asarray`` (= one device→host round
    trip) per shard: chunks return a packed [B, k, 2] int32 array (score
    bit patterns, idx) and multi-chunk shards were already gathered into one
    ``("cat", packed, layout)`` entry on the device thread at dispatch time.
    Pure READS of device arrays, so the pipelined poster thread may call
    it."""
    first = pending[0]
    if isinstance(first[0], str):  # ("cat", packed, layout)
        _, packed_d, layout = first
        arr = np.asarray(packed_d)
        out, off = [], 0
        for B, n in layout:
            out.append(arr[off:off + n])
            off += B
        arr = np.concatenate(out)
    else:  # (packed, n)
        packed_d, n = first
        arr = np.asarray(packed_d)[:n]
    vals = np.ascontiguousarray(arr[..., 0]).view(np.float32)
    idx = np.ascontiguousarray(arr[..., 1])
    return vals, idx


def _get_cpu_runtime():
    global _cpu_runtime
    if _cpu_runtime is None:
        import jax

        from agent_tpu.config import DeviceConfig
        from agent_tpu.runtime.runtime import TpuRuntime

        # One device, dp=1: the degraded path must accept chunks staged for
        # ANY primary mesh (every batch bucket divides 1), and production
        # hosts expose a single cpu device anyway.
        _cpu_runtime = TpuRuntime(
            config=DeviceConfig(tpu_disabled=True),
            devices=jax.devices("cpu")[:1],
        )
    return _cpu_runtime


def stage(payload: Any, ctx: Optional[object] = None):
    """Host-only phase. Returns ``("done", result)`` for immediate soft
    results (bad input) or ``("staged", state)`` for :func:`execute`.

    Thread-safe: touches no device state (the mesh shape read off an existing
    runtime is host metadata). Shard-read and tokenize errors follow the
    drain contract — ValueError → soft result, I/O / integrity errors raise.
    """
    t0 = time.perf_counter()
    if not isinstance(payload, dict):
        return "done", bad_input("payload must be a dict")

    topk = payload.get("topk", DEFAULT_TOPK)
    if isinstance(topk, bool) or not isinstance(topk, int) or topk <= 0:
        return "done", bad_input("topk must be a positive int")
    result_format = payload.get("result_format", "rows")
    if result_format not in ("rows", "columnar"):
        return "done", bad_input("result_format must be 'rows' or 'columnar'")

    model_id = _resolve_model_id(payload)
    family = _resolve_family(model_id)
    from agent_tpu.ops._model_common import resolve_runtime

    rt = resolve_runtime(ctx)  # one resolution serves guards and staging
    try:
        # Checkpoint-integrity problems (unreadable config.json, missing
        # vocab) raise past this handler on purpose: they fail the shard for
        # retry rather than soft-dropping it as caller error.
        cfg = (
            _get_bert_cfg(model_id, payload) if family == "bert"
            else _get_cfg(payload)
        )
        from agent_tpu.ops._model_common import apply_quant_env

        cfg = apply_quant_env(payload, cfg)
        if family == "encoder":
            # Strategy-combination guards (caller error → soft bad_input):
            # pp stages the stacked block pytree and MoE/int8 reshape its
            # leaves — the unsupported pairings must reject, not mis-serve.
            # The EFFECTIVE pp is the mesh's pp axis when the serving mesh
            # has one (execute routes through the pipeline for it with no
            # payload involvement), else model_config's pp — guarding only
            # cfg.pp would let the mesh-axis route bypass every check.
            mesh_pp = rt.axis_size("pp") if rt is not None else 1
            eff_pp = mesh_pp if mesh_pp > 1 else getattr(cfg, "pp", 1)
            # (int8 composes with BOTH pp and MoE since round 5: quantized
            # leaves are ordinary pytrees for the GPipe stack/scan, and MoE
            # expert FFNs take per-expert int8 — quant.qmoe_expert. The
            # former soft-rejections are now equality-tested serving modes,
            # tests/test_pp_moe_serving.py.)
            if eff_pp > 1:
                if cfg.n_layers % eff_pp != 0:
                    raise ValueError(
                        f"n_layers {cfg.n_layers} not divisible by pp={eff_pp}"
                    )
                if cfg.moe_experts > 0:
                    raise ValueError(
                        "pp and moe_experts cannot combine in one config"
                    )
                if mesh_pp <= 1 and rt is not None \
                        and rt.n_devices % eff_pp != 0:
                    raise ValueError(
                        f"pp={eff_pp} does not divide the "
                        f"{rt.n_devices}-device mesh"
                    )
        items, kind, single = _collect_sequences(payload, cfg)
        from agent_tpu.ops._model_common import (
            validate_output_uri,
            validate_start_row,
        )

        output_dir = validate_output_uri(payload)
        start_row = validate_start_row(payload)
    except ValueError as exc:
        return "done", bad_input(str(exc))

    # Batch buckets must divide the mesh that will execute them. The pp
    # schedule additionally needs batches divisible by n_micro × pipeline-dp
    # (= pp·dp on a pp mesh; = all devices for a derived mesh), so pp
    # configs stage with that larger divisor.
    dp_stage = rt.axis_size("dp") if rt is not None else 1
    if family == "encoder" and rt is not None:
        if rt.axis_size("pp") > 1:
            dp_stage = rt.axis_size("pp") * rt.axis_size("dp")
        elif getattr(cfg, "pp", 1) > 1:
            dp_stage = rt.n_devices
    chunks = _stage_chunks(
        dp_stage, items, kind, cfg, family=family, model_id=model_id,
        pack=_takes_packed_rows(cfg, family, rt),
    )

    state = {
        "t0": t0,
        "chunks": chunks,
        "token_slots": _token_slots(chunks),
        "n_rows": len(items),
        "cfg": cfg,
        "k": min(topk, cfg.n_classes),  # clamp so lax.top_k stays legal
        "model_id": model_id,
        "family": family,
        "result_format": result_format,
        "allow_fallback": bool(payload.get("allow_fallback", True)),
        "single": single,
        "output_dir": output_dir,
        "start_row": start_row,
        "t_staged": time.perf_counter(),
    }
    return "staged", state


def _token_slots(chunks: List[Tuple]) -> Tuple[int, int, bool]:
    """(real tokens, token slots dispatched, any chunk packed) of a staged
    shard, from the staged lengths and shapes: what
    ``classify_token_slots_total`` and ``classify_shards_total`` tick."""
    from agent_tpu.ops._model_common import PackedChunk

    real = slots = 0
    packed = False
    for chunk in chunks:
        slots += int(chunk[0].shape[0] * chunk[0].shape[1])
        if isinstance(chunk, PackedChunk):
            packed = True
            real += int(chunk.segment_lengths.sum())
        else:
            real += int(np.minimum(chunk[1][:chunk[2]], chunk[0].shape[1]).sum())
    return real, slots, packed


def _stamp_flops(state: Dict[str, Any], ctx: Optional[object]) -> None:
    """Analytic-FLOPs attribution (ISSUE 8): estimate the dispatched matmul
    FLOPs from the staged chunk shapes and the model config, stamped into
    ``ctx.tags["device_attr"]`` so the agent can export ``device_mfu{op}``.
    Dimension names differ per family (encoder: d_model/d_ff/n_layers,
    BERT: hidden_size/intermediate_size/num_layers); a config missing them
    simply doesn't stamp — MFU is then absent, never wrong."""
    cfg = state.get("cfg")
    d = getattr(cfg, "d_model", None) or getattr(cfg, "hidden_size", None)
    f = getattr(cfg, "d_ff", None) or getattr(cfg, "intermediate_size", None)
    n_layers = (
        getattr(cfg, "n_layers", None) or getattr(cfg, "num_layers", None)
    )
    if not (d and f and n_layers):
        return
    from agent_tpu.ops._model_common import (
        encoder_fwd_flops,
        stamp_device_flops,
    )

    total = 0.0
    biggest = (0, "?")
    for chunk in state.get("chunks") or []:
        try:
            B, L = chunk[0].shape
        except Exception:  # noqa: BLE001 — estimation must never fail a shard
            continue
        total += encoder_fwd_flops(
            B, L, d, f, n_layers, getattr(cfg, "n_classes", 0) or 0
        )
        if B * L > biggest[0]:
            biggest = (B * L, f"B{B}xL{L}")
    if total > 0:
        stamp_device_flops(ctx, total, biggest[1])


def execute(state: Dict[str, Any], ctx: Optional[object] = None) -> Dict[str, Any]:
    """Device phase (owning thread only): run staged chunks on the mesh,
    falling back to the CPU backend per the degraded-mode contract."""
    # Stamped here, not at stage end: in pipelined mode the item may sit in
    # the bounded queue between phases, and that wait must not count as
    # device time (it shows up as queue_ms instead).
    state["t_exec0"] = time.perf_counter()
    _stamp_flops(state, ctx)
    obs_trace.record_classify_shard(*state["token_slots"])
    model_id, cfg, k = state["model_id"], state["cfg"], state["k"]
    fallback_reason = None
    try:
        if ctx is not None and getattr(ctx, "require_runtime", None):
            runtime = ctx.require_runtime()
        else:
            from agent_tpu.runtime.runtime import get_runtime

            runtime = get_runtime()
        if not state["allow_fallback"]:
            # Drain mode (no CPU retry promised): leave the device arrays
            # unfetched so finalize — the pipeline's poster thread — pays
            # the device→host round trip while THIS thread dispatches the
            # next shard. A device failure then surfaces at fetch time and
            # fails the shard, exactly the no-fallback contract.
            state.update(
                pending_dev=_execute_chunks(
                    runtime, state["chunks"], model_id, cfg, k,
                    family=state["family"], fetch=False,
                ),
                device=runtime.platform,
                fallback_reason=None,
                t_device=time.perf_counter(),
            )
            # Dispatch only: finalize stamps the completion (t_ready).
            return state
        vals, idx = _execute_chunks(
            runtime, state["chunks"], model_id, cfg, k,
            family=state["family"],
        )
        device = runtime.platform
    except Exception as exc:  # noqa: BLE001 — any device failure → fallback path
        if not state["allow_fallback"]:
            raise
        try:
            runtime = _get_cpu_runtime()
            vals, idx = _execute_chunks(
                runtime, state["chunks"], model_id, cfg, k,
                family=state["family"],
            )
            device = runtime.platform
            fallback_reason = f"{type(exc).__name__}: {exc}"
        except Exception as cpu_exc:  # noqa: BLE001 — truly degraded
            if not state["single"]:
                # Batch/drain shards must FAIL (→ controller retry), not
                # report a degraded empty result that silently drops every
                # row of the shard; the reference's degraded contract is a
                # single-row interactive shape (ref :22-28).
                raise
            state["degraded_reason"] = (
                f"{type(exc).__name__}: {exc}; cpu retry: {cpu_exc}"
            )
            state["t_ready"] = state["t_device"] = time.perf_counter()
            return state
    # Fetched above: the results are on the host, the device is done.
    state["t_ready"] = t_device = time.perf_counter()
    state.update(
        vals=vals, idx=idx, device=device, fallback_reason=fallback_reason,
        t_device=t_device,
    )
    return state


def finalize(state: Dict[str, Any], ctx: Optional[object] = None) -> Dict[str, Any]:
    """Host serialization phase: numpy top-k → the JSON-shaped result. Safe
    off the device thread (reads fetched arrays only)."""
    t0, model_id = state["t0"], state["model_id"]
    result_format = state["result_format"]

    if "degraded_reason" in state:
        # Reference degraded shape (ref ops/map_classify_tpu.py:22-28),
        # carrying whichever empty result keys the requested format promises.
        out = {
            "ok": True,
            "op": "map_classify_tpu",
            "model_path": model_id,
            "fallback": "cpu",
            "reason": state["degraded_reason"][:500],
            "elapsed_ms": (time.perf_counter() - t0) * 1000.0,
        }
        if result_format == "columnar":
            out["indices"] = []
            out["scores"] = []
        else:
            out["topk"] = []
        return out

    if "pending_dev" in state:
        # Deferred fetch (no-fallback mode): sync the device results here,
        # off the device thread. elapsed_ms keeps covering the true span;
        # the wait is stamped as timings.fetch_ms (device_ms is dispatch
        # only in this mode).
        with obs_trace.phase("fetch") as fetched:
            vals, idx = _fetch_pending(state["pending_dev"])
        state["t_ready"] = fetched.t1
        state["fetch_ms"] = fetched.seconds * 1000.0
    else:
        vals, idx = state["vals"], state["idx"]

    if ctx is not None and hasattr(ctx, "tags"):
        # Per-stage trace (SURVEY.md §5.1): staging = payload → token rows
        # (incl. shard read); queue = wait between phases (pipelined mode);
        # device = params + transfer + compute (+ fetch, except in the
        # deferred-fetch no-fallback mode, where the fetch lands in
        # fetch_ms on the finalize span so the device thread stays free to
        # dispatch).
        ctx.tags.setdefault("timings", {}).update(
            stage_ms=round((state["t_staged"] - t0) * 1000.0, 3),
            queue_ms=round((state["t_exec0"] - state["t_staged"]) * 1000.0, 3),
            device_ms=round((state["t_device"] - state["t_exec0"]) * 1000.0, 3),
            **(
                {"fetch_ms": round(state["fetch_ms"], 3)}
                if "fetch_ms" in state else {}
            ),
        )
    from agent_tpu.ops._model_common import stamp_rows

    stamp_rows(ctx, state["n_rows"])
    out: Dict[str, Any] = {
        "ok": True,
        "op": "map_classify_tpu",
        "model_path": model_id,
        "device": state["device"],
        "n_rows": state["n_rows"],
        "elapsed_ms": (time.perf_counter() - t0) * 1000.0,
    }
    if state["fallback_reason"] is not None:
        out["fallback"] = "cpu"
        out["reason"] = state["fallback_reason"]

    if state["output_dir"] is not None:
        # Result-sink mode: full per-row top-k goes to disk; the wire carries
        # a receipt. At drain scale the controller must not hold row payloads.
        from agent_tpu.ops._model_common import write_output_shard

        idx_l = np.asarray(idx).tolist()
        val_l = np.round(np.asarray(vals), 6).tolist()
        path, n = write_output_shard(
            state["output_dir"], "map_classify_tpu", state["start_row"],
            ({"indices": i, "scores": s} for i, s in zip(idx_l, val_l)),
        )
        out["output_path"] = path
        out["rows_written"] = n
        return out

    if result_format == "columnar":
        if ctx is not None and hasattr(ctx, "tags") \
                and ctx.tags.get("wire") == "b1":
            # Binary shard wire (ISSUE 6): ship the [N, k] columns as raw
            # arrays (indices width-shrunk, scores as the rounded f32 bit
            # patterns) instead of tolist()-ing them into JSON — the
            # controller decodes to the exact lists the JSON path would
            # have produced (same np.round(…, 6) then-widen semantics), so
            # binary and JSON drains are bit-identical.
            from agent_tpu.data import wire

            return wire.attach_result_columns(out, {
                "indices": np.ascontiguousarray(idx),
                "scores": np.round(np.asarray(vals), 6),
            })
        # Drain-friendly wire shape: [N, k] index/score arrays instead of
        # 5·N score dicts: a third of the JSON, and no dict per score to
        # build, when results travel per-shard over HTTP.
        out["indices"] = np.asarray(idx).tolist()
        out["scores"] = np.round(np.asarray(vals), 6).tolist()
        return out

    from agent_tpu.models.encoder import topk_rows

    per_row = topk_rows(vals, idx)
    out["topk"] = per_row[0]
    if not state["single"]:
        out["results"] = [{"topk": t} for t in per_row]
    return out


@register_op("map_classify_tpu")
def run(payload: Any, ctx: Optional[object] = None) -> Dict[str, Any]:
    """Classic monolithic entry: stage → execute → finalize inline."""
    phase, value = stage(payload, ctx)
    if phase == "done":
        return value
    return finalize(execute(value, ctx), ctx)


# Phase hooks for the pipelined drain (agent_tpu.agent.pipeline): the agent
# discovers them via these attributes, so ops without phases run monolithic.
run.stage = stage
run.execute = execute
run.finalize = finalize
# execute may return with the device still working (drain mode defers the
# fetch to finalize): the state carries ``t_ready``, the instant the results
# were on the host, from whichever phase fetched them.
run.deferred = True
