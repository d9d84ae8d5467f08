"""Summarization on the mesh — successor of the reference's torch-BART op.

Capability parity with reference ``ops/map_summarize.py:35-68``:

- Payload: required ``text`` (plus the batched upgrade ``texts``), optional
  ``max_length`` (default 130, ref ``:46``), ``model_path``.
- Result: ``{ok, summary, device, model}`` (ref ``:61-67``), plus timing.
- Input truncated at 1024 tokens (ref ``:49``).
- Lazy once-per-process model init (ref ``:17-33``) — via the runtime's HBM
  params store instead of a module-global + lock.

The decode itself is ``models.seq2seq.greedy_generate``: one compiled program,
``lax.scan`` over static steps, KV cache in HBM — replacing the reference's
host-side ``model.generate`` beam loop (ref ``:52-59``). SUMMARIZE_FORCE_CPU is
still honored as a kill-switch (ref ``:10``) but defaults off: BASELINE.json's
north star is zero CPU-side model execution.

Like ``map_classify_tpu``, the op is **phase-split** for the pipelined drain:
:func:`stage` (host — validation, shard read, fused tokenize+pad),
:func:`execute` (device — params, compiled decode *dispatch*; the token
arrays come back unfetched), :func:`finalize` (host — the deferred
device→host token fetch, a thread-safe read, then detokenize, sink write,
result shape). The summarize leg of an at-scale drain therefore overlaps
next-shard tokenization, the previous shard's fetch, and result posting
with device decode; ``run`` composes the phases for monolithic callers.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from agent_tpu.obs import trace as obs_trace
from agent_tpu.ops import register_op
from agent_tpu.utils.errors import bad_input

DEFAULT_MODEL_ID = "summarize-default"
DEFAULT_MAX_LENGTH = 130

# One-shot guard for the default-inversion notice in stage(): the framework
# default (device execution) is the INVERSE of the reference's CPU-on default,
# and that must be visible in operational logs of processes that actually run
# summarize (only those — hence here, not in config.py).
_force_cpu_default_logged = False


def _resolve_model_id(payload: Dict[str, Any]) -> str:
    from agent_tpu.ops._model_common import resolve_model_id

    return resolve_model_id(payload, "BART_MODEL", DEFAULT_MODEL_ID)


def _get_cfg(payload: Dict[str, Any]):
    from agent_tpu.models.seq2seq import Seq2SeqConfig
    from agent_tpu.ops._model_common import config_from_payload

    return config_from_payload(payload, Seq2SeqConfig)


def _resolve_family(model_id: str) -> str:
    """``model_path`` pointing at a local HF checkpoint directory serves the
    pretrained family: BART (the reference's actual summarize model, ref
    ``ops/map_summarize.py:29-32``) or T5 (the family BASELINE.json names);
    else the in-house seq2seq.

    Any OTHER checkpoint directory (an HF dir of a different model_type)
    fails the shard loudly: silently serving seeded random weights for what
    was unambiguously a checkpoint would return ok=true nonsense."""
    from agent_tpu.models import bart, bert, t5

    if bart.is_hf_bart_dir(model_id):
        return "bart"
    if t5.is_hf_t5_dir(model_id):
        return "t5"
    if bert.is_hf_dir(model_id):  # generic "HF checkpoint dir" detector
        raise RuntimeError(
            f"model_path {model_id!r} is a checkpoint directory but not a "
            "BART/T5 one (map_summarize serves model_type=bart|t5; "
            "classify serves BERT)"
        )
    return "seq2seq"


# model_config fields a payload may override for a checkpoint model:
# serving controls only (structural fields are the checkpoint's). "quant"
# accepts "int8" (W8A8) and "w8a16" (weight-only — the decode-targeted mode:
# summarize is weight-HBM-bound per step, so a T5/BART checkpoint serves
# with int8-resident weights dequantized in-register at dtype).
_CKPT_SERVING_OVERRIDES = ("dtype", "quant")


def _get_ckpt_cfg(model_id: str, payload: Dict[str, Any], family: str):
    import os as _os

    if family == "t5":
        from agent_tpu.models.t5 import T5Config as config_cls
    else:
        from agent_tpu.models.bart import BartConfig as config_cls

    overrides = payload.get("model_config")
    allowed = {}
    if isinstance(overrides, dict):
        allowed = {
            k: v for k, v in overrides.items()
            if k in _CKPT_SERVING_OVERRIDES
        }
    return config_cls.from_hf_json(
        _os.path.join(model_id, "config.json"), **allowed
    )


def _build_params(model_id: str, cfg, family: str = "seq2seq"):
    if family == "bart":
        from agent_tpu.models import bart

        _, params = bart.load_hf_dir(model_id, dtype=cfg.dtype)
    elif family == "t5":
        from agent_tpu.models import t5

        _, params = t5.load_hf_dir(model_id, dtype=cfg.dtype)
    else:
        from agent_tpu.models import seq2seq

        if model_id.endswith(".npz") and os.path.exists(model_id):
            params = seq2seq.load_npz(model_id, cfg)
        else:
            params = seq2seq.init_params(cfg, model_id=model_id)
    from agent_tpu.ops._model_common import maybe_quantize_params

    return maybe_quantize_params(params, family, cfg)


# Decode-row budget per compiled program: the per-step decode matmuls are
# [rows, d_model]-thin, so a bigger program gives the MXU more rows a step.
# One 8,192-row program against chained 1,024-row ones: not measured on the
# present tree (PERF.md §7, rows 6-8: scan decode in `map_summarize`; dense
# KV may fill memory at this cap). Beam search multiplies rows in
# flight by num_beams (beams flatten into the batch dim, and the KV caches
# size with B*K), so staging divides the budget by num_beams.
MAX_DECODE_ROWS = 8192


def _stage_chunks(dp: int, texts: List[str], cfg, num_beams: int = 1,
                  family: str = "seq2seq", model_id: str = "") -> List:
    """Shared staging scaffolding (``_model_common.stage_text_chunks``):
    fused byte tokenize+pad with BOS/EOS for the in-house seq2seq, the
    checkpoint's byte-level BPE (``<s> … </s>``) for the BART family."""
    from agent_tpu.ops._model_common import stage_text_chunks

    encode_pad = None
    if family == "bart":
        from agent_tpu.models import bart

        tok = bart.hf_bpe(model_id)

        def encode_pad(chunk, lb, bb):
            return bart.encode_pad_batch(tok, chunk, cfg, bb, lb)

    elif family == "t5":
        from agent_tpu.models import t5

        sp = t5.hf_spm(model_id)  # gated: actionable error sans sentencepiece

        def encode_pad(chunk, lb, bb):
            return t5.encode_pad_batch(sp, chunk, cfg, bb, lb)

    return stage_text_chunks(
        dp, texts, max_len=cfg.max_src_len, vocab_size=cfg.vocab_size,
        max_batch=max(1, MAX_DECODE_ROWS // num_beams),
        add_bos=True, add_eos=True,
        encode_pad=encode_pad,
    )


def _decode_chunks(runtime, chunks: List, model_id: str, cfg,
                   max_new: int, num_beams: int,
                   length_penalty: float = 1.0,
                   early_stopping: bool = False,
                   min_length: int = 0,
                   family: str = "seq2seq") -> List[Tuple[Any, int]]:
    """Device phase: decode staged chunks → pending ``[(toks_dev, n), ...]``
    device arrays (deferred fetch — see the return comment below; same
    pattern as classify's no-fallback mode).
    """
    import jax

    from agent_tpu.models import seq2seq
    from agent_tpu.ops._model_common import cfg_key
    from agent_tpu.parallel.shardings import (
        bart_param_specs,
        seq2seq_param_specs,
        t5_param_specs,
    )

    specs = (
        bart_param_specs(cfg) if family == "bart"
        else t5_param_specs(cfg) if family == "t5"
        else seq2seq_param_specs(cfg)
    )
    from agent_tpu.ops._model_common import maybe_quantize_specs

    specs = maybe_quantize_specs(specs, family, cfg)
    # tp>1 mesh → weights land sharded, same serving-path TP as classify.
    params = runtime.get_params(
        f"{model_id}#{family}#{hash(cfg_key(cfg)) & 0xFFFFFFFF:08x}",
        lambda: _build_params(model_id, cfg, family),
        specs=specs,
    )
    attn_fn = runtime.attention_fn()  # ring over sp for the encoder pass
    pending = []
    for ids, lengths, n in chunks:
        B, Ls = ids.shape

        # Lengths-on-wire like classify: ship uint16 ids + one length per
        # row, rebuild ids dtype and the [B, L] mask inside the compiled
        # program: two bytes an id and one length a row cross the wire, not
        # two [B, L] int32 arrays.
        def build(Ls=Ls):
            import jax.numpy as jnp

            if family == "bart":
                from agent_tpu.models import bart

                gen = lambda p, i, m: bart.generate(  # noqa: E731
                    p, i, m, cfg, max_new, num_beams=num_beams,
                    length_penalty=length_penalty,
                    early_stopping=early_stopping, min_length=min_length,
                    attn_fn=attn_fn,
                )
            elif family == "t5":
                from agent_tpu.models import t5

                # No generic attn_fn: T5's bias-carrying attention has its
                # own fused path — the runtime's mesh-aware kernel wrapper
                # (make_flash_attention_t5: batch over dp, heads over tp;
                # bias computed per tile in VMEM) goes to t5.encode, which
                # falls back to dense for short/unsupported shapes. Ring-
                # over-sp composition remains a known limitation.
                t5_kernel = runtime.t5_attention_kernel()
                gen = lambda p, i, m: t5.generate(  # noqa: E731
                    p, i, m, cfg, max_new, num_beams=num_beams,
                    length_penalty=length_penalty,
                    early_stopping=early_stopping, min_length=min_length,
                    kernel=t5_kernel,
                )
            else:
                gen = (
                    (lambda p, i, m: seq2seq.greedy_generate(
                        p, i, m, cfg, max_new, min_length=min_length,
                        attn_fn=attn_fn))
                    if num_beams <= 1
                    else (lambda p, i, m: seq2seq.beam_generate(
                        p, i, m, cfg, max_new, num_beams=num_beams,
                        length_penalty=length_penalty,
                        early_stopping=early_stopping,
                        min_length=min_length, attn_fn=attn_fn))
                )

            def run_gen(p, i, n):
                mask = (jnp.arange(Ls)[None, :] < n[:, None]).astype(jnp.int32)
                return gen(p, i.astype(jnp.int32), mask)

            return jax.jit(run_gen)

        fn = runtime.compiled(
            ("map_summarize", model_id, family, B, Ls, max_new, num_beams,
             length_penalty, early_stopping, min_length, cfg_key(cfg)),
            build,
        )
        toks, _ = fn(
            params, runtime.put_batch(ids), runtime.put_batch(lengths)
        )
        pending.append((toks, n))
    # Unfetched: finalize (the pipeline's poster thread) syncs, so the
    # device thread can dispatch the next shard during this one's
    # device→host round trip (reading a jax.Array is thread-safe).
    return pending


def stage(payload: Any, ctx: Optional[object] = None):
    """Host-only phase: validation, shard read, tokenize+pad. Returns
    ``("done", result)`` for soft errors or ``("staged", state)``."""
    t0 = time.perf_counter()
    if not isinstance(payload, dict):
        return "done", bad_input("payload must be a dict")

    texts = payload.get("texts")
    single = texts is None and "source_uri" not in payload
    empty_rows: List[int] = []  # drain-mode blank cells → empty summaries
    if texts is None and "source_uri" in payload:
        # CSV shard addressing — the summarize half of the BASELINE.json
        # classify+summarize drain. Shared contract with classify
        # (``read_shard_texts``): ValueError → soft bad_input; shard
        # integrity / I/O problems raise so the shard FAILS and retries.
        from agent_tpu.data.csv_index import read_shard_texts

        try:
            texts = read_shard_texts(payload)
        except ValueError as exc:
            return "done", bad_input(str(exc))
        # Messy data is normal in drains: blank cells get an empty summary
        # (overwritten after generation) instead of failing the shard or
        # emitting model output for no input — the payload 'texts' path
        # keeps its strict non-empty contract.
        empty_rows = [i for i, t in enumerate(texts) if not t]
        if empty_rows:
            texts = [t or " " for t in texts]
    elif single:
        text = payload.get("text")
        if not isinstance(text, str) or not text:
            return "done", bad_input("payload requires a non-empty 'text' string")
        texts = [text]
    elif not isinstance(texts, list) or not texts or not all(
        isinstance(t, str) and t for t in texts
    ):
        return "done", bad_input("texts must be a non-empty list of non-empty strings")

    max_new = payload.get("max_length", DEFAULT_MAX_LENGTH)
    if isinstance(max_new, bool) or not isinstance(max_new, int) or max_new <= 0:
        return "done", bad_input("max_length must be a positive int")

    # Beam search opt-in (the reference always decoded with num_beams=4,
    # reference ops/map_summarize.py:57; greedy default keeps the fast path).
    num_beams = payload.get("num_beams", 1)
    if isinstance(num_beams, bool) or not isinstance(num_beams, int) or \
            not 1 <= num_beams <= 16:
        return "done", bad_input("num_beams must be an int in [1, 16]")
    # Beam score normalization exponent (HF semantics: selection scores
    # divide by length**length_penalty). bart-large-cnn — the reference's
    # actual model — generates with 2.0; our default stays HF's generic 1.0.
    length_penalty = payload.get("length_penalty", 1.0)
    if isinstance(length_penalty, bool) or \
            not isinstance(length_penalty, (int, float)) or \
            not -4.0 <= float(length_penalty) <= 4.0:
        return "done", bad_input(
            "length_penalty must be a number in [-4, 4]"
        )
    length_penalty = float(length_penalty)
    early_stopping = payload.get("early_stopping", False)
    if not isinstance(early_stopping, bool):
        return "done", bad_input("early_stopping must be a bool")
    # HF counting: min_length bounds the FULL decoder sequence (start +
    # generated); bart-large-cnn generated with 56.
    min_length = payload.get("min_length", 0)
    if isinstance(min_length, bool) or not isinstance(min_length, int) or \
            min_length < 0:
        return "done", bad_input("min_length must be a non-negative int")

    from agent_tpu.ops._model_common import (
        validate_output_uri,
        validate_start_row,
    )

    try:
        output_dir = validate_output_uri(payload)
        start_row = validate_start_row(payload)
    except ValueError as exc:
        return "done", bad_input(str(exc))

    model_id = _resolve_model_id(payload)
    family = _resolve_family(model_id)
    # Checkpoint-integrity problems (unreadable config.json) raise past the
    # soft-error handlers on purpose: retryable shard failure, not bad input.
    cfg = (
        _get_ckpt_cfg(model_id, payload, family)
        if family in ("bart", "t5") else _get_cfg(payload)
    )
    try:
        from agent_tpu.ops._model_common import apply_quant_env

        cfg = apply_quant_env(payload, cfg)
    except ValueError as exc:
        return "done", bad_input(str(exc))
    max_new = min(max_new, cfg.max_tgt_len)

    from agent_tpu.config import OpsConfig

    # The typed config is authoritative (its default is the single source;
    # standalone calls read the env through OpsConfig.from_env).
    ops_cfg = (
        ctx.config.ops
        if ctx is not None and getattr(ctx, "config", None) is not None
        else OpsConfig.from_env()
    )
    global _force_cpu_default_logged
    if not ops_cfg.summarize_force_cpu and not _force_cpu_default_logged \
            and "SUMMARIZE_FORCE_CPU" not in os.environ:
        # Only on the untouched-default path: an operator who set the var
        # (either way) made a choice and needs no notice.
        _force_cpu_default_logged = True
        from agent_tpu.utils.logging import log as _log

        _log(
            "summarize runs on the device backend by default "
            "(the reference defaulted to CPU; SUMMARIZE_FORCE_CPU=1 forces CPU)"
        )

    # Batch buckets must divide the executing mesh. Force-CPU always
    # executes on the 1-device CPU runtime → dp=1.
    from agent_tpu.ops._model_common import resolve_dp

    dp = 1 if ops_cfg.summarize_force_cpu else resolve_dp(ctx)

    state = {
        "t0": t0,
        "chunks": _stage_chunks(
            dp, texts, cfg, num_beams=num_beams, family=family,
            model_id=model_id,
        ),
        "empty_rows": empty_rows,
        "single": single,
        "max_new": max_new,
        "num_beams": num_beams,
        "length_penalty": length_penalty,
        "early_stopping": early_stopping,
        "min_length": min_length,
        "model_id": model_id,
        "family": family,
        "cfg": cfg,
        "force_cpu": ops_cfg.summarize_force_cpu,
        "output_dir": output_dir,
        "start_row": start_row,
        "t_staged": time.perf_counter(),
    }
    return "staged", state


def _stamp_flops(state: Dict[str, Any], ctx: Optional[object]) -> None:
    """Analytic-FLOPs attribution (ISSUE 8): encode + incremental decode
    estimate from the staged chunk shapes, stamped into
    ``ctx.tags["device_attr"]`` for the agent's ``device_mfu{op}`` gauge.
    Configs missing the dimensions (exotic checkpoints) don't stamp."""
    cfg = state.get("cfg")
    d = getattr(cfg, "d_model", None)
    f = getattr(cfg, "d_ff", None)
    n_enc = getattr(cfg, "n_enc_layers", None)
    n_dec = getattr(cfg, "n_dec_layers", None)
    if not (d and f and n_enc and n_dec):
        return
    from agent_tpu.ops._model_common import (
        seq2seq_fwd_flops,
        stamp_device_flops,
    )

    total = 0.0
    biggest = (0, "?")
    for chunk in state.get("chunks") or []:
        try:
            B, L = chunk[0].shape
        except Exception:  # noqa: BLE001 — estimation must never fail a shard
            continue
        total += seq2seq_fwd_flops(
            B, L, state["max_new"], d, f, n_enc, n_dec,
            vocab_size=getattr(cfg, "vocab_size", 0) or 0,
            num_beams=state["num_beams"],
        )
        if B * L > biggest[0]:
            biggest = (B * L, f"B{B}xL{L}xT{state['max_new']}")
    if total > 0:
        stamp_device_flops(ctx, total, biggest[1])


def execute(state: Dict[str, Any], ctx: Optional[object] = None) -> Dict[str, Any]:
    """Device phase (owning thread only): compiled decode of staged chunks."""
    state["t_exec0"] = time.perf_counter()
    _stamp_flops(state, ctx)
    if state["force_cpu"]:
        from agent_tpu.ops.map_classify_tpu import _get_cpu_runtime

        runtime = _get_cpu_runtime()
    elif ctx is not None and getattr(ctx, "require_runtime", None):
        runtime = ctx.require_runtime()
    else:
        from agent_tpu.runtime.runtime import get_runtime

        runtime = get_runtime()

    state["token_chunks"] = _decode_chunks(
        runtime, state["chunks"], state["model_id"], state["cfg"],
        state["max_new"], state["num_beams"],
        length_penalty=state["length_penalty"],
        early_stopping=state["early_stopping"],
        min_length=state["min_length"], family=state["family"],
    )
    state["device"] = runtime.platform
    state["t_device"] = time.perf_counter()
    # Dispatch only: finalize stamps the completion (t_ready).
    return state


def finalize(state: Dict[str, Any], ctx: Optional[object] = None) -> Dict[str, Any]:
    """Host phase: detokenize fetched token rows, write the sink, shape the
    result. Safe off the device thread (reads numpy arrays only)."""
    # Deferred fetch: sync the device token arrays here, off the device
    # thread (the pipeline's poster thread pays the round trip).
    with obs_trace.phase("fetch") as fetched:
        token_chunks = [
            np.asarray(toks)[:n] for toks, n in state["token_chunks"]
        ]
    state["t_ready"] = fetched.t1
    fetch_ms = fetched.seconds * 1000.0
    summaries: List[str] = []
    if state["family"] == "t5":
        from agent_tpu.models import t5

        cfg = state["cfg"]
        sp = t5.hf_spm(state["model_id"])
        n_pieces = sp.GetPieceSize()
        # Same id set transformers' skip_special_tokens drops — incl. unk.
        skip = {cfg.pad_id, cfg.eos_id, sp.unk_id()}
        for toks in token_chunks:
            summaries.extend(
                sp.DecodeIds(
                    [int(t) for t in row
                     if int(t) not in skip and int(t) < n_pieces]
                ).strip()
                for row in toks
            )
    elif state["family"] == "bart":
        from agent_tpu.models import bart

        cfg = state["cfg"]
        tok = bart.hf_bpe(state["model_id"])
        # Same id set transformers' skip_special_tokens drops — including
        # <unk> — so the served text matches the reference decode.
        skip = {cfg.pad_id, cfg.bos_id, cfg.eos_id, cfg.decoder_start_id}
        unk = tok.vocab.get("<unk>")
        if unk is not None:
            skip.add(unk)
        for toks in token_chunks:
            summaries.extend(
                tok.decode([t for t in row if int(t) not in skip]).strip()
                for row in toks
            )
    else:
        from agent_tpu.models.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
        for toks in token_chunks:
            summaries.extend(
                tok.decode([t for t in row if t > 0]) for row in toks
            )
    for i in state["empty_rows"]:
        summaries[i] = ""  # no input → no summary, not model noise

    if ctx is not None and hasattr(ctx, "tags"):
        # Same timings schema as classify: stage = payload → token rows;
        # queue = wait between phases (pipelined mode); device = params +
        # transfer + decode + fetch. Detokenize lands in the result's total.
        ctx.tags.setdefault("timings", {}).update(
            stage_ms=round((state["t_staged"] - state["t0"]) * 1000.0, 3),
            queue_ms=round(
                (state["t_exec0"] - state["t_staged"]) * 1000.0, 3
            ),
            # device_ms is the dispatch span; the decode's device→host sync
            # lands in fetch_ms (deferred to this, the poster thread).
            device_ms=round(
                (state["t_device"] - state["t_exec0"]) * 1000.0, 3
            ),
            fetch_ms=round(fetch_ms, 3),
        )

    from agent_tpu.ops._model_common import stamp_rows

    stamp_rows(ctx, len(summaries))
    out: Dict[str, Any] = {
        "ok": True,
        # Explicit op attribution (ISSUE 2 satellite): the reference shape
        # carried no "op" key, forcing utils/spans.result_op to guess from
        # "summaries" — the heuristic survives only for old bodies.
        "op": "map_summarize",
        "device": state["device"],
        "model": state["model_id"],
        "num_beams": state["num_beams"],
        "elapsed_ms": (time.perf_counter() - state["t0"]) * 1000.0,
    }
    if state["output_dir"] is not None:
        # Result-sink mode (see classify): summaries go to disk, the wire
        # carries a receipt — a 10M-row summarize drain posts ~KBs/shard,
        # not the row payloads.
        from agent_tpu.ops._model_common import write_output_shard

        path, n = write_output_shard(
            state["output_dir"], "map_summarize", state["start_row"],
            ({"summary": s} for s in summaries),
        )
        out["output_path"] = path
        out["rows_written"] = n
        return out
    out["summary"] = summaries[0]
    if not state["single"]:
        if ctx is not None and hasattr(ctx, "tags") \
                and ctx.tags.get("wire") == "b1":
            # Binary shard wire (ISSUE 6): the summaries column is the bulk
            # of a drain result body — ship it length-prefixed + deflated
            # (repetitive summaries compress hard) instead of as escaped
            # JSON strings. The controller decodes back to the identical
            # ``summaries`` list.
            from agent_tpu.data import wire

            return wire.attach_result_columns(out, {"summaries": summaries})
        out["summaries"] = summaries
    return out


@register_op("map_summarize")
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
# execute returns with the device still decoding (finalize fetches): the
# state carries ``t_ready``, the instant the tokens were on the host.
run.deferred = True
