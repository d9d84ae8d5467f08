"""Shared plumbing for the model-backed ops (classify, summarize).

Factored out so the two ops cannot drift: model-id resolution (payload →
env → default, the precedence of reference ``ops/_tpu_runtime.py:23-31``),
config-from-payload parsing, **config-aware cache keys** (a payload that
overrides ``model_config`` must never reuse weights or executables built for
a different config), batch-size buckets, and chunking for batches that exceed
the top bucket.
"""

from __future__ import annotations

import os
from dataclasses import fields
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple


# ---- analytic FLOPs (ISSUE 8: the MFU numerator) ----
#
# Matmul terms only (2·M·N·K per matmul; elementwise/softmax are noise at
# model scale), stamped per executed shard so the agent can export a
# live device_mfu{op} gauge. These are ESTIMATES by design: the point is a
# stable utilization trend per shape bucket, not a profiler.

def encoder_fwd_flops(
    batch: int, seq_len: int, d_model: int, d_ff: int, n_layers: int,
    n_classes: int = 0,
) -> float:
    """Forward FLOPs of ``batch`` rows through an encoder stack at padded
    length ``seq_len``: QKVO projections + score/value matmuls + FFN per
    layer, plus the classifier head."""
    d, f, L = float(d_model), float(d_ff), float(seq_len)
    attn_proj = 8.0 * L * d * d          # 4 projections × 2·L·d·d
    attn_sdpa = 4.0 * L * L * d          # QKᵀ and P·V × 2·L²·d
    ffn = 4.0 * L * d * f                # 2 matmuls × 2·L·d·f
    per_row = n_layers * (attn_proj + attn_sdpa + ffn) + 2.0 * d * n_classes
    return batch * per_row


def seq2seq_fwd_flops(
    batch: int, src_len: int, new_tokens: int, d_model: int, d_ff: int,
    n_enc_layers: int, n_dec_layers: int, vocab_size: int = 0,
    num_beams: int = 1,
) -> float:
    """Forward FLOPs of an encode + incremental greedy/beam decode:
    the encoder stack over ``src_len``, then per generated token a
    single-position decoder step (self-attn + cross-attn projections, FFN,
    cross-attention reads over the cached ``src_len`` keys, vocab
    projection). Beams multiply the decode rows in flight."""
    d, f = float(d_model), float(d_ff)
    enc = encoder_fwd_flops(batch, src_len, d_model, d_ff, n_enc_layers)
    rows = float(batch * max(1, num_beams))
    per_tok_layer = (
        8.0 * d * d          # self-attn QKVO projections (one position)
        + 8.0 * d * d        # cross-attn QKVO projections
        + 4.0 * src_len * d  # cross-attn scores + values over the cache
        + 4.0 * d * f        # FFN
    )
    dec = rows * new_tokens * (
        n_dec_layers * per_tok_layer + 2.0 * d * vocab_size
    )
    return enc + dec


def stamp_device_flops(ctx, flops: float, shape: str) -> None:
    """Accumulate an op's analytic-FLOPs estimate (and its dominant shape
    bucket) into ``ctx.tags["device_attr"]`` — the channel the agent's
    dispatch loop reads to feed ``device_flops_total{op,shape}`` and the
    ``device_mfu{op}`` gauge. No-op without a ctx (pure-op callers)."""
    if ctx is None or not hasattr(ctx, "tags") or flops <= 0:
        return
    attr = ctx.tags.setdefault("device_attr", {})
    attr["flops"] = attr.get("flops", 0.0) + float(flops)
    attr["shape"] = str(shape)


def stamp_rows(ctx, rows: Any) -> None:
    """Accumulate the rows this task processed into the result's usage
    block (ISSUE 9) — the numerator of the showback report's rows column
    and swarmtop's rows/s sparkline. No-op without a ctx or a positive
    count (pure-op callers, empty shards)."""
    if ctx is None or not hasattr(ctx, "tags"):
        return
    if isinstance(rows, bool) or not isinstance(rows, int) or rows <= 0:
        return
    from agent_tpu.obs.usage import stamp_usage

    stamp_usage(ctx.tags, rows=rows)


def resolve_model_id(payload: Dict[str, Any], env_var: str, default: str) -> str:
    """payload ``model_path`` → env var → default (ref ``_tpu_runtime.py:23-31``)."""
    mp = payload.get("model_path")
    if isinstance(mp, str) and mp:
        return mp
    return os.environ.get(env_var) or default


def config_from_payload(payload: Dict[str, Any], config_cls):
    """Build ``config_cls`` applying any recognized ``model_config`` overrides."""
    overrides = payload.get("model_config")
    if isinstance(overrides, dict):
        allowed = {
            k: v for k, v in overrides.items()
            if k in config_cls.__dataclass_fields__
        }
        return config_cls(**allowed)
    return config_cls()


def apply_quant_env(payload: Dict[str, Any], cfg):
    """Quant-mode resolution shared by the model ops: payload
    ``model_config.quant`` wins; else ``TPU_QUANT`` env; else the config
    default.

    Error contract: a bad *payload* value raises ValueError (→ soft
    bad_input, caller error); a bad *env* value raises RuntimeError — a
    worker deployment misconfig must fail the shard for retry/visibility,
    not soft-drop every task as caller error (same rule as the checkpoint
    integrity errors, ``models/bert.py`` from_hf_json).
    """
    from dataclasses import replace

    from agent_tpu.models.quant import validate_quant

    overrides = payload.get("model_config")
    if isinstance(overrides, dict) and "quant" in overrides:
        # Apply the payload value here, self-contained — not via the family
        # override whitelists (a whitelist that forgot "quant" would
        # otherwise silently serve unquantized while this "validated" the
        # default).
        return replace(cfg, quant=validate_quant(overrides["quant"]))
    env = os.environ.get("TPU_QUANT", "").strip().lower()
    if env:
        try:
            return replace(cfg, quant=validate_quant(env))
        except ValueError as exc:
            raise RuntimeError(f"bad TPU_QUANT env: {exc}") from exc
    return cfg


def maybe_quantize_params(params, family: str, cfg):
    """The shared quantized-mode build-time transform gate (guard +
    dispatch), so the two model ops cannot drift. Covers both execution
    modes — ``int8`` (W8A8, the encoder mode) and ``w8a16`` (weight-only,
    the decode mode). Host-side quantization BEFORE HBM placement: the int8
    tables — 4× smaller than f32 — are what transfer and stay resident
    (``models.quant``)."""
    mode = getattr(cfg, "quant", "none")
    from agent_tpu.models.quant import QUANTIZED_MODES

    if mode in QUANTIZED_MODES:
        from agent_tpu.models.quant import quantize_for_family

        return quantize_for_family(family, params, mode)
    return params


def maybe_quantize_specs(specs, family: str, cfg):
    """Spec-tree twin of :func:`maybe_quantize_params`: the quantized tree
    has ``{"w_q", "w_scale"}`` (int8) or ``{"w8", "w_scale"}`` (w8a16)
    leaves, so tp placement specs transform the same paths."""
    mode = getattr(cfg, "quant", "none")
    from agent_tpu.models.quant import QUANTIZED_MODES

    if mode in QUANTIZED_MODES:
        from agent_tpu.models.quant import quantize_specs_for_family

        return quantize_specs_for_family(family, specs, mode)
    return specs


def _fuses_qkv(family: str, cfg, tp: int) -> bool:
    """Is ``[Q | K | V]`` the right column order for this placement? By what
    the build can see: the in-house encoder's bias-free projections (the
    pretrained ``bert`` family has its own, with biases), unquantized leaves
    (quantized ones keep [d, H, E] tables and the dense path), and no ``tp``
    axis to split by heads (``P(None, "tp")`` on the fused columns would
    hand a chip all of Q and none of V)."""
    return (family == "encoder" and getattr(cfg, "quant", "none") == "none"
            and tp <= 1)


def maybe_fuse_qkv_params(params, family: str, cfg, tp: int):
    """Build-time twin of :func:`maybe_quantize_params` for the SERVING
    layout of a block's self-attention projections: ``wq``, ``wk``, ``wv``
    become ONE leaf ``wqkv`` (``models.layers.fuse_qkv``: same bytes, same
    stored dtype) where :func:`_fuses_qkv` says so, once a model, outside
    any program; ``layers.attention`` then reads a block's activations once
    (one matmul for the three). Elsewhere the tree is returned as built.

    IN PLACE, block by block: the tree is the build's own, and a block's
    three leaves are let go as its fused leaf is made, so the device never
    holds a model's projections twice (12 tenants' builds would each peak
    85 MB over what stays resident)."""
    if not _fuses_qkv(family, cfg, tp):
        return params
    from agent_tpu.models.layers import fuse_qkv

    for block in params["blocks"]:
        block["attn"] = fuse_qkv(block["attn"])
    return params


def maybe_fuse_qkv_specs(specs, family: str, cfg, tp: int):
    """Spec-tree twin of :func:`maybe_fuse_qkv_params`, so ``get_params``'
    trees stay congruent where ``ep`` > 1 places by specs: the fused leaf
    replicates (it exists only where no ``tp`` axis splits it)."""
    if not _fuses_qkv(family, cfg, tp):
        return specs
    from jax.sharding import PartitionSpec as P

    def fuse(attn):
        kept = {k: s for k, s in attn.items() if k not in ("wq", "wk", "wv")}
        return {**kept, "wqkv": P()}

    return {**specs, "blocks": [
        {**block, "attn": fuse(block["attn"])} for block in specs["blocks"]]}


def cfg_key(cfg) -> Tuple:
    """Hashable fingerprint of a frozen config dataclass — goes into both the
    params-store key and the executable-cache key so distinct configs never
    alias (two payloads with different ``model_config`` must get distinct
    weights and distinct compiled programs)."""
    return tuple((f.name, getattr(cfg, f.name)) for f in fields(cfg))


def batch_buckets(dp: int, cap: int) -> List[int]:
    """Batch-size buckets dp, 2·dp, … ≤ cap, so the batch dim always divides
    the mesh ``dp`` axis and the executable cache stays small."""
    out, b = [], max(1, dp)
    while b <= cap:
        out.append(b)
        b *= 2
    return out or [max(1, dp)]


# Device-dispatch chunk budget (rows × padded length) for DENSE-attention
# shapes. The dense path materializes [B, H, L, L] score temps, which grow
# with the rows of a program; chunks dispatch back-to-back, so the split
# costs no extra host↔device round trips. Whether 131k tokens a program is
# still the best size now that the whole-row kernel holds the scores in
# VMEM: not measured on the present tree (PERF.md §7, "one 512 x 512
# program a long shard"; `bert-base.drain-long` would show it).
# Flash-path lengths (``kernels.flash_attention.selects_flash``)
# stream their scores through VMEM and keep the large-batch grid.
DENSE_CHUNK_TOKENS = 131_072


def chunk_token_budget() -> int:
    env = os.environ.get("TPU_CHUNK_TOKENS", "").strip()
    return int(env) if env else DENSE_CHUNK_TOKENS


def split_padded_chunk(ids, lengths, n: int, dp: int) -> List[Tuple]:
    """Split one padded ``(ids [B, L], lengths [B], n_real)`` staging chunk
    into device-dispatch slices of at most :func:`chunk_token_budget` tokens.

    The slice size is the largest batch bucket (power-of-two multiple of
    ``dp``) within budget, so every slice's batch dim still divides the mesh
    and the executable cache sees ONE shape for all full slices. ``B`` is
    itself a bucket, so the slice size always divides it exactly. Slices
    holding only padding rows are dropped.
    """
    from agent_tpu.kernels.flash_attention import selects_flash

    B, L = ids.shape
    budget = chunk_token_budget()
    if selects_flash(L) or B * L <= budget:
        return [(ids, lengths, n)]
    rows = max(1, budget // L)
    cap = max(1, dp)
    while cap * 2 <= rows:
        cap *= 2
    if cap >= B:
        return [(ids, lengths, n)]
    out: List[Tuple] = []
    for s in range(0, B, cap):
        n_i = min(n - s, cap)
        if n_i <= 0:
            break
        out.append((ids[s:s + cap], lengths[s:s + cap], n_i))
    return out


# ---- sequence packing: short rows share a program row ----
#
# A chunk padded to the length bucket of its longest row computes on every
# slot; where the rows are short most slots are padding (rows of 8-64 bytes,
# median 28, in a bucket of 64: 49 % real). Packed, several rows lie end to
# end in one program row of the SAME length under a segment mask
# (``models/encoder.py: pooled_segments``), and the chunk goes out as slices
# of ONE fixed number of program rows: one executable whatever the rows'
# lengths, the last slice filled with empty program rows.

# Token slots of one packed slice program (program rows x length). From chip
# runs of the BERT-base encoder on `bert-base.drain-short` (512 rows of 8-64
# bytes, length 64; `drain_rows_per_s`, my chip runs, PR 29): slices of 128
# program rows 19.4-19.6 k, of 64 rows 23.1-23.4 k, of 32 rows 23.2-23.6 k,
# of 16 rows 23.3 k (padded: 13.4 k). Smaller slices waste less of the last
# one but cost a program each: 64 rows ties 32 within the run-to-run spread
# at half the dispatches (PERF.md section 5).
PACKED_SLICE_TOKENS = 4096
# The shortest row that still fills its share of a program row: a program
# row of length L holds at most L // PACKED_MIN_SEGMENT rows, so a slice has
# PACKED_SLICE_TOKENS // PACKED_MIN_SEGMENT segment slots at every length.
PACKED_MIN_SEGMENT = 8


class PackedChunk(NamedTuple):
    """One staging chunk in the packed layout: what goes over the wire to
    the device, and the way back to the chunk's own row order."""

    ids: Any              # [slices * slice_rows, L] wire dtype: rows end to end
    segment_lengths: Any  # [slices * slice_rows, G] int32: their token counts
    row_slots: Any        # [B] int32: program row * G + segment of chunk row r
    n: int                # real rows of the chunk (the rest of B is padding)
    slice_rows: int       # program rows of one slice program


def packed_slice_rows(length: int, dp: int) -> int:
    """Program rows of one packed slice at ``length``: the power-of-two
    multiple of ``dp`` (so the slice's rows divide the mesh, as batch
    buckets do) nearest under ``PACKED_SLICE_TOKENS`` token slots."""
    rows = max(1, dp)
    while rows * 2 * length <= PACKED_SLICE_TOKENS:
        rows *= 2
    return rows


def pack_rows(lengths: Sequence[int], capacity: int, max_segments: int
              ) -> Tuple[List[int], List[int], int]:
    """Best-fit-decreasing bin packing of rows into program rows of
    ``capacity`` token slots and at most ``max_segments`` rows each; a row
    is never split. Returns ``(program row of row r, its segment index
    there, program rows used)``. Deterministic: the same lengths give the
    same pack (longest first, ties in row order; a row goes where the least
    room is left that still holds it).

    Pure Python on purpose, and O(rows): ``open_at`` is a bit set of the
    free-room values some open program row has, so "least room that fits" is
    one shift and one lowest-set-bit."""
    order = sorted(range(len(lengths)), key=lambda r: -int(lengths[r]))
    where, segment = [0] * len(order), [0] * len(order)
    by_room: List[List[int]] = [[] for _ in range(capacity + 1)]
    count: List[int] = []
    open_at = 0
    for r in order:
        need = int(lengths[r])
        if not 0 <= need <= capacity:
            raise ValueError(f"row of {need} tokens in a row of {capacity}")
        fits = open_at >> need
        if fits:
            room = need + (fits & -fits).bit_length() - 1
            rows_here = by_room[room]
            b = rows_here.pop()
            if not rows_here:
                open_at &= ~(1 << room)
        else:
            room, b = capacity, len(count)
            count.append(0)
        where[r], segment[r] = b, count[b]
        count[b] += 1
        room -= need
        if count[b] < max_segments:
            by_room[room].append(b)
            open_at |= 1 << room
    return where, segment, len(count)


def pack_padded_chunk(ids, lengths, n: int, dp: int) -> Optional[PackedChunk]:
    """The packed layout of one padded ``(ids [B, L], lengths [B], n_real)``
    staging chunk, or ``None`` where the chunk stays padded: THE predicate of
    sequence packing, on what staging can see of the chunk (its length
    bucket, its rows, their lengths) and nothing else. It packs only where
    the slices dispatched hold FEWER token slots than the padded chunk
    would; a chunk whose rows fill their bucket is answered ``None`` and runs
    the padded program under the padded program's executable key. Streaming
    (flash) lengths stay padded too: a block-diagonal mask has no streaming
    kernel.

    The wire stays narrow: the ids in the dtype they have (raw bytes stay
    uint8), one int32 a segment slot, one int32 a chunk row."""
    import numpy as np

    from agent_tpu.kernels.flash_attention import selects_flash

    B, L = ids.shape
    if n <= 0 or selects_flash(L):
        return None
    rows = packed_slice_rows(L, dp)
    real = np.minimum(np.asarray(lengths[:n], dtype=np.int64), L)
    # The fewest slices any pack could need: where even that many hold no
    # fewer slots than the padded chunk, there is nothing to compute.
    if -(-int(real.sum()) // (L * rows)) * rows >= B:
        return None
    segments = max(1, L // PACKED_MIN_SEGMENT)
    where, segment, used = pack_rows(real.tolist(), L, segments)
    total = -(-used // rows) * rows
    if total >= B:
        return None
    where_a = np.asarray(where, dtype=np.int64)
    segment_a = np.asarray(segment, dtype=np.int64)
    seg_lengths = np.zeros((total, segments), dtype=np.int32)
    seg_lengths[where_a, segment_a] = real
    # A row's first slot: the tokens of the segments before it in its
    # program row (segments are numbered in the order they were placed).
    starts = np.cumsum(seg_lengths, axis=1) - seg_lengths
    first = where_a * L + starts[where_a, segment_a]
    token = np.arange(L)[None, :] < real[:, None]             # [n, L]
    packed = np.zeros((total, L), dtype=ids.dtype)
    packed.reshape(-1)[(first[:, None] + np.arange(L)[None, :])[token]] = (
        ids[:n][token])
    row_slots = np.zeros(B, dtype=np.int32)
    row_slots[:n] = where_a * segments + segment_a
    return PackedChunk(packed, seg_lengths, row_slots, n, rows)


def iter_chunks(seqs: Sequence, max_chunk: int) -> Iterator[Sequence]:
    """Slice an oversize batch into ≤ max_chunk pieces — rows beyond the top
    batch bucket run as extra device calls instead of overflowing ``pad_batch``
    (which would allocate fewer rows than sequences and crash)."""
    for i in range(0, len(seqs), max_chunk):
        yield seqs[i : i + max_chunk]


def resolve_runtime(ctx):
    """The runtime this op will execute on, or ``None`` when no backend is
    available. A host-side metadata read — never initializes device state
    beyond what the runtime singleton already did."""
    try:
        if ctx is not None and getattr(ctx, "require_runtime", None):
            return ctx.require_runtime()
        from agent_tpu.runtime.runtime import get_runtime

        return get_runtime()
    except Exception:  # noqa: BLE001 — no backend
        return None


def resolve_dp(ctx) -> int:
    """The mesh ``dp`` extent the op's batches must divide — a host-side
    metadata read. The pipeline always injects a built runtime; standalone
    calls resolve the singleton here, on the owning thread. No backend at
    all ⇒ 1, matching the degraded CPU path's shapes."""
    rt = resolve_runtime(ctx)
    return rt.axis_size("dp") if rt is not None else 1


def length_buckets_for(max_len: int) -> List[int]:
    """Length buckets capped at ``max_len`` (never exceeding the model's
    position table), with ``max_len`` itself as the top bucket when the
    standard powers of two don't reach it — so a full-length row is always
    representable instead of silently truncating to the largest power."""
    from agent_tpu.models.tokenizer import DEFAULT_BUCKETS

    buckets = [b for b in DEFAULT_BUCKETS if b < max_len]
    buckets.append(max_len)
    return buckets


def stage_text_chunks(
    dp: int,
    texts: Sequence[str],
    *,
    max_len: int,
    vocab_size: int,
    max_batch: int,
    add_bos: bool = False,
    add_eos: bool = False,
    encode_pad=None,
    split_for_dispatch: bool = False,
    pack_short_rows: bool = False,
) -> List[Tuple]:
    """Pure host: tokenize+pad ``texts`` into device-ready
    ``[(ids[B, L] wire-dtype, lengths[B] int32, n_real_rows), ...]`` chunks —
    the shared staging scaffolding of both model ops and both vocab families.

    ``encode_pad(chunk, length_buckets, batch_buckets) -> (ids, lengths)``
    supplies the tokenizer (e.g. a checkpoint's wordpiece vocab); the default
    is the fused byte path (``byte_encode_pad``).

    Host→device traffic is a per-task tax (its share of a shard's time is
    not measured on a directly attached chip): ship the narrowest exact
    encoding + one length per row and let the compiled program rebuild int32
    ids and the [B, L] mask on device. Wire dtypes, narrowest first:

    - uint8 **unshifted bytes** — byte-vocab path with no BOS/EOS: exact
      reconstruction is ``(raw + N_SPECIAL) * mask`` (see
      ``tokenizer.byte_encode_pad(raw_uint8=True)``); uint8 on this wire
      ALWAYS means shifted-raw — real id arrays never stage as uint8.
    - uint16 ids — any vocab < 2^16 (wordpiece/BPE/byte-with-specials).
    - int32 ids — vocabs past 2^16 (none in-repo today).

    Length buckets come from :func:`length_buckets_for`; batch buckets are
    multiples of ``dp`` so the batch dim always divides the mesh.

    ``pack_short_rows`` (the classify op alone asks for it, as it does for
    ``split_for_dispatch``; summarize's staging is untouched by it): a
    dispatch chunk whose rows leave most of their length bucket empty comes
    back as a :class:`PackedChunk` — several rows end to end in a program
    row of the same length, in slices of one fixed number of program rows —
    where :func:`pack_padded_chunk` says that dispatches fewer token slots;
    every other chunk stays the padded triple above.
    """
    import numpy as np

    from agent_tpu.models.tokenizer import N_SPECIAL, byte_encode_pad

    buckets = length_buckets_for(max_len)
    bbuckets = batch_buckets(dp, max_batch)
    wire_dtype = np.uint16 if vocab_size <= (1 << 16) else np.int32
    custom_encode = encode_pad is not None
    if encode_pad is None:
        # Raw-byte wire needs the byte ids 4..259 resident in the embedding
        # table; the byte tokenizer requires that of its models anyway.
        raw_u8 = (not add_bos and not add_eos
                  and vocab_size >= N_SPECIAL + 256)

        def encode_pad(chunk, lb, bb):
            return byte_encode_pad(
                chunk, buckets=lb, batch_buckets=bb,
                max_len_cap=max_len, add_bos=add_bos, add_eos=add_eos,
                raw_uint8=raw_u8,
            )
    chunks: List[Tuple] = []
    # Oversize batches run as extra device calls on the top bucket shape.
    for chunk in iter_chunks(texts, bbuckets[-1]):
        ids, lengths = encode_pad(chunk, buckets, bbuckets)
        if ids.dtype == np.uint8:
            # uint8 on this wire is an in-band sentinel meaning shifted-raw
            # bytes; only the internal byte path above may emit it. A custom
            # tokenizer returning uint8 real ids would be silently corrupted
            # by the device-side (+N_SPECIAL)*mask rebuild — reject it here.
            if custom_encode:
                raise TypeError(
                    "encode_pad returned uint8 ids: the uint8 wire is "
                    "reserved for the internal raw-byte path; return "
                    "int32/uint16 ids from custom tokenizers"
                )
        else:
            ids = ids.astype(wire_dtype)
        staged = [(ids, lengths, len(chunk))]
        if split_for_dispatch:
            # Dense-path dispatch budget (split_padded_chunk docstring):
            # slices dispatch back-to-back, fetched once, so the split is
            # free on the wire but keeps score temps at the budgeted
            # per-program size.
            staged = split_padded_chunk(*staged[0], dp)
        if pack_short_rows:
            staged = [pack_padded_chunk(*c, dp) or c for c in staged]
        chunks.extend(staged)
    return chunks


def validate_start_row(payload: Dict[str, Any]) -> int:
    """``start_row`` as a non-negative int (0 when absent); ValueError — the
    soft-error path — on anything else. Sink-mode shard files are named by
    it, so a bad value must fail validation, not generate garbage names."""
    raw = payload.get("start_row", 0)
    if raw is None:
        return 0
    if isinstance(raw, bool) or not isinstance(raw, int) or raw < 0:
        raise ValueError("start_row must be a non-negative int")
    return raw


def validate_output_uri(payload: Dict[str, Any]):
    """Optional result sink: ``output_uri`` names a local directory the op
    writes full per-row results to, posting only a small receipt back to the
    controller. The at-scale drain pattern (BASELINE.json 10M-row job): row
    payloads (10M summaries ≈ GBs) stream to disk next to the data instead of
    accumulating in controller memory and the result journal.

    Returns the validated directory (created if missing) or None; raises
    ValueError (→ soft bad_input) when unusable.
    """
    uri = payload.get("output_uri")
    if uri is None:
        return None
    if not isinstance(uri, str) or not uri:
        raise ValueError("output_uri must be a non-empty directory path")
    try:
        os.makedirs(uri, exist_ok=True)
    except OSError as exc:
        raise ValueError(f"output_uri not creatable: {exc}") from exc
    if not os.path.isdir(uri) or not os.access(uri, os.W_OK):
        raise ValueError(f"output_uri not a writable directory: {uri}")
    return uri


def write_output_shard(
    output_dir: str, op: str, start_row: int, rows: Iterator[Dict[str, Any]]
) -> Tuple[str, int]:
    """Write one shard's rows as JSONL → (path, n_rows). Line ``k`` holds
    absolute dataset row ``start_row + k``.

    Atomic (tmp + ``os.replace``) so a controller retry of the same shard
    (idempotent shard addressing, SURVEY.md §5.4) can never leave a torn
    file — the retry simply rewrites the identical content.
    """
    import json

    path = os.path.join(output_dir, f"{op}_rows_{start_row:012d}.jsonl")
    tmp = f"{path}.tmp.{os.getpid()}"
    n = 0
    with open(tmp, "w") as f:
        for row in rows:
            f.write(json.dumps(row, separators=(",", ":")))
            f.write("\n")
            n += 1
    os.replace(tmp, path)
    return path, n
