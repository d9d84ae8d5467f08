"""Benchmark harness — prints ONE JSON line with the headline metric.

Headline: rows/sec/chip on ``map_classify_tpu`` (the BASELINE.json north-star
metric; target ≥10,000 rows/sec/chip). Ops are measured end to end — host
tokenization, padding, device transfer, jitted forward, top-k — because that
is what a leased task pays; compile time is excluded by warmup (the executable
cache makes it a once-per-process cost, reference handle-singleton semantics).

Methodology: every throughput number is the **median of N measurement
windows** with the min→max spread recorded next to it (``spread_pct``), so a
lucky window can't inflate the trend line and a noisy one can't hide.

Legs (the ``legs`` object in the output line):

- ``flagship``     — classify at the default serving config (the r01/r02
                     trend line; BASELINE.json north star ≥10k rows/s/chip).
- ``bert_base``    — classify at the BERT-base scale BASELINE.json names
                     (d_model 768 / 12 layers / 12 heads / seq 512), with an
                     **mfu** field: achieved FLOP/s ÷ the chip's peak bf16
                     FLOP/s (looked up from device_kind, override with
                     ``BENCH_PEAK_TFLOPS``).
- ``bert_base_int8`` — the same BERT-base leg under
                     ``model_config {"quant": "int8"}`` (W8A8, models/quant.py)
                     with the speedup over bf16 and the top-1 agreement rate
                     vs bf16 on a diverse 512-row batch.
- ``long_ctx``     — classify over 4k-token documents. The warmup *proves*
                     the compiled program contains the Pallas flash kernel by
                     diffing the kernel's trace-time selection counters
                     (``kernels.flash_attention.SELECTION_COUNTS``); it also
                     records a dense-vs-flash model-level speedup ratio.
- ``summarize``    — greedy decode tokens/sec at the serving config.
- ``csv_index``    — cold CSV index build MB/s (the C++/Python scanner).
- ``drain``        — controller→HTTP→agent drain of a sharded CSV through the
                     **pipelined** runner (host-side double buffering), both
                     classify-only (comparable to the pure-op number) and
                     **mixed classify+summarize** (the BASELINE.json north-star
                     job shape at bench scale).
- ``drain_multichip`` — the swarm across N chips (ISSUE 7): a fleet of N
                     device-pinned agent subprocesses and a dp=N mesh agent
                     drain the same sharded job on the forced-host CPU smoke
                     shape, bit-identical to the 1-chip reference, with
                     ``scaling_efficiency`` = rows/sec at N ÷ N·rows/sec at 1
                     (asserted ≥ 0.8 when the host has ≥ N cores).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time

# Measurement configuration — single definitions shared by the bench
# functions and the bench_params field in the output line, so the recorded
# config can never drift from the executed one.
WINDOWS = 3
NOISY_WINDOWS = 5  # flagship + long-ctx legs (see main)
FLAGSHIP_BATCH = 8192
FLAGSHIP_ITERS = 10
# 4096-row payloads dispatch as 16 back-to-back 256-row device programs
# (ops._model_common.split_padded_chunk) — the measured v5e sweet spot for
# dense seq-512 attention — with ONE deferred fetch, so the host↔device
# round trip amortizes over the whole payload.
BERT_BATCH = 4096
BERT_ITERS = 2
BERT_CONFIG = {
    "d_model": 768, "n_heads": 12, "n_layers": 12, "d_ff": 3072,
    "max_len": 512,
}
LONG_CTX_BATCH = 128
LONG_CTX_ITERS = 5
# d_head = 128 (d_model/n_heads): the flash kernel's matmuls carry the head
# dim on the MXU contraction, so d_head < 128 underfills the systolic array —
# measured on v5e: 15 TF/s at d_head 32 vs 68 TF/s at d_head 128. Long-context
# configs in this framework keep d_head at the MXU tile width.
LONG_CTX_CONFIG = {"d_model": 512, "n_heads": 4, "max_len": 4096}
SUMMARIZE_BATCH = 256
SUMMARIZE_MAX_NEW = 32
# Quantization-fidelity sample size (rows) for the agreement numbers that
# ride next to the int8/w8a16 throughput legs. 512 rows put the one-sided
# 95% CI for "agreement ≥ 0.99" at ~±0.9 points — too loose for a headline;
# ≥5k rows tightens it below ±0.3 (round-4 ask #4).
AGREEMENT_ROWS = 5120
# Batch 128 + remat-free is the measured optimum now that the trainable
# flash kernel gates at 512 (FLASH_TRAIN_MIN_KEY_LEN): no stored score
# tensors OR block activations. Swept on v5e: 128/none 308 ex/s (45.3%
# MFU) > 256/full-remat 246 (36.2%) > 512/full 230; 256/none OOMs.
TRAIN_BATCH = 128
TRAIN_STEPS = 8
DRAIN_ROWS = 65_536
DRAIN_SHARD_SIZE = 8192
DRAIN_SUMMARIZE_ROWS = 16_384
# Multi-chip drain leg (ISSUE 7): N device-pinned agent subprocesses (and a
# dp=N mesh agent) drain the same sharded job on the forced-host CPU smoke
# shape — the scaling demonstration runs on virtual chips so the leg is
# recordable on any host; real-TPU fleets use scripts/fleet.py directly.
MULTICHIP_AGENTS = 4
MULTICHIP_ROWS = 16_384
MULTICHIP_SHARD = 512
MULTICHIP_MODEL = {
    "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
    "max_len": 64, "dtype": "float32", "n_classes": 16,
}
# Near-linear bar: rows/sec at N agents ≥ 0.8 · N · rows/sec at 1 agent.
# Asserted only when the host has at least one core per agent — on fewer
# cores the fleet can only conserve throughput, and "0.25 at 4 agents on 1
# core" is the expected physics, not a regression.
MULTICHIP_SCALING_FLOOR = 0.8
# Summarize throughput scales with decode rows in flight: measured 4,980 /
# 6,588 / 7,779 / 8,093 rows/s at payload 1k/2k/4k/8k (chained ≤1024-row
# programs at the time), 9,132 as ONE B=8192 program — per-step decode
# matmuls are [B, d_model]-thin, so only batch fills the MXU (see
# ops/map_summarize.MAX_DECODE_ROWS).
DRAIN_SUMMARIZE_SHARD = 8192

# Peak dense bf16 FLOP/s by device_kind (public spec sheets); MFU is achieved
# model FLOP/s over this. Unknown kinds record mfu=null rather than guess.
PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v5": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def _peak_flops(runtime):
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env) * 1e12
    kind = getattr(runtime.devices[0], "device_kind", "")
    tf = PEAK_BF16_TFLOPS.get(kind)
    return tf * 1e12 if tf else None


def encoder_flops_per_row(cfg, seq_len: int) -> float:
    """Analytic forward FLOPs for one row at padded length ``seq_len``
    (matmul terms only — 2·M·N·K per matmul; elementwise is noise):
    QKVO projections + score/value matmuls + FFN, summed over layers."""
    d, f, L = cfg.d_model, cfg.d_ff, seq_len
    attn_proj = 8 * L * d * d          # 4 projections × 2·L·d·d
    attn_sdpa = 4 * L * L * d          # QKᵀ and P·V × 2·L²·d
    ffn = 4 * L * d * f                # 2 matmuls × 2·L·d·f
    return cfg.n_layers * (attn_proj + attn_sdpa + ffn) + 2 * d * cfg.n_classes


def _median_windows(run_window, windows: int):
    """run_window() -> (rows_per_sec, p50_ms); returns the median-rate window
    plus the min→max spread as a percentage of the median."""
    samples = [run_window() for _ in range(windows)]
    rates = sorted(s[0] for s in samples)
    med = statistics.median(rates)
    spread = (rates[-1] - rates[0]) / med * 100.0 if med else 0.0
    # p50 latency reported from the median-rate window.
    p50 = min(samples, key=lambda s: abs(s[0] - med))[1]
    return med, p50, spread


def _bench_classify_leg(runtime, *, batch: int, text_len: int, iters: int,
                        windows: int = WINDOWS, model_config=None):
    """One classify throughput leg → dict. Texts are ~text_len bytes so the
    byte tokenizer lands them in the bucket the leg targets."""
    from agent_tpu.ops import get_op
    from agent_tpu.runtime.context import OpContext

    classify = get_op("map_classify_tpu")
    ctx = OpContext(runtime=runtime)
    texts = [
        ("sample record %06d " % i) * max(1, text_len // 20)
        for i in range(batch)
    ]
    payload = {"texts": texts, "topk": 5, "allow_fallback": False}
    if model_config:
        payload["model_config"] = dict(model_config)

    out = classify(payload, ctx)  # warmup: tokenize + compile + run
    assert out["ok"] is True and out.get("fallback") is None, out

    def window():
        lat = []
        t0 = time.perf_counter()
        for _ in range(iters):
            it0 = time.perf_counter()
            o = classify(payload, ctx)
            lat.append(time.perf_counter() - it0)
        wall = time.perf_counter() - t0
        assert o["ok"] is True, o
        lat.sort()
        return batch * iters / wall, lat[len(lat) // 2] * 1000.0

    rows_per_sec, p50_ms, spread = _median_windows(window, windows)
    return {
        "rows_per_sec": round(rows_per_sec, 1),
        "p50_batch_ms": round(p50_ms, 2),
        "spread_pct": round(spread, 2),
        "windows": windows,
        "batch": batch,
    }


def _bench_bert_base(runtime):
    """BERT-base-scale classify (BASELINE.json configs[2]) with an MFU figure."""
    from agent_tpu.models.encoder import EncoderConfig
    from agent_tpu.models.tokenizer import DEFAULT_BUCKETS, bucket_length

    smoke = runtime.platform != "tpu"
    batch = 64 if smoke else BERT_BATCH
    iters = 1 if smoke else BERT_ITERS
    windows = 1 if smoke else WINDOWS
    text_len = 480
    # quant pinned: a fleet-wide TPU_QUANT=int8 env must not silently turn
    # the bf16 reference leg (and the int8 leg's agreement baseline) int8.
    leg = _bench_classify_leg(
        runtime, batch=batch, text_len=text_len, iters=iters,
        windows=windows, model_config={**BERT_CONFIG, "quant": "none"},
    )
    cfg = EncoderConfig(**BERT_CONFIG)
    seq = bucket_length(text_len, [b for b in DEFAULT_BUCKETS
                                   if b <= cfg.max_len])
    flops_row = encoder_flops_per_row(cfg, seq)
    # rows_per_sec is whole-mesh throughput; peak is one chip's — normalize.
    achieved = leg["rows_per_sec"] * flops_row / runtime.n_devices
    peak = _peak_flops(runtime)
    n_params = (
        cfg.vocab_size * cfg.d_model
        + cfg.n_layers * (4 * cfg.d_model**2 + 2 * cfg.d_model * cfg.d_ff)
        + cfg.d_model * cfg.n_classes
    )
    leg.update(
        seq_len=seq,
        params_m=round(n_params / 1e6, 1),
        gflops_per_row=round(flops_row / 1e9, 2),
        achieved_tflops=round(achieved / 1e12, 2),
        mfu=round(achieved / peak, 4) if peak else None,
    )
    return leg


MOE_EXPERTS = 8


def _bench_moe(runtime):
    """Switch-MoE encoder served through ``map_classify_tpu`` — the EP
    capability (SURVEY §2.8, `models/moe.py`) as a recorded throughput
    number beside the dense legs: BERT-base width with every FFN replaced
    by an 8-expert top-1 MoE (8× the FFN parameters, ~dense activated
    FLOPs per token + routing). Single chip ⇒ experts unsharded; the ep>1
    placement itself is proven in tests/dryrun, this leg prices the
    routed-execution overhead."""
    smoke = runtime.platform != "tpu"
    cfg = {
        **BERT_CONFIG, "moe_experts": MOE_EXPERTS,
        "quant": "none",
    } if not smoke else {
        "d_model": 64, "n_heads": 4, "n_layers": 2, "d_ff": 128,
        "max_len": 64, "moe_experts": 4, "quant": "none",
    }
    try:
        leg = _bench_classify_leg(
            runtime,
            batch=64 if smoke else 1024,
            text_len=480,
            iters=1 if smoke else BERT_ITERS,
            windows=1 if smoke else WINDOWS,
            model_config=cfg,
        )
    finally:
        # The 8-expert tree is ~2 GB resident; later legs (train at batch
        # 128, summarize) need that HBM back — measured RESOURCE_EXHAUSTED
        # without this, and a FAILED leg must release it too. Earlier legs'
        # models re-transfer on their next use.
        runtime.clear_params()
    leg["moe_experts"] = cfg["moe_experts"]
    return leg


def _bench_bert_base_int8(runtime, bf16_leg):
    """BERT-base classify with ``model_config {"quant": "int8"}`` (W8A8,
    models/quant.py) — the reference's INT8 device story as an execution
    mode. Records the speedup over the bf16 leg at the same batch and the
    top-1 agreement rate vs bf16 on a diverse batch (the quantization
    fidelity number next to the throughput number)."""
    import numpy as np

    from agent_tpu.ops import get_op
    from agent_tpu.runtime.context import OpContext

    smoke = runtime.platform != "tpu"
    batch = 64 if smoke else BERT_BATCH
    iters = 1 if smoke else BERT_ITERS
    windows = 1 if smoke else WINDOWS
    leg = _bench_classify_leg(
        runtime, batch=batch, text_len=480, iters=iters, windows=windows,
        model_config={**BERT_CONFIG, "quant": "int8"},
    )
    if bf16_leg and bf16_leg.get("rows_per_sec"):
        leg["speedup_vs_bf16"] = round(
            leg["rows_per_sec"] / bf16_leg["rows_per_sec"], 3
        )

    # Top-1 agreement on a diverse batch: per-row distinct content so the
    # argmax isn't one degenerate class. Same texts through both modes.
    classify = get_op("map_classify_tpu")
    ctx = OpContext(runtime=runtime)
    rng = np.random.default_rng(7)
    words = ["alpha", "risk", "ledger", "breach", "routine", "audit",
             "wire", "flag", "normal", "urgent", "invoice", "metric"]
    texts = [
        " ".join(rng.choice(words, size=60).tolist()) + f" case {i}"
        for i in range(AGREEMENT_ROWS if not smoke else 64)
    ]
    payload = {"texts": texts, "topk": 1, "allow_fallback": False,
               "result_format": "columnar",
               "model_config": {**BERT_CONFIG, "quant": "none"}}
    ref = classify(payload, ctx)
    q = classify({**payload,
                  "model_config": {**BERT_CONFIG, "quant": "int8"}}, ctx)
    assert ref["ok"] is True and q["ok"] is True, (ref, q)
    top1_ref = np.asarray(ref["indices"])[:, 0]
    top1_q = np.asarray(q["indices"])[:, 0]
    leg["agreement_top1"] = round(float((top1_ref == top1_q).mean()), 4)
    leg["agreement_rows"] = len(texts)
    return leg


def _bench_long_ctx(runtime):
    """4k-token classify that provably takes the Pallas flash path, plus a
    model-level dense-vs-flash timing ratio at the same sequence length."""
    import importlib

    # The kernels package re-exports the flash_attention FUNCTION, shadowing
    # the submodule attribute — resolve the module itself for the counters.
    fa = importlib.import_module("agent_tpu.kernels.flash_attention")

    if runtime.platform != "tpu":
        return {"skipped": "flash kernel only selected on real TPU"}

    before = dict(fa.SELECTION_COUNTS)
    leg = _bench_classify_leg(
        runtime, batch=LONG_CTX_BATCH, text_len=4000, iters=LONG_CTX_ITERS,
        model_config=LONG_CTX_CONFIG, windows=NOISY_WINDOWS,
    )
    flash_new = fa.SELECTION_COUNTS["flash"] - before["flash"]
    dense_new = fa.SELECTION_COUNTS["dense"] - before["dense"]
    # The compiled executable must contain the kernel on every layer's
    # attention — a silent dense fallback here is a bench failure, not noise.
    assert flash_new > 0 and dense_new == 0, (
        f"long-ctx leg did not take the flash path "
        f"(flash+{flash_new}, dense+{dense_new})"
    )
    leg["flash_selected"] = True
    leg["seq_len"] = 4096
    try:
        leg["flash_vs_dense_speedup"] = round(_flash_vs_dense(runtime), 2)
    except Exception as exc:  # noqa: BLE001 — ratio is informative, not vital
        leg["flash_vs_dense_error"] = f"{type(exc).__name__}: {exc}"[:200]
    try:
        # The 8k point, where the dense path's [L, L] score materialization
        # thrashes HBM — recorded so the kernel docstring's 8k headline is a
        # driver artifact, not prose (batch 2 keeps dense's scores in HBM).
        leg["flash_vs_dense_8k"] = round(
            _flash_vs_dense(runtime, batch=2, seq=8192), 2
        )
    except Exception as exc:  # noqa: BLE001
        leg["flash_vs_dense_8k_error"] = f"{type(exc).__name__}: {exc}"[:200]
    return leg


def _flash_vs_dense(runtime, batch: int = 4, seq: int = 4096):
    """Per-call attention time, dense XLA vs the Pallas kernel, at the
    long-ctx leg's shape. Small batch: the dense path materializes
    [B, H, L, L] scores in HBM (the kernel's whole advantage), which caps B
    at 4k ctx.

    Methodology: a single call's wall time includes the host→device round
    trip (not measured on a directly attached chip), not kernel time alone.
    Each path is timed as a ``fori_loop`` chaining N calls inside ONE
    program, synced by a scalar fetch; per-call = (t_21 − t_1) / 20."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from agent_tpu.kernels.flash_attention import flash_attention
    from agent_tpu.models.encoder import EncoderConfig
    from agent_tpu.models.layers import dot_product_attention

    cfg = EncoderConfig(**LONG_CTX_CONFIG)
    d_head = cfg.d_model // cfg.n_heads
    rng = np.random.default_rng(0)
    shape = (batch, cfg.n_heads, seq, d_head)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape), dtype=cfg.compute_dtype)
        for _ in range(3)
    )
    m = jnp.ones((batch, 1, 1, seq), dtype=jnp.int32)
    fetch = jax.jit(lambda o: jnp.sum(o[:1, :1, :8, :8]))

    def timed(attn, n, reps: int = 5):
        f = jax.jit(
            lambda q, k, v, m: jax.lax.fori_loop(
                0, n, lambda i, x: attn(x, k, v, m), q
            )
        )
        float(fetch(f(q, k, v, m)))  # compile
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(fetch(f(q, k, v, m)))
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    def per_call(attn):
        return (timed(attn, 21) - timed(attn, 1)) / 20

    flash = functools.partial(flash_attention, min_key_len=0)
    return per_call(dot_product_attention) / per_call(flash)


def _bench_train(runtime):
    """Training throughput at BERT-base scale: one jitted fwd+bwd+adamw step
    (models/train.py), examples/sec and training MFU (flops ≈ 3× forward).

    Steps chain on device (step i+1 consumes step i's params), so timing N
    dispatches and syncing once amortizes the host round trip the same way
    the flash ratio measurement does."""
    import jax
    import numpy as np

    from agent_tpu.models import encoder
    from agent_tpu.models.encoder import EncoderConfig
    from agent_tpu.models.train import make_train_step

    smoke = runtime.platform != "tpu"
    cfg = EncoderConfig(
        **(BERT_CONFIG if not smoke
           else {"d_model": 64, "n_heads": 4, "n_layers": 2, "d_ff": 128,
                 "max_len": 64})
    )
    batch = 32 if smoke else TRAIN_BATCH
    seq = 64 if smoke else 512
    steps = 2 if smoke else TRAIN_STEPS

    # Remat-free training at batch 128 budgets essentially the whole chip;
    # serving models resident from earlier legs would shave the headroom.
    runtime.clear_params()
    params = jax.device_put(
        encoder.init_params(cfg, model_id="bench-train"), runtime.replicated()
    )
    # remat=False when the TRAINING flash gate selects the kernel: its
    # backward stores no [B, H, L, L] scores, so at batch 128 the whole
    # backward fits without rematerialization — the measured optimum (see
    # TRAIN_BATCH note). selects_flash_train (not the attn_fn identity!)
    # also covers the mesh wrapper's dp/tp-divisibility dense fallback.
    # Off-TPU (dense path) the smoke shapes are tiny and need no remat; a
    # TPU run with pallas disabled keeps remat=True to avoid the ~39 GB
    # dense score store.
    import importlib

    fa = importlib.import_module("agent_tpu.kernels.flash_attention")
    from agent_tpu.models.layers import dot_product_attention

    attn_fn = runtime.train_attention_fn()
    flash_train = (
        attn_fn is not dot_product_attention
        and fa.selects_flash_train(
            seq, batch=batch, n_heads=cfg.n_heads, mesh=runtime.mesh
        )
    )
    init_state, step = make_train_step(
        cfg, remat=not (smoke or flash_train), attn_fn=attn_fn
    )
    opt_state = init_state(params)
    rng = np.random.default_rng(0)
    ids = runtime.put_batch(
        rng.integers(4, cfg.vocab_size, (batch, seq)).astype(np.int32)
    )
    mask = runtime.put_batch(np.ones((batch, seq), dtype=np.int32))
    labels = runtime.put_batch(
        rng.integers(0, cfg.n_classes, (batch,)).astype(np.int32)
    )

    # TWO warmup steps: the first compiles for the init-state avals, the
    # second for the steady-state ones (the returned opt_state's weak-typed
    # scalars become strong, which retriggers compilation exactly once).
    before_ft = fa.SELECTION_COUNTS.get("flash_train", 0)
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, ids, mask, labels)
        float(loss)
    if flash_train:
        # The remat=False decision above is only safe on the kernel path —
        # prove the compiled step actually contains it.
        assert fa.SELECTION_COUNTS.get("flash_train", 0) > before_ft, (
            "train leg disabled remat but the flash kernel was not selected"
        )

    def window():
        nonlocal params, opt_state
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, ids, mask, labels)
        final = float(loss)  # one sync for the chained steps
        wall = time.perf_counter() - t0
        assert final == final, "train loss is NaN"
        return batch * steps / wall, wall * 1000.0 / steps

    ex_per_sec, step_ms, spread = _median_windows(window, WINDOWS)
    flops_ex = 3 * encoder_flops_per_row(cfg, seq)  # fwd + ~2× for bwd
    achieved = ex_per_sec * flops_ex / runtime.n_devices
    peak = _peak_flops(runtime)
    return {
        "examples_per_sec": round(ex_per_sec, 1),
        "step_ms": round(step_ms, 2),
        "spread_pct": round(spread, 2),
        "batch": batch,
        "seq_len": seq,
        "gflops_per_example": round(flops_ex / 1e9, 2),
        "achieved_tflops": round(achieved / 1e12, 2),
        "mfu": round(achieved / peak, 4) if peak else None,
    }


# Batch 128 × seq 2048 = 262k tokens per step; batch 16 measured 8 points
# of MFU lower (too little work per dispatch), 256 adds nothing (405 vs
# 400 ex/s) for 2× the activation memory.
TRAIN_LONG_CTX_BATCH = 128
TRAIN_LONG_CTX_SEQ = 2048
TRAIN_LONG_CTX_STEPS = 4


def _bench_train_long_ctx(runtime):
    """Long-context training (seq 2048) through the DIFFERENTIABLE Pallas
    flash kernel — fwd and bwd both streaming, no [L, L] score matrices in
    HBM in either direction. Asserts the ``flash_train`` selection counter
    ticked and ``dense_train`` did not: the compiled train step provably
    contains the kernel pair, the same proof discipline as the serving
    ``long_ctx`` leg. This leg did not exist before the backward kernel —
    dense-backward training at 2k+ context OOMed or crawled."""
    import importlib

    import jax
    import numpy as np

    from agent_tpu.models import encoder
    from agent_tpu.models.encoder import EncoderConfig
    from agent_tpu.models.train import make_train_step

    fa = importlib.import_module("agent_tpu.kernels.flash_attention")
    if runtime.platform != "tpu":
        return {"skipped": "flash kernel only selected on real TPU"}

    cfg = EncoderConfig(**{**LONG_CTX_CONFIG, "max_len": TRAIN_LONG_CTX_SEQ})
    batch, seq, steps = (
        TRAIN_LONG_CTX_BATCH, TRAIN_LONG_CTX_SEQ, TRAIN_LONG_CTX_STEPS,
    )
    params = jax.device_put(
        encoder.init_params(cfg, model_id="bench-train-longctx"),
        runtime.replicated(),
    )
    before = dict(fa.SELECTION_COUNTS)
    # remat=False ON PURPOSE: the flash backward keeps [L, L] score
    # tensors out of HBM in both directions, so 262k tokens of activations
    # fit without rematerialization — measured 1.36× faster than the
    # remat step (400 vs 295 ex/s). The seq-512 train leg now does the
    # same (FLASH_TRAIN_MIN_KEY_LEN gates at 512). Disabling remat is only
    # safe on the kernel path, so consult the selection predicate (which
    # includes the mesh wrapper's dp/tp fallback) rather than assuming —
    # a dense fallback here would store 262k-token score tensors and OOM
    # before the post-warmup counter assert could explain why.
    if not fa.selects_flash_train(
        seq, batch=batch, n_heads=cfg.n_heads, mesh=runtime.mesh
    ):
        return {"skipped": "flash-train kernel not selectable on this mesh"}
    init_state, step = make_train_step(
        cfg, remat=False, attn_fn=runtime.train_attention_fn()
    )
    opt_state = init_state(params)
    rng = np.random.default_rng(0)
    ids = runtime.put_batch(
        rng.integers(4, cfg.vocab_size, (batch, seq)).astype(np.int32)
    )
    mask = runtime.put_batch(np.ones((batch, seq), dtype=np.int32))
    labels = runtime.put_batch(
        rng.integers(0, cfg.n_classes, (batch,)).astype(np.int32)
    )
    for _ in range(2):  # two warmups, same rationale as _bench_train
        params, opt_state, loss = step(params, opt_state, ids, mask, labels)
        float(loss)
    flash_new = fa.SELECTION_COUNTS.get("flash_train", 0) - before.get(
        "flash_train", 0
    )
    dense_new = fa.SELECTION_COUNTS.get("dense_train", 0) - before.get(
        "dense_train", 0
    )
    assert flash_new > 0 and dense_new == 0, (
        f"long-ctx train leg did not take the flash path "
        f"(flash_train+{flash_new}, dense_train+{dense_new})"
    )

    def window():
        nonlocal params, opt_state
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, ids, mask,
                                           labels)
        final = float(loss)
        wall = time.perf_counter() - t0
        assert final == final, "long-ctx train loss is NaN"
        return batch * steps / wall, wall * 1000.0 / steps

    ex_per_sec, step_ms, spread = _median_windows(window, WINDOWS)
    flops_ex = 3 * encoder_flops_per_row(cfg, seq)
    achieved = ex_per_sec * flops_ex / runtime.n_devices
    peak = _peak_flops(runtime)
    return {
        "examples_per_sec": round(ex_per_sec, 1),
        "step_ms": round(step_ms, 2),
        "spread_pct": round(spread, 2),
        "batch": batch,
        "seq_len": seq,
        "flash_train_selected": True,
        "gflops_per_example": round(flops_ex / 1e9, 2),
        "achieved_tflops": round(achieved / 1e12, 2),
        "mfu": round(achieved / peak, 4) if peak else None,
    }


SUMMARIZE_ITERS = 4


def _bench_summarize(runtime, batch: int = SUMMARIZE_BATCH,
                     max_new: int = SUMMARIZE_MAX_NEW,
                     iters: int = SUMMARIZE_ITERS, num_beams: int = 1,
                     quant: str = None):
    """Decode throughput through the op. ``num_beams=4`` is the reference's
    unconditional decode mode (``/root/reference/ops/map_summarize.py:57``;
    greedy is this framework's documented default-divergence) — the beam leg
    records what that output-quality parity costs. tok/s counts EMITTED
    tokens; beam explores num_beams× more decoder compute per emitted token.
    ``quant`` serves the mode via ``model_config`` ("w8a16" is the
    decode-targeted weight-only mode, models/quant.py)."""
    from agent_tpu.ops import get_op
    from agent_tpu.runtime.context import OpContext

    summarize = get_op("map_summarize")
    ctx = OpContext(runtime=runtime)
    payload = {
        "texts": ["a document to compress " * 20] * batch,
        "max_length": max_new,
        **({"num_beams": num_beams} if num_beams > 1 else {}),
        **({"model_config": {"quant": quant}} if quant else {}),
    }
    summarize(payload, ctx)  # warmup/compile

    # Several op calls per window, so one decode's host-device round trip
    # (not measured on a directly attached chip) does not set the variance.
    def window():
        t0 = time.perf_counter()
        for _ in range(iters):
            out = summarize(payload, ctx)
            assert out["ok"] is True, out  # a failed call must not be timed
        dt = time.perf_counter() - t0
        return batch * max_new * iters / dt, dt * 1000.0

    tok_per_sec, _, spread = _median_windows(window, WINDOWS)
    # Per-chip normalization like the classify flat field (ISSUE 15
    # satellite): real TPU legs engage the whole mesh; on host backends the
    # forced virtual devices share one CPU and are not chips.
    chips = runtime.n_devices if runtime.platform == "tpu" else 1
    leg = {"decode_tok_per_sec": round(tok_per_sec, 1),
           "tok_per_sec_per_chip": round(tok_per_sec / chips, 1),
           "n_chips_used": chips,
           "spread_pct": round(spread, 2), "windows": WINDOWS,
           "iters": iters, "num_beams": num_beams}
    if quant:
        leg["quant"] = quant
    return leg


def _w8a16_decode_agreement(runtime, num_beams: int = 4, max_new: int = 16):
    """Token/sequence agreement of W8A16 decode vs the bf16 reference over
    ≥``AGREEMENT_ROWS`` rows (smoke: 64) at the serving seq2seq config —
    the quantization-fidelity number next to the w8a16 throughput legs.

    Model-level on purpose: comparing emitted TOKEN arrays (not detokenized
    strings) makes the metric exact, and the op above already proves the
    serving contract. Chunked decode bounds the [B·K, H, T, D] cache HBM.

    ``agreement_control_token`` is the NO-QUANT control: the same bf16
    reference against the f32 decode of the SAME weights. Free-running
    decode on the bench's untrained deterministic-random model amplifies
    any perturbation (near-uniform next-token distributions + cascades), so
    the control prices that substrate noise — measured on CPU dev runs the
    bf16-vs-f32 control (0.976) disagrees MORE than w8a16-vs-bf16 (0.988):
    weight-only int8 adds no token flips beyond existing compute-dtype
    noise, which is the claim that matters. Judge agreement_token against
    the control, not against 1.0."""
    import jax
    import numpy as np
    from dataclasses import replace

    from agent_tpu.models import quant, seq2seq

    smoke = runtime.platform != "tpu"
    rows = 64 if smoke else AGREEMENT_ROWS
    chunk = 64 if smoke else 1024
    # Smoke shrinks the model like the other legs do (CPU beam-4 decode at
    # the serving config takes minutes/row-batch); TPU measures the real one.
    cfg = seq2seq.Seq2SeqConfig() if not smoke else seq2seq.Seq2SeqConfig(
        d_model=64, n_heads=4, n_enc_layers=1, n_dec_layers=1, d_ff=128,
        max_src_len=64, max_tgt_len=16, dtype="float32",
    )
    ctl_cfg = replace(
        cfg, dtype="float32" if cfg.dtype != "float32" else "bfloat16"
    )
    params = seq2seq.init_params(cfg, model_id="bench-w8a16-agree")
    qparams = quant.quantize_for_family("seq2seq", params, "w8a16")
    params = jax.device_put(params, runtime.replicated())
    qparams = jax.device_put(qparams, runtime.replicated())

    def make_gen(c):
        return jax.jit(
            lambda p, i, m: seq2seq.beam_generate(
                p, i, m, c, max_new, num_beams=num_beams,
            )
        )

    gen, gen_ctl = make_gen(cfg), make_gen(ctl_cfg)
    rng = np.random.default_rng(11)
    src_len = 32 if smoke else 64
    tok_match = ctl_match = tok_total = seq_match = 0
    for s in range(0, rows, chunk):
        n = min(chunk, rows - s)
        ids = rng.integers(4, cfg.vocab_size, size=(n, src_len)).astype(
            np.int32
        )
        mask = np.ones((n, src_len), dtype=np.int32)
        ref = np.asarray(gen(params, ids, mask)[0])
        got = np.asarray(gen(qparams, ids, mask)[0])
        ctl = np.asarray(gen_ctl(params, ids, mask)[0])
        tok_match += int((ref == got).sum())
        ctl_match += int((ref == ctl).sum())
        tok_total += ref.size
        seq_match += int((ref == got).all(axis=1).sum())
    return {
        "agreement_token": round(tok_match / tok_total, 4),
        "agreement_seq": round(seq_match / rows, 4),
        "agreement_control_token": round(ctl_match / tok_total, 4),
        "agreement_rows": rows,
        "agreement_num_beams": num_beams,
    }


def _bench_summarize_w8a16(runtime, greedy_ref, beam_ref):
    """W8A16 weight-only decode (models/quant.py wdense/wproj_*): the
    memory-bound recipe for [rows, d]-thin decode matmuls — int8-resident
    weights (half the bf16 HBM bytes) dequantized in-register, activations
    untouched, NO dynamic quantization pass. Records greedy and beam-4
    throughput, the ``w8a16_vs_bf16`` speedups vs the recorded bf16 legs,
    and token/sequence agreement over ≥``AGREEMENT_ROWS`` rows.

    Returns (greedy_leg, beam_leg); agreement fields ride on the beam leg
    (beam-4 is the reference's decode mode and the mode the speedup bar
    ≥1.15 targets)."""
    smoke = runtime.platform != "tpu"
    kw = dict(batch=8, max_new=8, iters=1) if smoke else {}
    leg = _bench_summarize(runtime, quant="w8a16", **kw)
    if not smoke and greedy_ref and greedy_ref.get("decode_tok_per_sec"):
        leg["w8a16_vs_bf16"] = round(
            leg["decode_tok_per_sec"] / greedy_ref["decode_tok_per_sec"], 3
        )
    beam = _bench_summarize(runtime, num_beams=4, quant="w8a16", **kw)
    if not smoke and beam_ref and beam_ref.get("decode_tok_per_sec"):
        beam["w8a16_vs_bf16"] = round(
            beam["decode_tok_per_sec"] / beam_ref["decode_tok_per_sec"], 3
        )
    beam.update(_w8a16_decode_agreement(runtime))
    return leg, beam


def _bench_csv_index(tmpdir: str, n_rows: int = 1_000_000, repeats: int = 3):
    """Index-build MB/s, best of ``repeats`` cold builds of a ~38 MB file.

    The memchr scanner builds at ~1 GB/s, so the file must be big enough to
    out-time the per-build constant costs, and best-of-N (fresh file per
    build ⇒ every build is index-cold, page-cache warm after the first)
    filters host-contention spikes the way the windowed legs do."""
    import shutil

    from agent_tpu.data.csv_index import CsvIndex

    src = os.path.join(tmpdir, "bench_rows_0.csv")
    with open(src, "w") as f:
        f.write("id,text,risk\n")
        for i in range(n_rows):
            f.write(f'{i},"record {i} with some text payload",{i % 97}\n')
    best = 0.0
    for r in range(repeats):
        # Fresh path per repeat: CsvIndex caches by (path, size, mtime), so a
        # copy keeps every build index-cold while the page cache stays warm.
        path = src if r == 0 else os.path.join(tmpdir, f"bench_rows_{r}.csv")
        if r > 0:
            shutil.copy(src, path)
        size_mb = os.path.getsize(path) / 1e6
        t0 = time.perf_counter()
        index = CsvIndex.for_file(path)  # fresh temp file ⇒ cold index build
        dt = time.perf_counter() - t0
        assert index.n_data_rows == n_rows, index.n_data_rows
        if r > 0:
            os.remove(path)
        best = max(best, size_mb / dt)
    os.remove(src)
    return best


def _drain_until_done(agent, controller, depth: int = 2, workers=None,
                      autotune=None, double_buffer=None) -> float:
    """Run the pipelined runner until the controller drains; returns the wall
    seconds to the drain moment (not thread-teardown time). ``workers``/
    ``autotune``/``double_buffer`` override the staging-pool config
    (ISSUE 6); None keeps the STAGE_* defaults."""
    from agent_tpu.agent.pipeline import PipelineRunner

    agent.running = True
    done = {}

    def watch():
        while not controller.drained():
            time.sleep(0.01)
        done["wall"] = time.perf_counter() - t0
        agent.running = False

    watcher = threading.Thread(target=watch, daemon=True)
    t0 = time.perf_counter()
    watcher.start()
    PipelineRunner(agent, depth=depth, workers=workers, autotune=autotune,
                   double_buffer=double_buffer).run()
    watcher.join(timeout=10)
    return done.get("wall", time.perf_counter() - t0)


def _bench_drain(runtime, n_rows: int = DRAIN_ROWS,
                 shard_size: int = DRAIN_SHARD_SIZE):
    """Framework-level drain: controller shards a CSV into tasks, one agent
    drains them over real HTTP through the pipelined runner — the
    BASELINE.json 10M-row drain shape at bench scale.

    Returns (classify_only_leg, mixed_leg): classify-only is the r01/r02
    trend line (directly comparable to the pure-op number — the double-
    buffering win shows up as drain ≈ pure-op); mixed adds summarize shards,
    the literal "classify+summarize job" of the north star."""
    import tempfile

    import requests

    from agent_tpu.agent.app import Agent
    from agent_tpu.config import AgentConfig, Config
    from agent_tpu.controller.core import Controller
    from agent_tpu.controller.server import ControllerServer

    def check_all_ok(controller):
        counts = controller.counts()
        assert counts.get("failed", 0) == 0, counts
        # Soft-failed shards are recorded SUCCEEDED — check result bodies
        # so a drain that classified nothing can't report throughput.
        bad = [
            r for r in controller.results().values()
            if not (isinstance(r, dict) and r.get("ok") is True)
        ]
        assert not bad, f"{len(bad)} shards returned non-ok results"

    classify_extra = {"text_field": "text", "allow_fallback": False,
                      "result_format": "columnar"}
    # bf16, NOT int8, on purpose: decode steps are [B, 256]-shaped matmuls,
    # small enough that W8A8's dynamic activation quantization costs more
    # than the MXU saves — measured 3,983 rows/s int8 vs 4,980 bf16 at
    # B=1024 through this op. int8's win is the big-matmul encoders
    # (BERT-base leg: 1.21×); the summarize levers are decode BATCH (4,980 →
    # 8,093 rows/s from B=1024 → 8192 — see DRAIN_SUMMARIZE_SHARD) and
    # W8A16 weight-only quant (no activation-quant pass, half the weight
    # HBM bytes — the summarize_w8a16 legs record it; the drain default
    # stays bf16 until a recorded w8a16 drain win justifies flipping it).
    summarize_extra = {"text_field": "text", "max_length": SUMMARIZE_MAX_NEW,
                       "allow_fallback": False}

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "drain.csv")
        with open(path, "w") as f:
            f.write("id,text,risk\n")
            for i in range(n_rows):
                f.write(f'{i},"drain record {i} with a payload of text",{i % 89}\n')

        from agent_tpu.config import SloConfig

        # SLO-judged drain (ISSUE 8): op-keyed objectives with a generous
        # p99 (bulk shards legitimately run seconds) so the health leg
        # records attainment/verdict without paging a healthy bench.
        controller = Controller(lease_ttl_sec=600.0, slo=SloConfig(spec=(
            '[{"name": "classify", "op": "map_classify_tpu",'
            ' "p99_ms": 600000, "availability": 0.999},'
            ' {"name": "summarize", "op": "map_summarize",'
            ' "p99_ms": 600000, "availability": 0.999}]'
        )))
        with ControllerServer(controller) as server:
            cfg = Config(
                agent=AgentConfig(
                    controller_url=server.url,
                    agent_name="bench-drain",
                    tasks=("map_classify_tpu", "map_summarize"),
                    idle_sleep_sec=0.0,
                )
            )
            agent = Agent(config=cfg, session=requests.Session(),
                          runtime=runtime)
            agent._profile = {"tier": "bench"}

            # Warm the executable cache outside the timed window (compile is
            # a once-per-process cost, reference handle-singleton semantics).
            controller.submit_csv_job(
                path, total_rows=shard_size, shard_size=shard_size,
                map_op="map_classify_tpu", extra_payload=classify_extra,
            )
            controller.submit_csv_job(
                path, total_rows=DRAIN_SUMMARIZE_SHARD,
                shard_size=DRAIN_SUMMARIZE_SHARD,
                map_op="map_summarize", extra_payload=summarize_extra,
            )
            _drain_until_done(agent, controller)
            check_all_ok(controller)

            # Leg 1: classify-only (trend line vs pure-op throughput).
            controller.submit_csv_job(
                path, total_rows=n_rows, shard_size=shard_size,
                map_op="map_classify_tpu", extra_payload=classify_extra,
            )
            wall = _drain_until_done(agent, controller)
            check_all_ok(controller)
            classify_leg = {
                "rows_per_sec": round(n_rows / wall, 1),
                "rows": n_rows,
                "pipelined": True,
            }

            # Leg 2: mixed classify+summarize, one drain. Snapshot the result
            # keys first: Controller.results() is cumulative across legs, and
            # the busy accounting below must cover ONLY this leg's shards.
            # Same for the scraped metrics: counters are cumulative, so the
            # per-leg attribution is the scrape DELTA across the leg.
            from agent_tpu.obs.scrape import (
                fetch_metrics_text,
                op_phase_seconds,
            )

            drain_ops = ("map_classify_tpu", "map_summarize")
            pre = fetch_metrics_text(server.url)
            span_pre = (
                op_phase_seconds(pre, drain_ops) if pre is not None else None
            )
            seen_jobs = set(controller.results())
            controller.submit_csv_job(
                path, total_rows=n_rows, shard_size=shard_size,
                map_op="map_classify_tpu", extra_payload=classify_extra,
            )
            controller.submit_csv_job(
                path, total_rows=DRAIN_SUMMARIZE_ROWS,
                shard_size=DRAIN_SUMMARIZE_SHARD,
                map_op="map_summarize", extra_payload=summarize_extra,
            )
            wall = _drain_until_done(agent, controller)
            check_all_ok(controller)
            # Per-op spans (dispatch + deferred fetch): primary source is
            # the scraped /v1/metrics fleet series (execute+fetch phase
            # sums, delta across the leg); utils.spans result-body summing
            # is the fallback when scraping is unavailable.
            post = fetch_metrics_text(server.url)
            span_s: dict = {}
            span_source = "scrape"
            if span_pre is not None and post is not None:
                span_post = op_phase_seconds(post, drain_ops)
                span_s = {
                    op: span_post[op] - span_pre[op] for op in drain_ops
                }
            if not any(span_s.values()):
                from agent_tpu.utils.spans import op_span_ms

                span_source = "result_bodies"
                span_ms = op_span_ms(
                    (
                        r for job_id, r in controller.results().items()
                        if job_id not in seen_jobs
                    ),
                    drain_ops,
                )
                span_s = {op: span_ms[op] / 1e3 for op in drain_ops}
            # Slowest-job trace breakdown (ISSUE 5 satellite): fetched from
            # GET /v1/trace/{job_id} so a regression in the trace path
            # fails the bench loudly instead of rotting silently.
            from agent_tpu.obs import trace as obs_trace
            from agent_tpu.obs.scrape import slowest_trace
            from agent_tpu.obs.trace import phase_breakdown

            trace_line = None
            if obs_trace.enabled():
                worst = slowest_trace(server.url)
                assert worst is not None, (
                    "trace path broken: /v1/traces or /v1/trace/{job_id} "
                    "returned nothing for a drained leg"
                )
                trace_line = phase_breakdown(worst)
                print(f"[slowest shard] {trace_line}", flush=True)
            # Fleet health rollup (ISSUE 8 satellite): the verdict and the
            # per-op attainment/MFU ride the artifact as flat fields; an
            # unreachable /v1/health FAILS the leg instead of silently
            # omitting them.
            from agent_tpu.obs.scrape import fetch_health

            health = fetch_health(server.url)
            assert health is not None, (
                "health path broken: GET /v1/health unreachable for a "
                "drained leg"
            )
            print(f"[health] verdict={health['verdict']}", flush=True)
            slo_attain = {
                o.get("op", o["objective"]): o.get("attainment")
                for o in health["slo"]["objectives"]
            }
            mfu_by_op: dict = {}
            for row in (health.get("agents") or {}).values():
                for op, v in (row.get("mfu") or {}).items():
                    mfu_by_op[op] = v
            # Usage showback rollup (ISSUE 9): the mixed leg's billed
            # device/host seconds and rows off GET /v1/usage — an
            # unreachable report fails the leg like an unreachable health.
            from agent_tpu.obs.scrape import fetch_json as _fetch_json

            usage = _fetch_json(server.url, "/v1/usage")
            assert isinstance(usage, dict) and usage.get("enabled"), (
                "usage path broken: GET /v1/usage unreachable for a "
                "drained leg"
            )
            total_rows = n_rows + DRAIN_SUMMARIZE_ROWS
            mixed_leg = {
                "health_verdict": health["verdict"],
                "slo_attainment": slo_attain,
                "mfu": mfu_by_op or None,
                "usage_device_seconds": usage["totals"]["device_seconds"],
                "usage_host_seconds": usage["totals"]["host_seconds"],
                "usage_rows": usage["totals"]["rows"],
                "usage_billed_tasks": usage["billed_tasks"],
                "rows_per_sec": round(total_rows / wall, 1),
                "classify_rows": n_rows,
                "summarize_rows": DRAIN_SUMMARIZE_ROWS,
                "classify_span_s": round(span_s["map_classify_tpu"], 2),
                "summarize_span_s": round(span_s["map_summarize"], 2),
                "span_source": span_source,
                "slowest_trace": trace_line,
                "wall_s": round(wall, 2),
                "pipelined": True,
            }
    return classify_leg, mixed_leg


def _drain_harness(runtime, n_rows, extra, td, wire_binary=True):
    """(controller, server, agent, csv_path) for one drain leg — shared by
    the staged-parallel and binary-wire legs (ISSUE 6)."""
    import requests

    from agent_tpu.agent.app import Agent
    from agent_tpu.config import AgentConfig, Config
    from agent_tpu.controller.core import Controller
    from agent_tpu.controller.server import ControllerServer

    path = os.path.join(td, "drain.csv")
    if not os.path.exists(path):
        with open(path, "w") as f:
            f.write("id,text,risk\n")
            for i in range(n_rows):
                f.write(
                    f'{i},"drain record {i} with a payload of text",{i % 89}\n'
                )
    controller = Controller(lease_ttl_sec=600.0, wire_binary=wire_binary)
    server = ControllerServer(controller).start()
    cfg = Config(agent=AgentConfig(
        controller_url=server.url, agent_name="bench-drain-dp",
        tasks=("map_classify_tpu",), idle_sleep_sec=0.0,
    ))
    agent = Agent(config=cfg, session=requests.Session(), runtime=runtime)
    agent._profile = {"tier": "bench"}
    return controller, server, agent, path


def _scrape_http_bytes(url):
    """{(route, direction): bytes} from controller_http_bytes_total."""
    from agent_tpu.obs.metrics import parse_exposition
    from agent_tpu.obs.scrape import fetch_metrics_text

    text = fetch_metrics_text(url)
    out = {}
    if text is None:
        return out
    try:
        samples = parse_exposition(text)
    except ValueError:
        return out
    for labels, value in samples.get("controller_http_bytes_total", []):
        out[(labels.get("route"), labels.get("direction"))] = value
    return out


def _bench_drain_staged(runtime, n_rows: int = DRAIN_ROWS,
                        shard_size: int = DRAIN_SHARD_SIZE):
    """``drain_staged_parallel`` leg (ISSUE 6): the classify drain with the
    staging pool at 4 autotuned workers + double-buffered feed vs the
    single-worker reference — same rows, bit-identical results asserted."""
    import tempfile

    extra = {"text_field": "text", "allow_fallback": False,
             "result_format": "columnar"}
    leg = {"rows": n_rows}
    with tempfile.TemporaryDirectory() as td:
        results = {}
        for key, workers, autotune in (("workers_1", 1, False),
                                       ("workers_4", 4, True)):
            controller, server, agent, path = _drain_harness(
                runtime, n_rows, extra, td
            )
            try:
                # Warm outside the timed window (compile is per-process).
                controller.submit_csv_job(
                    path, total_rows=shard_size, shard_size=shard_size,
                    map_op="map_classify_tpu", extra_payload=extra,
                )
                _drain_until_done(agent, controller, workers=workers,
                                  autotune=autotune)
                warm_jobs = set(controller.results())
                controller.submit_csv_job(
                    path, total_rows=n_rows, shard_size=shard_size,
                    map_op="map_classify_tpu", extra_payload=extra,
                )
                wall = _drain_until_done(agent, controller, workers=workers,
                                         autotune=autotune)
                counts = controller.counts()
                assert counts.get("failed", 0) == 0, counts
                leg[f"{key}_rows_per_sec"] = round(n_rows / wall, 1)
                results[key] = {
                    controller.job(j).payload["start_row"]:
                        (r["indices"], r["scores"])
                    for j, r in controller.results().items()
                    if j not in warm_jobs
                }
            finally:
                server.stop()
        assert results["workers_1"] == results["workers_4"], (
            "multi-worker staging diverged from the single-worker reference"
        )
        leg["bit_identical"] = True
        leg["speedup"] = round(
            leg["workers_4_rows_per_sec"] / leg["workers_1_rows_per_sec"], 3
        )
        leg["rows_per_sec"] = leg["workers_4_rows_per_sec"]
    return leg


def _bench_drain_binary(runtime, n_rows: int = DRAIN_ROWS,
                        shard_size: int = DRAIN_SHARD_SIZE):
    """``drain_binary_wire`` leg (ISSUE 6): the classify drain over real
    HTTP with the binary shard wire negotiated vs a JSON-only controller —
    rows/sec plus REAL wire bytes/row (server-side Content-Length deltas on
    /v1/leases out + /v1/results in) and the shrink factor."""
    import tempfile

    extra = {"text_field": "text", "allow_fallback": False,
             "result_format": "columnar"}
    leg = {"rows": n_rows}
    with tempfile.TemporaryDirectory() as td:
        for key, wire_binary in (("json", False), ("b1", True)):
            controller, server, agent, path = _drain_harness(
                runtime, n_rows, extra, td, wire_binary=wire_binary
            )
            try:
                controller.submit_csv_job(
                    path, total_rows=shard_size, shard_size=shard_size,
                    map_op="map_classify_tpu", extra_payload=extra,
                )
                _drain_until_done(agent, controller)
                pre = _scrape_http_bytes(server.url)
                controller.submit_csv_job(
                    path, total_rows=n_rows, shard_size=shard_size,
                    map_op="map_classify_tpu", extra_payload=extra,
                )
                wall = _drain_until_done(agent, controller)
                counts = controller.counts()
                assert counts.get("failed", 0) == 0, counts
                post = _scrape_http_bytes(server.url)
                data_bytes = sum(
                    post.get(k, 0.0) - pre.get(k, 0.0)
                    for k in (("/v1/results", "in"), ("/v1/leases", "out"))
                )
                leg[f"{key}_rows_per_sec"] = round(n_rows / wall, 1)
                leg[f"{key}_bytes_per_row"] = round(data_bytes / n_rows, 1)
            finally:
                server.stop()
        if leg.get("b1_bytes_per_row"):
            leg["wire_shrink_x"] = round(
                leg["json_bytes_per_row"] / leg["b1_bytes_per_row"], 2
            )
        leg["rows_per_sec"] = leg["b1_rows_per_sec"]
        leg["bytes_per_row"] = leg["b1_bytes_per_row"]
    return leg


def _fleet_drain_mode(
    csv_path, extra, warm_file, *, n_agents, devices_per_agent,
    mesh_shape, name_prefix, log_dir, rows, shard_size,
):
    """One fleet/mesh drain over real HTTP → (rows_per_sec, per-agent shard
    counts, results keyed by start_row). Children are spawned, warmed, and
    READY (first controller poll seen) before the timed submit, so
    per-process compile cost stays outside the window — the same warm-
    exclusion methodology as every other drain leg."""
    from agent_tpu.agent import fleet
    from agent_tpu.config import SchedConfig
    from agent_tpu.controller.core import Controller
    from agent_tpu.controller.server import ControllerServer

    # Fair policy on purpose: idle-preference + queue_depth-aware grants
    # are what spread shards across the fleet (ISSUE 7 tentpole a).
    controller = Controller(
        lease_ttl_sec=600.0, sched=SchedConfig(policy="fair")
    )
    server = ControllerServer(controller).start()
    handle = fleet.spawn_fleet(
        n_agents, devices_per_agent,
        controller_url=server.url, tasks="map_classify_tpu",
        platform="cpu", name_prefix=name_prefix, mesh_shape=mesh_shape,
        warm_file=warm_file, log_dir=log_dir,
        extra_env={
            "IDLE_SLEEP_SEC": "0.02",
            # One virtual chip = one core's worth of BLAS: a 1-agent
            # reference that borrows the whole host's thread pool would
            # deflate every scaling ratio derived from it.
            "OMP_NUM_THREADS": "1",
            "OPENBLAS_NUM_THREADS": "1",
        },
    )
    try:
        assert fleet.wait_for_agents(
            controller.agents_summary, handle.names, timeout=300.0,
            fleet=handle,
        ), (
            f"fleet {name_prefix} not ready "
            f"(failures={handle.poll_failures()})"
        )
        t0 = time.perf_counter()
        shard_ids, _ = controller.submit_csv_job(
            csv_path, total_rows=rows, shard_size=shard_size,
            map_op="map_classify_tpu", extra_payload=extra,
        )
        deadline = time.monotonic() + 600.0
        while not controller.drained():
            assert time.monotonic() < deadline, (
                f"fleet {name_prefix} drain stuck: {controller.counts()}"
            )
            assert not handle.poll_failures(), (
                f"fleet member died: {handle.poll_failures()}"
            )
            time.sleep(0.02)
        wall = time.perf_counter() - t0
        counts = controller.counts()
        assert counts.get("failed", 0) == 0, counts
        per_agent = {name: 0 for name in handle.names}
        results = {}
        for jid in shard_ids:
            snap = controller.job_snapshot(jid)
            r = snap["result"]
            assert isinstance(r, dict) and r.get("ok") is True, (jid, r)
            results[controller.job(jid).payload["start_row"]] = (
                r["indices"], r["scores"]
            )
            if snap["agent"] in per_agent:
                per_agent[snap["agent"]] += 1
        return rows / wall, per_agent, results
    finally:
        handle.stop()
        server.stop()


def _bench_drain_multichip(n_rows: int = MULTICHIP_ROWS,
                           shard_size: int = MULTICHIP_SHARD):
    """``drain_multichip`` leg (ISSUE 7): the swarm across N chips, both
    ways — a fleet of N single-chip agent processes (device-pinned via
    ``CHIP_SLICE``) and one dp=N mesh agent — against the 1-chip reference
    drain. Records per-mode rows/sec, ``n_chips``, per-agent shard counts,
    and ``scaling_efficiency`` = rows/sec at N ÷ (N × rows/sec at 1),
    asserting ≥ MULTICHIP_SCALING_FLOOR at N agents when the host has the
    cores to scale. Bit-identity of fleet and mesh results vs the 1-chip
    reference is always asserted."""
    import tempfile

    n = MULTICHIP_AGENTS
    extra = {"text_field": "text", "allow_fallback": False,
             "result_format": "columnar",
             "model_config": dict(MULTICHIP_MODEL), "topk": 5}
    leg: dict = {"rows": n_rows, "agents": n, "n_chips": n}
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "multichip.csv")
        with open(path, "w") as f:
            f.write("id,text\n")
            for i in range(n_rows):
                f.write(f'{i},"drain record {i} with a payload of text"\n')
        warm_file = os.path.join(td, "warm.json")
        with open(warm_file, "w") as f:
            json.dump([{
                "op": "map_classify_tpu",
                "payload": {**extra, "source_uri": path, "start_row": 0,
                            "shard_size": shard_size},
            }], f)
        results = {}
        for mode, n_agents, dev_per, mesh in (
            ("agents_1", 1, 1, ""),
            (f"agents_{n}", n, 1, ""),
            (f"mesh_dp{n}", 1, n, f"dp={n}"),
        ):
            rate, per_agent, res = _fleet_drain_mode(
                path, extra, warm_file,
                n_agents=n_agents, devices_per_agent=dev_per,
                mesh_shape=mesh, name_prefix=f"bench-{mode}",
                log_dir=os.path.join(td, f"logs_{mode}"),
                rows=n_rows, shard_size=shard_size,
            )
            leg[f"{mode}_rows_per_sec"] = round(rate, 1)
            results[mode] = res
            if n_agents > 1:
                leg["per_agent_shards"] = per_agent
                assert all(v > 0 for v in per_agent.values()), (
                    f"agent(s) got zero shards: {per_agent}"
                )
        for mode in (f"agents_{n}", f"mesh_dp{n}"):
            assert results[mode] == results["agents_1"], (
                f"{mode} drain diverged from the 1-chip reference"
            )
        leg["bit_identical"] = True
        eff = (
            leg[f"agents_{n}_rows_per_sec"]
            / (n * leg["agents_1_rows_per_sec"])
        )
        leg["scaling_efficiency"] = round(eff, 3)
        leg["host_cores"] = os.cpu_count()
        if (os.cpu_count() or 1) >= n:
            assert eff >= MULTICHIP_SCALING_FLOOR, (
                f"scaling_efficiency {eff:.3f} < {MULTICHIP_SCALING_FLOOR} "
                f"at {n} agents on {os.cpu_count()} cores"
            )
        else:
            # Fewer cores than agents: the bar is physically unreachable;
            # record why instead of asserting fiction.
            leg["scaling_gated"] = (
                f"{os.cpu_count()} cores < {n} agents; floor not asserted"
            )
        leg["rows_per_sec"] = leg[f"agents_{n}_rows_per_sec"]
    return leg


# Serving leg (ISSUE 15). Request mix: 90% short answers / 10% full-length
# — the interactive shape continuous batching exists for (short requests
# exit the running batch and free their slot; a static batch pays its
# longest rider for every seat). Recorded in the leg so the speedup is
# attributable to a stated workload, not a tuned one. MICRO_STEPS fuses
# decode iterations per dispatch where dispatch overhead would otherwise
# dominate (CPU smoke, tiny models); membership changes between chunks.
SERVE_BENCH_REQUESTS = 240
SERVE_BENCH_SLOTS = 8
SERVE_BENCH_SHORT_FRAC = 0.9
SERVE_BENCH_MICRO_STEPS = 4
SERVE_HTTP_DURATION_SEC = 8.0
SERVE_HTTP_RATE = 4.0

# Disaggregated-serving sub-leg (ISSUE 16). The mix is prefix-heavy on
# purpose: 3 of every 4 requests re-summarize one of a few shared
# documents (the millions-of-users shape — repeated system prompts and
# shared contexts), every 4th is a one-off. The shared rows hit the
# content-hashed prefix cache after the warm round; the one-offs keep the
# hit rate honest (expected 0.75 measured, bar ≥ 0.5).
SERVE_DISAGG_REQUESTS = 32
SERVE_DISAGG_DOCS = 4
SERVE_DISAGG_BULK_ROWS = 512
SERVE_DISAGG_BULK_SHARD = 64


def _bench_serving_beam(runtime):
    """Continuous-batching beam decode vs the static-batch beam baseline on
    the SAME seeded request stream (per-request token budgets drawn 90/10
    short/long): the static path decodes arrival-order batches of
    ``SERVE_BENCH_SLOTS`` requests, each batch running to its longest
    rider's budget (what a batch-serving stack without iteration-level
    membership does — BENCH_r05's beam leg shape); the continuous path runs
    the engine with per-slot limits, exits freeing slots for the backlog
    between steps. Per-request outputs equal a solo decode of that
    request's own budget (regression-tested in tests/test_serving.py);
    tok/s counts the REQUESTED token budgets both sides, so the speedup is
    useful-tokens wall-clock, not padding."""
    import jax
    import numpy as np

    from agent_tpu.models import seq2seq
    from agent_tpu.models.decoding import ContinuousBatcher
    from agent_tpu.models.tokenizer import BOS_ID, EOS_ID, PAD_ID

    smoke = runtime.platform != "tpu"
    cfg = seq2seq.Seq2SeqConfig() if not smoke else seq2seq.Seq2SeqConfig(
        d_model=128, n_heads=4, n_enc_layers=2, n_dec_layers=2, d_ff=256,
        max_src_len=64, max_tgt_len=64, dtype="float32",
    )
    n_req = SERVE_BENCH_REQUESTS
    K, slots = 4, SERVE_BENCH_SLOTS
    # Dispatch-bound smoke shapes amortize dispatch via fused micro-steps;
    # real TPU runs pure iteration-level stepping (buffer donation works).
    micro = SERVE_BENCH_MICRO_STEPS if smoke else 1
    src_len = 64
    T = cfg.max_tgt_len
    short = max(2, T // 32)
    rng = np.random.default_rng(5)
    limits = [
        short if rng.random() < SERVE_BENCH_SHORT_FRAC else T
        for _ in range(n_req)
    ]
    ids = rng.integers(4, cfg.vocab_size, (n_req, src_len)).astype(np.int32)
    mask = np.ones((n_req, src_len), dtype=np.int32)
    params = jax.device_put(
        seq2seq.init_params(cfg, model_id="bench-serving"),
        runtime.replicated(),
    )

    # ---- static baseline: arrival-order batches, padded to batch max ----
    gens: dict = {}

    def gen_for(n, max_new):
        key = (n, max_new)
        if key not in gens:
            gens[key] = jax.jit(
                lambda p, i, m, mn=max_new: seq2seq.beam_generate(
                    p, i, m, cfg, mn, num_beams=K,
                )
            )
        return gens[key]

    batches = [
        (slice(s, min(s + slots, n_req)),
         max(limits[s: min(s + slots, n_req)]))
        for s in range(0, n_req, slots)
    ]
    for n, mx in {(b.stop - b.start, mx) for b, mx in batches}:
        np.asarray(gen_for(n, mx)(params, ids[:n], mask[:n])[0])  # warm
    t0 = time.perf_counter()
    static_steps = 0
    for bat, mx in batches:
        np.asarray(gen_for(bat.stop - bat.start, mx)(
            params, ids[bat], mask[bat]
        )[0])
        static_steps += mx
    static_wall = time.perf_counter() - t0

    # ---- continuous engine on the identical stream ----
    enc_fn = jax.jit(
        lambda p, i, m: seq2seq.encode(p, i, m, cfg).astype(jax.numpy.float32)
    )
    enc_all = np.asarray(enc_fn(params, ids, mask))
    # ONE persistent engine, like the serving agent's: the warm pass pays
    # trace+compile, the measured pass is the steady-state cost.
    engine = ContinuousBatcher(
        seq2seq.make_positional_step(cfg),
        seq2seq.make_cache_factory(cfg),
        params=params,
        slots=slots, vocab_size=cfg.vocab_size, max_tokens=T,
        enc_len=src_len, d_model=cfg.d_model,
        start_id=BOS_ID, eos_id=EOS_ID, pad_id=PAD_ID, num_beams=K,
        micro_steps=micro,
    )

    def run_engine():
        tickets = [
            engine.admit(enc_all[i], mask[i], limits[i], data=i)
            for i in range(n_req)
        ]
        s0 = engine.steps_run
        while engine.has_work():
            engine.step()
        return tickets, engine.steps_run - s0

    run_engine()  # warm the step/insert/prefill programs
    t0 = time.perf_counter()
    tickets, engine_steps = run_engine()
    cont_wall = time.perf_counter() - t0
    # Same numerator both sides: the tokens the requests ASKED for (the
    # static path additionally decoded short rows out to the batch max —
    # that padding waste is exactly the cost being measured).
    tokens = sum(t.steps for t in tickets)
    return {
        "requests": n_req,
        "num_beams": K,
        "slots": slots,
        "micro_steps": micro,
        "short_frac": SERVE_BENCH_SHORT_FRAC,
        "limit_short": short,
        "limit_long": T,
        "tokens": tokens,
        "static_steps": static_steps,
        "engine_steps": engine_steps,
        "static_tok_per_sec": round(tokens / static_wall, 1),
        "continuous_tok_per_sec": round(tokens / cont_wall, 1),
        "speedup_vs_static": round(static_wall / cont_wall, 3),
        "mean_occupancy": round(engine.mean_occupancy(), 2),
    }


def _audit_ttft_decomposition(controller):
    """TTFT decomposition audit shared by the serving legs (ISSUE 17):
    every completed record in the wide-event request log whose component
    chain is whole must telescope back to its measured TTFT within 10% —
    drift means the component histograms misattribute where time went.
    Returns ``(n_records, max_err, modal dominant component)``."""
    recs = [
        r for r in controller.requests_json(limit=2048)["requests"]
        if r.get("outcome") == "completed"
        and isinstance(r.get("ttft_ms"), (int, float))
        and r["ttft_ms"] > 0
        and len(r.get("components") or {}) == 6
    ]
    errs = [
        abs(sum(r["components"].values()) - r["ttft_ms"]) / r["ttft_ms"]
        for r in recs
    ]
    assert not errs or max(errs) <= 0.10, (
        f"TTFT components drifted {max(errs):.1%} from measured TTFT "
        f"(tolerance 10%)"
    )
    dom_counts: dict = {}
    for r in recs:
        d = r.get("dominant_component")
        if d:
            dom_counts[d] = dom_counts.get(d, 0) + 1
    return (
        len(recs),
        round(max(errs), 4) if errs else None,
        max(dom_counts, key=dom_counts.get) if dom_counts else None,
    )


def _bench_serving_disagg(runtime):
    """``serving.disagg`` sub-leg (ISSUE 16): the SAME seeded prefix-heavy
    greedy summarize stream driven through two in-process controller
    stacks while a bulk classify drain shares the lease loop —

    - **baseline**: the PR 15 colocated shape (dense per-slot KV, prefix
      cache off, prefill+decode fused in one ``serve_summarize`` job);
    - **disagg**: the ISSUE 16 stack (paged KV pool, content-hashed
      prefix cache, ``serve_prefill`` → dep-gated ``serve_decode``).

    The baseline run never caches, so one identity assert covers both
    acceptance bars at once: disagg-vs-colocated AND cached-vs-cold
    summaries are bit-identical (engine-vs-solo greedy identity is pinned
    separately in tests/test_serving.py + tests/test_paged_kv.py). The
    measured-round prefix hit rate is asserted ≥ 0.5; TTFT p50/p99, the
    p99/p50 tail ratio, and tok/s are recorded per stack."""
    import statistics as _stats
    import tempfile

    from agent_tpu.config import Config, ServeConfig
    from agent_tpu.controller.core import Controller
    from agent_tpu.ops import load_ops
    from agent_tpu.ops.serve_infer import reset_engines
    from agent_tpu.runtime.context import OpContext

    smoke = runtime.platform != "tpu"
    # Prefill-heavy shape ON PURPOSE (even in smoke): a deep encoder over a
    # long source vs a shallow few-step decode, so the leg measures what
    # the prefix cache actually buys — skipped prefill — rather than
    # host dispatch overhead. The shared documents fill the source bucket.
    s2s_cfg = None if not smoke else {
        "d_model": 128, "n_heads": 4, "n_enc_layers": 6, "n_dec_layers": 1,
        "d_ff": 512, "max_src_len": 256, "max_tgt_len": 8,
        "dtype": "float32",
    }
    cls_cfg = None if not smoke else {
        "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
        "max_len": 64, "dtype": "float32", "n_classes": 16,
    }
    n_req = SERVE_DISAGG_REQUESTS
    docs = [
        f"shared context document {d} " + "with common preamble content " * 8
        for d in range(SERVE_DISAGG_DOCS)
    ]

    def stream(round_idx):
        out = []
        for i in range(n_req):
            if i % 4 == 0:
                out.append(
                    f"one-off request r{round_idx} i{i} "
                    + "tail words " * 18
                )
            else:
                out.append(docs[i % SERVE_DISAGG_DOCS])
        return out

    def params():
        p = {"max_length": 4}
        if s2s_cfg:
            p["model_config"] = s2s_cfg
        return p

    bulk_extra = {"text_field": "text", "allow_fallback": False,
                  "result_format": "columnar"}
    if cls_cfg:
        bulk_extra["model_config"] = cls_cfg

    def drain(controller, handlers, ctx):
        """Lease loop until EVERYTHING (serving + bulk) drains. Returns the
        wall-clock instant the serving work finished — the bulk drain is
        identical constant work on both stacks, so folding its tail into
        the serving window would dilute the ratio being measured toward 1.
        """
        deadline = time.monotonic() + 600.0
        serve_done = None
        while True:
            controller._serve_pump()
            door = controller.serve_door
            if (serve_done is None and door.stats()["bucketed"] == 0
                    and not door.job_ids()):
                serve_done = time.perf_counter()
            lease = controller.lease(
                agent="bench-disagg",
                capabilities={"ops": sorted(handlers)},
                max_tasks=4,
            )
            if lease is None:
                if serve_done is not None and controller.drained():
                    controller._serve_pump()  # final reap
                    return serve_done
                assert time.monotonic() < deadline, controller.counts()
                time.sleep(0.002)
                continue
            for task in lease["tasks"]:
                result = handlers[task["op"]](task["payload"], ctx)
                controller.report(
                    lease_id=lease["lease_id"], job_id=task["id"],
                    job_epoch=task["job_epoch"],
                    status="succeeded" if result.get("ok") else "failed",
                    result=result,
                )

    def run_stack(serve_cfg, agent_serve_cfg):
        reset_engines()
        controller = Controller(lease_ttl_sec=600.0, serve=serve_cfg)
        # The decode knobs (KV layout, prefix cache) are AGENT-side config:
        # in production they arrive via SERVE_* env on the agent process.
        # The in-process lease loop injects them through the op context.
        ctx = OpContext(config=Config(serve=agent_serve_cfg))
        handlers = load_ops([
            "serve_summarize", "serve_prefill", "serve_decode",
            "map_classify_tpu",
        ])
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "bulk.csv")
            with open(path, "w") as f:
                f.write("id,text\n")
                for i in range(SERVE_DISAGG_BULK_ROWS):
                    f.write(f'{i},"drain record {i} with a payload"\n')
            # Warm round: compiles every bucket/batch shape AND seeds the
            # prefix cache with the shared documents (the warm round is the
            # cold pass — its shared rows all miss).
            controller.submit_csv_job(
                path, total_rows=SERVE_DISAGG_BULK_SHARD,
                shard_size=SERVE_DISAGG_BULK_SHARD,
                map_op="map_classify_tpu", extra_payload=bulk_extra,
            )
            for text in stream(0):
                controller.submit_infer("summarize", text, params=params())
            drain(controller, handlers, ctx)
            hits0 = controller._m_serve_prefix.value(event="hits")
            miss0 = controller._m_serve_prefix.value(event="misses")

            # Measured round: bulk drain + the prefix-heavy stream through
            # the same lease loop.
            controller.submit_csv_job(
                path, total_rows=SERVE_DISAGG_BULK_ROWS,
                shard_size=SERVE_DISAGG_BULK_SHARD,
                map_op="map_classify_tpu", extra_payload=bulk_extra,
            )
            t0 = time.perf_counter()
            rids = [
                controller.submit_infer("summarize", text, params=params())
                for text in stream(1)
            ]
            serve_done = drain(controller, handlers, ctx)
            wall = serve_done - t0
        snaps = []
        for rid in rids:
            snap = controller.infer_snapshot(rid)
            assert snap is not None and snap["state"] == "done", snap
            snaps.append(snap)
        ttfts = sorted(
            s["ttft_ms"] for s in snaps if s.get("ttft_ms") is not None
        )
        tokens = sum(s.get("tokens") or 0 for s in snaps)
        hits = controller._m_serve_prefix.value(event="hits") - hits0
        misses = controller._m_serve_prefix.value(event="misses") - miss0
        looked = hits + misses
        n_dec, max_err, dominant = _audit_ttft_decomposition(controller)
        out = {
            "requests": len(snaps),
            "ttft_decomposed_requests": n_dec,
            "ttft_decomposition_max_err": max_err,
            "ttft_dominant_component": dominant,
            "bulk_rows": SERVE_DISAGG_BULK_ROWS,
            "window_s": round(wall, 2),
            "tok_per_sec": round(tokens / wall, 1) if wall else None,
            "ttft_p50_ms": round(_stats.median(ttfts), 1) if ttfts else None,
            "ttft_p99_ms": round(
                ttfts[max(0, int(len(ttfts) * 0.99) - 1)], 1
            ) if ttfts else None,
            "prefix_hit_rate": round(hits / looked, 3) if looked else None,
            "kv_blocks_total": controller._m_serve_kv_total.value(),
        }
        if out["ttft_p50_ms"]:
            out["ttft_tail_ratio"] = round(
                out["ttft_p99_ms"] / out["ttft_p50_ms"], 2
            )
        summaries = [s["result"]["summary"] for s in snaps]
        ops_seen = {
            r.get("op") for r in controller.results().values()
            if isinstance(r, dict)
        }
        return out, summaries, ops_seen

    pr15 = ServeConfig(
        max_wait_ms=5.0, max_batch=8, kv_layout="dense",
        prefix_cache_enabled=False,
    )
    baseline, base_sums, _ = run_stack(pr15, pr15)
    issue16 = ServeConfig(max_wait_ms=5.0, max_batch=8, disaggregated=True)
    disagg, dis_sums, dis_ops = run_stack(issue16, issue16)
    assert base_sums == dis_sums, (
        "disaggregated/cached summaries diverged from the colocated cold run"
    )
    assert {"serve_prefill", "serve_decode"} <= dis_ops, dis_ops
    assert (disagg["prefix_hit_rate"] or 0.0) >= 0.5, (
        f"prefix hit rate {disagg['prefix_hit_rate']} < 0.5 on the seeded "
        "shared-prefix mix"
    )
    assert disagg["kv_blocks_total"] > 0, "paged KV pool gauge never set"
    leg = dict(disagg)
    leg["baseline"] = baseline
    leg["bit_identical"] = True
    if baseline.get("tok_per_sec") and disagg.get("tok_per_sec"):
        leg["vs_colocated"] = round(
            disagg["tok_per_sec"] / baseline["tok_per_sec"], 3
        )
    return leg


def _bench_serving(runtime):
    """``serving`` leg (ISSUE 15): loadgen-driven interactive classify +
    summarize requests against a REAL ``POST /v1/infer`` HTTP front door
    *while* a bulk classify drain runs through the same pipelined agent —
    TTFT p50/p99 and tok/s for the interactive traffic, the /v1/health
    verdict (per-tier SLOs judging it), plus the continuous-vs-static beam
    engine comparison above."""
    import statistics as _stats
    import tempfile
    import threading

    import requests

    from agent_tpu.agent.app import Agent
    from agent_tpu.agent.pipeline import PipelineRunner
    from agent_tpu.config import AgentConfig, Config, ServeConfig
    from agent_tpu.controller.core import Controller
    from agent_tpu.controller.server import ControllerServer
    from agent_tpu.loadgen import ArrivalPattern, LoadGen, TrafficClass
    from agent_tpu.loadgen import session_submitter

    smoke = runtime.platform != "tpu"
    s2s_cfg = None if not smoke else {
        "d_model": 32, "n_heads": 4, "n_enc_layers": 1, "n_dec_layers": 1,
        "d_ff": 64, "max_src_len": 64, "max_tgt_len": 16, "dtype": "float32",
    }
    cls_cfg = None if not smoke else {
        "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
        "max_len": 64, "dtype": "float32", "n_classes": 16,
    }
    bulk_rows, bulk_shard = (2048, 256) if smoke else (DRAIN_ROWS,
                                                      DRAIN_SHARD_SIZE)
    duration = SERVE_HTTP_DURATION_SEC
    rate = SERVE_HTTP_RATE

    def params_for(op):
        if op == "summarize":
            p = {"max_length": 8}
            if s2s_cfg:
                p["model_config"] = s2s_cfg
            return p
        p = {"topk": 1}
        if cls_cfg:
            p["model_config"] = cls_cfg
        return p

    classes = [
        TrafficClass(
            name="infer_classify", op="classify", weight=2.0, route="infer",
            payload_fn=lambda rng, seq: {
                "text": f"interactive classify request {seq} "
                        + "with payload " * (seq % 3 + 1),
                "params": params_for("classify"),
            },
        ),
        TrafficClass(
            name="infer_summarize", op="summarize", weight=2.0,
            route="infer",
            payload_fn=lambda rng, seq: {
                "text": f"interactive summarize request {seq} "
                        + "with payload " * (seq % 3 + 1),
                "params": {
                    **params_for("summarize"),
                    "max_length": 4 + seq % 8,
                },
            },
        ),
    ]
    leg: dict = {}
    controller = Controller(
        lease_ttl_sec=600.0,
        serve=ServeConfig(max_wait_ms=20.0, max_batch=8),
    )
    server = ControllerServer(controller).start()
    try:
        cfg = Config(agent=AgentConfig(
            controller_url=server.url, agent_name="bench-serving",
            tasks=("serve_classify", "serve_summarize", "map_classify_tpu"),
            idle_sleep_sec=0.0,
        ))
        agent = Agent(config=cfg, session=requests.Session(),
                      runtime=runtime)
        agent._profile = {"tier": "bench"}
        runner = PipelineRunner(agent, depth=2)
        rt = threading.Thread(target=runner.run, daemon=True)
        rt.start()
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "bulk.csv")
            with open(path, "w") as f:
                f.write("id,text\n")
                for i in range(bulk_rows):
                    f.write(f'{i},"drain record {i} with a payload"\n')
            bulk_extra = {"text_field": "text", "allow_fallback": False,
                          "result_format": "columnar"}
            if cls_cfg:
                bulk_extra["model_config"] = cls_cfg
            # Warm the serving + bulk executables outside the window.
            sess = requests.Session()
            for op in ("classify", "summarize"):
                r = sess.post(server.url + "/v1/infer", json={
                    "op": op, "text": "warm the serving path",
                    "params": params_for(op),
                }, timeout=300)
                assert r.status_code == 200 and \
                    r.json()["state"] == "done", r.text
            controller.submit_csv_job(
                path, total_rows=bulk_shard, shard_size=bulk_shard,
                map_op="map_classify_tpu", extra_payload=bulk_extra,
            )
            while not controller.drained():
                time.sleep(0.02)

            # The measured window: bulk drain + open-loop interactive load.
            controller.submit_csv_job(
                path, total_rows=bulk_rows, shard_size=bulk_shard,
                map_op="map_classify_tpu", extra_payload=bulk_extra,
            )
            gen = LoadGen(classes, ArrivalPattern(rate), seed=7)
            t0 = time.perf_counter()
            stats = gen.run(
                session_submitter(sess, server.url), duration
            )
            req_ids = stats.job_ids()
            snaps = []
            for rid in req_ids:
                snap = controller.wait_infer(rid, 300.0)
                assert snap is not None and snap["state"] == "done", snap
                snaps.append(snap)
            window = time.perf_counter() - t0
            while not controller.drained():
                time.sleep(0.02)
            ttfts = sorted(
                s["ttft_ms"] for s in snaps if s.get("ttft_ms") is not None
            )
            tokens = sum(s.get("tokens") or 0 for s in snaps)
            from agent_tpu.obs.scrape import fetch_health

            health = fetch_health(server.url)
            n_dec, max_err, dominant = _audit_ttft_decomposition(controller)
            leg.update(
                requests=len(snaps),
                rejected=stats.total_rejected(),
                bulk_rows=bulk_rows,
                window_s=round(window, 2),
                ttft_p50_ms=round(_stats.median(ttfts), 1) if ttfts else None,
                ttft_p99_ms=round(
                    ttfts[max(0, int(len(ttfts) * 0.99) - 1)], 1
                ) if ttfts else None,
                tok_per_sec=round(tokens / window, 1) if window else None,
                health_verdict=(health or {}).get("verdict"),
                ttft_decomposed_requests=n_dec,
                ttft_decomposition_max_err=max_err,
                ttft_dominant_component=dominant,
            )
        agent.running = False
        rt.join(timeout=60)
    finally:
        server.stop()
    leg["beam"] = _bench_serving_beam(runtime)
    chips = runtime.n_devices if runtime.platform == "tpu" else 1
    leg["beam_tok_per_sec_per_chip"] = round(
        leg["beam"]["continuous_tok_per_sec"] / chips, 1
    )
    # Disaggregated prefill/decode + prefix-cache run (ISSUE 16) — its
    # bit-identity assertion failure must surface in the artifact without
    # killing the colocated numbers above.
    try:
        leg["disagg"] = _bench_serving_disagg(runtime)
    except Exception as exc:  # noqa: BLE001
        leg["disagg"] = {"error": f"{type(exc).__name__}: {exc}"[:300]}
    return leg


DAG_WORKFLOWS = 40        # zipfian submissions per leg
DAG_FAN = 4               # classify shards per workflow (7 jobs each)
DAG_TEXTS = 256           # rows per classify shard (real forward pass)
DAG_POOL = 8              # distinct payload variants
DAG_ZIPF_S = 1.3          # head-heavy: most submissions repeat a variant


def _bench_dag_cache() -> dict:
    """Workflow DAG + result cache leg (ISSUE 19): a zipfian mix of
    fan-out/fan-in workflows (echo → DAG_FAN classify shards → collect →
    report) drained twice — cache OFF (every stage computes) and cache ON
    (repeated variants land as content-addressed hits) — through the
    in-process lease/report loop executing the REAL ops.

    Asserts the acceptance bar: the warm leg's hit rate clears 0.6 and its
    effective rows/sec is ≥2× the cold leg's. The hit count is
    deterministic given the seed (a function of the zipf draw, not
    timing); the classify forward pass supplies real per-shard compute, so
    the speedup measures cache-skipped work, not bookkeeping noise.
    """
    import random as _random

    from agent_tpu.config import FlowConfig
    from agent_tpu.controller.core import Controller
    from agent_tpu.loadgen import zipf_rank
    from agent_tpu.ops import load_ops
    from agent_tpu.runtime.context import OpContext

    tiny_cls = {
        "d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
        "max_len": 64, "dtype": "float32", "n_classes": 16,
    }
    handlers = load_ops(["echo", "map_classify_tpu"])
    ctx = OpContext()

    def cls_payload(variant: int) -> dict:
        return {
            "texts": [
                f"classify row {i} variant {variant}"
                for i in range(DAG_TEXTS)
            ],
            "model_config": tiny_cls, "topk": 2,
            "result_format": "columnar", "allow_fallback": False,
        }

    def variant_doc(variant: int) -> dict:
        return {"stages": [
            {"name": "tok", "op": "echo", "payload": {"variant": variant}},
            {"name": "cls", "op": "map_classify_tpu",
             "payload": cls_payload(variant),
             "after": ["tok"], "fan_out": DAG_FAN, "collect": False},
            {"name": "acc", "op": "echo", "payload": {},
             "after": ["cls"]},
            {"name": "rep", "op": "echo", "payload": {"variant": variant},
             "after": ["acc"]},
        ]}

    # Pay the classify compile before either timed leg (production pays it
    # at boot; the cold leg must measure execution, not tracing).
    handlers["map_classify_tpu"](cls_payload(0), ctx)

    def run_leg(cache_enabled: bool):
        controller = Controller(
            flow=FlowConfig(cache_enabled=cache_enabled),
        )
        rng = _random.Random(19)
        jobs = 0
        t0 = time.perf_counter()
        for _ in range(DAG_WORKFLOWS):
            variant = zipf_rank(rng, DAG_POOL, DAG_ZIPF_S)
            out = controller.submit_workflow(variant_doc(variant))
            jobs += len(out["job_ids"])
            deadline = time.monotonic() + 300
            while True:
                lease = controller.lease(
                    "bench", {"ops": sorted(handlers)}, max_tasks=8,
                )
                if lease is None:
                    wj = controller.workflow_json(out["workflow_id"])
                    if wj["state"] != "running":
                        break
                    assert time.monotonic() < deadline, wj
                    continue
                for t in lease["tasks"]:
                    result = handlers[t["op"]](t["payload"], ctx)
                    controller.report(
                        lease["lease_id"], t["id"], t["job_epoch"],
                        "succeeded", result=result,
                    )
        wall = time.perf_counter() - t0
        stats = (
            controller.result_cache.stats()
            if controller.result_cache is not None else None
        )
        return jobs, wall, stats

    cold_jobs, cold_wall, _ = run_leg(cache_enabled=False)
    warm_jobs, warm_wall, stats = run_leg(cache_enabled=True)
    assert cold_jobs == warm_jobs, (cold_jobs, warm_jobs)
    cold_rate = cold_jobs / cold_wall
    warm_rate = warm_jobs / warm_wall
    hit_rate = stats["hit_rate"]
    speedup = warm_rate / cold_rate
    assert hit_rate >= 0.6, (
        f"zipfian mix hit rate {hit_rate:.2f} below 0.6 "
        f"(hits {stats['hits']}, misses {stats['misses']})"
    )
    assert speedup >= 2.0, (
        f"cache effective speedup {speedup:.2f}x below the 2x bar "
        f"(cold {cold_rate:.0f} rows/s, warm {warm_rate:.0f} rows/s)"
    )
    return {
        "workflows": DAG_WORKFLOWS,
        "stage_jobs": cold_jobs,
        "rows_per_sec": round(cold_rate, 1),
        "effective_rows_per_sec": round(warm_rate, 1),
        "effective_speedup": round(speedup, 3),
        "hit_rate": round(hit_rate, 4),
        "hits": stats["hits"],
        "misses": stats["misses"],
    }


def _bench_telemetry() -> dict:
    """Durable telemetry leg (ISSUE 20): the cost of persisting every
    sweep sample + scoring it for anomalies, and the forensic snapshot
    latency. Two numbers for the trend line:

    - ``tsdb_overhead_ratio`` — rows/sec on a pure-controller loopback
      drain with the on-disk store + detector + bundler enabled, over the
      same drain with them off (best-of-3 interleaved; ≈1.0 means the
      durable pipeline rides the sweep for free).
    - ``incident_capture_ms`` — median wall time of one correlated bundle
      snapshot (timeseries window + flight recorder + reqlog tail +
      status + health) on a controller with a warm ring.
    """
    import statistics
    import tempfile as _tempfile

    from agent_tpu.agent.app import Agent as _Agent
    from agent_tpu.chaos import LoopbackSession
    from agent_tpu.config import AgentConfig, Config, ObsConfig
    from agent_tpu.controller.core import Controller

    rows, shard = 65536, 1024

    def run_drain(tmp: str, enabled: bool, i: int) -> float:
        csv_path = os.path.join(tmp, "rows.csv")
        if not os.path.exists(csv_path):
            with open(csv_path, "w", encoding="utf-8") as f:
                f.write("id,text,risk\n")
                for r in range(rows):
                    f.write(f'{r},"record {r}",{(r % 13) * 0.5}\n')
        obs = ObsConfig(
            tsdb_dir=os.path.join(tmp, f"tsdb-{i}") if enabled else "",
            tsdb_interval_sec=0.1,
            anomaly_enabled=enabled, incident_enabled=enabled,
        )
        controller = Controller(journal_path=None, obs=obs)
        controller.submit_csv_job(
            csv_path, total_rows=rows, shard_size=shard,
            map_op="risk_accumulate", extra_payload={"field": "risk"},
        )
        cfg = Config(agent=AgentConfig(
            controller_url="http://loopback", agent_name=f"tel-{i}",
            tasks=("risk_accumulate",), max_tasks=4, idle_sleep_sec=0.0,
            error_backoff_sec=0.0,
        ))
        agent = _Agent(config=cfg, session=LoopbackSession(controller))
        agent._profile = {"tier": "bench"}
        t0 = time.perf_counter()
        deadline = time.monotonic() + 120
        while not controller.drained() and time.monotonic() < deadline:
            leased = agent.lease_once()
            if leased is None:
                controller.sweep()
                continue
            lease_id, tasks = leased
            for task in tasks:
                agent.run_task(lease_id, task)
        dt = time.perf_counter() - t0
        assert controller.drained(), controller.counts()
        controller.close()
        return rows / dt

    with _tempfile.TemporaryDirectory(prefix="bench_telemetry_") as tmp:
        best_on = best_off = 0.0
        for i in range(3):
            best_off = max(best_off, run_drain(tmp, False, i))
            best_on = max(best_on, run_drain(tmp, True, i))

        # Capture latency on a warm controller: populated ring + recorder.
        obs = ObsConfig(
            tsdb_dir=os.path.join(tmp, "tsdb-cap"),
            tsdb_interval_sec=0.0,
            incident_dir=os.path.join(tmp, "inc-cap"),
            incident_min_interval_sec=0.0,
        )
        controller = Controller(journal_path=None, obs=obs)
        for i in range(8):
            controller.submit("echo", {"i": i})
            controller.sweep()
        capture_ms = []
        for i in range(7):
            t0 = time.perf_counter()
            controller._capture_incident(
                "anomaly", f"bench-{i}", {"watch": "bench", "z": 10.0}
            )
            capture_ms.append((time.perf_counter() - t0) * 1e3)
        controller.close()

    return {
        "rows_per_sec_off": round(best_off, 1),
        "rows_per_sec_on": round(best_on, 1),
        "overhead_ratio": round(best_on / best_off, 4) if best_off else None,
        "incident_capture_ms": round(statistics.median(capture_ms), 3),
    }


def main() -> int:
    from agent_tpu.runtime.runtime import get_runtime

    runtime = get_runtime()
    n_chips = runtime.n_devices
    legs: dict = {}

    # 5 windows on the two noisiest legs (r3 spreads: flagship 11.7%,
    # long-ctx 14.0% at windows=3) — the median tightens, the spread field
    # shows it.
    flagship = _bench_classify_leg(
        runtime, batch=FLAGSHIP_BATCH, text_len=100, iters=FLAGSHIP_ITERS,
        windows=NOISY_WINDOWS,
    )
    legs["flagship"] = flagship
    # Per-chip normalization from the devices the LEG actually used
    # (ISSUE 7 satellite): real TPU legs engage the whole mesh; on host
    # backends the forced virtual devices share one CPU and are not chips —
    # dividing the host rate by 8 fabricated per-chip throughput. Fleet
    # legs carry their own n_chips.
    flagship_chips = n_chips if runtime.platform == "tpu" else 1
    flagship["n_chips_used"] = flagship_chips
    rows_per_sec_per_chip = flagship["rows_per_sec"] / flagship_chips

    for name, fn in (
        ("bert_base", lambda: _bench_bert_base(runtime)),
        ("bert_base_int8", lambda: _bench_bert_base_int8(
            runtime, legs.get("bert_base"))),
        ("moe", lambda: _bench_moe(runtime)),
        ("long_ctx", lambda: _bench_long_ctx(runtime)),
        ("train", lambda: _bench_train(runtime)),
        ("train_long_ctx", lambda: _bench_train_long_ctx(runtime)),
        ("summarize", lambda: _bench_summarize(runtime)),
        ("summarize_beam", lambda: _bench_summarize(runtime, num_beams=4)),
    ):
        try:
            legs[name] = fn()
        except Exception as exc:  # noqa: BLE001 — secondary legs must not
            # kill the line, but the cause must surface in the artifact.
            legs[name] = {"error": f"{type(exc).__name__}: {exc}"[:300]}

    # W8A16 weight-only decode: two legs (greedy + beam-4) from one runner,
    # speedups referenced against the bf16 legs recorded just above.
    try:
        w_greedy, w_beam = _bench_summarize_w8a16(
            runtime, legs.get("summarize"), legs.get("summarize_beam")
        )
        legs["summarize_w8a16"] = w_greedy
        legs["summarize_w8a16_beam"] = w_beam
    except Exception as exc:  # noqa: BLE001
        legs["summarize_w8a16"] = {
            "error": f"{type(exc).__name__}: {exc}"[:300]
        }
        legs["summarize_w8a16_beam"] = legs["summarize_w8a16"]

    import tempfile

    try:
        with tempfile.TemporaryDirectory() as td:
            legs["csv_index"] = {
                "mb_per_sec": round(_bench_csv_index(td), 1)
            }
    except Exception as exc:  # noqa: BLE001
        legs["csv_index"] = {"error": f"{type(exc).__name__}: {exc}"[:300]}

    # Control-plane micro-bench (ISSUE 14): submits/sec, lease-grants/sec,
    # and the replay-compaction speedup — no jax, pure controller. Lives
    # in scripts/controller_bench.py so CI can run (and gate) it without
    # paying for the model legs.
    try:
        sys.path.insert(
            0,
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts"),
        )
        import controller_bench

        # partitions=3: the ISSUE 18 aggregate-submits leg — N partition
        # processes journaling concurrently, the partitioned control
        # plane's scaling claim as a tracked number.
        ctrl = controller_bench.run_bench(partitions=3)
        legs["controller"] = {
            k: v for k, v in ctrl.items() if k != "detail"
        }
    except Exception as exc:  # noqa: BLE001
        legs["controller"] = {"error": f"{type(exc).__name__}: {exc}"[:300]}

    # Workflow DAG + result cache (ISSUE 19): zipfian fan-out/fan-in mix,
    # cold vs cache-warm — asserts hit rate and the ≥2x effective-rate bar.
    try:
        legs["dag_cache"] = _bench_dag_cache()
    except Exception as exc:  # noqa: BLE001 — an AssertionError here is
        # the cache failing its own acceptance bar; it must surface.
        legs["dag_cache"] = {"error": f"{type(exc).__name__}: {exc}"[:300]}

    # Durable telemetry (ISSUE 20): sweep-sample persistence overhead on a
    # pure-controller drain + the incident snapshot latency.
    try:
        legs["telemetry"] = _bench_telemetry()
    except Exception as exc:  # noqa: BLE001
        legs["telemetry"] = {"error": f"{type(exc).__name__}: {exc}"[:300]}

    try:
        classify_drain, mixed_drain = _bench_drain(runtime)
        legs["drain"] = classify_drain
        legs["drain_mixed"] = mixed_drain
    except Exception as exc:  # noqa: BLE001 — an AssertionError here means
        # shards FAILED — a correctness signal, not an environment quirk.
        legs["drain"] = {"error": f"{type(exc).__name__}: {exc}"[:300]}

    # Data-plane legs (ISSUE 6): staging-pool parallelism and the binary
    # shard wire, both against the same classify drain shape as `drain`.
    for name, fn in (
        ("drain_staged_parallel", lambda: _bench_drain_staged(runtime)),
        ("drain_binary_wire", lambda: _bench_drain_binary(runtime)),
        # Multi-chip swarm drain (ISSUE 7): fleet of N pinned agent
        # processes + dp=N mesh agent vs the 1-chip reference, scaling
        # efficiency asserted when the host has the cores.
        ("drain_multichip", _bench_drain_multichip),
        # Online serving (ISSUE 15): loadgen-driven POST /v1/infer traffic
        # concurrent with a bulk drain (TTFT p50/p99, tok/s, SLO verdict) +
        # the continuous-vs-static beam engine comparison.
        ("serving", lambda: _bench_serving(runtime)),
    ):
        try:
            legs[name] = fn()
        except Exception as exc:  # noqa: BLE001 — bit-identity assertion
            # failures must surface in the artifact, not kill the line.
            legs[name] = {"error": f"{type(exc).__name__}: {exc}"[:300]}

    baseline = 10_000.0  # BASELINE.md north star: ≥10k rows/sec/chip

    # Host-shape stamp + starved-leg marking (ISSUE 16 satellite): a
    # 1-core container CAN run the multichip/staged legs, but the numbers
    # measure core starvation, not the code (BENCH_r06 recorded
    # scaling_efficiency 0.187 that way). Stamp the cores into every
    # artifact and name the flat fields the regression checker must skip,
    # so starved rounds neither regress nor set baselines.
    host_cores = os.cpu_count() or 1
    starved_fields: list = []
    if host_cores < 4:  # the staged pool's parallel side runs 4 workers
        starved_fields.append("drain_staged_rows_per_sec")
        if isinstance(legs.get("drain_staged_parallel"), dict):
            legs["drain_staged_parallel"]["starved"] = True
    if host_cores < MULTICHIP_AGENTS:
        starved_fields += [
            "multichip_rows_per_sec", "multichip_scaling_efficiency",
        ]
        if isinstance(legs.get("drain_multichip"), dict):
            legs["drain_multichip"]["starved"] = True
    if host_cores < 4:  # 3 partition children + the bench parent
        starved_fields.append("controller_agg_submits_per_sec")

    print(
        json.dumps(
            {
                # Measurement config rides with the numbers so trend readers
                # can tell workload changes from framework changes.
                "bench_params": {
                    "windows": WINDOWS,
                    "noisy_windows": NOISY_WINDOWS,  # flagship + long_ctx
                    "classify_batch": FLAGSHIP_BATCH,
                    "classify_iters": FLAGSHIP_ITERS,
                    "bert_batch": BERT_BATCH,
                    "bert_config": BERT_CONFIG,
                    "long_ctx_batch": LONG_CTX_BATCH,
                    "summarize_batch": SUMMARIZE_BATCH,
                    "summarize_max_new": SUMMARIZE_MAX_NEW,
                    "summarize_iters": SUMMARIZE_ITERS,
                    "agreement_rows": AGREEMENT_ROWS,
                    "train_batch": TRAIN_BATCH,
                    "train_steps": TRAIN_STEPS,
                    "drain_rows": DRAIN_ROWS,
                    "drain_shard_size": DRAIN_SHARD_SIZE,
                    "drain_summarize_rows": DRAIN_SUMMARIZE_ROWS,
                    "multichip_agents": MULTICHIP_AGENTS,
                    "multichip_rows": MULTICHIP_ROWS,
                    "multichip_shard_size": MULTICHIP_SHARD,
                    "serve_bench_requests": SERVE_BENCH_REQUESTS,
                    "serve_bench_slots": SERVE_BENCH_SLOTS,
                    "serve_bench_short_frac": SERVE_BENCH_SHORT_FRAC,
                    "serve_http_duration_sec": SERVE_HTTP_DURATION_SEC,
                    "serve_http_rate": SERVE_HTTP_RATE,
                    "serve_disagg_requests": SERVE_DISAGG_REQUESTS,
                    "serve_disagg_docs": SERVE_DISAGG_DOCS,
                },
                "host_cores": host_cores,
                "starved_fields": starved_fields,
                "metric": "map_classify_tpu rows/sec/chip",
                "value": round(rows_per_sec_per_chip, 1),
                "unit": "rows/s/chip",
                "vs_baseline": round(rows_per_sec_per_chip / baseline, 3),
                "platform": runtime.platform,
                "device_kind": getattr(
                    runtime.devices[0], "device_kind", None
                ),
                "n_chips": n_chips,
                "legs": legs,
                # Flat trend fields (r01/r02 continuity).
                "classify_p50_batch_ms": flagship["p50_batch_ms"],
                "bert_base_rows_per_sec": legs["bert_base"].get("rows_per_sec"),
                "bert_base_mfu": legs["bert_base"].get("mfu"),
                "bert_base_int8_rows_per_sec": legs["bert_base_int8"].get(
                    "rows_per_sec"
                ),
                "int8_agreement_top1": legs["bert_base_int8"].get(
                    "agreement_top1"
                ),
                "moe_rows_per_sec": legs["moe"].get("rows_per_sec"),
                "long_ctx_rows_per_sec": legs["long_ctx"].get("rows_per_sec"),
                "train_examples_per_sec": legs["train"].get("examples_per_sec"),
                "train_mfu": legs["train"].get("mfu"),
                "train_long_ctx_mfu": legs["train_long_ctx"].get("mfu"),
                "summarize_decode_tok_per_sec": legs["summarize"].get(
                    "decode_tok_per_sec"
                ),
                "summarize_beam_tok_per_sec": legs["summarize_beam"].get(
                    "decode_tok_per_sec"
                ),
                "summarize_w8a16_tok_per_sec": legs["summarize_w8a16"].get(
                    "decode_tok_per_sec"
                ),
                "summarize_w8a16_beam_tok_per_sec": legs[
                    "summarize_w8a16_beam"
                ].get("decode_tok_per_sec"),
                "w8a16_vs_bf16": legs["summarize_w8a16_beam"].get(
                    "w8a16_vs_bf16"
                ),
                "w8a16_agreement_token": legs["summarize_w8a16_beam"].get(
                    "agreement_token"
                ),
                "w8a16_agreement_control": legs["summarize_w8a16_beam"].get(
                    "agreement_control_token"
                ),
                "flash_vs_dense_8k": legs["long_ctx"].get("flash_vs_dense_8k"),
                "csv_index_mb_per_sec": legs["csv_index"].get("mb_per_sec"),
                "e2e_drain_rows_per_sec": legs["drain"].get("rows_per_sec"),
                "drain_staged_rows_per_sec": legs["drain_staged_parallel"]
                .get("rows_per_sec"),
                "wire_bytes_per_row": legs["drain_binary_wire"]
                .get("bytes_per_row"),
                "wire_shrink_x": legs["drain_binary_wire"]
                .get("wire_shrink_x"),
                # Multi-chip flat fields (ISSUE 7): the trajectory finally
                # records n_chips > 1 and the scaling it buys.
                "multichip_rows_per_sec": legs["drain_multichip"]
                .get("rows_per_sec"),
                "multichip_scaling_efficiency": legs["drain_multichip"]
                .get("scaling_efficiency"),
                "multichip_n_chips": legs["drain_multichip"].get("n_chips"),
                # Fleet health flat fields (ISSUE 8): verdict + per-op SLO
                # attainment and live MFU off GET /v1/health for the mixed
                # drain leg.
                "health_verdict": legs.get("drain_mixed", {})
                .get("health_verdict"),
                "slo_attainment_classify": (
                    legs.get("drain_mixed", {}).get("slo_attainment") or {}
                ).get("map_classify_tpu"),
                "slo_attainment_summarize": (
                    legs.get("drain_mixed", {}).get("slo_attainment") or {}
                ).get("map_summarize"),
                "mfu_classify": (
                    legs.get("drain_mixed", {}).get("mfu") or {}
                ).get("map_classify_tpu"),
                "mfu_summarize": (
                    legs.get("drain_mixed", {}).get("mfu") or {}
                ).get("map_summarize"),
                # Resource accounting flat fields (ISSUE 9): billed device
                # seconds + rows off GET /v1/usage for the mixed drain leg.
                "usage_device_seconds": legs.get("drain_mixed", {})
                .get("usage_device_seconds"),
                "usage_rows": legs.get("drain_mixed", {}).get("usage_rows"),
                # Serving flat fields (ISSUE 15): interactive TTFT/tok-per-
                # sec measured concurrently with a bulk drain, plus the
                # continuous-batching beam engine vs the static-batch
                # baseline on the same request stream.
                "serving_ttft_p50_ms": legs["serving"].get("ttft_p50_ms"),
                "serving_ttft_p99_ms": legs["serving"].get("ttft_p99_ms"),
                "serving_tok_per_sec": legs["serving"].get("tok_per_sec"),
                "serving_beam_tok_per_sec": (
                    legs["serving"].get("beam") or {}
                ).get("continuous_tok_per_sec"),
                "serving_beam_speedup_vs_static": (
                    legs["serving"].get("beam") or {}
                ).get("speedup_vs_static"),
                # Request-level observability flat fields (ISSUE 17): the
                # modal dominant TTFT component across the leg's completed
                # requests (a string — the regression judge skips it) and
                # the worst component-sum drift vs measured TTFT.
                "serving_ttft_dominant_component": legs["serving"]
                .get("ttft_dominant_component"),
                "serving_ttft_decomposition_max_err": legs["serving"]
                .get("ttft_decomposition_max_err"),
                # Disaggregated serving flat fields (ISSUE 16): the
                # prefix-heavy mix through the paged-KV + prefix-cache +
                # prefill/decode-split stack, vs the colocated cold
                # baseline on the identical stream.
                "serving_disagg_tok_per_sec": (
                    legs["serving"].get("disagg") or {}
                ).get("tok_per_sec"),
                "serving_disagg_ttft_p99_ms": (
                    legs["serving"].get("disagg") or {}
                ).get("ttft_p99_ms"),
                "serving_disagg_vs_colocated": (
                    legs["serving"].get("disagg") or {}
                ).get("vs_colocated"),
                "serving_prefix_hit_rate": (
                    legs["serving"].get("disagg") or {}
                ).get("prefix_hit_rate"),
                # Control-plane flat fields (ISSUE 14): the controller
                # ceiling as tracked numbers — submit/lease throughput and
                # the snapshot-compaction replay speedup.
                "controller_submits_per_sec": legs["controller"]
                .get("submits_per_sec"),
                "controller_lease_grants_per_sec": legs["controller"]
                .get("lease_grants_per_sec"),
                "controller_tasks_leased_per_sec": legs["controller"]
                .get("tasks_leased_per_sec"),
                "controller_replay_events_per_sec": legs["controller"]
                .get("replay_events_per_sec"),
                "controller_replay_compacted_sec": legs["controller"]
                .get("replay_compacted_sec"),
                "controller_replay_speedup": legs["controller"]
                .get("replay_speedup"),
                # Partitioned aggregate (ISSUE 18): N concurrent
                # partition processes vs one — starved-stamped on
                # < 4-core hosts above.
                "controller_agg_submits_per_sec": legs["controller"]
                .get("agg_submits_per_sec"),
                "controller_agg_speedup_vs_single": legs["controller"]
                .get("agg_speedup_vs_single"),
                # Workflow DAG + result cache flat fields (ISSUE 19): cold
                # DAG drain throughput, the zipfian mix's dedupe hit rate,
                # and the effective-rate multiple the cache buys.
                "dag_rows_per_sec": legs["dag_cache"].get("rows_per_sec"),
                "cache_hit_rate": legs["dag_cache"].get("hit_rate"),
                "cache_effective_speedup": legs["dag_cache"]
                .get("effective_speedup"),
                # Durable telemetry flat fields (ISSUE 20): the throughput
                # cost of persisting+scoring every sweep sample (≈1.0 =
                # free) and the forensic bundle snapshot latency.
                "tsdb_overhead_ratio": legs["telemetry"]
                .get("overhead_ratio"),
                "incident_capture_ms": legs["telemetry"]
                .get("incident_capture_ms"),
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
