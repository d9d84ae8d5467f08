#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the three user-facing paths once — a bulk drain over ``/v1/jobs``,
online ``/v1/infer``, and a ``train_classifier`` job — through the normal
entry points (HTTP ``ControllerServer`` → lease → ``Agent`` with the pipelined
runner → op → ``TpuRuntime`` → kernel) at a published width (BERT-base
classify, a 768-wide 6+6 seq2seq), with seeded random weights and seeded data,
and checks what comes out against float32 references computed on the same
device.

    python chip_smoke.py             one chip, one process (what the driver runs):
                                     device, kernels, train, drain, infer
    python chip_smoke.py --chips 4   only the cross-chip paths (builder-run)

One JSON object per phase goes to stdout; the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase ends the run with ``{"ok": false, ...}`` and a non-zero exit.
There is no CPU path: a machine without a TPU fails in the ``device`` phase.

One process per chip. With ``--chips 4`` the parent never touches JAX: it owns
the HTTP controller and starts every chip-holding child itself
(``spawn_fleet(platform="tpu")``), one stage after another.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))

# ---- what the smoke runs -------------------------------------------------
# Module constants, not options: tests/test_chip_smoke.py rehearses the phase
# functions on the CPU by patching these to a tiny width.

REQUIRED_PLATFORM = "tpu"

# BERT-base (Devlin et al. 2018, bert-base-uncased config.json): 12 layers,
# hidden 768, 12 heads, FFN 3072, 512 positions. bf16 (the config default);
# the byte vocabulary and the 1000-way head are the repo's own.
CLASSIFY_MODEL = {
    "d_model": 768, "n_heads": 12, "n_layers": 12, "d_ff": 3072,
    "max_len": 512,
}
# The one family /v1/infer admits (ops/serve_infer._resolve), at 768 wide:
# 12 heads, 6+6 layers, FFN 3072 (the BART-base / T5-base block widths).
SEQ2SEQ_MODEL = {
    "d_model": 768, "n_heads": 12, "n_enc_layers": 6, "n_dec_layers": 6,
    "d_ff": 3072,
}
ROW_BYTES = 600            # > max_len: every classify row fills 512 tokens
DRAIN_ROWS = 2048
DRAIN_SHARD = 512          # 4 shards, each two 256-row device programs
REFERENCE_ROWS = 8         # rows re-run in float32 for the top-1 check
SUMMARIZE_ROWS = 16        # one shard: half short rows, half long
SUMMARIZE_LONG_BYTES = 700
SUMMARIZE_MAX_NEW = 24
INFER_PREFIX_TOKENS = 8    # engine vs scan: these must agree exactly
TRAIN_ROWS = 640           # eval holdout 1/5 → 512 train rows
TRAIN_BATCH = 128          # without remat such a step takes 14.75 GB of the 16
TRAIN_EPOCHS = 2           # 4 steps each
TRAIN_CLASSES = 4
# The kernels at the shapes the main path uses: BERT-base heads.
KERNEL_HEADS, KERNEL_D_HEAD, KERNEL_BATCH = 12, 64, 2
KERNEL_INTERPRET = False   # the chip's compiler, never the interpreter

# Cross-chip job (``--chips 4``): 64 shards of one 256-row program each —
# enough that four members prefetching a few shards each all get some.
FLEET_ROWS = 16384
FLEET_SHARD = 256

JOB_TIMEOUT_S = 900.0
FLEET_TIMEOUT_S = 300.0    # a stage's members ready, and its drain done
WORDS = (
    "swarm lease shard agent tensor kernel mesh drain batch token stream "
    "ledger window vector router replica journal fence bucket cache"
).split()


class SmokeFailure(Exception):
    """A check did not hold; the run ends non-zero."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# Where the JSON records go. ``main`` keeps the real stdout for them and
# sends everything else the process prints there (the repo's own log lines)
# to stderr.
RECORDS = None


def emit(phase: str, **fields: Any) -> None:
    print(json.dumps({"phase": phase, **fields}, sort_keys=True),
          file=RECORDS or sys.stdout, flush=True)


# ---- seeded data ---------------------------------------------------------

def make_text(rng, n_bytes: int) -> str:
    """Seeded filler text of at least ``n_bytes`` bytes (ASCII words)."""
    shortest = min(len(w) for w in WORDS) + 1
    picks = rng.integers(len(WORDS), size=n_bytes // shortest + 1)
    return " ".join(WORDS[i] for i in picks)


def write_csv(path: str, header: str, rows: Sequence[Sequence[Any]]) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write(header + "\n")
        for row in rows:
            f.write(",".join(
                f'"{v}"' if isinstance(v, str) else str(v) for v in row
            ) + "\n")


def build_data(tmp: str, seed: int) -> Dict[str, Any]:
    """The seeded CSVs every phase reads, written under ``tmp``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    classify = [make_text(rng, ROW_BYTES) for _ in range(DRAIN_ROWS)]
    summarize = [
        make_text(rng, SUMMARIZE_LONG_BYTES if i % 2 else 24)
        for i in range(SUMMARIZE_ROWS)
    ]
    # Learnable labels: the class word opens the row and recurs through it.
    labels = [int(rng.integers(TRAIN_CLASSES)) for _ in range(TRAIN_ROWS)]
    train = [
        " ".join(
            WORDS[label] if j % 3 == 0 else WORDS[i]
            for j, i in enumerate(
                rng.integers(len(WORDS), size=ROW_BYTES // 4))
        )
        for label in labels
    ]
    data = {
        "classify_csv": os.path.join(tmp, "classify.csv"),
        "summarize_csv": os.path.join(tmp, "summarize.csv"),
        "train_csv": os.path.join(tmp, "train.csv"),
        "classify_texts": classify,
        "summarize_texts": summarize,
        "train_labels": labels,
    }
    write_csv(data["classify_csv"], "id,text",
              [(i, t) for i, t in enumerate(classify)])
    write_csv(data["summarize_csv"], "id,text",
              [(i, t) for i, t in enumerate(summarize)])
    write_csv(data["train_csv"], "id,text,label",
              [(i, t, y) for i, (t, y) in enumerate(zip(train, labels))])
    return data


# ---- the stack: HTTP controller + one in-process pipelined agent ---------

class Stack:
    """A real ``ControllerServer`` on port 0 and one in-process ``Agent``
    draining it through the pipelined runner (the pattern of
    ``bench._bench_drain``). Everything the phases do goes over HTTP."""

    def __init__(self, runtime, tasks: Sequence[str]) -> None:
        import requests

        from agent_tpu.agent.app import Agent
        from agent_tpu.agent.pipeline import PipelineRunner
        from agent_tpu.config import AgentConfig, Config, DeviceConfig
        from agent_tpu.controller.core import Controller
        from agent_tpu.controller.server import ControllerServer

        self.controller = Controller(lease_ttl_sec=JOB_TIMEOUT_S * 2)
        self.server = ControllerServer(self.controller).start()
        self.url = self.server.url
        self.http = requests.Session()
        config = Config(
            agent=AgentConfig(
                controller_url=self.url, agent_name="chip-smoke",
                tasks=tuple(tasks), idle_sleep_sec=0.0,
            ),
            device=DeviceConfig.from_env(),
        )
        self.agent = Agent(
            config=config, session=requests.Session(), runtime=runtime
        )
        self.agent._profile = {"tier": "smoke"}
        self._thread = threading.Thread(
            target=PipelineRunner(self.agent, depth=2).run, daemon=True
        )
        self._thread.start()

    def close(self) -> None:
        self.agent.running = False
        self._thread.join(timeout=60)
        self.server.stop()

    def post_csv_job(self, csv_path: str, *, map_op: str, total_rows: int,
                     shard_size: int, extra: Dict[str, Any]) -> List[str]:
        r = self.http.post(self.url + "/v1/jobs", json={
            "source_uri": csv_path, "total_rows": total_rows,
            "shard_size": shard_size, "map_op": map_op,
            "extra_payload": extra,
        }, timeout=30)
        check(r.status_code == 200, f"POST /v1/jobs → {r.status_code} {r.text}")
        return list(r.json()["job_ids"])

    def post_job(self, op: str, payload: Dict[str, Any]) -> str:
        r = self.http.post(self.url + "/v1/jobs",
                           json={"op": op, "payload": payload}, timeout=30)
        check(r.status_code == 200, f"POST /v1/jobs → {r.status_code} {r.text}")
        return str(r.json()["job_id"])

    def wait_jobs(self, job_ids: Sequence[str]) -> List[Dict[str, Any]]:
        """Poll ``GET /v1/jobs/<id>`` until every job is terminal; every one
        must have succeeded with an ``ok: true`` body."""
        deadline = time.monotonic() + JOB_TIMEOUT_S
        snaps: Dict[str, Dict[str, Any]] = {}
        while len(snaps) < len(job_ids):
            check(time.monotonic() < deadline,
                  f"jobs not done after {JOB_TIMEOUT_S}s: "
                  f"{self.controller.counts()}")
            check(self._thread.is_alive(), "the agent's runner thread died")
            for jid in job_ids:
                if jid in snaps:
                    continue
                snap = self.http.get(
                    f"{self.url}/v1/jobs/{jid}", timeout=30
                ).json()
                if snap["state"] in ("succeeded", "failed", "dead"):
                    snaps[jid] = snap
            time.sleep(0.05)
        out = [snaps[jid] for jid in job_ids]
        for snap in out:
            check(snap["state"] == "succeeded",
                  f"job {snap['job_id']} {snap['state']}: {snap['error']}")
            check_result_body(snap["result"], snap["job_id"])
        return out

    def infer(self, op: str, text: str, params: Dict[str, Any]
              ) -> Dict[str, Any]:
        """One ``POST /v1/infer`` → the request's ``done`` snapshot, with a
        TTFT, served by the matching serving op on the required device."""
        r = self.http.post(self.url + "/v1/infer", json={
            "op": op, "text": text, "params": params, "wait": False,
        }, timeout=30)
        check(r.status_code == 200,
              f"POST /v1/infer → {r.status_code} {r.text}")
        rid = r.json()["req_id"]
        deadline = time.monotonic() + JOB_TIMEOUT_S
        while True:
            check(time.monotonic() < deadline, f"infer {rid} timed out")
            check(self._thread.is_alive(), "the agent's runner thread died")
            snap = self.http.get(
                f"{self.url}/v1/infer/{rid}?wait_ms=5000", timeout=60
            ).json()
            if snap.get("state") in ("done", "failed"):
                break
        what = f"infer {op} ({len(text)} bytes)"
        check(snap["state"] == "done", f"{what}: {snap}")
        check(snap["ttft_ms"] is not None, f"{what}: no TTFT")
        job = self.wait_jobs([snap["job_id"]])[0]
        check(job["op"] == f"serve_{op}", f"{what}: served by {job['op']}")
        return snap


def check_result_body(result: Any, what: str) -> None:
    """Every result on the smoke path: ok, on the required device, and not
    the op-level CPU retry."""
    check(isinstance(result, dict) and result.get("ok") is True,
          f"{what}: result not ok: {str(result)[:300]}")
    check(result.get("device") == REQUIRED_PLATFORM,
          f"{what}: device {result.get('device')!r}, "
          f"want {REQUIRED_PLATFORM!r}")
    check("fallback" not in result, f"{what}: fell back: {result.get('reason')}")


# ---- phase: device -------------------------------------------------------

def phase_device() -> Tuple[Any, Dict[str, Any]]:
    """``jax.devices()`` must be a TPU this repo knows the peaks of. Builds
    the process's one runtime (which also places the compile cache)."""
    import jax

    from agent_tpu.config import DeviceConfig
    from agent_tpu.obs.health import PEAK_BF16_TFLOPS
    from agent_tpu.runtime.runtime import get_runtime

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    check(device["platform"] == REQUIRED_PLATFORM,
          f"no accelerator: jax.devices() is {devices}")
    check(device["kind"] in PEAK_BF16_TFLOPS,
          f"device_kind {device['kind']!r} is not in the peaks table "
          f"{sorted(PEAK_BF16_TFLOPS)}")
    runtime = get_runtime(DeviceConfig.from_env())
    check(runtime.platform == REQUIRED_PLATFORM,
          f"runtime.platform is {runtime.platform!r}")
    cache = jax.config.jax_compilation_cache_dir
    emit("device", ok=True, device=device, jax=jax.__version__,
         compile_cache_dir=cache, cache_entries_before=count_entries(cache))
    return runtime, device


def count_entries(path: Optional[str]) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return len(os.listdir(path))


def attention_blocks(registry=None) -> Dict[str, float]:
    """``attention_blocks_traced_total`` by path, from ``registry`` (an
    agent's: what its tasks traced) or the process registry (what code
    outside any task traced): which attention path the programs built so far
    contain."""
    from agent_tpu.obs.metrics import get_registry

    family = (registry or get_registry()).snapshot().get(
        "attention_blocks_traced_total") or {"series": []}
    return {s["labels"]["path"]: float(s["value"]) for s in family["series"]}


# ---- phase: kernels ------------------------------------------------------

def phase_kernels() -> None:
    """Each Pallas entry point, compiled (``interpret=False``), at the main
    path's shapes, against ``dot_product_attention`` in float32 on the same
    device. The selection counters must tick: a dense substitution would
    agree with the reference and prove nothing."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("agent_tpu.kernels.flash_attention")
    from agent_tpu.models.layers import NEG_INF, dot_product_attention
    from agent_tpu.models.t5 import relative_position_bucket

    t0 = time.perf_counter()
    B, H, D = KERNEL_BATCH, KERNEL_HEADS, KERNEL_D_HEAD
    interpret = KERNEL_INTERPRET

    def inputs(L: int, seed: int):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q, k, v = (
            jax.random.normal(kk, (B, H, L, D), jnp.float32).astype(
                jnp.bfloat16
            )
            for kk in ks[:3]
        )
        # Key-padding mask: row 0 full, row 1 padded past three quarters.
        lens = jnp.asarray([L] + [3 * L // 4] * (B - 1))
        mask = (jnp.arange(L)[None, :] < lens[:, None]).astype(jnp.int32)
        return q, k, v, mask[:, None, None, :]

    def f32(*xs):
        return tuple(x.astype(jnp.float32) for x in xs)

    def ticked(key: str, before: Dict[str, float]) -> None:
        now = attention_blocks()
        check(now.get(key, 0) > before.get(key, 0),
              f"attention_blocks_traced_total{{path={key!r}}} did not tick — "
              f"the kernel was not selected ({now})")

    def close(name: str, got, want, tol: float) -> float:
        got = np.asarray(got, dtype=np.float32)
        want = np.asarray(want, dtype=np.float32)
        check(got.shape == want.shape, f"{name}: shape {got.shape}")
        check(bool(np.isfinite(got).all()), f"{name}: non-finite values")
        scale = max(float(np.abs(want).max()), 1e-6)
        err = float(np.abs(got - want).max()) / scale
        check(err <= tol, f"{name}: max error {err:.4g} of the reference's "
                          f"range, tolerance {tol}")
        return round(err, 5)

    # bf16 operands and a bf16 result against float32 arithmetic: a few
    # bf16 ulps (2^-8) of the output's range.
    tol = 2e-2
    errors: Dict[str, float] = {}

    def reference(fn, *args):
        """A float32 reference at reduced matmul precision would hide kernel
        faults of the size looked for, so it runs at "highest". Only the
        reference: Mosaic refuses a kernel's bf16 matmul traced under that
        setting ("Bad lhs type")."""
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    for L in (2048, 4096):
        q, k, v, mask = inputs(L, seed=L)
        before = attention_blocks()
        got = jax.jit(lambda q, k, v, m: fa.flash_attention(
            q, k, v, m, interpret=interpret))(q, k, v, mask)
        ticked("flash", before)
        want = reference(dot_product_attention, *f32(q, k, v), mask)
        errors[f"flash_attention_L{L}"] = close(
            f"flash_attention L={L}", got, want, tol)

    # The whole-row kernel on the projections' own [B, L, H*D] layout, at
    # the two lengths the benchmark's cells run.
    def lane_dense(t):
        return t.transpose(0, 2, 1, 3).reshape(B, t.shape[2], H * D)

    for L in (512, 64):
        q, k, v, mask = inputs(L, seed=L)
        check(fa.selects_whole_row(L, L, H, D, key_padding=True,
                                   dtype=q.dtype),
              f"selects_whole_row({L}) is false")
        before = attention_blocks()
        got = jax.jit(lambda q, k, v, m: fa.whole_row_attention(
            lane_dense(q), lane_dense(k), lane_dense(v), m, n_heads=H,
            interpret=interpret))(q, k, v, mask)
        ticked("whole_row", before)
        want = reference(dot_product_attention, *f32(q, k, v), mask)
        errors[f"whole_row_attention_L{L}"] = close(
            f"whole_row_attention L={L}", got, lane_dense(want), tol)

    # Training pair at L 512: forward and the gradient of a weighted sum.
    q, k, v, mask = inputs(512, seed=512)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def grads(attn):
        def f(q, k, v):
            return jnp.sum(attn(q, k, v, mask).astype(jnp.float32) * w)
        return jax.grad(f, argnums=(0, 1, 2))

    def trainable(q, k, v, m):
        return fa.flash_attention_trainable(q, k, v, m, interpret=interpret)

    before = attention_blocks()
    got_o = jax.jit(trainable)(q, k, v, mask)
    got_g = jax.jit(grads(trainable))(q, k, v)
    ticked("flash_train", before)
    want_o = reference(dot_product_attention, *f32(q, k, v), mask)
    want_g = reference(grads(dot_product_attention), *f32(q, k, v))
    errors["flash_train_fwd_L512"] = close(
        "flash_attention_trainable forward", got_o, want_o, tol)
    for name, g, wg in zip("qkv", got_g, want_g):
        errors[f"flash_train_d{name}_L512"] = close(
            f"flash_attention_trainable d{name}", g, wg, tol)

    # T5 bias kernel at L 2048: unscaled scores + bucketed bias.
    L, buckets, max_distance = 2048, 32, 128
    q, k, v, mask = inputs(L, seed=5)
    q = (q.astype(jnp.float32) * D ** -0.5).astype(jnp.bfloat16)
    table = jax.random.normal(jax.random.PRNGKey(9), (buckets, H))
    before = attention_blocks()
    got = jax.jit(lambda q, k, v, m, t: fa.flash_attention_t5(
        q, k, v, m, t, bidirectional=True, max_distance=max_distance,
        scale=1.0, interpret=interpret))(q, k, v, mask, table)
    check(got is not None, "flash_attention_t5 declined L=2048")
    ticked("t5_flash", before)

    def t5_dense(q, k, v, mask, table):
        pos = jnp.arange(L, dtype=jnp.int32)
        bucket = relative_position_bucket(
            pos[None, :] - pos[:, None], True, buckets, max_distance)
        bias = table[bucket].transpose(2, 0, 1)[None]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) + bias
        s = jnp.where(mask > 0, s, NEG_INF)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    want = reference(t5_dense, *f32(q, k, v), mask, table)
    errors["flash_attention_t5_L2048"] = close(
        "flash_attention_t5", got, want, tol)

    # Ring fold: two 1024-key hops folded into one state == dense
    # attention over all 2048 keys.
    hop = 1024
    q, k, v, mask = inputs(2 * hop, seed=11)
    q = q[:, :, :hop]

    def two_hops(q, k, v, mask):
        m = jnp.full((B, H, hop, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((B, H, hop, 1), jnp.float32)
        acc = jnp.zeros((B, H, hop, D), jnp.float32)
        for i in range(2):
            s = slice(i * hop, (i + 1) * hop)
            m, l, acc = fa.flash_fold(
                q, k[:, :, s], v[:, :, s], mask[..., s], m, l, acc,
                interpret=interpret)
        return acc / jnp.maximum(l, 1e-30)

    got = jax.jit(two_hops)(q, k, v, mask)
    want = reference(dot_product_attention, *f32(q, k, v), mask)
    errors["flash_fold_hop1024"] = close("flash_fold", got, want, tol)

    emit("kernels", ok=True, interpret=interpret, tolerance=tol,
         max_error_of_range=errors,
         wall_s=round(time.perf_counter() - t0, 2))


# ---- phase: drain --------------------------------------------------------

def classify_extra() -> Dict[str, Any]:
    return {"text_field": "text", "allow_fallback": False,
            "result_format": "columnar", "topk": 5,
            "model_config": dict(CLASSIFY_MODEL)}


def phase_drain(stack: Stack, data: Dict[str, Any]) -> Dict[str, Any]:
    """Bulk drain over ``/v1/jobs``: BERT-base classify shards plus one
    768-wide summarize shard. Returns what later phases compare against."""
    from agent_tpu.data.native import native_available

    extra = classify_extra()
    # The first shard alone pays params + compile; the rest run warm.
    t0 = time.perf_counter()
    first = stack.wait_jobs(stack.post_csv_job(
        data["classify_csv"], map_op="map_classify_tpu",
        total_rows=DRAIN_SHARD, shard_size=DRAIN_SHARD, extra=extra))
    t1 = time.perf_counter()
    if REQUIRED_PLATFORM == "tpu":  # the CPU runtime selects no kernel
        blocks = attention_blocks(stack.agent.obs)
        check(blocks.get("whole_row", 0) >= CLASSIFY_MODEL["n_layers"],
              "the classify program did not take the whole-row kernel: "
              f"attention_blocks_traced_total is {blocks}")
    ids = stack.post_csv_job(
        data["classify_csv"], map_op="map_classify_tpu",
        total_rows=DRAIN_ROWS, shard_size=DRAIN_SHARD, extra=extra)
    shards = stack.wait_jobs(ids)
    t2 = time.perf_counter()
    indices: List[List[int]] = []
    scores: List[List[float]] = []
    for snap in shards:  # job_ids come back in shard order
        indices.extend(snap["result"]["indices"])
        scores.extend(snap["result"]["scores"])
    check(len(indices) == DRAIN_ROWS, f"{len(indices)} rows classified")
    check(shards[0]["result"]["indices"] == first[0]["result"]["indices"],
          "the same shard classified twice gave different labels")

    summarize = stack.wait_jobs(stack.post_csv_job(
        data["summarize_csv"], map_op="map_summarize",
        total_rows=SUMMARIZE_ROWS, shard_size=SUMMARIZE_ROWS,
        extra={"text_field": "text", "max_length": SUMMARIZE_MAX_NEW,
               "model_config": dict(SEQ2SEQ_MODEL)}))
    t3 = time.perf_counter()
    summaries = summarize[0]["result"]["summaries"]
    check(len(summaries) == SUMMARIZE_ROWS, f"{len(summaries)} summaries")

    agreement = classify_reference(data["classify_texts"], indices, scores)
    n_shards = len(ids)
    steady = (t2 - t1) / n_shards
    emit("drain", ok=True, rows=DRAIN_ROWS, shards=n_shards,
         failed_shards=stack.controller.counts().get("failed", 0),
         csv_scanner="native" if native_available() else "python",
         first_shard_s=round(t1 - t0, 2),
         steady_s=round(t2 - t1, 2),
         compile_and_load_s=round(max(0.0, (t1 - t0) - steady), 2),
         summarize_rows=SUMMARIZE_ROWS,
         summarize_s=round(t3 - t2, 2),
         reference=agreement, wall_s=round(t3 - t0, 2))
    return {"indices": indices, "scores": scores, "summaries": summaries}


def reference_probs(texts: Sequence[str]):
    """Class probabilities from ``models.encoder.forward`` on the serving
    model's parameters, in float32 with ``dot_product_attention`` at the
    highest matmul precision — [len(texts), n_classes] numpy."""
    import dataclasses

    import jax
    import numpy as np

    from agent_tpu.models import encoder
    from agent_tpu.models.layers import dot_product_attention
    from agent_tpu.models.tokenizer import byte_encode_pad
    from agent_tpu.ops._model_common import config_from_payload
    from agent_tpu.ops.map_classify_tpu import DEFAULT_MODEL_ID

    cfg = dataclasses.replace(
        config_from_payload({"model_config": CLASSIFY_MODEL},
                            encoder.EncoderConfig),
        dtype="float32",
    )
    params = encoder.init_params(cfg, model_id=DEFAULT_MODEL_ID)
    ids, lengths = byte_encode_pad(
        list(texts), buckets=(cfg.max_len,), max_len_cap=cfg.max_len)
    mask = np.arange(ids.shape[1])[None, :] < lengths[:, None]
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, i, m: encoder.forward(
            p, i, m, cfg, attn_fn=dot_product_attention
        ))(params, ids.astype(np.int32), mask.astype(np.int32))
        return np.asarray(jax.nn.softmax(logits, axis=-1))


# bf16 through the whole stack moves a probability by a percent or two of
# itself (1.4% seen on the chip); a wrong program (other weights, other
# tokens, a broken kernel) moves it by its whole value.
REFERENCE_REL_TOL = 0.05


def agrees_with_reference(what: str, probs, got_cls: int, got_p: float
                          ) -> Tuple[bool, float]:
    """One served top-1 against one reference row → (exact, relative gap).

    With random weights the two largest of 1000 logits can sit closer than
    bf16 resolves, so "agrees" is judged on probabilities: the served class
    must be, in the reference, within tolerance of the reference's own
    best, and its score within tolerance of the reference's probability
    for that class."""
    best, ref_p = float(probs.max()), float(probs[got_cls])
    gap = max(abs(ref_p - best), abs(got_p - ref_p)) / best
    check(gap <= REFERENCE_REL_TOL,
          f"{what}: served top-1 class {got_cls} p={got_p:.6f}; the float32 "
          f"reference gives it {ref_p:.6f} and its own best {best:.6f}")
    return got_cls == int(probs.argmax()), gap


def classify_reference(texts: Sequence[str], indices, scores
                       ) -> Dict[str, Any]:
    """Top-1 of a sample of drained rows against the float32 reference."""
    step = max(1, len(texts) // REFERENCE_ROWS)
    rows = list(range(0, len(texts), step))[:REFERENCE_ROWS]
    probs = reference_probs([texts[i] for i in rows])
    verdicts = [
        agrees_with_reference(
            f"row {i}", probs[j], int(indices[i][0]), float(scores[i][0]))
        for j, i in enumerate(rows)
    ]
    return {"rows": len(rows),
            "top1_exact": sum(exact for exact, _ in verdicts),
            "worst_relative_gap": round(max(g for _, g in verdicts), 4),
            "tolerance": REFERENCE_REL_TOL}


# ---- phase: infer --------------------------------------------------------

def phase_infer(stack: Stack, data: Dict[str, Any], drained: Dict[str, Any]
                ) -> None:
    """A handful of ``POST /v1/infer`` requests, classify and summarize,
    short and long, through the front door to the serving ops (default
    paged KV layout). The summarize tokens are compared with what
    ``map_summarize`` returned for the same rows in the drain, and the two
    engines (one per length bucket) must end with one step and one insert
    executable each: the second request of a bucket is served warm."""
    from agent_tpu.ops import serve_infer

    t0 = time.perf_counter()
    results = []
    s_params = {"model_config": dict(SEQ2SEQ_MODEL),
                "max_length": SUMMARIZE_MAX_NEW}
    # Rows 0/2 are short, 1/3 long (build_data): two length buckets.
    for row in range(4):
        text = data["summarize_texts"][row]
        snap = stack.infer("summarize", text, s_params)
        check(snap["tokens"] > 0, f"summarize row {row}: no tokens")
        got, want = snap["result"]["summary"], drained["summaries"][row]
        # Both entry points return decoded text only; every character is at
        # least one byte-token, so N equal characters are ≥ N equal tokens.
        same = len(os.path.commonprefix([got, want]))
        need = min(INFER_PREFIX_TOKENS, len(want), len(got))
        check(same >= need,
              f"summarize row {row}: engine and scan agree on {same} "
              f"characters, need {need}: {got[:40]!r} vs {want[:40]!r}")
        results.append({
            "op": "summarize", "row": row, "bytes_in": len(text),
            "tokens": snap["tokens"], "ttft_ms": snap["ttft_ms"],
            "first_difference": None if got == want else same,
        })
    c_params = {"model_config": dict(CLASSIFY_MODEL), "topk": 5}
    c_texts = [data["classify_texts"][0][:48],
               data["classify_texts"][1][:ROW_BYTES]]
    probs = reference_probs(c_texts)
    for j, text in enumerate(c_texts):
        snap = stack.infer("classify", text, c_params)
        check(len(snap["result"]["indices"]) == 5, f"classify request {j}")
        exact, gap = agrees_with_reference(
            f"classify request {j}", probs[j],
            int(snap["result"]["indices"][0]),
            float(snap["result"]["scores"][0]))
        results.append({
            "op": "classify", "bytes_in": len(text),
            "ttft_ms": snap["ttft_ms"], "top1_exact": exact,
            "relative_gap": round(gap, 4),
            "top5": snap["result"]["indices"],
        })
    # The second request is the drain's row 1 at another batch shape.
    results[-1]["drain_top5"] = drained["indices"][1]
    # Rows 2/3 joined the engines rows 0/1 built: a warm engine holds ONE
    # step and ONE insert executable. A count, not a timing — an engine that
    # retraces at every join answers correctly and would pass without it.
    engines = serve_infer.engine_executables()
    check(len(engines) == 2, f"want one engine per bucket, got {engines}")
    for eng in engines:
        check(eng["step"] == 1 and eng["insert"] == 1,
              f"summarize rows 2/3 were not served warm: {engines}")
    emit("infer", ok=True, requests=results, engines=engines,
         kv_layout=stack.agent.config.serve.kv_layout,
         wall_s=round(time.perf_counter() - t0, 2))


# ---- phase: train --------------------------------------------------------

def phase_train(stack: Stack, data: Dict[str, Any], tmp: str) -> None:
    """A ``train_classifier`` job at BERT-base width, sequence 512, then the
    ``.npz`` it wrote served by one ``map_classify_tpu`` shard via
    ``model_path`` — the lifecycle ``ops/train_classifier.py`` promises."""
    import importlib
    import math

    fa = importlib.import_module("agent_tpu.kernels.flash_attention")
    runtime = stack.agent.runtime
    t0 = time.perf_counter()
    out_path = os.path.join(tmp, "trained.npz")
    before = attention_blocks(stack.agent.obs)
    train = stack.wait_jobs([stack.post_job("train_classifier", {
        "source_uri": data["train_csv"], "text_field": "text",
        "label_field": "label", "output_path": out_path,
        "model_config": dict(CLASSIFY_MODEL),
        "batch_size": TRAIN_BATCH, "epochs": TRAIN_EPOCHS,
        "learning_rate": 1e-4, "seed": 0,
    })])[0]["result"]
    t1 = time.perf_counter()
    first, last = train["first_epoch_loss"], train["last_epoch_loss"]
    check(math.isfinite(first) and math.isfinite(last),
          f"losses not finite: {first}, {last}")
    check(last <= first, f"loss rose: {first} → {last}")
    check(os.path.exists(out_path), "no .npz written")
    seq = CLASSIFY_MODEL["max_len"]
    # The TPU runtime counts a program's temporaries as *reserved*, not
    # *in use*: the step's footprint shows in peak_bytes_reserved.
    stats = runtime.devices[0].memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    reserved = stats.get("peak_bytes_reserved")
    if REQUIRED_PLATFORM == "tpu":  # no kernel, no memory stats on the CPU
        check(fa.selects_flash_train(
            seq, batch=TRAIN_BATCH, n_heads=CLASSIFY_MODEL["n_heads"],
            mesh=runtime.mesh,
        ), f"selects_flash_train({seq}) is false")
        check(attention_blocks(stack.agent.obs).get("flash_train", 0)
              > before.get("flash_train", 0),
              "the training step did not select flash_train")
        check(peak is not None and limit is not None
              and max(peak, reserved or 0) < limit,
              f"peak_bytes_in_use {peak} / peak_bytes_reserved {reserved} "
              f"not under bytes_limit {limit}")

    served = stack.wait_jobs(stack.post_csv_job(
        data["train_csv"], map_op="map_classify_tpu",
        total_rows=TRAIN_BATCH * 2, shard_size=TRAIN_BATCH * 2,
        extra={"text_field": "text", "allow_fallback": False,
               "result_format": "columnar", "topk": 1,
               "model_path": out_path,
               "model_config": train["model_config"]}))[0]["result"]
    check(served["model_path"] == out_path, "served another model")
    hits = sum(
        int(row[0] == y)
        for row, y in zip(served["indices"], data["train_labels"])
    )
    emit("train", ok=True, steps=train["n_steps"], batch=TRAIN_BATCH,
         seq_len=seq, remat=False, first_epoch_loss=round(first, 4),
         last_epoch_loss=round(last, 4),
         eval_accuracy=train["eval_accuracy"],
         flash_train_selected=REQUIRED_PLATFORM == "tpu",
         peak_bytes_in_use=peak, peak_bytes_reserved=reserved,
         bytes_limit=limit,
         served_rows=len(served["indices"]), served_top1_correct=hits,
         train_s=round(t1 - t0, 2), wall_s=round(time.perf_counter() - t0, 2))


# ---- one chip ------------------------------------------------------------

SMOKE_TASKS = ("map_classify_tpu", "map_summarize", "train_classifier",
               "serve_classify", "serve_summarize")


def run_one_chip(seed: int) -> Dict[str, Any]:
    import jax

    runtime, device = phase_device()
    phase_kernels()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        data = build_data(tmp, seed)
        stack = Stack(runtime, SMOKE_TASKS)
        try:
            # Training first: a batch-128 step without remat takes 14.75 GB
            # of a chip's 16 by the compiler's own account, and the params
            # store never evicts — behind the drain's and the serving
            # engines' resident models (~1.2 GB) it would not fit.
            phase_train(stack, data, tmp)
            drained = phase_drain(stack, data)
            phase_infer(stack, data, drained)
        finally:
            stack.close()
    emit("cache", compile_cache_dir=jax.config.jax_compilation_cache_dir,
         cache_entries_after=count_entries(
             jax.config.jax_compilation_cache_dir))
    return device


# ---- four chips ----------------------------------------------------------

# (stage, n_agents, chips per agent, MESH_SHAPE)
FLEET_STAGES = (
    ("one_chip", 1, 1, ""),
    ("four_agents", 4, 1, ""),
    ("mesh_dp4", 1, 4, "dp=4"),
)


def fleet_stage(name: str, n_agents: int, chips: int, mesh: str,
                csv_path: str, tmp: str) -> Dict[str, Any]:
    """One drain of the seeded BERT-base job by ``n_agents`` child agents,
    each pinned to ``chips`` chips. The parent (this process) holds the
    controller and never touches JAX."""
    from agent_tpu.agent import fleet
    from agent_tpu.config import SchedConfig
    from agent_tpu.controller.core import Controller
    from agent_tpu.controller.server import ControllerServer

    extra = classify_extra()
    warm_file = os.path.join(tmp, f"warm_{name}.json")
    with open(warm_file, "w", encoding="utf-8") as f:
        json.dump([{"op": "map_classify_tpu", "payload": {
            **extra, "source_uri": csv_path, "start_row": 0,
            "shard_size": FLEET_SHARD,
        }}], f)
    log_dir = os.path.join(tmp, f"logs_{name}")
    # The fair policy spreads shards over idle members.
    controller = Controller(lease_ttl_sec=JOB_TIMEOUT_S * 2,
                            sched=SchedConfig(policy="fair"))
    server = ControllerServer(controller).start()
    t0 = time.perf_counter()
    members = fleet.spawn_fleet(
        n_agents, chips, controller_url=server.url,
        tasks="map_classify_tpu", platform=REQUIRED_PLATFORM,
        name_prefix=name,
        mesh_shape=mesh, warm_file=warm_file, log_dir=log_dir,
        extra_env={"IDLE_SLEEP_SEC": "0.02", "MAX_TASKS": "1"},
    )
    try:
        ready = fleet.wait_for_agents(
            controller.agents_summary, members.names,
            timeout=FLEET_TIMEOUT_S, fleet=members)
        check(ready, f"{name}: members not ready (alive={members.alive()}, "
                     f"exits={members.poll_failures()})\n"
                     + tail_logs(log_dir))
        t1 = time.perf_counter()
        shard_ids, _ = controller.submit_csv_job(
            csv_path, total_rows=FLEET_ROWS, shard_size=FLEET_SHARD,
            map_op="map_classify_tpu", extra_payload=extra)
        deadline = time.monotonic() + FLEET_TIMEOUT_S
        while not controller.drained():
            check(time.monotonic() < deadline,
                  f"{name}: drain not done: {controller.counts()}\n"
                  + tail_logs(log_dir))
            check(not members.poll_failures(),
                  f"{name}: a member died: {members.poll_failures()}\n"
                  + tail_logs(log_dir))
            time.sleep(0.02)
        t2 = time.perf_counter()
        per_agent = {n: 0 for n in members.names}
        indices, scores = [], []
        for jid in shard_ids:
            snap = controller.job_snapshot(jid)
            check(snap["state"] == "succeeded", f"{name}: {jid} {snap}")
            check_result_body(snap["result"], f"{name}/{jid}")
            indices.extend(snap["result"]["indices"])
            scores.extend(snap["result"]["scores"])
            per_agent[snap["agent"]] = per_agent.get(snap["agent"], 0) + 1
        check(all(n > 0 for n in per_agent.values()),
              f"{name}: a member completed no shard: {per_agent}")
        # What each member's runtime reported on its last lease poll.
        time.sleep(0.5)
        devices = {
            a: (e.get("metrics") or {}).get("device") or {}
            for a, e in controller.agents_summary().items()
        }
    finally:
        members.stop()
        server.stop()
    for agent_name, dev in devices.items():
        check(dev.get("platform") == REQUIRED_PLATFORM,
              f"{name}/{agent_name}: {dev}")
        check(dev.get("n_devices") == chips,
              f"{name}/{agent_name}: owns {dev.get('n_devices')} devices")
        used = [d.get("used", 0) for d in dev.get("hbm_per_device") or []]
        if REQUIRED_PLATFORM == "tpu":  # the CPU reports no memory stats
            check(len(used) == chips and all(u > 0 for u in used),
                  f"{name}/{agent_name}: bytes_in_use per device {used}")
    record = {
        "stage": name, "agents": n_agents, "chips_per_agent": chips,
        "mesh": mesh or None, "rows": FLEET_ROWS, "shards": len(shard_ids),
        "per_agent_shards": per_agent,
        "ready_s": round(t1 - t0, 2), "drain_s": round(t2 - t1, 2),
        "bytes_in_use": {
            a: [d.get("used") for d in dev.get("hbm_per_device") or []]
            for a, dev in devices.items()
        },
    }
    return {"record": record, "indices": indices, "scores": scores}


def tail_logs(log_dir: str, n: int = 3000) -> str:
    out = []
    if os.path.isdir(log_dir):
        for name in sorted(os.listdir(log_dir)):
            with open(os.path.join(log_dir, name), "rb") as f:
                data = f.read()[-n:]
            out.append(f"--- {name} ---\n{data.decode(errors='replace')}")
    return "\n".join(out)


def compare_to_reference(name: str, ref: Dict[str, Any], got: Dict[str, Any]
                         ) -> Dict[str, Any]:
    """Same top-1 label for every row, scores within bf16 tolerance."""
    check(len(got["indices"]) == len(ref["indices"]), f"{name}: row count")
    flips = [
        i for i, (a, b) in enumerate(zip(ref["indices"], got["indices"]))
        if a[0] != b[0]
    ]
    check(not flips, f"{name}: top-1 differs from the one-chip reference "
                     f"on {len(flips)} rows, first {flips[:5]}")
    worst = max(
        abs(a[0] - b[0]) / max(abs(a[0]), 1e-12)
        for a, b in zip(ref["scores"], got["scores"])
    )
    check(worst <= 2 ** -6, f"{name}: top-1 score off by {worst:.4g}")
    return {
        "top1_same": True, "worst_relative_score_gap": round(worst, 6),
        "bit_identical": (ref["indices"] == got["indices"]
                          and ref["scores"] == got["scores"]),
    }


def tp_child_main() -> int:
    """The child that holds all four chips: the body of
    ``__graft_entry__.dryrun_multichip`` on real devices (dp=2, tp=2:
    sharded training step, psum, tp-sharded serving). Prints one JSON line."""
    import jax

    import __graft_entry__ as graft

    devices = jax.devices()
    check(devices[0].platform == "tpu" and len(devices) == 4,
          f"tp child wants four TPU chips, has {devices}")
    # The body's assertions compare sharded with unsharded float32 results;
    # on a TPU that needs float32 matmuls, not the default bf16 passes.
    with jax.default_matmul_precision("highest"):
        report = graft.dryrun_multichip(4, {"dp": 2, "tp": 2})
    check(report["param_device_span"] == 4,
          f"tp-sharded parameters span {report['param_device_span']} devices")
    check(report["param_leaves_split"] > 0, "no parameter leaf was split")
    check(all(b and b > 0 for b in report["bytes_in_use"]),
          f"bytes_in_use per device: {report['bytes_in_use']}")
    print(json.dumps({"tp_child": report, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


def run_tp_child(tmp: str) -> Dict[str, Any]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    log = os.path.join(tmp, "tp_child.log")
    with open(log, "wb") as err:
        proc = subprocess.run(
            [sys.executable, "-c",
             "import sys, chip_smoke; sys.exit(chip_smoke.tp_child_main())"],
            cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=err,
            timeout=FLEET_TIMEOUT_S * 2,
        )
    with open(log, "rb") as f:
        tail = f.read()[-3000:].decode(errors="replace")
    check(proc.returncode == 0, f"tp child exited {proc.returncode}\n{tail}")
    lines = [ln for ln in proc.stdout.decode().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def run_four_chips(seed: int) -> Dict[str, Any]:
    """Only the cross-chip paths and what they are compared with."""
    import numpy as np

    with tempfile.TemporaryDirectory(prefix="chip_smoke4_") as tmp:
        rng = np.random.default_rng(seed)
        csv_path = os.path.join(tmp, "fleet.csv")
        write_csv(csv_path, "id,text", [
            (i, make_text(rng, ROW_BYTES)) for i in range(FLEET_ROWS)
        ])
        runs: Dict[str, Dict[str, Any]] = {}
        for name, n_agents, chips, mesh in FLEET_STAGES:
            runs[name] = fleet_stage(name, n_agents, chips, mesh,
                                     csv_path, tmp)
            record = runs[name]["record"]
            if name != "one_chip":
                record["vs_one_chip"] = compare_to_reference(
                    name, runs["one_chip"], runs[name])
            emit("fleet", ok=True, **record)
        child = run_tp_child(tmp)
        emit("tp_child", ok=True, **child["tp_child"])
    return child["device"]


# ---- entry ---------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926,
                    help="seed of the generated data")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the cross-chip paths (needs four chips)")
    args = ap.parse_args(argv)
    run: Callable[[int], Dict[str, Any]] = (
        run_four_chips if args.chips == 4 else run_one_chip
    )
    global RECORDS
    RECORDS, sys.stdout = sys.stdout, sys.stderr
    t0 = time.perf_counter()
    try:
        try:
            device = run(args.seed)
        except Exception as exc:  # noqa: BLE001 — reported, then non-zero
            import traceback

            traceback.print_exc()
            print(json.dumps({
                "ok": False, "error": f"{type(exc).__name__}: {exc}"[:2000],
                "wall_s": round(time.perf_counter() - t0, 2),
            }), file=RECORDS, flush=True)
            return 1
        emit("total", wall_s=round(time.perf_counter() - t0, 2))
        print(json.dumps({"ok": True, "device": device}),
              file=RECORDS, flush=True)
        return 0
    finally:
        sys.stdout, RECORDS = RECORDS, None


if __name__ == "__main__":
    sys.exit(main())
