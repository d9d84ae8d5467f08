"""Traffic kind ``score``: pre-tokenized documents as CSV jobs over ``POST
/v1/jobs`` for ``map_score_lm``, the backlog outlasting the window, drained
by the in-process agent — the ``drain`` kind's measurement (its clock, its
window that opens and closes at an acceptance, its job helpers, imported
from it) over another kind of row and another check.

Rows are documents of token ids: their lengths the quantiles of the traffic
file's ``doc_tokens`` in seeded order, their ids Zipf-distributed over the
whole vocabulary through a seeded permutation. ``correct``: a seeded sample
of the documents the window answered goes through the plain float32
reference (``benchmarks/reference/<config.reference>.py``), which makes the
weights itself from the model id; their ``block_logprob_sums`` are compared.

The ``run`` record carries the keys the ``.drain`` and ``.setup`` readers
read (``kind: "drain"``, ``op``, ``agent_metrics``, ``controller_cpu_s``,
``post_span_s``, ``compiles_in_window``, ``trace``), and for the language
model's own readers ``lm_needed`` (operations and bytes a document needs,
``harness/lm_flops.py``) and, traced, ``op_times`` (``harness/op_times.py``
over the readers' ``OP_PATTERNS``)."""

from __future__ import annotations

import glob
import math
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmarks.harness import (lm_flops, manifest, op_times, procs, schedule,
                                stack, stats, trace_reduce)
from benchmarks.harness.compile_count import CompileCounter
from benchmarks.harness.stack import check, emit

drain = manifest.load_kind("drain")


def documents(traffic: Dict[str, Any], vocab_size: int, seed: int, n: int
              ) -> List[np.ndarray]:
    """``n`` documents of token ids, no two alike."""
    lengths = schedule.size_set(traffic["doc_tokens"], n)
    schedule.rng_of(seed, "order").shuffle(lengths)
    spec = traffic["token_ids"]
    check(spec["dist"] == "zipf", f"unknown id distribution {spec['dist']!r}")
    weight = 1.0 / np.arange(1, vocab_size + 1) ** float(spec["exponent"])
    cdf = np.cumsum(weight / weight.sum())
    rng = schedule.rng_of(seed, "ids")
    by_rank = rng.permutation(vocab_size)
    ranks = np.minimum(np.searchsorted(cdf, rng.random(int(lengths.sum()))),
                       vocab_size - 1)
    ids = by_rank[ranks].astype(np.int32)
    return np.split(ids, np.cumsum(lengths)[:-1])


def write_csv(path: str, docs: List[np.ndarray]) -> None:
    """``id,ids``: one document a row, its ids space-separated."""
    with open(path, "wb") as f:
        f.write(b"id,ids\n")
        for i, doc in enumerate(docs):
            f.write(f"{i},".encode("ascii"))
            f.write(" ".join(map(str, doc.tolist())).encode("ascii"))
            f.write(b"\n")


def check_documents(ctx: Dict[str, Any], docs: List[np.ndarray],
                    accepted: List[Tuple[int, str, Dict[str, Any]]]
                    ) -> List[Dict[str, Any]]:
    """A seeded sample of the answered documents against the reference.
    ``accepted`` is ``(first row, model id, result body)`` per shard."""
    config = ctx["config"]
    ref = manifest.load_reference(config["reference"])
    spec = config["check"]
    pool = [(start + j, model_id, body, j) for start, model_id, body in accepted
            for j in range(len(body["n_tokens"]))]
    rng = schedule.rng_of(ctx["seed"], "check")
    take = rng.choice(len(pool), size=min(int(spec["docs"]), len(pool)),
                      replace=False)
    sample = sorted((pool[int(i)] for i in take), key=lambda s: (s[1], s[0]))
    served, reference, n_tokens = [], [], []
    for model_id in sorted({m for _, m, _, _ in sample}):
        mine = [s for s in sample if s[1] == model_id]
        logprobs = ref.token_logprobs(config["model"], model_id,
                                      [docs[r] for r, _, _, _ in mine])
        for (_, _, body, j), lp in zip(mine, logprobs):
            served.append(body["block_logprob_sums"][j])
            reference.append(ref.block_sums(lp).tolist())
            n_tokens.append(int(body["n_tokens"][j]))
    ctx["check_data"] = {
        "served": np.asarray([x for s in served for x in s], np.float64),
        "reference": np.asarray([x for r in reference for x in r], np.float64),
        "n_tokens": np.asarray(n_tokens, np.int64),
    }
    values = ref.compare(served, reference, n_tokens) if sample else {}
    return [{
        "number": number, "value": float(values.get(number, float("inf"))),
        "limit": float(limit), "docs": len(sample),
        "ok": bool(number in values and values[number] <= limit),
    } for number, limit in spec["limits"].items()]


def op_patterns(ctx: Dict[str, Any]) -> Dict[str, str]:
    """``OP_PATTERNS`` of the cell's per-layer readers."""
    patterns: Dict[str, str] = {}
    for entry in manifest.metrics_of_cell(
            ctx["manifest"], ctx["cell"]["name"], "per_layer"):
        patterns.update(getattr(
            manifest.load_layer_metric(entry["name"]), "OP_PATTERNS", {}))
    return patterns


def run_cell(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    seed, seconds = int(ctx["seed"]), float(ctx["seconds"])
    out = stack.output_dir(cell["name"], seed, int(ctx["trace"]))
    device = stack.init_device(int(cell["chips"]))
    counter = CompileCounter().install()

    shard, tenants = int(traffic["shard_rows"]), int(traffic["tenants"])
    job_rows = int(traffic["job_rows"])
    check(job_rows % shard == 0, "job_rows must be whole shards")
    lead_in = int(traffic["lead_in_shards"])
    n_jobs = math.ceil((float(traffic["backlog_rows_per_s"]) * seconds
                        + (lead_in + 2) * shard) / job_rows)
    n_jobs = tenants * math.ceil(n_jobs / tenants)
    warm_rows = tenants * shard
    docs = documents(traffic, int(config["model"]["vocab_size"]), seed,
                     n_jobs * job_rows + warm_rows)
    warm, docs = docs[:warm_rows], docs[warm_rows:]
    payloads = [drain.model_payload(config, seed, k) for k in range(tenants)]
    map_op = config["op"]["map_op"]
    csvs: List[str] = []

    def submit(name: str, rows: List[np.ndarray], tenant: int) -> List[str]:
        path = os.path.join(out, name)
        write_csv(path, rows)
        csvs.append(path)
        return drain.post_csv_job(url, path, len(rows), shard, map_op,
                                  payloads[tenant][1], f"tenant-{tenant}")

    controller = procs.ControllerProcess(os.path.join(out, "controller.log"))
    url = controller.url
    agent: Optional[stack.AgentStack] = None
    try:
        clock = drain.PostClock()
        agent = stack.AgentStack(controller.url, config["op"]["tasks"])
        agent.agent.post_session_factory = clock.factory

        # ---- warm-up: one shard a tenant, the cell's own shapes ---------
        warm_ids = [jid for k in range(tenants) for jid in submit(
            f"warm-{k}.csv", warm[k * shard:(k + 1) * shard], k)]
        clock.wait_for(lambda: len(clock.posts) >= len(warm_ids),
                       drain.JOB_TIMEOUT_S, agent.alive)
        for jid in warm_ids:
            snap = drain.job_snapshot(url, jid)
            check(snap["state"] == "succeeded",
                  f"warm-up shard {jid} {snap['state']}: {snap.get('error')}")
        warm_totals = counter.totals()

        # ---- the backlog; the window opens at an acceptance -------------
        where: Dict[str, Tuple[int, str]] = {}
        for j in range(n_jobs):
            k = j % tenants
            ids = submit(f"job-{j}.csv", docs[j * job_rows:(j + 1) * job_rows], k)
            for i, jid in enumerate(ids):
                where[jid] = (j * job_rows + i * shard, payloads[k][0])
        n0 = len(clock.posts)
        clock.wait_for(lambda: len(clock.posts) >= n0 + lead_in + 1,
                       drain.JOB_TIMEOUT_S, agent.alive)
        open_at = n0 + lead_in
        t_open = clock.posts[open_at][0]
        setup_s = t_open - ctx["t_start"]
        m0, cpu0 = agent.metrics(), controller.cpu_seconds()
        tracer = None
        if ctx["trace"]:
            tracer = stack.Tracer(os.path.join(out, "trace"))
            tracer.capture(t_open, traffic)
        clock.wait_for(
            lambda: clock.posts[-1][0] >= t_open + seconds,
            seconds + 120.0, agent.alive)
        with clock.cond:
            posts = list(clock.posts)
        close_at = next(i for i, p in enumerate(posts)
                        if i > open_at and p[0] >= t_open + seconds)
        t_close = posts[close_at][0]
        m1, cpu1 = agent.metrics(), controller.cpu_seconds()
        compiles = counter.between(t_open, t_close)
        agent.close()
        post_spans = [(b - a) / 1e9 for name, a, b in
                      agent.host_spans(t_open, t_close) if name == "post"
                      and t_open <= b / 1e9 <= t_close]
        peak = stack.memory_peak_bytes()
        emit("memory", **stack.memory_stats())

        # ---- what the window answered -----------------------------------
        window = posts[open_at + 1:close_at + 1]
        accepted, failed = [], 0
        for _, jid, status, code in window:
            snap = drain.job_snapshot(url, jid) if jid in where else None
            if (snap is None or status != "succeeded" or code != 200
                    or snap["state"] != "succeeded"):
                failed += 1
                continue
            body = snap["result"]
            good = (isinstance(body, dict) and body.get("ok") is True
                    and body.get("device") == stack.REQUIRED_PLATFORM
                    and "fallback" not in body
                    and len(body.get("n_tokens") or []) == shard
                    and len(body.get("logprob_sum") or []) == shard
                    and len(body.get("block_logprob_sums") or []) == shard)
            if good:
                accepted.append((*where[jid], body))
            else:
                failed += 1
        window_s = t_close - t_open
        n_rows = sum(len(b["n_tokens"]) for _, _, b in accepted)
        check(len(accepted) > 0, f"no shard succeeded in the window: {window}")
        backlog_left = len(where) - (close_at - n0 + 1)
        check(backlog_left > 0, "the backlog ran empty inside the window: "
              f"{len(where)} shards, raise backlog_rows_per_s")
        # The reference needs the chip's memory: the served weights go first.
        agent.runtime.clear_params()
        checks = check_documents(ctx, docs, accepted)
        for verdict in checks:
            emit("check", **verdict)
    finally:
        if agent is not None:
            agent.close()
        controller.stop()
        for path in csvs:
            if os.path.exists(path):
                os.remove(path)

    lengths = [int(n) for _, _, b in accepted for n in b["n_tokens"]]
    needed = lm_flops.mean_needed(config["model"], lengths)
    run: Dict[str, Any] = {
        "kind": "drain", "cell": cell, "config": config, "traffic": traffic,
        "device": device, "window_s": window_s, "t_open": t_open,
        "t_close": t_close, "shards": len(accepted), "rows": n_rows,
        "agent_metrics": (m0, m1), "controller_cpu_s": cpu1 - cpu0,
        "post_span_s": post_spans,
        "compiles_in_window": compiles, "op": map_op,
        "mean_flops_per_row": needed["flops"], "lm_needed": needed,
        "trace": None, "op_times": None, "memory_peak_bytes": peak,
        "attempted": len(window), "failed": failed,
        "correct": bool(all(c["ok"] for c in checks) and failed == 0),
        "checks": checks, "check_data": ctx.get("check_data"),
        "end_to_end": {
            "drain_rows_per_s": stats.rate(n_rows, window_s),
            "setup_s": setup_s,
        },
    }
    stamps = [p[0] for p in posts[open_at:close_at + 1]]
    emit("window", window_s=window_s, shards=len(accepted), rows=n_rows,
         tokens=sum(lengths),
         longest_silence_s=max(b - a for a, b in zip(stamps, stamps[1:])),
         failed=failed, setup_s=setup_s, compiles_in_window=compiles,
         warm_up=warm_totals, all_compiles=counter.totals(),
         backlog_left=backlog_left)
    if tracer is not None:
        run["trace"] = tracer.reduce(agent, ctx["program_patterns"])
        paths = sorted(glob.glob(os.path.join(
            tracer.directory, "plugins", "profile", "*", "*.xplane.pb")))
        run["op_times"] = op_times.reduce_ops(
            trace_reduce.load(paths[-1]), op_patterns(ctx))
        emit("op_times", **run["op_times"])
    return run
