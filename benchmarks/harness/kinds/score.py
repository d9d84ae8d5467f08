"""Traffic kind ``score``: pre-tokenized documents as CSV jobs over ``POST
/v1/jobs`` for ``map_score_lm``: the ``drain`` kind's measurement
(``harness/backlog.py``: backlog, window, ``setup_s``) over another kind of
row and another check.

Rows are documents of token ids: their lengths the quantiles of the traffic
file's ``doc_tokens`` in the order its ``order_seed`` gives, their ids
Zipf-distributed over the whole vocabulary through a seeded permutation.
``correct``: a seeded sample of the documents the window answered goes
through the plain float32 reference
(``benchmarks/reference/<config.reference>.py``), which makes the weights
itself from the model id; their ``block_logprob_sums`` are compared.

Beside the keys the ``.drain`` and ``.setup`` readers read, the ``run``
record carries for the language model's own readers ``lm_needed``
(operations and bytes a document needs: ``mean_needed(model, lengths)`` of
the counter the configuration file names under ``needed_work``, found by
that name as the reference is; ``flops``, ``head_flops`` and ``head_bytes``
are what ``lm_roofline`` and ``loss_head_roofline`` read of it whatever the
family, any further key is the family's own) and, traced, ``op_times``
(``harness/op_times.py`` over the readers' ``OP_PATTERNS``)."""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmarks.harness import (backlog, manifest, op_times, schedule,
                                trace_reduce)
from benchmarks.harness.stack import check, emit


def documents(traffic: Dict[str, Any], vocab_size: int, seed: int, n: int
              ) -> List[np.ndarray]:
    """``n`` documents of token ids, no two alike."""
    lengths = schedule.ordered_sizes(traffic["doc_tokens"], n,
                                     traffic["order_seed"])
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
    config = ctx["config"]
    run = backlog.run(
        ctx, make_rows=lambda n: documents(
            ctx["traffic"], int(config["model"]["vocab_size"]),
            int(ctx["seed"]), n),
        write_csv=write_csv,
        answer_keys=("n_tokens", "logprob_sum", "block_logprob_sums"))
    docs, accepted = run["backlog"], run["accepted"]
    lengths = [int(n) for _, _, b in accepted for n in b["n_tokens"]]
    run["lm_needed"] = manifest.load_needed_work(
        config["needed_work"]).mean_needed(config["model"], lengths)
    run["mean_flops_per_row"] = run["lm_needed"]["flops"]
    run["op_times"] = None
    emit("window", **run["window_record"])
    emit("documents", docs=len(lengths), tokens=sum(lengths))
    # The reference needs the chip's memory: the served weights go first.
    run["agent"].runtime.clear_params()
    tracer = backlog.finish(run, ctx, check_documents(ctx, docs, accepted))
    if tracer is not None:
        paths = sorted(glob.glob(os.path.join(
            tracer.directory, "plugins", "profile", "*", "*.xplane.pb")))
        run["op_times"] = op_times.reduce_ops(
            trace_reduce.load(paths[-1]), op_patterns(ctx))
        emit("op_times", **run["op_times"])
    return run
