"""Traffic kind ``drain``: CSV jobs of texts for a classify op. The traffic
file's ``tenants`` take turns to submit a dataset of ``job_rows`` rows, each
tenant for its own model (``<model id>-t<k>``), so the agent holds that many
models and every one of them answers inside the window. Backlog, window and
``setup_s`` are ``harness/backlog.py``'s; this file brings the rows and the
check."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmarks.harness import backlog, flops, manifest, schedule
# ``scripts/check_device_account.py`` taps what a run reads and prints by
# these two names: ``run_cell`` looks both up here, at call time.
from benchmarks.harness.backlog import job_snapshot
from benchmarks.harness.stack import emit


def check_rows(ctx: Dict[str, Any], rows: List[str],
               accepted: List[Tuple[int, str, Dict[str, Any]]]
               ) -> List[Dict[str, Any]]:
    """A seeded sample of the rows the window answered against the plain
    float32 reference, which makes each tenant's weights itself from the
    model id. ``accepted`` is ``(first row, model id, result body)`` per
    shard. Returns one record per number compared (value, limit, verdict);
    ``ctx["check_data"]`` keeps what was compared."""
    import numpy as np

    config = ctx["config"]
    ref = manifest.load_reference(config["reference"])
    spec = config["check"]
    pool = [(start + j, model_id, body, j) for start, model_id, body in accepted
            for j in range(len(body["indices"]))]
    rng = schedule.rng_of(ctx["seed"], "check")
    take = rng.choice(len(pool), size=min(int(spec["rows"]), len(pool)),
                      replace=False)
    # Sorted by model, then row: the reference's rows, model after model,
    # come out in the sample's own order.
    sample = sorted((pool[int(i)] for i in take), key=lambda s: (s[1], s[0]))
    models = sorted({m for _, m, _, _ in sample})
    data = {
        "ref_logits": np.concatenate([
            ref.logits(config["model"], model_id,
                       [rows[r] for r, m, _, _ in sample if m == model_id])
            for model_id in models]) if sample else np.zeros((0, 1), np.float32),
        "indices": np.asarray([b["indices"][j] for _, _, b, j in sample], np.int64),
        "scores": np.asarray([b["scores"][j] for _, _, b, j in sample], np.float64),
        "model": np.asarray([models.index(m) for _, m, _, _ in sample], np.int64),
    }
    ctx["check_data"] = data
    values = ref.compare(**data) if len(sample) else {}
    return [{
        "number": number, "value": float(values.get(number, float("inf"))),
        "limit": float(limit), "rows": len(sample), "models": len(models),
        "ok": bool(number in values and values[number] <= limit),
    } for number, limit in spec["limits"].items()]


def run_cell(ctx: Dict[str, Any]) -> Dict[str, Any]:
    config, seed = ctx["config"], int(ctx["seed"])
    run = backlog.run(
        ctx, make_rows=lambda n: schedule.drain_rows(ctx["traffic"], seed, n),
        write_csv=schedule.write_csv, answer_keys=("indices", "scores"),
        snapshot=lambda url, jid: job_snapshot(url, jid))
    rows, accepted = run["backlog"], run["accepted"]
    shard = int(ctx["traffic"]["shard_rows"])
    real_tokens = [min(len(r), int(config["model"]["max_len"]))
                   for start, _, _ in accepted for r in rows[start:start + shard]]
    run["mean_flops_per_row"] = flops.encoder_flops_needed(
        config["model"], real_tokens) / max(1, len(real_tokens))
    emit("window", **run["window_record"])
    backlog.finish(run, ctx, check_rows(ctx, rows, accepted))
    return run
