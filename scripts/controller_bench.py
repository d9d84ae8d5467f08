#!/usr/bin/env python3
"""Controller micro-bench — a CI guard on journal replay being O(live
state), not O(history) (ISSUE 14). No chip and no jax are involved: the
figures it prints are the CI runner's and go into no record of speed.
Three legs, < 30 s:

- **submits/sec** — in-process ``Controller.submit`` throughput against a
  live segmented journal (the production write path: JSON encode + append
  + flush per event).
- **lease-grants/sec** — ``lease()`` round-trips granting ``--grant``
  tasks each (the scheduler take + lease bookkeeping + task
  serialization hot path), and the tasks/sec they move.
- **replay** — the compaction claim as a number: a ``--events``-event
  journal (synthetic submit/result pairs, a ``--live`` pending tail —
  O(history) is the point, so history dwarfs live state) replayed two
  ways: full history (legacy single file) vs snapshot + tail (after one
  compacting snapshot). ``--assert-speedup N`` fails the run when
  snapshot replay is not at least N× faster — the ISSUE 14 acceptance
  bar runs this at 5 on a ≥ 50k-event journal in CI.

Emits one flat JSON line (``controller_*`` fields).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from agent_tpu.config import JournalConfig
from agent_tpu.controller.core import Controller

SEG_CFG = JournalConfig(
    segment_max_bytes=4 * 1024 * 1024, snapshot_every_events=0
)


def bench_submits(n: int, tmp: str) -> Dict[str, Any]:
    path = os.path.join(tmp, "submit_bench.jsonl")
    c = Controller(journal_path=path, journal=SEG_CFG)
    t0 = time.perf_counter()
    for i in range(n):
        c.submit("echo", {"i": i})
    dt = time.perf_counter() - t0
    c.close()
    return {
        "submits": n,
        "submits_per_sec": round(n / dt, 1),
        "wall_s": round(dt, 4),
    }


def bench_leases(n_jobs: int, grant: int, tmp: str) -> Dict[str, Any]:
    path = os.path.join(tmp, "lease_bench.jsonl")
    c = Controller(journal_path=path, journal=SEG_CFG)
    for i in range(n_jobs):
        c.submit("echo", {"i": i})
    caps = {"ops": ["echo"]}
    grants = 0
    tasks = 0
    t0 = time.perf_counter()
    while True:
        lease = c.lease("bench", caps, max_tasks=grant)
        if lease is None:
            break
        grants += 1
        tasks += len(lease["tasks"])
    dt = time.perf_counter() - t0
    c.close()
    return {
        "grants": grants,
        "tasks_leased": tasks,
        "grant_size": grant,
        "lease_grants_per_sec": round(grants / dt, 1),
        "tasks_leased_per_sec": round(tasks / dt, 1),
        "wall_s": round(dt, 4),
    }


def _write_synthetic_journal(path: str, n_events: int, live: int) -> int:
    """A journal whose history dwarfs its live state: ``n_events`` as
    submit+result pairs (terminal jobs — pure history) followed by
    ``live`` pending submits (the state that must survive). Written as
    raw JSONL — exactly the bytes the controller would have journaled,
    without paying the controller to produce them."""
    written = 0
    with open(path, "w", encoding="utf-8") as f:
        pairs = max(0, (n_events - live) // 2)
        for i in range(pairs):
            jid = f"hist-{i}"
            f.write(json.dumps({
                "ev": "submit", "job_id": jid, "op": "echo",
                "payload": {"i": i}, "after": [], "required_labels": {},
                "max_attempts": None,
            }) + "\n")
            f.write(json.dumps({
                "ev": "result", "job_id": jid, "state": "succeeded",
                "epoch": 0, "attempts": 1, "result": None, "error": None,
            }) + "\n")
            written += 2
        for i in range(live):
            f.write(json.dumps({
                "ev": "submit", "job_id": f"live-{i}", "op": "echo",
                "payload": {"i": i}, "after": [], "required_labels": {},
                "max_attempts": None,
            }) + "\n")
            written += 1
    return written


def bench_replay(n_events: int, live: int, tmp: str) -> Dict[str, Any]:
    path = os.path.join(tmp, "replay_bench.jsonl")
    written = _write_synthetic_journal(path, n_events, live)

    # Full-history replay: the legacy cost a restarted controller paid.
    t0 = time.perf_counter()
    c = Controller(journal_path=path)
    t_full = time.perf_counter() - t0
    counts_full = c.counts()
    assert counts_full.get("pending") == live, counts_full
    c.close()

    # Compact: one snapshot covers the whole history. The planet-scale
    # configuration bounds terminal-job retention (SNAPSHOT_RETAIN_
    # TERMINAL) — that is what makes the snapshot O(live state + window)
    # instead of O(every job ever submitted).
    snap_cfg = JournalConfig(
        segment_max_bytes=4 * 1024 * 1024, snapshot_every_events=1,
        snapshot_retain_terminal=max(100, live),
    )
    c = Controller(journal_path=path, journal=snap_cfg)
    c.maybe_snapshot(force=True)
    c.close()
    # ...and the next incarnation replays snapshot + empty tail.
    t0 = time.perf_counter()
    c = Controller(journal_path=path, journal=snap_cfg)
    t_compacted = time.perf_counter() - t0
    counts_snap = c.counts()
    # Live state is intact; history beyond the retention window is
    # forgotten (late duplicates reject as unknown job — still at most
    # once).
    assert counts_snap.get("pending") == live, counts_snap
    assert counts_snap.get("succeeded", 0) <= counts_full["succeeded"]
    assert c.journal_status()["last_replay_sec"] <= t_compacted
    c.close()

    return {
        "events": written,
        "live_jobs": live,
        "replay_full_sec": round(t_full, 4),
        "replay_events_per_sec": round(written / t_full, 1),
        "replay_compacted_sec": round(t_compacted, 4),
        "replay_speedup": round(t_full / max(1e-9, t_compacted), 1),
    }


def bench_partitioned_submits(
    submits: int, partitions: int, tmp: str
) -> Dict[str, Any]:
    """Aggregate submit throughput of N partitions running CONCURRENTLY
    in separate processes (ISSUE 18) — each partition a real
    ``Controller`` journaling to its own segmented journal, exactly the
    per-partition write path of the partitioned control plane. Separate
    processes because that is the deployment shape AND the measurement
    requirement: N controllers in one process share a GIL and would bench
    lock contention, not scaling. Aggregate = total submits / slowest
    child wall (children start together; python startup is excluded
    because each child times only its own submit loop)."""
    import subprocess

    procs = []
    for i in range(partitions):
        path = os.path.join(tmp, f"agg_submit.p{i}.jsonl")
        procs.append(subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__),
                "--_child-submits", str(submits),
                "--_child-journal", path,
                "--_child-partition", f"p{i}",
            ],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, cwd=REPO,
        ))
    total = 0
    walls: List[float] = []
    for proc in procs:
        out, err = proc.communicate(timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"partition child failed rc={proc.returncode}: "
                f"{err.decode(errors='replace')[:300]}"
            )
        child = json.loads(out.decode())
        total += child["submits"]
        walls.append(child["wall_s"])
    wall = max(walls)
    return {
        "partitions": partitions,
        "submits": total,
        "agg_submits_per_sec": round(total / wall, 1),
        "child_walls_s": [round(w, 4) for w in walls],
        "wall_s": round(wall, 4),
    }


def run_bench(
    submits: int = 20_000,
    lease_jobs: int = 20_000,
    grant: int = 16,
    replay_events: int = 50_000,
    replay_live: int = 500,
    partitions: int = 0,
) -> Dict[str, Any]:
    """All legs → one flat dict (the ``controller_*`` fields).
    ``partitions > 0`` adds the ISSUE 18 aggregate-submits leg (N
    concurrent partition processes) and its ``agg_*`` fields."""
    with tempfile.TemporaryDirectory(prefix="controller_bench_") as tmp:
        sub = bench_submits(submits, tmp)
        lease = bench_leases(lease_jobs, grant, tmp)
        replay = bench_replay(replay_events, replay_live, tmp)
        agg = (
            bench_partitioned_submits(submits, partitions, tmp)
            if partitions > 0 else None
        )
    out = {
        "submits_per_sec": sub["submits_per_sec"],
        "lease_grants_per_sec": lease["lease_grants_per_sec"],
        "tasks_leased_per_sec": lease["tasks_leased_per_sec"],
        "replay_events": replay["events"],
        "replay_full_sec": replay["replay_full_sec"],
        "replay_events_per_sec": replay["replay_events_per_sec"],
        "replay_compacted_sec": replay["replay_compacted_sec"],
        "replay_speedup": replay["replay_speedup"],
        "detail": {"submit": sub, "lease": lease, "replay": replay},
    }
    if agg is not None:
        host_cores = os.cpu_count() or 1
        out["agg_partitions"] = partitions
        out["agg_submits_per_sec"] = agg["agg_submits_per_sec"]
        out["agg_speedup_vs_single"] = round(
            agg["agg_submits_per_sec"] / max(1e-9, sub["submits_per_sec"]),
            2,
        )
        # Core-count-aware floor: N partition children + the parent need
        # real cores or the leg measures scheduling starvation, not the
        # control plane (the ISSUE 16 starved_fields convention).
        out["agg_starved"] = host_cores < partitions + 1
        out["host_cores"] = host_cores
        out["detail"]["agg"] = agg
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--submits", type=int, default=20_000)
    ap.add_argument("--lease-jobs", type=int, default=20_000)
    ap.add_argument("--grant", type=int, default=16)
    ap.add_argument("--replay-events", type=int, default=50_000)
    ap.add_argument("--replay-live", type=int, default=500)
    ap.add_argument("--assert-speedup", type=float, default=0.0,
                    help="fail unless snapshot replay is at least this "
                         "many times faster than full-history replay "
                         "(the ISSUE 14 acceptance bar runs 5)")
    ap.add_argument("--quick", action="store_true",
                    help="CI sizing (replay stays >= 50k events — the "
                         "acceptance bar's floor)")
    ap.add_argument("--partitions", type=int, default=0,
                    help="also bench N concurrent partition processes "
                         "(ISSUE 18); records controller_agg_submits_"
                         "per_sec and asserts aggregate >= 2x single on "
                         "hosts with enough cores")
    # Hidden child mode: one partition's submit loop in its own process.
    ap.add_argument("--_child-submits", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--_child-journal", default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--_child-partition", default="",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args._child_submits > 0:
        # Child mode — one partition, own journal, JSON on stdout.
        tmp = os.path.dirname(args._child_journal) or "."
        c = Controller(
            journal_path=args._child_journal, journal=SEG_CFG,
            partition=args._child_partition or None,
        )
        t0 = time.perf_counter()
        for i in range(args._child_submits):
            c.submit("echo", {"i": i})
        dt = time.perf_counter() - t0
        c.close()
        print(json.dumps({
            "partition": args._child_partition,
            "submits": args._child_submits,
            "wall_s": dt,
            "tmp": tmp,
        }), flush=True)
        return 0

    if args.quick:
        args.submits = min(args.submits, 10_000)
        args.lease_jobs = min(args.lease_jobs, 10_000)

    out = run_bench(
        submits=args.submits, lease_jobs=args.lease_jobs,
        grant=args.grant, replay_events=args.replay_events,
        replay_live=args.replay_live, partitions=args.partitions,
    )
    print(json.dumps(out, sort_keys=True), flush=True)
    if args.assert_speedup > 0 and out["replay_speedup"] < args.assert_speedup:
        print(
            f"FAILED: replay speedup {out['replay_speedup']}x < required "
            f"{args.assert_speedup}x on a {out['replay_events']}-event "
            "journal — snapshot replay is not O(live state)"
        )
        return 1
    if args.partitions > 0:
        if out["agg_starved"]:
            print(
                f"STARVED: {out['host_cores']} cores < "
                f"{args.partitions + 1} needed — aggregate recorded "
                "but the >=2x floor is not asserted", file=sys.stderr,
            )
        elif out["agg_speedup_vs_single"] < 2.0:
            print(
                f"FAILED: aggregate {out['agg_submits_per_sec']}/s is "
                f"only {out['agg_speedup_vs_single']}x the single-"
                f"partition {out['submits_per_sec']}/s across "
                f"{args.partitions} partitions on a {out['host_cores']}-"
                "core host — sharding is not scaling submits"
            )
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
