#!/usr/bin/env python3
"""Fleet launcher CLI (ISSUE 7): N device-pinned agent processes on this
host, all leasing from one controller.

    # 4 single-chip agents against a running controller
    python scripts/fleet.py --agents 4 --controller http://ctrl:8080 \
        --tasks map_classify_tpu,map_summarize --platform tpu

    # CI/virtual shape: 2 agents x 2 forced host devices each
    python scripts/fleet.py --agents 2 --devices-per-agent 2 \
        --controller http://127.0.0.1:8080

Each member is pinned to a disjoint device slice (``CHIP_SLICE``; plus
libtpu's per-process chip visibility, bounds and port on hardware — see
``tpu_process_env`` in ``agent_tpu/agent/fleet.py``) and
optionally pre-warms its executables from ``--warm-file`` before the first
lease. The launcher waits for every member's first controller poll, then
blocks until SIGINT/SIGTERM, which it forwards for a graceful drain.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_TASKS = "map_classify_tpu,map_summarize"


def _http_agents(controller_url: str):
    """``agents_summary`` via GET /v1/status (the launcher has no in-process
    controller)."""
    url = controller_url.rstrip("/") + "/v1/status"
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return json.load(resp).get("agents") or {}
    except Exception:  # noqa: BLE001 — not up yet
        return {}


def main() -> int:
    from agent_tpu.agent import fleet

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--agents", type=int, default=2)
    ap.add_argument("--devices-per-agent", type=int, default=1)
    ap.add_argument("--controller", required=True,
                    help="controller base URL (http://host:port)")
    ap.add_argument("--tasks", default=DEFAULT_TASKS)
    ap.add_argument("--platform", choices=("cpu", "tpu"), default="cpu",
                    help="cpu = forced-host virtual devices (CI shape); "
                         "tpu = hardware chip pinning")
    ap.add_argument("--mesh-shape", default="",
                    help='per-member MESH_SHAPE, e.g. "dp=2"')
    ap.add_argument("--warm-file", default="",
                    help="JSON [{op, payload}] each member runs pre-lease")
    ap.add_argument("--log-dir", default="",
                    help="per-member log files (default: inherit stdout)")
    ap.add_argument("--name-prefix", default=fleet.DEFAULT_NAME_PREFIX)
    ap.add_argument("--ready-timeout", type=float, default=300.0)
    args = ap.parse_args()
    if args.agents < 1:
        print("--agents must be >= 1", flush=True)
        return 2

    handle = fleet.spawn_fleet(
        args.agents, args.devices_per_agent,
        controller_url=args.controller, tasks=args.tasks,
        platform=args.platform, name_prefix=args.name_prefix,
        mesh_shape=args.mesh_shape, warm_file=args.warm_file,
        log_dir=args.log_dir or None,
    )
    print(
        f"fleet up: {args.agents} agent(s) x {args.devices_per_agent} "
        f"device(s) ({args.platform}), members={handle.names}",
        flush=True,
    )
    ready = fleet.wait_for_agents(
        lambda: _http_agents(args.controller), handle.names,
        timeout=args.ready_timeout, fleet=handle,
    )
    if not ready:
        print("fleet NOT ready (timeout or member death) — stopping",
              flush=True)
        handle.stop()
        return 1
    print("fleet ready: every member polled the controller", flush=True)

    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    while not stop.is_set():
        stop.wait(1.0)
        failures = handle.poll_failures()
        if failures:
            print(f"fleet member(s) died: exit codes {failures}", flush=True)
            handle.stop()
            return 1
    print("stopping fleet (graceful drain)", flush=True)
    handle.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
