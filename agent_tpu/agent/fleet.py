"""Device-pinned agent fleets (ISSUE 7 tentpole a).

One host, N agent processes, each owning a **disjoint slice** of the host's
accelerator devices, all leasing from one controller — the multi-process
complement of mesh mode (one agent, ``MESH_SHAPE="dp=N"``, batches sharded
across its whole mesh). The fleet is how ``n_chips > 1`` becomes real
without multi-host SPMD: the controller's fair scheduler already reads
``device_kind``/``mesh_devices``/``queue_depth`` from lease capabilities,
so shards spread across the fleet with no new protocol.

Pinning model (two fences, one grammar):

- ``CHIP_SLICE="start:count"`` — in-process: the runtime claims only that
  slice of ``jax.devices(platform)`` (``runtime.apply_chip_slice``). This is
  the only fence available on the forced-host CPU shape CI uses
  (``XLA_FLAGS=--xla_force_host_platform_device_count=K`` makes every
  process see all K virtual devices).
- ``TPU_VISIBLE_CHIPS="2,3"`` plus the process's chip bounds and its own
  runtime port (:func:`tpu_process_env`) — process-level, TPU hardware only:
  libtpu hides the other chips entirely, so the runtime of agent *i* cannot
  touch a neighbor's chips even by bug, and N libtpu processes share one
  host. The launcher sets both; on hardware the in-process slice then
  reduces to ``0:count`` over the already-restricted view.

``python -m agent_tpu.agent.fleet`` is the **child** entry point: it
optionally pre-warms the op executables from ``AGENT_WARM_FILE`` (a JSON
list of ``{op, payload}`` — compile is a once-per-process cost, and a fleet
that compiles inside the timed window corrupts every scaling number), then
runs the standard agent loop (``agent/app.py``). ``scripts/fleet.py`` is
the operator CLI over :func:`spawn_fleet`.
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import time
from typing import Any, Callable, Dict, Iterable, List, Optional

from agent_tpu.utils.logging import log
from agent_tpu.utils.paths import REPO_ROOT

_FORCE_DEVICES_RE = re.compile(
    r"--xla_force_host_platform_device_count=\d+"
)

DEFAULT_NAME_PREFIX = "fleet"


def force_host_devices(xla_flags: str, n: int) -> str:
    """``XLA_FLAGS`` with the forced-host device count set to exactly ``n``
    (replacing any inherited value — a parent test env pinning 8 must not
    leak a different mesh size into fleet children)."""
    flags = _FORCE_DEVICES_RE.sub("", xla_flags or "").strip()
    return (f"{flags} --xla_force_host_platform_device_count={n}").strip()


# The chips ONE libtpu process owns, as the x,y,z bounds libtpu wants, by how
# many it owns. Only the counts that have run on a v5e 2x2 host
# (chip_smoke.py --chips 4): a wrong row is a hang on hardware, so any other
# count is a ValueError until someone has run it.
_TPU_CHIP_BOUNDS = {1: "1,1,1", 4: "2,2,1"}


# libtpu's default runtime port; a member listens on this plus the index of
# its first chip. Chips are exclusive, so members of one host never collide.
_TPU_PORT_BASE = 8476


def tpu_process_env(index: int, devices_per_agent: int) -> Dict[str, str]:
    """The libtpu environment that pins fleet member ``index`` to its own
    chips on a host it shares with other libtpu processes.

    Visibility alone is not enough for several processes on one host: each
    must also be told the bounds of the chips it owns, that it is a whole
    slice by itself (one process, no peers to wait for), and a runtime port
    of its own — without these every process sizes itself for the whole
    host and they collide on the default port."""
    bounds = _TPU_CHIP_BOUNDS.get(devices_per_agent)
    if bounds is None:
        raise ValueError(
            f"no libtpu chip bounds known for {devices_per_agent} chips per "
            f"process (known: {sorted(_TPU_CHIP_BOUNDS)})"
        )
    chips = range(index * devices_per_agent, (index + 1) * devices_per_agent)
    port = _TPU_PORT_BASE + chips[0]
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def fleet_slice(index: int, devices_per_agent: int) -> str:
    """The ``CHIP_SLICE`` of fleet member ``index``: disjoint, contiguous,
    in launch order."""
    return f"{index * devices_per_agent}:{devices_per_agent}"


def agent_env(
    index: int,
    n_agents: int,
    devices_per_agent: int = 1,
    *,
    controller_url: str,
    tasks: str,
    platform: str = "cpu",
    base_env: Optional[Dict[str, str]] = None,
    name_prefix: str = DEFAULT_NAME_PREFIX,
    mesh_shape: str = "",
    warm_file: str = "",
    extra_env: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """The environment for fleet member ``index`` of ``n_agents``.

    ``platform="cpu"`` is the CI/virtual shape: every child forces
    ``n_agents * devices_per_agent`` host devices and pins itself to its
    slice in-process. ``platform="tpu"`` is hardware: the child's process
    sees only its chips (:func:`tpu_process_env`) and the in-process slice
    becomes ``0:count`` over that restricted view. ``mesh_shape`` (e.g.
    ``"dp=4"``) rides through to ``MESH_SHAPE`` for mesh-mode members.
    """
    if index < 0 or index >= n_agents:
        raise ValueError(f"index {index} outside fleet of {n_agents}")
    if devices_per_agent < 1:
        raise ValueError("devices_per_agent must be >= 1")
    env = dict(base_env if base_env is not None else os.environ)
    env["CONTROLLER_URL"] = controller_url
    env["AGENT_NAME"] = f"{name_prefix}-{index}"
    env["TASKS"] = tasks
    # Children run `-m agent_tpu...` and must import the same tree the
    # parent did, installed or not.
    env["PYTHONPATH"] = (
        REPO_ROOT + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH") else REPO_ROOT
    )
    if platform == "tpu":
        # Process-level pinning: libtpu hides every chip outside the slice,
        # so the in-process slice is the identity over the visible view.
        # JAX_PLATFORMS=tpu makes a child that cannot reach its chip fail at
        # start-up instead of carrying on on the CPU (and overrides a parent
        # held to the CPU: tests, a controller host).
        env["JAX_PLATFORMS"] = "tpu"
        env.update(tpu_process_env(index, devices_per_agent))
        env["CHIP_SLICE"] = f"0:{devices_per_agent}"
    else:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = force_host_devices(
            env.get("XLA_FLAGS", ""), n_agents * devices_per_agent
        )
        env["CHIP_SLICE"] = fleet_slice(index, devices_per_agent)
    if mesh_shape:
        env["MESH_SHAPE"] = mesh_shape
    if warm_file:
        env["AGENT_WARM_FILE"] = warm_file
    if extra_env:
        env.update(extra_env)
    return env


class Fleet:
    """Handle on a spawned fleet: the child processes plus their names (the
    controller-side keys readiness and shard accounting use)."""

    def __init__(
        self, procs: List[subprocess.Popen], names: List[str]
    ) -> None:
        self.procs = procs
        self.names = names

    def alive(self) -> int:
        return sum(1 for p in self.procs if p.poll() is None)

    def poll_failures(self) -> List[int]:
        """Return codes of members that already exited nonzero — a dead
        member mid-drain means the scaling numbers are fiction."""
        return [
            p.returncode for p in self.procs
            if p.poll() is not None and p.returncode not in (0, None)
        ]

    def stop(self, timeout: float = 30.0) -> None:
        """Graceful drain (SIGTERM → the agent's signal handler finishes the
        in-flight task), escalating to SIGKILL past ``timeout``."""
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + timeout
        for p in self.procs:
            remaining = max(0.1, deadline - time.monotonic())
            try:
                p.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)


def spawn_fleet(
    n_agents: int,
    devices_per_agent: int = 1,
    *,
    controller_url: str,
    tasks: str,
    platform: str = "cpu",
    name_prefix: str = DEFAULT_NAME_PREFIX,
    mesh_shape: str = "",
    warm_file: str = "",
    extra_env: Optional[Dict[str, str]] = None,
    log_dir: Optional[str] = None,
) -> Fleet:
    """Spawn ``n_agents`` pinned agent processes leasing from
    ``controller_url``. Child stdout/stderr go to ``<log_dir>/<name>.log``
    when given (the launcher's own stdout stays readable at fleet scale),
    else they inherit the parent's."""
    procs: List[subprocess.Popen] = []
    names: List[str] = []
    for i in range(n_agents):
        env = agent_env(
            i, n_agents, devices_per_agent,
            controller_url=controller_url, tasks=tasks, platform=platform,
            name_prefix=name_prefix, mesh_shape=mesh_shape,
            warm_file=warm_file, extra_env=extra_env,
        )
        names.append(env["AGENT_NAME"])
        out: Any = None
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            out = open(
                os.path.join(log_dir, f"{env['AGENT_NAME']}.log"), "ab"
            )
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "agent_tpu.agent.fleet"],
            env=env, stdout=out, stderr=subprocess.STDOUT if out else None,
            close_fds=True,
        ))
        if out is not None:
            out.close()  # the child holds its own fd now
    return Fleet(procs, names)


def wait_for_agents(
    agents_fn: Callable[[], Dict[str, Any]],
    names: Iterable[str],
    timeout: float = 180.0,
    fleet: Optional[Fleet] = None,
) -> bool:
    """Block until every name in ``names`` has polled the controller at
    least once (``agents_fn`` → the ``agents_summary()`` dict, in-process or
    scraped from ``GET /v1/status``). This is the warm/ready gate: work
    submitted before a member's first poll would be drained by a partial
    fleet and every scaling number would lie. Returns False on timeout or
    when a fleet member died before reporting in."""
    want = set(names)
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            seen = set(agents_fn() or {})
        except Exception:  # noqa: BLE001 — controller may still be booting
            seen = set()
        if want <= seen:
            return True
        if fleet is not None and fleet.poll_failures():
            return False
        time.sleep(0.1)
    return False


# ---- child entry point (`python -m agent_tpu.agent.fleet`) ----

def warm_from_file(path: str) -> int:
    """Run each ``{op, payload}`` of the warm file once against the real
    runtime, building the executable cache before the first lease. Warm
    results never touch the controller; a warm failure is fatal (exit 3) —
    a member that would compile inside the timed window must not join the
    fleet silently."""
    from agent_tpu.config import Config
    from agent_tpu.ops import get_op
    from agent_tpu.runtime.context import OpContext
    from agent_tpu.runtime.runtime import get_runtime

    with open(path, "r", encoding="utf-8") as f:
        specs = json.load(f)
    if not isinstance(specs, list):
        raise ValueError("warm file must be a JSON list of {op, payload}")
    config = Config.from_env()
    runtime = get_runtime(config.device)
    n = 0
    for spec in specs:
        op = get_op(str(spec["op"]))
        t0 = time.perf_counter()
        out = op(
            dict(spec.get("payload") or {}),
            OpContext(runtime=runtime, config=config),
        )
        if not (isinstance(out, dict) and out.get("ok") is True):
            raise RuntimeError(
                f"warm op {spec['op']!r} did not succeed: {str(out)[:200]}"
            )
        log(
            "fleet member warmed", op=spec["op"],
            ms=round((time.perf_counter() - t0) * 1e3, 1),
        )
        n += 1
    return n


def child_main() -> int:
    """Fleet member: warm (optional), then the standard agent loop."""
    warm_file = os.environ.get("AGENT_WARM_FILE", "")
    if warm_file:
        try:
            warm_from_file(warm_file)
        except Exception as exc:  # noqa: BLE001 — fatal by contract
            print(
                f"[agent-tpu] fleet warmup failed: "
                f"{type(exc).__name__}: {exc}",
                flush=True,
            )
            return 3
    from agent_tpu.agent.app import main as agent_main

    return agent_main()


if __name__ == "__main__":
    signal.signal(signal.SIGPIPE, signal.SIG_DFL)  # piped-log friendliness
    sys.exit(child_main())
