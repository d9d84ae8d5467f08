"""The run's other process. The controller runs as a child through its own
entry point and never imports JAX, so it does not share the agent's
interpreter lock — as in a deployment. Nothing outlives the run: the child
is stopped and waited for."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, Optional

from benchmarks.harness.manifest import ROOT


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return int(s.getsockname()[1])


def child_env(extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def cpu_seconds(pid: int) -> float:
    """User + system CPU seconds of ``pid`` so far (``/proc/<pid>/stat``)."""
    with open(f"/proc/{pid}/stat", "rb") as f:
        fields = f.read().rsplit(b") ", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


def http_json(url: str, body: Any = None, timeout: float = 60.0
              ) -> "tuple[int, Any]":
    """One JSON request with the standard library → (status, parsed body)."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            raw = r.read()
            return r.status, (json.loads(raw) if raw else None)
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            return e.code, json.loads(raw)
        except ValueError:
            return e.code, {"error": raw.decode(errors="replace")[:500]}


class ControllerProcess:
    """``python -m agent_tpu.controller.server`` on a free local port, every
    knob at the program's default except the address and a lease that cannot
    expire under a long first compile."""

    def __init__(self, log_path: str, env: Optional[Dict[str, str]] = None):
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "agent_tpu.controller.server"],
            cwd=ROOT, stdout=self._log, stderr=subprocess.STDOUT,
            env=child_env({
                "CONTROLLER_HOST": "127.0.0.1",
                "CONTROLLER_PORT": str(self.port),
                "LEASE_TTL_SEC": "1800",
                **(env or {}),
            }),
        )
        deadline = time.monotonic() + 60.0
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"controller exited {self.proc.returncode} at start")
            try:
                status, _ = http_json(self.url + "/v1/depth", timeout=2.0)
                if status == 200:
                    break
            except OSError:
                pass
            if time.monotonic() > deadline:
                self.stop()
                raise RuntimeError("controller did not come up in 60 s")
            time.sleep(0.05)

    @property
    def pid(self) -> int:
        return self.proc.pid

    def cpu_seconds(self) -> float:
        return cpu_seconds(self.proc.pid)

    def stop(self) -> None:
        stop_process(self.proc)
        self._log.close()


def stop_process(proc: subprocess.Popen, grace: float = 10.0) -> None:
    """Terminate, wait, kill if it will not go; always reaps."""
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            proc.kill()
    proc.wait()
