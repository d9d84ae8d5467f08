"""``BENCHMARK.json`` and the files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric sits in a file of its
own, found here by the name in the manifest:

    benchmarks/configs/<config>.json        sizes, dtype, op payload, control
    benchmarks/traffic/<mix>.json           kind and its parameters (among them
                                            ``order_seed``: the order of its sizes;
                                            ``agent``: the agent's knobs that the
                                            deployment sets for this traffic)
    benchmarks/layer_metrics/<metric>.py    read(run) -> number or None
    benchmarks/harness/kinds/<kind>.py      run_cell(cell, args) for a traffic kind
    benchmarks/reference/<family>.py        the plain float32 reference
    benchmarks/harness/<needed_work>.py     mean_needed(model, lengths): the work a
                                            configuration's rows need, for its rooflines
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

# Tests rehearse a cell on the CPU at a tiny width by patching these two (as
# tests/test_chip_smoke.py patches chip_smoke's constants). No option and no
# environment variable reaches them: a run of the command is always at the
# configuration's own sizes.
MODEL_OVERRIDES: Dict[str, Dict[str, Any]] = {}      # config name -> model keys
TRAFFIC_OVERRIDES: Dict[str, Dict[str, Any]] = {}    # traffic name -> keys


def load_manifest(path: str = MANIFEST) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(
        f"no workload {name!r} in BENCHMARK.json "
        f"(has {[c['name'] for c in manifest['workloads']]})")


def load_config(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    """The configuration file of ``name`` as the manifest names it; its
    ``model`` group is what the op's ``model_config`` payload carries."""
    for entry in manifest["configs"]:
        if entry["name"] == name:
            cfg = _load_json(os.path.join(ROOT, entry["file"]))
            cfg["name"] = name
            if name in MODEL_OVERRIDES:
                cfg["model"] = {**cfg["model"], **MODEL_OVERRIDES[name]}
            return cfg
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> Dict[str, Any]:
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic", name + ".json"))
    traffic["name"] = name
    traffic.update(TRAFFIC_OVERRIDES.get(name, {}))
    return traffic


def _load_named(what: str, name: str, *folders: str):
    """``benchmarks/<folders>/<name>.py``, ``name`` as the manifest's rules
    for a name have it."""
    if not NAME.match(name):
        raise ValueError(f"bad {what} {name!r}")
    return _load_module(os.path.join(BENCH_DIR, *folders, name + ".py"),
                        f"benchmarks_{what.replace(' ', '_')}_{name}")


def load_kind(kind: str):
    return _load_named("traffic kind", kind, "harness", "kinds")


def load_reference(family: str):
    return _load_named("reference", family, "reference")


def load_needed_work(name: str):
    """The counter a configuration file names under ``needed_work``: what
    its model needs of the chip for rows of given lengths, from shapes."""
    return _load_named("needed-work counter", name, "harness")


def load_layer_metric(name: str):
    return _load_module(
        os.path.join(BENCH_DIR, "layer_metrics", name + ".py"),
        "benchmarks_metric_" + re.sub(r"[^A-Za-z0-9_]", "_", name))


def metrics_of_cell(manifest: Dict[str, Any], cell_name: str, group: str
                    ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    that list it under ``workloads``, and those with no such key whose
    end-to-end metric (themselves, or their ``moves``) the cell reports."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def in_cell(metric: Dict[str, Any]) -> bool:
        return cell_name in metric["workloads"] if "workloads" in metric \
            else True

    if group == "end_to_end":
        return [m for m in manifest[group] if in_cell(m)]
    return [m for m in manifest[group]
            if (in_cell(m) if "workloads" in m else in_cell(e2e[m["moves"]]))]
