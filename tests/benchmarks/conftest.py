"""What the benchmark's tests share: a tree a test can ADD files to as a later
PR would, and the committed manifest beside a copy that such a PR has
appended to.

A later PR adds files and appends entries and may edit nothing that is
there, least of all a test. So a test that holds an entry of
``BENCHMARK.json`` takes ``manifests`` and holds it in both: the entry is
THERE, wherever later entries put it, and no count or order is pinned."""

from __future__ import annotations

import copy
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

APPENDED_CELL = "appended-lm.appended-mix"


def mirror(src: str, dst: str) -> None:
    """``dst``: the directories of ``src``, every file a symlink."""
    for folder, dirs, files in os.walk(src):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        there = os.path.join(dst, os.path.relpath(folder, src))
        os.makedirs(there, exist_ok=True)
        for name in files:
            os.symlink(os.path.join(folder, name), os.path.join(there, name))


@pytest.fixture()
def bench_tree(tmp_path, monkeypatch):
    """``benchmarks/`` mirrored under ``tmp_path``, found by the harness in
    place of the repository's: returns the mirror's ``benchmarks`` path."""
    # ``procs`` and ``stack`` copy ``manifest.ROOT`` as they are imported (the
    # controller child and the run's scratch stay in the repository): first.
    from benchmarks.harness import backlog, manifest  # noqa: F401

    bench = str(tmp_path / "benchmarks")
    mirror(os.path.join(ROOT, "benchmarks"), bench)
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    monkeypatch.setattr(manifest, "BENCH_DIR", bench)
    return bench


def write(path: str, text: str) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)


def append_a_model_config_pr(bench: str, m: dict) -> dict:
    """What a ``model_config`` PR brings, as files in ``bench`` and entries
    at the END of a copy of ``m``: a second decoder family's configuration
    with its own needed-work counter, a traffic file, a four-chip cell, a
    per-layer entry of that cell and one with no ``workloads`` key."""
    m = copy.deepcopy(m)
    with open(os.path.join(bench, "configs", "brumby-14b-base.json")) as f:
        config = json.load(f)
    config["needed_work"] = "appended_needed"
    write(os.path.join(bench, "configs", "appended-lm.json"), json.dumps(config))
    with open(os.path.join(bench, "traffic", "score-long.json")) as f:
        traffic = json.load(f)
    traffic["doc_tokens"] = {"dist": "uniform", "min": 2048, "max": 16384}
    write(os.path.join(bench, "traffic", "appended-mix.json"), json.dumps(traffic))
    write(os.path.join(bench, "harness", "appended_needed.py"),
          "def mean_needed(model, lengths):\n"
          "    n = sum(lengths) / max(1, len(lengths))\n"
          "    return {'flops': 7.0 * n, 'head_flops': 2.0 * n, 'head_bytes': 3.0 * n}\n")
    write(os.path.join(bench, "layer_metrics", "appended_ms.drain.py"),
          "def read(run):\n    return run.get('appended')\n")
    write(os.path.join(bench, "layer_metrics", "appended_s.setup.py"),
          "def read(run):\n    return None\n")
    m["configs"].append({
        "name": "appended-lm", "source": "https://example.org/config.json",
        "file": "benchmarks/configs/appended-lm.json",
        "reduced": config["reduced"], "why": "a second decoder family"})
    m["workloads"].append({
        "name": APPENDED_CELL, "config": "appended-lm",
        "traffic": "appended-mix", "chips": 4,
        "why": "a model that exists only across chips"})
    # Its cell joins the lists of what it shares with the score cell (as PR
    # 27's joined the ``.drain`` readers'); the mixer's readers stay brumby's.
    for metric in m["end_to_end"] + m["per_layer"]:
        if ("brumby-14b-base.score-long" in metric.get("workloads", ())
                and not metric["name"].startswith("retention_")):
            metric["workloads"].append(APPENDED_CELL)
    m["per_layer"].append({
        "name": "appended_ms.drain", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "Ops", "moves": "drain_rows_per_s",
        "workloads": [APPENDED_CELL]})
    m["per_layer"].append({
        "name": "appended_s.setup", "unit": "s", "better": "lower",
        "source": "program_counter", "layer": "Runtime", "moves": "setup_s"})
    return m


@pytest.fixture()
def appended(bench_tree):
    """The manifest after such a PR, its files in the ``bench_tree``."""
    from benchmarks.harness import manifest

    return append_a_model_config_pr(bench_tree, manifest.load_manifest())


@pytest.fixture(params=["committed", "appended"])
def manifests(request):
    """The committed ``BENCHMARK.json``, and the ``appended`` one."""
    from benchmarks.harness import manifest

    if request.param == "committed":
        return manifest.load_manifest()
    return request.getfixturevalue("appended")
