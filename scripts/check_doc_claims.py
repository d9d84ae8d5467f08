#!/usr/bin/env python3
"""CI guard against citation drift: docs and source that cite what the
repository does not hold, and a speed table that drifts from the ledger.

``benchmarks/run.py`` with ``PERF_LEDGER.jsonl`` is the one benchmark and
the one record of speed. An older harness, its record files and the prose
quoting them were deleted; this script keeps them gone and keeps the one
table of figures equal to the arbiter:

- every ``scripts/<name>.py`` citation must name a file under ``scripts/``;
- every ``BENCH_r<NN>`` / ``MULTICHIP_r<NN>`` record key must have its
  ``.json`` at the repository's root;
- a Python file cited by its bare name (``chip_smoke.py``) must exist, at
  the root or under that name anywhere in the tree. A name standing alone
  in quotes is a file the code creates, not a citation; ``REFERENCE_FILES``
  are the reference repository's, which SURVEY/PARITY/MIGRATION map from;
- the README's table under the heading "Measured on the chip": each row's
  cell and metric must be names ``BENCHMARK.json`` declares (read, never
  written), and where ``PERF_LEDGER.jsonl`` holds a line for the row's PR
  and cell, the figure must equal that line's ``change`` median to the
  digits printed. The driver rewrites and prunes the repository's copy of
  the ledger, so a row whose PR the copy no longer holds passes, and so
  does a missing ledger.

What ``.gitignore`` names is not walked (an unpacked archive of the tree
is a second copy of every citation). Reviewer and driver files
(VERDICT.md, ADVICE.md, ISSUE.md, CHANGES.md) are excluded: naming what is
missing, deleted or still to come is their job, and CHANGES.md is where a
deleted file's name belongs. So is ``tests/test_doc_claims.py``, whose
fixtures break each rule on purpose.

Run from anywhere: paths resolve relative to the repo root (this file's
parent's parent). Exit 0 = clean, 1 = problems (one per line as
``path:lineno: message``).
"""

from __future__ import annotations

import fnmatch
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Files whose JOB is to cite missing/future artifacts; the last two are
# this script, whose docstring spells the patterns, and its own test, whose
# fixtures are stale citations by design.
EXCLUDE_FILES = {"VERDICT.md", "ADVICE.md", "ISSUE.md", "CHANGES.md",
                 "check_doc_claims.py", "test_doc_claims.py"}
# Files of the REFERENCE repository (SURVEY.md's inventory) that no
# module here is named after.
REFERENCE_FILES = {"ops_loader.py", "worker_sizing.py", "_tpu_runtime.py",
                   "tpu_ops.py"}

SCRIPT_RE = re.compile(r"scripts/([A-Za-z0-9_\-]+\.py)")
RECORD_RE = re.compile(r"\b((?:BENCH|MULTICHIP)_r\d+)\b")
BARE_PY_RE = re.compile(r"(?<![\w/.\-\"'])([A-Za-z_][A-Za-z0-9_]*\.py)\b")
TABLE_HEADING_RE = re.compile(r"^#+\s*Measured on the chip\s*$")


def _ignored_names(root: str) -> list:
    """Basename patterns of the root ``.gitignore`` (directories and files
    alike; a trailing slash only says which of the two a pattern is)."""
    patterns = [".git"]
    try:
        with open(os.path.join(root, ".gitignore"), encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if line and not line.startswith("#"):
                    patterns.append(line.strip("/"))
    except OSError:
        pass
    return patterns


def _walk(root: str):
    """The ``.py`` and ``.md`` files the rules read, and every ``.py``
    basename of the tree (what a bare citation may resolve to)."""
    ignored = _ignored_names(root)

    def keep(name: str) -> bool:
        return not any(fnmatch.fnmatch(name, p) for p in ignored)

    paths, py_names = [], set()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if keep(d))
        for name in sorted(filenames):
            if not keep(name) or not name.endswith((".py", ".md")):
                continue
            if name.endswith(".py"):
                py_names.add(name)
            if name not in EXCLUDE_FILES:
                paths.append(os.path.join(dirpath, name))
    return paths, py_names


def _scan_file(root: str, path: str, py_names: set) -> list:
    problems = []
    rel = os.path.relpath(path, root)
    try:
        with open(path, encoding="utf-8", errors="replace") as f:
            lines = f.readlines()
    except OSError as exc:
        return [f"{rel}:0: unreadable ({exc})"]
    for lineno, line in enumerate(lines, 1):
        for m in SCRIPT_RE.finditer(line):
            if not os.path.exists(os.path.join(root, "scripts", m.group(1))):
                problems.append(
                    f"{rel}:{lineno}: cites scripts/{m.group(1)} "
                    "which does not exist"
                )
        for m in RECORD_RE.finditer(line):
            if not os.path.exists(os.path.join(root, m.group(1) + ".json")):
                problems.append(
                    f"{rel}:{lineno}: cites {m.group(1)} but "
                    f"{m.group(1)}.json is not recorded in the repo"
                )
        for m in BARE_PY_RE.finditer(line):
            name = m.group(1)
            if name not in py_names and name not in REFERENCE_FILES:
                problems.append(
                    f"{rel}:{lineno}: cites {name} which does not exist "
                    "(a deleted file is named in CHANGES.md only)"
                )
    return problems


# ---- the README's table against the ledger ----

def _table_rows(readme_lines: list):
    """``(lineno, {column: text})`` for each row of the first table under
    the "Measured on the chip" heading; backticks and padding stripped."""
    rows, header, in_section = [], None, False
    for lineno, line in enumerate(readme_lines, 1):
        if TABLE_HEADING_RE.match(line.strip()):
            in_section = True
            continue
        if not in_section:
            continue
        if line.startswith("#"):
            break
        if not line.lstrip().startswith("|"):
            if header is not None:
                break
            continue
        cells = [c.strip().strip("`") for c in line.strip().strip("|").split("|")]
        if header is None:
            header = [c.lower() for c in cells]
        elif not all(set(c) <= set("-: ") for c in cells):
            rows.append((lineno, dict(zip(header, cells))))
    return rows


def _ledger_changes(root: str) -> dict:
    """``{(PR, cell, metric): change median}`` of the ledger's copy. No
    ledger, a pruned PR and a line without the metric all mean there is
    nothing to hold a figure to, so each is simply absent."""
    changes = {}
    try:
        with open(os.path.join(root, "PERF_LEDGER.jsonl"), encoding="utf-8") as f:
            raws = f.readlines()
    except OSError:
        return changes
    for raw in raws:
        try:
            line = json.loads(raw)
        except ValueError:
            continue
        for group in ("end_to_end", "per_layer"):
            for metric, pair in (line.get(group) or {}).items():
                if isinstance(pair, list) and len(pair) == 2 \
                        and isinstance(pair[1], (int, float)):
                    changes[line.get("pr"), line.get("workload"), metric] = \
                        float(pair[1])
    return changes


def _check_table(root: str) -> tuple:
    """Problems of the README's table, and how many rows it has."""
    readme = os.path.join(root, "README.md")
    try:
        with open(readme, encoding="utf-8", errors="replace") as f:
            rows = _table_rows(f.readlines())
    except OSError:
        return [], 0
    if not rows:
        return [], 0
    try:
        with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
            manifest = json.load(f)
    except (OSError, ValueError) as exc:
        return [f"README.md:{rows[0][0]}: the table cannot be held to "
                f"BENCHMARK.json ({exc})"], len(rows)
    cells = {w["name"] for w in manifest.get("workloads", [])}
    metrics = {m["name"] for group in ("end_to_end", "per_layer")
               for m in manifest.get(group, [])}
    changes = _ledger_changes(root)
    problems = []
    for lineno, row in rows:
        where = f"README.md:{lineno}"
        cell, metric = row.get("cell", ""), row.get("metric", "")
        figure = row.get("value", "").replace(",", "")
        if cell not in cells:
            problems.append(f"{where}: cell {cell!r} is not a workload of "
                            "BENCHMARK.json")
        if metric not in metrics:
            problems.append(f"{where}: metric {metric!r} is not a metric of "
                            "BENCHMARK.json")
        pr_text = row.get("ledger pr", "")
        if not pr_text.isdigit() or not re.fullmatch(r"-?\d+(\.\d+)?", figure):
            problems.append(f"{where}: a row needs a figure and the number "
                            "of the ledger's PR")
            continue
        change = changes.get((int(pr_text), cell, metric))
        if change is None:
            continue
        digits = len(figure.partition(".")[2])
        if abs(float(figure) - change) > 0.5 * 10 ** -digits + 1e-9:
            problems.append(
                f"{where}: {metric} in {cell} reads {row['value']} but "
                f"PERF_LEDGER.jsonl, PR {pr_text}, holds {change}"
            )
    return problems, len(rows)


def check(root: str) -> tuple:
    """``(problems, files scanned, table rows)`` of the tree at ``root``."""
    paths, py_names = _walk(root)
    problems = []
    for path in paths:
        problems.extend(_scan_file(root, path, py_names))
    table_problems, n_rows = _check_table(root)
    return problems + table_problems, len(paths), n_rows


def main() -> int:
    problems, n_scanned, n_rows = check(REPO)
    if problems:
        print(f"check_doc_claims: {len(problems)} problem(s) "
              f"in {n_scanned} files:")
        for p in problems:
            print(f"  {p}")
        return 1
    print(f"check_doc_claims: OK ({n_scanned} files scanned, "
          f"{n_rows} table rows held to the ledger)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
