"""``scripts/check_doc_claims.py``: each rule fails on a fixture tree that
breaks it, what must not fail it does not, and the repository passes.

The fixtures spell the names of deleted files in pieces, so that this file
itself holds no citation of them.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "check_doc_claims", os.path.join(REPO, "scripts", "check_doc_claims.py"))
cdc = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cdc)

OLD_HARNESS = "bench" + ".py"
OLD_RECORD = "BENCH" + "_r09"
OLD_FLEET_RECORD = "MULTICHIP" + "_r02"

MANIFEST = {
    "workloads": [{"name": "tiny.drain"}],
    "end_to_end": [{"name": "drain_rows_per_s"}],
    "per_layer": [{"name": "encoder_roofline"}],
}
LEDGER = [
    {"pr": 7, "workload": None, "notes": ["a line without a cell"]},
    {"pr": 7, "workload": "tiny.drain",
     "end_to_end": {"drain_rows_per_s": [1500.0, 1592.14]},
     "per_layer": {"encoder_roofline": [70.0, 78.259]}},
]


def _readme(rows):
    table = "\n".join(f"| `{c}` | `{m}` | {v} | x | {pr} |" for c, m, v, pr in rows)
    return ("# tiny\n\nNo figure here.\n\n### Measured on the chip\n\n"
            "| Cell | Metric | Value | Unit | Ledger PR |\n"
            "| --- | --- | --- | --- | --- |\n" + table + "\n\n## Layout\n")


def _tree(tmp_path, *, files=None, rows=None, ledger=LEDGER, gitignore=""):
    """A small repository: a script, a root-level program, a manifest, a
    ledger and a README whose table holds ``rows``."""
    (tmp_path / "scripts").mkdir()
    (tmp_path / "scripts" / "real.py").write_text("# a script\n")
    (tmp_path / "chip_smoke.py").write_text("# cites scripts/real.py\n")
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "encoder.py").write_text("# cited by its short name\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(MANIFEST))
    if ledger is not None:
        (tmp_path / "PERF_LEDGER.jsonl").write_text(
            "".join(json.dumps(line) + "\n" for line in ledger))
    if gitignore:
        (tmp_path / ".gitignore").write_text(gitignore)
    rows = rows if rows is not None else [
        ("tiny.drain", "drain_rows_per_s", "1,592.1", 7),
        ("tiny.drain", "encoder_roofline", "78.259", 7),
    ]
    (tmp_path / "README.md").write_text(_readme(rows))
    for rel, text in (files or {}).items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    return str(tmp_path)


# (case, what the fixture adds or changes, a word of each expected problem);
# no fixture: the repository itself, its own README, manifest and ledger.
CASES = [
    ("the_repository", None, []),
    ("sound_tree",
     {"files": {"NOTES.md": "Run `python chip_smoke.py`, see scripts/real.py "
                            "and `encoder.py`; the reference had worker_sizing.py.\n",
                "pkg/writer.py": 'path = os.path.join(tmp, "made_here.py")\n'}},
     []),
    ("missing_script",
     {"files": {"NOTES.md": "see scripts/gone.py\n"}},
     ["scripts/gone.py"]),
    ("missing_record",
     {"files": {"pkg/encoder.py": f"# measured in {OLD_RECORD}\n"}},
     [OLD_RECORD]),
    ("missing_fleet_record",
     {"files": {"NOTES.md": f"scaling is in {OLD_FLEET_RECORD}.\n"}},
     [OLD_FLEET_RECORD]),
    ("missing_root_program",
     {"files": {"pkg/encoder.py": f"# {OLD_HARNESS} can sweep it\n"}},
     [OLD_HARNESS]),
    ("table_off_by_one_digit",
     {"rows": [("tiny.drain", "drain_rows_per_s", "1,592.2", 7)]},
     ["1,592.2"]),
    ("table_unknown_cell",
     {"rows": [("tiny.serve", "drain_rows_per_s", "1,592.1", 7)]},
     ["tiny.serve"]),
    ("table_unknown_metric",
     {"rows": [("tiny.drain", "serving_ttft_p99_ms", "12", 7)]},
     ["serving_ttft_p99_ms"]),
    ("table_row_without_pr",
     {"rows": [("tiny.drain", "drain_rows_per_s", "1,592.1", "-")]},
     ["number of the ledger's PR"]),
    ("ignored_directory_with_stale_citation",
     {"gitignore": "# an unpacked archive\n_archive/\n*.log\n",
      "files": {"_archive/README.md": f"`python {OLD_HARNESS}` wrote {OLD_RECORD}\n",
                "_archive/scripts/x.py": "# see scripts/gone.py\n"}},
     []),
    ("table_row_of_a_pruned_pr",
     {"rows": [("tiny.drain", "drain_rows_per_s", "999", 3)]},
     []),
    ("no_ledger",
     {"ledger": None},
     []),
    ("fewer_digits_printed",
     {"rows": [("tiny.drain", "drain_rows_per_s", "1,592", 7),
               ("tiny.drain", "encoder_roofline", "78.3", 7)]},
     []),
]


@pytest.mark.parametrize("fixture,expected", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_doc_claims(tmp_path, fixture, expected):
    if fixture is None:
        problems, n_scanned, n_rows = cdc.check(REPO)
        # The table must be there; it is held to whatever lines the
        # ledger's copy still has.
        assert n_scanned > 100 and n_rows >= 6
    else:
        problems, n_scanned, n_rows = cdc.check(_tree(tmp_path, **fixture))
        assert n_scanned >= 3 and n_rows >= 1
    assert len(problems) == len(expected), problems
    for problem, word in zip(problems, expected):
        assert word in problem, problems
