"""``fused_qkv_attention_blocks.setup`` (written in PR 31, listed in PR 32):
the reader on hand-made runs and its entry in the manifest, appended after
PR 29's. The CPU rehearsal of ``test_bench_packing.py`` reads it through the
command."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest  # noqa: E402

NAME = "fused_qkv_attention_blocks.setup"
FAMILY = "attention_qkv_traced_total"


def qkv(fused=None, separate=None):
    series = [{"labels": {"form": form}, "value": value}
              for form, value in (("fused", fused), ("separate", separate))
              if value is not None]
    return {FAMILY: {"type": "counter", "series": series}}


def run_of(before, after, kind="drain"):
    return {"kind": kind, "op": "map_classify_tpu", "shards": 20,
            "agent_metrics": (before, after)}


@pytest.mark.parametrize("before, after, want", [
    # 12 tenants x 12 blocks, all traced in set-up: the window adds none.
    (qkv(144.0, 0.0), qkv(144.0, 0.0), 144.0),
    # The count at the window's END, not what the window gained.
    (qkv(132.0), qkv(144.0), 144.0),
    (qkv(144.0, 24.0), qkv(144.0, 24.0), 144.0),      # separate calls apart
    ({}, qkv(None, 12.0), 0.0),       # every call on three leaves: none fused
])
def test_reader_reads_the_count_at_the_windows_end(before, after, want):
    read = manifest.load_layer_metric(NAME).read
    assert read(run_of(before, after)) == want


@pytest.mark.parametrize("before, after, kind", [
    ({}, {}, "drain"),                              # the parent of PR 31
    ({}, {"attention_blocks_traced_total": {"series": [
        {"labels": {"path": "whole_row"}, "value": 144.0}]}}, "drain"),
    (qkv(144.0), qkv(144.0), "infer"),
])
def test_reader_gives_nothing_where_there_is_nothing_to_read(before, after, kind):
    read = manifest.load_layer_metric(NAME).read
    assert read(run_of(before, after, kind)) is None


def test_manifest_entry(manifests):
    m = manifests
    (entry,) = [e for e in m["per_layer"] if e["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Kernels",
        "moves": "drain_rows_per_s",
        "workloads": ["bert-base.drain-long", "bert-base.drain-short"]}
    names = [e["name"] for e in m["per_layer"]]
    # After PR 29's entry, which nothing moved.
    assert names.index(NAME) == names.index("classify_real_token_share.drain") + 1
    for cell in m["workloads"]:
        listed = {e["name"] for e in manifest.metrics_of_cell(
            m, cell["name"], "per_layer")}
        assert (NAME in listed) == cell["name"].startswith("bert-base.")
