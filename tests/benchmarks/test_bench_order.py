"""The order of a backlog's sizes is the traffic file's (``order_seed``), as
their set is (PR 32): every run of a cell offers the same sequence of
shards, and ``--seed`` decides what the rows say, the tenants' weights and
the checked sample. Counts on the CPU; no number here is a device number."""

from __future__ import annotations

import hashlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from agent_tpu.ops._model_common import pack_rows, packed_slice_rows  # noqa: E402
from benchmarks.harness import backlog, manifest, schedule  # noqa: E402

SEEDS = [1, 2, 3, 2 ** 31 + 5, 2 ** 31 + 6, 2 ** 31 + 7, 2 ** 31 + 400,
         2 ** 31 + 401, 2 ** 31 + 402, 2 ** 31 + 403, 2 ** 31 + 404,
         2 ** 31 + 405]


def digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


def rows_of_a_run(traffic) -> int:
    """Rows a 10-second run draws: the backlog and the warm-up shards."""
    size = backlog.plan(traffic, 10.0)
    return size["rows"] + size["warm_rows"]


def slices_a_shard(lengths, shard: int = 512, bucket: int = 64) -> np.ndarray:
    """Slices the classify op's staging dispatches for each shard of
    ``lengths`` (``pack_padded_chunk``'s arithmetic: ``pack_rows`` into
    program rows of ``bucket`` tokens, whole slices of them)."""
    per_slice = packed_slice_rows(bucket, 1)
    tokens = np.minimum(np.asarray(lengths), bucket)
    return np.asarray([
        -(-pack_rows(tokens[i:i + shard].tolist(), bucket, bucket // 8)[2]
          // per_slice) for i in range(0, len(tokens), shard)])


# ---- ordered_sizes ---------------------------------------------------------

@pytest.mark.parametrize("dist", [
    {"dist": "lognormal", "median": 28, "sigma": 0.6, "min": 8, "max": 64},
    {"dist": "uniform", "min": 5, "max": 40},
    {"dist": "fixed", "value": 600},
])
def test_ordered_sizes_is_one_fixed_shuffle_of_the_size_set(dist):
    a = schedule.ordered_sizes(dist, 4096, 7)
    assert np.array_equal(a, schedule.ordered_sizes(dist, 4096, 7))
    assert np.array_equal(np.sort(a), schedule.size_set(dist, 4096))
    if dist["dist"] != "fixed":
        assert not np.array_equal(a, schedule.ordered_sizes(dist, 4096, 8))
        assert not np.array_equal(a, np.sort(a))


# ---- the three traffic files -------------------------------------------------

@pytest.mark.parametrize("name, key, n, total, sha", [
    # name, the key of its sizes, rows a 10-second run draws, their sum and
    # the digest of the sorted sizes: all taken from the parent (6ed2b0b).
    ("drain-long", "row_bytes", 30720, 18432000, "d2d99460a0c81f4d"),
    ("drain-short", "row_bytes", 448512, 14179136, "2cd05edab62109ac"),
    ("score-long", "doc_tokens", 25, 409600, "75b485b889d9ee1b"),
])
def test_size_sets_are_what_they_were(name, key, n, total, sha):
    traffic = manifest.load_traffic(name)
    assert rows_of_a_run(traffic) == n
    assert isinstance(traffic["order_seed"], int)
    sizes = schedule.ordered_sizes(traffic[key], n, traffic["order_seed"])
    assert int(sizes.sum()) == total
    assert digest(np.sort(sizes).tobytes()) == sha


def test_long_rows_are_the_parents_byte_for_byte():
    """Every size is equal there, so the order changes nothing: three rows'
    digests, taken from the parent (6ed2b0b) at this seed."""
    rows = schedule.drain_rows(manifest.load_traffic("drain-long"),
                               2 ** 31 + 5, 64)
    assert [digest(rows[i].encode()) for i in (0, 1, 63)] == [
        "859da19ff4acfb90", "9982057ff34ba946", "af9e2527d641bad8"]


def test_score_documents_are_the_parents_byte_for_byte():
    docs = manifest.load_kind("score").documents(
        manifest.load_traffic("score-long"), 151936, 2 ** 31 + 5, 3)
    assert docs[0].dtype == np.int32
    assert digest(docs[0].tobytes()) == "5e86bf94fc3dac63"


# ---- the short backlog: the same shards in every run ----------------------

@pytest.fixture(scope="module")
def short():
    """The short cell's backlog as a 10-second run lays it out: its lengths,
    the slices of each of its 864 shards, and one seed's first rows."""
    from types import SimpleNamespace

    traffic = manifest.load_traffic("drain-short")
    n = rows_of_a_run(traffic)
    lengths = schedule.ordered_sizes(traffic["row_bytes"], n,
                                     traffic["order_seed"])
    warm = backlog.plan(traffic, 10.0)["warm_rows"]
    return SimpleNamespace(
        traffic=traffic, n=n, lengths=lengths,
        slices=slices_a_shard(lengths[warm:]),
        first=schedule.drain_rows(traffic, 0, n)[:2048])


def test_the_short_backlog_keeps_the_populations_level(short):
    """``order_seed`` was chosen by this count (PERF.md section 4): the
    shards a window holds cost what all 864 do, so the cell keeps its level
    and answers a change of packing as the population would."""
    slices = short.slices
    assert len(slices) == 864 and set(slices.tolist()) == {4, 5}
    whole, window = slices.mean(), slices[8:469].mean()
    assert whole == pytest.approx(4.4479, abs=1e-4)      # 240 orders: 4.4472
    assert window == pytest.approx(4.4490, abs=1e-4)
    assert abs(window / whole - 1) <= 0.002
    assert (slices == 5).mean() == pytest.approx(0.4479, abs=1e-4)
    # Whatever rate a later PR reaches: no window from shards 8-40 to shards
    # 420-720 is further than 0.2 % from the whole.
    total = np.concatenate([[0], np.cumsum(slices)])
    worst = max(abs((total[b] - total[a]) / (b - a) / whole - 1)
                for a in range(8, 41, 4) for b in range(420, 721, 10))
    assert worst <= 0.002
    # Not one job's eight shards repeated: the shards differ as a
    # population's do.
    assert len({tuple(slices[i:i + 8]) for i in range(0, 864, 8)}) > 50


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_offers_the_short_backlog_the_same_shards(short, seed):
    """Row for row the lengths ``short.slices`` were packed from (the pack
    is a function of the lengths alone), filled with other bytes."""
    rows = schedule.drain_rows(short.traffic, seed, short.n)
    got = np.fromiter(map(len, rows), dtype=np.int64, count=short.n)
    assert np.array_equal(got, short.lengths)
    assert not set(rows[:2048]) & set(short.first)
    assert len(set(rows[:4096])) == 4096


# ---- a score backlog of mixed lengths ----------------------------------------

def test_a_score_backlog_of_mixed_lengths_keeps_one_order_over_seeds():
    documents = manifest.load_kind("score").documents
    traffic = {"doc_tokens": {"dist": "uniform", "min": 100, "max": 900},
               "token_ids": {"dist": "zipf", "exponent": 1.1}, "order_seed": 3}
    a, b = documents(traffic, 2048, 11, 40), documents(traffic, 2048, 12, 40)
    lengths = [len(d) for d in a]
    assert lengths == [len(d) for d in b] != sorted(lengths)
    assert sorted(lengths) == schedule.size_set(
        traffic["doc_tokens"], 40).tolist()
    assert not any(np.array_equal(x, y) for x, y in zip(a, b))
    c = documents(dict(traffic, order_seed=4), 2048, 11, 40)
    assert [len(d) for d in c] != lengths
