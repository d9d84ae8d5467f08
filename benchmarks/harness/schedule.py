"""Seeded inputs: the rows a drain job reads. One general generator for
every traffic file.

The SET of sizes is a fixed function of the traffic file (quantiles of its
distribution, not draws), and so is their ORDER (its ``order_seed``): every
run of a cell offers the same sequence of shards, each of the same lengths.
``--seed`` decides what the rows SAY (the bytes of every text, the token ids
and their permutation), the tenants' model ids and so their weights, and the
rows the check samples. So runs with different seeds differ no more than two
runs of one seed do: what is left to differ is the system. (A shard's cost
can depend on which lengths fall into it: packed, a short shard is 4 slices
or 5. An order drawn from ``--seed`` gave every seed another amount of
work.) Everything is drawn up front (as ``agent_tpu/loadgen.py`` does)."""

from __future__ import annotations

from statistics import NormalDist
from typing import Any, List, Mapping

import numpy as np


def rng_of(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of one seed (any non-negative int,
    beyond 32 bits too)."""
    salt = int.from_bytes(stream.encode("utf-8")[:8].ljust(8, b"\0"), "big")
    return np.random.default_rng([int(seed), salt])


def size_set(dist: Mapping[str, Any], n: int) -> np.ndarray:
    """``n`` sizes that follow ``dist`` exactly in shape: the quantiles at
    (i + ½)/n. ``{"dist": "fixed", "value": v}``, ``{"dist": "uniform",
    "min": a, "max": b}`` or ``{"dist": "lognormal", "median": m, "sigma":
    s, "min": a, "max": b}`` (clipped). Sorted ascending, int."""
    u = (np.arange(n) + 0.5) / max(1, n)
    kind = dist["dist"]
    if kind == "fixed":
        out = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        out = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        out = np.minimum(np.floor(out), dist["max"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        out = np.clip(np.rint(dist["median"] * np.exp(dist["sigma"] * z)),
                      dist["min"], dist["max"])
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    return out.astype(np.int64)


def ordered_sizes(dist: Mapping[str, Any], n: int, order_seed: int
                  ) -> np.ndarray:
    """``size_set(dist, n)`` in the order the traffic file's ``order_seed``
    gives it: one fixed shuffle of the whole backlog, so its shards differ
    from each other as a population's do and are the same in every run."""
    lengths = size_set(dist, n)
    rng_of(order_seed, "order").shuffle(lengths)
    return lengths


PALETTE = 6     # letters a row draws from


def texts(rng: np.random.Generator, lengths: np.ndarray) -> List[str]:
    """One ASCII text of exactly ``lengths[i]`` bytes per row: seeded letters
    and spaces, a letter at both ends (nothing a CSV reader could trim). Each
    row draws from its own palette of ``PALETTE`` letters, so rows differ in
    what they say and not only in order: with every row drawn from the whole
    alphabet a mean-pooled model answers all long rows alike (their logits
    then differ by less than bf16 resolves, and a check over many rows has
    one measurement, many times)."""
    n, width = len(lengths), int(lengths.max()) if len(lengths) else 0
    palettes = rng.permuted(np.tile(np.arange(26, dtype=np.uint8), (n, 1)),
                            axis=1)[:, :PALETTE]
    picks = rng.integers(0, PALETTE + 2, size=(n, width), dtype=np.uint8)
    letters = np.take_along_axis(palettes, np.minimum(picks, PALETTE - 1), axis=1)
    body = np.where(picks >= PALETTE, np.uint8(ord(" ")),
                    letters + np.uint8(ord("a")))
    edge = np.take_along_axis(palettes, picks % PALETTE, axis=1) + np.uint8(ord("a"))
    body[:, 0] = edge[:, 0]
    last = np.maximum(lengths - 1, 0)
    body[np.arange(n), last] = edge[np.arange(n), last]
    blob = body.tobytes()
    return [blob[i * width:i * width + int(lengths[i])].decode("ascii")
            for i in range(n)]


# ---- drain ---------------------------------------------------------------

def drain_rows(traffic: Mapping[str, Any], seed: int, n_rows: int
               ) -> List[str]:
    """The ``n_rows`` texts of a drain job, no row twice."""
    lengths = ordered_sizes(traffic["row_bytes"], n_rows,
                            traffic["order_seed"])
    return texts(rng_of(seed, "text"), lengths)


def write_csv(path: str, rows: List[str]) -> None:
    """``id,text`` with every text quoted (rows hold no quote character)."""
    with open(path, "wb") as f:
        f.write(b"id,text\n")
        f.write("".join(f'{i},"{t}"\n' for i, t in enumerate(rows)
                        ).encode("ascii"))
