"""Seeded inputs: the rows a drain job reads. One general generator for
every traffic file.

The SET of sizes is a fixed function of the traffic file (quantiles of its
distribution, not draws), and the seed decides their ORDER and the bytes of
every text. So every seed offers the same work, in another order: runs with
different seeds differ no more than two runs of one seed do. Everything is
drawn up front from one seed (as ``agent_tpu/loadgen.py`` does)."""

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
    lengths = size_set(traffic["row_bytes"], n_rows)
    rng_of(seed, "order").shuffle(lengths)
    return texts(rng_of(seed, "text"), lengths)


def write_csv(path: str, rows: List[str]) -> None:
    """``id,text`` with every text quoted (rows hold no quote character)."""
    with open(path, "wb") as f:
        f.write(b"id,text\n")
        f.write("".join(f'{i},"{t}"\n' for i, t in enumerate(rows)
                        ).encode("ascii"))
