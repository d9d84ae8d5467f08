"""Rate and spread arithmetic — the only place they are done."""

from __future__ import annotations

import statistics
from typing import Sequence


def rate(count: float, seconds: float) -> float:
    """Work over time; the window must have a length."""
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return float(count) / float(seconds)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile (Python's
    ``statistics.quantiles(values, n=4)``) as a share of the median — the
    spread the bounds in ``BENCHMARK.json`` are set from."""
    q1, _, q3 = statistics.quantiles([float(v) for v in values], n=4)
    med = statistics.median(values)
    if med == 0:
        raise ValueError("spread of values whose median is 0")
    return (q3 - q1) / abs(med)
