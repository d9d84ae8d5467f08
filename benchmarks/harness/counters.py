"""Reading counters out of a registry snapshot (``Agent.obs.snapshot()``, as
``run["agent_metrics"]`` keeps it at the window's two ends), for the
per-layer readers. ``stack.histogram_delta`` is the like helper for
histograms. ``None`` means the program has no such series at all — it
predates the counter — which a reader passes on as "nothing to read"."""

from __future__ import annotations

from typing import Any, Dict, Optional


def counter_sum(snap: Dict[str, Any], name: str, **labels: str
                ) -> Optional[float]:
    """Sum of the family's series that carry ``labels``; ``None`` where the
    snapshot has no such family."""
    series = (snap.get(name) or {}).get("series")
    if series is None:
        return None
    return sum(float(s["value"]) for s in series
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def counter_delta(before: Dict[str, Any], after: Dict[str, Any], name: str,
                  **labels: str) -> Optional[float]:
    """What the counter gained between two snapshots; ``None`` where the
    later one has no such family (absent from the earlier one, it gained all
    it holds)."""
    a = counter_sum(after, name, **labels)
    if a is None:
        return None
    return a - (counter_sum(before, name, **labels) or 0.0)
