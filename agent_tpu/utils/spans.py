"""Which op a controller result body belongs to.

Per-op time is attributed from the scraped ``/v1/metrics`` series
(``agent_tpu.obs.scrape.op_phase_seconds``); what is left here is the one
lookup a result body still needs.
"""

from __future__ import annotations

from typing import Mapping


def result_op(result: Mapping) -> str | None:
    """The op a result body belongs to. Every op now stamps ``"op"`` into
    its result (ISSUE 2 satellite); the summaries/sink sniffing below is
    kept ONLY as a fallback for old bodies (pre-stamp journals, agents a
    version behind) and must not grow new cases — new attribution should
    come from the explicit key or from scraping ``/v1/metrics``
    (``agent_tpu.obs.scrape``)."""
    op = result.get("op")
    if op:
        return op
    if (
        "summaries" in result
        or "summary" in result
        or "map_summarize" in str(result.get("output_path", ""))
    ):
        return "map_summarize"
    return None
