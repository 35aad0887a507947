"""Per-layer metrics read from the program's counters and histograms
(their change over the window)."""

from __future__ import annotations


def fill_pct(ctx: dict, params: dict):
    """Tokens a decode iteration emitted against the slots it could
    have filled."""
    d = ctx.get("counters", {})
    steps = d.get(params["steps"], 0)
    slots = ctx["config"]["engine"]["max_slots"]
    if not steps:
        return None
    return 100.0 * d.get(params["tokens"], 0) / (steps * slots)


def hist_mean_ms(ctx: dict, params: dict):
    """Mean of a span histogram's observations in the window."""
    total, count = ctx.get("counters", {}).get(params["histogram"],
                                               (0.0, 0))
    return 1e3 * total / count if count else None
