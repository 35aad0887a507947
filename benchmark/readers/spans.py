"""Per-layer metrics read from the program's spans (the trace
store's records inside the window)."""

from __future__ import annotations

import statistics


def _named(ctx: dict, name: str) -> "list[dict]":
    return [s for s in ctx.get("spans", []) if s["name"] == name]


def mean_field_ms(ctx: dict, params: dict):
    """Mean of one annotated field (seconds) of the named spans."""
    vals = [s["fields"][params["field"]]
            for s in _named(ctx, params["span"])
            if s["fields"].get(params["field"]) is not None]
    return 1e3 * statistics.fmean(vals) if vals else None


def start_gap_p50_ms(ctx: dict, params: dict):
    """Median distance between the starts of consecutive named spans:
    for ``train/step``, dispatch to dispatch."""
    starts = sorted(s["t_start"] for s in _named(ctx, params["span"]))
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    return 1e3 * statistics.median(gaps) if gaps else None


def dur_p95_ms(ctx: dict, params: dict):
    """95th percentile of the named spans' durations; for
    ``decode/admit``, whose record runs from submit to the admission
    that samples the first token, that is time to first token."""
    durs = sorted(s["dur_s"] for s in _named(ctx, params["span"]))
    if len(durs) < 20:
        return None
    return 1e3 * durs[min(len(durs) - 1, int(0.95 * len(durs)))]
