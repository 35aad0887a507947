"""Per-layer metrics read from the spans the program opens where its
work happens (``ctx["spans"]``: `docs/observability.md`, Span
reference) and from the trace reduced to the program's own names
(``ctx["trace"]["program"]``: `benchmark/reduce/program.py`). A
reader that finds no such span or key returns None, so a program
from before these spans leaves the metric out."""

from __future__ import annotations

import statistics


def _named(ctx: dict, names) -> "list[dict]":
    names = {names} if isinstance(names, str) else set(names)
    return [s for s in ctx.get("spans", []) if s["name"] in names]


def mean_dur_ms(ctx: dict, params: dict):
    """Mean duration of the named spans."""
    durs = [s["dur_s"] for s in _named(ctx, params["span"])]
    return 1e3 * statistics.fmean(durs) if durs else None


def sum_dur_ms(ctx: dict, params: dict):
    """Summed duration of the named spans inside the window; 0 where
    there is none: for ``xla/compile``, whose absence is the
    reading."""
    return 1e3 * sum(s["dur_s"] for s in _named(ctx, params["span"]))


def dur_share_pct(ctx: dict, params: dict):
    """The named spans' summed duration over the window's length."""
    spans = _named(ctx, params["span"])
    if not spans or not ctx.get("window_s"):
        return None
    return 100.0 * sum(s["dur_s"] for s in spans) / ctx["window_s"]


def self_time_ms(ctx: dict, params: dict):
    """Mean self time of the named spans: each one's duration less
    that of the ``inside`` spans of its trace id."""
    inner: "dict[str, float]" = {}
    for s in _named(ctx, params["inside"]):
        inner[s["trace_id"]] = inner.get(s["trace_id"], 0.0) + \
            s["dur_s"]
    own = [max(0.0, s["dur_s"] - inner.get(s["trace_id"], 0.0))
           for s in _named(ctx, params["span"])]
    return 1e3 * statistics.fmean(own) if own else None


def _program(ctx: dict) -> "dict | None":
    return (ctx.get("trace") or {}).get("program")


def idle_pct(ctx: dict, params: dict):
    """Share of the traced window that is idle under the program
    spans whose names start with one of ``prefixes``
    (``unattributed``: under none)."""
    p = _program(ctx)
    if not p or p["window_s"] <= 0:
        return None
    idle = [s for name, s in p["idle_by_span"].items()
            if name.startswith(tuple(params["prefixes"]))]
    return 100.0 * sum(idle) / p["window_s"] if idle else None


def scope_share_pct(ctx: dict, params: dict):
    """Share of the device's busy time in operations traced under
    the named scopes."""
    p = _program(ctx)
    total = sum(p["scope_s"].values()) if p else 0.0
    # nothing under any scope: a program from before the scopes, or
    # executables out of a compile cache it filled (the cache's key
    # leaves names out, so they keep the names they were built with)
    if total <= 0 or set(p["scope_s"]) <= {"unscoped"}:
        return None
    return 100.0 * sum(p["scope_s"].get(s, 0.0)
                       for s in params["scopes"]) / total
