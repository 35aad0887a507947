"""Per-layer metrics of the batcher's own records of a request's life
and of a pass of its loop (`docs/observability.md`, Span reference:
``decode/first_token``, ``decode/retire``, ``decode/iteration`` and
the counters beside them). A reader that finds no such span, field
or counter returns None, so a program from before them leaves the
metric out."""

from __future__ import annotations

import statistics


def _fields(ctx: dict, span: str, field: str) -> "list[tuple]":
    """(duration, field) of the named spans that carry the field
    (``dur_s``: the duration itself)."""
    out = []
    for s in ctx.get("spans", []):
        if s["name"] == span:
            v = s["dur_s"] if field == "dur_s" \
                else s["fields"].get(field)
            if v is not None:
                out.append((s["dur_s"], v))
    return out


def field_p95_ms(ctx: dict, params: dict):
    """95th percentile of one annotated field (seconds) over the
    named spans, None under 20 of them: for ``decode/retire``'s
    ``gap_max_s``, the stall the unluckiest requests met."""
    vals = sorted(v for _d, v in
                  _fields(ctx, params["span"], params["field"]))
    if len(vals) < 20:
        return None
    return 1e3 * vals[min(len(vals) - 1, int(0.95 * len(vals)))]


def dur_less_field_ms(ctx: dict, params: dict):
    """Mean of the named spans' durations less one annotated field
    (seconds): for ``decode/iteration`` less ``wait_s``, the host's
    own time in a pass, overlapped by the device or not."""
    own = [max(0.0, d - v) for d, v in
           _fields(ctx, params["span"], params["field"])]
    return 1e3 * statistics.fmean(own) if own else None


def _total(ctx: dict, of: dict):
    """One total over the window: a counter's change, a histogram's
    summed observations, or the named spans' summed field
    (``dur_s``: their durations). None where the program has no such
    counter, or no such span carries the field."""
    if "span" in of:
        vals = [v for _d, v in _fields(ctx, of["span"], of["field"])]
        return sum(vals) if vals else None
    got = ctx.get("counters", {}).get(
        of.get("counter") or of["histogram"])
    if got is None:
        return None
    return got[0] if "histogram" in of else got


def ratio_pct(ctx: dict, params: dict):
    """``num`` over ``den`` as a share, each a total of
    :func:`_total`'s kinds. None when the denominator is missing or
    0 (0 / 0 is no reading, not 0) and when no span carries the
    numerator's field; a counter missing beside a denominator that
    is there counts as 0 (a counter is born at its first
    increment)."""
    den = _total(ctx, params["den"])
    if not den:
        return None
    num = _total(ctx, params["num"])
    if num is None:
        if "span" in params["num"]:
            return None
        num = 0.0
    return 100.0 * num / den
