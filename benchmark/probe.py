"""The program's own spans and counters, as the per-layer readers get
them: the trace store's records since a cursor, and the metric
registry's counters and histograms as plain numbers whose difference
over a window can be taken. The only reading of the program's
observability in the benchmark; nothing here changes it.
"""

from __future__ import annotations


def span_cursor() -> int:
    from analytics_zoo_tpu.common import tracing
    return tracing.get_store().latest_seq()


def spans_since(cursor: int) -> "tuple[int, list[dict]]":
    """(new cursor, records since the old one) as dicts with ``name``,
    ``trace_id``, ``t_start`` (epoch seconds), ``dur_s`` and ``fields``. The store
    is a ring: poll often enough that nothing falls off it."""
    from analytics_zoo_tpu.common import tracing
    cursor, recs = tracing.get_store().records_since(cursor)
    return cursor, [{"name": r.name, "trace_id": r.trace_id,
                     "t_start": r.t_start, "dur_s": r.dur_s,
                     "fields": dict(r.fields)} for r in recs]


def metrics() -> dict:
    """{name: value} for counters and gauges, {name: (sum, count)}
    for histograms, labels summed over."""
    from analytics_zoo_tpu.common import observability as obs
    out = {}
    for name, fam in obs.snapshot().items():
        if fam["type"] == "histogram":
            out[name] = (sum(v["sum"] for v in fam["values"]),
                         sum(v["count"] for v in fam["values"]))
        else:
            out[name] = sum(v["value"] for v in fam["values"])
    return out


def delta(before: dict, after: dict) -> dict:
    """after - before, name by name (a name new in ``after`` counts
    from nought)."""
    out = {}
    for name, a in after.items():
        b = before.get(name, (0.0, 0) if isinstance(a, tuple) else 0.0)
        out[name] = (a[0] - b[0], a[1] - b[1]) \
            if isinstance(a, tuple) else a - b
    return out
