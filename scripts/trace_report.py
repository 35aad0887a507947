"""Offline trace/diagnostics report over a JSONL event log.

`make trace-report` renders the structured event log written by
``ZOO_TPU_EVENT_LOG`` (see docs/observability.md) into three views:

  1. per-step training timeline — one line per ``train/step`` span
     with the data-wait / dispatch / device / checkpoint breakdown
  2. top-N slowest serving requests — ``serving/request`` roots
     joined to their child spans (queue wait, pad, predict, scatter)
     by trace id
  3. anomaly digest — ``diagnostics/anomaly`` events grouped by kind

``--chrome OUT`` additionally exports every traced span as Perfetto-
loadable chrome-trace JSON (open at https://ui.perfetto.dev).

``--fleet URL`` switches the source from an offline event log to a
*running* fleet router: it pulls the stitched cross-process traces
from ``GET /debug/traces?fleet=1`` (docs/observability.md, Fleet
federation) and renders the slowest stitched requests with their
per-source (router / replica) span breakdown; ``--chrome`` then
exports one Perfetto process lane per source.

Filters: ``--last N`` keeps only the newest N events; ``--since TS``
(epoch seconds, as in the records' ``ts`` field) keeps events at or
after TS. ``--check`` turns the anomaly digest into a CI gate: exit
code 2 when any anomalies survive the filters (pair with ``--since``
to gate on "no anomalies since the last deploy"). Rotated ``.gz``
segments load transparently.

``--history FILE`` additionally summarizes an exported metric-history
JSON document (``MetricHistory.export()`` /
``GET /debug/metrics/history`` — docs/observability.md §History).

Usage:
    python scripts/trace_report.py --events PATH [--top N]
                                   [--last N] [--since TS]
                                   [--check] [--chrome OUT]
                                   [--history FILE]
    python scripts/trace_report.py --fleet http://router:8080
                                   [--top N] [--chrome OUT]
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # `python scripts/trace_report.py` from root
    sys.path.insert(0, ROOT)

from analytics_zoo_tpu.common import tracing  # noqa: E402


def load_events(path: str) -> "List[Dict[str, Any]]":
    """Parse a JSONL event log (gzip-compressed rotated segments
    too), skipping malformed lines (a crashed writer may leave a
    truncated tail)."""
    out: "List[Dict[str, Any]]" = []
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


def _fmt_ms(v) -> str:
    return "-" if v is None else f"{float(v) * 1e3:8.2f}"


def step_timeline(events, out=sys.stdout):
    steps = [e for e in events if e.get("event") == "train/step"]
    print(f"\n== training timeline ({len(steps)} steps) ==", file=out)
    if not steps:
        return
    print("  step  epoch   total_ms    wait_ms   dispatch_ms  "
          "  ckpt_ms", file=out)
    for e in steps:
        print(f"  {e.get('step', '?'):>4}  {e.get('epoch', '?'):>5}"
              f"  {_fmt_ms(e.get('dur_s')):>9}"
              f"  {_fmt_ms(e.get('data_wait_s')):>9}"
              f"  {_fmt_ms(e.get('dispatch_s')):>11}"
              f"  {_fmt_ms(e.get('checkpoint_s')):>9}", file=out)


def slowest_requests(events, top: int, out=sys.stdout):
    reqs = [e for e in events if e.get("event") == "serving/request"
            and e.get("dur_s") is not None]
    reqs.sort(key=lambda e: float(e["dur_s"]), reverse=True)
    by_trace: "Dict[str, List[Dict[str, Any]]]" = {}
    for e in events:
        tid = e.get("trace_id")
        if tid and e.get("event") != "serving/request":
            by_trace.setdefault(tid, []).append(e)
    print(f"\n== slowest serving requests (top {top} of"
          f" {len(reqs)}) ==", file=out)
    for e in reqs[:top]:
        tid = e.get("trace_id")
        print(f"  {_fmt_ms(e['dur_s'])} ms  status={e.get('status')}"
              f"  trace={tid}", file=out)
        for c in sorted(by_trace.get(tid, []),
                        key=lambda c: c.get("t_start", c.get("ts", 0))):
            extra = "".join(
                f" {k}={c[k]}" for k in ("rows", "bucket", "fill")
                if c.get(k) is not None)
            print(f"      {_fmt_ms(c.get('dur_s'))} ms "
                  f" {c.get('event')}{extra}", file=out)


def anomaly_digest(events, out=sys.stdout) -> "Dict[str, int]":
    """Print the per-kind anomaly counts; returns them so
    ``--check`` can gate on a non-empty digest."""
    anomalies = [e for e in events
                 if e.get("event") == "diagnostics/anomaly"]
    print(f"\n== anomalies ({len(anomalies)}) ==", file=out)
    kinds: "Dict[str, int]" = {}
    for e in anomalies:
        kinds[e.get("kind", "?")] = kinds.get(e.get("kind", "?"), 0) + 1
    for kind, n in sorted(kinds.items()):
        print(f"  {kind}: {n}", file=out)
    return kinds


def filter_events(events, last=None, since=None):
    """``--last N`` / ``--since TS`` filters: newest-N (by file
    order — the writer appends chronologically) and/or at-or-after
    an epoch-seconds timestamp (events without a ``ts`` are kept)."""
    if since is not None:
        events = [e for e in events
                  if e.get("ts") is None
                  or float(e["ts"]) >= float(since)]
    if last is not None and last >= 0:
        events = events[-last:] if last else []
    return events


def history_report(doc, out=sys.stdout):
    """Summarize an exported metric-history document
    (``MetricHistory.export()`` shape): store stats plus one line
    per family — type, series count, point count, last value of the
    first series."""
    stats = doc.get("stats") or {}
    fams = doc.get("families") or {}
    print(f"\n== metric history ({len(fams)} families, "
          f"{stats.get('raw_samples', '?')} raw samples, "
          f"{stats.get('resident_bytes', '?')} resident bytes) ==",
          file=out)
    for name in sorted(fams):
        ser = fams[name] or {}
        series = ser.get("series") or []
        n_pts = sum(len(s.get("points") or []) for s in series)
        last = None
        for s in series:
            for p in reversed(s.get("points") or []):
                for k in ("value", "q99", "count"):
                    if p.get(k) is not None:
                        last = f"{k}={p[k]}"
                        break
                if last:
                    break
            break
        print(f"  {name} [{ser.get('type', '?')}] "
              f"{len(series)} series / {n_pts} pts"
              f"{'  last ' + last if last else ''}", file=out)


def export_chrome(events, path: str):
    """Write the traced subset of the event log as chrome-trace JSON
    (the same schema :func:`tracing.to_chrome_trace` emits live)."""
    doc = {"traceEvents": tracing.chrome_events(events),
           "displayTimeUnit": "ms"}
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    print(f"\nchrome trace -> {path} "
          f"({len(doc['traceEvents'])} events); open in "
          "https://ui.perfetto.dev")


def fetch_fleet_traces(base: str, n: int = 50) -> list:
    """Pull stitched traces from a running fleet router
    (``GET /debug/traces?fleet=1`` — docs/observability.md)."""
    import urllib.request
    url = f"{base.rstrip('/')}/debug/traces?fleet=1&n={n}"
    with urllib.request.urlopen(url, timeout=30) as r:
        doc = json.loads(r.read())
    if not doc.get("fleet"):
        raise SystemExit(
            f"{base} answered /debug/traces without fleet data — "
            f"is it a fleet router with federation enabled?")
    return doc.get("traces") or []


def fleet_report(traces, top: int, out=sys.stdout):
    """Slowest stitched cross-process requests, per-source span
    breakdown under each."""
    traces = sorted(traces, key=lambda t: t.get("dur_s") or 0.0,
                    reverse=True)
    n_spans = sum(t.get("n_spans", 0) for t in traces)
    print(f"\n== stitched fleet traces (top {top} of {len(traces)}; "
          f"{n_spans} spans) ==", file=out)
    for t in traces[:top]:
        srcs = ",".join(t.get("sources") or [])
        print(f"  {_fmt_ms(t.get('dur_s'))} ms  "
              f"trace={t.get('trace_id')}  sources=[{srcs}]",
              file=out)
        for s in t.get("spans") or []:
            print(f"      {_fmt_ms(s.get('dur_s'))} ms  "
                  f"[{s.get('source', 'router')}] {s.get('name')}",
                  file=out)


def export_fleet_chrome(traces, path: str):
    """Chrome-trace JSON with one process lane per source (router
    and each replica get distinct pids)."""
    recs = [s for t in traces for s in (t.get("spans") or [])]
    doc = {"traceEvents": tracing.chrome_events(
               recs, source_lanes=True),
           "displayTimeUnit": "ms"}
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    print(f"\nchrome trace -> {path} "
          f"({len(doc['traceEvents'])} events); open in "
          "https://ui.perfetto.dev")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--events",
                    default=os.environ.get("ZOO_TPU_EVENT_LOG"),
                    help="event-log JSONL path (default: "
                         "$ZOO_TPU_EVENT_LOG)")
    ap.add_argument("--top", type=int, default=10,
                    help="how many slow requests to show")
    ap.add_argument("--chrome", metavar="OUT",
                    help="also export chrome-trace JSON to OUT")
    ap.add_argument("--fleet", metavar="URL",
                    help="pull stitched traces from a running fleet "
                         "router instead of reading an event log")
    ap.add_argument("--last", type=int, metavar="N",
                    help="only the newest N events")
    ap.add_argument("--since", type=float, metavar="TS",
                    help="only events at/after this epoch-seconds "
                         "timestamp")
    ap.add_argument("--check", action="store_true",
                    help="exit 2 when the (filtered) anomaly digest "
                         "is non-empty — a CI gate")
    ap.add_argument("--history", metavar="FILE",
                    help="also summarize an exported metric-history "
                         "JSON document")
    args = ap.parse_args(argv)
    if args.fleet:
        traces = fetch_fleet_traces(args.fleet,
                                    n=max(args.top, 50))
        print(f"{len(traces)} stitched traces from {args.fleet}")
        fleet_report(traces, args.top)
        if args.chrome:
            export_fleet_chrome(traces, args.chrome)
        return 0
    if not args.events:
        ap.error("--events required (or set ZOO_TPU_EVENT_LOG)")
    if not os.path.exists(args.events):
        print(f"no event log at {args.events}", file=sys.stderr)
        return 1
    events = load_events(args.events)
    n_all = len(events)
    events = filter_events(events, last=args.last,
                           since=args.since)
    suffix = (f" ({n_all} before filters)"
              if len(events) != n_all else "")
    print(f"{len(events)} events from {args.events}{suffix}")
    step_timeline(events)
    slowest_requests(events, args.top)
    kinds = anomaly_digest(events)
    if args.chrome:
        export_chrome(events, args.chrome)
    if args.history:
        with open(args.history, "r", encoding="utf-8") as fh:
            history_report(json.load(fh))
    if args.check and kinds:
        total = sum(kinds.values())
        print(f"\nCHECK FAILED: {total} anomalies "
              f"({', '.join(sorted(kinds))})", file=sys.stderr)
        return 2
    if args.check:
        print("\ncheck passed: no anomalies")
    return 0


if __name__ == "__main__":
    sys.exit(main())
