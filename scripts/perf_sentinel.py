#!/usr/bin/env python
"""Perf-regression sentinel over the bench artifact history.

Nobody notices a perf trajectory by rereading JSON — this tool makes
the comparison mechanical. It loads every ``BENCH_r<NN>.json``
wrapper in ``--dir`` (a driver's ``{n, cmd, rc, tail, parsed}``
capture — the artifact line is recovered from ``tail``; the repo
itself ships none), plus ``BENCH_serving.json`` and
``BASELINE.json``, normalizes every number into per-metric series,
and judges the NEWEST numbered round against the best comparable
prior value of each series.

Lineage discipline (the whole point): chip measurements and host-CPU
fallback measurements are SEPARATE series. An artifact is fallback
when it carries ``cpu_fallback_value``/``fallback`` (or a fallback
diag); ``*_CPU_FALLBACK`` metric names are normalized into the cpu
lineage under their base name. A host-CPU number is never compared
against a chip headline. Fleet artifacts
(``BENCH_serving_fleet.json`` / any record carrying a ``"fleet"``
block — `bench_serving.py --replicas N`) get a ``-fleet`` lineage
suffix for the same reason: N replicas time-slicing a host is a
different series from one single-process server, and neither may
judge the other. Generation artifacts (``BENCH_generate.json`` / any
record carrying a ``"generate"`` block — `bench_generate.py`) get a
``-generate`` suffix likewise: decode tokens/s is not predict-path
rows/s and the two must never be compared. Autotuned runs (any record
whose ``"autotune"`` provenance block says ``enabled: true`` —
``ZOO_TPU_AUTOTUNE>=1``, docs/autotune.md) additionally get a
``-tuned`` suffix on top of the workload split, so a tuned number is
never judged against a heuristic-config baseline or vice versa.

Direction is inferred from the metric name (err/p99/latency/_ms/
seconds → lower is better; everything else → higher is better).
A regression is a drop past ``--tolerance`` (default 10%) below the
best prior comparable value (or, lower-better, a rise past the
tolerance above it, with a small absolute floor so a 1e-9 conformance
wiggle over a 0.0 best does not page).

``--history FILE`` additionally digests an exported metric-history
document (``MetricHistory.export()`` /
``GET /debug/metrics/history`` — docs/observability.md §History)
into live serving vitals (QPS, worst p99, queue depth, forecast
ETAs) printed next to the trajectory table, so a bench round's
artifact numbers can be eyeballed against what the serving plane
actually saw over the same window.

Exit codes: 1 when the newest round regressed (0 with
``--advisory``), 2 when no artifacts could be loaded, else 0.
``make perf-sentinel`` runs it enforcing; ``make test`` runs it
advisory so every run prints the trajectory table.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")
_FB_SUFFIX = "_CPU_FALLBACK"
_LOWER_RE = re.compile(
    r"(err|error|p99|latency|_ms$|_ms_|seconds)", re.I)


def direction(metric: str) -> str:
    """'lower' when smaller values are better, else 'higher'."""
    return "lower" if _LOWER_RE.search(metric) else "higher"


def _json_lines(text: str) -> "List[dict]":
    out = []
    for line in (text or "").splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass  # truncated mid-line by a kill
    return out


def load_artifact(path: str) -> Optional[dict]:
    """The most complete merged artifact record in ``path``: either
    the file IS the artifact (BENCH_serving.json), or it is a driver
    wrapper whose ``tail`` holds the bench's incremental JSON lines
    (the last line is the most complete; ``parsed`` is the
    fallback)."""
    try:
        with open(path, encoding="utf-8") as fh:
            d = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(d, dict):
        return None
    if "tail" in d or "parsed" in d:
        recs = _json_lines(d.get("tail", ""))
        if recs:
            return recs[-1]
        parsed = d.get("parsed")
        return parsed if isinstance(parsed, dict) else None
    return d


def is_fallback_artifact(rec: dict) -> bool:
    """Chip-unreachable rounds: the cpu_fallback_value/fallback keys
    (or a fallback diag) mark every number in the record as host-CPU
    lineage."""
    if rec.get("cpu_fallback_value") is not None:
        return True
    if rec.get("fallback"):
        return True
    return "fallback" in (rec.get("diag") or "").lower()


def is_fleet_artifact(rec: dict) -> bool:
    """Replicated-fleet runs (`bench_serving.py --replicas N`) carry
    a ``"fleet"`` block; their numbers form their own lineage."""
    return isinstance(rec.get("fleet"), dict)


def is_generate_artifact(rec: dict) -> bool:
    """Decode-path runs (`bench_generate.py`) carry a ``"generate"``
    block; generation tokens/s is its own lineage, never compared
    against predict-path throughput."""
    return isinstance(rec.get("generate"), dict)


def is_disagg_artifact(rec: dict) -> bool:
    """Disaggregated-serving runs (`bench_generate.py --disagg`)
    carry a ``"disagg"`` block; prefill/decode-pool numbers (handoff
    latency in the path, pool-bound capacity) are their own lineage,
    never compared against monolithic decode throughput."""
    return isinstance(rec.get("disagg"), dict)


def is_tuned_artifact(rec: dict) -> bool:
    """Runs under ``ZOO_TPU_AUTOTUNE>=1`` carry an ``"autotune"``
    provenance block with ``enabled: true`` (bench_common.
    attach_metrics_snapshot); their numbers get a ``-tuned`` lineage
    so a tuned run never masquerades as a heuristic-config win
    (docs/autotune.md)."""
    at = rec.get("autotune")
    return isinstance(at, dict) and bool(at.get("enabled"))


def extract_series(rec: dict) -> "Dict[Tuple[str, str], float]":
    """``{(lineage, metric): value}`` for one artifact.
    ``lineage`` is ``"chip"`` or ``"cpu"`` — comparisons only ever
    happen within one lineage."""
    out: "Dict[Tuple[str, str], float]" = {}
    if not isinstance(rec, dict):
        return out
    fb = is_fallback_artifact(rec)
    # mutually exclusive in practice (a record is a disagg run OR a
    # fleet run OR a generation run); disagg wins over the plain
    # generate lineage its records also qualify for
    if is_disagg_artifact(rec):
        sfx = "-disagg"
    elif is_fleet_artifact(rec):
        sfx = "-fleet"
    elif is_generate_artifact(rec):
        sfx = "-generate"
    else:
        sfx = ""
    # autotuned runs split into their own lineages on top of the
    # workload split: tuned-vs-heuristic configs are never comparable
    if is_tuned_artifact(rec):
        sfx += "-tuned"
    art_lin = ("cpu" if fb else "chip") + sfx
    cpu_lin = "cpu" + sfx
    headline = rec.get("metric") or "headline"
    value = rec.get("value")
    # a 0.0 headline is this schema's "nothing measured" sentinel
    if isinstance(value, (int, float)) and value > 0:
        out[(art_lin, headline)] = float(value)
    cfv = rec.get("cpu_fallback_value")
    if isinstance(cfv, (int, float)) and cfv > 0:
        out[(cpu_lin, headline)] = float(cfv)
    for m in rec.get("extra_metrics") or []:
        if not isinstance(m, dict):
            continue
        name = m.get("metric")
        v = m.get("value")
        if isinstance(name, str) and isinstance(v, (int, float)):
            if name.endswith(_FB_SUFFIX):
                out[(cpu_lin, name[:-len(_FB_SUFFIX)])] = float(v)
            else:
                out[(art_lin, name)] = float(v)
        elif "mode" in m and isinstance(
                m.get("rows_per_sec"), (int, float)):
            out[(art_lin, f"rows_per_sec[{m['mode']}]")] = float(
                m["rows_per_sec"])
    return out


def load_rounds(dirpath: str):
    """Numbered rounds (sorted) + optional serving artifact + the
    BASELINE descriptor. Returns ``(rounds, serving, baseline)``
    where rounds is ``[(n, label, series_dict), ...]``."""
    rounds = []
    for fn in sorted(os.listdir(dirpath)):
        m = ROUND_RE.match(fn)
        if not m:
            continue
        rec = load_artifact(os.path.join(dirpath, fn))
        series = extract_series(rec) if rec else {}
        rounds.append((int(m.group(1)), f"r{int(m.group(1)):02d}",
                       series))
    rounds.sort()
    # named (non-round) artifacts, each its own trajectory column;
    # the fleet artifact's series land in the *-fleet lineages
    named = []
    for label, fn in (("serving", "BENCH_serving.json"),
                      ("fleet", "BENCH_serving_fleet.json"),
                      ("generate", "BENCH_generate.json")):
        p = os.path.join(dirpath, fn)
        if os.path.exists(p):
            rec = load_artifact(p)
            if rec:
                named.append((label, extract_series(rec)))
    baseline = None
    bp = os.path.join(dirpath, "BASELINE.json")
    if os.path.exists(bp):
        baseline = load_artifact(bp)
    return rounds, named, baseline


def judge_latest(rounds, tolerance: float,
                 floor: float = 1e-3) -> "List[dict]":
    """Regressions of the newest numbered round vs the best
    comparable (same lineage+metric) value from any prior round."""
    if len(rounds) < 2:
        return []
    latest_n, latest_label, latest = rounds[-1]
    regressions = []
    for key, value in sorted(latest.items()):
        prior = [series[key] for _, _, series in rounds[:-1]
                 if key in series]
        if not prior:
            continue  # nothing comparable — never cross lineages
        lineage, metric = key
        if direction(metric) == "higher":
            best = max(prior)
            bad = value < best * (1.0 - tolerance)
        else:
            best = min(prior)
            bad = value > max(best * (1.0 + tolerance),
                              best + floor)
        if bad:
            regressions.append({
                "round": latest_label, "lineage": lineage,
                "metric": metric, "value": value, "best": best,
                "direction": direction(metric)})
    return regressions


def _fmt(v: Optional[float]) -> str:
    if v is None:
        return "—"
    if v == 0:
        return "0"
    if abs(v) >= 1000:
        return f"{v:,.0f}"
    if abs(v) >= 1:
        return f"{v:.2f}"
    return f"{v:.4g}"


def trajectory_table(rounds, named=None) -> str:
    """Per-series trajectory across rounds (named artifacts —
    serving, fleet — as their own columns), one block per lineage:
    chip, cpu, then the fleet lineages."""
    cols = [label for _, label, _ in rounds]
    series_by_round = {label: s for _, label, s in rounds}
    for label, series in (named or []):
        cols.append(label)
        series_by_round[label] = series
    keys = sorted({k for s in series_by_round.values() for k in s})
    lines = []
    lin_w = max([len(lin) for lin, _ in keys] + [8]) + 2
    width = max([len(m) for _, m in keys] + [24]) + 2
    header = ("lineage".ljust(lin_w) + "metric".ljust(width)
              + "".join(c.rjust(12) for c in cols))
    lines.append(header)
    lines.append("-" * len(header))
    base = ("chip", "cpu")
    lineages = list(base) + sorted(
        {lin for lin, _ in keys} - set(base))
    for lineage in lineages:
        for key in keys:
            if key[0] != lineage:
                continue
            row = (lineage.ljust(lin_w) + key[1].ljust(width)
                   + "".join(
                       _fmt(series_by_round[c].get(key)).rjust(12)
                       for c in cols))
            lines.append(row)
    return "\n".join(lines)


def _history_points(doc: dict, family: str) -> "List[dict]":
    ser = (doc.get("families") or {}).get(family) or {}
    out = []
    for s in ser.get("series") or []:
        out.extend(s.get("points") or [])
    return out


def history_vitals(doc: dict) -> "List[str]":
    """Live serving vitals out of an exported metric-history
    document: mean QPS, worst windowed p99, last queue depth, and
    any finite forecast ETAs."""
    lines = []
    rates = [p["rate"] for p in _history_points(
        doc, "zoo_tpu_serving_requests_total")
        if isinstance(p.get("rate"), (int, float))]
    if rates:
        lines.append(f"  qps(mean/max): {_fmt(sum(rates) / len(rates))}"
                     f" / {_fmt(max(rates))}")
    q99s = [p["q99"] for p in _history_points(
        doc, "zoo_tpu_serving_request_seconds")
        if isinstance(p.get("q99"), (int, float))]
    if q99s:
        lines.append(f"  p99_s(worst): {_fmt(max(q99s))}")
    depths = [p["value"] for p in _history_points(
        doc, "zoo_tpu_serving_queue_depth")
        if isinstance(p.get("value"), (int, float))]
    if depths:
        lines.append(f"  queue_depth(last/max): {_fmt(depths[-1])}"
                     f" / {_fmt(max(depths))}")
    etas = (doc.get("families") or {}).get(
        "zoo_tpu_forecast_eta_s") or {}
    for s in etas.get("series") or []:
        pts = [p["value"] for p in s.get("points") or []
               if isinstance(p.get("value"), (int, float))]
        if not pts:
            continue
        res = (s.get("labels") or {}).get("resource", "?")
        last = pts[-1]
        shown = "none" if last >= 1e8 else _fmt(last) + "s"
        lines.append(f"  forecast_eta[{res}]: {shown}")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding BENCH_*.json / BASELINE.json")
    ap.add_argument("--tolerance", type=float, default=float(
        os.environ.get("ZOO_TPU_SENTINEL_TOLERANCE", "0.10")),
        help="relative regression tolerance (default 0.10)")
    ap.add_argument("--floor", type=float, default=1e-3,
                    help="absolute slack for lower-is-better metrics "
                         "whose best prior is ~0")
    ap.add_argument("--advisory", action="store_true",
                    help="print the verdict but always exit 0")
    ap.add_argument("--history", metavar="FILE",
                    help="exported metric-history JSON to digest "
                         "into live serving vitals")
    args = ap.parse_args(argv)

    if args.history:
        try:
            with open(args.history, encoding="utf-8") as fh:
                hdoc = json.load(fh)
            lines = history_vitals(hdoc)
            print(f"# live history vitals ({args.history})")
            print("\n".join(lines) if lines
                  else "  (no serving series in the export)")
        except (OSError, ValueError) as e:
            print(f"perf-sentinel: bad --history file: {e}",
                  file=sys.stderr)

    rounds, named, baseline = load_rounds(args.dir)
    if not rounds and not named:
        print("perf-sentinel: no BENCH artifacts found in "
              f"{args.dir}", file=sys.stderr)
        return 0 if args.advisory else 2

    print("# perf trajectory "
          f"({len(rounds)} rounds, tolerance {args.tolerance:.0%})")
    if baseline and baseline.get("metric"):
        print(f"# baseline: {baseline['metric']}")
    print(trajectory_table(rounds, named))

    regressions = judge_latest(rounds, args.tolerance, args.floor)
    if regressions:
        print()
        for r in regressions:
            worse = ("below" if r["direction"] == "higher"
                     else "above")
            print(f"REGRESSION [{r['lineage']}] {r['metric']}: "
                  f"{_fmt(r['value'])} is >{args.tolerance:.0%} "
                  f"{worse} best prior {_fmt(r['best'])} "
                  f"({r['round']})")
        print(f"\nperf-sentinel: {len(regressions)} regression(s) "
              f"in {rounds[-1][1]}"
              + (" [advisory]" if args.advisory else ""))
        return 0 if args.advisory else 1
    latest = rounds[-1][1] if rounds else "serving"
    print(f"\nperf-sentinel: OK — no comparable series in {latest} "
          f"regressed past {args.tolerance:.0%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
