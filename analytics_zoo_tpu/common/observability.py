"""Unified telemetry core (the observability layer, L1).

Reference: BigDL's `TrainSummary`/`ValidationSummary` scalars plus
Spark's executor metrics were the reference platform's entire
operational signal (SURVEY §5, `Topology.scala:197-284`). This module
is the TPU-native, serving-aware replacement: one process-global,
thread-safe registry that every layer (training, ingest, serving)
writes into and that two exposition formats read out of.

Three primitives:

- **metrics registry** — named counters, gauges and fixed-bucket
  histograms with label support (`counter()`, `gauge()`,
  `histogram()`); process-global by default, instantiable
  (:class:`MetricsRegistry`) for tests;
- **spans** — ``with span("train/step", step=i): ...`` times a block
  into a wall-time histogram (``zoo_tpu_train_step_seconds``) and,
  when ``ZOO_TPU_EVENT_LOG`` names a file, appends a structured JSONL
  event (the extra keyword fields go to the event log only, never to
  metric labels — unbounded values like step numbers must not explode
  label cardinality);
- **exposition** — :func:`snapshot` (JSON-able dict) and
  :func:`to_prometheus` (Prometheus text format, served by the
  inference servers' ``GET /metrics``).

Zero dependencies beyond the stdlib on purpose: the ingest path runs
inside pickled closures on Spark executors and the serving path inside
the native front-end's worker threads; neither may drag in jax.

Naming convention (see docs/observability.md): every metric is
``zoo_tpu_<area>_<what>[_<unit>]`` with areas ``train``, ``ingest``,
``serving``; counters end in ``_total``, durations in ``_seconds``.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import re
import shutil
import threading
import time
from typing import Any, Dict, Optional, Sequence, Tuple

from analytics_zoo_tpu.common import tracing as _tracing

__all__ = [
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "Span",
    "counter",
    "gauge",
    "histogram",
    "span",
    "event",
    "snapshot",
    "to_prometheus",
    "get_registry",
    "reset_metrics",
    "bucket_quantile",
    "DEFAULT_BUCKETS",
    "SIZE_BUCKETS",
]

# Prometheus-style latency buckets, widened for both sub-ms dispatch
# and minute-scale epochs/compiles.
DEFAULT_BUCKETS: "Tuple[float, ...]" = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)

# Power-of-two buckets for batch sizes / record counts.
SIZE_BUCKETS: "Tuple[float, ...]" = (
    1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096, 16384)

_NAME_SUB = re.compile(r"[^a-zA-Z0-9_:]")


def _sanitize(name: str) -> str:
    """Coerce to a legal Prometheus metric name."""
    name = _NAME_SUB.sub("_", name)
    if not name or name[0].isdigit():
        name = "_" + name
    return name


def _fmt(v: float) -> str:
    """Prometheus sample value: integral floats print as ints."""
    f = float(v)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


def _escape_label(v: Any) -> str:
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _label_key(labels: Optional[Dict[str, Any]]
               ) -> "Tuple[Tuple[str, str], ...]":
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _label_str(key: "Tuple[Tuple[str, str], ...]") -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{_escape_label(v)}"' for k, v in key)
    return "{" + inner + "}"


def bucket_quantile(buckets: "Sequence[float]",
                    counts: "Sequence[float]", q: float) -> float:
    """Prometheus-style quantile estimate from per-bucket counts.

    ``buckets`` are the finite upper bounds (sorted ascending);
    ``counts`` are *per-bucket* (not cumulative) observation counts,
    with one extra trailing entry for the ``+Inf`` overflow bucket
    (``len(counts) == len(buckets) + 1``). Linear interpolation
    inside the winning bucket, a lower edge of 0 for the first
    bucket, and — like Prometheus ``histogram_quantile`` — the
    highest finite bound when the rank lands in the overflow bucket.
    Returns NaN when there are no observations.
    """
    if len(counts) != len(buckets) + 1:
        raise ValueError("counts must be per-bucket plus overflow")
    total = float(sum(counts))
    if total <= 0:
        return float("nan")
    q = min(max(float(q), 0.0), 1.0)
    rank = q * total
    acc = 0.0
    for i, hi in enumerate(buckets):
        prev = acc
        acc += counts[i]
        if acc >= rank:
            if counts[i] <= 0:
                return float(hi)
            lo = float(buckets[i - 1]) if i > 0 else 0.0
            frac = (rank - prev) / counts[i]
            return lo + (float(hi) - lo) * min(max(frac, 0.0), 1.0)
    return float(buckets[-1])  # rank fell in the +Inf bucket


class Counter:
    """Monotonic counter (one labeled child of a family)."""

    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0):
        if amount < 0:
            raise ValueError("counters only go up; use a gauge")
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    """Point-in-time value."""

    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, value: float):
        with self._lock:
            self._value = float(value)

    def inc(self, amount: float = 1.0):
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1.0):
        self.inc(-amount)

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Fixed-bucket histogram (cumulative on exposition, like
    Prometheus: ``le`` is inclusive)."""

    __slots__ = ("buckets", "_counts", "_sum", "_lock")

    def __init__(self, buckets: "Sequence[float]" = DEFAULT_BUCKETS):
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError("histogram needs at least one bucket")
        self._counts = [0] * (len(self.buckets) + 1)  # last = +Inf
        self._sum = 0.0
        self._lock = threading.Lock()

    def observe(self, value: float):
        v = float(value)
        i = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v

    @property
    def count(self) -> int:
        with self._lock:
            return sum(self._counts)

    @property
    def sum(self) -> float:
        return self._sum

    def cumulative(self) -> "list[tuple[str, int]]":
        """[(le_str, cumulative_count), ..., ("+Inf", total)]."""
        with self._lock:
            counts = list(self._counts)
        out, acc = [], 0
        for b, c in zip(self.buckets, counts):
            acc += c
            out.append((_fmt(b), acc))
        out.append(("+Inf", acc + counts[-1]))
        return out

    def quantile(self, q: float) -> float:
        """Estimate the q-quantile (0 ≤ q ≤ 1) from the bucket
        counts via :func:`bucket_quantile`. Accuracy is bounded by
        the bucket width around the true quantile; NaN when empty."""
        with self._lock:
            counts = list(self._counts)
        return bucket_quantile(self.buckets, counts, q)


class _Family:
    """One metric name: type, help, and labeled children."""

    __slots__ = ("name", "mtype", "help", "buckets", "children",
                 "_lock")

    def __init__(self, name: str, mtype: str, help_: str,
                 buckets: "Optional[Sequence[float]]" = None):
        self.name = name
        self.mtype = mtype
        self.help = help_
        self.buckets = buckets
        self.children: "Dict[tuple, Any]" = {}
        self._lock = threading.Lock()

    def child(self, labels: Optional[Dict[str, Any]]):
        key = _label_key(labels)
        with self._lock:
            m = self.children.get(key)
            if m is None:
                if self.mtype == "counter":
                    m = Counter()
                elif self.mtype == "gauge":
                    m = Gauge()
                else:
                    m = Histogram(self.buckets or DEFAULT_BUCKETS)
                self.children[key] = m
            return m


class MetricsRegistry:
    """Thread-safe registry of metric families. The module-level
    helpers use one process-global instance (:func:`get_registry`);
    tests may instantiate their own."""

    def __init__(self):
        self._lock = threading.Lock()
        self._families: "Dict[str, _Family]" = {}

    def _family(self, name: str, mtype: str, help_: str,
                buckets: "Optional[Sequence[float]]" = None) -> _Family:
        name = _sanitize(name)
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = _Family(name, mtype, help_, buckets)
                self._families[name] = fam
            elif fam.mtype != mtype:
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{fam.mtype}, not {mtype}")
            return fam

    def counter(self, name: str, help: str = "",
                labels: Optional[Dict[str, Any]] = None) -> Counter:
        return self._family(name, "counter", help).child(labels)

    def gauge(self, name: str, help: str = "",
              labels: Optional[Dict[str, Any]] = None) -> Gauge:
        return self._family(name, "gauge", help).child(labels)

    def histogram(self, name: str, help: str = "",
                  labels: Optional[Dict[str, Any]] = None,
                  buckets: "Optional[Sequence[float]]" = None
                  ) -> Histogram:
        return self._family(name, "histogram", help,
                            buckets).child(labels)

    # -- exposition ---------------------------------------------------------
    def snapshot(self) -> dict:
        """JSON-able dump of every family (histograms include
        cumulative bucket counts, like the text format)."""
        out: "Dict[str, dict]" = {}
        with self._lock:
            fams = sorted(self._families.values(),
                          key=lambda f: f.name)
        for fam in fams:
            with fam._lock:
                items = sorted(fam.children.items())
            values = []
            for key, m in items:
                rec: "Dict[str, Any]" = {"labels": dict(key)}
                if fam.mtype == "histogram":
                    rec["count"] = m.count
                    rec["sum"] = m.sum
                    rec["buckets"] = dict(m.cumulative())
                else:
                    rec["value"] = m.value
                values.append(rec)
            out[fam.name] = {"type": fam.mtype, "help": fam.help,
                             "values": values}
        return out

    def to_prometheus(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        lines: "list[str]" = []
        with self._lock:
            fams = sorted(self._families.values(),
                          key=lambda f: f.name)
        for fam in fams:
            if fam.help:
                lines.append(f"# HELP {fam.name} {fam.help}")
            lines.append(f"# TYPE {fam.name} {fam.mtype}")
            with fam._lock:
                items = sorted(fam.children.items())
            for key, m in items:
                ls = _label_str(key)
                if fam.mtype == "histogram":
                    for le, cum in m.cumulative():
                        bl = _label_str(key + (("le", le),))
                        lines.append(
                            f"{fam.name}_bucket{bl} {cum}")
                    lines.append(
                        f"{fam.name}_sum{ls} {_fmt(m.sum)}")
                    lines.append(
                        f"{fam.name}_count{ls} {m.count}")
                else:
                    lines.append(f"{fam.name}{ls} {_fmt(m.value)}")
        return "\n".join(lines) + ("\n" if lines else "")

    def reset(self):
        with self._lock:
            self._families.clear()


# ---------------------------------------------------------------------------
# Process-global default registry + module-level convenience API
# ---------------------------------------------------------------------------

_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _REGISTRY


def counter(name: str, help: str = "",
            labels: Optional[Dict[str, Any]] = None) -> Counter:
    return _REGISTRY.counter(name, help, labels)


def gauge(name: str, help: str = "",
          labels: Optional[Dict[str, Any]] = None) -> Gauge:
    return _REGISTRY.gauge(name, help, labels)


def histogram(name: str, help: str = "",
              labels: Optional[Dict[str, Any]] = None,
              buckets: "Optional[Sequence[float]]" = None) -> Histogram:
    return _REGISTRY.histogram(name, help, labels, buckets)


def snapshot() -> dict:
    return _REGISTRY.snapshot()


def to_prometheus() -> str:
    return _REGISTRY.to_prometheus()


# ---------------------------------------------------------------------------
# Structured event log (JSONL sink, env-selected)
# ---------------------------------------------------------------------------

_event_lock = threading.Lock()
_event_path: Optional[str] = None
_event_fh = None
_rotated_bytes = 0  # total size of rotated segments (metrics feed)


def _event_log_keep() -> int:
    try:
        return int(os.environ.get("ZOO_TPU_EVENT_LOG_KEEP", "3"))
    except ValueError:
        return 3


def _gzip_segment(path: str):
    """Compress a freshly-rotated segment in place (``path`` →
    ``path.gz``). Best-effort: on failure the uncompressed segment
    is kept and the partial ``.gz`` removed."""
    try:
        with open(path, "rb") as src, \
                gzip.open(path + ".gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        os.remove(path)
    except OSError:
        try:
            os.remove(path + ".gz")
        except OSError:
            pass


def _scan_rotated_bytes() -> int:
    """On-disk size of the rotated segments (``.N.gz`` and legacy
    uncompressed ``.N``) still inside the keep window."""
    if not _event_path:
        return 0
    total = 0
    for i in range(1, _event_log_keep() + 1):
        for ext in (".gz", ""):
            try:
                total += os.path.getsize(
                    f"{_event_path}.{i}{ext}")
            except OSError:
                pass
    return total


def _rotate_locked():
    """Size-based rotation: when ``ZOO_TPU_EVENT_LOG_MAX_MB`` is set
    and the sink grew past it, shift ``path.1 → path.2 → ...``
    (keeping ``ZOO_TPU_EVENT_LOG_KEEP`` rotated files, default 3),
    gzip-compress the fresh ``path.1`` (``ZOO_TPU_EVENT_LOG_GZIP=0``
    keeps it raw) and reopen a fresh ``path``. Each rotation bumps
    ``zoo_tpu_event_log_rotations_total``. Called with
    ``_event_lock`` held."""
    global _event_fh, _rotated_bytes
    raw = os.environ.get("ZOO_TPU_EVENT_LOG_MAX_MB")
    if not raw or _event_fh is None:
        return
    try:
        max_bytes = float(raw) * 1024 * 1024
    except ValueError:
        return
    if max_bytes <= 0:
        return
    try:
        if _event_fh.tell() < max_bytes:
            return
        _event_fh.close()
    except (OSError, ValueError):
        return
    keep = _event_log_keep()
    rotated = False
    try:
        for i in range(max(keep - 1, 0), 0, -1):
            for ext in (".gz", ""):
                src = f"{_event_path}.{i}{ext}"
                if os.path.exists(src):
                    os.replace(src, f"{_event_path}.{i + 1}{ext}")
        if keep >= 1:
            os.replace(_event_path, _event_path + ".1")
            rotated = True
            if os.environ.get("ZOO_TPU_EVENT_LOG_GZIP",
                              "1") != "0":
                _gzip_segment(_event_path + ".1")
        else:
            os.remove(_event_path)
            rotated = True
    except OSError:
        pass  # rotation is best-effort; keep logging regardless
    _event_fh = open(_event_path, "a", encoding="utf-8")
    _rotated_bytes = _scan_rotated_bytes()
    if rotated:
        counter("zoo_tpu_event_log_rotations_total",
                help="event-log segment rotations").inc()


def _event_sink():
    """Cached append handle for ``ZOO_TPU_EVENT_LOG`` (re-resolved
    per call so tests can repoint the env var)."""
    global _event_path, _event_fh, _rotated_bytes
    path = os.environ.get("ZOO_TPU_EVENT_LOG")
    if not path:
        return None
    if path != _event_path:
        if _event_fh is not None:
            try:
                _event_fh.close()
            except OSError:
                pass
        _event_fh = open(path, "a", encoding="utf-8")
        _event_path = path
        _rotated_bytes = _scan_rotated_bytes()
    _rotate_locked()
    return _event_fh


def event(name: str, **fields):
    """Append one structured JSONL event to the ``ZOO_TPU_EVENT_LOG``
    sink (no-op when the env var is unset: it returns before the
    process-wide lock, so spans on several threads never contend for
    a sink nobody asked for). Non-JSON-able field values are
    stringified rather than dropped."""
    if not os.environ.get("ZOO_TPU_EVENT_LOG"):
        return
    with _event_lock:
        fh = _event_sink()
        if fh is None:
            return
        rec = {"ts": round(time.time(), 6), "event": name}
        rec.update(fields)
        try:
            line = json.dumps(rec)
        except (TypeError, ValueError):
            rec = {k: (v if isinstance(
                v, (int, float, str, bool, type(None))) else str(v))
                for k, v in rec.items()}
            line = json.dumps(rec)
        fh.write(line + "\n")
        fh.flush()
        try:
            # live + rotated footprint: the disk feed the capacity
            # forecaster extrapolates (docs/observability.md)
            gauge("zoo_tpu_event_log_bytes",
                  help="event-log bytes on disk (live segment + "
                       "rotated)").set(fh.tell() + _rotated_bytes)
        except (OSError, ValueError):
            pass


def _close_event_log():
    global _event_path, _event_fh, _rotated_bytes
    with _event_lock:
        if _event_fh is not None:
            try:
                _event_fh.close()
            except OSError:
                pass
        _event_fh = None
        _event_path = None
        _rotated_bytes = 0


def reset_metrics():
    """Clear the process-global registry and release the event-log
    handle (test isolation)."""
    _REGISTRY.reset()
    _close_event_log()


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

class Span:
    """Times a ``with`` block into the wall-time histogram
    ``zoo_tpu_<name>_seconds`` (name sanitized: ``train/step`` →
    ``zoo_tpu_train_step_seconds``) and appends a JSONL event when
    ``ZOO_TPU_EVENT_LOG`` is set. ``fields`` go to the event log only
    — never to metric labels (unbounded values like step indices must
    not explode label cardinality). ``elapsed`` holds the duration in
    seconds after exit; :meth:`annotate` adds fields measured inside
    the block.

    When an ambient trace is open (see
    :mod:`~analytics_zoo_tpu.common.tracing`) the span also joins it
    as a child, and the emitted event carries the trace/span ids so
    the event log stays joinable per trace. Unless ``ZOO_TPU_TRACE=0``
    the span is also a ``zoo:<name>`` annotation on the profiler's
    clock (`tracing.set_annotation_hook`)."""

    __slots__ = ("name", "fields", "elapsed", "_t0", "_registry",
                 "_trace_tok", "_ann")

    def __init__(self, name: str, registry: MetricsRegistry,
                 fields: Dict[str, Any]):
        self.name = name
        self.fields = fields
        self.elapsed = 0.0
        self._t0 = 0.0
        self._registry = registry
        self._trace_tok = None
        self._ann = None

    def annotate(self, **fields):
        """Add fields measured inside the block (None is skipped)."""
        for k, v in fields.items():
            if v is not None:
                self.fields[k] = v

    def __enter__(self) -> "Span":
        self._trace_tok = _tracing.span_start(self.name)
        self._ann = _tracing.annotation_start(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.elapsed = time.perf_counter() - self._t0
        _tracing.annotation_end(self._ann)
        metric = "zoo_tpu_" + _sanitize(self.name) + "_seconds"
        self._registry.histogram(
            metric, help=f"wall time of {self.name} spans").observe(
            self.elapsed)
        if self._trace_tok is not None:
            _tracing.span_end(self._trace_tok, self.name,
                              self.elapsed, self.fields)
        if os.environ.get("ZOO_TPU_EVENT_LOG"):
            fields = dict(self.fields)
            fields["dur_s"] = round(self.elapsed, 6)
            if exc_type is not None:
                fields["error"] = exc_type.__name__
            if self._trace_tok is not None:
                _tok, tid, sid, parent, t0_wall = self._trace_tok
                fields.update(trace_id=tid, span_id=sid,
                              parent_id=parent,
                              t_start=round(t0_wall, 6))
            event(self.name, **fields)
        return False  # never swallow exceptions


def span(name: str, registry: Optional[MetricsRegistry] = None,
         **fields) -> Span:
    """``with span("train/step", step=i): ...``"""
    return Span(name, registry or _REGISTRY, fields)


# Route tracing's root/explicit span records into the event log.
# (Span emits its own events above, so it bypasses this hook.)
_tracing.set_event_hook(event)
