"""Anomaly detection & device diagnostics (the diagnostics layer,
L1.5).

Turns the raw telemetry of
:mod:`~analytics_zoo_tpu.common.observability` into *judgements*:
"this process is recompiling in a storm", "that step was a
straggler", "device memory is near its limit". Every detector emits
one structured ``diagnostics/anomaly`` event plus a
``zoo_tpu_anomalies_total{kind}`` counter, so alerting needs exactly
one PromQL expression (see the anomaly catalog in
docs/observability.md).

Detectors:

- :class:`RecompileMonitor` — listens for XLA ``backend_compile``
  events via ``jax.monitoring`` (the same signal
  ``tests/test_serving_batch.py`` uses to prove zero steady-state
  recompiles) and fires ``kind="recompile_storm"`` when more than
  ``threshold`` compiles land inside a rolling ``window_s`` window.
  A warmed serving process or a shape-stable train loop should
  compile a handful of times and then never again; a storm means a
  shape/dtype leak is thrashing the compile cache.
- :class:`StepTimeWatcher` — rolling-median straggler detection:
  ``kind="step_time_regression"`` when one step exceeds ``factor``
  × the window median (the first compile-heavy steps are excused by
  ``min_samples``).
- :func:`update_device_memory_gauges` — per-device HBM watermarks
  (``zoo_tpu_device_memory_bytes{device,kind}``) from
  ``device.memory_stats()``; silently skips backends (CPU) that
  expose none.

jax is imported lazily so this module stays importable from
executor-side code that must not drag in the runtime.
"""

from __future__ import annotations

import os
import statistics
import threading
import time
from collections import deque
from typing import Optional

from analytics_zoo_tpu.common import observability as obs
from analytics_zoo_tpu.common import tracing

__all__ = [
    "anomaly",
    "add_anomaly_listener",
    "remove_anomaly_listener",
    "RecompileMonitor",
    "StepTimeWatcher",
    "ReplicaSkewDetector",
    "install_recompile_monitor",
    "get_recompile_monitor",
    "update_device_memory_gauges",
    "update_process_vitals",
    "build_info",
    "update_build_info",
]

# control loops (e.g. the rollout controller's canary auto-rollback,
# pipeline/inference/registry.py) subscribe here to REACT to
# anomalies instead of polling the counter
_listener_lock = threading.Lock()
_listeners: list = []


def add_anomaly_listener(fn) -> None:
    """Register ``fn(kind, fields)`` to be called synchronously on
    every :func:`anomaly` (after the counter/event are recorded).
    Listener exceptions are swallowed — a broken reactor must not
    mask the anomaly it reacted to."""
    with _listener_lock:
        if fn not in _listeners:
            _listeners.append(fn)


def remove_anomaly_listener(fn) -> None:
    """Unregister a listener (no-op when absent)."""
    with _listener_lock:
        try:
            _listeners.remove(fn)
        except ValueError:
            pass


def anomaly(kind: str, **fields):
    """Record one detected anomaly: bump
    ``zoo_tpu_anomalies_total{kind}`` and append a structured
    ``diagnostics/anomaly`` event (fields carry the evidence), then
    notify registered listeners."""
    obs.counter("zoo_tpu_anomalies_total",
                help="anomalies detected, by kind",
                labels={"kind": kind}).inc()
    obs.event("diagnostics/anomaly", kind=kind, **fields)
    with _listener_lock:
        listeners = list(_listeners)
    for fn in listeners:
        try:
            fn(kind, dict(fields))
        except Exception as e:
            from analytics_zoo_tpu.common.nncontext import logger
            logger.warning("anomaly listener %r failed: %s", fn, e)


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


# -- expected-compile excusal -------------------------------------------
# Deliberate compiles — engine warm-up, AOT bucket builds — fire the
# same jax.monitoring backend_compile events as pathological
# recompiles, and a GenerationEngine.warm() alone (step + a bucket
# ladder of prefills, 9 programs) trips the default storm threshold
# of 5. Callers that KNOW they are compiling bracket the work with
# :func:`expected_compiles`; jax compiles synchronously on the
# calling thread, so a thread-local depth cleanly scopes the excusal
# to exactly those compiles while concurrent traffic on other
# threads stays monitored.
_expected = threading.local()


class expected_compiles:
    """Context manager marking compiles on THIS thread as expected:
    still counted in ``zoo_tpu_xla_compiles_total``, but excluded
    from the RecompileMonitor storm window. Re-entrant."""

    def __enter__(self):
        _expected.depth = getattr(_expected, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _expected.depth -= 1
        return False


def compiles_expected() -> bool:
    return getattr(_expected, "depth", 0) > 0


class RecompileMonitor:
    """Rolling-window XLA compile-storm detector.

    :meth:`note` is the pure core (unit-testable with fake clocks);
    :meth:`install` registers a ``jax.monitoring`` event-duration
    listener that calls it on every ``backend_compile`` event. At
    most one anomaly fires per window, so a storm does not itself
    become an event storm. Compiles inside an
    :func:`expected_compiles` bracket (warm-up/AOT spans) are
    counted but never storm."""

    def __init__(self, threshold: Optional[int] = None,
                 window_s: Optional[float] = None):
        if threshold is None:
            threshold = int(_env_float(
                "ZOO_TPU_RECOMPILE_THRESHOLD", 5))
        if window_s is None:
            window_s = _env_float("ZOO_TPU_RECOMPILE_WINDOW_S", 60.0)
        self.threshold = max(1, threshold)
        self.window_s = window_s
        self.storms = 0
        self._times: "deque[float]" = deque()
        self._muted_until = float("-inf")
        self._lock = threading.Lock()
        self._installed = False

    def note(self, now: Optional[float] = None) -> bool:
        """Record one compile at monotonic time ``now`` (defaults to
        the real clock). Returns True when this compile tips the
        window over the threshold (and fires the anomaly). Expected
        compiles (see :func:`expected_compiles`) bump the counter but
        skip the storm window entirely."""
        if now is None:
            now = time.monotonic()
        if compiles_expected():
            obs.counter(
                "zoo_tpu_xla_compiles_total",
                help="XLA backend_compile events observed").inc()
            return False
        with self._lock:
            self._times.append(now)
            cutoff = now - self.window_s
            while self._times and self._times[0] <= cutoff:
                self._times.popleft()
            in_window = len(self._times)
            storm = (in_window > self.threshold
                     and now >= self._muted_until)
            if storm:
                self._muted_until = now + self.window_s
                self.storms += 1
        obs.counter("zoo_tpu_xla_compiles_total",
                    help="XLA backend_compile events observed").inc()
        if storm:
            anomaly("recompile_storm", compiles=in_window,
                    window_s=self.window_s,
                    threshold=self.threshold)
        return storm

    def _listener(self, event_name: str, duration: float, **kw):
        # jax stamps e.g. ".../jax_backend_compile_duration".
        if event_name.endswith("backend_compile_duration"):
            # one ``xla/compile`` record a compile, under the span
            # that triggered it when there is one: a compile inside a
            # measured window shows by name, not as a slow step
            tracing.record_span(
                tracing.current() or (tracing.new_trace_id(), None),
                "xla/compile", time.time() - duration, duration,
                expected=compiles_expected())
            self.note()

    def install(self) -> "RecompileMonitor":
        """Register the jax.monitoring listener (idempotent; there is
        no unregister API, so one listener per process)."""
        with self._lock:
            if self._installed:
                return self
            self._installed = True
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(
            self._listener)
        return self


_monitor_lock = threading.Lock()
_monitor: Optional[RecompileMonitor] = None


def get_recompile_monitor() -> Optional[RecompileMonitor]:
    return _monitor


def install_recompile_monitor() -> RecompileMonitor:
    """Process-global :class:`RecompileMonitor`, installed once; the
    Estimator train loop and the DynamicBatcher both call this on
    start."""
    global _monitor
    with _monitor_lock:
        if _monitor is None:
            _monitor = RecompileMonitor()
    return _monitor.install()


class StepTimeWatcher:
    """Straggler / regression detection over a rolling window of step
    wall times. A step slower than ``factor`` × the window median
    fires ``kind="step_time_regression"``; after firing, detection
    mutes for ``cooldown`` observations so a sustained regression
    (which also drags the median up) reports once, not every step."""

    def __init__(self, window: int = 64, min_samples: int = 16,
                 factor: Optional[float] = None, cooldown: int = 16):
        if factor is None:
            factor = _env_float("ZOO_TPU_STEP_ANOMALY_FACTOR", 3.0)
        self.window = max(2, window)
        self.min_samples = max(1, min_samples)
        self.factor = factor
        self.cooldown = max(0, cooldown)
        self.fired = 0
        self._buf: "deque[float]" = deque(maxlen=self.window)
        self._mute = 0
        self._lock = threading.Lock()

    def observe(self, dur_s: float, step: Optional[int] = None
                ) -> bool:
        """Feed one step's wall time; returns True when it fired."""
        dur_s = float(dur_s)
        fired = False
        median = 0.0
        with self._lock:
            if self._mute > 0:
                self._mute -= 1
            elif (len(self._buf) >= self.min_samples
                  and self.factor > 0):
                median = statistics.median(self._buf)
                if median > 0 and dur_s > self.factor * median:
                    fired = True
                    self.fired += 1
                    self._mute = self.cooldown
            self._buf.append(dur_s)
        if fired:
            anomaly("step_time_regression", step=step,
                    dur_s=round(dur_s, 6),
                    median_s=round(median, 6), factor=self.factor)
        return fired


class ReplicaSkewDetector:
    """Fleet-level outlier detection: one replica drifting away from
    its siblings (a thermally throttled host, a leaking process, a
    bad NIC) while the fleet averages still look healthy.

    :meth:`observe` takes per-replica window stats — latency p99 and
    error ratio, as computed by the telemetry collector from
    consecutive snapshot deltas (`common/federation.py`) — and
    compares each replica against the **median of the other
    replicas** (not the full-fleet median: with N=2 a plain median
    averages the outlier in and can never flag it). A replica whose
    p99 exceeds ``factor`` × that median, or whose error ratio
    exceeds it by ``error_margin`` absolute, fires
    ``zoo_tpu_anomalies_total{kind="replica_skew"}`` — which the
    rollout controller's anomaly listener can act on. After firing,
    the replica mutes for ``cooldown_s`` (one anomaly per breach
    episode, not per tick). Pure function of its inputs + injected
    ``now``: fully unit-testable with fake clocks, no sleeps."""

    def __init__(self, factor: Optional[float] = None,
                 error_margin: Optional[float] = None,
                 min_events: int = 4,
                 cooldown_s: float = 60.0):
        if factor is None:
            factor = _env_float("ZOO_TPU_SKEW_FACTOR", 3.0)
        if error_margin is None:
            error_margin = _env_float("ZOO_TPU_SKEW_ERROR_MARGIN",
                                      0.25)
        self.factor = float(factor)
        self.error_margin = float(error_margin)
        self.min_events = max(1, int(min_events))
        self.cooldown_s = float(cooldown_s)
        self.fired = 0
        self._muted_until: "dict" = {}  # replica -> now threshold
        self._lock = threading.Lock()
        self.last: "dict" = {}  # latest verdicts, for debug payloads

    @staticmethod
    def _median_others(stats, name: str, key: str):
        vals = [s.get(key) for n, s in stats.items()
                if n != name and s.get(key) is not None]
        if not vals:
            return None
        return statistics.median(vals)

    def observe(self, stats: "dict",
                now: Optional[float] = None) -> "list":
        """``stats`` maps replica name → ``{"p99_s": float|None,
        "error_ratio": float|None, "events": int}`` for one window.
        Returns the list of anomalies fired (possibly empty)."""
        if now is None:
            now = time.monotonic()
        fired = []
        verdicts = {}
        for name, s in stats.items():
            events = int(s.get("events") or 0)
            verdict = {"events": events, "skew": None}
            p99 = s.get("p99_s")
            med_p99 = self._median_others(stats, name, "p99_s")
            err = s.get("error_ratio")
            med_err = self._median_others(stats, name,
                                          "error_ratio")
            if events >= self.min_events:
                if (p99 is not None and med_p99 is not None
                        and med_p99 > 0 and self.factor > 0
                        and p99 > self.factor * med_p99):
                    verdict["skew"] = {
                        "metric": "latency_p99",
                        "value": round(float(p99), 6),
                        "fleet_median": round(float(med_p99), 6)}
                elif (err is not None and med_err is not None
                        and err - med_err > self.error_margin):
                    verdict["skew"] = {
                        "metric": "error_ratio",
                        "value": round(float(err), 6),
                        "fleet_median": round(float(med_err), 6)}
            verdicts[name] = verdict
            if verdict["skew"] is None:
                with self._lock:
                    self._muted_until.pop(name, None)
                continue
            with self._lock:
                muted = now < self._muted_until.get(
                    name, float("-inf"))
                if not muted:
                    self._muted_until[name] = now + self.cooldown_s
                    self.fired += 1
            if muted:
                continue
            fields = dict(verdict["skew"], replica=name,
                          factor=self.factor, events=events)
            anomaly("replica_skew", **fields)
            fired.append(fields)
        self.last = verdicts
        return fired


def _read_rss_bytes() -> Optional[int]:
    """Resident-set size from /proc (Linux); None where absent."""
    try:
        with open("/proc/self/statm", "r", encoding="ascii") as fh:
            pages = int(fh.read().split()[1])
        return pages * (os.sysconf("SC_PAGE_SIZE")
                        if hasattr(os, "sysconf") else 4096)
    except (OSError, ValueError, IndexError):
        return None


_PROC_T0 = time.monotonic()  # fallback uptime origin (import time)


def _uptime_s() -> float:
    try:  # true process uptime via /proc (Linux)
        with open("/proc/self/stat", "rb") as fh:
            start_ticks = float(fh.read().rsplit(b")", 1)[-1]
                                .split()[19])
        with open("/proc/uptime", "r", encoding="ascii") as fh:
            host_up = float(fh.read().split()[0])
        hz = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") \
            else 100
        return max(0.0, host_up - start_ticks / float(hz))
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _PROC_T0


def update_process_vitals() -> dict:
    """Refresh this process's vitals gauges —
    ``zoo_tpu_process_rss_bytes``, ``zoo_tpu_process_uptime_s`` and
    (where /proc exists) ``zoo_tpu_process_open_fds`` — so federated
    views can spot a leaking or wedged replica without attaching a
    profiler. Called on every ``/metrics`` render; cheap (three
    /proc reads) and a clean partial no-op on platforms without
    /proc. Returns the values set."""
    out: "dict" = {}
    rss = _read_rss_bytes()
    if rss is not None:
        obs.gauge("zoo_tpu_process_rss_bytes",
                  help="resident set size of this process").set(rss)
        out["rss_bytes"] = rss
    up = _uptime_s()
    obs.gauge("zoo_tpu_process_uptime_s",
              help="seconds since this process started").set(up)
    out["uptime_s"] = up
    try:
        n_fds = len(os.listdir("/proc/self/fd"))
    except OSError:
        n_fds = None
    if n_fds is not None:
        obs.gauge("zoo_tpu_process_open_fds",
                  help="open file descriptors in this "
                       "process").set(n_fds)
        out["open_fds"] = n_fds
    return out


# build_info is stable for the life of the process (version, jax,
# device kind, flag fingerprint) — computed once, cached
_build_info_lock = threading.Lock()
_build_info: "Optional[dict]" = None


def build_info() -> dict:
    """Provenance of this process: package + jax versions, the
    accelerator kind, and a fingerprint (first 12 sha256 hex chars)
    of every active ``ZOO_TPU_*`` flag — enough to answer "what
    exactly was running?" from a scrape or a bench artifact. Cached;
    jax is probed lazily and failure degrades to ``"none"`` /
    ``"unknown"`` (the executor-side import constraint)."""
    global _build_info
    with _build_info_lock:
        if _build_info is not None:
            return dict(_build_info)
        import hashlib

        from analytics_zoo_tpu.version import __version__
        jax_version = "none"
        device = "unknown"
        try:
            import jax

            jax_version = jax.__version__
            devs = jax.devices()
            if devs:
                device = getattr(devs[0], "device_kind",
                                 devs[0].platform)
        except Exception:
            pass
        flags = sorted(f"{k}={v}" for k, v in os.environ.items()
                       if k.startswith("ZOO_TPU_"))
        fp = hashlib.sha256(
            "\n".join(flags).encode()).hexdigest()[:12]
        _build_info = {
            "version": __version__,
            "jax": jax_version,
            "device": str(device),
            "flags_fingerprint": fp,
            "flags": flags,
        }
        return dict(_build_info)


def update_build_info() -> dict:
    """Publish :func:`build_info` as the info-style gauge
    ``zoo_tpu_build_info{version,jax,device,flags}`` (value pinned
    to 1 — the labels ARE the payload, the Prometheus
    ``*_build_info`` convention). Called on every ``/metrics``
    render next to :func:`update_process_vitals`."""
    info = build_info()
    obs.gauge("zoo_tpu_build_info",
              help="build/runtime provenance as labels "
                   "(value is always 1)",
              labels={"version": info["version"],
                      "jax": info["jax"],
                      "device": info["device"],
                      "flags": info["flags_fingerprint"]}).set(1)
    return info


def update_device_memory_gauges() -> int:
    """Refresh ``zoo_tpu_device_memory_bytes{device,kind}`` watermark
    gauges from each local device's ``memory_stats()``. Returns the
    number of samples set (0 on backends without memory stats)."""
    import jax

    n = 0
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        for key, kind in (("bytes_in_use", "in_use"),
                          ("peak_bytes_in_use", "peak"),
                          ("bytes_limit", "limit")):
            v = stats.get(key)
            if v is None:
                continue
            obs.gauge("zoo_tpu_device_memory_bytes",
                      help="device memory watermarks by kind",
                      labels={"device": str(d.id),
                              "kind": kind}).set(v)
            n += 1
    return n
