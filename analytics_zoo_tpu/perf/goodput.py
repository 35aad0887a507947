"""Live goodput / MFU ledger for the training loop.

Makes the roofline chase (0.45 MFU, BASELINE.md) a *live* signal:
every Estimator step feeds a :class:`GoodputLedger`, which maintains

- ``zoo_tpu_mfu`` — executed-semantics FLOPs per step (from
  :mod:`analytics_zoo_tpu.perf.flops`, the same counter behind
  ``make flops-audit``) ÷ step wall time ÷ the device-kind peak from
  :data:`PEAK_FLOPS_BY_DEVICE_KIND` (``ZOO_TPU_PEAK_TFLOPS``
  overrides);
- ``zoo_tpu_goodput_ratio`` — the share of step wall time spent in
  compute, where wall time decomposes into
  compute / data-wait / dispatch / checkpoint using the PR 5
  step-trace fields (compute is the residual, so the shares sum to
  1.0 by construction);
- ``zoo_tpu_goodput_share{component}`` — the full decomposition
  (the ``data_wait`` share also feeds the shipped training SLO in
  :mod:`analytics_zoo_tpu.common.slo`).

Per-epoch summaries (:meth:`GoodputLedger.epoch_summary`) land in the
Estimator's training history and — via
``bench_common.attach_metrics_snapshot`` — in every bench artifact.

``ZOO_TPU_GOODPUT=0`` disables the ledger entirely;
``ZOO_TPU_GOODPUT_FLOPS=0`` skips the one-off train-step lowering
used to count FLOPs (the decomposition gauges stay live, MFU reads
0). jax is never imported at module scope — the peak-FLOPs lookup
takes a device-kind string.
"""

from __future__ import annotations

import os
import threading
from collections import deque
from typing import Any, Dict, Optional

from analytics_zoo_tpu.common import observability as obs

__all__ = [
    "GoodputLedger",
    "PEAK_FLOPS_BY_DEVICE_KIND",
    "COMPONENTS",
    "resolve_peak_flops",
    "ledger_for_backend",
    "recent_summaries",
    "reset_goodput",
    "enabled",
    "flops_enabled",
]

# THE peaks table: per-chip dense peak FLOP/s at the dtype the train
# step actually runs (bf16 on TPU; Google Cloud TPU documentation,
# per-generation system-architecture pages). Matched by lowercase
# substring against ``jax.devices()[0].device_kind``; first hit wins,
# most specific first. A kind that is not here is an error, never a
# default. The CPU entry is a nominal single-core figure that keeps
# the ledger's arithmetic defined under the CPU tests; an MFU against
# it is not a device metric.
PEAK_FLOPS_BY_DEVICE_KIND = (
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v6e", 918e12),
    ("v6 lite", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
    ("cpu", 1e11),
)

# Wall-time decomposition components; "compute" is the residual so
# the shares always sum to 1.0.
COMPONENTS = ("compute", "data_wait", "dispatch", "checkpoint")


def enabled() -> bool:
    return os.environ.get("ZOO_TPU_GOODPUT", "1") != "0"


def flops_enabled() -> bool:
    """Gate for the one-off ``train_step.lower()`` retrace used to
    count executed FLOPs (skippable for huge models)."""
    return os.environ.get("ZOO_TPU_GOODPUT_FLOPS", "1") != "0"


def resolve_peak_flops(device_kind: str) -> float:
    """Peak FLOP/s for a ``device_kind`` string, from
    :data:`PEAK_FLOPS_BY_DEVICE_KIND`. ``ZOO_TPU_PEAK_TFLOPS``
    overrides the table. A kind the table does not know raises
    ``ValueError``: no peak is ever assumed."""
    raw = os.environ.get("ZOO_TPU_PEAK_TFLOPS")
    if raw:
        return float(raw) * 1e12
    kind = (device_kind or "").lower()
    for sub, peak in PEAK_FLOPS_BY_DEVICE_KIND:
        if sub in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s known for device_kind {device_kind!r}: add "
        "it to perf.goodput.PEAK_FLOPS_BY_DEVICE_KIND with its "
        "source, or set ZOO_TPU_PEAK_TFLOPS")


class GoodputLedger:
    """Accumulates per-step wall-time decomposition + FLOPs into live
    gauges and per-epoch summaries. Thread-safe (the train loop owns
    it, but `/debug` surfaces may read concurrently)."""

    def __init__(self, peak_flops: Optional[float] = None,
                 device_kind: str = "", n_devices: int = 1,
                 registry: "Optional[obs.MetricsRegistry]" = None):
        if peak_flops is None:
            peak_flops = resolve_peak_flops(device_kind)
        self.peak_flops = float(peak_flops) * max(1, int(n_devices))
        self.device_kind = device_kind
        self.flops_per_step: Optional[float] = None
        self._lock = threading.Lock()
        self._registry = registry or obs.get_registry()
        self._reset_epoch_locked()

    def _reset_epoch_locked(self):
        self._steps = 0
        self._wall_s = 0.0
        self._parts = {c: 0.0 for c in COMPONENTS}

    def set_flops_per_step(self, flops: Optional[float]):
        with self._lock:
            self.flops_per_step = (
                float(flops) if flops else None)

    def note_step(self, wall_s: float, data_wait_s: float = 0.0,
                  dispatch_s: float = 0.0,
                  checkpoint_s: float = 0.0) -> dict:
        """Feed one step's wall time and its measured non-compute
        components (each clamped into the wall); compute is the
        residual. Updates the live gauges and returns this step's
        decomposition."""
        wall_s = max(float(wall_s), 1e-9)
        parts = {"data_wait": max(float(data_wait_s), 0.0),
                 "dispatch": max(float(dispatch_s), 0.0),
                 "checkpoint": max(float(checkpoint_s), 0.0)}
        overhead = sum(parts.values())
        if overhead > wall_s:  # measurement skew: scale into the wall
            scale = wall_s / overhead
            parts = {k: v * scale for k, v in parts.items()}
            overhead = wall_s
        parts["compute"] = wall_s - overhead
        with self._lock:
            self._steps += 1
            self._wall_s += wall_s
            for k, v in parts.items():
                self._parts[k] += v
            flops = self.flops_per_step
        goodput = parts["compute"] / wall_s
        mfu = ((flops / wall_s) / self.peak_flops
               if flops and self.peak_flops > 0 else 0.0)
        reg = self._registry
        reg.gauge("zoo_tpu_mfu",
                  help="model FLOPs utilization of the last train "
                       "step (executed FLOPs / wall / peak)"
                  ).set(mfu)
        reg.gauge("zoo_tpu_goodput_ratio",
                  help="compute share of the last train step's wall "
                       "time").set(goodput)
        for comp in COMPONENTS:
            reg.gauge("zoo_tpu_goodput_share",
                      help="train-step wall-time decomposition "
                           "(shares sum to 1)",
                      labels={"component": comp}
                      ).set(parts[comp] / wall_s)
        return {k: parts[k] / wall_s for k in COMPONENTS}

    def epoch_summary(self, epoch: Optional[int] = None,
                      reset: bool = True) -> Optional[dict]:
        """Aggregate decomposition for the epoch so far (None when no
        steps landed): per-component seconds + shares (summing to
        ~1.0), mean MFU, and goodput ratio. Emitted as a
        ``perf/goodput_epoch`` event, appended to the module summary
        ring (bench artifacts attach it), and — by default — the
        epoch accumulators reset."""
        with self._lock:
            if self._steps == 0:
                return None
            steps, wall = self._steps, self._wall_s
            parts = dict(self._parts)
            flops = self.flops_per_step
            if reset:
                self._reset_epoch_locked()
        shares = {k: v / wall for k, v in parts.items()}
        mfu = ((flops * steps / wall) / self.peak_flops
               if flops and self.peak_flops > 0 and wall > 0
               else 0.0)
        summary: "Dict[str, Any]" = {
            "epoch": epoch,
            "steps": steps,
            "wall_s": round(wall, 6),
            "seconds": {k: round(v, 6) for k, v in parts.items()},
            "shares": {k: round(v, 6) for k, v in shares.items()},
            "goodput_ratio": round(shares["compute"], 6),
            # significant figures, not decimal places: a toy CPU fit
            # has an MFU of ~1e-9 and must not summarize as 0.0
            "mfu": float(f"{mfu:.6g}"),
            "flops_per_step": flops,
            "peak_flops": self.peak_flops,
            "device_kind": self.device_kind,
        }
        obs.event("perf/goodput_epoch", **summary)
        with _summaries_lock:
            _summaries.append(summary)
        return summary


# Recent epoch summaries, process-wide: bench_common attaches these
# to every artifact.
_summaries_lock = threading.Lock()
_summaries: "deque" = deque(maxlen=32)


def recent_summaries() -> "list[dict]":
    with _summaries_lock:
        return list(_summaries)


def reset_goodput():
    """Clear the process-global summary ring (test isolation)."""
    with _summaries_lock:
        _summaries.clear()


def ledger_for_backend(
        registry: "Optional[obs.MetricsRegistry]" = None
) -> Optional[GoodputLedger]:
    """A ledger sized for the current jax backend (device kind, peak
    FLOPs, local device count); None when ``ZOO_TPU_GOODPUT=0``. An
    unknown ``device_kind`` raises (see :func:`resolve_peak_flops`)."""
    if not enabled():
        return None
    import jax
    return GoodputLedger(
        device_kind=jax.local_devices()[0].device_kind,
        n_devices=jax.local_device_count(), registry=registry)
