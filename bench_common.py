"""Shared harness for the bench scripts (bench, bench_ncf, bench_bert).

Measurement recipe: ONE compiled lax.scan chain per workload, one
scalar host fetch per run, the constant dispatch overhead (min of 5
tiny-jit samples — a single transient spike must not inflate
throughput) subtracted from the best of ``reps`` runs.

Record schema (the JSON lines bench.py prints; the last line is the
complete record):

- ``metric``/``unit``: what the headline measures
  (``resnet50_train_images_per_sec_per_chip``, images/sec).
- ``value``: the headline, measured on the device ``device`` names.
- ``device``: ``{platform, kind, count}`` as JAX reports them
  (`select_device`). A run that finds no TPU exits non-zero; only
  the tests' explicit ``ZOO_TPU_BENCH_PLATFORM=cpu`` smoke runs
  elsewhere, and its record carries ``smoke`` saying so.
- ``vs_baseline``: achieved MFU / 0.45.
- ``extra_metrics``: list of per-workload records (ncf, bert), each
  with its own metric/value/unit.
- ``telemetry``: process-global metrics snapshot
  (`attach_metrics_snapshot`).
- ``goodput``: recent per-epoch goodput/MFU summaries from
  `analytics_zoo_tpu.perf.goodput` when an Estimator fit ran in this
  process (docs/observability.md).
- ``autotune``: ``{enabled, cache_hits, cache_misses, sweeps,
  source}`` provenance from `analytics_zoo_tpu.perf.autotune` —
  scripts/perf_sentinel.py splits tuned runs into their own ``-tuned``
  lineages keyed on ``enabled``.
- ``build_info``: package/jax versions, device kind, and the active
  ``ZOO_TPU_*`` flag fingerprint (`common/diagnostics.build_info` —
  the same record the ``zoo_tpu_build_info`` gauge exposes).

Exit code 0 iff every leg measured; any leg that raises fails the run.
"""

from __future__ import annotations

import os
import time

import numpy as np


RTT_BOUND_NOTE = ("rtt_bound: the constant dispatch round-trip "
                  "dominates this chain; treat as a lower-confidence "
                  "number")


def select_device() -> dict:
    """Pick the device the way every bench script does and name it.

    JAX's default platform, or the one ``ZOO_TPU_BENCH_PLATFORM``
    names (the tests' CPU smoke — a chip run sets nothing). Returns
    ``{"platform", "kind", "count"}`` as JAX reports them. A
    measurement needs a TPU: any other platform that was not asked
    for by name exits non-zero, and one that was is labelled
    ``smoke`` in the record."""
    import jax

    plat = os.environ.get("ZOO_TPU_BENCH_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and plat != dev.platform:
        raise SystemExit(
            f"this benchmark measures on a TPU; JAX found "
            f"{dev.platform!r}. ZOO_TPU_BENCH_PLATFORM={dev.platform} "
            "runs a labelled smoke for the tests, never a "
            "measurement.")
    rec = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(devices)}
    if dev.platform != "tpu":
        rec["smoke"] = (f"explicit ZOO_TPU_BENCH_PLATFORM={plat}: "
                        "not a chip measurement")
    return rec


def flag_rtt_bound(rec: dict, rtt_bound: bool) -> dict:
    """Attach the shared quality note to a metric record when the
    measurement was round-trip-dominated (see time_chain)."""
    if rtt_bound:
        rec["quality"] = RTT_BOUND_NOTE
    return rec


def attach_metrics_snapshot(rec: dict) -> dict:
    """Embed the process-global telemetry snapshot
    (`common/observability.py`) in a bench JSON artifact under
    ``"telemetry"`` — so a bench run's step/ingest/serving metrics
    ride along with its headline number. No-op when nothing was
    recorded (raw jit chains bypass the instrumented layers)."""
    from analytics_zoo_tpu.common.observability import snapshot
    from analytics_zoo_tpu.common import diagnostics
    from analytics_zoo_tpu.perf import autotune
    from analytics_zoo_tpu.perf.goodput import recent_summaries
    snap = snapshot()
    if snap:
        rec["telemetry"] = snap
    summaries = recent_summaries()
    if summaries:
        rec["goodput"] = summaries
    # provenance: was this run tuned? perf_sentinel keys its
    # tuned-vs-heuristic lineage split on autotune.enabled, so a
    # tuned run can never masquerade as a heuristic-config win
    rec["autotune"] = autotune.stats()
    # provenance: package/jax versions, device kind, and the
    # ZOO_TPU_* flag fingerprint this run executed under — the same
    # record the zoo_tpu_build_info gauge exposes
    rec["build_info"] = diagnostics.build_info()
    return rec


def dispatch_overhead(samples: int = 5) -> float:
    """Constant per-dispatch round-trip cost, min over ``samples``."""
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda a: a + 1.0).lower(
        jnp.zeros((), jnp.float32)).compile()
    float(np.asarray(tiny(jnp.zeros((), jnp.float32))))  # warm
    overhead = float("inf")
    for _ in range(samples):
        t0 = time.perf_counter()
        float(np.asarray(tiny(jnp.zeros((), jnp.float32))))
        overhead = min(overhead, time.perf_counter() - t0)
    return overhead


def time_chain(compiled, args, reps: int = 3,
               with_quality: bool = False):
    """Best wall time of ``compiled(*args)`` (last output = scalar
    loss fetched to host as the sync point) minus the dispatch
    overhead. Returns ``(dt_seconds, last_loss)`` — or with
    ``with_quality=True``, ``(dt, loss, rtt_bound)`` where
    ``rtt_bound`` flags a measurement the constant round-trip
    overhead dominates (dt after subtraction is under half the raw
    wall time): such numbers are jitter, not throughput, and callers should
    label them or lengthen the chain."""
    def timed():
        t0 = time.perf_counter()
        out = compiled(*args)
        loss = out[-1] if isinstance(out, (list, tuple)) else out
        # the host fetch IS the sync point — it must complete before
        # the clock stops (a `return elapsed, fetch()` tuple evaluates
        # the elapsed time first and times only the async dispatch)
        loss_val = float(np.asarray(loss))
        return time.perf_counter() - t0, loss_val

    timed()                                   # warmup run
    overhead = dispatch_overhead()
    best_dt, loss = None, float("nan")
    for _ in range(reps):
        dt_i, loss = timed()
        best_dt = dt_i if best_dt is None else min(best_dt, dt_i)
    dt = max(best_dt - overhead, 1e-9)
    if with_quality:
        return dt, loss, dt < 0.5 * best_dt
    return dt, loss
