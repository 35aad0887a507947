"""Test bootstrap: force an 8-device virtual CPU mesh.

Mirrors the reference's philosophy of testing distributed semantics on
`local[N]` Spark without a real cluster (SURVEY.md §4.3): N virtual CPU
devices stand in for N TPU chips; the pjit/GSPMD code paths are identical.
"""

import os

if not os.environ.get("ZOO_TPU_TEST_REAL_DEVICE"):
    os.environ["JAX_PLATFORMS"] = "cpu"
# hermetic: no persistent compile cache shared between xdist workers
# and between runs (init_nncontext would otherwise place one in the
# checkout); the placement itself is tested in subprocesses
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# no background federation ticker threads in tests: every fleet
# router a test starts would otherwise scrape/merge on a 5s cadence
# and race the per-test registry resets below. Tests drive
# TelemetryCollector.tick() manually (the injectable-clock path).
os.environ.setdefault("ZOO_TPU_FED_TICK_S", "0")
# hermetic autotune: never read (or pollute) the developer's
# ~/.cache/zoo_tpu/autotune.json — swept winners leaking in could
# flip crossover gates the tests assert on (e.g. flash_profitable).
# Tests that exercise sweeping repoint this themselves via
# monkeypatch + autotune.reset_cache().
os.environ.setdefault(
    "ZOO_TPU_AUTOTUNE_CACHE",
    os.path.join("/tmp", f"zoo_tpu_test_autotune_{os.getpid()}.json"))
os.environ.setdefault("ZOO_TPU_AUTOTUNE", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_context():
    """Reset the process-wide NNContext between tests."""
    yield
    from analytics_zoo_tpu.common import nncontext
    nncontext.reset_nncontext()


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Reset the global metrics registry, trace-span buffer, SLO
    engine and goodput ring around every test, so counters/spans/
    breach state leaked by one test can never satisfy (or break)
    another's assertions."""
    from analytics_zoo_tpu.common import (
        faults, forecast, observability, slo, timeseries, tracing)
    from analytics_zoo_tpu.perf import autotune, goodput
    observability.reset_metrics()
    tracing.reset_tracing()
    slo.reset_slo()
    timeseries.reset_history()
    forecast.reset_forecast()
    goodput.reset_goodput()
    faults.reset_faults()
    autotune.reset_cache()
    yield
    observability.reset_metrics()
    tracing.reset_tracing()
    slo.reset_slo()
    timeseries.reset_history()
    forecast.reset_forecast()
    goodput.reset_goodput()
    faults.reset_faults()
    autotune.reset_cache()


@pytest.fixture
def rng():
    return np.random.RandomState(42)
