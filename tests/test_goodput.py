"""Goodput/MFU ledger (perf/goodput.py): share math, peak-FLOPs
resolution, gauge wiring, epoch summaries, and the Estimator
integration (acceptance: a 2-step CPU fit exposes non-zero
zoo_tpu_mfu / zoo_tpu_goodput_ratio and a decomposition summing to
~1.0 in the training history). Tier-1 fast."""

import numpy as np
import pytest

from analytics_zoo_tpu.common import observability as obs
from analytics_zoo_tpu.perf import goodput
from analytics_zoo_tpu.perf.goodput import (
    COMPONENTS, GoodputLedger, recent_summaries, resolve_peak_flops)


def _gauges(reg):
    snap = reg.snapshot()

    def val(name, labels=None):
        for rec in snap.get(name, {}).get("values", ()):
            if labels is None or rec["labels"] == labels:
                return rec["value"]
        return None
    return snap, val


# -- peak resolution --------------------------------------------------------

@pytest.mark.parametrize("kind,expect", [
    ("TPU v5p", 459e12),
    ("TPU v5e", 197e12),
    ("TPU v5 lite", 197e12),
    ("TPU v4", 275e12),
    ("TPU v3", 123e12),
    ("cpu", 1e11),
])
def test_resolve_peak_flops(kind, expect):
    assert resolve_peak_flops(kind) == expect


@pytest.mark.parametrize("kind", ["Golden Gate", "", "TPU v9"])
def test_unknown_device_kind_raises(kind):
    # an unknown device is an error, never an assumed v5e
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        resolve_peak_flops(kind)
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        GoodputLedger(device_kind=kind,
                      registry=obs.MetricsRegistry())


def test_peak_env_override(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_PEAK_TFLOPS", "2.5")
    assert resolve_peak_flops("TPU v5e") == 2.5e12


def test_peak_scales_by_device_count():
    led = GoodputLedger(peak_flops=100.0, n_devices=8,
                        registry=obs.MetricsRegistry())
    assert led.peak_flops == 800.0


# -- share math -------------------------------------------------------------

def test_note_step_decomposition_sums_to_one():
    reg = obs.MetricsRegistry()
    led = GoodputLedger(peak_flops=1e12, registry=reg)
    led.set_flops_per_step(2e11)
    shares = led.note_step(1.0, data_wait_s=0.2, dispatch_s=0.1,
                           checkpoint_s=0.0)
    assert shares["compute"] == pytest.approx(0.7)
    assert shares["data_wait"] == pytest.approx(0.2)
    assert sum(shares.values()) == pytest.approx(1.0)
    _snap, val = _gauges(reg)
    assert val("zoo_tpu_mfu") == pytest.approx(0.2)
    assert val("zoo_tpu_goodput_ratio") == pytest.approx(0.7)
    for comp in COMPONENTS:
        assert val("zoo_tpu_goodput_share",
                   {"component": comp}) is not None


def test_note_step_overhead_skew_clamped():
    """Measured overhead exceeding the wall (clock skew) scales into
    it instead of producing a negative compute share."""
    led = GoodputLedger(peak_flops=1e12,
                        registry=obs.MetricsRegistry())
    shares = led.note_step(1.0, data_wait_s=3.0, dispatch_s=1.0)
    assert shares["compute"] == pytest.approx(0.0)
    assert shares["data_wait"] == pytest.approx(0.75)
    assert shares["dispatch"] == pytest.approx(0.25)
    assert sum(shares.values()) == pytest.approx(1.0)


def test_mfu_zero_without_flops():
    reg = obs.MetricsRegistry()
    led = GoodputLedger(peak_flops=1e12, registry=reg)
    led.note_step(0.5)
    _snap, val = _gauges(reg)
    assert val("zoo_tpu_mfu") == 0.0
    assert val("zoo_tpu_goodput_ratio") == pytest.approx(1.0)


# -- epoch summaries --------------------------------------------------------

def test_epoch_summary_aggregates_and_resets():
    led = GoodputLedger(peak_flops=1e12,
                        registry=obs.MetricsRegistry())
    led.set_flops_per_step(1e11)
    led.note_step(1.0, data_wait_s=0.5)
    led.note_step(1.0, data_wait_s=0.1)
    s = led.epoch_summary(epoch=3)
    assert s["epoch"] == 3 and s["steps"] == 2
    assert s["wall_s"] == pytest.approx(2.0)
    assert sum(s["shares"].values()) == pytest.approx(1.0, abs=1e-4)
    assert s["shares"]["data_wait"] == pytest.approx(0.3)
    assert s["goodput_ratio"] == pytest.approx(0.7)
    assert s["mfu"] == pytest.approx(0.1)
    # ring captured it (this is what bench artifacts attach)
    assert recent_summaries()[-1] == s
    # reset: a second call with no new steps returns None
    assert led.epoch_summary(epoch=4) is None


def test_epoch_summary_empty_is_none():
    led = GoodputLedger(peak_flops=1e12,
                        registry=obs.MetricsRegistry())
    assert led.epoch_summary() is None


def test_ledger_for_backend_disabled(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_GOODPUT", "0")
    assert goodput.ledger_for_backend() is None


def test_ledger_for_backend_cpu():
    led = goodput.ledger_for_backend(registry=obs.MetricsRegistry())
    assert led is not None
    # conftest pins an 8-device virtual CPU mesh; the honest
    # single-core CPU peak is scaled by the device count
    assert led.peak_flops == pytest.approx(8 * 1e11)


# -- Estimator integration (acceptance) -------------------------------------

def test_estimator_fit_exposes_goodput(rng):
    """2-step CPU fit: live MFU/goodput gauges are non-zero and the
    per-epoch summary in the training history decomposes wall time
    into shares summing to ~1.0."""
    from analytics_zoo_tpu.pipeline.api.keras import (
        Sequential, layers as L)
    m = Sequential()
    m.add(L.Dense(4, input_shape=(3,)))
    m.add(L.Dense(1))
    m.compile(optimizer="sgd", loss="mse")
    x = rng.randn(16, 3).astype(np.float32)
    y = rng.randn(16, 1).astype(np.float32)
    res = m.fit(x, y, batch_size=8, nb_epoch=1)  # 2 steps

    snap = obs.snapshot()
    mfu = snap["zoo_tpu_mfu"]["values"][0]["value"]
    ratio = snap["zoo_tpu_goodput_ratio"]["values"][0]["value"]
    assert mfu > 0.0
    assert 0.0 < ratio <= 1.0
    share_sum = sum(r["value"] for r in
                    snap["zoo_tpu_goodput_share"]["values"])
    assert share_sum == pytest.approx(1.0, abs=1e-6)

    gp = res.history[-1]["goodput"]
    assert gp["steps"] == 2
    assert gp["mfu"] > 0.0
    assert gp["flops_per_step"] > 0
    assert sum(gp["shares"].values()) == pytest.approx(1.0,
                                                       abs=1e-4)
    assert set(gp["shares"]) == set(COMPONENTS)
    # the summary ring feeds bench artifacts
    assert recent_summaries()[-1]["steps"] == 2


def test_estimator_goodput_disabled(rng, monkeypatch):
    monkeypatch.setenv("ZOO_TPU_GOODPUT", "0")
    from analytics_zoo_tpu.pipeline.api.keras import (
        Sequential, layers as L)
    m = Sequential()
    m.add(L.Dense(1, input_shape=(3,)))
    m.compile(optimizer="sgd", loss="mse")
    x = rng.randn(8, 3).astype(np.float32)
    y = rng.randn(8, 1).astype(np.float32)
    res = m.fit(x, y, batch_size=8, nb_epoch=1)
    assert "zoo_tpu_mfu" not in obs.snapshot()
    assert "goodput" not in res.history[-1]
