"""A four-chip data-parallel train cell is data only: the toy cell
``resnet50-train-dp4-toy`` is one workload file beside the one-chip
toy's, and the same driver runs it over four virtual CPU devices
against the one-device reference at the global batch. A rehearsal of
the path, never a measurement."""

import time

import jax
import pytest

from benchmark import run
from benchmark.tests.conftest import DATA


def test_train_cell_with_four_chips_needs_no_code():
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4")
    line, res = run.run_cell("resnet50-train-dp4-toy", 17, 1.0, False,
                             jax.devices(), time.perf_counter(),
                             root=DATA)
    assert line["device"]["count"] == 4
    assert res["layers"]["batch"] == 4 * 16
    assert line["correct"] is True, line["compared"]


def test_cell_that_asks_for_more_chips_than_present_is_refused():
    with pytest.raises(RuntimeError, match="needs 4 chips"):
        run.run_cell("resnet50-train-dp4-toy", 17, 1.0, False,
                     jax.devices()[:1], time.perf_counter(), root=DATA)
