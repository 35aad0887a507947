"""The control of ``correct``, at a size a test run can hold: the
plain reference computed in float8 and put in the program's place has
to come out as not correct under the toy cells' limits, beside a sound
run of the program that passes them. (The cells' own limits were set
from readings on the chip at the cells' own sizes: PERF.md.)"""

import jax
import pytest

from benchmark import calibrate, harness
from benchmark.tests.conftest import DATA


def _over(side: dict) -> "list[str]":
    return [n for n, d in side.items() if not d["value"] <= d["limit"]]


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4])
def test_train_control_and_half_batch_fault_fail(seed):
    loaded = harness.load_cell("resnet50-train-toy", DATA)
    sides = calibrate.train_readings(loaded, seed, jax.devices()[:1])
    assert _over(sides["program"]) == []
    assert _over(sides["control_f8"]), sides
    assert _over(sides["fault_half_batch"]), sides


@pytest.mark.parametrize("seed", [3, 5])
def test_generate_control_fails(seed):
    loaded = harness.load_cell("gpt2-generate-toy", DATA)
    r = calibrate.generate_readings(loaded, seed, jax.devices()[:1],
                                    seconds=2.0)
    assert _over(r["program"]) == [] and r["failed"] == 0
    assert _over(r["control_f8"]), r
