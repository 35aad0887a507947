"""``selfcheck.py``'s checks, one test each, so that the count says
which of them broke."""

import pytest

from benchmark import selfcheck


@pytest.fixture(scope="module")
def cells():
    return selfcheck.check_data_files()


def test_data_files(cells):
    assert cells


def test_manifest(cells):
    selfcheck.check_manifest(cells)


def test_traffic(cells):
    selfcheck.check_traffic(cells)


@pytest.mark.parametrize("check", [selfcheck.check_flops,
                                   selfcheck.check_reduction,
                                   selfcheck.check_peaks])
def test_arithmetic(check):
    check()


def test_toy_cells_parse():
    from benchmark.tests.conftest import DATA
    assert set(selfcheck.check_data_files(DATA)) == {
        "resnet50-train-toy", "resnet50-train-dp4-toy",
        "gpt2-generate-toy"}
