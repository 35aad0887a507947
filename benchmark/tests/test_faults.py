"""The rest of a run with the timed path broken underneath: past the
harness's look for a chip, ``run_cell`` drives a toy cell on the CPU
and ``correct`` has to come out false, once for each fault a cell can
have. The sound run beside each shows the limits do not fail it."""

import time

import jax
import numpy as np
import pytest

from benchmark import run
from benchmark.tests.conftest import DATA


def _cell(name: str, seconds: float = 1.0):
    line, res = run.run_cell(name, 2 ** 31 + 11, seconds, False,
                             jax.devices()[:1], time.perf_counter(),
                             root=DATA)
    return line, res


@pytest.fixture
def estimator():
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    return Estimator


def test_train_sound_run_is_correct():
    line, _res = _cell("resnet50-train-toy")
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0


def test_train_step_that_returns_its_state_unchanged(
        monkeypatch, estimator):
    build = estimator._build_train_step

    def broken(self, tx):
        step = build(self, tx)

        def unchanged(params, opt_state, rng, x, y):
            _p, _o, loss = jax.jit(
                lambda *a: step.__wrapped__(*a))(
                    params, opt_state, rng, x, y)
            return params, opt_state, loss
        return unchanged
    monkeypatch.setattr(estimator, "_build_train_step", broken)
    line, _res = _cell("resnet50-train-toy")
    assert line["correct"] is False
    assert line["compared"]["weight_change_gap"]["value"] >= 0.99


def test_train_half_of_the_batch_left_out(monkeypatch, estimator):
    build = estimator._build_train_step

    def broken(self, tx):
        step = build(self, tx).__wrapped__

        def half(params, opt_state, rng, x, y):
            n = x.shape[0] // 2
            return step(params, opt_state, rng, x[:n], y[:n])
        return jax.jit(half, donate_argnums=(0, 1))
    monkeypatch.setattr(estimator, "_build_train_step", broken)
    line, _res = _cell("resnet50-train-toy")
    assert line["correct"] is False, line["compared"]


def test_generate_sound_run_is_correct():
    line, _res = _cell("gpt2-generate-toy", 2.0)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0


def test_generate_token_altered_where_it_is_produced(monkeypatch):
    from analytics_zoo_tpu.pipeline.inference.generation import \
        GenerationEngine
    step = GenerationEngine.step

    def altered(self, active):
        toks = step(self, active)
        # every 7th decode step hands back another token
        if self._step_id % 7 == 0:
            toks = (np.asarray(toks) + 1) % self.net.vocab
        return toks
    monkeypatch.setattr(GenerationEngine, "step", altered)
    line, _res = _cell("gpt2-generate-toy", 2.0)
    assert line["correct"] is False
    assert line["compared"]["logit_gap"]["value"] > \
        line["compared"]["logit_gap"]["limit"]
