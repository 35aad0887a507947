"""The expert-layer generate cell at toy size on the CPU, through
``run.run_cell``: a sound run is correct, and the two faults a share
of a deployment can have on top of `test_faults.py`'s (a token
altered where it is produced; the wrong experts' weights held) are
not. The new FLOP and byte functions against the issue's hand
arithmetic."""

import os
import time

import jax
import numpy as np
import pytest

from benchmark import flops_deepseek as fd, harness, run

# a root of its own: `test_selfcheck.py` names the cells of `data/`
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "data_moe")


def _cell(seconds: float = 3.0):
    line, res = run.run_cell("deepseek-generate-toy", 2 ** 31 + 13,
                             seconds, False, jax.devices()[:1],
                             time.perf_counter(), root=DATA)
    return line, res


def test_sound_run_is_correct_and_counts_its_experts():
    line, res = _cell()
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    d = res["layers"]["counters"]
    total = d["zoo_tpu_moe_assignments_total"]
    # top-3 over two expert layers, a decoded token
    assert total == d["zoo_tpu_serving_gen_tokens_total"] * 3 * 2
    assert 0 < d["zoo_tpu_moe_assignments_held_total"] < total
    from benchmark.readers import moe
    per = moe.held_per_token(
        res["layers"], harness.load_named(
            harness.BENCH_DIR, "metrics",
            "moe_held_per_token.generate")["params"])
    assert 0.5 < per < 2.5           # 1.5 under even routing


def test_token_altered_where_it_is_produced(monkeypatch):
    from analytics_zoo_tpu.pipeline.inference.generation import \
        GenerationEngine
    step = GenerationEngine.step

    def altered(self, active):
        toks = step(self, active)
        if self._step_id % 7 == 0:
            toks = (np.asarray(toks) + 1) % self.net.vocab
        return toks
    monkeypatch.setattr(GenerationEngine, "step", altered)
    line, _res = _cell()
    assert line["correct"] is False
    assert line["compared"]["logit_gap"]["value"] > \
        line["compared"]["logit_gap"]["limit"]


def test_the_wrong_experts_held(monkeypatch):
    """The engine is given experts 8-15's weights where it holds
    0-7 (in the cell: 40-79 for 0-39); the reference makes 0-7's."""
    from benchmark import weights_deepseek as wd
    from benchmark.drivers import generate_moe
    layer = wd.layer

    def swapped(cfg, seed, dtype):
        return {**wd.embeddings(cfg, seed, dtype),
                "layers": [layer(cfg, seed, i, dtype, experts=(8, 8))
                           for i in range(cfg["n_layer"])]}
    monkeypatch.setattr(generate_moe.wd, "weights", swapped)
    line, _res = _cell()
    assert line["correct"] is False, line["compared"]


def test_traced_line_reports_the_counter_metrics():
    line, _res = run.run_cell(
        "deepseek-generate-toy", 5, 3.0, True, jax.devices()[:1],
        time.perf_counter(), root=DATA)
    # the CPU has no device plane: the trace's metrics are left out,
    # the program's spans and counters are read
    assert "moe_held_per_token.generate" in line["metrics"]
    assert "prefill_share_pct.generate" in line["metrics"]
    assert "decode_step_roofline_moe" not in line["metrics"]


def test_flops_and_bytes_against_the_hand_arithmetic():
    cfg = harness.load_named(harness.BENCH_DIR, "configs",
                             "deepseek-v2-ep4")
    p = fd.params(cfg)
    assert abs(p["attention"] - 149.23e6) < 0.005e6
    assert abs(p["dense_mlp"] - 188.74e6) < 0.005e6
    assert abs(p["shared"] - 47.19e6) < 0.005e6
    assert abs(p["expert"] - 23.59e6) < 0.005e6
    assert abs(p["expert_layer"] - 1140.96e6) < 0.02e6
    assert abs(p["dense_layer"] - 337.98e6) < 0.02e6
    assert abs(2 * p["total"] - 10.33e9) < 0.005e9
    assert abs(fd.experts_touched(cfg, 16) - 18.3) < 0.05
    assert abs(fd.routed_experts_min_bytes(cfg, 16, 2) - 3.45e9) \
        < 0.01e9
    # the issue's 5.96 GB leaves out the routers (6.6 MB) and norms
    step = fd.decode_step_min_bytes(cfg, 16, 0, 2, 2)
    assert abs(step - 5.96e9) < 0.015e9, step
    assert fd.latent_row_bytes(cfg, 2) == 1152
    rows = 16 * 500
    assert fd.decode_step_min_bytes(cfg, 16, rows, 2, 2) - step == \
        rows * 5 * 1152
    # one token: 2 x (attention x 5 + dense MLP + 4 x (shared +
    # router + 1.5 experts)) + 81920 FLOPs a position and layer
    active = 5 * p["attention"] + p["dense_mlp"] + 4 * (
        p["shared"] + p["router"] + 1.5 * p["expert"])
    assert fd.token_flops(cfg, 0, False) == 2.0 * active
    assert fd.token_flops(cfg, 100, False) - 2.0 * active == \
        5 * 81920 * 100
    assert fd.token_flops(cfg, 0, True) - 2.0 * active == \
        2.0 * 5120 * 25600
    # a mix's answers fit the batcher's cap the driver asks for
    from benchmark import traffic
    mix = harness.load_named(harness.BENCH_DIR, "traffic",
                             "longanswer16")
    sizes = traffic.size_pool(mix)
    assert max(o for _p, o in sizes) <= mix["max_new_cap"] == 512
    assert min(o for _p, o in sizes) >= 64
    assert all(p_ + o <= 2048 for p_, o in sizes)
