"""The dots3-note generate cell at toy size on the CPU, through
``run.run_cell``: a sound run is correct and counts what its new
mechanisms did; a wrong chosen set (in the chunks, and in the decode
steps alone), a window off by one and a dropped gate (each where the
served program computes it, the reference left alone) are not. The
new FLOP and byte functions against the issue's hand arithmetic and
against the tree the program builds."""

import os
import time

import jax
import numpy as np
import pytest

from benchmark import flops_dots3 as f3, harness, run

# a root of its own: `test_selfcheck.py` names the cells of `data/`
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "data_dots3")


def _cell(seconds: float = 3.0, trace: bool = False, seed=2 ** 31 + 17):
    return run.run_cell("dots3-generate-toy", seed, seconds, trace,
                        jax.devices()[:1], time.perf_counter(),
                        root=DATA)


def test_sound_run_is_correct_and_counts_its_mechanisms():
    line, res = _cell()
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    d = res["layers"]["counters"]
    # every prompt of the mix is longer than a chunk of 16 and than
    # the 16 keys an indexer keeps
    assert d["zoo_tpu_serving_gen_prefill_chunks_total"] > 0
    assert 0 < d["zoo_tpu_dsa_keys_selected_total"] < \
        d["zoo_tpu_dsa_keys_visible_total"]
    assert d["zoo_tpu_window_pages_recycled_total"] > 0
    assert 0 < d["zoo_tpu_moe_assignments_held_total"] < \
        d["zoo_tpu_moe_assignments_total"]
    chunks = [s for s in res["layers"]["spans"]
              if s["name"] == "decode/prefill_chunk"]
    # one chunk program a span, whatever the prompts mid-prefill
    assert chunks and all(
        0 < s["fields"]["tokens"] <= 16 <= 16 * s["fields"]["n"]
        for s in chunks)
    assert any(s["fields"]["context"] >= 16 for s in chunks)


def _faulty(monkeypatch, spoil):
    from benchmark.drivers import generate_dots3
    make = generate_dots3.make_net

    def made(cfg):
        net = make(cfg)
        for att in set(net.attentions):
            spoil(att)
        return net
    monkeypatch.setattr(generate_dots3, "make_net", made)


def test_a_wrong_chosen_set_is_not_correct(monkeypatch):
    """The chunk programs keep the 16 keys of LOWEST index score."""
    from analytics_zoo_tpu.ops.attention import topk_mask
    from analytics_zoo_tpu.pipeline.api.keras.layers import decoder
    monkeypatch.setattr(decoder, "topk_mask",
                        lambda s, vis, k: topk_mask(-s, vis, k))
    line, _res = _cell()
    assert line["correct"] is False, line["compared"]


def test_a_wrong_chosen_set_in_decode_is_not_correct(monkeypatch):
    """The chunk programs choose soundly; the decode step keeps the
    16 keys of LOWEST index score (its own selection: `lax.top_k`
    over the cached index keys' scores)."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import decoder
    scores = decoder.index_scores
    monkeypatch.setattr(
        decoder, "index_scores",
        lambda q, w, k: scores(q, w, k) * (-1 if q.shape[1] == 1
                                           else 1))
    line, _res = _cell()
    assert line["correct"] is False, line["compared"]


def test_a_window_off_by_one_is_not_correct(monkeypatch):
    def narrower(att):
        if att.window:
            att.window -= 1
    _faulty(monkeypatch, narrower)
    line, _res = _cell()
    assert line["correct"] is False, line["compared"]


def test_a_dropped_gate_is_not_correct(monkeypatch):
    def ungated(att):
        att.gate = False
    _faulty(monkeypatch, ungated)
    line, _res = _cell()
    assert line["correct"] is False, line["compared"]
    assert line["compared"]["logit_gap"]["value"] > \
        line["compared"]["logit_gap"]["limit"]


def test_traced_line_reports_the_counter_and_span_metrics():
    line, _res = _cell(trace=True, seed=5)
    # the CPU has no device plane: the trace's metrics are left out,
    # the program's spans and counters are read
    for name in ("dsa_keep_pct.generate", "prefill_chunk_ms.generate",
                 "moe_held_per_token.generate"):
        assert name in line["metrics"], line["metrics"].keys()
    assert 0 < line["metrics"]["dsa_keep_pct.generate"]["value"] < 100
    for name in ("mfu.generate_dots3", "decode_step_roofline_dots3",
                 "dsa_share_pct.generate", "swa_share_pct.generate"):
        assert name not in line["metrics"]


def test_readers_return_nothing_where_the_program_has_nothing():
    """A parent that has no such span, counter or scope: every new
    reader leaves its metric out and none raises."""
    from benchmark.readers import dots3, program
    cfg = harness.load_named(harness.BENCH_DIR, "configs",
                             "dots3-note-prev-ep8")
    ctx = {"config": cfg, "counters": {}, "spans": [
        {"name": "decode/prefill_chunk", "t_start": 1.0, "dur_s": 0.1,
         "fields": {"n": 1}}], "trace": None, "peak": None}
    for name in ("mfu.generate_dots3", "decode_step_roofline_dots3",
                 "dsa_keep_pct.generate"):
        d = harness.load_named(harness.BENCH_DIR, "metrics", name)
        fn = getattr(dots3, d["reader"].split(":")[1])
        assert fn(ctx, d["params"]) is None
    d = harness.load_named(harness.BENCH_DIR, "metrics",
                           "dsa_share_pct.generate")
    assert program.scope_share_pct(ctx, d["params"]) is None


def test_flops_and_bytes_against_the_hand_arithmetic():
    cfg = harness.load_named(harness.BENCH_DIR, "configs",
                             "dots3-note-prev-ep8")
    p = f3.params(cfg)
    # the issue's table (its 144.05 and 90.83 leave out the norms)
    assert abs(p["full_attention"] - 144.05e6) < 0.01e6
    assert abs(p["sliding_attention"] - 90.83e6) < 0.01e6
    assert abs(p["expert"] - 23.59e6) < 0.005e6
    assert abs(p["dense_mlp"] - 212.34e6) < 0.005e6
    assert abs(p["router"] - 1.31e6) < 0.005e6
    assert abs(p["embed"] + p["head"] - 194.6e6) < 0.05e6
    assert abs(f3.param_bytes(cfg, 2) - 10.02e9) < 0.005e9
    # to the byte: the tree `dots3_note_decoder` builds
    from benchmark.drivers.generate_dots3 import make_net
    tree = jax.eval_shape(lambda: make_net(cfg).build(
        jax.random.key(0), (16,)))
    assert p["total"] == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    # one token at position 9999 (10000 keys visible): 2 a weight,
    # the indexer on 10000 keys, attention on 2048 and on 513
    active = 3 * p["full_attention"] + 3 * p["sliding_attention"] + \
        p["dense_mlp"] + 5 * (p["shared"] + p["router"] +
                              8 * (32 / 256) * p["expert"])
    want = 2.0 * active + 3 * (2 * 64 * 128 * 10000 +
                               2 * 128 * 320 * 2048) + \
        3 * 2 * 64 * 384 * 513
    assert abs(f3.token_flops(cfg, 9999, False) - want) < 1.0
    assert f3.token_flops(cfg, 9999, True) - want == \
        2.0 * 5120 * 19008
    # a chunk is its tokens one by one
    assert abs(f3.span_flops(cfg, 4096, 2048, 0.0) - sum(
        f3.token_flops(cfg, 4096 + i, False) for i in range(2048))) \
        < 1e-6 * f3.span_flops(cfg, 4096, 2048, 0.0)
    # a decode step of 8 slots at 14000 tokens each: the index keys
    # of every token, 2048 latent rows and 513 window rows a slot
    rows = f3.cache_row_bytes(cfg, 2)
    assert rows == {"latent": 1152, "index": 256, "window": 2176}
    step0 = f3.decode_step_min_bytes(cfg, 8, 0, 2, 2)
    step = f3.decode_step_min_bytes(cfg, 8, 8 * 14000, 2, 2)
    assert step - step0 == 3 * (8 * 14000 * 256 + 8 * 2048 * 1152) + \
        3 * 8 * 513 * 2176
    assert 3.5e9 < step0 < 4.6e9, step0     # the issue's "about 4.0"
    # the mix: every prompt past a chunk and past index_topk
    from benchmark import traffic
    mix = harness.load_named(harness.BENCH_DIR, "traffic", "longdoc8")
    sizes = traffic.size_pool(mix)
    assert min(p_ for p_, _o in sizes) >= 3072
    assert max(p_ + o for p_, o in sizes) <= 32768
    assert max(o for _p, o in sizes) <= mix["max_new_cap"] == 256
    assert min(o for _p, o in sizes) >= 32
