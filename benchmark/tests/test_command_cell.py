"""The Command A+ generate cell at toy size on the CPU, through
``run.run_cell``: a sound run is correct, takes both admission paths
in one queue, crosses the window in its chunks and counts the keys
its window layers read; a sequential block, a rotate-half rotary, a
rotary on the full layer, a window one key short, a summed shared
path and the wrong experts held (each where the served program
computes it, the reference left alone) are not. A program without
the decoder fails at once. The new FLOP and byte functions against
the sizes reckoned by hand and the readers against a traced step."""

import os
import time

import jax
import pytest

from benchmark import flops_command as fc, harness, run, selfcheck

# a root of its own: `test_selfcheck.py` names the cells of `data/`
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "data_command")


def _cell(seconds: float = 3.0, trace: bool = False, seed=2 ** 31 + 31):
    return run.run_cell("commanda-generate-toy", seed, seconds, trace,
                        jax.devices()[:1], time.perf_counter(),
                        root=DATA)


def test_toy_cell_parses():
    assert set(selfcheck.check_data_files(DATA)) == \
        {"commanda-generate-toy"}


def test_sound_run_is_correct_and_reads_its_windows():
    line, res = _cell()
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    d = res["layers"]["counters"]
    assert d["zoo_tpu_serving_gen_prefill_chunks_total"] > 0
    assert d["zoo_tpu_window_pages_recycled_total"] > 0
    assert 0 < d["zoo_tpu_window_keys_read_total"] < \
        d["zoo_tpu_window_keys_causal_total"]
    assert 0 < d["zoo_tpu_moe_assignments_held_total"] < \
        d["zoo_tpu_moe_assignments_total"]
    spans = res["layers"]["spans"]
    chunks = [s for s in spans if s["name"] == "decode/prefill_chunk"]
    assert any(s["fields"]["context"] >= 16 for s in chunks)


def _faulty(monkeypatch, spoil):
    """The served net with ``spoil`` applied to it."""
    from benchmark.drivers import generate_command
    make = generate_command.make_net

    def made(cfg):
        net = make(cfg)
        spoil(net)
        return net
    monkeypatch.setattr(generate_command, "make_net", made)


def _rotate_half(net):
    from analytics_zoo_tpu.pipeline.api.keras.layers import YarnRope
    for att in net.attentions:
        if att.rope is not None:
            att.rope = YarnRope(att.rope.dim, theta=att.rope.theta)


def _rotary_on_the_full_layer(net):
    from analytics_zoo_tpu.pipeline.api.keras.layers import YarnRope
    for att in net.attentions:
        if att.rope is None:
            att.rope = YarnRope(att.k_dim, theta=50000.0,
                                interleaved=True)


def _window_one_key_short(net):
    for att in net.attentions:
        if att.window:
            att.window -= 1


def _summed_shared(net):
    for ffn in set(net.feed_forward):
        ffn.shared_combine = "sum"


@pytest.mark.parametrize("spoil", [
    _rotate_half, _rotary_on_the_full_layer, _window_one_key_short,
    _summed_shared], ids=lambda f: f.__name__.strip("_"))
def test_a_fault_in_the_program_is_not_correct(monkeypatch, spoil):
    _faulty(monkeypatch, spoil)
    line, _res = _cell()
    assert line["correct"] is False, line["compared"]


def test_a_sequential_block_is_not_correct(monkeypatch):
    """The same weights through the sequential block, a second norm
    of the first's gain before the experts."""
    from benchmark.drivers import generate_command
    wc = generate_command.wc
    weights = wc.weights

    def sequential(net):
        net.parallel = False
    _faulty(monkeypatch, sequential)
    monkeypatch.setattr(wc, "weights", lambda cfg, seed, dtype: (
        lambda t: dict(t, layers=[dict(p, norm2=p["norm1"])
                                  for p in t["layers"]]))(
        weights(cfg, seed, dtype)))
    line, _res = _cell()
    assert line["correct"] is False, line["compared"]


def test_the_wrong_experts_held_is_not_correct(monkeypatch):
    """The program computes with experts 4-7's weights under the ids
    0-3; the reference holds 0-3."""
    from benchmark.drivers import generate_command
    wc = generate_command.wc
    layer = wc.layer
    monkeypatch.setattr(
        wc, "weights", lambda cfg, seed, dtype: {
            **wc.embeddings(cfg, seed, dtype),
            "layers": [layer(cfg, seed, i, dtype, experts=(4, 4))
                       for i in range(cfg["n_layer"])]})
    line, _res = _cell()
    assert line["correct"] is False, line["compared"]


def test_a_program_without_the_decoder_fails_at_once(monkeypatch):
    """The parent commit has no `command_a_decoder`: the cell fails
    in ``build``, before anything is placed on the device."""
    from analytics_zoo_tpu.pipeline.api.keras import layers as L
    monkeypatch.delattr(L, "command_a_decoder")
    t = time.perf_counter()
    with pytest.raises(AttributeError, match="command_a_decoder"):
        _cell()
    assert time.perf_counter() - t < 5.0


def test_traced_line_reports_the_counter_and_span_metrics():
    line, _res = _cell(trace=True, seed=5)
    # the CPU has no device plane: the trace's metrics are left out,
    # the program's spans and counters are read
    for name in ("prefill_chunk_ms.generate", "window_keys_pct.generate",
                 "moe_held_per_token.generate",
                 "latency_p95_ms.generate"):
        assert name in line["metrics"], line["metrics"].keys()
    assert 0 < line["metrics"]["window_keys_pct.generate"]["value"] \
        < 100
    for name in ("mfu.generate_command", "decode_step_roofline_command",
                 "moe_experts_roofline_command",
                 "zoo_paged_gqa_decode_roofline_command",
                 "swa_share_pct.generate"):
        assert name not in line["metrics"]


def _traced_ctx(cfg, scope_s, kernel_s=None):
    spans = [{"name": "decode/step", "t_start": 10.0 + i / 10,
              "dur_s": 0.05, "fields": {"n": 16, "pages_live": 9200}}
             for i in range(10)]
    spans += [{"name": "decode/prefill_chunk", "t_start": 10.5,
               "dur_s": 0.1, "fields": {"n": 1, "tokens": 2048,
                                        "context": 6144}}]
    return {"config": cfg, "spans": spans, "traced_wall": (10.0, 11.0),
            "counters": {},
            "traced_counters": {
                "zoo_tpu_moe_assignments_total": 4 * 8 * 160,
                "zoo_tpu_moe_assignments_held_total": 4 * 160},
            "weight_bytes": 2, "kv_value_bytes": 2,
            "peak": {"bf16_flops_per_s": 197e12,
                     "hbm_bytes_per_s": 819e9},
            "trace": {"modules": {"jit__step_fn": {
                "count": 10, "total_s": 0.2}},
                "program": {"scope_s": scope_s,
                            "kernel_s": kernel_s or {}}}}


def test_readers_read_a_traced_step():
    """One traced second: 10 steps of 16 slots that hold 9200 pages
    and a chunk of 2048 behind 6144; the step module 20 ms, 8 ms of it
    under the experts' scope, 5 ms in the paged kernel."""
    from benchmark.readers import command
    cfg = harness.load_named(harness.BENCH_DIR, "configs",
                             "command-a-plus-ep8")
    ctx = _traced_ctx(cfg, {"decode/moe_experts": 0.08},
                      {"zoo_paged_gqa_decode": 0.05})
    rows = 9200 * 16 - 16 * 8
    work = 160 * fc.token_flops(cfg, rows / 16, True) + \
        fc.span_flops(cfg, 6144, 2048, 0.0)
    assert abs(command.mfu_generate(ctx, {}) -
               100 * work / 197e12) < 1e-9
    step = fc.decode_step_min_bytes(cfg, 16, rows, 2, 2, 1.0)
    assert abs(command.decode_step_roofline(
        ctx, {"module": "_step_fn"}) - 100 * step / 819e9 / 0.02) < 1e-9
    got = command.experts_roofline(
        ctx, {"module": "_step_fn", "scope": "decode/moe_experts"})
    # 4 layers x the 16 held experts that 16 tokens at 1 held
    # assignment a token reach, 2 bytes a weight, in 8 ms
    reached = 16 * (1 - (1 - 1 / 16) ** 16)
    assert abs(got - 100 * 4 * reached * fc.params(cfg)["expert"] * 2
               / 819e9 / 0.008) < 1e-9
    got = command.kernel_roofline(ctx, {"kernel": "zoo_paged_gqa_decode"})
    # 160 slot-steps: the full layer's 9192 rows each, three rings of
    # 4095, 4096 bytes a row, in 50 ms
    nbytes = 160 * (9192 + 3 * 4095) * 4096
    assert abs(got - 100 * nbytes / 819e9 / 0.05) < 1e-9
    assert 0 < got < 100


def test_readers_return_nothing_where_the_program_has_nothing():
    """A parent that has no such span, counter, scope or kernel: every
    new reader leaves its metric out and none raises."""
    from benchmark.readers import command, dots3
    cfg = harness.load_named(harness.BENCH_DIR, "configs",
                             "command-a-plus-ep8")
    bare = {"config": cfg, "counters": {}, "spans": [
        {"name": "decode/step", "t_start": 1.0, "dur_s": 0.1,
         "fields": {"n": 4}}], "trace": None, "peak": None}
    traced = dict(bare, traced_wall=(0.0, 2.0), peak={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"modules": {}, "program": {"scope_s": {
            "unscoped": 1.0}}})
    for ctx in (bare, traced):
        for name in ("mfu.generate_command",
                     "decode_step_roofline_command",
                     "moe_experts_roofline_command",
                     "zoo_paged_gqa_decode_roofline_command"):
            d = harness.load_named(harness.BENCH_DIR, "metrics", name)
            fn_ = getattr(command, d["reader"].split(":")[1])
            assert fn_(ctx, d["params"]) is None, name
        d = harness.load_named(harness.BENCH_DIR, "metrics",
                               "window_keys_pct.generate")
        assert dots3.keep_pct(ctx, d["params"]) is None


def test_flops_and_bytes_against_the_hand_arithmetic():
    cfg = harness.load_named(harness.BENCH_DIR, "configs",
                             "command-a-plus-ep8")
    p = fc.params(cfg)
    # the sizes reckoned by hand from the configuration
    assert abs(p["attention"] - 142.61e6) < 0.005e6
    assert abs(p["shared"] - 201.33e6) < 0.005e6
    assert abs(p["attention"] + p["shared"] + p["router"] + p["norm"] +
               16 * p["expert"] - 1149.77e6) < 0.01e6
    assert abs(p["embed"] - 134.22e6) < 0.005e6
    assert abs(p["total"] - 4733.29e6) < 0.01e6
    assert abs(fc.param_bytes(cfg, 2) - 9.47e9) < 0.005e9
    # to the element: the tree `command_a_decoder` builds
    import numpy as np
    from benchmark.drivers.generate_command import make_net
    tree = jax.eval_shape(lambda: make_net(cfg).build(
        jax.random.key(0), (16,)))
    assert p["total"] == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    # a step of 16 slots at about 9.2k cached tokens: 4.15 GB of
    # routed experts, 1.41 GB of K/V, 8.58 GB in all
    rows = 16 * 9200
    assert abs(fc.routed_experts_min_bytes(cfg, 16, 2) - 4.15e9) < 0.01e9
    assert abs(fc.paged_decode_work(cfg, 16, rows, 2)[0] - 1.41e9) \
        < 0.01e9
    assert abs(fc.decode_step_min_bytes(cfg, 16, rows, 2, 2) - 8.58e9) \
        < 0.01e9
    assert fc.cache_row_bytes(cfg, 2) == 4096
    # a prompt token at position 0: 3.16 GFLOP; its attention grows by
    # 64 KFLOP a key in the full layer, and in the window layers until
    # the window is full
    first = fc.span_flops(cfg, 0, 1, 0.0)
    assert abs(first - 3.16e9) < 0.01e9
    assert fc.span_flops(cfg, 4095, 1, 0.0) - first == \
        pytest.approx(4 * 4095 * 65536)
    assert fc.span_flops(cfg, 8191, 1, 0.0) - \
        fc.span_flops(cfg, 4095, 1, 0.0) == pytest.approx(4096 * 65536)
    # a chunk is its tokens one by one
    assert abs(fc.span_flops(cfg, 2048, 2048, 0.0) - sum(
        fc.span_flops(cfg, 2048 + i, 1, 0.0) for i in range(2048))) \
        < 1e-6 * fc.span_flops(cfg, 2048, 2048, 0.0)
    # the mix: every prompt past the window and in chunks, 2 of 32 past
    # 16384, every request inside the context
    from benchmark import traffic
    mix = harness.load_named(harness.BENCH_DIR, "traffic", "rag16")
    sizes = traffic.size_pool(mix)
    assert min(p_ for p_, _o in sizes) > max(
        cfg["sliding_window"], cfg["engine"]["prefill_chunk"])
    assert sum(p_ > 16384 for p_, _o in sizes) == 2
    assert max(p_ + o for p_, o in sizes) <= cfg["engine"]["max_context"]
    assert (min(o for _p, o in sizes), max(o for _p, o in sizes)) == \
        (96, 1024)
    # a step's 16 x 8 grouped-product rows are no prompt bucket's
    from analytics_zoo_tpu.pipeline.inference.generation import \
        prompt_ladder
    rows_ = cfg["engine"]["max_slots"] * cfg["num_experts_per_tok"]
    assert rows_ == 128
    assert all(b * cfg["num_experts_per_tok"] != rows_
               for b in prompt_ladder(cfg["engine"]["max_context"]))


def test_the_drain_lasts_as_long_as_the_longest_answer(monkeypatch):
    from benchmark.drivers import generate_command as gc, generate_mimo
    mix = harness.load_named(harness.BENCH_DIR, "traffic", "rag16")
    seen = []
    monkeypatch.setattr(generate_mimo, "run", lambda loaded, **kw:
                        seen.append(generate_mimo.DRAIN_S))
    gc.run({"traffic": mix})
    assert seen == [pytest.approx(102.4)]
    assert generate_mimo.DRAIN_S == 60.0
