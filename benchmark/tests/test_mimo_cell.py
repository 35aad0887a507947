"""The MiMo-V2-Flash generate cell at toy size on the CPU, through
``run.run_cell``: a sound run is correct, takes both admission paths
in one queue and counts what its mechanisms did; a window off by one,
a dropped sink, heads grouped ``j % G``, the rotary on the whole
head, a dropped value scale and the wrong experts held (each where
the served program computes it, the reference left alone) are not.
The new FLOP and byte functions against the issue's hand arithmetic
and against the tree the program builds."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_mimo as fm, harness, run, selfcheck

# a root of its own: `test_selfcheck.py` names the cells of `data/`
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "data_mimo")


def _cell(seconds: float = 3.0, trace: bool = False, seed=2 ** 31 + 17):
    return run.run_cell("mimo-generate-toy", seed, seconds, trace,
                        jax.devices()[:1], time.perf_counter(),
                        root=DATA)


def test_toy_cell_parses():
    assert set(selfcheck.check_data_files(DATA)) == \
        {"mimo-generate-toy"}


def test_sound_run_is_correct_and_takes_both_admission_paths():
    line, res = _cell()
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    d = res["layers"]["counters"]
    assert d["zoo_tpu_serving_gen_prefill_chunks_total"] > 0
    assert d["zoo_tpu_window_pages_recycled_total"] > 0
    assert d["zoo_tpu_dsa_keys_visible_total"] == 0
    assert 0 < d["zoo_tpu_moe_assignments_held_total"] < \
        d["zoo_tpu_moe_assignments_total"]
    assert 0 < d["zoo_tpu_decode_pages_live_total"] < \
        d["zoo_tpu_decode_pages_table_total"]
    spans = res["layers"]["spans"]
    chunks = [s for s in spans if s["name"] == "decode/prefill_chunk"]
    assert chunks and all(
        0 < s["fields"]["tokens"] <= 16 for s in chunks)
    assert any(s["fields"]["context"] >= 16 for s in chunks)
    # whole prompts in one-row programs beside them, in one queue
    whole = [s for s in spans if s["name"] == "decode/prefill"
             and s["fields"]["calls"] > 0]
    parked = [s for s in spans if s["name"] == "decode/prefill"
              and s["fields"]["calls"] == 0]
    assert whole and parked
    assert all(s["fields"]["bucket"] <= 32 for s in whole)


def _faulty(monkeypatch, spoil):
    from benchmark.drivers import generate_mimo
    make = generate_mimo.make_net

    def made(cfg):
        net = make(cfg)
        for att in set(net.attentions):
            spoil(att)
        return net
    monkeypatch.setattr(generate_mimo, "make_net", made)


def _narrower(att):
    if att.window:
        att.window -= 1


def _no_sink(att):
    att.sink = False


def _modulo_groups(att):
    project = att._project

    def wrong(p, x, positions):
        q, row = project(p, x, positions)
        return jnp.swapaxes(q.reshape(
            q.shape[:-3] + (att.rep, att.n_kv, att.k_dim)), -3, -2), row
    att._project = wrong


def _rotary_everywhere(att):
    from analytics_zoo_tpu.pipeline.api.keras.layers import YarnRope
    att.rope = YarnRope(att.k_dim, theta=att.rope.theta)


def _unscaled_values(att):
    att.value_scale = 1.0


@pytest.mark.parametrize("spoil", [
    _narrower, _no_sink, _modulo_groups, _rotary_everywhere,
    _unscaled_values], ids=lambda f: f.__name__.strip("_"))
def test_a_fault_in_the_attention_is_not_correct(monkeypatch, spoil):
    _faulty(monkeypatch, spoil)
    line, _res = _cell()
    assert line["correct"] is False, line["compared"]


def test_the_wrong_experts_held_is_not_correct(monkeypatch):
    """The program computes with experts 8-15's weights under the
    ids 0-7; the reference holds 0-7."""
    from benchmark.drivers import generate_mimo
    wm = generate_mimo.wm
    layer = wm.layer
    monkeypatch.setattr(
        wm, "weights", lambda cfg, seed, dtype: {
            **wm.embeddings(cfg, seed, dtype),
            "layers": [layer(cfg, seed, i, dtype, experts=(8, 8))
                       for i in range(cfg["n_layer"])]})
    line, _res = _cell()
    assert line["correct"] is False, line["compared"]


def test_traced_line_reports_the_counter_and_span_metrics():
    line, _res = _cell(trace=True, seed=5)
    # the CPU has no device plane: the trace's metrics are left out,
    # the program's spans and counters are read
    for name in ("kv_live_pct.generate", "prefill_chunk_ms.generate",
                 "moe_held_per_token.generate",
                 "latency_p95_ms.generate"):
        assert name in line["metrics"], line["metrics"].keys()
    assert 0 < line["metrics"]["kv_live_pct.generate"]["value"] < 100
    for name in ("mfu.generate_mimo", "decode_step_roofline_mimo",
                 "moe_experts_roofline_mimo", "gqa_share_pct.generate",
                 "zoo_paged_gqa_decode_roofline",
                 "swa_share_pct.generate"):
        assert name not in line["metrics"]


def test_readers_return_nothing_where_the_program_has_nothing():
    """A parent that has no such span, counter, scope or kernel:
    every new reader leaves its metric out and none raises."""
    from benchmark.readers import dots3, mimo, program
    cfg = harness.load_named(harness.BENCH_DIR, "configs",
                             "mimo-v2-flash-ep16")
    bare = {"config": cfg, "counters": {}, "spans": [
        {"name": "decode/step", "t_start": 1.0, "dur_s": 0.1,
         "fields": {"n": 4}}], "trace": None, "peak": None}
    traced = dict(bare, traced_wall=(0.0, 2.0), peak={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"modules": {}, "program": {"scope_s": {
            "unscoped": 1.0}}})
    for ctx in (bare, traced):
        for name in ("mfu.generate_mimo", "decode_step_roofline_mimo",
                     "moe_experts_roofline_mimo",
                     "zoo_paged_gqa_decode_roofline"):
            d = harness.load_named(harness.BENCH_DIR, "metrics", name)
            fn = getattr(mimo, d["reader"].split(":")[1])
            assert fn(ctx, d["params"]) is None, name
        d = harness.load_named(harness.BENCH_DIR, "metrics",
                               "kv_live_pct.generate")
        assert dots3.keep_pct(ctx, d["params"]) is None
        d = harness.load_named(harness.BENCH_DIR, "metrics",
                               "gqa_share_pct.generate")
        assert program.scope_share_pct(ctx, d["params"]) is None


def test_readers_read_a_traced_step():
    """One traced second: 10 steps of 16 slots that hold 2000 pages,
    a chunk and a whole prompt; the step module 20 ms, 2 ms of it in
    the paged kernel and 5 ms under the experts' scope."""
    from benchmark.readers import mimo
    cfg = harness.load_named(harness.BENCH_DIR, "configs",
                             "mimo-v2-flash-ep16")
    spans = [{"name": "decode/step", "t_start": 10.0 + i / 10,
              "dur_s": 0.05, "fields": {"n": 16, "pages_live": 2000}}
             for i in range(10)]
    spans += [{"name": "decode/prefill_chunk", "t_start": 10.5,
               "dur_s": 0.1, "fields": {"n": 1, "tokens": 2048,
                                        "context": 4096}},
              {"name": "decode/admit", "t_start": 10.2, "dur_s": 0.1,
               "fields": {"slot": 1, "prompt_len": 512}},
              {"name": "decode/admit", "t_start": 10.3, "dur_s": 0.1,
               "fields": {"slot": 2, "prompt_len": 9047}}]
    ctx = {"config": cfg, "spans": spans, "traced_wall": (10.0, 11.0),
           "counters": {},
           "traced_counters": {
               "zoo_tpu_moe_assignments_total": 8 * 6 * 160,
               "zoo_tpu_moe_assignments_held_total": 6 * 80},
           "weight_bytes": 2, "kv_value_bytes": 2,
           "peak": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9},
           "trace": {"modules": {"jit__step_fn": {
               "count": 10, "total_s": 0.2}},
               "program": {"scope_s": {"decode/moe_experts": 0.05},
                           "kernel_s": {
                               "zoo_paged_gqa_decode": 0.02}}}}
    rows = 2000 * 16 - 16 * 8
    work = 160 * fm.token_flops(cfg, rows / 16, True) + \
        fm.span_flops(cfg, 4096, 2048, 0.0) + \
        fm.span_flops(cfg, 0, 512, 1.0)
    assert abs(mimo.mfu_generate(ctx, {}) -
               100 * work / 197e12) < 1e-9
    step = fm.decode_step_min_bytes(cfg, 16, rows, 2, 2, 0.5)
    assert abs(mimo.decode_step_roofline(ctx, {"module": "_step_fn"})
               - 100 * step / 819e9 / 0.02) < 1e-9
    experts = fm.routed_experts_min_bytes(cfg, 16, 2, 0.5)
    assert abs(mimo.experts_roofline(
        ctx, {"module": "_step_fn", "scope": "decode/moe_experts"})
        - 100 * experts / 819e9 / 0.005) < 1e-9
    nbytes, ops = fm.paged_decode_work(cfg, 160, 10 * rows, 2)
    assert nbytes / 819e9 > ops / 197e12
    got = mimo.kernel_roofline(ctx, {"kernel": "zoo_paged_gqa_decode"})
    assert abs(got - 100 * nbytes / 819e9 / 0.02) < 1e-9
    assert 0 < got < 100


def test_kernel_times_sums_the_named_operations():
    from benchmark.reduce.kernels import kernel_times

    class Ev:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Profile:
        planes = [Plane("/device:TPU:0", [Line("XLA Ops", [
            Ev("%zoo_paged_gqa_decode.3 = (f32[16,64,512]) "
               "custom-call()", 0, 700),
            Ev("%fusion.1 = bf16[16,4096] fusion()", 800, 100),
            Ev("%zoo_paged_gqa_decode.4 = (f32[16,64,1024]) "
               "custom-call()", 1000, 300)])]),
            Plane("/host:CPU", [])]

    assert kernel_times(Profile, ("zoo_paged_gqa_decode",)) == \
        {"zoo_paged_gqa_decode": 1e-6}
    assert kernel_times(Profile, ("zoo_flash_fwd",)) == {}


def test_flops_and_bytes_against_the_hand_arithmetic():
    cfg = harness.load_named(harness.BENCH_DIR, "configs",
                             "mimo-v2-flash-ep16")
    p = fm.params(cfg)
    # the issue's table
    assert abs(p["full_attention"] - 89.13e6) < 0.005e6
    assert abs(p["sliding_attention"] - 94.37e6) < 0.005e6
    assert p["sliding_attention"] - p["full_attention"] == \
        4096 * 4 * (192 + 128) + 64
    assert abs(p["expert"] - 25.17e6) < 0.005e6
    assert abs(p["dense_mlp"] - 201.33e6) < 0.005e6
    assert abs(p["router"] - 1.05e6) < 0.005e6
    assert abs(p["embed"] + p["head"] - 156.2e6) < 0.05e6
    assert abs(p["total"] - 3430.0e6) < 0.1e6
    assert abs(fm.param_bytes(cfg, 2) - 6.86e9) < 0.005e9
    # to the element: the tree `mimo_v2_flash_decoder` builds
    from benchmark.drivers.generate_mimo import make_net
    tree = jax.eval_shape(lambda: make_net(cfg).build(
        jax.random.key(0), (16,)))
    assert p["total"] == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    # one token at position 9999 (10000 keys visible): 2 a weight,
    # attention on 10000 keys in 2 layers and on 128 in 5
    active = 2 * p["full_attention"] + 5 * p["sliding_attention"] + \
        p["dense_mlp"] + 6 * (p["router"] +
                              8 * (16 / 256) * p["expert"])
    want = 2.0 * active + 2 * 2 * 64 * 320 * 10000 + \
        5 * 2 * 64 * 320 * 128
    assert abs(fm.token_flops(cfg, 9999, False) - want) < 1.0
    assert fm.token_flops(cfg, 9999, True) - want == \
        2.0 * 4096 * 19072
    # a chunk is its tokens one by one
    assert abs(fm.span_flops(cfg, 4096, 2048, 0.0) - sum(
        fm.token_flops(cfg, 4096 + i, False) for i in range(2048))) \
        < 1e-6 * fm.span_flops(cfg, 4096, 2048, 0.0)
    rows = fm.cache_row_bytes(cfg, 2)
    assert rows == {"full": 2560, "sliding": 5120}
    # a decode step of 16 slots, one at 17000 and fifteen at 200:
    # every live row of the two full layers, 127 rows a slot of the
    # five sliding ones
    live = 17000 + 15 * 200
    step0 = fm.decode_step_min_bytes(cfg, 16, 0, 2, 2)
    step = fm.decode_step_min_bytes(cfg, 16, live, 2, 2)
    assert step - step0 == 2 * live * 2560 + 5 * 16 * 127 * 5120
    # 16 tokens reach 16 x (1 - (248/256)^16) = 6.37 of the 16 held
    assert abs(fm.experts_touched(cfg, 16) - 6.373) < 0.001
    assert abs(fm.experts_touched(cfg, 16, 0.5) - 6.373) < 0.001
    # 1.87 GB outside the routed experts, 6 x 6.37 experts of 50 MB
    assert 3.7e9 < step0 < 3.9e9, step0
    # the mix: both admission paths, the sizes the issue names
    from benchmark import traffic
    mix = harness.load_named(harness.BENCH_DIR, "traffic", "mixed16")
    sizes = traffic.size_pool(mix)
    prompts = sorted(p_ for p_, _o in sizes)
    assert prompts[:3] == [64, 116, 162] and \
        prompts[-4:] == [5066, 6468, 9047, 16840]
    assert sum(p_ <= 2048 for p_ in prompts) == 22
    assert max(p_ + o for p_, o in sizes) <= 32768
    assert (min(o for _p, o in sizes), max(o for _p, o in sizes)) \
        == (44, 512) and mix["max_new_cap"] == 512
