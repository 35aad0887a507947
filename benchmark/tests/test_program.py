"""The readers over the program's own spans (`readers/program.py`) on
hand-made contexts, through their metric files and the harness; the
same readers over a rehearsed toy cell of each driver; and the
reduction of a trace to the program's names (`reduce/program.py`) on
``fixtures/v5e_program.xplane.pb``, recorded on a v5e by
``fixtures/record_program.py``, its numbers worked out by hand from a
listing of its events."""

import os
import time

import jax
import pytest

from benchmark import harness, run
from benchmark.readers import program as readers
from benchmark.reduce import program, trace
from benchmark.tests.conftest import DATA

TRAIN = ["input_gather_ms.train", "input_place_ms.train",
         "dispatch_ms.train", "epoch_turn_ms.train",
         "compile_ms_in_window.train", "idle_unattributed_pct.train",
         "idle_input_pct.train", "bn_share_pct.train"]
GENERATE = ["queue_wait_p95_ms.generate", "prefill_share_pct.generate",
            "loop_host_ms.generate", "token_fetch_ms.generate",
            "compile_ms_in_window.generate",
            "idle_unattributed_pct.generate",
            "kv_update_share_pct.generate"]


def _span(name, dur_s, trace_id="t", t_start=0.0, **fields):
    return {"name": name, "trace_id": trace_id, "t_start": t_start,
            "dur_s": dur_s, "fields": fields}


def _read(names, ctx):
    """The named metrics through their files and `read_layers`."""
    loaded = {"cell": {"per_layer": names},
              "metrics": {m: harness.load_named(harness.BENCH_DIR,
                                                "metrics", m)
                          for m in names}}
    return {m: v["value"]
            for m, v in harness.read_layers(loaded, ctx).items()}


HAND_TRAIN = {
    "window_s": 2.0,
    "spans": [
        _span("train/input_gather", 0.030, "a", rows=128, bytes=77),
        _span("train/input_gather", 0.050, "b", rows=128, bytes=77),
        _span("train/input_place", 0.040, "a", bytes=77),
        _span("train/input_place", 0.020, "b", bytes=77),
        _span("train/step", 0.002, "a", data_wait_s=0.07,
              dispatch_s=0.0015),
        _span("train/step", 0.002, "b", data_wait_s=0.08,
              dispatch_s=0.0005),
        _span("train/epoch_turn", 0.150, "c", epoch=1, fetch_s=0.01),
    ],
    "trace": {"program": {
        "window_s": 4.0,
        "idle_by_span": {"train/input_place": 1.0,
                         "train/input_gather": 0.5,
                         "train/epoch_turn": 0.25,
                         "unattributed": 0.1, "shorter_gaps": 0.01},
        "scope_s": {"bn/stats": 0.6, "bn/apply": 0.2,
                    "conv/convolve": 0.9, "unscoped": 0.3}}},
}
HAND_TRAIN_WANT = {
    "input_gather_ms.train": 40.0, "input_place_ms.train": 30.0,
    "dispatch_ms.train": 1.0, "epoch_turn_ms.train": 150.0,
    "compile_ms_in_window.train": 0.0,
    "idle_unattributed_pct.train": 2.5,
    "idle_input_pct.train": 37.5, "bn_share_pct.train": 40.0,
}

HAND_GENERATE = {
    "window_s": 10.0,
    "spans": (
        [_span("decode/queue_wait", 0.001 * i, f"r{i}")
         for i in range(1, 21)] +
        [_span("decode/iteration", 0.130, "i1", admitted=1, active=8,
               emitted=8, retired=0),
         _span("decode/prefill", 0.050, "i1", n=1, bucket=128,
               prompt_tokens=100),
         _span("decode/step", 0.076, "i1", n=8, dispatch_s=0.002,
               fetch_s=0.073),
         _span("decode/iteration", 0.080, "i2", admitted=0, active=8,
               emitted=8, retired=1),
         _span("decode/step", 0.078, "i2", n=8, dispatch_s=0.002,
               fetch_s=0.075),
         _span("decode/release", 0.001, "i2", slot=0, tokens=9),
         _span("xla/compile", 0.25, "i2", expected=False)]),
    "trace": {"program": {
        "window_s": 4.0,
        "idle_by_span": {"decode/step": 0.1, "unattributed": 0.02},
        "scope_s": {"kv_cache/append": 1.0, "kv_cache/gather": 0.5,
                    "decode/attention": 0.5, "unscoped": 2.0}}},
}
HAND_GENERATE_WANT = {
    "queue_wait_p95_ms.generate": 20.0,       # the 20th of 20
    "prefill_share_pct.generate": 0.5,
    "loop_host_ms.generate": 3.0,             # (4 + 2) / 2
    "token_fetch_ms.generate": 74.0,
    "compile_ms_in_window.generate": 250.0,
    "idle_unattributed_pct.generate": 0.5,
    "kv_update_share_pct.generate": 37.5,
}


@pytest.mark.parametrize("metric,want", sorted(
    {**HAND_TRAIN_WANT, **HAND_GENERATE_WANT}.items()))
def test_metric_by_hand(metric, want):
    ctx = HAND_TRAIN if metric.endswith(".train") else HAND_GENERATE
    assert _read([metric], ctx)[metric] == pytest.approx(want)


@pytest.mark.parametrize("metric", TRAIN + GENERATE)
def test_metric_of_a_program_without_the_spans(metric):
    """The parent of the PR that brought the spans: nothing to read
    is no result and no error; no compile record is 0 ms."""
    ctx = {"window_s": 2.0, "trace": {"busy_s": 1.0, "window_s": 2.0},
           "spans": [_span("train/step", 0.002, data_wait_s=0.07),
                     _span("decode/admit", 0.5, prompt_len=100)]}
    got = _read([metric], ctx)
    if metric.startswith("compile_ms_in_window"):
        assert got == {metric: 0.0}
    else:
        assert got == {}
    assert _read([metric], {"spans": [], "trace": None}) in (
        {}, {metric: 0.0})


def test_metric_files_name_their_layers():
    for m in TRAIN + GENERATE:
        d = harness.load_named(harness.BENCH_DIR, "metrics", m)
        assert d["kind"] == "per_layer" and d["name"] == m
        assert d["moves"] == ("train_img_per_s" if m.endswith(
            ".train") else "gen_tok_per_s")
        assert d["source"] == ("device_trace" if d["reader"].split(
            ":")[1] in ("idle_pct", "scope_share_pct")
            else "program_span")
        mod, fn = d["reader"].split(":")
        assert callable(getattr(__import__(
            f"benchmark.readers.{mod}", fromlist=[fn]), fn))


@pytest.mark.parametrize("cell,names", [
    ("resnet50-train-toy", TRAIN[:5]),
    ("gpt2-generate-toy", GENERATE[1:5]),
])
def test_span_metrics_of_a_rehearsed_cell(cell, names):
    """The spans the readers want are the spans the program writes:
    a toy cell of each driver on the CPU, its context read through
    the new metric files."""
    _line, res = run.run_cell(cell, 2 ** 31 + 5, 2.0, False,
                              jax.devices()[:1], time.perf_counter(),
                              root=DATA)
    got = _read(names, res["layers"])
    assert set(got) == set(names), got
    assert all(v >= 0 for v in got.values())


def test_scope_of():
    assert program.scope_of(
        "jit(f)/while/body/zoo:decode/layer/zoo:kv_cache/append/"
        "scatter") == "kv_cache/append"
    assert program.scope_of(
        "jit(train_step)/transpose(jvp(zoo:bn/stats))/mul:") == \
        "bn/stats"
    assert program.scope_of("jit(step)/dot_general:") == "unscoped"
    assert program.scope_of(None) == "unscoped"


FIXTURE = os.path.join(harness.BENCH_DIR, "fixtures",
                       "v5e_program.xplane.pb")


def test_old_fixture_has_no_program_names():
    path = os.path.join(harness.BENCH_DIR, "fixtures",
                        "v5e_mlp6.xplane.pb")
    red = program.reduce_program_trace(path)
    whole = trace.reduce_trace(path)
    assert red["window_s"] == pytest.approx(whole["window_s"],
                                            abs=1e-12)
    # 470,046 ns of the idle time lie before the host's first event
    assert red["idle_by_span"] == {
        "unattributed": pytest.approx(22208863e-9, abs=1e-12),
        "outside_host_trace": pytest.approx(470046e-9, abs=1e-12)}
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        whole["window_s"] - whole["busy_s"], abs=1e-9)
    assert red["scope_s"] == {"unscoped": pytest.approx(
        whole["busy_s"], abs=1e-12)}
    assert {n.split("/")[0] for n in
            program.op_names(path).values()} == {"jit(step)"}


def test_fixture_idle_by_program_span():
    """By hand from the listing of the fixture's events: 18 runs of
    the program, 17 gaps over 0.5 ms between them (the other 77 add
    up to 125 ns). The device's clock reads about 2 ms behind the
    host's, so each gap lies under the tail of the host phase before
    it as well: that is what the half-cover rule is for."""
    red = program.reduce_program_trace(FIXTURE)
    whole = trace.reduce_trace(FIXTURE)
    assert red["window_s"] == pytest.approx(137992933e-9, abs=1e-12)
    assert whole["busy_s"] == pytest.approx(861552e-9, abs=1e-12)
    idle = {k: round(v * 1e9) for k, v in red["idle_by_span"].items()}
    near = lambda ns: pytest.approx(ns, abs=100)
    # the three gaps of 13.3, 13.7 and 13.5 ms under `fixture/input`
    # (cover 11.3-12.6 ms each), less the 861,585 ns of the first
    # that lie before the host's first event
    assert idle["outside_host_trace"] == 861585
    assert idle["fixture/input"] == near(
        13292793 - 861585 + 13716643 + 13456946)
    # the three bare sleeps (9.9, 9.6, 9.5 ms; 0.9-1.0 ms of each
    # under the tail of `fixture/input`: less than half), and two
    # dispatch gaps of 0.59 and 0.67 ms covered for 0.17 and 0.05 ms
    assert idle["unattributed"] == near(
        9909395 + 9597814 + 9505192 + 590398 + 668592)
    # `fixture/place` on the worker thread (cover 9.4-10.0 ms of
    # 11.3-12.3 ms) beats `fixture/data_wait` on the main thread,
    # which covers more: a wait does not win against work
    assert idle["fixture/place"] == near(12292723 + 12042214 + 11304156)
    # `fixture/data_wait` alone (9.3, 9.7, 9.4 ms; `place`'s tail
    # covers 0.6-0.7 ms: less than half), and the three dispatch
    # gaps of 0.75-0.86 ms that lie wholly under its tail
    assert idle["fixture/data_wait"] == near(
        9301303 + 9651231 + 9375727 + 818316 + 856081 + 751732)
    assert "fixture/step" not in idle      # 2 ms of skew: see above
    assert sum(idle.values()) == near(137992933 - 861552)


def test_fixture_device_time_by_scope():
    """The first fusion (matmul + tanh) was traced under
    ``zoo:fixture/layer/zoo:fixture/matmul``: the innermost scope
    gets its 18 x 23.6 us; the second matmul and the copies ran under
    none."""
    names = program.op_names(FIXTURE)
    assert set(names.values()) == {
        "jit(step)/dot_general:",
        "jit(step)/zoo:fixture/layer/zoo:fixture/matmul/dot_general:"}
    red = program.reduce_program_trace(FIXTURE)
    assert {k: round(v * 1e9) for k, v in red["scope_s"].items()} == {
        "unscoped": 436197, "fixture/matmul": 425355}
    ctx = {"trace": {"program": red}}
    assert readers.scope_share_pct(
        ctx, {"scopes": ["fixture/matmul"]}) == pytest.approx(
        100 * 425355 / 861552)
    assert readers.idle_pct(ctx, {"prefixes": ["fixture/"]}) == \
        pytest.approx(100 * (137131381 - 861585 - 30271423)
                      / 137992933, abs=1e-4)
