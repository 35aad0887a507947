"""The readers of the batcher's records of a request's life and of a
pass (`readers/requests.py`) on hand-made spans and counters, the
seven metric files they serve, and a toy cell of its own that lists
the seven: its CPU rehearsal returns every one."""

import os
import time

import jax
import pytest

from benchmark import harness, run, selfcheck
from benchmark.readers import requests

# a root of its own: `test_selfcheck.py` names the cells of `data/`
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "data_requests")
SEVEN = ["first_token_p95_ms.generate", "token_gap_ms.generate",
         "token_stall_p95_ms.generate",
         "gap_behind_prompt_pct.generate", "pass_host_ms.generate",
         "loop_wait_pct.generate", "steps_ahead_pct.generate"]
GAPS = "zoo_tpu_serving_gen_token_gap_seconds"
BEHIND = "zoo_tpu_serving_gen_token_gap_behind_prompt_seconds_total"


def _span(name, dur_s, **fields):
    return {"name": name, "trace_id": "t", "t_start": 1.0,
            "dur_s": dur_s, "fields": fields}


def _params(metric):
    return harness.load_named(harness.BENCH_DIR, "metrics",
                              metric)["params"]


def _read(metric, ctx):
    d = harness.load_named(harness.BENCH_DIR, "metrics", metric)
    mod, fn = d["reader"].split(":")
    assert mod in ("requests", "spans", "counters")
    module = __import__(f"benchmark.readers.{mod}", fromlist=[fn])
    return getattr(module, fn)(ctx, d["params"])


def test_field_p95_needs_twenty_records():
    retires = [_span("decode/retire", 1.0, tokens=9,
                     gap_max_s=0.001 * i) for i in range(1, 20)]
    ctx = {"spans": retires}
    assert _read("token_stall_p95_ms.generate", ctx) is None
    ctx["spans"] = retires + [_span("decode/retire", 1.0, tokens=9,
                                    gap_max_s=0.5)]
    assert _read("token_stall_p95_ms.generate", ctx) == \
        pytest.approx(500.0)
    # a record from before the field (the parent's) is not a reading
    ctx["spans"] = retires + [_span("decode/retire", 1.0, tokens=9)]
    assert _read("token_stall_p95_ms.generate", ctx) is None
    # the accepted span reader serves `decode/first_token` as it is
    firsts = [_span("decode/first_token", 0.01 * i, path="prefill")
              for i in range(1, 21)]
    assert _read("first_token_p95_ms.generate",
                 {"spans": firsts}) == pytest.approx(200.0)
    assert _read("first_token_p95_ms.generate",
                 {"spans": firsts[:19]}) is None


def test_pass_host_and_the_loops_slack():
    its = [_span("decode/iteration", 0.010, wait_s=0.007,
                 dispatch_s=0.001, programs=1),
           _span("decode/iteration", 0.006, wait_s=0.005,
                 dispatch_s=0.001, programs=2),
           _span("decode/step", 0.005, fetch_s=0.004)]
    ctx = {"spans": its}
    assert _read("pass_host_ms.generate", ctx) == pytest.approx(2.0)
    assert _read("loop_wait_pct.generate", ctx) == pytest.approx(75.0)
    # a loop that never waits is paced by the host: 0, a reading
    ctx = {"spans": [_span("decode/iteration", 0.010, wait_s=0.0)]}
    assert _read("loop_wait_pct.generate", ctx) == 0.0
    assert _read("pass_host_ms.generate", ctx) == pytest.approx(10.0)
    # the parent's iterations carry no `wait_s`: nothing, not 0
    ctx = {"spans": [_span("decode/iteration", 0.010, admitted=1)]}
    assert _read("pass_host_ms.generate", ctx) is None
    assert _read("loop_wait_pct.generate", ctx) is None


def test_ratio_of_counters_and_nothing_over_nothing():
    ctx = {"counters": {GAPS: (8.0, 4000), BEHIND: 2.0,
                        "zoo_tpu_decode_steps_ahead_total": 291.0,
                        "zoo_tpu_serving_gen_steps_total": 292.0}}
    assert _read("gap_behind_prompt_pct.generate", ctx) == \
        pytest.approx(25.0)
    assert _read("token_gap_ms.generate", ctx) == pytest.approx(2.0)
    assert _read("steps_ahead_pct.generate", ctx) == \
        pytest.approx(100.0 * 291 / 292)
    # no prompt program ran behind a resident decode: the counter
    # was never born, the gaps were
    ctx = {"counters": {GAPS: (8.0, 4000)}}
    assert _read("gap_behind_prompt_pct.generate", ctx) == 0.0
    # 0 / 0 is no reading, and neither is a program without them
    for empty in ({"counters": {GAPS: (0.0, 0), BEHIND: 0.0,
                                "zoo_tpu_serving_gen_steps_total": 0.0}},
                  {"counters": {}}, {}):
        assert _read("gap_behind_prompt_pct.generate", empty) is None
        assert _read("steps_ahead_pct.generate", empty) is None
        assert _read("token_gap_ms.generate", empty) is None
    assert requests.ratio_pct({}, _params("loop_wait_pct.generate")) \
        is None


@pytest.mark.parametrize("metric", SEVEN)
def test_metric_file(metric):
    d = harness.load_named(harness.BENCH_DIR, "metrics", metric)
    assert d["name"] == metric and d["kind"] == "per_layer"
    assert d["layer"] == "batcher" and d["moves"] == "gen_tok_per_s"
    assert d["source"] in ("program_span", "program_counter")
    assert harness.UNIT.match(d["unit"])
    # in no accepted cell's list and not in BENCHMARK.json: a
    # `benchmark` PR wires them (PERF.md section 7)
    bm = harness.load_json(harness.REPO_DIR, "BENCHMARK.json")
    assert metric not in {m["name"] for m in bm["per_layer"]}


@pytest.fixture(scope="module")
def rehearsal():
    return run.run_cell("requests-generate-toy", 2 ** 31 + 19, 3.0,
                        True, jax.devices()[:1], time.perf_counter(),
                        root=DATA)


def test_toy_cell_lists_the_seven_and_parses():
    cells = selfcheck.check_data_files(DATA)
    assert set(cells) == {"requests-generate-toy"}
    assert cells["requests-generate-toy"]["cell"]["per_layer"] == SEVEN


def test_rehearsal_returns_all_seven(rehearsal):
    line, res = rehearsal
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    got = {m: line["metrics"][m]["value"] for m in SEVEN}
    assert all(v >= 0 for v in got.values()), got
    assert got["steps_ahead_pct.generate"] > 50
    assert 0 < got["loop_wait_pct.generate"] < 100
    assert 0 < got["gap_behind_prompt_pct.generate"] <= 100
    assert got["token_stall_p95_ms.generate"] >= \
        got["token_gap_ms.generate"]
    # both admission paths left their first tokens in the window
    paths = {s["fields"]["path"] for s in res["layers"]["spans"]
             if s["name"] == "decode/first_token"}
    assert paths == {"prefill", "chunked"}
