"""Tracing layer (common/tracing.py): context propagation, the span
ring buffer, chrome-trace export, and the end-to-end serving/training
wiring (one trace id front-end -> batcher -> model). Tier-1 fast."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.common import observability as obs
from analytics_zoo_tpu.common import tracing


# -- core ------------------------------------------------------------------

def test_trace_mints_and_adopts_ids():
    with tracing.trace("unit/root") as tr:
        assert tr.trace_id and tr.span_id
    with tracing.trace("unit/root", trace_id="req-42") as tr:
        assert tr.trace_id == "req-42"
    # header values are sanitized, not trusted
    assert tracing.sanitize_trace_id("ok-1_2.3") == "ok-1_2.3"
    assert tracing.sanitize_trace_id("bad id\nx") is None
    assert tracing.sanitize_trace_id("a" * 65) is None
    assert tracing.sanitize_trace_id(None) is None


def test_obs_span_joins_ambient_trace():
    with tracing.trace("unit/root") as tr:
        with obs.span("unit/child", step=3):
            pass
    recs = tracing.get_store().spans(tr.trace_id)
    by_name = {r.name: r for r in recs}
    assert set(by_name) == {"unit/root", "unit/child"}
    root, child = by_name["unit/root"], by_name["unit/child"]
    assert root.parent_id is None
    assert child.parent_id == root.span_id
    assert child.fields["step"] == 3


def test_nested_spans_chain_parents():
    with tracing.trace("unit/root") as tr:
        with obs.span("unit/outer"):
            with obs.span("unit/inner"):
                pass
    by_name = {r.name: r for r in
               tracing.get_store().spans(tr.trace_id)}
    assert by_name["unit/inner"].parent_id == \
        by_name["unit/outer"].span_id
    assert by_name["unit/outer"].parent_id == \
        by_name["unit/root"].span_id


def test_span_without_trace_records_nothing():
    with obs.span("unit/orphan"):
        pass
    assert len(tracing.get_store()) == 0


def test_cross_thread_propagation():
    """current() + activate()/record_span() carry a trace into worker
    threads (contextvars do not cross threads by themselves)."""
    got = {}

    def worker(ctx):
        with tracing.activate(ctx):
            with obs.span("unit/worker_span"):
                pass
        tracing.record_span(ctx, "unit/explicit",
                            time.time(), 0.001, rows=4)
        got["done"] = True

    with tracing.trace("unit/root") as tr:
        t = threading.Thread(target=worker,
                             args=(tracing.current(),))
        t.start()
        t.join()
    assert got["done"]
    recs = tracing.get_store().spans(tr.trace_id)
    names = {r.name for r in recs}
    assert {"unit/root", "unit/worker_span", "unit/explicit"} <= names
    root = next(r for r in recs if r.name == "unit/root")
    for r in recs:
        if r.name != "unit/root":
            assert r.parent_id == root.span_id
    explicit = next(r for r in recs if r.name == "unit/explicit")
    assert explicit.fields["rows"] == 4


def test_store_ring_buffer_bound():
    store = tracing.TraceStore(capacity=8)
    for i in range(50):
        store.add(tracing.SpanRecord(
            f"t{i}", f"s{i}", None, "unit/x", time.time(), 0.0,
            "main", {}))
    assert len(store) == 8
    assert store.records()[0].trace_id == "t42"  # oldest evicted


def test_recent_groups_by_trace():
    with tracing.trace("unit/a") as ta:
        with obs.span("unit/a_child"):
            pass
    with tracing.trace("unit/b") as tb:
        pass
    recent = tracing.get_store().recent(10)
    assert [t["trace_id"] for t in recent[:2]] == \
        [tb.trace_id, ta.trace_id]  # newest first
    a = recent[1]
    assert a["n_spans"] == 2
    assert {s["name"] for s in a["spans"]} == \
        {"unit/a", "unit/a_child"}
    json.dumps(recent)  # payload must be JSON-able


# -- disabled: guarded no-op -----------------------------------------------

def test_disabled_is_noop(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_TRACE", "0")
    assert not tracing.enabled()
    with tracing.trace("unit/root", trace_id="x") as tr:
        assert tr.trace_id is None
        # the hot-path guard: span_start bails before any allocation
        assert tracing.span_start("unit/child") is None
        with obs.span("unit/child"):  # still times the histogram
            pass
        tracing.record_span(("t", "s"), "unit/x", time.time(), 0.0)
    assert len(tracing.get_store()) == 0
    assert tracing.current() is None


def test_disabled_span_keeps_metrics(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_TRACE", "0")
    with obs.span("unit/timed"):
        pass
    s = obs.snapshot()
    assert s["zoo_tpu_unit_timed_seconds"]["values"][0]["count"] == 1


# -- chrome-trace export ---------------------------------------------------

def test_chrome_trace_structure():
    with tracing.trace("unit/root") as tr:
        with obs.span("unit/child", rows=2):
            pass
    doc = tracing.to_chrome_trace([tr.trace_id])
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert {m["name"] for m in meta} >= {"process_name",
                                         "thread_name"}
    assert {s["name"] for s in spans} == {"unit/root", "unit/child"}
    child = next(s for s in spans if s["name"] == "unit/child")
    root = next(s for s in spans if s["name"] == "unit/root")
    assert child["pid"] == root["pid"]  # same trace -> same process
    assert child["args"]["parent_id"] == root["args"]["span_id"]
    assert child["args"]["rows"] == 2
    for s in spans:  # ts/dur are microseconds
        assert s["ts"] > 1e15 and s["dur"] >= 0
    json.dumps(doc)


def test_chrome_events_from_event_log_dicts():
    """The exporter accepts parsed event-log lines, which stamp exit
    time (`ts`) rather than `t_start`."""
    evs = tracing.chrome_events([
        {"event": "serving/request", "trace_id": "t1",
         "span_id": "s1", "parent_id": None, "ts": 100.0,
         "dur_s": 0.25, "status": 200},
        {"event": "untraced/event", "ts": 100.0},  # skipped
    ])
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 1
    assert xs[0]["name"] == "serving/request"
    assert xs[0]["ts"] == pytest.approx((100.0 - 0.25) * 1e6)


# -- serving end-to-end ----------------------------------------------------

def _toy_model():
    from analytics_zoo_tpu.pipeline.api.keras import (
        Sequential, layers as L)
    m = Sequential()
    m.add(L.Dense(4, input_shape=(3,)))
    m.add(L.Dense(1))
    m.compile(optimizer="sgd", loss="mse")
    return m


def _server(cls_name="InferenceServer"):
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.pipeline.inference import serving
    im = InferenceModel(supported_concurrent_num=2)
    im.load_keras_net(_toy_model())
    return getattr(serving, cls_name)(im, port=0)


def _post_predict(port, x, trace_id=None):
    headers = {"Content-Type": "application/json"}
    if trace_id:
        headers[tracing.TRACE_HEADER] = trace_id
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict",
        data=json.dumps({"inputs": x.tolist()}).encode(),
        headers=headers)
    return urllib.request.urlopen(req)


def test_serving_single_trace_id_end_to_end(rng):
    """Acceptance: one traced request shows a single trace id
    spanning front-end -> batcher queue/pad/execute -> model."""
    srv = _server().start()
    try:
        # 3 rows never fill a power-of-two bucket -> the pad span runs
        x = rng.randn(3, 3).astype(np.float32)
        resp = _post_predict(srv.port, x, trace_id="req-abc")
        assert json.loads(resp.read())["outputs"]
        assert resp.headers[tracing.TRACE_HEADER] == "req-abc"
        # the batcher records `serving/scatter` after it has resolved
        # the request's future: the answer can overtake the record
        deadline = time.monotonic() + 5.0
        while True:
            dbg = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/traces?n=50"
            ).read())
            if time.monotonic() > deadline or any(
                    s["name"] == "serving/scatter"
                    for t in dbg["traces"] for s in t["spans"]):
                break
            time.sleep(0.01)
    finally:
        srv.stop()
    assert dbg["enabled"] is True
    ours = [t for t in dbg["traces"] if t["trace_id"] == "req-abc"]
    assert len(ours) == 1, dbg["traces"]
    spans = ours[0]["spans"]
    assert all(s["trace_id"] == "req-abc" for s in spans)
    names = {s["name"] for s in spans}
    assert {"serving/request", "serving/queue_wait",
            "serving/pad", "serving/predict",
            "serving/scatter"} <= names
    root = next(s for s in spans if s["name"] == "serving/request")
    assert root["parent_id"] is None
    assert root["fields"]["status"] == 200
    # child spans hang off the request root (directly or nested)
    ids = {s["span_id"] for s in spans}
    for s in spans:
        if s["parent_id"] is not None:
            assert s["parent_id"] in ids


def test_serving_minted_trace_id_when_header_absent(rng):
    srv = _server().start()
    try:
        x = rng.randn(2, 3).astype(np.float32)
        resp = _post_predict(srv.port, x)
        minted = resp.headers[tracing.TRACE_HEADER]
        assert minted  # server minted one and echoed it
    finally:
        srv.stop()
    assert any(r.trace_id == minted for r in
               tracing.get_store().records())


def test_serving_trace_disabled(rng, monkeypatch):
    monkeypatch.setenv("ZOO_TPU_TRACE", "0")
    srv = _server().start()
    try:
        x = rng.randn(2, 3).astype(np.float32)
        resp = _post_predict(srv.port, x, trace_id="ignored")
        assert resp.headers.get(tracing.TRACE_HEADER) is None
        dbg = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/traces").read())
    finally:
        srv.stop()
    assert dbg == {"enabled": False, "traces": []}


def test_native_serving_trace_header(rng):
    """The C++ front-end parses X-Zoo-Trace-Id, hands it to Python
    alongside the path, and echoes it on the response."""
    try:
        srv = _server("NativeInferenceServer")
    except (RuntimeError, OSError):
        pytest.skip("native toolchain unavailable")
    srv.start()
    try:
        x = rng.randn(2, 3).astype(np.float32)
        resp = _post_predict(srv.port, x, trace_id="native-1")
        assert json.loads(resp.read())["outputs"]
        assert resp.headers[tracing.TRACE_HEADER] == "native-1"
        dbg = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/traces?n=50"
        ).read())
    finally:
        srv.stop()
    ours = [t for t in dbg["traces"] if t["trace_id"] == "native-1"]
    assert len(ours) == 1
    assert {"serving/request", "serving/predict"} <= \
        {s["name"] for s in ours[0]["spans"]}


def test_debug_profile_capture(tmp_path, monkeypatch):
    from analytics_zoo_tpu.pipeline.inference import serving
    calls = []

    def fake_capture(out_dir, ms):
        calls.append((out_dir, ms))

    monkeypatch.setattr(serving, "_profiler_capture", fake_capture)
    status, body = serving.handle_profile(
        json.dumps({"dir": str(tmp_path), "ms": 5}).encode())
    assert status == 200 and body["status"] == "capturing"
    serving._profile_thread.join(timeout=10)
    assert calls == [(str(tmp_path), 5.0)]
    # bad requests are structured 400s
    assert serving.handle_profile(b"{nope")[0] == 400
    assert serving.handle_profile(b"{}")[0] == 400
    assert serving.handle_profile(
        json.dumps({"dir": "x", "ms": "NaN?"}).encode())[0] == 400


# -- estimator integration -------------------------------------------------

def test_estimator_step_traces(rng):
    m = _toy_model()
    x = rng.randn(16, 3).astype(np.float32)
    y = rng.randn(16, 1).astype(np.float32)
    m.fit(x, y, batch_size=8, nb_epoch=1)  # 2 steps
    steps = [r for r in tracing.get_store().records()
             if r.name == "train/step"]
    assert len(steps) == 2
    for r in steps:
        assert r.parent_id is None
        assert r.fields["data_wait_s"] >= 0
        assert r.fields["dispatch_s"] >= 0
    assert [r.fields["step"] for r in steps] == [1, 2]


def test_evaluate_traced(rng):
    m = _toy_model()
    x = rng.randn(16, 3).astype(np.float32)
    y = rng.randn(16, 1).astype(np.float32)
    m.fit(x, y, batch_size=8, nb_epoch=1)
    m.evaluate(x, y, batch_size=8)
    recs = tracing.get_store().records()
    runs = [r for r in recs if r.name == "train/eval_run"]
    assert len(runs) == 1
    evals = [r for r in recs if r.name == "train/eval"
             and r.trace_id == runs[0].trace_id]
    assert len(evals) == 1
    assert evals[0].parent_id == runs[0].span_id


# -- PR 27: the program's spans where the work happens, on one clock -------

def _dicts(records):
    return [r.to_dict() for r in records]


def _toy_convnet():
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        BatchNormalization, Convolution2D, Dense, Flatten,
        MaxPooling2D)
    from analytics_zoo_tpu.pipeline.api.keras.models import Sequential
    m = Sequential()
    m.add(Convolution2D(4, 3, 3, border_mode="same", bias=False,
                        input_shape=(8, 8, 3)))
    m.add(BatchNormalization())
    m.add(MaxPooling2D(pool_size=(2, 2)))
    m.add(Flatten())
    m.add(Dense(3))
    return m


@pytest.fixture(scope="module")
def train_records():
    """Every span record of a two-epoch toy `Estimator.train`."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    tracing.reset_tracing()
    ctx = zoo.init_nncontext(seed=0, log_level="WARNING")
    est = Estimator(_toy_convnet(), optimizer="sgd", loss="mse",
                    ctx=ctx)
    rs = np.random.RandomState(0)
    x = rs.rand(32, 8, 8, 3).astype(np.float32)
    y = rs.rand(32, 3).astype(np.float32)
    est.train(x, y, batch_size=8, nb_epoch=2)
    return _dicts(tracing.get_store().records())


@pytest.mark.parametrize("name,count,fields", [
    ("train/input_gather", 8, {"rows", "bytes", "threads", "recycled"}),
    ("train/input_place", 8, {"bytes", "reuse_wait_s"}),
    ("train/step", 8, {"step", "epoch", "data_wait_s", "dispatch_s"}),
    ("train/epoch_turn", 2, {"epoch", "fetch_s"}),
    ("train/flops_lowering", 1, set()),
    ("xla/compile", None, {"expected"}),
])
def test_train_span_in_store_with_fields(train_records, name, count,
                                         fields):
    recs = [r for r in train_records if r["name"] == name]
    assert recs, f"no {name} record"
    if count is not None:
        assert len(recs) == count
    for r in recs:
        assert fields <= set(r["fields"]), r
        assert r["dur_s"] >= 0
    if name == "train/input_gather":
        assert recs[0]["fields"]["rows"] == 8
        assert recs[0]["fields"]["bytes"] == 8 * (8 * 8 * 3 + 3) * 4
        assert {r["thread"] for r in recs} == {"zoo-tpu-prefetch"}
        # 2 KB a batch: one thread, whatever `ingest_threads` allows
        assert {r["fields"]["threads"] for r in recs} == {1}


def test_train_batch_reads_gather_place_step(train_records):
    by_trace = {}
    for r in train_records:
        by_trace.setdefault(r["trace_id"], []).append(r["name"])
    steps = [names for names in by_trace.values()
             if "train/step" in names]
    assert len(steps) == 8
    for names in steps:
        assert {"train/input_gather", "train/input_place",
                "train/step"} <= set(names)
        assert names.index("train/input_gather") < \
            names.index("train/input_place") < \
            names.index("train/step")
    # annotation only, and the removed per-step sync's field is gone
    assert not [r for r in train_records
                if r["name"] == "train/data_wait"]
    assert not [r for r in train_records if "device_s" in r["fields"]]
    # steady state writes three records a step (the ring holds 4096)
    steady = [n for names in steps[1:] for n in names]
    assert len(steady) == 3 * 7


def _toy_engine():
    import jax
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.api.keras.layers.transformer \
        import TransformerLayer
    from analytics_zoo_tpu.pipeline.inference.generation import \
        GenerationEngine
    init_nncontext(seed=0, log_level="WARNING")
    net = TransformerLayer(n_block=2, hidden_size=32, n_head=2,
                           seq_len=32, vocab=61, hidden_p_drop=0.0,
                           attn_p_drop=0.0, embed_p_drop=0.0)
    params = net.build(jax.random.key(0), (32,))
    return GenerationEngine(net, params, max_slots=2, max_context=32,
                            page_size=8)


@pytest.fixture(scope="module")
def decode_records():
    """Every span record of a toy `ContinuousBatcher` run: five
    traced requests over two slots."""
    from analytics_zoo_tpu.pipeline.inference.batching import \
        ContinuousBatcher
    eng = _toy_engine()
    cb = ContinuousBatcher(eng, queue_depth=16).start()
    tracing.reset_tracing()
    try:
        futs = []
        for n, m in [(3, 6), (7, 4), (2, 8), (5, 5), (4, 7)]:
            with tracing.trace("serving/request"):
                futs.append(cb.submit(list(range(1, n + 1)),
                                      max_new_tokens=m))
        for f in futs:
            f.result(timeout=60)
    finally:
        cb.stop()
    return _dicts(tracing.get_store().records())


@pytest.mark.parametrize("name,fields", [
    ("decode/iteration", {"admitted", "active", "emitted",
                          "retired", "dispatch_s", "wait_s",
                          "programs"}),
    ("decode/prefill", {"n", "bucket", "prompt_tokens", "calls",
                        "rows"}),
    ("decode/step", {"n", "dispatch_s", "fetch_s", "pages_live",
                     "pages_table", "ahead"}),
    ("decode/first_token", {"slot", "prompt_len", "path", "chunks",
                            "queue_s"}),
    ("decode/queue_wait", set()),
    ("decode/admit", {"slot", "prompt_len"}),
    ("decode/retire", {"slot", "tokens", "first_token_s",
                       "gap_mean_s", "gap_max_s",
                       "gaps_behind_prompt"}),
])
def test_decode_span_in_store_with_fields(decode_records, name,
                                          fields):
    recs = [r for r in decode_records if r["name"] == name]
    assert recs, f"no {name} record"
    for r in recs:
        assert fields <= set(r["fields"]), r
    if name in ("decode/queue_wait", "decode/admit", "decode/retire",
                "decode/first_token"):
        assert len(recs) == 5       # one a request
    if name == "decode/first_token":
        # the one-row path: the admission's fetch brings the token
        admits = {r["trace_id"]: r["dur_s"] for r in decode_records
                  if r["name"] == "decode/admit"}
        for r in recs:
            assert r["fields"]["path"] == "prefill"
            assert r["dur_s"] == admits[r["trace_id"]]
    if name == "decode/step":
        for r in recs:
            f = r["fields"]
            assert 0 <= f["dispatch_s"] and 0 <= f["fetch_s"]
            assert f["dispatch_s"] + f["fetch_s"] <= r["dur_s"] + 1e-4
    if name == "decode/prefill":
        assert sum(r["fields"]["prompt_tokens"] for r in recs) == \
            3 + 7 + 2 + 5 + 4
        # the engine's ladder starts at its floor; a program holds
        # one prompt, so an admission runs a row a request
        assert all(r["fields"]["bucket"] == 32 for r in recs)
        assert all(r["fields"]["rows"] == r["fields"]["calls"]
                   == r["fields"]["n"] >= 1 for r in recs)
    if name == "decode/iteration":
        assert sum(r["fields"]["admitted"] for r in recs) == 5
        assert sum(r["fields"]["retired"] for r in recs) == 5
        # every token but each request's first comes from a step
        assert sum(r["fields"]["emitted"] for r in recs) == \
            6 + 4 + 8 + 5 + 7 - 5


def test_decode_step_counts_the_pages_its_slots_hold(decode_records):
    """`pages_live` is the pages up to each active slot's cached
    length (its prompt and all but the last of its tokens), from the
    host's own lengths; `pages_table` slots x pages a slot."""
    steps = [r["fields"] for r in decode_records
             if r["name"] == "decode/step"]
    assert all(f["pages_table"] == 2 * 4 for f in steps)
    assert all(f["n"] <= f["pages_live"] <= f["pages_table"]
               for f in steps)
    # a request of prompt n and m tokens decodes m - 1 steps, at
    # cached lengths n, n + 1, ...: pages of 8
    want = sum(-(-(n + j) // 8)
               for n, m in [(3, 6), (7, 4), (2, 8), (5, 5), (4, 7)]
               for j in range(m - 1))
    assert sum(f["pages_live"] for f in steps) == want


def test_decode_step_says_when_it_ran_ahead(decode_records):
    """`ahead` is 1 on a step dispatched while the step before was
    unfetched (whose wait is then that span's `fetch_s`), 0 on one
    dispatched with nothing in flight, which waits for nothing."""
    steps = sorted((r for r in decode_records
                    if r["name"] == "decode/step"),
                   key=lambda r: r["t_start"])
    flags = [r["fields"]["ahead"] for r in steps]
    assert set(flags) == {0, 1} and flags[0] == 0
    assert sum(flags) >= len(flags) - 3     # the loop stays ahead
    for r in steps:
        if not r["fields"]["ahead"]:
            assert r["fields"]["fetch_s"] == 0.0


def test_steps_ahead_counter_follows_the_span():
    from analytics_zoo_tpu.common import observability as obs
    from analytics_zoo_tpu.pipeline.inference.batching import \
        ContinuousBatcher
    before = {n: obs.counter(n).value for n in (
        "zoo_tpu_decode_steps_ahead_total",
        "zoo_tpu_serving_gen_steps_total",
        "zoo_tpu_decode_rows_discarded_total")}
    cb = ContinuousBatcher(_toy_engine(), queue_depth=4).start()
    try:
        cb.submit([1, 2, 3, 4, 5, 6, 7], max_new_tokens=6).result(
            timeout=60)
    finally:
        cb.stop()
    got = {n: obs.counter(n).value - v for n, v in before.items()}
    # five steps behind the prefill's token; all but the first were
    # dispatched with the one before unfetched
    assert got == {"zoo_tpu_decode_steps_ahead_total": 4,
                   "zoo_tpu_serving_gen_steps_total": 5,
                   "zoo_tpu_decode_rows_discarded_total": 0}


def test_decode_page_counters_follow_the_span():
    from analytics_zoo_tpu.common import observability as obs
    from analytics_zoo_tpu.pipeline.inference.batching import \
        ContinuousBatcher
    cb = ContinuousBatcher(_toy_engine(), queue_depth=4).start()
    try:
        cb.submit([1, 2, 3, 4, 5, 6, 7], max_new_tokens=4).result(
            timeout=60)
    finally:
        cb.stop()
    live = obs.counter("zoo_tpu_decode_pages_live_total").value
    table = obs.counter("zoo_tpu_decode_pages_table_total").value
    # three steps at cached lengths 7, 8, 9 over a table of 2 x 4
    assert (live, table) == (1 + 1 + 2, 3 * 8)


def test_decode_children_of_one_iteration(decode_records):
    its = {r["span_id"]: r for r in decode_records
           if r["name"] == "decode/iteration"}
    assert not [r for r in decode_records
                if r["name"] == "decode/release"]
    for name in ("decode/prefill", "decode/step"):
        for r in decode_records:
            if r["name"] == name:
                parent = its[r["parent_id"]]
                assert parent["trace_id"] == r["trace_id"]
                assert parent["t_start"] <= r["t_start"] + 1e-3
    both = [it for it in its.values() if {"decode/prefill",
            "decode/step"} <= {r["name"] for r in decode_records
                               if r["parent_id"] == it["span_id"]}]
    assert both, "no iteration holds both a prefill and a step"


def test_no_span_name_means_two_things(decode_records):
    # the per-request records keep their names and meaning: the
    # benchmark's readers index fields["prompt_len"] of every one
    for r in decode_records:
        if r["name"] == "decode/admit":
            assert "prompt_len" in r["fields"]
            assert r["parent_id"] is not None
    loop = {r["name"] for r in decode_records
            if r["thread"] == "zoo-tpu-gen-batcher"
            and r["trace_id"] in {i["trace_id"] for i in decode_records
                                  if i["name"] == "decode/iteration"}}
    per_request = {"decode/queue_wait", "decode/admit",
                   "decode/retire"}
    assert not loop & per_request
    assert obs.snapshot().get("zoo_tpu_decode_admit_seconds") is None


class _FakeAnnotation:
    opened = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.opened.append(self.name)
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.opened.append("/" + self.name)
        return False


_OPENERS = {
    "obs.span": lambda: obs.span("unit/x", k=1),
    "obs.span in trace": lambda: obs.span("unit/x"),
    "tracing.trace": lambda: tracing.trace("unit/x"),
    "tracing.annotate": lambda: tracing.annotate("unit/x"),
}


@pytest.mark.parametrize("enabled", [True, False])
@pytest.mark.parametrize("opener", sorted(_OPENERS))
def test_annotation_hook_once_per_span(monkeypatch, opener, enabled):
    monkeypatch.setattr(tracing, "_annotation_hook", _FakeAnnotation)
    if not enabled:
        monkeypatch.setenv("ZOO_TPU_TRACE", "0")
    _FakeAnnotation.opened = []
    if opener == "obs.span in trace":
        with tracing.trace("unit/outer"):
            _FakeAnnotation.opened = []
            with _OPENERS[opener]():
                pass
            seen = list(_FakeAnnotation.opened)
    else:
        with _OPENERS[opener]():
            pass
        seen = list(_FakeAnnotation.opened)
    assert seen == (["zoo:unit/x", "/zoo:unit/x"] if enabled else [])
    # the already-timed cross-thread form stays store-only
    _FakeAnnotation.opened = []
    tracing.record_span(("t1", "s1"), "unit/recorded", time.time(),
                        0.1)
    assert _FakeAnnotation.opened == []


def test_jax_installs_the_profiler_annotation():
    import jax
    import analytics_zoo_tpu  # noqa: F401 — nncontext installs it
    assert tracing._annotation_hook is jax.profiler.TraceAnnotation
    assert tracing.ANNOTATION_PREFIX == "zoo:"


def test_failing_annotation_hook_never_breaks_the_span(monkeypatch):
    def boom(name):
        raise RuntimeError("profiler gone")
    monkeypatch.setattr(tracing, "_annotation_hook", boom)
    with tracing.trace("unit/root") as tr:
        with obs.span("unit/child"):
            pass
    assert len(tracing.get_store().spans(tr.trace_id)) == 2


def test_xla_compile_record_under_the_triggering_span():
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.common import diagnostics
    diagnostics.install_recompile_monitor()
    x = jnp.ones(7).block_until_ready()
    tracing.reset_tracing()
    with tracing.trace("unit/root") as tr:
        jax.jit(lambda a: a * 3 + 1)(x).block_until_ready()
    with diagnostics.expected_compiles():
        jax.jit(lambda a: a * 5 - 2)(x).block_until_ready()
    recs = [r for r in tracing.get_store().records()
            if r.name == "xla/compile"]
    assert [r.fields["expected"] for r in recs] == [False, True]
    assert recs[0].trace_id == tr.trace_id and \
        recs[0].parent_id == tr.span_id
    assert recs[1].parent_id is None and recs[1].dur_s > 0


# -- device work under the program's names ---------------------------------

class _NoScope:
    """`jax.named_scope` with the name taken away, as context manager
    and as decorator."""

    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __call__(self, fn):
        return fn


def _unscope(monkeypatch):
    """The programs as they were before the scopes: ``with`` and
    nested-decorator sites through ``jax.named_scope``, module-level
    decorated functions through ``__wrapped__``."""
    import jax
    from analytics_zoo_tpu.ops import attention, kv_cache, sampling
    from analytics_zoo_tpu.pipeline.api.keras.layers import conv
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    for mod, names in ((kv_cache, ("append_rows", "gather_layer",
                                   "_lay_rows",
                                   "write_prompt_layer")),
                       (attention, ("decode_attention",
                                    "chunk_attention")),
                       (sampling, ("sample_tokens",)),
                       (conv._ConvND, ("_convolve",))):
        for n in names:
            monkeypatch.setattr(mod, n, getattr(mod, n).__wrapped__)


def _train_step_and_args():
    import jax
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    ctx = zoo.init_nncontext(seed=0, log_level="WARNING")
    est = Estimator(_toy_convnet(), optimizer="sgd", loss="mse",
                    ctx=ctx)
    est._ensure_initialized()
    rs = np.random.RandomState(1)
    args = (est.params, est.opt_state, jax.random.key(0),
            rs.rand(8, 8, 8, 3).astype(np.float32),
            rs.rand(8, 3).astype(np.float32))
    return est._build_train_step(est._tx()), args


def _decode_step_and_args():
    import jax
    eng = _toy_engine()
    eng.admit([([5, 9, 2], 4, 0.0)])
    active = np.array([True, False])
    args = (eng.cache, eng.params, eng._last_tok, active, eng._temps,
            eng._rng, np.int32(1))
    return jax.jit(eng._step_fn), args


_PROGRAMS = {
    "train_step": (_train_step_and_args, [
        "zoo:bn/stats", "zoo:bn/apply", "zoo:conv/convolve",
        "zoo:pool/maxpool_bwd", "zoo:train/loss",
        "zoo:train/optimizer"]),
    "decode_step": (_decode_step_and_args, [
        "zoo:kv_cache/append", "zoo:kv_cache/gather",
        "zoo:decode/attention", "zoo:decode/sampling",
        "zoo:decode/layer", "zoo:decode/lm_head"]),
}


@pytest.mark.parametrize("program", sorted(_PROGRAMS))
def test_scopes_in_hlo_and_outputs_bit_identical(monkeypatch,
                                                 program):
    import jax
    build, scopes = _PROGRAMS[program]
    fn, args = build()
    text = fn.lower(*args).as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, f"{scope} not in the lowered {program}"
    # donated arguments die with the call: run on copies
    copy = lambda t: jax.tree_util.tree_map(
        lambda a: a.copy() if hasattr(a, "copy") else a, t)
    scoped = jax.device_get(fn(*copy(args)))
    _unscope(monkeypatch)
    plain_fn, _ = build()
    assert "zoo:" not in plain_fn.lower(*args).as_text(
        debug_info=True).replace("zoo:pool/", "")
    plain = jax.device_get(plain_fn(*copy(args)))
    for a, b in zip(jax.tree_util.tree_leaves(scoped),
                    jax.tree_util.tree_leaves(plain)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype.kind not in "biufc":   # typed PRNG keys
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        assert a.tobytes() == b.tobytes()


def test_prefill_program_carries_its_scopes():
    import jax
    eng = _toy_engine()
    ids = np.zeros((1, 32), np.int32)
    text = jax.jit(eng._prefill_fn).lower(
        eng.cache, eng.params, eng._last_tok, ids,
        np.array([3], np.int32),
        np.array([1], np.int32), eng._temps[:1], eng._rng,
        np.int32(0)).as_text(debug_info=True)
    for scope in ("zoo:kv_cache/write_prompt", "zoo:prefill/layer",
                  "zoo:prefill/attention", "zoo:prefill/lm_head",
                  "zoo:decode/sampling"):
        assert scope in text
