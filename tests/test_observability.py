"""Telemetry core + wiring (common/observability.py): registry
thread-safety, Prometheus golden output, JSONL event log, span API,
and the training / serving / ingest integrations. Tier-1 fast."""

import gzip
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.common.observability import (
    MetricsRegistry, counter, gauge, histogram, reset_metrics,
    snapshot, span, to_prometheus)


@pytest.fixture(autouse=True)
def _fresh_metrics():
    """Process-global registry isolation per test."""
    reset_metrics()
    yield
    reset_metrics()


# -- core ------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    c = counter("zoo_tpu_x_total", labels={"k": "a"})
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = gauge("zoo_tpu_g")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5.0
    h = histogram("zoo_tpu_h_seconds", buckets=(0.5, 2.0))
    for v in (0.25, 0.5, 4.0):
        h.observe(v)
    assert h.count == 3
    assert h.sum == 4.75
    assert h.cumulative() == [("0.5", 2), ("2", 2), ("+Inf", 3)]


def test_same_family_same_child():
    assert counter("zoo_tpu_s_total") is counter("zoo_tpu_s_total")
    a = counter("zoo_tpu_s_total", labels={"p": "1"})
    assert a is not counter("zoo_tpu_s_total")
    with pytest.raises(ValueError):
        gauge("zoo_tpu_s_total")  # type conflict


def test_concurrent_updates_from_threads():
    """8 threads x 1000 increments/observations land exactly."""
    c = counter("zoo_tpu_conc_total")
    h = histogram("zoo_tpu_conc_seconds", buckets=(0.5,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000
    assert h.cumulative() == [("0.5", 8000), ("+Inf", 8000)]


def test_prometheus_golden():
    reg = MetricsRegistry()
    reg.counter("req_total", help="requests",
                labels={"path": "/p", "status": "200"}).inc(3)
    reg.gauge("inflight").set(2)
    h = reg.histogram("lat_seconds", help="latency",
                      buckets=(0.5, 2.0))
    for v in (0.25, 0.5, 4.0):
        h.observe(v)
    assert reg.to_prometheus() == (
        "# TYPE inflight gauge\n"
        "inflight 2\n"
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.5"} 2\n'
        'lat_seconds_bucket{le="2"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        "lat_seconds_sum 4.75\n"
        "lat_seconds_count 3\n"
        "# HELP req_total requests\n"
        "# TYPE req_total counter\n"
        'req_total{path="/p",status="200"} 3\n')


def test_prometheus_label_escaping_and_name_sanitizing():
    reg = MetricsRegistry()
    reg.counter("bad name!", labels={"v": 'a"b\\c\nd'}).inc()
    text = reg.to_prometheus()
    assert "bad_name_" in text
    assert '{v="a\\"b\\\\c\\nd"}' in text


@pytest.mark.parametrize("raw,escaped", [
    ('quo"te', 'quo\\"te'),
    ("back\\slash", "back\\\\slash"),
    ("new\nline", "new\\nline"),
    ('all\\"\n', 'all\\\\\\"\\n'),
])
def test_prometheus_label_escaping_each_char(raw, escaped):
    reg = MetricsRegistry()
    reg.counter("esc_total", labels={"v": raw}).inc()
    line = [ln for ln in reg.to_prometheus().splitlines()
            if ln.startswith("esc_total{")][0]
    assert line == 'esc_total{v="%s"} 1' % escaped
    assert "\n" not in line  # a raw newline would split the line


def test_render_while_writing_from_threads():
    """to_prometheus() stays consistent while counters and histogram
    buckets are being hammered from other threads."""
    reg = MetricsRegistry()
    c = reg.counter("rw_total")
    h = reg.histogram("rw_seconds", buckets=(0.5,))
    stop = threading.Event()

    def work():
        while not stop.is_set():
            c.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(50):
            text = reg.to_prometheus()
            # bucket counts render monotone: le="0.5" <= le="+Inf"
            lines = {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
                     for ln in text.splitlines()
                     if ln.startswith("rw_")}
            lo = lines.get('rw_seconds_bucket{le="0.5"}', 0)
            hi = lines.get('rw_seconds_bucket{le="+Inf"}', 0)
            assert lo <= hi
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_snapshot_shape():
    counter("zoo_tpu_snap_total", help="h").inc(2)
    s = snapshot()
    fam = s["zoo_tpu_snap_total"]
    assert fam["type"] == "counter" and fam["help"] == "h"
    assert fam["values"] == [{"labels": {}, "value": 2.0}]
    json.dumps(s)  # snapshot must be JSON-able


def test_span_times_block_and_registers_histogram():
    with span("unit/op", step=1) as sp:
        pass
    assert sp.elapsed >= 0
    s = snapshot()
    assert s["zoo_tpu_unit_op_seconds"]["values"][0]["count"] == 1


def test_span_reraises_and_still_records():
    with pytest.raises(RuntimeError):
        with span("unit/fail"):
            raise RuntimeError("boom")
    assert snapshot()["zoo_tpu_unit_fail_seconds"][
        "values"][0]["count"] == 1


def test_event_log_jsonl_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "events.jsonl"
    monkeypatch.setenv("ZOO_TPU_EVENT_LOG", str(path))
    from analytics_zoo_tpu.common.observability import event
    event("ingest/start", stage="rdd", n=3)
    with span("unit/op", step=7):
        pass
    reset_metrics()  # closes the sink handle
    lines = [json.loads(ln) for ln in
             path.read_text().strip().splitlines()]
    assert [ln["event"] for ln in lines] == ["ingest/start", "unit/op"]
    assert lines[0]["stage"] == "rdd" and lines[0]["n"] == 3
    assert lines[1]["step"] == 7 and lines[1]["dur_s"] >= 0
    assert all("ts" in ln for ln in lines)


def test_event_log_size_rotation(tmp_path, monkeypatch):
    """ZOO_TPU_EVENT_LOG_MAX_MB rotates path -> path.1.gz ->
    path.2.gz (rotated segments gzip-compressed by default),
    keeping ZOO_TPU_EVENT_LOG_KEEP rotated files."""
    path = tmp_path / "events.jsonl"
    monkeypatch.setenv("ZOO_TPU_EVENT_LOG", str(path))
    # ~200-byte threshold: a handful of events per generation
    monkeypatch.setenv("ZOO_TPU_EVENT_LOG_MAX_MB", "0.0002")
    monkeypatch.setenv("ZOO_TPU_EVENT_LOG_KEEP", "2")
    from analytics_zoo_tpu.common.observability import event
    for i in range(60):
        event("rotate/test", i=i, pad="x" * 40)
    snap = snapshot()
    rot = snap["zoo_tpu_event_log_rotations_total"]["values"][0]
    assert rot["value"] >= 2  # at least two generations turned over
    # bytes gauge covers live segment + rotated generations
    total = (path.stat().st_size
             + (tmp_path / "events.jsonl.1.gz").stat().st_size
             + (tmp_path / "events.jsonl.2.gz").stat().st_size)
    assert snap["zoo_tpu_event_log_bytes"]["values"][0]["value"] == \
        pytest.approx(total, abs=200)
    reset_metrics()
    assert path.exists()
    assert (tmp_path / "events.jsonl.1.gz").exists()
    assert (tmp_path / "events.jsonl.2.gz").exists()
    assert not (tmp_path / "events.jsonl.3.gz").exists()  # keep=2
    assert not (tmp_path / "events.jsonl.1").exists()  # compressed
    # every surviving segment holds whole, parseable JSONL lines
    for ln in path.read_text().strip().splitlines():
        assert json.loads(ln)["event"] == "rotate/test"
    for p in (tmp_path / "events.jsonl.1.gz",
              tmp_path / "events.jsonl.2.gz"):
        with gzip.open(p, "rt", encoding="utf-8") as fh:
            lines = fh.read().strip().splitlines()
        assert lines  # non-empty after decompression
        for ln in lines:
            assert json.loads(ln)["event"] == "rotate/test"


def test_event_log_rotation_gzip_disabled(tmp_path, monkeypatch):
    """ZOO_TPU_EVENT_LOG_GZIP=0 keeps the legacy bare .1/.2
    rotated-segment naming (no compression)."""
    path = tmp_path / "events.jsonl"
    monkeypatch.setenv("ZOO_TPU_EVENT_LOG", str(path))
    monkeypatch.setenv("ZOO_TPU_EVENT_LOG_MAX_MB", "0.0002")
    monkeypatch.setenv("ZOO_TPU_EVENT_LOG_KEEP", "2")
    monkeypatch.setenv("ZOO_TPU_EVENT_LOG_GZIP", "0")
    from analytics_zoo_tpu.common.observability import event
    for i in range(60):
        event("rotate/test", i=i, pad="x" * 40)
    reset_metrics()
    assert (tmp_path / "events.jsonl.1").exists()
    assert (tmp_path / "events.jsonl.2").exists()
    assert not (tmp_path / "events.jsonl.1.gz").exists()
    for p in (path, tmp_path / "events.jsonl.1",
              tmp_path / "events.jsonl.2"):
        for ln in p.read_text().strip().splitlines():
            assert json.loads(ln)["event"] == "rotate/test"
    # rotated generations stay under threshold + one event
    assert (tmp_path / "events.jsonl.1").stat().st_size < 400


def test_event_log_no_rotation_without_flag(tmp_path, monkeypatch):
    path = tmp_path / "events.jsonl"
    monkeypatch.setenv("ZOO_TPU_EVENT_LOG", str(path))
    monkeypatch.delenv("ZOO_TPU_EVENT_LOG_MAX_MB", raising=False)
    from analytics_zoo_tpu.common.observability import event
    for i in range(50):
        event("no/rotate", i=i, pad="x" * 40)
    reset_metrics()
    assert not (tmp_path / "events.jsonl.1").exists()
    assert len(path.read_text().strip().splitlines()) == 50


def test_event_log_noop_without_env(monkeypatch):
    monkeypatch.delenv("ZOO_TPU_EVENT_LOG", raising=False)
    from analytics_zoo_tpu.common.observability import event
    event("no/sink", k=1)  # must not raise


# -- training integration ---------------------------------------------------

def _toy_model():
    from analytics_zoo_tpu.pipeline.api.keras import (
        Sequential, layers as L)
    m = Sequential()
    m.add(L.Dense(4, input_shape=(3,)))
    m.add(L.Dense(1))
    return m


def test_estimator_fit_populates_metrics(rng):
    from analytics_zoo_tpu.ops.optimizers import SGD
    m = _toy_model()
    m.compile(optimizer=SGD(lr=0.05), loss="mse")
    x = rng.randn(32, 3).astype(np.float32)
    y = rng.randn(32, 1).astype(np.float32)
    m.fit(x, y, batch_size=8, nb_epoch=2)
    m.evaluate(x, y, batch_size=8)
    s = snapshot()
    # 2 epochs x 4 batches
    step = s["zoo_tpu_train_step_seconds"]["values"][0]
    assert step["count"] == 8 and step["sum"] > 0
    assert s["zoo_tpu_train_steps_total"]["values"][0]["value"] == 8
    assert s["zoo_tpu_train_examples_total"][
        "values"][0]["value"] == 64
    assert s["zoo_tpu_train_throughput_examples_per_sec"][
        "values"][0]["value"] > 0
    assert s["zoo_tpu_train_first_step_seconds"][
        "values"][0]["value"] > 0
    assert s["zoo_tpu_train_epoch_seconds"]["values"][0]["count"] == 2
    assert s["zoo_tpu_train_eval_seconds"]["values"][0]["count"] == 1
    assert s["zoo_tpu_learning_rate"]["values"][0]["value"] == 0.05


def test_learning_rate_summary_trigger(rng):
    from analytics_zoo_tpu.ops.optimizers import SGD
    from analytics_zoo_tpu.pipeline.estimator import SeveralIteration
    m = _toy_model()
    m.compile(optimizer=SGD(lr=0.125), loss="mse")
    est = m.estimator
    est.set_summary_trigger("LearningRate", SeveralIteration(2))
    with pytest.raises(ValueError):
        est.set_summary_trigger("Gradients", SeveralIteration(1))
    x = rng.randn(16, 3).astype(np.float32)
    y = rng.randn(16, 1).astype(np.float32)
    m.fit(x, y, batch_size=8, nb_epoch=1)
    assert snapshot()["zoo_tpu_learning_rate"][
        "values"][0]["value"] == 0.125


def test_checkpoint_span_recorded(tmp_path, rng):
    m = _toy_model()
    m.compile(optimizer="sgd", loss="mse")
    est = m.estimator
    est.set_checkpoint(str(tmp_path))
    x = rng.randn(8, 3).astype(np.float32)
    y = rng.randn(8, 1).astype(np.float32)
    m.fit(x, y, batch_size=8, nb_epoch=1)
    assert snapshot()["zoo_tpu_train_checkpoint_seconds"][
        "values"][0]["count"] >= 1


def test_tensorboard_writer_closed_on_fit_exit(tmp_path, rng):
    pytest.importorskip("torch")
    m = _toy_model()
    m.compile(optimizer="sgd", loss="mse")
    est = m.estimator
    est.set_tensorboard(str(tmp_path), "app")
    x = rng.randn(8, 3).astype(np.float32)
    y = rng.randn(8, 1).astype(np.float32)
    m.fit(x, y, batch_size=8, nb_epoch=1)
    assert est._tb_writer is None  # closed, not leaked
    # closed on the exception path too
    est.set_tensorboard(str(tmp_path), "app2")

    class Boom(Exception):
        pass

    class ExplodingDs:
        num_samples = 8

        def iter_batches(self, *a, **kw):
            raise Boom()
            yield  # pragma: no cover

    with pytest.raises(Boom):
        est.train(ExplodingDs(), batch_size=8)
    assert est._tb_writer is None


# -- serving integration ----------------------------------------------------

def _serving_fixture():
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.pipeline.inference.serving import (
        InferenceServer)
    m = _toy_model()
    m.compile(optimizer="sgd", loss="mse")
    im = InferenceModel(supported_concurrent_num=2)
    im.load_keras_net(m)
    return InferenceServer(im, port=0).start()


def test_serving_metrics_endpoint_reflects_requests(rng):
    srv = _serving_fixture()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        x = rng.randn(4, 3).astype(np.float32)
        req = urllib.request.Request(
            url + "/predict",
            data=json.dumps({"inputs": x.tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req).read())
        assert np.asarray(out["outputs"]).shape == (4, 1)
        resp = urllib.request.urlopen(url + "/metrics")
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    finally:
        srv.stop()
    assert ('zoo_tpu_serving_requests_total'
            '{path="/predict",status="200"} 1') in text
    assert ('zoo_tpu_serving_request_seconds_bucket'
            '{path="/predict",le="+Inf"} 1') in text
    assert 'zoo_tpu_serving_request_seconds_count{path="/predict"} 1' \
        in text
    assert "zoo_tpu_serving_batch_size_bucket" in text
    assert "zoo_tpu_serving_predict_seconds" in text
    assert "zoo_tpu_serving_in_flight 0" in text


def test_serving_structured_errors_and_counters():
    srv = _serving_fixture()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        # malformed JSON -> 400 with a structured body
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                url + "/predict", data=b"{not json"))
        assert ei.value.code == 400
        body = json.loads(ei.value.read())
        assert body["error"]["code"] == 400
        assert "malformed JSON" in body["error"]["message"]
        # JSON object without "inputs" -> 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                url + "/predict", data=b'{"x": 1}'))
        assert ei.value.code == 400
        assert '"inputs"' in json.loads(
            ei.value.read())["error"]["message"]
        # unknown GET and POST paths -> 404
        for mk in (lambda: urllib.request.Request(url + "/nope"),
                   lambda: urllib.request.Request(url + "/nope",
                                                  data=b"{}")):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(mk())
            assert ei.value.code == 404
            err = json.loads(ei.value.read())["error"]
            assert err["code"] == 404 and err["path"] == "/nope"
    finally:
        srv.stop()
    s = snapshot()
    kinds = {v["labels"]["kind"]: v["value"]
             for v in s["zoo_tpu_serving_errors_total"]["values"]}
    assert kinds["bad_json"] == 1
    assert kinds["bad_request"] == 1
    assert kinds["not_found"] == 2


def test_native_serving_metrics_endpoint(rng):
    """GET /metrics through the C++ front-end's worker path."""
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.pipeline.inference.serving import (
        NativeInferenceServer)
    m = _toy_model()
    m.compile(optimizer="sgd", loss="mse")
    im = InferenceModel(supported_concurrent_num=2)
    im.load_keras_net(m)
    try:
        srv = NativeInferenceServer(im)
    except (RuntimeError, OSError):
        pytest.skip("native toolchain unavailable")
    srv.start()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        x = rng.randn(2, 3).astype(np.float32)
        req = urllib.request.Request(
            url + "/predict",
            data=json.dumps({"inputs": x.tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        json.loads(urllib.request.urlopen(req).read())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(url + "/nope", data=b"{}"))
        assert ei.value.code == 404
        text = urllib.request.urlopen(url + "/metrics").read().decode()
    finally:
        srv.stop()
    assert ('zoo_tpu_serving_requests_total'
            '{path="/predict",status="200"} 1') in text
    assert "zoo_tpu_serving_request_seconds_bucket" in text
    assert 'kind="not_found"' in text


# -- ingest integration -----------------------------------------------------

def test_ingest_counters():
    from analytics_zoo_tpu.feature.common import (
        SeqToTensor, TensorToSample)
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.feature.rdd import LocalRdd
    recs = [([float(i)] * 3, float(i % 2)) for i in range(20)]
    FeatureSet.from_rdd(LocalRdd(recs, num_partitions=4))
    pre = SeqToTensor((3,)) >> TensorToSample()
    FeatureSet.from_iterable([r[0] for r in recs], pre)
    s = snapshot()
    rec = {v["labels"]["stage"]: v["value"]
           for v in s["zoo_tpu_ingest_records_total"]["values"]}
    assert rec["rdd"] == 20
    assert rec["feature_set"] == 40  # both FeatureSets cached
    assert rec["SeqToTensor"] == 20
    assert rec["TensorToSample"] == 20
    byt = {v["labels"]["stage"]: v["value"]
           for v in s["zoo_tpu_ingest_bytes_total"]["values"]}
    assert byt["feature_set"] > 0


def test_to_prometheus_served_registry_is_global():
    """The module-level helpers and /metrics read the same registry."""
    counter("zoo_tpu_global_check_total").inc()
    assert "zoo_tpu_global_check_total 1" in to_prometheus()


# -- bucket quantiles (SLO latency estimator) -------------------------------

def test_bucket_quantile_known_uniform():
    """1000 uniform observations over (0, 10] against unit-width
    buckets: interpolation pins p50/p90/p99 to the true quantiles."""
    from analytics_zoo_tpu.common.observability import bucket_quantile
    buckets = [float(b) for b in range(1, 11)]
    counts = [100.0] * 10 + [0.0]  # per-bucket + empty overflow
    assert bucket_quantile(buckets, counts, 0.5) == pytest.approx(
        5.0, abs=0.02)
    assert bucket_quantile(buckets, counts, 0.9) == pytest.approx(
        9.0, abs=0.02)
    assert bucket_quantile(buckets, counts, 0.99) == pytest.approx(
        9.9, abs=0.02)
    assert bucket_quantile(buckets, counts, 0.0) == 0.0
    assert bucket_quantile(buckets, counts, 1.0) == 10.0


def test_bucket_quantile_skewed_and_overflow():
    from analytics_zoo_tpu.common.observability import bucket_quantile
    # 90% fast, 10% slow: p50 interpolates inside the first bucket
    assert bucket_quantile([0.1, 1.0], [90.0, 0.0, 10.0], 0.5) == \
        pytest.approx(0.1 * (50 / 90))
    # rank falling in +Inf clamps to the highest finite bound
    assert bucket_quantile([0.1, 1.0], [90.0, 0.0, 10.0], 0.99) == \
        pytest.approx(1.0)


def test_bucket_quantile_edge_cases():
    from analytics_zoo_tpu.common.observability import bucket_quantile
    import math
    assert math.isnan(bucket_quantile([1.0], [0.0, 0.0], 0.5))
    with pytest.raises(ValueError):
        bucket_quantile([1.0, 2.0], [1.0, 1.0], 0.5)  # no overflow


def test_histogram_quantile_method():
    """Histogram.quantile on a known distribution: 100 obs spread
    1..100 ms against default-ish bucket edges."""
    h = histogram("zoo_tpu_q_seconds",
                  buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0))
    for i in range(1, 101):  # 1ms..100ms uniform
        h.observe(i / 1000.0)
    assert h.quantile(0.5) == pytest.approx(0.05, rel=0.15)
    assert h.quantile(0.99) == pytest.approx(0.1, rel=0.05)
    import math
    empty = histogram("zoo_tpu_q2_seconds", buckets=(1.0,))
    assert math.isnan(empty.quantile(0.5))


# -- PR 27: one clock, no needless lock, no jax ----------------------------

def test_observability_and_tracing_import_without_jax():
    """The two modules run inside Spark executors' pickled closures
    and the native front-end's worker threads: stdlib only. The
    package's ``__init__`` pulls in jax, so load them under stand-in
    parent packages with jax made unimportable."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = f"""
import sys, types
sys.modules["jax"] = None            # any `import jax` now raises
for name, sub in (("analytics_zoo_tpu", ""),
                  ("analytics_zoo_tpu.common", "common")):
    pkg = types.ModuleType(name)
    pkg.__path__ = [{root!r} + "/analytics_zoo_tpu/" + sub]
    sys.modules[name] = pkg
from analytics_zoo_tpu.common import observability as obs, tracing
with tracing.trace("unit/root") as tr:
    with obs.span("unit/child") as sp:
        sp.annotate(k=1)
assert len(tracing.get_store().spans(tr.trace_id)) == 2
assert tracing._annotation_hook is None
assert not [m for m in sys.modules if m.split(".")[0] in
            ("jax", "jaxlib", "numpy") and sys.modules[m] is not None]
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "ok", \
        out.stderr


@pytest.mark.parametrize("log_set", [False, True])
def test_event_takes_no_lock_without_a_sink(monkeypatch, tmp_path,
                                            log_set):
    from analytics_zoo_tpu.common import observability as obs

    class Watched:
        taken = 0

        def __enter__(self):
            Watched.taken += 1

        def __exit__(self, *exc):
            return False

    if log_set:
        monkeypatch.setenv("ZOO_TPU_EVENT_LOG",
                           str(tmp_path / "events.jsonl"))
    else:
        monkeypatch.delenv("ZOO_TPU_EVENT_LOG", raising=False)
    monkeypatch.setattr(obs, "_event_lock", Watched())
    with span("unit/x", step=1):
        pass
    obs.event("unit/y")
    assert Watched.taken == (2 if log_set else 0)
    if log_set:
        monkeypatch.undo()      # the real lock, to close the sink
        lines = [json.loads(ln) for ln in
                 (tmp_path / "events.jsonl").read_text().splitlines()]
        assert [e["event"] for e in lines] == ["unit/x", "unit/y"]
        assert lines[0]["step"] == 1 and "dur_s" in lines[0]


def test_span_annotate_reaches_store_and_event_log(monkeypatch,
                                                   tmp_path):
    from analytics_zoo_tpu.common import tracing
    monkeypatch.setenv("ZOO_TPU_EVENT_LOG",
                       str(tmp_path / "events.jsonl"))
    with tracing.trace("unit/root") as tr:
        with span("unit/child", n=2) as sp:
            sp.annotate(fetch_s=0.25, skipped=None)
    child = [r for r in tracing.get_store().spans(tr.trace_id)
             if r.name == "unit/child"][0]
    assert child.fields == {"n": 2, "fetch_s": 0.25}
    reset_metrics()
    first = json.loads(
        (tmp_path / "events.jsonl").read_text().splitlines()[0])
    assert first["event"] == "unit/child" and first["fetch_s"] == 0.25
    assert first["trace_id"] == tr.trace_id
