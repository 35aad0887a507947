"""HTTP serving facade over InferenceModel.

Plays the role of the reference's plain-Java `AbstractInferenceModel`
POJO + Spring-boot web-service samples (reference
`java/.../inference/AbstractInferenceModel.java:25-103`,
`apps/web-service-sample/`): a language-agnostic boundary for web
services, here a stdlib HTTP/JSON endpoint (no framework deps).

POST /predict  {"inputs": [[...], ...]}  →  {"outputs": [[...], ...]}
GET  /health   →  {"status": "ok", "free_slots": N, "batcher": {...}}
GET  /metrics  →  Prometheus text exposition (docs/observability.md);
     ``?fleet=1`` on a fleet front door serves the MERGED fleet view
     from the federation collector (ticked first unless ``tick=0``)
GET  /metrics/json  →  registry snapshot as JSON (the federation
     collector's scrape format; explicit application/json)
GET  /debug/traces[?n=20]  →  recent traces as JSON (docs/observability.md);
     ``?since=<seq>`` switches to the incremental span scrape the
     federation collector uses (cursor + new spans, zero loss/dup);
     ``?fleet=1`` lists stitched traces from the fleet aggregator
GET  /debug/trace/<id>[?chrome=1]  →  ONE stitched cross-process
     timeline for a trace id (fleet aggregator when mounted, local
     ring otherwise); ``chrome=1`` renders Perfetto JSON with one
     process lane per source
GET  /debug/fleet/telemetry  →  federation collector state (sources,
     scrape health, skew verdicts); 404 when no collector mounted
GET  /debug/slo[?tick=0]  →  live SLO status (docs/slo.md): shipped
     serving objectives (p99 latency, error burn rate, queue depth)
     are installed at server start; the engine re-evaluates on each
     request unless ``tick=0``
GET  /debug/fleet  →  fleet topology + per-replica lifecycle state
     when a FleetRouter fronts this server (docs/serving.md fleet
     section); 404 on single-model servers
GET  /debug/rollout  →  warm-swap rollout state machine + canary
     split + per-replica versions (docs/robustness.md); 404 on
     single-model servers
GET  /debug/metrics/history[?family=&window=&fleet=1]  →  windowed
     metric time series from the in-process history store
     (docs/observability.md §History): no ``family`` lists known
     families + store stats; with one, per-label-set points
     (counters as deltas+rates, histograms as quantile summaries).
     ``fleet=1`` reads the federation collector's merged fleet
     timeline instead of the local store
GET  /debug/dashboard  →  dependency-free single-file HTML live
     dashboard (inline SVG sparklines over the history API: QPS,
     p99, queue depth, goodput/MFU, KV pages free, forecast ETAs,
     anomaly rate + SLO state); ``?fleet=1`` renders the merged
     fleet timeline
POST /debug/profile {"dir": ..., "ms": 500}  →  on-demand jax.profiler
     capture written to ``dir`` (one at a time; 503 while busy)

Tracing: /predict accepts and echoes an ``X-Zoo-Trace-Id`` header
(minted server-side when absent); the request runs under that trace,
so the batcher's queue/pad/execute/scatter child spans and the model
span land in ``GET /debug/traces`` under one id. ``ZOO_TPU_TRACE=0``
disables all of it (the hot path then skips trace bookkeeping
entirely).

Requests route through a :class:`DynamicBatcher`
(`pipeline/inference/batching.py`, docs/serving.md) by default:
cross-request coalescing onto AOT-warmed bucket shapes, with
backpressure. ``ZOO_TPU_SERVING_BATCH=0`` (or ``batcher=None``)
reverts to the per-request path.

Errors are structured JSON — ``{"error": {"code": N, "message": ...}}``
— with real status codes: 404 for unknown paths, 400 for malformed
JSON / missing "inputs" / un-coercible inputs, 500 for model and
runtime failures, 503 (+ ``Retry-After``) when the batcher queue is
full, 504 when a queued request's deadline expires. Each increments
``zoo_tpu_serving_errors_total{kind=...}``.
"""

from __future__ import annotations

import json
import time
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple

import numpy as np

from analytics_zoo_tpu.common import diagnostics
from analytics_zoo_tpu.common import forecast as forecast_lib
from analytics_zoo_tpu.common import observability as obs
from analytics_zoo_tpu.common import slo as slo_lib
from analytics_zoo_tpu.common import timeseries
from analytics_zoo_tpu.common import tracing
from analytics_zoo_tpu.pipeline.inference.batching import (
    DeadlineExpiredError, DynamicBatcher, QueueFullError)
from analytics_zoo_tpu.pipeline.inference.inference_model import (
    InferenceModel)


def _error_body(code: int, message: str, **extra) -> dict:
    err = {"code": code, "message": message}
    err.update(extra)
    return {"error": err}


def _count_error(kind: str):
    obs.counter("zoo_tpu_serving_errors_total",
                help="serving errors by kind",
                labels={"kind": kind}).inc()


def _record_request(path: str, status: int, dt: float):
    """Shared per-request telemetry for both HTTP front-ends. Query
    strings are stripped so label cardinality stays bounded."""
    path = path.split("?", 1)[0]
    obs.counter("zoo_tpu_serving_requests_total",
                help="HTTP requests served",
                labels={"path": path, "status": str(status)}).inc()
    obs.histogram("zoo_tpu_serving_request_seconds",
                  help="request latency (handler wall time)",
                  labels={"path": path}).observe(dt)


def _in_flight() -> "obs.Gauge":
    return obs.gauge("zoo_tpu_serving_in_flight",
                     help="requests currently being handled")


def _coerce_inputs(model: InferenceModel, inputs) -> "list":
    """JSON inputs → list of arrays, honoring the loaded model's
    declared example-input dtypes when available (an embedding/NCF
    model's integer ids must NOT be silently cast to f32); f32 is the
    fallback for undeclared models. Raises ValueError/TypeError on
    un-coercible payloads (ragged rows, non-numeric) — a CLIENT
    error."""
    specs = model.example_input_specs

    def dtype_for(i: int):
        if specs is not None and i < len(specs):
            return specs[i][1]
        return np.float32

    if isinstance(inputs, list) and inputs and \
            isinstance(inputs[0], dict):
        return [np.asarray(d["data"], dtype_for(i))
                for i, d in enumerate(inputs)]
    return [np.asarray(inputs, dtype_for(0))]


def handle_predict(model: InferenceModel, body: bytes,
                   batcher: "Optional[DynamicBatcher]" = None
                   ) -> "Tuple[int, dict]":
    """The /predict contract, shared by the stdlib and native
    front-ends: JSON body → (http_status, payload_dict). With a
    ``batcher``, row-aligned requests ride the coalescing path
    (docs/serving.md); without one (or for inputs the batcher cannot
    coalesce) the model runs per-request.

    Status mapping: client mistakes are 400 (malformed JSON, missing
    "inputs", un-coercible arrays), backpressure is 503 with a
    ``retry_after_s`` hint, expired deadlines are 504, and model or
    runtime failures are 500 ``kind="internal"``."""
    try:
        req = json.loads(body)
    except (ValueError, UnicodeDecodeError) as e:
        _count_error("bad_json")
        return 400, _error_body(400, f"malformed JSON body: {e}")
    try:
        inputs = req["inputs"]
    except (KeyError, TypeError):
        _count_error("bad_request")
        return 400, _error_body(
            400, 'request must be a JSON object with an "inputs" key')
    try:
        xs = _coerce_inputs(model, inputs)
    except (ValueError, TypeError, KeyError) as e:
        _count_error("bad_request")
        return 400, _error_body(
            400, f"inputs are not coercible to arrays: {e}")
    try:
        if batcher is not None and batcher.batchable(xs):
            out = batcher.submit(xs).result()
        else:
            out = model.predict(xs if len(xs) > 1 else xs[0])
        if isinstance(out, list):
            if len(out) == 1:
                return 200, {"outputs": out[0].tolist()}
            return 200, {"outputs": [o.tolist() for o in out]}
        return 200, {"outputs": out.tolist()}
    except QueueFullError as e:
        # admission control: bounded queueing latency, not unbounded
        # (the batcher already counted kind="queue_full")
        return 503, _error_body(
            503, str(e), retry_after_s=round(e.retry_after_s, 3))
    except DeadlineExpiredError as e:
        # the batcher already counted kind="deadline_expired"
        return 504, _error_body(504, str(e))
    except Exception as e:  # serving boundary: report, not die
        _count_error("internal")
        return 500, _error_body(500, str(e), kind="internal")


def handle_generate(model: InferenceModel, body: bytes,
                    gen_batcher=None) -> "Tuple[int, dict]":
    """The /generate contract, shared by both front-ends: JSON body →
    (http_status, payload_dict).

    Request: ``{"prompt": [ids...]}`` (one sequence) or
    ``{"prompts": [[ids...], ...]}``, with optional
    ``max_new_tokens`` (default 32), ``temperature`` (default 0 =
    greedy) and ``eos_id``. Response mirrors the request's shape:
    ``{"tokens": [...]}`` or ``{"tokens": [[...], ...]}`` — the NEWLY
    generated ids only (eos, when hit, included).

    With a :class:`ContinuousBatcher` the sequences join the live
    decode batch (one compiled step, token-boundary admission —
    docs/serving.md); without one they run the sequential compiled
    whole-loop path (`InferenceModel.generate`). The engine-side
    capacity levers — chunked prefill, int8 paged KV, speculative
    decoding (docs/serving.md, docs/perf_flags.md) — are transparent
    to this contract: same request/response either way, with the
    active configuration reported under ``generator`` in
    ``GET /health``. 501 when the model has no generator loaded."""
    try:
        req = json.loads(body)
    except (ValueError, UnicodeDecodeError) as e:
        _count_error("bad_json")
        return 400, _error_body(400, f"malformed JSON body: {e}")
    if not isinstance(req, dict) or \
            ("prompt" not in req) == ("prompts" not in req):
        _count_error("bad_request")
        return 400, _error_body(
            400, 'request must be a JSON object with exactly one of '
            '"prompt" (one token-id list) or "prompts" (a list of '
            'them)')
    if gen_batcher is None and \
            getattr(model, "generator", None) is None:
        _count_error("no_generator")
        return 501, _error_body(
            501, "this server has no generative model loaded "
            "(InferenceModel.load_generator)")
    single = "prompt" in req
    prompts = [req["prompt"]] if single else req["prompts"]
    try:
        prompts = [[int(t) for t in p] for p in prompts]
        max_new = int(req.get("max_new_tokens", 32))
        temperature = float(req.get("temperature", 0.0))
        eos_id = req.get("eos_id")
        eos_id = None if eos_id is None else int(eos_id)
    except (TypeError, ValueError) as e:
        _count_error("bad_request")
        return 400, _error_body(
            400, f"prompts must be lists of token ids: {e}")
    try:
        if gen_batcher is not None:
            futures = [gen_batcher.submit(
                p, max_new_tokens=max_new, temperature=temperature,
                eos_id=eos_id) for p in prompts]
            outs = [f.result() for f in futures]
        else:
            outs = model.generate(prompts, max_new_tokens=max_new,
                                  temperature=temperature,
                                  eos_id=eos_id)
        toks = [[int(t) for t in o] for o in outs]
        return 200, {"tokens": toks[0] if single else toks}
    except QueueFullError as e:
        return 503, _error_body(
            503, str(e), retry_after_s=round(e.retry_after_s, 3))
    except ValueError as e:  # prompt/budget outside the cache bounds
        _count_error("bad_request")
        return 400, _error_body(400, str(e))
    except Exception as e:  # serving boundary: report, not die
        _count_error("internal")
        return 500, _error_body(500, str(e), kind="internal")


def handle_prefill(model: InferenceModel, body: bytes,
                   gen_batcher=None) -> "Tuple[int, dict]":
    """``POST /generate/prefill`` — the disaggregated fleet's
    prefill-pool ingress (docs/serving.md §Disaggregation). Request:
    ``{"prompt": [ids...]}`` with optional ``max_new_tokens`` /
    ``temperature``. The prompt runs to its first sampled token,
    then the sequence's KV pages leave the cache as a handoff blob:
    response ``{"handoff": {...}}`` in the base64 wire form
    (`ops/kv_cache.handoff_to_wire`), ready to POST at a decode
    replica's ``/generate/handoff``. 501 unless this server's
    batcher fronts a prefill-capable engine."""
    sub = getattr(gen_batcher, "submit_prefill", None)
    if sub is None:
        _count_error("no_generator")
        return 501, _error_body(
            501, "this server has no prefill-capable generation "
            "batcher mounted (disaggregated prefill pool only)")
    try:
        req = json.loads(body)
    except (ValueError, UnicodeDecodeError) as e:
        _count_error("bad_json")
        return 400, _error_body(400, f"malformed JSON body: {e}")
    if not isinstance(req, dict) or "prompt" not in req:
        _count_error("bad_request")
        return 400, _error_body(
            400, 'request must be a JSON object with a "prompt" '
            'token-id list')
    try:
        prompt = [int(t) for t in req["prompt"]]
        max_new = int(req.get("max_new_tokens", 32))
        temperature = float(req.get("temperature", 0.0))
    except (TypeError, ValueError) as e:
        _count_error("bad_request")
        return 400, _error_body(
            400, f"prompt must be a list of token ids: {e}")
    from analytics_zoo_tpu.ops.kv_cache import handoff_to_wire
    try:
        blob = sub(prompt, max_new_tokens=max_new,
                   temperature=temperature).result()
        return 200, {"handoff": handoff_to_wire(blob)}
    except QueueFullError as e:
        return 503, _error_body(
            503, str(e), retry_after_s=round(e.retry_after_s, 3))
    except ValueError as e:
        _count_error("bad_request")
        return 400, _error_body(400, str(e))
    except Exception as e:  # serving boundary: report, not die
        _count_error("internal")
        return 500, _error_body(500, str(e), kind="internal")


def handle_handoff(model: InferenceModel, body: bytes,
                   gen_batcher=None) -> "Tuple[int, dict]":
    """``POST /generate/handoff`` — the disaggregated fleet's
    decode-pool ingress. Request: ``{"handoff": {...}}`` (wire form
    from a prefill replica) with optional ``max_new_tokens`` /
    ``eos_id``. The blob's pages splice into this replica's cache
    with no forward pass and the sequence resumes decoding; response
    ``{"tokens": [...]}`` is the FULL new-token stream including the
    prefill-sampled first token — byte-identical to what a
    monolithic ``/generate`` would have returned. 501 unless this
    server's batcher can admit handoffs."""
    sub = getattr(gen_batcher, "submit_handoff", None)
    if sub is None:
        _count_error("no_generator")
        return 501, _error_body(
            501, "this server has no handoff-capable generation "
            "batcher mounted (disaggregated decode pool only)")
    try:
        req = json.loads(body)
    except (ValueError, UnicodeDecodeError) as e:
        _count_error("bad_json")
        return 400, _error_body(400, f"malformed JSON body: {e}")
    if not isinstance(req, dict) or \
            not isinstance(req.get("handoff"), dict):
        _count_error("bad_request")
        return 400, _error_body(
            400, 'request must be a JSON object with a "handoff" '
            'wire blob (POST /generate/prefill produces one)')
    from analytics_zoo_tpu.ops.kv_cache import handoff_from_wire
    try:
        max_new = int(req.get("max_new_tokens", 32))
        eos_id = req.get("eos_id")
        eos_id = None if eos_id is None else int(eos_id)
        blob = handoff_from_wire(req["handoff"])
    except (TypeError, ValueError, KeyError) as e:
        _count_error("bad_request")
        return 400, _error_body(400, f"bad handoff blob: {e}")
    try:
        toks = sub(blob, max_new_tokens=max_new,
                   eos_id=eos_id).result()
        return 200, {"tokens": [int(t) for t in toks]}
    except QueueFullError as e:
        return 503, _error_body(
            503, str(e), retry_after_s=round(e.retry_after_s, 3))
    except ValueError as e:  # blob/engine geometry mismatch
        _count_error("bad_request")
        return 400, _error_body(400, str(e))
    except Exception as e:
        _count_error("internal")
        return 500, _error_body(500, str(e), kind="internal")


def _health_payload(model: InferenceModel,
                    batcher: "Optional[DynamicBatcher]",
                    gen_batcher=None) -> dict:
    """Shared /health body: model pool capacity plus the batcher's
    queue/bucket state (docs/serving.md), and — when a generator is
    mounted — the continuous batcher's slot/page occupancy."""
    payload = {
        "status": "ok",
        "free_slots": model.concurrent_slots_free,
        "batcher": (batcher.stats() if batcher is not None
                    else {"enabled": False}),
    }
    if gen_batcher is not None:
        payload["generator"] = gen_batcher.stats()
    elif getattr(model, "generator", None) is not None:
        payload["generator"] = dict(model.generator.stats(),
                                    enabled=False)
    return payload


def _fed_collector(batcher):
    """The FleetRouter's federation ``TelemetryCollector`` when this
    server fronts a started fleet (None otherwise — the attribute's
    presence is how these routes discover the telemetry plane)."""
    return getattr(batcher, "telemetry", None)


def _metrics_text() -> bytes:
    """Local-registry Prometheus text; refreshes the process vitals
    + build-info gauges first so every scrape carries current
    RSS/uptime/fd readings and provenance (docs/observability.md)."""
    diagnostics.update_process_vitals()
    diagnostics.update_build_info()
    return obs.to_prometheus().encode()


def _metrics_json_payload() -> dict:
    """``GET /metrics/json``: the registry snapshot the federation
    collector scrapes — same data as ``/metrics``, machine-mergeable
    (explicit ``application/json``)."""
    diagnostics.update_process_vitals()
    diagnostics.update_build_info()
    return {"ts": time.time(), "metrics": obs.snapshot()}


def _fleet_metrics_text(path: str, batcher
                        ) -> "Tuple[int, Optional[bytes]]":
    """``GET /metrics?fleet=1``: merged fleet-wide Prometheus text
    from the federation collector (HELP/TYPE deduplicated). Ticks
    the collector first by default so exact-sum assertions see this
    instant, not the last background scrape; ``tick=0`` reads
    passively. ``(404, None)`` when no collector is mounted."""
    from urllib.parse import parse_qs, urlsplit
    q = parse_qs(urlsplit(path).query)
    tele = _fed_collector(batcher)
    if tele is None:
        _count_error("not_found")
        return 404, None
    if q.get("tick", ["1"])[0] != "0":
        tele.tick()
    return 200, tele.fleet_prometheus().encode()


def _traces_payload(path: str, batcher=None) -> dict:
    """``GET /debug/traces[?n=20]``: the most recent traces from the
    in-process ring buffer, newest first. ``?since=<seq>`` switches
    to the federation collector's incremental scrape: the ring's
    cursor plus every span recorded after ``seq`` (cursor and spans
    read under one lock — zero loss, zero duplication). ``?fleet=1``
    on a fleet front door lists stitched traces from the
    aggregator."""
    from urllib.parse import parse_qs, urlsplit
    q = parse_qs(urlsplit(path).query)
    try:
        n = int(q.get("n", ["20"])[0])
    except ValueError:
        n = 20
    n = max(1, min(n, 200))
    if "since" in q:
        try:
            since = int(q["since"][0])
        except ValueError:
            since = 0
        seq, recs = tracing.get_store().records_since(since)
        return {"enabled": tracing.enabled(), "seq": seq,
                "spans": [r.to_dict() for r in recs]}
    tele = _fed_collector(batcher)
    if q.get("fleet", ["0"])[0] == "1" and tele is not None:
        return {"enabled": tracing.enabled(), "fleet": True,
                "traces": tele.aggregator.recent(n)}
    return {"enabled": tracing.enabled(),
            "traces": tracing.get_store().recent(n)}


def _stitched_trace_payload(route: str, path: str, batcher
                            ) -> "Tuple[int, dict]":
    """``GET /debug/trace/<id>[?chrome=1]``: ONE stitched timeline
    for a trace id — from the fleet aggregator when the federation
    plane is mounted (spans from every process, freshened by a
    synchronous collector tick), falling back to the local ring.
    ``chrome=1`` renders Perfetto-loadable JSON with a distinct
    process lane (pid) per source process."""
    from urllib.parse import parse_qs, urlsplit
    q = parse_qs(urlsplit(path).query)
    tid = route[len("/debug/trace/"):]
    chrome = q.get("chrome", ["0"])[0] == "1"
    tele = _fed_collector(batcher)
    if tele is not None:
        tele.tick()  # pull any spans still sitting in the sources
        agg = tele.aggregator
        if agg.spans(tid):
            return 200, (agg.chrome(tid) if chrome
                         else agg.trace(tid))
    recs = sorted((r for r in tracing.get_store().records()
                   if r.trace_id == tid),
                  key=lambda r: r.t_start)
    if not recs:
        _count_error("not_found")
        return 404, _error_body(404, f"unknown trace id {tid!r}")
    if chrome:
        return 200, {"traceEvents": tracing.chrome_events(
            [r.to_dict() for r in recs], source_lanes=True),
            "displayTimeUnit": "ms"}
    t0 = min(r.t_start for r in recs)
    t1 = max(r.t_start + r.dur_s for r in recs)
    return 200, {"trace_id": tid, "t_start": round(t0, 6),
                 "dur_s": round(t1 - t0, 6), "n_spans": len(recs),
                 "sources": ["router"],
                 "spans": [r.to_dict() for r in recs]}


def _fleet_telemetry_payload(batcher) -> "Tuple[int, dict]":
    """``GET /debug/fleet/telemetry``: the federation collector's
    own state — sources and scrape health, merge conflicts, the last
    per-replica window stats and skew verdicts. 404 when this server
    fronts no fleet telemetry plane."""
    tele = _fed_collector(batcher)
    if tele is None:
        _count_error("not_found")
        return 404, _error_body(
            404, "no fleet telemetry collector mounted")
    return 200, tele.status()


def _slo_payload(path: str) -> dict:
    """``GET /debug/slo[?tick=0]``: live objective status from the
    process-global SLO engine (docs/slo.md). Ticks the engine first
    by default so the report reflects this instant, not the last
    background tick; ``tick=0`` reads passively."""
    from urllib.parse import parse_qs, urlsplit
    q = parse_qs(urlsplit(path).query)
    engine = slo_lib.get_engine()
    if q.get("tick", ["1"])[0] != "0":
        return engine.tick()
    return engine.status()


def _history_payload(path: str, batcher=None
                     ) -> "Tuple[int, dict]":
    """``GET /debug/metrics/history[?family=&window=&fleet=1]``:
    windowed series from the in-process
    :class:`~analytics_zoo_tpu.common.timeseries.MetricHistory`.
    Without ``family``, lists known families + store stats. The
    local store takes a fresh sample first by default (so the
    response reflects this instant even with no background ticker;
    ``sample=0`` reads passively); ``fleet=1`` serves the federation
    collector's merged fleet timeline instead (``tick=1`` forces a
    synchronous collector tick first)."""
    from urllib.parse import parse_qs, urlsplit
    q = parse_qs(urlsplit(path).query)
    fleet = q.get("fleet", ["0"])[0] == "1"
    if fleet:
        tele = _fed_collector(batcher)
        if tele is None:
            _count_error("not_found")
            return 404, _error_body(
                404, "no fleet telemetry collector mounted")
        if q.get("tick", ["0"])[0] == "1":
            tele.tick()
        hist = tele.history
    else:
        hist = timeseries.get_history()
        if q.get("sample", ["1"])[0] != "0":
            hist.sample()
    window_s = None
    if q.get("window"):
        try:
            window_s = float(q["window"][0])
        except ValueError:
            _count_error("bad_request")
            return 400, _error_body(
                400, f"bad window {q['window'][0]!r} "
                "(seconds expected)")
        if window_s <= 0:
            _count_error("bad_request")
            return 400, _error_body(
                400, "window must be positive seconds")
    family = q.get("family", [None])[0]
    if not family:
        return 200, {"fleet": fleet,
                     "families": hist.families(),
                     "stats": hist.stats()}
    return 200, dict(hist.series(family, window_s=window_s),
                     fleet=fleet)


# The live dashboard: ONE self-contained HTML file, zero external
# assets (loads even when the fleet is on fire and a CDN is not an
# option). All series come from /debug/metrics/history; sparklines
# are inline SVG built client-side.
_DASHBOARD_PAGE = """<!doctype html>
<html><head><meta charset="utf-8">
<title>analytics-zoo-tpu dashboard</title>
<style>
body{font:13px/1.4 system-ui,sans-serif;margin:16px;
     background:#0b0e14;color:#d6deeb}
h1{font-size:16px;margin:0 0 2px}
#meta{color:#7a88a8;margin-bottom:12px}
#panels{display:grid;gap:10px;
        grid-template-columns:repeat(auto-fill,minmax(290px,1fr))}
.panel{background:#131824;border:1px solid #232b3d;
       border-radius:6px;padding:8px 10px}
.panel h2{font-size:12px;margin:0 0 4px;color:#9fb2d8;
          font-weight:600}
.row{display:flex;align-items:center;gap:8px;margin:2px 0}
.lbl{color:#7a88a8;font-size:11px;white-space:nowrap;
     overflow:hidden;text-overflow:ellipsis;max-width:45%}
.val{margin-left:auto;font-variant-numeric:tabular-nums}
.nodata{color:#53607c;font-style:italic}
svg{flex:1 1 auto;min-width:60px}
polyline{fill:none;stroke:#58a6ff;stroke-width:1.5}
.bad polyline{stroke:#ff7b72}
#slo .breach{color:#ff7b72}
#slo .ok{color:#3fb950}
#slo .no_data{color:#53607c}
</style></head><body>
<h1>analytics-zoo-tpu &mdash; live dashboard</h1>
<div id="meta">loading&hellip;</div>
<div id="panels"></div>
<div class="panel" id="slo" style="margin-top:10px">
<h2>SLO state &amp; recent anomalies</h2>
<div id="slobody" class="nodata">loading&hellip;</div></div>
<script>
"use strict";
var FLEET = new URLSearchParams(location.search)
    .get("fleet") === "1";
var SUFFIX = FLEET ? "&fleet=1" : "";
var PANELS = [
  {t: "QPS (requests/s)", f: "zoo_tpu_serving_requests_total",
   k: "rate"},
  {t: "p99 latency (s)", f: "zoo_tpu_serving_request_seconds",
   k: "q99"},
  {t: "queue depth", f: "zoo_tpu_serving_queue_depth",
   k: "value"},
  {t: "KV pages free", f: "zoo_tpu_serving_gen_free_pages",
   k: "value"},
  {t: "goodput share", f: "zoo_tpu_goodput_share", k: "value"},
  {t: "MFU", f: "zoo_tpu_mfu", k: "value"},
  {t: "forecast ETA (s)", f: "zoo_tpu_forecast_eta_s",
   k: "value", bad: function (v) { return v < 600; }},
  {t: "anomalies/s", f: "zoo_tpu_anomalies_total", k: "rate",
   bad: function (v) { return v > 0; }}
];
function esc(s) {
  return String(s).replace(/[&<>"]/g, function (c) {
    return {"&": "&amp;", "<": "&lt;", ">": "&gt;",
            '"': "&quot;"}[c];
  });
}
function spark(vals) {
  var w = 120, h = 26;
  if (vals.length < 2) {
    return '<svg width="' + w + '" height="' + h + '"></svg>';
  }
  var lo = Math.min.apply(null, vals);
  var hi = Math.max.apply(null, vals);
  var span = (hi - lo) || 1;
  var pts = vals.map(function (v, i) {
    var x = i * w / (vals.length - 1);
    var y = h - 2 - (v - lo) / span * (h - 4);
    return x.toFixed(1) + "," + y.toFixed(1);
  }).join(" ");
  return '<svg width="' + w + '" height="' + h +
    '" viewBox="0 0 ' + w + " " + h +
    '"><polyline points="' + pts + '"/></svg>';
}
function fmtv(v) {
  if (v === null || v === undefined) { return "-"; }
  if (v >= 1e8) { return "&#8734;"; }
  if (Math.abs(v) >= 100) { return v.toFixed(0); }
  return v.toPrecision(3);
}
function labelText(labels) {
  var ks = Object.keys(labels);
  if (!ks.length) { return "total"; }
  return ks.map(function (k) {
    return k + "=" + labels[k];
  }).join(",");
}
function renderPanel(p, doc) {
  var html = "<h2>" + esc(p.t) + "</h2>";
  var series = (doc && doc.series) || [];
  var rows = 0;
  series.forEach(function (s) {
    var vals = s.points.map(function (pt) {
      return pt[p.k];
    }).filter(function (v) {
      return v !== null && v !== undefined;
    });
    if (!vals.length) { return; }
    rows += 1;
    var last = vals[vals.length - 1];
    var bad = p.bad && p.bad(last);
    html += '<div class="row' + (bad ? " bad" : "") +
      '"><span class="lbl" title="' +
      esc(labelText(s.labels)) + '">' +
      esc(labelText(s.labels)) + "</span>" + spark(vals) +
      '<span class="val">' + fmtv(last) + "</span></div>";
  });
  if (!rows) {
    html += '<div class="nodata">no data</div>';
  }
  return html;
}
function refresh() {
  PANELS.forEach(function (p, i) {
    fetch("/debug/metrics/history?family=" + p.f + SUFFIX)
      .then(function (r) { return r.json(); })
      .then(function (doc) {
        document.getElementById("p" + i).innerHTML =
          renderPanel(p, doc);
      }).catch(function () {});
  });
  fetch("/debug/metrics/history?" + (FLEET ? "fleet=1" : ""))
    .then(function (r) { return r.json(); })
    .then(function (doc) {
      var st = doc.stats || {};
      document.getElementById("meta").textContent =
        (FLEET ? "fleet-merged timeline" : "local timeline") +
        " \\u00b7 " + (st.raw_samples || 0) + " samples over " +
        (st.span_s || 0).toFixed(0) + "s \\u00b7 " +
        ((st.resident_bytes || 0) / 1024).toFixed(0) +
        " KiB resident \\u00b7 " + new Date().toLocaleTimeString();
    }).catch(function () {});
  fetch("/debug/slo?tick=0")
    .then(function (r) { return r.json(); })
    .then(function (doc) {
      var html = "";
      (doc.objectives || []).forEach(function (o) {
        html += '<div class="row"><span class="lbl">' +
          esc(o.id) + '</span><span class="' + esc(o.state) +
          '">' + esc(o.state) + "</span>" +
          '<span class="val">' + fmtv(o.value) + "</span></div>";
      });
      document.getElementById("slobody").innerHTML =
        html || '<div class="nodata">no objectives</div>';
    }).catch(function () {});
}
var panels = document.getElementById("panels");
PANELS.forEach(function (p, i) {
  var d = document.createElement("div");
  d.className = "panel";
  d.id = "p" + i;
  d.innerHTML = "<h2>" + esc(p.t) +
    '</h2><div class="nodata">loading&hellip;</div>';
  panels.appendChild(d);
});
refresh();
setInterval(refresh, 5000);
</script></body></html>
"""


def _dashboard_html() -> bytes:
    """``GET /debug/dashboard``: the self-contained live dashboard
    page (same bytes on both front-ends)."""
    return _DASHBOARD_PAGE.encode()


# On-demand jax.profiler capture: one at a time per process (the XLA
# profiler is a process-global singleton).
_profile_lock = threading.Lock()
_profile_thread: "Optional[threading.Thread]" = None


def _fleet_payload(batcher, gen_batcher=None) -> "Tuple[int, dict]":
    """``GET /debug/fleet``: topology + per-replica lifecycle state
    (state machine, outstanding rows, failure counts, per-queue
    batcher stats) when a ``FleetRouter`` fronts this server — or,
    on a disaggregated generation front door, the
    :class:`DisaggRouter`'s role-tagged replicas and per-pool page
    headroom. Single-model servers 404 — the route's presence is how
    clients discover they are talking to a fleet."""
    status_fn = getattr(batcher, "fleet_status", None)
    if status_fn is None:
        status_fn = getattr(gen_batcher, "fleet_status", None)
    if status_fn is None:
        _count_error("not_found")
        return 404, _error_body(
            404, "no fleet router mounted on this server")
    return 200, status_fn()


def _rollout_payload(batcher) -> "Tuple[int, dict]":
    """``GET /debug/rollout``: the rollout state machine (rolling →
    canary → promoted | rolled_back), per-replica versions, swap
    log, and the active canary split — the observable surface of
    ``FleetRouter.rollout`` (docs/robustness.md). 404 on
    single-model servers, ``{"state": "idle"}`` on fleets that never
    rolled."""
    status_fn = getattr(batcher, "rollout_status", None)
    if status_fn is None:
        _count_error("not_found")
        return 404, _error_body(
            404, "no fleet router mounted on this server")
    return 200, status_fn()


def _profiler_capture(out_dir: str, ms: float):
    """Capture ``ms`` milliseconds of jax.profiler trace into
    ``out_dir`` (module-level so tests can stub it)."""
    import jax

    jax.profiler.start_trace(out_dir)
    try:
        time.sleep(ms / 1e3)
    finally:
        jax.profiler.stop_trace()


def handle_profile(body: bytes) -> "Tuple[int, dict]":
    """``POST /debug/profile {"dir": ..., "ms": 500}``: trigger an
    on-demand ``jax.profiler`` capture in a background thread (the
    train loop's ``StepTraceAnnotation`` step markers line up with
    our spans in the result). Returns immediately; 503 while a
    capture is already running."""
    global _profile_thread
    try:
        req = json.loads(body) if body else {}
    except (ValueError, UnicodeDecodeError) as e:
        _count_error("bad_json")
        return 400, _error_body(400, f"malformed JSON body: {e}")
    if not isinstance(req, dict) or not req.get("dir"):
        _count_error("bad_request")
        return 400, _error_body(
            400, 'request must be a JSON object with a "dir" key '
            '(profile output directory); optional "ms" duration')
    out_dir = str(req["dir"])
    try:
        ms = float(req.get("ms", 500))
    except (TypeError, ValueError):
        _count_error("bad_request")
        return 400, _error_body(400, '"ms" must be a number')
    ms = max(1.0, min(ms, 60_000.0))
    if not _profile_lock.acquire(blocking=False):
        _count_error("profile_busy")
        return 503, _error_body(
            503, "a profiler capture is already running")

    def _run():
        try:
            _profiler_capture(out_dir, ms)
            obs.event("serving/profile_capture", dir=out_dir, ms=ms)
        except Exception as e:
            obs.event("serving/profile_error", dir=out_dir,
                      error=f"{type(e).__name__}: {e}")
        finally:
            _profile_lock.release()

    t = threading.Thread(target=_run, name="zoo-tpu-profiler",
                         daemon=True)
    _profile_thread = t
    t.start()
    return 200, {"status": "capturing", "dir": out_dir, "ms": ms}


def _resolve_gen_batcher(model: InferenceModel, gen_batcher):
    """``"auto"`` → a :class:`ContinuousBatcher` over the model's
    loaded generator (None when no generator is loaded or
    ``ZOO_TPU_GEN_BATCH=0`` — /generate then runs the sequential
    per-request path); explicit ``None`` / instance pass through. A
    FleetRouter standing in for the model has no generator, so fleet
    front doors resolve to None and /generate degrades cleanly.

    ``ZOO_TPU_DISAGG=1`` swaps the ContinuousBatcher for a
    :class:`fleet.DisaggRouter` carved out of the loaded generator
    (pool sizes from ``ZOO_TPU_DISAGG_PREFILL_REPLICAS`` /
    ``ZOO_TPU_DISAGG_DECODE_REPLICAS``): /generate then runs the
    prefill→handoff→decode path transparently, same contract. Only a
    ``role="both"`` engine is split — pool workers (role-specific
    engines behind /generate/prefill + /generate/handoff) keep their
    plain batcher."""
    if gen_batcher == "auto":
        import os
        engine = getattr(model, "generator", None)
        if engine is None or \
                os.environ.get("ZOO_TPU_GEN_BATCH", "1") == "0":
            return None
        if os.environ.get("ZOO_TPU_DISAGG", "0") not in ("", "0") \
                and getattr(engine, "role", "both") == "both":
            from analytics_zoo_tpu.pipeline.inference.fleet import \
                DisaggRouter
            return DisaggRouter.for_engine(engine)
        from analytics_zoo_tpu.pipeline.inference.batching import \
            ContinuousBatcher
        return ContinuousBatcher(engine)
    return gen_batcher


def _resolve_batcher(model: InferenceModel, batcher):
    """``"auto"`` → env-configured batcher (None when
    ``ZOO_TPU_SERVING_BATCH=0``); explicit ``None`` → per-request
    serving; a DynamicBatcher instance passes through. A
    ``FleetRouter`` passed as the *model* is its own batcher (it
    duck-types both surfaces — `pipeline/inference/fleet.py`), so
    ``make_inference_server(router)`` just works."""
    if batcher == "auto":
        if hasattr(model, "fleet_status"):
            return model
        return DynamicBatcher.from_env(model)
    return batcher


class InferenceServer:
    def __init__(self, model: InferenceModel, host: str = "127.0.0.1",
                 port: int = 0, batcher="auto", gen_batcher="auto"):
        self.model = model
        self.batcher = _resolve_batcher(model, batcher)
        self.gen_batcher = _resolve_gen_batcher(model, gen_batcher)
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _reply(self, code: int, payload: dict,
                       headers: Optional[dict] = None):
                body = json.dumps(payload).encode()
                self._reply_raw(code, body, "application/json",
                                headers)

            def _reply_raw(self, code: int, body: bytes,
                           ctype: str,
                           headers: Optional[dict] = None):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                if code == 503:
                    err = {}
                    try:
                        err = json.loads(body).get("error", {})
                    except ValueError:
                        pass
                    retry = err.get("retry_after_s")
                    if retry is not None:
                        import math
                        self.send_header(
                            "Retry-After",
                            str(max(1, math.ceil(retry))))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                t0 = time.perf_counter()
                _in_flight().inc()
                status = 0
                payload = None
                raw = None  # (body, ctype) short-circuits _reply
                route = self.path.split("?", 1)[0]
                try:
                    if route == "/health":
                        status = 200
                        payload = _health_payload(
                            server.model, server.batcher,
                            server.gen_batcher)
                    elif route == "/metrics" and \
                            "fleet=1" in self.path:
                        status, body = _fleet_metrics_text(
                            self.path, server.batcher)
                        if body is None:
                            payload = _error_body(
                                404, "no fleet telemetry "
                                "collector mounted")
                        else:
                            raw = (body,
                                   "text/plain; version=0.0.4")
                    elif route == "/metrics":
                        status = 200  # rendered after accounting
                    elif route == "/metrics/json":
                        status = 200
                        payload = _metrics_json_payload()
                    elif route == "/debug/traces":
                        status = 200
                        payload = _traces_payload(
                            self.path, server.batcher)
                    elif route.startswith("/debug/trace/"):
                        status, payload = _stitched_trace_payload(
                            route, self.path, server.batcher)
                    elif route == "/debug/slo":
                        status = 200
                        payload = _slo_payload(self.path)
                    elif route == "/debug/fleet/telemetry":
                        status, payload = _fleet_telemetry_payload(
                            server.batcher)
                    elif route == "/debug/fleet":
                        status, payload = _fleet_payload(
                            server.batcher, server.gen_batcher)
                    elif route == "/debug/rollout":
                        status, payload = _rollout_payload(
                            server.batcher)
                    elif route == "/debug/metrics/history":
                        status, payload = _history_payload(
                            self.path, server.batcher)
                    elif route == "/debug/dashboard":
                        status = 200
                        raw = (_dashboard_html(),
                               "text/html; charset=utf-8")
                    else:
                        status = 404
                        _count_error("not_found")
                        payload = _error_body(
                            404, "not found", path=route)
                finally:
                    # account BEFORE replying: a client that scrapes
                    # /metrics right after a response must see its own
                    # request already counted (and in-flight back at 0)
                    _in_flight().dec()
                    _record_request(self.path, status,
                                    time.perf_counter() - t0)
                if raw is None and payload is None:
                    # local /metrics renders AFTER accounting so the
                    # scrape sees itself counted
                    raw = (_metrics_text(),
                           "text/plain; version=0.0.4")
                if raw is not None:
                    self._reply_raw(status, raw[0], raw[1])
                else:
                    self._reply(status, payload)

            def do_POST(self):
                t0 = time.perf_counter()
                _in_flight().inc()
                status = 0
                trace_id = None
                route = self.path.split("?", 1)[0]
                try:
                    if route not in ("/predict", "/generate",
                                     "/generate/prefill",
                                     "/generate/handoff",
                                     "/debug/profile"):
                        status = 404
                        _count_error("not_found")
                        payload = _error_body(
                            404, "not found", path=route)
                    else:
                        try:
                            n = int(self.headers.get(
                                "Content-Length", 0))
                            body = self.rfile.read(n)
                        except Exception as e:  # client gone
                            status = 400
                            _count_error("bad_request")
                            payload = _error_body(400, str(e))
                        else:
                            if route == "/debug/profile":
                                status, payload = handle_profile(
                                    body)
                            else:
                                with tracing.trace(
                                        "serving/request",
                                        trace_id=self.headers.get(
                                            tracing.TRACE_HEADER),
                                        path=route) as tr:
                                    if route == \
                                            "/generate/prefill":
                                        status, payload = \
                                            handle_prefill(
                                                server.model, body,
                                                server.gen_batcher)
                                    elif route == \
                                            "/generate/handoff":
                                        status, payload = \
                                            handle_handoff(
                                                server.model, body,
                                                server.gen_batcher)
                                    elif route == "/generate":
                                        status, payload = \
                                            handle_generate(
                                                server.model, body,
                                                server.gen_batcher)
                                    else:
                                        status, payload = \
                                            handle_predict(
                                                server.model, body,
                                                batcher=server
                                                .batcher)
                                    tr.annotate(status=status)
                                trace_id = tr.trace_id
                finally:
                    _in_flight().dec()
                    _record_request(route, status,
                                    time.perf_counter() - t0)
                self._reply(
                    status, payload,
                    {tracing.TRACE_HEADER: trace_id}
                    if trace_id else None)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    def start(self, background: bool = True):
        # bucket warm-up happens HERE (AOT, before traffic): steady
        # state then serves any request-size mix with zero compiles
        if self.batcher is not None:
            self.batcher.start()
        if self.gen_batcher is not None:
            self.gen_batcher.start()
        # shipped serving objectives + background evaluation ticker
        # (docs/slo.md; ZOO_TPU_SLO=0 disables); a fleet front door
        # adds the fleet-level objectives on top. The SLO ticker
        # also feeds the shared MetricHistory, which the capacity
        # forecaster rides (docs/observability.md §Forecasting).
        slo_lib.ensure_default_slos("serving")
        slo_lib.ensure_default_slos("forecast")
        forecast_lib.ensure_forecaster()
        if hasattr(self.batcher, "fleet_status"):
            slo_lib.ensure_default_slos("fleet")
            if _fed_collector(self.batcher) is not None:
                slo_lib.ensure_default_slos("fed")
        if background:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever, daemon=True)
            self._thread.start()
        else:
            self._httpd.serve_forever()
        return self

    def stop(self):
        self._httpd.shutdown()
        if self._thread:
            self._thread.join(timeout=5)
        if self.batcher is not None:
            self.batcher.stop()
        if self.gen_batcher is not None:
            self.gen_batcher.stop()


class NativeInferenceServer:
    """Same /predict contract as :class:`InferenceServer`, fronted by
    the C++ HTTP server (`native/src/serving_http.cpp`): socket accept,
    HTTP parsing, request queueing, and /health all run native (no GIL
    contention with the XLA dispatch thread) — the role the reference's
    JVM/Spring + JNI serving stack played (SURVEY §2.8/§2.11.2).

    Worker threads (= model concurrency) pull raw request bytes over
    the C ABI, run `InferenceModel.predict`, and post response bytes
    back. ``GET /metrics`` routes through the worker (Python owns the
    registry); /health stays native.
    """

    def __init__(self, model: InferenceModel, port: int = 0,
                 workers: Optional[int] = None, batcher="auto",
                 gen_batcher="auto"):
        from analytics_zoo_tpu.native import NativeHttpServer
        self.model = model
        self.batcher = _resolve_batcher(model, batcher)
        self.gen_batcher = _resolve_gen_batcher(model, gen_batcher)
        self._srv = NativeHttpServer(port=port)
        self._workers = workers or model.supported_concurrent_num
        self._threads: "list[threading.Thread]" = []
        self._stopping = False

    @property
    def port(self) -> int:
        return self._srv.port

    def _serve_one(self, rid: int, path: str, body: bytes,
                   trace_hdr: "Optional[str]" = None):
        t0 = time.perf_counter()
        _in_flight().inc()
        status = 0
        out = b""
        trace_id = None
        route = path.split("?", 1)[0]
        try:
            if route == "/metrics" and "fleet=1" in path:
                status, body = _fleet_metrics_text(
                    path, self.batcher)
                out = body if body is not None else json.dumps(
                    _error_body(404, "no fleet telemetry "
                                "collector mounted")).encode()
            elif route == "/metrics":
                status = 200
                out = None  # rendered after accounting, below
            elif route == "/metrics/json":
                status = 200
                out = json.dumps(_metrics_json_payload()).encode()
            elif route == "/debug/traces":
                status = 200
                out = json.dumps(_traces_payload(
                    path, self.batcher)).encode()
            elif route.startswith("/debug/trace/"):
                status, payload = _stitched_trace_payload(
                    route, path, self.batcher)
                out = json.dumps(payload).encode()
            elif route == "/debug/slo":
                status = 200
                out = json.dumps(_slo_payload(path)).encode()
            elif route == "/debug/fleet/telemetry":
                status, payload = _fleet_telemetry_payload(
                    self.batcher)
                out = json.dumps(payload).encode()
            elif route == "/debug/fleet":
                status, payload = _fleet_payload(self.batcher,
                                                 self.gen_batcher)
                out = json.dumps(payload).encode()
            elif route == "/debug/rollout":
                status, payload = _rollout_payload(self.batcher)
                out = json.dumps(payload).encode()
            elif route == "/debug/metrics/history":
                status, payload = _history_payload(
                    path, self.batcher)
                out = json.dumps(payload).encode()
            elif route == "/debug/dashboard":
                status = 200
                out = _dashboard_html()
            elif route == "/debug/profile":
                status, payload = handle_profile(body)
                out = json.dumps(payload).encode()
            elif route not in ("/predict", "/generate",
                               "/generate/prefill",
                               "/generate/handoff"):
                status = 404
                _count_error("not_found")
                out = json.dumps(
                    _error_body(404, "not found",
                                path=route)).encode()
            else:
                with tracing.trace("serving/request",
                                   trace_id=trace_hdr,
                                   path=route) as tr:
                    if route == "/generate/prefill":
                        status, payload = handle_prefill(
                            self.model, body, self.gen_batcher)
                    elif route == "/generate/handoff":
                        status, payload = handle_handoff(
                            self.model, body, self.gen_batcher)
                    elif route == "/generate":
                        status, payload = handle_generate(
                            self.model, body, self.gen_batcher)
                    else:
                        status, payload = handle_predict(
                            self.model, body, batcher=self.batcher)
                    tr.annotate(status=status)
                trace_id = tr.trace_id
                out = json.dumps(payload).encode()
        except Exception as e:
            status = 500
            out = json.dumps(_error_body(
                500, str(e), kind="internal")).encode()
        finally:
            # account BEFORE responding: a client that scrapes
            # /metrics right after its response must see this request
            # already counted (and in-flight back at 0)
            _in_flight().dec()
            _record_request(route, status, time.perf_counter() - t0)
        if out is None:
            out = _metrics_text()
        try:
            self._srv.respond(rid, status, out, trace_id=trace_id)
        except Exception:
            pass  # client gone — nothing to tell it
        # refresh the C++-cached health AFTER the slot freed, so
        # /health reflects post-request capacity (and current
        # batcher queue state; the native front-end cannot set a
        # Retry-After header, so 503 bodies carry retry_after_s)
        self._srv.set_health(json.dumps(
            _health_payload(self.model, self.batcher,
                            self.gen_batcher)))

    def _loop(self):
        from analytics_zoo_tpu.common.nncontext import logger
        while not self._stopping:
            try:
                got = self._srv.next_request(timeout_ms=200)
            except StopIteration:
                return
            except Exception as e:  # transient — keep the worker alive
                if self._stopping:
                    return
                logger.warning("native serving worker error: %s", e)
                continue
            if got is None:
                continue
            self._serve_one(*got)

    def start(self, background: bool = True):
        if self.batcher is not None:
            self.batcher.start()
        if self.gen_batcher is not None:
            self.gen_batcher.start()
        slo_lib.ensure_default_slos("serving")
        slo_lib.ensure_default_slos("forecast")
        forecast_lib.ensure_forecaster()
        if hasattr(self.batcher, "fleet_status"):
            slo_lib.ensure_default_slos("fleet")
            if _fed_collector(self.batcher) is not None:
                slo_lib.ensure_default_slos("fed")
        self._srv.set_health(json.dumps(
            _health_payload(self.model, self.batcher,
                            self.gen_batcher)))
        for _ in range(self._workers):
            t = threading.Thread(target=self._loop, daemon=True)
            t.start()
            self._threads.append(t)
        if not background:
            for t in self._threads:
                t.join()
        return self

    def stop(self):
        # workers drain first (they poll with a 200ms timeout; an
        # in-flight predict finishes), THEN the native handle is
        # destroyed — never while a thread may be inside zoo_http_*.
        # If a worker is wedged (hung predict), leak the native handle
        # instead of freeing under it or hanging the caller forever.
        self._stopping = True
        deadline = time.monotonic() + 60.0
        for t in self._threads:
            t.join(timeout=max(deadline - time.monotonic(), 0.1))
        if self.batcher is not None:
            self.batcher.stop()
        if self.gen_batcher is not None:
            self.gen_batcher.stop()
        if any(t.is_alive() for t in self._threads):
            from analytics_zoo_tpu.common.nncontext import logger
            logger.warning(
                "native serving: a worker is still busy after 60s; "
                "leaking the native server handle instead of freeing "
                "it underneath the worker")
            return
        self._srv.close()


def make_inference_server(model: InferenceModel, port: int = 0,
                          prefer_native: bool = True,
                          batcher="auto", gen_batcher="auto"):
    """Native C++ front-end when the toolchain built it, else the
    stdlib ThreadingHTTPServer (with a warning that says why) — same
    endpoints either way.
    ``batcher``: ``"auto"`` (env-configured dynamic batching),
    ``None`` (per-request), or a :class:`DynamicBatcher`.
    ``gen_batcher``: same trio for /generate — ``"auto"`` mounts a
    :class:`ContinuousBatcher` iff the model has a generator loaded
    (and ``ZOO_TPU_GEN_BATCH`` != 0)."""
    if prefer_native:
        try:
            return NativeInferenceServer(model, port=port,
                                         batcher=batcher,
                                         gen_batcher=gen_batcher)
        except (RuntimeError, OSError) as e:
            from analytics_zoo_tpu.common.nncontext import logger
            logger.warning(
                "native HTTP front-end unavailable (%s: %s); serving "
                "from the stdlib front-end", type(e).__name__, e)
    return InferenceServer(model, port=port, batcher=batcher,
                           gen_batcher=gen_batcher)
