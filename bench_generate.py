"""Generation-path benchmark: continuous batching vs sequential decode.

Closed-loop multi-client harness over the decode fast path
(`pipeline/inference/generation.py` + `ContinuousBatcher`): N client
threads each submit generation requests (mixed prompt lengths and
decode budgets) as fast as results return, for a fixed wall-clock
window. Run twice:

- **continuous** — every client submits into the live
  `ContinuousBatcher`; sequences share ONE compiled decode step and
  join/leave at token boundaries (ORCA-style iteration scheduling);
- **continuous+levers** (only when a lever flag is set) — the same
  harness on a second engine with the requested capacity levers,
  so the artifact carries a levers-off/levers-on A/B on identical
  traffic;
- **sequential** — the per-request baseline: one compiled whole-loop
  `generate` at a time (`InferenceModel.generate`, batch 1),
  serialized the way per-request decode actually serializes.

Reports tokens/sec, request latency p50/p99, and mean time-to-first-
token for every mode. The levered window (or the plain continuous
one when no levers are set) also runs a small pool of closed-loop
TTFT probe clients: alternating short and LONG single-token requests
whose per-request latencies give `ttft_{short,long}_p{50,99}_ms` —
the chunked-prefill acceptance signal is long-prompt TTFT p99
staying within 1.5x of short-prompt p99 while decode traffic flows
(several probe clients so each shape's p99 rests on hundreds of
samples taken at realistic slot occupancy, not the max of a hundred
lightly-loaded ones). Note the CPU host
under-reports the levered mode's throughput: per-iteration dispatch
overhead dominates the tiny toy model, so speculation's extra
tokens/step (~9.7 vs ~5.6 levers-off in the committed artifact) do
not translate into CPU tokens/s the way they do on a
bandwidth-bound accelerator decode.

``--disagg`` adds the disaggregated-serving A/B on top (ISSUE 19 /
docs/serving.md §Disaggregation): the same closed-loop mix — sized
up so the decode pool saturates — through a ``DisaggRouter`` over
1 prefill + 2 decode replicas, measured twice: **disagg-inproc**
(blob hands off as a host dict) and **disagg-http** (the same
warmed pool engines behind stdlib HTTP front-ends, pages base64 on
the wire). Both windows run the TTFT probe: with prefill on its own
pool the long/short p99 ratio stays ≈1 even while every decode slot
is busy — the contention case a monolithic engine cannot shield —
and the artifact's ``disagg{...}`` block records the ratio plus the
per-window handoff latency quantiles from
``zoo_tpu_serving_gen_handoff_seconds``.

The capacity levers are A/B'd from the command line and recorded in
the artifact's sentinel key block: ``--prefill-chunk N`` (chunked
prefill), ``--kv-dtype f32|bf16|int8`` (paged-cache storage), and
``--spec-k N`` (speculative decoding with a half-width drafter; the
continuous record then carries ``spec_accept_rate`` and the realized
``tokens_per_step``). Prints ONE JSON line in the bench_common
artifact schema and ALSO writes it to ``BENCH_generate.json``:

    {"metric": "generate_throughput_tokens_per_sec",
     "unit": "tokens/sec", "value": N, "vs_baseline": null,
     "generate": {...}, "extra_metrics": [...], "telemetry": {...}}

The ``"generate"`` block (slots, page_size, max_context, clients) is
what `scripts/perf_sentinel.py` keys on to give generation runs their
own lineage — decode tokens/s is never compared against predict-path
rows/s. With ``--cpu-fallback`` the headline ``value`` is null and
the measured number moves to ``cpu_fallback_value`` (the schema's
rule: a null headline can never be mistaken for chip perf). The
acceptance gate is continuous >= sequential tokens/s at >= 4
concurrent clients.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_t_start = time.perf_counter()

# mixed workload, cycled per client: (prompt_len, max_new_tokens) —
# varied on both axes so admission is genuinely staggered and the
# prompt-bucket ladder is exercised past one shape
# short conversational shapes plus two long-prompt entries so the
# background mix actually exercises chunked prefill (PR 17): under
# monolithic prefill the long prompts inflate every neighbour's
# latency; under chunking they amortize one chunk per iteration
WORK_MIX = ((4, 16), (9, 24), (17, 8), (6, 32), (12, 16), (27, 12),
            (72, 8), (100, 6))

SLOTS = 8
SEQ_LEN = 128
VOCAB = 256

# TTFT probe shapes: single-token requests whose request latency IS
# the time to first token; the long one spans many prefill chunks.
# Several closed-loop probe clients run at once so the per-shape p99
# rests on hundreds of samples at realistic slot occupancy instead of
# being the max of ~100 lightly-loaded ones.
PROBE_SHORT, PROBE_LONG = 4, 100
PROBE_CLIENTS = 3


def _build_engine(prefill_chunk=0, spec_k=0, kv_dtype="f32",
                  slots=SLOTS, role="both"):
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.api.keras.layers.transformer \
        import TransformerLayer
    from analytics_zoo_tpu.pipeline.inference import InferenceModel

    from analytics_zoo_tpu.common import diagnostics

    init_nncontext(seed=0, log_level="WARNING")
    import jax
    # enough width that a decode step has real matmul traffic, small
    # enough that the CPU host finishes the window in seconds
    net = TransformerLayer(n_block=2, hidden_size=128, n_head=4,
                           seq_len=SEQ_LEN, vocab=VOCAB,
                           hidden_p_drop=0.0, attn_p_drop=0.0,
                           embed_p_drop=0.0)
    # param-init and loader compiles are deliberate bench setup, not
    # a storm (the engine excuses its own warm() internally)
    with diagnostics.expected_compiles():
        params = net.build(jax.random.key(0), (SEQ_LEN,))
        kw = dict(max_slots=slots, max_context=SEQ_LEN, page_size=16,
                  prefill_chunk=prefill_chunk, spec_k=spec_k,
                  cache_dtype=kv_dtype, role=role)
        if spec_k > 0:
            # half-width, half-depth drafter sharing the vocabulary
            drafter = TransformerLayer(n_block=1, hidden_size=64,
                                       n_head=4, seq_len=SEQ_LEN,
                                       vocab=VOCAB, hidden_p_drop=0.0,
                                       attn_p_drop=0.0,
                                       embed_p_drop=0.0)
            kw["drafter"] = drafter
            kw["drafter_params"] = drafter.build(jax.random.key(1),
                                                 (SEQ_LEN,))
        im = InferenceModel()
        im.load_generator(net, params, **kw)
    return im


def _ttft_mean_ms(before: "tuple[float, float]") -> "float | None":
    """Mean time-to-first-token over the window, from the serving
    histogram's (sum, count) delta. None when nothing was observed."""
    from analytics_zoo_tpu.common import observability as obs
    h = obs.histogram("zoo_tpu_serving_gen_ttft_seconds",
                      help="time from submit to first generated token")
    ds, dc = h.sum - before[0], h.count - before[1]
    return round(ds / dc * 1e3, 2) if dc else None


def _ttft_state() -> "tuple[float, float]":
    from analytics_zoo_tpu.common import observability as obs
    h = obs.histogram("zoo_tpu_serving_gen_ttft_seconds",
                      help="time from submit to first generated token")
    return h.sum, h.count


def _run_clients(submit, clients: int, duration_s: float):
    """Closed loop: every client submits back-to-back until the
    window closes. ``submit(prompt, max_new) -> token array``.
    Returns (tokens_done, request_latencies_s, errors)."""
    rs = np.random.RandomState(7)
    prompts = {n: rs.randint(1, VOCAB, size=n).tolist()
               for n, _ in WORK_MIX}
    stop_at = time.perf_counter() + duration_s
    lock = threading.Lock()
    lat, toks, errors = [], [0], [0]

    def client(cid: int):
        i = cid  # stagger the mix across clients
        while time.perf_counter() < stop_at:
            n, max_new = WORK_MIX[i % len(WORK_MIX)]
            i += 1
            t0 = time.perf_counter()
            try:
                out = submit(prompts[n], max_new)
            except Exception:  # load generator: count, keep going
                with lock:
                    errors[0] += 1
                continue
            dt = time.perf_counter() - t0
            with lock:
                lat.append(dt)
                toks[0] += len(out)

    ts = [threading.Thread(target=client, args=(c,))
          for c in range(clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return toks[0], lat, errors[0]


def _run_ttft_probe(submit, duration_s: float) -> dict:
    """PROBE_CLIENTS extra closed-loop clients alternating short/long
    single-token prompts while the mix clients keep the decode batch
    busy: each request's latency IS its TTFT. Returns per-shape
    p50/p99 (ms) and the long/short p99 ratio the chunked-prefill
    acceptance gate reads."""
    rs = np.random.RandomState(11)
    prompts = {n: rs.randint(1, VOCAB, size=n).tolist()
               for n in (PROBE_SHORT, PROBE_LONG)}
    samples = {PROBE_SHORT: [], PROBE_LONG: []}
    errors = [0]
    lock = threading.Lock()
    stop_at = time.perf_counter() + duration_s
    shapes = (PROBE_SHORT, PROBE_LONG)

    def client(cid: int):
        i = cid  # offset so clients interleave shapes
        while time.perf_counter() < stop_at:
            n = shapes[i % 2]
            i += 1
            t0 = time.perf_counter()
            try:
                submit(prompts[n], 1)
            except Exception:  # load generator: count, keep probing
                with lock:
                    errors[0] += 1
                continue
            dt = (time.perf_counter() - t0) * 1e3
            with lock:
                samples[n].append(dt)

    ts = [threading.Thread(target=client, args=(c,))
          for c in range(PROBE_CLIENTS)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    out = {"ttft_probe_errors": errors[0]}
    for n, name in ((PROBE_SHORT, "short"), (PROBE_LONG, "long")):
        if not samples[n]:  # a failed probe is not a 0 ms TTFT
            raise RuntimeError(
                f"TTFT probe: no {name} request succeeded "
                f"({errors[0]} errors)")
        arr = np.asarray(samples[n])
        out[f"ttft_{name}_p50_ms"] = round(
            float(np.percentile(arr, 50)), 2)
        out[f"ttft_{name}_p99_ms"] = round(
            float(np.percentile(arr, 99)), 2)
        out[f"ttft_{name}_samples"] = len(samples[n])
    p99s, p99l = out["ttft_short_p99_ms"], out["ttft_long_p99_ms"]
    out["ttft_long_vs_short_p99"] = (
        round(p99l / p99s, 2) if p99s else None)
    return out


def _counter_value(name: str) -> float:
    from analytics_zoo_tpu.common import observability as obs
    return obs.counter(name, help=name).value


def _handoff_hist():
    from analytics_zoo_tpu.common import observability as obs
    return obs.histogram(
        "zoo_tpu_serving_gen_handoff_seconds",
        help="prefill-pool export to decode-pool admission latency")


def _hist_counts(h) -> "list[int]":
    """Per-bucket counts (last = +Inf overflow) from the public
    cumulative exposition, so window deltas can be quantiled."""
    cum = [c for _, c in h.cumulative()]
    return [cum[0]] + [b - a for a, b in zip(cum, cum[1:])]


def _hist_window_quantiles(h, before: "list[int]") -> dict:
    """p50/p99 (ms) + count of the observations since ``before``
    (a `_hist_counts` snapshot) — per-mode handoff latency even
    though the histogram accumulates across the whole bench."""
    from analytics_zoo_tpu.common.observability import bucket_quantile
    delta = [b - a for a, b in zip(before, _hist_counts(h))]
    n = sum(delta)
    if not n:
        return {"handoffs": 0}
    return {
        "handoffs": n,
        "handoff_p50_ms": round(
            bucket_quantile(h.buckets, delta, 0.5) * 1e3, 2),
        "handoff_p99_ms": round(
            bucket_quantile(h.buckets, delta, 0.99) * 1e3, 2),
    }


def _measure_disagg(mode: str, router, im, clients: int,
                    duration_s: float) -> dict:
    """One disagg window: the standard closed-loop mix (sized to
    saturate the decode pool) + the TTFT probe, annotated with the
    window's handoff latency quantiles."""
    h = _handoff_hist()
    before = _hist_counts(h)
    rec = measure(mode, im, clients, duration_s, probe_ttft=True,
                  router=router)
    rec.update(_hist_window_quantiles(h, before))
    return rec


def measure(mode: str, im, clients: int, duration_s: float,
            probe_ttft: bool = False, router=None) -> dict:
    from analytics_zoo_tpu.pipeline.inference import ContinuousBatcher

    engine = im.generator
    cb = None
    if router is not None:
        # disaggregated path: the router fans prompts to the prefill
        # pool and ships KV pages to the decode pool (caller owns the
        # router's lifecycle — pools warm at router.start())
        def submit(prompt, max_new):
            return router.submit(prompt,
                                 max_new_tokens=max_new).result(120)
    elif mode.startswith("continuous"):
        cb = ContinuousBatcher(engine, queue_depth=512).start()

        def submit(prompt, max_new):
            return cb.submit(prompt,
                             max_new_tokens=max_new).result(120)
    else:
        # sequential per-request decode: whole-loop generate, batch 1,
        # one at a time — the engine is single-driver by contract, and
        # that serialization IS the baseline being measured
        seq_lock = threading.Lock()

        def submit(prompt, max_new):
            with seq_lock:
                return im.generate(prompt,
                                   max_new_tokens=max_new)[0]
    stream = cb is not None or router is not None
    probe_rec = {}
    try:
        # warmup outside the window: every (bucket, budget) shape in
        # the mix compiles here, not inside the measurement. The
        # sequential path compiles on THIS thread (the continuous
        # one brackets its own warm()), so excuse the burst from the
        # recompile-storm detector — it is deliberate.
        from analytics_zoo_tpu.common import diagnostics
        with diagnostics.expected_compiles():
            for n, max_new in WORK_MIX:
                submit(list(range(1, n + 1)), max_new)
            if stream:
                submit(list(range(1, PROBE_LONG + 1)), 1)  # probe
                submit(list(range(1, PROBE_SHORT + 1)), 1)
        ttft0 = _ttft_state()
        tok0 = _counter_value("zoo_tpu_serving_gen_tokens_total")
        step0 = _counter_value("zoo_tpu_serving_gen_steps_total")
        spec0 = (engine.spec_proposed, engine.spec_accepted) \
            if getattr(engine, "spec_k", 0) else None
        t0 = time.perf_counter()
        # the probe runs beside the mix clients; result() re-raises
        # what a bare thread would only print
        with ThreadPoolExecutor(max_workers=1) as pool:
            fut = pool.submit(_run_ttft_probe, submit, duration_s) \
                if stream and probe_ttft else None
            tokens, lat, errors = _run_clients(submit, clients,
                                               duration_s)
            if fut is not None:
                probe_rec = fut.result()
        window = time.perf_counter() - t0
        d_tok = _counter_value(
            "zoo_tpu_serving_gen_tokens_total") - tok0
        d_step = _counter_value(
            "zoo_tpu_serving_gen_steps_total") - step0
    finally:
        if cb is not None:
            cb.stop()
    if not lat:  # a window in which nothing finished is no result
        raise RuntimeError(
            f"[{mode}] no request succeeded ({errors} errors)")
    lat_ms = np.asarray(lat) * 1e3
    rec = {
        "mode": mode,
        "clients": clients,
        "window_s": round(window, 2),
        "requests": len(lat),
        "tokens_per_sec": round(tokens / window, 1),
        "requests_per_sec": round(len(lat) / window, 1),
        "latency_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "latency_p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "errors": errors,
    }
    ttft = _ttft_mean_ms(ttft0)
    # sequential has no streaming boundary: first token arrives with
    # the rest, so mean latency IS its time-to-first-token
    rec["ttft_mean_ms"] = (ttft if stream
                           else round(float(np.mean(lat_ms)), 2))
    if stream:
        rec.update(probe_rec)
        # realized tokens per decode iteration: > 1 only when
        # speculation lands multi-token rounds
        rec["tokens_per_step"] = (round(d_tok / d_step, 2)
                                  if d_step else None)
        if spec0 is not None:
            dp = engine.spec_proposed - spec0[0]
            da = engine.spec_accepted - spec0[1]
            rec["spec_proposed"] = int(dp)
            rec["spec_accepted"] = int(da)
            rec["spec_accept_rate"] = (round(da / dp, 3)
                                       if dp else None)
    print(f"# [{mode}] {rec['tokens_per_sec']} tok/s "
          f"{rec['requests_per_sec']} req/s "
          f"p50={rec['latency_p50_ms']}ms "
          f"p99={rec['latency_p99_ms']}ms "
          f"ttft={rec['ttft_mean_ms']}ms errors={errors}",
          file=sys.stderr, flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--clients", type=int, default=int(os.environ.get(
        "ZOO_TPU_BENCH_GEN_CLIENTS", "6")))
    ap.add_argument("--duration", type=float,
                    default=float(os.environ.get(
                        "ZOO_TPU_BENCH_GEN_DURATION", "6")))
    ap.add_argument("--prefill-chunk", type=int, default=int(
        os.environ.get("ZOO_TPU_PREFILL_CHUNK", "0")),
        help="chunked prefill: prompt tokens written per batcher "
        "iteration (0 = whole-prompt bucketed prefill)")
    ap.add_argument("--spec-k", type=int, default=int(
        os.environ.get("ZOO_TPU_SPEC_K", "0")),
        help="speculative decoding: draft tokens per verify round "
        "(0 = off); the drafter is a half-width half-depth stack")
    ap.add_argument("--kv-dtype", default=os.environ.get(
        "ZOO_TPU_KV_DTYPE", "f32"),
        choices=("f32", "bf16", "int8"),
        help="paged KV cache storage dtype")
    ap.add_argument("--disagg", action="store_true",
                    help="add the disaggregated-serving A/B: the "
                    "same mix through a DisaggRouter (1 prefill + 2 "
                    "decode replicas) in-process AND over an HTTP "
                    "hop, with the decode pool saturated; the "
                    "artifact gains a disagg{...} block and its own "
                    "perf_sentinel lineage")
    ap.add_argument("--cpu-fallback", action="store_true",
                    help="pin the run to the host CPU backend; the "
                    "measurement lands in cpu_fallback_value and the "
                    "chip headline stays null")
    args = ap.parse_args()
    if args.disagg and args.spec_k > 0:
        ap.error("--disagg is incompatible with --spec-k (the "
                 "verify step needs prefill+decode on one engine)")

    import jax
    if args.cpu_fallback:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    print(f"# backend={devices[0].platform} "
          f"n_devices={len(devices)} clients={args.clients} "
          f"duration={args.duration}s slots={SLOTS} "
          f"prefill_chunk={args.prefill_chunk} "
          f"spec_k={args.spec_k} kv_dtype={args.kv_dtype}",
          file=sys.stderr, flush=True)

    levers_on = (args.prefill_chunk > 0 or args.spec_k > 0
                 or args.kv_dtype != "f32")
    # the A/B: the baseline (levers off) keeps the tokens/s lineage
    # comparable across PRs — continuous vs sequential on identical
    # engines — while the levered run carries the TTFT probe,
    # acceptance-rate and tokens/step fields the PR 17 gate reads
    im = _build_engine()
    continuous = measure("continuous", im, args.clients,
                         args.duration, probe_ttft=not levers_on)
    levered = None
    if levers_on:
        im_lev = _build_engine(prefill_chunk=args.prefill_chunk,
                               spec_k=args.spec_k,
                               kv_dtype=args.kv_dtype)
        levered = measure("continuous+levers", im_lev, args.clients,
                          args.duration, probe_ttft=True)
    sequential = measure("sequential", im, args.clients,
                         args.duration)
    speedup = (continuous["tokens_per_sec"]
               / sequential["tokens_per_sec"]
               if sequential["tokens_per_sec"] else float("inf"))
    print(f"# continuous speedup={speedup:.2f}x over sequential "
          f"per-request decode ({args.clients} clients)",
          file=sys.stderr, flush=True)

    disagg_inproc = disagg_http = disagg_block = None
    if args.disagg:
        from analytics_zoo_tpu.pipeline.inference import \
            ContinuousBatcher
        from analytics_zoo_tpu.pipeline.inference.fleet import (
            DisaggRouter, HttpDisaggReplica)
        from analytics_zoo_tpu.pipeline.inference.serving import \
            InferenceServer
        # small per-replica pools so the closed-loop mix actually
        # saturates the decode pool (the gate's contention case);
        # the prefill pool runs whole-prompt bucketed prefill —
        # chunking exists to protect co-resident decode, which
        # disaggregation removes
        d_slots = 4
        n_prefill, n_decode = 1, 2
        d_clients = max(args.clients, n_decode * d_slots + 2)
        im_d = _build_engine(kv_dtype=args.kv_dtype, slots=d_slots)
        router = DisaggRouter.for_engine(
            im_d.generator, n_prefill=n_prefill, n_decode=n_decode)
        router.start()
        disagg_inproc = _measure_disagg(
            "disagg-inproc", router, im_d, d_clients, args.duration)
        router.drain()
        pool = [(r.engine, r.role)
                for r in router.prefill + router.decode]
        router.stop()
        # HTTP hop: the SAME warmed pool engines behind stdlib HTTP
        # front-ends — the delta vs in-process is pure wire cost
        # (base64 pages + two request hops), no new compiles
        servers, reps = [], {"prefill": [], "decode": []}
        for i, (eng, role) in enumerate(pool):
            srv = InferenceServer(im_d, port=0, batcher=None,
                                  gen_batcher=ContinuousBatcher(eng))
            srv.start()
            servers.append(srv)
            reps[role].append(HttpDisaggReplica(
                f"http://127.0.0.1:{srv.port}", role,
                name=f"http-{role}{i}"))
        router2 = DisaggRouter(reps["prefill"], reps["decode"])
        router2.start()
        disagg_http = _measure_disagg(
            "disagg-http", router2, im_d, d_clients, args.duration)
        router2.stop()
        for srv in servers:
            srv.stop()
        ratio = disagg_inproc.get("ttft_long_vs_short_p99")
        disagg_block = {
            "prefill_replicas": n_prefill,
            "decode_replicas": n_decode,
            "slots_per_replica": d_slots,
            "page_size": 16,
            "kv_dtype": args.kv_dtype,
            "mix_clients": d_clients,
            "decode_slots": n_decode * d_slots,
            "ttft_long_vs_short_p99": ratio,
            "handoff_p50_ms": disagg_inproc.get("handoff_p50_ms"),
            "handoff_p99_ms": disagg_inproc.get("handoff_p99_ms"),
            "handoff_http_p50_ms": disagg_http.get(
                "handoff_p50_ms"),
            "handoff_http_p99_ms": disagg_http.get(
                "handoff_p99_ms"),
        }
        print(f"# disagg TTFT long/short p99 ratio={ratio} "
              f"(gate: <= 1.1 with the decode pool saturated); "
              f"handoff p99 in-proc="
              f"{disagg_block['handoff_p99_ms']}ms http="
              f"{disagg_block['handoff_http_p99_ms']}ms",
              file=sys.stderr, flush=True)

    headline = continuous["tokens_per_sec"]
    rec = {
        "metric": "generate_throughput_tokens_per_sec",
        "unit": "tokens/sec",
        "value": None if args.cpu_fallback else headline,
        "vs_baseline": None,
        # the sentinel keys on this block: generation runs are their
        # own lineage, never compared against predict-path rows
        "generate": {
            "slots": SLOTS,
            "page_size": 16,
            "max_context": SEQ_LEN,
            "clients": args.clients,
            "prefill_chunk": args.prefill_chunk,
            "spec_k": args.spec_k,
            "kv_dtype": args.kv_dtype,
        },
        "extra_metrics": [
            continuous,
            *([levered] if levered else []),
            sequential,
            *([disagg_inproc] if disagg_inproc else []),
            *([disagg_http] if disagg_http else []),
            {"metric": "generate_continuous_speedup",
             "value": round(speedup, 2), "unit": "x"},
        ],
    }
    if disagg_block is not None:
        # perf_sentinel keys on this block: disagg runs are their own
        # lineage, never compared against monolithic decode rows
        rec["disagg"] = disagg_block
    if args.cpu_fallback:
        rec["cpu_fallback_value"] = headline
        rec["fallback"] = (f"cpu clients={args.clients} "
                           f"duration={args.duration}s")
    from bench_common import attach_metrics_snapshot
    rec = attach_metrics_snapshot(rec)
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_generate.json")
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(json.dumps(rec), flush=True)
    print(f"# wrote {out_path}", file=sys.stderr)
    print(f"# total={time.perf_counter() - _t_start:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
