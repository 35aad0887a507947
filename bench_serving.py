"""Serving-path benchmark: dynamic batching vs per-request.

Closed-loop multi-client harness over the real HTTP front-end
(`pipeline/inference/serving.py`): N client threads each POST
/predict as fast as responses return, for a fixed wall-clock window,
with a mixed request-size workload (mostly singletons — the
pathological per-request shape — plus some small batches). Run twice,
batched (`DynamicBatcher`, docs/serving.md) and unbatched
(``batcher=None``), and report throughput (rows/sec) plus request
latency p50/p99 for both.

Prints ONE JSON line in the bench_common artifact schema:

    {"metric": "serving_throughput_rows_per_sec", "unit": "rows/sec",
     "value": N, "vs_baseline": null, "extra_metrics": [...],
     "telemetry": {...}}

``value`` is the BATCHED chip throughput; with ``--cpu-fallback`` the
run is pinned to the host CPU backend, ``value`` is null and the
measured number moves to ``cpu_fallback_value`` (the schema's rule: a
null headline can never be mistaken for chip perf). ``extra_metrics``
carries the unbatched counterpart, the latency percentiles for both
modes, and the speedup — the acceptance gate is >= 2x throughput with
>= 8 clients and batched p99 <= unbatched p99 + max_wait_ms.

``--replicas N`` switches to the FLEET A/B sweep instead: the same
closed-loop load against a 1-replica fleet and an N-replica fleet
(`pipeline/inference/fleet.py`; one virtual host device per replica,
forced via ``--xla_force_host_platform_device_count`` before jax
loads). The artifact gains a ``"fleet"`` block ({replicas,
host_cores, ...}) and is ALSO written to ``BENCH_serving_fleet.json``
— the perf sentinel keys on the block to give fleet runs their own
lineage, never compared against single-process serving rows. On a
host with fewer physical cores than replicas the sweep measures
router overhead, not real parallelism — ``host_cores`` is recorded
precisely so the reader can tell which one they are looking at.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

_t_start = time.perf_counter()

# mixed request-size workload, cycled per client: mostly single-row
# (the per-request pathology batching exists to fix), some batches
SIZE_MIX = (1, 1, 1, 2, 1, 4, 1, 2)


def _build_server(batched: bool, max_wait_ms: float):
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.api.keras import (
        Sequential, layers as L)
    from analytics_zoo_tpu.pipeline.inference import (
        DynamicBatcher, InferenceModel, InferenceServer)

    init_nncontext(seed=0, log_level="WARNING")
    # a forward with real weight traffic (a wide MLP tower): batch-1
    # inference is bound by streaming the weights, so coalescing
    # amortizes it — the same economics as the MXU's batch-1
    # starvation on chip. Batching has nothing to win when the
    # per-row compute is free.
    m = Sequential()
    m.add(L.Dense(4096, activation="relu", input_shape=(256,)))
    m.add(L.Dense(4096, activation="relu"))
    m.add(L.Dense(512, activation="relu"))
    m.add(L.Dense(10))
    m.compile(optimizer="sgd", loss="mse")
    im = InferenceModel(supported_concurrent_num=2)
    rs = np.random.RandomState(0)
    if batched:
        # declared example inputs: the batcher AOT-warms its whole
        # bucket ladder at server start from this signature
        im.load_keras_net(
            m, example_inputs=[rs.randn(8, 256).astype(np.float32)])
    else:
        # the per-request baseline must stay on the retraceable jit
        # path: an AOT fixed-shape executable cannot serve a mixed
        # request-size load at all (each size re-jits instead)
        im.load_keras_net(m)
    batcher = (DynamicBatcher(im, max_batch_size=32,
                              max_wait_ms=max_wait_ms,
                              queue_depth=512)
               if batched else None)
    return InferenceServer(im, port=0, batcher=batcher).start()


def _build_fleet_server(n_replicas: int, max_wait_ms: float):
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.api.keras import (
        Sequential, layers as L)
    from analytics_zoo_tpu.pipeline.inference import (
        make_fleet_server)
    from analytics_zoo_tpu.pipeline.inference.fleet import (
        FleetRouter, ReplicaPool)

    init_nncontext(seed=0, log_level="WARNING")
    m = Sequential()
    m.add(L.Dense(4096, activation="relu", input_shape=(256,)))
    m.add(L.Dense(4096, activation="relu"))
    m.add(L.Dense(512, activation="relu"))
    m.add(L.Dense(10))
    m.compile(optimizer="sgd", loss="mse")
    rs = np.random.RandomState(0)
    pool = ReplicaPool.for_keras(
        m, example_inputs=[rs.randn(8, 256).astype(np.float32)],
        n_replicas=n_replicas, devices_per_replica=1,
        batcher_kwargs={"max_batch_size": 32,
                        "max_wait_ms": max_wait_ms,
                        "queue_depth": 512})
    router = FleetRouter(pool)
    return make_fleet_server(router).start()


def _run_clients(port: int, clients: int, duration_s: float):
    """Closed loop: every client POSTs back-to-back until the window
    closes. Returns (rows_done, request_latencies_s, errors)."""
    url = f"http://127.0.0.1:{port}/predict"
    rs = np.random.RandomState(1)
    bodies = {
        n: json.dumps({"inputs": rs.randn(n, 256).round(3).tolist()}
                      ).encode()
        for n in sorted(set(SIZE_MIX))
    }
    stop_at = time.perf_counter() + duration_s
    lock = threading.Lock()
    lat, rows, errors = [], [0], [0]

    def client(cid: int):
        i = cid  # stagger the size mix across clients
        while time.perf_counter() < stop_at:
            n = SIZE_MIX[i % len(SIZE_MIX)]
            i += 1
            t0 = time.perf_counter()
            try:
                with urllib.request.urlopen(
                        urllib.request.Request(url, data=bodies[n]),
                        timeout=60) as r:
                    r.read()
            except Exception:  # load generator: count, keep going
                with lock:
                    errors[0] += 1
                continue
            dt = time.perf_counter() - t0
            with lock:
                lat.append(dt)
                rows[0] += n
    ts = [threading.Thread(target=client, args=(c,))
          for c in range(clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return rows[0], lat, errors[0]


def measure(mode: str, clients: int, duration_s: float,
            max_wait_ms: float, replicas: int = 0) -> dict:
    if replicas:
        srv = _build_fleet_server(replicas, max_wait_ms)
    else:
        srv = _build_server(batched=(mode == "batched"),
                            max_wait_ms=max_wait_ms)
    try:
        # warmup outside the window: compiles every size in the mix
        # on the unbatched path (the batched path warmed at start())
        _run_clients(srv.port, clients, min(1.0, duration_s))
        t0 = time.perf_counter()
        rows, lat, errors = _run_clients(srv.port, clients,
                                         duration_s)
        window = time.perf_counter() - t0
    finally:
        srv.stop()
    if not lat:  # a window in which nothing finished is no result
        raise RuntimeError(
            f"[{mode}] no request succeeded ({errors} errors)")
    lat_ms = np.asarray(lat) * 1e3
    rec = {
        "mode": mode,
        "clients": clients,
        "window_s": round(window, 2),
        "requests": len(lat),
        "rows_per_sec": round(rows / window, 1),
        "requests_per_sec": round(len(lat) / window, 1),
        "latency_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "latency_p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "errors": errors,
    }
    print(f"# [{mode}] {rec['rows_per_sec']} rows/s "
          f"{rec['requests_per_sec']} req/s "
          f"p50={rec['latency_p50_ms']}ms "
          f"p99={rec['latency_p99_ms']}ms errors={errors}",
          file=sys.stderr, flush=True)
    return rec


def _main_fleet(args):
    """``--replicas N``: the fleet A/B sweep. Same closed-loop load,
    1-replica fleet vs N-replica fleet, artifact to stdout AND
    ``BENCH_serving_fleet.json`` (own perf-sentinel lineage)."""
    one = measure("fleet1", args.clients, args.duration,
                  args.max_wait_ms, replicas=1)
    many = measure(f"fleet{args.replicas}", args.clients,
                   args.duration, args.max_wait_ms,
                   replicas=args.replicas)
    speedup = (many["rows_per_sec"] / one["rows_per_sec"]
               if one["rows_per_sec"] else float("inf"))
    cores = os.cpu_count() or 1
    print(f"# fleet speedup={speedup:.2f}x over 1 replica "
          f"(replicas={args.replicas}, host_cores={cores})",
          file=sys.stderr, flush=True)

    headline = many["rows_per_sec"]
    rec = {
        "metric": "serving_fleet_throughput_rows_per_sec",
        "unit": "rows/sec",
        "value": None if args.cpu_fallback else headline,
        "vs_baseline": None,
        # the sentinel keys on this block: fleet runs are their own
        # lineage, never compared against single-process rows.
        # host_cores tells the reader whether N replicas had N cores
        # to scale onto or were time-slicing one (router-overhead
        # measurement, not real parallelism).
        "fleet": {
            "replicas": args.replicas,
            "devices_per_replica": 1,
            "policy": "least_loaded",
            "host_cores": cores,
        },
        "extra_metrics": [
            one, many,
            {"metric": "serving_fleet_speedup",
             "value": round(speedup, 2), "unit": "x"},
        ],
    }
    if args.cpu_fallback:
        rec["cpu_fallback_value"] = headline
        rec["fallback"] = (f"cpu clients={args.clients} "
                           f"duration={args.duration}s "
                           f"replicas={args.replicas}")
    from bench_common import attach_metrics_snapshot
    rec = attach_metrics_snapshot(rec)
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_serving_fleet.json")
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(rec, fh)
        fh.write("\n")
    print(json.dumps(rec), flush=True)
    print(f"# wrote {out_path}", file=sys.stderr)
    print(f"# total={time.perf_counter() - _t_start:.1f}s",
          file=sys.stderr)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--clients", type=int, default=int(os.environ.get(
        "ZOO_TPU_BENCH_SERVING_CLIENTS", "12")))
    ap.add_argument("--duration", type=float,
                    default=float(os.environ.get(
                        "ZOO_TPU_BENCH_SERVING_DURATION", "5")))
    ap.add_argument("--max-wait-ms", type=float,
                    default=float(os.environ.get(
                        "ZOO_TPU_SERVING_MAX_WAIT_MS", "2")))
    ap.add_argument("--cpu-fallback", action="store_true",
                    help="pin the run to the host CPU backend; the "
                    "measurement lands in cpu_fallback_value and the "
                    "chip headline stays null")
    ap.add_argument("--replicas", type=int, default=0,
                    help="fleet A/B sweep: 1 replica vs N replicas "
                    "behind the FleetRouter, writing "
                    "BENCH_serving_fleet.json (own sentinel lineage)")
    args = ap.parse_args()

    if args.replicas:
        # one virtual host device per replica; must land in XLA_FLAGS
        # before jax initializes its backends
        flag = ("--xla_force_host_platform_device_count="
                f"{max(2, args.replicas)}")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    import jax
    if args.cpu_fallback:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    print(f"# backend={devices[0].platform} "
          f"n_devices={len(devices)} clients={args.clients} "
          f"duration={args.duration}s "
          f"max_wait_ms={args.max_wait_ms}",
          file=sys.stderr, flush=True)

    if args.replicas:
        return _main_fleet(args)

    batched = measure("batched", args.clients, args.duration,
                      args.max_wait_ms)
    unbatched = measure("unbatched", args.clients, args.duration,
                        args.max_wait_ms)
    speedup = (batched["rows_per_sec"] / unbatched["rows_per_sec"]
               if unbatched["rows_per_sec"] else float("inf"))
    p99_budget = unbatched["latency_p99_ms"] + args.max_wait_ms
    print(f"# speedup={speedup:.2f}x  batched_p99="
          f"{batched['latency_p99_ms']}ms vs budget "
          f"{p99_budget:.2f}ms (unbatched_p99 + max_wait_ms)",
          file=sys.stderr, flush=True)

    headline = batched["rows_per_sec"]
    rec = {
        "metric": "serving_throughput_rows_per_sec",
        "unit": "rows/sec",
        # null headline on the CPU fallback: the schema's rule that a
        # host number can never be mistaken for chip perf
        "value": None if args.cpu_fallback else headline,
        "vs_baseline": None,
        "extra_metrics": [
            batched, unbatched,
            {"metric": "serving_batched_speedup",
             "value": round(speedup, 2), "unit": "x"},
            {"metric": "serving_batched_p99_minus_budget_ms",
             "value": round(batched["latency_p99_ms"] - p99_budget,
                            2),
             "unit": "ms"},
        ],
    }
    if args.cpu_fallback:
        rec["cpu_fallback_value"] = headline
        rec["fallback"] = (f"cpu clients={args.clients} "
                           f"duration={args.duration}s")
    from bench_common import attach_metrics_snapshot
    rec = attach_metrics_snapshot(rec)
    print(json.dumps(rec), flush=True)
    print(f"# total={time.perf_counter() - _t_start:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
