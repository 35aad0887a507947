"""Replicated-fleet smoke: 2-replica CPU fleet, kill one, lose
nothing.

`make fleet-smoke` runs this on the CPU backend (2 virtual devices).
One process, end to end through the fleet stack (docs/serving.md):

  1. build a 2-replica ReplicaPool over a toy Keras net (one device
     per replica, params committed per slice) and serve it behind
     the standard front-end via make_fleet_server
  2. fire mixed-size concurrent /predict requests, assert every
     response is 200 with rows exactly matching a direct forward
  3. inject replica death (r0's compiled calls start raising) and
     fire a second concurrent wave WHILE r0 is dying: every request
     must still return 200 with exact values (sibling retry — zero
     lost acked work) and r0 must be ejected (/debug/fleet: down)
  4. heal r0, drive the router's revival tick, assert re-admission
     (/debug/fleet: admitting again) and that it serves traffic
  5. assert the fleet gauge/counter families are on /metrics

A second phase then proves the **fleet telemetry plane**
(docs/observability.md, Fleet federation) against REAL subprocess
replicas: 2 `HttpReplica` workers (spawned as
`fleet_smoke.py --worker`) behind a router front-end take a
concurrent wave, and

  6. the federated `GET /metrics?fleet=1` acked-request counter
     equals the router's own count plus the per-replica
     `GET /metrics/json` counts EXACTLY (every acked request counted
     once, fleet-wide)
  7. one worker process is SIGKILLed and a traced wave fired WHILE
     it dies: every request still succeeds, and the traced request's
     `GET /debug/trace/<id>` returns ONE stitched timeline with
     spans from the router process AND a replica process, on
     distinct Perfetto process lanes (`?chrome=1` pids)

A third phase proves **disaggregated generation serving**
(docs/serving.md §Disaggregation) the same way:

  8. in-process: a `DisaggRouter` (1 prefill + 2 decode replicas
     carved from one toy transformer) serves a concurrent /generate
     wave byte-identical to a monolithic engine; a decode replica is
     poisoned mid-wave and every request STILL returns the exact
     stream (the KV handoff blob re-prefills on the sibling —
     exactly-once); the router drains clean and the
     `zoo_tpu_serving_gen_handoff_pages_leaked` audit counter stays
     0 (exact page refill, no orphaned slots)
  9. subprocess: 1 prefill + 2 decode workers (`--gen-worker ROLE`)
     behind HTTP front-ends take a concurrent wave; the prefill
     worker is SIGKILLed mid-wave — every 200 is byte-exact (zero
     lost acked requests), failures are only retryable transport
     errors, and the decode workers' /health settles back to
     free_pages == total_pages (the pool refills exactly)

Exit code 0 = the fleet absorbed a mid-load replica kill with zero
lost acked requests and re-admitted the healed replica, the
telemetry plane federated/stitched across real process boundaries,
and the disaggregated pools survived both a decode and a prefill
death without losing or corrupting an acked token.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # `python scripts/fleet_smoke.py`
    sys.path.insert(0, ROOT)

# A CPU smoke by construction: this process and every worker it
# spawns each initialise JAX, and a chip belongs to one process.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")

SIZES = [1, 3, 2, 8, 5, 4, 1, 6]  # one request per entry, concurrent


class _KillableModel:
    """Proxy over a real InferenceModel whose compiled-bucket calls
    and per-request predicts raise while ``dead`` is set — the fault
    injector for mid-request replica death (the batcher executes
    compiled bucket fns from lower_for, so the wrapper must poison
    those, not just predict)."""

    def __init__(self, im):
        self._im = im
        self.dead = threading.Event()

    def __getattr__(self, name):
        return getattr(self._im, name)

    def _check(self):
        if self.dead.is_set():
            raise RuntimeError("injected replica death")

    def lower_for(self, example_args):
        fn = self._im.lower_for(example_args)

        def wrapped(*xs):
            self._check()
            return fn(*xs)
        return wrapped

    def predict(self, inputs, timeout_ms=-1):
        self._check()
        return self._im.predict(inputs, timeout_ms=timeout_ms)


def _wave(url, xs, label):
    """Fire one concurrent request per array in ``xs``; return the
    (status, payload) list, every slot filled or asserted."""
    results: "list" = [None] * len(xs)

    def client(i: int):
        req = urllib.request.Request(
            url + "/predict",
            data=json.dumps({"inputs": xs[i].tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                results[i] = (r.status, json.loads(r.read()))
        except urllib.error.HTTPError as e:  # noqa: F821
            results[i] = (e.code, json.loads(e.read()))

    import urllib.error  # noqa: F401  (client() above)
    ts = [threading.Thread(target=client, args=(i,))
          for i in range(len(xs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    for i, r in enumerate(results):
        assert r is not None, f"{label}: request {i} hung"
    return results


def _fleet_debug(url) -> dict:
    return json.loads(urllib.request.urlopen(
        url + "/debug/fleet", timeout=30).read())


# -- federation phase: real subprocess replicas -------------------------


def _worker() -> int:
    """`fleet_smoke.py --worker`: one subprocess replica — a toy
    doubler behind the standard front-end. Prints the bound port as
    JSON on stdout, then parks forever (the parent kills it)."""
    from analytics_zoo_tpu.pipeline.inference.serving import (
        InferenceServer)

    class _Doubler:
        concurrent_slots_free = 8
        supported_concurrent_num = 8
        example_input_specs = None
        generator = None

        def predict(self, xs, timeout_ms=-1):
            return [np.asarray(x, dtype=np.float32) * 2
                    for x in xs]

    srv = InferenceServer(_Doubler(), port=0, batcher=None)
    srv.start()
    print(json.dumps({"port": srv.port}), flush=True)
    while True:
        time.sleep(3600)


def _spawn_worker():
    import subprocess
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get(
        "PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env)


def _counter_value(snap, name, **labels) -> float:
    total = 0.0
    for rec in (snap.get(name) or {}).get("values", ()):
        rl = rec.get("labels", {})
        if all(rl.get(k) == v for k, v in labels.items()):
            total += rec["value"]
    return total


def _traced_post(url, payload):
    req = urllib.request.Request(
        url + "/predict", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return (r.status, r.headers.get("X-Zoo-Trace-Id"),
                json.loads(r.read()))


def federation_phase() -> int:
    """Phase 6+7 of the module docstring: exact federated counter
    sums and cross-process trace stitching over real subprocess
    `HttpReplica` workers."""
    from analytics_zoo_tpu.common import observability as obs
    from analytics_zoo_tpu.pipeline.inference import InferenceServer
    from analytics_zoo_tpu.pipeline.inference.fleet import (
        FleetRouter, HttpReplica, ReplicaPool)

    procs = [_spawn_worker() for _ in range(2)]
    router = srv = None
    try:
        urls = []
        for p in procs:
            line = p.stdout.readline()
            assert line, "replica worker died before binding"
            urls.append(
                f"http://127.0.0.1:{json.loads(line)['port']}")
        pool = ReplicaPool(replicas=[
            HttpReplica(u, name=f"r{i}")
            for i, u in enumerate(urls)])
        router = FleetRouter(pool, probe_interval_s=0,
                             eject_after=1)
        srv = InferenceServer(router, port=0)
        srv.start()
        url = f"http://127.0.0.1:{srv.port}"

        # 6) concurrent wave, then exact federated counter sums
        xs = [np.full((n, 4), float(i), np.float32)
              for i, n in enumerate(SIZES)]
        for i, (status, out) in enumerate(
                _wave(url, xs, "federated")):
            assert status == 200, (i, status, out)
            got = np.asarray(out["outputs"], np.float32).ravel()
            assert got[0] == 2.0 * float(i), (i, got[:4])
        acked = len(SIZES)

        per_replica = []
        for u in urls:
            doc = json.loads(urllib.request.urlopen(
                u + "/metrics/json", timeout=30).read())
            per_replica.append(_counter_value(
                doc["metrics"], "zoo_tpu_serving_requests_total",
                path="/predict", status="200"))
        assert sum(per_replica) == acked, (per_replica, acked)

        text = urllib.request.urlopen(
            url + "/metrics?fleet=1", timeout=30).read().decode()
        local = _counter_value(
            obs.snapshot(), "zoo_tpu_serving_requests_total",
            path="/predict", status="200")
        import re
        m = re.search(
            r'^zoo_tpu_serving_requests_total\{[^}]*'
            r'path="/predict"[^}]*status="200"[^}]*\} ([0-9.]+)',
            text, re.M)
        assert m, text
        fed_val = float(m.group(1))
        assert fed_val == local + sum(per_replica), (
            fed_val, local, per_replica)

        # 7) SIGKILL one worker and fire a traced wave WHILE it
        # dies: zero lost acked work, and the trace still stitches
        # across the surviving processes
        procs[0].kill()
        tid = None
        for k in range(len(SIZES)):
            status, tid, out = _traced_post(
                url, {"inputs": [[9.0, 1.0, 2.0, 3.0]]})
            assert status == 200, (k, status, out)
            got = np.asarray(out["outputs"], np.float32).ravel()
            assert got[0] == 18.0, got[:4]
        assert tid

        t = json.loads(urllib.request.urlopen(
            f"{url}/debug/trace/{tid}", timeout=30).read())
        assert t["trace_id"] == tid, t
        assert "router" in t["sources"], t["sources"]
        assert any(s in ("r0", "r1") for s in t["sources"]), (
            t["sources"])
        ch = json.loads(urllib.request.urlopen(
            f"{url}/debug/trace/{tid}?chrome=1", timeout=30).read())
        pids = {e.get("pid") for e in ch["traceEvents"]
                if e.get("ph") == "X"}
        assert len(pids) >= 2, pids  # distinct Perfetto lanes

        n_spans = t["n_spans"]
    finally:
        if srv is not None:
            srv.stop()
        elif router is not None:
            router.stop()
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=30)

    print(f"fleet-smoke federation OK: {acked} acked requests "
          f"federated exactly ({'+'.join(str(int(v)) for v in per_replica)}"
          f"+{int(local)} local = {int(fed_val)}); mid-kill trace "
          f"{tid} stitched {n_spans} spans from "
          f"{len(t['sources'])} processes on {len(pids)} lanes")
    return 0


# -- disagg phase: prefill/decode pools with KV-page handoff ------------

GEN_SEQ, GEN_VOCAB = 32, 61


def _gen_net():
    """The disagg phase's toy transformer — seeded build, so every
    process (parent, prefill worker, decode workers) holds IDENTICAL
    params and greedy streams are comparable byte-for-byte."""
    from analytics_zoo_tpu import init_nncontext
    init_nncontext(seed=0, log_level="WARNING")
    import jax
    from analytics_zoo_tpu.pipeline.api.keras.layers.transformer \
        import TransformerLayer
    net = TransformerLayer(n_block=2, hidden_size=32, n_head=2,
                           seq_len=GEN_SEQ, vocab=GEN_VOCAB,
                           hidden_p_drop=0.0, attn_p_drop=0.0,
                           embed_p_drop=0.0)
    params = net.build(jax.random.key(0), (GEN_SEQ,))
    return net, params


def _gen_prompts():
    rs = np.random.RandomState(3)
    return [rs.randint(1, GEN_VOCAB, size=n).tolist()
            for n in (3, 7, 5, 11, 9, 4)]


def _gen_worker(role: str) -> int:
    """`fleet_smoke.py --gen-worker prefill|decode`: one pool
    replica — a role-specific generation engine behind the standard
    front-end (its /generate/prefill · /generate/handoff routes are
    the pool surface). Prints the bound port, parks forever."""
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.pipeline.inference.serving import (
        InferenceServer)

    net, params = _gen_net()
    im = InferenceModel()
    im.load_generator(net, params, max_slots=4, max_context=GEN_SEQ,
                      page_size=8, role=role,
                      prefill_chunk=4 if role == "prefill" else 0)
    srv = InferenceServer(im, port=0, batcher=None)
    srv.start()
    print(json.dumps({"port": srv.port}), flush=True)
    while True:
        time.sleep(3600)


def _spawn_gen_worker(role: str):
    import subprocess
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.pop("ZOO_TPU_DISAGG", None)  # workers are pools, not routers
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--gen-worker",
         role],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env)


def _gen_wave(url, prompts, max_new, n_reqs, label,
              mid_wave=None):
    """Fire ``n_reqs`` concurrent /generate requests (prompts
    cycled); run ``mid_wave()`` once the wave is in flight. Returns
    the (status, payload) list — transport failures land as
    status 599 so the caller can classify them as retryable."""
    import urllib.error
    results: "list" = [None] * n_reqs
    started = threading.Event()

    def client(i: int):
        body = {"prompt": prompts[i % len(prompts)],
                "max_new_tokens": max_new}
        req = urllib.request.Request(
            url + "/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        started.set()
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                results[i] = (r.status, json.loads(r.read()))
        except urllib.error.HTTPError as e:
            try:
                results[i] = (e.code, json.loads(e.read()))
            except (ValueError, OSError):
                results[i] = (e.code, {})
        except Exception as e:  # connection died mid-request
            results[i] = (599, {"error": str(e)})

    ts = [threading.Thread(target=client, args=(i,))
          for i in range(n_reqs)]
    for t in ts:
        t.start()
    if mid_wave is not None:
        started.wait(timeout=30)
        mid_wave()
    for t in ts:
        t.join(timeout=120)
    for i, r in enumerate(results):
        assert r is not None, f"{label}: request {i} hung"
    return results


def disagg_phase() -> int:
    """Phase 8+9 of the module docstring."""
    from analytics_zoo_tpu.common import observability as obs
    from analytics_zoo_tpu.pipeline.inference import (
        ContinuousBatcher, GenerationEngine)
    from analytics_zoo_tpu.pipeline.inference.fleet import (
        DisaggRouter, HttpDisaggReplica)
    from analytics_zoo_tpu.pipeline.inference.serving import (
        InferenceServer)

    net, params = _gen_net()
    prompts = _gen_prompts()
    max_new = 8

    # the monolithic reference stream every disagg answer must match
    mono = GenerationEngine(net, params, max_slots=4,
                            max_context=GEN_SEQ, page_size=8)
    mb = ContinuousBatcher(mono).start()
    expect = [mb.submit(p, max_new_tokens=max_new).result(120)
              .tolist() for p in prompts]
    mb.stop()

    # 8) in-process pools; poison a decode replica mid-wave
    tmpl = GenerationEngine(net, params, max_slots=4,
                            max_context=GEN_SEQ, page_size=8,
                            prefill_chunk=4)
    router = DisaggRouter.for_engine(tmpl, n_prefill=1, n_decode=2,
                                     eject_after=1)
    router.start()
    victim = router.decode[0]

    def poison():
        def dying(blob, mx, eos):
            from concurrent.futures import Future
            f = Future()
            f.set_exception(
                ConnectionError("injected decode death"))
            return f
        victim.decode = dying

    n_reqs = 2 * len(prompts)
    futs = [router.submit(prompts[i % len(prompts)],
                          max_new_tokens=max_new)
            for i in range(n_reqs)]
    poison()  # in flight: some handoffs now land on a dead replica
    for i, f in enumerate(futs):
        got = f.result(120).tolist()
        assert got == expect[i % len(prompts)], (i, got)
    assert not victim.admitting(), "dead decode replica not ejected"
    assert router.drain(), "disagg pools did not drain"
    leaked = obs.counter(
        "zoo_tpu_serving_gen_handoff_pages_leaked",
        help="pages the drain audit reclaimed from slots no "
        "request owned (0 = exact pool refill)").value
    assert leaked == 0, f"drain audit reclaimed {leaked} pages"
    for r in router.prefill + router.decode:
        assert r.free_pages() == r.total_pages(), r.name
    router.stop()
    retried = obs.counter(
        "zoo_tpu_serving_gen_handoff_retries_total",
        help="handoffs retried after a pool replica failed "
        "mid-flight (the blob re-prefills on a sibling)").value
    print(f"fleet-smoke disagg(in-process) OK: {n_reqs} streams "
          f"byte-identical to monolithic through a mid-wave decode "
          f"death ({int(retried)} handoffs re-prefilled); drained "
          f"with 0 leaked pages")

    # 9) subprocess pools; SIGKILL the prefill worker mid-wave
    procs = {"prefill": [_spawn_gen_worker("prefill")],
             "decode": [_spawn_gen_worker("decode"),
                        _spawn_gen_worker("decode")]}
    srv = None
    try:
        urls = {}
        for role, ps in procs.items():
            urls[role] = []
            for p in ps:
                line = p.stdout.readline()
                assert line, f"{role} worker died before binding"
                urls[role].append(
                    f"http://127.0.0.1:{json.loads(line)['port']}")
        router = DisaggRouter(
            [HttpDisaggReplica(u, "prefill", name=f"hp{i}")
             for i, u in enumerate(urls["prefill"])],
            [HttpDisaggReplica(u, "decode", name=f"hd{i}")
             for i, u in enumerate(urls["decode"])],
            eject_after=1)

        class _NoModel:  # front door: routing only, no local model
            concurrent_slots_free = 8
            supported_concurrent_num = 8
            example_input_specs = None
            generator = None

        srv = InferenceServer(_NoModel(), port=0, batcher=None,
                              gen_batcher=router)
        srv.start()
        url = f"http://127.0.0.1:{srv.port}"

        # warm the workers' compiled programs outside the kill wave
        warm = _gen_wave(url, prompts[:2], max_new, 2, "warm")
        for i, (status, out) in enumerate(warm):
            assert status == 200, (i, status, out)
            assert out["tokens"] == expect[i], (i, out)

        # role + per-pool page headroom on the front door
        fleet = _fleet_debug(url)
        assert fleet.get("disagg") is True, fleet
        roles = sorted(r["role"] for r in fleet["replicas"])
        assert roles == ["decode", "decode", "prefill"], roles
        assert fleet["pools"]["decode"]["pages_total"] > 0, fleet

        results = _gen_wave(
            url, prompts, max_new, 3 * len(prompts), "kill",
            mid_wave=procs["prefill"][0].kill)
        acked = failed = 0
        for i, (status, out) in enumerate(results):
            if status == 200:
                acked += 1
                assert out["tokens"] == expect[i % len(prompts)], (
                    i, out)  # an acked stream is NEVER corrupt
            else:
                failed += 1
                # with the only prefill replica dead, new admissions
                # can only fail retryably (5xx/transport), never as
                # a client error and never with a wrong stream
                assert status in (500, 503, 599), (i, status, out)

        # the decode pool settles back to an exactly-full free list
        deadline = time.monotonic() + 60
        settled = []
        while time.monotonic() < deadline:
            settled = []
            for u in urls["decode"]:
                gen = json.loads(urllib.request.urlopen(
                    u + "/health", timeout=30).read())["generator"]
                settled.append(
                    gen["slots_active"] == 0 and
                    gen["free_pages"] == gen["total_pages"])
            if all(settled):
                break
            time.sleep(0.2)
        assert all(settled), "decode pool did not refill exactly"
    finally:
        if srv is not None:
            srv.stop()
        for ps in procs.values():
            for p in ps:
                p.kill()
        for ps in procs.values():
            for p in ps:
                p.wait(timeout=30)

    print(f"fleet-smoke disagg(subprocess) OK: prefill worker "
          f"SIGKILLed mid-wave; {acked} acked streams all "
          f"byte-exact, {failed} failures all retryable, decode "
          f"pool refilled exactly")
    return 0


def main() -> int:
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.parallel import replica_device_slices
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.models import (
        Sequential)
    from analytics_zoo_tpu.pipeline.inference import (
        InferenceModel, make_fleet_server)
    from analytics_zoo_tpu.pipeline.inference.fleet import (
        FleetRouter, Replica, ReplicaPool)

    init_nncontext(seed=0, log_level="WARNING")
    net = Sequential()
    net.add(Dense(16, activation="relu", input_shape=(6,)))
    net.add(Dense(3))
    net.compile(optimizer="sgd", loss="mse")
    params = net.estimator.params
    if params is None:
        net.estimator._ensure_initialized()
        params = net.estimator.params

    rs = np.random.RandomState(0)
    example = [rs.randn(4, 6).astype(np.float32)]

    import jax
    slices = replica_device_slices(2, 1, jax.devices()[:2])
    models = []
    replicas = []
    for i, sl in enumerate(slices):
        placed = jax.tree_util.tree_map(
            lambda x, d=sl[0]: jax.device_put(x, d), params)
        im = InferenceModel()
        im.load_keras_net(net, params=placed,
                          example_inputs=example)
        km = _KillableModel(im)
        models.append(km)
        replicas.append(Replica(
            f"r{i}", km, batcher_kwargs={"max_wait_ms": 5}))
    pool = ReplicaPool(replicas=replicas)
    router = FleetRouter(pool, probe_interval_s=0, eject_after=1)
    srv = make_fleet_server(router).start()
    front = type(srv).__name__
    try:
        url = f"http://127.0.0.1:{srv.port}"

        def ref(x):
            return np.asarray(net.forward(params, x,
                                          training=False))

        def check_wave(xs, results, label):
            for i, x in enumerate(xs):
                status, out = results[i]
                assert status == 200, (label, i, status, out)
                got = np.asarray(out["outputs"], np.float32)
                assert got.shape[0] == x.shape[0], (label, i,
                                                    got.shape)
                np.testing.assert_allclose(got, ref(x), rtol=1e-4,
                                           atol=1e-5)

        # 1) healthy fleet serves a mixed concurrent wave exactly
        xs = [rs.randn(n, 6).astype(np.float32) for n in SIZES]
        check_wave(xs, _wave(url, xs, "healthy"), "healthy")
        fleet = _fleet_debug(url)
        assert fleet["replicas_admitting"] == 2, fleet

        # 2) kill r0 and fire a second wave while it is dying: the
        # router retries r0's failures on r1 — zero lost acked work
        models[0].dead.set()
        xs2 = [rs.randn(n, 6).astype(np.float32) for n in SIZES]
        check_wave(xs2, _wave(url, xs2, "kill"), "kill")
        fleet = _fleet_debug(url)
        states = {r["name"]: r["state"] for r in fleet["replicas"]}
        assert states["r0"] == "down", fleet
        assert states["r1"] == "admitting", fleet

        # 3) heal r0 and drive revival ticks until re-admitted
        models[0].dead.clear()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            router.tick(now=time.monotonic() + 3600)  # backoff due
            if router._replica("r0").admitting():
                break
            time.sleep(0.05)
        fleet = _fleet_debug(url)
        states = {r["name"]: r["state"] for r in fleet["replicas"]}
        assert states["r0"] == "admitting", fleet
        xs3 = [rs.randn(n, 6).astype(np.float32) for n in SIZES]
        check_wave(xs3, _wave(url, xs3, "recovered"), "recovered")

        text = urllib.request.urlopen(
            url + "/metrics", timeout=30).read().decode()
    finally:
        srv.stop()

    required = [
        "zoo_tpu_fleet_replicas_admitting",
        "zoo_tpu_fleet_replicas_total",
        "zoo_tpu_fleet_replica_up",
        "zoo_tpu_fleet_outstanding_rows",
        "zoo_tpu_fleet_dispatches_total",
        "zoo_tpu_fleet_requests_total",
        "zoo_tpu_fleet_retries_total",
        "zoo_tpu_fleet_ejections_total",
        "zoo_tpu_fleet_readmissions_total",
    ]
    missing = [m for m in required if m not in text]
    if missing:
        print(f"FAIL: missing metrics {missing}\n---\n{text}",
              file=sys.stderr)
        return 1
    print(f"fleet-smoke OK: {front} served {3 * len(SIZES)} "
          f"requests across 2 replicas; r0 killed mid-load with "
          f"zero lost acked requests, ejected, and re-admitted")
    rc = federation_phase()
    if rc:
        return rc
    return disagg_phase()


if __name__ == "__main__":
    if "--worker" in sys.argv[1:]:
        sys.exit(_worker())
    if "--gen-worker" in sys.argv[1:]:
        role = sys.argv[sys.argv.index("--gen-worker") + 1]
        sys.exit(_gen_worker(role))
    sys.exit(main())
