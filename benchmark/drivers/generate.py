"""Driver of the generation cells: ``POST /generate`` on the native
front-end, in this process, under a closed loop of client threads.

Set-up makes the weights on the device from the seed, loads them into
``InferenceModel.load_generator`` through its public arguments, starts
``make_inference_server(im, gen_batcher="auto")`` (which warms the
engine's programs), sends one short request through every prompt
bucket the mix reaches and lets the clients fill the slots for the
mix's ``warm_seconds``. The window is the ``--seconds`` after that:
its requests are those sent inside it, each followed to its answer
(up to a minute past the close); its tokens are those generated
inside it. Then the engine is released and a sample of the finished
requests, the longest among them, goes through the plain float32
reference.
"""

from __future__ import annotations

import gc
import http.client
import itertools
import json
import threading
import time

import numpy as np

from benchmark import harness, probe, traffic, weights
from benchmark.reference import transformer as ref


# -- the program under test -------------------------------------------

def weights_dtype(cfg: dict):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16,
            "float32": jnp.float32}[cfg["weights_dtype"]]


def build(loaded: dict, seed: int, devices):
    """Context, weights, engine and the started server."""
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.api.keras import layers as L
    from analytics_zoo_tpu.pipeline.inference import (
        InferenceModel, make_inference_server)

    cfg, mix = loaded["config"], loaded["traffic"]
    if cfg["family"] != "transformer":
        raise ValueError("the generate driver serves TransformerLayer")
    if len(devices) != 1:
        raise ValueError("the generate driver serves from one chip")
    init_nncontext(tpu_mesh={"data": 1}, devices=devices,
                   seed=seed & 0x7FFFFFFF, log_level="WARNING")
    params = weights.transformer_weights(cfg, seed,
                                         weights_dtype(cfg))
    net = L.TransformerLayer(
        n_block=cfg["n_layer"], hidden_size=cfg["n_embd"],
        n_head=cfg["n_head"], seq_len=cfg["n_positions"],
        vocab=cfg["vocab_size"], intermediate_size=cfg["n_inner"],
        hidden_p_drop=0.0, attn_p_drop=0.0, embed_p_drop=0.0)
    eng = cfg["engine"]
    im = InferenceModel(supported_concurrent_num=int(mix["clients"]))
    im.load_generator(net, params, max_slots=eng["max_slots"],
                      max_context=eng["max_context"],
                      page_size=eng["page_size"],
                      cache_dtype=cfg["cache_dtype"])
    del params
    srv = make_inference_server(im, gen_batcher="auto").start()
    return im, srv


def release(im, srv):
    """Stop serving. The batcher is stopped first and at once, so
    that a request still decoding after the drain (or after an error
    in the run) is failed and not waited for."""
    if srv.gen_batcher is not None:
        srv.gen_batcher.stop(timeout=0.2)
    srv.stop()


# -- the load: a closed loop of clients -------------------------------

def post_generate(port: int, req: dict, timeout: float = 300.0):
    """(status, tokens or None) of one POST /generate."""
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=timeout)
    try:
        conn.request("POST", "/generate", body=json.dumps(req),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200:
            return resp.status, None
        return 200, json.loads(body).get("tokens")
    finally:
        conn.close()


class Load:
    """``clients`` threads that each send the stream's next request
    as soon as their last one has returned, until told to stop."""

    def __init__(self, port: int, stream: "list[dict]", clients: int):
        self.port, self.stream = port, stream
        self.next = itertools.count()
        self.lock = threading.Lock()
        self.stop = threading.Event()
        self.done: "list[dict]" = []
        self.threads = [threading.Thread(target=self._client,
                                         daemon=True,
                                         name=f"bench-client-{i}")
                        for i in range(clients)]

    def _client(self):
        while not self.stop.is_set():
            with self.lock:
                i = next(self.next)
            req = self.stream[i % len(self.stream)]
            t_send = time.perf_counter()
            try:
                status, tokens = post_generate(self.port, req)
            except (OSError, http.client.HTTPException, ValueError):
                status, tokens = -1, None
            rec = {"i": i, "t_send": t_send,
                   "t_done": time.perf_counter(), "status": status,
                   "tokens": tokens, "req": req}
            with self.lock:
                self.done.append(rec)

    def start(self):
        for t in self.threads:
            t.start()

    def finish(self, timeout: float = 10.0) -> bool:
        """After ``stop`` is set: wait up to ``timeout`` seconds in
        all for the clients to get their last answers. True when
        every one has ended."""
        deadline = time.perf_counter() + timeout
        for t in self.threads:
            t.join(timeout=max(0.0, deadline - time.perf_counter()))
        return not any(t.is_alive() for t in self.threads)


# how long past the window's close an answer is waited for
DRAIN_S = 60.0


def tokens_inside(rec: dict, lo: float, hi: float) -> float:
    """The part of a finished request's tokens that falls inside
    [lo, hi], taking them as evenly spread from send to return (the
    answer comes whole, so a client sees no more): all of them for a
    request that lies inside the window, its share for one that
    straddles an edge."""
    span = rec["t_done"] - rec["t_send"]
    inside = min(hi, rec["t_done"]) - max(lo, rec["t_send"])
    if inside <= 0 or span <= 0:
        return 0.0
    return len(rec["tokens"]) * inside / span


def ok(rec: dict) -> bool:
    return rec["status"] == 200 and \
        isinstance(rec["tokens"], list) and \
        len(rec["tokens"]) == rec["req"]["max_new_tokens"]


# -- what the per-layer readers get -----------------------------------

def requests_from_spans(spans: "list[dict]") -> "list[dict]":
    """One record a request from the batcher's ``decode/admit`` and
    ``decode/retire`` spans (joined by trace id): submit, admission
    (first token) and retirement in epoch seconds, and prompt
    length. ``t_retire`` is None for one still decoding."""
    out = {}
    for s in spans:
        if s["name"] == "decode/admit":
            out[s["trace_id"]] = {
                "t_submit": s["t_start"],
                "t_admit": s["t_start"] + s["dur_s"],
                "t_retire": None,
                "prompt_len": int(s["fields"]["prompt_len"])}
    for s in spans:
        if s["name"] == "decode/retire" and s["trace_id"] in out:
            out[s["trace_id"]]["t_retire"] = s["t_start"] + s["dur_s"]
    return list(out.values())


def live_tokens(reqs: "list[dict]", lo: float, hi: float,
                tokens_per_s: float) -> float:
    """Mean over [lo, hi] of the tokens held in the slots: each
    request's prompt plus what it has generated since admission, at
    one token a decode step."""
    total = 0.0
    for r in reqs:
        a = max(lo, r["t_admit"])
        b = min(hi, r["t_retire"] if r["t_retire"] is not None
                else hi)
        if b <= a:
            continue
        t0 = r["t_admit"]
        total += r["prompt_len"] * (b - a) + tokens_per_s * \
            ((b - t0) ** 2 - (a - t0) ** 2) / 2.0
    return total / (hi - lo)


def traced_layers(reqs, counters: dict, lo: float, hi: float) -> dict:
    """The traced part of the window in the readers' terms."""
    steps = counters.get("zoo_tpu_serving_gen_steps_total", 0)
    decoded = counters.get("zoo_tpu_serving_gen_tokens_total", 0)
    if not steps or hi <= lo:
        return {}
    live = live_tokens(reqs, lo, hi, steps / (hi - lo))
    return {
        "live_tokens_traced": live,
        "traced_work": {
            "seconds": hi - lo,
            "prompt_lens": [r["prompt_len"] for r in reqs
                            if lo <= r["t_admit"] < hi],
            "decoded_tokens": decoded,
            "mean_context": live / (decoded / steps)}}


# -- the comparison ---------------------------------------------------

def pick_sample(finished: "list[dict]", seed: int, n: int
                ) -> "list[dict]":
    """``n`` of the window's finished requests, drawn from the seed,
    the longest (prompt plus answer) always among them."""
    if not finished:
        return []
    size = lambda r: len(r["req"]["prompt"]) + len(r["tokens"])
    by_order = sorted(finished, key=lambda r: r["i"])
    longest = max(by_order, key=size)
    rest = [r for r in by_order if r is not longest]
    rs = traffic.rng_for(seed, 31)
    take = rs.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(take)]


def reference_gaps(cfg: dict, seed: int, sample: "list[dict]",
                   quant: bool = False, pad_to: int = 128) -> dict:
    """One dense float32 pass over each sampled prompt with its
    served tokens. ``gaps``: how far each served token's logit lies
    below the reference's best at its position (one array a request).
    With ``quant`` the same pass runs in float8 and ``control_gaps``
    reads, in the float32 logits, the gap of the token the lower
    precision puts first."""
    import jax
    import jax.numpy as jnp
    dtype = weights_dtype(cfg)
    lens = [len(r["req"]["prompt"]) + len(r["tokens"])
            for r in sample]
    t = min(cfg["n_positions"], -(-max(lens) // pad_to) * pad_to)
    ids = np.zeros((len(sample), t), np.int32)
    for row, r in enumerate(sample):
        seq = r["req"]["prompt"] + r["tokens"]
        ids[row, :len(seq)] = seq
    emb = weights.transformer_embeddings(
        cfg, weights.seed_key(seed, 2), dtype)
    block_key = weights.seed_key(seed, 1)
    make_block = jax.jit(lambda b: weights.transformer_block(
        cfg, jax.random.fold_in(block_key, b), dtype))
    out = {"gaps": [], "control_gaps": []}
    hid = ref.hidden(cfg, emb, make_block, ids, quant=False)
    hid_q = ref.hidden(cfg, emb, make_block, ids, quant=True) \
        if quant else None
    for row, r in enumerate(sample):
        n, m = len(r["req"]["prompt"]), len(r["tokens"])
        pos = jnp.arange(n - 1, n - 1 + m)
        rows = ref.head(hid[row][pos], emb["tok_embed"])
        toks = jnp.asarray(r["tokens"], jnp.int32)
        out["gaps"].append(np.asarray(ref.gaps_of(rows, toks)))
        if quant:
            rows_q = ref.head(hid_q[row][pos], emb["tok_embed"],
                              quant=True)
            out["control_gaps"].append(np.asarray(ref.gaps_of(
                rows, jnp.argmax(rows_q, axis=-1))))
    return out


def compare(limits: dict, sample, gaps, window_ok: int
            ) -> harness.Compared:
    out = harness.Compared()
    worst = max((float(g.max()) for g in gaps), default=float("nan"))
    out.add("logit_gap", worst, limits["logit_gap"])
    # nothing finished, nothing compared: never a pass
    out.add("requests_unchecked", 0.0 if sample and window_ok
            else 1.0, 0.0)
    return out


# -- one run ----------------------------------------------------------

def warm_ladder(port: int, cfg: dict, mix: dict, seed: int):
    """One two-token request through every prompt bucket (powers of
    two, as the engine pads them) that the mix's sizes reach, so that
    no program runs for the first time inside the window."""
    pool = traffic.size_pool(mix)
    buckets = sorted({1 << max(0, (p - 1).bit_length())
                      for p, _o in pool})
    rs = traffic.rng_for(seed, 41)
    for b in buckets:
        n = min(b, cfg["engine"]["max_context"] - 2)
        status, tokens = post_generate(port, {
            "prompt": rs.integers(0, cfg["vocab_size"],
                                  size=n).tolist(),
            "max_new_tokens": 2, "temperature": 0.0})
        if status != 200 or len(tokens or ()) != 2:
            raise RuntimeError(f"warm-up request of {n} tokens "
                               f"failed: HTTP {status}")


def run(loaded: dict, *, seed: int, seconds: float, trace: bool,
        devices, t0: float, control: bool = False) -> dict:
    """``control`` (the calibration's, never a benchmark run's) also
    reads the float8 control over the same sample."""
    cfg, mix, cell = loaded["config"], loaded["traffic"], loaded["cell"]
    im, srv = build(loaded, seed, devices)
    front_end = type(srv).__name__
    try:
        if front_end != "NativeInferenceServer":
            raise RuntimeError(f"{front_end} answered, not the native "
                               "front-end the cell measures")
        warm_ladder(srv.port, cfg, mix, seed)
        stream = traffic.requests(mix, seed, cfg["vocab_size"])
        load = Load(srv.port, stream, int(mix["clients"]))
        cursor, spans = probe.span_cursor(), []
        load.start()
        time.sleep(float(mix["warm_seconds"]))

        tracer = harness.tracer_for(loaded, seconds, trace)
        snaps = {}
        if tracer is not None:
            tracer.on_start = lambda: snaps.__setitem__(
                "t0", probe.metrics())
            tracer.on_stop = lambda: snaps.__setitem__(
                "t1", probe.metrics())
        before = probe.metrics()
        t_open = time.perf_counter()
        wall_open = time.time()
        if tracer is not None:
            tracer.arm()
        while True:
            now = time.perf_counter()
            if now - t_open >= seconds:
                break
            cursor, new = probe.spans_since(cursor)
            spans.extend(new)
            time.sleep(min(0.25, max(0.0, seconds - (now - t_open))))
        t_close = time.perf_counter()
        wall_close = time.time()
        load.stop.set()
        after = probe.metrics()
        if tracer is not None:
            tracer.finish()
        cursor, new = probe.spans_since(cursor)
        spans.extend(new)
        # every request sent in the window is followed to its answer:
        # one that comes late is late, one that never comes has failed
        drained = load.finish(timeout=DRAIN_S)
        peak = harness.memory_peak_bytes(devices)
    finally:
        release(im, srv)
    clients_ended = drained or load.finish()
    del im, srv
    gc.collect()

    with load.lock:
        done = list(load.done)
    window = [r for r in done if t_open <= r["t_send"] <= t_close]
    good = [r for r in window if ok(r)]
    wall = t_close - t_open
    lat_ms = [1e3 * (r["t_done"] - r["t_send"]) if ok(r)
              else max(1e3 * DRAIN_S, 1e3 * (r["t_done"] - r["t_send"]))
              for r in window]
    tokens = sum(tokens_inside(r, t_open, t_close) for r in done
                 if ok(r))

    sample = pick_sample(good, seed, int(mix["check_requests"]))
    read = reference_gaps(cfg, seed, sample, quant=control) \
        if sample else {"gaps": [], "control_gaps": []}
    gaps = read["gaps"]
    compared = compare(cell["limits"], sample, gaps, len(good))

    reqs = requests_from_spans(spans)
    layers = {
        "trace": tracer.reduction() if tracer else None,  # sets reduce_s
        "config": cfg, "traffic": mix, "chips": 1,
        "peak": harness.peak_or_none(devices),
        "window_s": wall, "latencies_ms": lat_ms,
        "counters": probe.delta(before, after),
        "spans": [s for s in spans
                  if wall_open <= s["t_start"] + s["dur_s"]
                  <= wall_close],
        "weight_bytes": 2 if cfg["weights_dtype"] == "bfloat16" else 4,
        "kv_value_bytes": 2 if cfg["cache_dtype"] == "bf16" else 4,
    }
    if tracer is not None and "t1" in snaps:
        layers.update(traced_layers(
            reqs, probe.delta(snaps["t0"], snaps["t1"]),
            tracer.wall_start, tracer.wall_stop))
    return {
        "attempted": len(window), "failed": len(window) - len(good),
        "end_to_end": {
            "gen_tok_per_s": tokens / wall,
            "setup_s": t_open - t0},
        "memory_peak_bytes": peak, "compared": compared,
        "layers": layers,
        "notes": {"front_end": front_end,
                  **({"trace_reduce_s": tracer.reduce_s}
                     if tracer else {}),
                  "clients_ended": clients_ended,
                  "checked_requests": len(sample),
                  "checked_tokens": int(sum(len(g) for g in gaps)),
                  **({"control_logit_gap": max(
                      float(g.max()) for g in read["control_gaps"])}
                     if read["control_gaps"] else {})},
    }
