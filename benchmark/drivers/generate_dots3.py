"""Driver of the dots3-note generation cell: `dots3_note_decoder`
behind ``POST /generate`` on the native front-end, under
`drivers/generate.py`'s closed loop of clients, window accounting and
sampling of checked requests, and `drivers/generate_moe.py`'s
comparison of means and reduction of the trace to the program's own
names (all imported, not copied).

What differs: the net is the configuration's share of a dots3-note
deployment (layers, experts and vocabulary rows held) with its
weights from `benchmark/weights_dots3.py`; the engine prefills in
chunks of the configuration's ``engine.prefill_chunk`` (every prompt
of the mix is longer than one), so the warm-up's requests run the
chunk program at every cached-context length it branches between;
the reference is `reference/dots3_note.py`, one request at a time
(contexts of up to 32k tokens: one sequence's index scores and
attention are what fits), and the per-layer readers also get the
traced window's edges, for the chunk spans.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import harness, probe, traffic, weights_dots3 as wd
from benchmark.drivers.generate import (
    DRAIN_S, Load, compare, ok, pick_sample, release,
    requests_from_spans, tokens_inside, traced_layers, warm_ladder,
    weights_dtype)
from benchmark.drivers.generate_moe import mean_gap, reduction
from benchmark.reference import dots3_note as ref


# -- the program under test -------------------------------------------

def make_net(cfg: dict):
    from analytics_zoo_tpu.pipeline.api.keras import layers as L
    return L.dots3_note_decoder(
        dict(cfg, n_routed_experts=wd.experts_total(cfg)),
        n_layer=cfg["n_layer"], experts_held=wd.experts_held(cfg))


def build(loaded: dict, seed: int, devices):
    """Context, weights, engine and the started server."""
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.inference import (
        InferenceModel, make_inference_server)
    from analytics_zoo_tpu.pipeline.inference.batching import \
        ContinuousBatcher

    cfg, mix = loaded["config"], loaded["traffic"]
    if cfg["family"] != "dots3_note":
        raise ValueError("the generate_dots3 driver serves dots3-note")
    if len(devices) != 1:
        raise ValueError("the generate_dots3 driver serves one "
                         "chip's share from one chip")
    net = make_net(cfg)      # a program without the decoder ends here
    init_nncontext(tpu_mesh={"data": 1}, devices=devices,
                   seed=seed & 0x7FFFFFFF, log_level="WARNING")
    params = wd.weights(cfg, seed, weights_dtype(cfg))
    eng = cfg["engine"]
    im = InferenceModel(supported_concurrent_num=int(mix["clients"]))
    im.load_generator(net, params,
                      max_slots=eng["max_slots"],
                      max_context=eng["max_context"],
                      page_size=eng["page_size"],
                      cache_dtype=cfg["cache_dtype"],
                      prefill_chunk=eng["prefill_chunk"])
    del params
    batcher = ContinuousBatcher(im.generator,
                                max_new_cap=int(mix["max_new_cap"]))
    srv = make_inference_server(im, gen_batcher=batcher).start()
    return im, srv


# -- the comparison ---------------------------------------------------

def reference_gaps(cfg: dict, seed: int, sample: "list[dict]",
                   quant: bool = False, pad_to: int = 2048) -> dict:
    """`drivers.generate_moe.reference_gaps` over the dots3-note
    reference, a request a pass (padded to a multiple of ``pad_to``
    so that few lengths are compiled): ``gaps`` of the served tokens
    below the reference's best, and with ``quant`` the
    ``control_gaps`` of the float8 pass's choices."""
    import jax.numpy as jnp
    dtype = weights_dtype(cfg)
    held, eps = wd.experts_held(cfg), cfg["rms_norm_eps"]
    emb = wd.embeddings(cfg, seed, dtype)
    make_layer = lambda i: wd.layer(cfg, seed, i, dtype)
    out = {"gaps": [], "control_gaps": []}
    for r in sample:
        seq = r["req"]["prompt"] + r["tokens"]
        n, m = len(r["req"]["prompt"]), len(r["tokens"])
        ids = np.zeros((1, -(-len(seq) // pad_to) * pad_to), np.int32)
        ids[0, :len(seq)] = seq
        pos = jnp.arange(n - 1, n - 1 + m)
        hid = ref.hidden(cfg, emb, make_layer, ids, held)[0][pos]
        rows = ref.head(hid, emb["norm_f"], emb["lm_head"], eps)
        toks = jnp.asarray(r["tokens"], jnp.int32)
        out["gaps"].append(np.asarray(ref.gaps_of(rows, toks)))
        if quant:
            hid_q = ref.hidden(cfg, emb, make_layer, ids, held,
                               quant=True)[0][pos]
            rows_q = ref.head(hid_q, emb["norm_f"], emb["lm_head"],
                              eps, quant=True)
            out["control_gaps"].append(np.asarray(ref.gaps_of(
                rows, jnp.argmax(rows_q, axis=-1))))
    return out


# -- one run ----------------------------------------------------------

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
    t_ref = time.perf_counter()
    read = reference_gaps(cfg, seed, sample, quant=control) \
        if sample else {"gaps": [], "control_gaps": []}
    t_ref = time.perf_counter() - t_ref
    gaps = read["gaps"]
    compared = compare(cell["limits"], sample, gaps, len(good))
    compared.add("logit_gap_mean", mean_gap(gaps),
                 cell["limits"]["logit_gap_mean"])

    reqs = requests_from_spans(spans)
    layers = {
        "trace": reduction(
            tracer, cfg["engine"]["max_slots"] *
            cfg["num_experts_per_tok"]) if tracer else None,
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
        traced = probe.delta(snaps["t0"], snaps["t1"])
        layers["traced_counters"] = traced
        layers["traced_wall"] = (tracer.wall_start, tracer.wall_stop)
        layers.update(traced_layers(reqs, traced, tracer.wall_start,
                                    tracer.wall_stop))
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
                  "checked_longest": max(
                      (len(r["req"]["prompt"]) + len(r["tokens"])
                       for r in sample), default=0),
                  "reference_s": round(t_ref, 1),
                  **({"control_logit_gap": max(
                      float(g.max()) for g in read["control_gaps"]),
                      "control_logit_gap_mean": mean_gap(
                          read["control_gaps"])}
                     if read["control_gaps"] else {})},
    }
