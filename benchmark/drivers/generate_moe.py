"""Driver of the expert-layer generation cells: DeepSeek-V2 behind
``POST /generate`` on the native front-end, under `drivers/generate.py`'s
closed loop of clients, window accounting and sampling of checked
requests (imported, not copied).

What differs: the net is `deepseek_v2_decoder` over the
configuration's share of the deployment (layers, experts and
vocabulary rows held), its weights come layer by layer from
`benchmark/weights_deepseek.py`, the batcher is built with the mix's
``max_new_cap``, the reference is `reference/deepseek_v2.py`, and a
traced run reduces its trace once more to the program's own names
(`reduce/program.py`, with the grouped matrix products put back under
their scopes by `reduce/moe.py`) before the harness deletes it.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import harness, probe, traffic, weights_deepseek as wd
from benchmark.drivers.generate import (
    DRAIN_S, Load, compare, ok, pick_sample, release,
    requests_from_spans, tokens_inside, traced_layers, warm_ladder,
    weights_dtype)
from benchmark.reference import deepseek_v2 as ref


# -- the program under test -------------------------------------------

def make_net(cfg: dict):
    from analytics_zoo_tpu.pipeline.api.keras import layers as L
    return L.deepseek_v2_decoder(
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
    if cfg["family"] != "deepseek_v2":
        raise ValueError("the generate_moe driver serves DeepSeek-V2")
    if len(devices) != 1:
        raise ValueError("the generate_moe driver serves one chip's "
                         "share from one chip")
    init_nncontext(tpu_mesh={"data": 1}, devices=devices,
                   seed=seed & 0x7FFFFFFF, log_level="WARNING")
    params = wd.weights(cfg, seed, weights_dtype(cfg))
    eng = cfg["engine"]
    im = InferenceModel(supported_concurrent_num=int(mix["clients"]))
    im.load_generator(make_net(cfg), params,
                      max_slots=eng["max_slots"],
                      max_context=eng["max_context"],
                      page_size=eng["page_size"],
                      cache_dtype=cfg["cache_dtype"])
    del params
    batcher = ContinuousBatcher(im.generator,
                                max_new_cap=int(mix["max_new_cap"]))
    srv = make_inference_server(im, gen_batcher=batcher).start()
    return im, srv


# -- the comparison ---------------------------------------------------

def reference_gaps(cfg: dict, seed: int, sample: "list[dict]",
                   quant: bool = False, pad_to: int = 128) -> dict:
    """`drivers.generate.reference_gaps` over the DeepSeek-V2
    reference: ``gaps`` of the served tokens below the reference's
    best, and with ``quant`` the ``control_gaps`` of the float8
    pass's choices."""
    import jax.numpy as jnp
    dtype = weights_dtype(cfg)
    held, eps = wd.experts_held(cfg), cfg["rms_norm_eps"]
    lens = [len(r["req"]["prompt"]) + len(r["tokens"])
            for r in sample]
    t = -(-max(lens) // pad_to) * pad_to
    ids = np.zeros((len(sample), t), np.int32)
    for row, r in enumerate(sample):
        seq = r["req"]["prompt"] + r["tokens"]
        ids[row, :len(seq)] = seq
    emb = wd.embeddings(cfg, seed, dtype)
    make_layer = lambda i: wd.layer(cfg, seed, i, dtype)
    out = {"gaps": [], "control_gaps": []}
    hid = ref.hidden(cfg, emb, make_layer, ids, held)
    hid_q = ref.hidden(cfg, emb, make_layer, ids, held, quant=True) \
        if quant else None
    for row, r in enumerate(sample):
        n, m = len(r["req"]["prompt"]), len(r["tokens"])
        pos = jnp.arange(n - 1, n - 1 + m)
        rows = ref.head(hid[row][pos], emb["norm_f"], emb["lm_head"],
                        eps)
        toks = jnp.asarray(r["tokens"], jnp.int32)
        out["gaps"].append(np.asarray(ref.gaps_of(rows, toks)))
        if quant:
            rows_q = ref.head(hid_q[row][pos], emb["norm_f"],
                              emb["lm_head"], eps, quant=True)
            out["control_gaps"].append(np.asarray(ref.gaps_of(
                rows, jnp.argmax(rows_q, axis=-1))))
    return out


def mean_gap(gaps) -> float:
    """Mean over every checked token of how far it lies below the
    reference's best. A routing flip (a rounded router score that
    moves a token to another expert) moves single tokens' logits by
    far more than the rounding did, so the widest gap cannot tell
    bfloat16 from float8; the mean over a thousand tokens can."""
    return float(np.concatenate(gaps).mean()) if gaps \
        else float("nan")


# -- the trace in the program's names ---------------------------------

def reduction(tracer, decode_rows: int) -> "dict | None":
    """The harness's reduction with ``program`` beside it: idle time
    by span and device time by scope."""
    import jax.profiler
    from benchmark.reduce import moe, program, trace
    if tracer.wall_stop is None:
        return None
    t = time.perf_counter()
    path = trace.find_xplane(tracer.dir)
    prog = program.reduce_program(
        jax.profiler.ProfileData.from_file(path),
        moe.rescoped(program.op_names(path), decode_rows))
    red = tracer.reduction()           # deletes the trace
    if red is not None:
        red["program"] = prog
    tracer.reduce_s = time.perf_counter() - t
    return red


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
    read = reference_gaps(cfg, seed, sample, quant=control) \
        if sample else {"gaps": [], "control_gaps": []}
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
                  **({"control_logit_gap": max(
                      float(g.max()) for g in read["control_gaps"]),
                      "control_logit_gap_mean": mean_gap(
                          read["control_gaps"])}
                     if read["control_gaps"] else {})},
    }
