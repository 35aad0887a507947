"""Driver of the MiMo-V2-Flash generation cell:
`mimo_v2_flash_decoder` behind ``POST /generate`` on the native
front-end, under `drivers/generate.py`'s closed loop of clients,
window accounting and sampling of checked requests, and
`drivers/generate_moe.py`'s comparison of means (all imported, not
copied).

What differs: the net is the configuration's share of a
MiMo-V2-Flash deployment (layers, experts and vocabulary rows held)
with its weights from `benchmark/weights_mimo.py`; the engine
prefills prompts of up to ``engine.prefill_chunk`` tokens whole, in
one-row buckets, and longer ones in chunks, and the mix sends both
in one queue, so the warm-up's ladder runs every bucket and the
chunk program at every cached-context length it branches between;
the reference is `reference/mimo_v2_flash.py`, one request at a
time; the traced window is also reduced to the device time of the
Pallas kernels the configuration names.

The window loop is :func:`run`, written over ``build`` and
``reference_gaps`` as arguments: what a generate cell's driver has
to bring is those two (and the kernels it wants timed).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import harness, probe, traffic, weights_mimo as wm
from benchmark.drivers.generate import (
    DRAIN_S, Load, compare, ok, pick_sample, release,
    requests_from_spans, tokens_inside, traced_layers, warm_ladder,
    weights_dtype)
from benchmark.drivers.generate_moe import mean_gap
from benchmark.reference import mimo_v2_flash as ref

# the Pallas kernels whose device time a traced run reads
KERNELS = ("zoo_paged_gqa_decode",)


# -- the program under test -------------------------------------------

def make_net(cfg: dict):
    from analytics_zoo_tpu.pipeline.api.keras import layers as L
    return L.mimo_v2_flash_decoder(
        dict(cfg, n_routed_experts=wm.experts_total(cfg)),
        n_layer=cfg["n_layer"], experts_held=wm.experts_held(cfg),
        vocab=cfg["vocab_size"])


def build(loaded: dict, seed: int, devices):
    """Context, weights, engine and the started server."""
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.inference import (
        InferenceModel, make_inference_server)
    from analytics_zoo_tpu.pipeline.inference.batching import \
        ContinuousBatcher

    cfg, mix = loaded["config"], loaded["traffic"]
    if cfg["family"] != "mimo_v2_flash":
        raise ValueError("the generate_mimo driver serves "
                         "MiMo-V2-Flash")
    if len(devices) != 1:
        raise ValueError("the generate_mimo driver serves one "
                         "chip's share from one chip")
    net = make_net(cfg)      # a program without the decoder ends here
    init_nncontext(tpu_mesh={"data": 1}, devices=devices,
                   seed=seed & 0x7FFFFFFF, log_level="WARNING")
    params = wm.weights(cfg, seed, weights_dtype(cfg))
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
    """`drivers.generate_moe.reference_gaps` over the MiMo-V2-Flash
    reference, a request a pass (padded to a multiple of ``pad_to``
    so that few lengths are compiled): ``gaps`` of the served tokens
    below the reference's best, and with ``quant`` the
    ``control_gaps`` of the float8 pass's choices."""
    import jax.numpy as jnp
    dtype = weights_dtype(cfg)
    held, eps = wm.experts_held(cfg), cfg["layernorm_epsilon"]
    emb = wm.embeddings(cfg, seed, dtype)
    make_layer = lambda i: wm.layer(cfg, seed, i, dtype)
    out = {"gaps": [], "control_gaps": []}
    for r in sample:
        seq = r["req"]["prompt"] + r["tokens"]
        n, m = len(r["req"]["prompt"]), len(r["tokens"])
        ids = np.zeros((1, -(-len(seq) // pad_to) * pad_to), np.int32)
        ids[0, :len(seq)] = seq
        pos = jnp.arange(n - 1, n - 1 + m)
        q_block = min(512, pad_to)
        hid = ref.hidden(cfg, emb, make_layer, ids, held,
                         q_block=q_block)[0][pos]
        rows = ref.head(hid, emb["norm_f"], emb["lm_head"], eps)
        toks = jnp.asarray(r["tokens"], jnp.int32)
        out["gaps"].append(np.asarray(ref.gaps_of(rows, toks)))
        if quant:
            hid_q = ref.hidden(cfg, emb, make_layer, ids, held,
                               quant=True, q_block=q_block)[0][pos]
            rows_q = ref.head(hid_q, emb["norm_f"], emb["lm_head"],
                              eps, quant=True)
            out["control_gaps"].append(np.asarray(ref.gaps_of(
                rows, jnp.argmax(rows_q, axis=-1))))
    return out


# -- the trace in the program's names ---------------------------------

def reduction(tracer, decode_rows: int, kernels=KERNELS
              ) -> "dict | None":
    """`drivers.generate_moe.reduction` with the named kernels'
    device time beside the scopes' (``program.kernel_s``), from the
    one reading of the trace."""
    import jax.profiler
    from benchmark.reduce import moe, program, trace
    from benchmark.reduce.kernels import kernel_times
    if tracer.wall_stop is None:
        return None
    t = time.perf_counter()
    path = trace.find_xplane(tracer.dir)
    profile = jax.profiler.ProfileData.from_file(path)
    prog = program.reduce_program(
        profile, moe.rescoped(program.op_names(path), decode_rows))
    if prog is not None:
        prog["kernel_s"] = kernel_times(profile, kernels)
    red = tracer.reduction()           # deletes the trace
    if red is not None:
        red["program"] = prog
    tracer.reduce_s = time.perf_counter() - t
    return red


# -- one run ----------------------------------------------------------

def run(loaded: dict, *, seed: int, seconds: float, trace: bool,
        devices, t0: float, control: bool = False, build=build,
        reference_gaps=reference_gaps, reduction=reduction) -> dict:
    """One run of a generate cell whose program ``build`` starts and
    whose served tokens ``reference_gaps`` reads against a reference.
    ``control`` (the calibration's, never a benchmark run's) also
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
