"""Driver of the Command A+ generation cell: `command_a_decoder` behind
``POST /generate`` on the native front-end, run by
`drivers/generate_mimo.py`'s window loop (imported, not copied) over
this module's ``build`` and ``reference_gaps``.

What differs from the MiMo cell: the net is the configuration's share
of a Command A+ deployment (layers, experts and vocabulary rows held)
with its weights from `benchmark/weights_command.py`; every prompt is
longer than the 4096-token window and goes in 2048-token chunks, so
the sliding layers' ring wraps in every request; the reference is
`reference/command_a.py`, one request at a time, its layers' weights
made again from the seed one layer at a time; the answers run to 1024
tokens, so a request is followed past the window's close as long as
`drivers/generate_nemotron.py`'s drain says.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights_command as wc
from benchmark.drivers import generate_mimo
from benchmark.drivers.generate import weights_dtype
from benchmark.drivers.generate_nemotron import drain_s
from benchmark.reference import command_a as ref


# -- the program under test -------------------------------------------

def make_net(cfg: dict):
    from analytics_zoo_tpu.pipeline.api.keras import layers as L
    return L.command_a_decoder(
        cfg, n_layer=cfg["n_layer"], experts_held=wc.experts_held(cfg),
        vocab=cfg["vocab_size"])


def build(loaded: dict, seed: int, devices):
    """Context, weights, engine and the started server."""
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.inference import (
        InferenceModel, make_inference_server)
    from analytics_zoo_tpu.pipeline.inference.batching import \
        ContinuousBatcher

    cfg, mix = loaded["config"], loaded["traffic"]
    if cfg["family"] != "cohere2_moe":
        raise ValueError("the generate_command driver serves "
                         "Command A+")
    if len(devices) != 1:
        raise ValueError("the generate_command driver serves one "
                         "chip's share from one chip")
    net = make_net(cfg)      # a program without the decoder ends here
    init_nncontext(tpu_mesh={"data": 1}, devices=devices,
                   seed=seed & 0x7FFFFFFF, log_level="WARNING")
    params = wc.weights(cfg, seed, weights_dtype(cfg))
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
    """`drivers.generate_moe.reference_gaps` over the Command A+
    reference, a request a pass (padded to a multiple of ``pad_to`` so
    that few lengths are compiled): ``gaps`` of the served tokens below
    the reference's best, and with ``quant`` the ``control_gaps`` of
    the float8 pass's choices."""
    import jax.numpy as jnp
    dtype = weights_dtype(cfg)
    held, eps = wc.experts_held(cfg), cfg["layer_norm_eps"]
    scale = float(cfg.get("logit_scale", 1.0))
    emb = wc.embeddings(cfg, seed, dtype)
    make_layer = lambda i: wc.layer(cfg, seed, i, dtype)
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
        rows = ref.head(hid, emb["norm_f"], emb["tok_embed"], eps,
                        scale)
        toks = jnp.asarray(r["tokens"], jnp.int32)
        out["gaps"].append(np.asarray(ref.gaps_of(rows, toks)))
        if quant:
            hid_q = ref.hidden(cfg, emb, make_layer, ids, held,
                               quant=True, q_block=q_block)[0][pos]
            rows_q = ref.head(hid_q, emb["norm_f"], emb["tok_embed"],
                              eps, scale, quant=True)
            out["control_gaps"].append(np.asarray(ref.gaps_of(
                rows, jnp.argmax(rows_q, axis=-1))))
    return out


# -- one run ----------------------------------------------------------

def run(loaded: dict, **kw) -> dict:
    """`drivers.generate_mimo.run` over this cell's program and
    reference, with the drain scaled to the mix's longest answer;
    ``kw`` as there (``control`` included)."""
    saved = generate_mimo.DRAIN_S
    generate_mimo.DRAIN_S = drain_s(loaded["traffic"])
    try:
        return generate_mimo.run(loaded, build=build,
                                 reference_gaps=reference_gaps, **kw)
    finally:
        generate_mimo.DRAIN_S = saved
