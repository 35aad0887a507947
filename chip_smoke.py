"""chip_smoke.py — does train, serve and generate still start on the chip?

One process drives the system's main path through the entry points a
user calls, at the published widths of two models the repo supports
(ResNet-50 at 224x224 / batch 128, and `TransformerLayer` at
GPT-2-small widths: 12 blocks x 768 x 12 heads, vocab 50257, context
1024), with seeded random weights and data, and checks every phase
against a plain reference:

  1. train     init_nncontext -> Estimator.train, mixed_bfloat16
  2. serve     InferenceModel + DynamicBatcher behind the native HTTP
               front-end, concurrent /predict of mixed batch sizes
  3. generate  load_generator -> /generate through ContinuousBatcher,
               then one Estimator step at T=1024 through the flash
               kernel, forward and backward
  4. kernels   every Pallas entry point (the flash attention family,
               the two paged decode kernels)
               compiled (not interpreted), run at T=4096 shapes and
               compared with its XLA reference
  5. four chips (only with ``--chips 4``, which runs nothing else):
               the phase-1 job data-parallel and FSDP over four chips,
               and a ring-attention step over {"data": 2, "seq": 2}
               at T=4096, each against the one-device result

Each phase prints one JSON line. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``
and ``ok`` is true only on a TPU with every phase passed.

Exit codes: 0 every phase passed on a TPU; 1 a phase failed; 2 no
chip — JAX found no TPU (nothing is run and no result is printed), or
``--rehearse`` ran every phase at toy size under the Pallas
interpreter and all of them passed (a rehearsal is never a chip run).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
import urllib.request

import numpy as np

EXIT_OK, EXIT_PHASE_FAILED, EXIT_NO_CHIP = 0, 1, 2

# bf16 carries 8 bits of mantissa (eps 2^-8 = 0.4%). A whole network
# in bf16 against its float32 reference is held to 5% of the largest
# reference value; one kernel against its XLA reference, both fed the
# same bf16 operands, to 2%.
NET_TOL, KERNEL_TOL = 5e-2, 2e-2


class Sizes:
    """Published widths (the default) or toy widths (``--rehearse``).
    Widths of the models are never cut on the chip; only the
    rehearsal shrinks them."""

    def __init__(self, rehearse: bool):
        r = rehearse
        # ResNet-50 train / serve
        self.image = 32 if r else 224
        self.batch = 8 if r else 128
        self.train_steps = 6
        self.serve_rows = [1, 2, 4, 1]
        # GPT-2-small generate / train
        self.gpt = dict(
            n_block=2 if r else 12, hidden_size=64 if r else 768,
            n_head=2 if r else 12, seq_len=256 if r else 1024,
            vocab=211 if r else 50257)
        self.prompt_lens = [5, 40, 150, 20] if r else [37, 300, 900, 150]
        self.max_new = 6 if r else 16
        self.gpt_train_batch = 2 if r else 4
        # kernels
        self.attn = dict(b=1, t=256, h=2, d=64) if r else \
            dict(b=4, t=4096, h=16, d=64)
        self.decode = dict(s=2, t=256, h=2, d=64) if r else \
            dict(s=8, t=4096, h=12, d=64)
        # four chips
        self.ring_t = 512 if r else 4096
        self.ring_blocks = 1 if r else 4
        self.ring_batch = 2


# dropout off: two runs from the same weights must give the same loss
NO_DROPOUT = dict(hidden_p_drop=0.0, attn_p_drop=0.0, embed_p_drop=0.0)


def _lm_classifier(gpt: dict, **layer_kw):
    """`TransformerLayer` at the given widths, the last position's
    state, a small head: the trainable net of the attention steps.
    Fixed layer names, so every instance shares one params tree."""
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras import layers as L
    m = Sequential()
    m.add(L.TransformerLayer(**gpt, **NO_DROPOUT, name="gpt",
                             **layer_kw))
    m.add(L.Select(1, -1, name="last"))
    m.add(L.Dense(8, name="head"))
    return m


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _rel_err(got, want) -> float:
    """max|got - want| over the largest |want|: one number for "how
    far off, relative to the scale of the answer"."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) /
                 max(float(np.max(np.abs(want))), 1e-30))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class _CompileClock:
    """Sums XLA backend-compile seconds (a persistent-cache hit counts
    the read) between ``reset()`` and ``read()``."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)

    def _on_event(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.total += secs

    def reset(self):
        self.total = 0.0

    def read(self) -> float:
        return round(self.total, 2)


# ---------------------------------------------------------------------
# data
# ---------------------------------------------------------------------

def _image_data(rs, n: int, image: int, classes: int = 10):
    """Seeded learnable images: noise plus a class pattern (a colour
    per quadrant), labels from ``classes`` of the 1000 ids, so a few
    SGD steps lower the loss on a batch they never saw."""
    y = rs.randint(0, classes, size=(n, 1)).astype(np.int32)
    pattern = rs.randn(classes, 2, 2, 3).astype(np.float32)
    half = image // 2
    x = rs.randn(n, image, image, 3).astype(np.float32) * 0.5
    x += np.repeat(np.repeat(pattern[y[:, 0]], half, 1), half, 2)
    return x, y


# ---------------------------------------------------------------------
# phase 1: train
# ---------------------------------------------------------------------

def phase_train(sz: Sizes, seed: int, clock: _CompileClock,
                state: dict, out: dict) -> None:
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.common.device import setup_compile_cache
    from analytics_zoo_tpu.models.image.imageclassification import (
        resnet50)
    from analytics_zoo_tpu.ops import losses
    from analytics_zoo_tpu.ops.optimizers import SGD
    from analytics_zoo_tpu.parallel.mesh import shard_params
    from analytics_zoo_tpu.pipeline.estimator import Estimator

    cache_dir = setup_compile_cache()
    entries_before = len(os.listdir(cache_dir)) \
        if os.path.isdir(cache_dir) else 0
    dev = jax.devices()[0]
    ctx = init_nncontext(tpu_mesh={"data": 1}, devices=[dev],
                         seed=seed, log_level="WARNING")
    rs = np.random.RandomState(seed)
    n = sz.batch * sz.train_steps
    x, y = _image_data(rs, n, sz.image)
    hx, hy = _image_data(rs, sz.batch, sz.image)   # the held batch

    model = resnet50(input_shape=(sz.image, sz.image, 3), classes=1000)

    def new_estimator():
        return Estimator(model, optimizer=SGD(lr=0.002, momentum=0.9),
                         loss="softmax_cross_entropy", ctx=ctx,
                         dtype_policy="mixed_bfloat16")

    # numpy, so every placement below is a fresh device buffer (the
    # train step donates its inputs)
    init_params = jax.device_get(model.init_params(
        jax.random.key(seed), device="host"))
    est = new_estimator()
    est.params = shard_params(init_params, ctx.mesh)

    # the held batch's training-mode loss, by a plain jitted function
    # (not the Estimator): the same weights must score lower after
    # the steps than before them
    loss_fn = losses.get("softmax_cross_entropy")
    hxb, hyd = jnp.asarray(hx, jnp.bfloat16), jnp.asarray(hy)

    # the batch is an argument: closed over, it would ride the
    # cached executable as a 38 MB constant
    @jax.jit
    def held_loss(p, xb, yb):
        out, _ = model.apply(p, xb, training=True)
        return loss_fn(yb, out.astype(jnp.float32))

    held_before = float(held_loss(est.params, hxb, hyd))

    # step 1 alone: its compile is the cold (or cache-warm) reading
    clock.reset()
    t0 = time.perf_counter()
    first = est.train(x[:sz.batch], y[:sz.batch],
                      batch_size=sz.batch, nb_epoch=1)
    first_call_s = time.perf_counter() - t0
    compile_first = clock.read()
    loss0 = first.history[0]["loss"]

    t0 = time.perf_counter()
    rest = est.train(x[sz.batch:], y[sz.batch:], batch_size=sz.batch,
                     nb_epoch=1)
    jax.block_until_ready(est.params)
    rest_call_s = time.perf_counter() - t0
    loss_rest = rest.history[0]["loss"]
    held_after = float(held_loss(est.params, hxb, hyd))

    # the same step again from a fresh Estimator: a new jit object, so
    # XLA is asked again and the persistent cache has to answer
    est2 = new_estimator()
    est2.params = shard_params(init_params, ctx.mesh)
    clock.reset()
    again = est2.train(x[:sz.batch], y[:sz.batch],
                       batch_size=sz.batch, nb_epoch=1)
    compile_again = clock.read()
    del est2

    # logits of 8 images: the chip's bf16 policy against the same
    # weights in float32 on the host's CPU device, same process
    x8 = np.round(hx[:8], 3)
    logits = est.predict(x8, batch_size=8)
    cpu = jax.local_devices(backend="cpu")[0]
    ref = np.asarray(jax.jit(
        lambda p, a: model.forward(p, a, training=False))(
            jax.device_put(jax.device_get(est.params), cpu),
            jax.device_put(x8, cpu)))
    logits_err = _rel_err(logits, ref)

    leaves = jax.tree_util.tree_leaves(est.params)
    resident = all(leaf.devices() == {dev} for leaf in leaves)

    # what later phases reuse and what the phase line says go down
    # before the checks, so one failed check costs neither
    state.update(model=model, est=est, x8=x8, ref_logits=ref)
    cold = entries_before == 0
    out.update({
        "steps": est.step, "loss_step1": round(loss0, 4),
        "loss_mean_steps_2_to_n": round(loss_rest, 4),
        "held_loss": [round(held_before, 4), round(held_after, 4)],
        "logits_rel_err_vs_f32_cpu": round(logits_err, 5),
        "params_on": str(dev),
        "compile_s": {"cold": compile_first if cold else None,
                      "warm": compile_again if cold
                      else compile_first},
        # whole train() calls, fixed costs included (host shuffle,
        # the FLOPs ledger's one-off re-lowering): not step times
        "first_call_s": round(first_call_s, 2),
        "rest_call_s": round(rest_call_s, 2),
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            # -1 = unbounded; a bound smaller than one run's
            # executables evicts this phase's before the next run
            "max_size": jax.config.jax_compilation_cache_max_size},
    })
    _check(np.isfinite([loss0, loss_rest, held_before,
                        held_after]).all(), "non-finite loss")
    _check(est.step == sz.train_steps,
           f"ran {est.step} steps, wanted {sz.train_steps}")
    _check(held_after < held_before,
           f"held-batch loss did not fall: {held_before:.4f} -> "
           f"{held_after:.4f}")
    _check(resident, f"parameters are not all resident on {dev}")
    _check(logits.shape == (8, 1000) and np.isfinite(logits).all(),
           f"bad logits {logits.shape}")
    _check(logits_err <= NET_TOL,
           f"logits differ from the float32 CPU reference by "
           f"{logits_err:.4f} (> {NET_TOL})")
    _check(abs(again.history[0]["loss"] - loss0) <= 1e-3 * abs(loss0),
           "the same step from the same weights gave another loss")


# ---------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------

def _post(url: str, body: dict, timeout: float = 900.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _concurrently(fn, n: int, timeout: float = 1000.0) -> list:
    """Run fn(i) on n threads; every thread's result or exception."""
    out = [None] * n

    def run(i):
        try:
            out[i] = fn(i)
        except Exception as e:  # re-raised below, on the main thread
            out[i] = e
    ts = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=timeout)
    for i, r in enumerate(out):
        if isinstance(r, Exception):
            raise r
        _check(r is not None, f"request {i} did not return")
    return out


def phase_serve(sz: Sizes, seed: int, clock: _CompileClock,
                state: dict, out: dict) -> None:
    from analytics_zoo_tpu.pipeline.inference import (
        DynamicBatcher, InferenceModel, make_inference_server)
    from analytics_zoo_tpu.pipeline.inference.serving import (
        NativeInferenceServer)

    _check("est" in state, "needs the model phase 1 trained")
    model, est, x8 = state["model"], state["est"], state["x8"]
    clock.reset()
    im = InferenceModel(supported_concurrent_num=2)
    im.load_keras_net(model, params=est.params, example_inputs=[x8])
    direct = np.asarray(im.predict(x8))
    batcher = DynamicBatcher(im, max_batch_size=8, max_wait_ms=10)
    srv = make_inference_server(im, batcher=batcher).start()
    try:
        out["front_end"] = type(srv).__name__
        # no quiet stdlib substitute: the C++ front-end is built from
        # the committed sources on this path, or the phase fails
        _check(isinstance(srv, NativeInferenceServer),
               f"{out['front_end']} answered, not the native "
               f"front-end")
        url = f"http://127.0.0.1:{srv.port}"
        bounds = np.cumsum([0] + sz.serve_rows)
        t0 = time.perf_counter()
        answers = _concurrently(
            lambda i: _post(url + "/predict", {
                "inputs": x8[bounds[i]:bounds[i + 1]].tolist()}),
            len(sz.serve_rows))
        wall = time.perf_counter() - t0
        health = json.loads(urllib.request.urlopen(
            url + "/health", timeout=60).read())
    finally:
        srv.stop()
    got = []
    for i, (status, body) in enumerate(answers):
        _check(status == 200, f"request {i}: HTTP {status} {body}")
        rows = np.asarray(body["outputs"], np.float32)
        _check(rows.shape == (sz.serve_rows[i], 1000),
               f"request {i}: shape {rows.shape}")
        got.append(rows)
    got = np.concatenate(got)
    err_direct = _rel_err(got, direct)
    err_ref = _rel_err(got, state["ref_logits"])
    bt = health["batcher"]
    out.update({"requests": sz.serve_rows, "buckets": bt["buckets"],
                "rel_err_vs_direct_predict": round(err_direct, 6),
                "rel_err_vs_f32_cpu": round(err_ref, 5),
                "compile_s": clock.read(),
                "requests_wall_s": round(wall, 3)})
    _check(np.isfinite(got).all(), "non-finite answers")
    # the same weights through another batch bucket's program: an
    # order of magnitude inside the whole-network tolerance
    _check(err_direct <= NET_TOL / 10,
           f"HTTP answers differ from InferenceModel.predict by "
           f"{err_direct:.5f} (> {NET_TOL / 10})")
    _check(err_ref <= NET_TOL,
           f"HTTP answers differ from the float32 CPU reference by "
           f"{err_ref:.4f} (> {NET_TOL})")
    _check(bt["enabled"] and
           bt["warmed_buckets"] == len(bt["buckets"]),
           f"bucket ladder not warmed: {bt}")


# ---------------------------------------------------------------------
# phase 3: generate
# ---------------------------------------------------------------------

def phase_generate(sz: Sizes, seed: int, clock: _CompileClock,
                   state: dict, out: dict) -> None:
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.ops import flash_attention as fa
    from analytics_zoo_tpu.ops.optimizers import Adam
    from analytics_zoo_tpu.parallel.mesh import shard_params
    from analytics_zoo_tpu.pipeline.api.keras import layers as L
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    from analytics_zoo_tpu.pipeline.inference import (
        InferenceModel, make_inference_server)
    from analytics_zoo_tpu.pipeline.inference.serving import (
        NativeInferenceServer)

    dev = jax.devices()[0]
    ctx = init_nncontext(tpu_mesh={"data": 1}, devices=[dev],
                         seed=seed, log_level="WARNING")
    g = sz.gpt
    seq_len, vocab = g["seq_len"], g["vocab"]
    net = L.TransformerLayer(**g, **NO_DROPOUT)
    params = net.build(jax.random.key(seed + 1), (seq_len,))

    # -- /generate through the continuous batcher ----------------------
    clock.reset()
    flash0 = fa.invocations
    im = InferenceModel()
    im.load_generator(net, params, max_slots=4, max_context=seq_len,
                      page_size=16)
    rs = np.random.RandomState(seed + 1)
    prompts = [rs.randint(1, vocab, size=n).tolist()
               for n in sz.prompt_lens]
    srv = make_inference_server(im, gen_batcher="auto").start()
    try:
        out["front_end"] = type(srv).__name__
        _check(isinstance(srv, NativeInferenceServer),
               f"{out['front_end']} answered, not the native "
               f"front-end")
        url = f"http://127.0.0.1:{srv.port}"
        t0 = time.perf_counter()
        answers = _concurrently(
            lambda i: _post(url + "/generate", {
                "prompt": prompts[i], "max_new_tokens": sz.max_new}),
            len(prompts))
        wall = time.perf_counter() - t0
        health = json.loads(urllib.request.urlopen(
            url + "/health", timeout=60).read())
    finally:
        srv.stop()
    out.update({
        "prompt_lens": sz.prompt_lens, "max_new_tokens": sz.max_new,
        "prefill_attention":
            "flash" if fa.invocations > flash0 else "xla",
        "serve_compile_s": clock.read(),
        "requests_wall_s": round(wall, 3)})

    # plain reference: ONE dense full forward over prompt + answer
    # (right-padding is safe under the causal mask), weight-tied
    # logits at every position. A greedy token must be the reference
    # argmax — exactly on the CPU; on the chip, where near-ties in
    # random-weight logits fall inside bf16 rounding, within
    # `tie_tol` of the reference maximum.
    ref_net = L.TransformerLayer(**g, **NO_DROPOUT,
                                 attention_impl="xla")

    @jax.jit
    def ref_rows(p, ids, pos, toks):
        """Per generated token: the reference argmax at the position
        that predicted it, and how far below the reference maximum
        the generated token scores."""
        h = ref_net.call(p, ids[None], training=False)
        rows = h[0][pos] @ p["tok_embed"].T          # (max_new, vocab)
        chosen = jnp.take_along_axis(rows, toks[:, None], 1)[:, 0]
        return jnp.argmax(rows, -1), jnp.max(rows, -1) - chosen

    tie_tol = 0.05 if dev.platform == "tpu" else 0.0
    exact = total = 0
    shortfall = 0.0
    for i, (status, body) in enumerate(answers):
        _check(status == 200, f"request {i}: HTTP {status} {body}")
        toks = body["tokens"]
        _check(len(toks) == sz.max_new,
               f"request {i}: {len(toks)} tokens")
        n = len(prompts[i])
        ids = np.zeros((seq_len,), np.int32)
        ids[:n + len(toks)] = prompts[i] + toks
        best, short = ref_rows(
            params, jnp.asarray(ids),
            jnp.arange(n - 1, n - 1 + len(toks)),
            jnp.asarray(toks, jnp.int32))
        exact += int(np.sum(np.asarray(best) == np.asarray(toks)))
        total += len(toks)
        shortfall = max(shortfall, float(jnp.max(short)))
    out.update({"tokens_equal_reference_argmax": f"{exact}/{total}",
                "max_logit_shortfall": round(shortfall, 5),
                "tie_tol": tie_tol})
    _check(shortfall <= tie_tol,
           f"a generated token scores {shortfall:.4f} below the "
           f"reference argmax (> {tie_tol})")
    _check(health["generator"]["slots_active"] == 0,
           "slots still active after every request returned")

    # -- one Estimator step at T=seq_len through the flash kernel ------
    bsz = sz.gpt_train_batch
    xt = rs.randint(1, vocab, size=(bsz, seq_len)).astype(np.int32)
    yt = rs.randint(0, 8, size=(bsz, 1)).astype(np.int32)
    tparams = None
    step = {}
    for impl in (None, "xla"):       # None = "auto": must take flash
        m = _lm_classifier(g, attention_impl=impl)
        if tparams is None:
            tparams = jax.device_get(m.init_params(
                jax.random.key(seed + 2), device="host"))
        est = Estimator(m, optimizer=Adam(lr=1e-4),
                        loss="softmax_cross_entropy", ctx=ctx,
                        dtype_policy="mixed_bfloat16")
        est.params = shard_params(tparams, ctx.mesh)
        clock.reset()
        before = fa.invocations
        t0 = time.perf_counter()
        res = est.train(xt, yt, batch_size=bsz, nb_epoch=1)
        jax.block_until_ready(est.params)
        step[impl or "auto"] = dict(
            loss=res.history[0]["loss"],
            flash_calls=fa.invocations - before,
            wall_s=round(time.perf_counter() - t0, 2),
            compile_s=clock.read())
        del est
    auto, dense = step["auto"], step["xla"]
    loss_err = abs(auto["loss"] - dense["loss"]) / abs(dense["loss"])
    out["train_step"] = {
        "T": seq_len, "batch": bsz,
        "attention": "flash" if auto["flash_calls"] else "xla",
        "flash_calls_traced": auto["flash_calls"],
        "loss": round(auto["loss"], 5),
        "loss_dense_reference": round(dense["loss"], 5),
        "compile_s": auto["compile_s"], "wall_s": auto["wall_s"]}
    _check(np.isfinite(auto["loss"]), "non-finite loss")
    # the kernel's custom VJP owns the backward: a forward that
    # entered flash_attention differentiates through its Pallas
    # dq / dk,dv kernels, never through dense attention
    _check(auto["flash_calls"] >= 1 and dense["flash_calls"] == 0,
           f"attention 'auto' did not take the flash kernel at "
           f"T={seq_len}: {auto['flash_calls']} kernel calls "
           f"(dense run: {dense['flash_calls']})")
    _check(loss_err <= KERNEL_TOL,
           f"flash step loss {auto['loss']:.5f} vs dense "
           f"{dense['loss']:.5f}")


# ---------------------------------------------------------------------
# phase 4: kernels
# ---------------------------------------------------------------------

def _kernel_case(name, fn, ref, args, interpret: bool) -> dict:
    """Compile ``fn`` (a Pallas entry point with ``interpret`` bound),
    assert the kernel is in the lowered program, run it and compare
    every output with ``ref``'s."""
    import jax
    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    if not interpret:
        _check("tpu_custom_call" in lowered.as_text(),
               f"{name}: no tpu_custom_call in the lowered program")
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    got = jax.block_until_ready(compiled(*args))
    want = jax.block_until_ready(jax.jit(ref)(*args))
    got = got if isinstance(got, (tuple, list)) else (got,)
    want = want if isinstance(want, (tuple, list)) else (want,)
    _check(len(got) == len(want), f"{name}: output count")
    err = 0.0
    for a, b in zip(got, want):
        _check(a.shape == b.shape, f"{name}: {a.shape} vs {b.shape}")
        _check(bool(np.isfinite(np.asarray(a, np.float32)).all()),
               f"{name}: non-finite output")
        err = max(err, _rel_err(a, b))
    _check(err <= KERNEL_TOL,
           f"{name}: differs from its XLA reference by {err:.4f}")
    return {"kernel": name, "passed": True, "rel_err": round(err, 5),
            "compile_s": round(compile_s, 2)}


def _by_batch(f):
    """Reference attention one batch row at a time: the dense logits
    of B=4, H=16, T=4096 would not fit beside their backward."""
    import jax

    def one_row(row):
        out = f(*[r[None] for r in row])
        return jax.tree_util.tree_map(lambda o: o[0], out)
    return lambda *a: jax.lax.map(one_row, a)


def phase_kernels(sz: Sizes, seed: int, clock: _CompileClock,
                  state: dict, out: dict) -> None:
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops import attention as att
    from analytics_zoo_tpu.ops import flash_attention as fa
    from analytics_zoo_tpu.ops import kv_cache as kvc

    interp = jax.devices()[0].platform != "tpu"
    rs = np.random.RandomState(seed + 3)
    bf, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.key(seed + 3), 256))

    def rnd(*shape, dtype=bf, scale=1.0):
        # operands are made on the device, in bulk
        return (jax.random.normal(next(keys), shape, f32) *
                scale).astype(dtype)

    def uni(n):
        return jax.random.uniform(next(keys), (n,), f32) + 0.5

    cases = out.setdefault("cases", [])
    out["interpret"] = interp

    def run(name, fn, ref, *args):
        # every case runs; the failed ones fail the phase at the end
        try:
            cases.append(_kernel_case(name, fn, ref, args, interp))
        except Exception as e:
            traceback.print_exc()
            cases.append({"kernel": name, "passed": False,
                          "error": f"{type(e).__name__}: {e}"[:600]})

    # -- flash attention ----------------------------------------------
    a = sz.attn
    q, k, v = (rnd(a["b"], a["t"], a["h"], a["d"]) for _ in range(3))

    def dense(q, k, v, mask=None):
        return att.dot_product_attention(q, k, v, mask=mask,
                                         causal=mask is None,
                                         impl="xla")

    def grads(f):
        def loss(q, k, v):
            return jnp.sum(f(q, k, v).astype(f32) ** 2)
        return lambda q, k, v: jax.grad(loss, argnums=(0, 1, 2))(
            q, k, v)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True,
                                  interpret=interp)
    run("flash_attention fwd", flash, _by_batch(dense), q, k, v)
    run("flash_attention fwd+bwd", grads(flash),
        _by_batch(grads(dense)), q, k, v)
    kmask = jnp.asarray(
        np.arange(a["t"])[None, :] <
        rs.randint(a["t"] // 2, a["t"], size=(a["b"], 1)), f32)
    run("flash_attention masked fwd",
        lambda q, k, v, m: fa.flash_attention(
            q, k, v, key_mask=m, interpret=interp),
        _by_batch(lambda q, k, v, m: dense(
            q, k, v, mask=m[:, None, None, :])), q, k, v, kmask)

    # one ring step's block — the diagonal one, where the causal
    # mask cuts (offset 0) — as unnormalised softmax partials
    half = a["t"] // 2
    scale = 1.0 / a["d"] ** 0.5

    def partial_ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(f32) * scale
        s = jnp.where(jnp.tril(jnp.ones((half, half), bool)), s,
                      -1e30)
        m = jnp.max(s, -1)
        p = jnp.exp(s - m[..., None])
        acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype),
                         v).astype(f32)
        return acc, m, jnp.sum(p, -1)
    run("flash_block_partial",
        lambda q, k, v: fa.flash_block_partial(
            q, k, v, jnp.int32(0), causal=True, scale=scale,
            interpret=interp),
        _by_batch(partial_ref), q[:, :half], k[:, :half], v[:, :half])

    # a chunk's attention under a mask no rule describes: the last
    # half of the positions as queries over all the keys but their
    # last 40, values half as wide as the keys; a window of a quarter
    # of the context and every third key before it, so that tiles
    # above the diagonal are skipped and those below it are not
    tc = a["t"] - 40
    back = (half + np.arange(half))[:, None] - np.arange(tc)[None, :]
    cmask = jnp.asarray(np.broadcast_to(
        (back >= 0) & ((back < a["t"] // 4) | (back % 3 == 0)),
        (a["b"], half, tc)))
    bq, bk = fa.chunk_blocks(half, tc)
    run("masked_chunk_attention",
        lambda q, k, v, m: fa.masked_chunk_attention(
            q, k, v, m, scale, block_q=bq, block_k=bk,
            interpret=interp),
        _by_batch(lambda q, k, v, m: att._masked_attention_xla(
            q, k, v, m, scale)),
        q[:, half:], k[:, :tc], v[:, :tc, :, :a["d"] // 2], cmask)

    # -- flash decode (bf16 and int8 cache) ------------------------------
    d = sz.decode
    dq = rnd(d["s"], d["h"], d["d"])
    dk, dv = (rnd(d["s"], d["t"], d["h"], d["d"]) for _ in range(2))
    lens = jnp.asarray(rs.randint(d["t"] // 2, d["t"],
                                  size=(d["s"],)), jnp.int32)
    dmask = (jnp.arange(d["t"])[None, :] < lens[:, None])
    dscale = 1.0 / d["d"] ** 0.5
    run("flash_decode_attention bf16",
        lambda q, k, v: fa.flash_decode_attention(
            q, k, v, dmask, scale=dscale, interpret=interp),
        lambda q, k, v: att.decode_attention(q, k, v, lens,
                                             impl="xla"),
        dq, dk, dv)
    (k8, ks), (v8, vs) = kvc.quantize_rows(dk), kvc.quantize_rows(dv)
    run("flash_decode_attention int8",
        lambda q, k, v, ks, vs: fa.flash_decode_attention(
            q, k, v, dmask, scale=dscale, interpret=interp,
            k_scales=ks, v_scales=vs),
        lambda q, k, v, ks, vs: att.decode_attention(
            q, k, v, lens, impl="xla", k_scales=ks, v_scales=vs),
        dq, k8, v8, ks, vs)

    # -- paged decode: the same context as pages of 16 rows, read
    # where they lie (no dense view), up to each slot's length ----------
    page = 16
    table = jnp.arange(d["s"] * d["t"] // page, dtype=jnp.int32
                       ).reshape(d["s"], -1)

    width = -(-d["h"] * d["d"] // kvc.ROW_ALIGN) * kvc.ROW_ALIGN

    def as_rows(x):
        # (..., H, D) -> (..., W): heads side by side, as a pool's row
        return kvc._pool_rows(jax.ShapeDtypeStruct((width,), bf), x)[0]

    def paged(q, k, v):
        o, _, l = fa.paged_decode_partial(
            as_rows(q), as_rows(k).reshape(1, -1, page, width),
            as_rows(v).reshape(1, -1, page, width), table, lens, 0,
            heads=d["h"], head_dim=d["d"], scale=dscale,
            interpret=interp)
        return (o / l[..., None]).astype(q.dtype)
    run("paged_decode_partial bf16", paged,
        lambda q, k, v: att.decode_attention(q, k, v, lens,
                                             impl="xla"),
        dq, dk, dv)

    # -- paged decode for grouped-query heads: two K/V heads shared by
    # the query heads, a token's K and V side by side in one pool row,
    # from a lower edge on (a sliding layer's window) --------------------
    g, rep = 2, d["h"] // 2
    low = jnp.maximum(lens - d["t"] // 4, 0)

    def paged_gqa(q, k, v):
        rows = jnp.concatenate(
            [k[:, :, :g].reshape(d["s"], d["t"], -1),
             v[:, :, :g].reshape(d["s"], d["t"], -1)], axis=-1)
        o, _, l = fa.paged_gqa_decode_partial(
            q.reshape(d["s"], g, rep, d["d"]),
            rows.reshape(1, -1, page, rows.shape[-1]), table, lens,
            low, 0, k_dim=d["d"], v_dim=d["d"], scale=dscale,
            interpret=interp)
        return (o / l[..., None]).astype(q.dtype).reshape(q.shape)

    def gqa_ref(q, k, v):
        shared = lambda x: jnp.repeat(x[:, :, :g], rep, axis=2)
        s = jnp.einsum("shd,sthd->sht", q, shared(k)).astype(f32)
        at = jnp.arange(d["t"])[None, None, :]
        seen = (at >= low[:, None, None]) & (at < lens[:, None, None])
        p = jax.nn.softmax(jnp.where(seen, s * dscale, -1e30), -1)
        return jnp.einsum("sht,sthd->shd", p.astype(q.dtype),
                          shared(v))
    run("paged_gqa_decode_partial bf16", paged_gqa, gqa_ref,
        dq, dk, dv)

    bad = [c["kernel"] for c in cases if not c["passed"]]
    _check(not bad, f"kernels failed: {bad}")


# ---------------------------------------------------------------------
# phase 5: four chips
# ---------------------------------------------------------------------

def phase_four_chips(sz: Sizes, seed: int, clock: _CompileClock,
                     state: dict, out: dict) -> None:
    import jax

    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.models.image.imageclassification import (
        resnet50)
    from analytics_zoo_tpu.ops.optimizers import SGD, Adam
    from analytics_zoo_tpu.parallel.mesh import (
        shard_batch, shard_params, shard_params_fsdp)
    from analytics_zoo_tpu.pipeline.estimator import Estimator

    devices = jax.devices()[:4]
    _check(len({d.id for d in devices}) == 4,
           f"need 4 devices, JAX has {len(jax.devices())}")
    rs = np.random.RandomState(seed)
    steps = 3
    x, y = _image_data(rs, sz.batch * steps, sz.image)
    model = resnet50(input_shape=(sz.image, sz.image, 3), classes=1000)
    init_params = jax.device_get(model.init_params(
        jax.random.key(seed), device="host"))

    def resnet_job(mesh_axes, devs, mode):
        ctx = init_nncontext(tpu_mesh=mesh_axes, devices=devs,
                             seed=seed, log_level="WARNING")
        est = Estimator(model, optimizer=SGD(lr=0.002, momentum=0.9),
                        loss="softmax_cross_entropy", ctx=ctx,
                        parallel_mode=mode,
                        dtype_policy="mixed_bfloat16")
        place = shard_params_fsdp if mode == "fsdp" else shard_params
        est.params = place(init_params, ctx.mesh)
        clock.reset()
        t0 = time.perf_counter()
        # step 1 alone (its loss is at the shared initial weights),
        # then the other steps as one epoch (their mean loss)
        losses = [est.train(x[:sz.batch], y[:sz.batch],
                            batch_size=sz.batch,
                            nb_epoch=1).history[0]["loss"],
                  est.train(x[sz.batch:], y[sz.batch:],
                            batch_size=sz.batch,
                            nb_epoch=1).history[0]["loss"]]
        jax.block_until_ready(est.params)
        placed = shard_batch(x[:sz.batch], ctx.mesh)
        leaves = jax.tree_util.tree_leaves(est.params)
        # DP replicates every leaf over the chips; FSDP splits the
        # large ones
        return dict(losses=losses,
                    leaf_devices={len(leaf.devices())
                                  for leaf in leaves},
                    sharded=sum(
                        not leaf.sharding.is_fully_replicated
                        for leaf in leaves),
                    batch_devices=len(placed.sharding.device_set),
                    batch_shard=placed.addressable_shards[0].data.shape,
                    wall_s=round(time.perf_counter() - t0, 2),
                    compile_s=clock.read())

    one = resnet_job({"data": 1}, devices[:1], "dp")
    out["one_device"] = {
        "losses": [round(v, 4) for v in one["losses"]],
        "compile_s": one["compile_s"], "wall_s": one["wall_s"]}
    for name, axes, mode in (("dp", {"data": 4}, "dp"),
                             ("fsdp", {"fsdp": 4}, "fsdp")):
        job = resnet_job(axes, devices, mode)
        errs = [abs(a - b) / abs(b)
                for a, b in zip(job["losses"], one["losses"])]
        sharded = job["sharded"]
        out[name] = {
            "losses": [round(v, 4) for v in job["losses"]],
            "max_rel_err_vs_one_device": round(max(errs), 5),
            "batch_devices": job["batch_devices"],
            "batch_shard": list(job["batch_shard"]),
            "param_leaves_sharded": sharded,
            "compile_s": job["compile_s"], "wall_s": job["wall_s"]}
        _check(np.isfinite(job["losses"]).all(),
               f"{name}: non-finite loss")
        # step 1 runs from the same weights: a whole bf16 network
        # summed in another order. After it the weights themselves
        # drift apart, so the later steps' mean gets three times that
        _check(errs[0] <= NET_TOL and max(errs) <= 3 * NET_TOL,
               f"{name}: losses {job['losses']} vs one device "
               f"{one['losses']}")
        _check(job["batch_devices"] == 4 and
               job["batch_shard"][0] == sz.batch // 4,
               f"{name}: batch on {job['batch_devices']} devices, "
               f"shard {job['batch_shard']}")
        _check(job["leaf_devices"] == {4},
               f"{name}: parameters on {job['leaf_devices']} devices")
        _check((sharded > 0) == (mode == "fsdp"),
               f"{name}: {sharded} sharded parameter leaves")

    # -- ring attention over {"data": 2, "seq": 2} -----------------------
    g = dict(sz.gpt, seq_len=sz.ring_t, n_block=sz.ring_blocks)
    xt = rs.randint(1, g["vocab"],
                    size=(sz.ring_batch, sz.ring_t)).astype(np.int32)
    yt = rs.randint(0, 8, size=(sz.ring_batch, 1)).astype(np.int32)
    tparams = None
    ring = {}
    for name, axes, devs, sp in (
            ("one_device", {"data": 1}, devices[:1], None),
            ("ring", {"data": 2, "seq": 2}, devices, "seq")):
        ctx = init_nncontext(tpu_mesh=axes, devices=devs, seed=seed,
                             log_level="WARNING")
        m = _lm_classifier(g, sequence_parallel_axis=sp)
        if tparams is None:
            tparams = jax.device_get(m.init_params(
                jax.random.key(seed + 2), device="host"))
        est = Estimator(m, optimizer=Adam(lr=1e-4),
                        loss="softmax_cross_entropy", ctx=ctx,
                        dtype_policy="mixed_bfloat16")
        est.params = shard_params(tparams, ctx.mesh)
        clock.reset()
        t0 = time.perf_counter()
        res = est.train(xt, yt, batch_size=sz.ring_batch, nb_epoch=1)
        jax.block_until_ready(est.params)
        ring[name] = dict(loss=res.history[0]["loss"],
                          devices=len(ctx.mesh.devices.flat),
                          wall_s=round(time.perf_counter() - t0, 2),
                          compile_s=clock.read())
        del est
    ring_err = abs(ring["ring"]["loss"] - ring["one_device"]["loss"]) \
        / abs(ring["one_device"]["loss"])
    out["ring_attention"] = {
        "T": sz.ring_t, "blocks": sz.ring_blocks,
        "mesh": {"data": 2, "seq": 2},
        "loss": round(ring["ring"]["loss"], 5),
        "loss_one_device": round(ring["one_device"]["loss"], 5),
        "rel_err": round(ring_err, 6),
        "compile_s": ring["ring"]["compile_s"],
        "wall_s": ring["ring"]["wall_s"]}
    _check(np.isfinite(ring["ring"]["loss"]), "ring: non-finite loss")
    _check(ring["ring"]["devices"] == 4, "ring mesh is not 4 devices")
    _check(ring_err <= NET_TOL,
           f"ring-attention loss {ring['ring']['loss']:.5f} vs one "
           f"device {ring['one_device']['loss']:.5f}")


# ---------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------

ONE_CHIP = [("train", phase_train), ("serve", phase_serve),
            ("generate", phase_generate), ("kernels", phase_kernels)]
FOUR_CHIPS = [("four_chips", phase_four_chips)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the four-chip phase and what it is "
                         "compared with, and no one-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths under the Pallas interpreter, "
                         "for the sandbox and the tests; never ok")
    args = ap.parse_args(argv)

    if args.rehearse:
        # the interpreter stands in for the chip's kernels: let
        # attention "auto" route to them off-TPU and at toy lengths
        os.environ.setdefault("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
        os.environ.setdefault("ZOO_TPU_FLASH_MIN_T", "128")
        if args.chips == 4 and "xla_force_host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=4").strip()

    import jax

    # beside chip_smoke.py alone there is no program to smoke: fail
    # here, before anything is printed
    import analytics_zoo_tpu  # noqa: F401

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{dev.platform!r}); nothing was run. --rehearse runs "
              f"the toy-size rehearsal.", file=sys.stderr)
        return EXIT_NO_CHIP
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX has "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return EXIT_NO_CHIP

    sz = Sizes(args.rehearse)
    clock = _CompileClock()
    state: dict = {}
    failed = []
    t_all = time.perf_counter()
    for name, phase in (FOUR_CHIPS if args.chips == 4 else ONE_CHIP):
        t0 = time.perf_counter()
        rec = {"phase": name}
        try:
            phase(sz, args.seed, clock, state, rec)
            rec["passed"] = True
        except Exception as e:  # reported, counted, exit code 1
            traceback.print_exc()
            rec.update(passed=False,
                       error=f"{type(e).__name__}: {e}"[:2000])
            failed.append(name)
        rec["wall_s"] = round(time.perf_counter() - t0, 2)
        _emit(rec)
    on_chip = dev.platform == "tpu" and not args.rehearse
    _emit({"phase": "summary", "failed": failed,
           "rehearsal": args.rehearse,
           "wall_s": round(time.perf_counter() - t_all, 2)})
    # the last line carries these two keys and nothing else
    _emit({"ok": on_chip and not failed, "device": device})
    if failed:
        return EXIT_PHASE_FAILED
    return EXIT_OK if on_chip else EXIT_NO_CHIP


if __name__ == "__main__":
    sys.exit(main())
