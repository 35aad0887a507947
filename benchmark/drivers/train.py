"""Driver of the training cells: one ``Estimator.train`` call is the
window.

Set-up builds ONE Estimator, gives it weights made from the seed,
drives it through its first steps on rows that all differ (each a
``train`` call of its own, so that each step's loss can be read) and
hands the same object, compiled step and state included, to the
window. Those first steps are what the plain float32 reference
follows once the window has closed and the program's state is freed.

The mesh comes from the cell's ``chips`` (``{"data": chips}`` unless
the traffic file gives a ``mesh``), the global batch is the
configuration's batch per chip times the chips, and everything else
from the two data files.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import harness, probe, traffic, weights
from benchmark.reference import resnet as ref


class Window:
    """The ``end_trigger`` of the window's ``train`` call. The loop
    calls it after every step: the first call, which follows the
    call's one-off first step (synchronised by the loop itself), opens
    the window; the call that finds ``seconds`` gone closes it."""

    def __init__(self, seconds: float, tracer=None):
        self.seconds = seconds
        self.tracer = tracer
        self.t_open = self.step_open = None
        self.step_last = 0

    def __call__(self, epoch, iteration, epoch_end, **state) -> bool:
        now = time.perf_counter()
        self.step_last = iteration
        if self.t_open is None:
            self.t_open, self.step_open = now, iteration
            if self.tracer is not None:
                self.tracer.arm()
            return False
        return now - self.t_open >= self.seconds


def _flat(tree) -> dict:
    """{(key, ...): leaf} of a nested dict pytree."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[tuple(k.key for k in path)] = leaf
    return out


def _first_gradient(opt_state) -> dict:
    """The gradient the optimizer got at step 1: after one step
    SGD's momentum trace is that gradient. {param path: array}."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            opt_state)[0]:
        names = [getattr(k, "name", None) for k in path]
        if "trace" in names:
            keys = path[names.index("trace") + 1:]
            out[tuple(k.key for k in keys)] = leaf
    return out


def place_weights(model, flat_np: dict):
    """The benchmark's weights in the program's parameter tree: the
    tree's structure from the model, every leaf from ``flat_np``; the
    two have to name exactly the same leaves."""
    import jax
    template = jax.eval_shape(
        lambda: model.init_params(jax.random.key(0), device="host"))
    want = {p: tuple(l.shape) for p, l in _flat(template).items()}
    have = {p: tuple(v.shape) for p, v in flat_np.items()}
    if want != have:
        odd = sorted(set(want.items()) ^ set(have.items()))[:6]
        raise RuntimeError("the program's parameter tree and the "
                           f"reference's differ, e.g. {odd}")
    paths = [tuple(k.key for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(template)[0]]
    treedef = jax.tree_util.tree_structure(template)
    return jax.tree_util.tree_unflatten(
        treedef, [flat_np[p] for p in paths])


def norm_gap(got: dict, want: dict, keys, over=np.max) -> float:
    """Worst leaf (or ``over`` the leaves) of |‖got‖ − ‖want‖|
    against the larger of the reference's norm of that leaf and of
    the median leaf."""
    keys = list(keys)
    if not keys:
        return float("nan")
    g = np.array([np.linalg.norm(np.asarray(got[k], np.float64))
                  for k in keys])
    w = np.array([np.linalg.norm(np.asarray(want[k], np.float64))
                  for k in keys])
    return float(over(np.abs(g - w) /
                      np.maximum(w, max(np.median(w), 1e-30))))


def compare(limits: dict, prog: dict, refr: dict
            ) -> harness.Compared:
    """``prog``/``refr``: losses, g1 (flat), p0, pk (flat). Five
    numbers: the steps' losses, the first gradient, the weights'
    change, and the change of BatchNorm's moving statistics by the
    worst leaf and by the median leaf (the one a lower precision
    shows in: PERF.md)."""
    out = harness.Compared()
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], refr["losses"]))
    out.add("loss_gap", loss_gap, limits["loss_gap"])
    train_keys = sorted(refr["g1"])
    out.add("grad_norm_gap", norm_gap(prog["g1"], refr["g1"],
                                      train_keys),
            limits["grad_norm_gap"])
    # a leaf whose reference gradient is nought to rounding moves by
    # round-off alone: left out of the change by that rule
    gn = {k: np.linalg.norm(np.asarray(refr["g1"][k], np.float64))
          for k in train_keys}
    floor = 1e-3 * float(np.median(list(gn.values())))
    moved = [k for k in train_keys if gn[k] >= floor]
    change = lambda side: {k: np.asarray(side["pk"][k], np.float64) -
                           np.asarray(side["p0"][k], np.float64)
                           for k in side["pk"]}
    dp, dr = change(prog), change(refr)
    out.add("weight_change_gap", norm_gap(dp, dr, moved),
            limits["weight_change_gap"])
    state_keys = sorted(k for k in refr["pk"] if "_state" in k)
    if state_keys:
        out.add("bn_state_change_gap", norm_gap(dp, dr, state_keys),
                limits["bn_state_change_gap"])
        out.add("bn_state_median_gap",
                norm_gap(dp, dr, state_keys, over=np.median),
                limits["bn_state_median_gap"])
    return out


def build(loaded: dict, seed: int, devices, n_batches=None):
    """Context, data, model, weights and the one Estimator
    (``n_batches``: fewer than the mix holds, for the calibration,
    which needs only the first steps' rows)."""
    import jax
    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.models.image.imageclassification import \
        resnet50
    from analytics_zoo_tpu.ops.optimizers import SGD
    from analytics_zoo_tpu.parallel.mesh import shard_params
    from analytics_zoo_tpu.pipeline.estimator import Estimator

    cfg, mix = loaded["config"], loaded["traffic"]
    if cfg["family"] != "resnet" or cfg["stage_blocks"] != [3, 4, 6, 3]:
        raise ValueError("the train driver builds the zoo's resnet50")
    chips = len(devices)
    ctx = init_nncontext(tpu_mesh=mix.get("mesh", {"data": chips}),
                         devices=devices, seed=seed & 0x7FFFFFFF,
                         log_level="WARNING")
    batch = cfg["batch_per_chip"] * chips
    x, y = traffic.images(seed, (n_batches or mix["n_batches"]) * batch,
                          cfg["image_size"], cfg["in_channels"],
                          mix["pattern_classes"], mix["noise_std"])
    model = resnet50(
        input_shape=(cfg["image_size"], cfg["image_size"],
                     cfg["in_channels"]),
        classes=cfg["num_classes"])
    flat = jax.device_get(
        weights.resnet_weights(ref.param_shapes(cfg), seed,
                               cfg["init"]))
    opt = cfg["optimizer"]
    est = Estimator(model, optimizer=SGD(lr=opt["lr"],
                                         momentum=opt["momentum"]),
                    loss=cfg["loss"], ctx=ctx,
                    dtype_policy=cfg["precision"])
    est.params = shard_params(place_weights(model, flat), ctx.mesh)
    return est, x, y, batch, flat


def first_steps(est, x, y, batch: int, k: int) -> dict:
    """Steps 1..k through the window's own call and feed, one call a
    step. Returns what the comparison needs, on the host."""
    import jax
    prog = {"losses": []}
    for i in range(k):
        rows = slice(i * batch, (i + 1) * batch)
        res = est.train(x[rows], y[rows], batch_size=batch, nb_epoch=1)
        prog["losses"].append(float(res.history[0]["loss"]))
        if i == 0:
            prog["g1"] = jax.device_get(_first_gradient(est.opt_state))
    prog["pk"] = jax.device_get(_flat(est.params))
    return prog


def reference_steps(cfg: dict, flat: dict, x, y, batch: int, k: int,
                    quant: bool = False, rows_per_batch=None,
                    step=None, frozen: bool = False) -> dict:
    """The plain float32 model over the same rows. For the
    calibration, ``rows_per_batch`` plants the half-batch fault and
    ``frozen`` the step that returns its state unchanged (what the
    program's state would then show: no gradient in the optimizer, no
    change in any leaf)."""
    import jax
    n = rows_per_batch or batch
    batches = [(x[i * batch:i * batch + n], y[i * batch:i * batch + n])
               for i in range(k)]
    losses, g1, pk = ref.run_steps(cfg, flat, batches, quant=quant,
                                   step=step, lr=0.0 if frozen else None)
    g1, pk = jax.device_get(g1), jax.device_get(pk)
    if frozen:
        g1 = {k: np.zeros_like(v) for k, v in g1.items()}
        pk = dict(flat)
    return {"losses": losses, "g1": g1, "p0": flat, "pk": pk}


def run(loaded: dict, *, seed: int, seconds: float, trace: bool,
        devices, t0: float) -> dict:
    import jax
    cfg, mix, cell = loaded["config"], loaded["traffic"], loaded["cell"]
    est, x, y, batch, flat = build(loaded, seed, devices)
    k = int(mix["check_steps"])
    prog = first_steps(est, x, y, batch, k)
    prog["p0"] = flat

    tracer = harness.tracer_for(loaded, seconds, trace)
    win = Window(seconds, tracer)
    cursor = probe.span_cursor()
    result = est.train(x, y, batch_size=batch, nb_epoch=10 ** 9,
                       end_trigger=win)
    jax.block_until_ready(est.params)
    t_close = time.perf_counter()
    if tracer is not None:
        tracer.finish()
    _, spans = probe.spans_since(cursor)
    steps = win.step_last - win.step_open
    wall = t_close - win.t_open
    bad = sum(1 for e in result.history if not np.isfinite(e["loss"]))
    peak = harness.memory_peak_bytes(devices)

    # the program's state goes before the reference comes
    est.params = est.opt_state = None
    del est, result
    gc.collect()
    refr = reference_steps(cfg, flat, x, y, batch, k)
    compared = compare(cell["limits"], prog, refr)

    t_open_wall = time.time() - (time.perf_counter() - win.t_open)
    reduction = tracer.reduction() if tracer else None
    return {
        "attempted": steps, "failed": steps if bad else 0,
        "end_to_end": {"train_img_per_s": steps * batch / wall,
                       "setup_s": win.t_open - t0},
        "memory_peak_bytes": peak, "compared": compared,
        "notes": {"trace_reduce_s": tracer.reduce_s} if tracer else {},
        "layers": {
            "trace": reduction,
            "config": cfg, "traffic": mix, "chips": len(devices),
            "peak": harness.peak_or_none(devices),
            "batch": batch, "window_s": wall, "steps": steps,
            "spans": [s for s in spans
                      if s["t_start"] >= t_open_wall],
        },
    }
