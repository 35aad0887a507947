"""Headline benchmark: ResNet-50 training throughput on the local chip.

One process, one device, no fallback: it measures on the device JAX
names and prints JSON lines {"metric", "value", "unit", "vs_baseline",
"device", ...}; the last line is the complete record. It exits
non-zero when the platform is not ``tpu``, or when the ResNet step,
the NCF leg or the BERT leg raises. ``ZOO_TPU_BENCH_PLATFORM=cpu`` runs
the same code on the CPU for the tests; the record then says so
(``device.platform`` and ``smoke``) and is not a measurement.

The BASELINE.json target is the nnframes ResNet-50 ImageNet recipe at
>=45% MFU (v5e). vs_baseline here = achieved MFU / 0.45, with FLOPs
taken from XLA's own cost analysis of the compiled train step and the
peak from `perf.goodput`'s table keyed by ``device_kind``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_t_start = time.perf_counter()
# progressively-updated best-known result
_result = {
    "metric": "resnet50_train_images_per_sec_per_chip",
    "value": 0.0,
    "unit": "images/sec",
    "vs_baseline": 0.0,
}


def _emit() -> None:
    print(json.dumps(_result), flush=True)


def _resnet_train_chain(model, tx, loss_fn, steps):
    """Returns ``(train_step, run)`` where ``run`` is a
    ``steps``-long ``lax.scan`` chain of ``train_step`` over a fixed
    batch (one dispatch + one scalar fetch per measurement)."""
    import jax
    import optax

    from analytics_zoo_tpu.pipeline.estimator import Estimator

    def train_step(params, opt_state, x, y):
        def compute_loss(p):
            out, upd = model.apply(p, x, training=True)
            return loss_fn(y, out), upd

        (loss, upd), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(params)
        updates, opt_state2 = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        params = Estimator._merge_updates(params, upd)
        return params, opt_state2, loss

    def run(params, opt_state, x, y):
        def body(carry, _):
            p, o = carry
            p, o, loss = train_step(p, o, x, y)
            return (p, o), loss
        (p, o), losses_seq = jax.lax.scan(
            body, (params, opt_state), None, length=steps)
        return p, o, losses_seq[-1]

    return train_step, run


def main():
    batch = int(os.environ.get("ZOO_TPU_BENCH_BATCH", "128"))
    image = int(os.environ.get("ZOO_TPU_BENCH_IMAGE", "224"))
    steps = int(os.environ.get("ZOO_TPU_BENCH_STEPS", "20"))

    import jax
    import jax.numpy as jnp

    from bench_common import select_device
    device = _result["device"] = select_device()
    devices = jax.devices()
    print(f"# backend={device['platform']} kind={device['kind']} "
          f"n_devices={device['count']}", file=sys.stderr, flush=True)

    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.models.image.imageclassification import resnet50
    from analytics_zoo_tpu.ops import losses, optimizers
    from analytics_zoo_tpu.perf import flops as perf_flops
    from analytics_zoo_tpu.perf.goodput import resolve_peak_flops

    # unknown device_kind raises: no peak is assumed
    peak_flops = resolve_peak_flops(device["kind"])

    init_nncontext(tpu_mesh={"data": 1}, devices=devices[:1],
                   log_level="WARNING")
    s2d = os.environ.get("ZOO_TPU_BENCH_S2D", "1") == "1"
    loss_fn = losses.softmax_cross_entropy
    tx = optimizers.SGD(lr=0.1, momentum=0.9).to_optax()

    rs = np.random.RandomState(0)
    # bf16 inputs: layers compute in input dtype, params stay f32
    x = jnp.asarray(rs.randn(batch, image, image, 3), jnp.bfloat16)
    y = jnp.asarray(rs.randint(0, 1000, size=(batch, 1)), jnp.int32)

    # analytic estimate: fwd ~4.09 GFLOPs/img @224, train ~3x fwd
    flops_analytic = 3 * 4.09e9 * batch * (image / 224.0) ** 2

    def _cost_flops(comp) -> float:
        cost = comp.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        if cost is None:
            # the TPU backend analyses compiled programs only: a
            # Lowered has no count there (0.0 = "none visible"; the
            # compiled program's count or the analytic one stands in)
            return 0.0
        # XLA's HloCostAnalysis counts a while/scan body ONCE, not
        # per trip, so the chain's flops ~= one step's
        return float(cost.get("flops", 0.0))

    # constant dispatch overhead estimate (min of 5 samples: a single
    # transient spike must not inflate the reported MFU)
    tiny = jax.jit(lambda a: a + 1.0).lower(
        jnp.zeros((), jnp.float32)).compile()
    float(np.asarray(tiny(jnp.zeros((), jnp.float32))))  # warm
    overhead = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        float(np.asarray(tiny(jnp.zeros((), jnp.float32))))
        overhead = min(overhead, time.perf_counter() - t0)

    tag = "resnet50"
    model = resnet50(input_shape=(image, image, 3), classes=1000,
                     space_to_depth=s2d)
    t0 = time.perf_counter()
    # host-CPU param + opt init (``init_params(device="host")`` returns
    # CPU-committed leaves, so the eager ``tx.init`` zeros follow them
    # onto the CPU), then one device transfer
    params = model.init_params(jax.random.PRNGKey(0), device="host")
    params, opt_state = jax.device_put(
        (params, tx.init(params)), jax.devices()[0])
    jax.block_until_ready((params, opt_state))
    print(f"# [{tag}] host init+transfer="
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr,
          flush=True)
    # ONE compiled program: a lax.scan chain of `steps` train
    # steps — one dispatch + one scalar fetch; the constant
    # dispatch overhead is subtracted.
    _, run = _resnet_train_chain(model, tx, loss_fn, steps)

    t0 = time.perf_counter()
    lowered = jax.jit(run).lower(params, opt_state, x, y)
    # executed-vs-model FLOPs ratio of the XLA graph measured
    # (perf.flops: dilation zeros count as executed;
    # HloCostAnalysis discounts them and cannot see the gap).
    # flops_analytic counts MACs (torchvision's 4.09e9/img);
    # executed_flops counts 2 FLOPs/MAC — hence the 2x.
    _result["flops_ratio_executed_vs_model"] = round(
        perf_flops.executed_flops(
            perf_flops.hlo_text(lowered)) /
        (2.0 * flops_analytic), 4)
    lowered_flops = _cost_flops(lowered)
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    print(f"# [{tag}] compile={t_compile:.1f}s", file=sys.stderr,
          flush=True)

    flops_per_step = max(_cost_flops(compiled), lowered_flops)
    if not (0.2 * flops_analytic < flops_per_step <
            5 * flops_analytic):
        # nan/zero, or a cost-model change (per-trip counting)
        flops_per_step = flops_analytic

    def timed():
        t0 = time.perf_counter()
        p, o, loss = compiled(params, opt_state, x, y)
        loss_val = float(np.asarray(loss))  # host fetch = sync
        return time.perf_counter() - t0, loss_val

    def derive(best_dt):
        dt = max(best_dt - overhead, 1e-9)
        images_per_sec = batch * steps / dt
        mfu = (flops_per_step * steps / dt) / peak_flops
        # model-FLOPs MFU: the honest number (analytic 3x-forward
        # FLOPs, not XLA's hardware-op count which includes remat
        # and counts some fusions generously)
        mfu_model = (flops_analytic * steps / dt) / peak_flops
        return dt, images_per_sec, mfu, mfu_model

    timed()  # warmup (execution path, allocator)
    profile_dir = os.environ.get("ZOO_TPU_BENCH_PROFILE_DIR")
    if profile_dir:  # jax.profiler trace of one measured chain
        jax.profiler.start_trace(os.path.join(profile_dir, tag))
        timed()
        jax.profiler.stop_trace()
        print(f"# [{tag}] profile trace -> {profile_dir}/{tag}",
              file=sys.stderr, flush=True)
    best_dt, loss = None, float("nan")
    for _ in range(2):
        dt_i, loss = timed()
        if not np.isfinite(loss):
            raise RuntimeError(
                f"non-finite loss {loss} after {steps} steps")
        best_dt = dt_i if best_dt is None else min(best_dt, dt_i)
    dt, images_per_sec, mfu, mfu_model = derive(best_dt)
    _result.update(
        value=round(images_per_sec, 2),
        vs_baseline=round(mfu / 0.45, 4),
        mfu_xla_flops=round(mfu, 6),
        mfu_model_flops=round(mfu_model, 6),
        vs_baseline_model_flops=round(mfu_model / 0.45, 6))
    print(f"# [{tag}] batch={batch} image={image} steps={steps} "
          f"step_time={dt / steps * 1000:.1f}ms mfu={mfu:.3f} "
          f"mfu_model={mfu_model:.3f} "
          f"loss={loss:.3f} flops/step={flops_per_step:.3e} "
          f"overhead={overhead * 1000:.1f}ms "
          f"compile={t_compile:.1f}s", file=sys.stderr, flush=True)
    if os.environ.get("ZOO_TPU_BENCH_NCF", "1") == "1":
        # second BASELINE.json workload rides the same record
        from bench_ncf import measure as ncf_measure
        _result.setdefault("extra_metrics", []).append(
            ncf_measure(
                batch=int(os.environ.get("ZOO_TPU_BENCH_NCF_BATCH",
                                         "8192")),
                steps=steps))
    # third BASELINE workload (config #5, BERT fine-tune): "auto" runs
    # it on the chip and skips it in the tests' CPU smoke (a 4-block
    # BERT-base step takes minutes there); "1" forces, "0" skips
    bert_mode = os.environ.get("ZOO_TPU_BENCH_BERT", "auto")
    if bert_mode == "1" or (bert_mode == "auto"
                            and device["platform"] == "tpu"):
        from bench_bert import measure as bert_measure
        _result.setdefault("extra_metrics", []).append(bert_measure(
            batch=int(os.environ.get("ZOO_TPU_BENCH_BERT_BATCH", "32")),
            steps=min(steps, 10),
            hidden=int(os.environ.get("ZOO_TPU_BENCH_BERT_HIDDEN",
                                      "768")),
            blocks=int(os.environ.get("ZOO_TPU_BENCH_BERT_BLOCKS",
                                      "4"))))
    else:
        print(f"# [bert] skipped (ZOO_TPU_BENCH_BERT={bert_mode}, "
              f"platform={device['platform']})", file=sys.stderr,
              flush=True)
    _emit()
    print(f"# total={time.perf_counter() - _t_start:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
