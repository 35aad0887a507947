"""Secondary benchmark: NeuralCF training throughput (samples/sec/chip).

BASELINE.json names two workloads — "nnframes ResNet-50 images/sec/chip;
NCF recsys samples/sec". `bench.py` owns the first; this prints ONE JSON
line for the second:

    {"metric": "ncf_train_samples_per_sec_per_chip", "value": N,
     "unit": "samples/sec", "vs_baseline": null}

`vs_baseline` is null: the reference publishes no NCF throughput number
(BASELINE.md lists the workload without a target), so there is nothing
honest to normalise against. The measured number lives in PERF.md, and
`bench.py` embeds this metric in its own JSON line (`extra_metrics`) so
the driver's BENCH artifact carries both workloads.

Model/recipe: the reference NeuralCF ml-1m example
(`examples/recommendation/NeuralCFexample.scala`: 6040 users, 3706
items, 5 rating classes, userEmbed=itemEmbed=mfEmbed=20, MLP
40→20→10, Adam) — the same architecture `models/recommendation/
neuralcf.py` builds. Timing follows bench.py: one jitted lax.scan
chain of train steps, one scalar host fetch, min-of-5 dispatch
overhead subtracted (it would otherwise dominate this sub-ms step).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_t_start = time.perf_counter()


def measure(batch: int = 8192, steps: int = 20,
            metric: str = "ncf_train_samples_per_sec_per_chip") -> dict:
    """Measure NCF training throughput on the ALREADY-initialized
    backend; returns the metric record (callable in-process from
    bench.py after its own backend init)."""
    import jax
    import jax.numpy as jnp
    import optax

    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.models.recommendation import NeuralCF

    init_nncontext(tpu_mesh={"data": 1}, devices=jax.devices()[:1],
                   log_level="WARNING")
    # ml-1m scale + the reference example's dims
    ncf = NeuralCF(user_count=6040, item_count=3706, num_classes=5,
                   user_embed=20, item_embed=20,
                   hidden_layers=(40, 20, 10), mf_embed=20)
    model = ncf.build_model()
    params = model.init_params()
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    def nll(y, logp):  # model ends in log_softmax (reference LogSoftMax)
        picked = jnp.take_along_axis(logp, y.astype(jnp.int32), axis=-1)
        return -jnp.mean(picked)

    def train_step(params, opt_state, x, y):
        def compute_loss(p):
            out, upd = model.apply(p, x, training=True)
            return nll(y, out), upd
        (loss, upd), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    rs = np.random.RandomState(0)
    users = rs.randint(0, 6040, size=batch)
    items = rs.randint(0, 3706, size=batch)
    x = jnp.asarray(np.stack([users, items], 1), jnp.int32)
    y = jnp.asarray(((users + items) % 5)[:, None], jnp.int32)

    def run(params, opt_state, x, y):
        def body(carry, _):
            p, o = carry
            p, o, loss = train_step(p, o, x, y)
            return (p, o), loss
        (p, o), losses_seq = jax.lax.scan(
            body, (params, opt_state), None, length=steps)
        return p, o, losses_seq[-1]

    t0 = time.perf_counter()
    compiled = jax.jit(run).lower(params, opt_state, x, y).compile()
    t_compile = time.perf_counter() - t0

    from bench_common import time_chain
    dt, loss, rtt_bound = time_chain(
        compiled, (params, opt_state, x, y), with_quality=True)
    samples_per_sec = batch * steps / dt
    print(f"# [ncf] batch={batch} steps={steps} "
          f"step_time={dt / steps * 1e6:.0f}us loss={loss:.3f} "
          f"compile={t_compile:.1f}s rtt_bound={rtt_bound}",
          file=sys.stderr, flush=True)
    from bench_common import flag_rtt_bound
    return flag_rtt_bound({
        "metric": metric,
        "value": round(samples_per_sec, 1),
        "unit": "samples/sec",
        "vs_baseline": None,
    }, rtt_bound)


def main():
    batch = int(os.environ.get("ZOO_TPU_BENCH_NCF_BATCH", "8192"))
    steps = int(os.environ.get("ZOO_TPU_BENCH_STEPS", "20"))

    from bench_common import attach_metrics_snapshot, select_device
    device = select_device()
    print(f"# backend={device['platform']} n_devices={device['count']}",
          file=sys.stderr, flush=True)
    rec = attach_metrics_snapshot(measure(batch=batch, steps=steps))
    rec["device"] = device
    print(json.dumps(rec), flush=True)
    print(f"# total={time.perf_counter() - _t_start:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
