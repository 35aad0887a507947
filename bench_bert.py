"""Tertiary benchmark: BERT fine-tune training throughput
(samples/sec/chip).

BASELINE.json's config list names "TFPark TFOptimizer: distributed
BERT-base fine-tune on TPU pod" as the fifth recipe. This measures the
single-chip fine-tune step — the native BERT encoder
(`layers/transformer.py`, reference `BERT.scala:53-110`) + pooled
classifier head, bf16 activations, Adam — and prints ONE JSON line:

    {"metric": "bert_finetune_samples_per_sec_per_chip", "value": N,
     "unit": "samples/sec", "vs_baseline": null, "config": "..."}

`vs_baseline` is null (the reference publishes no BERT throughput).
`bench.py` embeds this record in `extra_metrics` budget-permitting, so
a live BENCH artifact carries all three BASELINE workloads. The
default config is BERT-base-shaped but truncated to 4 blocks so the
measurement + compile fit the bench budget window; the `config` field
says exactly what ran (scale honestly, never silently).

Timing follows bench.py: one jitted lax.scan chain of train steps,
one scalar host fetch, min-of-5 dispatch overhead subtracted.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def measure(batch: int = 32, steps: int = 10, seq_len: int = 128,
            hidden: int = 768, blocks: int = 4,
            metric: str = "bert_finetune_samples_per_sec_per_chip"
            ) -> dict:
    """Measure on the ALREADY-initialized backend; returns the metric
    record (callable in-process from bench.py)."""
    import jax
    import jax.numpy as jnp
    import optax

    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.pipeline.api.keras import layers as L

    init_nncontext(tpu_mesh={"data": 1}, devices=jax.devices()[:1],
                   log_level="WARNING")
    vocab, classes = 30522, 2   # BERT-base vocab; sentence-pair task
    bert = L.BERT(vocab=vocab, hidden_size=hidden, n_block=blocks,
                  n_head=hidden // 64, seq_len=seq_len,
                  intermediate_size=4 * hidden,
                  output_all_block=False, input_shape=[(seq_len,)] * 4)
    rngk = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(rngk)
    params = {"bert": bert.build(k1, [(seq_len,)] * 4)}
    params["head_w"] = jax.random.normal(
        k2, (hidden, classes), jnp.float32) * 0.02
    params["head_b"] = jnp.zeros((classes,), jnp.float32)

    tx = optax.adam(5e-5)
    opt_state = tx.init(params)

    rs = np.random.RandomState(0)
    tok = jnp.asarray(rs.randint(1, vocab, (batch, seq_len)), jnp.int32)
    seg = jnp.zeros((batch, seq_len), jnp.int32)
    pos = jnp.tile(jnp.arange(seq_len, dtype=jnp.int32), (batch, 1))
    msk = jnp.ones((batch, seq_len), jnp.bfloat16)
    y = jnp.asarray(rs.randint(0, classes, (batch,)), jnp.int32)

    def train_step(params, opt_state, rng):
        def compute_loss(p):
            # bf16 activations via bf16 embeddings (framework policy)
            bp = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16)
                if a.dtype == jnp.float32 else a, p["bert"])
            _, pooled = bert.call(bp, [tok, seg, pos, msk],
                                  training=True, rng=rng)
            logits = pooled.astype(jnp.float32) @ p["head_w"] \
                + p["head_b"]
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(
                jnp.take_along_axis(logp, y[:, None], axis=1))

        loss, grads = jax.value_and_grad(compute_loss)(params)
        updates, opt_state2 = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state2, loss

    def run(params, opt_state, rng):
        def body(carry, i):
            p, o = carry
            p, o, loss = train_step(p, o, jax.random.fold_in(rng, i))
            return (p, o), loss
        (p, o), losses_seq = jax.lax.scan(
            body, (params, opt_state), jnp.arange(steps))
        return p, o, losses_seq[-1]

    t0 = time.perf_counter()
    compiled = jax.jit(run).lower(params, opt_state, rngk).compile()
    t_compile = time.perf_counter() - t0

    from bench_common import time_chain
    dt, loss, rtt_bound = time_chain(
        compiled, (params, opt_state, rngk), with_quality=True)
    samples_per_sec = batch * steps / dt
    print(f"# [bert] batch={batch} T={seq_len} hidden={hidden} "
          f"blocks={blocks} steps={steps} "
          f"step_time={dt / steps * 1000:.1f}ms loss={loss:.3f} "
          f"compile={t_compile:.1f}s rtt_bound={rtt_bound}",
          file=sys.stderr, flush=True)
    from bench_common import flag_rtt_bound
    return flag_rtt_bound({
        "metric": metric,
        "value": round(samples_per_sec, 1),
        "unit": "samples/sec",
        "vs_baseline": None,
        "config": f"hidden={hidden} blocks={blocks} T={seq_len} "
                  f"batch={batch} bf16",
    }, rtt_bound)


def main():
    from bench_common import attach_metrics_snapshot, select_device
    device = select_device()
    rec = measure(
        batch=int(os.environ.get("ZOO_TPU_BENCH_BERT_BATCH", "32")),
        steps=int(os.environ.get("ZOO_TPU_BENCH_STEPS", "10")),
        hidden=int(os.environ.get("ZOO_TPU_BENCH_BERT_HIDDEN", "768")),
        blocks=int(os.environ.get("ZOO_TPU_BENCH_BERT_BLOCKS", "4")))
    rec["device"] = device
    print(json.dumps(attach_metrics_snapshot(rec)), flush=True)


if __name__ == "__main__":
    main()
