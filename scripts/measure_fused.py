"""Fused conv+BN measurement: kernel micro-benches and the model A/B.

A chip belongs to one process at a time, so the parent never imports
JAX: it starts one child at a time and waits for it to exit before
the next —
  1. kernel microbench (this script, ``--role micro``): matmul_bn vs
     the equivalent unfused XLA graph (prologue-apply+relu, matmul,
     single-pass stats) on ResNet-50's 1x1 shapes, fwd and fwd+bwd,
     plus the residual-epilogue, 3x3 and stride-2-backward A/Bs;
  2. full-model A/B: ResNet-50 train step fused=0 vs fused=1
     (one bench.py child each).
Any child that exits non-zero fails the run.

Usage:  python scripts/measure_fused.py [--skip-micro] [--skip-model]
        [--steps 20] [--tiny]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

# (M, K, N): ResNet-50 1x1 conv shapes at batch 128
_RESNET_SHAPES = [
    (128 * 56 * 56, 64, 64),      # s0 c1
    (128 * 56 * 56, 64, 256),     # s0 c3
    (128 * 56 * 56, 256, 64),     # s0b1 c1
    (128 * 28 * 28, 512, 128),    # s1 c1
    (128 * 28 * 28, 128, 512),    # s1 c3
    (128 * 14 * 14, 1024, 256),   # s2 c1
    (128 * 14 * 14, 256, 1024),   # s2 c3
    (128 * 7 * 7, 2048, 512),     # s3 c1
    (128 * 7 * 7, 512, 2048),     # s3 c3
]


def _parse(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--skip-micro", action="store_true")
    p.add_argument("--skip-model", action="store_true")
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--tiny", action="store_true",
                   help="smoke-run mechanics on CPU-size shapes")
    p.add_argument("--autotune-ab", action="store_true",
                   help="tuned-vs-heuristic block-config A/B per "
                        "shape + second-pass zero-sweep assertion "
                        "(run under ZOO_TPU_AUTOTUNE=1; "
                        "docs/autotune.md)")
    p.add_argument("--role", choices=["parent", "micro"],
                   default="parent", help=argparse.SUPPRESS)
    return p.parse_args(argv)


def _micro(args) -> int:
    """The kernel micro-benches, in a process of their own."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.common.device import setup_compile_cache

    setup_compile_cache()
    devices = jax.devices()
    print(f"# backend={devices[0].platform}", flush=True)

    steps = args.steps

    def _t(f):
        t0 = time.perf_counter()
        f()
        return time.perf_counter() - t0

    def chain_time(fn, x, *consts):
        """ms per call of fn(x, *consts): one jitted scan chain of
        `steps` iterations feeding x -> x, min of 3 runs, dispatch
        overhead subtracted."""
        @jax.jit
        def chain(x, *consts):
            def body(c, _):
                out = fn(c, *consts)
                return out.astype(c.dtype), jnp.zeros(())
            c, _ = jax.lax.scan(body, x, None, length=steps)
            return jnp.sum(c.astype(jnp.float32))
        float(np.asarray(chain(x, *consts)))            # compile+warm
        tiny = jax.jit(lambda a: a + 1.0)
        float(np.asarray(tiny(jnp.zeros(()))))
        over = min(_t(lambda: float(np.asarray(tiny(jnp.zeros(())))))
                   for _ in range(5))
        best = min(_t(lambda: float(np.asarray(chain(x, *consts))))
                   for _ in range(3))
        return max(best - over, 1e-9) / steps * 1e3

    if not args.skip_micro:
        from analytics_zoo_tpu.ops.conv_bn import conv3x3_bn, matmul_bn

        shapes = [(512, 128, 256), (256, 256, 128)] if args.tiny \
            else _RESNET_SHAPES
        rs = np.random.RandomState(0)
        print("# micro: fused kernel vs unfused XLA "
              "(prologue-apply+relu, matmul, stats)", flush=True)
        for m, k, n in shapes:
            x = jnp.asarray(rs.randn(m, k), jnp.bfloat16)
            w = jnp.asarray(rs.randn(k, n) * 0.05, jnp.bfloat16)
            s = jnp.asarray(rs.rand(k) + 0.5, jnp.float32)
            t = jnp.asarray(rs.randn(k) * 0.1, jnp.float32)
            sh = jnp.asarray(rs.randn(n) * 0.1, jnp.float32)

            def fused(x, w):
                y, sm, sq = matmul_bn(x, w, in_scale=s, in_shift=t,
                                      relu_in=True, stat_shift=sh)
                # touch the stats so nothing is dead-code-eliminated;
                # keep the carry shape (M, K) by projecting back
                y = y + (sm + sq)[None, :].astype(y.dtype) * 0
                return y[:, :x.shape[1]] if n >= x.shape[1] else \
                    jnp.pad(y, ((0, 0), (0, x.shape[1] - n)))

            def unfused(x, w):
                xp = jnp.maximum(
                    x * s[None, :].astype(x.dtype) +
                    t[None, :].astype(x.dtype), 0)
                y = xp @ w
                d = y.astype(jnp.float32) - sh[None, :]
                sm, sq = jnp.sum(d, 0), jnp.sum(d * d, 0)
                y = y + (sm + sq)[None, :].astype(y.dtype) * 0
                return y[:, :x.shape[1]] if n >= x.shape[1] else \
                    jnp.pad(y, ((0, 0), (0, x.shape[1] - n)))

            def grad_of(fn):
                def loss(x, w):
                    return jnp.sum(fn(x, w).astype(jnp.float32))
                g = jax.grad(loss, argnums=0)
                return lambda x, w: g(x, w)

            tf_ = chain_time(fused, x, w)
            tu = chain_time(unfused, x, w)
            gtf = chain_time(grad_of(fused), x, w)
            gtu = chain_time(grad_of(unfused), x, w)
            print(f"M={m:9d} K={k:4d} N={n:4d}  "
                  f"fwd {tu:7.3f}->{tf_:7.3f} ms ({tu / tf_:4.2f}x)  "
                  f"fwd+bwd {gtu:7.3f}->{gtf:7.3f} ms "
                  f"({gtu / gtf:4.2f}x)", flush=True)

    if not args.skip_micro:
        # residual-epilogue A/B (round-6 lever): a deferred block
        # tail (prev bn3 folded apply + residual add + ReLU) riding
        # the consuming c1's matmul_bn prologue — vs the same tail as
        # unfused XLA ops feeding a plain matmul+stats. The c1
        # block-boundary shapes are exactly where the chained
        # deferred stage runs; fwd+bwd also times the dx kernel's
        # in-VMEM ReLU/residual VJP + dr epilogue.
        from analytics_zoo_tpu.ops.conv_bn import matmul_bn as _mm
        res_shapes = [(512, 128, 256), (256, 256, 128)] if args.tiny \
            else [
                (128 * 56 * 56, 256, 64),     # s0 interior c1
                (128 * 28 * 28, 512, 128),    # s1 interior c1
                (128 * 14 * 14, 1024, 256),   # s2 interior c1
                (128 * 7 * 7, 2048, 512),     # s3 interior c1
            ]
        print("# micro: residual-epilogue matmul_bn(in_residual=) "
              "vs unfused XLA tail", flush=True)
        for m, k, n in res_shapes:
            x = jnp.asarray(rs.randn(m, k), jnp.bfloat16)
            w = jnp.asarray(rs.randn(k, n) * 0.05, jnp.bfloat16)
            r = jnp.asarray(rs.randn(m, k), jnp.bfloat16)
            s = jnp.asarray(rs.rand(k) + 0.5, jnp.float32)
            t = jnp.asarray(rs.randn(k) * 0.1, jnp.float32)
            sh = jnp.asarray(rs.randn(n) * 0.1, jnp.float32)

            def fused_r(x, w, r):
                y, sm, sq = _mm(x, w, in_scale=s, in_shift=t,
                                relu_in=True, stat_shift=sh,
                                in_residual=r)
                y = y + (sm + sq)[None, :].astype(y.dtype) * 0
                return y[:, :x.shape[1]] if n >= x.shape[1] else \
                    jnp.pad(y, ((0, 0), (0, x.shape[1] - n)))

            def unfused_r(x, w, r):
                xp = jnp.maximum(
                    x * s[None, :].astype(x.dtype) +
                    t[None, :].astype(x.dtype) + r, 0)
                y = xp @ w
                d = y.astype(jnp.float32) - sh[None, :]
                sm, sq = jnp.sum(d, 0), jnp.sum(d * d, 0)
                y = y + (sm + sq)[None, :].astype(y.dtype) * 0
                return y[:, :x.shape[1]] if n >= x.shape[1] else \
                    jnp.pad(y, ((0, 0), (0, x.shape[1] - n)))

            def grad_r(fn):
                def loss(x, w, r):
                    return jnp.sum(fn(x, w, r).astype(jnp.float32))
                # grad wrt x AND r: the backward must produce the
                # residual cotangent, that's the lever being timed
                g = jax.grad(loss, argnums=(0, 2))
                return lambda x, w, r: g(x, w, r)[0]

            tf_ = chain_time(fused_r, x, w, r)
            tu = chain_time(unfused_r, x, w, r)
            gtf = chain_time(grad_r(fused_r), x, w, r)
            gtu = chain_time(grad_r(unfused_r), x, w, r)
            print(f"M={m:9d} K={k:4d} N={n:4d} +res  "
                  f"fwd {tu:7.3f}->{tf_:7.3f} ms ({tu / tf_:4.2f}x)  "
                  f"fwd+bwd {gtu:7.3f}->{gtf:7.3f} ms "
                  f"({gtu / gtf:4.2f}x)", flush=True)

    if not args.skip_micro:
        # 3×3 kernel A/B (fwd only: the carry-chain trick needs
        # matching in/out channels, so conv shapes time one call per
        # scan step with Cin==Cout): stride 1 and the round-4 stride-2
        # stage-transition shapes at batch 8 tiles
        conv_shapes = [(8, 16, 16, 64, 1), (8, 8, 8, 64, 2)] \
            if args.tiny else [
                (8, 56, 56, 64, 1), (8, 28, 28, 128, 1),
                (8, 28, 28, 128, 2), (8, 14, 14, 256, 2),
                (8, 7, 7, 512, 1)]
        print("# micro: fused conv3x3_bn vs unfused XLA conv+stats",
              flush=True)
        for b, h, wd, c, stride in conv_shapes:
            xc = jnp.asarray(rs.randn(b, h, wd, c), jnp.bfloat16)
            wc = jnp.asarray(rs.randn(3, 3, c, c) * 0.05, jnp.bfloat16)
            sc = jnp.asarray(rs.rand(c) + 0.5, jnp.float32)
            tc = jnp.asarray(rs.randn(c) * 0.1, jnp.float32)
            shc = jnp.asarray(rs.randn(c) * 0.1, jnp.float32)

            def fused_c(x, w):
                y, sm, sq = conv3x3_bn(x, w, in_scale=sc, in_shift=tc,
                                       relu_in=True, stat_shift=shc,
                                       stride=stride)
                y = y + (sm + sq)[None, None, None, :].astype(y.dtype) * 0
                return y if stride == 1 else \
                    jnp.concatenate([y] * 2, 1).repeat(2, 2)[:, :h, :wd]

            def unfused_c(x, w):
                xp = jnp.maximum(
                    x * sc[None, None, None, :].astype(x.dtype) +
                    tc[None, None, None, :].astype(x.dtype), 0)
                y = jax.lax.conv_general_dilated(
                    xp, w, (stride, stride), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                d = y.astype(jnp.float32) - shc[None, None, None, :]
                sm = jnp.sum(d, (0, 1, 2))
                sq = jnp.sum(d * d, (0, 1, 2))
                y = y + (sm + sq)[None, None, None, :].astype(y.dtype) * 0
                return y if stride == 1 else \
                    jnp.concatenate([y] * 2, 1).repeat(2, 2)[:, :h, :wd]

            tf_ = chain_time(fused_c, xc, wc)
            tu = chain_time(unfused_c, xc, wc)
            print(f"conv3x3 B={b} {h}x{wd} C={c} s={stride}  "
                  f"fwd {tu:7.3f}->{tf_:7.3f} ms ({tu / tf_:4.2f}x)",
                  flush=True)

    if not args.skip_micro:
        # stride-2 backward A/B (round-7 lever): jax's transpose rule
        # (lhs-dilated dx conv + rhs-dilated dw conv) vs the
        # phase-decomposed backward (ops.conv_grad: s^2 dense stride-1
        # convs + interleave). Times grad wrt (x, w) of one strided
        # conv at ResNet-50's stage-transition shapes; the chain
        # carries dx (same shape as x).
        from analytics_zoo_tpu.ops import conv_grad
        ph_shapes = [(8, 16, 16, 32, 32, 3), (8, 16, 16, 32, 64, 1)] \
            if args.tiny else [
                (8, 56, 56, 128, 128, 3),     # s1 c2 3x3 s2
                (8, 28, 28, 256, 256, 3),     # s2 c2 3x3 s2
                (8, 14, 14, 512, 512, 3),     # s3 c2 3x3 s2
                (8, 56, 56, 256, 512, 1),     # s1 downsample 1x1 s2
                (8, 28, 28, 512, 1024, 1),    # s2 downsample 1x1 s2
                (8, 14, 14, 1024, 2048, 1),   # s3 downsample 1x1 s2
            ]
        print("# micro: stride-2 backward, transpose-rule (dilated) "
              "vs phase-decomposed", flush=True)
        for b, h, wd, ci, co, kk in ph_shapes:
            xc = jnp.asarray(rs.randn(b, h, wd, ci), jnp.bfloat16)
            wc = jnp.asarray(rs.randn(kk, kk, ci, co) * 0.05,
                             jnp.bfloat16)

            def grad_conv(phase):
                def loss(x, w):
                    y = conv_grad.conv2d(x, w, stride=(2, 2),
                                         padding="SAME",
                                         phase_bwd=phase)
                    return jnp.sum(y.astype(jnp.float32))
                g = jax.grad(loss, argnums=(0, 1))
                def f(x, w):
                    dx, dw = g(x, w)
                    # fold dw into the carry so neither grad is DCE'd
                    return dx + jnp.sum(dw.astype(jnp.float32)
                                        ).astype(dx.dtype) * 0
                return f

            td = chain_time(grad_conv(False), xc, wc)
            tp = chain_time(grad_conv(True), xc, wc)
            print(f"conv{kk}x{kk} B={b} {h}x{wd} {ci}->{co} s=2  "
                  f"fwd+bwd {td:7.3f}->{tp:7.3f} ms "
                  f"({td / tp:4.2f}x)", flush=True)

    if args.autotune_ab:
        # tuned-vs-heuristic block-config A/B (ISSUE 18 acceptance
        # gate): at every swept shape the tuned pick must not be
        # slower than the analytic heuristic beyond noise, and a
        # second pass over the same keys must perform ZERO sweeps
        # (pure cache hits — the persistence contract).
        from analytics_zoo_tpu.ops.conv_bn import matmul_bn as _mmab
        from analytics_zoo_tpu.perf import autotune
        ab_shapes = [(512, 128, 256), (256, 256, 128)] if args.tiny \
            else _RESNET_SHAPES
        rs = np.random.RandomState(0)
        enabled = autotune.sweep_enabled() >= 1
        print(f"# autotune A/B: tuned vs heuristic conv_bn blocks "
              f"(sweep {'on' if enabled else 'OFF -- set '}"
              f"{'' if enabled else 'ZOO_TPU_AUTOTUNE=1'})",
              flush=True)
        failures = []

        def time_blocks(cfg, x, w):
            def fn(x, w):
                y, sm, sq = _mmab(x, w)
                y = y + (sm + sq)[None, :].astype(y.dtype) * 0
                n_ = y.shape[1]
                return y[:, :x.shape[1]] if n_ >= x.shape[1] else \
                    jnp.pad(y, ((0, 0), (0, x.shape[1] - n_)))
            with autotune.forced("conv_bn_blocks", cfg):
                return chain_time(fn, x, w)

        for m, k, n in ab_shapes:
            params = {"m": m, "k": k, "n": n, "isz": 2}
            tuned = autotune.decide("conv_bn_blocks", params)
            heur = autotune.heuristic("conv_bn_blocks", params)
            x = jnp.asarray(rs.randn(m, k), jnp.bfloat16)
            w = jnp.asarray(rs.randn(k, n) * 0.05, jnp.bfloat16)
            t_tuned = time_blocks(tuned, x, w)
            t_heur = time_blocks(heur, x, w)
            verdict = "ok"
            # generous runtime margin: the sweep already enforced the
            # 2% NOISE_MARGIN at selection time, this re-measures on
            # a possibly noisy box
            if t_tuned > t_heur * 1.25 + 0.05:
                verdict = "TUNED SLOWER"
                failures.append((m, k, n, t_tuned, t_heur))
            print(f"M={m:9d} K={k:4d} N={n:4d}  tuned={tuned} "
                  f"{t_tuned:7.3f} ms  heur={heur} {t_heur:7.3f} ms "
                  f"({t_heur / t_tuned:4.2f}x) {verdict}", flush=True)
        before = autotune.stats()
        for m, k, n in ab_shapes:      # second pass: must be warm
            autotune.decide("conv_bn_blocks",
                            {"m": m, "k": k, "n": n, "isz": 2})
        after = autotune.stats()
        new_sweeps = after["sweeps"] - before["sweeps"]
        new_misses = after["cache_misses"] - before["cache_misses"]
        print(f"# second pass: sweeps={new_sweeps} "
              f"misses={new_misses} (want 0/0 with sweep on)",
              flush=True)
        if enabled and (new_sweeps or new_misses):
            print("FAIL: second pass swept or missed", flush=True)
            return 1
        if failures:
            print(f"FAIL: tuned slower than heuristic at "
                  f"{len(failures)} shape(s)", flush=True)
            return 1

    return 0


def _model_ab(args) -> int:
    """ResNet-50 fused=0 vs fused=1, one bench.py child each."""
    import json
    import subprocess
    print("# model A/B: ZOO_TPU_BENCH_FUSED 0 vs 1:", flush=True)
    values = {}
    for fused in ("0", "1"):
        env = dict(os.environ, ZOO_TPU_BENCH_FUSED=fused,
                   ZOO_TPU_BENCH_STEPS=str(args.steps),
                   ZOO_TPU_BENCH_BATCH=str(args.batch),
                   ZOO_TPU_BENCH_NCF="0",  # A/B needs no NCF leg
                   ZOO_TPU_BENCH_BERT="0")
        if args.tiny:
            env.update(ZOO_TPU_BENCH_BATCH="4",
                       ZOO_TPU_BENCH_IMAGE="64",
                       ZOO_TPU_BENCH_STEPS="2",
                       ZOO_TPU_BENCH_PLATFORM=os.environ.get(
                           "ZOO_TPU_BENCH_PLATFORM", "cpu"))
        out = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "bench.py")],
            capture_output=True, text=True, env=env, timeout=1800)
        if out.returncode != 0:
            sys.stderr.write(out.stderr[-4000:])
            print(f"FAIL: bench.py fused={fused} exited "
                  f"{out.returncode}", flush=True)
            return 1
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("{")][-1]
        diag = next((ln for ln in out.stderr.splitlines()
                     if "step_time" in ln), "")
        print(f"fused={fused}: {line}\n  {diag}", flush=True)
        values[fused] = float(json.loads(line)["value"])
    # a >=3% margin so a within-run-variance difference cannot flip
    # the global 'auto' default; near-ties say so explicitly
    if values["1"] > values["0"] * 1.03:
        print(f"# FUSED WINS ({values['1']:.1f} vs "
              f"{values['0']:.1f} img/s) — flip "
              "ops/conv_bn.py MEASURED_WIN to True so the 'auto' "
              "default routes fused on TPU", flush=True)
    elif values["1"] > values["0"] * 0.97:
        print(f"# NEAR TIE ({values['1']:.1f} vs "
              f"{values['0']:.1f} img/s, within the 3% noise "
              "margin) — re-run before flipping MEASURED_WIN",
              flush=True)
    else:
        print("# fused does not beat unfused at this config — "
              "keep MEASURED_WIN=False", flush=True)
    return 0


def main(argv=None) -> int:
    args = _parse(argv)
    if args.role == "micro":
        return _micro(args)
    # the parent holds no device: every child gets the chip to itself
    if "jax" in sys.modules:
        raise RuntimeError("measure_fused.py's parent imported jax; "
                           "its children could not get the chip")
    if not args.skip_micro or args.autotune_ab:
        import subprocess
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--role",
             "micro"] + list(sys.argv[1:] if argv is None else argv)
        ).returncode
        if rc != 0:
            return rc
    if not args.skip_model:
        return _model_ab(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
