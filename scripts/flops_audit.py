"""Executed-FLOPs audit: where do the MXU cycles actually go?

Two modes:

  1. Model mode (default, CPU-safe — `make flops-audit`): lowers the
     ResNet-50 train step (bench.py's `_resnet_train_chain`) and
     reports per-category executed FLOPs (perf.flops counting:
     dilation zeros are EXECUTED, unlike HloCostAnalysis which
     discounts them), the executed-vs-model-FLOPs ratio, and the
     top-N costliest ops.

  2. Dump mode (`--dump-dir DIR`): audits the *after_optimizations*
     HLO modules of an `--xla_dump_to` dump, so the numbers reflect
     what the backend compiler actually emitted (fusion choices,
     layout padding), not the pre-optimization graph. Includes a
     channel-padding audit: conv feature extents not aligned to the
     128-wide TPU lane (the MXU zero-pads them).

The model denominator is torchvision's 4.09e9/img, which counts
MACs; executed FLOPs count 2 FLOPs/MAC — the 2x below matches the
conventions (PERF.md round 7).

Usage:
  python scripts/flops_audit.py [--image 224] [--batch 1] [--top 10]
  python scripts/flops_audit.py --dump-dir /tmp/xla_dump [--top 10]
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from analytics_zoo_tpu.perf import flops as pf  # noqa: E402


def _category(op) -> str:
    if op.kind == "dot":
        return "dot"
    if "lhs_dilate" in op.detail:
        return "conv lhs_dilated (dx of strided)"
    if "rhs_dilate" in op.detail:
        return "conv rhs_dilated (dw of strided)"
    return "conv plain"


def report(text: str, label: str, top: int,
           model_flops: float | None) -> float:
    ops = pf.parse_hlo_ops(text)
    total = sum(o.flops for o in ops)
    print(f"\n== {label}: executed {total:.4e} FLOPs "
          f"({len(ops)} MXU ops)")
    if model_flops:
        print(f"   model {model_flops:.4e} -> "
              f"ratio_executed_vs_model {total / model_flops:.3f}")
    cats = {}
    for o in ops:
        k = _category(o)
        n, f = cats.get(k, (0, 0.0))
        cats[k] = (n + 1, f + o.flops)
    for k, (n, f) in sorted(cats.items(), key=lambda kv: -kv[1][1]):
        print(f"   {k:36s} n={n:3d} flops={f:.4e} "
              f"({100 * f / total:5.1f}%)")
    print(f"   top {top} ops:")
    for o in sorted(ops, key=lambda o: -o.flops)[:top]:
        print(f"     {o.flops:.3e}  {o.name:28s} {o.detail[:70]}")
    pads = pf.channel_padding(text)
    if pads:
        print("   channel padding (feature extent % 128 != 0):")
        seen = set()
        for p in pads:
            key = (p.role, p.extent)
            if key in seen:
                continue
            seen.add(key)
            n = sum(1 for q in pads if (q.role, q.extent) == key)
            print(f"     {p.role:6s} extent={p.extent:5d} "
                  f"lane_util={p.util:.3f} x{n} "
                  f"(e.g. {p.name})")
    else:
        print("   channel padding: all conv feature extents "
              "128-aligned")
    return total


def audit_dump(dump_dir: str, top: int) -> None:
    pats = ["*after_optimizations*.txt", "*.before_optimizations.txt",
            "module_*.txt"]
    files = []
    for pat in pats:
        files = sorted(glob.glob(os.path.join(dump_dir, pat)))
        if files:
            break
    if not files:
        sys.exit(f"no HLO .txt modules under {dump_dir} "
                 "(run with XLA_FLAGS=--xla_dump_to=DIR)")
    for path in files:
        with open(path) as f:
            text = f.read()
        if "HloModule" not in text:
            continue
        report(text, os.path.basename(path), top, None)


def audit_model(image: int, batch: int, top: int):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    jax.config.update("jax_platforms",
                      os.environ["JAX_PLATFORMS"])

    from analytics_zoo_tpu import init_nncontext
    from analytics_zoo_tpu.models.image.imageclassification import (
        resnet50)
    from analytics_zoo_tpu.ops import losses, optimizers
    from bench import _resnet_train_chain

    init_nncontext(tpu_mesh={"data": 1}, devices=jax.devices()[:1],
                   log_level="WARNING")
    tx = optimizers.SGD(lr=0.1, momentum=0.9).to_optax()
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, image, image, 3), jnp.bfloat16)
    y = jnp.asarray(rs.randint(0, 1000, size=(batch, 1)), jnp.int32)
    model_flops = 2.0 * 3 * 4.09e9 * batch * (image / 224.0) ** 2

    model = resnet50(input_shape=(image, image, 3), classes=1000,
                     space_to_depth=False)
    params = model.init_params(jax.random.PRNGKey(0), device="host")
    step, _ = _resnet_train_chain(
        model, tx, losses.softmax_cross_entropy, 1)
    text = pf.hlo_text(
        jax.jit(step).lower(params, tx.init(params), x, y))
    report(text, f"ResNet-50 train step image={image} batch={batch}",
           top, model_flops)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--top", type=int, default=10)
    p.add_argument("--dump-dir", default=None,
                   help="audit an --xla_dump_to directory instead "
                        "of lowering the model")
    args = p.parse_args()
    if args.dump_dir:
        audit_dump(args.dump_dir, args.top)
    else:
        audit_model(args.image, args.batch, args.top)


if __name__ == "__main__":
    main()
