"""Fused 1×1-conv (matmul) + BatchNorm Pallas kernel.

The ResNet-50 training step is HBM-bound on BatchNorm traffic, not
MXU-bound (PERF.md profile: BN statistics reductions ≈33% and BN
apply/FMA fusions ≈24% of device time vs ≈25% for the convs). The
reference hits the same wall differently — its MKL-DNN engine fuses
conv+BN+ReLU into one primitive (`zoo/.../IRconvertor` lowers
conv_bn chains to fused MKL ops); this module is the TPU analog for
the 1×1 convs that dominate a bottleneck block, where a 1×1 NHWC conv
IS a matmul over (N·H·W, Cin):

- **prologue**: the PREVIOUS BN's folded apply (``x·scale+shift``)
  and ReLU run on the input tile in VMEM while it feeds the MXU — the
  normalized activation never exists in HBM;
- **matmul**: (M, K) @ (K, N) in bf16 on the MXU, f32 accumulator;
- **epilogue**: per-channel ``Σy`` and ``Σy²`` (f32, shifted by the
  moving mean for cancellation safety — same scheme as
  `keras.layers.BatchNormalization`) accumulate while the output tile
  is written — THIS layer's BN statistics cost no extra HBM pass.

Per conv+BN+ReLU the activation traffic drops from
write + stats-read + apply-read + apply-write (4 passes) to a single
write, and the input-side apply pass of the previous layer disappears.

The backward is a `jax.custom_vjp` expressed in JAX: the statistics
cotangents fold into ONE augmented cotangent
``g = dy + dΣ + 2(y−shift)·dΣ²`` feeding both backward matmuls, and
the prologue's VJP (ReLU mask × scale, plus the reductions giving
d(scale)/d(shift)) fuses into the dx pass — fewer reduction passes
than autodiff of the unfused graph.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.common.device import on_tpu
from analytics_zoo_tpu.ops import conv_grad
from analytics_zoo_tpu.perf import autotune

# test observability, like ops.flash_attention.invocations
invocations = 0

# Measured-win gate for the fused-ResNet "auto" default: flip to True
# once a chip benchmark shows the fused bottlenecks beating the XLA
# graph (ROADMAP S2). Until then "auto" resolves unfused and the
# kernels stay opt-in (ZOO_TPU_FUSED_RESNET=1): conformance-clean,
# compiled and run on the v5e by chip_smoke.py, never timed there.
MEASURED_WIN = False


def fused_profitable() -> bool:
    """Whether the "auto" fused-ResNet default may route to the Pallas
    conv+BN bottlenecks: a real TPU backend AND a measured on-chip win
    (``MEASURED_WIN``). ``ZOO_TPU_FUSED_WIN=0/1`` overrides both (1:
    CPU kernel-coverage tests and measurement runs; 0: kill switch)."""
    env = os.environ.get("ZOO_TPU_FUSED_WIN")
    if env is not None:
        return env == "1"
    return MEASURED_WIN and on_tpu()


def _heuristic_blocks(m: int, k: int, n: int, itemsize: int = 2
                      ) -> Tuple[int, int]:
    """Analytic (block_m, block_k); N is never tiled (ResNet channel
    counts are ≤2048 and 128-multiples, so the whole (bm, N) f32
    accumulator and the (bk, N) weight tile fit VMEM comfortably)."""
    # any admitted k is a 64-multiple, so 64 terminates the search
    bk = next(b for b in (512, 384, 256, 128, 64) if k % b == 0) \
        if k > 512 else k
    # VMEM budget ~ acc(bm·n·4) + x(bm·bk·isz) + w(bk·n·isz): keep
    # ≲6MB (leaves headroom for Pallas double-buffering in 16MB VMEM)
    bm = 512
    while bm > 128 and \
            bm * n * 4 + (bm * bk + bk * n) * itemsize > 6 * 2 ** 20:
        bm //= 2
    return max(bm, 128), bk


def _pick_blocks(m: int, k: int, n: int, itemsize: int = 2
                 ) -> Tuple[int, int]:
    """(block_m, block_k) for one fused matmul, via the autotuner
    ("conv_bn_blocks" op; itemsize keys the sweep so residual-doubled
    budgets tune separately). Falls back to
    :func:`_heuristic_blocks` when nothing is swept or cached."""
    cfg = autotune.decide(
        "conv_bn_blocks",
        {"m": m, "k": k, "n": n, "isz": itemsize})
    return cfg["bm"], cfg["bk"]


def _prologue_accumulate(x_ref, w_ref, s_ref, t_ref, acc_ref, ki,
                         relu_in, affine_in, r_ref=None):
    """The compute path SHARED by the stats (`_kernel`) and apply
    (`_apply_kernel`) epilogues: zero the accumulator at ki==0, apply
    the input affine (+ optional residual tile) + ReLU prologue in
    VMEM, accumulate one (bm, bk)@(bk, N) MXU tap in f32. The
    residual adds AFTER the affine, BEFORE the ReLU — the form of a
    deferred bottleneck output ``relu(y3·scale3+shift3 + shortcut)``
    consumed by the NEXT block's 1×1 (the round-5 deferred-apply
    lever)."""
    @pl.when(ki == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if affine_in:
        x = x.astype(jnp.float32) * s_ref[0, :][None, :] + \
            t_ref[0, :][None, :]
    if r_ref is not None:
        x = x.astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    if relu_in:
        x = jnp.maximum(x, 0.0)
    x = x.astype(w_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _kernel(x_ref, w_ref, s_ref, t_ref, sh_ref, *rest,
            n_k: int, relu_in: bool, affine_in: bool, has_res: bool,
            out_dtype):
    """One (mi, ki) grid step. Refs:
    x (bm, bk) input tile; w (bk, N); s/t (1, bk) prologue
    scale/shift; sh (1, N) stats shift; ``rest`` is Pallas's
    input→output→scratch tail ``([r (bm, bk),] y (bm, N), sum/sq
    (1, N) f32 accumulated across mi, acc (bm, N) f32 scratch)``.
    Grid order (mi, ki): ki innermost."""
    if has_res:
        r_ref, y_ref, sum_ref, sq_ref, acc_ref = rest
    else:
        r_ref = None
        y_ref, sum_ref, sq_ref, acc_ref = rest
    mi = pl.program_id(0)
    ki = pl.program_id(1)
    _prologue_accumulate(x_ref, w_ref, s_ref, t_ref, acc_ref, ki,
                         relu_in, affine_in, r_ref=r_ref)

    @pl.when(ki == n_k - 1)
    def _finalize():
        acc = acc_ref[...]
        y_ref[...] = acc.astype(out_dtype)
        d = acc - sh_ref[0, :][None, :]      # shifted for stability

        @pl.when(mi == 0)
        def _first():
            sum_ref[...] = jnp.sum(d, axis=0, keepdims=True)
            sq_ref[...] = jnp.sum(d * d, axis=0, keepdims=True)

        @pl.when(mi != 0)
        def _rest():
            sum_ref[...] += jnp.sum(d, axis=0, keepdims=True)
            sq_ref[...] += jnp.sum(d * d, axis=0, keepdims=True)


def _matmul_bn_fwd_pallas(x, w, s, t, sh, r, relu_in, affine_in,
                          interpret):
    m, k = x.shape
    n = w.shape[1]
    has_res = r is not None
    isz = max(jnp.dtype(x.dtype).itemsize,
              jnp.dtype(w.dtype).itemsize)
    # the residual adds a second (bm, bk) double-buffered input tile:
    # doubling the x-tile itemsize keeps the budget formula honest
    bm, bk = _pick_blocks(m, k, n, isz * 2 if has_res else isz)
    if m % bm:                       # pad rows to a block multiple
        pad = bm - m % bm
        x = jnp.pad(x, ((0, pad), (0, 0)))
        if has_res:
            r = jnp.pad(r, ((0, pad), (0, 0)))
        mp = m + pad
    else:
        mp = m
    n_m, n_k = mp // bm, k // bk
    kernel = functools.partial(
        _kernel, n_k=n_k, relu_in=relu_in, affine_in=affine_in,
        has_res=has_res, out_dtype=jnp.dtype(x.dtype))
    in_specs = [
        pl.BlockSpec((bm, bk), lambda mi, ki: (mi, ki)),
        pl.BlockSpec((bk, n), lambda mi, ki: (ki, 0)),
        pl.BlockSpec((1, bk), lambda mi, ki: (0, ki)),
        pl.BlockSpec((1, bk), lambda mi, ki: (0, ki)),
        pl.BlockSpec((1, n), lambda mi, ki: (0, 0)),
    ]
    operands = [x, w, s, t, sh]
    if has_res:
        in_specs.append(pl.BlockSpec((bm, bk), lambda mi, ki: (mi, ki)))
        operands.append(r)
    y, ssum, ssq = pl.pallas_call(
        kernel,
        name="zoo_matmul_bn_fwd",
        grid=(n_m, n_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, n), lambda mi, ki: (mi, 0)),
            pl.BlockSpec((1, n), lambda mi, ki: (0, 0)),
            pl.BlockSpec((1, n), lambda mi, ki: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)
    if mp != m:
        # padded (all-zero) input rows still produce a nonzero output
        # row when the prologue has a shift/ReLU: y0 = prologue(0) @ w
        # (the residual pads with ZEROS, so row0 is unchanged by it).
        # Subtract their exact statistics contribution.
        extra = jnp.float32(mp - m)
        if affine_in:
            row0 = t[0, :]
            if relu_in:
                row0 = jnp.maximum(row0, 0.0)
            # match the kernel's compute path exactly: the prologue
            # output is cast to the weight dtype before the MXU dot
            y0 = jax.lax.dot_general(
                row0.astype(w.dtype)[None, :], w,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)[0]
        else:
            y0 = jnp.zeros((n,), jnp.float32)
        d0 = y0 - sh[0, :]
        ssum = ssum - extra * d0[None, :]
        ssq = ssq - extra * (d0 ** 2)[None, :]
        y = y[:m]
    return y, ssum[0], ssq[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _matmul_bn(x, w, s, t, sh, r, relu_in, affine_in, interpret):
    return _matmul_bn_fwd_pallas(x, w, s, t, sh, r, relu_in,
                                 affine_in, interpret)


def _matmul_bn_vjp_fwd(x, w, s, t, sh, r, relu_in, affine_in,
                       interpret):
    out = _matmul_bn_fwd_pallas(x, w, s, t, sh, r, relu_in, affine_in,
                                interpret)
    y, _, _ = out
    return out, (x, w, s, t, sh, r, y)


def _pallas_bwd_wins(m: int, k: int, n: int) -> bool:
    """Whether the fused Pallas backward beats the XLA reference at
    this matmul shape — the autotuned form of the old
    ``ZOO_TPU_CONV_BN_PALLAS_BWD`` constant toggle. The flag, when
    set, is honored verbatim (source="flag"); unset, the tuner's
    cache/defaults decide, heuristic Pallas-on (the pre-tuner
    default)."""
    return bool(autotune.decide("conv_bn_bwd",
                                {"m": m, "k": k, "n": n})["pallas"])


def _matmul_bn_vjp_bwd(relu_in, affine_in, interpret, res, cots):
    x, w, s, t, sh, r, y = res
    dy, dsum, dsq = cots
    # with a residual the Pallas dx kernel recomputes the ReLU/
    # residual VJP in VMEM and emits the residual cotangent through
    # the same epilogue (dr = masked g@Wᵀ) — the augmented cotangent
    # never exists in HBM on either path
    if _pallas_bwd_wins(x.shape[0], x.shape[1], w.shape[1]):
        out = _bwd_pallas(x, w, s, t, sh, y, dy, dsum, dsq,
                          relu_in, affine_in, interpret, r=r)
    else:
        out = _bwd_jax(x, w, s, t, sh, y, dy, dsum, dsq,
                       relu_in, affine_in, r=r)
    # custom_vjp wants a 6-tuple; no residual input → cotangent None
    return out if r is not None else out + (None,)


def _bwd_jax(x, w, s, t, sh, y, dy, dsum, dsq, relu_in, affine_in,
             r=None):
    """XLA-expressed backward (the `ZOO_TPU_CONV_BN_PALLAS_BWD=0`
    reference path, and the ground truth the Pallas backward is
    conformance-tested against)."""
    f32 = jnp.float32
    # stats cotangents fold into one augmented output cotangent:
    # y feeds (y, Σ(y-sh), Σ(y-sh)²) so g = dy + dΣ + 2(y-sh)·dΣ²
    g = dy.astype(f32) + dsum[None, :] + \
        2.0 * (y.astype(f32) - sh[0, :][None, :]) * dsq[None, :]
    # recompute the prologue (cheaper than saving x' — one read of x
    # instead of a second M×K tensor in HBM)
    if affine_in:
        xa = x.astype(f32) * s[0, :][None, :] + t[0, :][None, :]
    else:
        xa = x.astype(f32)
    if r is not None:
        xa = xa + r.astype(f32)
    xp = jnp.maximum(xa, 0.0) if relu_in else xa
    # backward matmuls run in the forward's compute dtype (bf16 on the
    # MXU) with f32 accumulation — mixed-precision standard; only the
    # elementwise algebra stays f32
    cd = x.dtype
    gc = g.astype(cd)
    dw = jax.lax.dot_general(xp.astype(cd), gc,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=f32)
    dxp = jax.lax.dot_general(gc, w.astype(cd),
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=f32)
    if relu_in:
        dxp = jnp.where(xa > 0.0, dxp, 0.0)
    if affine_in:
        dx = dxp * s[0, :][None, :]
        ds = jnp.sum(dxp * x.astype(f32), axis=0, keepdims=True)
        dt = jnp.sum(dxp, axis=0, keepdims=True)
    else:
        dx = dxp
        ds = jnp.zeros_like(s)
        dt = jnp.zeros_like(t)
    base = (dx.astype(x.dtype), dw.astype(w.dtype),
            ds.astype(s.dtype), dt.astype(t.dtype),
            jnp.zeros_like(sh))
    # 5-tuple without r (matching _bwd_pallas and its fallbacks into
    # this function); 6-tuple with the residual cotangent otherwise
    return base if r is None else base + (dxp.astype(r.dtype),)


def _g_tile(dy, y, sh_row, dsum_row, dsq_row):
    """The augmented cotangent on one tile, in f32 (single copy of the
    formula shared by both backward kernels)."""
    return (dy.astype(jnp.float32) + dsum_row +
            2.0 * (y.astype(jnp.float32) - sh_row) * dsq_row)


def _dx_kernel(dy_ref, y_ref, x_ref, w_ref, s_ref, t_ref, sh_ref,
               dsum_ref, dsq_ref, *rest,
               relu_in: bool, affine_in: bool, has_res: bool,
               out_dtype, res_dtype=None):
    """Grid (mi,): dx tile = prologue'(x) ⊙ (g @ Wᵀ); ds/dt accumulate
    across mi. g is recomputed from dy/y in VMEM — it never exists in
    HBM (the XLA path materialises it as both matmuls' operand). With
    ``has_res`` the prologue recomputation includes the residual tile
    (xa = x·s+t+r) and the residual cotangent dr = masked g@Wᵀ leaves
    through an extra output in the same epilogue — the deferred
    block's elementwise-tail VJP never touches HBM either."""
    if has_res:
        r_ref, dx_ref, ds_ref, dt_ref, dr_ref = rest
    else:
        r_ref = dr_ref = None
        dx_ref, ds_ref, dt_ref = rest
    mi = pl.program_id(0)
    g = _g_tile(dy_ref[...], y_ref[...], sh_ref[0, :][None, :],
                dsum_ref[0, :][None, :], dsq_ref[0, :][None, :])
    dxp = jax.lax.dot_general(
        g.astype(w_ref.dtype), w_ref[...],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    xf = x_ref[...].astype(jnp.float32)
    if affine_in:
        xa = xf * s_ref[0, :][None, :] + t_ref[0, :][None, :]
    else:
        xa = xf
    if has_res:
        xa = xa + r_ref[...].astype(jnp.float32)
    if relu_in:
        dxp = jnp.where(xa > 0.0, dxp, 0.0)
    if has_res:
        dr_ref[...] = dxp.astype(res_dtype)
    if affine_in:
        dx_ref[...] = (dxp * s_ref[0, :][None, :]).astype(out_dtype)
        ds_new = jnp.sum(dxp * xf, axis=0, keepdims=True)
        dt_new = jnp.sum(dxp, axis=0, keepdims=True)
    else:
        dx_ref[...] = dxp.astype(out_dtype)
        ds_new = jnp.zeros_like(ds_ref)
        dt_new = jnp.zeros_like(dt_ref)

    @pl.when(mi == 0)
    def _first():
        ds_ref[...] = ds_new
        dt_ref[...] = dt_new

    @pl.when(mi != 0)
    def _rest():
        ds_ref[...] += ds_new
        dt_ref[...] += dt_new


def _dw_kernel(dy_ref, y_ref, x_ref, s_ref, t_ref, sh_ref,
               dsum_ref, dsq_ref, *rest,
               n_m: int, relu_in: bool, affine_in: bool,
               has_res: bool):
    """Grid (ni, mi): dW[:, ni] += prologue(x)ᵀ @ g, accumulated over
    mi in a VMEM scratch, written at the last mi. ``has_res``: the
    prologue recomputation includes the residual tile, like
    `_dx_kernel`."""
    if has_res:
        r_ref, dw_ref, acc_ref = rest
    else:
        r_ref = None
        dw_ref, acc_ref = rest
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = _g_tile(dy_ref[...], y_ref[...], sh_ref[0, :][None, :],
                dsum_ref[0, :][None, :], dsq_ref[0, :][None, :])
    xf = x_ref[...].astype(jnp.float32)
    if affine_in:
        xf = xf * s_ref[0, :][None, :] + t_ref[0, :][None, :]
    if has_res:
        xf = xf + r_ref[...].astype(jnp.float32)
    if relu_in:
        xf = jnp.maximum(xf, 0.0)
    cd = x_ref.dtype
    acc_ref[...] += jax.lax.dot_general(
        xf.astype(cd), g.astype(cd), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(mi == n_m - 1)
    def _write():
        dw_ref[...] = acc_ref[...]


def _bwd_pallas(x, w, s, t, sh, y, dy, dsum, dsq, relu_in, affine_in,
                interpret, r=None):
    m, k = x.shape
    n = w.shape[1]
    f32 = jnp.float32
    has_res = r is not None
    x_isz = jnp.dtype(x.dtype).itemsize
    w_isz = jnp.dtype(w.dtype).itemsize
    r_isz = jnp.dtype(r.dtype).itemsize if has_res else 0
    if k * n * w_isz >= 8 * 2 ** 20:
        # the dx kernel keeps the whole (K, N) weight resident; beyond
        # ~8MB that cannot fit VMEM with the row tiles — use the XLA
        # backward (ResNet's largest is 1024x2048 bf16 = 4MB)
        return _bwd_jax(x, w, s, t, sh, y, dy, dsum, dsq,
                        relu_in, affine_in, r=r)
    # dW scratch + output block are (K, bn_w) f32: bound K·bn_w, not
    # K·N; no qualifying column tile (extreme K) → XLA backward
    bn_w = next((b for b in (2048, 1024, 512, 256, 128, 64)
                 if n % b == 0 and k * b * 4 <= 4 * 2 ** 20), None)
    if bn_w is None:
        return _bwd_jax(x, w, s, t, sh, y, dy, dsum, dsq,
                        relu_in, affine_in, r=r)
    dsum2 = dsum.astype(f32).reshape(1, n)
    dsq2 = dsq.astype(f32).reshape(1, n)
    # block rows: bound VMEM by the fattest resident set, INCLUDING
    # the (K, N) weight tile the dx kernel holds (a residual adds an
    # r input tile and a dr output tile, both (bm, K))
    def _resident(bm):
        return bm * 2 * n * x_isz + bm * k * x_isz + \
            bm * k * 4 + k * n * w_isz + bm * k * 2 * r_isz
    bm = 512
    while bm > 128 and _resident(bm) > 8 * 2 ** 20:
        bm //= 2
    if _resident(bm) > 8 * 2 ** 20:
        # even the smallest row tile busts VMEM (f32 at large K·N):
        # fall back rather than fail Mosaic allocation on chip
        return _bwd_jax(x, w, s, t, sh, y, dy, dsum, dsq,
                        relu_in, affine_in, r=r)
    if m % bm:
        pad = bm - m % bm
        # zero-padded rows: g_pad = dsum (nonzero!) but relu'/affine
        # masks make dx rows garbage we slice off; for ds/dt the
        # padded rows contribute dxp_pad·0 (xf=0) to ds and dxp_pad to
        # dt — correct dt exactly below. dW pads xp rows as
        # prologue(0) like the forward — corrected below too. The
        # residual pads with ZEROS, so xa_pad stays prologue(0) and
        # every correction below is unchanged; dr pad rows slice off.
        x_p = jnp.pad(x, ((0, pad), (0, 0)))
        dy_p = jnp.pad(dy, ((0, pad), (0, 0)))
        y_p = jnp.pad(y, ((0, pad), (0, 0)))
        r_p = jnp.pad(r, ((0, pad), (0, 0))) if has_res else None
    else:
        pad = 0
        x_p, dy_p, y_p, r_p = x, dy, y, r
    mp = m + pad
    n_m = mp // bm

    dx_specs = [
        pl.BlockSpec((bm, n), lambda mi: (mi, 0)),    # dy
        pl.BlockSpec((bm, n), lambda mi: (mi, 0)),    # y
        pl.BlockSpec((bm, k), lambda mi: (mi, 0)),    # x
        pl.BlockSpec((k, n), lambda mi: (0, 0)),      # w
        pl.BlockSpec((1, k), lambda mi: (0, 0)),      # s
        pl.BlockSpec((1, k), lambda mi: (0, 0)),      # t
        pl.BlockSpec((1, n), lambda mi: (0, 0)),      # sh
        pl.BlockSpec((1, n), lambda mi: (0, 0)),      # dsum
        pl.BlockSpec((1, n), lambda mi: (0, 0)),      # dsq
    ]
    dx_ops = [dy_p, y_p, x_p, w, s, t, sh, dsum2, dsq2]
    dx_out_specs = [
        pl.BlockSpec((bm, k), lambda mi: (mi, 0)),
        pl.BlockSpec((1, k), lambda mi: (0, 0)),
        pl.BlockSpec((1, k), lambda mi: (0, 0)),
    ]
    dx_out_shape = [
        jax.ShapeDtypeStruct((mp, k), x.dtype),
        jax.ShapeDtypeStruct((1, k), f32),
        jax.ShapeDtypeStruct((1, k), f32),
    ]
    if has_res:
        dx_specs.append(pl.BlockSpec((bm, k), lambda mi: (mi, 0)))
        dx_ops.append(r_p)
        # dr leaves through the same epilogue as dx
        dx_out_specs.append(pl.BlockSpec((bm, k), lambda mi: (mi, 0)))
        dx_out_shape.append(jax.ShapeDtypeStruct((mp, k), r.dtype))
    outs = pl.pallas_call(
        functools.partial(_dx_kernel, relu_in=relu_in,
                          affine_in=affine_in, has_res=has_res,
                          out_dtype=jnp.dtype(x.dtype),
                          res_dtype=jnp.dtype(r.dtype) if has_res
                          else None),
        name="zoo_matmul_bn_bwd_dx",
        grid=(n_m,),
        in_specs=dx_specs,
        out_specs=dx_out_specs,
        out_shape=dx_out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*dx_ops)
    if has_res:
        dx, ds, dt, dr = outs
    else:
        (dx, ds, dt), dr = outs, None

    dw_specs = [
        pl.BlockSpec((bm, bn_w), lambda ni, mi: (mi, ni)),  # dy
        pl.BlockSpec((bm, bn_w), lambda ni, mi: (mi, ni)),  # y
        pl.BlockSpec((bm, k), lambda ni, mi: (mi, 0)),      # x
        pl.BlockSpec((1, k), lambda ni, mi: (0, 0)),        # s
        pl.BlockSpec((1, k), lambda ni, mi: (0, 0)),        # t
        pl.BlockSpec((1, bn_w), lambda ni, mi: (0, ni)),    # sh
        pl.BlockSpec((1, bn_w), lambda ni, mi: (0, ni)),    # dsum
        pl.BlockSpec((1, bn_w), lambda ni, mi: (0, ni)),    # dsq
    ]
    dw_ops = [dy_p, y_p, x_p, s, t, sh, dsum2, dsq2]
    if has_res:
        dw_specs.append(pl.BlockSpec((bm, k),
                                     lambda ni, mi: (mi, 0)))
        dw_ops.append(r_p)
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, n_m=n_m, relu_in=relu_in,
                          affine_in=affine_in, has_res=has_res),
        name="zoo_matmul_bn_bwd_dw",
        grid=(n // bn_w, n_m),
        in_specs=dw_specs,
        out_specs=pl.BlockSpec((k, bn_w), lambda ni, mi: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((k, n), f32),
        scratch_shapes=[pltpu.VMEM((k, bn_w), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*dw_ops)

    if pad:
        dx = dx[:m]
        if has_res:
            dr = dr[:m]
        if affine_in:
            # padded-row corrections (exact; dy=y=x=0 on those rows):
            # g_pad = dsum − 2·sh·dsq, xp_pad = prologue(0) = relu(t)
            cd = x.dtype
            g_pad = dsum2[0] - 2.0 * sh[0, :] * dsq2[0]     # (N,)
            row0 = jnp.maximum(t[0, :], 0.0) if relu_in else t[0, :]
            # dW accumulated pad·(xp_pad ⊗ g_pad) — subtract it
            dw = dw - jnp.float32(pad) * jax.lax.dot_general(
                row0.astype(cd)[:, None], g_pad.astype(cd)[None, :],
                (((1,), (0,)), ((), ())), preferred_element_type=f32)
            # dt accumulated pad·dxp_pad where dxp_pad is the masked
            # backward of one padded row (ds got dxp_pad·x = 0: exact)
            dxp_pad = jax.lax.dot_general(
                g_pad.astype(cd)[None, :], w.astype(cd),
                (((1,), (1,)), ((), ())),
                preferred_element_type=f32)[0]
            if relu_in:
                dxp_pad = jnp.where(t[0, :] > 0.0, dxp_pad, 0.0)
            dt = dt - jnp.float32(pad) * dxp_pad[None, :]
        # no affine: xp_pad = 0 (and relu mask kills dxp_pad), so dW
        # needs no correction and ds/dt are zeroed below anyway

    if not affine_in:
        ds = jnp.zeros((1, k), f32)
        dt = jnp.zeros((1, k), f32)
    base = (dx, dw.astype(w.dtype), ds.astype(s.dtype),
            dt.astype(t.dtype), jnp.zeros_like(sh))
    # 5-tuple without r, 6-tuple with the residual cotangent —
    # matching _bwd_jax
    return base if not has_res else base + (dr,)


_matmul_bn.defvjp(_matmul_bn_vjp_fwd, _matmul_bn_vjp_bwd)


def matmul_bn(x: jnp.ndarray, w: jnp.ndarray,
              in_scale: Optional[jnp.ndarray] = None,
              in_shift: Optional[jnp.ndarray] = None,
              relu_in: bool = False,
              stat_shift: Optional[jnp.ndarray] = None,
              in_residual: Optional[jnp.ndarray] = None,
              interpret: Optional[bool] = None):
    """Fused ``relu(x·in_scale+in_shift [+ in_residual]) @ w`` with
    BN-statistics epilogue.

    x: (M, K); w: (K, N) — K, N must be 64-multiples (128 preferred:
    the native lane width; 64 covers ResNet's stage-0 convs via lane
    padding). Returns ``(y (M, N), sum (N,), sumsq (N,))`` where
    the statistics are over ``y - stat_shift`` in f32 (pass the BN's
    moving mean, stop-gradded, as ``stat_shift``; see
    `BatchNormalization.apply` for the scheme).

    `in_scale`/`in_shift` (K,): previous-BN folded apply on the input,
    in VMEM (skip both for a raw matmul); ``relu_in`` applies ReLU
    after the affine. ``in_residual`` (M, K) adds after the affine,
    before the ReLU — the shape of a DEFERRED bottleneck output
    ``relu(y3·scale3+shift3 + shortcut)`` consumed here instead of
    being materialized by its own whole-tensor pass (the round-5
    deferred-apply lever). The backward recomputes the ReLU/residual
    VJP in VMEM inside the Pallas dx kernel and emits the residual
    cotangent through the same epilogue — it never exists in HBM
    (``ZOO_TPU_CONV_BN_PALLAS_BWD=0`` selects the XLA reference
    backward). Differentiable in x, w, in_scale, in_shift,
    in_residual.
    """
    global invocations
    invocations += 1
    m, k = x.shape
    n = w.shape[1]
    if k % 64 or n % 64:
        # 128 is the native lane width; 64 still compiles (Mosaic pads
        # lanes) and covers ResNet's stage-0 64-channel convs
        raise ValueError(f"K={k} and N={n} must be 64-multiples")
    if in_residual is not None and in_residual.shape != (m, k):
        raise ValueError(f"in_residual must be {(m, k)}, got "
                         f"{in_residual.shape}")
    if interpret is None:
        interpret = not on_tpu()
    # shift-only callers get scale=1, not a silently dropped shift
    affine_in = in_scale is not None or in_shift is not None
    f32 = jnp.float32
    s = (in_scale.astype(f32) if in_scale is not None else
         jnp.ones((k,), f32)).reshape(1, k)
    t = (in_shift.astype(f32) if in_shift is not None else
         jnp.zeros((k,), f32)).reshape(1, k)
    sh = (stat_shift.astype(f32) if stat_shift is not None else
          jnp.zeros((n,), f32)).reshape(1, n)
    return _matmul_bn(x, w.astype(x.dtype), s, t, sh, in_residual,
                      relu_in, affine_in, bool(interpret))


def _apply_kernel(x_ref, w_ref, s_ref, t_ref, os_ref, ot_ref,
                  *rest, n_k: int, relu_in: bool,
                  affine_in: bool, has_res: bool, relu_out: bool,
                  out_dtype):
    """Eval-mode variant of `_kernel`: no statistics epilogue; instead
    the OUTPUT affine (this BN's moving-stats fold), an optional
    residual tile, and an optional ReLU apply while the tile writes —
    the raw conv output never exists in HBM. ``rest`` is Pallas's
    input→output→scratch tail: ``([r_ref,] y_ref, acc_ref)``."""
    if has_res:
        r_ref, y_ref, acc_ref = rest
    else:
        y_ref, acc_ref = rest
    ki = pl.program_id(1)
    _prologue_accumulate(x_ref, w_ref, s_ref, t_ref, acc_ref, ki,
                         relu_in, affine_in)

    @pl.when(ki == n_k - 1)
    def _finalize():
        y = acc_ref[...] * os_ref[0, :][None, :] + \
            ot_ref[0, :][None, :]
        if has_res:
            y = y + r_ref[...].astype(jnp.float32)
        if relu_out:
            y = jnp.maximum(y, 0.0)
        y_ref[...] = y.astype(out_dtype)


def _apply_ref(x, w, s, t, os_, ot, res, relu_in, affine_in,
               relu_out):
    """Reference expression for `matmul_bn_apply` (ground truth +
    the autodiff backward). Accepts the affine vectors 1-D or as the
    kernel's (1, K)/(1, N) rows."""
    f32 = jnp.float32
    s = None if s is None else s.reshape(-1)
    t = None if t is None else t.reshape(-1)
    os_ = os_.reshape(-1)
    ot = ot.reshape(-1)
    xf = x.astype(f32)
    if affine_in:
        xf = xf * s[None, :] + t[None, :]
    if relu_in:
        xf = jnp.maximum(xf, 0.0)
    y = jax.lax.dot_general(xf.astype(w.dtype), w,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)
    y = y * os_[None, :] + ot[None, :]
    if res is not None:
        y = y + res.astype(f32)
    if relu_out:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _matmul_apply(x, w, s, t, os_, ot, res, relu_in, affine_in,
                  relu_out, interpret):
    m, k = x.shape
    n = w.shape[1]
    bm, bk = _pick_blocks(
        m, k, n, max(jnp.dtype(x.dtype).itemsize,
                     jnp.dtype(w.dtype).itemsize))
    has_res = res is not None
    if m % bm:
        pad = bm - m % bm
        x = jnp.pad(x, ((0, pad), (0, 0)))
        if has_res:
            res = jnp.pad(res, ((0, pad), (0, 0)))
        mp = m + pad
    else:
        mp = m
    n_m, n_k = mp // bm, k // bk
    kernel = functools.partial(
        _apply_kernel, n_k=n_k, relu_in=relu_in, affine_in=affine_in,
        has_res=has_res, relu_out=relu_out, out_dtype=jnp.dtype(x.dtype))
    in_specs = [
        pl.BlockSpec((bm, bk), lambda mi, ki: (mi, ki)),
        pl.BlockSpec((bk, n), lambda mi, ki: (ki, 0)),
        pl.BlockSpec((1, bk), lambda mi, ki: (0, ki)),
        pl.BlockSpec((1, bk), lambda mi, ki: (0, ki)),
        pl.BlockSpec((1, n), lambda mi, ki: (0, 0)),
        pl.BlockSpec((1, n), lambda mi, ki: (0, 0)),
    ]
    operands = [x, w, s, t, os_, ot]
    if has_res:
        in_specs.append(pl.BlockSpec((bm, n), lambda mi, ki: (mi, 0)))
        operands.append(res)
    y = pl.pallas_call(
        kernel,
        name="zoo_matmul_apply",
        grid=(n_m, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n), lambda mi, ki: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return y[:m] if mp != m else y


def _matmul_apply_vjp_fwd(x, w, s, t, os_, ot, res, relu_in,
                          affine_in, relu_out, interpret):
    y = _matmul_apply(x, w, s, t, os_, ot, res, relu_in, affine_in,
                      relu_out, interpret)
    return y, (x, w, s, t, os_, ot, res)


def _matmul_apply_vjp_bwd(relu_in, affine_in, relu_out, interpret,
                          primals, dy):
    # the apply path is an INFERENCE fold; a rare grad through it uses
    # autodiff of the reference expression (XLA-fused, exact)
    x, w, s, t, os_, ot, res = primals
    if res is None:
        def f(x, w, s, t, os_, ot):
            return _apply_ref(x, w, s, t, os_, ot, None, relu_in,
                              affine_in, relu_out)
        _, vjp = jax.vjp(f, x, w, s, t, os_, ot)
        return vjp(dy) + (None,)
    _, vjp = jax.vjp(
        lambda x, w, s, t, os_, ot, res: _apply_ref(
            x, w, s, t, os_, ot, res, relu_in, affine_in, relu_out),
        x, w, s, t, os_, ot, res)
    return vjp(dy)


_matmul_apply.defvjp(_matmul_apply_vjp_fwd, _matmul_apply_vjp_bwd)


def matmul_bn_apply(x: jnp.ndarray, w: jnp.ndarray,
                    in_scale: Optional[jnp.ndarray] = None,
                    in_shift: Optional[jnp.ndarray] = None,
                    relu_in: bool = False,
                    out_scale: Optional[jnp.ndarray] = None,
                    out_shift: Optional[jnp.ndarray] = None,
                    residual: Optional[jnp.ndarray] = None,
                    relu_out: bool = False,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Inference fold of ``relu(prologue(x) @ w · out_scale +
    out_shift + residual)`` — :func:`matmul_bn` for EVAL mode, where
    this BN's moving-stats fold (``out_scale``/``out_shift``) is known
    BEFORE the matmul, so the epilogue applies it (plus the residual
    add and ReLU) while the tile writes: the raw conv output and a
    separate whole-tensor apply pass never exist in HBM. Returns just
    ``y (M, N)`` (no statistics — eval uses moving stats)."""
    global invocations
    invocations += 1
    m, k = x.shape
    n = w.shape[1]
    if k % 64 or n % 64:
        raise ValueError(f"K={k} and N={n} must be 64-multiples")
    if interpret is None:
        interpret = not on_tpu()
    affine_in = in_scale is not None or in_shift is not None
    f32 = jnp.float32
    s_v = (in_scale.astype(f32) if in_scale is not None else
           jnp.ones((k,), f32)).reshape(1, k)
    t_v = (in_shift.astype(f32) if in_shift is not None else
           jnp.zeros((k,), f32)).reshape(1, k)
    os_v = (out_scale.astype(f32) if out_scale is not None else
            jnp.ones((n,), f32)).reshape(1, n)
    ot_v = (out_shift.astype(f32) if out_shift is not None else
            jnp.zeros((n,), f32)).reshape(1, n)
    return _matmul_apply(x, w, s_v, t_v, os_v, ot_v, residual,
                         relu_in, affine_in, relu_out, bool(interpret))


def conv1x1_bn_apply(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
                     residual: Optional[jnp.ndarray] = None,
                     **kwargs) -> jnp.ndarray:
    """NHWC wrapper over :func:`matmul_bn_apply` (eval fold).
    ``residual``: (N, H', W', F), added pre-ReLU."""
    if w.ndim == 4:
        w = w[0, 0]
    if stride != 1:
        x = x[:, ::stride, ::stride, :]
    b, h, wd, c = x.shape
    res2 = residual.reshape(b * h * wd, w.shape[-1]) \
        if residual is not None else None
    y2 = matmul_bn_apply(x.reshape(b * h * wd, c), w, residual=res2,
                         **kwargs)
    return y2.reshape(b, h, wd, w.shape[-1])


def conv1x1_bn(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1,
               in_residual: Optional[jnp.ndarray] = None,
               **kwargs):
    """NHWC 1×1 conv + BN statistics via :func:`matmul_bn`.
    x: (N, H, W, C); w: (1, 1, C, F) or (C, F); ``in_residual``
    (N, H', W', C) joins the prologue (see `matmul_bn`). Returns
    ``(y (N, H', W', F), sum (F,), sumsq (F,))``."""
    if w.ndim == 4:
        w = w[0, 0]
    if stride != 1:
        x = x[:, ::stride, ::stride, :]
    b, h, wd, c = x.shape
    if in_residual is not None:
        kwargs["in_residual"] = in_residual.reshape(b * h * wd, c)
    y2, ssum, ssq = matmul_bn(x.reshape(b * h * wd, c), w, **kwargs)
    return y2.reshape(b, h, wd, w.shape[-1]), ssum, ssq


# ---------------------------------------------------------------------------
# 3×3 stride-1 SAME conv + BN (the residual-block 3×3s)
# ---------------------------------------------------------------------------

def _conv3_ref(x, w, s, t, sh, relu_in, affine_in, stride=1):
    """Reference expression for conv3x3_bn — the ground truth the
    kernel is tested against AND the function whose `jax.vjp` is the
    backward (exact gradients, standard XLA conv backward perf)."""
    f32 = jnp.float32
    xf = x.astype(f32)
    if affine_in:
        xf = xf * s[None, None, None, :] + t[None, None, None, :]
    if relu_in:
        xf = jnp.maximum(xf, 0.0)
    # compute-dtype conv without a promoted output type: the conv
    # transpose rule needs all three dtypes equal, so a promoted-f32
    # output makes bf16 autodiff through this expression crash.
    # conv_grad.conv2d == the same lax conv forward, but its backward
    # is gated between the transpose rule and the phase decomposition
    # (no dilated operand — ZOO_TPU_PHASE_BWD, trace-time)
    y = conv_grad.conv2d(
        xf.astype(x.dtype), w.astype(x.dtype),
        stride=(stride, stride), padding="SAME")
    d = y.astype(f32) - sh[None, None, None, :]
    return (y, jnp.sum(d, axis=(0, 1, 2)),
            jnp.sum(d * d, axis=(0, 1, 2)))


def _conv3_acc(x_ref, w_ref, s_ref, t_ref, relu_in, affine_in,
               stride):
    """3×3-tap compute SHARED by the stats and apply conv kernels:
    prologue (affine+ReLU) once on the full-plane tile, then the 3×3
    as shifted (bb·Ho·Wo, Cin)@(Cin, Cout) MXU taps accumulated in
    f32. ``stride=2`` (even H/W, SAME ⇒ pad (0,1)): each tap takes
    every other row/column via an even reshape — no strided loads.
    Returns (acc, bb, ho, wo, cout)."""
    xb = x_ref[...].astype(jnp.float32)
    if affine_in:
        xb = xb * s_ref[0, :] + t_ref[0, :]
    if relu_in:
        xb = jnp.maximum(xb, 0.0)
    xb = xb.astype(w_ref.dtype)
    bb, h, wd, cin = xb.shape
    cout = w_ref.shape[3]
    if stride == 1:
        ho, wo = h, wd
        xp = jnp.pad(xb, ((0, 0), (1, 1), (1, 1), (0, 0)))

        def tap(dh, dw):
            return jax.lax.slice(
                xp, (0, dh, dw, 0), (bb, dh + h, dw + wd, cin))
    else:
        ho, wo = h // 2, wd // 2
        # SAME @ stride 2, even extent: pad (0, 1); one extra row/col
        # of zeros keeps the every-other-row reshape even
        xp = jnp.pad(xb, ((0, 0), (0, 2), (0, 2), (0, 0)))

        def tap(dh, dw):
            win = jax.lax.slice(
                xp, (0, dh, dw, 0),
                (bb, dh + 2 * ho, dw + 2 * wo, cin))
            win = win.reshape(bb, ho, 2, wo, 2, cin)
            return win[:, :, 0, :, 0, :]
    acc = jnp.zeros((bb * ho * wo, cout), jnp.float32)
    for dh in range(3):
        for dw in range(3):
            acc += jax.lax.dot_general(
                tap(dh, dw).reshape(bb * ho * wo, cin), w_ref[dh, dw],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    return acc, bb, ho, wo, cout


def _conv3_kernel(x_ref, w_ref, s_ref, t_ref, sh_ref,
                  y_ref, sum_ref, sq_ref, *,
                  relu_in: bool, affine_in: bool, out_dtype,
                  stride: int = 1):
    """Grid (bi,): one batch tile, FULL spatial plane in VMEM — no
    halos; the epilogue reduces the accumulator for the BN
    statistics (compute path shared with `_conv3_apply_kernel`)."""
    bi = pl.program_id(0)
    acc, bb, ho, wo, cout = _conv3_acc(x_ref, w_ref, s_ref, t_ref,
                                       relu_in, affine_in, stride)
    y_ref[...] = acc.reshape(bb, ho, wo, cout).astype(out_dtype)
    d = acc - sh_ref[0, :]
    snew = jnp.sum(d, axis=0, keepdims=True)
    qnew = jnp.sum(d * d, axis=0, keepdims=True)

    @pl.when(bi == 0)
    def _first():
        sum_ref[...] = snew
        sq_ref[...] = qnew

    @pl.when(bi != 0)
    def _rest():
        sum_ref[...] += snew
        sq_ref[...] += qnew


def _conv3_apply_kernel(x_ref, w_ref, s_ref, t_ref, os_ref, ot_ref,
                        y_ref, *, relu_in: bool, affine_in: bool,
                        relu_out: bool, out_dtype, stride: int = 1):
    """Eval-mode conv3 epilogue: this BN's moving-stats fold (+ReLU)
    applies while the tile writes — no statistics, no separate
    whole-tensor apply pass (compute path shared with
    `_conv3_kernel`)."""
    acc, bb, ho, wo, cout = _conv3_acc(x_ref, w_ref, s_ref, t_ref,
                                       relu_in, affine_in, stride)
    y = acc * os_ref[0, :][None, :] + ot_ref[0, :][None, :]
    if relu_out:
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.reshape(bb, ho, wo, cout).astype(out_dtype)


def _conv3_apply_ref(x, w, s, t, os_, ot, relu_in, affine_in,
                     relu_out, stride):
    """Ground truth + autodiff backward for `conv3x3_bn_apply`."""
    f32 = jnp.float32
    xf = x.astype(f32)
    if affine_in:
        xf = xf * s.reshape(-1)[None, None, None, :] + \
            t.reshape(-1)[None, None, None, :]
    if relu_in:
        xf = jnp.maximum(xf, 0.0)
    y = jax.lax.conv_general_dilated(
        xf.astype(x.dtype), w.astype(x.dtype),
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = y.astype(f32) * os_.reshape(-1)[None, None, None, :] + \
        ot.reshape(-1)[None, None, None, :]
    if relu_out:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _conv3_apply(x, w, s, t, os_, ot, relu_in, affine_in, relu_out,
                 stride, interpret):
    b, h, wd, cin = x.shape
    cout = w.shape[3]
    ho, wo = h // stride, wd // stride
    bb = _conv3_batch_tile(x.shape, cout,
                           jnp.dtype(x.dtype).itemsize, stride)
    return pl.pallas_call(
        functools.partial(_conv3_apply_kernel, relu_in=relu_in,
                          affine_in=affine_in, relu_out=relu_out,
                          out_dtype=jnp.dtype(x.dtype), stride=stride),
        name="zoo_conv3x3_apply",
        grid=(b // bb,),
        in_specs=[
            pl.BlockSpec((bb, h, wd, cin), lambda bi: (bi, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin, cout), lambda bi: (0, 0, 0, 0)),
            pl.BlockSpec((1, cin), lambda bi: (0, 0)),
            pl.BlockSpec((1, cin), lambda bi: (0, 0)),
            pl.BlockSpec((1, cout), lambda bi: (0, 0)),
            pl.BlockSpec((1, cout), lambda bi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, ho, wo, cout),
                               lambda bi: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, ho, wo, cout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_CONV3_VMEM_LIMIT),
        interpret=interpret,
    )(x, w.astype(x.dtype), s, t, os_, ot)


def _conv3_apply_vjp_fwd(x, w, s, t, os_, ot, relu_in, affine_in,
                         relu_out, stride, interpret):
    y = _conv3_apply(x, w, s, t, os_, ot, relu_in, affine_in,
                     relu_out, stride, interpret)
    return y, (x, w, s, t, os_, ot)


def _conv3_apply_vjp_bwd(relu_in, affine_in, relu_out, stride,
                         interpret, primals, dy):
    # inference fold; a rare grad uses autodiff of the reference
    x, w, s, t, os_, ot = primals
    _, vjp = jax.vjp(
        lambda x, w, s, t, os_, ot: _conv3_apply_ref(
            x, w, s, t, os_, ot, relu_in, affine_in, relu_out,
            stride),
        x, w, s, t, os_, ot)
    return vjp(dy)


_conv3_apply.defvjp(_conv3_apply_vjp_fwd, _conv3_apply_vjp_bwd)


def conv3x3_bn_apply(x: jnp.ndarray, w: jnp.ndarray,
                     in_scale: Optional[jnp.ndarray] = None,
                     in_shift: Optional[jnp.ndarray] = None,
                     relu_in: bool = False,
                     out_scale: Optional[jnp.ndarray] = None,
                     out_shift: Optional[jnp.ndarray] = None,
                     relu_out: bool = False,
                     stride: int = 1,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Inference fold of the 3×3: :func:`conv3x3_bn` for EVAL mode —
    the known moving-stats fold (``out_scale``/``out_shift``) and ReLU
    apply in the epilogue; returns just ``y``. Same constraints as
    `conv3x3_bn`; oversized planes/odd strided extents fall back to
    the XLA reference expression."""
    global invocations
    invocations += 1
    if w.shape[:2] != (3, 3):
        raise ValueError(f"kernel must be 3x3, got {w.shape[:2]}")
    if stride not in (1, 2):
        raise ValueError(f"stride must be 1 or 2, got {stride}")
    cin, cout = w.shape[2], w.shape[3]
    if cin % 64 or cout % 64:
        raise ValueError(f"Cin={cin} and Cout={cout} must be "
                         "64-multiples")
    if interpret is None:
        interpret = not on_tpu()
    affine_in = in_scale is not None or in_shift is not None
    f32 = jnp.float32
    s_v = (in_scale.astype(f32) if in_scale is not None else
           jnp.ones((cin,), f32))
    t_v = (in_shift.astype(f32) if in_shift is not None else
           jnp.zeros((cin,), f32))
    os_v = (out_scale.astype(f32) if out_scale is not None else
            jnp.ones((cout,), f32))
    ot_v = (out_shift.astype(f32) if out_shift is not None else
            jnp.zeros((cout,), f32))
    odd = stride == 2 and (x.shape[1] % 2 or x.shape[2] % 2)
    if odd or _conv3_batch_tile(x.shape, cout,
                                jnp.dtype(x.dtype).itemsize,
                                stride) is None:
        return _conv3_apply_ref(x, w, s_v, t_v, os_v, ot_v, relu_in,
                                affine_in, relu_out, stride)
    return _conv3_apply(x, w, s_v.reshape(1, cin), t_v.reshape(1, cin),
                        os_v.reshape(1, cout), ot_v.reshape(1, cout),
                        relu_in, affine_in, relu_out, int(stride),
                        bool(interpret))


# The conv3 batch tile is sized against Mosaic's default scoped-VMEM
# limit, but the kernels ask Mosaic for four times that: the model in
# `_conv3_batch_tile` is an envelope of the blocks and copies the
# kernel names, not of every relayout temporary Mosaic keeps (the v5e
# compiler counts 25 MiB for one 56x56x128 stride-2 image that models
# at 14). A v5e core has 128 MiB of VMEM.
_CONV3_TILE_BUDGET = 16 * 2 ** 20
_CONV3_VMEM_LIMIT = 64 * 2 ** 20


def _vmem_bytes(shape, itemsize) -> int:
    """Bytes a value of ``shape`` occupies in VMEM: the minor dim
    pads to 128 lanes, the second-minor to a full sublane tile (8
    rows of 32 bits — 8 for f32, 16 for bf16)."""
    *lead, sub, lane = shape
    sub_tile = 8 * max(4 // itemsize, 1)
    n = itemsize * (-(-sub // sub_tile) * sub_tile) * \
        (-(-lane // 128) * 128)
    for d in lead:
        n *= d
    return n


def _conv3_batch_tile(shape, cout, itemsize, stride=1) -> Optional[int]:
    """Largest divisor of B (≤16) whose full-plane residency fits
    ``_CONV3_TILE_BUDGET``, counted the way the compiler lays it out
    (`_vmem_bytes`): the input and output blocks twice (the pipeline
    double-buffers them), the f32 prologue copy, the zero-padded
    compute-dtype copy, one tap operand, the f32 accumulator and its
    epilogue copy, the double-buffered weights — and at stride 2 the
    every-other-row pick, whose size-2 dim pads to a sublane tile.
    None when even one image does not fit."""
    b, h, wd, cin = shape
    ho, wo = h // stride, wd // stride
    per_img = (2 * _vmem_bytes((h, wd, cin), itemsize) +
               2 * _vmem_bytes((ho, wo, cout), itemsize) +
               _vmem_bytes((h, wd, cin), 4) +
               _vmem_bytes((h + 2, wd + 2, cin), itemsize) +
               _vmem_bytes((ho * wo, cin), itemsize) +
               2 * _vmem_bytes((ho * wo, cout), 4))
    if stride == 2:
        per_img += _vmem_bytes((ho, 2, wo, 2, cin), itemsize)
    w_bytes = 2 * 9 * _vmem_bytes((cin, cout), itemsize)
    for cand in range(min(b, 16), 0, -1):
        if b % cand == 0 and \
                cand * per_img + w_bytes <= _CONV3_TILE_BUDGET:
            return cand
    return None


def _conv3_fwd_pallas(x, w, s, t, sh, relu_in, affine_in, stride,
                      interpret):
    b, h, wd, cin = x.shape
    cout = w.shape[3]
    ho, wo = h // stride, wd // stride
    bb = _conv3_batch_tile(x.shape, cout,
                           jnp.dtype(x.dtype).itemsize, stride)
    assert bb is not None  # conv3x3_bn falls back before reaching here
    f32 = jnp.float32
    y, ssum, ssq = pl.pallas_call(
        functools.partial(_conv3_kernel, relu_in=relu_in,
                          affine_in=affine_in,
                          out_dtype=jnp.dtype(x.dtype),
                          stride=stride),
        name="zoo_conv3x3_bn_fwd",
        grid=(b // bb,),
        in_specs=[
            pl.BlockSpec((bb, h, wd, cin), lambda bi: (bi, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin, cout), lambda bi: (0, 0, 0, 0)),
            pl.BlockSpec((1, cin), lambda bi: (0, 0)),
            pl.BlockSpec((1, cin), lambda bi: (0, 0)),
            pl.BlockSpec((1, cout), lambda bi: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, ho, wo, cout), lambda bi: (bi, 0, 0, 0)),
            pl.BlockSpec((1, cout), lambda bi: (0, 0)),
            pl.BlockSpec((1, cout), lambda bi: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, ho, wo, cout), x.dtype),
            jax.ShapeDtypeStruct((1, cout), f32),
            jax.ShapeDtypeStruct((1, cout), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_CONV3_VMEM_LIMIT),
        interpret=interpret,
    )(x, w.astype(x.dtype), s, t, sh)
    return y, ssum[0], ssq[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _conv3(x, w, s, t, sh, relu_in, affine_in, stride, interpret):
    return _conv3_fwd_pallas(x, w, s, t, sh, relu_in, affine_in,
                             stride, interpret)


def _conv3_vjp_fwd(x, w, s, t, sh, relu_in, affine_in, stride,
                   interpret):
    out = _conv3_fwd_pallas(x, w, s, t, sh, relu_in, affine_in,
                            stride, interpret)
    y, _, _ = out
    return out, (x, w, s, t, sh, y)


def _same_pads_k3(sz, stride):
    """(lo, hi) SAME padding for the k=3 conv over extent ``sz``."""
    ho = -(-sz // stride)
    total = max((ho - 1) * stride + 3 - sz, 0)
    lo = total // 2
    return lo, total - lo


def _conv3_dilated_bwd(gc, wc, xpc, stride, hh, ww_):
    """jax's own conv transpose formulations written explicitly (the
    pre-phase-decomposition backward, kept for the ZOO_TPU_PHASE_BWD
    A/B): dXp slides the full kernel over the stride-DILATED
    cotangent, so at stride 2 three quarters of its MACs multiply
    inserted zeros (the executed-FLOPs excess ops.conv_grad removes).
    Padding algebra is the SAME-padding k=3 specialization of jax's
    _conv_general_vjp_{lhs,rhs}_padding."""
    f32 = jnp.float32

    def _pads(sz):
        ho = -(-sz // stride)               # SAME output extent
        total = max((ho - 1) * stride + 3 - sz, 0)
        lo = total // 2
        return lo, 1 + (ho - 1) * stride    # lo, dilated out size

    lo_h, od_h = _pads(hh)
    lo_w, od_w = _pads(ww_)
    # dXp: conv of the (stride-dilated) cotangent with the
    # spatially-reversed, I/O-swapped kernel
    dx_pad = ((2 - lo_h, (hh + 2) - od_h - (2 - lo_h)),
              (2 - lo_w, (ww_ + 2) - od_w - (2 - lo_w)))
    dxp = jax.lax.conv_general_dilated(
        gc, jax.lax.rev(wc, (0, 1)),
        window_strides=(1, 1), padding=dx_pad,
        lhs_dilation=(stride, stride), rhs_dilation=(1, 1),
        dimension_numbers=("NHWC", "HWOI", "NHWC"),
        preferred_element_type=f32)
    # dW: contract over batch — x' as ("CHWN") against the
    # stride-dilated cotangent as ("IHWO"), producing ("HWNC")
    dw_pad = ((lo_h, (od_h - hh) + (2 - lo_h)),
              (lo_w, (od_w - ww_) + (2 - lo_w)))
    dw = jax.lax.conv_general_dilated(
        xpc, gc, window_strides=(1, 1), padding=dw_pad,
        lhs_dilation=(1, 1), rhs_dilation=(stride, stride),
        dimension_numbers=("CHWN", "IHWO", "HWNC"),
        preferred_element_type=f32)
    return dxp, dw


def _conv3_vjp_bwd(relu_in, affine_in, stride, interpret, res, cots):
    """XLA backward: the conv is linear in each operand, so
    `jax.linear_transpose` gives dW/dxp without re-running the
    forward; the stats cotangents fold into the same augmented g as
    the matmul kernel's backward."""
    x, w, s, t, sh, y = res
    dy, dsum, dsq = cots
    f32 = jnp.float32
    g = dy.astype(f32) + dsum[None, None, None, :] + \
        2.0 * (y.astype(f32) - sh[0][None, None, None, :]) * \
        dsq[None, None, None, :]
    xf = x.astype(f32)
    if affine_in:
        xa = xf * s[0] + t[0]
    else:
        xa = xf
    xp = jnp.maximum(xa, 0.0) if relu_in else xa
    cd = x.dtype

    xpc = xp.astype(cd)
    wc = w.astype(cd)
    if os.environ.get("ZOO_TPU_CONV3_BWD_F32") == "1":
        # escape hatch: the round-4 f32-operand backward (for A/B and
        # numerics debugging)
        def conv(l, r):
            return jax.lax.conv_general_dilated(
                l.astype(f32), r.astype(f32),
                window_strides=(stride, stride), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        dw = jax.linear_transpose(lambda ww: conv(xpc, ww), wc)(g)[0]
        dxp = jax.linear_transpose(
            lambda xx: conv(xx, wc), xpc)(g)[0].astype(f32)
    else:
        # bf16-operand backward convs with f32 accumulation
        # (preferred_element_type) — full MXU rate, the standard
        # mixed-precision recipe (VERDICT r4 next-round #3). These are
        # jax's own conv transpose formulations written explicitly:
        # `linear_transpose` can't be used because the transpose rule
        # rebuilds a conv between the cotangent and the saved operand
        # and conv_general_dilated requires equal operand dtypes —
        # with f32 cotangents and bf16 residuals it crashes, and
        # casting the operands up (round 4) halves backward MXU
        # throughput. Padding algebra below is the SAME-padding k=3
        # specialization of jax's _conv_general_vjp_{lhs,rhs}_padding.
        gc = g.astype(cd)
        hh, ww_ = xp.shape[1], xp.shape[2]
        if stride != 1 and conv_grad.phase_bwd_enabled():
            # phase-decomposed backward (ops.conv_grad): same sums
            # reassociated into stride-1 convs over UNDILATED
            # operands — the executed-FLOPs lever; the dilated
            # formulation below wastes (s^2-1)/s^2 of its dx MACs on
            # inserted zeros
            sp = tuple(_same_pads_k3(sz, stride) for sz in (hh, ww_))
            dxp = conv_grad.phase_dx(
                gc, wc, (hh, ww_), (stride, stride), sp,
                preferred_element_type=f32)
            dw = conv_grad.phase_dw(
                xpc, gc, (3, 3), (stride, stride), sp,
                preferred_element_type=f32)
        else:
            dxp, dw = _conv3_dilated_bwd(gc, wc, xpc, stride, hh,
                                         ww_)
    if relu_in:
        dxp = jnp.where(xa > 0.0, dxp, 0.0)
    if affine_in:
        dx = (dxp * s[0]).astype(x.dtype)
        ds = jnp.sum(dxp * xf, axis=(0, 1, 2)).reshape(1, -1)
        dt = jnp.sum(dxp, axis=(0, 1, 2)).reshape(1, -1)
    else:
        dx = dxp.astype(x.dtype)
        ds = jnp.zeros_like(s)
        dt = jnp.zeros_like(t)
    return (dx, dw.astype(w.dtype), ds.astype(s.dtype),
            dt.astype(t.dtype), jnp.zeros_like(sh))


_conv3.defvjp(_conv3_vjp_fwd, _conv3_vjp_bwd)


def conv3x3_bn(x: jnp.ndarray, w: jnp.ndarray,
               in_scale: Optional[jnp.ndarray] = None,
               in_shift: Optional[jnp.ndarray] = None,
               relu_in: bool = False,
               stat_shift: Optional[jnp.ndarray] = None,
               stride: int = 1,
               interpret: Optional[bool] = None):
    """Fused 3×3 SAME conv + BN statistics (the VERDICT r3 target:
    the residual-block 3×3s). x: (B, H, W, Cin); w: (3, 3, Cin, Cout),
    Cin/Cout 64-multiples; ``stride`` 1 or 2 (2 covers the stage-
    transition blocks — VERDICT r4 lever; even H/W required, else the
    XLA reference path). Prologue/epilogue and returns exactly like
    :func:`matmul_bn`; ``stat_shift`` must be non-differentiated (pass
    the BN's moving mean stop-gradded — its cotangent is defined as
    zero, like matmul_bn's). Backward runs as two explicit XLA
    transpose convs with compute-dtype (bf16) operands and f32
    accumulation (`ZOO_TPU_CONV3_BWD_F32=1` selects the f32-operand
    `linear_transpose` form instead). Planes too large for a
    one-image VMEM tile fall back to the XLA reference expression."""
    global invocations
    invocations += 1
    if w.shape[:2] != (3, 3):
        raise ValueError(f"kernel must be 3x3, got {w.shape[:2]}")
    if stride not in (1, 2):
        raise ValueError(f"stride must be 1 or 2, got {stride}")
    cin, cout = w.shape[2], w.shape[3]
    if cin % 64 or cout % 64:
        raise ValueError(f"Cin={cin} and Cout={cout} must be "
                         "64-multiples")
    if interpret is None:
        interpret = not on_tpu()
    affine_in = in_scale is not None or in_shift is not None
    f32 = jnp.float32
    s_v = (in_scale.astype(f32) if in_scale is not None else
           jnp.ones((cin,), f32))
    t_v = (in_shift.astype(f32) if in_shift is not None else
           jnp.zeros((cin,), f32))
    sh_v = (stat_shift.astype(f32) if stat_shift is not None else
            jnp.zeros((cout,), f32))
    odd = stride == 2 and (x.shape[1] % 2 or x.shape[2] % 2)
    if odd or _conv3_batch_tile(x.shape, cout,
                                jnp.dtype(x.dtype).itemsize,
                                stride) is None:
        # plane too large for VMEM (or odd strided extent): the
        # reference expression (autodiff supplies the same gradients
        # the custom path computes)
        return _conv3_ref(x, w, s_v, t_v, sh_v, relu_in, affine_in,
                          stride)
    return _conv3(x, w, s_v.reshape(1, cin), t_v.reshape(1, cin),
                  sh_v.reshape(1, cout), relu_in, affine_in,
                  int(stride), bool(interpret))


# -- autotuner specs --------------------------------------------------------
# Registered here so the legacy env flag stays read under ops/ (the
# lint override gate) and the probes exercise the real custom_vjp
# call sites via autotune.forced(), not a reimplementation.

def _blocks_heuristic(p):
    bm, bk = _heuristic_blocks(p["m"], p["k"], p["n"], p["isz"])
    return {"bm": bm, "bk": bk}


def _blocks_candidates(p):
    """Every (bm, bk) pair that divides the problem and respects the
    dtype-aware ~6MB VMEM budget — the same feasibility rule the
    heuristic enforces, enumerated instead of solved greedily."""
    k, n, isz = p["k"], p["n"], p["isz"]
    bks = [b for b in (512, 384, 256, 128, 64) if k % b == 0] \
        if k > 512 else [k]
    return [{"bm": bm, "bk": bk}
            for bk in bks
            for bm in (512, 256, 128)
            if bm * n * 4 + (bm * bk + bk * n) * isz <= 6 * 2 ** 20]


def _fused_probe_operands(p):
    import numpy as np
    rs = np.random.RandomState(0)
    m, k, n = p["m"], p["k"], p["n"]
    dt = jnp.float32 if p.get("isz", 2) >= 4 else jnp.bfloat16
    x = jnp.asarray(rs.randn(m, k), dt)
    w = jnp.asarray(rs.randn(k, n) * 0.05, dt)
    s = jnp.asarray(rs.rand(1, k) + 0.5, jnp.float32)
    t = jnp.asarray(rs.randn(1, k), jnp.float32)
    sh = jnp.zeros((1, n), jnp.float32)
    return x, w, s, t, sh


def _blocks_runner(p, cfg):
    m, k, n = p["m"], p["k"], p["n"]
    if k % 64 or n % 64 or m % 8:
        return None
    interpret = not on_tpu()
    if interpret and m * k > (1 << 18):
        return None            # interpreter budget off-chip
    x, w, s, t, sh = _fused_probe_operands(p)

    @jax.jit
    def probe(x, w, s, t, sh):
        y, su, sq = _matmul_bn(x, w, s, t, sh, None, True, True,
                               interpret)
        return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(su) +
                jnp.sum(sq))

    def run():
        # forced() pins the candidate through the real _pick_blocks
        # call at trace time (first call, inside expected_compiles)
        with autotune.forced("conv_bn_blocks", cfg):
            jax.block_until_ready(probe(x, w, s, t, sh))
    return run


def _bwd_flag(p):
    env = os.environ.get("ZOO_TPU_CONV_BN_PALLAS_BWD")
    if env is None:
        return None
    return {"pallas": env == "1"}


def _bwd_runner(p, cfg):
    m, k, n = p["m"], p["k"], p["n"]
    if k % 64 or n % 64 or m % 8:
        return None
    interpret = not on_tpu()
    if interpret and m * k > (1 << 18):
        return None
    x, w, s, t, sh = _fused_probe_operands(p)

    @jax.jit
    def probe(x, w, s, t, sh):
        def loss(x, w):
            y, su, sq = _matmul_bn(x, w, s, t, sh, None, True, True,
                                   interpret)
            return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(su) +
                    jnp.sum(sq))
        val, (dx, dw) = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        return (val + jnp.sum(dx.astype(jnp.float32)) +
                jnp.sum(dw.astype(jnp.float32)))

    def run():
        with autotune.forced("conv_bn_bwd", cfg):
            jax.block_until_ready(probe(x, w, s, t, sh))
    return run


autotune.register(autotune.OpSpec(
    "conv_bn_blocks", heuristic=_blocks_heuristic,
    candidates=_blocks_candidates, runner=_blocks_runner))

autotune.register(autotune.OpSpec(
    "conv_bn_bwd",
    heuristic=lambda p: {"pallas": True},
    candidates=lambda p: [{"pallas": True}, {"pallas": False}],
    flag_value=_bwd_flag, runner=_bwd_runner))
