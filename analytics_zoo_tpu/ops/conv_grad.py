"""Phase-decomposed backward for strided convolutions.

PERF.md round 6: fusion levers cap out near 0.32–0.36 model-MFU
because XLA executes ~1.95x the model FLOPs, dominated by the
input-dilated stride-2 backward convs — jax's conv transpose rule
computes dx by zero-dilating the cotangent (``lhs_dilation=(s, s)``)
and sliding the full kernel over it, so (s^2-1)/s^2 of the executed
MACs multiply inserted zeros (the hardware conv unit cannot skip
them). The standard fix in TPU convnet stacks is the sub-pixel /
phase decomposition of the transposed conv:

dx: split the kernel into s^2 spatial phases ``w[ph::s, pw::s]``;
each output phase ``dx[s*m+ph]`` is an ordinary *stride-1* conv of
the UNDILATED cotangent with the reversed sub-kernel, and the s^2
phase planes interleave back with a reshape (inverse
space-to-depth). Executed MACs == model MACs — 4x fewer at s=2.

dw: jax's rule dilates the cotangent on the *rhs* side
(``rhs_dilation=(s, s)``). Phase-slice the input instead:
``dw[s*j+ph] = sum_p x~[s*p + s*j + ph] * dy[p]`` is a dense VALID
stride-1 conv of the phase-sliced input ``x~[ph::s]`` against the
cotangent — every tap an ordinary dense reduction, no dilated
operand anywhere.

Exact same sums as the transpose rule, reassociated — gradients
match to f32 roundoff. `ZOO_TPU_PHASE_BWD=0` selects jax's
transpose-rule backward for A/B; the auto default routes through a
measured-win gate like `conv_bn.fused_profitable` (pending an
on-chip verdict from scripts/measure_fused.py).

Note for FLOPs accounting (scripts/flops_audit.py): XLA's
HloCostAnalysis already discounts dilation-inserted zeros, so its
`flops` does NOT drop under this rewrite — the executed-semantics
count (full window taps x output elements, what a systolic array
actually runs) is the number this lever moves.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.common.device import on_tpu

_DN = ("NHWC", "HWIO", "NHWC")

# test observability, like ops.conv_bn.invocations
invocations = {"conv2d": 0, "bwd_phase": 0, "bwd_ref": 0}

# Measured-win gate for the auto default (the conv_bn.MEASURED_WIN
# playbook): flip to True once scripts/measure_fused.py section E
# shows the phase backward beating the dilated transpose rule on
# real hardware. Until then the phase path is opt-in
# (ZOO_TPU_PHASE_BWD=1) — it is grads-exact and strictly fewer
# executed MACs, but chip-unmeasured (s^2 smaller convs could lose
# to one big dilated conv on grid overhead).
PHASE_MEASURED_WIN = False


def phase_bwd_enabled() -> bool:
    """Whether strided convs default to the phase-decomposed
    backward. ``ZOO_TPU_PHASE_BWD=0/1`` overrides (read at trace
    time); otherwise a real TPU backend AND a measured on-chip win
    (``PHASE_MEASURED_WIN``)."""
    env = os.environ.get("ZOO_TPU_PHASE_BWD")
    if env is not None:
        return env != "0"
    return PHASE_MEASURED_WIN and on_tpu()


def _same_pads(size: int, k: int, stride: int) -> Tuple[int, int]:
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    lo = total // 2
    return lo, total - lo


def normalize_padding(padding, x_spatial: Sequence[int],
                      k_spatial: Sequence[int],
                      stride: Sequence[int]
                      ) -> Tuple[Tuple[int, int], ...]:
    """Resolve "SAME"/"VALID"/explicit padding to per-dim (lo, hi)
    pairs (jax's own SAME algebra: lo = total // 2)."""
    if isinstance(padding, str):
        p = padding.upper()
        if p == "VALID":
            return tuple((0, 0) for _ in x_spatial)
        if p == "SAME":
            return tuple(_same_pads(sz, k, s) for sz, k, s in
                         zip(x_spatial, k_spatial, stride))
        raise ValueError(f"padding must be SAME|VALID, got {padding}")
    return tuple((int(lo), int(hi)) for lo, hi in padding)


def _grid(size: int, lo: int, hi: int, k: int, stride: int
          ) -> Tuple[int, int, int]:
    """(padded extent, conv output extent, phase-plane extent M).
    M = ceil(padded / s) is uniform across phases: every output
    phase plane is computed at extent M and the interleave slices
    the (lo, hi) padding back off."""
    padded = size + lo + hi
    out = (padded - k) // stride + 1
    return padded, out, -(-padded // stride)


def phase_dx(g: jnp.ndarray, w: jnp.ndarray,
             x_spatial: Tuple[int, int],
             stride: Tuple[int, int],
             pads: Tuple[Tuple[int, int], Tuple[int, int]],
             preferred_element_type=None) -> jnp.ndarray:
    """dx of ``conv(x, w, stride, pads)`` (NHWC/HWIO) without a
    dilated operand: s^2 stride-1 convs of the undilated cotangent
    ``g`` with the reversed sub-kernels ``w[ph::s, pw::s]``
    (I/O-swapped dims, like jax's rule), interleaved by an inverse
    space-to-depth reshape. Per-phase padding ``(K_ph - 1, M - Ho)``
    may be negative on the high side (a crop) — lax accepts that.
    Empty phases (e.g. a 1x1 kernel at s=2) are zero planes."""
    n, ho, wo, cout = g.shape
    kh, kw, cin, _ = w.shape
    sh, sw = stride
    (lo_h, hi_h), (lo_w, hi_w) = pads
    hx, wx = x_spatial
    _, oh, mh = _grid(hx, lo_h, hi_h, kh, sh)
    _, ow, mw = _grid(wx, lo_w, hi_w, kw, sw)
    assert (oh, ow) == (ho, wo), ((oh, ow), (ho, wo))
    res_dtype = preferred_element_type or g.dtype

    rows = []
    for ph in range(sh):
        cols = []
        for pw in range(sw):
            wsub = w[ph::sh, pw::sw]
            kph, kpw = wsub.shape[0], wsub.shape[1]
            if kph == 0 or kpw == 0:
                cols.append(jnp.zeros((n, mh, mw, cin), res_dtype))
                continue
            cols.append(jax.lax.conv_general_dilated(
                g, jax.lax.rev(wsub, (0, 1)),
                window_strides=(1, 1),
                padding=((kph - 1, mh - ho), (kpw - 1, mw - wo)),
                dimension_numbers=("NHWC", "HWOI", "NHWC"),
                preferred_element_type=preferred_element_type))
        rows.append(jnp.stack(cols, axis=3))    # (N, Mh, Mw, sw, C)
    dxt = jnp.stack(rows, axis=2)          # (N, Mh, sh, Mw, sw, C)
    dxt = dxt.reshape(n, sh * mh, sw * mw, cin)
    return dxt[:, lo_h:lo_h + hx, lo_w:lo_w + wx, :]


def phase_dw(x: jnp.ndarray, g: jnp.ndarray,
             k_spatial: Tuple[int, int],
             stride: Tuple[int, int],
             pads: Tuple[Tuple[int, int], Tuple[int, int]],
             preferred_element_type=None) -> jnp.ndarray:
    """dw of ``conv(x, w, stride, pads)`` (NHWC/HWIO) without a
    dilated operand: phase-slice the padded input (a pad-to-multiple
    + reshape, no strided gather) so each sub-kernel tap row
    ``dw[s*j+ph]`` is a dense VALID stride-1 conv of ``x[ph::s]``
    against the cotangent-as-kernel (jax's ``("CHWN","IHWO","HWNC")``
    contraction, minus the ``rhs_dilation``). Executed MACs == the
    model's dw count exactly."""
    n, hx, wx, cin = x.shape
    _, ho, wo, cout = g.shape
    kh, kw = k_spatial
    sh, sw = stride
    (lo_h, hi_h), (lo_w, hi_w) = pads
    _, oh, mh = _grid(hx, lo_h, hi_h, kh, sh)
    _, ow, mw = _grid(wx, lo_w, hi_w, kw, sw)
    assert (oh, ow) == (ho, wo), ((oh, ow), (ho, wo))
    res_dtype = preferred_element_type or x.dtype

    # pad: conv padding, then up to the next stride multiple so the
    # phase slice is a plain reshape+index
    xt = jnp.pad(x, ((0, 0),
                     (lo_h, mh * sh - hx - lo_h),
                     (lo_w, mw * sw - wx - lo_w),
                     (0, 0)))
    xt = xt.reshape(n, mh, sh, mw, sw, cin)

    dw = jnp.zeros((kh, kw, cin, cout), res_dtype)
    for ph in range(sh):
        kph = len(range(ph, kh, sh))
        if kph == 0:
            continue
        for pw in range(sw):
            kpw = len(range(pw, kw, sw))
            if kpw == 0:
                continue
            xphase = xt[:, :, ph, :, pw, :]     # (N, Mh, Mw, Cin)
            dw_p = jax.lax.conv_general_dilated(
                xphase, g, window_strides=(1, 1),
                padding=((0, ho - 1 + kph - mh),
                         (0, wo - 1 + kpw - mw)),
                dimension_numbers=("CHWN", "IHWO", "HWNC"),
                preferred_element_type=preferred_element_type)
            dw = dw.at[ph::sh, pw::sw, :, :].set(
                dw_p.astype(res_dtype))
    return dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv2d(x, w, stride, pads, use_phase):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=stride, padding=pads,
        dimension_numbers=_DN)


def _conv2d_fwd(x, w, stride, pads, use_phase):
    return _conv2d(x, w, stride, pads, use_phase), (x, w)


def _conv2d_bwd(stride, pads, use_phase, res, g):
    x, w = res
    if use_phase:
        invocations["bwd_phase"] += 1
        dx = phase_dx(g, w, x.shape[1:3], stride, pads)
        dw = phase_dw(x, g, w.shape[:2], stride, pads)
    else:
        invocations["bwd_ref"] += 1
        # jax's own transpose rule (dilated operands) for A/B
        _, vjp = jax.vjp(
            lambda xx, ww: jax.lax.conv_general_dilated(
                xx, ww, window_strides=stride, padding=pads,
                dimension_numbers=_DN), x, w)
        dx, dw = vjp(g)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_conv2d.defvjp(_conv2d_fwd, _conv2d_bwd)


def conv2d(x: jnp.ndarray, w: jnp.ndarray,
           stride: Union[int, Tuple[int, int]] = (1, 1),
           padding="SAME", *,
           phase_bwd: Optional[bool] = None) -> jnp.ndarray:
    """NHWC/HWIO 2-D conv whose backward never materializes a
    dilated operand (gated): forward is a plain
    `lax.conv_general_dilated`; the custom VJP computes dx/dw via
    :func:`phase_dx`/:func:`phase_dw` when the phase backward is on
    (``phase_bwd=None`` resolves :func:`phase_bwd_enabled` at trace
    time; pass True/False for an in-process A/B, e.g.
    scripts/measure_fused.py section E). Groups and kernel dilation
    are not supported — callers gate on that."""
    if isinstance(stride, int):
        stride = (stride, stride)
    stride = tuple(int(s) for s in stride)
    pads = normalize_padding(padding, x.shape[1:3], w.shape[:2],
                             stride)
    if phase_bwd is None:
        phase_bwd = phase_bwd_enabled()
    invocations["conv2d"] += 1
    return _conv2d(x, w, stride, pads, bool(phase_bwd))
