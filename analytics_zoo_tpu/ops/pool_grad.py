"""Mask-based backward for 2-D max pooling.

jax differentiates `reduce_window(max)` through XLA's
`select_and_scatter`, a sequential window scan. The backward here,
the default of every `MaxPooling2D`, is dense vector work instead
(23.3% of the ResNet-50 step's device time on the v5e, PERF.md §5;
it has not been paired against `select_and_scatter` on the chip:
ROADMAP D12): re-extract the k^2 strided window patches of the (padded)
input, mask each against the pooled output (``patch == y``), and
distribute the cotangent by mask / tie-count — k^2 compares, one
count, k^2 pad-shifted adds, all trivially fusable element-wise HLO.

Tie semantics differ from XLA on purpose: `select_and_scatter`
routes the whole cotangent to the FIRST max in scan order; the mask
backward splits it EQUALLY among tied maxima (count-normalized), a
valid subgradient either way (ties have measure zero under
continuous inputs; tests pin the split behaviour explicitly).

``ZOO_TPU_MAXPOOL_MASK_BWD=0`` reverts to jax's select_and_scatter
backward (read at trace time).
"""

from __future__ import annotations

import functools
import os
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

# test observability: traces through each side of the custom VJP
invocations = {"fwd": 0, "bwd_mask": 0}


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


def mask_bwd_enabled() -> bool:
    """Whether MaxPooling2D routes through the mask backward
    (default on; ``ZOO_TPU_MAXPOOL_MASK_BWD=0`` reverts to the
    select_and_scatter transpose rule)."""
    return os.environ.get("ZOO_TPU_MAXPOOL_MASK_BWD") != "0"


@jax.named_scope("zoo:pool/maxpool")
def _reduce_max(x, window, strides, pads4):
    init = jnp.array(-jnp.inf, x.dtype)
    return jax.lax.reduce_window(
        x, init, jax.lax.max,
        (1,) + window + (1,), (1,) + strides + (1,), pads4)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _maxpool2d(x, window, strides, pads):
    return _reduce_max(x, window, strides, ((0, 0),) + pads +
                       ((0, 0),))


def _maxpool2d_fwd(x, window, strides, pads):
    y = _maxpool2d(x, window, strides, pads)
    return y, (x, y)


@jax.named_scope("zoo:pool/maxpool_bwd")
def _maxpool2d_bwd(window, strides, pads, res, g):
    x, y = res
    invocations["bwd_mask"] += 1
    kh, kw = window
    sh, sw = strides
    (lo_h, hi_h), (lo_w, hi_w) = pads
    n, hx, wx, c = x.shape
    ho, wo = y.shape[1], y.shape[2]
    ht, wt = hx + lo_h + hi_h, wx + lo_w + hi_w
    f32 = jnp.float32

    # -inf padding never ties with a window max (every SAME window
    # overlaps at least one real element)
    xt = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)),
                 constant_values=-jnp.inf)

    # strided window patches: patch[kh,kw][p, q] = xt[s*p+kh, s*q+kw]
    masks = []
    for dh in range(kh):
        for dw in range(kw):
            patch = jax.lax.slice(
                xt, (0, dh, dw, 0),
                (n, dh + (ho - 1) * sh + 1, dw + (wo - 1) * sw + 1,
                 c),
                (1, sh, sw, 1))
            masks.append((patch == y).astype(f32))
    count = sum(masks)                  # >= 1: the max is in-window
    gn = g.astype(f32) / count          # equal split among ties

    # scatter-back built from pure pads (no scatter op): zero-
    # interleave each contribution to stride spacing, shift by the
    # window offset (lax.pad accepts the negative high pads where
    # the window overhangs), and sum
    dxt = jnp.zeros((n, ht, wt, c), f32)
    i = 0
    for dh in range(kh):
        for dw in range(kw):
            v = masks[i] * gn
            i += 1
            v6 = v[:, :, None, :, None, :]
            v6 = jnp.pad(v6, ((0, 0), (0, 0), (0, sh - 1), (0, 0),
                              (0, sw - 1), (0, 0)))
            vz = v6.reshape(n, ho * sh, wo * sw, c)
            dxt = dxt + jax.lax.pad(
                vz, jnp.array(0.0, f32),
                ((0, 0, 0), (dh, ht - ho * sh - dh, 0),
                 (dw, wt - wo * sw - dw, 0), (0, 0, 0)))
    dx = dxt[:, lo_h:lo_h + hx, lo_w:lo_w + wx, :]
    return (dx.astype(x.dtype),)


_maxpool2d.defvjp(_maxpool2d_fwd, _maxpool2d_bwd)


def maxpool2d(x: jnp.ndarray, pool_size: Tuple[int, int],
              strides: Tuple[int, int], padding) -> jnp.ndarray:
    """NHWC 2-D max pool whose backward is the mask/count
    distribution above instead of `select_and_scatter`. Forward is
    the identical `lax.reduce_window` the plain path emits; float
    dtypes only (the -inf padding and tie-count need them)."""
    window = tuple(int(p) for p in pool_size)
    strides = tuple(int(s) for s in strides)
    pads = normalize_padding(padding, x.shape[1:3], window, strides)
    invocations["fwd"] += 1
    return _maxpool2d(x, window, strides, pads)
