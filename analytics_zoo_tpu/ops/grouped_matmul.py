"""Grouped matrix product of rows sorted by group, as one Pallas TPU
kernel (`zoo_grouped_matmul`): the routed experts' products of
`layers/moe.py::GroupLimitedMoE`.

``out[r] = x[r] @ w[g]`` for every row ``r`` of group ``g``: the rows
come sorted by group, ``sizes[g]`` of them a group, and rows past the
last group are left alone. The kernel streams each group's weights
from HBM once and all of that group's rows through them:

- grid (column tile, visit). A *visit* is one (group, row tile) pair
  that holds rows of the group, in the order of the rows; the visits'
  table reaches the kernel by scalar prefetch (`_visits`, computed in
  XLA from ``sizes``). Consecutive visits of one group name the same
  weight block, which Pallas then does not fetch again; a group with
  no rows has no visit, so its weights are neither read nor used.
- a row tile that two groups share is visited once for each, and
  each visit writes only its own group's rows of the resident output
  block (the block is written back when the next visit moves on);
- the grid has the static length the worst case needs (``rows /
  tile + groups - 1`` visits); the steps past the last visit repeat
  its blocks, so they fetch nothing, and compute nothing.

Numerics are the grouped product's: operands in their own dtype, f32
accumulation, the result in the rows' dtype.

The entry `grouped_matmul` picks its tiles from the static shapes
(`grouped_tiles`) and calls `_grouped_matmul`, a module-level
`jax.jit`: every call of one shape in a program shares one lowered
function, so a program holds one Mosaic body per distinct product
shape and not one per layer. On non-TPU backends the same kernel runs
under the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.common.device import on_tpu

# a weight block larger than this is split into column tiles (two of
# them are in VMEM at once, the one in use and the next)
_WEIGHT_BLOCK_BYTES = 16 << 20


def grouped_tiles(m: int, k: int, n: int, groups: int,
                  itemsize: int) -> "tuple[int, int]":
    """(row tile, column tile) of a grouped product of ``m`` rows of
    ``k`` into ``n`` over ``groups`` groups of ``itemsize``-byte
    weights.

    Rows: the power of two in [16, 128] nearest below ``m / groups``,
    the rows a group would get if every row fell on one of these
    groups (16: a bf16 sublane tile; 128: the MXU's edge). A visit
    costs its group's weights pushed through the MXU whatever its
    rows, so a tile near a group's rows keeps both the visits and the
    padded rows few: 16 for the decode steps' 64-128 rows over 16-40
    experts, 32 for the Nemotron step's 576 over 16, 128 for a
    2048-token block. Then halved until it divides ``m``, which the
    caller pads to 16.

    Columns: all ``n`` while a (k, n) weight block is at most 16 MiB,
    as every expert of the four configurations is; else the widest
    multiple of 128 dividing ``n`` whose block is."""
    tm = 16
    while tm < 128 and 2 * tm * groups <= m:
        tm *= 2
    while m % tm:
        tm //= 2
    tn = n
    if k * n * itemsize > _WEIGHT_BLOCK_BYTES:
        fit = [c for c in range(128, n, 128)
               if n % c == 0 and k * c * itemsize <= _WEIGHT_BLOCK_BYTES]
        tn = max(fit, default=n)
    return tm, tn


@functools.partial(jax.jit, static_argnames=("tm", "steps"))
def _visits(sizes: jnp.ndarray, tm: int, steps: int):
    """The visits' table: ``(group, tile, lo, hi)``, each (steps,)
    int32, visit ``v`` multiplying rows ``[lo[v], hi[v])`` of row tile
    ``tile[v]`` by the weights of ``group[v]``. Past the last visit:
    its group and tile again and an empty range.

    Every engine program lowers it at each set-up, so it is a `jax.jit`
    of its own (the products of a layer, which share their rows'
    grouping, share one lowered table) written in `jax.lax` calls with
    no gather and no array operator: an operator or an index goes
    through a jitted `jnp` function whose tracing and lowering cost
    several times the primitive's."""
    lax = jax.lax
    groups = sizes.shape[0]
    full = lambda shape, x: lax.full(shape, x, jnp.int32)
    # (groups,) -> (steps, groups)
    row = lambda a: lax.broadcast(a, (steps,))
    ends = lax.cumsum(sizes)
    starts = lax.sub(ends, sizes)
    first = lax.div(starts, full((groups,), tm))
    last = lax.div(lax.sub(ends, full((groups,), 1)), full((groups,), tm))
    count = lax.select(lax.gt(sizes, full((groups,), 0)),
                       lax.add(lax.sub(last, first), full((groups,), 1)),
                       full((groups,), 0))
    upto = lax.cumsum(count)
    begin = lax.sub(upto, count)
    # visit v belongs to group g where it falls in [begin[g], upto[g])
    v = lax.broadcasted_iota(jnp.int32, (steps, groups), 0)
    sel = lax.bitwise_and(lax.ge(v, row(begin)), lax.lt(v, row(upto)))
    zeros = full((steps, groups), 0)
    pick = lambda a: lax.reduce_sum(lax.select(sel, a, zeros), (1,))
    group = pick(lax.broadcasted_iota(jnp.int32, (steps, groups), 1))
    tile = pick(lax.add(v, row(lax.sub(first, begin))))
    edge = lax.mul(tile, full((steps,), tm))
    lo = lax.max(pick(row(starts)), edge)
    hi = lax.min(pick(row(ends)), lax.add(edge, full((steps,), tm)))
    real = lax.reduce_or(sel, (1,))
    # groups and tiles only grow along the visits: the last real
    # visit's are the largest
    keep = lambda a: lax.select(real, a, lax.broadcast(
        lax.reduce_max(a, (0,)), (steps,)))
    none = full((steps,), 0)
    return (keep(group), keep(tile), lax.select(real, lo, none),
            lax.select(real, hi, none))


def _kernel(group_ref, tile_ref, lo_ref, hi_ref, x_ref, w_ref, o_ref, *,
            lanes_k: bool):
    """One visit: ``x_ref`` (tm, k) the row tile, ``w_ref`` the
    group's column tile, (k, tn) or, ``lanes_k``, (tn, k); ``o_ref``
    (tm, tn), whose rows outside ``[lo, hi)`` keep what the block
    holds. In `jax.lax` calls, as `_visits` is, for what it costs to
    trace."""
    del group_ref
    lax = jax.lax
    v = pl.program_id(1)
    lo, hi = lo_ref[v], hi_ref[v]

    @pl.when(lax.lt(lo, hi))
    def _visit():
        tm, tn = o_ref.shape
        # the visit's rows, relative to the tile's first
        first = lax.mul(tile_ref[v], jnp.int32(tm))
        at = lambda s: lax.broadcast(lax.sub(s, first), (tm, tn))
        r = lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = lax.bitwise_and(lax.ge(r, at(lo)), lax.lt(r, at(hi)))
        y = lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (1 if lanes_k else 0,)),
                                     ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = lax.select(mine, lax.convert_element_type(
            y, o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _grouped_matmul(x, w, sizes, *, tm: int, tn: int, interpret: bool):
    m, k = x.shape
    groups, _, n = w.shape
    # the weights as the chip lays them out: an array whose last dim is
    # no whole number of lane tiles while the one before is, the
    # compiler keeps with that one minor; the kernel then takes the
    # transposed view, which is the same bytes, and contracts it on
    # its lanes (otherwise the call would copy every expert first)
    lanes_k = bool(n % 128 and not k % 128)
    if lanes_k:
        w = jnp.swapaxes(w, 1, 2)
        w_block = pl.BlockSpec((None, tn, k),
                               lambda j, v, g, t, *_: (g[v], j, 0))
    else:
        w_block = pl.BlockSpec((None, k, tn),
                               lambda j, v, g, t, *_: (g[v], 0, j))
    steps = m // tm + groups - 1
    table = _visits(sizes.astype(jnp.int32), tm, steps)
    item = jnp.dtype(x.dtype).itemsize
    # the pipeline's two buffers of each block and the f32 product
    vmem = 2 * (tm * k + k * tn + tm * tn) * item + tm * tn * 4
    return pl.pallas_call(
        functools.partial(_kernel, lanes_k=lanes_k),
        name="zoo_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, steps),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda j, v, g, t, *_: (t[v], 0)),
                      w_block],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, g, t, *_: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem) + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + groups * k * n + m * n) * item),
        interpret=interpret,
    )(*table, x, w)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray,
                   sizes: jnp.ndarray) -> jnp.ndarray:
    """``x`` (M, K) rows sorted by group, ``w`` (G, K, N), ``sizes``
    (G,) int32 rows a group: (M, N) in ``x``'s dtype, row ``r`` of
    group ``g`` being ``x[r] @ w[g]`` (f32 accumulation). Rows past
    ``sum(sizes)`` are left undefined: the caller masks them. What
    `jax.lax.ragged_dot(x, w, sizes)` computes, as one kernel."""
    m, k = x.shape
    pad = -m % 16
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    tm, tn = grouped_tiles(m + pad, k, w.shape[2], w.shape[0],
                           jnp.dtype(w.dtype).itemsize)
    out = _grouped_matmul(x, w.astype(x.dtype), sizes, tm=tm, tn=tn,
                          interpret=not on_tpu())
    return out[:m] if pad else out
