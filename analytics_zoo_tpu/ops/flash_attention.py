"""Pallas TPU flash-attention kernel.

The native-kernel tier for the attention hot path (SURVEY.md §2.11:
the reference's per-layer perf tier is MKL/MKL-DNN JNI kernels, e.g.
`TransformerLayer.scala`/`BERT.scala` bottoming out in BigDL MKL; the
TPU analog is XLA + Pallas). XLA already fuses the dense O(T²)
attention well, but it materialises the (B, H, Tq, Tk) logits in HBM;
this kernel keeps the running softmax statistics in VMEM so HBM
traffic stays O(T·D) — the flash-attention recipe tiled for the MXU
(128-lane blocks, f32 accumulators, bf16 matmul inputs).

Forward and backward are both Pallas kernels: the backward follows
the FlashAttention-2 recipe — the forward saves only the per-row
logsumexp, and two kernels (dk/dv over q-blocks, dq over k-blocks)
recompute the probabilities blockwise in VMEM — so gradient memory
stays O(T·D) too (measured: 3.72x over XLA dense fwd+bwd at T=4096
bf16, and grads at T=8192 where dense OOMs; `parallel.ring_attention`
owns the sharded longer-T regime).

On non-TPU backends the same kernel runs under `interpret=True`
(numerics identical, speed irrelevant) so the CPU test mesh exercises
the exact kernel code path.

Beside the family, `paged_decode_partial` (`zoo_paged_decode`):
single-query decode attention straight from the live pages of a paged
K/V pool, sharing the family's softmax recursion
(`_softmax_accumulate`), and `paged_gqa_decode_partial`
(`zoo_paged_gqa_decode`): the same loop (`_paged_attend`) for
grouped-query heads over one pool of ``[K | V]`` rows, from a lower
edge on for a sliding layer's ring.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.common.device import on_tpu
from analytics_zoo_tpu.perf import autotune

_NEG_INF = -1e30

# Incremented (at trace time) on every flash_attention /
# flash_block_partial entry, so tests can assert that a given API
# call actually routed to the Pallas kernel.
invocations = 0


def _apply_causal_mask(s, qi, ki, off, block_q, block_k,
                       fill=_NEG_INF):
    """End-aligned causal mask (query i sees keys <= i + off) on one
    (block_q, block_k) tile — the single copy of the masking rule,
    shared by forward and backward (`fill=0.0` masks gradient tiles
    the way the dense reference's `where` cuts grads at masked
    positions)."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos + off >= k_pos, s, fill)


def _softmax_accumulate(s, v, acc_ref, m_ref, l_ref):
    """One step of the flash recursion: fold the masked, scaled f32
    scores ``s`` (rows, keys) of a key block and its values ``v``
    (keys, D) into the running statistics. The single copy, shared by
    `_attn_body` and the paged decode kernel (rows = heads, D = the
    pool's row there)."""
    m_prev = m_ref[:, :1]                    # (rows, 1)
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)          # rescale old accumulator
    p = jnp.exp(s - m_new)                   # (rows, keys) f32
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _attn_body(off, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
               scale: float, causal: bool, block_q: int, block_k: int,
               kmask_ref=None):
    """Shared init + blockwise-softmax accumulation for one
    (batch, head, q-block, k-block) grid step — the single copy of the
    flash recursion used by both `_fwd_kernel` and `_block_kernel`
    (they differ only in how `off` is sourced and what the last k step
    writes).

    `off`: causal offset (int, static or traced) — end-aligned like
    the dense reference's tril(k=Tk-Tq): query i sees keys <= i + off.
    `kmask_ref`: optional key-validity block ref, (1, 8, block_k) f32
    0/1 replicated over the sublane dim (TPU tiling needs the
    second-to-last block dim divisible by 8) — keys with 0 are masked
    for every query row (the BERT padding-mask shape (B, 1, 1, Tk)).

    Scratch (VMEM, persistent across the innermost `k` grid dim):
      acc_ref (block_q, D) f32   un-normalised output accumulator
      m_ref   (block_q, 128) f32 running row max (lanes replicated)
      l_ref   (block_q, 128) f32 running softmax denominator
    """
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    qi = pl.program_id(2)
    # the whole k-block is masked iff its first key position exceeds
    # the q-block's last query position — skip it entirely
    run = (ki * block_k <=
           qi * block_q + (block_q - 1) + off) if causal else (ki >= 0)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                      # (block_q, D)
        k = k_ref[0, 0]                      # (block_k, D)
        v = v_ref[0, 0]                      # (block_k, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _apply_causal_mask(s, qi, ki, off, block_q, block_k)
        if kmask_ref is not None:
            s = jnp.where(kmask_ref[0][:1, :] > 0, s, _NEG_INF)

        _softmax_accumulate(s, v, acc_ref, m_ref, l_ref)


def _fwd_finalize(o_ref, acc_ref, l_ref):
    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _final():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[:] /
                       jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                causal_offset: int):
    """Self-contained flash forward: normalised output, static offset."""
    _attn_body(causal_offset, q_ref, k_ref, v_ref, acc_ref, m_ref,
               l_ref, scale=scale, causal=causal, block_q=block_q,
               block_k=block_k)
    _fwd_finalize(o_ref, acc_ref, l_ref)


def _fwd_kernel_masked(q_ref, k_ref, v_ref, km_ref, o_ref,
                       acc_ref, m_ref, l_ref, *,
                       scale: float, causal: bool, block_q: int,
                       block_k: int, causal_offset: int):
    """`_fwd_kernel` + key-validity mask input."""
    _attn_body(causal_offset, q_ref, k_ref, v_ref, acc_ref, m_ref,
               l_ref, scale=scale, causal=causal, block_q=block_q,
               block_k=block_k, kmask_ref=km_ref)
    _fwd_finalize(o_ref, acc_ref, l_ref)


def _kmask8(key_mask, tk):
    """(B, Tk) 0/1 → (B, 8, Tk) f32, sublane-replicated for tiling."""
    km = jnp.asarray(key_mask).astype(jnp.float32)
    return jnp.broadcast_to(km[:, None, :], (km.shape[0], 8, tk))


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               key_mask=None):
    """q,k,v: (B, H, T, D) — head-major layout for contiguous blocks.
    `key_mask`: optional (B, Tk) 0/1 key-validity mask."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k
    cfg = dict(scale=scale, causal=causal, block_q=block_q,
               block_k=block_k, causal_offset=tk - tq)
    blk = lambda bs, im: pl.BlockSpec((1, 1, bs, d), im)
    in_specs = [
        blk(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        blk(block_k, lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        blk(block_k, lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
    ]
    args = [q, k, v]
    if key_mask is None:
        kernel = functools.partial(_fwd_kernel, **cfg)
    else:
        kernel = functools.partial(_fwd_kernel_masked, **cfg)
        in_specs.append(pl.BlockSpec(
            (1, 8, block_k), lambda bi, hi, qi, ki: (bi, 0, ki)))
        args.append(_kmask8(key_mask, tk))
    return pl.pallas_call(
        kernel,
        name="zoo_flash_fwd",
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=blk(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*args)


def _recompute_p(q_blk, k_blk, m_col, l_col, qi, ki, off, scale,
                 causal, block_q, block_k, km_ref=None):
    """Recompute the softmax probabilities of one (q-block, k-block)
    tile from the saved row statistics — shared by both backward
    kernels. p = exp(s - m)/l, NOT exp(s - (m + log l)): the fused
    logsumexp catastrophically absorbs log(l) when m = -1e30
    (fully-masked causal rows), yielding p = 1 per key instead of the
    forward's uniform 1/l and overscaling those rows' gradients by Tk.
    `m_col`, `l_col`: (block_q, 1) f32."""
    s = jax.lax.dot_general(
        q_blk, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        s = _apply_causal_mask(s, qi, ki, off, block_q, block_k)
    if km_ref is not None:
        s = jnp.where(km_ref[0][:1, :] > 0, s, _NEG_INF)
    return jnp.exp(s - m_col) / jnp.maximum(l_col, 1e-30)


def _mask_ds(ds, qi, ki, off, causal, block_q, block_k, km_ref):
    """Zero ds at masked positions: the dense reference's where-mask
    passes no gradient there; fully-masked rows have NONZERO uniform p
    (it feeds dv like the dense path) but must not leak into dq/dk."""
    if causal:
        ds = _apply_causal_mask(ds, qi, ki, off, block_q, block_k,
                                fill=0.0)
    if km_ref is not None:
        ds = jnp.where(km_ref[0][:1, :] > 0, ds, 0.0)
    return ds


def _bwd_dkdv_impl(q_ref, k_ref, v_ref, do_ref, m_in_ref, l_in_ref,
                   delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                   scale: float, causal: bool, block_q: int,
                   block_k: int, causal_offset: int, km_ref=None):
    """Grid (B, H, nk, nq): each k-block accumulates dk/dv over all
    q-blocks. delta = rowsum(do ⊙ o) (precomputed outside)."""
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    ki = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (qi * block_q + (block_q - 1) + causal_offset >=
           ki * block_k) if causal else (qi >= 0)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                      # (block_q, D)
        k = k_ref[0, 0]                      # (block_k, D)
        v = v_ref[0, 0]
        do = do_ref[0, 0]                    # (block_q, D)
        p = _recompute_p(q, k, m_in_ref[0, 0][:, :1],
                         l_in_ref[0, 0][:, :1], qi, ki,
                         causal_offset, scale, causal, block_q,
                         block_k, km_ref=km_ref)
        # dv += pᵀ·do ; dp = do·vᵀ ; ds = p⊙(dp − Δ)·scale ; dk += dsᵀ·q
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, :1]) * scale
        ds = _mask_ds(ds, qi, ki, causal_offset, causal, block_q,
                      block_k, km_ref)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _final():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, m_in_ref, l_in_ref,
                     delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                     **cfg):
    _bwd_dkdv_impl(q_ref, k_ref, v_ref, do_ref, m_in_ref, l_in_ref,
                   delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, **cfg)


def _bwd_dkdv_kernel_masked(q_ref, k_ref, v_ref, do_ref, km_ref,
                            m_in_ref, l_in_ref, delta_ref,
                            dk_ref, dv_ref, dk_acc, dv_acc, **cfg):
    _bwd_dkdv_impl(q_ref, k_ref, v_ref, do_ref, m_in_ref, l_in_ref,
                   delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                   km_ref=km_ref, **cfg)


def _bwd_dq_impl(q_ref, k_ref, v_ref, do_ref, m_in_ref, l_in_ref,
                 delta_ref, dq_ref, dq_acc, *,
                 scale: float, causal: bool,
                 block_q: int, block_k: int, causal_offset: int,
                 km_ref=None):
    """Grid (B, H, nq, nk): each q-block accumulates dq over k-blocks."""
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (ki * block_k <=
           qi * block_q + (block_q - 1) + causal_offset) if causal \
        else (ki >= 0)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        p = _recompute_p(q, k, m_in_ref[0, 0][:, :1],
                         l_in_ref[0, 0][:, :1], qi, ki,
                         causal_offset, scale, causal, block_q,
                         block_k, km_ref=km_ref)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, :1]) * scale
        ds = _mask_ds(ds, qi, ki, causal_offset, causal, block_q,
                      block_k, km_ref)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _final():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, m_in_ref, l_in_ref,
                   delta_ref, dq_ref, dq_acc, **cfg):
    _bwd_dq_impl(q_ref, k_ref, v_ref, do_ref, m_in_ref, l_in_ref,
                 delta_ref, dq_ref, dq_acc, **cfg)


def _bwd_dq_kernel_masked(q_ref, k_ref, v_ref, do_ref, km_ref,
                          m_in_ref, l_in_ref, delta_ref, dq_ref,
                          dq_acc, **cfg):
    _bwd_dq_impl(q_ref, k_ref, v_ref, do_ref, m_in_ref, l_in_ref,
                 delta_ref, dq_ref, dq_acc, km_ref=km_ref, **cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, key_mask, scale, causal, block_q, block_k,
           interpret):
    """`key_mask`: (B, Tk) 0/1 f32 or an all-ones dummy when the
    static `masked` bit of the caller is off (it is a diff arg so it
    can be traced; its gradient is defined as zeros)."""
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                      interpret,
                      key_mask=key_mask if key_mask.ndim == 2 else None)


def _flash_vjp_fwd(q, k, v, key_mask, scale, causal, block_q, block_k,
                   interpret):
    # run the partials kernel (unnormalised acc + m/l) so the row
    # statistics needed by the Pallas backward come out of the same
    # pass; normalise outside — same math as _fwd_kernel's in-kernel
    # divide, one extra O(T·D) HBM round-trip at trace-under-grad only
    tk, tq = k.shape[2], q.shape[2]
    km = key_mask if key_mask.ndim == 2 else None
    acc, m, l = _block_partials(q, k, v, tk - tq, causal, scale,
                                block_q, block_k, interpret,
                                key_mask=km)
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out, (q, k, v, key_mask, out, m, l)


def _flash_vjp_bwd(scale, causal, block_q, block_k, interpret, res, g):
    """FlashAttention-2 backward as two Pallas kernels (dk/dv then dq);
    probabilities are recomputed blockwise from the saved row
    statistics, so grad-time memory stays O(T·D) like the forward."""
    q, k, v, key_mask, out, m, l = res
    b, h, tq, d = q.shape
    tk = k.shape[2]
    masked = key_mask.ndim == 2
    do = g.astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                 # (B, H, Tq)
    # lanes-replicated (B, H, Tq, 128) rows — see _block_kernel._final
    lanes = (b, h, tq, 128)
    m_r = jnp.broadcast_to(m[..., None], lanes)
    l_r = jnp.broadcast_to(l[..., None], lanes)
    delta_r = jnp.broadcast_to(delta[..., None], lanes)
    off = tk - tq
    blk = lambda bs, im: pl.BlockSpec((1, 1, bs, d), im)
    row = lambda bs, im: pl.BlockSpec((1, 1, bs, 128), im)
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, causal_offset=off)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))
    km8 = _kmask8(key_mask, tk) if masked else None
    km_spec_kv = pl.BlockSpec((1, 8, block_k),
                              lambda bi, hi, ki, qi: (bi, 0, ki))
    km_spec_q = pl.BlockSpec((1, 8, block_k),
                             lambda bi, hi, qi, ki: (bi, 0, ki))

    in_specs_kv = [
        blk(block_q, lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
        blk(block_k, lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        blk(block_k, lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        blk(block_q, lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
    ] + ([km_spec_kv] if masked else []) + [
        row(block_q, lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
        row(block_q, lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
        row(block_q, lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
    ]
    args_kv = [q, k, v, do] + ([km8] if masked else []) + \
        [m_r, l_r, delta_r]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel_masked if masked else _bwd_dkdv_kernel,
            **common),
        name="zoo_flash_bwd_dkdv",
        grid=(b, h, tk // block_k, tq // block_q),
        in_specs=in_specs_kv,
        out_specs=[
            blk(block_k, lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            blk(block_k, lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(*args_kv)

    in_specs_q = [
        blk(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        blk(block_k, lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        blk(block_k, lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        blk(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
    ] + ([km_spec_q] if masked else []) + [
        row(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        row(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        row(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
    ]
    args_q = [q, k, v, do] + ([km8] if masked else []) + \
        [m_r, l_r, delta_r]
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel_masked if masked else _bwd_dq_kernel,
            **common),
        name="zoo_flash_bwd_dq",
        grid=(b, h, tq // block_q, tk // block_k),
        in_specs=in_specs_q,
        out_specs=blk(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(*args_q)

    return dq, dk, dv, jnp.zeros_like(key_mask)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _block_finalize(o_ref, m_out_ref, l_out_ref, acc_ref, m_ref,
                    l_ref):
    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _final():
        o_ref[0, 0] = acc_ref[:]
        # m/l leave the kernel lanes-replicated at (block_q, 128) — a
        # (1, 1, bq) block over (B, H, T) violates the TPU tiling rule
        # (last two block dims must divide (8, 128) or equal the array
        # dims); (B, H, T, 128) is the official flash kernel's layout
        m_out_ref[0, 0] = m_ref[:]
        l_out_ref[0, 0] = l_ref[:]


def _block_kernel(off_ref, q_ref, k_ref, v_ref,
                  o_ref, m_out_ref, l_out_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool,
                  block_q: int, block_k: int):
    """Partial-softmax block attention: same recursion as
    `_fwd_kernel` (via `_attn_body`) but emits the UNNORMALISED
    accumulator plus running (m, l) statistics, so a caller (ring
    attention, the custom VJP forward) can merge partials or build the
    backward's row statistics.
    `off_ref` (SMEM, (1,1) int32) holds the global causal offset
    q_global_start - k_global_start, which is traced (it depends on
    `lax.axis_index` inside shard_map) and therefore can't be a Python
    static like `_fwd_kernel`'s causal_offset."""
    _attn_body(off_ref[0, 0], q_ref, k_ref, v_ref, acc_ref, m_ref,
               l_ref, scale=scale, causal=causal, block_q=block_q,
               block_k=block_k)
    _block_finalize(o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref)


def _block_kernel_masked(off_ref, q_ref, k_ref, v_ref, km_ref,
                         o_ref, m_out_ref, l_out_ref,
                         acc_ref, m_ref, l_ref, *,
                         scale: float, causal: bool,
                         block_q: int, block_k: int):
    """`_block_kernel` + key-validity mask input."""
    _attn_body(off_ref[0, 0], q_ref, k_ref, v_ref, acc_ref, m_ref,
               l_ref, scale=scale, causal=causal, block_q=block_q,
               block_k=block_k, kmask_ref=km_ref)
    _block_finalize(o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref)


def _block_partials(qt, kt, vt, qk_offset, causal, scale,
                    block_q, block_k, interpret, key_mask=None):
    """Head-major core of `flash_block_partial` (also the forward of
    the custom VJP, which needs the row statistics). qt/kt/vt:
    (B, H, T, D); returns (acc (B, H, Tq, D) f32 unnormalised,
    m (B, H, Tq) f32, l (B, H, Tq) f32)."""
    b, h, tq, d = qt.shape
    tk = kt.shape[2]
    off = jnp.asarray(qk_offset, jnp.int32).reshape(1, 1)
    masked = key_mask is not None
    kernel = functools.partial(
        _block_kernel_masked if masked else _block_kernel,
        scale=scale, causal=causal, block_q=block_q, block_k=block_k)
    blk = lambda bs, im: pl.BlockSpec((1, 1, bs, d), im)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        blk(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        blk(block_k, lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        blk(block_k, lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
    ]
    args = [off, qt, kt, vt]
    if masked:
        in_specs.append(pl.BlockSpec(
            (1, 8, block_k), lambda bi, hi, qi, ki: (bi, 0, ki)))
        args.append(_kmask8(key_mask, tk))
    acc, m, l = pl.pallas_call(
        kernel,
        name="zoo_flash_block_partial",
        grid=(b, h, tq // block_q, tk // block_k),
        in_specs=in_specs,
        out_specs=[
            blk(block_q, lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, tq, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, h, tq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*args)
    return acc, m[..., 0], l[..., 0]


def flash_block_partial(q, k, v, qk_offset, causal: bool, scale: float,
                        interpret: Optional[bool] = None):
    """One flash pass over a K/V block, returning partials for
    cross-block merging (the ring-attention inner op).

    q, k, v: (B, Tq, H, D) / (B, Tk, H, D); `qk_offset` a traced int32
    scalar = q_global_start - k_global_start (causal only). Returns
    (acc (B, Tq, H, D) f32 unnormalised, m (B, H, Tq) f32,
    l (B, H, Tq) f32) with softmax base `m`.
    """
    global invocations
    invocations += 1
    if interpret is None:
        interpret = not on_tpu()
    b, tq, h, d = q.shape
    tk = k.shape[1]
    bq, bk = _pick_blocks(tq, tk, jnp.dtype(q.dtype).itemsize)
    acc, m, l = _block_partials(
        jnp.transpose(q, (0, 2, 1, 3)),
        jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3)),
        qk_offset, causal, scale, bq, bk, interpret)
    return jnp.transpose(acc, (0, 2, 1, 3)), m, l


def flash_decode_attention(q: jnp.ndarray, k: jnp.ndarray,
                           v: jnp.ndarray, key_mask: jnp.ndarray,
                           scale: float,
                           interpret: Optional[bool] = None,
                           k_scales: Optional[jnp.ndarray] = None,
                           v_scales: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """Single-query decode attention over a cached context, as a
    Pallas kernel reusing the flash block machinery.

    q: (S, H, D) — ONE new token per slot; k, v: (S, T, H, D) — the
    dense page-table gather of the cache; key_mask: (S, T) 0/1
    validity (1 = real cached token). Returns (S, H, D).

    Int8 caches (``ZOO_TPU_KV_DTYPE=int8``) pass the gathered views
    still quantized plus per-row scales ``k_scales``/``v_scales``
    (S, T, H): dequant runs here at the kernel's gather boundary, as
    one fused scale-multiply XLA folds into the transposes feeding
    VMEM, so the kernel body itself stays dtype-agnostic (int8's
    (32, 128) native tile would force a different block geometry —
    see the Pallas guide's quantization pattern; not worth it for a
    1-query kernel whose win is HBM traffic, already halved by
    reading int8 pages from HBM).

    The query tile is the kernel's only novelty: TPU blocks need a
    sublane dim divisible by 8, so the single query row is replicated
    to an (8, D) tile and row 0 of the output is taken — the other 7
    rows compute the identical softmax for free (the VPU processes
    8×128 lanes regardless). Everything else IS `_attn_body` +
    `_fwd_finalize` — same accumulation, same masking rule, same
    VMEM scratch — on grid (S, H, 1, nk), causal off (the cache only
    holds visible positions; `key_mask` owns validity). Inference
    only: no VJP is defined (decode never differentiates).
    """
    global invocations
    invocations += 1
    if k_scales is not None:
        from analytics_zoo_tpu.ops import kv_cache as kvc
        k = kvc.dequantize_rows(k, k_scales, q.dtype)
        v = kvc.dequantize_rows(v, v_scales, q.dtype)
    s, h, d = q.shape
    t = k.shape[1]
    _, bk = _pick_blocks(t, t, jnp.dtype(q.dtype).itemsize)
    if bk is None or d > 256:
        raise ValueError(
            f"flash_decode_attention needs T divisible by 128 and "
            f"D <= 256; got T={t} D={d} (use decode_attention's "
            f"dense path)")
    if interpret is None:
        interpret = not on_tpu()
    qt = jnp.broadcast_to(q[:, :, None, :], (s, h, 8, d))
    kt = jnp.transpose(k, (0, 2, 1, 3))      # (S, H, T, D)
    vt = jnp.transpose(v, (0, 2, 1, 3))
    kernel = functools.partial(
        _fwd_kernel_masked, scale=scale, causal=False,
        block_q=8, block_k=bk, causal_offset=0)
    blk = lambda bs, im: pl.BlockSpec((1, 1, bs, d), im)
    out = pl.pallas_call(
        kernel,
        name="zoo_flash_decode",
        grid=(s, h, 1, t // bk),
        in_specs=[
            blk(8, lambda bi, hi, qi, ki: (bi, hi, 0, 0)),
            blk(bk, lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            blk(bk, lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 8, bk),
                         lambda bi, hi, qi, ki: (bi, 0, ki)),
        ],
        out_specs=blk(8, lambda bi, hi, qi, ki: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s, h, 8, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((8, d), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt, _kmask8(key_mask, t))
    return out[:, :, 0]


# -- paged single-query attention -------------------------------------------
# Decode attention straight from the page pools: no dense (S, T, W)
# view of a layer's cache is ever formed. One grid step a slot; inside,
# a loop over blocks of `_PAGED_BLOCK` tokens whose trip count is the
# slot's own length, each block's live pages brought from HBM by one
# async copy a page into double-buffered VMEM (the DMA pattern of jax's
# `pallas/ops/tpu/paged_attention`; the pool keeps this repo's
# `(L, P, page, W)` row-major layout, so a page is one contiguous
# copy). The last block of a slot prefetches the first block of the
# next slot that holds tokens, so only a call's first block waits for
# its pages with nothing to overlap.

# tokens a compute block: one lane tile of scores
_PAGED_BLOCK = 128


def paged_decode_supported(page_size: int, dtype) -> bool:
    """Whether `paged_decode_partial` takes pools of this geometry: a
    floating-point pool whose page is a whole number of the dtype's
    sublane tiles (16 rows for bfloat16, 8 for float32), so that a
    page lands tile-aligned in VMEM and a block of pages is one
    (block, W) matrix with no relayout, and whose pages fill a
    128-token block exactly."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize > 4:
        return False
    return page_size % (32 // dtype.itemsize) == 0 and \
        _PAGED_BLOCK % page_size == 0


def _paged_attend(table_ref, lens_ref, first_ref, layer, pools, bufs,
                  sems, state, acc_ref, m_ref, l_ref, prepare, q_of,
                  kv_of, *, scale: float, pages_per_slot: int):
    """The body the paged decode kernels share: one grid step = one
    slot's single-query attention over the pages it holds, a loop of
    blocks of whole pages copied HBM -> double-buffered VMEM where
    only live pages are fetched, folded into ``acc_ref`` / ``m_ref``
    / ``l_ref`` by `_softmax_accumulate`.

    ``table_ref`` (S * pages_per_slot,) and ``lens_ref`` (S,) in
    SMEM; ``first_ref`` (S,) or None: slot s attends to positions
    ``[first[s], lens[s])`` of its table row (None: from 0), pages
    wholly outside them are never fetched and the loop starts at the
    block that holds ``first[s]``. ``pools``: the HBM pools
    (L, P, page, W_i) a page is copied from, ``bufs`` their
    (2, pages a block, page, W_i) buffers, ``sems`` DMA
    (len(pools), 2). ``state`` SMEM (2,) = (buffer the next block is
    in, whether the previous slot already started this slot's first
    copies), carried from one grid step to the next. ``prepare()``
    runs once before a slot's loop; ``q_of()`` gives the (rows, Wk)
    query matrix and ``kv_of(buffer)`` a block's ``(K (keys, Wk),
    V (keys, Wv))`` inside it."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    n_pool = pools[0].shape[1]
    _, ppb, page, _ = bufs[0].shape
    bk = ppb * page
    hp = acc_ref.shape[0]
    n_tok = lens_ref[s]
    n_blk = jax.lax.div(n_tok + (bk - 1), bk)
    first_of = (lambda slot: first_ref[slot]) if first_ref is not None \
        else (lambda slot: 0)
    lo = first_of(s)

    def copies(slot, blk, buf):
        # block `blk` of `slot`: (is the page live, its copies) a
        # page; pages outside the slot's positions are never fetched
        tok, low = lens_ref[slot], first_of(slot)
        out = []
        for i in range(ppb):
            j = blk * ppb + i
            pid = table_ref[slot * pages_per_slot +
                            jnp.minimum(j, pages_per_slot - 1)]
            pid = jnp.clip(pid, 0, n_pool - 1)
            live = j * page < tok
            if first_ref is not None:
                live = jnp.logical_and(live, (j + 1) * page > low)
            out.append((live, [
                pltpu.make_async_copy(pool.at[layer, pid],
                                      b.at[buf, i], sems.at[n, buf])
                for n, (pool, b) in enumerate(zip(pools, bufs))]))
        return out

    def start(slot, blk, buf):
        for live, cs in copies(slot, blk, buf):
            @pl.when(live)
            def _go():
                for c in cs:
                    c.start()

    def wait(slot, blk, buf):
        for live, cs in copies(slot, blk, buf):
            @pl.when(live)
            def _arrived():
                for c in cs:
                    c.wait()

    @pl.when(s == 0)
    def _first():
        state[0] = 0
        state[1] = 0
        # a value page that is never fetched multiplies probabilities
        # that are exactly 0: it must hold numbers, whatever they are
        bufs[-1][...] = jnp.zeros_like(bufs[-1])

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(n_tok > lo)
    def _attend():
        @pl.when(state[1] == 0)
        def _cold():
            start(s, lo // bk, state[0])

        prepare()

        def block(b, cur):
            nxt = 1 - cur

            @pl.when(b + 1 < n_blk)
            def _next_block():
                start(s, b + 1, nxt)

            @pl.when(b + 1 == n_blk)
            def _next_slot():
                # the first later slot that holds tokens, if any
                s2 = jax.lax.fori_loop(
                    s + 1, n_slots,
                    lambda i, c: jnp.where(
                        jnp.logical_and(c == n_slots,
                                        lens_ref[i] > first_of(i)),
                        i, c),
                    n_slots)

                @pl.when(s2 < n_slots)
                def _prefetch():
                    start(s2, first_of(s2) // bk, nxt)
                state[1] = (s2 < n_slots).astype(jnp.int32)

            wait(s, b, cur)
            k, v = kv_of(cur)
            sc = jax.lax.dot_general(
                q_of(), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            pos = b * bk + jax.lax.broadcasted_iota(
                jnp.int32, (hp, bk), 1)
            seen = pos < n_tok
            if first_ref is not None:
                seen = jnp.logical_and(seen, pos >= lo)
            sc = jnp.where(seen, sc, _NEG_INF)
            _softmax_accumulate(sc, v, acc_ref, m_ref, l_ref)
            return nxt

        state[0] = jax.lax.fori_loop(lo // bk, n_blk, block, state[0])


def _paged_decode_kernel(table_ref, lens_ref, layer_ref,
                         q_ref, k_hbm, v_hbm,
                         o_ref, m_out_ref, l_out_ref,
                         k_buf, v_buf, sems, q_bd, acc_ref, m_ref,
                         l_ref, state, *, scale: float, head_dim: int,
                         pages_per_slot: int):
    """One slot's attention over its cached pages, heads side by side
    (`_paged_attend` over a K and a V pool of one shape).

    Scalar prefetch (SMEM): the page table flattened
    (S * pages_per_slot,), ``seq_lens`` (S,), the layer (1,). ``q_ref``
    (1, 1, W): the slot's query row as the pool lays a row out;
    ``k_hbm``/``v_hbm`` (L, P, page, W) stay in HBM. The query becomes
    a block-diagonal (HP, W) matrix (row h holds head h's ``head_dim``
    values in its own columns, zeros elsewhere), so ``Q_bd @ K_blk^T``
    is every head's scores against a block of whole rows and
    ``P @ V_blk`` an (HP, W) partial whose diagonal blocks are the
    heads' outputs: `_softmax_accumulate` with D = W, for any
    (heads, head_dim). Writes the unnormalised output row (1, 1, W)
    f32 and the lanes-replicated statistics (1, HP, 128); a slot with
    no cached token writes o = 0, m = -1e30, l = 0.

    Scratch: ``k_buf``/``v_buf`` (2, pages a block, page, W), ``sems``
    DMA (2, 2) = (pool, buffer), ``q_bd`` (HP, W), the accumulators,
    and ``state`` SMEM (2,)."""
    _, ppb, page, w = k_buf.shape
    bk = ppb * page
    hp = q_bd.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (hp, w), 0) * head_dim
    cols = jax.lax.broadcasted_iota(jnp.int32, (hp, w), 1)
    diag = jnp.logical_and(cols >= rows, cols < rows + head_dim)

    def prepare():
        q = jnp.broadcast_to(q_ref[0].astype(jnp.float32), (hp, w))
        q_bd[:] = jnp.where(diag, q, 0.0).astype(q_bd.dtype)

    _paged_attend(
        table_ref, lens_ref, None, layer_ref[0], (k_hbm, v_hbm),
        (k_buf, v_buf), sems, state, acc_ref, m_ref, l_ref, prepare,
        lambda: q_bd[:],
        lambda cur: (k_buf[cur].reshape(bk, w),
                     v_buf[cur].reshape(bk, w)),
        scale=scale, pages_per_slot=pages_per_slot)
    o_ref[0] = jnp.sum(jnp.where(diag, acc_ref[:], 0.0), axis=0,
                       keepdims=True)
    m_out_ref[0] = m_ref[:]
    l_out_ref[0] = l_ref[:]


def _paged_gqa_kernel(table_ref, lens_ref, first_ref, layer_ref,
                      q_ref, rows_hbm, o_ref, m_out_ref, l_out_ref,
                      buf, sems, acc_ref, m_ref, l_ref, state, *,
                      scale: float, k_width: int,
                      pages_per_slot: int):
    """`_paged_attend` for grouped-query heads over ONE pool whose
    row is ``[K of the G heads (k_width) | V of the G heads | pad]``.
    ``q_ref`` (1, HP, k_width): the slot's queries laid out
    block-diagonally outside (row j holds query head j's values in
    the columns of its K/V head), so ``Q @ K_blk^T`` is every query
    head's scores against the rows' K part and ``P @ V_blk`` an
    (HP, v_width) partial in which head j's output is its K/V head's
    columns: written whole, ``o_ref`` (1, HP, v_width) f32, and cut
    by the caller. ``first_ref``: a sliding layer's lower edge."""
    _, ppb, page, _ = buf.shape
    bk = ppb * page
    wv = acc_ref.shape[1]
    _paged_attend(
        table_ref, lens_ref, first_ref, layer_ref[0], (rows_hbm,),
        (buf,), sems, state, acc_ref, m_ref, l_ref, lambda: None,
        lambda: q_ref[0],
        lambda cur: (
            buf[cur, :, :, :k_width].reshape(bk, k_width),
            buf[cur, :, :, k_width:k_width + wv].reshape(bk, wv)),
        scale=scale, pages_per_slot=pages_per_slot)
    o_ref[0] = acc_ref[:]
    m_out_ref[0] = m_ref[:]
    l_out_ref[0] = l_ref[:]


def paged_decode_partial(q_rows: jnp.ndarray, k_pages: jnp.ndarray,
                         v_pages: jnp.ndarray, page_table: jnp.ndarray,
                         seq_lens: jnp.ndarray, layer, *, heads: int,
                         head_dim: int, scale: float,
                         interpret: Optional[bool] = None):
    """Single-query attention of every slot over the pages it holds,
    read where they lie.

    ``q_rows`` (S, W): each slot's query, heads side by side and
    zero-padded as a pool row is (`ops.kv_cache`); ``k_pages`` /
    ``v_pages`` (L, P, page_size, W), the stacked pools, never copied
    (`memory_space=pl.ANY`: inside a layer scan they stay
    loop-invariant); ``page_table`` (S, pages_per_slot), ``seq_lens``
    (S,) and the scalar ``layer`` (traced or not) go to SMEM. Slot s
    attends to positions ``[0, seq_lens[s])``: pages past them are
    never fetched, stale rows inside the last live page are masked.

    Returns the partials `flash_block_partial` returns, for a caller
    that has more keys to merge (the step's own token, which is not
    in the pool yet): ``o`` (S, heads, head_dim) f32 unnormalised,
    ``m`` and ``l`` (S, heads) f32 with softmax base ``m``. A slot
    with ``seq_lens == 0`` gives o = 0, m = -1e30, l = 0. Scores and
    softmax in f32, probabilities cast to the pool's dtype before the
    value product, as `ops.attention.decode_attention` does. Inference
    only: no VJP."""
    global invocations
    invocations += 1
    if interpret is None:
        interpret = not on_tpu()
    s, w = q_rows.shape
    n_layers, _, page, wp = k_pages.shape
    if wp != w or v_pages.shape != k_pages.shape or \
            not paged_decode_supported(page, k_pages.dtype):
        raise ValueError(
            f"paged_decode_partial: pools {k_pages.shape} / "
            f"{v_pages.shape} {k_pages.dtype} against query rows "
            f"{q_rows.shape} (see paged_decode_supported)")
    dtype = k_pages.dtype
    ppb = _PAGED_BLOCK // page
    # heads on sublanes: a whole number of the dtype's tiles
    tile = 32 // jnp.dtype(dtype).itemsize
    hp = -(-heads // tile) * tile
    row = pl.BlockSpec((1, 1, w), lambda i, *_: (i, 0, 0))
    stat = pl.BlockSpec((1, hp, 128), lambda i, *_: (i, 0, 0))
    o, m, l = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, scale=float(scale),
            head_dim=int(head_dim),
            pages_per_slot=page_table.shape[1]),
        name="zoo_paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[row, stat, stat],
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page, w), dtype),
                pltpu.VMEM((2, ppb, page, w), dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hp, w), dtype),
                pltpu.VMEM((hp, w), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((s, 1, w), jnp.float32),
            jax.ShapeDtypeStruct((s, hp, 128), jnp.float32),
            jax.ShapeDtypeStruct((s, hp, 128), jnp.float32),
        ],
        # the buffer state and the prefetched block pass from one slot
        # to the next: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32),
      seq_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q_rows.astype(dtype)[:, None, :], k_pages, v_pages)
    o = o[:, 0, :heads * head_dim].reshape(s, heads, head_dim)
    return o, m[:, :heads, 0], l[:, :heads, 0]


def paged_gqa_decode_partial(q: jnp.ndarray, rows: jnp.ndarray,
                             page_table: jnp.ndarray,
                             seq_lens: jnp.ndarray,
                             first: jnp.ndarray, layer, *,
                             k_dim: int, v_dim: int, scale: float,
                             interpret: Optional[bool] = None):
    """:func:`paged_decode_partial` for grouped-query heads over a
    pool of ``[K | V]`` rows (`zoo_paged_gqa_decode`).

    ``q`` (S, G, R, k_dim): R query heads to each of G K/V heads;
    ``rows`` (L, P, page_size, W) the stacked pool, a token's row
    ``[k of the G heads (G * k_dim) | v of the G heads (G * v_dim) |
    padding]``, never copied; ``page_table`` (S, n) the pages of each
    slot in order (a context pool's table, or the pages of a ring
    that hold a window), ``seq_lens`` and ``first`` (S,): slot s
    attends to positions ``[first[s], seq_lens[s])`` of its table
    row. Returns the partials ``o`` (S, G, R, v_dim) f32
    unnormalised, ``m`` and ``l`` (S, G, R); a slot with no such
    position gives o = 0, m = -1e30, l = 0. Needs
    `paged_gqa_supported`."""
    global invocations
    invocations += 1
    if interpret is None:
        interpret = not on_tpu()
    s, g, r, _ = q.shape
    _, _, page, w = rows.shape
    wk, wv = g * k_dim, g * v_dim
    if not paged_gqa_supported(page, rows.dtype, w, wk, wv):
        raise ValueError(
            f"paged_gqa_decode_partial: pool {rows.shape} "
            f"{rows.dtype} against {g} K/V heads of {k_dim} + "
            f"{v_dim} (see paged_gqa_supported)")
    dtype = rows.dtype
    ppb = _PAGED_BLOCK // page
    tile = 32 // jnp.dtype(dtype).itemsize
    heads = g * r
    hp = -(-heads // tile) * tile
    # row j = (g, r) holds its query in K/V head g's columns
    eye = jnp.eye(g, dtype=q.dtype)
    q_bd = jnp.einsum("sgrd,gh->sgrhd", q, eye).reshape(s, heads, wk)
    q_bd = jnp.pad(q_bd.astype(dtype), [(0, 0), (0, hp - heads),
                                        (0, 0)])
    stat = pl.BlockSpec((1, hp, 128), lambda i, *_: (i, 0, 0))
    o, m, l = pl.pallas_call(
        functools.partial(
            _paged_gqa_kernel, scale=float(scale), k_width=wk,
            pages_per_slot=page_table.shape[1]),
        name="zoo_paged_gqa_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s,),
            in_specs=[pl.BlockSpec((1, hp, wk),
                                   lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, hp, wv),
                                    lambda i, *_: (i, 0, 0)),
                       stat, stat],
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page, w), dtype),
                pltpu.SemaphoreType.DMA((1, 2)),
                pltpu.VMEM((hp, wv), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((s, hp, wv), jnp.float32),
            jax.ShapeDtypeStruct((s, hp, 128), jnp.float32),
            jax.ShapeDtypeStruct((s, hp, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32),
      seq_lens.astype(jnp.int32), first.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q_bd, rows)
    o = jnp.einsum("sgrhd,gh->sgrd",
                   o[:, :heads].reshape(s, g, r, g, v_dim),
                   jnp.eye(g, dtype=jnp.float32))
    return o, m[:, :heads, 0].reshape(s, g, r), \
        l[:, :heads, 0].reshape(s, g, r)


def paged_gqa_supported(page_size: int, dtype, row_width: int,
                        k_width: int, v_width: int) -> bool:
    """Whether `paged_gqa_decode_partial` takes a pool of this
    geometry: `paged_decode_supported`'s pages, and a row whose K
    part and V part each fill whole 128-lane tiles, so that both are
    cut out of a page in VMEM with no relayout."""
    return paged_decode_supported(page_size, dtype) and \
        k_width % 128 == 0 and v_width % 128 == 0 and \
        row_width % 128 == 0 and k_width + v_width <= row_width


# -- a chunk's attention under an arbitrary mask ----------------------------
# `ops.attention.masked_attention` on the chip: the flash recursion over
# (block_q, block_k) tiles of a mask that no rule describes (an indexer's
# top-k, a window over a ring), with a table of the tiles that hold a 1.
# One grid step is one tile of the mask against ALL the heads of the
# call: the mask tile is read and turned into its additive form once for
# the H heads, which is what makes an int8 tile (as many bytes as a
# head's K and V tiles together) cheap enough to carry.


def chunk_blocks(c: int, t: int) -> "Optional[tuple[int, int]]":
    """(block_q, block_k) of `masked_chunk_attention` for ``c``
    queries a row against ``t`` keys: the largest block of queries
    among 512, 256 and 128 that divides ``c`` (none does: None, the
    kernel does not take the call) against 1024 keys, or all of
    fewer rounded up to a lane tile.

    Read on the v5e at dots3-note's shapes, 2048 queries of 16 bf16
    heads a call, milliseconds a call with the transposes and the
    mask's preparation (my chip run, PR 39,
    `scripts/chunk_attention_sweep.py`), behind 32768 / 8192 / 0
    cached keys on a full layer (keys 192 wide) and on a sliding one
    (4624 keys, 256 wide), where the XLA body takes 23.95 / 5.93 /
    0.60 and 2.82: 256x256 18.10 / 5.25 / 1.01 and 1.08; 512x512
    10.46 / 3.12 / 0.72 and 1.04; 512x1024 7.22 / 2.43 / 0.59 and
    1.01; 1024x1024 6.70 / 2.15 / 0.59 and 1.01; 512x2048 7.32 /
    2.39 / 0.61 and 1.26. A step's fixed cost (16 heads' statistics
    read and written) is paid a tile, so wide tiles win until the
    skip's grain costs what they save; past 512x1024 nothing is left
    to win and VMEM doubles."""
    bq = next((b for b in (512, 256, 128) if c % b == 0), None)
    return bq and (bq, min(1024, -(-t // 128) * 128))


def mask_tiles(mask: jnp.ndarray, block_q: int, block_k: int
               ) -> jnp.ndarray:
    """``occupied`` (A, C / block_q, ceil(T / block_k)) int32 of a
    mask (A, C, T): 1 where the tile holds a key some query of the
    block sees."""
    a, c, t = mask.shape
    nk = -(-t // block_k)
    m = jnp.pad(mask.astype(jnp.bool_),
                [(0, 0), (0, 0), (0, nk * block_k - t)])
    return jnp.any(m.reshape(a, c // block_q, block_q, nk, block_k),
                   axis=(2, 4)).astype(jnp.int32)


def _chunk_kernel(occ_ref, idx_ref, q_ref, k_ref, v_ref, mask_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, scale: float):
    """Grid (A, nq, nk): one (block_q, block_k) tile of the mask
    against every head. Scalar prefetch: ``occ_ref`` and ``idx_ref``
    (A * nq * nk,), whether the tile holds a 1 and the key block the
    index maps fetched for it (its own where occupied, else the
    block already there). ``q_ref`` (1, H, block_q, D), ``k_ref``
    (1, H, block_k, D), ``v_ref`` (1, H, block_k, Dv), ``mask_ref``
    (1, block_q, block_k) int8, ``o_ref`` (1, H, block_q, Dv).
    Scratch, one a head: ``acc_ref`` (H, block_q, Dv) f32,
    ``m_ref``/``l_ref`` (H, block_q, 128) f32."""
    del idx_ref
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    ki = pl.program_id(2)
    heads = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(occ_ref[(pl.program_id(0) * nq + pl.program_id(1)) * nk
                     + ki] > 0)
    def _tile():
        # s * scale + (0 | -1e30) is where(mask, s * scale, -1e30) to
        # the bit: a score is nowhere near 1e30 * 2^-24
        bias = jnp.where(mask_ref[0].astype(jnp.int32) != 0, 0.0,
                         _NEG_INF)

        def head(h, carry):
            s = jax.lax.dot_general(
                q_ref[0, h], k_ref[0, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + bias
            _softmax_accumulate(s, v_ref[0, h], acc_ref.at[h],
                                m_ref.at[h], l_ref.at[h])
            return carry

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when(ki == nk - 1)
    def _final():
        # a block of queries with no tile at all: 0 / 1e-30 of zeros
        o_ref[0] = (acc_ref[:] / jnp.maximum(
            l_ref[:, :, :1], 1e-30)).astype(o_ref.dtype)


def masked_chunk_attention(q: jnp.ndarray, k: jnp.ndarray,
                           v: jnp.ndarray, mask: jnp.ndarray,
                           scale: float, *, block_q: int,
                           block_k: int, skip: bool = True,
                           interpret: Optional[bool] = None
                           ) -> jnp.ndarray:
    """Softmax attention of queries ``q`` (A, C, H, D) over keys ``k``
    (A, T, H, D) and values ``v`` (A, T, H, Dv) under an arbitrary
    ``mask`` (A, C, T) shared by the heads, in one pass
    (`zoo_flash_chunk`): the flash recursion, f32 statistics in VMEM,
    the probabilities rounded to the operands' dtype before the
    second product, and no tile of the mask run that holds no 1
    (`mask_tiles`; ``skip=False`` runs every tile, to the same
    result). Any T: keys, values and mask are padded to ``block_k``
    with keys no query sees, and values to whole lane tiles of 128.
    C a multiple of ``block_q``. A query with no key comes out
    finite (the mean of the values of the tiles its block ran, or
    0). Returns (A, C, H, Dv)."""
    global invocations
    invocations += 1
    if interpret is None:
        interpret = not on_tpu()
    a, c, h, d = q.shape
    t, dv_in = k.shape[1], v.shape[-1]
    dv = -(-dv_in // 128) * 128       # a head's accumulator: whole tiles
    nq, nk = c // block_q, -(-t // block_k)
    if c % block_q:
        raise ValueError(f"masked_chunk_attention: {c} queries in "
                         f"blocks of {block_q}")
    pad = (0, nk * block_k - t)
    mask = jnp.pad(mask.astype(jnp.int8), [(0, 0), (0, 0), pad])
    occ = mask_tiles(mask, block_q, block_k)
    if not skip:
        occ = jnp.ones_like(occ)
    # the block an unoccupied tile leaves in place: the last occupied
    # one before it, or the first to come
    ks = jnp.arange(nk, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(occ > 0, ks, -1), axis=2)
    idx = jnp.where(last >= 0, last,
                    jnp.argmax(occ, axis=2).astype(jnp.int32)[..., None])
    heads_major = lambda x, w: jnp.transpose(
        jnp.pad(x, [(0, 0), pad, (0, 0), (0, w - x.shape[-1])]),
        (0, 2, 1, 3))
    at = lambda i, j, n, idx_ref: idx_ref[(i * nq + j) * nk + n]
    # what a step holds in VMEM: the blocks of q, out, k, v and the
    # mask twice (the pipeline's two buffers), the heads' accumulators
    # and statistics, and a handful of f32 tiles of scores in flight;
    # at 512 x 1024 and 16 heads of 192 + 128 about 58 MB of the
    # chip's 128
    item = jnp.dtype(q.dtype).itemsize
    vmem = 2 * (h * (block_q + block_k) * (d + dv) * item
                + block_q * block_k) \
        + h * block_q * (dv + 256) * 4 + 6 * block_q * block_k * 4
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=float(scale)),
        name="zoo_flash_chunk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(a, nq, nk),
            in_specs=[
                pl.BlockSpec((1, h, block_q, d),
                             lambda i, j, n, *_: (i, 0, j, 0)),
                pl.BlockSpec((1, h, block_k, d),
                             lambda i, j, n, _, idx_ref:
                             (i, 0, at(i, j, n, idx_ref), 0)),
                pl.BlockSpec((1, h, block_k, dv),
                             lambda i, j, n, _, idx_ref:
                             (i, 0, at(i, j, n, idx_ref), 0)),
                pl.BlockSpec((1, block_q, block_k),
                             lambda i, j, n, _, idx_ref:
                             (i, j, at(i, j, n, idx_ref))),
            ],
            out_specs=pl.BlockSpec((1, h, block_q, dv),
                                   lambda i, j, n, *_: (i, 0, j, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, block_q, dv), jnp.float32),
                pltpu.VMEM((h, block_q, 128), jnp.float32),
                pltpu.VMEM((h, block_q, 128), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((a, h, c, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem) + (8 << 20)),
        interpret=interpret,
    )(occ.reshape(-1), idx.reshape(-1), jnp.transpose(q, (0, 2, 1, 3)),
      heads_major(k, d), heads_major(v, dv), mask)
    return jnp.transpose(out, (0, 2, 1, 3))[..., :dv_in]


def as_key_mask(mask, b: int, tk: int):
    """Reduce an attention mask (broadcastable to (B, H, Tq, Tk)) to
    the kernel-native (B, Tk) key-validity form, or None if it varies
    per query/head (detected STATICALLY from the shape: dims 1 and 2
    must be broadcast dims). Only the explicit 4-D (B|1, 1, 1, Tk)
    form qualifies — exactly BERT's padding mask (`layers/BERT.scala`
    extended attention mask); a 2-D mask is NOT accepted because the
    dense path broadcasts 2-D as (Tq, Tk), a different meaning."""
    if mask is None:
        return None
    shp = tuple(mask.shape)
    if mask.ndim == 4 and shp[1] == 1 and shp[2] == 1 and \
            shp[3] == tk and shp[0] in (1, b):
        km = mask[:, 0, 0, :]
        return jnp.broadcast_to(km, (b, tk))
    return None


def supports(tq: int, tk: int, d: int,
             mask: Optional[jnp.ndarray], b: Optional[int] = None
             ) -> bool:
    """Whether the kernel handles this problem (else caller falls back
    to the XLA path): block-divisible sequence lengths, a head dim
    that fits VMEM tiles, and a mask that is either absent or a pure
    key-padding mask (causal is native). Feasibility only — block
    divisibility is identical for every tuner candidate, so this
    consults the heuristic and never the cache."""
    bq, bk = _heuristic_blocks(tq, tk)
    if bq is None or bk is None or d > 256:
        return False
    if mask is None:
        return True
    return b is not None and as_key_mask(mask, b, tk) is not None


def _heuristic_blocks(tq: int, tk: int, itemsize: int = 2):
    # biggest wins on v5e (measured: [1024,1024] beats [256,512] by
    # 1.2-2.2x at T=2k-8k), but the BACKWARD holds ~4 f32
    # (block_q, block_k) tiles in VMEM at once, which at f32 operands
    # with 1024-blocks exceeds the 16MB scoped-VMEM budget (measured
    # 17.05M) — cap f32 at 512. Forward and backward MUST share the
    # blocks: the causal whole-block skip decides which fully-masked
    # query rows participate, and a fwd/bwd mismatch desyncs their
    # gradients.
    cap = 512 if itemsize >= 4 else 1024
    sizes = tuple(b for b in (1024, 512, 256, 128) if b <= cap)
    bq = next((b for b in sizes if tq % b == 0), None)
    bk = next((b for b in sizes if tk % b == 0), None)
    return bq, bk


def _pick_blocks(tq: int, tk: int, itemsize: int = 2):
    """Tuned (block_q, block_k) via the autotuner ("flash_blocks"
    op); the heuristic above stays the fallback and the sweep
    baseline. (None, None) for non-128-divisible T remains the
    static infeasibility signal and never reaches the tuner."""
    bq, bk = _heuristic_blocks(tq, tk, itemsize)
    if bq is None or bk is None:
        return bq, bk
    cfg = autotune.decide(
        "flash_blocks", {"tq": tq, "tk": tk, "isz": itemsize},
        dtype="f32" if itemsize >= 4 else "bf16")
    return cfg["bq"], cfg["bk"]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    key_mask: Optional[jnp.ndarray] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention. q,k,v: (B, T, H, D) → (B, T, H, D).

    Same contract as :func:`ops.attention.dot_product_attention`
    (f32 softmax, bf16-safe); Tq/Tk must be multiples of 128.
    `key_mask`: optional (B, Tk) 0/1 key-validity (padding) mask,
    applied natively in the kernel (fwd AND bwd).
    `interpret=None` auto-selects the Pallas interpreter off-TPU.
    """
    global invocations
    invocations += 1
    d = q.shape[-1]
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    b, tq, tk = q.shape[0], q.shape[1], k.shape[1]
    bq, bk = _pick_blocks(tq, tk, jnp.dtype(q.dtype).itemsize)
    if bq is None or bk is None:
        raise ValueError(
            f"flash_attention needs Tq/Tk divisible by 128; got "
            f"Tq={tq} Tk={tk} (use dot_product_attention)")
    if interpret is None:
        interpret = not on_tpu()
    qt = jnp.transpose(q, (0, 2, 1, 3))      # (B, H, T, D)
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if key_mask is None:
        # scalar dummy: ndim != 2 is the static "no mask" bit of the
        # custom_vjp (the mask must be a diff arg because it is traced)
        km = jnp.zeros((), jnp.float32)
    else:
        if tuple(key_mask.shape) != (b, tk):
            raise ValueError(
                f"key_mask must be (B, Tk)=({b}, {tk}); got "
                f"{tuple(key_mask.shape)}")
        km = key_mask.astype(jnp.float32)
    out = _flash(qt, kt, vt, km, scale, causal, bq, bk,
                 bool(interpret))
    return jnp.transpose(out, (0, 2, 1, 3))


# -- autotuner spec ---------------------------------------------------------
# "flash_blocks": the shared fwd/bwd (block_q, block_k) tiling, swept
# over every divisibility-feasible pair under the dtype-aware VMEM cap
# (the same cap the heuristic enforces). No legacy env flag exists for
# the blocks, so there is no flag_value. The probe times fwd+bwd
# together — the blocks are shared, so a fwd-only winner that loses
# the backward budget must not win the sweep.

def _blocks_heuristic(p):
    bq, bk = _heuristic_blocks(p["tq"], p["tk"], p["isz"])
    return {"bq": bq, "bk": bk}


def _blocks_candidates(p):
    cap = 512 if p["isz"] >= 4 else 1024
    sizes = [b for b in (1024, 512, 256, 128) if b <= cap]
    return [{"bq": bq, "bk": bk}
            for bq in sizes if p["tq"] % bq == 0
            for bk in sizes if p["tk"] % bk == 0]


def _blocks_runner(p, cfg):
    tq, tk, isz = p["tq"], p["tk"], p["isz"]
    interpret = not on_tpu()
    if interpret and max(tq, tk) > 512:
        return None    # interpreter probes are for smoke shapes only
    import numpy as np
    dtype = jnp.float32 if isz >= 4 else jnp.bfloat16
    rs = np.random.RandomState(0)
    b, h, d = 1, 2, 64
    q = jnp.asarray(rs.randn(b, h, tq, d), dtype)
    k = jnp.asarray(rs.randn(b, h, tk, d), dtype)
    v = jnp.asarray(rs.randn(b, h, tk, d), dtype)
    km = jnp.zeros((), jnp.float32)
    scale = 1.0 / (d ** 0.5)

    @jax.jit
    def probe(q, k, v):
        def loss(q):
            out = _flash(q, k, v, km, scale, True, cfg["bq"],
                         cfg["bk"], interpret)
            return jnp.sum(out.astype(jnp.float32))
        val, dq = jax.value_and_grad(loss)(q)
        return val + jnp.sum(dq.astype(jnp.float32))

    def run():
        jax.block_until_ready(probe(q, k, v))
    return run


autotune.register(autotune.OpSpec(
    "flash_blocks", heuristic=_blocks_heuristic,
    candidates=_blocks_candidates, runner=_blocks_runner))
