"""Attention ops.

The reference's longest context is BERT-512 with dense attention inside
`TransformerLayer.scala`/`BERT.scala` (SURVEY.md §5 "Long-context:
absent"). Here attention is a first-class op with two interchangeable
implementations:

- :func:`dot_product_attention` — plain XLA (fused by the compiler),
  or the Pallas flash kernel (`impl="flash"` / ``ZOO_TPU_ATTENTION``
  env, `ops.flash_attention`) which keeps softmax statistics in VMEM
  instead of materialising the (B, H, Tq, Tk) logits in HBM;
- `parallel.ring_attention` — sequence-parallel ring attention over a
  mesh axis for long contexts (K/V blocks rotate over ICI while each
  device accumulates flash-style softmax statistics).
- `parallel.ulysses` — all-to-all head-repartition sequence
  parallelism (two large collectives instead of n ring rounds; needs
  heads % axis == 0).

Ring shares this module's blockwise-softmax accumulation math, so
ring == dense numerically; ulysses runs ordinary dense attention
locally after the head all-to-all (both tested to 1e-5 vs dense).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.common.device import on_tpu
from analytics_zoo_tpu.perf import autotune


def resolve_attention_impl(impl: Optional[str]) -> str:
    """Resolve an attention-impl selector: None → ``ZOO_TPU_ATTENTION``
    env (default "auto" — the Pallas flash kernel whenever it wins);
    validates against the known impls. The single copy of this policy —
    used by dot_product_attention, the sequence-parallel attentions,
    and the transformer layers."""
    impl = impl or os.environ.get("ZOO_TPU_ATTENTION", "auto")
    if impl not in ("xla", "flash", "auto"):
        raise ValueError(f"unknown attention impl {impl!r}")
    return impl


def flash_backend_ok() -> bool:
    """Whether "auto" may route to the Pallas kernel on this backend:
    real TPU, or anywhere when ``ZOO_TPU_FLASH_FORCE_INTERPRET=1``
    (CPU kernel-coverage tests). Explicit ``impl="flash"`` ignores
    this and runs the interpreter off-TPU."""
    if os.environ.get("ZOO_TPU_FLASH_FORCE_INTERPRET") == "1":
        return True
    return on_tpu()


def flash_profitable(tk: int) -> bool:
    """Whether flash beats XLA dense at this key length. Measured on
    the v5e (fwd+bwd, B=4 H=16 D=64 bf16, causal): dense wins at
    Tk ≤ 512 (0.48x/0.13x at 256/512), flash wins from 1024 up
    (1.82x/2.47x/3.7x at 1024/2048/4096 — PERF.md); that 1024
    crossover is now the autotuner heuristic for the
    "attn_crossover" op, and swept winners override it per (Tk,
    device). ``ZOO_TPU_FLASH_MIN_T`` set bypasses the tuner
    verbatim (source="flag")."""
    return bool(autotune.decide("attn_crossover",
                                {"tk": tk})["use_flash"])


def decode_flash_profitable(tk: int) -> bool:
    """Whether the Pallas decode kernel beats XLA dense single-query
    attention at this cached length. A 1-query attention is tiny —
    the dense logits are only (S, H, 1, Tk) — so the kernel's win is
    HBM traffic at long contexts, not FLOPs; the crossover sits
    higher than the training kernel's (heuristic 2048, tuned per
    device as the "decode_crossover" op).
    ``ZOO_TPU_DECODE_FLASH_MIN_T`` set bypasses the tuner verbatim
    (source="flag")."""
    return bool(autotune.decide("decode_crossover",
                                {"tk": tk})["use_flash"])


@jax.named_scope("zoo:decode/attention")
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     seq_lens: jnp.ndarray,
                     scale: Optional[float] = None,
                     impl: Optional[str] = None,
                     k_scales: Optional[jnp.ndarray] = None,
                     v_scales: Optional[jnp.ndarray] = None
                     ) -> jnp.ndarray:
    """Single-query (decode-mode) attention against a cached context.

    The generation-time sibling of :func:`dot_product_attention`,
    sharing its impl selector: ``q`` is ONE new token per slot,
    (S, H, D); ``k``/``v`` are the gathered cache, (S, T, H, D) (the
    dense view from `ops.kv_cache.gather_layer`); ``seq_lens`` (S,)
    int32 masks positions ``>= seq_lens[s]`` (stale pages, pad rows).
    Returns (S, H, D). Softmax in f32 regardless of input dtype.

    Int8 caches pass the gathered views still quantized plus their
    per-row scales ``k_scales``/``v_scales`` (S, T, H): dequant
    happens here, at the consumption boundary, so the model layer
    never touches quantization (the flash path forwards the scales
    into `flash_decode_attention`, which dequantizes at its gather).

    Routing mirrors the training path: "auto" takes the Pallas decode
    kernel (`ops.flash_attention.flash_decode_attention`, which
    reuses the flash block machinery with the query replicated across
    one sublane tile) when the backend qualifies, T is 128-divisible,
    and T is past the decode crossover (`decode_flash_profitable` —
    higher than the training crossover because single-query dense is
    so cheap); otherwise XLA dense. No causal mask is needed — the
    cache only ever holds positions the new token may see.
    """
    impl = resolve_attention_impl(impl)
    d = q.shape[-1]
    t = k.shape[1]
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    use_kernel = t % 128 == 0 and d <= 256 and (
        impl == "flash" or (impl == "auto" and flash_backend_ok()
                            and decode_flash_profitable(t)))
    if use_kernel:
        from analytics_zoo_tpu.ops import flash_attention as fa
        key_mask = (jnp.arange(t, dtype=jnp.int32)[None, :] <
                    seq_lens[:, None])
        return fa.flash_decode_attention(q, k, v, key_mask,
                                         scale=scale,
                                         k_scales=k_scales,
                                         v_scales=v_scales)
    if k_scales is not None:
        from analytics_zoo_tpu.ops import kv_cache as kvc
        k = kvc.dequantize_rows(k, k_scales, q.dtype)
        v = kvc.dequantize_rows(v, v_scales, q.dtype)
    # dense: (S, H, 1, T) logits never materialise more than one
    # query row per slot — already cheap at serving contexts
    logits = jnp.einsum("shd,sthd->sht", q, k).astype(jnp.float32)
    logits = logits * scale
    valid = (jnp.arange(t, dtype=jnp.int32)[None, None, :] <
             seq_lens[:, None, None])
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("sht,sthd->shd", probs, v)


def paged_decode_ok(cache, dtype, impl: Optional[str] = None) -> bool:
    """Whether a decode step over ``cache`` with activations of
    ``dtype`` takes its context from the pages themselves
    (:func:`paged_decode_attention`) rather than from a dense
    gathered view (:func:`decode_attention`). Decided by what the
    step can observe, nothing to set: the backend runs the Pallas
    kernels (`flash_backend_ok`), the selector does not force XLA,
    and the pools are floating point, hold the activations' own dtype
    (the products then round nothing the dense path keeps) and have a
    page that is a whole number of that dtype's sublane tiles
    (`ops.flash_attention.paged_decode_supported`). Int8 pools, with
    their scale pools, take the dense view."""
    if resolve_attention_impl(impl) == "xla" or cache.quantized or \
            jnp.dtype(dtype) != cache.pool_dtype or \
            not flash_backend_ok():
        return False
    from analytics_zoo_tpu.ops import flash_attention as fa
    return fa.paged_decode_supported(cache.page_size, cache.pool_dtype)


@jax.named_scope("zoo:decode/attention")
def paged_decode_attention(q: jnp.ndarray, k_row: jnp.ndarray,
                           v_row: jnp.ndarray, cache, layer,
                           writes: jnp.ndarray,
                           scale: Optional[float] = None
                           ) -> jnp.ndarray:
    """:func:`decode_attention` with the cached context read page by
    page where it lies (`ops.flash_attention.paged_decode_partial`):
    one algorithm, operands pages and not a view.

    ``q`` (S, H, D); ``k_row``/``v_row`` (S, W): the step's own token
    as the pool will store it (`ops.kv_cache.decode_rows`), not in
    the pool yet; ``cache`` a float `PagedKVCache` whose stacked pools
    are only read; ``layer`` the scalar layer index; ``writes`` (S,)
    bool, the slots whose row lands this step
    (`ops.kv_cache._decode_writes`). Slot s attends to its
    ``cache.seq_lens[s]`` cached positions and, where it writes, to
    its own row: the kernel's partials over the pages are merged with
    the row's score and value by the arithmetic ring attention merges
    block partials with. A slot with nothing cached that does not
    write returns zeros. Returns (S, H, D) in ``q``'s dtype."""
    from analytics_zoo_tpu.ops import flash_attention as fa
    from analytics_zoo_tpu.ops import kv_cache as kvc
    _, h, d = q.shape
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    q_row, _ = kvc._pool_rows(cache.k_pages, q)
    o, m, l = fa.paged_decode_partial(
        q_row, cache.k_pages, cache.v_pages, cache.page_table,
        cache.seq_lens, layer, heads=h, head_dim=d, scale=scale)
    k_new = kvc.split_heads(k_row, h, d).astype(jnp.float32)
    v_new = kvc.split_heads(v_row, h, d).astype(jnp.float32)
    s_new = jnp.sum(q.astype(jnp.float32) * k_new, axis=-1) * scale
    return merge_decode_partials(o, m, l, s_new, v_new, writes,
                                 dtype=q.dtype)


def latent_decode_attention(q_lat: jnp.ndarray, q_pe: jnp.ndarray,
                            ctx: jnp.ndarray, valid: jnp.ndarray,
                            scale: float) -> jnp.ndarray:
    """Single-query latent attention in the absorbed form over the
    rows ``ctx`` (S, T, W) = ``[c_kv (R) | k_pe (P) | padding]`` that
    ``valid`` (S, T) marks: whichever rows the caller gathered (a
    whole context, a window, the rows an indexer chose). ``q_lat``
    (S, H, R) is each head's content query carried into the latent
    space, ``q_pe`` (S, H, P) its rotated part; scores are
    ``(q_lat.c_kv + q_pe.k_pe) * scale``, softmax in f32. Returns the
    latent outputs ``P c_kv`` (S, H, R)."""
    r, p = q_lat.shape[-1], q_pe.shape[-1]
    ctx = ctx.astype(q_lat.dtype)
    c_kv, k_pe = ctx[..., :r], ctx[..., r:r + p]
    f32 = dict(preferred_element_type=jnp.float32)
    logits = (jnp.einsum("shr,str->sht", q_lat, c_kv, **f32) +
              jnp.einsum("shp,stp->sht", q_pe, k_pe, **f32)) * scale
    probs = jax.nn.softmax(jnp.where(valid[:, None, :], logits, -1e30),
                           axis=-1)
    return jnp.einsum("sht,str->shr", probs.astype(q_lat.dtype), c_kv)


@jax.named_scope("zoo:decode/mla_attention")
def mla_decode_attention(q_lat: jnp.ndarray, q_pe: jnp.ndarray,
                         ctx: jnp.ndarray, seq_lens: jnp.ndarray,
                         scale: float) -> jnp.ndarray:
    """:func:`latent_decode_attention` over a whole gathered context
    (the view from `ops.kv_cache.row_decode_view`): the 128 query
    heads of a slot all attend to ONE latent row a cached token, no
    per-head key or value is ever formed from the cache, and
    ``seq_lens`` (S,) masks positions ``>= seq_lens[s]``."""
    valid = (jnp.arange(ctx.shape[1], dtype=jnp.int32)[None, :] <
             seq_lens[:, None])
    return latent_decode_attention(q_lat, q_pe, ctx, valid, scale)


def index_scores(q: jnp.ndarray, w: jnp.ndarray, k: jnp.ndarray,
                 head_block: int = 16) -> jnp.ndarray:
    """A sparse-attention indexer's scores (DeepSeek-V3.2-Exp):
    ``I[a, c, t] = sum_h w[a, c, h] * relu(q[a, c, h] . k[a, t])``
    for queries ``q`` (A, C, H, D) with head weights ``w`` (A, C, H)
    f32 against ONE index key a token ``k`` (A, T, D). Float32
    (A, C, T); ``head_block`` heads at a time, so the per-head
    products never exist for every head at once."""
    a, c, h, d = q.shape
    hb = head_block if h % head_block == 0 else h
    qs = jnp.moveaxis(q.reshape(a, c, h // hb, hb, d), 2, 0)
    ws = jnp.moveaxis(w.reshape(a, c, h // hb, hb), 2, 0)

    def block(acc, qw):
        qb, wb = qw
        dots = jnp.einsum("achd,atd->acht", qb, k,
                          preferred_element_type=jnp.float32)
        return acc + jnp.einsum("acht,ach->act", jax.nn.relu(dots),
                                wb.astype(jnp.float32)), None

    out, _ = jax.lax.scan(
        block, jnp.zeros((a, c, k.shape[1]), jnp.float32), (qs, ws))
    return out


def _ordered_bits(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 whose unsigned order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def topk_mask(scores: jnp.ndarray, visible: jnp.ndarray, k: int
              ) -> jnp.ndarray:
    """The EXACT top-``k`` of ``scores`` (..., T) among ``visible``
    (..., T) as a mask: every visible key where fewer than ``k``
    are, else the ``k`` of largest score, equal scores by lowest
    index (`jax.lax.top_k`'s order). Finds each row's k-th largest
    score bit by bit (32 counting passes over the scores, no sort),
    and orders equal scores only in a row that has them at the
    cut."""
    if scores.shape[-1] <= k:
        return visible
    u = jnp.where(visible, _ordered_bits(scores), jnp.uint32(0))

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(u.shape[:-1], jnp.uint32))
    above = u > kth[..., None]
    at = u == kth[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    tied = jnp.sum(at, axis=-1, dtype=jnp.int32) > room
    take = jax.lax.cond(
        jnp.any(tied),
        lambda: jnp.logical_and(at, jnp.cumsum(
            at, axis=-1, dtype=jnp.int32) <= room[..., None]),
        lambda: at)
    return jnp.logical_and(jnp.logical_or(above, take), visible)


def masked_flash_blocks(q, k, v):
    """The (block_q, block_k) with which :func:`masked_attention`
    takes the chunk kernel (`ops.flash_attention.
    masked_chunk_attention`) for these operands, or None where it
    keeps the XLA body. Decided by what the call can observe, nothing
    to set: the backend runs the Pallas kernels (`flash_backend_ok`),
    ``ZOO_TPU_ATTENTION`` does not force XLA, the operands are floating point
    of one dtype, keys and values are at most 256 wide, and the
    queries a row are a whole number of the kernel's query blocks
    (a multiple of 128)."""
    if resolve_attention_impl(None) == "xla" or not flash_backend_ok():
        return None
    dt = jnp.dtype(q.dtype)
    if not jnp.issubdtype(dt, jnp.floating) or dt.itemsize > 4 or \
            k.dtype != dt or v.dtype != dt or \
            max(q.shape[-1], v.shape[-1]) > 256:
        return None
    from analytics_zoo_tpu.ops import flash_attention as fa
    return fa.chunk_blocks(q.shape[1], k.shape[1])


def mask_tile_counts(q, k, v, mask) -> jnp.ndarray:
    """int32 ``[tiles, tiles run]`` of one :func:`masked_attention`
    call with these operands: the size and the sum of the table of
    occupied tiles the chunk kernel is handed; zeros where the call
    keeps the XLA body, which has no tiles."""
    blocks = masked_flash_blocks(q, k, v)
    if blocks is None:
        return jnp.zeros((2,), jnp.int32)
    from analytics_zoo_tpu.ops import flash_attention as fa
    occ = fa.mask_tiles(mask, *blocks)
    return jnp.stack([jnp.asarray(occ.size, jnp.int32), jnp.sum(occ)])


def masked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     mask: jnp.ndarray, scale: float,
                     q_block: int = 256) -> jnp.ndarray:
    """Attention of a chunk's queries ``q`` (A, C, H, D) over keys
    ``k`` (A, T, H, D) and values ``v`` (A, T, H, Dv) under an
    arbitrary ``mask`` (A, C, T) (1 = attend: causality, a window, an
    indexer's choice), softmax in f32, the probabilities rounded to
    the operands' dtype before the second product. A query with no
    key gets a finite row, never NaN: the caller drops it. Returns
    (A, C, H, Dv).

    Where :func:`masked_flash_blocks` allows, one pass of the chunk
    kernel that keeps the scores on the chip and runs no tile of the
    mask that is empty; else (the CPU, ``ZOO_TPU_ATTENTION=xla``, a C
    that is no multiple of 128, as a whole prompt under
    `PatternDecoder.prefill` may be) the dense XLA body under it, the
    kernel's reference: ``q_block`` queries at a time, so that the
    f32 scores are (A, H, q_block, T)."""
    blocks = masked_flash_blocks(q, k, v)
    if blocks is not None:
        from analytics_zoo_tpu.ops import flash_attention as fa
        return fa.masked_chunk_attention(
            q, k, v, mask, scale, block_q=blocks[0], block_k=blocks[1])
    return _masked_attention_xla(q, k, v, mask, scale, q_block)


def _masked_attention_xla(q, k, v, mask, scale: float,
                          q_block: int = 256) -> jnp.ndarray:
    """:func:`masked_attention` as dense products: every query with
    every key, the mask applied to the scores. A query with no key
    gets a uniform softmax."""
    a, c, h, d = q.shape
    qb = q_block if c % q_block == 0 else c

    def block(qm):
        qs, ms = qm                        # (A, qb, H, D), (A, qb, T)
        logits = jnp.einsum("aqhd,athd->ahqt", qs, k,
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(
            jnp.where(ms[:, None], logits * scale, -1e30), axis=-1)
        return jnp.einsum("ahqt,athd->aqhd", probs.astype(q.dtype), v)

    if qb == c:
        return block((q, mask))
    out = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(a, c // qb, qb, h, d), 1, 0),
        jnp.moveaxis(mask.reshape(a, c // qb, qb, -1), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(a, c, h, v.shape[-1])


def _sink_softmax(logits: jnp.ndarray, sink=None) -> jnp.ndarray:
    """Softmax of masked f32 ``logits`` over their last axis. With
    ``sink`` (broadcast against ``logits[..., :1]``) the denominator
    holds one more term, ``exp(sink)``: a learned logit that takes
    weight and carries no value (gpt-oss's attention sink), so the
    probabilities sum to less than 1."""
    if sink is None:
        return jax.nn.softmax(logits, axis=-1)
    m = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), sink)
    p = jnp.exp(logits - m)
    return p / (jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sink - m))


def grouped_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      mask: jnp.ndarray, scale: float, sink=None,
                      q_block: int = 256) -> jnp.ndarray:
    """:func:`masked_attention` for grouped-query heads: queries ``q``
    (A, C, G, R, D), R query heads to each of the G K/V heads, over
    keys ``k`` (A, T, G, D) and values ``v`` (A, T, G, Dv) that are
    never repeated, under ``mask`` (A, C, T). ``sink`` (G, R) f32: a
    logit a head in the softmax's denominator (:func:`_sink_softmax`).
    ``q_block`` queries at a time: the f32 scores are
    (A, G, R, q_block, T). Returns (A, C, G, R, Dv)."""
    a, c, g, r, d = q.shape
    qb = q_block if c % q_block == 0 else c
    if sink is not None:
        sink = sink.astype(jnp.float32)[None, :, :, None, None]

    def block(qm):
        qs, ms = qm                   # (A, qb, G, R, D), (A, qb, T)
        logits = jnp.einsum("aqgrd,atgd->agrqt", qs, k,
                            preferred_element_type=jnp.float32)
        probs = _sink_softmax(
            jnp.where(ms[:, None, None], logits * scale, -1e30), sink)
        return jnp.einsum("agrqt,atgd->aqgrd", probs.astype(q.dtype),
                          v)

    if qb == c:
        return block((q, mask))
    out = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(a, c // qb, qb, g, r, d), 1, 0),
        jnp.moveaxis(mask.reshape(a, c // qb, qb, -1), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(a, c, g, r, v.shape[-1])


def banded_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                     k_valid: jnp.ndarray, window: int, scale: float,
                     sink=None) -> jnp.ndarray:
    """Sliding-window attention of a chunk whose work is bounded by
    the window, not by the keys held: queries ``q`` (A, C, G, R, D) at
    positions ``q_pos`` (A, C); keys ``k`` (A, B + C, G, D) and values
    ``v`` (A, B + C, G, Dv) at positions ``k_pos`` (A, B + C), real
    where ``k_valid``: the B positions before the chunk, then the
    chunk's own, consecutive. B, the band's block, is ``k.shape[1] -
    C``: at least ``window - 1`` and a divisor of C. Query block i
    (B queries) is multiplied with key blocks i and i + 1 only, 2B
    keys whatever C is; a query sees the keys at ``0 <= q_pos - k_pos
    < window``. ``sink`` as in :func:`grouped_attention`. Returns
    (A, C, G, R, Dv)."""
    a, c, g, r, d = q.shape
    b = k.shape[1] - c
    if b < window - 1 or c % b:
        raise ValueError(f"banded_attention: a band of {b} keys "
                         f"before {c} queries, window {window}")
    n = c // b
    blocks = lambda x: x.reshape((a, n + 1, b) + x.shape[2:])
    two = lambda x: jnp.concatenate(
        [blocks(x)[:, :-1], blocks(x)[:, 1:]], axis=2)
    k2, v2, p2, ok2 = two(k), two(v), two(k_pos), two(k_valid)
    qs, qp = q.reshape(a, n, b, g, r, d), q_pos.reshape(a, n, b)
    back = qp[:, :, :, None] - p2[:, :, None, :]     # (A, n, B, 2B)
    mask = jnp.logical_and(
        ok2[:, :, None, :],
        jnp.logical_and(back >= 0, back < window))
    logits = jnp.einsum("anqgrd,antgd->angrqt", qs, k2,
                        preferred_element_type=jnp.float32)
    if sink is not None:
        sink = sink.astype(jnp.float32)[None, None, :, :, None, None]
    probs = _sink_softmax(
        jnp.where(mask[:, :, None, None], logits * scale, -1e30), sink)
    out = jnp.einsum("angrqt,antgd->anqgrd", probs.astype(q.dtype), v2)
    return out.reshape(a, c, g, r, v.shape[-1])


def merge_decode_partials(o, m, l, s_new, v_new, writes, sink=None,
                          dtype=jnp.float32) -> jnp.ndarray:
    """A decode step's attention output from the flash partials over
    the cached keys (``o`` (S, H, Dv) f32 unnormalised, ``m`` and
    ``l`` (S, H), softmax base ``m``), the step's own key (score
    ``s_new`` (S, H), value ``v_new`` (S, H, Dv), present where
    ``writes`` (S,)) and, with ``sink`` (H,), one more term in the
    denominator that has no value: the arithmetic ring attention
    merges block partials with. A slot with no key at all gives
    zeros."""
    # a slot that does not write has no such key: weight exp(-inf) = 0
    # (m is finite, -1e30 at least, so no inf - inf)
    s_new = jnp.where(writes[:, None], s_new, -jnp.inf)
    m_new = jnp.maximum(m, s_new)
    if sink is not None:
        sink = jnp.broadcast_to(sink.astype(jnp.float32)[None],
                                m.shape)
        m_new = jnp.maximum(m_new, sink)
    a_old = jnp.exp(m - m_new)
    a_new = jnp.exp(s_new - m_new)
    l = l * a_old + a_new
    o = o * a_old[..., None] + v_new * a_new[..., None]
    if sink is not None:
        # no key at all: the sink alone would make 0 / exp(0), still 0
        l = l + jnp.exp(sink - m_new)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def paged_rows_ok(pool, dtype, k_width: int, v_width: int,
                  impl: Optional[str] = None) -> bool:
    """:func:`paged_decode_ok` for a grouped-query layer's pool of
    ``[K | V]`` rows (`ops.kv_cache.RowPagedCache.pages` or
    ``.window``): whether a decode step reads it page by page where
    it lies (`ops.flash_attention.paged_gqa_decode_partial`) rather
    than through a gathered view. Decided by what the step can
    observe: backend, selector, the pool's dtype against the
    activations' and the geometry `paged_gqa_supported` names."""
    if resolve_attention_impl(impl) == "xla" or \
            jnp.dtype(dtype) != pool.dtype or not flash_backend_ok():
        return False
    from analytics_zoo_tpu.ops import flash_attention as fa
    return fa.paged_gqa_supported(pool.shape[2], pool.dtype,
                                  pool.shape[3], k_width, v_width)


def gqa_decode_attention(q: jnp.ndarray, new_row: jnp.ndarray,
                         pool: jnp.ndarray, layer,
                         page_table: jnp.ndarray, lens: jnp.ndarray,
                         first: jnp.ndarray, writes: jnp.ndarray, *,
                         v_dim: int, scale: float, sink=None,
                         impl: Optional[str] = None) -> jnp.ndarray:
    """Single-query attention of grouped-query heads over a pool of
    ``[k of the G heads | v of the G heads]`` rows, for full and
    sliding layers alike.

    ``q`` (S, G, R, D): R query heads to each K/V head; ``new_row``
    (S, W): the step's own token as the pool will store it, not in
    the pool yet; ``pool`` (L, P, page, W), only read; ``page_table``
    (S, n), ``lens`` and ``first`` (S,): slot s attends to positions
    ``[first[s], lens[s])`` of its table row (a context pool's table
    from 0, or `ops.kv_cache.window_table`) and, where ``writes``,
    to its own row; ``sink`` (G, R): one more term in the softmax's
    denominator, merged exactly as the own token is. Where
    :func:`paged_rows_ok` the cached part is the Pallas kernel's
    partials over the live pages; otherwise (the CPU) the same
    partials from a gathered view. Returns (S, G, R, v_dim)."""
    s, g, r, d = q.shape
    wk, wv = g * d, g * v_dim
    f32 = jnp.float32
    if paged_rows_ok(pool, q.dtype, wk, wv, impl):
        from analytics_zoo_tpu.ops import flash_attention as fa
        o, m, l = fa.paged_gqa_decode_partial(
            q, pool, page_table, lens, first, layer, k_dim=d,
            v_dim=v_dim, scale=scale)
    else:
        page = pool.shape[2]
        ctx = pool.at[layer, page_table].get(mode="clip").reshape(
            s, page_table.shape[1] * page, -1).astype(q.dtype)
        at = jnp.arange(ctx.shape[1], dtype=jnp.int32)[None]
        seen = jnp.logical_and(at >= first[:, None], at < lens[:, None])
        k = ctx[..., :wk].reshape(s, -1, g, d)
        v = ctx[..., wk:wk + wv].reshape(s, -1, g, v_dim)
        sc = jnp.einsum("sgrd,stgd->sgrt", q, k,
                        preferred_element_type=f32) * scale
        sc = jnp.where(seen[:, None, None], sc, -1e30)
        m = jnp.max(sc, axis=-1)
        p = jnp.where(seen[:, None, None],
                      jnp.exp(sc - m[..., None]), 0.0)
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("sgrt,stgd->sgrd", p.astype(q.dtype), v,
                       preferred_element_type=f32)
    new = new_row.astype(f32)
    k_new = new[:, :wk].reshape(s, g, 1, d)
    v_new = jnp.broadcast_to(
        new[:, wk:wk + wv].reshape(s, g, 1, v_dim), (s, g, r, v_dim))
    s_new = jnp.sum(q.astype(f32) * k_new, axis=-1) * scale
    flat = lambda x: x.reshape((s, g * r) + x.shape[3:])
    out = merge_decode_partials(
        flat(o), flat(m), flat(l), flat(s_new), flat(v_new), writes,
        sink=None if sink is None else sink.reshape(-1),
        dtype=q.dtype)
    return out.reshape(s, g, r, v_dim)


@jax.named_scope("zoo:decode/chunk_attention")
def chunk_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    q_positions: jnp.ndarray,
                    scale: Optional[float] = None,
                    k_scales: Optional[jnp.ndarray] = None,
                    v_scales: Optional[jnp.ndarray] = None
                    ) -> jnp.ndarray:
    """Multi-query decode attention for a CHUNK of new tokens per
    slot — the workhorse of chunked prefill and speculative verify.

    ``q``: (S, C, H, D) — C new tokens per slot at absolute positions
    ``q_positions`` (S, C); ``k``/``v``: (S, T, H, D) gathered cache
    views that ALREADY contain the chunk's own rows (callers scatter
    before gathering, exactly like `decode_step`). The mask
    ``key_pos <= q_pos`` then yields both intra-chunk causality and
    validity in one comparison: every cache position at or before a
    query's own position is a real token of that slot, everything
    after (stale pages, the chunk's later rows) is invisible. Rows of
    inactive slots produce garbage that callers drop — with every
    key masked the f32 softmax degrades to uniform, never NaN.

    Dense XLA only: chunks are small (C ≪ T) and the (S, H, C, T)
    logits are MXU-shaped already; the single-query Pallas kernel's
    HBM win does not apply at C > 1 sublane occupancy. Int8 caches
    pass scales as in :func:`decode_attention`. Returns (S, C, H, D).
    """
    d = q.shape[-1]
    t = k.shape[1]
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    if k_scales is not None:
        from analytics_zoo_tpu.ops import kv_cache as kvc
        k = kvc.dequantize_rows(k, k_scales, q.dtype)
        v = kvc.dequantize_rows(v, v_scales, q.dtype)
    logits = jnp.einsum("schd,sthd->shct", q, k).astype(jnp.float32)
    logits = logits * scale
    visible = (jnp.arange(t, dtype=jnp.int32)[None, None, :] <=
               q_positions[:, :, None])                   # (S, C, T)
    logits = jnp.where(visible[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("shct,sthd->schd", probs, v)


def dot_product_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          mask: Optional[jnp.ndarray] = None,
                          causal: bool = False,
                          scale: Optional[float] = None,
                          impl: Optional[str] = None) -> jnp.ndarray:
    """Standard attention. q,k,v: (B, T, H, D) → (B, T, H, D).

    `mask`: broadcastable to (B, H, Tq, Tk), 1 = attend. Softmax in f32
    regardless of input dtype (bf16-safe).

    `impl`: "auto" (the default: Pallas flash kernel when the problem
    qualifies — 128-divisible sequence lengths, a mask that is absent
    or a pure key-padding mask like BERT's (B, 1, 1, Tk), a TPU
    backend, and Tk past the measured dense/flash crossover — else
    XLA dense), "flash" (force the kernel; interpret mode off-TPU),
    or "xla" (force dense). ``ZOO_TPU_ATTENTION`` sets the default
    process-wide.
    """
    impl = resolve_attention_impl(impl)
    # cheap gates first so the default ("auto") path off-TPU / below
    # the crossover never imports pallas or inspects the mask
    if impl == "flash" or (impl == "auto" and flash_backend_ok()
                           and flash_profitable(k.shape[1])):
        from analytics_zoo_tpu.ops import flash_attention as fa
        # single routing decision: shapes kernel-compatible AND the
        # mask (if any) reduces to the kernel's key-padding form
        km = fa.as_key_mask(mask, q.shape[0], k.shape[1])
        supported = fa.supports(q.shape[1], k.shape[1], q.shape[-1],
                                None) and (mask is None or km is not None)
        if supported:
            return fa.flash_attention(q, k, v, causal=causal,
                                      scale=scale, key_mask=km)
        if impl == "flash":
            raise ValueError(
                f"impl='flash' unsupported for Tq={q.shape[1]} "
                f"Tk={k.shape[1]} mask={mask is not None} (need "
                f"128-divisible T and a key-padding-only mask); use "
                f"'auto' to fall back silently")
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # (B, H, Tq, Tk)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((tq, tk), jnp.bool_),
                               k=tk - tq)
        logits = jnp.where(causal_mask, logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask.astype(jnp.bool_), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_block_update(carry, s, v_blk):
    """One blockwise-softmax accumulation step (shared by ring
    attention). carry = (o_acc, m, l); s: (B, H, Tq, Tk_blk) f32 logits;
    v_blk: (B, Tk_blk, H, D)."""
    o_acc, m, l = carry
    m_blk = jnp.max(s, axis=-1)               # (B, H, Tq)
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)                # rescale old accumulator
    p = jnp.exp(s - m_new[..., None])         # (B, H, Tq, Tk)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk)
    o_new = o_acc * alpha.transpose(0, 2, 1)[..., None] + \
        pv.astype(jnp.float32)
    return o_new, m_new, l_new


# -- autotuner specs --------------------------------------------------------
# The dense-vs-flash crossover IS the candidate set: the tuner times
# both routings at the call shape and memoizes the winner, retiring
# the hand-measured ZOO_TPU_{FLASH,DECODE_FLASH}_MIN_T constants to
# verbatim overrides (set -> tuner bypassed, source="flag"). The env
# reads stay in this module so lint's check_autotune_overrides sees
# every ops/ gate where it is consumed.

def _attn_flag(p):
    env = os.environ.get("ZOO_TPU_FLASH_MIN_T")
    if env is None:
        return None
    return {"use_flash": p["tk"] >= int(env)}


def _decode_flag(p):
    env = os.environ.get("ZOO_TPU_DECODE_FLASH_MIN_T")
    if env is None:
        return None
    return {"use_flash": p["tk"] >= int(env)}


def _crossover_candidates(p):
    return [{"use_flash": False}, {"use_flash": True}]


def _attn_runner(p, cfg):
    """fwd+bwd probe at (B=1, H=2, D=64, Tq=Tk) bf16 causal — the
    PERF.md crossover measurement's geometry, scaled down."""
    tk = p["tk"]
    interpret = not on_tpu()
    if interpret and (tk > 4096 or (cfg["use_flash"] and tk > 512)):
        return None
    if cfg["use_flash"] and tk % 128 != 0:
        return None
    import numpy as np
    rs = np.random.RandomState(0)
    b, h, d = 1, 2, 64
    shape = (b, tk, h, d)
    q = jnp.asarray(rs.randn(*shape), jnp.bfloat16)
    k = jnp.asarray(rs.randn(*shape), jnp.bfloat16)
    v = jnp.asarray(rs.randn(*shape), jnp.bfloat16)
    if cfg["use_flash"]:
        from analytics_zoo_tpu.ops import flash_attention as fa

        @jax.jit
        def probe(q, k, v):
            def loss(q):
                out = fa.flash_attention(q, k, v, causal=True)
                return jnp.sum(out.astype(jnp.float32))
            val, dq = jax.value_and_grad(loss)(q)
            return val + jnp.sum(dq.astype(jnp.float32))
    else:
        @jax.jit
        def probe(q, k, v):
            def loss(q):
                out = dot_product_attention(q, k, v, causal=True,
                                            impl="xla")
                return jnp.sum(out.astype(jnp.float32))
            val, dq = jax.value_and_grad(loss)(q)
            return val + jnp.sum(dq.astype(jnp.float32))

    def run():
        jax.block_until_ready(probe(q, k, v))
    return run


def _decode_runner(p, cfg):
    """Single-query decode probe at (S=4, H=2, D=64) over a T-length
    cache — forward only (decode never differentiates)."""
    t = p["tk"]
    interpret = not on_tpu()
    if t % 128 != 0 or (interpret and
                        (t > 4096 or (cfg["use_flash"] and t > 512))):
        return None
    import numpy as np
    rs = np.random.RandomState(0)
    s, h, d = 4, 2, 64
    q = jnp.asarray(rs.randn(s, h, d), jnp.bfloat16)
    k = jnp.asarray(rs.randn(s, t, h, d), jnp.bfloat16)
    v = jnp.asarray(rs.randn(s, t, h, d), jnp.bfloat16)
    seq_lens = jnp.full((s,), t, jnp.int32)
    if cfg["use_flash"]:
        from analytics_zoo_tpu.ops import flash_attention as fa
        key_mask = jnp.ones((s, t), jnp.float32)

        @jax.jit
        def probe(q, k, v):
            return jnp.sum(fa.flash_decode_attention(
                q, k, v, key_mask,
                scale=1.0 / (d ** 0.5)).astype(jnp.float32))
    else:
        @jax.jit
        def probe(q, k, v):
            return jnp.sum(decode_attention(
                q, k, v, seq_lens, impl="xla").astype(jnp.float32))

    def run():
        jax.block_until_ready(probe(q, k, v))
    return run


autotune.register(autotune.OpSpec(
    "attn_crossover",
    heuristic=lambda p: {"use_flash": p["tk"] >= 1024},
    candidates=_crossover_candidates, flag_value=_attn_flag,
    runner=_attn_runner))

autotune.register(autotune.OpSpec(
    "decode_crossover",
    heuristic=lambda p: {"use_flash": p["tk"] >= 2048},
    candidates=_crossover_candidates, flag_value=_decode_flag,
    runner=_decode_runner))
