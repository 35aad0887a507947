"""Paged KV cache for autoregressive decode.

vLLM's PagedAttention (SOSP '23) insight, applied to this stack:
instead of one contiguous (B, T_max, H, D) K/V buffer per layer —
whose T axis either reallocates as sequences grow (recompile) or pads
every sequence to the worst case (HBM waste) — K/V live in a
fixed-size pool of small pages, `(max_pages, page_size, row)` per
layer, preallocated once. A per-slot page table maps
logical token positions to physical pages, so sequence growth only
ever writes one row into an existing page (or walks onto a freshly
assigned one) and NO array shape ever changes: the whole decode loop
stays one compiled program regardless of how many sequences join,
leave, or how long they run.

A token's row holds every head side by side, `heads * head_dim`
padded up to whole 128-lane tiles (`ROW_ALIGN`), because the device
tiles an array's two minor axes (8 or 16 x 128 on a TPU) and picks
the layout that pads least: with `(25, 64)` or `(16, 1600)` minor,
the v5e lays the PAGE axis out minor-most, where no page is
contiguous, and its compiler re-lays the whole pool out around every
gather and scatter by page. With `(page_size, 1664)` minor the layout
is row-major, a page is one contiguous block, and pages are gathered
from and rows scattered into the pool where it lies
(tests/test_tpu_compile.py holds the compiler to it). The views
handed to attention are split back to `(heads, head_dim)`.

Everything device-side here is shape-static and jit-safe:

- :func:`init_cache` — allocate the pool (zeros) + identity tables;
- :func:`decode_rows` — a decode step's new rows as the pool stores
  them, which is all a step needs from here when its attention reads
  the pages where they lie (`ops.attention.paged_decode_attention`:
  float pools wherever the Pallas kernels run);
- :func:`decode_view` — otherwise (int8 pools, the CPU), one layer's
  dense (S, T, H, D) context for a decode step: ONE gather straight
  out of the stacked pool through (layer, page table), with the new
  token's row laid over position ``seq_lens[s]`` of every writing
  slot. Either way the pools are only read, so they stay
  loop-invariant in the layer scan (scanning them as ``xs``/``ys``
  made XLA copy every layer's slab, and the whole stack, in every
  step);
- :func:`append_rows` — the step's one write: scatter every layer's
  new rows `(L, S, row)` into the stacked pools after the scan, in
  place when the cache is donated (inactive slots and full contexts
  are routed out-of-range and dropped, so padded batch slots never
  corrupt live pages);
- :func:`write_prompt_layer` — bulk-scatter a whole (right-padded)
  prompt's K/V at prefill (pad rows land in pages past `seq_len` and
  are never gathered — the length mask owns validity); a per-slot
  ``start`` offset writes a partial chunk of the prompt instead, the
  primitive chunked prefill is built on;
- :func:`gather_layer` / :func:`length_mask` — page-table gather back
  to a dense (S, T, row) view + key-validity mask for attention, from
  one layer's pool or, given ``layer``, from the stacked pool;
  :func:`split_heads` makes it (S, T, H, D).

Int8 pages (``ZOO_TPU_KV_DTYPE=int8``): the pool stores int8 rows
plus a per-row-per-head scale array of the same page geometry
(`(num_layers, max_pages, page_size, heads)` f32 — "per-page scales
stored alongside the pages"). :func:`quantize_rows` computes the
symmetric scale `max|x| / 127` over ``head_dim`` at every write
(append and prompt scatter share the coordinate math, so the scale
rows land exactly where their K/V rows do; a decode step quantizes
its row once, and the overlay and the append use that same row), and
:func:`dequantize_rows` restores values at the gather before
attention — roughly 2x resident-sequence capacity for a bounded,
tested accuracy cost (tests/test_generate.py's kv-dtype conformance
matrix).

Row pages (:class:`RowPagedCache`, `PatternDecoder`'s cache): a
token's row is ONE vector a layer, whatever the layer's attention
part makes it (`part.row_width` values): multi-head latent
attention's normalised KV latent with the rotated key part beside it,
shared by every head; a grouped-query part's ``[k of its G heads | v
of its G heads]``. So there is one pool, not a K and a V pool, and
nothing here knows what a row holds: :func:`init_row_cache`,
:func:`row_decode_view`, :func:`append_pool_rows` and
:func:`write_prompt_rows` are the four operations above over that one
pool, through the same coordinates, gather and in-place scatters.
Int8 row pages are refused by name (the scales are per (token, head)
and the pool does not know a row's heads; a latent row has none), and
so is the handoff codec, which carries K and V.

A row cache may hold layers of two kinds side by side, each with its
own pool, row width and rule for which positions of a slot are live
(two geometries under one page table and one allocator: a model's
full layers may have 4 K/V heads and its sliding layers 8). *Context*
layers keep every position: ``pages`` (and, for layers whose
attention chooses its keys, ``index``: the index key of each token in
a pool of the same page geometry, so that scoring a context reads 128
values a token and not the row), addressed through the page table the
allocator fills. *Window* layers keep the last ``window`` positions
and the chunk being written: ``window`` is a ring of pages a slot
(logical page j of slot s lives in ring page ``s * ring + j % ring``),
so a page that has slid out of the window is the page the next tokens
are written to, the pool's size does not depend on ``max_context``,
and a slot's ring comes and goes with the slot (nothing to allocate
or release). :func:`window_view`, :func:`window_rows`,
:func:`window_table`, :func:`write_window_rows` and
:func:`gather_rows` are their primitives.

The host-side :class:`PageAllocator` is the bookkeeping half: a free
list of physical page ids for the continuous batcher, which assigns
pages at admission / token-boundary growth and reclaims them at
retirement (`pipeline/inference/batching.py::ContinuousBatcher`).
"""

from __future__ import annotations

import base64
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class PagedKVCache(NamedTuple):
    """The device-side cache state threaded through the decode loop.

    ``k_pages``/``v_pages``: (num_layers, max_pages, page_size, W)
    — the preallocated pools, W = ``heads * head_dim`` rounded up to
    ``ROW_ALIGN``.
    ``page_table``: (max_slots, pages_per_slot) int32 physical page
    ids (logical page j of slot s lives in ``page_table[s, j]``).
    ``seq_lens``: (max_slots,) int32 tokens currently cached per slot
    (0 = free slot; doubles as the active mask).
    ``k_scales``/``v_scales``: (num_layers, max_pages, page_size,
    heads) f32 per-row-per-head dequant scales, present only when the
    pools are int8 (None otherwise — None leaves are empty pytree
    nodes, so the jit'd programs simply specialize per cache dtype).
    """

    k_pages: jnp.ndarray
    v_pages: jnp.ndarray
    page_table: jnp.ndarray
    seq_lens: jnp.ndarray
    k_scales: "jnp.ndarray | None" = None
    v_scales: "jnp.ndarray | None" = None

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    @property
    def max_context(self) -> int:
        return self.page_table.shape[1] * self.page_size

    @property
    def max_slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def quantized(self) -> bool:
        return self.k_scales is not None

    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def pool_dtype(self):
        return self.k_pages.dtype


class RowPagedCache(NamedTuple):
    """A paged cache whose rows are one vector a token and layer,
    laid out by the layer's attention part (a latent, or the K and V
    of a few shared heads side by side): ``pages`` (num_layers,
    max_pages, page_size, W), W the row's width rounded up to
    ``ROW_ALIGN``; ``page_table`` and ``seq_lens`` as in
    :class:`PagedKVCache`, whose geometry properties it shares so
    that the engine's allocator and programs take either."""

    pages: jnp.ndarray
    page_table: jnp.ndarray
    seq_lens: jnp.ndarray
    # (context layers that index, max_pages, page_size, index width):
    # the index keys, at their rows' coordinates; None without
    index: "jnp.ndarray | None" = None
    # (window layers, max_slots * ring, page_size, window row): the
    # window layers' ring of pages a slot; None without
    window: "jnp.ndarray | None" = None

    page_size = property(lambda self: self.pages.shape[2])
    max_context = PagedKVCache.max_context
    max_slots = PagedKVCache.max_slots
    num_pages = property(lambda self: self.pages.shape[1])
    pool_dtype = property(lambda self: self.pages.dtype)
    window_ring = property(
        lambda self: self.window.shape[1] // self.page_table.shape[0])


# K/V rows are padded to whole lane tiles: what keeps the device's
# layout of a pool row-major (see the module docstring)
ROW_ALIGN = 128


def init_cache(num_layers: int, max_slots: int, max_context: int,
               heads: int, head_dim: int, page_size: int = 16,
               max_pages: int = 0,
               dtype=jnp.float32) -> PagedKVCache:
    """Allocate the pool. ``max_context`` rounds up to whole pages.
    ``max_pages`` defaults to ``max_slots * pages_per_slot`` (every
    slot can reach max_context simultaneously) and the table starts as
    the identity mapping — the compiled-loop `generate()` path uses it
    as-is; the continuous batcher overwrites tables from its
    :class:`PageAllocator` as sequences come and go."""
    shape, table = _pool_geometry(num_layers, max_slots, max_context,
                                  heads * head_dim, page_size,
                                  max_pages)
    quantized = jnp.dtype(dtype) == jnp.dtype(jnp.int8)
    scale_shape = shape[:3] + (heads,)
    return PagedKVCache(
        k_pages=jnp.zeros(shape, dtype),
        v_pages=jnp.zeros(shape, dtype),
        page_table=table,
        seq_lens=jnp.zeros((int(max_slots),), jnp.int32),
        k_scales=jnp.zeros(scale_shape, jnp.float32)
        if quantized else None,
        v_scales=jnp.zeros(scale_shape, jnp.float32)
        if quantized else None,
    )


def _pool_geometry(num_layers, max_slots, max_context, width,
                   page_size, max_pages):
    """A pool's shape, rows padded to ``ROW_ALIGN``, and the identity
    page table."""
    max_slots, page_size = int(max_slots), int(page_size)
    pages_per_slot = -(-int(max_context) // page_size)
    max_pages = int(max_pages) or max_slots * pages_per_slot
    if max_pages < max_slots * pages_per_slot:
        raise ValueError(
            f"max_pages {max_pages} < max_slots*pages_per_slot "
            f"{max_slots * pages_per_slot}; the identity table "
            f"would alias pages")
    shape = (int(num_layers), max_pages, page_size,
             -(-int(width) // ROW_ALIGN) * ROW_ALIGN)
    table = np.arange(max_slots * pages_per_slot, dtype=np.int32)
    return shape, jnp.asarray(table.reshape(max_slots, pages_per_slot))


def init_row_cache(num_layers: int, max_slots: int,
                   max_context: int, width: int,
                   page_size: int = 16, max_pages: int = 0,
                   dtype=jnp.float32, index_layers: int = 0,
                   index_width: int = 0, window_layers: int = 0,
                   window_width: int = 0, window_tokens: int = 0
                   ) -> RowPagedCache:
    """Allocate a pool of ``width``-value rows (whatever the
    attention part caches of a token: `part.row_width`) for
    ``num_layers`` context layers; geometry and table as
    :func:`init_cache`. With
    ``index_layers`` an index pool of ``index_width`` beside it, and
    with ``window_layers`` the window pool: rows of ``window_width``
    in a ring a slot that holds ``window_tokens`` consecutive
    positions wherever they start (one page more than they fill)."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        raise ValueError(
            "int8 pages for a row cache (RowPagedCache; a latent cache "
            "among them): the int8 scales are per (token, head) and "
            "the pool does not know a row's heads; use f32 or bf16")
    shape, table = _pool_geometry(num_layers, max_slots, max_context,
                                  width, page_size, max_pages)
    index = window = None
    if index_layers:
        index = jnp.zeros((int(index_layers),) + shape[1:3] + (
            -(-int(index_width) // ROW_ALIGN) * ROW_ALIGN,), dtype)
    if window_layers:
        ring = -(-int(window_tokens) // shape[2]) + 1
        window = jnp.zeros(
            (int(window_layers), int(max_slots) * ring, shape[2],
             -(-int(window_width) // ROW_ALIGN) * ROW_ALIGN), dtype)
    return RowPagedCache(
        pages=jnp.zeros(shape, dtype), page_table=table,
        seq_lens=jnp.zeros((int(max_slots),), jnp.int32),
        index=index, window=window)


# int8 pages: symmetric per-(token, head) quantization over head_dim.
# 127 (not 128) keeps the grid symmetric so dequant is a plain scale.
INT8_QMAX = 127.0


def quantize_rows(x):
    """Quantize K/V rows ``(…, heads, head_dim)`` to int8 with one
    f32 scale per ``(…, heads)`` row: ``scale = max|x| / 127`` over
    head_dim, ``q = round(x / scale)``. Zero rows get scale 0 and
    dequantize back to exact zeros."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / INT8_QMAX
    q = jnp.round(xf / jnp.maximum(scale, 1e-12)[..., None])
    q = jnp.clip(q, -INT8_QMAX, INT8_QMAX).astype(jnp.int8)
    return q, scale


def dequantize_rows(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_rows`: ``(…, H, D)`` int8 + ``(…,
    H)`` f32 scales back to ``dtype`` values."""
    out = q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
    return out.astype(dtype)


def _scatter_coords(page_table, seq_lens, positions, page_size,
                    active):
    """(physical page, in-page offset) per (slot, position); inactive
    rows are pushed out of range so ``mode="drop"`` discards them."""
    pages_per_slot = page_table.shape[1]
    logical = positions // page_size                 # (S, ...) int32
    # clamp the table lookup; `active` (which callers AND with
    # position < max_context) owns whether the row lands at all
    logical = jnp.minimum(logical, pages_per_slot - 1)
    phys = jnp.take_along_axis(
        page_table, logical.reshape(page_table.shape[0], -1), axis=1
    ).reshape(logical.shape)
    offset = positions % page_size
    max_pages_shape = page_table.shape[0] * page_table.shape[1]
    # any value past every real page id works as the drop sentinel
    phys = jnp.where(active, phys, max_pages_shape + 2 ** 20)
    return phys, offset


def _pool_rows(pages, x):
    """K/V rows ``(…, heads, head_dim)`` as the pool stores them:
    ``(…, W)``, heads side by side and zero-padded to the pool's row,
    in its dtype — through :func:`quantize_rows` with ``(…, heads)``
    scales when the pool is int8 (None otherwise)."""
    if pages.dtype == jnp.int8:
        x, scales = quantize_rows(x)
    else:
        x, scales = x.astype(pages.dtype), None
    x = x.reshape(x.shape[:-2] + (-1,))
    pad = [(0, 0)] * (x.ndim - 1) + [(0, pages.shape[-1] - x.shape[-1])]
    return jnp.pad(x, pad), scales


def split_heads(view, heads: int, head_dim: int):
    """A gathered K/V view ``(S, T, W)`` as attention takes it:
    ``(S, T, heads, head_dim)``, the row's padding cut off."""
    return view[..., :heads * head_dim].reshape(
        view.shape[:-1] + (heads, head_dim))


def _decode_writes(cache, active):
    """(S,) bool: the slots whose new row lands this step — active
    (``None`` = every slot) and not yet at ``max_context``."""
    room = cache.seq_lens < cache.max_context
    return room if active is None else jnp.logical_and(active, room)


def decode_rows(cache: PagedKVCache, k_new, v_new):
    """A decode step's new K/V rows as :func:`append_rows` writes
    them once every layer's are stacked. ``k_new``/``v_new``:
    (S, H, D), the new token of every slot. Returns ``(k_row, v_row,
    k_srow, v_srow)``: (S, W) rows in the pool's dtype (quantized for
    int8 pools) and their (S, H) scales (None for float pools). No
    read of the pools: attention that takes its context from the
    pages themselves (`ops.attention.paged_decode_attention`) needs
    nothing else from here."""
    k_row, k_srow = _pool_rows(cache.k_pages, k_new)
    v_row, v_srow = _pool_rows(cache.v_pages, v_new)
    return k_row, v_row, k_srow, v_srow


def decode_view(cache: PagedKVCache, layer, k_new, v_new,
                active=None):
    """One layer's dense attention operands for a decode step, the
    pools only read.

    ``layer``: scalar index into the stacked pools (traced inside the
    layer scan); ``k_new``/``v_new``: (S, H, D) — the new token of
    every slot. Gathers the layer's dense context through the page
    table and lays each writing slot's row (:func:`decode_rows`) over
    position ``seq_lens[s]``, so attention sees exactly what a gather
    after the append would hold. Returns ``(ctx, rows)``: ``ctx =
    (k_ctx, v_ctx, k_sctx, v_sctx)``, (S, T, H, D) views in the
    pool's dtype and their (S, T, H) scale views (None for float
    pools); ``rows``: :func:`decode_rows`' result."""
    t_max = cache.max_context
    writes = _decode_writes(cache, active)
    rows = decode_rows(cache, k_new, v_new)

    def view(pages, scales, row, srow, heads_dim):
        ctx = _lay_rows(
            gather_layer(pages, cache.page_table, t_max, layer),
            cache.seq_lens, row, writes)
        ctx = split_heads(ctx, *heads_dim)
        if scales is None:
            return ctx, None
        return ctx, _lay_rows(
            gather_layer(scales, cache.page_table, t_max, layer),
            cache.seq_lens, srow, writes)

    k_row, v_row, k_srow, v_srow = rows
    k_ctx, k_sctx = view(cache.k_pages, cache.k_scales, k_row, k_srow,
                         k_new.shape[1:])
    v_ctx, v_sctx = view(cache.v_pages, cache.v_scales, v_row, v_srow,
                         v_new.shape[1:])
    return (k_ctx, v_ctx, k_sctx, v_sctx), rows


@jax.named_scope("zoo:kv_cache/gather")
def _lay_rows(ctx, seq_lens, rows, writes):
    """``ctx`` (S, T, W) with ``rows[s]`` (W,) at position
    ``seq_lens[s]`` of every slot in ``writes``."""
    at = jnp.arange(ctx.shape[1], dtype=jnp.int32)[None, :]
    hit = jnp.logical_and(at == seq_lens[:, None], writes[:, None])
    return jnp.where(hit[:, :, None], rows[:, None], ctx)


@jax.named_scope("zoo:kv_cache/append")
def append_rows(cache: PagedKVCache, rows, active=None):
    """Scatter one decode step's rows of EVERY layer into the stacked
    pools: the step's only write to them.

    ``rows``: :func:`decode_view`'s second result with a leading
    layer axis — k/v (L, S, W) in the pool's dtype, scale rows
    (L, S, H) for int8 pools — written at position ``seq_lens[s]``
    of every slot. Slots with ``active == False`` and slots already
    at ``max_context`` are routed out of range and dropped, not
    written; the scale rows scatter through the SAME coordinates, so
    drop semantics match exactly. Returns the cache with the pools
    replaced (``seq_lens`` is the caller's to advance). Shape-static;
    one in-place scatter a pool when the cache is donated."""
    phys, offset = _scatter_coords(
        cache.page_table, cache.seq_lens, cache.seq_lens,
        cache.page_size, _decode_writes(cache, active))
    k_row, v_row, k_srow, v_srow = rows
    cache = cache._replace(
        k_pages=_put_rows(cache.k_pages, phys, offset, k_row),
        v_pages=_put_rows(cache.v_pages, phys, offset, v_row))
    if not cache.quantized:
        return cache
    return cache._replace(
        k_scales=_put_rows(cache.k_scales, phys, offset, k_srow),
        v_scales=_put_rows(cache.v_scales, phys, offset, v_srow))


def _put_rows(pages, phys, offset, rows):
    """Scatter ``rows`` (..., W) to (page, in-page offset) ``phys`` /
    ``offset`` (...) of one layer's pool (P, page, W); out-of-range
    pages drop. A stacked pool (L, P, page, W) takes rows with a
    leading layer axis at the same coordinates in every layer — each
    (W,) row its own update: a window over the layer axis makes the
    TPU compiler re-lay the whole pool out around the scatter."""
    if pages.ndim == 3:
        return pages.at[phys, offset].set(rows, mode="drop")
    layers = jnp.arange(pages.shape[0], dtype=jnp.int32).reshape(
        (-1,) + (1,) * phys.ndim)
    return pages.at[layers, phys[None], offset[None]].set(
        rows, mode="drop")


def _padded_rows(pages, x):
    """Rows ``(…, width)`` as the pool stores them: its dtype,
    zero-padded to its row."""
    pad = [(0, 0)] * (x.ndim - 1) + [(0, pages.shape[-1] - x.shape[-1])]
    return jnp.pad(x.astype(pages.dtype), pad)


def row_decode_view(cache: RowPagedCache, layer, new, active=None):
    """:func:`decode_view` for a row pool. ``new``: (S, width),
    the new token's row of every slot. Returns ``(ctx, row)``:
    the layer's (S, T, W) context in the pool's dtype with each
    writing slot's row at position ``seq_lens[s]``, and the (S, W)
    row :func:`append_pool_rows` writes."""
    row = _padded_rows(cache.pages, new)
    ctx = _lay_rows(
        gather_layer(cache.pages, cache.page_table, cache.max_context,
                     layer),
        cache.seq_lens, row, _decode_writes(cache, active))
    return ctx, row


@jax.named_scope("zoo:kv_cache/append")
def append_pool_rows(cache: RowPagedCache, rows, active=None,
                     index_rows=None, window_rows=None):
    """:func:`append_rows` for a row cache: ``rows`` (L, S, W),
    every context layer's new row, scattered in place at
    ``seq_lens[s]``; ``index_rows`` and ``window_rows`` likewise into
    the index and the window pool."""
    writes = _decode_writes(cache, active)
    phys, offset = _scatter_coords(
        cache.page_table, cache.seq_lens, cache.seq_lens,
        cache.page_size, writes)
    if rows is not None:
        cache = cache._replace(
            pages=_put_rows(cache.pages, phys, offset, rows))
    if index_rows is not None:
        cache = cache._replace(
            index=_put_rows(cache.index, phys, offset, index_rows))
    if window_rows is not None:
        cache = cache._replace(window=write_window_rows(
            cache, jnp.arange(cache.max_slots, dtype=jnp.int32),
            cache.seq_lens[:, None], writes[:, None],
            window_rows[:, :, None]))
    return cache


def _window_coords(cache: RowPagedCache, slots, positions, active):
    """(ring page, in-page offset) of ``positions`` (A, C) of slots
    ``slots`` (A,) in the window pool; inactive ones out of range."""
    ring, page = cache.window_ring, cache.page_size
    phys = slots[:, None] * ring + (positions // page) % ring
    return jnp.where(active, phys, cache.window.shape[1] + 2 ** 20), \
        positions % page


@jax.named_scope("zoo:kv_cache/write_prompt")
def write_window_rows(cache: RowPagedCache, slots, positions,
                      active, rows):
    """The window pool with ``rows`` (L, A, C, width) written at
    ``positions`` (A, C) of slots ``slots`` (A,) where ``active``.
    The positions written in one call must lie within one turn of the
    ring (``(ring - 1) * page_size`` consecutive positions a slot),
    or two of them would fall on one row."""
    phys, offset = _window_coords(cache, slots, positions, active)
    return _put_rows(cache.window, phys, offset,
                     _padded_rows(cache.window, rows))


@jax.named_scope("zoo:kv_cache/gather")
def window_view(cache: RowPagedCache, layer, slots, first_page,
                n_pages: int):
    """``n_pages`` consecutive logical pages of window layer
    ``layer`` from ``first_page`` (A,) on, for slots ``slots`` (A,):
    ``(rows (A, n_pages * page_size, W), positions (A, n_pages *
    page_size))``. A row holds its position only if that position was
    written within the last turn of the ring: the caller's mask owns
    validity."""
    ring, page = cache.window_ring, cache.page_size
    logical = first_page[:, None] + jnp.arange(n_pages,
                                               dtype=jnp.int32)[None]
    picked = cache.window.at[
        layer, slots[:, None] * ring + logical % ring].get(mode="clip")
    positions = (logical[:, :, None] * page + jnp.arange(
        page, dtype=jnp.int32)).reshape(len(slots), n_pages * page)
    return picked.reshape(len(slots), n_pages * page, -1), positions


@jax.named_scope("zoo:kv_cache/gather")
def window_rows(cache: RowPagedCache, layer, slots, positions):
    """Single rows of window layer ``layer`` out of the ring:
    ``positions`` (A, K) of slots ``slots`` (A,) -> (A, K, W). A row
    holds its position only if that position was written within the
    last turn of the ring and is not negative: the caller's mask owns
    validity."""
    ring, page = cache.window_ring, cache.page_size
    at = jnp.maximum(positions, 0)
    return cache.window.at[
        layer, slots[:, None] * ring + (at // page) % ring,
        at % page].get(mode="clip")


def window_table(cache: RowPagedCache, window: int):
    """What a decode step's sliding layers read of the ring, as a
    page table: ``(table (S, n), lens (S,), first (S,))``. Row s
    lists the ring pages that hold slot s's last ``window - 1``
    cached positions, in order; of the positions of that row, counted
    from its first page's first, ``[first[s], lens[s])`` are the
    window (the new token's own position is the caller's to add)."""
    ring, page = cache.window_ring, cache.page_size
    n = max(window - 2, 0) // page + 2
    low = jnp.maximum(cache.seq_lens - (window - 1), 0)
    page0 = low // page
    slots = jnp.arange(cache.max_slots, dtype=jnp.int32)
    table = slots[:, None] * ring + (
        page0[:, None] + jnp.arange(n, dtype=jnp.int32)[None]) % ring
    return table, cache.seq_lens - page0 * page, low - page0 * page


@jax.named_scope("zoo:kv_cache/gather")
def gather_rows(pages, page_table, positions, layer):
    """Single rows of one layer of a stacked pool: ``positions``
    (A, K) of the slots whose table rows are ``page_table`` (A,
    pages_per_slot) -> (A, K, W)."""
    page = pages.shape[-2]
    phys = jnp.take_along_axis(
        page_table, jnp.minimum(positions // page,
                                page_table.shape[1] - 1), axis=1)
    return pages.at[layer, phys, positions % page].get(mode="clip")


def prompt_seq_lens(seq_lens, slots, prompt_lens):
    """``seq_lens`` (S,) after a prefill of rows ``slots`` (A,):
    ``prompt_lens[a]`` at slot ``slots[a]`` of every row that holds a
    prompt; a row with ``prompt_lens == 0`` leaves its slot's length,
    and every slot not named keeps its own."""
    return seq_lens.at[slots].set(
        jnp.where(prompt_lens > 0, prompt_lens, seq_lens[slots]))


@jax.named_scope("zoo:kv_cache/write_prompt")
def write_prompt_rows(pages, page_table, prompt_lens, rows,
                      start=None):
    """:func:`write_prompt_layer` for a row pool: ``rows``
    (L, A, T, width) hold every layer's (right-padded) prompt rows,
    ``page_table`` (A, pages_per_slot) the table row of each;
    positions past ``prompt_lens[a]`` are dropped. With ``start``
    (A,) row j lands at ``start[a] + j`` and ``prompt_lens`` is the
    length after the chunk. Returns the pool."""
    a, t = rows.shape[1], rows.shape[2]
    page_size = pages.shape[-2]
    positions = jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32)[None, :], (a, t))
    if start is not None:
        positions = positions + jnp.asarray(start, jnp.int32)[:, None]
    active = jnp.logical_and(
        positions < prompt_lens[:, None],
        positions < page_table.shape[1] * page_size)
    phys, offset = _scatter_coords(page_table, prompt_lens,
                                   positions, page_size, active)
    return _put_rows(pages, phys, offset, _padded_rows(pages, rows))


@jax.named_scope("zoo:kv_cache/write_prompt")
def write_prompt_layer(k_pages, v_pages, page_table, prompt_lens,
                       k_seq, v_seq, start=None,
                       k_scales=None, v_scales=None):
    """Bulk prefill scatter for one layer: k_seq/v_seq (S, T, H, D)
    hold the (right-padded) prompt K/V; positions past
    ``prompt_lens[s]`` are dropped (never written), so pad tokens
    cannot leak into pages a later admit might reuse. Stacked pools
    (L, P, page, W) with k_seq/v_seq (L, S, T, H, D) write every
    layer at once (whole-prompt prefill, after its layer scan).
    ``page_table`` has one row for each row of k_seq: the cache's
    table when every slot is a row, ``cache.page_table[slots]`` when
    the rows are the slots being admitted.

    ``start`` (S,) int32 shifts each slot's write window: row j of
    k_seq lands at position ``start[s] + j`` (still gated by
    ``position < prompt_lens[s]``, where prompt_lens is the TOTAL
    length the sequence will have after this chunk). This is the
    partial-prompt primitive chunked prefill interleaves with decode
    steps — each chunk is one bounded scatter at its offset, and a
    slot not being chunk-prefilled passes ``prompt_lens == 0`` and is
    untouched. Scale pools (int8) take the scale rows through the
    same coordinates, as in :func:`append_rows`."""
    s, t = k_seq.shape[-4], k_seq.shape[-3]
    page_size = k_pages.shape[-2]
    positions = jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32)[None, :], (s, t))
    if start is not None:
        positions = positions + jnp.asarray(start, jnp.int32)[:, None]
    max_ctx = page_table.shape[1] * page_size
    active = jnp.logical_and(positions < prompt_lens[:, None],
                             positions < max_ctx)
    phys, offset = _scatter_coords(page_table, prompt_lens, positions,
                                   page_size, active)
    k_seq, k_s = _pool_rows(k_pages, k_seq)
    v_seq, v_s = _pool_rows(v_pages, v_seq)
    k_pages = _put_rows(k_pages, phys, offset, k_seq)
    v_pages = _put_rows(v_pages, phys, offset, v_seq)
    if k_scales is None:
        return k_pages, v_pages
    k_scales = _put_rows(k_scales, phys, offset, k_s)
    v_scales = _put_rows(v_scales, phys, offset, v_s)
    return k_pages, v_pages, k_scales, v_scales


@jax.named_scope("zoo:kv_cache/gather")
def gather_layer(pages, page_table, t_max: int, layer=None):
    """Page-table gather back to a dense (S, t_max, W) view of one
    layer's cache, W the pool's row: the padded ``heads * head_dim``
    of a K/V pool, ``heads`` of a scale pool (positions past a slot's
    ``seq_len`` hold stale/zero rows — :func:`length_mask` owns
    validity). ``t_max`` is static and must be a whole number of
    pages. ``pages`` is one layer's pool (P, page, W), or with
    ``layer`` (a scalar, traced or not) the stacked pool
    (L, P, page, W): the gather then indexes (layer, page) at once
    and no layer's slab is sliced out first."""
    page_size = pages.shape[-2]
    if t_max % page_size:
        raise ValueError(f"t_max {t_max} not a multiple of page_size "
                         f"{page_size}")
    ids = page_table[:, :t_max // page_size]
    if layer is None:
        picked = jnp.take(pages, ids, axis=0, mode="clip")
    else:
        picked = pages.at[layer, ids].get(mode="clip")
    # (S, n, page, W) -> (S, t_max, W)
    return picked.reshape(ids.shape[0], t_max, pages.shape[-1])


def length_mask(seq_lens, t: int):
    """(S, t) bool key-validity mask: position p of slot s is a real
    cached token iff ``p < seq_lens[s]``."""
    return jnp.arange(t, dtype=jnp.int32)[None, :] < seq_lens[:, None]


# -- KV-page handoff (prefill/decode disaggregation) ---------------------
#
# DistServe/Splitwise-style pool separation needs one sequence's cache
# state to MOVE between engines. Because the cache is block-granular,
# that transfer is a page gather on the source + a page scatter on the
# destination — never a per-token reshape — and both sides are
# shape-static over the full ``pages_per_slot`` width (unused entries
# ride along masked/dropped), so each engine compiles its half exactly
# once and reuses it for every handoff regardless of sequence length.


def refuse_row_handoff(cache):
    """Raise, by name, for a cache the handoff codec does not
    carry."""
    if isinstance(cache, RowPagedCache):
        raise TypeError(
            "the KV-page handoff carries K and V pools (blob version "
            f"{HANDOFF_VERSION}); a row cache (RowPagedCache; a "
            "latent cache among them) is not carried: serve it with "
            "role='both'")


def gather_slot_pages(cache: PagedKVCache, page_ids):
    """Gather one slot's pages out of every layer's pool.

    ``page_ids``: (P,) int32 physical page ids — the slot's page-table
    row, fixed width (entries past the used prefix may repeat a real
    page; the caller slices the used prefix host-side). Returns
    ``(k, v, k_scales, v_scales)`` with k/v shaped
    ``(num_layers, P, page_size, W)`` and scales
    ``(num_layers, P, page_size, heads)`` (None for float pools)."""
    refuse_row_handoff(cache)
    k = jnp.take(cache.k_pages, page_ids, axis=1, mode="clip")
    v = jnp.take(cache.v_pages, page_ids, axis=1, mode="clip")
    if cache.k_scales is None:
        return k, v, None, None
    k_s = jnp.take(cache.k_scales, page_ids, axis=1, mode="clip")
    v_s = jnp.take(cache.v_scales, page_ids, axis=1, mode="clip")
    return k, v, k_s, v_s


def scatter_slot_pages(cache: PagedKVCache, page_ids, active, slot,
                       seq_len, k_rows, v_rows, k_srows=None,
                       v_srows=None):
    """Splice gathered pages into freshly allocated destination pages.

    ``page_ids``: (P,) int32 destination physical ids; ``active``:
    (P,) bool — True for the used prefix (inactive entries are routed
    out of range and dropped, so zero padding never lands in live
    pages). ``slot``/``seq_len``: scalars — the destination slot's
    ``seq_lens`` entry is set so the very next decode step appends at
    the correct position. ``k_rows``/``v_rows`` (and scale rows for
    int8 pools) are the :func:`gather_slot_pages` outputs, zero-padded
    to width P. Returns the updated cache; the caller owns writing the
    destination page-table row (host-side bookkeeping)."""
    refuse_row_handoff(cache)
    max_pages = cache.k_pages.shape[1]
    phys = jnp.where(active, page_ids, max_pages + 2 ** 20)
    k_pages = cache.k_pages.at[:, phys].set(k_rows, mode="drop")
    v_pages = cache.v_pages.at[:, phys].set(v_rows, mode="drop")
    seq_lens = cache.seq_lens.at[slot].set(
        jnp.asarray(seq_len, jnp.int32))
    if cache.k_scales is None:
        return cache._replace(k_pages=k_pages, v_pages=v_pages,
                              seq_lens=seq_lens)
    k_scales = cache.k_scales.at[:, phys].set(k_srows, mode="drop")
    v_scales = cache.v_scales.at[:, phys].set(v_srows, mode="drop")
    return cache._replace(k_pages=k_pages, v_pages=v_pages,
                          seq_lens=seq_lens, k_scales=k_scales,
                          v_scales=v_scales)


# Handoff blob: a host-side dict holding one sequence's cache rows plus
# the decode-resume state. Array fields (below) are np arrays sliced to
# the used page count; everything else is plain scalars, so the wire
# codec round-trips through JSON for the HTTP hop.
HANDOFF_VERSION = 2
_WIRE_ARRAYS = ("k", "v", "k_scales", "v_scales")


def _arr_to_wire(a) -> dict:
    a = np.ascontiguousarray(a)
    return {"shape": list(a.shape), "dtype": a.dtype.name,
            "data": base64.b64encode(a.tobytes()).decode("ascii")}


def _arr_from_wire(w):
    a = np.frombuffer(base64.b64decode(w["data"]),
                      dtype=np.dtype(str(w["dtype"])))
    return a.reshape([int(d) for d in w["shape"]]).copy()


def handoff_to_wire(blob: dict) -> dict:
    """JSON-safe encoding of a handoff blob: arrays become
    ``{shape, dtype, data: base64}`` (bfloat16 rides through ml_dtypes'
    registered np dtype; int8 pages keep their ~3.7x size edge on the
    wire)."""
    wire = {k: v for k, v in blob.items() if k not in _WIRE_ARRAYS}
    for name in _WIRE_ARRAYS:
        a = blob.get(name)
        wire[name] = None if a is None else _arr_to_wire(a)
    return wire


def handoff_from_wire(wire: dict) -> dict:
    """Inverse of :func:`handoff_to_wire` — bit-exact array restore."""
    blob = {k: v for k, v in wire.items() if k not in _WIRE_ARRAYS}
    for name in _WIRE_ARRAYS:
        w = wire.get(name)
        blob[name] = None if w is None else _arr_from_wire(w)
    return blob


def handoff_nbytes(blob: dict) -> int:
    """Payload size of the blob's array fields (wire-cost metric)."""
    return sum(int(blob[n].nbytes) for n in _WIRE_ARRAYS
               if blob.get(n) is not None)


class PageAllocator:
    """Host-side free list over the physical page pool (the half of
    PagedAttention that is pure bookkeeping, so it stays in Python:
    the continuous batcher calls it between compiled steps, never
    inside them).

    Not thread-safe by itself — the batcher serializes access under
    its own lock.
    """

    def __init__(self, max_pages: int):
        self.max_pages = int(max_pages)
        self._free = list(range(self.max_pages - 1, -1, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> "list[int]":
        """Pop ``n`` physical page ids; raises MemoryError when the
        pool cannot satisfy the request (callers check
        :meth:`can_alloc` to defer admission instead)."""
        if n > len(self._free):
            raise MemoryError(
                f"page pool exhausted: want {n}, have "
                f"{len(self._free)} of {self.max_pages}")
        if n <= 0:
            return []
        out = self._free[-n:][::-1]
        del self._free[-n:]
        return out

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if not 0 <= p < self.max_pages:
                raise ValueError(f"bad page id {p}")
        self._free.extend(pages)

    @staticmethod
    def pages_needed(tokens: int, page_size: int) -> int:
        return -(-int(tokens) // int(page_size))
