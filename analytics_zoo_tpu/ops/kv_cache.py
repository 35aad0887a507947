"""Paged KV cache for autoregressive decode.

vLLM's PagedAttention (SOSP '23) insight, applied to this stack:
instead of one contiguous (B, T_max, H, D) K/V buffer per layer —
whose T axis either reallocates as sequences grow (recompile) or pads
every sequence to the worst case (HBM waste) — K/V live in a
fixed-size pool of small pages, `(max_pages, page_size, heads,
head_dim)` per layer, preallocated once. A per-slot page table maps
logical token positions to physical pages, so sequence growth only
ever writes one (heads, head_dim) row into an existing page (or walks
onto a freshly assigned one) and NO array shape ever changes: the
whole decode loop stays one compiled program regardless of how many
sequences join, leave, or how long they run.

Everything device-side here is shape-static and jit-safe:

- :func:`init_cache` — allocate the pool (zeros) + identity tables;
- :func:`append_layer` — scatter one new token's K/V per slot into
  one layer's pool (inactive slots are routed out-of-range and
  dropped, so padded batch slots never corrupt live pages);
- :func:`write_prompt_layer` — bulk-scatter a whole (right-padded)
  prompt's K/V at prefill (pad rows land in pages past `seq_len` and
  are never gathered — the length mask owns validity); a per-slot
  ``start`` offset writes a partial chunk of the prompt instead, the
  primitive chunked prefill is built on;
- :func:`gather_layer` / :func:`length_mask` — page-table gather back
  to a dense (S, T, H, D) view + key-validity mask for attention.

Int8 pages (``ZOO_TPU_KV_DTYPE=int8``): the pool stores int8 rows
plus a per-row-per-head scale array of the same page geometry
(`(num_layers, max_pages, page_size, heads)` f32 — "per-page scales
stored alongside the pages"). :func:`quantize_rows` computes the
symmetric scale `max|x| / 127` over ``head_dim`` at every write
(append and prompt scatter share the coordinate math, so the scale
rows land exactly where their K/V rows do), and
:func:`dequantize_rows` restores values at the gather before
attention — roughly 2x resident-sequence capacity for a bounded,
tested accuracy cost (tests/test_generate.py's kv-dtype conformance
matrix).

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

    ``k_pages``/``v_pages``: (num_layers, max_pages, page_size,
    heads, head_dim) — the preallocated pools.
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
    pages_per_slot = -(-int(max_context) // int(page_size))
    max_pages = int(max_pages) or int(max_slots) * pages_per_slot
    if max_pages < max_slots * pages_per_slot:
        raise ValueError(
            f"max_pages {max_pages} < max_slots*pages_per_slot "
            f"{max_slots * pages_per_slot}; the identity table "
            f"would alias pages")
    shape = (num_layers, max_pages, page_size, heads, head_dim)
    table = np.arange(max_slots * pages_per_slot, dtype=np.int32)
    quantized = jnp.dtype(dtype) == jnp.dtype(jnp.int8)
    scale_shape = (num_layers, max_pages, page_size, heads)
    return PagedKVCache(
        k_pages=jnp.zeros(shape, dtype),
        v_pages=jnp.zeros(shape, dtype),
        page_table=jnp.asarray(
            table.reshape(max_slots, pages_per_slot)),
        seq_lens=jnp.zeros((max_slots,), jnp.int32),
        k_scales=jnp.zeros(scale_shape, jnp.float32)
        if quantized else None,
        v_scales=jnp.zeros(scale_shape, jnp.float32)
        if quantized else None,
    )


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


def _quantize_for(pages, x):
    """Route a write through :func:`quantize_rows` when the pool is
    int8; (values, scales-or-None) otherwise."""
    if pages.dtype == jnp.int8:
        return quantize_rows(x)
    return x.astype(pages.dtype), None


@jax.named_scope("zoo:kv_cache/append")
def append_layer(k_pages, v_pages, page_table, seq_lens,
                 k_new, v_new, active=None,
                 k_scales=None, v_scales=None):
    """Scatter one decode step's K/V into one layer's pool.

    k_pages/v_pages: (P, page, H, D); k_new/v_new: (S, H, D) — the new
    token of every slot, written at position ``seq_lens[s]``. Slots
    with ``active == False`` (or ``seq_lens == 0`` when active is
    None... callers pass the done-mask) are dropped, not written.
    Returns the updated (k_pages, v_pages), plus the updated
    (k_scales, v_scales) when scale pools are passed (int8 pages:
    values are quantized per row and the scale rows scatter through
    the SAME coordinates, so drop semantics match exactly).
    Shape-static; safe inside scan/while_loop."""
    page_size = k_pages.shape[1]
    if active is None:
        active = jnp.ones(seq_lens.shape, jnp.bool_)
    max_ctx = page_table.shape[1] * page_size
    active = jnp.logical_and(active, seq_lens < max_ctx)
    phys, offset = _scatter_coords(page_table, seq_lens, seq_lens,
                                   page_size, active)
    k_new, k_s = _quantize_for(k_pages, k_new)
    v_new, v_s = _quantize_for(v_pages, v_new)
    k_pages = k_pages.at[phys, offset].set(k_new, mode="drop")
    v_pages = v_pages.at[phys, offset].set(v_new, mode="drop")
    if k_scales is None:
        return k_pages, v_pages
    k_scales = k_scales.at[phys, offset].set(k_s, mode="drop")
    v_scales = v_scales.at[phys, offset].set(v_s, mode="drop")
    return k_pages, v_pages, k_scales, v_scales


@jax.named_scope("zoo:kv_cache/write_prompt")
def write_prompt_layer(k_pages, v_pages, page_table, prompt_lens,
                       k_seq, v_seq, start=None,
                       k_scales=None, v_scales=None):
    """Bulk prefill scatter for one layer: k_seq/v_seq (S, T, H, D)
    hold the (right-padded) prompt K/V; positions past
    ``prompt_lens[s]`` are dropped (never written), so pad tokens
    cannot leak into pages a later admit might reuse.

    ``start`` (S,) int32 shifts each slot's write window: row j of
    k_seq lands at position ``start[s] + j`` (still gated by
    ``position < prompt_lens[s]``, where prompt_lens is the TOTAL
    length the sequence will have after this chunk). This is the
    partial-prompt primitive chunked prefill interleaves with decode
    steps — each chunk is one bounded scatter at its offset, and a
    slot not being chunk-prefilled passes ``prompt_lens == 0`` and is
    untouched. Scale pools (int8) behave as in
    :func:`append_layer`."""
    s, t = k_seq.shape[0], k_seq.shape[1]
    page_size = k_pages.shape[1]
    positions = jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32)[None, :], (s, t))
    if start is not None:
        positions = positions + jnp.asarray(start, jnp.int32)[:, None]
    max_ctx = page_table.shape[1] * page_size
    active = jnp.logical_and(positions < prompt_lens[:, None],
                             positions < max_ctx)
    phys, offset = _scatter_coords(page_table, prompt_lens, positions,
                                   page_size, active)
    k_seq, k_s = _quantize_for(k_pages, k_seq)
    v_seq, v_s = _quantize_for(v_pages, v_seq)
    k_pages = k_pages.at[phys, offset].set(k_seq, mode="drop")
    v_pages = v_pages.at[phys, offset].set(v_seq, mode="drop")
    if k_scales is None:
        return k_pages, v_pages
    k_scales = k_scales.at[phys, offset].set(k_s, mode="drop")
    v_scales = v_scales.at[phys, offset].set(v_s, mode="drop")
    return k_pages, v_pages, k_scales, v_scales


@jax.named_scope("zoo:kv_cache/gather")
def gather_layer(pages, page_table, t_max: int):
    """Page-table gather back to a dense (S, t_max, H, D) view of one
    layer's cache (positions past a slot's ``seq_len`` hold stale/zero
    rows — :func:`length_mask` owns validity). ``t_max`` is static and
    must be a whole number of pages."""
    page_size = pages.shape[1]
    if t_max % page_size:
        raise ValueError(f"t_max {t_max} not a multiple of page_size "
                         f"{page_size}")
    n = t_max // page_size
    picked = jnp.take(pages, page_table[:, :n], axis=0,
                      mode="clip")                 # (S, n, page, H, D)
    s = page_table.shape[0]
    return picked.reshape((s, t_max) + pages.shape[2:])


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


def gather_slot_pages(cache: PagedKVCache, page_ids):
    """Gather one slot's pages out of every layer's pool.

    ``page_ids``: (P,) int32 physical page ids — the slot's page-table
    row, fixed width (entries past the used prefix may repeat a real
    page; the caller slices the used prefix host-side). Returns
    ``(k, v, k_scales, v_scales)`` with k/v shaped
    ``(num_layers, P, page_size, heads, head_dim)`` and scales
    ``(num_layers, P, page_size, heads)`` (None for float pools)."""
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
HANDOFF_VERSION = 1
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
