"""A decoder built from block parts: the serving-side stack for
architectures whose layers are not all alike.

`TransformerLayer` is one hard-wired block (learned positions,
LayerNorm, fused-QKV heads, GELU MLP, tied head). Here a layer is
``h = x + Attn(norm1(x)); y = h + FFN(norm2(h))`` over RMSNorm, with
the attention part and the feed-forward part given PER LAYER (the
*pattern*: DeepSeek-V2 is one attention for all layers, one dense
SwiGLU layer, then expert layers; dots3-note is full layers whose
attention chooses its keys among sliding layers of other widths), a
MiMo-V2 is full grouped-query layers among sliding ones with twice
the K/V heads and a sink), a
final norm and an untied head. `prefill`, `decode_step`,
`forward_chunk` and `generate` are written once over the pattern, and
the surface is the one `GenerationEngine` drives
(``init_kv_cache / prefill / decode_step / forward_chunk /
generate``, ``seq_len``, ``vocab``).

Parts here: :class:`YarnRope` (rotary positions with YaRN scaling,
rotate-half convention), :class:`LatentAttention` (multi-head latent
attention: low-rank queries, one KV latent a token shared by all
heads; expanded per-head K/V for prompts and chunks, the absorbed
form against the latent page pool for a decode step; optionally
windowed, gated, or sparse through a :class:`SparseIndexer`),
:class:`GroupedQueryAttention` (a few K/V heads shared by groups of
query heads, keys wider than values, a partial rotary, optionally a
window and a learned sink; a step reads the live pages of its pool
through the Pallas kernel `zoo_paged_gqa_decode`).
Feed-forward parts are `layers.moe.GatedMLP` and
`layers.moe.GroupLimitedMoE`.

The layers are a Python loop, each with its own weight arrays: an
expert layer's weights are gigabytes, and a slab sliced out of a
stacked array for a scan's body would be copied every step. The
cache's pools are closed over, only read by the layers, and written
once after the last layer, as in `TransformerLayer.decode_step`.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops.attention import (
    banded_attention, dot_product_attention, gqa_decode_attention,
    grouped_attention, index_scores, latent_decode_attention,
    mask_tile_counts, masked_attention, mla_decode_attention,
    resolve_attention_impl, topk_mask)
from analytics_zoo_tpu.ops import ssm as ssm_ops
from analytics_zoo_tpu.pipeline.api.keras.engine import (KerasLayer,
                                                         ShapeLike)
from analytics_zoo_tpu.pipeline.api.keras.layers.transformer import (
    TransformerLayer, _normal)


def rms_norm(x, gain, eps: float):
    """RMSNorm with float32 statistics, in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                           + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


class YarnRope:
    """Rotary position embedding over ``dim`` values with YaRN
    context scaling (Peng et al. 2023, as DeepSeek-V2's
    ``rope_scaling`` block states it). ``factor == 1`` is plain RoPE.
    The pairs turned together are values (i, i + dim / 2), the
    rotate-half convention, or with ``interleaved`` values (2i, 2i + 1),
    GPT-J's (Cohere's ``rope_gptj``).

    Frequencies: ``inv_freq = inter / factor * (1 - m) + extra * m``,
    ``extra = theta^(-2i/dim)`` and ``m`` one minus the linear ramp
    between the dimensions that make ``beta_fast`` and ``beta_slow``
    rotations over ``original_max_position`` positions. cos and sin
    are scaled by ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``; :attr:`attention_mscale` =
    ``mscale(factor, mscale_all_dim)`` multiplies the softmax scale,
    squared."""

    def __init__(self, dim: int, theta: float = 10000.0,
                 factor: float = 1.0,
                 original_max_position: int = 4096,
                 beta_fast: float = 32.0, beta_slow: float = 1.0,
                 mscale: float = 1.0, mscale_all_dim: float = 0.0,
                 interleaved: bool = False):
        self.dim, self.theta = int(dim), float(theta)
        self.interleaved = bool(interleaved)
        self.factor = float(factor)
        self.original_max_position = int(original_max_position)
        self.beta_fast, self.beta_slow = float(beta_fast), \
            float(beta_slow)
        self.mscale, self.mscale_all_dim = float(mscale), \
            float(mscale_all_dim)

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else \
            0.1 * mscale * math.log(factor) + 1.0

    def _correction_dim(self, rotations: float) -> float:
        return self.dim * math.log(
            self.original_max_position / (rotations * 2 * math.pi)
        ) / (2 * math.log(self.theta))

    def inv_freq(self) -> np.ndarray:
        """(dim / 2,) float64 frequencies."""
        extra = self.theta ** (
            -np.arange(0, self.dim, 2, dtype=np.float64) / self.dim)
        if self.factor <= 1:
            return extra
        low = max(math.floor(self._correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(self._correction_dim(self.beta_slow)),
                   self.dim - 1)
        ramp = np.clip((np.arange(self.dim // 2, dtype=np.float64) -
                        low) / max(high - low, 1e-3), 0.0, 1.0)
        m = 1.0 - ramp
        return extra / self.factor * (1.0 - m) + extra * m

    @property
    def cos_sin_scale(self) -> float:
        return self._mscale(self.factor, self.mscale) / \
            self._mscale(self.factor, self.mscale_all_dim)

    @property
    def attention_mscale(self) -> float:
        return self._mscale(self.factor, self.mscale_all_dim) \
            if self.mscale_all_dim else 1.0

    def __call__(self, x, positions):
        """Rotate ``x`` (..., dim) at ``positions``, which
        broadcast against ``x``'s leading axes."""
        ang = jnp.asarray(positions, jnp.float32)[..., None] * \
            jnp.asarray(self.inv_freq(), jnp.float32)
        cos = jnp.cos(ang) * self.cos_sin_scale
        sin = jnp.sin(ang) * self.cos_sin_scale
        xf = x.astype(jnp.float32)
        if self.interleaved:
            pairs = xf.reshape(x.shape[:-1] + (self.dim // 2, 2))
            x1, x2 = pairs[..., 0], pairs[..., 1]
            return jnp.stack(
                [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                axis=-1).reshape(x.shape).astype(x.dtype)
        x1, x2 = jnp.split(xf, 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
            axis=-1).astype(x.dtype)


class SparseIndexer(NamedTuple):
    """The key chooser of a sparse latent attention
    (DeepSeek-V3.2-Exp's lightning indexer): ``n_head`` index queries
    of ``head_dim`` a token, ONE index key a token cached beside the
    latent, and the ``top_k`` keys of largest score kept for each
    query."""
    n_head: int
    head_dim: int
    top_k: int


# the indexer's LayerNorm on its key (DeepSeek-V3.2-Exp's)
INDEX_NORM_EPS = 1e-6


def _layer_norm(x, gain, bias, eps: float):
    """Mean-centred LayerNorm with float32 statistics, in ``x``'s
    dtype; ``bias`` None: a gain alone."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def layer_norm(x, gain, eps: float):
    """LayerNorm with a gain and no bias (Cohere's), float32
    statistics, in ``x``'s dtype."""
    return _layer_norm(x, gain, None, eps)


# a `PatternDecoder`'s norm kinds
NORMS = {"rms": rms_norm, "layer": layer_norm}


class LatentAttention:
    """Multi-head latent attention (DeepSeek-V2): queries through a
    ``q_lora_rank`` bottleneck, keys and values through ONE
    ``kv_lora_rank`` latent a token with a ``qk_rope_head_dim``
    rotated key part shared by every head. The cache row of a token
    is ``[norm(c_kv) | rope(k_pe)]``, :attr:`row_width` values.

    ``q_b`` gives each head ``[q_nope | q_pe]``, ``kv_b`` each head
    ``[k_nope | v]``. :meth:`prefill` forms per-head keys and values
    from the latent (the expanded form) ``head_block`` heads at a
    time; :meth:`decode` carries the query into the latent space and
    the result out of it (the absorbed form) and never expands the
    cache; :meth:`chunk` is the expanded form for a chunk of new
    tokens against what the cache holds before them.

    Which keys a query sees is the part's *kind*:

    - ``window = W``: the last ``W`` positions, its own among them
      (a sliding layer; its rows live in the cache's window pool);
    - ``indexer``: the ``top_k`` keys of largest index score among
      those before it (all of them while there are no more), scored
      from ONE index key a token that is cached beside the latent
      (:attr:`index_width` values);
    - neither: every key before it.

    ``gate``: a head-wise output gate, ``sigmoid(x W_g)`` one scalar
    a head, on the attention output before ``o`` (Gated Attention,
    arXiv:2505.06708). ``lora_rescale``: the normed latents times
    ``sqrt(hidden / rank)`` (LongCat-Flash's scale correction)."""

    # a window part's chunk is handed the whole ring and masks it
    # (`masked_attention` runs the tiles the window reaches)
    banded = False
    # its weights' key in a layer's tree
    key = "attn"

    def __init__(self, hidden_size: int, n_head: int,
                 q_lora_rank: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, rope: YarnRope,
                 rms_eps: float = 1e-6, head_block: int = 16,
                 window: int = 0, gate: bool = False,
                 indexer: Optional[SparseIndexer] = None,
                 lora_rescale: bool = False):
        if rope.dim != qk_rope_head_dim:
            raise ValueError("rope.dim must equal qk_rope_head_dim")
        if window and indexer:
            raise ValueError("a windowed part sees few keys: it takes "
                             "no indexer")
        self.hidden_size, self.n_head = int(hidden_size), int(n_head)
        self.q_rank, self.kv_rank = int(q_lora_rank), \
            int(kv_lora_rank)
        self.nope, self.rope_dim = int(qk_nope_head_dim), \
            int(qk_rope_head_dim)
        self.v_dim = int(v_head_dim)
        self.rope, self.rms_eps = rope, float(rms_eps)
        self.head_block = int(head_block) \
            if self.n_head % int(head_block) == 0 else self.n_head
        self.row_width = self.kv_rank + self.rope_dim
        self.scale = (self.nope + self.rope_dim) ** -0.5 * \
            rope.attention_mscale ** 2
        self.window, self.gate = int(window), bool(gate)
        self.indexer = SparseIndexer(*indexer) if indexer else None
        self.index_width = self.indexer.head_dim if indexer else 0
        self.q_rescale = math.sqrt(hidden_size / q_lora_rank) \
            if lora_rescale else 1.0
        self.kv_rescale = math.sqrt(hidden_size / kv_lora_rank) \
            if lora_rescale else 1.0
        # the pool its rows live in, and the scope its work is under
        self.kind = "window" if self.window else "context"
        self.scope = "swa_attention" if self.window else \
            "dsa_attention" if self.indexer else "mla_attention"
        self.plain = not (self.window or self.gate or self.indexer)

    def build(self, rng, stddev: float) -> dict:
        h, nh = self.hidden_size, self.n_head
        k = jax.random.split(rng, 9)
        out = {
            "q_a": _normal(k[0], (h, self.q_rank), stddev),
            "q_norm": jnp.ones((self.q_rank,), jnp.float32),
            "q_b": _normal(k[1], (self.q_rank,
                                  nh * (self.nope + self.rope_dim)),
                           stddev),
            "kv_a": _normal(k[2], (h, self.row_width), stddev),
            "kv_norm": jnp.ones((self.kv_rank,), jnp.float32),
            "kv_b": _normal(k[3], (self.kv_rank,
                                   nh * (self.nope + self.v_dim)),
                            stddev),
            "o": _normal(k[4], (nh * self.v_dim, h), stddev),
        }
        if self.gate:
            out["gate"] = _normal(k[5], (h, nh), stddev)
        if self.indexer:
            hi, di, _ = self.indexer
            out["index"] = {
                "q": _normal(k[6], (self.q_rank, hi * di), stddev),
                "k": _normal(k[7], (h, di), stddev),
                "k_gain": jnp.ones((di,), jnp.float32),
                "k_bias": jnp.zeros((di,), jnp.float32),
                "w": _normal(k[8], (h, hi), stddev)}
        return out

    def _latents(self, p, x, positions):
        """``x`` (..., hidden) at ``positions`` (...): the normed
        query latent and the cache row."""
        dt = x.dtype
        c_q = rms_norm(x @ p["q_a"].astype(dt), p["q_norm"],
                       self.rms_eps)
        kv = x @ p["kv_a"].astype(dt)
        c_kv = rms_norm(kv[..., :self.kv_rank], p["kv_norm"],
                        self.rms_eps)
        if self.q_rescale != 1.0 or self.kv_rescale != 1.0:
            c_q = (c_q * self.q_rescale).astype(dt)
            c_kv = (c_kv * self.kv_rescale).astype(dt)
        k_pe = self.rope(kv[..., self.kv_rank:], positions)
        return c_q, jnp.concatenate([c_kv, k_pe], axis=-1)

    def _partial_rope(self, x, positions):
        """The rotary part on the first ``rope_dim`` values of
        ``x``, the rest as they are (the indexer's convention)."""
        return jnp.concatenate(
            [self.rope(x[..., :self.rope_dim], positions),
             x[..., self.rope_dim:]], axis=-1)

    def _index_parts(self, p, x, c_q, positions):
        """The indexer's queries (..., H_I, D_I), head weights
        (..., H_I) f32 and the token's index key (..., D_I)."""
        hi, di, _ = self.indexer
        dt, pi = x.dtype, p["index"]
        q = (c_q @ pi["q"].astype(dt)).reshape(
            c_q.shape[:-1] + (hi, di))
        q = self._partial_rope(q, positions[..., None])
        k = _layer_norm(x @ pi["k"].astype(dt), pi["k_gain"],
                        pi["k_bias"], INDEX_NORM_EPS)
        k = self._partial_rope(k, positions)
        w = (x @ pi["w"].astype(dt)).astype(jnp.float32) * \
            (hi ** -0.5 * di ** -0.5)
        return q, w, k

    def _gates(self, p, x, phase: str):
        """(..., n_head) f32 head gates of ``x``, or None."""
        if not self.gate:
            return None
        with jax.named_scope(f"zoo:{phase}/attn_gate"):
            return jax.nn.sigmoid(
                (x @ p["gate"].astype(x.dtype)).astype(jnp.float32))

    def prefill(self, p, x, impl=None):
        """Causal self-attention of (S, T, hidden) prompts at
        positions 0..T-1, for a part that sees every key (no window,
        indexer or gate: those go through :meth:`chunk`). Returns
        ``(out (S, T, hidden), rows (S, T, row_width))``."""
        s, t, _ = x.shape
        dt = x.dtype
        nb, nh = self.head_block, self.n_head
        qk = self.nope + self.rope_dim
        pos = jnp.arange(t, dtype=jnp.int32)
        c_q, rows = self._latents(p, x, pos[None, :])
        c_kv, k_pe = rows[..., :self.kv_rank], rows[..., self.kv_rank:]
        q_b = p["q_b"].astype(dt).reshape(self.q_rank, nh // nb,
                                          nb * qk)
        kv_b = p["kv_b"].astype(dt).reshape(
            self.kv_rank, nh // nb, nb * (self.nope + self.v_dim))
        o_w = p["o"].astype(dt).reshape(nh // nb, nb * self.v_dim, -1)

        def heads(acc, w):
            q_w, kv_w, o_blk = w
            q = (c_q @ q_w).reshape(s, t, nb, qk)
            q = jnp.concatenate(
                [q[..., :self.nope],
                 self.rope(q[..., self.nope:], pos[None, :, None])],
                axis=-1)
            kv = (c_kv @ kv_w).reshape(s, t, nb,
                                       self.nope + self.v_dim)
            k = jnp.concatenate(
                [kv[..., :self.nope], jnp.broadcast_to(
                    k_pe[:, :, None, :], (s, t, nb, self.rope_dim))],
                axis=-1)
            # one head size for the attention kernels: values padded
            # to the keys' width with zeros, cut off again after
            v = jnp.pad(kv[..., self.nope:], [(0, 0)] * 3 +
                        [(0, max(0, qk - self.v_dim))])
            with jax.named_scope("zoo:prefill/mla_attention"):
                a = dot_product_attention(q, k, v, causal=True,
                                          scale=self.scale, impl=impl)
            a = a[..., :self.v_dim].reshape(s, t, nb * self.v_dim)
            return acc + a @ o_blk, None

        out, _ = jax.lax.scan(
            heads, jnp.zeros_like(x),
            (jnp.moveaxis(q_b, 1, 0), jnp.moveaxis(kv_b, 1, 0), o_w))
        return out, rows

    def _queries(self, p, c_q, positions):
        """Absorbed-form queries of (S, q_rank) latents: ``q_lat``
        (S, H, kv_rank), ``q_pe`` (S, H, rope) and ``kv_b`` split by
        head."""
        s, dt = c_q.shape[0], c_q.dtype
        nh, qk = self.n_head, self.nope + self.rope_dim
        q = (c_q @ p["q_b"].astype(dt)).reshape(s, nh, qk)
        q_pe = self.rope(q[..., self.nope:], positions[:, None])
        kv_b = p["kv_b"].astype(dt).reshape(self.kv_rank, nh,
                                            self.nope + self.v_dim)
        q_lat = jnp.einsum("shd,rhd->shr", q[..., :self.nope],
                           kv_b[..., :self.nope])
        return q_lat, q_pe, kv_b

    def decode(self, p, x, positions, view, lens_after):
        """One new token a slot: ``x`` (S, hidden) at ``positions``
        (S,). ``view(row)`` returns the layer's gathered latent
        context with the new row laid in, and the pool's row; a part
        that sees a window or chooses its keys reads the cache
        itself, through ``view.cache`` (only read), ``view.at`` =
        (the layer's index in its pool, in the index pool) and
        ``view.active``. Returns ``(out (S, hidden), pool row)``,
        and with an indexer the index pool's row third."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        s, dt = x.shape[0], x.dtype
        c_q, row = self._latents(p, x, positions)
        q_lat, q_pe, kv_b = self._queries(p, c_q, positions)
        rows = {}
        if self.window or self.indexer:
            cache, at, active = view.cache, view.at, view.active
        if self.window:
            rows["row"] = new = kvc._padded_rows(cache.window, row)
            with jax.named_scope("zoo:decode/swa_attention"):
                first = jnp.maximum(
                    cache.seq_lens - self.window + 1, 0) // \
                    cache.page_size
                ctx, at_pos = kvc.window_view(
                    cache, at[0], jnp.arange(s, dtype=jnp.int32),
                    first, max(self.window - 2, 0) //
                    cache.page_size + 2)
                valid = jnp.logical_and(
                    at_pos < cache.seq_lens[:, None],
                    at_pos > cache.seq_lens[:, None] - self.window)
                o_lat = latent_decode_attention(
                    q_lat, q_pe,
                    jnp.concatenate([ctx, new[:, None]], axis=1),
                    jnp.concatenate(
                        [valid, (lens_after > cache.seq_lens)[:, None]],
                        axis=1), self.scale)
        elif self.indexer:
            q_i, w_i, k_i = self._index_parts(p, x, c_q, positions)
            rows["row"] = new = kvc._padded_rows(cache.pages, row)
            rows["index"] = k_new = kvc._padded_rows(cache.index, k_i)
            writes = kvc._decode_writes(cache, active)
            t = cache.max_context
            with jax.named_scope("zoo:decode/dsa_index"):
                keys = kvc._lay_rows(
                    kvc.gather_layer(cache.index, cache.page_table, t,
                                     at[1]),
                    cache.seq_lens, k_new, writes)
                scores = index_scores(
                    q_i[:, None], w_i[:, None],
                    keys[..., :self.index_width].astype(dt))[:, 0]
                scores = jnp.where(kvc.length_mask(lens_after, t),
                                   scores, -jnp.inf)
            with jax.named_scope("zoo:decode/dsa_select"):
                _, chosen = jax.lax.top_k(
                    scores, min(self.indexer.top_k, t))
            with jax.named_scope("zoo:decode/dsa_attention"):
                ctx = kvc.gather_rows(cache.pages, cache.page_table,
                                      chosen, at[0])
                mine = jnp.logical_and(
                    chosen == cache.seq_lens[:, None],
                    writes[:, None])
                o_lat = latent_decode_attention(
                    q_lat, q_pe,
                    jnp.where(mine[:, :, None], new[:, None], ctx),
                    chosen < lens_after[:, None], self.scale)
        else:
            ctx, rows["row"] = view(row)
            o_lat = mla_decode_attention(q_lat, q_pe, ctx, lens_after,
                                         self.scale)
        o = jnp.einsum("shr,rhd->shd", o_lat, kv_b[..., self.nope:])
        g = self._gates(p, x, "decode")
        if g is not None:
            o = (o * g[..., None]).astype(dt)
        return (o.reshape(s, self.n_head * self.v_dim) @
                p["o"].astype(dt), *rows.values())

    def chunk(self, p, x, q_pos, valid, cached=None, phase="prefill"):
        """A chunk of new tokens a row: ``x`` (A, C, hidden) at
        positions ``q_pos`` (A, C), ``valid`` (A, C) the real ones,
        in the expanded form against ``cached`` = ``(rows (A, T, W),
        positions (A, T), valid (A, T), index keys (A, T, D_I) or
        None)``, what the cache holds of the positions before the
        chunk (None: nothing), and against the chunk's own rows in
        flight. The mask is the part's kind: causal, and the window
        or the indexer's exact top-k over causal keys. Returns
        ``(out (A, C, hidden), rows)`` as :meth:`decode`, rows
        (A, C, width) unpadded, and under ``rows["tiles"]`` the
        int32 :data:`CHUNK_TILE_COUNTERS` of its attention calls."""
        a, c, _ = x.shape
        dt = x.dtype
        nb, nh = self.head_block, self.n_head
        qk = self.nope + self.rope_dim
        c_q, new = self._latents(p, x, q_pos)
        rows = {"row": new}
        lat, k_pos, k_valid = new, q_pos, valid
        if cached is not None:
            lat = jnp.concatenate(
                [cached[0][..., :self.row_width].astype(dt), new],
                axis=1)
            k_pos = jnp.concatenate([cached[1], q_pos], axis=1)
            k_valid = jnp.concatenate([cached[2], valid], axis=1)
        mask = jnp.logical_and(
            k_valid[:, None, :],
            k_pos[:, None, :] <= q_pos[:, :, None])
        if self.window:
            mask = jnp.logical_and(
                mask, q_pos[:, :, None] - k_pos[:, None, :]
                < self.window)
        if self.indexer:
            q_i, w_i, k_i = self._index_parts(p, x, c_q, q_pos)
            rows["index"] = k_i
            if cached is not None:
                k_i = jnp.concatenate(
                    [cached[3][..., :self.index_width].astype(dt),
                     k_i], axis=1)
            with jax.named_scope(f"zoo:{phase}/dsa_index"):
                scores = index_scores(q_i, w_i, k_i)
            with jax.named_scope(f"zoo:{phase}/dsa_select"):
                mask = topk_mask(scores, mask, self.indexer.top_k)
        c_kv, k_pe = lat[..., :self.kv_rank], lat[..., self.kv_rank:]
        t = lat.shape[1]
        # one table of tiles a head block, all of them this mask's
        heads = lambda n, w: jax.ShapeDtypeStruct((a, n, nb, w), dt)
        rows["tiles"] = (nh // nb) * mask_tile_counts(
            heads(c, qk), heads(t, qk), heads(t, self.v_dim), mask)
        q_b = p["q_b"].astype(dt).reshape(self.q_rank, nh // nb,
                                          nb * qk)
        kv_b = p["kv_b"].astype(dt).reshape(
            self.kv_rank, nh // nb, nb * (self.nope + self.v_dim))
        o_w = p["o"].astype(dt).reshape(nh // nb, nb * self.v_dim, -1)
        g = self._gates(p, x, phase)
        gates = jnp.ones((nh // nb, a, c, nb), jnp.float32) \
            if g is None else jnp.moveaxis(
                g.reshape(a, c, nh // nb, nb), 2, 0)

        def heads(acc, w):
            q_w, kv_w, o_blk, g_blk = w
            q = (c_q @ q_w).reshape(a, c, nb, qk)
            q = jnp.concatenate(
                [q[..., :self.nope],
                 self.rope(q[..., self.nope:], q_pos[:, :, None])],
                axis=-1)
            kv = (c_kv @ kv_w).reshape(a, t, nb,
                                       self.nope + self.v_dim)
            k = jnp.concatenate(
                [kv[..., :self.nope], jnp.broadcast_to(
                    k_pe[:, :, None, :], (a, t, nb, self.rope_dim))],
                axis=-1)
            with jax.named_scope(f"zoo:{phase}/{self.scope}"):
                o = masked_attention(q, k, kv[..., self.nope:], mask,
                                     self.scale)
            if self.gate:
                o = (o * g_blk[..., None]).astype(dt)
            return acc + o.reshape(a, c, nb * self.v_dim) @ o_blk, None

        out, _ = jax.lax.scan(
            heads, jnp.zeros_like(x),
            (jnp.moveaxis(q_b, 1, 0), jnp.moveaxis(kv_b, 1, 0), o_w,
             gates))
        return out, rows


class GroupedQueryAttention:
    """Grouped-query attention (Ainslie et al. 2023) as a part of
    `PatternDecoder`: ``n_head`` query heads over ``n_kv_head`` K/V
    heads (query head j reads K/V head ``j // (n_head / n_kv_head)``),
    queries and keys ``head_dim`` wide, values ``v_head_dim`` (MiMo-V2
    has 192 and 128). ``rope`` rotates the first ``rope.dim`` values
    of every query and key head (in its convention, rotate-half or
    interleaved) and passes the rest (a partial rotary; None: no
    rotary at all, as Nemotron-H's attention layers and Command A's
    full layers have none); values are multiplied by ``value_scale``
    before the product.

    The cache row of a token is ``[k of the G heads | v of the G
    heads]``, :attr:`row_width` = ``n_kv_head * (head_dim +
    v_head_dim)`` values: a pool of rows, not a K and a V pool, so a
    model whose full and sliding layers differ in K/V heads keeps
    both kinds under one page table.

    Which keys a query sees is the part's *kind*, as for
    :class:`LatentAttention`: ``window = W`` the last ``W`` positions,
    its own among them (rows in the cache's ring), else every key
    before it. ``sink``: a learned logit a query head in the
    softmax's denominator, with no value (gpt-oss's attention sink;
    MiMo-V2's ``add_swa_attention_sink_bias``).

    :meth:`prefill` is the causal product over a whole prompt (the
    flash kernel where `ops.attention.dot_product_attention` routes
    to it, K/V heads repeated); :meth:`chunk` a chunk against what
    the cache holds (`grouped_attention`, or for a window the band
    `banded_attention` bounds by the window); :meth:`decode` one
    token a slot from the live pages (`gqa_decode_attention`).

    :attr:`q_block`, the queries `grouped_attention` scores at a time,
    follows the head count: 256 up to 64 heads, ``2**20 / n_head**2``
    past them, so that a block's f32 scores (n_head x q_block x keys)
    at 128 heads and 28672 keys are 0.94 GB and not 3.76."""

    indexer, index_width = None, 0
    # a window part's chunk reads the ``window`` positions before it
    # (`PatternDecoder.forward_chunk`), not the ring
    banded = True
    key = "attn"

    def __init__(self, hidden_size: int, n_head: int, n_kv_head: int,
                 head_dim: int, v_head_dim: int,
                 rope: Optional[YarnRope],
                 value_scale: float = 1.0, window: int = 0,
                 sink: bool = False,
                 attention_impl: Optional[str] = None):
        if n_head % n_kv_head:
            raise ValueError(f"{n_head} query heads do not divide "
                             f"over {n_kv_head} K/V heads")
        if rope is not None and (rope.dim > head_dim or rope.dim % 2):
            raise ValueError(f"rotary dims {rope.dim} of a head of "
                             f"{head_dim}")
        self.hidden_size, self.n_head = int(hidden_size), int(n_head)
        self.n_kv, self.rep = int(n_kv_head), n_head // n_kv_head
        self.k_dim, self.v_dim = int(head_dim), int(v_head_dim)
        self.rope, self.value_scale = rope, float(value_scale)
        self.window, self.sink = int(window), bool(sink)
        self.attention_impl = attention_impl
        self.scale = self.k_dim ** -0.5
        self.q_block = min(256, 2 ** 20 // self.n_head ** 2)
        self.k_width = self.n_kv * self.k_dim
        self.row_width = self.n_kv * (self.k_dim + self.v_dim)
        self.kind = "window" if self.window else "context"
        self.scope = "swa_attention" if self.window else \
            "gqa_attention"
        self.plain = not (self.window or self.sink)

    def build(self, rng, stddev: float) -> dict:
        h, nh, g = self.hidden_size, self.n_head, self.n_kv
        k = jax.random.split(rng, 5)
        out = {"q": _normal(k[0], (h, nh * self.k_dim), stddev),
               "k": _normal(k[1], (h, g * self.k_dim), stddev),
               "v": _normal(k[2], (h, g * self.v_dim), stddev),
               "o": _normal(k[3], (nh * self.v_dim, h), stddev)}
        if self.sink:
            out["sink"] = jnp.zeros((nh,), jnp.float32)
        return out

    def _rotate(self, x, positions):
        """The rotary part on the first ``rope.dim`` values of each
        head of ``x`` (..., heads, head_dim) at ``positions`` (...)."""
        if self.rope is None:
            return x
        d = self.rope.dim
        return jnp.concatenate(
            [self.rope(x[..., :d], positions[..., None]), x[..., d:]],
            axis=-1)

    def _project(self, p, x, positions):
        """``x`` (..., hidden) at ``positions`` (...): the queries
        (..., G, R, D), rotated, and the cache row (..., row_width)
        = rotated keys and scaled values, heads side by side."""
        dt, lead = x.dtype, x.shape[:-1]
        q = self._rotate((x @ p["q"].astype(dt)).reshape(
            lead + (self.n_head, self.k_dim)), positions)
        k = self._rotate((x @ p["k"].astype(dt)).reshape(
            lead + (self.n_kv, self.k_dim)), positions)
        v = x @ p["v"].astype(dt)
        if self.value_scale != 1.0:
            v = (v * self.value_scale).astype(dt)
        row = jnp.concatenate(
            [k.reshape(lead + (self.k_width,)), v], axis=-1)
        return q.reshape(lead + (self.n_kv, self.rep, self.k_dim)), row

    def _split(self, rows):
        """Rows (..., >= row_width) as ``(K (..., G, D), V (..., G,
        Dv))``."""
        lead = rows.shape[:-1]
        return (rows[..., :self.k_width].reshape(
                    lead + (self.n_kv, self.k_dim)),
                rows[..., self.k_width:self.row_width].reshape(
                    lead + (self.n_kv, self.v_dim)))

    def _sink(self, p):
        return p["sink"].reshape(self.n_kv, self.rep) \
            if self.sink else None

    def _out(self, p, o):
        """Head outputs (..., G, R, Dv) through ``o``."""
        return o.reshape(o.shape[:-3] + (self.n_head * self.v_dim,)) \
            @ p["o"].astype(o.dtype)

    def prefill(self, p, x, impl=None):
        """Causal self-attention of (S, T, hidden) prompts at
        positions 0..T-1, for a part that sees every key and has no
        sink (the others go through :meth:`chunk`). Returns ``(out
        (S, T, hidden), rows (S, T, row_width))``."""
        s, t, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None],
                               (s, t))
        q, rows = self._project(p, x, pos)
        k, v = self._split(rows)
        # one head count and one head size for the attention kernels:
        # K/V heads repeated, values zero-padded to the keys' width
        k = jnp.repeat(k, self.rep, axis=2)
        v = jnp.pad(jnp.repeat(v, self.rep, axis=2), [(0, 0)] * 3 +
                    [(0, max(0, self.k_dim - self.v_dim))])
        with jax.named_scope(f"zoo:prefill/{self.scope}"):
            a = dot_product_attention(
                q.reshape(s, t, self.n_head, self.k_dim), k, v,
                causal=True, scale=self.scale, impl=impl)
        a = a[..., :self.v_dim].reshape(
            s, t, self.n_kv, self.rep, self.v_dim)
        return self._out(p, a), rows

    def chunk(self, p, x, q_pos, valid, cached=None, phase="prefill"):
        """A chunk of new tokens a row, as
        :meth:`LatentAttention.chunk`: ``x`` (A, C, hidden) at
        ``q_pos`` (A, C) against ``cached`` = ``(rows (A, T, W),
        positions (A, T), valid (A, T), None)`` and the chunk's own
        rows in flight. For a window part ``cached`` holds the
        ``window`` positions before the chunk (None: nothing before
        it) and the product is banded. Returns ``(out, {"row": rows
        (A, C, row_width)})``."""
        dt = x.dtype
        q, new = self._project(p, x, q_pos)
        if self.window:
            o = self._banded(q, new, q_pos, valid, cached, phase,
                             self._sink(p))
            return self._out(p, o), {"row": new}
        rows, k_pos, k_valid = new, q_pos, valid
        if cached is not None:
            rows = jnp.concatenate(
                [cached[0][..., :self.row_width].astype(dt), new],
                axis=1)
            k_pos = jnp.concatenate([cached[1], q_pos], axis=1)
            k_valid = jnp.concatenate([cached[2], valid], axis=1)
        mask = jnp.logical_and(
            k_valid[:, None, :],
            k_pos[:, None, :] <= q_pos[:, :, None])
        k, v = self._split(rows)
        with jax.named_scope(f"zoo:{phase}/{self.scope}"):
            o = grouped_attention(q, k, v, mask, self.scale,
                                  sink=self._sink(p),
                                  q_block=self.q_block)
        return self._out(p, o), {"row": new}

    def _banded(self, q, new, q_pos, valid, cached, phase, sink):
        """The window part's chunk product: a chunk of at least a
        window is padded to whole blocks of ``window`` queries behind
        the one block of cached positions and multiplied band by band
        (`banded_attention`); a shorter one is one block with them."""
        a, c = q_pos.shape
        dt, w = q.dtype, self.window
        if cached is None:
            n = w if c >= w else 0
            before = (jnp.zeros((a, n, self.row_width), dt),
                      jnp.zeros((a, n), jnp.int32),
                      jnp.zeros((a, n), jnp.bool_))
        else:
            before = (cached[0][..., :self.row_width].astype(dt),
                      cached[1], cached[2])
        pad = -c % w if c >= w else 0
        tail = lambda x, fill: jnp.pad(
            x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2),
            constant_values=fill)
        k, v = self._split(jnp.concatenate(
            [before[0], tail(new, 0)], axis=1))
        k_pos = jnp.concatenate([before[1], tail(q_pos, 0)], axis=1)
        k_ok = jnp.concatenate([before[2], tail(valid, False)], axis=1)
        with jax.named_scope(f"zoo:{phase}/{self.scope}"):
            if c >= w:
                return banded_attention(
                    tail(q, 0), k, v, tail(q_pos, 0), k_pos, k_ok, w,
                    self.scale, sink=sink)[:, :c]
            back = q_pos[:, :, None] - k_pos[:, None, :]
            mask = jnp.logical_and(
                k_ok[:, None, :],
                jnp.logical_and(back >= 0, back < w))
            return grouped_attention(q, k, v, mask, self.scale,
                                     sink=sink, q_block=self.q_block)

    def decode(self, p, x, positions, view, lens_after):
        """One new token a slot, as :meth:`LatentAttention.decode`:
        the queries attend from the pool's live pages (a full layer:
        the slot's pages of the context pool; a sliding layer: the
        ring pages of its last ``window - 1`` positions) and to the
        token's own row. Returns ``(out (S, hidden), pool row)``."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        cache, at, active = view.cache, view.at, view.active
        q, row = self._project(p, x, positions)
        writes = kvc._decode_writes(cache, active)
        if self.window:
            pool = cache.window
            table, lens, first = kvc.window_table(cache, self.window)
        else:
            pool, table, lens = cache.pages, cache.page_table, \
                cache.seq_lens
            first = jnp.zeros_like(lens)
        new = kvc._padded_rows(pool, row)
        with jax.named_scope(f"zoo:decode/{self.scope}"):
            o = gqa_decode_attention(
                q, new, pool, at[0], table, lens, first, writes,
                v_dim=self.v_dim, scale=self.scale,
                sink=self._sink(p), impl=self.attention_impl)
        return self._out(p, o), new


class MambaMixer:
    """Mamba-2's mixer (Dao & Gu 2024, the SSD form) as a part of
    `PatternDecoder`: a state a slot in place of rows a token.

    ``[z | xBC | dt] = u W_in`` (``d_in = n_head * head_dim``, ``xBC``
    ``d_in + 2 * n_groups * state_size`` wide); ``xBC`` through a
    depthwise causal convolution of ``conv_kernel`` taps with a bias
    and ``silu``, then split into ``x`` (n_head heads of head_dim) and
    ``B``, ``C`` (n_groups groups of state_size; head h reads group
    ``h // (n_head / n_groups)``); ``dt = softplus(dt + dt_bias)``,
    ``A = -exp(A_log)``; the scan (`ops.ssm`) and ``y + D x``; the
    gated RMSNorm of ``y * silu(z)`` over each of the n_groups groups
    (Mamba2's ``MambaRMSNormGated``) and ``W_out``.

    What a slot keeps (``conv_shape`` and ``state_shape``, float32,
    in the cache's ``conv`` and ``ssm``): the convolution's last
    ``conv_kernel - 1`` real inputs and the scan's state. :meth:`chunk`
    runs a prompt or a chunk from a given state (None: zeros) and
    returns the state after its last real position; :meth:`decode` is
    one recurrence step a slot. A position that is not real (padding,
    an idle slot) gets ``dt = 0`` and ``x = 0`` and moves no state."""

    kind, key = "state", "ssm"
    # no rows a token: nothing in the pools, no key to choose
    indexer, index_width, window, row_width, plain = None, 0, 0, 0, True

    def __init__(self, hidden_size: int, n_head: int, head_dim: int,
                 n_groups: int, state_size: int, conv_kernel: int = 4,
                 chunk_size: int = 128, rms_eps: float = 1e-5):
        if n_head % n_groups:
            raise ValueError(f"{n_head} heads do not divide over "
                             f"{n_groups} groups")
        self.hidden_size, self.n_head = int(hidden_size), int(n_head)
        self.head_dim, self.n_groups = int(head_dim), int(n_groups)
        self.state_size = int(state_size)
        self.conv_kernel, self.chunk_size = int(conv_kernel), \
            int(chunk_size)
        self.rms_eps = float(rms_eps)
        self.d_in = self.n_head * self.head_dim
        self.conv_dim = self.d_in + 2 * self.n_groups * self.state_size
        self.conv_shape = (self.conv_kernel - 1, self.conv_dim)
        self.state_shape = (self.n_head, self.head_dim, self.state_size)

    def build(self, rng, stddev: float) -> dict:
        h, d, nh = self.hidden_size, self.d_in, self.n_head
        k = jax.random.split(rng, 3)
        return {
            "in_proj": _normal(k[0], (h, d + self.conv_dim + nh), stddev),
            "conv_w": _normal(k[1], (self.conv_kernel, self.conv_dim),
                              stddev),
            "conv_b": jnp.zeros((self.conv_dim,), jnp.float32),
            "dt_bias": jnp.zeros((nh,), jnp.float32),
            "A_log": jnp.zeros((nh,), jnp.float32),
            "D": jnp.ones((nh,), jnp.float32),
            "norm": jnp.ones((d,), jnp.float32),
            "out_proj": _normal(k[2], (d, h), stddev)}

    def _in(self, p, u):
        """``(z, xBC, dt)`` of ``u`` (..., hidden)."""
        zxd = u @ p["in_proj"].astype(u.dtype)
        d, cd = self.d_in, self.conv_dim
        return zxd[..., :d], zxd[..., d:d + cd], zxd[..., d + cd:]

    def _parts(self, p, xbc, dt, valid):
        """``x`` (..., H, P), ``B``/``C`` (..., G, N), ``dt`` (..., H)
        f32 and ``A`` (H,) f32 of the convolved ``xbc``; 0 in ``x`` and
        ``dt`` where not ``valid`` (...)."""
        lead, d, gn = xbc.shape[:-1], self.d_in, \
            self.n_groups * self.state_size
        x = jnp.where(valid[..., None], xbc[..., :d], 0).reshape(
            lead + (self.n_head, self.head_dim))
        grp = lambda z: z.reshape(lead + (self.n_groups,
                                          self.state_size))
        dt = jax.nn.softplus(dt.astype(jnp.float32) +
                             p["dt_bias"].astype(jnp.float32))
        return (x, grp(xbc[..., d:d + gn]), grp(xbc[..., d + gn:]),
                jnp.where(valid[..., None], dt, 0.0),
                -jnp.exp(p["A_log"].astype(jnp.float32)))

    def _out(self, p, y, x, z):
        """``(GroupRMSNorm(y + D x) * silu(z)) W_out``."""
        y = y + p["D"].astype(jnp.float32)[:, None] * \
            x.astype(jnp.float32)
        y = y.reshape(z.shape).astype(z.dtype)
        y = ssm_ops.gated_rms_norm(y, z, p["norm"], self.n_groups,
                                   self.rms_eps)
        return y @ p["out_proj"].astype(y.dtype)

    def chunk(self, p, u, valid, state=None, phase="prefill"):
        """Rows of new tokens ``u`` (A, C, hidden), ``valid`` (A, C)
        the real ones (a prefix of each row), from ``state`` = ``(conv
        (A, K - 1, conv_dim), ssm (A, H, P, N))`` (None: a fresh one).
        Returns ``(out (A, C, hidden), {"conv": .., "ssm": ..})``, the
        state after each row's last real position."""
        a, _c, _h = u.shape
        if state is None:
            state = (jnp.zeros((a,) + self.conv_shape, jnp.float32),
                     jnp.zeros((a,) + self.state_shape, jnp.float32))
        with jax.named_scope(f"zoo:{phase}/ssm_proj"):
            z, xbc, dt = self._in(p, u)
            xbc, tail = ssm_ops.causal_conv(
                xbc, state[0], p["conv_w"], p["conv_b"],
                jnp.sum(valid, axis=1))
            x, b, c, dt, a_ = self._parts(p, xbc, dt, valid)
        with jax.named_scope(f"zoo:{phase}/ssm_scan"):
            y, s = ssm_ops.ssd_chunked(x, dt, a_, b, c, state[1],
                                       self.chunk_size)
        with jax.named_scope(f"zoo:{phase}/ssm_proj"):
            out = self._out(p, y, x, z)
        return out, {"conv": tail, "ssm": s}

    def decode(self, p, u, state, active):
        """One new token a slot, ``u`` (S, hidden), from ``state`` =
        ``(conv (S, K - 1, conv_dim), ssm (S, H, P, N))``. Returns
        ``(out (S, hidden), (conv, ssm))``: the slots' next state, a
        slot not ``active`` (S,) keeping its own."""
        with jax.named_scope("zoo:decode/ssm_proj"):
            z, xbc, dt = self._in(p, u)
            xbc, tail = ssm_ops.conv_step(xbc, state[0], p["conv_w"],
                                          p["conv_b"])
            x, b, c, dt, a_ = self._parts(p, xbc, dt, active)
        with jax.named_scope("zoo:decode/ssm_scan"):
            y, s = ssm_ops.ssd_step(x, dt, a_, b, c, state[1])
            tail = jnp.where(active[:, None, None], tail, state[0])
        with jax.named_scope("zoo:decode/ssm_proj"):
            out = self._out(p, y, x, z)
        return out, (tail, s)


class _DecodeView:
    """What one layer's attention reads of the cache in a decode
    step: called with the new token's row, the gathered context of a
    latent layer that sees every key (`ops.kv_cache.row_decode_view`);
    ``cache``, ``at`` and ``active`` for the parts that read the
    pools themselves (a window, an indexer, grouped-query heads)."""

    def __init__(self, cache, at, active):
        self.cache, self.at, self.active = cache, at, active

    def __call__(self, row):
        from analytics_zoo_tpu.ops import kv_cache as kvc
        return kvc.row_decode_view(self.cache, self.at[0], row,
                                   active=self.active)


# per call, as int32: over the layers whose attention chooses its
# keys, the keys its queries could see and those they kept; over the
# window layers, the keys their queries attend (min(t + 1, window) a
# query at position t), the keys they would attend with no window
# (t + 1), and the pages of the window pool written over
ATTENTION_COUNTERS = ("zoo_tpu_dsa_keys_visible_total",
                      "zoo_tpu_dsa_keys_selected_total",
                      "zoo_tpu_window_keys_read_total",
                      "zoo_tpu_window_keys_causal_total",
                      "zoo_tpu_window_pages_recycled_total")


# per call, as int32: over the attention calls of the latent layers'
# chunks (one a head block and layer), the (query block, key block)
# tiles of their masks and those that held a key and were run; zeros
# where `ops.attention.masked_attention` keeps its XLA body
CHUNK_TILE_COUNTERS = ("zoo_tpu_chunk_attn_tiles_total",
                       "zoo_tpu_chunk_attn_tiles_run_total")


# per call, as int32: over the rows of a prompt or chunk program, those
# whose state-space state started from zero and those that continued
# their slot's cached state (zeros in a decode step)
SSM_COUNTERS = ("zoo_tpu_ssm_state_starts_total",
                "zoo_tpu_ssm_state_carried_total")


def _record_state(counts):
    """Add one call's counts to :data:`SSM_COUNTERS`."""
    from analytics_zoo_tpu.common import observability as obs
    starts, carried = (int(c) for c in counts)
    obs.counter(
        "zoo_tpu_ssm_state_starts_total",
        help="rows whose state-space state a prefill or chunk program "
        "started from zero").inc(starts)
    obs.counter(
        "zoo_tpu_ssm_state_carried_total",
        help="chunk rows that continued a slot's cached state-space "
        "state").inc(carried)


def _record_attention(counts):
    """Add one call's counts to :data:`ATTENTION_COUNTERS` and, where
    the call has them, to :data:`CHUNK_TILE_COUNTERS` behind them."""
    from analytics_zoo_tpu.common import observability as obs
    visible, kept, read, causal, recycled, *tiles = (
        int(c) for c in counts)
    if tiles:
        obs.counter(
            "zoo_tpu_chunk_attn_tiles_total",
            help="mask tiles (query block x key block) of the chunk "
            "attention kernel's calls").inc(tiles[0])
        obs.counter(
            "zoo_tpu_chunk_attn_tiles_run_total",
            help="those that held a key a query of the block sees, "
            "the only ones the kernel runs").inc(tiles[1])
    obs.counter(
        "zoo_tpu_dsa_keys_visible_total",
        help="keys visible to the queries of the sparse-attention "
        "layers, steps and chunks").inc(visible)
    obs.counter(
        "zoo_tpu_dsa_keys_selected_total",
        help="keys the indexer kept for them (its top-k of the "
        "visible)").inc(kept)
    obs.counter(
        "zoo_tpu_window_keys_read_total",
        help="keys the queries of the window layers attend, steps "
        "and chunks: min(t + 1, window) a query at position t").inc(read)
    obs.counter(
        "zoo_tpu_window_keys_causal_total",
        help="keys the same queries would attend with no window: "
        "t + 1 a query at position t").inc(causal)
    obs.counter(
        "zoo_tpu_window_pages_recycled_total",
        help="window-pool pages written over by positions one turn "
        "of the ring later").inc(recycled)


class PatternDecoder(KerasLayer):
    """Pre-norm decoder over a layer pattern: ``attention`` (one
    mixer part, a :class:`LatentAttention` or a
    :class:`GroupedQueryAttention`, for every layer, or one a layer:
    full and sliding layers side by side, or a :class:`MambaMixer`
    beside them) and ``feed_forward[i]`` (a `GatedMLP` or a
    `GroupLimitedMoE`) in layer i, a final norm and a head over
    ``vocab`` rows. A layer may hold one of the two alone (the other
    None: Nemotron-H's layers are a mixer or a feed-forward part);
    each part present sits behind its own norm, ``norm1`` before the
    mixer (under the part's ``key``: ``attn`` or ``ssm``) and
    ``norm2`` before ``ffn``: ``x + mixer(norm1(x))``, then that plus
    ``ffn(norm2(...))``. ``parallel`` (Cohere's parallel block): ONE
    norm, both parts reading it, ``x + mixer(u) + ffn(u)`` with ``u =
    norm1(x)``, and no ``norm2``. ``norm``: ``"rms"`` (RMSNorm) or
    ``"layer"`` (mean-centred LayerNorm with a gain and no bias), for
    the layers and the final norm alike, of ``norm_eps``.
    ``tie_head``: the logits are the final norm's output times the
    token embedding's transpose (no ``lm_head`` leaf); either head's
    logits are multiplied by ``logit_scale``. ``seq_len`` is the
    most positions the model declares (there is no position table).

    Input (seq_len,) int token ids; ``call`` returns logits
    (B, T, vocab). The decode surface is `TransformerLayer`'s,
    ``forward_chunk`` included. The cache is one
    `ops.kv_cache.RowPagedCache` of the parts' rows (``row_width``
    values a token, whatever they hold): the layers that keep their
    whole context share its page pool (and an index pool, if their
    attention chooses its keys), the window layers its ring of
    ``window - 1 + max_chunk`` positions a slot, ``max_chunk`` being
    the most tokens one chunk may write (:meth:`init_kv_cache`'s
    argument: the engine passes its own). A part that counts (an
    expert layer's assignments, a sparse attention's keys, a window's
    recycled pages, the mask tiles a latent layer's chunk runs, the
    rows a state layer started or carried) names its counts in
    ``step_counters``;
    ``decode_step`` and ``forward_chunk`` with ``stats=True`` then
    also return their sums over the layers as one int32 vector, which
    :meth:`record_step_counts` adds to the counters of those
    names. A state layer's state lives in the cache's ``conv`` and
    ``ssm`` arrays, one a slot (``state_layers`` of them)."""

    # cached-context lengths a chunk program branches between start
    # from this many tokens (and from the chunk's own length)
    ctx_bucket_floor = 512

    def __init__(self, vocab: int, hidden_size: int,
                 attention, feed_forward: Sequence, seq_len: int,
                 norm_eps: float = 1e-6,
                 initializer_range: float = 0.02,
                 attention_impl: Optional[str] = None,
                 norm: str = "rms", parallel: bool = False,
                 tie_head: bool = False, logit_scale: float = 1.0,
                 input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape or (seq_len,),
                         name=name, **kwargs)
        if attention_impl is not None:
            resolve_attention_impl(attention_impl)
        self.attention_impl = attention_impl
        self.vocab, self.hidden_size = int(vocab), int(hidden_size)
        self.feed_forward = list(feed_forward)
        self.n_block = len(self.feed_forward)
        self.attentions = list(attention) if isinstance(
            attention, (list, tuple)) else [attention] * self.n_block
        if len(self.attentions) != self.n_block:
            raise ValueError("one attention part a layer, or one for "
                             "all of them")
        mixers = [a for a in self.attentions if a is not None]
        self.attention = mixers[0]
        if norm not in NORMS:
            raise ValueError(f"norm {norm!r} not one of {sorted(NORMS)}")
        self.parallel = bool(parallel)
        if self.parallel and any(
                a is None or f is None
                for a, f in zip(self.attentions, self.feed_forward)):
            raise ValueError("a parallel layer holds a mixer and a "
                             "feed-forward part both")
        self.seq_len = int(seq_len)
        self.norm, self.norm_eps = norm, float(norm_eps)
        self.tie_head, self.logit_scale = bool(tie_head), \
            float(logit_scale)
        self.initializer_range = float(initializer_range)
        # where each layer's rows or state live: (index in its pool or
        # state arrays, index in the index pool); None: no mixer
        self._at, n = [], {"context": 0, "window": 0, "index": 0,
                           "state": 0}
        for att in self.attentions:
            if att is None:
                self._at.append(None)
                continue
            self._at.append((n[att.kind], n["index"]))
            n[att.kind] += 1
            n["index"] += bool(att.indexer)
        # the state arrays are no pool: counted apart
        self.state_layers = n.pop("state")
        self._pool_layers = n
        for kind in ("context", "window", "state"):
            widths = {(a.row_width, getattr(a, "state_shape", ()))
                      for a in mixers if a.kind == kind}
            if len(widths) > 1:
                raise ValueError(f"the {kind} layers' rows differ in "
                                 f"width: {sorted(widths)}")
        self._plain = all(a.plain for a in mixers)
        self._counting = next(
            (f for f in self.feed_forward if f is not None
             and f.step_counters), None)
        # the parts whose chunks go through `masked_attention`
        self._tiled = any(isinstance(a, LatentAttention) and not a.plain
                          for a in mixers)
        self.step_counters = (
            self._counting.step_counters if self._counting else ()) + (
            () if self._plain else ATTENTION_COUNTERS) + (
            CHUNK_TILE_COUNTERS if self._tiled else ()) + (
            SSM_COUNTERS if self.state_layers else ())

    def build(self, rng, input_shape: ShapeLike) -> dict:
        r, h = self.initializer_range, self.hidden_size
        k_tok, k_head, *k_layers = jax.random.split(
            rng, 2 + self.n_block)
        layers = []
        for key, att, ffn in zip(k_layers, self.attentions,
                                 self.feed_forward):
            k_a, k_f = jax.random.split(key)
            layer = {}
            if att is not None:
                layer.update({"norm1": jnp.ones((h,), jnp.float32),
                              att.key: att.build(k_a, r)})
            if ffn is not None:
                if not self.parallel:
                    layer["norm2"] = jnp.ones((h,), jnp.float32)
                layer["ffn"] = ffn.build(k_f, r)
            layers.append(layer)
        out = {"tok_embed": _normal(k_tok, (self.vocab, h), r),
               "layers": layers,
               "norm_f": jnp.ones((h,), jnp.float32)}
        if not self.tie_head:
            out["lm_head"] = _normal(k_head, (h, self.vocab), r)
        return out

    def compute_output_shape(self, input_shape: ShapeLike):
        return (input_shape[0], self.vocab)

    def record_step_counts(self, counts):
        """Add what a program counted with ``stats=True`` to the
        counters ``step_counters`` names."""
        n = len(self._counting.step_counters) if self._counting else 0
        if n:
            self._counting.record(counts[:n])
        if not self._plain:
            w = len(ATTENTION_COUNTERS) + (
                len(CHUNK_TILE_COUNTERS) if self._tiled else 0)
            _record_attention(counts[n:n + w])
            n += w
        if self.state_layers:
            _record_state(counts[n:n + len(SSM_COUNTERS)])

    # -- one layer, written once for prompts, chunks and steps ---------
    def _norm(self, x, gain):
        return NORMS[self.norm](x, gain, self.norm_eps)

    def _layer(self, p, att, ffn, x, valid, scope, mix):
        """Layer ``p`` on ``x`` (..., hidden), ``valid`` (...) its
        real tokens: ``mix(u)`` gives the mixer's ``(out, rows)`` of
        its normed input, the feed-forward part takes flat tokens.
        Sequential or parallel, as the decoder is. Returns ``(x,
        rows, the feed-forward part's counts or None)``."""
        rows, counts = {}, None
        if att is not None:
            u = self._norm(x, p["norm1"])
            a, rows = mix(u)
            if not self.parallel:
                x = x + a
        if ffn is not None:
            if not self.parallel:
                u = self._norm(x, p["norm2"])
            y, counts = ffn(p["ffn"], u.reshape(-1, x.shape[-1]),
                            valid.reshape(-1), scope)
            y = y.reshape(x.shape)
            x = x + a + y if self.parallel else x + y
        return x, rows, counts

    def _logits(self, params, h):
        h = self._norm(h, params["norm_f"])
        if self.tie_head:
            out = jnp.einsum("...h,vh->...v", h,
                             params["tok_embed"].astype(h.dtype))
        else:
            out = h @ params["lm_head"].astype(h.dtype)
        return out if self.logit_scale == 1.0 else \
            (out * self.logit_scale).astype(out.dtype)

    def _attention_counts(self, first, n_new, ring_pages: int,
                          page: int):
        """The :data:`ATTENTION_COUNTERS` of queries at positions
        ``first[a] .. first[a] + n_new[a] - 1``: a query at position
        t sees t + 1 keys, keeps ``min(t + 1, top_k)`` and in a window
        layer attends ``min(t + 1, window)``; a logical page past the
        ring's first turn lands on a page that held an older one."""
        first, n = first.astype(jnp.int32), n_new.astype(jnp.int32)
        last = first + n
        # sum of (t + 1) over the n positions from `first` on
        seen = n * first + n * (n + 1) // 2
        visible = kept = read = causal = jnp.zeros((), jnp.int32)
        for att in self.attentions:
            if att is not None and att.window:
                # the positions up to window - 1 see t + 1 keys, the
                # others the window
                m = jnp.clip(att.window - first, 0, n)
                read = read + jnp.sum(m * first + m * (m + 1) // 2 +
                                      (n - m) * att.window)
                causal = causal + jnp.sum(seen)
            if att is None or att.indexer is None:
                continue
            # what the positions past the top_k-th see beyond it
            lo = jnp.clip(first, att.indexer.top_k, last)
            m = last - lo
            over = m * (lo + 1 - att.indexer.top_k) + m * (m - 1) // 2
            visible = visible + jnp.sum(seen)
            kept = kept + jnp.sum(seen - over)
        # logical pages begun by this call (their first position in
        # [first, last)) past the ring's first turn
        up = lambda n: -(-n // page)
        recycled = jnp.sum(jnp.maximum(
            up(last) - jnp.maximum(up(first), ring_pages), 0)) * \
            self._pool_layers["window"] if ring_pages \
            else jnp.zeros((), jnp.int32)
        return jnp.stack([visible, kept, read, causal,
                          recycled.astype(jnp.int32)])

    def _prompt(self, params, token_ids, valid):
        """(hidden (S, T, hidden), rows: one dict a layer of
        (S, T, width) arrays, the feed-forward parts' counts) of
        right-padded prompts; ``valid`` (S, T) marks real tokens."""
        x = jnp.take(params["tok_embed"],
                     token_ids.astype(jnp.int32), axis=0)
        pos = jnp.broadcast_to(jnp.arange(
            x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
        rows, counts = [], []
        for p, att, ffn in zip(params["layers"], self.attentions,
                               self.feed_forward):

            def mix(u, p=p, att=att):
                if att.kind == "state":
                    return att.chunk(p[att.key], u, valid)
                if att.plain:
                    a, r = att.prefill(p[att.key], u,
                                       impl=self.attention_impl)
                    return a, {"row": r}
                return att.chunk(p[att.key], u, pos, valid)

            with jax.named_scope("zoo:prefill/layer"):
                x, r, cnt = self._layer(p, att, ffn, x, valid,
                                        "prefill", mix)
                if cnt is not None:
                    counts.append(cnt)
                rows.append(r)
        return x, rows, counts

    def call(self, params, x, *, training=False, rng=None):
        del training, rng
        h, *_ = self._prompt(params, x, jnp.ones(x.shape, jnp.bool_))
        return self._logits(params, h)

    # -- decode surface ------------------------------------------------
    def init_kv_cache(self, max_slots: int, max_context: int,
                      page_size: int = 16, dtype=None,
                      max_chunk: int = 1):
        """A fresh row cache sized for this stack from the parts'
        ``row_width``: the page pool
        of the layers that keep their context, the index pool, and
        the window layers' ring, which holds a window and the
        ``max_chunk`` tokens one :meth:`forward_chunk` call may write
        behind it (1: decode steps and whole-prompt prefill only), and
        the state layers' state a slot."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        by_kind = {a.kind: a for a in self.attentions if a is not None}
        n, win = self._pool_layers, by_kind.get("window")
        return kvc.init_row_cache(
            n["context"], int(max_slots), int(max_context),
            by_kind["context"].row_width if "context" in by_kind
            else 1, page_size=int(page_size),
            dtype=dtype or jnp.float32, index_layers=n["index"],
            index_width=max(a.index_width for a in by_kind.values()),
            window_layers=n["window"],
            window_width=win.row_width if win else 0,
            window_tokens=(win.window - 1 + max(int(max_chunk), 1))
            if win else 0, state_layers=self.state_layers,
            conv_shape=by_kind["state"].conv_shape
            if self.state_layers else (),
            ssm_shape=by_kind["state"].state_shape
            if self.state_layers else ())

    def _stacked(self, rows, key, kind=None):
        """Every layer's ``rows[key]`` of one kind, stacked on a
        leading layer axis in the order of its pool; None if none."""
        picked = [r[key] for r, a in zip(rows, self.attentions)
                  if key in r and (kind is None or a.kind == kind)]
        return jnp.stack(picked) if picked else None

    def _write_chunk(self, cache, rows, slots, starts, total, q_pos,
                     valid):
        """The cache with one chunk's rows of every layer written:
        ``rows`` as :meth:`LatentAttention.chunk` returns them, for
        positions ``q_pos`` (A, C) of slots ``slots``; ``total``
        (A,) the slots' lengths after it."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        table = cache.page_table[slots]
        ctx = self._stacked(rows, "row", "context")
        if ctx is not None:
            cache = cache._replace(pages=kvc.write_prompt_rows(
                cache.pages, table, total, ctx, start=starts))
        idx = self._stacked(rows, "index")
        if idx is not None:
            cache = cache._replace(index=kvc.write_prompt_rows(
                cache.index, table, total, idx, start=starts))
        win = self._stacked(rows, "row", "window")
        if win is not None:
            # one turn of the ring at most: of a prompt longer than
            # that, the positions the next tokens can still see
            turn = (cache.window_ring - 1) * cache.page_size
            cache = cache._replace(window=kvc.write_window_rows(
                cache, slots, q_pos, jnp.logical_and(
                    valid, q_pos >= total[:, None] - turn), win))
        conv = self._stacked(rows, "conv")
        if conv is not None:
            # a row with no token leaves its slot's state as it was
            cache = kvc.write_slot_state(cache, slots,
                                         jnp.any(valid, axis=1), conv,
                                         self._stacked(rows, "ssm"))
        return cache

    def prefill(self, params, cache, token_ids, prompt_lens,
                slots=None, stats: bool = False):
        """`TransformerLayer.prefill`'s contract over the row
        pools: the rows are the prompts being admitted, ``slots``
        says which cache slot each is, every other slot is
        untouched, and no padding reaches an expert. With ``stats``
        also the sums of ``step_counters``, as :meth:`forward_chunk`
        counts a chunk from position 0."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        a, t = token_ids.shape
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
        slots = jnp.arange(a, dtype=jnp.int32) if slots is None \
            else jnp.asarray(slots, jnp.int32)
        q_pos = jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32)[None, :], (a, t))
        valid = q_pos < prompt_lens[:, None]
        final, rows, counts = self._prompt(params, token_ids, valid)
        cache = self._write_chunk(
            cache, rows, slots, None, prompt_lens, q_pos,
            valid)._replace(seq_lens=kvc.prompt_seq_lens(
                cache.seq_lens, slots, prompt_lens))
        with jax.named_scope("zoo:prefill/lm_head"):
            logits = self._logits(params, final[
                jnp.arange(a), jnp.maximum(prompt_lens - 1, 0)])
        if not stats:
            return cache, logits
        return cache, logits, self._counts(
            counts, jnp.zeros_like(prompt_lens), prompt_lens, cache,
            rows, chunk=True)

    def _ctx_ladder(self, cache, chunk: int) -> "tuple[int, ...]":
        """The cached-context lengths a chunk program of ``chunk``
        tokens branches between: nothing, then powers of two from
        the chunk's own length (at least ``ctx_bucket_floor``) up to
        the cache's ``max_context``."""
        page, top = cache.page_size, cache.max_context
        b = max(1 << max(0, (chunk - 1).bit_length()),
                self.ctx_bucket_floor, page)
        out = [0]
        while b < top:
            out.append(b)
            b *= 2
        return tuple(out) + (top,)

    def forward_chunk(self, params, cache, token_ids, starts, n_new,
                      all_logits: bool = False, slots=None,
                      stats: bool = False):
        """`TransformerLayer.forward_chunk`'s contract over the
        row cache: row a holds the next ``n_new[a]`` tokens of
        slot ``slots[a]`` (every slot in order when None) from
        position ``starts[a]`` on. Each layer attends from the chunk's
        queries to the rows the cache holds before ``starts`` and to
        the chunk's own, in flight; the pools are only read, and
        every layer's rows are written once, after the last layer.
        The work over the cached context is sized by the longest
        ``starts`` of the rows that hold tokens, among
        :meth:`_ctx_ladder`'s lengths. With ``stats`` also the sums
        of ``step_counters``."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        a, c = token_ids.shape
        starts = jnp.asarray(starts, jnp.int32)
        n_new = jnp.asarray(n_new, jnp.int32)
        slots = jnp.arange(a, dtype=jnp.int32) if slots is None \
            else jnp.asarray(slots, jnp.int32)
        total = starts + n_new
        q_pos = starts[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        valid = jnp.arange(c, dtype=jnp.int32)[None] < n_new[:, None]
        table = cache.page_table[slots]
        ladder = self._ctx_ladder(cache, c)
        bucket = jnp.searchsorted(
            jnp.asarray(ladder, jnp.int32),
            jnp.max(jnp.where(n_new > 0, starts, 0)), side="left")
        win = cache.window is not None
        if win and c > (cache.window_ring - 1) * cache.page_size:
            raise ValueError(
                f"a chunk of {c} tokens does not fit the window "
                f"pool's ring ({cache.window_ring} pages a slot): "
                f"make the cache with max_chunk >= {c}")

        def cached(att, at, t_ctx):
            """What the cache holds before the chunk, for ``att``."""
            if att.kind == "window" and att.banded:
                # the window before the chunk, row by row: a banded
                # part multiplies no more of the ring
                pos = starts[:, None] - att.window + jnp.arange(
                    att.window, dtype=jnp.int32)[None]
                return (kvc.window_rows(cache, at[0], slots, pos),
                        pos, pos >= 0, None)
            if att.kind == "window":
                first = jnp.maximum(starts - att.window + 1, 0) // \
                    cache.page_size
                rows, pos = kvc.window_view(cache, at[0], slots,
                                            first, cache.window_ring)
                return rows, pos, pos < starts[:, None], None
            if not t_ctx:
                return None
            pos = jnp.broadcast_to(jnp.arange(
                t_ctx, dtype=jnp.int32)[None], (a, t_ctx))
            return (kvc.gather_layer(cache.pages, table, t_ctx, at[0]),
                    pos, pos < starts[:, None],
                    kvc.gather_layer(cache.index, table, t_ctx, at[1])
                    if att.indexer else None)

        def carried(at):
            """A state layer's state of the rows' slots, zeros for a
            row that starts its prompt."""
            with jax.named_scope("zoo:prefill/ssm_scan"):
                fresh = starts == 0
                return tuple(
                    jnp.where(fresh.reshape((a,) + (1,) * (s.ndim - 2)),
                              0.0, s[at[0], slots])
                    for s in (cache.conv, cache.ssm))

        x = jnp.take(params["tok_embed"],
                     token_ids.astype(jnp.int32), axis=0)
        rows, counts = [], []
        for p, att, at, ffn in zip(params["layers"], self.attentions,
                                   self._at, self.feed_forward):

            def mix(u, p=p, att=att, at=at):
                run = lambda t: att.chunk(p[att.key], u, q_pos, valid,
                                          cached(att, at, t))
                if att.kind == "state":
                    return att.chunk(p[att.key], u, valid, carried(at))
                if att.kind == "window":
                    return run(0)
                return jax.lax.switch(
                    bucket, [functools.partial(run, t) for t in ladder])

            with jax.named_scope("zoo:prefill/chunk_layer"):
                x, r, cnt = self._layer(p, att, ffn, x, valid,
                                        "prefill", mix)
                if cnt is not None:
                    counts.append(cnt)
                rows.append(r)
        cache = self._write_chunk(
            cache, rows, slots, starts, total, q_pos, valid)._replace(
                seq_lens=cache.seq_lens.at[slots].set(jnp.where(
                    n_new > 0, total, cache.seq_lens[slots])))
        with jax.named_scope("zoo:prefill/lm_head"):
            logits = self._logits(params, x if all_logits else x[
                jnp.arange(a), jnp.clip(n_new - 1, 0, c - 1)])
        if not stats:
            return cache, logits
        return cache, logits, self._counts(
            counts, starts, n_new, cache, rows, chunk=True)

    def _counts(self, ffn_counts, first, n_new, cache, rows=(),
                chunk=False):
        """The sums of ``step_counters`` of one call; ``rows``: what
        the layers' chunks returned (a step has no tiles); ``chunk``:
        a prompt or chunk program, whose rows from ``first == 0`` start
        their state and the others carry it (a step does neither)."""
        out = [sum(ffn_counts)] if ffn_counts else []
        if not self._plain:
            out.append(self._attention_counts(
                first, n_new,
                cache.window_ring if cache.window is not None else 0,
                cache.page_size))
        if self._tiled:
            out.append(sum((r["tiles"] for r in rows if "tiles" in r),
                           jnp.zeros((2,), jnp.int32)))
        if self.state_layers:
            real = jnp.logical_and(n_new > 0, chunk)
            out.append(jnp.stack([
                jnp.sum(jnp.logical_and(real, first == 0),
                        dtype=jnp.int32),
                jnp.sum(jnp.logical_and(real, first > 0),
                        dtype=jnp.int32)]))
        return jnp.concatenate(out) if out else \
            jnp.zeros((0,), jnp.int32)

    def decode_step(self, params, cache, token_ids, active=None,
                    stats: bool = False):
        """`TransformerLayer.decode_step`'s contract; with ``stats``
        also the int32 sums of ``step_counters`` over the layers,
        counted over active slots only."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        if active is None:
            active = cache.seq_lens > 0
        pos = jnp.clip(cache.seq_lens, 0, self.seq_len - 1)
        lens_after = cache.seq_lens + active.astype(jnp.int32)
        x = jnp.take(params["tok_embed"],
                     token_ids.astype(jnp.int32), axis=0)
        rows, counts = [], []
        conv, ssm = cache.conv, cache.ssm
        for p, att, at, ffn in zip(params["layers"], self.attentions,
                                   self._at, self.feed_forward):

            def mix(u, p=p, att=att, at=at):
                nonlocal conv, ssm
                if att.kind != "state":
                    a, *row = att.decode(
                        p[att.key], u, pos,
                        _DecodeView(cache, at, active), lens_after)
                    return a, dict(zip(("row", "index"), row))
                # the layer's slab of each state array is read and
                # written back in place (the step program donates the
                # cache); no other layer reads it
                with jax.named_scope("zoo:decode/ssm_scan"):
                    state = (conv[at[0]], ssm[at[0]])
                a, (tail, s) = att.decode(p[att.key], u, state, active)
                with jax.named_scope("zoo:decode/ssm_scan"):
                    conv = conv.at[at[0]].set(tail)
                    ssm = ssm.at[at[0]].set(s)
                return a, {}

            with jax.named_scope("zoo:decode/layer"):
                x, r, c = self._layer(p, att, ffn, x, active, "decode",
                                      mix)
                if c is not None:
                    counts.append(c)
                rows.append(r)
        tally = self._counts(counts, cache.seq_lens,
                             active.astype(jnp.int32), cache) \
            if stats else None
        cache = cache._replace(conv=conv, ssm=ssm)
        cache = kvc.append_pool_rows(
            cache, self._stacked(rows, "row", "context"),
            active=active, index_rows=self._stacked(rows, "index"),
            window_rows=self._stacked(rows, "row", "window")
        )._replace(seq_lens=lens_after)
        with jax.named_scope("zoo:decode/lm_head"):
            logits = self._logits(params, x)
        if not stats:
            return cache, logits
        return cache, logits, tally

    # written against init_kv_cache / prefill / decode_step alone
    generate = TransformerLayer.generate


def _feed_forward(config: dict, experts_held, **moe):
    """``ffn(i)``, layer i's feed-forward part from the keys the
    expert configurations share: a dense SwiGLU of
    ``intermediate_size`` in the first ``first_k_dense_replace``
    layers and wherever ``moe_layer_freq`` says (a stride, every
    n-th layer an expert layer, or a list with one 0/1 a layer), a
    `GroupLimitedMoE` elsewhere. A key the config holds as ``null``
    (MiMo-V2's ``n_shared_experts`` and ``routed_scaling_factor``)
    reads as its default. ``moe`` are further arguments of the expert
    layers."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.moe import (
        GatedMLP, GroupLimitedMoE)
    c = config
    get = lambda key, default: default if c.get(key) is None \
        else c[key]
    h, freq = c["hidden_size"], get("moe_layer_freq", 1)

    def dense(i):
        if i < get("first_k_dense_replace", 0):
            return True
        return not freq[i] if isinstance(freq, (list, tuple)) \
            else bool(i % freq)

    def ffn(i):
        if dense(i):
            return GatedMLP(h, c["intermediate_size"])
        return GroupLimitedMoE(
            h, c["moe_intermediate_size"], c["n_routed_experts"],
            c["num_experts_per_tok"], n_group=get("n_group", 1),
            topk_group=get("topk_group", 1),
            n_shared=get("n_shared_experts", 0),
            routed_scaling=get("routed_scaling_factor", 1.0),
            experts_held=experts_held, **moe)

    return ffn


def deepseek_v2_decoder(config: dict, *, n_layer: Optional[int] = None,
                        experts_held: "Optional[tuple]" = None,
                        vocab: Optional[int] = None,
                        **kwargs) -> PatternDecoder:
    """A `PatternDecoder` from the keys of a DeepSeek-V2
    ``config.json`` (``model_type`` ``deepseek_v2``): latent attention
    with YaRN-scaled rotary positions in every layer, a dense SwiGLU
    in the first ``first_k_dense_replace`` layers, group-limited
    expert layers after them.

    One chip's share of a deployment states what it holds:
    ``n_layer`` layers (default ``num_hidden_layers``),
    ``experts_held = (first, count)`` of the ``n_routed_experts``
    the router scores (default all), ``vocab`` rows of the vocabulary
    (default ``vocab_size``). ``kwargs`` go to `PatternDecoder`."""
    c = config
    sc = c.get("rope_scaling") or {}
    rope = YarnRope(
        c["qk_rope_head_dim"], theta=c.get("rope_theta", 10000.0),
        factor=sc.get("factor", 1.0),
        original_max_position=sc.get(
            "original_max_position_embeddings",
            c["max_position_embeddings"]),
        beta_fast=sc.get("beta_fast", 32), beta_slow=sc.get(
            "beta_slow", 1), mscale=sc.get("mscale", 1.0),
        mscale_all_dim=sc.get("mscale_all_dim", 0.0))
    h, eps = c["hidden_size"], c.get("rms_norm_eps", 1e-6)
    attention = LatentAttention(
        h, c["num_attention_heads"], c["q_lora_rank"],
        c["kv_lora_rank"], c["qk_nope_head_dim"],
        c["qk_rope_head_dim"], c["v_head_dim"], rope, rms_eps=eps)
    n_layer = c["num_hidden_layers"] if n_layer is None else n_layer
    ffn = _feed_forward(c, experts_held)

    return PatternDecoder(
        c["vocab_size"] if vocab is None else vocab, h, attention,
        [ffn(i) for i in range(n_layer)],
        seq_len=c["max_position_embeddings"], norm_eps=eps,
        initializer_range=c.get("initializer_range", 0.02), **kwargs)


def dots3_note_decoder(config: dict, *, n_layer: Optional[int] = None,
                       experts_held: "Optional[tuple]" = None,
                       vocab: Optional[int] = None,
                       **kwargs) -> PatternDecoder:
    """A `PatternDecoder` from the keys of a ``config.json`` of
    ``model_type`` ``dots3_note`` (the language model only): layer i
    is ``layer_types[i]``. A ``full_attention`` layer is latent
    attention whose queries keep the ``index_topk`` keys an indexer
    of ``index_n_heads`` x ``index_head_dim`` scores highest; a
    ``sliding_attention`` layer is latent attention at the ``swa_*``
    widths over the last ``sliding_window_size`` positions with its
    own rotary base; both with a head-wise output gate and rescaled
    latents where the config says so. A dense SwiGLU in the first
    ``first_k_dense_replace`` layers, then ungrouped sigmoid-scored
    expert layers with a selection bias (``noaux_tc``) and
    renormalised weights.

    ``n_layer``, ``experts_held`` and ``vocab`` state one chip's
    share, as for :func:`deepseek_v2_decoder`; ``kwargs`` go to
    `PatternDecoder`."""
    c = config
    if c.get("rope_scaling"):
        raise ValueError("dots3_note: rope_scaling is null in the "
                         "published config; none is implemented")
    h, eps = c["hidden_size"], c.get("rms_norm_eps", 1e-6)
    rescale = bool(c.get("apply_mla_qkv_lora_rescale", False))
    gated = lambda key: c.get(key) == "headwise"
    full = LatentAttention(
        h, c["num_attention_heads"], c["q_lora_rank"],
        c["kv_lora_rank"], c["qk_nope_head_dim"],
        c["qk_rope_head_dim"], c["v_head_dim"],
        YarnRope(c["qk_rope_head_dim"], theta=c["rope_theta"]),
        rms_eps=eps, gate=gated("attention_gate_type"),
        indexer=SparseIndexer(c["index_n_heads"], c["index_head_dim"],
                              c["index_topk"]),
        lora_rescale=rescale)
    sliding = LatentAttention(
        h, c["swa_num_attention_heads"], c["swa_q_lora_rank"],
        c["swa_kv_lora_rank"], c["swa_qk_nope_head_dim"],
        c["swa_qk_rope_head_dim"], c["swa_v_head_dim"],
        YarnRope(c["swa_qk_rope_head_dim"], theta=c["swa_rope_theta"]),
        rms_eps=eps, window=c["sliding_window_size"],
        gate=gated("swa_attention_gate_type"), lora_rescale=rescale)
    n_layer = c["num_hidden_layers"] if n_layer is None else n_layer
    kinds = {"full_attention": full, "sliding_attention": sliding}
    ffn = _feed_forward(c, experts_held,
                        scoring=c.get("scoring_func", "sigmoid"),
                        norm_topk=c.get("norm_topk_prob", True))

    return PatternDecoder(
        c["vocab_size"] if vocab is None else vocab, h,
        [kinds[c["layer_types"][i]] for i in range(n_layer)],
        [ffn(i) for i in range(n_layer)],
        seq_len=c["max_position_embeddings"], norm_eps=eps,
        initializer_range=c.get("initializer_range", 0.02), **kwargs)


def mimo_v2_flash_decoder(config: dict, *, n_layer: Optional[int] = None,
                          experts_held: "Optional[tuple]" = None,
                          vocab: Optional[int] = None,
                          **kwargs) -> PatternDecoder:
    """A `PatternDecoder` from the keys of a ``config.json`` of
    ``model_type`` ``mimo_v2_flash`` (the language model only): layer
    i is full where ``hybrid_layer_pattern[i]`` is 0 and sliding
    where it is 1. Both are grouped-query attention over
    ``num_attention_heads`` query heads of ``head_dim`` with values
    of ``v_head_dim`` times ``attention_value_scale`` and a rotary on
    the first ``floor(partial_rotary_factor * head_dim)`` values
    (rounded down to even); a full layer has ``num_key_value_heads``
    K/V heads and the base ``rope_theta``, a sliding one
    ``swa_num_key_value_heads``, ``swa_rope_theta``, a window of
    ``sliding_window`` positions and, where
    ``add_swa_attention_sink_bias``, a learned sink a head. A dense
    SwiGLU where ``moe_layer_freq[i]`` is 0, else an ungrouped
    sigmoid-scored expert layer with a selection bias
    (``noaux_tc``), renormalised weights and no shared expert.

    ``n_layer``, ``experts_held`` and ``vocab`` state one chip's
    share, as for :func:`deepseek_v2_decoder`; ``kwargs`` go to
    `PatternDecoder`."""
    c = config
    if (c.get("rope_scaling") or {}).get("rope_type", "default") \
            != "default":
        raise ValueError("mimo_v2_flash: no rope scaling is "
                         "published; none is implemented")
    h, eps = c["hidden_size"], c.get("layernorm_epsilon", 1e-5)
    impl = kwargs.get("attention_impl")

    def part(pre: str, theta: float, window: int, sink: bool):
        d = c[pre + "head_dim"]
        rotary = int(c.get("partial_rotary_factor", 1.0) * d) // 2 * 2
        return GroupedQueryAttention(
            h, c[pre + "num_attention_heads"],
            c[pre + "num_key_value_heads"], d, c[pre + "v_head_dim"],
            YarnRope(rotary, theta=theta),
            value_scale=c.get("attention_value_scale") or 1.0,
            window=window, sink=sink, attention_impl=impl)

    kinds = {
        0: part("", c["rope_theta"], 0,
                bool(c.get("add_full_attention_sink_bias"))),
        1: part("swa_", c["swa_rope_theta"], c["sliding_window"],
                bool(c.get("add_swa_attention_sink_bias")))}
    n_layer = c["num_hidden_layers"] if n_layer is None else n_layer
    ffn = _feed_forward(c, experts_held,
                        scoring=c.get("scoring_func", "sigmoid"),
                        norm_topk=c.get("norm_topk_prob", True))
    return PatternDecoder(
        c["vocab_size"] if vocab is None else vocab, h,
        [kinds[c["hybrid_layer_pattern"][i]] for i in range(n_layer)],
        [ffn(i) for i in range(n_layer)],
        seq_len=c["max_position_embeddings"], norm_eps=eps,
        initializer_range=c.get("initializer_range", 0.02), **kwargs)


def nemotron_h_decoder(config: dict, *, n_layer: Optional[int] = None,
                       experts_held: "Optional[tuple]" = None,
                       vocab: Optional[int] = None,
                       **kwargs) -> PatternDecoder:
    """A `PatternDecoder` from the keys of a ``config.json`` of
    ``model_type`` ``nemotron_h``: layer i is ONE part, as
    ``hybrid_override_pattern[i]`` says. ``M``: a Mamba-2 mixer
    (:class:`MambaMixer`: ``mamba_num_heads`` x ``mamba_head_dim``,
    ``n_groups`` groups of ``ssm_state_size``, ``conv_kernel`` taps,
    ``chunk_size`` the scan's chunk). ``*``: grouped-query attention of
    ``num_attention_heads`` over ``num_key_value_heads`` heads of
    ``head_dim``, causal, with no rotary (the published attention
    applies none; ``rope_theta`` and ``partial_rotary_factor`` are
    unused). ``E``: sigmoid-routed non-gated relu^2 experts of
    ``moe_intermediate_size`` with a selection bias, renormalised
    weights times ``routed_scaling_factor``, and one shared expert of
    ``moe_shared_expert_intermediate_size``. Every part behind an
    RMSNorm of ``layer_norm_epsilon``.

    ``n_layer``, ``experts_held`` and ``vocab`` state one chip's
    share, as for :func:`deepseek_v2_decoder`; ``kwargs`` go to
    `PatternDecoder`."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.moe import \
        GroupLimitedMoE
    c = config
    for key, want in (("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu")):
        if c.get(key, want) != want:
            raise ValueError(f"nemotron_h: {key} {c[key]!r}; only "
                             f"{want!r} is implemented")
    n_layer = c["num_hidden_layers"] if n_layer is None else n_layer
    pattern = c["hybrid_override_pattern"][:n_layer]
    if set(pattern) - set("M*E"):
        raise ValueError(f"nemotron_h: layer kinds "
                         f"{sorted(set(pattern) - set('M*E'))} of "
                         "hybrid_override_pattern are not implemented")
    h = c["hidden_size"]
    eps = c.get("layer_norm_epsilon", c.get("norm_eps", 1e-5))
    mamba = MambaMixer(
        h, c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
        c["ssm_state_size"], conv_kernel=c["conv_kernel"],
        chunk_size=c["chunk_size"], rms_eps=eps)
    attention = GroupedQueryAttention(
        h, c["num_attention_heads"], c["num_key_value_heads"],
        c["head_dim"], c["head_dim"], None,
        attention_impl=kwargs.get("attention_impl"))
    experts = GroupLimitedMoE(
        h, c["moe_intermediate_size"], c["n_routed_experts"],
        c["num_experts_per_tok"], n_group=c.get("n_group") or 1,
        topk_group=c.get("topk_group") or 1,
        n_shared=c.get("n_shared_experts") or 0,
        shared_width=c.get("moe_shared_expert_intermediate_size"),
        routed_scaling=c.get("routed_scaling_factor") or 1.0,
        experts_held=experts_held, scoring="sigmoid",
        norm_topk=c.get("norm_topk_prob", True), activation="relu2")
    return PatternDecoder(
        c["vocab_size"] if vocab is None else vocab, h,
        [{"M": mamba, "*": attention}.get(k) for k in pattern],
        [experts if k == "E" else None for k in pattern],
        seq_len=c["max_position_embeddings"], norm_eps=eps,
        initializer_range=c.get("initializer_range", 0.02), **kwargs)


def command_a_decoder(config: dict, *, n_layer: Optional[int] = None,
                      experts_held: "Optional[tuple]" = None,
                      vocab: Optional[int] = None,
                      **kwargs) -> PatternDecoder:
    """A `PatternDecoder` from the keys of a ``config.json`` of
    ``model_type`` ``cohere2_moe`` (Command A+; the language model
    only): layer i is ``layer_types[i]``. Both kinds are grouped-query
    attention of ``num_attention_heads`` over ``num_key_value_heads``
    heads of ``head_dim`` with no bias and no qk-norm; a
    ``sliding_attention`` layer rotates the first ``rotary_pct`` of
    each head in the ``position_embedding_type`` ``rope_gptj``
    (interleaved) convention at ``rope_theta`` and sees the last
    ``sliding_window`` positions, its own among them; a
    ``full_attention`` layer has no positional encoding at all and
    sees every key before it. Every layer's feed-forward part is
    ``num_experts`` SwiGLU experts of ``intermediate_size``, the top
    ``num_experts_per_tok`` by their sigmoid scores with no selection
    bias, weights renormalised (``norm_topk_prob``), beside
    ``num_shared_experts`` shared ones (one SwiGLU of their summed
    width), the two paths joined as
    ``shared_expert_combination_strategy`` says. ``use_parallel_block``:
    attention and experts read one LayerNorm (``layer_norm_eps``, no
    bias) and both add to the residual; ``tie_word_embeddings``: the
    head is the embedding's transpose, times ``logit_scale``.

    A value of these keys that is not implemented is refused by name.
    ``n_layer``, ``experts_held`` and ``vocab`` state one chip's
    share, as for :func:`deepseek_v2_decoder`; ``kwargs`` go to
    `PatternDecoder`."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.moe import \
        GroupLimitedMoE
    c = config
    for key, want in (("position_embedding_type", "rope_gptj"),
                      ("expert_selection_fn", "sigmoid"),
                      ("hidden_act", "silu"),
                      ("use_gated_activation", True),
                      ("use_qk_norm", False), ("attention_bias", False),
                      ("first_k_dense_replace", 0),
                      ("use_parallel_embedding", False)):
        if c.get(key, want) != want:
            raise ValueError(f"cohere2_moe: {key} {c[key]!r} is not "
                             f"implemented (only {want!r})")
    if (c.get("rope_parameters") or {}).get("rope_type",
                                             "default") != "default":
        raise ValueError("cohere2_moe: rope_type "
                         f"{c['rope_parameters']['rope_type']!r} is not "
                         "implemented (only 'default')")
    combine = c.get("shared_expert_combination_strategy", "sum")
    if combine not in ("sum", "average"):
        raise ValueError("cohere2_moe: shared_expert_combination_"
                         f"strategy {combine!r} is not implemented")
    n_layer = c["num_hidden_layers"] if n_layer is None else n_layer
    types = c["layer_types"][:n_layer]
    unknown = set(types) - {"sliding_attention", "full_attention"}
    if unknown:
        raise ValueError(f"cohere2_moe: layer types {sorted(unknown)} "
                         "are not implemented")
    h, d = c["hidden_size"], c["head_dim"]
    impl = kwargs.get("attention_impl")
    rotary = int(c.get("rotary_pct", 1.0) * d) // 2 * 2
    part = lambda rope, window: GroupedQueryAttention(
        h, c["num_attention_heads"], c["num_key_value_heads"], d, d,
        rope, window=window, attention_impl=impl)
    kinds = {
        "sliding_attention": part(
            YarnRope(rotary, theta=c["rope_theta"], interleaved=True),
            c["sliding_window"]),
        "full_attention": part(None, 0)}
    experts = GroupLimitedMoE(
        h, c["intermediate_size"], c["num_experts"],
        c["num_experts_per_tok"], n_shared=c["num_shared_experts"],
        experts_held=experts_held, scoring="sigmoid",
        selection_bias=False, norm_topk=c.get("norm_topk_prob", True),
        shared_combine=combine)
    return PatternDecoder(
        c["vocab_size"] if vocab is None else vocab, h,
        [kinds[k] for k in types], [experts] * n_layer,
        seq_len=c["max_position_embeddings"],
        norm_eps=c["layer_norm_eps"], norm="layer",
        parallel=bool(c.get("use_parallel_block", False)),
        tie_head=bool(c.get("tie_word_embeddings", False)),
        logit_scale=c.get("logit_scale", 1.0),
        initializer_range=c.get("initializer_range", 0.02), **kwargs)
