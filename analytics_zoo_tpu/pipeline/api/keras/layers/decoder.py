"""A decoder built from block parts: the serving-side stack for
architectures whose layers are not all alike.

`TransformerLayer` is one hard-wired block (learned positions,
LayerNorm, fused-QKV heads, GELU MLP, tied head). Here a layer is
``h = x + Attn(norm1(x)); y = h + FFN(norm2(h))`` over RMSNorm, with
the attention part shared by every layer and the feed-forward part
given PER LAYER (the *pattern*: DeepSeek-V2 is one dense SwiGLU layer,
then expert layers), a final norm and an untied head. `prefill`,
`decode_step` and `generate` are written once over the pattern, and
the surface is the one `GenerationEngine` drives
(``init_kv_cache / prefill / decode_step / generate``, ``seq_len``,
``vocab``); ``forward_chunk`` is not there, so chunked prefill and
speculative verify are refused for this decoder by the engine.

Parts here: :class:`YarnRope` (rotary positions with YaRN scaling,
rotate-half convention), :class:`LatentAttention` (multi-head latent
attention: low-rank queries, one KV latent a token shared by all
heads; expanded per-head K/V for the prompt, the absorbed form
against the latent page pool for a decode step). Feed-forward parts
are `layers.moe.GatedMLP` and `layers.moe.GroupLimitedMoE`.

The layers are a Python loop, each with its own weight arrays: an
expert layer's weights are gigabytes, and a slab sliced out of a
stacked array for a scan's body would be copied every step. The page
pool is closed over and written once after the last layer, as in
`TransformerLayer.decode_step`.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops.attention import (dot_product_attention,
                                             mla_decode_attention,
                                             resolve_attention_impl)
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

    Frequencies: ``inv_freq = inter / factor * (1 - m) + extra * m``,
    ``extra = theta^(-2i/dim)`` and ``m`` one minus the linear ramp
    between the dimensions that make ``beta_fast`` and ``beta_slow``
    rotations over ``original_max_position`` positions. cos and sin
    are scaled by ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``; :attr:`attention_mscale` =
    ``mscale(factor, mscale_all_dim)`` multiplies the softmax scale,
    squared. Rotate-half convention: the two halves of the vector are
    the pairs' first and second members."""

    def __init__(self, dim: int, theta: float = 10000.0,
                 factor: float = 1.0,
                 original_max_position: int = 4096,
                 beta_fast: float = 32.0, beta_slow: float = 1.0,
                 mscale: float = 1.0, mscale_all_dim: float = 0.0):
        self.dim, self.theta = int(dim), float(theta)
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
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
            axis=-1).astype(x.dtype)


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
    cache."""

    def __init__(self, hidden_size: int, n_head: int,
                 q_lora_rank: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, rope: YarnRope,
                 rms_eps: float = 1e-6, head_block: int = 16):
        if rope.dim != qk_rope_head_dim:
            raise ValueError("rope.dim must equal qk_rope_head_dim")
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

    def build(self, rng, stddev: float) -> dict:
        h, nh = self.hidden_size, self.n_head
        k = jax.random.split(rng, 5)
        return {
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

    def _latents(self, p, x, positions):
        """``x`` (..., hidden) at ``positions`` (...): the normed
        query latent and the cache row."""
        dt = x.dtype
        c_q = rms_norm(x @ p["q_a"].astype(dt), p["q_norm"],
                       self.rms_eps)
        kv = x @ p["kv_a"].astype(dt)
        c_kv = rms_norm(kv[..., :self.kv_rank], p["kv_norm"],
                        self.rms_eps)
        k_pe = self.rope(kv[..., self.kv_rank:], positions)
        return c_q, jnp.concatenate([c_kv, k_pe], axis=-1)

    def prefill(self, p, x, impl=None):
        """Causal self-attention of (S, T, hidden) prompts at
        positions 0..T-1. Returns ``(out (S, T, hidden), rows
        (S, T, row_width))``."""
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

    def decode(self, p, x, positions, view, lens_after):
        """One new token a slot: ``x`` (S, hidden) at ``positions``
        (S,). ``view(row)`` returns the layer's gathered latent
        context with the new row laid in, and the pool's row.
        Returns ``(out (S, hidden), pool row)``."""
        s = x.shape[0]
        dt = x.dtype
        nh, qk = self.n_head, self.nope + self.rope_dim
        c_q, row = self._latents(p, x, positions)
        q = (c_q @ p["q_b"].astype(dt)).reshape(s, nh, qk)
        q_pe = self.rope(q[..., self.nope:], positions[:, None])
        kv_b = p["kv_b"].astype(dt).reshape(self.kv_rank, nh,
                                            self.nope + self.v_dim)
        q_lat = jnp.einsum("shd,rhd->shr", q[..., :self.nope],
                           kv_b[..., :self.nope])
        ctx, pool_row = view(row)
        o_lat = mla_decode_attention(q_lat, q_pe, ctx, lens_after,
                                     self.scale)
        o = jnp.einsum("shr,rhd->shd", o_lat, kv_b[..., self.nope:])
        return o.reshape(s, nh * self.v_dim) @ p["o"].astype(dt), \
            pool_row


class PatternDecoder(KerasLayer):
    """Pre-norm decoder over a layer pattern: ``attention`` (a
    :class:`LatentAttention`) in every layer, ``feed_forward[i]`` (a
    `GatedMLP` or a `GroupLimitedMoE`) in layer i, RMSNorm, a final
    norm and an untied head over ``vocab`` rows. ``seq_len`` is the
    most positions the model declares (there is no position table).

    Input (seq_len,) int token ids; ``call`` returns logits
    (B, T, vocab). The decode surface is `TransformerLayer`'s, less
    ``forward_chunk``. A feed-forward part that counts (an expert
    layer's assignments) names its counts in ``step_counters``;
    ``decode_step(..., stats=True)`` then also returns their sums
    over the layers as one int32 vector, which
    :meth:`record_step_counts` adds to the counters of those
    names."""

    def __init__(self, vocab: int, hidden_size: int,
                 attention: LatentAttention,
                 feed_forward: Sequence, seq_len: int,
                 rms_eps: float = 1e-6,
                 initializer_range: float = 0.02,
                 attention_impl: Optional[str] = None,
                 input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape or (seq_len,),
                         name=name, **kwargs)
        if attention_impl is not None:
            resolve_attention_impl(attention_impl)
        self.attention_impl = attention_impl
        self.vocab, self.hidden_size = int(vocab), int(hidden_size)
        self.attention = attention
        self.feed_forward = list(feed_forward)
        self.n_block = len(self.feed_forward)
        self.seq_len = int(seq_len)
        self.rms_eps = float(rms_eps)
        self.initializer_range = float(initializer_range)
        self._counting = next(
            (f for f in self.feed_forward if f.step_counters), None)
        self.step_counters = self._counting.step_counters \
            if self._counting else ()

    def build(self, rng, input_shape: ShapeLike) -> dict:
        r, h = self.initializer_range, self.hidden_size
        k_tok, k_head, *k_layers = jax.random.split(
            rng, 2 + self.n_block)
        layers = []
        for key, ffn in zip(k_layers, self.feed_forward):
            k_a, k_f = jax.random.split(key)
            layers.append({
                "norm1": jnp.ones((h,), jnp.float32),
                "attn": self.attention.build(k_a, r),
                "norm2": jnp.ones((h,), jnp.float32),
                "ffn": ffn.build(k_f, r)})
        return {"tok_embed": _normal(k_tok, (self.vocab, h), r),
                "layers": layers,
                "norm_f": jnp.ones((h,), jnp.float32),
                "lm_head": _normal(k_head, (h, self.vocab), r)}

    def compute_output_shape(self, input_shape: ShapeLike):
        return (input_shape[0], self.vocab)

    def record_step_counts(self, counts):
        """Add what ``decode_step(..., stats=True)`` counted to the
        counters ``step_counters`` names."""
        self._counting.record(counts)

    # -- the pattern, once for prompts and once for a step -------------
    def _ffn(self, ffn, p, x, valid, scope):
        """``x`` (..., hidden) through a feed-forward part that takes
        flat tokens."""
        y, counts = ffn(p["ffn"], rms_norm(
            x, p["norm2"], self.rms_eps).reshape(-1, x.shape[-1]),
            valid.reshape(-1), scope)
        return x + y.reshape(x.shape), counts

    def _logits(self, params, h):
        h = rms_norm(h, params["norm_f"], self.rms_eps)
        return h @ params["lm_head"].astype(h.dtype)

    def _prompt(self, params, token_ids, valid):
        """(hidden (S, T, hidden), rows (L, S, T, row_width)) of
        right-padded prompts; ``valid`` (S, T) marks real tokens."""
        x = jnp.take(params["tok_embed"],
                     token_ids.astype(jnp.int32), axis=0)
        rows = []
        for p, ffn in zip(params["layers"], self.feed_forward):
            with jax.named_scope("zoo:prefill/layer"):
                a, r = self.attention.prefill(
                    p["attn"], rms_norm(x, p["norm1"], self.rms_eps),
                    impl=self.attention_impl)
                x, _ = self._ffn(ffn, p, x + a, valid, "prefill")
                rows.append(r)
        return x, jnp.stack(rows)

    def call(self, params, x, *, training=False, rng=None):
        del training, rng
        h, _ = self._prompt(params, x, jnp.ones(x.shape, jnp.bool_))
        return self._logits(params, h)

    # -- decode surface ------------------------------------------------
    def init_kv_cache(self, max_slots: int, max_context: int,
                      page_size: int = 16, dtype=None):
        """A fresh latent page pool sized for this stack."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        return kvc.init_latent_cache(
            self.n_block, int(max_slots), int(max_context),
            self.attention.row_width, page_size=int(page_size),
            dtype=dtype or jnp.float32)

    def prefill(self, params, cache, token_ids, prompt_lens,
                slots=None):
        """`TransformerLayer.prefill`'s contract over the latent
        pool: the rows are the prompts being admitted, ``slots``
        says which cache slot each is, every other slot is
        untouched, and no padding reaches an expert."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        a, t = token_ids.shape
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
        slots = jnp.arange(a, dtype=jnp.int32) if slots is None \
            else jnp.asarray(slots, jnp.int32)
        valid = jnp.arange(t, dtype=jnp.int32)[None, :] < \
            prompt_lens[:, None]
        final, rows = self._prompt(params, token_ids, valid)
        cache = cache._replace(
            pages=kvc.write_latent_prompt(
                cache.pages, cache.page_table[slots], prompt_lens,
                rows),
            seq_lens=kvc.prompt_seq_lens(cache.seq_lens, slots,
                                         prompt_lens))
        with jax.named_scope("zoo:prefill/lm_head"):
            logits = self._logits(params, final[
                jnp.arange(a), jnp.maximum(prompt_lens - 1, 0)])
        return cache, logits

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
        for i, (p, ffn) in enumerate(zip(params["layers"],
                                         self.feed_forward)):
            with jax.named_scope("zoo:decode/layer"):
                a, row = self.attention.decode(
                    p["attn"], rms_norm(x, p["norm1"], self.rms_eps),
                    pos, lambda r, i=i: kvc.latent_decode_view(
                        cache, i, r, active=active), lens_after)
                x, c = self._ffn(ffn, p, x + a, active, "decode")
                rows.append(row)
                if c is not None:
                    counts.append(c)
        cache = kvc.append_latent_rows(
            cache, jnp.stack(rows), active=active)._replace(
                seq_lens=lens_after)
        with jax.named_scope("zoo:decode/lm_head"):
            logits = self._logits(params, x)
        if not stats:
            return cache, logits
        return cache, logits, sum(counts) if counts else \
            jnp.zeros((0,), jnp.int32)

    # written against init_kv_cache / prefill / decode_step alone
    generate = TransformerLayer.generate


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
    from analytics_zoo_tpu.pipeline.api.keras.layers.moe import (
        GatedMLP, GroupLimitedMoE)
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

    def ffn(i):
        if i < c.get("first_k_dense_replace", 0) or \
                i % c.get("moe_layer_freq", 1):
            return GatedMLP(h, c["intermediate_size"])
        return GroupLimitedMoE(
            h, c["moe_intermediate_size"], c["n_routed_experts"],
            c["num_experts_per_tok"], n_group=c.get("n_group", 1),
            topk_group=c.get("topk_group", 1),
            n_shared=c.get("n_shared_experts", 0),
            routed_scaling=c.get("routed_scaling_factor", 1.0),
            experts_held=experts_held)

    return PatternDecoder(
        c["vocab_size"] if vocab is None else vocab, h, attention,
        [ffn(i) for i in range(n_layer)],
        seq_len=c["max_position_embeddings"], rms_eps=eps,
        initializer_range=c.get("initializer_range", 0.02), **kwargs)
