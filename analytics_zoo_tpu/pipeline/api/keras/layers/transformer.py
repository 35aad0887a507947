"""Transformer layers: MultiHeadAttention, TransformerLayer (GPT-style),
BERT.

Reference surface: `Z/pipeline/api/keras/layers/TransformerLayer.scala:50`
(input [batch, seqLen, 2] = token+position ids, post-LN blocks,
`bidirectional` flag) and `BERT.scala:53-110` (4 inputs: ids, segment
ids, position ids, attention mask; pooled first-token output;
`output_all_block`).

TPU-first redesign:
- all N blocks share ONE traced program: per-block params are stacked on
  a leading axis and the depth loop is a `lax.scan` — compile time and
  HLO size are O(1) in depth (the reference unrolls per block);
- attention runs in f32 softmax over bf16 QK^T on the MXU
  (`ops.attention`), or sequence-parallel ring attention over a mesh
  axis when `sequence_parallel_axis` is set (long-context path the
  reference lacks);
- weights init normal(0, initializer_range) like the reference.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.attention import (dot_product_attention,
                                             resolve_attention_impl)
from analytics_zoo_tpu.pipeline.api.keras.engine import (
    KerasLayer, Shape, ShapeLike)


def _normal(rng, shape, stddev):
    return jax.random.normal(rng, shape, jnp.float32) * stddev


def _layer_norm(x, g, b, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return y * g.astype(y.dtype) + b.astype(y.dtype)


def _dropout(x, p, rng, training):
    if not training or p <= 0.0 or rng is None:
        return x
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


class MultiHeadAttention(KerasLayer):
    """Self-attention layer (the per-block attention of the reference's
    TransformerLayer, exposed standalone)."""

    def __init__(self, hidden_size: int, n_head: int,
                 attn_p_drop: float = 0.1, resid_p_drop: float = 0.1,
                 causal: bool = False, initializer_range: float = 0.02,
                 sequence_parallel_axis: Optional[str] = None,
                 sequence_parallel_mode: str = "ring",
                 attention_impl: Optional[str] = None,
                 input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        if hidden_size % n_head:
            raise ValueError("hidden_size must divide by n_head")
        from analytics_zoo_tpu.parallel import get_sp_attention
        get_sp_attention(sequence_parallel_mode)  # validate early
        # None → ZOO_TPU_ATTENTION env (default "auto": the Pallas
        # flash kernel on TPU past the crossover, else XLA dense);
        # "flash"/"xla" force one path (ops/flash_attention.py)
        if attention_impl is not None:
            resolve_attention_impl(attention_impl)  # validate early
        self.attention_impl = attention_impl
        self.hidden_size = int(hidden_size)
        self.n_head = int(n_head)
        self.attn_p_drop = float(attn_p_drop)
        self.resid_p_drop = float(resid_p_drop)
        self.causal = causal
        self.initializer_range = float(initializer_range)
        self.sequence_parallel_axis = sequence_parallel_axis
        self.sequence_parallel_mode = sequence_parallel_mode

    def build(self, rng, input_shape: Shape) -> dict:
        h = self.hidden_size
        k1, k2 = jax.random.split(rng)
        return {
            "qkv_kernel": _normal(k1, (h, 3 * h), self.initializer_range),
            "qkv_bias": jnp.zeros((3 * h,), jnp.float32),
            "out_kernel": _normal(k2, (h, h), self.initializer_range),
            "out_bias": jnp.zeros((h,), jnp.float32),
        }

    def _attend(self, q, k, v, mask):
        if self.sequence_parallel_axis:
            if mask is not None:
                raise NotImplementedError(
                    "attention masks are not supported under sequence "
                    "parallelism (causal masking is); drop padding or "
                    "unset sequence_parallel_axis")
            from analytics_zoo_tpu.common.nncontext import get_nncontext
            from analytics_zoo_tpu.parallel import get_sp_attention
            sp = get_sp_attention(self.sequence_parallel_mode)
            return sp(q, k, v, get_nncontext().mesh,
                      axis=self.sequence_parallel_axis,
                      causal=self.causal, impl=self.attention_impl)
        return dot_product_attention(q, k, v, mask=mask,
                                     causal=self.causal,
                                     impl=self.attention_impl)

    def call(self, params, x, *, training=False, rng=None, mask=None):
        b, t, h = x.shape
        nh, hd = self.n_head, h // self.n_head
        qkv = x @ params["qkv_kernel"].astype(x.dtype) + \
            params["qkv_bias"].astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, nh, hd)
        k = k.reshape(b, t, nh, hd)
        v = v.reshape(b, t, nh, hd)
        out = self._attend(q, k, v, mask).reshape(b, t, h)
        out = out @ params["out_kernel"].astype(out.dtype) + \
            params["out_bias"].astype(out.dtype)
        if rng is not None:
            out = _dropout(out, self.resid_p_drop, rng, training)
        return out

    def compute_output_shape(self, input_shape: Shape) -> Shape:
        return input_shape


class TransformerLayer(KerasLayer):
    """GPT-style decoder stack (reference `TransformerLayer.scala:50`).

    Input: (seq_len,) int token ids (positions are implicit 0..T-1 —
    covers the reference's [seqLen, 2] token+position input, which is
    also accepted). Output: (seq_len, hidden_size), or a list of every
    block's output when `output_all_block`.
    """

    def __init__(self, n_block: int = 12, hidden_size: int = 768,
                 n_head: int = 12, seq_len: int = 512,
                 vocab: int = 40990, intermediate_size: int = 0,
                 hidden_p_drop: float = 0.1, attn_p_drop: float = 0.1,
                 initializer_range: float = 0.02,
                 bidirectional: bool = False,
                 output_all_block: bool = False,
                 embed_p_drop: float = 0.1,
                 sequence_parallel_axis: Optional[str] = None,
                 sequence_parallel_mode: str = "ring",
                 attention_impl: Optional[str] = None,
                 remat: bool = False,
                 pipeline_parallel_axis: Optional[str] = None,
                 pipeline_microbatches: Optional[int] = None,
                 input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape or (seq_len,),
                         name=name, **kwargs)
        if hidden_size % n_head:
            raise ValueError("hidden_size must divide by n_head")
        if pipeline_parallel_axis and sequence_parallel_axis:
            raise ValueError(
                "pipeline_parallel_axis and sequence_parallel_axis "
                "cannot combine (nested shard_map); pick one")
        if pipeline_parallel_axis and output_all_block:
            raise ValueError(
                "output_all_block is unavailable under pipeline "
                "parallelism (only the final stage's output exists)")
        self.pipeline_parallel_axis = pipeline_parallel_axis
        self.pipeline_microbatches = pipeline_microbatches
        from analytics_zoo_tpu.parallel import get_sp_attention
        get_sp_attention(sequence_parallel_mode)  # validate early
        self.sequence_parallel_mode = sequence_parallel_mode
        if attention_impl is not None:
            resolve_attention_impl(attention_impl)  # validate early
        self.attention_impl = attention_impl
        self.remat = bool(remat)
        self.n_block = int(n_block)
        self.hidden_size = int(hidden_size)
        self.n_head = int(n_head)
        self.seq_len = int(seq_len)
        self.vocab = int(vocab)
        self.intermediate_size = int(intermediate_size) or \
            4 * self.hidden_size
        self.hidden_p_drop = float(hidden_p_drop)
        self.attn_p_drop = float(attn_p_drop)
        self.initializer_range = float(initializer_range)
        self.bidirectional = bidirectional
        self.output_all_block = output_all_block
        self.embed_p_drop = float(embed_p_drop)
        self.sequence_parallel_axis = sequence_parallel_axis

    # -- params -------------------------------------------------------------
    def _build_blocks(self, rng) -> dict:
        """Per-block params stacked on a leading n_block axis."""
        h, m, n = self.hidden_size, self.intermediate_size, self.n_block
        ks = jax.random.split(rng, 4)
        r = self.initializer_range
        return {
            "qkv_kernel": _normal(ks[0], (n, h, 3 * h), r),
            "qkv_bias": jnp.zeros((n, 3 * h), jnp.float32),
            "attn_out_kernel": _normal(ks[1], (n, h, h), r),
            "attn_out_bias": jnp.zeros((n, h), jnp.float32),
            "ln1_g": jnp.ones((n, h), jnp.float32),
            "ln1_b": jnp.zeros((n, h), jnp.float32),
            "mlp_in_kernel": _normal(ks[2], (n, h, m), r),
            "mlp_in_bias": jnp.zeros((n, m), jnp.float32),
            "mlp_out_kernel": _normal(ks[3], (n, m, h), r),
            "mlp_out_bias": jnp.zeros((n, h), jnp.float32),
            "ln2_g": jnp.ones((n, h), jnp.float32),
            "ln2_b": jnp.zeros((n, h), jnp.float32),
        }

    def build(self, rng, input_shape: ShapeLike) -> dict:
        k_embed, k_pos, k_blocks = jax.random.split(rng, 3)
        r = self.initializer_range
        return {
            "tok_embed": _normal(k_embed, (self.vocab, self.hidden_size),
                                 r),
            "pos_embed": _normal(k_pos, (self.seq_len, self.hidden_size),
                                 r),
            "blocks": self._build_blocks(k_blocks),
        }

    # -- forward ------------------------------------------------------------
    def _split_qkv(self, p, x):
        """(…, H) → q, k, v with heads split — the projection half of
        a block, shared by the full forward and the cached decode path
        so both trace the exact same matmul."""
        nh = self.n_head
        hd = self.hidden_size // nh
        qkv = x @ p["qkv_kernel"].astype(x.dtype) + \
            p["qkv_bias"].astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = x.shape[:-1] + (nh, hd)
        return q.reshape(shp), k.reshape(shp), v.reshape(shp)

    def _block_tail(self, p, x, attn, r1=None, r2=None,
                    training=False):
        """Out-projection + residual/LN + MLP half of a block (every
        op after attention) — the single copy run by the full forward
        AND the decode step, so the paged-cache path is numerically
        the training graph, not a reimplementation of it. Shape-
        agnostic over leading dims ((B, T, H) or (S, H))."""
        attn = attn @ p["attn_out_kernel"].astype(x.dtype) + \
            p["attn_out_bias"].astype(x.dtype)
        attn = _dropout(attn, self.hidden_p_drop, r1, training)
        x = _layer_norm(x + attn, p["ln1_g"], p["ln1_b"])
        mlp = jax.nn.gelu(x @ p["mlp_in_kernel"].astype(x.dtype) +
                          p["mlp_in_bias"].astype(x.dtype))
        mlp = mlp @ p["mlp_out_kernel"].astype(x.dtype) + \
            p["mlp_out_bias"].astype(x.dtype)
        mlp = _dropout(mlp, self.hidden_p_drop, r2, training)
        return _layer_norm(x + mlp, p["ln2_g"], p["ln2_b"])

    def _embed(self, params, x):
        if x.ndim == 3:  # reference layout (B, T, 2): token + position
            tok_ids = x[..., 0].astype(jnp.int32)
            pos_ids = x[..., 1].astype(jnp.int32)
            pos = jnp.take(params["pos_embed"], pos_ids, axis=0)
        else:
            tok_ids = x.astype(jnp.int32)
            pos = params["pos_embed"][None, :tok_ids.shape[1]]
        return jnp.take(params["tok_embed"], tok_ids, axis=0) + pos

    def _run_blocks(self, params, h0, mask, training, rng):
        causal = not self.bidirectional
        sp_axis = self.sequence_parallel_axis
        n = self.n_block
        rngs = (jax.random.split(rng, n) if rng is not None
                else jnp.zeros((n, 2), jnp.uint32))

        def block_body(x, p, blk_rng, mask):
            b, t, hsz = x.shape
            r1 = r2 = r3 = None
            if rng is not None:
                key = jax.random.wrap_key_data(blk_rng) if \
                    blk_rng.dtype == jnp.uint32 else blk_rng
                r1, r2, r3 = jax.random.split(key, 3)
            q, k, v = self._split_qkv(p, x)
            if sp_axis:
                if mask is not None:
                    raise NotImplementedError(
                        "attention masks are not supported under "
                        "sequence parallelism (causal masking is); "
                        "drop padding or unset sequence_parallel_axis")
                from analytics_zoo_tpu.common.nncontext import \
                    get_nncontext
                from analytics_zoo_tpu.parallel import get_sp_attention
                sp = get_sp_attention(self.sequence_parallel_mode)
                attn = sp(q, k, v, get_nncontext().mesh,
                          axis=sp_axis, causal=causal,
                          impl=self.attention_impl)
            else:
                attn = dot_product_attention(q, k, v, mask=mask,
                                             causal=causal,
                                             impl=self.attention_impl)
            attn = attn.reshape(b, t, hsz)
            return self._block_tail(p, x, attn, r1, r2, training)

        if rng is not None:
            rngs_data = jax.vmap(jax.random.key_data)(rngs)
        else:
            rngs_data = rngs
        if self.remat:
            # per-block rematerialization: the backward recomputes each
            # block's activations instead of keeping all n_block of
            # them live — O(1)-in-depth activation memory for ~1/3
            # extra FLOPs (the TPU HBM lever for deep/long-context
            # training; composes with the scan's O(1) compile time)
            block_body = jax.checkpoint(block_body)

        if self.pipeline_parallel_axis:
            final = self._run_blocks_gpipe(params, h0, mask,
                                           rngs_data, block_body)
            return final, None

        def block(x, inputs):
            p, blk_rng = inputs
            out = block_body(x, p, blk_rng, mask)
            return out, out

        final, all_blocks = jax.lax.scan(
            block, h0, (params["blocks"], rngs_data))
        return final, all_blocks

    def _run_blocks_gpipe(self, params, h0, mask, rngs_data,
                          block_body):
        """GPipe the block stack over the mesh's
        ``pipeline_parallel_axis``: ``n_block/S`` consecutive blocks
        per stage, microbatches rotating via ppermute
        (`parallel/pipeline.py`). Per-microbatch dropout keys are
        derived by folding the microbatch index into each block's key
        (the sequential path draws ONE key per block for the whole
        batch, so training randomness differs — inference and no-
        dropout training match exactly)."""
        from analytics_zoo_tpu.common.nncontext import get_nncontext
        from analytics_zoo_tpu.parallel.pipeline import gpipe_apply

        axis = self.pipeline_parallel_axis
        mesh = get_nncontext().mesh
        if axis not in mesh.shape:
            raise ValueError(
                f"pipeline_parallel_axis {axis!r} not in mesh axes "
                f"{tuple(mesh.shape)}")
        s = mesh.shape[axis]
        n = self.n_block
        if n % s:
            raise ValueError(
                f"n_block {n} must divide by the {axis!r} axis size "
                f"{s}")
        nb = n // s
        stage_params = {
            "blocks": jax.tree_util.tree_map(
                lambda a: a.reshape((s, nb) + a.shape[1:]),
                params["blocks"]),
            "rngs": rngs_data.reshape((s, nb) + rngs_data.shape[1:]),
        }
        m = self.pipeline_microbatches or s
        # per-sample masks (batch-leading, e.g. BERT's (B,1,1,T))
        # ride per microbatch; broadcastable masks ((1,1,T,T), (T,T))
        # are microbatch-independent and go to every stage whole
        margs, bargs = [], []
        if mask is not None:
            # a (1,1,T,T) broadcast mask with batch==1 must not be
            # classified per-sample (it would be split over
            # microbatches); only a >1 leading dim matching the batch
            # is genuinely per-sample
            per_sample = (mask.ndim == 4 and mask.shape[0] > 1
                          and mask.shape[0] == h0.shape[0])
            (margs if per_sample else bargs).append(mask)

        def stage(sp, h, mb_idx, *rest):
            mask_mb = rest[0] if rest else None

            def inner(x, inp):
                p, blk_rng = inp
                # distinct dropout per microbatch: fold mb_idx in
                blk_rng = jax.random.key_data(jax.random.fold_in(
                    jax.random.wrap_key_data(blk_rng), mb_idx))
                out = block_body(x, p, blk_rng, mask_mb)
                return out, None

            h, _ = jax.lax.scan(inner, h,
                                (sp["blocks"], sp["rngs"]))
            return h

        return gpipe_apply(stage, stage_params, h0, mesh=mesh,
                           axis=axis, microbatches=m,
                           microbatched_args=margs,
                           broadcast_args=bargs,
                           pass_mb_index=True)

    def call(self, params, x, *, training=False, rng=None, mask=None):
        r_embed = None
        if rng is not None:
            rng, r_embed = jax.random.split(rng)
        h0 = self._embed(params, x)
        h0 = _dropout(h0, self.embed_p_drop, r_embed, training)
        final, all_blocks = self._run_blocks(params, h0, mask, training,
                                             rng)
        if self.output_all_block:
            return [all_blocks[i] for i in range(self.n_block)]
        return final

    def compute_output_shape(self, input_shape: ShapeLike):
        t = (input_shape[0] if not is_multi(input_shape)
             else input_shape[0][0])
        shape = (t, self.hidden_size)
        if self.output_all_block:
            return [shape] * self.n_block
        return shape

    # -- decode fast path ---------------------------------------------------
    # Autoregressive generation with a paged KV cache (ops/kv_cache):
    # `prefill` runs the prompt once and caches every block's K/V;
    # `decode_step` extends every slot by ONE token against the cache
    # (O(T) per token instead of the naive O(T²) re-forward);
    # `forward_chunk` extends every slot by a BOUNDED chunk of C
    # tokens at a per-slot offset — the shared primitive under
    # chunked prefill (C-token slices of a long prompt interleaved
    # with decode iterations) and speculative verify (score C drafted
    # tokens in one pass); and `generate` wires prefill + decode_step
    # into a lax.while_loop whose shapes are static in (slots, pages)
    # — the whole loop compiles once and is AOT-warmable. Logits are
    # tied to `tok_embed` (h @ tok_embedᵀ), the weight-tying the
    # reference's LM head uses. Int8 caches carry per-row scale pools
    # (`ops.kv_cache`): writes quantize, attention dequantizes at the
    # gather — this layer only threads the scale arrays through.
    # Inference-only: no dropout, no sequence/pipeline parallelism.

    def init_kv_cache(self, max_slots: int, max_context: int,
                      page_size: int = 16, dtype=None,
                      max_chunk: int = 1):
        """A fresh paged cache sized for this stack: one page pool
        per block, identity page table (see `ops.kv_cache`).
        ``max_chunk`` (the most tokens one :meth:`forward_chunk` call
        will write a slot) sizes nothing here: every position of the
        context keeps its page."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        del max_chunk
        return kvc.init_cache(
            self.n_block, int(max_slots), int(max_context),
            self.n_head, self.hidden_size // self.n_head,
            page_size=int(page_size), dtype=dtype or jnp.float32)

    def prefill(self, params, cache, token_ids, prompt_lens,
                slots=None):
        """Run the (right-padded) prompts once, writing every block's
        K/V into the cache, and return ``(cache', logits)`` with
        logits taken at each row's last real prompt position.

        token_ids: (A, T) int, the prompts being admitted, one a row;
        prompt_lens: (A,) int32; slots: (A,) int32, distinct — the
        cache slot each row is (``None``: row a is slot a, every slot
        a row, which is how `generate` calls). The cache is touched
        through ``slots`` alone: the rows' K/V go to the pages of
        ``cache.page_table[slots]``, ``seq_lens`` is set at ``slots``,
        and logits are (A, vocab). Every other slot — its pages, its
        length, what it decodes next — is what it was, and so is the
        slot of a row with ``prompt_lens == 0``; that is what lets
        the continuous batcher admit into a live batch with a program
        no larger than the prompts it admits. Causality makes
        right-padding safe: pad positions sit after every real token,
        so they influence nothing — their K/V rows are dropped at the
        scatter and masked at gather anyway."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        a, t = token_ids.shape
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
        slots = jnp.arange(a, dtype=jnp.int32) if slots is None \
            else jnp.asarray(slots, jnp.int32)
        h0 = self._embed(params, token_ids)
        causal = not self.bidirectional

        @jax.named_scope("zoo:prefill/layer")
        def block(x, p):
            q, k, v = self._split_qkv(p, x)
            with jax.named_scope("zoo:prefill/attention"):
                attn = dot_product_attention(
                    q, k, v, causal=causal, impl=self.attention_impl)
            attn = attn.reshape(a, t, self.hidden_size)
            return self._block_tail(p, x, attn), (k, v)

        final, (k_all, v_all) = jax.lax.scan(block, h0,
                                             params["blocks"])
        cache = self._write_prompt_all(
            cache, cache.page_table[slots], k_all, v_all, prompt_lens)
        cache = cache._replace(seq_lens=kvc.prompt_seq_lens(
            cache.seq_lens, slots, prompt_lens))
        with jax.named_scope("zoo:prefill/lm_head"):
            last = final[jnp.arange(a),
                         jnp.maximum(prompt_lens - 1, 0)]
            logits = last @ params["tok_embed"].astype(last.dtype).T
        return cache, logits

    def _write_prompt_all(self, cache, table, k_all, v_all,
                          total_lens):
        """Scatter every block's prompt K/V (k_all/v_all:
        (L, A, T, nh, hd)) into the stacked pools through ``table``
        (A, pages_per_slot), the rows' own table rows; quantized
        caches thread their scale pools through the same coordinates.
        Returns the cache with pages (and scales) replaced —
        ``seq_lens`` is the caller's to update."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        pools = kvc.write_prompt_layer(
            cache.k_pages, cache.v_pages, table, total_lens, k_all,
            v_all, k_scales=cache.k_scales, v_scales=cache.v_scales)
        return cache._replace(**dict(zip(
            ("k_pages", "v_pages", "k_scales", "v_scales"), pools)))

    def decode_step(self, params, cache, token_ids, active=None):
        """One decode step for every slot: consume ``token_ids`` (S,)
        — each slot's previously sampled token — at position
        ``cache.seq_lens[s]``, append its K/V, attend over the cache,
        and return ``(cache', logits (S, V))``. Slots with
        ``active == False`` are frozen: nothing is written, their
        seq_lens do not advance, and (because inactive scatters are
        dropped) their pages cannot be perturbed by neighbours.
        Shape-static — safe inside while_loop and as ONE compiled
        program under continuous batching."""
        from analytics_zoo_tpu.ops import kv_cache as kvc
        from analytics_zoo_tpu.ops.attention import (
            decode_attention, paged_decode_attention, paged_decode_ok)
        s = token_ids.shape[0]
        if active is None:
            active = cache.seq_lens > 0
        pos = jnp.clip(cache.seq_lens, 0, self.seq_len - 1)
        x = jnp.take(params["tok_embed"],
                     token_ids.astype(jnp.int32), axis=0) + \
            jnp.take(params["pos_embed"], pos, axis=0)
        lens_after = cache.seq_lens + active.astype(jnp.int32)
        # one algorithm, single-query attention over a paged cache,
        # whose operands are the pages where the kernel runs and a
        # dense gathered view elsewhere (`paged_decode_ok`'s rule)
        paged = paged_decode_ok(cache, x.dtype, self.attention_impl)
        writes = kvc._decode_writes(cache, active) if paged else None

        # the pools are closed over, not scanned: the body only reads
        # them, and the step's rows are written once after the scan
        @jax.named_scope("zoo:decode/layer")
        def block(x, xs):
            p, layer = xs
            q, k_new, v_new = self._split_qkv(p, x)
            if paged:
                rows = kvc.decode_rows(cache, k_new, v_new)
                attn = paged_decode_attention(
                    q, rows[0], rows[1], cache, layer, writes)
            else:
                (k_ctx, v_ctx, sk, sv), rows = kvc.decode_view(
                    cache, layer, k_new, v_new, active=active)
                if sk is None:
                    k_ctx = k_ctx.astype(x.dtype)
                    v_ctx = v_ctx.astype(x.dtype)
                attn = decode_attention(q, k_ctx, v_ctx, lens_after,
                                        impl=self.attention_impl,
                                        k_scales=sk, v_scales=sv)
            attn = attn.reshape(s, self.hidden_size)
            return self._block_tail(p, x, attn), rows

        final, rows = jax.lax.scan(
            block, x, (params["blocks"],
                       jnp.arange(self.n_block, dtype=jnp.int32)))
        cache = kvc.append_rows(cache, rows, active=active)._replace(
            seq_lens=lens_after)
        with jax.named_scope("zoo:decode/lm_head"):
            logits = final @ params["tok_embed"].astype(final.dtype).T
        return cache, logits

    def forward_chunk(self, params, cache, token_ids, starts, n_new,
                      all_logits: bool = False, slots=None):
        """Consume a bounded CHUNK of new tokens per slot against the
        cache — `decode_step` generalized from 1 to C tokens, with a
        per-slot write offset.

        token_ids: (S, C) int — each slot's next tokens, left-aligned
        and right-padded; starts: (S,) int32 — the absolute position
        the chunk begins at (== the slot's current cached length);
        n_new: (S,) int32 — how many of the C rows are real for each
        slot (0 = slot untouched: nothing written, seq_lens frozen,
        and — because inactive scatters drop — neighbours cannot be
        perturbed). Every block writes the chunk's K/V into the pages
        FIRST, then attends over the gathered cache with the mask
        ``key_pos <= start + j`` (`ops.attention.chunk_attention`),
        so intra-chunk causality and cache validity are one rule and
        the math is the training graph's.

        Returns ``(cache', logits)``: logits (S, V) at each slot's
        LAST real chunk position (chunked prefill — sample the first
        token when the final chunk lands), or (S, C, V) at every
        chunk position when ``all_logits`` (speculative verify —
        score every draft). ``seq_lens`` advances to
        ``starts + n_new`` for touched slots. Shape-static in (S, C);
        safe to AOT-compile once per chunk width.

        ``slots`` (A,) int32: the rows are chunks of THESE slots (as
        :meth:`prefill`'s rows are the prompts being admitted), not
        of every slot in order; no other slot is read or written.
        """
        from analytics_zoo_tpu.ops import kv_cache as kvc
        from analytics_zoo_tpu.ops.attention import chunk_attention
        s, c = token_ids.shape
        starts = jnp.asarray(starts, jnp.int32)
        n_new = jnp.asarray(n_new, jnp.int32)
        total = starts + n_new
        q_pos = starts[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        pos_ids = jnp.clip(q_pos, 0, self.seq_len - 1)
        x = jnp.take(params["tok_embed"],
                     token_ids.astype(jnp.int32), axis=0) + \
            jnp.take(params["pos_embed"], pos_ids, axis=0)
        t_max = cache.max_context
        slots = jnp.arange(s, dtype=jnp.int32) if slots is None \
            else jnp.asarray(slots, jnp.int32)
        table = cache.page_table[slots]

        @jax.named_scope("zoo:prefill/chunk_layer")
        def block(x, xs):
            p, kp, vp, ks, vs = xs
            q, k_new, v_new = self._split_qkv(p, x)
            if ks is None:
                kp, vp = kvc.write_prompt_layer(
                    kp, vp, table, total, k_new, v_new, start=starts)
                sk = sv = None
            else:
                kp, vp, ks, vs = kvc.write_prompt_layer(
                    kp, vp, table, total, k_new, v_new, start=starts,
                    k_scales=ks, v_scales=vs)
                sk = kvc.gather_layer(ks, table, t_max)
                sv = kvc.gather_layer(vs, table, t_max)
            k_ctx = kvc.split_heads(
                kvc.gather_layer(kp, table, t_max), *k_new.shape[2:])
            v_ctx = kvc.split_heads(
                kvc.gather_layer(vp, table, t_max), *v_new.shape[2:])
            if ks is None:
                k_ctx = k_ctx.astype(x.dtype)
                v_ctx = v_ctx.astype(x.dtype)
            attn = chunk_attention(q, k_ctx, v_ctx, q_pos,
                                   k_scales=sk, v_scales=sv)
            attn = attn.reshape(s, c, self.hidden_size)
            return self._block_tail(p, x, attn), (kp, vp, ks, vs)

        final, (k_pages, v_pages, k_scales, v_scales) = jax.lax.scan(
            block, x, (params["blocks"], cache.k_pages,
                       cache.v_pages, cache.k_scales,
                       cache.v_scales))
        cache = cache._replace(
            k_pages=k_pages, v_pages=v_pages,
            k_scales=k_scales, v_scales=v_scales,
            seq_lens=cache.seq_lens.at[slots].set(jnp.where(
                n_new > 0, total, cache.seq_lens[slots])))
        embed_t = params["tok_embed"].astype(final.dtype).T
        if all_logits:
            return cache, final @ embed_t
        last = final[jnp.arange(s),
                     jnp.clip(n_new - 1, 0, c - 1)]
        return cache, last @ embed_t

    def generate(self, params, prompts, prompt_lens=None,
                 max_new_tokens: int = 32, *, temperature=0.0,
                 top_k: int = 0, eos_id=None, rng=None,
                 page_size: int = 16, cache_dtype=None):
        """Compiled autoregressive generation: prefill + a
        `lax.while_loop` of decode steps over (cache, token buffer,
        done-mask). Greedy when ``temperature <= 0`` (per-slot —
        temperature may be a (S,) vector), else softmax sampling with
        optional static ``top_k`` truncation. Stops early when every
        slot has emitted ``eos_id``.

        prompts: (S, T) int, right-padded to ``prompt_lens``.
        Returns ``(tokens (S, T + max_new_tokens), lengths (S,))`` —
        per slot, ``tokens[s, :lengths[s]]`` is prompt + generation
        (contiguous even when the prompt was padded). Shapes are
        static in (S, T, max_new_tokens): wrap in `jax.jit` (or AOT
        `.lower().compile()`) and the whole loop is one program."""
        from analytics_zoo_tpu.ops.sampling import sample_tokens
        prompts = jnp.asarray(prompts, jnp.int32)
        s, tp = prompts.shape
        if prompt_lens is None:
            prompt_lens = jnp.full((s,), tp, jnp.int32)
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
        if rng is None:
            rng = jax.random.key(0)
        max_new = int(max_new_tokens)
        total = tp + max_new
        cache = self.init_kv_cache(s, total, page_size=page_size,
                                   dtype=cache_dtype)
        cache, logits = self.prefill(params, cache, prompts,
                                     prompt_lens)
        temp = jnp.broadcast_to(
            jnp.asarray(temperature, jnp.float32), (s,))
        buf = jnp.zeros((s, total), jnp.int32)
        buf = buf.at[:, :tp].set(prompts)
        tok = sample_tokens(jax.random.fold_in(rng, 0), logits, temp,
                            top_k)
        buf = buf.at[jnp.arange(s), prompt_lens].set(tok)
        done = (tok == eos_id) if eos_id is not None else \
            jnp.zeros((s,), jnp.bool_)
        n_new = jnp.ones((s,), jnp.int32)

        def cond(st):
            _, _, _, done, _, i = st
            return jnp.logical_and(i < max_new,
                                   jnp.logical_not(jnp.all(done)))

        def body(st):
            cache, buf, tok, done, n_new, i = st
            active = jnp.logical_not(done)
            cache, logits = self.decode_step(params, cache, tok,
                                             active=active)
            nxt = sample_tokens(jax.random.fold_in(rng, i), logits,
                                temp, top_k)
            pos = jnp.clip(prompt_lens + i, 0, total - 1)
            cur = buf[jnp.arange(s), pos]
            buf = buf.at[jnp.arange(s), pos].set(
                jnp.where(active, nxt, cur))
            n_new2 = n_new + active.astype(jnp.int32)
            if eos_id is not None:
                done = jnp.logical_or(
                    done, jnp.logical_and(active, nxt == eos_id))
            tok = jnp.where(active, nxt, tok)
            return (cache, buf, tok, done, n_new2, i + 1)

        st = (cache, buf, tok, done, n_new, jnp.asarray(1, jnp.int32))
        _, buf, _, _, n_new, _ = jax.lax.while_loop(cond, body, st)
        return buf, prompt_lens + n_new


def is_multi(s):
    return isinstance(s, list) or (isinstance(s, tuple) and s and
                                   isinstance(s[0], (tuple, list)))


class BERT(TransformerLayer):
    """BERT encoder (reference `BERT.scala:53-110`).

    Inputs: a list of 4 arrays — `[token_ids (B, T), token_type_ids
    (B, T), position_ids (B, T), attention_mask (B, T)]` (reference
    input contract). Output: `[sequence_output(s), pooled_output]` —
    per-block sequence outputs when `output_all_block`, else the last
    block's, plus the tanh-Dense pooled first token.
    """

    def __init__(self, vocab: int = 40990, hidden_size: int = 768,
                 n_block: int = 12, n_head: int = 12, seq_len: int = 512,
                 intermediate_size: int = 3072,
                 hidden_p_drop: float = 0.1, attn_p_drop: float = 0.1,
                 initializer_range: float = 0.02,
                 output_all_block: bool = True,
                 n_token_types: int = 2,
                 sequence_parallel_axis: Optional[str] = None,
                 input_shape=None, name=None, **kwargs):
        super().__init__(
            n_block=n_block, hidden_size=hidden_size, n_head=n_head,
            seq_len=seq_len, vocab=vocab,
            intermediate_size=intermediate_size,
            hidden_p_drop=hidden_p_drop, attn_p_drop=attn_p_drop,
            initializer_range=initializer_range, bidirectional=True,
            output_all_block=output_all_block,
            sequence_parallel_axis=sequence_parallel_axis,
            input_shape=input_shape or [(seq_len,)] * 4,
            name=name, **kwargs)
        self.n_token_types = int(n_token_types)

    def build(self, rng, input_shape: ShapeLike) -> dict:
        k1, k2 = jax.random.split(rng)
        params = super().build(k1, input_shape)
        r = self.initializer_range
        k_type, k_pool = jax.random.split(k2)
        params["type_embed"] = _normal(
            k_type, (self.n_token_types, self.hidden_size), r)
        params["embed_ln_g"] = jnp.ones((self.hidden_size,), jnp.float32)
        params["embed_ln_b"] = jnp.zeros((self.hidden_size,), jnp.float32)
        params["pooler_kernel"] = _normal(
            k_pool, (self.hidden_size, self.hidden_size), r)
        params["pooler_bias"] = jnp.zeros((self.hidden_size,),
                                          jnp.float32)
        return params

    def call(self, params, inputs, *, training=False, rng=None):
        token_ids, token_type_ids, position_ids, attn_mask = inputs
        tok = jnp.take(params["tok_embed"],
                       token_ids.astype(jnp.int32), axis=0)
        pos = jnp.take(params["pos_embed"],
                       position_ids.astype(jnp.int32), axis=0)
        typ = jnp.take(params["type_embed"],
                       token_type_ids.astype(jnp.int32), axis=0)
        h0 = _layer_norm(tok + pos + typ, params["embed_ln_g"],
                         params["embed_ln_b"])
        r_embed = None
        if rng is not None:
            rng, r_embed = jax.random.split(rng)
        h0 = _dropout(h0, self.embed_p_drop, r_embed, training)
        # (B, 1, 1, T) multiplicative mask → attention bias semantics of
        # the reference's `(-mask + 1) * -10000`
        mask = attn_mask[:, None, None, :]
        final, all_blocks = self._run_blocks(params, h0, mask, training,
                                             rng)
        pooled = jnp.tanh(
            final[:, 0] @ params["pooler_kernel"].astype(final.dtype) +
            params["pooler_bias"].astype(final.dtype))
        if self.output_all_block:
            outs = [all_blocks[i] for i in range(self.n_block)]
        else:
            outs = [final]
        return outs + [pooled]

    def compute_output_shape(self, input_shape: ShapeLike):
        t = input_shape[0][0]
        seq_shape = (t, self.hidden_size)
        n = self.n_block if self.output_all_block else 1
        return [seq_shape] * n + [(self.hidden_size,)]
