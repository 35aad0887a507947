"""Plain float32 forward of a Command A+ decoder (the language model
of ``model_type`` ``cohere2_moe``; the configuration's ``assumed``
lists every reading that is an inference). Every layer is ONE
parallel block: ``u = LN(x)``, a mean-centred LayerNorm with a gain
and no bias, and ``x + Attn(u) + FFN(u)``.

- Attention: 128 query heads over 8 K/V heads of 128, query head j on
  K/V head ``j // 16``, scale ``head_dim^-0.5``, no bias, no qk-norm.
  A *sliding* layer rotates q and k over the first ``rotary_pct`` of a
  head in the GPT-J convention, pair (2i, 2i + 1) turned by ``p *
  theta^(-2i / rotary)``, written out pair by pair below, and its
  query at t sees the keys s with ``0 <= t - s < sliding_window``; a
  *full* layer has no positional encoding at all and sees every key
  before it. A K/V head and ``q_block`` queries at a time, so that
  25k tokens fit the chip; a sliding query block is multiplied with
  the keys it can see only.
- Experts: ``sigmoid(u W_r)`` over ALL routed experts, the top
  ``num_experts_per_tok`` by those scores alone (no selection bias),
  weighted by the scores over their sum; SwiGLU experts, only those in
  ``held``; the shared experts as one SwiGLU of their summed width;
  ``FFN(u) = (routed + shared) / 2`` (``"average"``), or their sum.
- Head: ``logit_scale * LN_f(x) E^T``, E the token embedding (tied).

One dense pass over prompt plus served tokens, every product at
``highest`` precision, no cache, no batching of requests in flight;
imports nothing of the program. The whole model in float32 does not
fit the chip: the caller hands the layers' weights one at a time
(``layer_weights(i)``, made again from the seed), and the
feed-forward part runs ``ffn_block`` tokens at a time. ``quant`` puts
the same pass in the next precision down: every matrix product's
operands, and the keys and values as a cache would hold them, rounded
to float8 e4m3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.transformer import (HIGHEST, _f8, _matmul,
                                             gaps_of)

__all__ = ["hidden", "head", "gaps_of", "route", "layer", "attention",
           "moe", "rope_gptj", "layer_norm"]


def layer_norm(x, g, eps):
    """Mean-centred LayerNorm with a gain and no bias."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g


def rope_gptj(theta: float, x, positions):
    """GPT-J's interleaved rotary of x (..., dim) at ``positions``
    (broadcast against x's leading axes): the pair (x[2i], x[2i + 1])
    turned by the angle ``p * theta^(-2i / dim)``."""
    dim = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                          / dim)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned_even = even * cos - odd * sin
    turned_odd = odd * cos + even * sin
    return jnp.stack([turned_even, turned_odd], axis=-1).reshape(
        x.shape)


# -- attention --------------------------------------------------------

def attention(cfg: dict, p, x, sliding: bool, quant: bool = False,
              q_block: int = 512):
    """One sequence ``x`` (T, hidden) through one layer's attention,
    the output projection included."""
    t = x.shape[0]
    nh, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    r = nh // g
    window = cfg["sliding_window"] if sliding else 0
    rd = int(cfg.get("rotary_pct", 1.0) * d) // 2 * 2
    pos = jnp.arange(t)
    q = _matmul(x, p["q"], quant).reshape(t, g, r, d)
    k = _matmul(x, p["k"], quant).reshape(t, g, d)
    v = _matmul(x, p["v"], quant).reshape(t, g, d)
    if sliding:
        part = lambda a, at: jnp.concatenate(
            [rope_gptj(cfg["rope_theta"], a[..., :rd], at),
             a[..., rd:]], axis=-1)
        q, k = part(q, pos[:, None, None]), part(k, pos[:, None])
    if quant:                       # as a cache would hold them
        k, v = _f8(k, -1), _f8(v, -1)
    qb = q_block if t % q_block == 0 else t
    # a sliding query block's keys: the block's own positions and the
    # window - 1 before them, cut out of the keys (padded in front by
    # as many rows, which the mask leaves out)
    back = window - 1 if window else 0
    front = lambda a: jnp.pad(a, [(back, 0)] + [(0, 0)] * (a.ndim - 1))

    def group(args):
        qg, kg, vg = args           # (T, R, d) (T + back, d) (.., d)

        def block(qa):
            qs, q0 = qa                            # (qb, R, d)
            at = q0 + jnp.arange(qb)
            if window:
                kb = jax.lax.dynamic_slice_in_dim(kg, q0, qb + back)
                vb = jax.lax.dynamic_slice_in_dim(vg, q0, qb + back)
                k_at = q0 - back + jnp.arange(qb + back)
                mask = jnp.logical_and(
                    jnp.logical_and(k_at[None] >= 0,
                                    k_at[None] <= at[:, None]),
                    at[:, None] - k_at[None] < window)
            else:
                kb, vb = kg, vg
                mask = pos[None, :] <= at[:, None]
            scores = jnp.einsum("qrd,kd->rqk", qs, kb,
                                precision=HIGHEST) * d ** -0.5
            probs = jax.nn.softmax(
                jnp.where(mask[None], scores, -1e30), axis=-1)
            return jnp.einsum("rqk,kd->qrd", probs, vb,
                              precision=HIGHEST)

        return jax.lax.map(block, (
            qg.reshape(t // qb, qb, r, d),
            jnp.arange(0, t, qb))).reshape(t, r, d)

    o = jax.lax.map(group, (jnp.moveaxis(q, 1, 0),
                            jnp.moveaxis(front(k), 1, 0),
                            jnp.moveaxis(front(v), 1, 0)))
    # (G, T, R, d) -> (T, G * R * d), query head j = g * R + r
    o = jnp.moveaxis(o, 0, 1).reshape(t, nh * d)
    return _matmul(o, p["o"], quant)


# -- feed-forward -----------------------------------------------------

def _swiglu(x, gate, up, down, quant):
    return _matmul(jax.nn.silu(_matmul(x, gate, quant)) *
                   _matmul(x, up, quant), down, quant)


def route(cfg: dict, router, x, quant: bool = False):
    """(experts (N, k), weights (N, k)) of tokens x (N, hidden) over
    ALL routed experts: the k of largest sigmoid score, weighted by
    their scores over the chosen ones' sum."""
    scores = jax.nn.sigmoid(_matmul(x, router, quant))
    weights, experts = jax.lax.top_k(scores,
                                     cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        weights = weights / (weights.sum(axis=-1, keepdims=True)
                             + 1e-20)
    return experts, weights


def moe(cfg: dict, p, x, held, quant: bool = False):
    """Tokens x (N, hidden) through the expert layer: the chosen
    experts in ``held`` = (first, count), a loop over them, each over
    every token weighted by the token's weight for it (nought where
    not chosen); the shared SwiGLU; the two joined as the
    configuration's strategy says."""
    n = x.shape[0]
    first, count = held
    experts, weights = route(cfg, p["router"], x, quant)
    dense_w = jnp.zeros((n, p["router"].shape[1]), jnp.float32).at[
        jnp.arange(n)[:, None], experts].set(weights)
    dense_w = dense_w[:, first:first + count]

    def one(acc, w):
        gate, up, down, col = w
        return acc + col[:, None] * _swiglu(
            x, gate.astype(jnp.float32), up.astype(jnp.float32),
            down.astype(jnp.float32), quant), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts_gate"], p["experts_up"], p["experts_down"],
         dense_w.T))
    shared = _swiglu(x, p["shared_gate"], p["shared_up"],
                     p["shared_down"], quant)
    if cfg.get("shared_expert_combination_strategy",
               "sum") == "average":
        return 0.5 * (routed + shared)
    return routed + shared


_KEYS = ("hidden_size", "layer_norm_eps", "num_attention_heads",
         "num_key_value_heads", "head_dim", "rotary_pct", "rope_theta",
         "sliding_window", "num_experts_per_tok", "norm_topk_prob",
         "shared_expert_combination_strategy")


@functools.partial(jax.jit, static_argnames=(
    "frozen", "sliding", "held", "quant", "q_block", "ffn_block"))
def _layer(x, p, frozen, sliding, held, quant, q_block, ffn_block):
    cfg = dict(frozen)
    eps = cfg["layer_norm_eps"]
    experts = {k: v for k, v in p["ffn"].items()
               if k.startswith("experts_")}
    p = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        {**p, "ffn": {k: v for k, v in p["ffn"].items()
                      if k not in experts}})
    ffn_p = {**p["ffn"], **experts}
    u = layer_norm(x, p["norm1"], eps)
    a = jax.lax.map(lambda row: attention(cfg, p["attn"], row, sliding,
                                          quant, q_block), u)
    flat = u.reshape(-1, u.shape[-1])
    fb = ffn_block if flat.shape[0] % ffn_block == 0 else flat.shape[0]
    f = jax.lax.map(lambda rows: moe(cfg, ffn_p, rows, held, quant),
                    flat.reshape(-1, fb, flat.shape[-1]))
    return x + a + f.reshape(u.shape)


def layer(cfg: dict, x, p, sliding: bool, held, quant: bool = False,
          q_block: int = 512, ffn_block: int = 4096):
    """One block on (B, T, hidden) float32; ``p`` the layer's weights
    as `benchmark/weights_command.py` makes them; ``held`` (first,
    count)."""
    frozen = tuple((k, cfg.get(k)) for k in _KEYS)
    return _layer(x, p, frozen, bool(sliding), tuple(held), quant,
                  q_block, ffn_block)


@functools.partial(jax.jit, static_argnames=("eps", "scale", "quant"))
def head(x, norm_f, tok_embed, eps: float, scale: float = 1.0,
         quant: bool = False):
    """Logits of hidden rows (..., hidden): the final norm, then the
    tied embedding's transpose over the held vocabulary, times
    ``scale`` (``logit_scale``)."""
    h = layer_norm(x, norm_f.astype(jnp.float32), eps)
    return scale * _matmul(h, tok_embed.astype(jnp.float32).T, quant)


def hidden(cfg: dict, embeddings: dict, layer_weights, ids, held,
           quant: bool = False, q_block: int = 512):
    """(B, T, hidden) float32 output of the last block (before the
    final norm) for right-padded ``ids`` (B, T); ``layer_weights(i)``
    returns layer i's weights. Causality makes right-padding
    harmless."""
    x = jnp.take(embeddings["tok_embed"].astype(jnp.float32),
                 jnp.asarray(ids, jnp.int32), axis=0)
    for i in range(cfg["n_layer"]):
        x = layer(cfg, x, layer_weights(i),
                  cfg["layer_types"][i] == "sliding_attention", held,
                  quant, q_block)
    return x
