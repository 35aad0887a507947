"""Plain float32 forward of a DeepSeek-V2 decoder (arXiv:2405.04434;
`config.json` of deepseek-ai/DeepSeek-V2): pre-norm RMSNorm blocks,
multi-head latent attention in its expanded form (per-head keys and
values formed from the latent), rotary positions with YaRN scaling,
a dense SwiGLU first layer, then expert layers with group-limited
greedy top-k routing, unnormalised softmax weights times the routed
scaling factor and shared experts; a final norm and an untied head.
One dense pass over prompt plus served tokens, every product at
``highest`` precision, no cache, no batching of requests in flight;
imports nothing of the program.

Given a chip's share of a deployment (``held.experts`` = [first,
end) of the routed experts), the routed sum runs over the chosen
experts that are held and leaves out what the others would add, as
the program does; the router always scores all of them.

Departures from the published code, each noted in the
configuration's ``assumed``: the rotate-half convention is applied to
``q_pe`` and ``k_pe`` as they come out of the projections (HF
de-interleaves them first: a permutation of the rows of random
weights).

``quant`` puts the same pass in the next precision down, as
`reference/transformer.py` does: every matrix product's operands, and
the latent row as a cache would hold it, rounded to float8 e4m3.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.transformer import (HIGHEST, _f8, _matmul,
                                             gaps_of)

__all__ = ["hidden", "head", "gaps_of", "yarn_inv_freq",
           "softmax_scale", "route", "layer"]


# -- positions --------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The rotary frequencies, (qk_rope_head_dim / 2,) float64."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / sc["factor"]
    orig = sc["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    span = high - low if high != low else 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / span, 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def softmax_scale(cfg: dict) -> float:
    sc = cfg["rope_scaling"]
    m = _yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rope(cfg: dict, x, positions):
    """Rotate-half rotary embedding of x (..., dim) at ``positions``
    (broadcast against x's leading axes)."""
    sc = cfg["rope_scaling"]
    m = _yarn_mscale(sc["factor"], sc["mscale"]) / \
        _yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    ang = positions[..., None].astype(jnp.float32) * \
        jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1) * m
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1) * m
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


# -- attention --------------------------------------------------------

def _attention(cfg: dict, p, x, quant: bool, head_block: int = 16):
    b, t, _ = x.shape
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rdim, vdim = (cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, scale = cfg["rms_norm_eps"], softmax_scale(cfg)
    pos = jnp.arange(t)
    c_q = _rms(_matmul(x, p["q_a"], quant), p["q_norm"], eps)
    kv = _matmul(x, p["kv_a"], quant)
    c_kv = _rms(kv[..., :rank], p["kv_norm"], eps)
    k_pe = _rope(cfg, kv[..., rank:], pos[None, :])
    if quant:                       # the row as a cache would hold it
        row = _f8(jnp.concatenate([c_kv, k_pe], axis=-1), -1)
        c_kv, k_pe = row[..., :rank], row[..., rank:]
    hb = head_block if nh % head_block == 0 else nh
    split = lambda w, per: jnp.moveaxis(
        w.reshape(w.shape[0], nh // hb, hb * per), 1, 0)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def heads(acc, w):
        q_w, kv_w, o_w = w
        q = _matmul(c_q, q_w, quant).reshape(b, t, hb, nope + rdim)
        q_pe = _rope(cfg, q[..., nope:], pos[None, :, None])
        kvh = _matmul(c_kv, kv_w, quant).reshape(b, t, hb,
                                                 nope + vdim)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope],
                             kvh[..., :nope], precision=HIGHEST) +
                  jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe,
                             precision=HIGHEST)) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30),
                               axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, kvh[..., nope:],
                       precision=HIGHEST).reshape(b, t, hb * vdim)
        return acc + _matmul(o, o_w, quant), None

    out, _ = jax.lax.scan(
        heads, jnp.zeros_like(x),
        (split(p["q_b"], nope + rdim), split(p["kv_b"], nope + vdim),
         p["o"].reshape(nh // hb, hb * vdim, -1)))
    return out


# -- feed-forward -----------------------------------------------------

def _swiglu(x, gate, up, down, quant):
    return _matmul(jax.nn.silu(_matmul(x, gate, quant)) *
                   _matmul(x, up, quant), down, quant)


def route(cfg: dict, router, x, quant: bool = False):
    """(kept groups (N, topk_group), experts (N, k), weights (N, k))
    of tokens x (N, hidden) over ALL routed experts."""
    scores = jax.nn.softmax(_matmul(x, router, quant), axis=-1)
    n, groups = scores.shape[0], cfg["n_group"]
    best = scores.reshape(n, groups, -1).max(axis=-1)
    _, kept = jax.lax.top_k(best, cfg["topk_group"])
    in_kept = (jnp.arange(groups)[None, :, None] ==
               kept[:, None, :]).any(axis=-1)            # (N, groups)
    masked = jnp.where(jnp.repeat(in_kept, scores.shape[1] // groups,
                                  axis=1), scores, 0.0)
    weights, experts = jax.lax.top_k(masked, cfg["num_experts_per_tok"])
    return kept, experts, weights * cfg["routed_scaling_factor"]


def _moe(cfg: dict, p, x, held, quant: bool):
    """Shared experts plus the chosen experts in ``held`` = (first,
    count): a loop over the held experts, each over every token,
    weighted by the token's weight for it (nought where not
    chosen)."""
    n = x.shape[0]
    first, count = held
    _, experts, weights = route(cfg, p["router"], x, quant)
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
    f32 = lambda name: p[name].astype(jnp.float32)
    return routed + _swiglu(x, f32("shared_gate"), f32("shared_up"),
                            f32("shared_down"), quant)


_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
         "rope_theta", "rope_scaling", "n_group", "topk_group",
         "num_experts_per_tok", "routed_scaling_factor")


@functools.partial(jax.jit, static_argnames=("frozen", "held",
                                             "quant"))
def _layer(x, p, frozen, held, quant):
    cfg = {k: dict(v) if k == "rope_scaling" else v
           for k, v in frozen}
    eps = cfg["rms_norm_eps"]
    experts = {k: v for k, v in p["ffn"].items()
               if k.startswith("experts_")}
    p = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        {**p, "ffn": {k: v for k, v in p["ffn"].items()
                      if k not in experts}})
    h = x + _attention(cfg, p["attn"], _rms(x, p["norm1"], eps), quant)
    y = _rms(h, p["norm2"], eps)
    flat = y.reshape(-1, y.shape[-1])
    if "router" in p["ffn"]:
        out = _moe(cfg, {**p["ffn"], **experts}, flat, held, quant)
    else:
        out = _swiglu(flat, p["ffn"]["gate"], p["ffn"]["up"],
                      p["ffn"]["down"], quant)
    return h + out.reshape(y.shape)


def layer(cfg: dict, x, p, held, quant: bool = False):
    """One block on (B, T, hidden) float32; ``p`` the layer's weights
    as `benchmark/weights_deepseek.py` makes them (a dense or an
    expert layer, by what they hold); ``held`` (first, count)."""
    frozen = tuple(
        (k, tuple(sorted(cfg[k].items())) if k == "rope_scaling"
         else cfg[k]) for k in _KEYS)
    return _layer(x, p, frozen, tuple(held), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, norm_f, lm_head, eps: float, quant: bool = False):
    """Logits of hidden rows (..., hidden): the final norm, then the
    untied head over the held vocabulary."""
    return _matmul(_rms(x, norm_f.astype(jnp.float32), eps),
                   lm_head.astype(jnp.float32), quant)


def hidden(cfg: dict, embeddings: dict, layer_weights, ids, held,
           quant: bool = False):
    """(B, T, hidden) float32 output of the last block (before the
    final norm) for right-padded ``ids`` (B, T); ``layer_weights(i)``
    returns layer i's weights. Causality makes right-padding
    harmless."""
    x = jnp.take(embeddings["tok_embed"].astype(jnp.float32),
                 jnp.asarray(ids, jnp.int32), axis=0)
    for i in range(cfg["n_layer"]):
        x = layer(cfg, x, layer_weights(i), held, quant)
    return x
