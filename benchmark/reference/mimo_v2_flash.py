"""Plain float32 forward of a MiMo-V2-Flash decoder (the language
model of ``model_type`` ``mimo_v2_flash``; the configuration's
``assumed`` lists every reading that is an inference): pre-norm
RMSNorm blocks of grouped-query attention, 64 query heads of 192 over
4 (a *full* layer) or 8 (a *sliding* layer) K/V heads whose values
are 128 wide and scaled by ``attention_value_scale``, query head j
reading K/V head ``j // (heads / kv_heads)``; a rotary on the first
``floor(partial_rotary_factor * head_dim)`` values of every query and
key head (rotate-half), each kind of layer with its own base; a full
layer's query sees every key before it, a sliding layer's the last
``sliding_window`` positions, its own among them, with one learned
logit a head in the softmax's denominator that carries no value (the
sink). A dense SwiGLU where ``moe_layer_freq[i]`` is 0, else experts
routed by sigmoid scores with a selection bias that chooses and does
not weigh, weights renormalised over the chosen; a final norm and an
untied head. One dense pass over prompt plus served tokens, every
product at ``highest`` precision, no cache, no batching of requests
in flight; imports nothing of the program.

The attention is the full O(T^2) one, a K/V head and ``q_block``
queries at a time so that 17k tokens fit the chip; a sliding layer's
query block is multiplied with the keys it can see (its own positions
and the window before them), not with all of them.

``held`` = (first, count) of the routed experts, as in
`reference/deepseek_v2.py`. ``quant`` puts the same pass in the next
precision down: every matrix product's operands, and the keys and
values as a cache would hold them, rounded to float8 e4m3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.transformer import (HIGHEST, _f8, _matmul,
                                             gaps_of)

__all__ = ["hidden", "head", "gaps_of", "route", "layer",
           "attention", "widths"]


def _rope(theta: float, x, positions):
    """Rotate-half rotary embedding of x (..., dim) at ``positions``
    (broadcast against x's leading axes); plain RoPE."""
    dim = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                          / dim)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    half = dim // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def widths(cfg: dict, full: bool) -> dict:
    """One kind of layer's attention, from the published keys:
    ``heads``, ``kv_heads``, ``head_dim``, ``v_head_dim``, ``theta``,
    ``window`` (0: none), ``sink`` and the ``rotary`` dims of a
    head."""
    pre = "" if full else "swa_"
    d = cfg[pre + "head_dim"]
    return {"heads": cfg[pre + "num_attention_heads"],
            "kv_heads": cfg[pre + "num_key_value_heads"],
            "head_dim": d, "v_head_dim": cfg[pre + "v_head_dim"],
            "theta": cfg["rope_theta" if full else "swa_rope_theta"],
            "window": 0 if full else cfg["sliding_window"],
            "sink": bool(cfg["add_full_attention_sink_bias" if full
                             else "add_swa_attention_sink_bias"]),
            "rotary": int(cfg["partial_rotary_factor"] * d) // 2 * 2}


# -- attention --------------------------------------------------------

def attention(cfg: dict, p, x, full: bool, quant: bool = False,
              q_block: int = 512):
    """One sequence ``x`` (T, hidden) through one layer's attention,
    the output projection included."""
    t = x.shape[0]
    w = widths(cfg, full)
    nh, g, dk, dv = (w["heads"], w["kv_heads"], w["head_dim"],
                     w["v_head_dim"])
    r, rd, theta = nh // g, w["rotary"], w["theta"]
    pos = jnp.arange(t)
    part = lambda a, at: jnp.concatenate(
        [_rope(theta, a[..., :rd], at), a[..., rd:]], axis=-1)
    q = part(_matmul(x, p["q"], quant).reshape(t, g, r, dk),
             pos[:, None, None])
    k = part(_matmul(x, p["k"], quant).reshape(t, g, dk),
             pos[:, None])
    v = cfg["attention_value_scale"] * \
        _matmul(x, p["v"], quant).reshape(t, g, dv)
    if quant:                       # as a cache would hold them
        k, v = _f8(k, -1), _f8(v, -1)
    qb = q_block if t % q_block == 0 else t
    # a sliding query block's keys: the block's own positions and the
    # window - 1 before them, cut out of the keys (padded in front by
    # as many rows, which the mask leaves out)
    back = w["window"] - 1 if w["window"] else 0
    front = lambda a: jnp.pad(a, [(back, 0)] + [(0, 0)] * (a.ndim - 1))
    sinks = p["sink"].reshape(g, r) if w["sink"] \
        else jnp.zeros((g, r), jnp.float32)

    def group(args):
        qg, kg, vg, sg = args       # (T, R, dk) (T+back, dk) (.., dv)

        def block(qa):
            qs, q0 = qa                            # (qb, R, dk)
            at = q0 + jnp.arange(qb)
            if w["window"]:
                kb = jax.lax.dynamic_slice_in_dim(kg, q0, qb + back)
                vb = jax.lax.dynamic_slice_in_dim(vg, q0, qb + back)
                k_at = q0 - back + jnp.arange(qb + back)
                mask = jnp.logical_and(
                    jnp.logical_and(k_at[None] >= 0,
                                    k_at[None] <= at[:, None]),
                    at[:, None] - k_at[None] < w["window"])
            else:
                kb, vb = kg, vg
                mask = pos[None, :] <= at[:, None]
            scores = jnp.einsum("qrd,kd->rqk", qs, kb,
                                precision=HIGHEST) * dk ** -0.5
            scores = jnp.where(mask[None], scores, -1e30)
            m = jnp.max(scores, axis=-1, keepdims=True)
            if w["sink"]:
                m = jnp.maximum(m, sg[:, None, None])
            e = jnp.exp(scores - m)
            denom = e.sum(axis=-1, keepdims=True)
            if w["sink"]:
                denom = denom + jnp.exp(sg[:, None, None] - m)
            return jnp.einsum("rqk,kd->qrd", e / denom, vb,
                              precision=HIGHEST)

        return jax.lax.map(block, (
            qg.reshape(t // qb, qb, r, dk),
            jnp.arange(0, t, qb))).reshape(t, r, dv)

    o = jax.lax.map(group, (jnp.moveaxis(q, 1, 0),
                            jnp.moveaxis(front(k), 1, 0),
                            jnp.moveaxis(front(v), 1, 0), sinks))
    # (G, T, R, dv) -> (T, G * R * dv), query head j = g * R + r
    o = jnp.moveaxis(o, 0, 1).reshape(t, nh * dv)
    return _matmul(o, p["o"], quant)


# -- feed-forward -----------------------------------------------------

def _swiglu(x, gate, up, down, quant):
    return _matmul(jax.nn.silu(_matmul(x, gate, quant)) *
                   _matmul(x, up, quant), down, quant)


def route(cfg: dict, router, bias, x, quant: bool = False):
    """(experts (N, k), weights (N, k)) of tokens x (N, hidden) over
    ALL routed experts: the k of largest ``sigmoid + bias``, weighted
    by their sigmoid scores over the chosen ones' sum."""
    scores = jax.nn.sigmoid(_matmul(x, router, quant))
    _, experts = jax.lax.top_k(scores + bias,
                               cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, experts, axis=1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (weights.sum(axis=-1, keepdims=True)
                             + 1e-20)
    return experts, weights * (cfg.get("routed_scaling_factor") or 1.0)


def moe(cfg: dict, p, x, held, quant: bool = False):
    """The chosen experts in ``held`` = (first, count): a loop over
    the held experts, each over every token, weighted by the token's
    weight for it (nought where not chosen). No shared expert."""
    n = x.shape[0]
    first, count = held
    experts, weights = route(cfg, p["router"], p["router_bias"], x,
                             quant)
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
    return routed


_KEYS = ("hidden_size", "layernorm_epsilon", "attention_value_scale",
         "partial_rotary_factor", "rope_theta", "swa_rope_theta",
         "sliding_window", "add_full_attention_sink_bias",
         "add_swa_attention_sink_bias", "num_experts_per_tok",
         "routed_scaling_factor", "norm_topk_prob") + tuple(
    pre + k for pre in ("", "swa_") for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "v_head_dim"))


@functools.partial(jax.jit, static_argnames=(
    "frozen", "full", "held", "quant", "q_block"))
def _layer(x, p, frozen, full, held, quant, q_block):
    cfg = dict(frozen)
    eps = cfg["layernorm_epsilon"]
    experts = {k: v for k, v in p["ffn"].items()
               if k.startswith("experts_")}
    p = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        {**p, "ffn": {k: v for k, v in p["ffn"].items()
                      if k not in experts}})
    y = _rms(x, p["norm1"], eps)
    h = x + jax.lax.map(
        lambda row: attention(cfg, p["attn"], row, full, quant,
                              q_block), y)
    y = _rms(h, p["norm2"], eps)
    flat = y.reshape(-1, y.shape[-1])
    if "router" in p["ffn"]:
        out = moe(cfg, {**p["ffn"], **experts}, flat, held, quant)
    else:
        out = _swiglu(flat, p["ffn"]["gate"], p["ffn"]["up"],
                      p["ffn"]["down"], quant)
    return h + out.reshape(y.shape)


def layer(cfg: dict, x, p, full: bool, held, quant: bool = False,
          q_block: int = 512):
    """One block on (B, T, hidden) float32; ``p`` the layer's weights
    as `benchmark/weights_mimo.py` makes them (a dense or an expert
    layer by what they hold, ``full`` says which attention);
    ``held`` (first, count)."""
    frozen = tuple((k, cfg.get(k)) for k in _KEYS)
    return _layer(x, p, frozen, bool(full), tuple(held), quant,
                  q_block)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, norm_f, lm_head, eps: float, quant: bool = False):
    """Logits of hidden rows (..., hidden): the final norm, then the
    untied head over the held vocabulary."""
    return _matmul(_rms(x, norm_f.astype(jnp.float32), eps),
                   lm_head.astype(jnp.float32), quant)


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
                  cfg["hybrid_layer_pattern"][i] == 0, held, quant,
                  q_block)
    return x
