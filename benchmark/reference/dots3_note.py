"""Plain float32 forward of a dots3-note decoder (the language model
of ``model_type`` ``dots3_note``; the configuration's ``assumed``
lists every reading that is an inference): pre-norm RMSNorm blocks;
*full* layers of multi-head latent attention in its expanded form
whose query t attends to the ``index_topk`` causal keys of largest
index score ``I[t, s] = sum_h w[t, h] ReLU(q_I[t, h] . k_I[s])``
(DeepSeek-V3.2-Exp's indexer), *sliding* layers of latent attention
at the ``swa_*`` widths over the last ``sliding_window_size``
positions, both with rescaled latents and a head-wise sigmoid gate on
the attention output; a dense SwiGLU first layer, then expert layers
routed by sigmoid scores with a selection bias that chooses and does
not weigh, weights renormalised over the chosen; a final norm and an
untied head. One dense pass over prompt plus served tokens, every
product at ``highest`` precision, no cache, no batching of requests
in flight; imports nothing of the program.

The index scores and the attention are the full O(T^2) ones, computed
``q_block`` queries at a time so that 32k tokens fit the chip; the
chosen set is `jax.lax.top_k`'s over the whole causal row. A sliding
layer's query block is multiplied with the keys it can see (its own
positions and the window before them), not with all of them.

``held`` = (first, count) of the routed experts, as in
`reference/deepseek_v2.py`. ``quant`` puts the same pass in the next
precision down: every matrix product's operands, and the cached rows
(latent, rotary key, index key), rounded to float8 e4m3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.transformer import (HIGHEST, _f8,
                                             _layer_norm, _matmul,
                                             gaps_of)

__all__ = ["hidden", "head", "gaps_of", "route", "layer",
           "index_choice"]

INDEX_NORM_EPS = 1e-6


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


def _widths(cfg: dict, full: bool) -> dict:
    pre = "" if full else "swa_"
    return {k: cfg[pre + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rope_theta")}


def _q_blocks(t: int, q_block: int) -> int:
    return q_block if t % q_block == 0 else t


# -- attention --------------------------------------------------------

def index_choice(cfg: dict, p, x, c_q, quant: bool, q_block: int):
    """(T, T) bool: the keys each query of one sequence ``x`` (T,
    hidden) keeps: its ``index_topk`` causal keys of largest index
    score, all of them while there are no more."""
    t = x.shape[0]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    rd, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    pos = jnp.arange(t)
    part = lambda v, at: jnp.concatenate(
        [_rope(theta, v[..., :rd], at), v[..., rd:]], axis=-1)
    q = part(_matmul(c_q, p["q"], quant).reshape(t, hi, di),
             pos[:, None])
    k = part(_layer_norm(_matmul(x, p["k"], quant), p["k_gain"],
                         p["k_bias"], INDEX_NORM_EPS), pos)
    w = _matmul(x, p["w"], quant) * (hi ** -0.5 * di ** -0.5)
    if quant:
        q, k = _f8(q, -1), _f8(k, -1)
    keep = min(cfg["index_topk"], t)
    qb = _q_blocks(t, q_block)

    hb = 8 if hi % 8 == 0 else hi

    def block(args):
        qs, ws, at = args                     # (qb, hi, di) (qb, hi)

        def heads(acc, qw):                   # hb heads at a time
            dots = jnp.einsum("qhd,kd->qhk", qw[0], k,
                              precision=HIGHEST)
            return acc + jnp.einsum("qhk,qh->qk", jax.nn.relu(dots),
                                    qw[1], precision=HIGHEST), None

        score, _ = jax.lax.scan(
            heads, jnp.zeros((qb, t), jnp.float32),
            (jnp.moveaxis(qs.reshape(qb, hi // hb, hb, di), 1, 0),
             jnp.moveaxis(ws.reshape(qb, hi // hb, hb), 1, 0)))
        causal = pos[None, :] <= at[:, None]
        _, chosen = jax.lax.top_k(
            jnp.where(causal, score, -jnp.inf), keep)
        mark = jnp.zeros((qb, t), bool).at[
            jnp.arange(qb)[:, None], chosen].set(True)
        return jnp.logical_and(mark, causal)

    return jax.lax.map(block, (q.reshape(t // qb, qb, hi, di),
                               w.reshape(t // qb, qb, hi),
                               pos.reshape(t // qb, qb))).reshape(t, t)


def _attention(cfg: dict, p, x, full: bool, quant: bool,
               q_block: int, head_block: int = 16):
    """One sequence (T, hidden)."""
    t, h = x.shape
    w = _widths(cfg, full)
    nh, rank = w["num_attention_heads"], w["kv_lora_rank"]
    nope, rdim, vdim = (w["qk_nope_head_dim"], w["qk_rope_head_dim"],
                        w["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], w["rope_theta"]
    scale = (nope + rdim) ** -0.5
    rescale = bool(cfg.get("apply_mla_qkv_lora_rescale"))
    pos = jnp.arange(t)
    c_q = _rms(_matmul(x, p["q_a"], quant), p["q_norm"], eps)
    kv = _matmul(x, p["kv_a"], quant)
    c_kv = _rms(kv[..., :rank], p["kv_norm"], eps)
    if rescale:
        c_q = c_q * (h / w["q_lora_rank"]) ** 0.5
        c_kv = c_kv * (h / rank) ** 0.5
    k_pe = _rope(theta, kv[..., rank:], pos)
    if quant:                       # the row as a cache would hold it
        row = _f8(jnp.concatenate([c_kv, k_pe], axis=-1), -1)
        c_kv, k_pe = row[..., :rank], row[..., rank:]
    hb = head_block if nh % head_block == 0 else nh
    qb = _q_blocks(t, q_block)
    gate = jax.nn.sigmoid(_matmul(x, p["gate"], quant))   # (T, nh)
    split = lambda m, per: jnp.moveaxis(
        m.reshape(m.shape[0], nh // hb, hb * per), 1, 0)
    if full:
        mask = index_choice(cfg, p["index"], x, c_q, quant, q_block)
        back = 0
    else:
        # a query block's keys: the block's own positions and the
        # window - 1 before them, cut out of the keys (padded in
        # front by as many rows, which the mask leaves out)
        back = cfg["sliding_window_size"] - 1
        k_at = (pos[:, None] // qb * qb - back +
                jnp.arange(qb + back)[None, :])           # (T, qb+back)
        mask = jnp.logical_and(
            jnp.logical_and(k_at >= 0, k_at <= pos[:, None]),
            pos[:, None] - k_at <= back)
    front = lambda a: jnp.pad(a, [(back, 0)] + [(0, 0)] * (a.ndim - 1))
    k_pe_all = front(k_pe)

    def heads(acc, ws):
        q_w, kv_w, o_w, g = ws
        q = _matmul(c_q, q_w, quant).reshape(t, hb, nope + rdim)
        q_pe = _rope(theta, q[..., nope:], pos[:, None])
        kvh = front(_matmul(c_kv, kv_w, quant).reshape(
            t, hb, nope + vdim))

        def block(args):
            qn, qp, m, q0 = args
            if full:
                kv_blk, pe_blk = kvh, k_pe_all
            else:
                kv_blk = jax.lax.dynamic_slice_in_dim(
                    kvh, q0, qb + back)
                pe_blk = jax.lax.dynamic_slice_in_dim(
                    k_pe_all, q0, qb + back)
            scores = (jnp.einsum("qhd,khd->hqk", qn,
                                 kv_blk[..., :nope],
                                 precision=HIGHEST) +
                      jnp.einsum("qhd,kd->hqk", qp, pe_blk,
                                 precision=HIGHEST)) * scale
            probs = jax.nn.softmax(
                jnp.where(m[None], scores, -1e30), axis=-1)
            return jnp.einsum("hqk,khd->qhd", probs,
                              kv_blk[..., nope:], precision=HIGHEST)

        o = jax.lax.map(block, (
            q[..., :nope].reshape(t // qb, qb, hb, nope),
            q_pe.reshape(t // qb, qb, hb, rdim),
            mask.reshape(t // qb, qb, -1),
            jnp.arange(0, t, qb))).reshape(t, hb, vdim)
        o = (o * g[..., None]).reshape(t, hb * vdim)
        return acc + _matmul(o, o_w, quant), None

    out, _ = jax.lax.scan(
        heads, jnp.zeros_like(x),
        (split(p["q_b"], nope + rdim), split(p["kv_b"], nope + vdim),
         p["o"].reshape(nh // hb, hb * vdim, -1),
         jnp.moveaxis(gate.reshape(t, nh // hb, hb), 1, 0)))
    return out


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
    return experts, weights * cfg["routed_scaling_factor"]


def _moe(cfg: dict, p, x, held, quant: bool):
    """The shared expert plus the chosen experts in ``held`` =
    (first, count): a loop over the held experts, each over every
    token, weighted by the token's weight for it (nought where not
    chosen)."""
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
    f32 = lambda name: p[name].astype(jnp.float32)
    return routed + _swiglu(x, f32("shared_gate"), f32("shared_up"),
                            f32("shared_down"), quant)


_KEYS = ("hidden_size", "rms_norm_eps", "index_n_heads",
         "index_head_dim", "index_topk", "sliding_window_size",
         "apply_mla_qkv_lora_rescale", "num_experts_per_tok",
         "routed_scaling_factor", "norm_topk_prob") + tuple(
    pre + k for pre in ("", "swa_") for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rope_theta"))


@functools.partial(jax.jit, static_argnames=(
    "frozen", "full", "held", "quant", "q_block"))
def _layer(x, p, frozen, full, held, quant, q_block):
    cfg = dict(frozen)
    eps = cfg["rms_norm_eps"]
    experts = {k: v for k, v in p["ffn"].items()
               if k.startswith("experts_")}
    p = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        {**p, "ffn": {k: v for k, v in p["ffn"].items()
                      if k not in experts}})
    y = _rms(x, p["norm1"], eps)
    h = x + jax.lax.map(
        lambda row: _attention(cfg, p["attn"], row, full, quant,
                               q_block), y)
    y = _rms(h, p["norm2"], eps)
    flat = y.reshape(-1, y.shape[-1])
    if "router" in p["ffn"]:
        out = _moe(cfg, {**p["ffn"], **experts}, flat, held, quant)
    else:
        out = _swiglu(flat, p["ffn"]["gate"], p["ffn"]["up"],
                      p["ffn"]["down"], quant)
    return h + out.reshape(y.shape)


def layer(cfg: dict, x, p, full: bool, held, quant: bool = False,
          q_block: int = 512):
    """One block on (B, T, hidden) float32; ``p`` the layer's weights
    as `benchmark/weights_dots3.py` makes them (a dense or an expert
    layer by what they hold, ``full`` says which attention);
    ``held`` (first, count)."""
    frozen = tuple((k, cfg[k]) for k in _KEYS)
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
                  cfg["layer_types"][i] == "full_attention", held,
                  quant, q_block)
    return x
