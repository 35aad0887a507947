"""Plain float32 forward of TransformerLayer's decoder stack (learned
positions, post-LayerNorm blocks, tanh GELU, causal attention, logits
tied to the token embedding): the reference the generate cells' served
tokens are held to. One dense pass over prompt plus served tokens,
every product at ``highest`` precision, no cache, no batching of
requests in flight; imports nothing of the program.

The weights are those ``benchmark/weights.py`` makes from the seed,
taken block by block, so the whole model is never held in float32.

``quant`` puts the same pass in the next precision down: every matrix
product's operands (activations per row, weights per output column,
and K and V as a cache would hold them) are rounded to float8 e4m3
at a scale of their largest magnitude, and accumulated in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8, F8_MAX = jnp.float8_e4m3fn, 448.0


def _f8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _matmul(x, w, quant):
    if quant:
        x, w = _f8(x, -1), _f8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "quant"))
def block(x, p, n_head: int, eps: float, quant: bool):
    """One post-LayerNorm block on (B, T, H) float32."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    b, t, h = x.shape
    hd = h // n_head
    qkv = _matmul(x, p["qkv_kernel"], quant) + p["qkv_bias"]
    q, k, v = (a.reshape(b, t, n_head, hd)
               for a in jnp.split(qkv, 3, axis=-1))
    if quant:
        k, v = _f8(k, -1), _f8(v, -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=HIGHEST) / (hd ** 0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                      precision=HIGHEST).reshape(b, t, h)
    attn = _matmul(attn, p["attn_out_kernel"], quant) + \
        p["attn_out_bias"]
    x = _layer_norm(x + attn, p["ln1_g"], p["ln1_b"], eps)
    mlp = jax.nn.gelu(_matmul(x, p["mlp_in_kernel"], quant) +
                      p["mlp_in_bias"], approximate=True)
    mlp = _matmul(mlp, p["mlp_out_kernel"], quant) + p["mlp_out_bias"]
    return _layer_norm(x + mlp, p["ln2_g"], p["ln2_b"], eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def head(x, tok_embed, quant: bool = False):
    """Logits of hidden rows (..., H) over the tied vocabulary."""
    return _matmul(x, tok_embed.astype(jnp.float32).T, quant)


def hidden(cfg: dict, embeddings: dict, block_weights, ids,
           quant: bool = False):
    """(B, T, H) float32 output of the last block for right-padded
    ``ids`` (B, T); ``block_weights(i)`` returns block i's weights.
    Causality makes right-padding harmless."""
    ids = jnp.asarray(ids, jnp.int32)
    tok = embeddings["tok_embed"].astype(jnp.float32)
    pos = embeddings["pos_embed"].astype(jnp.float32)
    x = jnp.take(tok, ids, axis=0) + pos[None, :ids.shape[1]]
    for i in range(cfg["n_layer"]):
        x = block(x, block_weights(i), cfg["n_head"],
                  cfg["layer_norm_epsilon"], quant)
    return x


@jax.jit
def gaps_of(rows, tokens):
    """How far below the row's best logit each token's logit lies."""
    chosen = jnp.take_along_axis(rows, tokens[..., None], -1)[..., 0]
    return jnp.max(rows, axis=-1) - chosen
