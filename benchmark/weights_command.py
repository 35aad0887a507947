"""Command A+'s weights from ``--seed``, layer by layer, laid out as
`PatternDecoder` holds them and handed to the program and to the
reference alike; `weights_deepseek.py`'s scheme (a layer is one
jitted call whose key is an argument, every routed expert draws from
its OWN key, the configuration's ``init`` group conditions the
embedding and the projections into the residual stream) over this
architecture's one kind of layer, a parallel block behind ONE norm:
{norm1, attn: grouped-query ``q``, ``k``, ``v``, ``o``; ffn: the
sigmoid router (no selection bias), the routed experts' ``gate``,
``up`` and ``down`` and the shared experts' as one SwiGLU}. Sliding
and full layers hold the same leaves. The head is tied: the
embeddings are the token embedding and the final norm alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key
from benchmark.weights_deepseek import _init, experts_held, experts_total

__all__ = ["layer", "embeddings", "weights", "experts_held",
           "experts_total"]

# leaves that write into the residual stream
_RESIDUAL_OUT = ("o", "experts_down", "shared_down")


def _shapes(cfg: dict) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, \
        cfg["num_key_value_heads"] * d
    m = cfg["intermediate_size"]
    ms = cfg["num_shared_experts"] * m
    e = cfg["router_width"]
    return {"norm1": (h,),
            "attn": {"q": (h, q), "k": (h, kv), "v": (h, kv),
                     "o": (q, h)},
            "ffn": {"router": (h, e),
                    "experts_gate": (h, m), "experts_up": (h, m),
                    "experts_down": (m, h),      # one expert's
                    "shared_gate": (h, ms), "shared_up": (h, ms),
                    "shared_down": (ms, h)}}


_SHAPE_KEYS = ("hidden_size", "head_dim", "num_attention_heads",
               "num_key_value_heads", "intermediate_size",
               "num_shared_experts", "initializer_range")


@functools.lru_cache(maxsize=None)
def _maker(items: tuple, dtype, experts: tuple):
    """The jitted maker of a layer; its key is an argument, so every
    layer and every seed run one program."""
    cfg = dict(items)
    first, count = experts

    def leaf(key, name, shape):
        r = cfg["initializer_range"]
        if name in _RESIDUAL_OUT:
            r *= cfg["residual_out_scale"]
        draw = lambda k: r * jax.random.normal(k, shape, jnp.float32)
        if name.startswith("experts_"):
            w = jax.vmap(lambda e: draw(jax.random.fold_in(key, e)))(
                first + jnp.arange(count))
        else:
            w = draw(key)
        return (1.0 + w if "norm" in name else w).astype(dtype)

    @jax.jit
    def make(key):
        n = [0]

        def fill(shapes):
            out = {}
            for name, shape in shapes.items():
                if isinstance(shape, dict):
                    out[name] = fill(shape)
                    continue
                out[name] = leaf(jax.random.fold_in(key, n[0]), name,
                                 shape)
                n[0] += 1
            return out

        return fill(_shapes(cfg))

    return make


def layer(cfg: dict, seed: int, index: int, dtype,
          experts: "tuple[int, int] | None" = None) -> dict:
    """Layer ``index``'s weights. ``experts`` (first, count)
    overrides the configuration's share (the shares test makes every
    share, and the whole)."""
    items = tuple((k, cfg[k]) for k in _SHAPE_KEYS) + (
        ("router_width", experts_total(cfg)),
        ("residual_out_scale", _init(cfg, "residual_out_scale", 1.0)))
    make = _maker(items, dtype, tuple(experts or experts_held(cfg)))
    return make(jax.random.fold_in(seed_key(seed, 1), index))


def embeddings(cfg: dict, seed: int, dtype) -> dict:
    """The held rows of the tied token embedding (normal of
    ``init.embed_std``: the head reads them too) and the final norm's
    gain."""
    v, h, r = cfg["vocab_size"], cfg["hidden_size"], \
        cfg["initializer_range"]
    std = _init(cfg, "embed_std", r)

    @jax.jit
    def make(key):
        n = lambda i, shape: jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        return {"tok_embed": (std * n(0, (v, h))).astype(dtype),
                "norm_f": (1.0 + r * n(1, (h,))).astype(dtype)}

    return make(seed_key(seed, 2))


def weights(cfg: dict, seed: int, dtype) -> dict:
    """The whole tree as `PatternDecoder` lays it out."""
    return {**embeddings(cfg, seed, dtype),
            "layers": [layer(cfg, seed, i, dtype)
                       for i in range(cfg["n_layer"])]}
