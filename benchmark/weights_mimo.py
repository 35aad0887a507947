"""MiMo-V2-Flash's weights from ``--seed``, layer by layer, laid out
as `PatternDecoder` holds them and handed to the program and to the
reference alike; `weights_deepseek.py`'s scheme (a layer is one
jitted call whose key is an argument, every routed expert draws from
its OWN key, the configuration's ``init`` group conditions the
embedding and the projections into the residual stream) over this
architecture's leaves: grouped-query projections at a full layer's or
a sliding layer's K/V head count, a sliding layer's sink biases, the
sigmoid router's selection bias.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.mimo_v2_flash import \
    widths as attention_widths
from benchmark.weights import seed_key
from benchmark.weights_deepseek import (_RESIDUAL_OUT, _init,
                                        embeddings, experts_held,
                                        experts_total)

__all__ = ["layer", "embeddings", "weights", "experts_held",
           "experts_total", "is_dense", "is_full", "attention_widths"]

# leaves kept in float32 whatever the weights' dtype: chosen with
# (the router's bias) or added to float32 scores (the sink)
_FLOAT32 = ("router_bias", "sink")


def is_dense(cfg: dict, layer: int) -> bool:
    return not cfg["moe_layer_freq"][layer]


def is_full(cfg: dict, layer: int) -> bool:
    return cfg["hybrid_layer_pattern"][layer] == 0


def _attn_shapes(cfg: dict, full: bool) -> dict:
    h, w = cfg["hidden_size"], attention_widths(cfg, full)
    out = {"q": (h, w["heads"] * w["head_dim"]),
           "k": (h, w["kv_heads"] * w["head_dim"]),
           "v": (h, w["kv_heads"] * w["v_head_dim"]),
           "o": (w["heads"] * w["v_head_dim"], h)}
    if w["sink"]:
        out["sink"] = (w["heads"],)
    return out


def _ffn_shapes(cfg: dict, dense: bool, router_width: int) -> dict:
    h = cfg["hidden_size"]
    if dense:
        m = cfg["intermediate_size"]
        return {"gate": (h, m), "up": (h, m), "down": (m, h)}
    m = cfg["moe_intermediate_size"]
    return {"router": (h, router_width),
            "router_bias": (router_width,),
            "experts_gate": (h, m), "experts_up": (h, m),
            "experts_down": (m, h)}      # one expert's; stacked below


_SHAPE_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "initializer_range", "partial_rotary_factor", "rope_theta",
    "swa_rope_theta", "sliding_window",
    "add_full_attention_sink_bias", "add_swa_attention_sink_bias"
) + tuple(pre + k for pre in ("", "swa_") for k in (
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "v_head_dim"))


@functools.lru_cache(maxsize=None)
def _maker(items: tuple, full: bool, dense: bool, dtype,
           experts: tuple):
    """The jitted maker of one kind of layer; its key is an argument,
    so every layer of the kind and every seed run one program."""
    cfg = dict(items)
    first, count = experts

    def leaf(key, name, shape):
        r = cfg["initializer_range"]
        if name in _RESIDUAL_OUT:
            r *= cfg["residual_out_scale"]
        if name == "router_bias":
            r = cfg["router_bias_std"]
        if name == "sink":
            r = cfg["sink_std"]
        draw = lambda k: r * jax.random.normal(k, shape, jnp.float32)
        if name.startswith("experts_"):
            w = jax.vmap(lambda e: draw(jax.random.fold_in(key, e)))(
                first + jnp.arange(count))
        else:
            w = draw(key)
        if name in _FLOAT32:
            return w
        return (1.0 + w if "norm" in name else w).astype(dtype)

    @jax.jit
    def make(key):
        h = cfg["hidden_size"]
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

        return fill({"attn": _attn_shapes(cfg, full),
                     "ffn": _ffn_shapes(cfg, dense,
                                        cfg["router_width"]),
                     "norm1": (h,), "norm2": (h,)})

    return make


def layer(cfg: dict, seed: int, index: int, dtype,
          experts: "tuple[int, int] | None" = None) -> dict:
    """Layer ``index``'s weights: {attn, ffn, norm1, norm2}.
    ``experts`` (first, count) overrides the configuration's share
    (the shares test makes every share, and the whole)."""
    items = tuple((k, cfg[k]) for k in _SHAPE_KEYS) + (
        ("router_width", experts_total(cfg)),
        ("residual_out_scale", _init(cfg, "residual_out_scale", 1.0)),
        ("router_bias_std", _init(cfg, "router_bias_std", 0.0)),
        ("sink_std", _init(cfg, "sink_std", 0.0)))
    make = _maker(items, is_full(cfg, index), is_dense(cfg, index),
                  dtype, tuple(experts or experts_held(cfg)))
    return make(jax.random.fold_in(seed_key(seed, 1), index))


def weights(cfg: dict, seed: int, dtype) -> dict:
    """The whole tree as `PatternDecoder` lays it out."""
    return {**embeddings(cfg, seed, dtype),
            "layers": [layer(cfg, seed, i, dtype)
                       for i in range(cfg["n_layer"])]}
