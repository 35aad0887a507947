"""dots3-note's weights from ``--seed``, layer by layer, laid out as
`PatternDecoder` holds them and handed to the program and to the
reference alike; `weights_deepseek.py`'s scheme (a layer is one
jitted call whose key is an argument, every routed expert draws from
its OWN key, the configuration's ``init`` group conditions the
embedding and the projections into the residual stream) over this
architecture's leaves: a full layer's attention with its indexer and
head gate, a sliding layer's at the ``swa_*`` widths, the sigmoid
router's selection bias.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key
from benchmark.weights_deepseek import (_RESIDUAL_OUT, _init,
                                        embeddings, experts_held,
                                        experts_total)

__all__ = ["layer", "embeddings", "weights", "experts_held",
           "experts_total", "is_dense", "is_full"]


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["first_k_dense_replace"] or \
        layer % cfg["moe_layer_freq"] != 0


def is_full(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == "full_attention"


def attention_widths(cfg: dict, full: bool) -> dict:
    """The widths of one kind of layer's attention, under the
    full-attention keys' names."""
    pre = "" if full else "swa_"
    return {k: cfg[pre + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")}


def _attn_shapes(cfg: dict, full: bool) -> dict:
    h, w = cfg["hidden_size"], attention_widths(cfg, full)
    nh, qr, kr = (w["num_attention_heads"], w["q_lora_rank"],
                  w["kv_lora_rank"])
    out = {
        "q_a": (h, qr), "q_norm": (qr,),
        "q_b": (qr, nh * (w["qk_nope_head_dim"] +
                          w["qk_rope_head_dim"])),
        "kv_a": (h, kr + w["qk_rope_head_dim"]), "kv_norm": (kr,),
        "kv_b": (kr, nh * (w["qk_nope_head_dim"] + w["v_head_dim"])),
        "o": (nh * w["v_head_dim"], h), "gate": (h, nh)}
    if full:
        hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
        out["index"] = {"q": (qr, hi * di), "k": (h, di),
                        "k_gain": (di,), "k_bias": (di,),
                        "w": (h, hi)}
    return out


def _ffn_shapes(cfg: dict, dense: bool, router_width: int) -> dict:
    h = cfg["hidden_size"]
    if dense:
        m = cfg["intermediate_size"]
        return {"gate": (h, m), "up": (h, m), "down": (m, h)}
    m, ms = cfg["moe_intermediate_size"], \
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return {"router": (h, router_width),
            "router_bias": (router_width,),
            "experts_gate": (h, m), "experts_up": (h, m),
            "experts_down": (m, h),      # one expert's; stacked below
            "shared_gate": (h, ms), "shared_up": (h, ms),
            "shared_down": (ms, h)}


_SHAPE_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "n_shared_experts", "initializer_range", "index_n_heads",
    "index_head_dim") + tuple(
        pre + k for pre in ("", "swa_") for k in (
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))


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
        draw = lambda k: r * jax.random.normal(k, shape, jnp.float32)
        if name.startswith("experts_"):
            w = jax.vmap(lambda e: draw(jax.random.fold_in(key, e)))(
                first + jnp.arange(count))
        else:
            w = draw(key)
        if name == "router_bias":    # chosen with, in float32
            return w
        return (1.0 + w if "norm" in name or name == "k_gain"
                else w).astype(dtype)

    @jax.jit
    def make(key):
        h = cfg["hidden_size"]
        count_ = [0]

        def fill(shapes):
            out = {}
            for name, shape in shapes.items():
                if isinstance(shape, dict):
                    out[name] = fill(shape)
                    continue
                out[name] = leaf(jax.random.fold_in(key, count_[0]),
                                 name, shape)
                count_[0] += 1
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
        ("router_bias_std", _init(cfg, "router_bias_std", 0.0)))
    make = _maker(items, is_full(cfg, index), is_dense(cfg, index),
                  dtype, tuple(experts or experts_held(cfg)))
    return make(jax.random.fold_in(seed_key(seed, 1), index))


def weights(cfg: dict, seed: int, dtype) -> dict:
    """The whole tree as `PatternDecoder` lays it out."""
    return {**embeddings(cfg, seed, dtype),
            "layers": [layer(cfg, seed, i, dtype)
                       for i in range(cfg["n_layer"])]}
