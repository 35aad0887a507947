"""DeepSeek-V2's weights from ``--seed``, layer by layer, laid out as
`PatternDecoder` holds them and handed to the program and to the
reference alike.

A layer is one jitted call whose key is an argument (``fold_in(
seed_key(seed, 1), layer)``), so the reference can make one layer's
again without holding the model in float32. Every routed expert draws
from its OWN key (``fold_in(leaf key, expert id)``): a chip's share of
the experts holds the very experts the uncut layer has under those
ids. Matrices are normal(0, ``initializer_range``), norm gains 1 +
that; the configuration's ``init`` group may give the token
embedding its own deviation (``embed_std``) and scale every
projection that writes into the residual stream (attention ``o``,
every ``down``) by ``residual_out_scale``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key


def experts_held(cfg: dict) -> "tuple[int, int]":
    """(first, count) of the routed experts this configuration
    holds."""
    first, end = cfg.get("held", {}).get(
        "experts", [0, cfg["n_routed_experts"]])
    return int(first), int(end) - int(first)


def experts_total(cfg: dict) -> int:
    """The router's width: the published count of routed experts."""
    return int(cfg.get("published", cfg)["n_routed_experts"])


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["first_k_dense_replace"] or \
        layer % cfg["moe_layer_freq"] != 0


def _attn_shapes(cfg: dict) -> dict:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "q_a": (h, cfg["q_lora_rank"]),
        "q_norm": (cfg["q_lora_rank"],),
        "q_b": (cfg["q_lora_rank"], nh * (cfg["qk_nope_head_dim"] +
                                          cfg["qk_rope_head_dim"])),
        "kv_a": (h, cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]),
        "kv_norm": (cfg["kv_lora_rank"],),
        "kv_b": (cfg["kv_lora_rank"],
                 nh * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
        "o": (nh * cfg["v_head_dim"], h)}


def _ffn_shapes(cfg: dict, dense: bool) -> dict:
    h = cfg["hidden_size"]
    if dense:
        m = cfg["intermediate_size"]
        return {"gate": (h, m), "up": (h, m), "down": (m, h)}
    m, ms = cfg["moe_intermediate_size"], \
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return {"router": (h, cfg["router_width"]),
            "experts_gate": (h, m), "experts_up": (h, m),
            "experts_down": (m, h),      # one expert's; stacked below
            "shared_gate": (h, ms), "shared_up": (h, ms),
            "shared_down": (ms, h)}


_SHAPE_KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank",
               "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
               "v_head_dim", "intermediate_size",
               "moe_intermediate_size", "n_shared_experts",
               "initializer_range")
# the leaves that write into the residual stream
_RESIDUAL_OUT = ("o", "down", "experts_down", "shared_down")


def _init(cfg: dict, key: str, default: float) -> float:
    return float(cfg.get("init", {}).get(key, default))


@functools.lru_cache(maxsize=None)
def _maker(shape_items: tuple, dense: bool, dtype, experts: tuple):
    """The jitted maker of one kind of layer; its key is an argument,
    so every layer of the kind and every seed run one program."""
    cfg = dict(shape_items)
    first, count = experts

    def leaf(key, name, shape):
        r = cfg["initializer_range"] * (
            cfg["residual_out_scale"] if name in _RESIDUAL_OUT else 1.0)
        draw = lambda k: r * jax.random.normal(k, shape, jnp.float32)
        if name.startswith("experts_"):
            w = jax.vmap(lambda e: draw(jax.random.fold_in(key, e)))(
                first + jnp.arange(count))
        else:
            w = draw(key)
        return (1.0 + w if "norm" in name else w).astype(dtype)

    @jax.jit
    def make(key):
        h = cfg["hidden_size"]
        shapes = [("attn", _attn_shapes(cfg)),
                  ("ffn", _ffn_shapes(cfg, dense)),
                  (None, {"norm1": (h,), "norm2": (h,)})]
        out, i = {}, 0
        for group, leaves in shapes:
            into = out.setdefault(group, {}) if group else out
            for name, shape in leaves.items():
                into[name] = leaf(jax.random.fold_in(key, i), name,
                                  shape)
                i += 1
        return out

    return make


def layer(cfg: dict, seed: int, index: int, dtype,
          experts: "tuple[int, int] | None" = None) -> dict:
    """Layer ``index``'s weights: {attn, ffn, norm1, norm2}.
    ``experts`` (first, count) overrides the configuration's share
    (the shares test makes every share, and the whole)."""
    items = tuple((k, cfg[k]) for k in _SHAPE_KEYS) + (
        ("router_width", experts_total(cfg)),
        ("residual_out_scale", _init(cfg, "residual_out_scale", 1.0)))
    make = _maker(items, is_dense(cfg, index), dtype,
                  tuple(experts or experts_held(cfg)))
    return make(jax.random.fold_in(seed_key(seed, 1), index))


def embeddings(cfg: dict, seed: int, dtype) -> dict:
    """The held rows of the token embedding, the final norm and the
    held columns of the untied head."""
    key = seed_key(seed, 2)
    v, h, r = cfg["vocab_size"], cfg["hidden_size"], \
        cfg["initializer_range"]
    embed = _init(cfg, "embed_std", r) / r

    @jax.jit
    def make(key):
        n = lambda i, shape: r * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        return {"tok_embed": (embed * n(0, (v, h))).astype(dtype),
                "norm_f": (1.0 + n(1, (h,))).astype(dtype),
                "lm_head": n(2, (h, v)).astype(dtype)}

    return make(key)


def weights(cfg: dict, seed: int, dtype) -> dict:
    """The whole tree as `PatternDecoder` lays it out."""
    return {**embeddings(cfg, seed, dtype),
            "layers": [layer(cfg, seed, i, dtype)
                       for i in range(cfg["n_layer"])]}
