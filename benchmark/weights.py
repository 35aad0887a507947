"""Weights from ``--seed``, made by the benchmark and handed to the
program and to the reference alike.

Each model's weights come out of one jitted call on the device, in
the type they are served in. The transformer's are generated block by
block from ``fold_in(key, block)``, so the reference can make one
block's again without ever holding the whole model in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int):
    """A key for one of the run's streams (weights, data, ...).
    ``--seed`` may need more than 32 bits: both halves are used."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.fold_in(key, stream)


# -- ResNet: a flat {layer: {leaf: array}} tree, filled by leaf name --

def _resnet_leaf(key, path: tuple, shape: tuple, init: dict):
    leaf = path[-1]
    n = lambda: jax.random.normal(key, shape, jnp.float32)
    if leaf == "kernel" and len(shape) == 4:      # He normal, fan-in
        return n() * math.sqrt(2.0 / (shape[0] * shape[1] * shape[2]))
    if leaf == "kernel":                          # the classifier
        return n() * init["fc_std"]
    if leaf == "gamma":
        # a residual branch's last BatchNorm starts small (Goyal et
        # al. 2017 start it at nought): the sum of sixteen branches
        # then keeps the backward pass well conditioned, so that
        # rounding shows as rounding in the comparison
        scale = init["last_bn_gamma"] if path[0].endswith("_c3_bn") \
            else 1.0
        return scale * (1.0 + 0.1 * n())
    if leaf in ("beta", "bias"):
        return 0.1 * n()
    if leaf == "moving_mean":
        return jnp.zeros(shape, jnp.float32)
    if leaf == "moving_var":
        return jnp.ones(shape, jnp.float32)
    raise ValueError(f"no rule for weight leaf {'/'.join(path)}")


def resnet_weights(shapes: dict, seed: int, init: dict) -> dict:
    """``shapes``: {(layer, ..., leaf): shape}; ``init``: the
    configuration's ``init`` group. Returns the same keys with
    float32 arrays; leaf i draws from ``fold_in(key, i)`` in
    sorted-path order, so the values do not depend on dict order."""
    paths = sorted(shapes)

    @jax.jit
    def make(key):
        return [_resnet_leaf(jax.random.fold_in(key, i), p, shapes[p],
                             init)
                for i, p in enumerate(paths)]

    return dict(zip(paths, make(seed_key(seed, 1))))


# -- TransformerLayer: stacked blocks, made block by block ------------

def _block_shapes(cfg: dict) -> dict:
    h, m = cfg["n_embd"], cfg["n_inner"]
    return {"qkv_kernel": (h, 3 * h), "qkv_bias": (3 * h,),
            "attn_out_kernel": (h, h), "attn_out_bias": (h,),
            "ln1_g": (h,), "ln1_b": (h,),
            "mlp_in_kernel": (h, m), "mlp_in_bias": (m,),
            "mlp_out_kernel": (m, h), "mlp_out_bias": (h,),
            "ln2_g": (h,), "ln2_b": (h,)}


def transformer_block(cfg: dict, key, dtype):
    """One block's weights from its key: normal(0, r) everywhere
    (r the configuration's ``initializer_range``), LayerNorm gains
    around 1."""
    out = {}
    r = cfg["initializer_range"]
    for i, (name, shape) in enumerate(_block_shapes(cfg).items()):
        w = r * jax.random.normal(jax.random.fold_in(key, i),
                                  shape, jnp.float32)
        if name.endswith("_g"):
            w = 1.0 + w
        out[name] = w.astype(dtype)
    return out


def transformer_embeddings(cfg: dict, key, dtype) -> dict:
    """``key``: ``seed_key(seed, 2)``."""
    h = cfg["n_embd"]
    r = cfg["initializer_range"]
    tok = r * jax.random.normal(jax.random.fold_in(key, 0),
                                (cfg["vocab_size"], h), jnp.float32)
    pos = r * jax.random.normal(jax.random.fold_in(key, 1),
                                (cfg["n_positions"], h), jnp.float32)
    return {"tok_embed": tok.astype(dtype),
            "pos_embed": pos.astype(dtype)}


def transformer_weights(cfg: dict, seed: int, dtype) -> dict:
    """The whole tree as TransformerLayer lays it out (blocks stacked
    on a leading axis), in ``dtype``, in one jitted call. The keys
    are arguments, so every seed runs the same compiled program;
    block ``b`` draws from ``fold_in(seed_key(seed, 1), b)``."""
    @jax.jit
    def make(block_key, embed_key):
        keys = jax.vmap(lambda b: jax.random.fold_in(block_key, b))(
            jnp.arange(cfg["n_layer"]))
        blocks = jax.vmap(
            lambda k: transformer_block(cfg, k, dtype))(keys)
        return {**transformer_embeddings(cfg, embed_key, dtype),
                "blocks": blocks}

    return make(seed_key(seed, 1), seed_key(seed, 2))
