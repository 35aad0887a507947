"""Operations and bytes a dots3-note step has to do, from the
configuration's shapes alone (`flops.py`'s rules: nothing here looks
at the program, a multiply-add is two operations).

The configuration holds one chip's share of a deployment, as
`flops_deepseek.py` reads it: ``n_layer`` layers in the published
``layer_types`` pattern, ``n_routed_experts`` of the
``published.n_routed_experts`` the router scores, ``vocab_size`` rows
of the vocabulary. Parameter counts are of what is held.

What the selection forces is counted, not what a dense pass would do:
a query in a full layer scores EVERY visible key with the indexer
(one index key of ``index_head_dim`` a token) and attends to the
``index_topk`` it keeps; a query in a sliding layer attends to its
window.
"""

from __future__ import annotations

import numpy as np

from benchmark.flops_deepseek import _experts_total, experts_touched

__all__ = ["params", "param_bytes", "span_flops", "token_flops",
           "cache_row_bytes", "decode_step_min_bytes",
           "experts_touched"]


def _widths(cfg: dict, full: bool) -> tuple:
    pre = "" if full else "swa_"
    return tuple(cfg[pre + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))


def _kinds(cfg: dict) -> "tuple[int, int]":
    """(full layers, sliding layers) among the ``n_layer`` held."""
    full = sum(t == "full_attention"
               for t in cfg["layer_types"][:cfg["n_layer"]])
    return full, cfg["n_layer"] - full


def _dense_layers(cfg: dict) -> int:
    return sum(1 for i in range(cfg["n_layer"])
               if i < cfg["first_k_dense_replace"]
               or i % cfg["moe_layer_freq"])


def _attention(cfg: dict, full: bool) -> int:
    h = cfg["hidden_size"]
    nh, qr, kr, nope, rope, v = _widths(cfg, full)
    n = h * qr + qr + qr * nh * (nope + rope) + h * (kr + rope) + kr \
        + kr * nh * (nope + v) + nh * v * h + h * nh
    if full:
        hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
        n += qr * hi * di + h * di + 2 * di + h * hi
    return n


def params(cfg: dict) -> dict:
    """Parameter counts: ``full_attention`` and ``sliding_attention``
    (one layer's projections, latent norms, head gate and, in a full
    layer, indexer), ``dense_mlp``, ``shared``, ``router`` (with its
    selection bias), ``expert`` (one routed expert), ``norms`` (a
    layer's two), ``embed``, ``head`` (with the final norm) and
    ``total``."""
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    e = _experts_total(cfg)
    out = {"full_attention": _attention(cfg, True),
           "sliding_attention": _attention(cfg, False),
           "dense_mlp": 3 * h * cfg["intermediate_size"],
           "shared": 3 * h * m * cfg["n_shared_experts"],
           "router": h * e + e, "expert": 3 * h * m, "norms": 2 * h,
           "embed": cfg["vocab_size"] * h,
           "head": h + h * cfg["vocab_size"]}
    full, sliding = _kinds(cfg)
    dense = _dense_layers(cfg)
    out["total"] = full * out["full_attention"] + \
        sliding * out["sliding_attention"] + \
        cfg["n_layer"] * out["norms"] + dense * out["dense_mlp"] + \
        (cfg["n_layer"] - dense) * (
            out["shared"] + out["router"] +
            cfg["n_routed_experts"] * out["expert"]) + \
        out["embed"] + out["head"]
    return out


def param_bytes(cfg: dict, weight_bytes: int) -> int:
    """Bytes of the held tree: every parameter at ``weight_bytes``
    but the routers' selection biases, which stay float32."""
    moe = cfg["n_layer"] - _dense_layers(cfg)
    return params(cfg)["total"] * weight_bytes + \
        moe * _experts_total(cfg) * (4 - weight_bytes)


def _active(cfg: dict) -> float:
    """Weights a token multiplies in the layers held, the head
    aside: attention, the dense MLP, the shared expert, the router
    and the token's expected share of its routed experts held
    here."""
    p = params(cfg)
    full, sliding = _kinds(cfg)
    dense = _dense_layers(cfg)
    held_share = cfg["n_routed_experts"] / _experts_total(cfg)
    return full * p["full_attention"] + \
        sliding * p["sliding_attention"] + dense * p["dense_mlp"] + \
        (cfg["n_layer"] - dense) * (
            p["shared"] + p["router"] +
            cfg["num_experts_per_tok"] * held_share * p["expert"])


def span_flops(cfg: dict, start: float, n: float, logit_rows: float
               ) -> float:
    """FLOPs to push ``n`` consecutive tokens of one sequence, the
    first at position ``start``, through the layers held: 2 a weight
    multiplied; in a full layer the indexer's score of every visible
    key (``2 * index_n_heads * index_head_dim`` each) and attention
    over the keys kept (``min(visible, index_topk)``, the expanded
    form's scores and weighted sum over every head); in a sliding
    layer attention over ``min(visible, window)`` keys; the head for
    ``logit_rows`` of them."""
    n_int = max(int(round(n)), 0)
    visible = float(start) + 1.0 + np.arange(n_int, dtype=np.float64)
    full, sliding = _kinds(cfg)
    fh, _, _, fn, fr, fv = _widths(cfg, True)
    sh, _, _, sn, sr, sv = _widths(cfg, False)
    index = 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]
    kept = np.minimum(visible, cfg["index_topk"])
    seen = np.minimum(visible, cfg["sliding_window_size"])
    attention = full * (index * visible.sum() +
                        2.0 * fh * (fn + fr + fv) * kept.sum()) + \
        sliding * 2.0 * sh * (sn + sr + sv) * seen.sum()
    return 2.0 * _active(cfg) * n_int + attention + \
        2.0 * cfg["hidden_size"] * cfg["vocab_size"] * logit_rows


def token_flops(cfg: dict, context: float, with_logits: bool) -> float:
    """One decoded token that attends from position ``context``."""
    return span_flops(cfg, context, 1, 1.0 if with_logits else 0.0)


def cache_row_bytes(cfg: dict, bytes_per_value: int) -> dict:
    """One token's cache rows (unpadded), a layer: ``latent`` and
    ``index`` of a full layer, ``window`` of a sliding one."""
    _, _, fk, _, fr, _ = _widths(cfg, True)
    _, _, sk, _, sr, _ = _widths(cfg, False)
    return {"latent": (fk + fr) * bytes_per_value,
            "index": cfg["index_head_dim"] * bytes_per_value,
            "window": (sk + sr) * bytes_per_value}


def decode_step_min_bytes(cfg: dict, tokens: float, live_rows: float,
                          weight_bytes: int, kv_value_bytes: int
                          ) -> float:
    """The least a decode step of ``tokens`` tokens (one a slot) has
    to move through HBM: every weight outside the routed experts once
    (of the embedding a row a token), the routed experts the tokens
    reach, and of the cache what the selection forces: in a full
    layer the index key of every one of the ``live_rows`` tokens in
    the slots once and the rows the indexer keeps once, in a sliding
    layer the window's rows (a slot's context taken as the mean,
    ``live_rows / tokens``)."""
    p = params(cfg)
    full, sliding = _kinds(cfg)
    moe = cfg["n_layer"] - _dense_layers(cfg)
    fixed = param_bytes(cfg, weight_bytes) - weight_bytes * (
        p["embed"] + moe * cfg["n_routed_experts"] * p["expert"])
    fixed += tokens * cfg["hidden_size"] * weight_bytes
    routed = moe * experts_touched(cfg, tokens) * p["expert"] * \
        weight_bytes
    row = cache_row_bytes(cfg, kv_value_bytes)
    context = live_rows / tokens if tokens else 0.0
    cache = full * (live_rows * row["index"] + tokens * min(
        context, cfg["index_topk"]) * row["latent"]) + \
        sliding * tokens * min(
            context, cfg["sliding_window_size"]) * row["window"]
    return fixed + routed + cache
