"""Operations and bytes a MiMo-V2-Flash step has to do, from the
configuration's shapes alone (`flops.py`'s rules: nothing here looks
at the program, a multiply-add is two operations).

The configuration holds one chip's share of a deployment, as
`flops_deepseek.py` reads it: ``n_layer`` layers in the published
``hybrid_layer_pattern`` (0 full, 1 sliding), ``n_routed_experts`` of
the ``published.n_routed_experts`` the router scores, ``vocab_size``
rows of the vocabulary. Parameter counts are of what is held.

A query in a full layer attends to every position before it, over 64
heads of 192 + 128; one in a sliding layer to its window. A cached
token is one row a layer: the K and V of the layer's K/V heads.
"""

from __future__ import annotations

import numpy as np

from benchmark.flops_deepseek import _experts_total
from benchmark.reference.mimo_v2_flash import widths

__all__ = ["params", "param_bytes", "span_flops", "token_flops",
           "cache_row_bytes", "experts_touched",
           "routed_experts_min_bytes", "decode_step_min_bytes",
           "paged_decode_work"]


def _widths(cfg: dict, full: bool) -> tuple:
    """(query heads, K/V heads, head_dim, v_head_dim, sink biases)."""
    w = widths(cfg, full)
    return (w["heads"], w["kv_heads"], w["head_dim"], w["v_head_dim"],
            w["heads"] if w["sink"] else 0)


def _kinds(cfg: dict) -> "tuple[int, int]":
    """(full layers, sliding layers) among the ``n_layer`` held."""
    held = cfg["hybrid_layer_pattern"][:cfg["n_layer"]]
    return held.count(0), held.count(1)


def _dense_layers(cfg: dict) -> int:
    return sum(1 for f in cfg["moe_layer_freq"][:cfg["n_layer"]]
               if not f)


def _attention(cfg: dict, full: bool) -> int:
    h = cfg["hidden_size"]
    nh, g, dk, dv, sink = _widths(cfg, full)
    return h * nh * dk + h * g * dk + h * g * dv + nh * dv * h + sink


def params(cfg: dict) -> dict:
    """Parameter counts: ``full_attention`` and ``sliding_attention``
    (one layer's four projections and, on the sliding kind, the sink
    biases), ``dense_mlp``, ``router`` (with its selection bias),
    ``expert`` (one routed expert), ``norms`` (a layer's two),
    ``embed``, ``head`` (with the final norm) and ``total``."""
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    e = _experts_total(cfg)
    out = {"full_attention": _attention(cfg, True),
           "sliding_attention": _attention(cfg, False),
           "dense_mlp": 3 * h * cfg["intermediate_size"],
           "router": h * e + e, "expert": 3 * h * m, "norms": 2 * h,
           "embed": cfg["vocab_size"] * h,
           "head": h + h * cfg["vocab_size"]}
    full, sliding = _kinds(cfg)
    dense = _dense_layers(cfg)
    out["total"] = full * out["full_attention"] + \
        sliding * out["sliding_attention"] + \
        cfg["n_layer"] * out["norms"] + dense * out["dense_mlp"] + \
        (cfg["n_layer"] - dense) * (
            out["router"] + cfg["n_routed_experts"] * out["expert"]) + \
        out["embed"] + out["head"]
    return out


def param_bytes(cfg: dict, weight_bytes: int) -> int:
    """Bytes of the held tree: every parameter at ``weight_bytes``
    but the routers' selection biases and the sink biases, which stay
    float32."""
    moe = cfg["n_layer"] - _dense_layers(cfg)
    full, sliding = _kinds(cfg)
    float32 = moe * _experts_total(cfg) + \
        full * _widths(cfg, True)[4] + sliding * _widths(cfg, False)[4]
    return params(cfg)["total"] * weight_bytes + \
        float32 * (4 - weight_bytes)


def _active(cfg: dict) -> float:
    """Weights a token multiplies in the layers held, the head
    aside: attention, the dense MLP, the router and the token's
    expected share of its routed experts held here."""
    p = params(cfg)
    full, sliding = _kinds(cfg)
    dense = _dense_layers(cfg)
    held_share = cfg["n_routed_experts"] / _experts_total(cfg)
    return full * p["full_attention"] + \
        sliding * p["sliding_attention"] + dense * p["dense_mlp"] + \
        (cfg["n_layer"] - dense) * (
            p["router"] +
            cfg["num_experts_per_tok"] * held_share * p["expert"])


def _per_key(cfg: dict, full: bool) -> float:
    """FLOPs of one (query token, key) pair over every head: the
    score and the weighted sum."""
    nh, _g, dk, dv, _s = _widths(cfg, full)
    return 2.0 * nh * (dk + dv)


def span_flops(cfg: dict, start: float, n: float, logit_rows: float
               ) -> float:
    """FLOPs to push ``n`` consecutive tokens of one sequence, the
    first at position ``start``, through the layers held: 2 a weight
    multiplied; attention over every visible key in a full layer and
    over ``min(visible, sliding_window)`` in a sliding one; the head
    for ``logit_rows`` of them."""
    n_int = max(int(round(n)), 0)
    visible = float(start) + 1.0 + np.arange(n_int, dtype=np.float64)
    full, sliding = _kinds(cfg)
    seen = np.minimum(visible, cfg["sliding_window"])
    attention = full * _per_key(cfg, True) * visible.sum() + \
        sliding * _per_key(cfg, False) * seen.sum()
    return 2.0 * _active(cfg) * n_int + attention + \
        2.0 * cfg["hidden_size"] * cfg["vocab_size"] * logit_rows


def token_flops(cfg: dict, context: float, with_logits: bool) -> float:
    """One decoded token that attends from position ``context``."""
    return span_flops(cfg, context, 1, 1.0 if with_logits else 0.0)


def cache_row_bytes(cfg: dict, bytes_per_value: int) -> dict:
    """One token's cache row (unpadded), a layer: ``full`` in the
    context pool, ``sliding`` in the ring: K and V of the layer's K/V
    heads."""
    row = lambda full: _widths(cfg, full)[1] * (
        _widths(cfg, full)[2] + _widths(cfg, full)[3]) * bytes_per_value
    return {"full": row(True), "sliding": row(False)}


def experts_touched(cfg: dict, tokens: float,
                    held_per_token: "float | None" = None) -> float:
    """Expected number of the held experts that ``tokens`` tokens
    reach, a layer: a token reaches a given held expert with
    probability ``held_per_token / held`` (its assignments that fall
    on held experts, from the program's counters; ``k * held / E``
    under even routing when None)."""
    held = cfg["n_routed_experts"]
    if held_per_token is None:
        held_per_token = cfg["num_experts_per_tok"] * held / \
            _experts_total(cfg)
    miss = max(0.0, 1.0 - held_per_token / held)
    return held * (1.0 - miss ** tokens)


def routed_experts_min_bytes(cfg: dict, tokens: float,
                             weight_bytes: int,
                             held_per_token: "float | None" = None
                             ) -> float:
    """The routed experts' weights a decode step of ``tokens`` tokens
    has to read, over the expert layers held."""
    moe = cfg["n_layer"] - _dense_layers(cfg)
    return moe * experts_touched(cfg, tokens, held_per_token) * \
        params(cfg)["expert"] * weight_bytes


def paged_decode_work(cfg: dict, tokens: float, live_rows: float,
                      kv_value_bytes: int) -> "tuple[float, float]":
    """(bytes, FLOPs) the attention of one decode step has to move
    and do over the cache, all layers held: ``tokens`` slots whose
    cached lengths sum to ``live_rows``. A full layer reads each live
    row once; a sliding layer the ``sliding_window - 1`` rows before
    the new token (a slot's length taken as the mean); each row read
    is scored by and weighed for every query head."""
    full, sliding = _kinds(cfg)
    row = cache_row_bytes(cfg, kv_value_bytes)
    mean = live_rows / tokens if tokens else 0.0
    seen = tokens * min(mean, cfg["sliding_window"] - 1)
    return (full * live_rows * row["full"] +
            sliding * seen * row["sliding"],
            full * live_rows * _per_key(cfg, True) +
            sliding * seen * _per_key(cfg, False))


def decode_step_min_bytes(cfg: dict, tokens: float, live_rows: float,
                          weight_bytes: int, kv_value_bytes: int,
                          held_per_token: "float | None" = None
                          ) -> float:
    """The least a decode step of ``tokens`` tokens (one a slot) has
    to move through HBM: every weight outside the routed experts once
    (of the embedding a row a token), the routed experts the tokens
    reach, and each slot's live rows once (`paged_decode_work`)."""
    p = params(cfg)
    moe = cfg["n_layer"] - _dense_layers(cfg)
    fixed = param_bytes(cfg, weight_bytes) - weight_bytes * (
        p["embed"] + moe * cfg["n_routed_experts"] * p["expert"])
    fixed += tokens * cfg["hidden_size"] * weight_bytes
    return fixed + \
        routed_experts_min_bytes(cfg, tokens, weight_bytes,
                                 held_per_token) + \
        paged_decode_work(cfg, tokens, live_rows, kv_value_bytes)[0]
