"""Operations and bytes a Command A+ step has to do, from the
configuration's shapes alone (`flops.py`'s rules: nothing here looks
at the program, a multiply-add is two operations).

The configuration holds one chip's share of a deployment, as
`flops_deepseek.py` reads it: ``n_layer`` layers of the published
``layer_types``, ``n_routed_experts`` of the
``published.n_routed_experts`` the router scores, ``vocab_size`` rows
of the vocabulary. Parameter counts are of what is held.

Every layer is the same parallel block: grouped-query attention (128
query heads of 128 over 8 K/V heads), a sigmoid router with no
selection bias, the routed SwiGLU experts and the shared experts as
one SwiGLU, behind ONE LayerNorm gain. A query in a full layer attends
to every position before it, one in a sliding layer to its window, its
own position among them. A cached token is one row a layer: the K and
V of the 8 K/V heads. The head is the token embedding's transpose:
its bytes are the embedding's, read once a step.
"""

from __future__ import annotations

import numpy as np

from benchmark.flops_deepseek import _experts_total

__all__ = ["params", "param_bytes", "span_flops", "token_flops",
           "cache_row_bytes", "experts_touched",
           "routed_experts_min_bytes", "decode_step_min_bytes",
           "paged_decode_work"]


def _kinds(cfg: dict) -> "tuple[int, int]":
    """(full layers, sliding layers) among the ``n_layer`` held."""
    held = cfg["layer_types"][:cfg["n_layer"]]
    return held.count("full_attention"), held.count("sliding_attention")


def params(cfg: dict) -> dict:
    """Parameter counts: ``attention`` (q, k, v, o), ``router``,
    ``expert`` (one routed expert), ``shared`` (the shared experts'
    one SwiGLU), ``norm`` (a layer's one gain), ``embed`` (the tied
    embedding, the head's matrix too), ``head`` (the final norm) and
    ``total``."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    m = cfg["intermediate_size"]
    q, kv = cfg["num_attention_heads"] * d, \
        cfg["num_key_value_heads"] * d
    out = {"attention": 2 * h * q + 2 * h * kv,
           "router": h * _experts_total(cfg), "expert": 3 * h * m,
           "shared": 3 * h * cfg["num_shared_experts"] * m,
           "norm": h, "embed": cfg["vocab_size"] * h, "head": h}
    out["total"] = cfg["n_layer"] * (
        out["attention"] + out["router"] + out["shared"] + out["norm"]
        + cfg["n_routed_experts"] * out["expert"]) + out["embed"] + \
        out["head"]
    return out


def param_bytes(cfg: dict, weight_bytes: int) -> int:
    """Bytes of the held tree: every parameter at ``weight_bytes``."""
    return params(cfg)["total"] * weight_bytes


def _active(cfg: dict) -> float:
    """Weights a token multiplies in the layers held, the head aside:
    attention, the router, the shared experts and the token's expected
    share of its routed experts held here."""
    p = params(cfg)
    held_share = cfg["n_routed_experts"] / _experts_total(cfg)
    return cfg["n_layer"] * (
        p["attention"] + p["router"] + p["shared"] +
        cfg["num_experts_per_tok"] * held_share * p["expert"])


def _per_key(cfg: dict) -> float:
    """FLOPs of one (query token, key) pair over every head: the
    score and the weighted sum."""
    return 2.0 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]


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
    attention = _per_key(cfg) * (full * visible.sum() +
                                 sliding * seen.sum())
    return 2.0 * _active(cfg) * n_int + attention + \
        2.0 * cfg["hidden_size"] * cfg["vocab_size"] * logit_rows


def token_flops(cfg: dict, context: float, with_logits: bool) -> float:
    """One decoded token that attends from position ``context``."""
    return span_flops(cfg, context, 1, 1.0 if with_logits else 0.0)


def cache_row_bytes(cfg: dict, bytes_per_value: int) -> int:
    """One token's cache row a layer, in the context pool and the
    ring alike: K and V of the K/V heads."""
    return cfg["num_key_value_heads"] * 2 * cfg["head_dim"] * \
        bytes_per_value


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
    has to read, over the layers held (every one an expert layer)."""
    return cfg["n_layer"] * experts_touched(cfg, tokens,
                                            held_per_token) * \
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
    mean = live_rows / tokens if tokens else 0.0
    rows = full * live_rows + \
        sliding * tokens * min(mean, cfg["sliding_window"] - 1)
    return rows * cache_row_bytes(cfg, kv_value_bytes), \
        rows * _per_key(cfg)


def decode_step_min_bytes(cfg: dict, tokens: float, live_rows: float,
                          weight_bytes: int, kv_value_bytes: int,
                          held_per_token: "float | None" = None
                          ) -> float:
    """The least a decode step of ``tokens`` tokens (one a slot) has
    to move through HBM: every weight outside the routed experts once
    (the tied embedding whole, as the head), the routed experts the
    tokens reach, and each slot's live rows once
    (`paged_decode_work`)."""
    fixed = param_bytes(cfg, weight_bytes) - weight_bytes * \
        cfg["n_layer"] * cfg["n_routed_experts"] * \
        params(cfg)["expert"]
    return fixed + \
        routed_experts_min_bytes(cfg, tokens, weight_bytes,
                                 held_per_token) + \
        paged_decode_work(cfg, tokens, live_rows, kv_value_bytes)[0]
