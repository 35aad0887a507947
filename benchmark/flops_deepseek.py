"""Operations and bytes a DeepSeek-V2 step has to do, from the
configuration's shapes alone (`flops.py`'s rules: nothing here looks
at the program, a multiply-add is two operations).

A configuration may hold one chip's share of a deployment:
``n_layer`` layers, ``n_routed_experts`` of the
``published.n_routed_experts`` the router scores, ``vocab_size`` rows
of the vocabulary. Parameter counts are of what is held.
"""

from __future__ import annotations


def _experts_total(cfg: dict) -> int:
    return int(cfg.get("published", cfg)["n_routed_experts"])


def _layers(cfg: dict) -> "tuple[int, int]":
    """(dense layers, expert layers) among the ``n_layer`` held."""
    dense = sum(1 for i in range(cfg["n_layer"])
                if i < cfg["first_k_dense_replace"]
                or i % cfg["moe_layer_freq"])
    return dense, cfg["n_layer"] - dense


def params(cfg: dict) -> dict:
    """Parameter counts: ``attention`` (one layer's five projections
    and two latent norms), ``dense_mlp``, ``shared`` (the shared
    experts of one layer), ``router``, ``expert`` (one routed
    expert), ``norms`` (a layer's two), ``expert_layer`` (everything
    one expert layer holds here), ``dense_layer``, ``embed``,
    ``head`` (with the final norm) and ``total``."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    attention = h * qr + qr + qr * nh * (nope + rope) + \
        h * (kr + rope) + kr + kr * nh * (nope + v) + nh * v * h
    m = cfg["moe_intermediate_size"]
    out = {"attention": attention,
           "dense_mlp": 3 * h * cfg["intermediate_size"],
           "shared": 3 * h * m * cfg["n_shared_experts"],
           "router": h * _experts_total(cfg),
           "expert": 3 * h * m, "norms": 2 * h}
    out["expert_layer"] = attention + out["norms"] + out["shared"] + \
        out["router"] + cfg["n_routed_experts"] * out["expert"]
    out["dense_layer"] = attention + out["norms"] + out["dense_mlp"]
    out["embed"] = cfg["vocab_size"] * h
    out["head"] = h + h * cfg["vocab_size"]
    dense, moe = _layers(cfg)
    out["total"] = dense * out["dense_layer"] + \
        moe * out["expert_layer"] + out["embed"] + out["head"]
    return out


def experts_touched(cfg: dict, tokens: float) -> float:
    """Held experts that ``tokens`` tokens reach in one layer, each
    token choosing ``num_experts_per_tok`` of all the experts evenly
    and independently: ``held * (1 - (1 - k / E)^tokens)``."""
    e, k = _experts_total(cfg), cfg["num_experts_per_tok"]
    return cfg["n_routed_experts"] * (1.0 - (1.0 - k / e) ** tokens)


def latent_row_bytes(cfg: dict, bytes_per_value: int) -> int:
    """One token's latent cache row in one layer (unpadded)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * \
        bytes_per_value


def routed_experts_min_bytes(cfg: dict, tokens: float,
                             weight_bytes: int) -> float:
    """The routed experts' weights a decode step of ``tokens`` tokens
    has to read, over the expert layers held."""
    _dense, moe = _layers(cfg)
    return moe * experts_touched(cfg, tokens) * \
        params(cfg)["expert"] * weight_bytes


def decode_step_min_bytes(cfg: dict, tokens: float, live_rows: float,
                          weight_bytes: int, kv_value_bytes: int
                          ) -> float:
    """The least a decode step of ``tokens`` tokens has to move
    through HBM: every weight outside the routed experts once (of the
    embedding a row a token), the routed experts the tokens reach,
    and the latent rows of the ``live_rows`` tokens in the slots."""
    p = params(cfg)
    fixed = p["total"] - p["embed"] - _layers(cfg)[1] * \
        cfg["n_routed_experts"] * p["expert"]
    fixed += tokens * cfg["hidden_size"]
    return fixed * weight_bytes + \
        routed_experts_min_bytes(cfg, tokens, weight_bytes) + \
        live_rows * cfg["n_layer"] * latent_row_bytes(
            cfg, kv_value_bytes)


def token_flops(cfg: dict, context: float, with_logits: bool) -> float:
    """FLOPs to push one token through the layers held while it
    attends to ``context`` positions: 2 per active weight (attention
    and norms aside, the dense MLP, the shared experts, the router
    and the token's expected share of its routed experts that are
    held here), the expanded attention's scores and weighted sum over
    every head and position, and the head where logits are wanted."""
    p = params(cfg)
    dense, moe = _layers(cfg)
    held_share = cfg["n_routed_experts"] / _experts_total(cfg)
    active = cfg["n_layer"] * p["attention"] + dense * p["dense_mlp"] \
        + moe * (p["shared"] + p["router"] +
                 cfg["num_experts_per_tok"] * held_share * p["expert"])
    per_position = 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] +
        cfg["v_head_dim"])
    flops = 2.0 * active + cfg["n_layer"] * per_position * context
    if with_logits:
        flops += 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return flops


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A causal prompt: position i attends to i + 1 positions; only
    the last one needs logits."""
    return prompt_len * token_flops(cfg, (prompt_len + 1) / 2.0,
                                    False) + \
        2.0 * cfg["hidden_size"] * cfg["vocab_size"]
