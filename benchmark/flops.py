"""Operations and bytes a step has to do, from the configuration's
shapes alone.

The yardstick's arithmetic: nothing here looks at the program, its
lowered HLO or its counters, so a share of the peak reads the same
work whatever implements it. A multiply-add is two operations, which
is how the peaks in ``peaks.json`` count them.
"""

from __future__ import annotations


def _same_out(size: int, stride: int) -> int:
    """Output length of a 'same'-padded convolution or pooling."""
    return -(-size // stride)


def resnet_forward_macs(cfg: dict) -> int:
    """Multiply-adds of one image's forward pass: every convolution
    and the classifier; BatchNorm, ReLU and pooling are not counted
    (the usual model-FLOPs convention). ResNet-50 at 224x224 comes
    to 4.09e9."""
    stem = cfg["stem"]
    size = _same_out(cfg["image_size"], stem["stride"])
    macs = size * size * stem["kernel"] ** 2 * cfg["in_channels"] * \
        stem["filters"]
    size = _same_out(size, stem["pool_stride"])
    cin = stem["filters"]
    exp = cfg["expansion"]
    for stage, (blocks, width) in enumerate(
            zip(cfg["stage_blocks"], cfg["stage_widths"])):
        for b in range(blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            out = _same_out(size, stride)
            # 1x1 reduce at the input resolution, 3x3 carries the
            # stride (v1.5), 1x1 expand, projection on the first block
            macs += size * size * cin * width
            macs += out * out * 9 * width * width
            macs += out * out * width * width * exp
            if b == 0:
                macs += out * out * cin * width * exp
            size, cin = out, width * exp
    return macs + cin * cfg["num_classes"]


def resnet_train_step_flops(cfg: dict, images: int) -> float:
    """Model FLOPs of one training step on ``images`` images: forward
    plus a backward that costs twice the forward. Recomputation does
    not count."""
    return 3.0 * 2.0 * resnet_forward_macs(cfg) * images


def transformer_params(cfg: dict) -> dict:
    """Parameter counts of TransformerLayer at the configuration's
    sizes: per block, both embeddings, and the total (the output head
    is the token embedding again)."""
    h, m = cfg["n_embd"], cfg["n_inner"]
    block = (h * 3 * h + 3 * h) + (h * h + h) + (h * m + m) + \
        (m * h + h) + 4 * h
    tok = cfg["vocab_size"] * h
    pos = cfg["n_positions"] * h
    return {"block": block, "tok_embed": tok, "pos_embed": pos,
            "total": cfg["n_layer"] * block + tok + pos}


def transformer_token_flops(cfg: dict, context: float,
                            with_logits: bool) -> float:
    """FLOPs to push one token through the stack while it attends to
    ``context`` positions: 2 per weight of every block, 4 x hidden per
    attended position and block (scores and weighted sum), and the
    vocabulary projection where the token's logits are wanted."""
    p = transformer_params(cfg)
    flops = 2.0 * cfg["n_layer"] * p["block"]
    flops += 4.0 * cfg["n_layer"] * cfg["n_embd"] * context
    if with_logits:
        flops += 2.0 * p["tok_embed"]
    return flops


def transformer_prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A causal prompt of ``prompt_len`` tokens: position i attends to
    i + 1 positions; only the last one needs logits."""
    mean_ctx = (prompt_len + 1) / 2.0
    return prompt_len * transformer_token_flops(cfg, mean_ctx, False) \
        + 2.0 * transformer_params(cfg)["tok_embed"]


def kv_bytes_per_token(cfg: dict, bytes_per_value: int) -> int:
    """K and V of one token in every block."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * bytes_per_value


def decode_step_min_bytes(cfg: dict, live_tokens: float,
                          weight_bytes: int, kv_value_bytes: int
                          ) -> float:
    """The least a decode step has to move through HBM: every weight
    once (the position table aside, of which it reads a row a slot)
    and K and V of the tokens live in the slots."""
    p = transformer_params(cfg)
    weights = (p["total"] - p["pos_embed"]) * weight_bytes
    return weights + live_tokens * kv_bytes_per_token(
        cfg, kv_value_bytes)
