"""The whole step's share of the chip's peak, from shapes
(``benchmark/flops.py``) and the table of peaks alone."""

from __future__ import annotations

from benchmark import flops
from benchmark.readers.device import find_module


def train(ctx: dict, params: dict):
    """Model FLOPs of the steps the trace holds whole, over the time
    from the first one's start to the last one's start, the chips and
    the peak."""
    t = ctx.get("trace")
    if not t or not ctx.get("peak"):
        return None
    mod = find_module(t, params["module"])
    if not mod or mod["count"] < 3:
        return None
    starts = sorted(mod["starts_s"])
    steps, span = len(starts) - 1, starts[-1] - starts[0]
    work = steps * flops.resnet_train_step_flops(ctx["config"],
                                                 ctx["batch"])
    return 100.0 * work / (span * ctx["chips"] *
                           ctx["peak"]["bf16_flops_per_s"])


def generate(ctx: dict, params: dict):
    """FLOPs of the prompts admitted and the tokens decoded between
    the trace's start and stop, over that time and the peak."""
    w = ctx.get("traced_work")
    if not w or not ctx.get("peak") or w["seconds"] <= 0:
        return None
    cfg = ctx["config"]
    work = sum(flops.transformer_prefill_flops(cfg, n)
               for n in w["prompt_lens"])
    work += w["decoded_tokens"] * flops.transformer_token_flops(
        cfg, w["mean_context"], True)
    return 100.0 * work / (w["seconds"] *
                           ctx["peak"]["bf16_flops_per_s"])
