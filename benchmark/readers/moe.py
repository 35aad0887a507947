"""Per-layer metrics of the expert-layer generate cells: shares of
the chip's peaks from `benchmark/flops_deepseek.py` and the table of
peaks, and the routing's counts from the program's counters. A reader
that finds nothing to read returns None."""

from __future__ import annotations

from benchmark import flops_deepseek as fd
from benchmark.readers.device import find_module


def _step_tokens(ctx: dict) -> "float | None":
    """Tokens a decode iteration of the traced window carried."""
    d = ctx.get("traced_counters") or {}
    steps = d.get("zoo_tpu_serving_gen_steps_total", 0)
    return d.get("zoo_tpu_serving_gen_tokens_total", 0) / steps \
        if steps else None


def mfu_generate(ctx: dict, params: dict):
    """FLOPs of the prompts admitted and the tokens decoded between
    the trace's start and stop, over that time and the peak: the
    whole step's share."""
    w = ctx.get("traced_work")
    if not w or not ctx.get("peak") or w["seconds"] <= 0:
        return None
    cfg = ctx["config"]
    work = sum(fd.prefill_flops(cfg, n) for n in w["prompt_lens"])
    work += w["decoded_tokens"] * fd.token_flops(
        cfg, w["mean_context"], True)
    return 100.0 * work / (w["seconds"] *
                           ctx["peak"]["bf16_flops_per_s"])


def decode_step_roofline(ctx: dict, params: dict):
    """The least time a decode step's bytes need at the chip's HBM
    bandwidth, over the step program's mean device time. Bound:
    memory. Tokens a step from the counters, live latent rows from
    the requests' admission and retirement times."""
    t, live = ctx.get("trace"), ctx.get("live_tokens_traced")
    tokens = _step_tokens(ctx)
    if not t or live is None or tokens is None or not ctx.get("peak"):
        return None
    mod = find_module(t, params["module"])
    if not mod or not mod["count"]:
        return None
    nbytes = fd.decode_step_min_bytes(
        ctx["config"], tokens, live, ctx["weight_bytes"],
        ctx["kv_value_bytes"])
    least_s = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (mod["total_s"] / mod["count"])


def experts_roofline(ctx: dict, params: dict):
    """The least time the routed experts' weights of a decode step
    need at the chip's HBM bandwidth, over the device time a step
    spends under the named scope. Bound: memory."""
    t, tokens = ctx.get("trace"), _step_tokens(ctx)
    prog = (t or {}).get("program")
    if not prog or tokens is None or not ctx.get("peak"):
        return None
    mod = find_module(t, params["module"])
    scope_s = prog["scope_s"].get(params["scope"], 0.0)
    if not mod or not mod["count"] or scope_s <= 0:
        return None
    least_s = fd.routed_experts_min_bytes(
        ctx["config"], tokens, ctx["weight_bytes"]) / \
        ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (scope_s / mod["count"])


def held_per_token(ctx: dict, params: dict):
    """Assignments that fell on held experts, a decoded token and
    expert layer: the counters' held over total, times the experts a
    token chooses (``k * held / E`` under even routing)."""
    d = ctx.get("counters", {})
    total = d.get(params["total"], 0)
    if not total:
        return None
    return ctx["config"]["num_experts_per_tok"] * \
        d.get(params["held"], 0) / total
