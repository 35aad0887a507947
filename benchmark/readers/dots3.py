"""Per-layer metrics of the dots3-note generate cell: shares of the
chip's peaks from `benchmark/flops_dots3.py` and the table of peaks,
the indexer's keep rate from the program's counters, the chunk
programs' time from their span. A reader that finds nothing to read
(no trace, a program without these spans or counters) returns
None."""

from __future__ import annotations

import statistics

from benchmark import flops_dots3 as f3
from benchmark.readers.device import find_module
from benchmark.readers.moe import _step_tokens


def _chunks(ctx: dict, lo: float, hi: float) -> "list[dict]":
    """The ``decode/prefill_chunk`` spans that ended in [lo, hi] and
    say what they wrote."""
    return [s for s in ctx.get("spans", [])
            if s["name"] == "decode/prefill_chunk"
            and s["fields"].get("tokens")
            and lo <= s["t_start"] + s["dur_s"] <= hi]


def mfu_generate(ctx: dict, params: dict):
    """FLOPs of the prompt chunks written and the tokens decoded
    between the trace's start and stop, over that time and the peak:
    the whole step's share. A chunk span is one one-row program that
    wrote ``tokens`` tokens behind ``context`` cached ones."""
    w, edges = ctx.get("traced_work"), ctx.get("traced_wall")
    if not w or not edges or not ctx.get("peak") or w["seconds"] <= 0:
        return None
    cfg = ctx["config"]
    work = w["decoded_tokens"] * f3.token_flops(
        cfg, w["mean_context"], True)
    for s in _chunks(ctx, *edges):
        work += f3.span_flops(cfg, s["fields"]["context"],
                              s["fields"]["tokens"], 0.0)
    return 100.0 * work / (w["seconds"] *
                           ctx["peak"]["bf16_flops_per_s"])


def decode_step_roofline(ctx: dict, params: dict):
    """The least time a decode step's bytes need at the chip's HBM
    bandwidth, over the step program's mean device time. Bound:
    memory."""
    t, live = ctx.get("trace"), ctx.get("live_tokens_traced")
    tokens = _step_tokens(ctx)
    if not t or live is None or not tokens or not ctx.get("peak"):
        return None
    mod = find_module(t, params["module"])
    if not mod or not mod["count"]:
        return None
    nbytes = f3.decode_step_min_bytes(
        ctx["config"], tokens, live, ctx["weight_bytes"],
        ctx["kv_value_bytes"])
    least_s = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (mod["total_s"] / mod["count"])


def keep_pct(ctx: dict, params: dict):
    """Keys the indexer kept over keys its queries could see, both
    phases: under 100 once contexts pass ``index_topk``."""
    d = ctx.get("counters", {})
    visible = d.get(params["visible"], 0)
    if not visible:
        return None
    return 100.0 * d.get(params["selected"], 0) / visible


def dur_p50_ms(ctx: dict, params: dict):
    """Median duration of the named spans (for
    ``decode/prefill_chunk``, one chunk program each)."""
    durs = [s["dur_s"] for s in ctx.get("spans", [])
            if s["name"] == params["span"]]
    return 1e3 * statistics.median(durs) if durs else None
