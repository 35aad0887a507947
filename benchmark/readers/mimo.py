"""Per-layer metrics of the MiMo-V2-Flash generate cell: shares of
the chip's peaks from `benchmark/flops_mimo.py` and the table of
peaks. What a step did comes from its own span (``decode/step``:
``n`` slots that decoded, ``pages_live`` pages they held), what an
admission did from ``decode/prefill_chunk`` and ``decode/admit``; the
experts a token reached from the program's counters. A reader that
finds nothing to read (no trace, a program without these spans or
counters) returns None."""

from __future__ import annotations

from benchmark import flops_mimo as fm
from benchmark.readers.device import find_module


def _traced(ctx: dict, name: str) -> "list[dict]":
    """The named spans that ended inside the traced part of the
    window."""
    edges = ctx.get("traced_wall")
    if not edges:
        return []
    lo, hi = edges
    return [s for s in ctx.get("spans", []) if s["name"] == name
            and lo <= s["t_start"] + s["dur_s"] <= hi]


def _steps(ctx: dict) -> "tuple[float, float, int] | None":
    """(slots that decoded, rows they held, steps) summed over the
    traced decode steps; a slot's last page is taken as half full."""
    page = ctx["config"]["engine"]["page_size"]
    spans = [s["fields"] for s in _traced(ctx, "decode/step")
             if s["fields"].get("pages_live") is not None]
    if not spans:
        return None
    n = sum(f["n"] for f in spans)
    rows = sum(max(f["pages_live"] * page - f["n"] * page / 2.0, 0.0)
               for f in spans)
    return float(n), float(rows), len(spans)


def _held_per_token(ctx: dict) -> "float | None":
    d = ctx.get("traced_counters") or ctx.get("counters") or {}
    total = d.get("zoo_tpu_moe_assignments_total", 0)
    if not total:
        return None
    return ctx["config"]["num_experts_per_tok"] * \
        d.get("zoo_tpu_moe_assignments_held_total", 0) / total


def mfu_generate(ctx: dict, params: dict):
    """FLOPs of everything the traced part of the window ran, over
    its length and the peak: the chunks of the long prompts (one
    program of ``tokens`` behind ``context``), the whole prompts that
    fit one chunk (admitted in one program each) and the decode
    steps' tokens at their slots' mean length."""
    edges, steps = ctx.get("traced_wall"), _steps(ctx)
    if not edges or not steps or not ctx.get("peak"):
        return None
    cfg = ctx["config"]
    n, rows, _count = steps
    work = n * fm.token_flops(cfg, rows / n, True) if n else 0.0
    for s in _traced(ctx, "decode/prefill_chunk"):
        if s["fields"].get("tokens"):
            work += fm.span_flops(cfg, s["fields"]["context"],
                                  s["fields"]["tokens"], 0.0)
    chunk = cfg["engine"]["prefill_chunk"]
    for s in _traced(ctx, "decode/admit"):
        if s["fields"]["prompt_len"] <= chunk:
            work += fm.span_flops(cfg, 0, s["fields"]["prompt_len"],
                                  1.0)
    return 100.0 * work / ((edges[1] - edges[0]) *
                           ctx["peak"]["bf16_flops_per_s"])


def decode_step_roofline(ctx: dict, params: dict):
    """The least time a decode step's bytes need at the chip's HBM
    bandwidth, over the step program's mean device time. Bound:
    memory."""
    t, steps = ctx.get("trace"), _steps(ctx)
    if not t or not steps or not ctx.get("peak"):
        return None
    mod = find_module(t, params["module"])
    if not mod or not mod["count"]:
        return None
    n, rows, count = steps
    nbytes = fm.decode_step_min_bytes(
        ctx["config"], n / count, rows / count, ctx["weight_bytes"],
        ctx["kv_value_bytes"], _held_per_token(ctx))
    least_s = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (mod["total_s"] / mod["count"])


def experts_roofline(ctx: dict, params: dict):
    """`readers.moe.experts_roofline` over this configuration's
    shapes: the least time the routed experts' weights of a decode
    step need at the chip's HBM bandwidth, over the device time a
    step spends under the named scope. Bound: memory."""
    t, steps = ctx.get("trace"), _steps(ctx)
    prog = (t or {}).get("program")
    if not prog or not steps or not ctx.get("peak"):
        return None
    mod = find_module(t, params["module"])
    scope_s = prog["scope_s"].get(params["scope"], 0.0)
    if not mod or not mod["count"] or scope_s <= 0:
        return None
    n, _rows, count = steps
    least_s = fm.routed_experts_min_bytes(
        ctx["config"], n / count, ctx["weight_bytes"],
        _held_per_token(ctx)) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (scope_s / mod["count"])


def kernel_roofline(ctx: dict, params: dict):
    """The named Pallas kernel's share of its roofline over the
    traced decode steps: the time its bytes need at the HBM bandwidth
    or its operations at the peak, whichever is longer, over the
    kernel's summed device time."""
    t, steps = ctx.get("trace"), _steps(ctx)
    prog = (t or {}).get("program") or {}
    spent = (prog.get("kernel_s") or {}).get(params["kernel"], 0.0)
    if not steps or spent <= 0 or not ctx.get("peak"):
        return None
    n, rows, _count = steps
    # every step's slots at the steps' mean length
    nbytes, ops = fm.paged_decode_work(
        ctx["config"], n, rows, ctx["kv_value_bytes"])
    least_s = max(nbytes / ctx["peak"]["hbm_bytes_per_s"],
                  ops / ctx["peak"]["bf16_flops_per_s"])
    return 100.0 * least_s / spent
