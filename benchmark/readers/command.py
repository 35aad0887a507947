"""Per-layer metrics of the Command A+ generate cell: shares of the
chip's peaks from `benchmark/flops_command.py` and the table of peaks.
What a step did comes from its own span (``decode/step``: ``n`` slots
that decoded, ``pages_live`` pages they held), what an admission did
from ``decode/prefill_chunk`` and ``decode/admit``; the experts a
token reached from the program's counters; where the routed experts
spent their device time from the ``decode/moe_experts`` scope, and the
paged kernel's from its name. A reader that finds nothing to read (no
trace, a program without these spans, counters or scopes) returns
None."""

from __future__ import annotations

from benchmark import flops_command as fc
from benchmark.readers.device import find_module
from benchmark.readers.mimo import _held_per_token, _steps, _traced
from benchmark.readers.nemotron import _scope_roofline


def mfu_generate(ctx: dict, params: dict):
    """FLOPs of everything the traced part of the window ran, over
    its length and the peak: the prompts' chunks, any whole prompt
    that fits one chunk, and the decode steps' tokens at their slots'
    mean length; attention at each query's own key count, the window
    layers' at most a window."""
    edges, steps = ctx.get("traced_wall"), _steps(ctx)
    if not edges or not steps or not ctx.get("peak"):
        return None
    cfg = ctx["config"]
    n, rows, _count = steps
    work = n * fc.token_flops(cfg, rows / n, True) if n else 0.0
    for s in _traced(ctx, "decode/prefill_chunk"):
        if s["fields"].get("tokens"):
            work += fc.span_flops(cfg, s["fields"]["context"],
                                  s["fields"]["tokens"], 0.0)
    chunk = cfg["engine"]["prefill_chunk"]
    for s in _traced(ctx, "decode/admit"):
        if s["fields"]["prompt_len"] <= chunk:
            work += fc.span_flops(cfg, 0, s["fields"]["prompt_len"],
                                  1.0)
    return 100.0 * work / ((edges[1] - edges[0]) *
                           ctx["peak"]["bf16_flops_per_s"])


def decode_step_roofline(ctx: dict, params: dict):
    """The least time a decode step's bytes need at the chip's HBM
    bandwidth (`flops_command.decode_step_min_bytes`: the weights
    outside the routed experts, the tied head among them, the routed
    experts reached, the ring and page rows the slots read), over the
    step program's mean device time. Bound: memory."""
    t, steps = ctx.get("trace"), _steps(ctx)
    if not t or not steps or not ctx.get("peak"):
        return None
    mod = find_module(t, params["module"])
    if not mod or not mod["count"]:
        return None
    n, rows, count = steps
    nbytes = fc.decode_step_min_bytes(
        ctx["config"], n / count, rows / count, ctx["weight_bytes"],
        ctx["kv_value_bytes"], _held_per_token(ctx))
    least_s = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (mod["total_s"] / mod["count"])


def experts_roofline(ctx: dict, params: dict):
    """The least time the held experts that a decode step's tokens
    reach (from the counters' held assignments a token) need at the
    chip's HBM bandwidth, over the device time a step spends under the
    named scope (the grouped product's kernel, whose 4096-wide
    experts go in two column tiles). Bound: memory."""
    return _scope_roofline(ctx, params, lambda tokens:
                           fc.routed_experts_min_bytes(
                               ctx["config"], tokens,
                               ctx["weight_bytes"],
                               _held_per_token(ctx)))


def kernel_roofline(ctx: dict, params: dict):
    """The named paged kernel's share of its roofline over the traced
    decode steps: the rows it must read (every live row of the full
    layer's pages, and the window - 1 rows before each slot's token in
    each sliding layer's ring) at the HBM bandwidth, or their scores
    and sums at the peak, whichever is longer, over the kernel's
    summed device time."""
    t, steps = ctx.get("trace"), _steps(ctx)
    prog = (t or {}).get("program") or {}
    spent = (prog.get("kernel_s") or {}).get(params["kernel"], 0.0)
    if not steps or spent <= 0 or not ctx.get("peak"):
        return None
    n, rows, _count = steps
    nbytes, ops = fc.paged_decode_work(ctx["config"], n, rows,
                                       ctx["kv_value_bytes"])
    least_s = max(nbytes / ctx["peak"]["hbm_bytes_per_s"],
                  ops / ctx["peak"]["bf16_flops_per_s"])
    return 100.0 * least_s / spent
