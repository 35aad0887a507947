"""Per-layer metrics read from the reduced device trace."""

from __future__ import annotations

from benchmark import flops


def idle_pct(ctx: dict, params: dict):
    """Share of the traced window in which no operation ran."""
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def find_module(trace: dict, needle: str):
    """The one traced program whose name holds ``needle``."""
    hits = [m for name, m in trace["modules"].items() if needle in name]
    return hits[0] if len(hits) == 1 else None


def decode_step_roofline(ctx: dict, params: dict):
    """The least time a decode step's bytes need at the chip's HBM
    bandwidth, over the step program's mean device time. Bound:
    memory. Live tokens are the mean over the traced window of
    prompt plus tokens generated so far in the slots, from the
    requests' own admission and retirement times."""
    t, live = ctx.get("trace"), ctx.get("live_tokens_traced")
    if not t or live is None or not ctx.get("peak"):
        return None
    mod = find_module(t, params["module"])
    if not mod or not mod["count"]:
        return None
    cfg = ctx["config"]
    nbytes = flops.decode_step_min_bytes(
        cfg, live, ctx["weight_bytes"], ctx["kv_value_bytes"])
    least_s = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (mod["total_s"] / mod["count"])
