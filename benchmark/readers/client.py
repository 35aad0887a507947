"""Per-layer metrics read from the load generator's own clock: what
a caller of the front end waits."""

from __future__ import annotations

import math


def latency_p95_ms(ctx: dict, params: dict):
    """95th percentile of send-to-last-byte over every request sent
    in the window, each followed to its answer; a failed one counts
    as the longest wait the run allows. At fewer than 20 requests
    there is no tail to read."""
    lat = sorted(ctx.get("latencies_ms", []))
    if len(lat) < 20:
        return None
    return lat[math.ceil(0.95 * len(lat)) - 1]
