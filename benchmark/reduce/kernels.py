"""Device time of named Pallas kernels in a profiler trace. A
``pallas_call`` keeps its ``name=`` as the operation's name
(``zoo_paged_gqa_decode.3``), so a kernel is found by that name
however the program around it is rearranged."""

from __future__ import annotations

from benchmark.reduce import trace


def kernel_times(profile, kernels) -> "dict[str, float]":
    """{kernel: seconds of device own-time in operations whose name
    starts with it}, a mean over the chips; a kernel that never ran
    is left out."""
    out: "dict[str, float]" = {}
    chips = 0
    for plane in profile.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        ops = trace._events(lines[trace.OPS_LINE], trace.op_name) \
            if trace.OPS_LINE in lines else []
        if not ops:
            continue
        chips += 1
        for name, ns in trace.self_times(ops).items():
            for k in kernels:
                if name.lstrip("%").startswith(k):
                    out[k] = out.get(k, 0.0) + ns / 1e9
    return {k: s / chips for k, s in out.items()} if chips else {}
