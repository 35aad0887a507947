"""From a profiler trace (``*.xplane.pb``) to the few numbers the
benchmark reports: how long the device was busy, which programs and
operations took the time, and what the host was doing in the longest
idle gaps (the chip-0 plane's, by the host span that covers most of
each). Reads the trace with ``jax.profiler.ProfileData`` alone.

A TPU's plane ``/device:TPU:<n>`` carries the lines ``XLA Modules``
(one event per execution of a compiled program) and ``XLA Ops`` (one
per operation, a loop enclosing its body's). Busy time is the union
of the operations' intervals; an operation's own time is its duration
less its children's. Host threads are the lines of ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_RUN_ID = re.compile(r"\(\d+\)$")
_HLO = re.compile(r"^%(\S+) = \(?(\w+\[[\d,]*\])?")


def op_name(text: str) -> str:
    """An operation's event carries its whole HLO line; keep the
    instruction's name and the shape of its (first) result:
    ``fusion.2 bf16[2048,1024]``."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line, name=str) -> "list[tuple[int, int, str]]":
    """(start_ns, end_ns, name) of a line, by start."""
    out = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
            name(e.name)) for e in line.events]
    out.sort(key=lambda t: (t[0], -t[1]))
    return out


def union(intervals) -> "list[tuple[int, int]]":
    """Merged, sorted, non-overlapping intervals."""
    merged: "list[list[int]]" = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def self_times(events) -> "dict[str, int]":
    """Own nanoseconds by operation name: an event's duration less
    that of the events nested inside it."""
    own: "dict[str, int]" = {}
    stack: "list[list]" = []      # [end, name, own_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _end, name, ns = stack.pop()
            own[name] = own.get(name, 0) + max(ns, 0)

    for start, end, name in events:
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    close(float("inf"))
    return own


def _gaps(busy, lo: int, hi: int) -> "list[tuple[int, int]]":
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


class HostSpans:
    """Every event of the host's threads, for naming idle gaps."""

    def __init__(self, planes):
        events = []
        for plane in planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    events.extend(_events(line))
        self.starts = np.array([e[0] for e in events], np.int64)
        self.ends = np.array([e[1] for e in events], np.int64)
        self.names = [e[2] for e in events]

    def blame(self, gap) -> str:
        """The host span that covers most of an idle gap (the
        shorter one on a tie, which is the more specific)."""
        if not self.names:
            return "untraced_host"
        a, b = gap
        cover = np.minimum(self.ends, b) - np.maximum(self.starts, a)
        best = int(cover.max())
        if best <= 0:
            return "untraced_host"
        tied = np.flatnonzero(cover == best)
        i = tied[np.argmin((self.ends - self.starts)[tied])]
        return self.names[int(i)][:80]


def idle_by_host_span(gaps, host: HostSpans, top: int,
                      named: int = 200) -> "list[list]":
    """Idle seconds by what the host was doing: the ``named``
    longest gaps each go to the host span that covers most of them,
    the rest are summed as ``shorter_gaps``."""
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    by: "dict[str, int]" = {}
    for g in gaps[:named]:
        name = host.blame(g)
        by[name] = by.get(name, 0) + g[1] - g[0]
    rest = sum(b - a for a, b in gaps[named:])
    if rest:
        by["shorter_gaps"] = rest
    order = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in order]


def reduce_profile(profile, top: int = 10) -> "dict | None":
    """The reduction of one loaded trace; None where it holds no TPU
    plane with operations on it (a CPU rehearsal)."""
    planes = list(profile.planes)
    chips = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        ops = _events(lines[OPS_LINE], op_name)
        if not ops:
            continue
        mods = _events(lines[MODULES_LINE]) \
            if MODULES_LINE in lines else []
        chips.append((plane.name, ops, mods))
    if not chips:
        return None
    host = HostSpans(planes)
    per_chip, modules = [], {}
    own_total: "dict[str, int]" = {}
    idle_gaps: "list[list]" = []
    for i, (_name, ops, mods) in enumerate(chips):
        lo = min(ops[0][0], mods[0][0] if mods else ops[0][0])
        hi = max(max(e for _s, e, _n in ops),
                 max((e for _s, e, _n in mods), default=0))
        busy = union((s, e) for s, e, _n in ops)
        per_chip.append({
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "window_s": (hi - lo) / 1e9})
        for name, ns in self_times(ops).items():
            own_total[name] = own_total.get(name, 0) + ns
        if i == 0:
            for s, e, name in mods:
                m = modules.setdefault(
                    _RUN_ID.sub("", name),
                    {"count": 0, "total_s": 0.0, "starts_s": []})
                m["count"] += 1
                m["total_s"] += (e - s) / 1e9
                m["starts_s"].append((s - lo) / 1e9)
            idle_gaps = idle_by_host_span(_gaps(busy, lo, hi),
                                          host, top)
    n = len(chips)
    device_ops = sorted(own_total.items(), key=lambda kv: -kv[1])
    return {
        "chips": n,
        "busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "window_s": sum(c["window_s"] for c in per_chip) / n,
        "per_chip": per_chip,
        "modules": modules,
        "device_ops": [[name[:80], ns / 1e9 / n]
                       for name, ns in device_ops[:top]],
        "idle_gaps": idle_gaps,
    }


def reduce_trace(path: str, top: int = 10) -> "dict | None":
    """``path``: an ``.xplane.pb`` file or a directory holding one."""
    import jax.profiler
    if os.path.isdir(path):
        path = find_xplane(path)
    return reduce_profile(
        jax.profiler.ProfileData.from_file(path), top=top)
