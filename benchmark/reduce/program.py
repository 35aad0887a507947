"""The program's own names in a profiler trace: idle time by the
``zoo:`` span the host was in, and device time by the ``zoo:`` scope
an operation was traced under.

Every span the program opens is also a profiler annotation named
``zoo:<span>`` on a host thread (`common/tracing.py`), and device work
is traced under ``jax.named_scope("zoo:<area>/<what>")``, which the
compiler keeps in each operation's ``op_name``. The trace stores that
string once per operation, as the ``tf_op`` stat of the event's
metadata, which ``jax.profiler.ProfileData`` does not hand out: it is
read here from the file's own bytes (`op_names`), a dozen lines of
protobuf wire format, and joined to the events by their name (the
operation's whole HLO line).

A program without such spans and scopes (the parent of the PR that
brought them) reduces to ``unattributed`` and ``unscoped`` alone.
"""

from __future__ import annotations

import os
import re

import numpy as np

from benchmark.reduce import trace

PREFIX = "zoo:"
SCOPE = re.compile(r"zoo:([\w.\-]+/[\w.\-]+)")
UNATTRIBUTED, UNSCOPED = "unattributed", "unscoped"
OUTSIDE = "outside_host_trace"


def scope_of(op_name: "str | None") -> str:
    """The innermost program scope on an operation's name stack:
    ``jit(f)/while/body/zoo:decode/layer/zoo:kv_cache/append/scatter``
    is ``kv_cache/append``."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


# -- the file's own bytes: event name -> op_name ----------------------

def _varint(buf, i: int) -> "tuple[int, int]":
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of one message: an int for a varint,
    (start, end) for a length-delimited field; fixed-width fields
    are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield num, val
        elif wire == 2:
            n, i = _varint(buf, i)
            yield num, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _plane_op_names(buf, lo: int, hi: int) -> "dict[str, str]":
    """One XPlane: {event metadata name: its ``tf_op`` stat}. XPlane:
    2 name, 4 event_metadata (map entry: 2 value), 5 stat_metadata;
    XEventMetadata: 2 name, 4 display_name, 5 stats; XStat: 1
    metadata_id, 5 str_value, 7 ref_value (a stat_metadata id whose
    name is the string); XStatMetadata: 1 id, 2 name."""
    name, stat_names, metas = "", {}, []
    for num, val in _fields(buf, lo, hi):
        if num == 2:
            name = _text(buf, val)
        elif num == 4:
            metas.extend(v for n, v in _fields(buf, *val) if n == 2)
        elif num == 5:
            for n, v in _fields(buf, *val):
                if n == 2:
                    sm = dict(_fields(buf, *v))
                    stat_names[sm.get(1, 0)] = _text(buf, sm[2]) \
                        if 2 in sm else ""
    if not trace.DEVICE_PLANE.match(name):
        return {}
    out = {}
    for meta in metas:
        names, op = [], None
        for num, val in _fields(buf, *meta):
            if num in (2, 4):
                names.append(_text(buf, val))
            elif num == 5:
                stat = dict(_fields(buf, *val))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                if 5 in stat:
                    op = _text(buf, stat[5])
                elif 7 in stat:
                    op = stat_names.get(stat[7])
        if op:
            out.update((n, op) for n in names if n)
    return out


def op_names(path: str) -> "dict[str, str]":
    """{operation event name: op_name} over the file's TPU planes
    (XSpace: 1 planes)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: "dict[str, str]" = {}
    for num, val in _fields(buf, 0, len(buf)):
        if num == 1:
            out.update(_plane_op_names(buf, *val))
    return out


# -- idle time by program span ----------------------------------------

class ProgramSpans(trace.HostSpans):
    """The host's ``zoo:`` annotations, all of them or only those
    that wait (``*_wait``) or only those that work."""

    def __init__(self, host: trace.HostSpans,
                 waits: "bool | None" = None):
        keep = [i for i, n in enumerate(host.names)
                if n.startswith(PREFIX)
                and waits in (None, n.endswith("_wait"))]
        self.starts, self.ends = host.starts[keep], host.ends[keep]
        self.names = [host.names[i][len(PREFIX):] for i in keep]

    def covered(self, gap) -> int:
        """Nanoseconds of the gap that lie under any of the spans."""
        a, b = gap
        hit = (self.ends > a) & (self.starts < b)
        return sum(e - s for s, e in trace.union(zip(
            np.maximum(self.starts[hit], a).tolist(),
            np.minimum(self.ends[hit], b).tolist())))


class _Blame:
    """Who gets a gap. Under no program span for more than half its
    length (host and device clocks of one capture can disagree by a
    millisecond or two, so a neighbour's edge is not cover):
    ``unattributed``. Else the working span that covers most of it,
    the shorter on a tie, as `HostSpans.blame` has it; a ``*_wait``
    span says who waited, not what was being done meanwhile, and
    gets a gap only where working spans cover less than half."""

    def __init__(self, planes):
        self.all = trace.HostSpans(planes)
        self.any = ProgramSpans(self.all)
        self.work = ProgramSpans(self.all, waits=False)
        self.waits = ProgramSpans(self.all, waits=True)

    def blame(self, gap) -> str:
        half = (gap[1] - gap[0]) / 2
        if self.any.covered(gap) < half:
            return UNATTRIBUTED
        if self.work.covered(gap) >= half:
            return self.work.blame(gap)
        return self.waits.blame(gap)


def reduce_program(profile, names: "dict[str, str]",
                   named: int = 2000) -> "dict | None":
    """``idle_by_span``: idle seconds of the chip-0 plane by the
    program span that covers most of each gap (the shorter on a tie;
    ``unattributed`` for none; gaps past the ``named`` longest summed
    as ``shorter_gaps``; ``outside_host_trace`` for what lies before
    the host's first event or after its last). ``scope_s``: device own-time by innermost
    program scope, a mean over the chips (``unscoped`` for none).
    None where the trace holds no TPU plane with operations."""
    planes = list(profile.planes)
    chips, mods = [], []
    for plane in planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if trace.OPS_LINE in lines:
            ops = trace._events(lines[trace.OPS_LINE])
            if ops:
                chips.append(ops)
                if len(chips) == 1 and trace.MODULES_LINE in lines:
                    mods = trace._events(lines[trace.MODULES_LINE])
    if not chips:
        return None
    # the window `reduce_profile` takes: first start to last end of
    # the chip-0 plane's operations and programs
    ops = chips[0]
    lo = min([ops[0][0]] + [s for s, _e, _n in mods[:1]])
    hi = max([e for _s, e, _n in ops] + [e for _s, e, _n in mods])
    busy = trace.union((s, e) for s, e, _n in ops)
    gaps = trace._gaps(busy, lo, hi)
    # the device's part of a capture starts before and stops after
    # the host's: a gap out there is under no span because nothing
    # was recording, and says nothing about the program
    blame = _Blame(planes)
    if len(blame.all.starts):
        h_lo, h_hi = int(blame.all.starts.min()), int(blame.all.ends.max())
        inside = [(max(a, h_lo), min(b, h_hi)) for a, b in gaps]
        inside = [(a, b) for a, b in inside if b > a]
    else:
        inside = []
    outside = sum(b - a for a, b in gaps) - sum(b - a for a, b in inside)
    idle = trace.idle_by_host_span(inside, blame, top=10 ** 9,
                                   named=named)
    if outside:
        idle.append([OUTSIDE, outside / 1e9])
    scope_ns: "dict[str, int]" = {}
    for ops in chips:
        for name, ns in trace.self_times(ops).items():
            scope = scope_of(names.get(name))
            scope_ns[scope] = scope_ns.get(scope, 0) + ns
    n = len(chips)
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_by_span": {UNATTRIBUTED: 0.0,
                         **{name: s for name, s in idle}},
        "scope_s": {k: v / 1e9 / n for k, v in sorted(
            scope_ns.items(), key=lambda kv: -kv[1])},
    }


def reduce_program_trace(path: str) -> "dict | None":
    """``path``: an ``.xplane.pb`` file or a directory holding one."""
    import jax.profiler
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    return reduce_program(jax.profiler.ProfileData.from_file(path),
                          op_names(path))
