"""Executed-semantics FLOP accounting over HLO text.

Why not XLA's HloCostAnalysis: its conv handler DISCOUNTS window
positions that read padding or dilation-inserted zeros, so an
input-dilated backward conv (jax's transpose rule for a strided
conv's dx) is costed as if the hardware skipped the zeros. A
systolic conv unit does not skip them — it executes
``out_elems x window_taps x Cin`` MACs regardless of what the taps
read. That gap (~1.95x model on ResNet-50, a count and not a time)
is invisible to `cost_analysis()`; these counters make it visible.
The Estimator's goodput ledger reads them.

Counting rules (MXU ops only — vector/elementwise work is excluded,
which understates absolute FLOPs but leaves conv/dot ratios exact):

- ``convolution``: 2 x out_elems x effective_window_taps x kernel
  input-feature extent. Dilation zeros are EXECUTED, not skipped,
  on both sides: `lhs_dilate` inflates out_elems (a dilated dx
  produces the FULL-resolution gradient with the full kernel at
  every position — the s^2 waste), and `rhs_dilate` inflates the
  effective window to (size-1)*d+1 per dim (a dilated dw slides
  the full dilated footprint).
- ``dot``: 2 x out_elems x prod(lhs contracting extents).

FLOPs here are 2 x MACs (one multiply + one add). Beware the
torchvision/fvcore "GFLOPs" convention, which counts MACs:
ResNet-50's canonical 4.09e9 is MACs, i.e. 8.18e9 in this unit.

Parses both post-optimization HLO (``compiled.as_text()``) and
pre-optimization HLO (``lowered.compiler_ir(dialect="hlo")``), which
share the op syntax.
"""

from __future__ import annotations

import re
from typing import List, NamedTuple


class OpCost(NamedTuple):
    name: str
    kind: str        # "convolution" | "dot"
    flops: float
    detail: str      # shapes/window snippet for the audit printout


_DEF = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = [a-z0-9]+\[([0-9,]*)\]",
    re.M)
_CONV = re.compile(
    r"%?([\w.\-]+) = \S+?\[([0-9,]*)\][^=\n]*? convolution\((.*?)\)"
    r"(.*)")
_DOT = re.compile(
    r"%?([\w.\-]+) = \S+?\[([0-9,]*)\][^=\n]*? dot\((.*?)\), (.*)")


def _prod(dims: str) -> int:
    out = 1
    for d in dims.split(","):
        if d:
            out *= int(d)
    return out


def _split_operands(args: str) -> List[str]:
    """Split an operand list on top-level commas only (shape dims
    and layouts contain commas: ``f32[2,28,28,128]{3,2,1,0} %a``)."""
    out, depth, cur = [], 0, []
    for ch in args:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return out


def _operand_dims(args: str, defs) -> List[str]:
    """Per-operand dims: inline type when present (optimized HLO
    prints ``f32[...]{...} %name``), else the operand name resolved
    through the module's definition lines (unoptimized HLO prints
    bare names)."""
    out = []
    for entry in _split_operands(args):
        entry = entry.strip()
        if not entry:
            continue
        m = re.match(r"[a-z0-9]+\[([0-9,]*)\]", entry)
        if m:
            out.append(m.group(1))
            continue
        name = entry.split()[-1].lstrip("%")
        out.append(defs.get(name, ""))
    return out


def parse_hlo_ops(text: str) -> List[OpCost]:
    """All convolution/dot ops in an HLO module text with their
    executed-semantics FLOPs (each op counted once, like
    HloCostAnalysis — a scan body's cost is one trip's)."""
    defs = {m.group(1): m.group(2) for m in _DEF.finditer(text)}
    ops = []
    for m in _CONV.finditer(text):
        name, out_dims, args, attrs = m.groups()
        taps = 1
        wm = re.search(r"window=\{[^}]*size=([0-9x]+)", attrs)
        rd = re.search(r"rhs_dilate=([0-9x]+)", attrs)
        if wm:
            sizes = [int(d) for d in wm.group(1).split("x")]
            dil = ([int(d) for d in rd.group(1).split("x")]
                   if rd else [1] * len(sizes))
            for s, d in zip(sizes, dil):
                taps *= (s - 1) * d + 1
        lm = re.search(r"dim_labels=(\S+?)(?:[,\s]|$)", attrs)
        kin = 1
        shapes = _operand_dims(args, defs)
        if lm and len(shapes) >= 2 and shapes[1]:
            rhs = lm.group(1).split("_", 1)[1].split("-", 1)[0]
            if "i" in rhs:
                kin = int(shapes[1].split(",")[rhs.index("i")])
        ops.append(OpCost(
            name, "convolution", 2.0 * _prod(out_dims) * taps * kin,
            f"out=[{out_dims}] taps={taps} kin={kin}"
            f"{' ' + attrs.strip(', ')[:60] if attrs else ''}"))
    for m in _DOT.finditer(text):
        name, out_dims, args, attrs = m.groups()
        shapes = _operand_dims(args, defs)
        contract = 1
        cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", attrs)
        if cm and shapes and shapes[0]:
            ldims = shapes[0].split(",")
            for d in cm.group(1).split(","):
                if d:
                    contract *= int(ldims[int(d)])
        ops.append(OpCost(
            name, "dot", 2.0 * _prod(out_dims) * contract,
            f"out=[{out_dims}] lhs=[{shapes[0] if shapes else ''}] "
            f"contract={contract}"))
    return ops


def executed_flops(text: str) -> float:
    """Total executed-semantics MXU FLOPs of an HLO module text."""
    return sum(op.flops for op in parse_hlo_ops(text))


def top_ops(text: str, n: int = 10) -> List[OpCost]:
    return sorted(parse_hlo_ops(text), key=lambda o: -o.flops)[:n]


class PadWaste(NamedTuple):
    name: str
    role: str        # "lhs_f" | "rhs_i" | "rhs_o"
    extent: int
    util: float      # extent / lane-padded extent


def channel_padding(text: str, lane: int = 128) -> List[PadWaste]:
    """Convolution feature extents that are not multiples of the TPU
    lane width: the MXU zero-pads features to ``lane``, so such an
    op executes ``extent/ceil_lane(extent)`` useful work on that
    axis (ResNet's 3-channel stem: 3/128). Feed this the
    ``*after_optimizations*`` module of an ``--xla_dump_to`` dump to
    see what the layout passes actually left padded."""
    defs = {m.group(1): m.group(2) for m in _DEF.finditer(text)}
    out = []
    for m in _CONV.finditer(text):
        name, _, args, attrs = m.groups()
        lm = re.search(r"dim_labels=(\S+?)(?:[,\s]|$)", attrs)
        if not lm:
            continue
        lhs_l, rest = lm.group(1).split("_", 1)
        rhs_l = rest.split("-", 1)[0]
        shapes = _operand_dims(args, defs)
        roles = []
        if "f" in lhs_l and shapes and shapes[0]:
            roles.append(
                ("lhs_f",
                 int(shapes[0].split(",")[lhs_l.index("f")])))
        if len(shapes) >= 2 and shapes[1]:
            rdims = shapes[1].split(",")
            for ch, role in (("i", "rhs_i"), ("o", "rhs_o")):
                if ch in rhs_l:
                    roles.append((role, int(rdims[rhs_l.index(ch)])))
        for role, ext in roles:
            if ext % lane:
                padded = -(-ext // lane) * lane
                out.append(PadWaste(name, role, ext, ext / padded))
    return out


def hlo_text(obj) -> str:
    """HLO text from a jax Lowered/Compiled (or a plain string).
    Compiled ``as_text()`` is already HLO; Lowered ``as_text()`` is
    StableHLO, so go through ``compiler_ir(dialect="hlo")`` — no
    backend compile needed."""
    if isinstance(obj, str):
        return obj
    ir = getattr(obj, "compiler_ir", None)
    if ir is not None:
        try:
            return ir(dialect="hlo").as_hlo_text()
        except Exception:
            pass
    txt = obj.as_text()
    if "HloModule" not in txt.split("\n", 1)[0]:
        raise ValueError("could not extract HLO text "
                         f"from {type(obj).__name__}")
    return txt
