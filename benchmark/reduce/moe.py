"""The grouped matrix product's operations under the program's
scopes again. The TPU compiler turns `jax.lax.ragged_dot` into its
own kernel and names it ``ragged-dot-<n>``, dropping the ``zoo:``
scope it was traced under; the expert layers' main operations would
then count as unscoped. A decode step's grouped products have
``slots * experts_per_token`` rows and a prefill's have more (a
prompt bucket is never shorter than one token a slot, and every
bucket the cells reach is longer), so the rows tell the two apart.
"""

from __future__ import annotations

import re

_ROWS = re.compile(r"= \(?\w+\[(\d+),")


def rescoped(names: "dict[str, str]", decode_rows: int
             ) -> "dict[str, str]":
    """``names`` ({operation event name: op_name}, from
    `reduce.program.op_names`) with every ``ragged-dot`` operation
    under ``zoo:decode/moe_experts`` or ``zoo:prefill/moe_experts``."""
    out = dict(names)
    for event, op in names.items():
        if not op.startswith("ragged-dot"):
            continue
        m = _ROWS.search(event)
        decode = m is not None and int(m.group(1)) == decode_rows
        out[event] = "zoo:%s/moe_experts" % (
            "decode" if decode else "prefill")
    return out
