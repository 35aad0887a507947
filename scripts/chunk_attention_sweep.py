#!/usr/bin/env python3
"""The chunk attention kernel against the XLA body it sits on, at
dots3-note's two shapes (16 bf16 heads a call, 2048 queries): a full
layer (keys 192, values 128 wide; the chunk's own 2048 keys behind a
bucket of cached ones, a top-2048 among the causal keys) and a
sliding layer (keys 256; a window of 513 over a ring of 2576 and the
chunk). ``python3 scripts/chunk_attention_sweep.py [out.json]``
prints one JSON line a (shape, blocks) with the milliseconds a call
and the tiles run; on the chip through ``chiprun``. Off the TPU it
only compiles each case for a described v5e.

How `ops.flash_attention.chunk_blocks` was chosen: PERF.md section 6.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax                                        # noqa: E402
import jax.numpy as jnp                           # noqa: E402
import numpy as np                                # noqa: E402

from analytics_zoo_tpu.ops import attention as att          # noqa: E402
from analytics_zoo_tpu.ops import flash_attention as fa     # noqa: E402

C, H, DV = 2048, 16, 128
BLOCKS = [(256, 256), (512, 512), (512, 1024), (1024, 1024), (512, 2048)]


def full_mask(rs, t_ctx: int, starts: int, top_k: int = 2048):
    """(1, C, t_ctx + C): the bucket's keys before ``starts``, the
    chunk's own causally, about ``top_k`` of them a query."""
    q_pos = starts + np.arange(C)
    k_pos = np.concatenate([np.arange(t_ctx), q_pos])
    ok = np.concatenate([np.arange(t_ctx) < starts, np.ones(C, bool)])
    vis = ok[None, :] & (k_pos[None, :] <= q_pos[:, None])
    keep = rs.rand(C, t_ctx + C) * vis.sum(1, keepdims=True) < top_k
    return (vis & keep)[None]


def window_mask(starts: int, ring: int = 2576, window: int = 513):
    """(1, C, ring + C): the ring's view from the page that holds
    ``starts - window + 1`` on, then the chunk."""
    first = max(starts - window + 1, 0) // 16 * 16
    q_pos = starts + np.arange(C)
    k_pos = np.concatenate([first + np.arange(ring), q_pos])
    ok = np.concatenate([first + np.arange(ring) < starts,
                         np.ones(C, bool)])
    back = q_pos[:, None] - k_pos[None, :]
    return (ok[None, :] & (back >= 0) & (back < window))[None]


def cases():
    rs = np.random.RandomState(0)
    for t_ctx, starts in ((0, 0), (2048, 2048), (8192, 6144),
                          (32768, 24576)):
        yield f"full_{t_ctx}", 192, full_mask(rs, t_ctx, starts)
    yield "window", 256, window_mask(8192)


def main(argv) -> int:
    on_chip = jax.devices()[0].platform == "tpu"
    if on_chip:
        place = lambda shape, dt: jnp.asarray(
            np.random.RandomState(1).randn(*shape), dt)
    else:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        place = lambda shape, dt: jax.ShapeDtypeStruct(
            shape, dt, sharding=one)
    rows = []
    for name, d, mask in cases():
        t = mask.shape[-1]
        q = place((1, C, H, d), jnp.bfloat16)
        k = place((1, t, H, d), jnp.bfloat16)
        v = place((1, t, H, DV), jnp.bfloat16)
        m = jnp.asarray(mask) if on_chip else place(mask.shape,
                                                    jnp.bool_)
        fns = {"xla": lambda q, k, v, m: att._masked_attention_xla(
            q, k, v, m, 0.07)}
        for bq, bk in BLOCKS:
            fns[f"{bq}x{bk}"] = lambda q, k, v, m, bq=bq, bk=bk: \
                fa.masked_chunk_attention(q, k, v, m, 0.07, block_q=bq,
                                          block_k=bk, interpret=False)
        ref = None
        for impl, fn in fns.items():
            row = {"case": name, "keys": t, "impl": impl}
            if impl != "xla":
                bq, bk = map(int, impl.split("x"))
                occ = np.asarray(fa.mask_tiles(jnp.asarray(mask), bq, bk))
                row.update(tiles=int(occ.size), tiles_run=int(occ.sum()))
            try:
                run = jax.jit(fn).lower(q, k, v, m).compile()
                if on_chip:
                    out = jax.block_until_ready(run(q, k, v, m))
                    t0 = time.perf_counter()
                    for _ in range(5):
                        out = run(q, k, v, m)
                    jax.block_until_ready(out)
                    row["ms"] = (time.perf_counter() - t0) / 5 * 1e3
                    seen = mask[0].any(-1)
                    o = np.asarray(out.astype(jnp.float32))[0][seen]
                    if ref is None:
                        ref = o
                    row["max_abs_diff"] = float(np.max(np.abs(o - ref)))
            except Exception as e:           # noqa: BLE001
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            rows.append(row)
            print(json.dumps(row), flush=True)
    if len(argv) > 0:
        os.makedirs(os.path.dirname(argv[0]) or ".", exist_ok=True)
        with open(argv[0], "w", encoding="utf-8") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
