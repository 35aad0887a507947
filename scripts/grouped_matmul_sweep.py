#!/usr/bin/env python3
"""The routed experts' grouped product, `ops/grouped_matmul.py`'s
kernel against `jax.lax.ragged_dot`, at the shapes of the benchmark's
expert programs: the decode steps of DeepSeek-V2 (96 rows, 40 held
experts), MiMo-V2-Flash (128, 16), dots3-note (64, 32) and Nemotron-H
(576, 16), and the 2048-token blocks of a chunk or prompt (16384 rows
over 32 or 16 experts, 12288 over 16 or 40). The held rows are those
of even routing (rows x held / all experts), spread over the held
experts at random. ``python3 scripts/grouped_matmul_sweep.py
[out.json]`` prints one JSON line a (case, implementation): the
milliseconds a call, the experts' bytes over 819 GB/s as a share of
that time, and the largest difference from `ragged_dot` on the held
rows; the kernel at the row tile `grouped_tiles` picks and at the
other row tiles. On a TPU it runs each case; elsewhere it only
compiles each case for a described v5e.

How `ops.grouped_matmul.grouped_tiles` was chosen: PERF.md section 6.
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

from analytics_zoo_tpu.ops import grouped_matmul as gm      # noqa: E402

HBM_BYTES_PER_S = 819e9         # TPU v5e (benchmark/peaks.json)
# (case, rows, held experts, all experts, k, n)
CASES = [
    ("deepseek_step_gate", 96, 40, 160, 5120, 1536),
    ("deepseek_step_down", 96, 40, 160, 1536, 5120),
    ("mimo_step_gate", 128, 16, 256, 4096, 2048),
    ("dots3_step_gate", 64, 32, 256, 5120, 1536),
    ("nemotron_step_up", 576, 16, 128, 2688, 1856),
    ("nemotron_step_down", 576, 16, 128, 1856, 2688),
    ("dots3_block_gate", 16384, 32, 256, 5120, 1536),
    ("mimo_block_gate", 16384, 16, 256, 4096, 2048),
    ("nemotron_block_up", 12288, 16, 128, 2688, 1856),
    ("deepseek_prompt_gate", 12288, 40, 160, 5120, 1536),
]


def main(argv) -> int:
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    rows_out = []
    for name, m, held, total, k, n in CASES:
        rs = np.random.RandomState(m + held)
        sizes = rs.multinomial(m * held // total,
                               np.ones(held) / held).astype(np.int32)
        reached = int((sizes > 0).sum())
        if on_chip:
            x = jnp.asarray(rs.randn(m, k), jnp.bfloat16)
            w = jnp.asarray(rs.randn(held, k, n) * 0.05, jnp.bfloat16)
            s = jnp.asarray(sizes)
        else:
            x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one)
            w = jax.ShapeDtypeStruct((held, k, n), jnp.bfloat16,
                                     sharding=one)
            s = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one)
        tm0, tn = gm.grouped_tiles(m, k, n, held, 2)
        fns = {"ragged_dot": lambda x, w, s: jax.lax.ragged_dot(x, w, s)}
        for tm in (16, 32, 64, 128):
            if m % tm == 0:
                fns[f"kernel_tm{tm}" + ("*" if tm == tm0 else "")] = \
                    lambda x, w, s, tm=tm: gm._grouped_matmul(
                        x, w, s, tm=tm, tn=tn, interpret=False)
        ref = None
        for impl, fn in fns.items():
            row = {"case": name, "rows": m, "experts": held, "k": k,
                   "n": n, "held_rows": int(sizes.sum()),
                   "reached": reached, "impl": impl}
            try:
                run = jax.jit(fn).lower(x, w, s).compile()
                if on_chip:
                    out = jax.block_until_ready(run(x, w, s))
                    t0 = time.perf_counter()
                    for _ in range(20):
                        out = run(x, w, s)
                    jax.block_until_ready(out)
                    sec = (time.perf_counter() - t0) / 20
                    row["ms"] = sec * 1e3
                    row["roofline_pct"] = 100.0 * reached * k * n * 2 / \
                        HBM_BYTES_PER_S / sec
                    o = np.asarray(out[:int(sizes.sum())], np.float32)
                    if ref is None:
                        ref = o
                    row["max_abs_diff"] = float(np.max(np.abs(o - ref),
                                                       initial=0.0))
                    row["ref_max"] = float(np.max(np.abs(ref),
                                                  initial=0.0))
            except Exception as e:           # noqa: BLE001
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            rows_out.append(row)
            print(json.dumps(row), flush=True)
    if argv:
        os.makedirs(os.path.dirname(argv[0]) or ".", exist_ok=True)
        with open(argv[0], "w", encoding="utf-8") as f:
            json.dump(rows_out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
