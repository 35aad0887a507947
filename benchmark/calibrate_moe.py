"""`calibrate.py`'s generate readings for a cell whose driver is not
``drivers/generate.py`` (that script names its driver): ``python3
benchmark/calibrate_moe.py --workload <cell> --seeds 1,2,3
[--seconds s]``. For every seed, one run of the cell's own driver with
``control=True``: what the program reads against the plain reference
and what the float8 control reads over the same sample. One JSON line
a seed, all appended to ``chiprun_out/calibrate/``. Never part of a
benchmark run; sets nothing itself.
"""

import argparse
import gc
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import harness       # noqa: E402


def readings(loaded: dict, seed: int, devices, seconds: float) -> dict:
    driver = importlib.import_module(
        "benchmark.drivers." + loaded["cell"]["driver"])
    res = driver.run(loaded, seed=seed, seconds=seconds, trace=False,
                     devices=devices, t0=time.perf_counter(),
                     control=True)
    limits = loaded["cell"]["limits"]
    return {"program": res["compared"].as_dict(),
            "control_f8": {name: {
                "value": res["notes"].get("control_" + name),
                "limit": limits[name]}
                for name in ("logit_gap", "logit_gap_mean")},
            "attempted": res["attempted"], "failed": res["failed"],
            "checked_tokens": res["notes"]["checked_tokens"],
            "end_to_end": res["end_to_end"],
            "memory_peak_bytes": res["memory_peak_bytes"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate_moe: no TPU", file=sys.stderr)
        return 2
    loaded = harness.load_cell(args.workload)
    dest = os.path.join(harness.REPO_DIR, "chiprun_out", "calibrate")
    os.makedirs(dest, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = readings(loaded, seed, devices[:1], args.seconds)
        r.update(seed=seed, seconds=round(time.perf_counter() - t, 1))
        print(json.dumps(r), flush=True)
        with open(os.path.join(dest, args.workload + ".jsonl"), "a",
                  encoding="utf-8") as f:
            f.write(json.dumps(r) + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
