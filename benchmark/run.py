"""One run of one cell: ``python3 benchmark/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

Prints one JSON object as the last line of standard output, and every
number it compared beside its limit as the last lines of standard
error. Exits non-zero, with no result, where JAX finds no TPU, a TPU
that ``peaks.json`` does not know, or fewer chips than the cell asks
for.
"""

import time
_T0 = time.perf_counter()          # the process's start, for setup_s

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import harness       # noqa: E402


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             devices, t0: float, root: str = harness.BENCH_DIR) -> dict:
    """Drive one loaded cell on ``devices`` and return its result
    line. ``main`` has looked for the chip already; a test that hands
    in CPU devices gets a rehearsal, never a measurement."""
    loaded = harness.load_cell(name, root)
    chips = int(loaded["cell"]["chips"])
    if len(devices) < chips:
        raise RuntimeError(f"cell {name} needs {chips} chips, "
                           f"{len(devices)} present")
    devices = list(devices)[:chips]
    driver = loaded["cell"]["driver"]
    if not harness.NAME.match(driver):
        raise ValueError(f"bad driver name {driver!r}")
    mod = importlib.import_module(f"benchmark.drivers.{driver}")
    res = mod.run(loaded, seed=seed, seconds=seconds, trace=trace,
                  devices=devices, t0=t0)
    res["per_layer"] = harness.read_layers(loaded, res["layers"]) \
        if trace else {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": res["memory_peak_bytes"]}
    return harness.result_line(loaded, res, trace, device), res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    # small programs too: a warm run should compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing is measured on "
              f"anything else", file=sys.stderr)
        return 2
    harness.peak_for(devices[0].device_kind)     # unknown kind raises
    chips = int(harness.load_named(
        harness.BENCH_DIR, "workloads", args.workload)["chips"])
    if len(devices) < chips:
        print(f"benchmark: cell {args.workload} needs {chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    line, res = run_cell(args.workload, args.seed, args.seconds,
                         bool(args.trace), devices, _T0)
    sys.stdout.flush()
    res["compared"].print()
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
