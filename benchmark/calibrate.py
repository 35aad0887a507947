"""The readings the limits of ``correct`` are set from, read on the
chip at a cell's own size: ``python3 benchmark/calibrate.py --workload
<cell> --seeds 1,2,3 [--seconds s]``.

For every seed, in one process: what sound runs of the program read
against the plain reference (the lower readings), what the control
reads (the reference in float8, put in the program's place) and, for a
training cell, what the planted faults read (half of the batch left
out; a step that returns its state unchanged). Prints one JSON
line a seed and writes them all to ``chiprun_out/calibrate/``. Never
part of a benchmark run; sets nothing itself.
"""

import time
_T0 = time.perf_counter()

import argparse                     # noqa: E402
import functools                    # noqa: E402
import gc                           # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import harness       # noqa: E402


@functools.lru_cache(maxsize=None)
def _reference_step(cfg_json: str, quant: bool):
    """One jitted reference step a configuration and precision, kept
    over the seeds so that each compiles once a process."""
    from benchmark.reference import resnet
    return resnet.make_step(json.loads(cfg_json), quant)


def train_readings(loaded: dict, seed: int, devices) -> dict:
    from benchmark.drivers import train
    cfg, mix = loaded["config"], loaded["traffic"]
    cfg_json = json.dumps(cfg, sort_keys=True)
    plain = _reference_step(cfg_json, False)
    k = int(mix["check_steps"])
    est, x, y, batch, flat = train.build(loaded, seed, devices,
                                         n_batches=k)
    prog = train.first_steps(est, x, y, batch, k)
    prog["p0"] = flat
    est.params = est.opt_state = None
    del est
    gc.collect()
    refr = train.reference_steps(cfg, flat, x, y, batch, k, step=plain)
    sides = {
        "program": prog,
        "control_f8": train.reference_steps(
            cfg, flat, x, y, batch, k,
            step=_reference_step(cfg_json, True)),
        "fault_half_batch": train.reference_steps(
            cfg, flat, x, y, batch, k, rows_per_batch=batch // 2,
            step=plain),
        "fault_state_unchanged": train.reference_steps(
            cfg, flat, x, y, batch, k, step=plain, frozen=True)}
    return {name: train.compare(loaded["cell"]["limits"], side,
                                refr).as_dict()
            for name, side in sides.items()}


def generate_readings(loaded: dict, seed: int, devices,
                      seconds: float) -> dict:
    from benchmark.drivers import generate
    res = generate.run(loaded, seed=seed, seconds=seconds,
                       trace=False, devices=devices,
                       t0=time.perf_counter(), control=True)
    limit = loaded["cell"]["limits"]["logit_gap"]
    return {"program": res["compared"].as_dict(),
            "control_f8": {"logit_gap": {
                "value": res["notes"].get("control_logit_gap"),
                "limit": limit}},
            "attempted": res["attempted"], "failed": res["failed"],
            "checked_tokens": res["notes"]["checked_tokens"],
            "end_to_end": res["end_to_end"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--root", default=harness.BENCH_DIR)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="a rehearsal of the script, not a reading")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.allow_cpu:
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    loaded = harness.load_cell(args.workload, args.root)
    devices = devices[:int(loaded["cell"]["chips"])]
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if loaded["cell"]["driver"] == "train":
            r = train_readings(loaded, seed, devices)
        else:
            r = generate_readings(loaded, seed, devices, args.seconds)
        r.update(seed=seed, seconds=round(time.perf_counter() - t, 1),
                 platform=devices[0].platform)
        out.append(r)
        print(json.dumps(r), flush=True)
        gc.collect()
    dest = os.path.join(harness.REPO_DIR, "chiprun_out", "calibrate")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, args.workload + ".jsonl"), "a",
              encoding="utf-8") as f:
        for r in out:
            f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
