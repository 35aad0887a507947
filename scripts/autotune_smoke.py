"""`make autotune-smoke`: end-to-end autotuner lifecycle on CPU.

Orchestrates, against a throwaway cache path:

1. phase ``sweep`` (subprocess, ``ZOO_TPU_AUTOTUNE=1``): resolve two
   tiny flash_blocks shapes through the real `_pick_blocks` call
   site — first sight of each key sweeps (interpret-guarded
   candidates) and persists the winners;
2. phase ``reload`` (FRESH subprocess, ``ZOO_TPU_AUTOTUNE=1``): the
   same two keys must resolve as pure cache hits — zero sweeps, zero
   misses, asserted via the ``zoo_tpu_autotune_*`` counters — and the
   served configs must match what phase 1 persisted;
3. the report renders against the populated cache.

Exit 0 only when all three hold. Run directly (no args) for the full
orchestration; ``--phase sweep|reload`` is the subprocess entry.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# two CPU-sized shapes (interpret-mode Pallas budget)
_SHAPES = [
    {"tq": 256, "tk": 256, "isz": 2},
    {"tq": 128, "tk": 256, "isz": 2},
]


def _pick(p):
    from analytics_zoo_tpu.ops import flash_attention
    return list(flash_attention._pick_blocks(p["tq"], p["tk"],
                                             p["isz"]))


def _name(p):
    return f"{p['tq']}x{p['tk']}"


def _counter_value(name: str) -> float:
    from analytics_zoo_tpu.common import observability as obs
    fam = obs.snapshot().get(name)
    if not fam:
        return 0.0
    return sum(v.get("value", 0.0) for v in fam.get("values", []))


def phase_sweep() -> int:
    from analytics_zoo_tpu.perf import autotune
    assert autotune.sweep_enabled() >= 1, "phase runs under AUTOTUNE=1"
    picks = {_name(p): _pick(p) for p in _SHAPES}
    s = autotune.stats()
    assert s["sweeps"] == len(_SHAPES), \
        f"expected {len(_SHAPES)} sweeps, got {s['sweeps']}"
    assert _counter_value("zoo_tpu_autotune_sweeps_total") == \
        len(_SHAPES), "sweep counter disagrees"
    assert os.path.exists(os.environ["ZOO_TPU_AUTOTUNE_CACHE"]), \
        "cache file not persisted"
    print(json.dumps({"picks": picks}))
    return 0


def phase_reload(expect: dict) -> int:
    from analytics_zoo_tpu.perf import autotune
    for p in _SHAPES:
        got, want = _pick(p), expect[_name(p)]
        assert got == want, f"reloaded pick {got} != swept {want}"
    s = autotune.stats()
    assert s["sweeps"] == 0, f"fresh process re-swept: {s}"
    assert s["cache_misses"] == 0, f"expected pure hits: {s}"
    assert s["cache_hits"] == len(_SHAPES), f"expected hits: {s}"
    assert _counter_value("zoo_tpu_autotune_hits_total") == \
        len(_SHAPES), "hit counter disagrees"
    assert _counter_value("zoo_tpu_autotune_sweeps_total") == 0, \
        "sweep counter nonzero on reload"
    print("reload: pure cache hits")
    return 0


def orchestrate() -> int:
    here = os.path.abspath(__file__)
    with tempfile.TemporaryDirectory(prefix="zoo_tpu_at_smoke_") as d:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   ZOO_TPU_AUTOTUNE="1",
                   ZOO_TPU_AUTOTUNE_CACHE=os.path.join(d, "at.json"))
        out = subprocess.run(
            [sys.executable, here, "--phase", "sweep"], env=env,
            capture_output=True, text=True, timeout=600)
        sys.stderr.write(out.stderr)
        print(out.stdout, end="")
        if out.returncode != 0:
            print("FAIL: sweep phase", file=sys.stderr)
            return 1
        picks = json.loads(out.stdout.strip().splitlines()[-1])["picks"]
        out = subprocess.run(
            [sys.executable, here, "--phase", "reload",
             "--expect", json.dumps(picks)], env=env,
            capture_output=True, text=True, timeout=600)
        sys.stderr.write(out.stderr)
        print(out.stdout, end="")
        if out.returncode != 0:
            print("FAIL: reload phase", file=sys.stderr)
            return 1
        rep = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(here),
                          "autotune_report.py")],
            env=env, capture_output=True, text=True, timeout=600)
        if rep.returncode != 0 or "autotune table" not in rep.stdout:
            sys.stderr.write(rep.stderr)
            print("FAIL: report did not render", file=sys.stderr)
            return 1
        print("report renders "
              f"({len(rep.stdout.splitlines())} lines)")
    print("autotune-smoke OK")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=["sweep", "reload"])
    ap.add_argument("--expect", default="{}")
    args = ap.parse_args()
    if args.phase == "sweep":
        return phase_sweep()
    if args.phase == "reload":
        return phase_reload(json.loads(args.expect))
    return orchestrate()


if __name__ == "__main__":
    sys.exit(main())
