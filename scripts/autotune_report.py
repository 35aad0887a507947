"""Autotune cache report + sweep driver (`make autotune`).

Three jobs, one process (one backend init):

- default: render the current decision table — every cached/default
  entry for this device, its winning config vs the analytic heuristic,
  and the measured delta when the entry came from a sweep;
- ``--sweep``: populate the cache for the bench shapes (the
  attention crossover key lengths) by routing each through
  ``autotune.decide`` with ``ZOO_TPU_AUTOTUNE=1`` semantics — the
  one-time search cost;
- ``--emit-defaults``: freeze the current entries into the committed
  per-device table ``perf/autotune_defaults/<device>.json``,
  stamping ``--round`` into the table header.

Usage:
  python scripts/autotune_report.py                      # table
  ZOO_TPU_AUTOTUNE=1 python scripts/autotune_report.py --sweep [--tiny]
  python scripts/autotune_report.py --emit-defaults --round chip_YYYYMMDD
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# sweep work-list: (op, params, dtype) per bench shape: PERF.md's
# attention crossover ladder.
_ATTN_T = [256, 512, 1024, 2048, 4096]
_TINY_ATTN_T = [128, 256]


def sweep_keys(tiny: bool):
    """The (op, params, dtype) work-list `--sweep` resolves."""
    ts = _TINY_ATTN_T if tiny else _ATTN_T
    keys = []
    for t in ts:
        keys.append(("attn_crossover", {"tk": t}, "any"))
        keys.append(("decode_crossover", {"tk": t}, "any"))
    return keys


def _register_ops():
    """Import the ops modules that register specs (registration is an
    import-time side effect of each decision point's owner)."""
    from analytics_zoo_tpu.ops import (  # noqa: F401
        attention, flash_attention)


def run_sweep(tiny: bool) -> int:
    from analytics_zoo_tpu.perf import autotune
    _register_ops()
    if autotune.sweep_enabled() < 1:
        print("# ZOO_TPU_AUTOTUNE is not set -- decisions will NOT "
              "be swept, only resolved", flush=True)
    cache = autotune.get_cache()
    keys = sweep_keys(tiny)
    for i, (op, params, dtype) in enumerate(keys):
        cfg = cache.decide(op, params, dtype)
        print(f"[{i + 1}/{len(keys)}] {op} {params} -> {cfg}",
              flush=True)
    s = cache.stats()
    print(f"# sweeps={s['sweeps']} hits={s['cache_hits']} "
          f"misses={s['cache_misses']}", flush=True)
    return 0


def render_table(out=sys.stdout) -> int:
    from analytics_zoo_tpu.perf import autotune
    _register_ops()
    cache = autotune.get_cache()
    entries = cache.entries()
    print(f"# autotune table · device={cache.device} · "
          f"cache={cache.path}", file=out)
    if not entries:
        print("(empty -- run `make autotune` with ZOO_TPU_AUTOTUNE=1 "
              "to populate)", file=out)
        return 0
    hdr = (f"{'key':<58} {'source':<9} {'winner':<28} "
           f"{'heuristic':<28} {'delta'}")
    print(hdr, file=out)
    print("-" * len(hdr), file=out)
    for key in sorted(entries):
        e = entries[key]
        cfg = json.dumps(e.get("config"), sort_keys=True)
        heur = ""
        try:
            heur = json.dumps(
                autotune.heuristic(e["op"], e["params"]),
                sort_keys=True)
        except Exception:
            pass
        ms, hms = e.get("ms"), e.get("heuristic_ms")
        if ms is not None and hms:
            delta = f"{(1.0 - ms / hms) * 100.0:+.1f}% vs heur"
        elif ms is not None:
            delta = f"{ms:.3f}ms"
        else:
            delta = "(not timed)"
        mark = "=" if heur and cfg == heur else "*"
        print(f"{key:<58} {e.get('source', '?'):<9} "
              f"{mark}{cfg:<27} {heur:<28} {delta}", file=out)
    print(f"(* tuned differs from heuristic, = matches; "
          f"{len(entries)} entries)", file=out)
    return 0


def emit_defaults(round_label: str, device: str = None) -> int:
    from analytics_zoo_tpu.perf import autotune
    cache = autotune.get_cache()
    device = device or cache.device
    path = os.path.join(
        os.path.dirname(os.path.abspath(autotune.__file__)),
        "autotune_defaults", f"{device}.json")
    entries = {}
    for key, e in sorted(cache.entries().items()):
        out = {k: v for k, v in e.items() if k != "source"}
        entries[key] = out
    payload = {"schema": autotune.SCHEMA_VERSION, "device": device,
               "round": round_label, "entries": entries}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)
    print(f"wrote {len(entries)} entries -> {path} "
          f"(round={round_label})")
    return 0


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--sweep", action="store_true",
                   help="resolve (and, with ZOO_TPU_AUTOTUNE=1, "
                        "sweep) the bench-shape work-list first")
    p.add_argument("--tiny", action="store_true",
                   help="CPU-sized work-list (smoke/interpret mode)")
    p.add_argument("--emit-defaults", action="store_true",
                   help="freeze current entries into the committed "
                        "perf/autotune_defaults/<device>.json table")
    p.add_argument("--device", default=None,
                   help="defaults-table device override")
    p.add_argument("--round", default="unstamped",
                   help="round label stamped into --emit-defaults")
    args = p.parse_args()

    rc = 0
    if args.sweep:
        rc = run_sweep(args.tiny)
    if args.emit_defaults:
        rc = emit_defaults(args.round, args.device) or rc
    render_table()
    return rc


if __name__ == "__main__":
    sys.exit(main())
