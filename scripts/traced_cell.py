#!/usr/bin/env python3
"""One traced run of a benchmark cell with what the result line
leaves out written to a file: ``python3 scripts/traced_cell.py <cell>
<seed> <seconds> <out.json> [metric ...]``.

It is ``benchmark/run.py --trace 1`` (same set-up, window, comparison
and last line of standard output) and imports the benchmark without
editing it. ``out.json`` holds the result line and, from the same
window: the program's spans by name (count, and mean, p50 and p95 of
the duration, of the distance from one start to the next and of every
numeric field), the counters' and histograms' change, the compiled
programs by module (count, mean device ms), idle seconds by program
span and device seconds by ``zoo:`` scope. Metrics named after
``out.json`` (files of ``benchmark/metrics/``) are read from the
run's context with the harness's own reader loop and written under
``extra_metrics``: how a metric that is in no cell's list yet gets
its first reading on the chip. What PERF.md section 5 calls "my
traced run" is this script's output.
"""

import time
_T0 = time.perf_counter()          # the process's start, for setup_s

import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import statistics                   # noqa: E402
import sys                          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import harness, run  # noqa: E402


def _stats(vals: "list[float]") -> dict:
    vals = sorted(vals)
    return {"mean": statistics.fmean(vals),
            "p50": vals[len(vals) // 2],
            "p95": vals[min(len(vals) - 1, int(0.95 * len(vals)))]}


def spans_by_name(spans: "list[dict]") -> dict:
    """{name: count, dur_s, start_gap_s and every numeric field, each
    as mean / p50 / p95}."""
    by_name: "dict[str, list[dict]]" = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    out = {}
    for name, recs in sorted(by_name.items()):
        recs.sort(key=lambda s: s["t_start"])
        row = {"count": len(recs),
               "dur_s": _stats([s["dur_s"] for s in recs])}
        if len(recs) > 1:
            row["start_gap_s"] = _stats(
                [b["t_start"] - a["t_start"]
                 for a, b in zip(recs, recs[1:])])
        fields: "dict[str, list[float]]" = {}
        for s in recs:
            for k, v in s["fields"].items():
                if isinstance(v, (int, float)) and \
                        not isinstance(v, bool):
                    fields.setdefault(k, []).append(float(v))
        row["fields"] = {k: _stats(v) for k, v in sorted(fields.items())}
        out[name] = row
    return out


def with_program(driver: str):
    """A driver that reduces its trace to the program's own names
    does so itself (``reduction`` in its module); for the others,
    `reduce/program.py`'s reduction is taken here, before the
    harness's own deletes the trace."""
    if hasattr(importlib.import_module(
            f"benchmark.drivers.{driver}"), "reduction"):
        return
    from benchmark.reduce import program
    plain = harness.Tracer.reduction

    def reduction(self):
        prog = None
        if self.wall_stop is not None and os.path.isdir(self.dir):
            prog = program.reduce_program_trace(self.dir)
        red = plain(self)
        if red is not None and prog is not None:
            red["program"] = prog
        return red
    harness.Tracer.reduction = reduction


def extra_metrics(names: "list[str]", layers: dict) -> dict:
    """Metric files read from the run's context although the cell
    does not list them."""
    listed = {"cell": {"per_layer": names},
              "metrics": {m: harness.load_named(
                  harness.BENCH_DIR, "metrics", m) for m in names}}
    return harness.read_layers(listed, layers)


def main(argv) -> int:
    if len(argv) < 4:
        print(__doc__, file=sys.stderr)
        return 2
    cell, seed, seconds, out_path = argv[0], int(argv[1]), \
        float(argv[2]), argv[3]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("traced_cell: JAX found no TPU; nothing is measured on "
              "anything else", file=sys.stderr)
        return 2
    with_program(harness.load_named(
        harness.BENCH_DIR, "workloads", cell)["driver"])
    line, res = run.run_cell(cell, seed, seconds, True, devices, _T0)
    layers = res["layers"]
    red = layers.get("trace") or {}
    prog = red.get("program") or {}
    out = {
        "cell": cell, "seed": seed, "seconds": seconds, "line": line,
        "end_to_end": res["end_to_end"],
        "extra_metrics": extra_metrics(argv[4:], layers),
        "spans": spans_by_name(layers.get("spans", [])),
        "counters": layers.get("counters", {}),
        "programs": {
            name: {"count": m["count"],
                   "mean_ms": 1e3 * m["total_s"] / m["count"]}
            for name, m in red.get("modules", {}).items()},
        "idle_by_span": prog.get("idle_by_span", {}),
        "scope_s": prog.get("scope_s", {}),
    }
    os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                exist_ok=True)
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1)
    res["compared"].print()
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
