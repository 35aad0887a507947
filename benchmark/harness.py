"""What every run of every cell shares: finding a cell's data files by
name, the table of peaks, the profiler window, the record of numbers
compared, the per-layer readers and the result line.

A cell is ``workloads/<cell>.json``; it names a configuration
(``configs/<config>.json``), a traffic mix (``traffic/<mix>.json``),
its driver (``drivers/<driver>.py``) and the metrics it reports
(``metrics/<metric>.json`` each, whose ``reader`` names a function
``<file>:<function>`` under ``readers/``). Adding any of them is adding
files; nothing here knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import re
import shutil
import sys
import threading
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter",
           "host_clock")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def load_named(root: str, kind: str, name: str) -> dict:
    """``<root>/<kind>/<name>.json``, or the benchmark's own where a
    test's root has none (its toy cells report the real metrics)."""
    if not NAME.match(name):
        raise ValueError(f"{kind} name {name!r} has characters a name "
                         "may not have")
    for base in (root, BENCH_DIR):
        path = os.path.join(base, kind, name + ".json")
        if os.path.isfile(path):
            return load_json(path)
    raise FileNotFoundError(f"no {kind} file {name}.json under {root}")


def load_cell(name: str, root: str = BENCH_DIR) -> dict:
    """A cell with everything it names: ``cell``, ``config``,
    ``traffic`` and ``metrics`` {name: definition}."""
    cell = load_named(root, "workloads", name)
    metrics = {m: load_named(root, "metrics", m)
               for m in cell["end_to_end"] + cell["per_layer"]}
    for m, d in metrics.items():
        if not UNIT.match(d["unit"]) or d["source"] not in SOURCES \
                or d["better"] not in ("lower", "higher"):
            raise ValueError(f"metric {m}: bad unit, source or better")
    return {"name": name, "cell": cell,
            "config": load_named(root, "configs", cell["config"]),
            "traffic": load_named(root, "traffic", cell["traffic"]),
            "metrics": metrics}


def peak_for(device_kind: str, root: str = BENCH_DIR) -> dict:
    """The chip's published peaks. A device that is not in the table
    is an error, never a default."""
    table = load_json(root, "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"(known: {sorted(table)}); no peak is assumed")
    return table[device_kind]


def peak_or_none(devices) -> "dict | None":
    """The peaks of the chip a run is on; None on the CPU, where a
    rehearsal reads no share of any peak."""
    if devices[0].platform != "tpu":
        return None
    return peak_for(devices[0].device_kind)


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest chip: the allocator's peak of
    live buffers plus its peak of memory reserved for the running
    programs' temporaries, which the v5e's runtime counts apart
    (0 where the backend keeps no such statistic, as the CPU)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)) +
                   int(stats.get("peak_bytes_reserved", 0)))
    return peak


class Tracer:
    """A profiler window inside a run, driven from a thread of its
    own so that the loop under test is never the one that waits for
    the profiler: ``arm()`` when the window opens, ``finish()`` once
    it has closed, then ``reduction()``. The trace lives under the
    checkout and is deleted as soon as it is reduced."""

    def __init__(self, cell_name: str, after_s: float, for_s: float):
        self.dir = os.path.join(REPO_DIR, ".bench_trace", cell_name)
        self.after_s, self.for_s = after_s, for_s
        self.wall_start = self.wall_stop = None  # epoch seconds
        self.on_start = self.on_stop = None    # drivers' snapshots
        self.reduce_s = None                   # reading the trace
        self._halt = threading.Event()
        self._thread = None

    def arm(self):
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-tracer")
        self._thread.start()

    def _run(self):
        import jax
        if self._halt.wait(self.after_s):
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host TraceMe spans only
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.wall_start = time.time()
        if self.on_start:
            self.on_start()
        self._halt.wait(self.for_s)
        if self.on_stop:
            self.on_stop()
        self.wall_stop = time.time()
        jax.profiler.stop_trace()

    def finish(self):
        """Stop a trace the window's end overtook, and wait for it."""
        self._halt.set()
        if self._thread is not None:
            self._thread.join()

    def reduction(self) -> "dict | None":
        from benchmark.reduce.trace import reduce_trace
        if self.wall_stop is None:
            return None
        t = time.perf_counter()
        try:
            return reduce_trace(self.dir)
        finally:
            self.reduce_s = time.perf_counter() - t
            shutil.rmtree(self.dir, ignore_errors=True)


def tracer_for(loaded: dict, seconds: float, trace: bool
               ) -> "Tracer | None":
    """A traced run's profiler window: the mix's ``trace_seconds``,
    from two fifths into the measured window."""
    if not trace:
        return None
    return Tracer(loaded["name"], 0.4 * seconds,
                  min(loaded["traffic"]["trace_seconds"], 0.4 * seconds))


class Compared:
    """Every number a run compares, beside its limit. A number that
    is not finite, or lies above its limit, makes the run not
    correct."""

    def __init__(self):
        self.rows: "list[tuple[str, float, float]]" = []

    def add(self, name: str, value: float, limit: float):
        self.rows.append((name, float(value), float(limit)))

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(
            math.isfinite(v) and v <= lim for _n, v, lim in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v if math.isfinite(v) else str(v),
                    "limit": lim} for n, v, lim in self.rows}

    def print(self):
        for n, v, lim in self.rows:
            verdict = "ok" if math.isfinite(v) and v <= lim else "OVER"
            print(f"compared {n}: {v:.6g} limit {lim:.6g} {verdict}",
                  file=sys.stderr)


def read_layers(loaded: dict, ctx: dict) -> dict:
    """The cell's per-layer metrics from the run's spans, counters
    and trace. A reader that finds nothing to read returns None and
    its metric is left out."""
    out = {}
    for name in loaded["cell"]["per_layer"]:
        d = loaded["metrics"][name]
        mod_name, fn_name = d["reader"].split(":")
        if not NAME.match(mod_name):
            raise ValueError(f"metric {name}: bad reader {d['reader']}")
        if not os.path.isfile(os.path.join(BENCH_DIR, "readers",
                                           mod_name + ".py")):
            raise FileNotFoundError(f"metric {name}: no reader file "
                                    f"readers/{mod_name}.py")
        mod = importlib.import_module(f"benchmark.readers.{mod_name}")
        value = getattr(mod, fn_name)(ctx, d.get("params", {}))
        if value is not None and math.isfinite(value):
            out[name] = {"value": float(value), "unit": d["unit"]}
    return out


def result_line(loaded: dict, res: dict, trace: bool, device: dict
                ) -> dict:
    """The one JSON object a run prints last."""
    if trace:
        metrics = res["per_layer"]
    else:
        metrics = {m: {"value": float(res["end_to_end"][m]),
                       "unit": loaded["metrics"][m]["unit"]}
                   for m in loaded["cell"]["end_to_end"]}
    line = {"correct": bool(res["compared"].ok),
            "attempted": int(res["attempted"]),
            "failed": int(res["failed"]),
            "metrics": metrics, "device": device}
    red = res["layers"].get("trace")
    if trace and red:
        line["device"]["busy_s"] = red["busy_s"]
        line["device"]["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    if res.get("notes"):
        line["notes"] = res["notes"]
    line["compared"] = res["compared"].as_dict()
    return line
