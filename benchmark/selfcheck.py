"""The benchmark's own arithmetic and data files, checked on the CPU
in seconds: ``python3 benchmark/selfcheck.py``. Touches no device and
describes no TPU topology. Exit 0 when every check passed.

It checks what a later PR can break by adding files: every data file
against the harness's schema and ``BENCHMARK.json`` against them; the
traffic generator's determinism; ``flops.py`` against the published
counts; the trace reduction against a trace recorded on a v5e
(``fixtures/v5e_mlp6.xplane.pb``, six steps of a small MLP) whose busy
time, top operations and gaps were worked out by hand; the table of
peaks refusing a device it does not know.
"""

from __future__ import annotations

import glob
import importlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import flops, harness, traffic       # noqa: E402

ONE_LINE = 200
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj",
               "n_embd", "n_inner", "head", "width", "expansion")


def _names(kind: str, root: str) -> "list[str]":
    return sorted(os.path.basename(f)[:-5] for f in
                  glob.glob(os.path.join(root, kind, "*.json")))


def _line(text, what: str):
    if not isinstance(text, str) or not 1 <= len(text) <= ONE_LINE \
            or "\n" in text or "\t" in text:
        raise AssertionError(f"{what}: not one line of 1..{ONE_LINE}")


def check_data_files(root: str = harness.BENCH_DIR) -> dict:
    """Every cell loads with everything it names; returns the cells."""
    cells = {}
    for name in _names("workloads", root):
        loaded = harness.load_cell(name, root)
        cell, cfg = loaded["cell"], loaded["config"]
        assert cell["name"] == name, f"{name}: file and name differ"
        assert cfg["name"] == cell["config"]
        assert loaded["traffic"]["name"] == cell["traffic"]
        assert loaded["traffic"]["driver"] == cell["driver"]
        assert cell["chips"] in (1, 4), f"{name}: chips"
        _line(cell["why"], f"{name}.why")
        for key in (name, cell["config"], cell["traffic"]):
            assert harness.NAME.match(key), f"bad name {key!r}"
        assert os.path.isfile(os.path.join(
            harness.BENCH_DIR, "drivers", cell["driver"] + ".py"))
        assert "setup_s" in cell["end_to_end"] and \
            len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        for key in cfg["reduced"]:
            assert harness.NAME.match(key), f"{name}: reduced {key}"
            # a measured cell never cuts a width; the tests' toys do
            assert root != harness.BENCH_DIR or not (any(
                w in key for w in WIDTH_WORDS) or key.endswith(
                    ("_dim", "_rank"))), f"{name}: reduced width {key}"
        for m in cell["end_to_end"]:
            assert loaded["metrics"][m]["kind"] == "end_to_end"
        for m in cell["per_layer"]:
            d = loaded["metrics"][m]
            assert d["kind"] == "per_layer" and d["name"] == m
            _line(d["layer"], f"{m}.layer")
            assert d["moves"] in cell["end_to_end"], \
                f"{name}: {m} moves {d['moves']}, which it lacks"
            mod, fn = d["reader"].split(":")
            reader = importlib.import_module(
                f"benchmark.readers.{mod}")
            assert callable(getattr(reader, fn)), d["reader"]
            if "roofline" in m or "mfu" in m:
                assert d["unit"] == "%"
        assert set(cell["limits"]), f"{name}: no limit of correct"
        cells[name] = loaded
    return cells


def check_manifest(cells: dict):
    """``BENCHMARK.json`` says what the data files say."""
    bm = harness.load_json(harness.REPO_DIR, "BENCHMARK.json")
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["benchmark"]
    assert isinstance(bm["run_seconds"], int) and \
        1 <= bm["run_seconds"] <= 51
    assert {w["name"] for w in bm["workloads"]} == set(cells)
    for w in bm["workloads"]:
        cell = cells[w["name"]]["cell"]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for key in ("config", "traffic", "chips", "why"):
            assert w[key] == cell[key], f"{w['name']}.{key}"
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    assert four <= max(1, len(bm["workloads"]) // 4)
    used = {w["config"] for w in bm["workloads"]}
    assert {c["name"] for c in bm["configs"]} == used
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = harness.load_json(harness.REPO_DIR, c["file"])
        assert cfg["name"] == c["name"] and \
            cfg["reduced"] == c["reduced"]
        _line(c["why"], c["name"] + ".why")
        _line(c["source"], c["name"] + ".source")
    seen = set()
    for kind in ("end_to_end", "per_layer"):
        for m in bm[kind]:
            d = harness.load_named(harness.BENCH_DIR, "metrics",
                                   m["name"])
            assert m["name"] not in seen
            seen.add(m["name"])
            assert harness.UNIT.match(m["unit"])
            want = {"name", "unit", "better", "source"} | (
                {"bound"} if kind == "end_to_end"
                else {"layer", "moves"})
            assert want <= set(m) <= want | {"workloads"}, m["name"]
            for key in want - {"bound"}:
                assert m[key] == d[key], f"{m['name']}.{key}"
            users = sorted(n for n, c in cells.items()
                           if m["name"] in c["cell"][kind])
            assert users, f"{m['name']}: no cell reports it"
            if kind == "end_to_end":
                assert 0.01 <= m["bound"] <= 0.1
                assert m["source"] in ("host_clock", "device_trace")
            if "workloads" in m or len(users) != len(cells):
                assert sorted(m["workloads"]) == users, m["name"]
    for name, c in cells.items():
        for m in c["cell"]["end_to_end"] + c["cell"]["per_layer"]:
            assert m in seen, f"{name}: {m} is not in BENCHMARK.json"


def check_traffic(cells: dict):
    """The same seed gives the same inputs, another seed the same
    sizes in another order with other contents."""
    for name, c in cells.items():
        if c["cell"]["driver"] != "generate":
            continue
        mix, vocab = c["traffic"], c["config"]["vocab_size"]
        a = traffic.requests(mix, 7, vocab)
        assert a == traffic.requests(mix, 7, vocab), name
        b = traffic.requests(mix, 2 ** 31 + 8, vocab)
        assert a != b, name
        size = lambda r: (len(r["prompt"]), r["max_new_tokens"])
        assert sorted(map(size, a)) == sorted(map(size, b)), name
        assert [size(r) for r in a] != [size(r) for r in b], name
        for p, o in map(size, a):
            assert p + o <= mix["context_max"] and p >= 1 and o >= 1
            assert o <= 256      # the batcher's default budget cap
    x, y = traffic.images(7, 6, 8, 3, 10, chunk=2)
    x2, y2 = traffic.images(7, 4, 8, 3, 10, chunk=2, threads=1)
    assert (x[:4] == x2).all() and (y[:4] == y2).all()
    x3, _ = traffic.images(8, 4, 8, 3, 10, chunk=2)
    assert (x2 != x3).any()
    assert len({row.tobytes() for row in x}) == len(x)


def check_flops():
    r50 = harness.load_named(harness.BENCH_DIR, "configs",
                             "resnet50-imagenet-b128")
    macs = flops.resnet_forward_macs(r50)
    assert abs(macs - 4.09e9) < 0.005e9, macs      # 4.089 G
    assert abs(2 * macs - 8.18e9) < 0.01e9
    assert flops.resnet_train_step_flops(r50, 128) == 6.0 * macs * 128
    xl = harness.load_named(harness.BENCH_DIR, "configs", "gpt2-xl")
    p = flops.transformer_params(xl)
    assert abs(p["total"] - 1.557e9) < 0.001e9, p["total"]
    assert flops.kv_bytes_per_token(xl, 2) == 307200
    # a decode step moves at least the weights: 3.1 GB in bfloat16
    assert 3.10e9 < flops.decode_step_min_bytes(xl, 0, 2, 2) < 3.12e9


def check_reduction():
    from benchmark.reduce import trace
    red = trace.reduce_trace(os.path.join(
        harness.BENCH_DIR, "fixtures", "v5e_mlp6.xplane.pb"))
    # by hand: six executions of jit_step, the union of whose
    # operations covers 361,213 ns of the 23,040,122 ns from the
    # first operation's start to the last one's end
    assert red["chips"] == 1
    assert abs(red["busy_s"] - 361213e-9) < 1e-12, red["busy_s"]
    assert abs(red["window_s"] - 23040122e-9) < 1e-12
    assert red["modules"]["jit_step"]["count"] == 6
    top = [(n.split(" ")[0], round(s * 1e9)) for n, s in
           red["device_ops"][:3]]
    assert top == [("fusion.2", 143092),
                   ("convolution_multiply_fusion", 142753),
                   ("fusion.1", 75116)], top
    # the longest gap, 21,144,562 ns, lies under the probe's
    # host_sleep annotation; the second, 540,775 ns, under "train"
    gaps = dict(red["idle_gaps"])
    assert gaps["host_sleep"] >= 21144562e-9
    assert abs(gaps["train"] - 540775e-9) < 1e-12, gaps
    idle = red["window_s"] - red["busy_s"]
    assert abs(sum(gaps.values()) - idle) < 1e-9
    assert trace.union([(5, 9), (1, 3), (2, 4), (9, 10)]) == \
        [(1, 4), (5, 10)]
    assert trace.self_times([(0, 10, "loop"), (1, 4, "a"),
                             (5, 9, "a")]) == {"loop": 3, "a": 7}


def check_peaks():
    assert harness.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        harness.peak_for("TPU v9 imaginary")
    except KeyError:
        return
    raise AssertionError("an unknown device kind was given a peak")


def main() -> int:
    cells = check_data_files()
    check_manifest(cells)
    check_traffic(cells)
    check_flops()
    check_reduction()
    check_peaks()
    print(f"selfcheck: {len(cells)} cells, all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
