"""Bench and smoke contract tests: the scripts are one process each,
name the device they ran on, and cannot end in exit 0 when a leg
failed or no chip was found. Runs on CPU with tiny sizes; nothing
here is a measurement."""

import json
import os
import subprocess
import sys
import time

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json_lines(stdout: str):
    recs = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            recs.append(json.loads(line))  # every line must parse
    return recs


def _run(args, timeout, **env):
    return subprocess.run(
        [sys.executable] + args, capture_output=True, text=True,
        timeout=timeout, cwd=_ROOT, env=dict(os.environ, **env))


_TINY_BENCH = dict(ZOO_TPU_BENCH_BATCH="2",
                   ZOO_TPU_BENCH_IMAGE="64", ZOO_TPU_BENCH_STEPS="2",
                   ZOO_TPU_BENCH_NCF_BATCH="64")


def test_bench_live_carries_both_workloads_and_model_mfu():
    # one record carries the ResNet headline, the NCF workload and
    # model-FLOPs MFU alongside the XLA-FLOPs number — and names the
    # device, labelled a smoke because the platform was asked for
    out = _run([os.path.join(_ROOT, "bench.py")], 420,
               ZOO_TPU_BENCH_PLATFORM="cpu", **_TINY_BENCH)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = _json_lines(out.stdout)[-1]
    assert rec["value"] > 0
    assert rec["mfu_model_flops"] > 0
    assert rec["mfu_xla_flops"] > 0
    assert rec["vs_baseline_model_flops"] is not None
    assert rec["device"]["platform"] == "cpu"
    assert rec["device"]["kind"] == "cpu"
    assert "not a chip measurement" in rec["device"]["smoke"]
    # executed-vs-model FLOPs ratio of the measured XLA graph: > 1,
    # the strided convolutions' backward multiplies dilation zeros
    assert rec["flops_ratio_executed_vs_model"] > 1.0
    assert "variant" not in rec
    extras = {m["metric"]: m for m in rec["extra_metrics"]}
    assert extras["ncf_train_samples_per_sec_per_chip"]["value"] > 0


@pytest.mark.parametrize("script", ["bench.py", "bench_ncf.py",
                                    "bench_bert.py"])
def test_bench_refuses_to_measure_without_a_chip(script):
    # JAX_PLATFORMS=cpu and no explicit bench platform: the run must
    # fail before measuring anything, not fall back to the CPU
    env = {k: v for k, v in os.environ.items()
           if k != "ZOO_TPU_BENCH_PLATFORM"}
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, script)],
        capture_output=True, text=True, timeout=120, cwd=_ROOT,
        env=dict(env, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "measures on a TPU" in out.stderr
    assert _json_lines(out.stdout) == []


def test_bench_exits_nonzero_when_a_leg_raises():
    # the NCF leg dies (batch 0 divides by nothing useful): the run
    # must end non-zero, and no final record may claim the legs ran
    out = _run([os.path.join(_ROOT, "bench.py")], 420,
               ZOO_TPU_BENCH_PLATFORM="cpu",
               **dict(_TINY_BENCH, ZOO_TPU_BENCH_NCF_BATCH="boom"))
    assert out.returncode != 0
    assert "ValueError" in out.stderr
    assert all("extra_metrics" not in r
               for r in _json_lines(out.stdout))


def test_bench_ncf_emits_json_line():
    out = _run([os.path.join(_ROOT, "bench_ncf.py")], 300,
               ZOO_TPU_BENCH_PLATFORM="cpu",
               ZOO_TPU_BENCH_NCF_BATCH="64", ZOO_TPU_BENCH_STEPS="2")
    assert out.returncode == 0, out.stderr[-2000:]
    recs = _json_lines(out.stdout)
    assert len(recs) == 1
    rec = recs[0]
    assert rec["metric"] == "ncf_train_samples_per_sec_per_chip"
    assert rec["unit"] == "samples/sec"
    assert rec["value"] > 0
    assert rec["vs_baseline"] is None
    assert rec["device"]["platform"] == "cpu"
    assert "smoke" in rec["device"]


def test_time_chain_counts_execution_not_just_dispatch():
    # `return elapsed, fetch()` evaluates the elapsed time BEFORE the
    # blocking fetch and times only the async dispatch (~ms) of a
    # multi-second program. The measured dt must be within a factor
    # of the fully-blocked wall time.
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench_common import time_chain

    def step(p, _):
        g = jnp.tanh(p @ p.T) @ p
        return p - 1e-3 * g, jnp.sum(g)

    def run(p):
        pf, ls = jax.lax.scan(step, p, None, length=4)
        return pf, ls[-1]

    p = jnp.asarray(np.random.RandomState(0).randn(800, 800),
                    jnp.float32)
    compiled = jax.jit(run).lower(p).compile()
    jax.block_until_ready(compiled(p))  # warm
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(p))
        walls.append(time.perf_counter() - t0)
    # the least of three: on a host shared with five other test
    # workers one slow blocked run made `wall` three times the
    # program's time and failed a sound `time_chain` (PR 34's run)
    wall = min(walls)
    dt, loss = time_chain(compiled, (p,), reps=2)
    assert np.isfinite(loss)
    assert dt > 0.3 * wall, \
        f"time_chain measured {dt:.4f}s vs blocked wall {wall:.4f}s"


# -- one installation: no platform rewrite at import ------------------

def test_package_import_leaves_the_platform_choice_alone():
    # importing the package neither reads nor rewrites JAX's platform
    # selection: a programmatic pin made before the import stands, and
    # JAX_PLATFORMS=cpu alone is all a CPU run needs
    code = (
        "import jax\n"
        "before = jax.config.jax_platforms\n"
        "import analytics_zoo_tpu\n"
        "assert jax.config.jax_platforms == before, "
        "jax.config.jax_platforms\n"
        "print('PLATFORM', jax.devices()[0].platform, before)\n")
    out = _run(["-c", code], 120, JAX_PLATFORMS="cpu")
    assert out.returncode == 0, (out.stdout + out.stderr)[-2000:]
    assert "PLATFORM cpu cpu" in out.stdout


def test_on_tpu_is_true_only_for_platform_tpu(monkeypatch):
    import jax

    from analytics_zoo_tpu.common import device
    assert device.on_tpu() is False          # the CPU test mesh
    for backend, want in (("tpu", True), ("cpu", False),
                          ("gpu", False), ("TPU", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert device.on_tpu() is want


# -- compile cache placement ------------------------------------------

_CACHE_PROBE = (
    "import jax\n"
    "import analytics_zoo_tpu as zoo\n"
    "zoo.init_nncontext(log_level='WARNING')\n"
    "print('CACHE_DIR', jax.config.jax_compilation_cache_dir)\n")


def test_compile_cache_env_set_means_code_sets_nothing(tmp_path):
    # JAX reads JAX_COMPILATION_CACHE_DIR itself; init_nncontext must
    # leave the config exactly there
    where = str(tmp_path / "cc")
    out = _run(["-c", _CACHE_PROBE], 120,
               JAX_COMPILATION_CACHE_DIR=where)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"CACHE_DIR {where}" in out.stdout


def test_compile_cache_unset_means_fixed_in_checkout_path():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], capture_output=True,
        text=True, timeout=120, cwd=_ROOT, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    want = os.path.join(_ROOT, ".zoo_tpu_cache", "xla")
    assert f"CACHE_DIR {want}" in out.stdout
    # fixed: no temp name, pid or time in it, and git ignores it
    from analytics_zoo_tpu.common import device
    assert device.DEFAULT_COMPILE_CACHE == want
    ignored = open(os.path.join(_ROOT, ".gitignore")).read().split()
    assert ".zoo_tpu_cache/" in ignored


def test_setup_compile_cache_returns_the_env_dir(monkeypatch):
    import jax

    from analytics_zoo_tpu.common import device
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert device.setup_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_no_script_sets_a_cache_dir_in_code():
    # the one place that may name a cache directory is common/device
    offenders = []
    for dirpath, dirs, files in os.walk(_ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d != "__pycache__"]
        for f in files:
            if not f.endswith(".py"):
                continue
            p = os.path.join(dirpath, f)
            rel = os.path.relpath(p, _ROOT)
            if rel in ("analytics_zoo_tpu/common/device.py",
                       "tests/test_bench.py"):
                continue
            if "jax_compilation_cache_dir" in open(p).read():
                offenders.append(rel)
    assert offenders == []


# -- the native library is built from its sources or not used -----------

def test_stale_native_binary_is_not_loaded(tmp_path, monkeypatch):
    from analytics_zoo_tpu import native

    src = tmp_path / "host_arena.cpp"
    src.write_text("// newer than the binary\n")
    so = tmp_path / "libzoo_native.so"
    so.write_bytes(b"not a library")
    old = time.time() - 3600
    os.utime(so, (old, old))
    monkeypatch.setattr(native, "_SRCS", [str(src)])
    monkeypatch.setattr(native, "_SO_PATH", str(so))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    assert native._stale()
    # the rebuild fails (the "source" defines nothing loadable, or
    # does not compile): the stale binary must NOT be loaded instead
    monkeypatch.setattr(native, "_build", lambda: False)
    assert native.load_native() is None
    assert native._build_failed is True
    # a binary at least as new as its sources is not stale
    os.utime(so, None)
    assert not native._stale()


def test_native_build_is_atomic_and_from_committed_sources():
    from analytics_zoo_tpu import native
    assert [os.path.basename(s) for s in native._SRCS] == [
        "host_arena.cpp", "serving_queue.cpp", "serving_http.cpp"]
    tracked = subprocess.run(
        ["git", "ls-files", "analytics_zoo_tpu/native/src"],
        capture_output=True, text=True, cwd=_ROOT).stdout.split()
    if tracked:  # a git checkout: sources tracked, binary never
        assert not [t for t in tracked if t.endswith(".so")]
        assert {os.path.basename(t) for t in tracked} >= {
            "host_arena.cpp", "serving_queue.cpp", "serving_http.cpp"}
    assert native.load_native() is not None
    assert not native._stale()
    assert not [f for f in os.listdir(native._NATIVE_DIR)
                if ".tmp." in f]


# -- chip_smoke.py ------------------------------------------------------

def test_chip_smoke_without_a_chip_runs_nothing_and_fails():
    out = _run([os.path.join(_ROOT, "chip_smoke.py")], 120,
               JAX_PLATFORMS="cpu")
    assert out.returncode == 2
    assert out.stdout == ""            # no result of any kind
    assert "found no TPU" in out.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(_ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env=env)
    assert out.returncode not in (0, 2)
    assert out.stdout == ""
    assert "No module named 'analytics_zoo_tpu'" in out.stderr


@pytest.fixture(scope="module")
def rehearsal():
    return _run([os.path.join(_ROOT, "chip_smoke.py"), "--rehearse"],
                1200, JAX_PLATFORMS="cpu")


def test_chip_smoke_rehearsal_exit_code_says_no_chip(rehearsal):
    # every phase passed, no chip: 2, distinct from a failed phase (1)
    assert rehearsal.returncode == 2, \
        (rehearsal.stdout[-3000:] + rehearsal.stderr[-3000:])
    assert '"ok": true' not in rehearsal.stdout


@pytest.mark.parametrize("phase", ["train", "serve", "generate",
                                   "kernels"])
def test_chip_smoke_rehearsal_runs_every_phase(rehearsal, phase):
    recs = {r.get("phase"): r for r in _json_lines(rehearsal.stdout)}
    assert recs[phase]["passed"] is True, recs[phase]


def test_chip_smoke_rehearsal_lines_say_what_ran(rehearsal):
    recs = _json_lines(rehearsal.stdout)
    by = {r.get("phase"): r for r in recs}
    # the last line is exactly the contract's two keys
    assert recs[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu",
        "count": recs[-1]["device"]["count"]}}
    assert by["summary"]["failed"] == []
    assert by["summary"]["rehearsal"] is True
    train = by["train"]
    assert train["held_loss"][1] < train["held_loss"][0]
    assert train["logits_rel_err_vs_f32_cpu"] <= 5e-2
    assert set(train["compile_s"]) == {"cold", "warm"}
    assert by["serve"]["front_end"] == "NativeInferenceServer"
    gen = by["generate"]
    assert gen["front_end"] == "NativeInferenceServer"
    n, total = gen["tokens_equal_reference_argmax"].split("/")
    assert n == total                     # exact on the CPU
    assert gen["train_step"]["attention"] == "flash"
    kern = by["kernels"]
    assert kern["interpret"] is True      # the Pallas interpreter
    # the flash attention family, the chunk kernel under
    # `masked_attention` and the two paged decode kernels are every
    # Pallas kernel there is
    assert len(kern["cases"]) == 9
    assert all(c["passed"] for c in kern["cases"])
    assert sorted({c["kernel"].split("_")[0] for c in kern["cases"]}
                  ) == ["flash", "masked", "paged"]
    assert "fused_resnet50_step" not in kern
