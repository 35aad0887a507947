"""Persistent kernel autotuner (perf/autotune.py, docs/autotune.md).

Covers the decision precedence (flag > cache > defaults > heuristic),
the sweep→persist→reload lifecycle, steady-state guarantees (hit path
sweeps nothing, recompiles nothing), the committed defaults tables'
heuristic-consistency (merging the tuner changed no behavior), and
conformance: every candidate config in every op's sweep space must
produce the same VALUES as the heuristic pick — tuning may change
speed, never numerics.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import attention, flash_attention
from analytics_zoo_tpu.perf import autotune


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    """A fresh singleton against a tmp cache path; sweeping off."""
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    monkeypatch.delenv("ZOO_TPU_AUTOTUNE", raising=False)
    autotune.reset_cache()
    yield autotune.get_cache()
    autotune.reset_cache()


def _plant(path, key, config, op="attn_crossover", params=None):
    payload = {"schema": autotune.SCHEMA_VERSION, "entries": {
        key: {"op": op, "params": params or {}, "dtype": "any",
              "config": config, "source": "sweep"}}}
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh)


# -- registration & heuristics ----------------------------------------------

def test_all_ops_registered():
    assert autotune.registered_ops() == [
        "attn_crossover", "decode_crossover", "flash_blocks"]


def test_crossover_heuristics_unchanged(tuner):
    """The pre-tuner constants, verbatim (PERF.md crossovers)."""
    assert not attention.flash_profitable(512)
    assert attention.flash_profitable(1024)
    assert not attention.decode_flash_profitable(1024)
    assert attention.decode_flash_profitable(2048)


def test_block_heuristics_unchanged(tuner):
    for tq, tk, isz in [(256, 256, 2), (1024, 2048, 4),
                        (512, 384, 2)]:
        assert flash_attention._pick_blocks(tq, tk, isz) == \
            flash_attention._heuristic_blocks(tq, tk, isz)


def test_candidates_include_heuristic_first(tuner):
    p = {"tq": 512, "tk": 256, "isz": 2}
    cands = autotune.candidates("flash_blocks", p)
    assert len(cands) > 1
    assert cands[0] == autotune.heuristic("flash_blocks", p)
    seen = [json.dumps(c, sort_keys=True) for c in cands]
    assert len(seen) == len(set(seen)), "candidates must deduplicate"
    assert len(cands) <= autotune.SWEEP_MAX_CANDIDATES


# -- precedence -------------------------------------------------------------

def test_flag_overrides_cache(tuner, monkeypatch):
    """A set legacy flag bypasses the tuner verbatim — even against a
    contradicting cached winner (source='flag' semantics)."""
    key = autotune.make_key("attn_crossover", {"tk": 512}, "any",
                            tuner.device)
    tuner._entries[key] = {"config": {"use_flash": False},
                           "source": "sweep"}
    monkeypatch.setenv("ZOO_TPU_FLASH_MIN_T", "256")
    assert attention.flash_profitable(512)      # flag wins
    monkeypatch.delenv("ZOO_TPU_FLASH_MIN_T")
    assert not attention.flash_profitable(512)  # cache now serves


def test_forced_outranks_flag(tuner, monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FLASH_MIN_T", "4096")
    with autotune.forced("attn_crossover", {"use_flash": True}):
        assert attention.flash_profitable(128)
    assert not attention.flash_profitable(128)


def test_cached_entry_served_over_heuristic(tuner):
    key = autotune.make_key("decode_crossover", {"tk": 512}, "any",
                            tuner.device)
    tuner._entries[key] = {"config": {"use_flash": True},
                           "source": "sweep"}
    assert attention.decode_flash_profitable(512)
    assert tuner.hits == 1


def test_unknown_op_without_entry_raises(tuner):
    with pytest.raises(KeyError):
        tuner.decide("no_such_op", {"x": 1})


# -- committed defaults tables ----------------------------------------------

@pytest.mark.parametrize("device", ["cpu", "v5e"])
def test_defaults_tables_heuristic_consistent(device):
    """The shipped tables are heuristic-seeded: config == the op's
    analytic pick at the stored params, so merging the tuner changed
    no behavior until a chip session refreshes them."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(autotune.__file__)),
        "autotune_defaults", f"{device}.json")
    with open(path, encoding="utf-8") as fh:
        table = json.load(fh)
    assert table["schema"] == autotune.SCHEMA_VERSION
    assert table["entries"], "table must not ship empty"
    for key, e in table["entries"].items():
        assert key.endswith(f"|{device}"), key
        assert e["config"] == autotune.heuristic(e["op"],
                                                 e["params"]), key


@pytest.mark.parametrize("device", ["cpu", "v5e"])
def test_tables_hold_no_entry_for_an_unregistered_op(device):
    """An entry whose op no module registers is served to nobody
    and its config checked by nothing (the conv_bn_* entries of the
    deleted fused kernels were 18 of each table's 28)."""
    cache = autotune.AutotuneCache(path=os.devnull, device=device)
    assert cache.entries()
    assert {e["op"] for e in cache.entries().values()} <= \
        set(autotune.registered_ops())


def test_defaults_table_loaded_as_defaults_source(tmp_path,
                                                  monkeypatch):
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "none.json"))
    autotune.reset_cache()
    cache = autotune.get_cache()
    entry_sources = {e.get("source")
                     for e in cache.entries().values()}
    # the committed cpu table is present on the CPU test mesh
    assert entry_sources == {"defaults"}
    autotune.reset_cache()


def test_disk_cache_overrides_defaults(tmp_path, monkeypatch):
    """A swept winner beats a shipped default for the same key."""
    path = tmp_path / "at.json"
    cache0 = autotune.AutotuneCache(path=str(path), device="cpu")
    key = next(iter(cache0.entries()))
    e = cache0.entries()[key]
    _plant(str(path), key, {"planted": True}, op=e["op"],
           params=e["params"])
    cache = autotune.AutotuneCache(path=str(path), device="cpu")
    assert cache.entries()[key]["config"] == {"planted": True}
    assert cache.entries()[key]["source"] == "cache"


# -- sweep lifecycle --------------------------------------------------------

# two candidates, (256, 128) and (128, 128), each timed under the
# Pallas interpreter
_TINY = {"tq": 256, "tk": 128, "isz": 2}


def test_sweep_persist_reload_hit(tmp_path, monkeypatch):
    path = str(tmp_path / "at.json")
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE_CACHE", path)
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE", "1")
    autotune.reset_cache()
    cfg = autotune.decide("flash_blocks", dict(_TINY))
    cache = autotune.get_cache()
    assert cache.sweeps == 1
    assert os.path.exists(path)
    with open(path, encoding="utf-8") as fh:
        on_disk = json.load(fh)
    assert on_disk["schema"] == autotune.SCHEMA_VERSION
    [entry] = [e for e in on_disk["entries"].values()
               if e["op"] == "flash_blocks"]
    assert entry["config"] == cfg
    assert entry["params"] == _TINY
    assert entry["ms"] > 0
    # "reload": a fresh cache object (new process stand-in), sweep OFF
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE", "0")
    autotune.reset_cache()
    assert autotune.decide("flash_blocks", dict(_TINY)) == cfg
    c2 = autotune.get_cache()
    assert (c2.hits, c2.misses, c2.sweeps) == (1, 0, 0)
    autotune.reset_cache()


def test_mode2_resweeps_once_per_process(tmp_path, monkeypatch):
    path = str(tmp_path / "at.json")
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE_CACHE", path)
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE", "1")
    autotune.reset_cache()
    autotune.decide("flash_blocks", dict(_TINY))
    assert autotune.get_cache().sweeps == 1
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE", "2")
    autotune.reset_cache()                    # entry now from disk
    autotune.decide("flash_blocks", dict(_TINY))
    cache = autotune.get_cache()
    assert cache.sweeps == 1                  # re-swept despite entry
    autotune.decide("flash_blocks", dict(_TINY))
    assert cache.sweeps == 1                  # once per process only
    assert cache.hits == 1
    autotune.reset_cache()


def test_sweep_skipped_inside_trace(tmp_path, monkeypatch):
    """decide() under an active jit trace must fall back, never
    sweep (sweeping launches its own compiles)."""
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE", "1")
    autotune.reset_cache()
    p = {"tq": 384, "tk": 128, "isz": 2}

    @jax.jit
    def traced(x):
        cfg = autotune.decide("flash_blocks", dict(p))
        return x * cfg["bq"]

    traced(jnp.ones(()))
    assert autotune.get_cache().sweeps == 0
    autotune.reset_cache()


def test_sweep_counters_and_span(tmp_path, monkeypatch):
    from analytics_zoo_tpu.common import observability as obs
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE", "1")
    autotune.reset_cache()
    autotune.decide("flash_blocks", dict(_TINY))
    snap = obs.snapshot()
    assert sum(v["value"] for v in
               snap["zoo_tpu_autotune_sweeps_total"]["values"]) == 1
    assert sum(v["value"] for v in
               snap["zoo_tpu_autotune_misses_total"]["values"]) >= 1
    # the sweep ran under an "autotune/sweep" span -> its wall-time
    # histogram exists and observed exactly one sweep
    assert "zoo_tpu_autotune_sweep_seconds" in snap
    autotune.decide("flash_blocks", dict(_TINY))
    snap = obs.snapshot()
    assert sum(v["value"] for v in
               snap["zoo_tpu_autotune_hits_total"]["values"]) == 1
    autotune.reset_cache()


def _toy_spec(fail):
    """A two-candidate op whose ``fail``-named configs raise on their
    first (compiling) call, the way a refused kernel does."""
    def runner(params, cfg):
        def run():
            if cfg["name"] in fail:
                raise RuntimeError(f"RESOURCE_EXHAUSTED: vmem "
                                   f"({cfg['name']})")
        return run
    return autotune.OpSpec(
        "toy_failing_op", heuristic=lambda p: {"name": "heur"},
        candidates=lambda p: [{"name": "heur"}, {"name": "other"}],
        runner=runner)


def test_candidate_that_fails_to_compile_is_reported(
        tmp_path, monkeypatch, caplog):
    # counter + a log line naming op, config and error; the sweep
    # carries on with the candidates that did compile
    from analytics_zoo_tpu.common import observability as obs
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE", "1")
    # init_nncontext, where an earlier test of this worker called it,
    # has stopped the package logger handing records to caplog's root
    monkeypatch.setattr(autotune.logger, "propagate", True)
    autotune.reset_cache()
    autotune.register(_toy_spec(fail={"other"}))
    try:
        with caplog.at_level("WARNING", logger="analytics_zoo_tpu"):
            cfg = autotune.decide("toy_failing_op", {"n": 1})
        assert cfg == {"name": "heur"}
        assert autotune.get_cache().sweeps == 1
        failures = obs.counter(
            "zoo_tpu_autotune_candidate_failures_total",
            help="x").value
        assert failures == 1
        [line] = [r.getMessage() for r in caplog.records
                  if "failed to compile" in r.getMessage()]
        assert "toy_failing_op" in line
        assert "'other'" in line
        assert "RESOURCE_EXHAUSTED" in line
    finally:
        autotune._SPECS.pop("toy_failing_op", None)
        autotune.reset_cache()


def test_failing_heuristic_config_raises(tmp_path, monkeypatch):
    # every call site falls back to the heuristic config: if the
    # compiler refuses THAT one the sweep must not swallow it
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE", "1")
    autotune.reset_cache()
    autotune.register(_toy_spec(fail={"heur"}))
    try:
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            autotune.decide("toy_failing_op", {"n": 1})
        # and the sweep state is left usable afterwards
        assert autotune._tls.in_sweep is False
    finally:
        autotune._SPECS.pop("toy_failing_op", None)
        autotune.reset_cache()


def test_trace_clean_asks_jax_and_never_defaults():
    # jax 0.9.0 dropped jax.core.trace_state_clean; the replacement
    # is asked directly — inside a trace it says so, outside it does
    # not, and nothing answers "not clean" by default
    import jax
    assert autotune._trace_clean() is True
    seen = []

    @jax.jit
    def f(x):
        seen.append(autotune._trace_clean())
        return x
    f(1.0)
    assert seen == [False]
    assert "except" not in __import__("inspect").getsource(
        autotune._trace_clean)


def test_stats_block_shape(tuner):
    s = autotune.stats()
    assert set(s) == {"enabled", "cache_hits", "cache_misses",
                      "sweeps", "source"}
    assert s["enabled"] is False
    assert s["source"] == "none"
    attention.flash_profitable(512)
    assert autotune.stats()["source"] in ("defaults", "heuristic")


def test_persist_tolerates_unwritable_path(monkeypatch):
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE_CACHE",
                       "/proc/0/nope/at.json")
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE", "1")
    autotune.reset_cache()
    cfg = autotune.decide("flash_blocks", dict(_TINY))
    assert set(cfg) == {"bq", "bk"}    # swept in-process, no crash
    assert autotune.get_cache().sweeps == 1
    autotune.reset_cache()


# -- steady state: hit path sweeps nothing, recompiles nothing --------------

def test_zero_recompile_zero_sweep_soak(tmp_path, monkeypatch):
    """The compile-event-listener soak (tests/test_generate.py's
    pattern): warm one tuned flash call + the decision keys, then
    repeated tuned calls must trigger ZERO backend compiles and ZERO
    sweeps — the hit path is a dict lookup, not a search."""
    from jax import monitoring
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    monkeypatch.setenv("ZOO_TPU_AUTOTUNE", "1")
    autotune.reset_cache()
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 256, 2, 32), jnp.float32)
    compiles = []
    armed = [False]

    def listener(name, dur, **kw):
        if armed[0] and name.endswith("backend_compile_duration"):
            compiles.append(name)

    monitoring.register_event_duration_secs_listener(listener)
    fn = jax.jit(lambda q: flash_attention.flash_attention(
        q, q, q, causal=True))
    # warm EVERY key the soak will touch: the jit compile, plus one
    # decide() per key so first-sight sweeps (and their deliberate
    # probe compiles) all land here, not in the armed window
    jax.block_until_ready(fn(q))
    attention.flash_profitable(256)
    attention.decode_flash_profitable(256)
    cache = autotune.get_cache()
    base_sweeps = cache.sweeps
    armed[0] = True
    try:
        for _ in range(20):
            jax.block_until_ready(fn(q))
            attention.flash_profitable(256)
            attention.decode_flash_profitable(256)
    finally:
        armed[0] = False
    assert compiles == [], (
        f"steady-state tuned calls compiled {len(compiles)} times")
    assert cache.sweeps == base_sweeps, "steady state swept"
    autotune.reset_cache()


# -- conformance: tuning may change speed, never values ---------------------

def _flash_candidates():
    return autotune.candidates("flash_blocks",
                               {"tq": 256, "tk": 256, "isz": 4})


@pytest.mark.parametrize("cfg", _flash_candidates())
def test_flash_fwd_bwd_conformance(cfg, tuner):
    """Every flash block candidate == the heuristic pick's values
    (f32 tight tolerance: block size changes reduction order)."""
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 256, 2, 32) * 0.5, jnp.float32)
    k = jnp.asarray(rs.randn(1, 256, 2, 32) * 0.5, jnp.float32)
    v = jnp.asarray(rs.randn(1, 256, 2, 32) * 0.5, jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention.flash_attention(
            q, k, v, causal=True) ** 2)

    def run(c):
        with autotune.forced("flash_blocks", c):
            out = flash_attention.flash_attention(q, k, v,
                                                  causal=True)
            g = jax.grad(loss)(q, k, v)
        return np.asarray(out), np.asarray(g)

    heur = autotune.heuristic("flash_blocks",
                              {"tq": 256, "tk": 256, "isz": 4})
    out_h, g_h = run(heur)
    out_c, g_c = run(cfg)
    np.testing.assert_allclose(out_c, out_h, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(g_c, g_h, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("cfg", [{"use_flash": False},
                                 {"use_flash": True}])
def test_decode_attention_conformance(cfg, tuner, monkeypatch):
    """Both sides of the decode crossover produce the same values
    through the real decode_attention routing."""
    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    rs = np.random.RandomState(6)
    s, t, h, d = 2, 256, 2, 32
    q = jnp.asarray(rs.randn(s, h, d), jnp.float32)
    k = jnp.asarray(rs.randn(s, t, h, d), jnp.float32)
    v = jnp.asarray(rs.randn(s, t, h, d), jnp.float32)
    seq_lens = jnp.asarray([t, t // 2], jnp.int32)
    ref = attention.decode_attention(q, k, v, seq_lens, impl="xla")
    with autotune.forced("decode_crossover", cfg):
        out = attention.decode_attention(q, k, v, seq_lens,
                                         impl="auto")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("cfg", [{"use_flash": False},
                                 {"use_flash": True}])
def test_train_attention_crossover_conformance(cfg, tuner,
                                               monkeypatch):
    monkeypatch.setenv("ZOO_TPU_FLASH_FORCE_INTERPRET", "1")
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(1, 256, 2, 32) * 0.5, jnp.float32)
    k = jnp.asarray(rs.randn(1, 256, 2, 32) * 0.5, jnp.float32)
    v = jnp.asarray(rs.randn(1, 256, 2, 32) * 0.5, jnp.float32)
    ref = attention.dot_product_attention(q, k, v, causal=True,
                                          impl="xla")
    with autotune.forced("attn_crossover", cfg):
        out = attention.dot_product_attention(q, k, v, causal=True,
                                              impl="auto")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
