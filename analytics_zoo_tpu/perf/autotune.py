"""Persistent kernel autotuner: sweep once, memoize to disk.

Every Pallas crossover in the tree used to be a hand-measured
constant — the `_pick_blocks` heuristic in
``ops/flash_attention.py`` and the dense-vs-flash gates in
``ops/attention.py``. This module replaces those constants with a
search-and-memoize layer in the AutoTVM/Ansor mold: measured configs
beat analytic heuristics, and a persistent cache makes the search a
one-time cost.

Decisions are keyed by ``(op, shape-signature, dtype, device-kind)``
and resolved in strict precedence order (docs/autotune.md):

1. ``forced()`` — thread-local test/sweep pin;
2. **flag** — the op's legacy ``ZOO_TPU_*`` env flag, honored
   verbatim when set (``source="flag"``; the tuner is bypassed, so
   flags are overrides, not requirements);
3. **cache** — a previously swept winner from the JSON cache
   (``ZOO_TPU_AUTOTUNE_CACHE``, default
   ``~/.cache/zoo_tpu/autotune.json``);
4. **defaults** — the committed per-device table in
   ``perf/autotune_defaults/<device>.json`` (cold starts without
   sweep budget still get tuned configs);
5. **heuristic** — the op's analytic fallback (the pre-tuner
   constants, verbatim).

Sweeping is opt-in: ``ZOO_TPU_AUTOTUNE=1`` sweeps a bounded
candidate set on first sight of a key (compile time excluded via
``diagnostics.expected_compiles()``), ``2`` force-resweeps each key
once per process, unset/``0`` never times anything. Sweeps never run
inside an active jax trace (``jax.core.trace_ctx.is_top_level``) — a
decision needed mid-trace falls back to cache/defaults/heuristic and
``make autotune`` populates the cache ahead of time at the bench
shapes. The heuristic config always competes in its own sweep and
wins ties within the noise margin, so a tuned pick is never slower
than the heuristic beyond noise *by construction*.

The steady-state hit path is one dict lookup — no locking; the lock
only guards sweep+persist. Persistence is atomic (tmp+rename) with a
versioned schema. Counters: ``zoo_tpu_autotune_hits_total`` /
``zoo_tpu_autotune_misses_total`` / ``zoo_tpu_autotune_sweeps_total``
plus an ``autotune/sweep`` span per sweep.

Op specs are registered by the ops modules themselves (so their
legacy env flags keep being *read* under ``ops/`` — the lint
``check_autotune_overrides`` gate cross-references those reads
against :data:`OVERRIDE_FLAGS` and docs/perf_flags.md in both
directions).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

logger = logging.getLogger("analytics_zoo_tpu")

__all__ = [
    "SCHEMA_VERSION", "OVERRIDE_FLAGS", "OpSpec", "AutotuneCache",
    "register", "registered_ops", "decide", "heuristic",
    "candidates", "forced", "get_cache", "reset_cache", "stats",
    "device_kind", "make_key", "sweep_enabled",
]

SCHEMA_VERSION = 1

# sweep budget: at most this many candidates timed per key, each
# best-of-SWEEP_REPS with the compile excluded; a non-heuristic
# winner must beat the heuristic by more than NOISE_MARGIN or the
# heuristic is kept (tuned is never slower than heuristic beyond
# noise, structurally)
SWEEP_MAX_CANDIDATES = 16
SWEEP_REPS = 3
NOISE_MARGIN = 0.02

# Every ZOO_TPU_* gate flag read under analytics_zoo_tpu/ops/, mapped
# to the autotuner op it overrides. A plain value means the op's spec
# consults the flag via ``flag_value`` (set -> tuner bypassed,
# source="flag"); an ``:pin`` suffix marks a flag that pins an
# implementation choice outside the tuner's sweep space (impl
# selectors, debug/kill switches) — registered here so the lint gate
# proves every ops/ gate is accounted for, in both directions.
# MUST stay a pure literal: scripts/lint.py ast.literal_eval's it.
OVERRIDE_FLAGS = {
    "ZOO_TPU_FLASH_MIN_T": "attn_crossover",
    "ZOO_TPU_DECODE_FLASH_MIN_T": "decode_crossover",
    "ZOO_TPU_ATTENTION": "attn_crossover:pin",
    "ZOO_TPU_FLASH_FORCE_INTERPRET": "attn_crossover:pin",
    "ZOO_TPU_MAXPOOL_MASK_BWD": "maxpool_bwd:pin",
}

_DEVICE_ALIASES = {
    "tpu-v5-lite": "v5e",
    "tpu-v5e": "v5e",
    "tpu-v5litepod": "v5e",
}


class OpSpec:
    """One tunable decision point.

    - ``heuristic(params) -> config``: the analytic pick (the
      pre-tuner constants, verbatim) — always a sweep candidate.
    - ``candidates(params) -> [config, ...]``: the bounded sweep
      space; must respect the op's own feasibility constraints
      (divisibility, dtype-aware VMEM caps).
    - ``flag_value(params) -> config | None``: the legacy env-flag
      override, or None when the flag is unset. Defined in the ops
      module so the env read stays under ``ops/``.
    - ``runner(params, config) -> callable | None``: builds a
      zero-arg blocking probe for timing, or None when this
      candidate cannot be timed here (e.g. interpreter budget
      off-chip) — the candidate is skipped.
    """

    __slots__ = ("name", "heuristic", "candidates", "flag_value",
                 "runner")

    def __init__(self, name: str,
                 heuristic: Callable[[dict], dict],
                 candidates: Optional[
                     Callable[[dict], List[dict]]] = None,
                 flag_value: Optional[
                     Callable[[dict], Optional[dict]]] = None,
                 runner: Optional[
                     Callable[[dict, dict],
                              Optional[Callable[[], Any]]]] = None):
        self.name = name
        self.heuristic = heuristic
        self.candidates = candidates
        self.flag_value = flag_value
        self.runner = runner


_SPECS: Dict[str, OpSpec] = {}
_tls = threading.local()
_device: Optional[str] = None


def register(spec: OpSpec) -> OpSpec:
    """Register (or replace) an op spec. Called at import time by the
    ops modules that own each decision point."""
    _SPECS[spec.name] = spec
    return spec


def registered_ops() -> List[str]:
    return sorted(_SPECS)


def heuristic(op: str, params: dict) -> dict:
    """The analytic pick for ``op`` at ``params`` (A/B baselines)."""
    return _SPECS[op].heuristic(dict(params))


def candidates(op: str, params: dict) -> List[dict]:
    """The bounded sweep space for ``op`` at ``params``, heuristic
    included and deduplicated (conformance tests iterate this)."""
    spec = _SPECS[op]
    out = [spec.heuristic(dict(params))]
    if spec.candidates is not None:
        for cfg in spec.candidates(dict(params)):
            if cfg not in out:
                out.append(cfg)
    return out[:SWEEP_MAX_CANDIDATES]


class forced:
    """Thread-locally pin ``op`` to ``config`` (highest precedence).

    The conformance tests and the sweep runners use this to route a
    specific candidate through the real call sites; re-entrant per
    op (inner pin wins)."""

    def __init__(self, op: str, config: dict):
        self.op = op
        self.config = config

    def __enter__(self):
        stack = getattr(_tls, "forced", None)
        if stack is None:
            stack = _tls.forced = {}
        stack.setdefault(self.op, []).append(self.config)
        return self

    def __exit__(self, *exc):
        _tls.forced[self.op].pop()
        if not _tls.forced[self.op]:
            del _tls.forced[self.op]
        return False


def sweep_enabled() -> int:
    """The ``ZOO_TPU_AUTOTUNE`` mode: 0 = never sweep (cache +
    defaults + heuristic only), 1 = sweep on first sight of a key,
    2 = force re-sweep each key once per process."""
    raw = os.environ.get("ZOO_TPU_AUTOTUNE", "0")
    try:
        return max(0, min(2, int(raw)))
    except ValueError:
        return 0


def device_kind() -> str:
    """Normalized device kind of the default backend (``cpu``,
    ``v5e``, ...) — the device component of every cache key."""
    global _device
    if _device is None:
        import jax
        d = jax.devices()[0]
        kind = (getattr(d, "device_kind", "") or d.platform or
                "unknown")
        kind = kind.strip().lower().replace(" ", "-")
        _device = _DEVICE_ALIASES.get(kind, kind)
    return _device


def make_key(op: str, params: dict, dtype: str, device: str) -> str:
    sig = ",".join(f"{k}={params[k]}" for k in sorted(params))
    return f"{op}|{sig}|{dtype}|{device}"


def _default_cache_path() -> str:
    env = os.environ.get("ZOO_TPU_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "zoo_tpu", "autotune.json")


def _defaults_dir() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "autotune_defaults")


def _count(which: str):
    from analytics_zoo_tpu.common import observability as obs
    if which == "hit":
        obs.counter("zoo_tpu_autotune_hits_total",
                    help="autotune decisions served from the "
                         "cache/defaults tables").inc()
    elif which == "miss":
        obs.counter("zoo_tpu_autotune_misses_total",
                    help="autotune decisions with no cached entry "
                         "(heuristic served unless a sweep ran)").inc()
    elif which == "candidate_failure":
        obs.counter("zoo_tpu_autotune_candidate_failures_total",
                    help="sweep candidates that failed to compile "
                         "or run and were skipped").inc()
    else:
        obs.counter("zoo_tpu_autotune_sweeps_total",
                    help="candidate sweeps executed and "
                         "persisted").inc()


class AutotuneCache:
    """The persistent decision cache. One process-wide instance via
    :func:`get_cache`; tests construct their own against tmp paths.

    Hot path (:meth:`decide` on a warm key) is a single dict lookup
    with no locking; ``self._lock`` only serializes sweep+persist."""

    def __init__(self, path: Optional[str] = None,
                 device: Optional[str] = None):
        self.path = path or _default_cache_path()
        self.device = device or device_kind()
        self._entries: Dict[str, dict] = {}
        self._lock = threading.RLock()
        self._reswept: set = set()
        self.hits = 0
        self.misses = 0
        self.sweeps = 0
        self.sources: Dict[str, int] = {}
        self._load_defaults()
        self._load_disk()

    # -- loading --------------------------------------------------------

    def _load_file(self, path: str, source: str):
        try:
            with open(path, encoding="utf-8") as fh:
                d = json.load(fh)
        except (OSError, ValueError):
            return
        if not isinstance(d, dict) or \
                d.get("schema") != SCHEMA_VERSION:
            return
        entries = d.get("entries")
        if not isinstance(entries, dict):
            return
        for key, entry in entries.items():
            if not isinstance(entry, dict) or \
                    not isinstance(entry.get("config"), dict):
                continue
            e = dict(entry)
            e["source"] = source
            self._entries[key] = e

    def _load_defaults(self):
        self._load_file(
            os.path.join(_defaults_dir(), f"{self.device}.json"),
            "defaults")

    def _load_disk(self):
        self._load_file(self.path, "cache")

    # -- the decision ---------------------------------------------------

    def decide(self, op: str, params: dict,
               dtype: str = "any") -> dict:
        pinned = getattr(_tls, "forced", None)
        if pinned and op in pinned:
            self._note("forced")
            return pinned[op][-1]
        spec = _SPECS.get(op)
        if spec is not None and spec.flag_value is not None:
            cfg = spec.flag_value(dict(params))
            if cfg is not None:
                self._note("flag")
                return cfg
        key = make_key(op, params, dtype, self.device)
        mode = sweep_enabled()
        entry = self._entries.get(key)
        if entry is not None and not (
                mode == 2 and key not in self._reswept):
            self.hits += 1
            _count("hit")
            self._note(entry.get("source", "cache"))
            return entry["config"]
        self.misses += 1
        _count("miss")
        if spec is None:
            raise KeyError(f"unknown autotune op {op!r} and no "
                           f"cached entry for {key!r}")
        heur = spec.heuristic(dict(params))
        if (mode >= 1 and spec.runner is not None
                and not getattr(_tls, "in_sweep", False)
                and _trace_clean()):
            swept = self._sweep(spec, op, dict(params), dtype, key,
                                heur, force=(mode == 2))
            if swept is not None:
                return swept
        self._note("heuristic")
        return heur

    def _note(self, source: str):
        self.sources[source] = self.sources.get(source, 0) + 1

    # -- sweeping -------------------------------------------------------

    def _sweep(self, spec: OpSpec, op: str, params: dict,
               dtype: str, key: str, heur: dict,
               force: bool) -> Optional[dict]:
        from analytics_zoo_tpu.common import observability as obs
        with self._lock:
            self._reswept.add(key)
            entry = self._entries.get(key)
            if entry is not None and not force:
                # another thread swept the key while we waited
                self.hits += 1
                _count("hit")
                self._note(entry.get("source", "cache"))
                return entry["config"]
            cands = [heur]
            if spec.candidates is not None:
                for cfg in spec.candidates(params):
                    if cfg not in cands:
                        cands.append(cfg)
            cands = cands[:SWEEP_MAX_CANDIDATES]
            timed: List[dict] = []
            _tls.in_sweep = True
            try:
                with obs.span("autotune/sweep", op=op, key=key):
                    for cfg in cands:
                        ms = self._time_candidate(
                            spec, op, params, cfg,
                            is_heuristic=cfg == heur)
                        if ms is not None:
                            timed.append({"config": cfg, "ms": ms})
            finally:
                _tls.in_sweep = False
            if not timed:
                return None    # nothing measurable here (no probe)
            heur_ms = next((t["ms"] for t in timed
                            if t["config"] == heur), None)
            best = min(timed, key=lambda t: t["ms"])
            if heur_ms is not None and \
                    best["ms"] >= heur_ms * (1.0 - NOISE_MARGIN):
                best = {"config": heur, "ms": heur_ms}
            entry = {
                "op": op, "params": params, "dtype": dtype,
                "config": best["config"], "ms": round(best["ms"], 4),
                "heuristic_ms": (None if heur_ms is None
                                 else round(heur_ms, 4)),
                "candidates": len(timed), "source": "sweep",
            }
            self._entries[key] = entry
            self.sweeps += 1
            _count("sweep")
            self._persist()
            self._note("sweep")
            return entry["config"]

    def _time_candidate(self, spec: OpSpec, op: str, params: dict,
                        cfg: dict, is_heuristic: bool
                        ) -> Optional[float]:
        """Best-of-``SWEEP_REPS`` wall ms of the spec's probe, with
        the compile excluded (the warm-up call runs inside an
        ``expected_compiles`` bracket so deliberate sweep compiles
        never read as a recompile storm). None when the runner
        declines the candidate here. A candidate whose first call
        fails (the compiler refused it) is counted, logged and
        skipped — unless it is the heuristic, the config every call
        site falls back to: then the sweep raises."""
        from analytics_zoo_tpu.common import diagnostics
        fn = spec.runner(params, cfg)
        if fn is None:
            return None
        try:
            with diagnostics.expected_compiles():
                fn()                       # compile + warm
        except Exception as e:
            _count("candidate_failure")
            logger.warning(
                "autotune: op %s config %s failed to compile or run "
                "(%s: %s)", op, cfg, type(e).__name__, e)
            if is_heuristic:
                raise
            return None
        best = float("inf")
        for _ in range(SWEEP_REPS):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    # -- persistence ----------------------------------------------------

    def _persist(self):
        """Merge this cache's swept entries into the on-disk file,
        atomically (tmp+rename). Called with ``self._lock`` held.
        Only ``source == "sweep"`` entries are persisted — defaults
        stay in their committed table."""
        disk: Dict[str, dict] = {}
        try:
            with open(self.path, encoding="utf-8") as fh:
                d = json.load(fh)
            if isinstance(d, dict) and \
                    d.get("schema") == SCHEMA_VERSION and \
                    isinstance(d.get("entries"), dict):
                disk = d["entries"]
        except (OSError, ValueError):
            pass
        for key, entry in self._entries.items():
            if entry.get("source") == "sweep":
                out = dict(entry)
                out["device"] = self.device
                disk[key] = out
        payload = {"schema": SCHEMA_VERSION, "entries": disk}
        try:
            os.makedirs(os.path.dirname(self.path) or ".",
                        exist_ok=True)
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(payload, fh, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass               # read-only FS: the cache stays warm
                               # in-process, just not persistent

    # -- introspection --------------------------------------------------

    def entries(self) -> Dict[str, dict]:
        return dict(self._entries)

    def stats(self) -> dict:
        """Bench-provenance block: ``{enabled, cache_hits,
        cache_misses, sweeps, source}`` where ``source`` is the
        dominant decision source so far (``none`` before any)."""
        src = max(self.sources, key=self.sources.get) \
            if self.sources else "none"
        return {"enabled": sweep_enabled() >= 1,
                "cache_hits": self.hits,
                "cache_misses": self.misses,
                "sweeps": self.sweeps,
                "source": src}


def _trace_clean() -> bool:
    import jax
    return jax.core.trace_ctx.is_top_level()


_cache: Optional[AutotuneCache] = None
_cache_lock = threading.Lock()


def get_cache() -> AutotuneCache:
    """The process-wide cache (constructed on first use, so the env
    and backend are settled by then)."""
    global _cache
    c = _cache
    if c is None:
        with _cache_lock:
            c = _cache
            if c is None:
                c = _cache = AutotuneCache()
    return c


def reset_cache():
    """Forget the singleton (tests repoint ``ZOO_TPU_AUTOTUNE_CACHE``
    and call this; the next decide() rebuilds from disk)."""
    global _cache
    with _cache_lock:
        _cache = None


def decide(op: str, params: dict, dtype: str = "any") -> dict:
    """Resolve one tuned decision — the single entry point every
    wired call site uses. See the module docstring for precedence."""
    return get_cache().decide(op, params, dtype)


def stats() -> dict:
    """Provenance of the process-wide cache (bench artifacts embed
    this under ``"autotune"``)."""
    return get_cache().stats()
