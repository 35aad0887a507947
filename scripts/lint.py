#!/usr/bin/env python
"""Dependency-free style gate (reference analog:
`pyzoo/dev/lint-python` / scalastyle — SURVEY.md §4.9). The image
ships no flake8/ruff, so this covers the high-signal subset with
stdlib ast:

- files must parse (syntax);
- no tabs in indentation, no trailing whitespace;
- line length <= 79 (reference pep8 default); URLs and noqa exempt;
- unused `import x` / `from x import y` at module top level
  (skipped in `__init__.py` re-export hubs, for names in `__all__`,
  and on lines carrying a `# noqa` comment);
- metric naming (package files only): every string-literal metric
  name passed to `counter()` / `gauge()` / `histogram()` must match
  `zoo_tpu_<snake_case>` (docs/observability.md naming contract);
- no bare `except:` in the robustness-critical trees
  (`pipeline/inference/`, `common/`): a bare clause swallows
  KeyboardInterrupt/SystemExit and masks injected faults the chaos
  harness relies on seeing — catch `Exception` (docs/robustness.md);
- shipped SLO defaults (`DEFAULT_SERVING_SLOS` /
  `DEFAULT_FLEET_SLOS` / `DEFAULT_FED_SLOS` /
  `DEFAULT_TRAINING_SLOS` in `common/slo.py`, kept as pure dict
  literals precisely so this works): every rule id is unique, every
  window positive and ascending, and every referenced metric name is
  one the package actually registers — a typoed selector would
  otherwise sit silently in `no_data` forever (docs/slo.md);
- metric-catalog drift: every registered metric family appears in
  the docs/observability.md catalog (between the
  `metric-catalog:begin/end` markers) and every catalog entry is
  still registered by some package file;
- perf-flag drift (both directions, mirroring the metric catalog):
  every `ZOO_TPU_*` env flag that `analytics_zoo_tpu/` or `scripts/`
  references appears in docs/perf_flags.md, and every flag the doc
  names is still referenced by code (docs/perf_flags.md);
- autotune override drift (both directions): every `ZOO_TPU_*` env
  flag actually READ under `analytics_zoo_tpu/ops/` (an
  `os.environ.get/[]`/`os.getenv` call with a literal name) must be
  registered in `perf/autotune.py`'s `OVERRIDE_FLAGS` (kept a pure
  dict literal precisely so this works) AND have a row in
  docs/perf_flags.md; every registered override must still be read
  under `ops/` — so a gate flag can never bypass the tuner silently
  (docs/autotune.md).

Run: `python scripts/lint.py` (exit 1 on findings). `make lint`.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TARGETS = ["analytics_zoo_tpu", "tests", "scripts", "apps",
           "bench.py", "bench_ncf.py", "bench_bert.py",
           "bench_common.py", "bench_serving.py",
           "bench_generate.py", "chip_smoke.py",
           "__graft_entry__.py"]
MAX_LEN = 79


def _py_files():
    for t in TARGETS:
        p = os.path.join(ROOT, t)
        if os.path.isfile(p):
            yield p
        else:
            for dirpath, _dirs, files in os.walk(p):
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(dirpath, f)


def _used_names(tree: ast.AST) -> set:
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            base = node
            while isinstance(base, ast.Attribute):
                base = base.value
            if isinstance(base, ast.Name):
                used.add(base.id)
    return used


def _string_mentions(tree: ast.AST) -> set:
    """Names referenced from string ANNOTATIONS and ``__all__``
    entries only — mining every string constant would whitelist any
    identifier a docstring happens to mention and mask genuinely
    unused imports."""
    out = set()

    def mine(value: str):
        for tok in (value.replace(".", " ").replace("[", " ")
                    .replace("]", " ").replace(",", " ").split()):
            if tok.isidentifier():
                out.add(tok)

    def mine_ann(ann):
        if ann is None:
            return
        for node in ast.walk(ann):
            if isinstance(node, ast.Constant) and isinstance(
                    node.value, str):
                mine(node.value)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            mine_ann(node.returns)
            a = node.args
            for arg in (a.posonlyargs + a.args + a.kwonlyargs
                        + ([a.vararg] if a.vararg else [])
                        + ([a.kwarg] if a.kwarg else [])):
                mine_ann(arg.annotation)
        elif isinstance(node, ast.AnnAssign):
            mine_ann(node.annotation)
        elif isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == "__all__" \
                        and isinstance(node.value,
                                       (ast.List, ast.Tuple)):
                    for el in node.value.elts:
                        if isinstance(el, ast.Constant) and \
                                isinstance(el.value, str):
                            out.add(el.value)
    return out


_METRIC_FNS = {"counter", "gauge", "histogram"}
_METRIC_RE = re.compile(r"^zoo_tpu_[a-z0-9]+(_[a-z0-9]+)*$")


def _metric_name_problems(rel: str, tree: ast.AST,
                          registered: set) -> list:
    """Metric naming contract (docs/observability.md): every literal
    name handed to counter()/gauge()/histogram() is `zoo_tpu_*`
    snake_case. Only package code is held to it — tests deliberately
    mint odd names to exercise escaping. Conforming names are
    accumulated into ``registered`` (the SLO-default check below
    validates selectors against this set)."""
    problems = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        fn_name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if fn_name not in _METRIC_FNS or not node.args:
            continue
        first = node.args[0]
        if isinstance(first, ast.Constant) and isinstance(
                first.value, str):
            if not _METRIC_RE.match(first.value):
                problems.append(
                    f"{rel}:{node.lineno}: metric name "
                    f"'{first.value}' violates zoo_tpu_* snake_case")
            else:
                registered.add(first.value)
    return problems


_NO_BARE_EXCEPT = (
    os.path.join("analytics_zoo_tpu", "pipeline", "inference") + os.sep,
    os.path.join("analytics_zoo_tpu", "common") + os.sep,
)


def _bare_except_problems(rel: str, tree: ast.AST) -> list:
    """Bare ``except:`` is banned in the serving and common trees:
    it catches KeyboardInterrupt/SystemExit/InjectedKillError and
    silently defeats both graceful shutdown and the fault-injection
    harness (docs/robustness.md). ``except Exception`` expresses the
    same intent without eating control-flow exceptions."""
    problems = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            problems.append(
                f"{rel}:{node.lineno}: bare 'except:' (catch "
                f"'Exception' instead; bare clauses swallow "
                f"KeyboardInterrupt and injected kill faults)")
    return problems


_SLO_DEFAULT_NAMES = ("DEFAULT_SERVING_SLOS", "DEFAULT_FLEET_SLOS",
                      "DEFAULT_FED_SLOS", "DEFAULT_TRAINING_SLOS",
                      "DEFAULT_FORECAST_SLOS")
_SLO_FILE = os.path.join("analytics_zoo_tpu", "common", "slo.py")


def _slo_rule_metrics(rule: dict) -> list:
    """Every metric family name a rule's selector references."""
    sig = rule.get("signal") or {}
    out = []
    for part in (sig, sig.get("numerator") or {},
                 sig.get("denominator") or {}):
        m = part.get("metric")
        if isinstance(m, str):
            out.append(m)
    return out


def check_slo_defaults(registered: set) -> list:
    """Validate the shipped SLO rules (docs/slo.md) without importing
    the package: the defaults are pure dict literals, so they
    ``ast.literal_eval`` straight off the tree. Flags duplicate ids
    (across BOTH lists), non-positive or non-ascending windows, and
    selectors naming metrics no package file registers."""
    path = os.path.join(ROOT, _SLO_FILE)
    if not os.path.isfile(path):
        return [f"{_SLO_FILE}: missing (SLO defaults unchecked)"]
    tree = ast.parse(open(path, encoding="utf-8").read())
    problems = []
    seen_ids = {}
    found = set()
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if not (isinstance(tgt, ast.Name)
                    and tgt.id in _SLO_DEFAULT_NAMES):
                continue
            found.add(tgt.id)
            try:
                rules = ast.literal_eval(node.value)
            except ValueError:
                problems.append(
                    f"{_SLO_FILE}:{node.lineno}: {tgt.id} is not a "
                    f"pure literal (lint cannot validate it)")
                continue
            for rule in rules:
                rid = rule.get("id")
                where = f"{_SLO_FILE}:{node.lineno}: {tgt.id}"
                if not rid or not isinstance(rid, str):
                    problems.append(f"{where}: rule without an id")
                    continue
                if rid in seen_ids:
                    problems.append(
                        f"{where}: duplicate slo id '{rid}' (also "
                        f"in {seen_ids[rid]})")
                seen_ids[rid] = tgt.id
                windows = rule.get("windows") or []
                if not windows:
                    problems.append(f"{where}: '{rid}' has no "
                                    f"windows")
                if any(not isinstance(w, (int, float)) or w <= 0
                       for w in windows):
                    problems.append(f"{where}: '{rid}' has a "
                                    f"non-positive window")
                elif list(windows) != sorted(windows):
                    problems.append(f"{where}: '{rid}' windows not "
                                    f"ascending")
                for metric in _slo_rule_metrics(rule):
                    if metric not in registered:
                        problems.append(
                            f"{where}: '{rid}' selects metric "
                            f"'{metric}' that no package file "
                            f"registers")
    for name in _SLO_DEFAULT_NAMES:
        if name not in found:
            problems.append(f"{_SLO_FILE}: {name} not found")
    return problems


_CATALOG_FILE = os.path.join("docs", "observability.md")
_CATALOG_BEGIN = "<!-- metric-catalog:begin -->"
_CATALOG_END = "<!-- metric-catalog:end -->"


def check_metric_catalog(registered: set) -> list:
    """Metric-catalog drift gate: every metric family a package file
    registers must be listed in the docs/observability.md catalog
    (between the ``metric-catalog`` markers), and every catalog entry
    must still be registered by some package file. Catches both
    silent additions (new metric nobody documented) and stale docs
    (metric renamed/removed but still advertised)."""
    path = os.path.join(ROOT, _CATALOG_FILE)
    if not os.path.isfile(path):
        return [f"{_CATALOG_FILE}: missing (metric catalog "
                f"unchecked)"]
    text = open(path, encoding="utf-8").read()
    try:
        lo = text.index(_CATALOG_BEGIN)
        hi = text.index(_CATALOG_END)
    except ValueError:
        return [f"{_CATALOG_FILE}: metric-catalog markers missing "
                f"({_CATALOG_BEGIN} / {_CATALOG_END})"]
    section = text[lo:hi]
    documented = set(re.findall(r"`(zoo_tpu_[a-z0-9_]+)`", section))
    problems = []
    for name in sorted(registered - documented):
        problems.append(
            f"{_CATALOG_FILE}: registered metric '{name}' missing "
            f"from the metric catalog")
    for name in sorted(documented - registered):
        problems.append(
            f"{_CATALOG_FILE}: catalog lists '{name}' but no "
            f"package file registers it")
    return problems


_FLAGS_FILE = os.path.join("docs", "perf_flags.md")
# non-perf toggles documented with their owning module instead of
# the flag tables: artifact locations and opt-in trust switches
_FLAGS_EXEMPT = {"ZOO_TPU_PRETRAINED_DIR", "ZOO_TPU_TRUST_TORCH_PICKLE"}
_FLAG_TOKEN = re.compile(r"ZOO_TPU_[A-Z0-9_]+")


def _flag_tokens(text: str) -> "tuple[set, set]":
    """(exact names, prefix mentions). A token ending in ``_`` is a
    line-wrapped or templated mention (``ZOO_TPU_SLO_<ID>_...``),
    useful only as a prefix witness, never as an exact flag."""
    exact, prefixes = set(), set()
    for tok in _FLAG_TOKEN.findall(text):
        (prefixes if tok.endswith("_") else exact).add(tok)
    return exact, prefixes


def check_perf_flags() -> list:
    """Perf-flag drift gate (the metric-catalog check's twin): every
    ``ZOO_TPU_*`` environment flag referenced under
    ``analytics_zoo_tpu/``, ``scripts/`` or the root bench entry
    points must have a row in docs/perf_flags.md, and every flag the
    doc names must still be referenced by code. Catches both silent
    knob additions (new env flag nobody documented) and stale docs
    (flag renamed/removed but still advertised). Prefix families
    cover both directions: a code flag extending a family the doc
    declares wholesale (``ZOO_TPU_BENCH_*`` selects workload shape,
    not library behavior) needs no own row, and a documented name
    extending a prefix the code templates
    (``ZOO_TPU_SLO_<ID>_THRESHOLD``) needs no literal reference."""
    path = os.path.join(ROOT, _FLAGS_FILE)
    if not os.path.isfile(path):
        return [f"{_FLAGS_FILE}: missing (perf flags unchecked)"]
    doc_exact, doc_prefixes = _flag_tokens(
        open(path, encoding="utf-8").read())
    code_exact, code_prefixes = set(), set()
    for p in _py_files():
        rel = os.path.relpath(p, ROOT)
        in_scope = (rel.startswith(("analytics_zoo_tpu" + os.sep,
                                    "scripts" + os.sep))
                    or (os.sep not in rel
                        and rel.startswith("bench")))
        if not in_scope:
            continue
        try:
            exact, prefixes = _flag_tokens(
                open(p, encoding="utf-8").read())
        except UnicodeDecodeError:
            continue  # check_file already reports it
        code_exact |= exact
        code_prefixes |= prefixes
    problems = []
    for name in sorted(code_exact - doc_exact - _FLAGS_EXEMPT):
        if any(name.startswith(pre) for pre in doc_prefixes):
            continue
        problems.append(
            f"{_FLAGS_FILE}: env flag '{name}' is referenced in "
            f"code but has no row in the flag tables")
    for name in sorted(doc_exact - code_exact):
        if any(name.startswith(pre) for pre in code_prefixes):
            continue
        problems.append(
            f"{_FLAGS_FILE}: documents '{name}' but nothing in "
            f"the package, scripts/ or the bench entry points "
            f"references it")
    return problems


_OVERRIDES_FILE = os.path.join("analytics_zoo_tpu", "perf",
                               "autotune.py")


def _env_reads(tree: ast.AST) -> set:
    """Literal ``ZOO_TPU_*`` names passed to ``os.environ.get``,
    ``os.environ[...]`` or ``os.getenv`` anywhere in ``tree`` —
    actual gate *reads*, not docstring mentions."""
    def _is_environ(node) -> bool:
        return (isinstance(node, ast.Attribute)
                and node.attr == "environ"
                and isinstance(node.value, ast.Name)
                and node.value.id == "os")

    names = set()
    for node in ast.walk(tree):
        arg = None
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute):
            f = node.func
            if (f.attr == "get" and _is_environ(f.value)) or \
                    (f.attr == "getenv"
                     and isinstance(f.value, ast.Name)
                     and f.value.id == "os"):
                arg = node.args[0] if node.args else None
        elif isinstance(node, ast.Subscript) and \
                _is_environ(node.value):
            arg = node.slice
        if isinstance(arg, ast.Constant) and \
                isinstance(arg.value, str) and \
                arg.value.startswith("ZOO_TPU_"):
            names.add(arg.value)
    return names


def _load_override_flags() -> "tuple[dict, list]":
    """`OVERRIDE_FLAGS` from perf/autotune.py, via literal_eval (the
    same trick as the SLO-defaults check — the dict is kept a pure
    literal so the lint gate can read it without importing jax)."""
    path = os.path.join(ROOT, _OVERRIDES_FILE)
    if not os.path.isfile(path):
        return {}, [f"{_OVERRIDES_FILE}: missing (autotune "
                    f"overrides unchecked)"]
    try:
        tree = ast.parse(open(path, encoding="utf-8").read())
    except SyntaxError:
        return {}, []  # check_file already reports it
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and \
                        t.id == "OVERRIDE_FLAGS":
                    try:
                        return ast.literal_eval(node.value), []
                    except ValueError:
                        return {}, [
                            f"{_OVERRIDES_FILE}: OVERRIDE_FLAGS must "
                            f"stay a pure dict literal (the lint "
                            f"gate literal_evals it)"]
    return {}, [f"{_OVERRIDES_FILE}: no OVERRIDE_FLAGS assignment "
                f"found"]


def check_autotune_overrides() -> list:
    """Autotune override drift gate: every ``ZOO_TPU_*`` flag READ
    under ``analytics_zoo_tpu/ops/`` must be registered in
    ``perf/autotune.py``'s ``OVERRIDE_FLAGS`` and documented in
    docs/perf_flags.md; every registered override must still be read
    under ``ops/``. A gate flag outside the table could bypass the
    tuner with no provenance (``source="flag"`` unrecorded)."""
    overrides, problems = _load_override_flags()
    ops_dir = os.path.join("analytics_zoo_tpu", "ops") + os.sep
    reads = set()
    for p in _py_files():
        rel = os.path.relpath(p, ROOT)
        if not rel.startswith(ops_dir):
            continue
        try:
            tree = ast.parse(open(p, encoding="utf-8").read())
        except (SyntaxError, UnicodeDecodeError):
            continue  # check_file already reports it
        reads |= _env_reads(tree)
    doc_exact: set = set()
    doc_path = os.path.join(ROOT, _FLAGS_FILE)
    if os.path.isfile(doc_path):
        doc_exact, _ = _flag_tokens(
            open(doc_path, encoding="utf-8").read())
    for name in sorted(reads - set(overrides)):
        problems.append(
            f"{_OVERRIDES_FILE}: ops/ reads env gate '{name}' but "
            f"OVERRIDE_FLAGS does not register it (add it, mapped "
            f"to the op it overrides, ':pin'-suffixed if outside "
            f"the sweep space)")
    for name in sorted(reads - doc_exact):
        problems.append(
            f"{_FLAGS_FILE}: ops/ gate '{name}' has no row in the "
            f"flag tables")
    for name in sorted(set(overrides) - reads):
        problems.append(
            f"{_OVERRIDES_FILE}: OVERRIDE_FLAGS registers '{name}' "
            f"but nothing under analytics_zoo_tpu/ops/ reads it")
    return problems


def check_file(path: str, registered: Optional[set] = None) -> list:
    rel = os.path.relpath(path, ROOT)
    try:
        src = open(path, encoding="utf-8").read()
    except UnicodeDecodeError:
        return [f"{rel}: not utf-8"]
    problems = []
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [f"{rel}:{e.lineno}: syntax error: {e.msg}"]
    for i, line in enumerate(src.splitlines(), 1):
        if line != line.rstrip():
            problems.append(f"{rel}:{i}: trailing whitespace")
        if "\t" in line:
            problems.append(f"{rel}:{i}: tab character")
        if (len(line) > MAX_LEN and "noqa" not in line
                and "http://" not in line and "https://" not in line):
            problems.append(
                f"{rel}:{i}: line too long ({len(line)} > {MAX_LEN})")
    if rel.startswith("analytics_zoo_tpu" + os.sep):
        problems.extend(_metric_name_problems(
            rel, tree, registered if registered is not None
            else set()))
    if rel.startswith(_NO_BARE_EXCEPT):
        problems.extend(_bare_except_problems(rel, tree))
    if os.path.basename(path) != "__init__.py":
        used = _used_names(tree) | _string_mentions(tree)
        lines = src.splitlines()
        for node in tree.body:  # top level only: locals are fine
            names = []
            if isinstance(node, ast.Import):
                names = [(a.asname or a.name.split(".")[0], a.name)
                         for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__" or any(
                        a.name == "*" for a in node.names):
                    continue
                names = [(a.asname or a.name, a.name)
                         for a in node.names]
            for bound, orig in names:
                line = lines[node.lineno - 1] if \
                    node.lineno <= len(lines) else ""
                if "noqa" in line:
                    continue
                if bound not in used:
                    problems.append(
                        f"{rel}:{node.lineno}: unused import "
                        f"'{orig}' (as '{bound}')")
    return problems


def main() -> int:
    all_problems = []
    registered: set = set()
    n = 0
    for path in _py_files():
        n += 1
        all_problems.extend(check_file(path, registered))
    all_problems.extend(check_slo_defaults(registered))
    all_problems.extend(check_metric_catalog(registered))
    all_problems.extend(check_perf_flags())
    all_problems.extend(check_autotune_overrides())
    for p in all_problems:
        print(p)
    print(f"# linted {n} files: "
          f"{'OK' if not all_problems else f'{len(all_problems)} problems'}",
          file=sys.stderr)
    return 1 if all_problems else 0


if __name__ == "__main__":
    sys.exit(main())
