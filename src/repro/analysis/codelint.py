"""Repo-specific static lint (AST-level), run by CI's lint job.

    python -m repro.analysis.codelint [--root PATH] [--json]

Seven rules encoding conventions this repo has paid for breaking:

  * ``kernel-oracle``   — every ``kernels/<name>/kernel.py`` ships a
    ``ref.py`` NumPy/JAX oracle AND an interpret-mode parity test (a test
    file that names the kernel and exercises ``interpret``).  Pallas
    kernels without an oracle rot silently on TPU-only CI.
  * ``at-set-loop``     — no ``.at[...].set(...)`` inside a Python loop in
    the restore hot path (``core/datapath.py``, ``core/executor.py``):
    each call is a full-slab XLA copy, the exact O(chunks x layers x
    fields) storm the fused datapath exists to avoid.  Annotate deliberate
    legacy baselines with ``# codelint: allow(at-set-loop)`` on the call
    or the loop header line.
  * ``unseeded-rng``    — no wall-clock (``time.time()``) or unseeded
    global RNG (bare ``random`` module, ``np.random.<dist>`` singleton,
    argument-less ``np.random.default_rng()``) in ``core/`` or
    ``storage/`` modules: both feed trace capture, and traces must replay
    bit-identically.  ``time.perf_counter`` (pure profiling) and
    ``jax.random`` (explicit keys) are fine.
  * ``trace-kinds``     — every trace event kind emitted or matched in
    ``core/trace.py`` is registered in the ``EVENT_KINDS`` schema version
    table, so the offline linter and the upgrader agree on the schema.
  * ``metric-catalog``  — every metric name passed as a string literal to a
    ``.counter()`` / ``.gauge()`` / ``.histogram()`` call anywhere under
    ``src/repro`` is registered in ``obs/registry.py``'s
    ``METRIC_CATALOG`` (the registry also enforces this at runtime, but
    telemetry is opt-in, so an unregistered name would otherwise only
    explode in the rare telemetry-on run).
  * ``span-catalog``    — likewise every span name passed as a string
    literal to a ``span()`` / ``instant()`` call under ``src/repro`` is
    declared in ``SPAN_CATALOG`` (the span recorder, ``obs/spans.py``,
    records whatever it is given; the benchmark's readers find spans by
    these names).
  * ``scope-catalog``   — likewise every ``jax.named_scope`` literal under
    ``src/repro`` is declared in ``SCOPE_CATALOG`` (the benchmark sums a
    device trace's ops by these names).

Each ``check_*`` function takes explicit paths so the mutation self-tests
can point them at synthetic files.
"""
from __future__ import annotations

import argparse
import ast
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import List, Sequence

ALLOW_PRAGMA = "# codelint: allow("

#: np.random module-singleton entry points that draw from unseeded global
#: state (calling these in trace-feeding code breaks replay determinism)
NP_GLOBAL_DISTS = {
    "rand", "randn", "randint", "random", "random_sample", "choice",
    "shuffle", "permutation", "normal", "uniform", "exponential", "poisson",
    "seed", "bytes",
}


@dataclass
class CodeLintFinding:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _allowed(lines: Sequence[str], rule: str, *linenos: int) -> bool:
    tag = f"{ALLOW_PRAGMA}{rule})"
    return any(0 < n <= len(lines) and tag in lines[n - 1] for n in linenos)


# ---------------------------------------------------------------------------
# kernel-oracle
# ---------------------------------------------------------------------------


def check_kernel_oracles(kernels_dir: Path,
                         tests_dir: Path) -> List[CodeLintFinding]:
    out: List[CodeLintFinding] = []
    if not kernels_dir.is_dir():
        return out
    test_texts = {p: p.read_text() for p in sorted(tests_dir.glob("test_*.py"))} \
        if tests_dir.is_dir() else {}
    for kernel in sorted(kernels_dir.glob("*/kernel.py")):
        name = kernel.parent.name
        if not (kernel.parent / "ref.py").exists():
            out.append(CodeLintFinding(
                "kernel-oracle", str(kernel), 1,
                f"kernel {name!r} has no ref.py oracle next to kernel.py"))
        if not any(name in txt and "interpret" in txt
                   for txt in test_texts.values()):
            out.append(CodeLintFinding(
                "kernel-oracle", str(kernel), 1,
                f"kernel {name!r} has no interpret-mode parity test (no "
                f"test_*.py mentions both {name!r} and 'interpret')"))
    return out


# ---------------------------------------------------------------------------
# at-set-loop
# ---------------------------------------------------------------------------


def _is_at_set_call(node: ast.AST) -> bool:
    """Matches ``<expr>.at[...].set(...)`` / ``.add(...)`` etc."""
    if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
        return False
    sub = node.func.value
    return (isinstance(sub, ast.Subscript)
            and isinstance(sub.value, ast.Attribute)
            and sub.value.attr == "at")


def check_at_set_loops(paths: Sequence[Path]) -> List[CodeLintFinding]:
    out: List[CodeLintFinding] = []
    for path in paths:
        if not path.exists():
            continue
        src = path.read_text()
        lines = src.splitlines()
        tree = ast.parse(src, filename=str(path))
        # map each offending call to ALL enclosing loops so the allow
        # pragma may sit on the call line or any loop header above it
        enclosing: dict = {}
        for loop in ast.walk(tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in ast.walk(loop):
                if _is_at_set_call(node):
                    enclosing.setdefault(id(node), (node, []))[1].append(
                        loop.lineno)
        for node, loop_lines in enclosing.values():
            if not _allowed(lines, "at-set-loop", node.lineno, *loop_lines):
                out.append(CodeLintFinding(
                    "at-set-loop", str(path), node.lineno,
                    f".at[].{node.func.attr}() inside a loop (line "
                    f"{min(loop_lines)}) — a full-slab XLA copy per "
                    f"iteration; use the fused datapath or annotate "
                    f"'{ALLOW_PRAGMA}at-set-loop)'"))
    return out


# ---------------------------------------------------------------------------
# unseeded-rng
# ---------------------------------------------------------------------------


def check_unseeded_rng(paths: Sequence[Path]) -> List[CodeLintFinding]:
    out: List[CodeLintFinding] = []
    for path in paths:
        if not path.exists():
            continue
        src = path.read_text()
        lines = src.splitlines()
        tree = ast.parse(src, filename=str(path))
        # names the stdlib random module is bound to in this file
        random_names = set()
        numpy_names = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "random":
                        random_names.add(a.asname or "random")
                    elif a.name == "numpy":
                        numpy_names.add(a.asname or "numpy")
            elif isinstance(node, ast.ImportFrom) and node.module == "random":
                if not _allowed(lines, "unseeded-rng", node.lineno):
                    out.append(CodeLintFinding(
                        "unseeded-rng", str(path), node.lineno,
                        "from random import ... pulls unseeded global-state "
                        "RNG into trace-feeding code; use "
                        "np.random.default_rng(seed)"))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            if _allowed(lines, "unseeded-rng", node.lineno):
                continue
            # time.time()
            if f.attr == "time" and isinstance(f.value, ast.Name) \
                    and f.value.id == "time":
                out.append(CodeLintFinding(
                    "unseeded-rng", str(path), node.lineno,
                    "time.time() is nondeterministic wall clock; engine "
                    "time must come from the simulated clock "
                    "(time.perf_counter is fine for pure profiling)"))
                continue
            # random.<fn>() on the stdlib module
            if isinstance(f.value, ast.Name) and f.value.id in random_names:
                out.append(CodeLintFinding(
                    "unseeded-rng", str(path), node.lineno,
                    f"random.{f.attr}() draws from unseeded global state; "
                    f"use np.random.default_rng(seed)"))
                continue
            # np.random.<...>
            mod = f.value
            if isinstance(mod, ast.Attribute) and mod.attr == "random" \
                    and isinstance(mod.value, ast.Name) \
                    and mod.value.id in (numpy_names | {"np"}):
                if f.attr == "default_rng" and not node.args \
                        and not node.keywords:
                    out.append(CodeLintFinding(
                        "unseeded-rng", str(path), node.lineno,
                        "np.random.default_rng() without a seed is "
                        "entropy-seeded; pass an explicit seed"))
                elif f.attr in NP_GLOBAL_DISTS:
                    out.append(CodeLintFinding(
                        "unseeded-rng", str(path), node.lineno,
                        f"np.random.{f.attr}() uses the unseeded global "
                        f"generator; use np.random.default_rng(seed)"))
    return out


# ---------------------------------------------------------------------------
# trace-kinds
# ---------------------------------------------------------------------------


def check_trace_kinds(trace_py: Path) -> List[CodeLintFinding]:
    out: List[CodeLintFinding] = []
    if not trace_py.exists():
        return [CodeLintFinding("trace-kinds", str(trace_py), 1,
                                "trace module not found")]
    tree = ast.parse(trace_py.read_text(), filename=str(trace_py))
    registered = None
    for node in ast.walk(tree):
        if isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name) and \
                node.target.id == "EVENT_KINDS" and node.value is not None:
            value = node.value
        elif isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "EVENT_KINDS"
                for t in node.targets):
            value = node.value
        else:
            continue
        if isinstance(value, ast.Dict):
            registered = {k.value for k in value.keys
                          if isinstance(k, ast.Constant)}
    if registered is None:
        return [CodeLintFinding(
            "trace-kinds", str(trace_py), 1,
            "no EVENT_KINDS literal dict found — the schema version table "
            "is gone")]
    for node in ast.walk(tree):
        # recorder emissions: _ev(kind="...") / TraceEvent(kind="...")
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "kind" and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str) \
                        and kw.value.value not in registered:
                    out.append(CodeLintFinding(
                        "trace-kinds", str(trace_py), node.lineno,
                        f"event kind {kw.value.value!r} emitted but not "
                        f"registered in EVENT_KINDS"))
        # consumers: <expr>.kind == "..." comparisons
        if isinstance(node, ast.Compare) and \
                isinstance(node.left, ast.Attribute) and \
                node.left.attr == "kind":
            for cmp in node.comparators:
                if isinstance(cmp, ast.Constant) \
                        and isinstance(cmp.value, str) \
                        and cmp.value not in registered:
                    out.append(CodeLintFinding(
                        "trace-kinds", str(trace_py), node.lineno,
                        f"event kind {cmp.value!r} matched but not "
                        f"registered in EVENT_KINDS"))
    return out


# ---------------------------------------------------------------------------
# metric-catalog
# ---------------------------------------------------------------------------

#: registry accessor methods whose first positional string argument is a
#: metric name (the scan keys on the METHOD name, so any registry-shaped
#: object — MetricsRegistry or a future facade — is covered)
METRIC_METHODS = {"counter", "gauge", "histogram"}
#: span-recorder entry points whose first positional string argument is a
#: span name (``obs/spans.py``; called bare or as a module attribute)
SPAN_FUNCTIONS = {"span", "instant"}
#: ``jax.named_scope`` (called as ``jax.named_scope`` or bare): its first
#: positional string argument is a scope name of the compiled programs
SCOPE_FUNCTIONS = {"named_scope"}


def _catalog_names(registry_py: Path, catalog: str = "METRIC_CATALOG"):
    """Parse the ``catalog`` literal dict's keys, or None if the
    assignment is missing/not a literal (mirrors EVENT_KINDS handling)."""
    tree = ast.parse(registry_py.read_text(), filename=str(registry_py))
    for node in ast.walk(tree):
        if isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name) and \
                node.target.id == catalog and node.value is not None:
            value = node.value
        elif isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == catalog
                for t in node.targets):
            value = node.value
        else:
            continue
        if isinstance(value, ast.Dict):
            return {k.value for k in value.keys
                    if isinstance(k, ast.Constant)}
    return None


def check_metric_catalog(registry_py: Path,
                         paths: Sequence[Path]) -> List[CodeLintFinding]:
    if not registry_py.exists():
        return [CodeLintFinding("metric-catalog", str(registry_py), 1,
                                "metrics registry module not found")]
    registered = _catalog_names(registry_py)
    if registered is None:
        return [CodeLintFinding(
            "metric-catalog", str(registry_py), 1,
            "no METRIC_CATALOG literal dict found — the metric catalog "
            "is gone")]
    return [CodeLintFinding(
        "metric-catalog", str(path), line,
        f"metric {name!r} used at a .{func}() call but not registered in "
        f"METRIC_CATALOG (obs/registry.py)")
        for path, line, func, name in _literal_calls(paths, METRIC_METHODS,
                                                     bare=False)
        if name not in registered]


def check_span_catalog(registry_py: Path,
                       paths: Sequence[Path]) -> List[CodeLintFinding]:
    registered = (_catalog_names(registry_py, "SPAN_CATALOG")
                  if registry_py.exists() else None)
    if registered is None:
        return [CodeLintFinding(
            "span-catalog", str(registry_py), 1,
            "no SPAN_CATALOG literal dict found — the span catalog is gone")]
    return [CodeLintFinding(
        "span-catalog", str(path), line,
        f"span {name!r} used at a {func}() call but not declared in "
        f"SPAN_CATALOG (obs/registry.py)")
        for path, line, func, name in _literal_calls(paths, SPAN_FUNCTIONS,
                                                     bare=True)
        if name not in registered]


def check_scope_catalog(registry_py: Path,
                        paths: Sequence[Path]) -> List[CodeLintFinding]:
    registered = (_catalog_names(registry_py, "SCOPE_CATALOG")
                  if registry_py.exists() else None)
    if registered is None:
        return [CodeLintFinding(
            "scope-catalog", str(registry_py), 1,
            "no SCOPE_CATALOG literal dict found — the scope catalog is "
            "gone")]
    return [CodeLintFinding(
        "scope-catalog", str(path), line,
        f"scope {name!r} used at a {func}() call but not declared in "
        f"SCOPE_CATALOG (obs/registry.py)")
        for path, line, func, name in _literal_calls(paths, SCOPE_FUNCTIONS,
                                                     bare=True)
        if name not in registered]


def _literal_calls(paths: Sequence[Path], funcs, *, bare: bool):
    """``(path, line, function, literal)`` of every call to one of
    ``funcs`` — as an attribute, and with ``bare`` as a plain name too —
    whose first positional argument is a string literal."""
    for path in paths:
        if not path.exists():
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            f = node.func
            name = (f.attr if isinstance(f, ast.Attribute) else
                    f.id if bare and isinstance(f, ast.Name) else None)
            if name in funcs:
                yield path, node.lineno, name, node.args[0].value


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def run_all(root: Path) -> List[CodeLintFinding]:
    src = root / "src" / "repro"
    findings: List[CodeLintFinding] = []
    findings += check_kernel_oracles(src / "kernels", root / "tests")
    findings += check_at_set_loops([src / "core" / "datapath.py",
                                    src / "core" / "executor.py"])
    rng_paths = sorted((src / "core").glob("*.py")) + \
        sorted((src / "storage").glob("*.py"))
    findings += check_unseeded_rng(rng_paths)
    findings += check_trace_kinds(src / "core" / "trace.py")
    findings += check_metric_catalog(src / "obs" / "registry.py",
                                     sorted(src.rglob("*.py")))
    findings += check_span_catalog(src / "obs" / "registry.py",
                                   sorted(src.rglob("*.py")))
    findings += check_scope_catalog(src / "obs" / "registry.py",
                                    sorted(src.rglob("*.py")))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.codelint",
        description="Repo-specific AST lint (see module docstring).")
    ap.add_argument("--root", default=None,
                    help="repo root (default: auto-detect from this file)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)
    root = Path(args.root) if args.root else \
        Path(__file__).resolve().parents[3]
    findings = run_all(root)
    if args.as_json:
        print(json.dumps([{"rule": f.rule, "path": f.path, "line": f.line,
                           "message": f.message} for f in findings]))
    elif findings:
        for f in findings:
            print(f)
        print(f"{len(findings)} finding(s)")
    else:
        print("codelint: clean")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
