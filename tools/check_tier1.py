#!/usr/bin/env python
"""Tier-1 marker audit: every test file must contribute to the tier-1
suite (``pytest -m 'not slow'``).

The tier-1 filter is the repo's correctness gate (ROADMAP.md).  Its
failure mode is silent: a test file whose every test carries (or
inherits) ``pytest.mark.slow`` simply stops being collected — nothing
fails, coverage just evaporates.  This tool audits the markers
STATICALLY (AST; no imports, no jax, runs in milliseconds) so it can
gate before anything jax-heavy runs:

  python tools/check_tier1.py            # audit ./tests, exit 1 on drift
  python tools/check_tier1.py --list     # per-file tier-1/slow counts

(Also runs as rule T1001 of the tffm-lint suite — ``python -m
tools.lint``, the tools/verify.sh entry point; see LINTING.md.)

Checks:
  1. every ``tests/test_*.py`` defines at least one test;
  2. every test file keeps at least one tier-1 (non-slow) test — no
     file silently drops out of the gate;
  3. every marker used via ``pytest.mark.<name>`` is declared in
     pytest.ini (an undeclared marker is a typo that silently marks
     nothing — ``-m 'not slo'`` style drift).

Marker detection covers the repo's idioms: decorators
(``@pytest.mark.slow``, ``@pytest.mark.slow(...)``), module-level
``pytestmark = pytest.mark.slow`` / ``pytestmark = [...]``, and class
decorators inherited by test methods.  Dynamic marking
(``request.applymarker``) is invisible to AST — none is used here, and
the audit errs on the side of counting such tests as tier-1 (the gate
then sees a file it believes is covered, which collection itself would
catch as an error if the file went fully slow at runtime).
"""

from __future__ import annotations

import argparse
import ast
import configparser
import os
import sys


def _marks_in(node: ast.AST) -> set:
    """Names X used as ``pytest.mark.X`` anywhere inside ``node``."""
    out = set()
    for sub in ast.walk(node):
        if (
            isinstance(sub, ast.Attribute)
            and isinstance(sub.value, ast.Attribute)
            and sub.value.attr == "mark"
            and isinstance(sub.value.value, ast.Name)
            and sub.value.value.id == "pytest"
        ):
            out.add(sub.attr)
    return out


def _decorator_marks(node) -> set:
    marks = set()
    for dec in getattr(node, "decorator_list", []):
        marks |= _marks_in(dec)
    return marks


def audit_file(path: str) -> dict:
    """{tests, tier1, slow, marks_used} for one test file."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    module_marks = set()
    marks_used = set()
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "pytestmark"
            for t in stmt.targets
        ):
            module_marks |= _marks_in(stmt.value)
    marks_used |= module_marks

    tests = tier1 = slow_n = 0

    def visit_fn(fn, inherited: set):
        nonlocal tests, tier1, slow_n
        if not fn.name.startswith("test"):
            return
        marks = inherited | _decorator_marks(fn)
        marks_used.update(_decorator_marks(fn))
        tests += 1
        if "slow" in marks:
            slow_n += 1
        else:
            tier1 += 1

    for stmt in tree.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            visit_fn(stmt, module_marks)
        elif isinstance(stmt, ast.ClassDef) and stmt.name.startswith(
            "Test"
        ):
            class_marks = module_marks | _decorator_marks(stmt)
            marks_used |= _decorator_marks(stmt)
            for sub in stmt.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    visit_fn(sub, class_marks)
    return {
        "tests": tests, "tier1": tier1, "slow": slow_n,
        "marks_used": marks_used,
    }


def declared_markers(repo_root: str) -> set:
    """Marker names declared in pytest.ini (empty set if none found)."""
    ini = os.path.join(repo_root, "pytest.ini")
    if not os.path.exists(ini):
        return set()
    cp = configparser.ConfigParser()
    cp.read(ini)
    raw = cp.get("pytest", "markers", fallback="")
    out = set()
    for line in raw.splitlines():
        line = line.strip()
        if line:
            out.add(line.split(":", 1)[0].strip())
    return out


# Markers pytest defines itself — always legal without declaration.
_BUILTIN_MARKS = {
    "parametrize", "skip", "skipif", "xfail", "usefixtures",
    "filterwarnings", "timeout",
}

# Files ALLOWED to be fully slow — each entry is a deliberate decision,
# not drift, and needs a reason here.  New test files must contribute
# tier-1 tests or be added here with a justification.
_ALL_SLOW_ALLOWED = {
    # Spawns two jax.distributed OS processes over loopback; the tier-1
    # gate runs single-process CPU and cannot host a coordinator.
    "test_dist_multiprocess.py",
}


def audit(test_dir: str = "tests",
          repo_root: str = ".") -> dict:
    """Audit every tests/test_*.py; returns a summary dict:
    {ok, files, tests, tier1, slow, problems: [str, ...],
     per_file: {name: {...}}}."""
    problems = []
    per_file = {}
    declared = declared_markers(repo_root) | _BUILTIN_MARKS
    names = sorted(
        n for n in os.listdir(test_dir)
        if n.startswith("test_") and n.endswith(".py")
    )
    if not names:
        return {"ok": False, "files": 0, "tests": 0, "tier1": 0,
                "slow": 0, "problems": [f"no test files in {test_dir}"],
                "per_file": {}}
    totals = {"tests": 0, "tier1": 0, "slow": 0}
    for name in names:
        path = os.path.join(test_dir, name)
        try:
            info = audit_file(path)
        except SyntaxError as e:
            problems.append(f"{name}: does not parse ({e})")
            continue
        per_file[name] = info
        for key in totals:
            totals[key] += info[key]
        if info["tests"] == 0:
            problems.append(f"{name}: defines no tests")
        elif info["tier1"] == 0 and name not in _ALL_SLOW_ALLOWED:
            problems.append(
                f"{name}: every test is marked slow — the file has "
                "silently dropped out of the tier-1 gate (add tier-1 "
                "tests, or allowlist it in tools/check_tier1.py with a "
                "reason)"
            )
        undeclared = info["marks_used"] - declared
        if undeclared:
            problems.append(
                f"{name}: undeclared marker(s) {sorted(undeclared)} — "
                "add to pytest.ini or fix the typo"
            )
    return {
        "ok": not problems, "files": len(names), **totals,
        "problems": problems, "per_file": per_file,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="audit tier-1 (non-slow) test coverage per file"
    )
    ap.add_argument("--tests", default="tests",
                    help="test directory (default ./tests)")
    ap.add_argument("--root", default=".",
                    help="repo root holding pytest.ini (default .)")
    ap.add_argument("--list", action="store_true",
                    help="print per-file tier-1/slow counts")
    args = ap.parse_args(argv)
    result = audit(args.tests, args.root)
    if args.list:
        print(f"{'file':40} {'tests':>6} {'tier1':>6} {'slow':>5}")
        for name, info in sorted(result["per_file"].items()):
            print(f"{name:40} {info['tests']:>6} {info['tier1']:>6} "
                  f"{info['slow']:>5}")
    print(
        f"tier-1 audit: {result['files']} files, {result['tests']} "
        f"tests, {result['tier1']} tier-1, {result['slow']} slow"
    )
    for p in result["problems"]:
        print(f"  ! {p}")
    if not result["ok"]:
        return 1
    print("ok: every test file contributes to the tier-1 gate")
    return 0


if __name__ == "__main__":
    sys.exit(main())
