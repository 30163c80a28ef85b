#!/usr/bin/env python
"""Lint: flight-recorder phase names declared in obs/flight.py PHASES
must match the literal ``note_phase(...)`` call sites, and every
declared phase must be charged somewhere; the span names declared in
SPANS must match the literal ``FLIGHT.span(...)`` sites the same way.

Why: the phase vocabulary is an API — statements_summary's avg_*
columns, the slow-log `# Phases` line and the tidbtpu_flight_phase_
seconds{phase} series all key on it. ``note_phase`` already rejects
undeclared names at runtime, but a dead declaration (a phase nothing
charges) silently rots into an always-zero column; the same pattern as
scripts/check_failpoints.py for failpoint SITES. Two rules:

  1. every literal ``note_phase("name", ...)`` site in engine code
     must name a declared phase (the runtime check made static);
  2. every name in PHASES must have at least one literal
     ``note_phase("name")`` call site OR be produced by
     note_shuffle_stage (the shuffle-* quartet is charged there from
     the worker-reported stage stats). A ``FLIGHT.span("name")`` site
     counts: a span named like a phase charges it;
  3. the same two rules for SPANS and the ``FLIGHT.span("name")``
     sites (a tree without SPANS has no span sites to check).

Usage: python scripts/check_flight_phases.py [root]
Exit 0 = clean, 1 = violations (printed one per line).
"""

from __future__ import annotations

import ast
import os
import re
import sys

NOTE = re.compile(r"\bnote_phase\(\s*[\"']([^\"']+)[\"']")
SPAN = re.compile(r"FLIGHT\.span\(\s*[\"']([^\"']+)[\"']")
SKIP_DIRS = {".git", ".jax_cache", "__pycache__", "node_modules"}
#: the registry itself (note_shuffle_stage charges the shuffle phases
#: with literal names — those count as call sites, handled below), the
#: lint, and the lint's own test quote undeclared names deliberately
SKIP_FILES = {
    os.path.join("scripts", "check_flight_phases.py"),
    os.path.join("tests", "test_flight_phases.py"),
}


def load_registry(root: str, name: str):
    """A registry literal (PHASES, SPANS), read via the AST (flight.py
    imports the package, so exec'ing it standalone — the failpoint
    lint's approach — would need the whole engine importable from the
    lint). None where the tree does not declare it."""
    path = os.path.join(root, "tidb_tpu", "obs", "flight.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == name
            for t in node.targets
        ):
            return tuple(ast.literal_eval(node.value))
    return None


def iter_py(root: str):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in SKIP_DIRS]
        for fn in filenames:
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def check(root: str):
    phases = load_registry(root, "PHASES")
    if phases is None:
        raise SystemExit("PHASES assignment not found in obs/flight.py")
    spans = load_registry(root, "SPANS") or ()
    if len(phases) != len(set(phases)) or len(spans) != len(set(spans)):
        return [("tidb_tpu/obs/flight.py", 1,
                 "duplicate names in PHASES or SPANS")]
    violations = []
    noted, spanned = {}, {}
    for path in sorted(iter_py(root)):
        rel = os.path.relpath(path, root)
        if rel in SKIP_FILES:
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                text = f.read()
        except OSError:
            continue
        for pattern, used, declared, what, registry in (
            (NOTE, noted, phases, "phase", "PHASES"),
            (SPAN, spanned, spans, "span", "SPANS"),
        ):
            for m in pattern.finditer(text):
                name = m.group(1)
                line = text.count("\n", 0, m.start()) + 1
                used.setdefault(name, (rel, line))
                if name not in declared:
                    violations.append(
                        (rel, line,
                         f"undeclared flight {what} {name!r} (declare it "
                         f"in tidb_tpu/obs/flight.py {registry})")
                    )
    for name in phases:
        if name not in noted and name not in spanned:
            violations.append(
                ("tidb_tpu/obs/flight.py", 1,
                 f"declared flight phase {name!r} has no note_phase() "
                 "call site (dead declaration)")
            )
    for name in spans:
        if name not in spanned:
            violations.append(
                ("tidb_tpu/obs/flight.py", 1,
                 f"declared flight span {name!r} has no FLIGHT.span() "
                 "call site (dead declaration)")
            )
    return violations


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    violations = check(root)
    for rel, line, msg in violations:
        print(f"{rel}:{line}: {msg}")
    if violations:
        print(f"{len(violations)} flight-phase violation(s)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
