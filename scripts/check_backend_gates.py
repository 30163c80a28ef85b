#!/usr/bin/env python
"""Lint: one question for the backend, no private JAX, no swallowed
device query, and an executor that asks neither backend nor shell.

`utils/backend.is_tpu()` is THE way engine code asks what it runs on:
it asks `jax.devices()[0].platform` once. A `jax.default_backend() ==
"tpu"` string compare asks the PJRT plugin's own name instead, which a
plugin is free to spell differently. And the executor asks not at all:
it lowers an operator one way, chosen from the shapes and widths it can
see when the program is traced, so the tests run the kernels the chip
runs.

Rules:
  1. anywhere in the repo's .py files: `default_backend() == "tpu"`
     (or !=) is an error;
  2. inside the tidb_tpu/ package (engine code), ANY `== "tpu"` /
     `!= "tpu"` string compare is an error, except in utils/backend.py
     (the helper's own implementation) or on lines carrying a
     `# backend-gate-ok` pragma;
  3. anywhere in the repo's .py files: importing JAX's private package
     (the `_src` tree) is an error — the program runs on the public API
     of the one installation there is;
  4. inside tidb_tpu/: a device query (`jax.devices()`,
     `local_devices()`, `default_backend()`, `memory_stats()`, ...)
     inside a `try` whose handler catches Exception (or everything) is
     an error — a failed device query is an error, not "CPU";
  5. inside tidb_tpu/executor/: a call of `is_tpu()` or
     `default_backend()`, or a read of the process environment
     (`os.environ`, `os.getenv`), is an error — which formulation of an
     operator runs is no business of the platform's or the shell's.

Usage: python scripts/check_backend_gates.py [root]
Exit 0 = clean, 1 = violations (printed one per line).
"""

from __future__ import annotations

import ast
import os
import re
import sys

DEFAULT_BACKEND_CMP = re.compile(
    r"default_backend\(\)\s*[=!]=\s*[\"']tpu[\"']"
)
ANY_TPU_CMP = re.compile(r"[=!]=\s*[\"']tpu[\"']")
#: spelled in two halves so a grep of the tree for the private package
#: finds importers only
PRIVATE_JAX = re.compile(r"^\s*(from|import)\s+jax\." + "_src" + r"\b")
DEVICE_QUERIES = {
    "devices", "local_devices", "default_backend", "memory_stats",
    "device_count", "local_device_count",
}
PRAGMA = "# backend-gate-ok"
#: the helper's own implementation, and this lint (its docstring quotes
#: the offending pattern)
ALLOWED = {
    os.path.join("tidb_tpu", "utils", "backend.py"),
    os.path.join("scripts", "check_backend_gates.py"),
}
SKIP_DIRS = {
    ".git", ".jax_cache", "__pycache__", "node_modules", "chiprun_out",
    ".proof",
}


def iter_py(root: str):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in SKIP_DIRS]
        for fn in filenames:
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def check(root: str):
    violations = []
    for path in sorted(iter_py(root)):
        rel = os.path.relpath(path, root)
        in_engine = rel.split(os.sep)[0] == "tidb_tpu"
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                lines = f.readlines()
        except OSError:
            continue
        for i, line in enumerate(lines, 1):
            if PRAGMA in line:
                continue
            if rel in ALLOWED:
                pass  # the string-compare rules exempt the helper itself
            elif DEFAULT_BACKEND_CMP.search(line):
                violations.append(
                    (rel, i, "default_backend() string-compared to 'tpu' "
                     "(the plugin's own name, not the device's platform) "
                     "— use utils.backend.is_tpu()")
                )
            elif in_engine and ANY_TPU_CMP.search(line):
                violations.append(
                    (rel, i, "raw == \"tpu\" compare in engine code — "
                     "use utils.backend.is_tpu() (or add "
                     f"{PRAGMA!r} if this is not a backend gate)")
                )
            if PRIVATE_JAX.search(line):
                violations.append(
                    (rel, i, "import of JAX's private _src package — use "
                     "the public API")
                )
        if in_engine:
            violations.extend(
                (rel, line, msg) for line, msg in _swallowed_queries(lines)
            )
        if rel.split(os.sep)[:2] == ["tidb_tpu", "executor"]:
            violations.extend(
                (rel, line, msg) for line, msg in _executor_asks(lines)
            )
    return violations


def _broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    names = t.elts if isinstance(t, ast.Tuple) else [t]
    return any(
        isinstance(n, ast.Name) and n.id in ("Exception", "BaseException")
        for n in names
    )


def _swallowed_queries(lines):
    """(line, message) for every device query inside the body of a try
    with a catch-all handler."""
    try:
        tree = ast.parse("".join(lines))
    except SyntaxError:
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try) or not any(map(_broad, node.handlers)):
            continue
        for stmt in node.body:
            for call in ast.walk(stmt):
                if (
                    isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Attribute)
                    and call.func.attr in DEVICE_QUERIES
                    and PRAGMA not in lines[call.lineno - 1]
                ):
                    out.append(
                        (call.lineno, f"device query .{call.func.attr}() under "
                         "`except Exception`: a failed device query is an "
                         "error, not a fallback")
                    )
    return out


def _executor_asks(lines):
    """(line, message) for every call of is_tpu() / default_backend()
    and every read of the environment."""
    try:
        tree = ast.parse("".join(lines))
    except SyntaxError:
        return []
    out = []
    for node in ast.walk(tree):
        if not hasattr(node, "lineno") or PRAGMA in lines[node.lineno - 1]:
            continue
        name = (
            node.id if isinstance(node, ast.Name)
            else node.attr if isinstance(node, ast.Attribute)
            else None
        )
        if name in ("environ", "getenv"):
            out.append(
                (node.lineno, "the executor reads the process environment: "
                 "a kernel is chosen from shapes and widths, not by the "
                 "shell that started the process")
            )
        elif isinstance(node, ast.Call) and (
            getattr(node.func, "id", None) or getattr(node.func, "attr", None)
        ) in ("is_tpu", "default_backend"):
            out.append(
                (node.lineno, "the executor asks which backend it runs on: "
                 "a kernel is chosen from shapes and widths, one lowering "
                 "on every platform")
            )
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    violations = check(root)
    for rel, line, msg in violations:
        print(f"{rel}:{line}: {msg}")
    if violations:
        print(f"{len(violations)} backend-gate violation(s)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
