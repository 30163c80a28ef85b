#!/usr/bin/env python
"""Lint: every metric registered on the global REGISTRY follows the
``tidbtpu_<subsystem>_<name>`` naming convention, with the subsystem
token drawn from the DECLARED registry below.

Why: metric names are an API — dashboards, alert rules and the BENCH
metrics snapshots all key on them. A drifting prefix (tidb_tpu_ vs
tidbtpu_ vs tidbtpu-) silently forks the series, and so does a
drifting subsystem token (tidbtpu_flight_ vs tidbtpu_flights_):
SUBSYSTEMS is the closed vocabulary (the failpoint-SITES pattern) — a
new family (e.g. PR 6's ``flight`` and ``link``) is declared here
FIRST, then used.

Scans every ``REGISTRY.counter/gauge/histogram("literal", ...)`` call
site (multi-line calls included) outside tests/. Non-literal names are
skipped — there are none today; keep it that way.

Usage: python scripts/check_metric_names.py [root]
Exit 0 = clean, 1 = violations (printed one per line).
"""

from __future__ import annotations

import os
import re
import sys

#: the declared subsystem vocabulary. delta = the HTAP delta tier
#: (PR 13, storage/delta.py — coordinator log depth/bytes, delta-sync
#: shipping, fold barriers, freshness waits), dcn = fragment scheduler,
#: shuffle = worker-to-worker data plane, engine = TPU engine watch,
#: flight = the query flight recorder, link = per-peer DCN link health
#: (both PR 6), admission = the serving tier's fleet admission
#: controller (PR 8, parallel/serving.py), timeline = the fleet
#: timeline tracer (PR 9, obs/timeline.py), chaos = the deterministic
#: fault-injection harness (PR 10, tidb_tpu/chaos/), tsdb = the
#: metric time-series store behind metrics_schema (PR 12,
#: obs/tsdb.py — sampler overhead self-metrics), inspection = the
#: declared-rule diagnosis engine (PR 12, obs/inspection.py),
#: topsql = the fleet-wide Top SQL continuous profiler (PR 14,
#: obs/profiler.py — per-digest cpu/device/stall attribution series
#: plus sampler self-metrics), aqe = adaptive query execution (PR 15,
#: parallel/aqe.py — decision counters, probe wall, misestimates).
#: The shuffle subsystem additionally carries the PR 19 runtime-filter
#: families: tidbtpu_shuffle_filter_built_total{kind},
#: tidbtpu_shuffle_filter_bytes, tidbtpu_shuffle_filter_dropped_rows_total
#: (parallel/shuffle.py) and the tidbtpu_shuffle_filter_selectivity
#: histogram (parallel/dcn.py — observed keep-rate per filtered stage).
#: planner = the logical planner's own decisions (PR 36,
#: planner/logical.py — where the join order put an IN's semi join).
SUBSYSTEMS = frozenset({
    "admission",
    "aqe",
    "chaos",
    "dcn",
    "delta",
    "engine",
    "executor",
    "flight",
    "inspection",
    "link",
    "planner",
    "session",
    "shuffle",
    "stats",
    "timeline",
    "topsql",
    "tsdb",
    "ttl",
    "watchdog",
})

CALL = re.compile(
    r"(?:REGISTRY|_REG)\s*\.\s*(?:counter|gauge|histogram)\(\s*[\"']([^\"']+)[\"']"
)
NAME = re.compile(r"^tidbtpu_([a-z][a-z0-9]*)_[a-z][a-z0-9_]*$")
SKIP_DIRS = {".git", ".jax_cache", "__pycache__", "node_modules", "tests"}
SKIP_FILES = {os.path.join("scripts", "check_metric_names.py")}


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
        if rel in SKIP_FILES:
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                text = f.read()
        except OSError:
            continue
        for m in CALL.finditer(text):
            name = m.group(1)
            nm = NAME.match(name)
            line = text.count("\n", 0, m.start()) + 1
            if not nm:
                violations.append(
                    (rel, line,
                     f"metric name {name!r} violates the "
                     "tidbtpu_<subsystem>_<name> convention")
                )
            elif nm.group(1) not in SUBSYSTEMS:
                violations.append(
                    (rel, line,
                     f"metric name {name!r} uses undeclared subsystem "
                     f"{nm.group(1)!r} (declare it in SUBSYSTEMS, "
                     "scripts/check_metric_names.py)")
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
        print(f"{len(violations)} metric-name violation(s)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
