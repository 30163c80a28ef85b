"""Stats handle: auto-analyze lifecycle.

Reference: pkg/statistics/handle — the stats owner tracks per-table
modify counters and HandleAutoAnalyze (handle/autoanalyze/
autoanalyze.go:264) re-analyzes tables whose modified-row ratio
exceeds tidb_auto_analyze_ratio. Here the counters live on the Table
(storage/table.modify_count); the handle offers both a synchronous
statement-boundary check (deterministic, used by the session after
DML) and a background daemon loop (the reference's analyze worker).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from tidb_tpu.stats.collect import analyze_table

#: tables smaller than this are not worth auto-analyzing (reference
#: keeps a similar floor so tiny tables don't churn the stats cache)
MIN_AUTO_ANALYZE_ROWS = 64


def needs_analyze(table, ratio: float) -> bool:
    changed = table.modify_count - table.analyzed_modify
    if changed <= 0:
        return False
    if getattr(table, "stats", None) is None:
        # never analyzed: wait for a non-trivial table
        return table.nrows >= MIN_AUTO_ANALYZE_ROWS
    # previously analyzed: refresh whenever the ratio trips — including
    # shrink-to-empty (DELETE all), where stale histograms would keep
    # reporting the old row counts to the planner
    return changed > ratio * max(table.nrows, 1)


def maybe_auto_analyze(table, ratio: float = 0.5) -> bool:
    """Analyze `table` if its modify ratio crossed the threshold.
    Returns True when an analyze ran."""
    if not needs_analyze(table, ratio):
        return False
    analyze_table(table)  # also resets table.analyzed_modify
    from tidb_tpu.utils.metrics import REGISTRY

    REGISTRY.counter(
        "tidbtpu_stats_auto_analyze_total", "auto-analyze runs"
    ).inc()
    return True


class StatsHandle:
    """Background auto-analyze worker over a catalog (the reference's
    stats owner loop). Start one per process; stop() on shutdown."""

    def __init__(self, catalog, interval_s: float = 30.0, ratio: float = 0.5):
        self.catalog = catalog
        self.interval_s = interval_s
        self.ratio = ratio
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _sysvar(self, name, default):
        g = getattr(self.catalog, "global_sysvars", None) or {}
        v = g.get(name)
        return default if v is None else v

    def tick(self) -> int:
        """One sweep; returns the number of tables analyzed. Honors the
        shared global sysvars (SET GLOBAL tidb_enable_auto_analyze /
        tidb_auto_analyze_ratio reach the daemon too)."""
        enabled = self._sysvar("tidb_enable_auto_analyze", True)
        if not enabled or str(enabled) in ("0", "OFF", "False"):
            return 0
        try:
            ratio = float(self._sysvar("tidb_auto_analyze_ratio", self.ratio))
        except (TypeError, ValueError):
            ratio = self.ratio
        n = 0
        for db in list(self.catalog.databases()):
            if db.startswith("_") or db == "information_schema":
                continue
            for name in list(self.catalog.tables(db)):
                try:
                    t = self.catalog.table(db, name)
                    if maybe_auto_analyze(t, ratio):
                        n += 1
                except Exception:
                    continue  # dropped mid-sweep etc.
        return n

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()  # restartable after stop()

        def loop():
            from tidb_tpu.obs.flight import FLIGHT

            while not self._stop.wait(self.interval_s):
                with FLIGHT.background("stats-auto-analyze"):
                    self.tick()

        self._thread = threading.Thread(
            target=loop, name="stats-auto-analyze", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
