"""TTL table expiry worker.

Reference: pkg/ttl — scan/delete job manager over TTL-attributed tables
(ttlworker/job_manager.go, scan.go, del.go) driven by the timer
framework. Here a catalog sweep compares the TTL column against
NOW() - INTERVAL host-side (numpy over the columnar blocks — expiry is
a data-management chore, not a device-compute problem) and drops the
expired rows through the table's versioned delete path.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

from tidb_tpu.dtypes import Kind, US_PER_SECOND

_UNIT_SECONDS = {
    "second": 1,
    "minute": 60,
    "hour": 3600,
    "day": 86400,
    "week": 7 * 86400,
    "month": 30 * 86400,  # TTL cutoffs are approximate by design
}


def expire_table(table, now_unix: Optional[float] = None) -> int:
    """Delete rows whose TTL column is older than now - interval.
    Returns the number of rows removed."""
    if table.ttl is None:
        return 0
    col, iv, unit = table.ttl
    now_unix = time.time() if now_unix is None else now_unix
    cutoff_s = now_unix - iv * _UNIT_SECONDS[unit]
    typ = table.schema.types.get(col)
    if typ is None:
        return 0
    if typ.kind == Kind.DATE:
        cutoff = int(cutoff_s // 86400)
    elif typ.kind == Kind.DATETIME:
        cutoff = int(cutoff_s * US_PER_SECOND)
    else:
        return 0
    # snapshot+mask+swap happen inside ONE table-lock hold so the sweep
    # can't race a concurrent INSERT (NULL TTL values never expire)
    removed = table.purge_expired(col, cutoff)
    if removed:
        from tidb_tpu.storage.scan import clear_scan_cache

        clear_scan_cache()
        from tidb_tpu.utils.metrics import REGISTRY

        REGISTRY.counter(
            "tidbtpu_ttl_expired_rows_total", "rows purged by TTL"
        ).inc(removed)
    return removed


class TTLWorker:
    """Background expiry sweep over a catalog (pkg/ttl job manager)."""

    def __init__(self, catalog, interval_s: float = 60.0):
        self.catalog = catalog
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def tick(self, now_unix: Optional[float] = None) -> int:
        n = 0
        for db in list(self.catalog.databases()):
            if db.startswith("_") or db == "information_schema":
                continue
            for name in list(self.catalog.tables(db)):
                try:
                    n += expire_table(self.catalog.table(db, name), now_unix)
                except Exception:
                    # a broken TTL config must be visible, not silent
                    from tidb_tpu.utils.metrics import REGISTRY

                    REGISTRY.counter(
                        "tidbtpu_ttl_errors_total", "failed TTL sweeps"
                    ).inc()
                    continue
        return n

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            from tidb_tpu.obs.flight import FLIGHT

            while not self._stop.wait(self.interval_s):
                with FLIGHT.background("ttl-worker"):
                    self.tick()

        self._thread = threading.Thread(
            target=loop, name="ttl-worker", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
