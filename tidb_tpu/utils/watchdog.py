"""Instance-level watchdogs: memory-usage alarm, expensive-query log,
server memory limit.

Reference: pkg/util/memoryusagealarm/memoryusagealarm.go (record alarm
when instance memory passes a ratio of total), pkg/util/expensivequery/
expensivequery.go (log statements running past a threshold), and
pkg/util/servermemorylimit/servermemorylimit.go:51 (kill the top memory
consumer when the instance limit is breached).

One daemon per catalog samples host RSS and walks the session registry
(the same WeakValueDictionary PROCESSLIST uses). The "top consumer" is
the active session with the largest admitted device/host working set
(PhysicalExecutor.last_working_set, the byte total the quota-admission
tracker computes per execution), falling back to the longest-running
statement. Events surface through information_schema.memory_usage /
memory_usage_alarm_records and the metrics registry.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional


def host_memory() -> tuple:
    """(rss bytes, total bytes) from /proc (Linux)."""
    rss = total = 0
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    rss = int(line.split()[1]) * 1024
                    break
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    total = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    return rss, total


def parse_mem_limit(v, total: int) -> int:
    """tidb_server_memory_limit: '80%' | bytes | '0' (off) -> bytes."""
    s = str(v).strip()
    if not s or s == "0":
        return 0
    if s.endswith("%"):
        try:
            return int(total * float(s[:-1]) / 100.0)
        except ValueError:
            return 0
    try:
        return int(float(s))
    except ValueError:
        return 0


def gvar(catalog, name, default):
    """A GLOBAL sysvar as the watchdog sees it: explicit SET GLOBAL
    value, else the registered SysVarDef default (so e.g. the
    reference's tidb_server_memory_limit='80%' default is ENFORCED,
    not just displayed), else `default`."""
    v = catalog.global_sysvars.get(name)
    if v is not None:
        return v
    from tidb_tpu.utils.sysvar import SYSVAR_DEFS

    d = SYSVAR_DEFS.get(name)
    return d.default if d is not None else default


class InstanceWatchdog(threading.Thread):
    """Daemon sampler over one catalog's sessions."""

    def __init__(self, catalog, interval: float = 2.0):
        super().__init__(daemon=True, name="watchdog-instance")
        self.catalog = catalog
        self.interval = interval
        self.stop_flag = threading.Event()
        self.alarm_records: List[dict] = []
        self.kill_records: List[dict] = []
        self.expensive_seen: set = set()
        self.last_rss = 0
        self.samples = 0

    def _gvar(self, name, default):
        return gvar(self.catalog, name, default)

    def run(self) -> None:  # pragma: no cover - loop plumbing
        from tidb_tpu.obs.flight import FLIGHT
        from tidb_tpu.utils.failpoint import FailpointError

        while not self.stop_flag.wait(self.interval):
            try:
                with FLIGHT.background("watchdog-instance"):
                    self.sample()
            except FailpointError:
                raise  # injected faults must be observable in tests
            except Exception:
                pass  # the watchdog must never take the engine down

    def sessions(self):
        reg = getattr(self.catalog, "_session_registry", None) or {}
        return [s for s in list(reg.values()) if s is not None]

    def sample(self) -> None:
        from tidb_tpu.utils.failpoint import inject
        from tidb_tpu.utils.metrics import REGISTRY

        inject("watchdog/sample")

        self.samples += 1
        now = time.time()
        rss, total = host_memory()
        self.last_rss = rss

        # ---- expensive-query log (expensivequery.go) ------------------
        thr = float(self._gvar("tidb_expensive_query_time_threshold", 60))
        for s in self.sessions():
            cur = s._current_stmt
            if cur is None:
                continue
            elapsed = now - cur[1]
            key = (s.conn_id, cur[1])
            if elapsed >= thr and key not in self.expensive_seen:
                self.expensive_seen.add(key)
                REGISTRY.counter(
                    "tidbtpu_watchdog_expensive_queries_total",
                    "statements running past the expensive threshold",
                ).inc()
                # the expensive-query entry rides the slow log, so it
                # honors the slow_query_log on/off switch like the
                # session call site. Its admission bar is its OWN
                # sysvar (tidb_expensive_query_time_threshold, checked
                # above) — the statement is still RUNNING here, so
                # comparing the in-flight elapsed against
                # tidb_slow_log_threshold would suppress entries whose
                # final elapsed crosses it moments later
                if bool(self._gvar("slow_query_log", True)):
                    from tidb_tpu.utils.metrics import SLOW_LOG

                    SLOW_LOG.record(
                        f"[expensive_query] conn={s.conn_id} "
                        f"elapsed={elapsed:.1f}s sql={str(cur[0])[:200]}",
                        elapsed,
                        conn_id=s.conn_id,
                    )
        if len(self.expensive_seen) > 4096:
            self.expensive_seen.clear()

        # ---- memory usage alarm (memoryusagealarm.go) -----------------
        ratio = float(self._gvar("tidb_memory_usage_alarm_ratio", 0.7))
        if total and rss > ratio * total:
            keep = int(self._gvar(
                "tidb_memory_usage_alarm_keep_record_num", 5
            ))
            self.alarm_records.append(
                {"time": now, "rss": rss, "total": total, "ratio": ratio}
            )
            del self.alarm_records[:-max(keep, 1)]
            REGISTRY.counter(
                "tidbtpu_watchdog_memory_usage_alarms_total",
                "instance memory passed the alarm ratio",
            ).inc()

        # ---- server memory limit (servermemorylimit.go:51) ------------
        limit = parse_mem_limit(
            self._gvar("tidb_server_memory_limit", "0"), total
        )
        if limit and rss > limit:
            victim = self.top_consumer()
            if victim is not None:
                victim.killer.kill()
                self.kill_records.append(
                    {
                        "time": now,
                        "conn_id": victim.conn_id,
                        "sql": str(victim._current_stmt[0])[:200]
                        if victim._current_stmt
                        else "",
                        "rss": rss,
                        "limit": limit,
                        "working_set": getattr(
                            victim.executor, "last_working_set", 0
                        ),
                    }
                )
                del self.kill_records[:-64]
                REGISTRY.counter(
                    "tidbtpu_watchdog_server_memory_limit_kills_total",
                    "statements killed at the instance memory limit",
                ).inc()

    def top_consumer(self) -> Optional[object]:
        """The active session with the largest admitted working set
        (falls back to the longest-running statement)."""
        best, best_key = None, (-1, -1.0)
        now = time.time()
        for s in self.sessions():
            cur = s._current_stmt
            if cur is None:
                continue
            ws = int(getattr(s.executor, "last_working_set", 0) or 0)
            key = (ws, now - cur[1])
            if key > best_key:
                best, best_key = s, key
        return best


def ensure_watchdog(catalog, interval: float = 2.0) -> InstanceWatchdog:
    """One watchdog per base catalog, started lazily (the TTL/auto-
    analyze daemon pattern)."""
    base = getattr(catalog, "_base", catalog)
    wd = getattr(base, "_watchdog", None)
    if wd is None or not wd.is_alive():
        wd = base._watchdog = InstanceWatchdog(base, interval=interval)
        wd.start()
    return wd
