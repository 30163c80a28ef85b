"""Bounded in-process metric time-series store — the retention tier
behind the ``metrics_schema`` virtual tables.

Reference: pkg/infoschema/metrics_schema.go exposes Prometheus HISTORY
as SQL (`metrics_schema.<metric>` tables with time/label columns the
inspection framework reads back); TiDB itself stores nothing — the
Prometheus server does. This engine has no Prometheus sidecar, so the
retention lives here: every registered tidbtpu_* counter/gauge/
histogram is sampled on a sysvar-tunable cadence into per-series
retention rings, and the catalog renders one virtual table per metric
family (storage/catalog.py) with time/label predicate pushdown into
this store (the session extracts WHERE conjuncts and sets a scan hint
before planning, so a `WHERE time >= ...` materializes only the
matching points, not the whole ring).

Sampling topology:

- the COORDINATOR samples its own registry locally (the background
  sampler thread at ``tidb_tpu_tsdb_sample_interval_s``, plus a
  passive statement-close tick — SAMPLER.maybe_sample — so an
  interval of 0 still accretes history at query cadence);
- WORKER processes sample their own registries and ship the pending
  rows piggybacked on the existing fenced fragment/shuffle replies
  (server/engine_rpc.py, the registry-delta pattern) plus an
  idle-flush on the heartbeat ping, merged here via ``merge_remote``
  with the worker clock rebased through the handshake offset.
  Delivery is AT-MOST-ONCE like the counter deltas: the ledger fence
  guarantees a reply's samples never merge twice; a lost reply drops
  its samples (the worker drained its buffer building the reply).

Bounded memory: per-series RAW ring (newest ``retention_points``
samples) + a DOWNSAMPLED ring behind it — every ``downsample_every``
points evicted from the raw ring fold into one coarse point (counters
keep the last cumulative value, gauges/histograms the mean), so old
history degrades in resolution instead of vanishing; coarse-ring
overflow is the only permanent loss and counts under
``tidbtpu_tsdb_points_evicted_total``. A series cap bounds label-
cardinality blowups the same way.

Self-metrics (declared under the ``tsdb`` subsystem):
tidbtpu_tsdb_samples_total, tidbtpu_tsdb_points_evicted_total,
tidbtpu_tsdb_sample_seconds. The store never samples itself
recursively — one sample pass reads the registry once, including
these.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Tuple

from tidb_tpu.utils import racecheck
from tidb_tpu.utils.metrics import (
    REGISTRY,
    STMT_HISTORY,
    STMT_SUMMARY,
    sample_rows,
)

#: the coordinator's host label in stored series (workers are labeled
#: by their engine-RPC address at merge)
COORDINATOR = "coordinator"


def _c_samples():
    return REGISTRY.counter(
        "tidbtpu_tsdb_samples_total",
        "metric samples stored (local sampler passes + merged worker "
        "rows)",
    )


def _c_evicted():
    return REGISTRY.counter(
        "tidbtpu_tsdb_points_evicted_total",
        "points permanently dropped from the downsampled ring (raw-"
        "ring evictions fold into coarse points and are not counted — "
        "they lose resolution, not history)",
    )


def _h_sample_seconds():
    return REGISTRY.histogram(
        "tidbtpu_tsdb_sample_seconds",
        "wall seconds per local registry sample pass (the sampler's "
        "own overhead, visible to the inspection engine like any "
        "other series)",
    )


class _Series:
    """One (metric, host, labelvalues) series: raw ring + coarse ring
    + the in-flight downsample accumulator. Mutated only under the
    store lock."""

    __slots__ = ("kind", "raw", "coarse", "acc_n", "acc_sum", "acc_last",
                 "acc_t")

    def __init__(self, kind: str, raw_cap: int, coarse_cap: int):
        self.kind = kind
        self.raw: "collections.deque" = collections.deque(maxlen=raw_cap)
        self.coarse: "collections.deque" = collections.deque(
            maxlen=coarse_cap
        )
        self.acc_n = 0
        self.acc_sum = 0.0
        self.acc_last = 0.0
        self.acc_t = 0.0


class TimeSeriesStore:
    """The bounded store. Series key: (metric, host, labelnames,
    labelvalues); the family registry (metric -> kind + labelnames)
    generates the metrics_schema table list."""

    def __init__(
        self,
        retention_points: int = 512,
        downsample_every: int = 8,
        max_series: int = 8192,
    ):
        self._lock = racecheck.make_lock("obs.tsdb")
        self._series: Dict[tuple, _Series] = {}
        #: metric -> (kind, labelnames) — the family vocabulary the
        #: catalog turns into virtual tables
        self._families: Dict[str, Tuple[str, tuple]] = {}
        self.retention_points = max(int(retention_points), 4)
        self.downsample_every = max(int(downsample_every), 1)
        self.max_series = max(int(max_series), 16)
        #: samples dropped because the series cap was hit (bounded-
        #: memory proof under label blowups; also visible via evicted)
        self.series_cap_drops = 0
        #: points materialized by the most recent query() — the
        #: pushdown tests assert a time-bounded scan reads fewer
        #: points than the ring holds
        self.last_scan_points = 0

    # -- write side -----------------------------------------------------
    def retune_retention(
        self,
        retention_points: Optional[int] = None,
        downsample_every: Optional[int] = None,
    ) -> None:
        """Live re-tune (the tidb_tpu_tsdb_* sysvar SET hook). New
        caps apply to every series: shrinking a raw ring folds the
        overflow through the normal downsample path."""
        with self._lock:
            if retention_points is not None:
                self.retention_points = max(int(retention_points), 4)
            if downsample_every is not None:
                self.downsample_every = max(int(downsample_every), 1)
            for s in self._series.values():
                if s.raw.maxlen != self.retention_points:
                    old = list(s.raw)
                    s.raw = collections.deque(
                        maxlen=self.retention_points
                    )
                    for pt in old[-self.retention_points:]:
                        s.raw.append(pt)
                    for pt in old[:-self.retention_points]:
                        self._fold(s, pt)
                if s.coarse.maxlen != self.retention_points:
                    s.coarse = collections.deque(
                        s.coarse, maxlen=self.retention_points
                    )

    def _fold(self, s: _Series, pt) -> None:
        """Fold one raw-ring evictee into the downsample accumulator;
        a full accumulator emits one coarse point. CUMULATIVE series —
        counters AND histogram count/sum stats — keep the last value
        (the mean of a cumulative series under-reads, which would
        inflate any window delta straddling the coarse->raw boundary);
        gauges keep the mean."""
        t, v = pt
        s.acc_n += 1
        s.acc_sum += v
        s.acc_last = v
        s.acc_t = t
        if s.acc_n >= self.downsample_every:
            agg = (
                s.acc_last if s.kind in ("counter", "histogram")
                else s.acc_sum / s.acc_n
            )
            if len(s.coarse) == s.coarse.maxlen:
                _c_evicted().inc()
            s.coarse.append((s.acc_t, agg))
            s.acc_n = 0
            s.acc_sum = 0.0

    def _append(self, key: tuple, kind: str, t: float, v: float) -> bool:
        """Append one point under the lock; returns False when the
        series cap rejected a NEW series."""
        s = self._series.get(key)
        if s is None:
            if len(self._series) >= self.max_series:
                self.series_cap_drops += 1
                return False
            s = self._series[key] = _Series(
                kind, self.retention_points, self.retention_points
            )
            self._families.setdefault(key[0], (kind, key[2]))
        if len(s.raw) == s.raw.maxlen:
            self._fold(s, s.raw[0])
        s.raw.append((t, v))
        return True

    def sample_registry(
        self,
        host: str = COORDINATOR,
        registry=REGISTRY,
        now: Optional[float] = None,
    ) -> int:
        """One local sample pass: every registered metric lands one
        point per series. Returns the number of points stored."""
        t0 = time.perf_counter()
        now = time.time() if now is None else float(now)
        rows = sample_rows(registry)
        stored = 0
        with self._lock:
            for name, lnames, lvalues, value, kind in rows:
                if self._append(
                    (name, host, tuple(lnames), tuple(lvalues)),
                    kind, now, value,
                ):
                    stored += 1
        _c_samples().inc(stored)
        _h_sample_seconds().observe(time.perf_counter() - t0)
        return stored

    def merge_remote(
        self, rows, host: str, offset_s: Optional[float] = None
    ) -> int:
        """Fold one reply's piggybacked worker sample rows in
        (``[name, [labelnames], [labelvalues], ts, value, kind]``,
        worker wall clock), rebasing onto the coordinator clock
        (coordinator_wall = worker_wall - offset, the timeline
        convention). Malformed rows from a skewed worker are dropped,
        never raised — telemetry must not fail the query. Called only
        behind the exactly-once ledger fence (dispatch replies) or on
        unique ping replies (the heartbeat idle-flush), so a sample
        batch lands at most once."""
        if not rows:
            return 0
        off = float(offset_s or 0.0)
        stored = 0
        with self._lock:
            for row in rows:
                try:
                    name, lnames, lvalues, ts, value, kind = row
                    if not str(name).startswith("tidbtpu_"):
                        continue
                    if self._append(
                        (str(name), str(host),
                         tuple(str(x) for x in lnames),
                         tuple(str(x) for x in lvalues)),
                        str(kind), float(ts) - off, float(value),
                    ):
                        stored += 1
                except Exception:
                    continue
        if stored:
            _c_samples().inc(stored)
        return stored

    # -- read side ------------------------------------------------------
    def families(self) -> Dict[str, Tuple[str, tuple]]:
        """metric -> (kind, labelnames): the metrics_schema table
        vocabulary (every name passed REGISTRY registration, which the
        check_metric_names lint pins to the declared subsystems)."""
        with self._lock:
            return dict(self._families)

    def family(self, metric: str) -> Optional[Tuple[str, tuple]]:
        with self._lock:
            return self._families.get(metric)

    def query(
        self,
        metric: str,
        t_lo: Optional[float] = None,
        t_hi: Optional[float] = None,
        labels: Optional[dict] = None,
        hosts=None,
    ) -> List[tuple]:
        """Matching points as (ts, host, labelvalues, value,
        resolution) rows, time-ascending. The time/label bounds are
        the PUSHDOWN surface — a bounded query materializes only the
        covered slice of each ring."""
        fam = self.family(metric)
        if fam is None:
            return []
        _kind, lnames = fam
        want = dict(labels or {})
        hosts = set(hosts) if hosts else None
        out: List[tuple] = []
        with self._lock:
            for key, s in self._series.items():
                name, host, knames, kvalues = key
                if name != metric:
                    continue
                if hosts is not None and host not in hosts:
                    continue
                if want:
                    kv = dict(zip(knames, kvalues))
                    if any(kv.get(k) != v for k, v in want.items()):
                        continue
                for ring, res in ((s.coarse, "ds"), (s.raw, "raw")):
                    for t, v in ring:
                        if t_lo is not None and t < t_lo:
                            continue
                        if t_hi is not None and t > t_hi:
                            continue
                        out.append((t, host, kvalues, v, res))
        out.sort(key=lambda r: (r[0], r[1], r[2]))
        self.last_scan_points = len(out)
        return out

    def series_count(self) -> int:
        with self._lock:
            return len(self._series)

    def point_count(self) -> int:
        """Total points held (raw + coarse) — the bounded-memory
        assertion surface."""
        with self._lock:
            return sum(
                len(s.raw) + len(s.coarse)
                for s in self._series.values()
            )

    def reset(self) -> None:
        with self._lock:
            self._series.clear()
            self._families.clear()
            self.series_cap_drops = 0
            self.last_scan_points = 0


TSDB = TimeSeriesStore()


# -- scan-hint pushdown ------------------------------------------------------
#
# The session extracts time/label conjuncts from a metrics_schema
# SELECT's WHERE clause and parks them here (thread-local) around
# planning + execution; the catalog's table builder consults the hint
# so only the covered slice materializes. Thread-local because the
# hint is per-statement state on the executing thread — concurrent
# sessions' scans must not see each other's bounds.

_scan_tls = threading.local()


def set_scan_hint(metric: str, t_lo=None, t_hi=None, labels=None) -> None:
    _scan_tls.hint = (str(metric), t_lo, t_hi, dict(labels or {}))


def clear_scan_hint() -> None:
    _scan_tls.hint = None


def scan_hint_for(metric: str):
    """(t_lo, t_hi, labels) when the current thread's hint targets
    ``metric``, else None (a join of two metric tables plans with no
    hint — correctness first, pushdown only on the single-table
    shape)."""
    hint = getattr(_scan_tls, "hint", None)
    if hint is None or hint[0] != metric:
        return None
    return hint[1], hint[2], hint[3]


# -- the sampler -------------------------------------------------------------


class TsdbSampler:
    """Cadence driver for the coordinator-local sample pass.

    Two modes, matching the heartbeat pattern (parallel/dcn.py):
    interval > 0 runs a daemon thread (live-retuned by the
    tidb_tpu_tsdb_sample_interval_s SET hook — an unchanged interval
    is a no-op, 0 stops the thread); interval == 0 leaves sampling to
    ``maybe_sample`` ticks at statement close (obs cost bounded by
    ``passive_interval_s``). Each tick also rotates the
    statements_summary history when its refresh interval elapsed, and
    feeds the fleet timeline's counter tracks while a capture is live
    — gauge samples between statements, so idle gaps stop rendering
    as flat lines (ISSUE 12 satellite)."""

    def __init__(self, store: TimeSeriesStore,
                 passive_interval_s: float = 15.0):
        self.store = store
        self.passive_interval_s = float(passive_interval_s)
        self._interval_s = 0.0
        self._last_sample = 0.0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # serializes retune against itself (two sessions SETting the
        # cadence concurrently must not leave two sampler threads)
        self._lock = racecheck.make_lock("obs.tsdb_sampler")

    def sample_once(self, now: Optional[float] = None) -> int:
        """One tick: local registry sample + history rotation + the
        timeline counter-track feed."""
        now = time.time() if now is None else float(now)
        self._last_sample = now
        n = self.store.sample_registry(now=now)
        try:
            STMT_HISTORY.maybe_rotate(STMT_SUMMARY, now=now)
        except Exception:
            pass  # history rotation must never fail a sample pass
        from tidb_tpu.obs.timeline import TIMELINE

        if TIMELINE.active():
            TIMELINE.sample_gauges()
        return n

    def maybe_sample(self, now: Optional[float] = None) -> bool:
        """Passive tick (statement close): sample when the effective
        interval elapsed. With a background thread running this is a
        cheap no-op — the thread owns the cadence."""
        if self._interval_s > 0:
            return False
        now = time.time() if now is None else float(now)
        if self._last_sample and (
            now - self._last_sample < self.passive_interval_s
        ):
            return False
        self.sample_once(now=now)
        return True

    def interval_s(self) -> float:
        return self._interval_s

    def retune(self, interval_s: float) -> None:
        interval_s = max(float(interval_s), 0.0)
        with self._lock:
            if interval_s == self._interval_s:
                return
            self._interval_s = interval_s
            # lock-blocking-ok: joining the outgoing sampler thread
            # under the retune lock is what guarantees at most one
            # ever runs (the heartbeat retune invariant); the thread
            # takes no locks of ours while exiting
            self._stop.set()
            if self._thread is not None:
                self._thread.join(timeout=5)
                self._thread = None
            self._stop = threading.Event()
            if interval_s > 0:
                self._thread = threading.Thread(
                    target=self._loop,
                    args=(interval_s, self._stop),
                    daemon=True, name="obs-tsdb-sampler",
                )
                self._thread.start()

    def _loop(self, interval_s: float, stop: threading.Event) -> None:
        # loops on ITS OWN stop event (captured at start): retune
        # replaces self._stop for the next thread — see the heartbeat
        # loop's rationale in parallel/dcn.py
        from tidb_tpu.obs.flight import FLIGHT

        while not stop.wait(interval_s):
            try:
                with FLIGHT.background("obs-tsdb-sampler"):
                    self.sample_once()
            except Exception:
                pass

    def stop(self) -> None:
        self.retune(0.0)


SAMPLER = TsdbSampler(TSDB)
