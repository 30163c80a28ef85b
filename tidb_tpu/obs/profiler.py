"""Top SQL: the fleet-wide continuous statement profiler.

Reference: pkg/util/topsql — TiDB keeps a low-overhead CPU-time
sampler running UNDER PRODUCTION LOAD, attributing every sampled
instant to the SQL digest executing on that goroutine and shipping
per-digest aggregates to a collector. "Accelerating Presto with GPUs"
(PAPERS.md) makes the same argument for accelerator fleets:
attribution must be cheap enough to leave on while serving, or the
question "who is burning the fleet's cycles right now" is only
answerable after the incident.

Topology (mirrors the PR 12 tsdb tier exactly):

- every PROCESS (coordinator + each dcn_worker) runs its own
  ``TopSqlProfiler``: a daemon thread walks ``sys._current_frames()``
  on a sysvar-tunable cadence (``tidb_tpu_topsql_sample_interval_s``)
  and attributes each registered thread's sampled instant to its live
  task context — the statement digest, the thread's live flight phase,
  and a cpu/device/stall kind classified from the sampled stack
  (frames inside jax/jaxlib = device work; an innermost blocking
  primitive = stall; anything else = python CPU);
- per-digest aggregates land in a bounded ``TopSqlStore`` AND move
  declared ``tidbtpu_topsql_*`` registry counters, so the coordinator
  tsdb sampler retains windowed history locally and WORKER windows
  ship piggybacked on the fenced fragment/shuffle replies plus the
  heartbeat idle-flush — the PR 12 rows, no new wire machinery;
- collapsed call stacks (the flamegraph half) cannot ride metric
  labels (unbounded cardinality), so each worker drains its pending
  stack deltas into a ``topsql`` reply key (``ship()``, at-most-once
  like the tsdb rows) and the coordinator folds them per instance
  (``merge_remote``) for the /profile exporter and the
  information_schema.top_sql virtual table.

Attribution contexts are a DECLARED registry (``CATEGORIES``, the
failpoint-SITES pattern): every ``begin_task``/``task_context`` call
site names a literal category, scripts/check_topsql_attrib.py
cross-checks the literals against the declaration (undeclared use and
dead declarations both fail), and the runtime rejects undeclared names
too. The thread registration itself is always on and O(1) (two dict
writes per statement/task) — only an ENABLED profiler pays for
sampling, and a disabled one costs one predicate per statement.

Bounded memory, the stmt-summary discipline:

- ``tidb_top_sql_max_time_series_count`` caps DISTINCT DIGESTS
  tracked per process. Admitting a new digest at the cap evicts the
  coldest entry and folds its aggregates + stacks into the reserved
  ``(others)`` digest (the StmtHistory evicted-digest fold-in:
  totals survive capacity churn, identity does not);
- ``tidb_top_sql_max_meta_count`` caps META: distinct collapsed-stack
  strings plus digest->text mappings. Overflowing stacks fold into a
  single ``(truncated)`` frame so sample COUNTS stay exact even when
  stack identity is dropped.

Digests are stable 16-hex sha1 prefixes of the normalized statement
text (utils/metrics.sql_digest) — ``hash()`` is per-process salted and
could never match across the fleet. Workers learn the digest from the
dispatch itself (the frag/shuffle_task specs carry it), so a worker
never attributes to a finished or foreign qid: no context, no sample.
"""

from __future__ import annotations

import contextlib
import hashlib
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from tidb_tpu.utils import racecheck
from tidb_tpu.utils.metrics import REGISTRY

#: declared sample-attribution categories (scripts/check_topsql_attrib
#: cross-checks every begin_task/task_context literal against this, and
#: a declared category no site uses fails the lint):
#: - statement: a session thread executing a top-level statement (the
#:   flight recorder registers it in FLIGHT.begin);
#: - fragment: a worker executing one dispatched plan fragment;
#: - shuffle: a worker shuffle-stage task (produce/push/wait/stage,
#:   including its shipper threads);
#: - sample: a range exchange's boundary-sampling round.
CATEGORIES = (
    "statement",
    "fragment",
    "shuffle",
    "sample",
)

_CATEGORY_SET = frozenset(CATEGORIES)

#: the reserved digest evicted entries fold into (never evicted itself,
#: exempt from the digest cap)
OTHERS_DIGEST = "(others)"
#: the reserved collapsed-stack meta overflow folds into
TRUNCATED_STACK = "(truncated)"

#: innermost-frame code names that mean the thread is PARKED, not
#: burning CPU: lock/cv waits, socket I/O, sleeps. A sample landing on
#: one of these classifies as "stall" — the third column of the
#: cpu/device/stall split top_sql surfaces.
_STALL_FUNCS = frozenset({
    "wait", "wait_for", "_wait_for_tstate_lock", "acquire", "sleep",
    "recv", "recv_into", "recvfrom", "accept", "connect", "send",
    "sendall", "select", "poll", "epoll", "read", "readinto",
    "readline", "flush", "getaddrinfo", "join", "get", "put",
    "settimeout", "do_handshake",
})

#: path fragments that mark a frame as INSIDE the jax/XLA runtime —
#: a thread sampled there is driving (or blocked on) device work, the
#: "device" kind. Matched on normalized forward-slash paths.
_DEVICE_PATH_MARKS = ("/jax/", "/jaxlib/", "/jax_plugins/")


def digest_of(normalized_sql: str) -> str:
    """Stable fleet-wide digest id for a normalized statement text
    (sql_digest output): 16 hex chars of sha1. hash() is per-process
    salted (PYTHONHASHSEED), so it can never join coordinator and
    worker attributions — this can."""
    return hashlib.sha1(
        normalized_sql.encode("utf-8", "replace")
    ).hexdigest()[:16]


# -- self-metrics (the `topsql` subsystem; the per-digest aggregate
# counters live here too so worker movement rides the PR 12 tsdb
# piggyback and the coordinator sampler retains local history) --------


def _c_cpu_seconds():
    return REGISTRY.counter(
        "tidbtpu_topsql_cpu_seconds",
        "sampled python-CPU seconds attributed per statement digest "
        "and flight phase",
        labels=("digest", "phase"),
    )


def _c_device_seconds():
    return REGISTRY.counter(
        "tidbtpu_topsql_device_seconds",
        "sampled seconds spent inside the jax/XLA runtime (driving or "
        "blocked on device work) per digest and phase",
        labels=("digest", "phase"),
    )


def _c_stall_seconds():
    return REGISTRY.counter(
        "tidbtpu_topsql_stall_seconds",
        "sampled seconds parked in blocking primitives (lock/socket/"
        "sleep) per digest and phase",
        labels=("digest", "phase"),
    )


def _c_samples():
    return REGISTRY.counter(
        "tidbtpu_topsql_samples_total",
        "attributed samples per declared attribution category",
        labels=("category",),
    )


def _c_dropped():
    return REGISTRY.counter(
        "tidbtpu_topsql_samples_dropped_total",
        "samples that could not be attributed (no digest on the task "
        "context, or the store's caps rejected the entry)",
    )


def _c_evictions():
    return REGISTRY.counter(
        "tidbtpu_topsql_digest_evictions_total",
        "digest entries evicted at the series cap and folded into the "
        "(others) aggregate",
    )


def _g_digests():
    return REGISTRY.gauge(
        "tidbtpu_topsql_digests",
        "distinct statement digests currently tracked by this "
        "process's store",
    )


def _h_pass_seconds():
    return REGISTRY.histogram(
        "tidbtpu_topsql_sample_pass_seconds",
        "wall seconds per sampler pass (the profiler's own overhead, "
        "measurable like any other series)",
    )


# -- thread task contexts ----------------------------------------------------


class _TaskCtx:
    """One thread's live attribution: who to charge samples to.
    ``digest`` may start None for statement contexts (computed lazily
    by the SAMPLER thread from the flight record's SQL, so the
    statement hot path never pays normalization); ``phase`` is read
    from the flight record when one is attached, else from the mutable
    field worker tasks update at their phase boundaries."""

    __slots__ = ("category", "digest", "phase", "rec", "sql")

    def __init__(self, category, digest=None, phase="execute",
                 rec=None, sql=None):
        self.category = category
        self.digest = digest
        self.phase = phase
        self.rec = rec
        self.sql = sql


#: thread ident -> _TaskCtx. Plain dict: single-key reads/writes are
#: GIL-atomic, and the sampler iterates over a list() snapshot — the
#: racy-read worst case is one sample attributed to a just-finished
#: task, which the at-begin re-registration bounds to one tick.
_TASKS: Dict[int, _TaskCtx] = {}


def begin_task(
    category: str, digest: Optional[str] = None, phase: str = "execute",
    rec=None, sql: Optional[str] = None,
) -> Optional[_TaskCtx]:
    """Register the CURRENT thread's attribution context; returns the
    context it replaced (restore it via ``end_task``). Undeclared
    categories raise — the registry, not the call site, owns the
    vocabulary."""
    if category not in _CATEGORY_SET:
        raise ValueError(
            f"undeclared topsql attribution category {category!r} "
            "(declare it in tidb_tpu/obs/profiler.py CATEGORIES)"
        )
    tid = threading.get_ident()
    prev = _TASKS.get(tid)
    _TASKS[tid] = _TaskCtx(category, digest, phase, rec, sql)
    return prev


def end_task(prev: Optional[_TaskCtx] = None) -> None:
    """Unregister the current thread (restoring ``prev`` when the
    task nested inside another registered context)."""
    tid = threading.get_ident()
    if prev is not None:
        _TASKS[tid] = prev
    else:
        _TASKS.pop(tid, None)


@contextlib.contextmanager
def task_context(
    category: str, digest: Optional[str] = None, phase: str = "execute",
    sql: Optional[str] = None,
):
    prev = begin_task(category, digest=digest, phase=phase, sql=sql)
    try:
        yield
    finally:
        end_task(prev)


def set_task_phase(phase: str) -> None:
    """Update the current thread's live phase marker (worker shuffle
    tasks call this at their produce/push/wait/stage boundaries)."""
    ctx = _TASKS.get(threading.get_ident())
    if ctx is not None:
        ctx.phase = phase


def current_digest() -> Optional[str]:
    """The current thread's attribution digest, computing (and
    caching) a statement context's digest from its SQL on demand —
    the dispatch payload builder (parallel/dcn.py) uses this to stamp
    fragments with the digest the workers attribute to."""
    ctx = _TASKS.get(threading.get_ident())
    if ctx is None:
        return None
    return _resolve_digest(ctx)


def _resolve_digest(ctx: _TaskCtx) -> Optional[str]:
    if ctx.digest:
        return ctx.digest
    sql = ctx.sql
    if sql is None and ctx.rec is not None:
        sql = getattr(ctx.rec, "sql", None)
    if not sql:
        return None
    from tidb_tpu.utils.metrics import sql_digest

    ctx.digest = digest_of(sql_digest(sql))
    return ctx.digest


# -- sample classification ---------------------------------------------------


def classify_frame(frame) -> str:
    """cpu | device | stall for one sampled top frame: frames inside
    the jax/XLA runtime (innermost 6 checked — the runtime often sits
    just under a thin engine wrapper) are device work; an innermost
    blocking primitive is a stall; everything else is python CPU."""
    f = frame
    depth = 0
    while f is not None and depth < 6:
        fn = f.f_code.co_filename.replace("\\", "/")
        if any(m in fn for m in _DEVICE_PATH_MARKS):
            return "device"
        f = f.f_back
        depth += 1
    if frame.f_code.co_name in _STALL_FUNCS:
        return "stall"
    return "cpu"


def collapse_stack(frame, max_depth: int = 64) -> str:
    """FlameGraph collapsed-stack string, root-first, ';'-joined
    ``file.func`` frames (module basename keeps lines short; spaces
    never appear in either part, so the collapsed format's trailing
    ' count' parses cleanly)."""
    parts: List[str] = []
    f = frame
    while f is not None and len(parts) < max_depth:
        code = f.f_code
        base = code.co_filename.replace("\\", "/").rsplit("/", 1)[-1]
        if base.endswith(".py"):
            base = base[:-3]
        parts.append(f"{base}.{code.co_name}")
        f = f.f_back
    parts.reverse()
    return ";".join(parts)


# -- the bounded per-digest store --------------------------------------------


class _DigestEntry:
    __slots__ = ("cpu_s", "device_s", "stall_s", "samples", "by_phase",
                 "stacks", "last_ts")

    def __init__(self):
        self.cpu_s = 0.0
        self.device_s = 0.0
        self.stall_s = 0.0
        self.samples = 0
        #: phase -> [cpu_s, device_s, stall_s]
        self.by_phase: Dict[str, list] = {}
        #: collapsed stack -> seconds (meta-capped; overflow folds
        #: into TRUNCATED_STACK)
        self.stacks: Dict[str, float] = {}
        self.last_ts = 0.0

    def total_s(self) -> float:
        return self.cpu_s + self.device_s + self.stall_s

    def fold_from(self, other: "_DigestEntry") -> None:
        self.cpu_s += other.cpu_s
        self.device_s += other.device_s
        self.stall_s += other.stall_s
        self.samples += other.samples
        for ph, row in other.by_phase.items():
            mine = self.by_phase.setdefault(ph, [0.0, 0.0, 0.0])
            for i in range(3):
                mine[i] += row[i]
        self.last_ts = max(self.last_ts, other.last_ts)
        # stacks fold under the caller's meta accounting


class TopSqlStore:
    """Bounded per-(instance, digest) sample aggregates + collapsed
    stacks. The coordinator's store holds its OWN samples under
    ``self.instance`` plus every worker's merged ship payloads under
    that worker's address; worker stores hold only their own (their
    instance label is applied by the coordinator at merge, the tsdb
    convention)."""

    def __init__(
        self,
        instance: str = "coordinator",
        max_digests: int = 100,
        max_meta: int = 5000,
    ):
        self.instance = instance
        self._lock = racecheck.make_lock("obs.topsql")
        #: (instance, digest) -> _DigestEntry
        self._entries: Dict[Tuple[str, str], _DigestEntry] = {}
        #: digest -> normalized statement text (meta-capped)
        self._texts: Dict[str, str] = {}
        self.max_digests = max(int(max_digests), 1)
        self.max_meta = max(int(max_meta), 8)
        self._meta_count = 0
        #: pending worker ship deltas: digest -> {phase: [c,d,s]},
        #: digest -> {stack: seconds} — drained at-most-once into one
        #: reply (the tsdb _tsdb_pending contract)
        self._ship_agg: Dict[str, Dict[str, list]] = {}
        self._ship_stacks: Dict[str, Dict[str, float]] = {}
        self.dropped = 0

    # -- write side ----------------------------------------------------
    def retune_caps(
        self, max_digests: Optional[int] = None,
        max_meta: Optional[int] = None,
    ) -> None:
        """Live re-tune (the tidb_top_sql_max_* SET GLOBAL hook).
        Shrinking the digest cap folds overflow immediately."""
        with self._lock:
            if max_digests is not None:
                self.max_digests = max(int(max_digests), 1)
            if max_meta is not None:
                self.max_meta = max(int(max_meta), 8)
            self._enforce_digest_cap()

    def _local_digests(self) -> List[str]:
        return [
            d for (inst, d) in self._entries
            if inst == self.instance and d != OTHERS_DIGEST
        ]

    def _enforce_digest_cap(self) -> None:
        """Evict coldest LOCAL digests past the cap, folding each into
        the (others) aggregate — called under the lock."""
        local = self._local_digests()
        while len(local) > self.max_digests:
            coldest = min(
                local,
                key=lambda d: self._entries[
                    (self.instance, d)
                ].total_s(),
            )
            self._fold_into_others(coldest)
            local.remove(coldest)

    def _fold_into_others(self, digest: str) -> None:
        ent = self._entries.pop((self.instance, digest))
        others = self._entries.setdefault(
            (self.instance, OTHERS_DIGEST), _DigestEntry()
        )
        others.fold_from(ent)
        # the evictee's stack meta folds into the truncated bucket;
        # its per-stack identity is the cost of staying bounded
        folded = sum(ent.stacks.values())
        if folded:
            others.stacks[TRUNCATED_STACK] = (
                others.stacks.get(TRUNCATED_STACK, 0.0) + folded
            )
        # meta accounting: only COUNTED entries decrement — the
        # evictee's (truncated) bucket was created cap-exempt (never
        # incremented), and a popped text mapping DID count
        self._meta_count -= len(ent.stacks) - (
            1 if TRUNCATED_STACK in ent.stacks else 0
        )
        if self._texts.pop(digest, None) is not None:
            self._meta_count -= 1
        # the REGISTRY half of the cap: drop the evicted digest's
        # per-digest counter children too, or label cardinality (and
        # through the tsdb sampler, series count) would grow with
        # every digest EVER seen instead of the configured cap. A
        # re-admitted digest recreates its children from zero —
        # counter_delta ships forward-snapshots, so nothing goes
        # negative.
        for fam_fn in (
            _c_cpu_seconds, _c_device_seconds, _c_stall_seconds,
        ):
            try:
                fam_fn().remove_matching(lambda lv: lv[0] == digest)
            except Exception:
                pass  # registry hygiene must never fail a sample
        # pending ship deltas for the evictee re-key to (others) so a
        # worker's next reply still accounts the seconds
        pend = self._ship_agg.pop(digest, None)
        if pend:
            tgt = self._ship_agg.setdefault(OTHERS_DIGEST, {})
            for ph, row in pend.items():
                t = tgt.setdefault(ph, [0.0, 0.0, 0.0, 0])
                for i in range(4):
                    t[i] += row[i]
        pend_st = self._ship_stacks.pop(digest, None)
        if pend_st:
            tgt_st = self._ship_stacks.setdefault(OTHERS_DIGEST, {})
            tgt_st[TRUNCATED_STACK] = (
                tgt_st.get(TRUNCATED_STACK, 0.0)
                + sum(pend_st.values())
            )
        _c_evictions().inc()

    def note_text(self, digest: str, text: str) -> None:
        """digest -> normalized text meta (coordinator side; workers
        only ever see digest ids). Meta-capped: an overflowing text is
        simply not remembered — the digest still aggregates."""
        with self._lock:
            if digest in self._texts:
                return
            if self._meta_count >= self.max_meta:
                return  # meta-capped: the digest still aggregates
            self._texts[digest] = str(text)[:512]
            self._meta_count += 1

    def record(
        self, digest: str, phase: str, kind: str, seconds: float,
        stack: str, now: Optional[float] = None,
    ) -> bool:
        """Attribute one sampled instant. Moves the registry counters
        (the tsdb-visible half) AND the store aggregates + pending
        worker ship deltas. Returns False when the caps dropped it."""
        now = time.time() if now is None else now
        with self._lock:
            key = (self.instance, digest)
            ent = self._entries.get(key)
            if ent is None:
                local = self._local_digests()
                if (
                    len(local) >= self.max_digests
                    and digest != OTHERS_DIGEST
                ):
                    # cap reached: admit the newcomer by folding the
                    # coldest entry into (others) — the hot set stays
                    # adaptive (a genuinely hot newcomer must be able
                    # to displace yesterday's cold digests; a cold one
                    # will itself be the next fold victim), totals
                    # survive the churn under the aggregate digest
                    coldest = min(
                        local,
                        key=lambda d: self._entries[
                            (self.instance, d)
                        ].total_s(),
                    )
                    self._fold_into_others(coldest)
                ent = self._entries[key] = _DigestEntry()
            ent.samples += 1
            ent.last_ts = now
            row = ent.by_phase.setdefault(phase, [0.0, 0.0, 0.0])
            idx = {"cpu": 0, "device": 1, "stall": 2}[kind]
            row[idx] += seconds
            if kind == "cpu":
                ent.cpu_s += seconds
            elif kind == "device":
                ent.device_s += seconds
            else:
                ent.stall_s += seconds
            if stack:
                if stack not in ent.stacks:
                    if self._meta_count >= self.max_meta:
                        stack = TRUNCATED_STACK
                        if stack not in ent.stacks:
                            # the truncated bucket itself is exempt
                            ent.stacks[stack] = 0.0
                    else:
                        ent.stacks[stack] = 0.0
                        self._meta_count += 1
                ent.stacks[stack] += seconds
                st = self._ship_stacks.setdefault(digest, {})
                st[stack] = st.get(stack, 0.0) + seconds
            pend = self._ship_agg.setdefault(digest, {})
            prow = pend.setdefault(phase, [0.0, 0.0, 0.0, 0])
            prow[idx] += seconds
            prow[3] += 1
            ndigests = len(self._local_digests())
        # registry counters OUTSIDE the store lock (they take the
        # family locks): the tsdb sampler + worker piggyback surface
        {
            "cpu": _c_cpu_seconds, "device": _c_device_seconds,
            "stall": _c_stall_seconds,
        }[kind]().labels(digest=digest, phase=phase).inc(seconds)
        _g_digests().set(ndigests)
        return True

    def note_dropped(self, n: int = 1) -> None:
        with self._lock:
            self.dropped += n
        _c_dropped().inc(n)

    # -- worker shipping -----------------------------------------------
    def ship(self) -> Optional[dict]:
        """Drain the pending deltas into ONE reply payload (at-most-
        once: a lost reply loses its batch, exactly the tsdb-row
        contract). None when nothing is pending — idle replies stay
        small."""
        with self._lock:
            if not self._ship_agg and not self._ship_stacks:
                return None
            agg = [
                [d, ph, row[0], row[1], row[2], row[3]]
                for d, phases in self._ship_agg.items()
                for ph, row in phases.items()
            ]
            stacks = [
                [d, st, s]
                for d, sts in self._ship_stacks.items()
                for st, s in sts.items()
            ]
            self._ship_agg = {}
            self._ship_stacks = {}
            return {"agg": agg, "stacks": stacks, "ts": time.time()}

    def merge_remote(self, payload, instance: str) -> int:
        """Fold one FENCED reply's worker payload in under that
        worker's instance label. Malformed rows are dropped, never
        raised — telemetry must not fail the query. Returns merged
        row count."""
        if not payload:
            return 0
        merged = 0
        with self._lock:
            for row in payload.get("agg") or ():
                try:
                    d, ph, cpu, dev, stall, n = row
                    ent = self._remote_entry(str(instance), str(d))
                    prow = ent.by_phase.setdefault(
                        str(ph), [0.0, 0.0, 0.0]
                    )
                    prow[0] += float(cpu)
                    prow[1] += float(dev)
                    prow[2] += float(stall)
                    ent.cpu_s += float(cpu)
                    ent.device_s += float(dev)
                    ent.stall_s += float(stall)
                    ent.samples += int(n)
                    ent.last_ts = time.time()
                    merged += 1
                except Exception:
                    continue
            for row in payload.get("stacks") or ():
                try:
                    d, st, s = row
                    ent = self._remote_entry(str(instance), str(d))
                    if st not in ent.stacks:
                        if self._meta_count >= self.max_meta:
                            st = TRUNCATED_STACK
                            ent.stacks.setdefault(st, 0.0)
                        else:
                            ent.stacks[str(st)] = 0.0
                            self._meta_count += 1
                    ent.stacks[str(st)] = (
                        ent.stacks.get(str(st), 0.0) + float(s)
                    )
                    merged += 1
                except Exception:
                    continue
        return merged

    def _remote_entry(self, instance: str, digest: str) -> _DigestEntry:
        """Entry for a worker-merged digest, cap-bounded PER INSTANCE
        the same way local admission is (a worker that somehow ships
        unbounded digest ids must not grow coordinator memory): past
        the cap, new remote digests fold into that instance's
        (others). Called under the lock."""
        key = (instance, digest)
        ent = self._entries.get(key)
        if ent is not None:
            return ent
        if digest != OTHERS_DIGEST:
            ndig = sum(
                1 for (inst, d) in self._entries
                if inst == instance and d != OTHERS_DIGEST
            )
            if ndig >= self.max_digests:
                key = (instance, OTHERS_DIGEST)
                ent = self._entries.get(key)
                if ent is not None:
                    return ent
        ent = self._entries[key] = _DigestEntry()
        return ent

    # -- read side ------------------------------------------------------
    def text_of(self, digest: str) -> str:
        with self._lock:
            return self._texts.get(digest, "")

    def rows(self) -> List[dict]:
        """Per-(instance, digest) aggregates for the top_sql virtual
        table: cpu/device/stall seconds, samples, the phase breakdown,
        and the hottest frame (top-of-stack of the hottest collapsed
        stack)."""
        out = []
        # the whole extraction runs UNDER the lock: entries' stacks/
        # by_phase dicts are mutated by the sampler and reply merges —
        # iterating them after release races a concurrent insert
        # ("dict changed size during iteration" surfacing in a user's
        # SELECT)
        with self._lock:
            for (inst, d), ent in self._entries.items():
                top_frame = ""
                if ent.stacks:
                    hot = max(
                        ent.stacks.items(), key=lambda kv: kv[1]
                    )[0]
                    top_frame = hot.rsplit(";", 1)[-1]
                top_phase = ""
                if ent.by_phase:
                    top_phase = max(
                        ent.by_phase.items(),
                        key=lambda kv: sum(kv[1]),
                    )[0]
                out.append({
                    "instance": inst,
                    "digest": d,
                    "digest_text": self._texts.get(d, ""),
                    "cpu_s": ent.cpu_s,
                    "device_s": ent.device_s,
                    "stall_s": ent.stall_s,
                    "samples": ent.samples,
                    "by_phase": {
                        ph: list(row)
                        for ph, row in ent.by_phase.items()
                    },
                    "top_phase": top_phase,
                    "top_frame": top_frame,
                    "last_ts": ent.last_ts,
                })
        return out

    def collapsed(
        self, instance: Optional[str] = None,
        digest: Optional[str] = None,
    ) -> List[str]:
        """FlameGraph/speedscope-loadable collapsed lines, fleet-
        merged (or one instance / one digest): each line is
        ``digest;frame;...;frame <milliseconds>`` with the digest as
        the root frame so per-statement towers stay separable in the
        merged fleet profile."""
        merged: Dict[str, float] = {}
        with self._lock:
            for (inst, d), ent in self._entries.items():
                if instance is not None and inst != instance:
                    continue
                if digest is not None and d != digest:
                    continue
                for st, s in ent.stacks.items():
                    key = f"{d};{st}"
                    merged[key] = merged.get(key, 0.0) + s
        return [
            f"{st} {max(int(s * 1000), 1)}"
            for st, s in sorted(merged.items())
        ]

    def digest_count(self) -> int:
        with self._lock:
            return len(self._local_digests())

    def status(self) -> dict:
        with self._lock:
            return {
                "instance": self.instance,
                "digests": len(self._entries),
                "meta": self._meta_count,
                "max_digests": self.max_digests,
                "max_meta": self.max_meta,
                "dropped": self.dropped,
            }

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self._texts.clear()
            self._ship_agg = {}
            self._ship_stacks = {}
            self._meta_count = 0
            self.dropped = 0


# -- the sampler -------------------------------------------------------------


class TopSqlProfiler:
    """Per-process cadence driver: one daemon thread walking
    ``sys._current_frames()`` while enabled, attributing registered
    threads' samples into the store. retune() follows the
    TsdbSampler/heartbeat discipline: serialized on its own lock, the
    loop holds the stop event it captured at start, an unchanged
    config is a no-op — SET GLOBAL storms can never orphan a second
    sampler thread."""

    DEFAULT_INTERVAL_S = 0.02

    def __init__(self, store: Optional[TopSqlStore] = None):
        self.store = store or TopSqlStore()
        self._interval_s = 0.0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = racecheck.make_lock("obs.topsql_sampler")
        self._last_pass = 0.0

    def running(self) -> bool:
        return self._interval_s > 0

    def interval_s(self) -> float:
        return self._interval_s

    def retune(
        self, interval_s: float,
        max_digests: Optional[int] = None,
        max_meta: Optional[int] = None,
    ) -> None:
        """Arm/disarm/re-cadence the sampler; cap changes re-tune the
        store live (the PR 12 retune pattern)."""
        if max_digests is not None or max_meta is not None:
            self.store.retune_caps(max_digests, max_meta)
        interval_s = max(float(interval_s), 0.0)
        with self._lock:
            if interval_s == self._interval_s:
                return
            self._interval_s = interval_s
            # lock-blocking-ok: joining the outgoing sampler thread
            # under the retune lock is what guarantees at most one
            # ever runs (the TsdbSampler invariant); the exiting
            # thread takes no locks of ours on its way out
            self._stop.set()
            if self._thread is not None:
                self._thread.join(timeout=5)
                self._thread = None
            self._stop = threading.Event()
            if interval_s > 0:
                self._last_pass = time.time()
                self._thread = threading.Thread(
                    target=self._loop,
                    args=(interval_s, self._stop),
                    daemon=True, name="obs-topsql-sampler",
                )
                self._thread.start()

    def stop(self) -> None:
        self.retune(0.0)

    def apply_sysvars(self, gv) -> None:
        """Wire the declared knobs: SET GLOBAL tidb_enable_top_sql
        starts/stops the sampler, the two tidb_top_sql_max_* caps
        re-tune the store live (session.py SetVariable hook calls
        this with a session-override-free global view)."""
        enabled = bool(gv.get("tidb_enable_top_sql"))
        interval = float(gv.get("tidb_tpu_topsql_sample_interval_s"))
        self.retune(
            interval if enabled else 0.0,
            max_digests=int(gv.get("tidb_top_sql_max_time_series_count")),
            max_meta=int(gv.get("tidb_top_sql_max_meta_count")),
        )

    # -- fleet config propagation --------------------------------------
    def dispatch_config(self) -> Optional[dict]:
        """The topsql entry dispatches/pings carry to worker
        processes: None while disabled (a worker receiving None stops
        its sampler), else cadence + caps. The per-dispatch DIGEST is
        added by the dispatch builder — it is statement state, not
        profiler state."""
        if not self.running():
            return None
        return {
            "on": True,
            "interval_s": self._interval_s,
            "max_digests": self.store.max_digests,
            "max_meta": self.store.max_meta,
        }

    def apply_config(self, cfg) -> None:
        """Worker side of dispatch_config: idempotent, cheap when
        unchanged (dispatch streams re-send it on every frame)."""
        if not cfg or not cfg.get("on"):
            if self.running():
                self.stop()
            return
        interval = float(
            cfg.get("interval_s") or self.DEFAULT_INTERVAL_S
        )
        md = cfg.get("max_digests")
        mm = cfg.get("max_meta")
        if (
            interval == self._interval_s
            and (md is None or int(md) == self.store.max_digests)
            and (mm is None or int(mm) == self.store.max_meta)
        ):
            return
        self.retune(
            interval,
            max_digests=int(md) if md is not None else None,
            max_meta=int(mm) if mm is not None else None,
        )

    # -- the sample pass ------------------------------------------------
    def sample_once(self, now: Optional[float] = None) -> int:
        """One pass: attribute every REGISTERED thread's current frame.
        Each sample charges the wall covered since the previous pass
        (clamped to 4 intervals so a late wakeup cannot over-attribute)
        — the estimator every sampling profiler uses. Returns samples
        attributed."""
        t0 = time.perf_counter()
        now = time.time() if now is None else now
        dt = now - self._last_pass
        self._last_pass = now
        interval = self._interval_s or self.DEFAULT_INTERVAL_S
        dt = min(max(dt, 0.0), 4 * interval) or interval
        tasks = list(_TASKS.items())
        if not tasks:
            _h_pass_seconds().observe(time.perf_counter() - t0)
            return 0
        frames = sys._current_frames()
        attributed = 0
        for tid, ctx in tasks:
            frame = frames.get(tid)
            if frame is None:
                continue
            digest = _resolve_digest(ctx)
            if not digest:
                self.store.note_dropped()
                continue
            rec = ctx.rec
            phase = (
                getattr(rec, "live_phase", None) if rec is not None
                else None
            ) or ctx.phase or "execute"
            kind = classify_frame(frame)
            stack = collapse_stack(frame)
            if self.store.record(digest, phase, kind, dt, stack,
                                 now=now):
                attributed += 1
                _c_samples().labels(category=ctx.category).inc()
            else:
                self.store.note_dropped()
        del frames  # frames hold references into every thread
        _h_pass_seconds().observe(time.perf_counter() - t0)
        return attributed

    def _loop(self, interval_s: float, stop: threading.Event) -> None:
        # loops on ITS OWN stop event (captured at start): retune
        # replaces self._stop for the next thread — the heartbeat
        # loop's rationale in parallel/dcn.py
        from tidb_tpu.obs.flight import FLIGHT

        while not stop.wait(interval_s):
            try:
                with FLIGHT.background("obs-topsql-sampler"):
                    self.sample_once()
            except Exception:
                pass  # the profiler must never take the engine down


TOPSQL = TopSqlProfiler()


def note_statement_text(digest: str, normalized_text: str) -> None:
    """Remember digest -> normalized text meta (meta-capped). The
    session's observe path calls this so top_sql rows carry readable
    statements; workers never need it (they ship digest ids only)."""
    TOPSQL.store.note_text(digest, normalized_text)
