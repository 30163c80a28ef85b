"""Query flight recorder: always-on per-query phase timelines, and the
per-peer DCN link health registry.

Reference: the slow-query log with plan capture (pkg/executor/
slow_query.go writes `# Time/# Query_time/# Plan` records the
infoschema reads back), stmtsummary's per-digest aggregates
(pkg/util/stmtsummary/statement_summary.go:73) and Top SQL's
always-on attribution (pkg/util/topsql). "Accelerating Presto with
GPUs" (PAPERS.md) shows the accelerator lesson: the next optimization
is findable only when every query carries a per-stage device-vs-host
time breakdown.

Accounting model (mirrors obs/engine_watch.py):

- the session opens a *flight* per top-level statement on the executing
  thread (thread-local current record, like EngineWatch);
- every layer notes **phase seconds** into the current flight —
  parse/plan in the session, compile in ``watched_jit``'s traced body,
  execute/final-merge around the engine run, fragment-dispatch plus the
  shuffle produce/push/wait/stage breakdown when the statement rides
  the DCN scheduler (derived from the worker-reported stage stats the
  PR 3/5 shuffle replies already ship);
- finished flights land in a bounded ring and feed the three surfaces:
  information_schema.statements_summary (per-digest percentiles +
  mean phase breakdown + engine-watch join), information_schema.
  slow_query (phase timeline + captured plan text), and the
  tidbtpu_flight_* metric family.

A boundary is timed by ONE call: ``with FLIGHT.span(name)`` records the
span on the flight (path, offset from the statement's root span,
seconds), charges the declared phase of the same name, marks
``live_phase`` for the Top SQL sampler, feeds the session's ``TRACE``
tracer, and enters a ``jax.profiler.TraceAnnotation`` named
``tidbtpu/<span path>`` that carries the flight's ``qid`` — so under a
profiler session the statement's spans lie on the device trace's clock.
``FLIGHT.background(name)`` does the same for one tick of a background
loop; a finishing flight copies the ticks that ran beside it.

Phase and span names are DECLARED registries (``PHASES``, ``SPANS``),
the failpoint-SITES pattern: ``note_phase`` and ``span`` reject
undeclared names at runtime and scripts/check_flight_phases.py
cross-checks the declarations against the literal call sites (tier-1
via tests/test_flight_phases.py), so a typo'd name can neither
silently fork the breakdown nor rot unused.

``LINKS`` is the sibling registry for per-peer DCN link health
(information_schema.cluster_links, the /links endpoint): RTT and clock
offset from the engine-RPC handshake, heartbeat age, and the
worker-to-worker tunnel telemetry (bytes/frames/rows pushed,
backpressure stall seconds, retransmits, negotiated codec) merged from
shuffle replies — DCN regressions become visible per link, not just
per fleet.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from tidb_tpu.obs import profiler
from tidb_tpu.obs.timeline import TIMELINE
from tidb_tpu.utils import racecheck
from tidb_tpu.utils.metrics import REGISTRY

#: every phase a flight may charge time to. parse/plan/compile mirror
#: the reference's session phases; execute/final-merge bracket the
#: local engine run; fragment-dispatch is the coordinator-side wall of
#: a DCN-scheduled statement; the shuffle-* quartet is the
#: worker-reported stage breakdown (produce = engine time below the
#: exchange, push = partition encode+ship, wait = blocked on peers,
#: stage = landing received partitions as device batches).
PHASES = (
    "parse",
    "plan",
    # serving-tier waits before dispatch: admission-queue time
    # (parallel/serving.py AdmissionController) and resource-group RU
    # throttle waits on DCN-routed statements — how fleet saturation
    # shows up in a statement's timeline, right next to
    # fragment-dispatch (PERF_NOTES "reading the admission queue")
    "queue-wait",
    "compile",
    "execute",
    "final-merge",
    "fragment-dispatch",
    "shuffle-produce",
    "shuffle-push",
    "shuffle-wait",
    "shuffle-stage",
)

_PHASE_SET = frozenset(PHASES)

#: every span ``FLIGHT.span`` may open. A served statement nests them as
#: stmt (server.py: command packet read -> answer written) > session
#: (Session.execute) > parse | plan | execute > compile | inputs |
#: dispatch | device-wait | fetch, then final-merge and observe under
#: session and wire/write under stmt. A span named like a phase charges
#: that phase; the others cut time the phases lump together or leave out.
SPANS = (
    "stmt",
    "session",
    "parse",
    "plan",
    "execute",
    "compile",
    "inputs",
    "dispatch",
    "device-wait",
    "fetch",
    "final-merge",
    "observe",
    "wire/write",
)

_SPAN_SET = frozenset(SPANS)

#: the spans a statement's tree may hang from: opened with nothing above
#: them they draw the ``qid`` the statement's flight and annotations
#: carry. Any other span opened with nothing above it (a compile on a
#: worker's or ANALYZE's thread, the prepared fast path run without a
#: server) is annotated with qid 0 and kept nowhere.
_ROOTS = frozenset(("stmt", "session"))

#: background ticks remembered for the flights that finish after them
_TICK_RING = 512


def _c_queries():
    return REGISTRY.counter(
        "tidbtpu_flight_queries_total", "statements the flight recorder closed"
    )


def _c_phase_seconds():
    return REGISTRY.counter(
        "tidbtpu_flight_phase_seconds",
        "cumulative seconds charged per flight phase",
        labels=("phase",),
    )


def _c_slow_captures():
    return REGISTRY.counter(
        "tidbtpu_flight_slow_plan_captures_total",
        "over-threshold statements whose plan text was captured",
    )


def _h_query_seconds():
    return REGISTRY.histogram(
        "tidbtpu_flight_query_seconds", "flight-recorded statement latency"
    )


class QueryFlight:
    """One statement's structured timeline. ``phases`` maps a declared
    phase name to [seconds, bytes, retries] (bytes/retries are phase
    attributes: shuffle-push carries tunneled bytes, fragment-dispatch
    carries stage retries)."""

    __slots__ = (
        "qid", "conn_id", "sql", "start_ts", "duration_s", "phases",
        "plan_cache", "plan_digest", "rows_sent", "plan_text",
        "jit_compilations", "retraces", "h2d_bytes", "d2h_bytes",
        "device_mem_peak_bytes", "compile_flops",
        "compile_bytes_accessed", "compile_output_bytes", "live_phase",
        "est_rows", "act_rows", "spans", "served_s", "background",
        "exchanges", "exchange_rows", "exchange_bytes",
        "join_expansions", "join_expand_rows", "join_expand_slots",
        "sorted_groupings", "sorted_group_rows", "sorted_groups",
        "sorted_group_slots",
    )

    def __init__(self, qid: int, conn_id: int, sql: str):
        self.qid = qid
        self.conn_id = conn_id
        self.sql = sql
        self.start_ts = time.time()
        self.duration_s = 0.0
        self.phases: Dict[str, list] = {}
        #: "hit" | "miss" | "" — last compiled-plan-cache outcome the
        #: executor reported while this flight was current
        self.plan_cache = ""
        #: short fingerprint of the executor's compiled-plan cache key
        #: (process-local grouping; the reference ships a plan digest
        #: next to the SQL digest in stmtsummary)
        self.plan_digest = ""
        self.rows_sent = 0
        #: captured plan text (EXPLAIN tree, or the full distributed
        #: EXPLAIN ANALYZE lines when the statement ran instrumented)
        self.plan_text = ""
        self.jit_compilations = 0
        self.retraces = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        #: what the mesh programs this statement ran exchanged between
        #: chips: exchanges executed, the valid rows they sent (summed
        #: over shards) and the bytes those rows must carry across
        #: (parallel/exchange.py); all 0 on a one-device executor
        self.exchanges = 0
        self.exchange_rows = 0
        self.exchange_bytes = 0
        #: what the expanding joins (a build key that is not unique:
        #: executor/join.py) of the programs this statement ran did:
        #: joins executed, the rows they had to emit, and the slots of
        #: their output tiles (rows over slots is the tiles' fill)
        self.join_expansions = 0
        self.join_expand_rows = 0
        self.join_expand_slots = 0
        #: what the sorted group-bys (a packed key wider than the dense
        #: domain: executor/sortops.py) of the programs this statement
        #: ran did: group-bys executed, the valid rows that entered
        #: them, the groups they found and their group tables' slots
        #: (groups over slots is the tables' fill)
        self.sorted_groupings = 0
        self.sorted_group_rows = 0
        self.sorted_groups = 0
        self.sorted_group_slots = 0
        self.device_mem_peak_bytes = 0
        # XLA cost analysis summed over this statement's compiles
        # (obs/engine_watch.py per-signature harvest)
        self.compile_flops = 0.0
        self.compile_bytes_accessed = 0.0
        self.compile_output_bytes = 0.0
        #: planner-estimated vs observed output rows of a routed
        #: statement (AQE, PR 15): statements_summary exposes the
        #: per-digest mean divergence, and the cardinality feedback
        #: store learns from the pair
        self.est_rows = 0.0
        self.act_rows = 0.0
        #: the phase the executing thread is INSIDE right now — the
        #: Top SQL sampler (obs/profiler.py) reads it from another
        #: thread to attribute a sampled instant. note_phase charges
        #: walls at their END, which a sampler cannot use; this marker
        #: is set where a phase-named span opens (plan/compile/
        #: final-merge) and, for the fleet phases that keep note_phase,
        #: via FLIGHT.set_live_phase.
        self.live_phase = "execute"
        #: closed spans as (path, start offset from the root span's
        #: start, seconds), in closing order: the thread's trip owns
        #: the list and ``begin`` hands it over, so it holds what closed
        #: before the flight began (parse) and keeps growing after the
        #: flight is in the ring (observe, session, wire/write, the root)
        self.spans: List[tuple] = []
        #: the root span's seconds (0.0 until it has closed)
        self.served_s = 0.0
        #: [name, overlap seconds] of the background ticks that ran
        #: between the root span's start and this flight's finish
        self.background: List[list] = []

    def phase_row(self, name: str) -> list:
        row = self.phases.get(name)
        if row is None:
            row = self.phases[name] = [0.0, 0, 0]
        return row

    def timeline(self) -> List[tuple]:
        """(phase, seconds, bytes, retries) in declared order — the
        slow-query log's `# Phases` line and the /links-free half of
        the bench --flight-out snapshot."""
        return [
            (p, self.phases[p][0], self.phases[p][1], self.phases[p][2])
            for p in PHASES
            if p in self.phases
        ]


class _Trip:
    """One thread's open span tree: a root span and what nests in it.
    The root opens before its flight begins (the server has read the
    packet, the session parses) and closes after it finished, so the
    trip, not the flight, owns the clock's zero, the ``qid`` the
    annotations carry and the ONE list of closed spans. The flight that
    begins inside takes the qid and a reference to the list, and keeps
    both: nothing is copied at ``begin`` or after ``finish``."""

    __slots__ = (
        "qid", "t0", "stack", "rows", "flight", "tracer", "tracer_depth",
    )

    def __init__(self, qid: int, flight: Optional[QueryFlight]):
        self.qid = qid
        self.t0 = 0.0
        self.stack: List["_Span"] = []
        #: the flight that began inside this trip (or was open when it
        #: started), and the list every closing span is appended to
        self.flight = flight
        self.rows: List[tuple] = flight.spans if flight is not None else []
        self.tracer = None
        self.tracer_depth = 0


class _Span:
    """``with FLIGHT.span(name) as sp``: see the module docstring.
    ``sp.seconds`` holds its wall once it has closed."""

    __slots__ = (
        "name", "path", "seconds", "_fr", "_trip", "_ann", "_t0",
        "_compile0", "_prev",
    )

    def __init__(self, fr: "FlightRecorder", name: str):
        self._fr = fr
        self.name = name
        self.seconds = 0.0

    def __enter__(self) -> "_Span":
        fr, name = self._fr, self.name
        tls = fr._tls
        trip = getattr(tls, "trip", None)
        if trip is None:
            rec = getattr(tls, "rec", None)
            if rec is not None:
                qid = rec.qid
            else:
                qid = next(fr._qid) if name in _ROOTS else 0
            trip = tls.trip = _Trip(qid, rec)
        self._trip = trip
        stack = trip.stack
        self.path = stack[-1].path + "/" + name if stack else name
        self._prev = fr.set_live_phase(name) if name in _PHASE_SET else None
        # execute's wall contains any jit traces watched_jit charges to
        # compile: taken out at close so the two phases stay additive
        self._compile0 = (
            fr.phase_seconds("compile") if name == "execute" else 0.0
        )
        self._ann = TraceAnnotation("tidbtpu/" + self.path, qid=trip.qid)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        if not stack:
            trip.t0 = self._t0
        stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        fr, trip, name = self._fr, self._trip, self.name
        stack = trip.stack
        stack.pop()
        self.seconds = seconds = t1 - self._t0
        trip.rows.append((self.path, self._t0 - trip.t0, seconds))
        if name in _PHASE_SET:
            fr.restore_live_phase(self._prev)
            # with no flight open yet (parse) only the counter moves:
            # ``begin`` charges the flight from the trip's rows
            fr.note_phase(
                name,
                seconds - (fr.phase_seconds("compile") - self._compile0)
                if name == "execute" else seconds,
            )
        tracer = trip.tracer
        if tracer is not None and tracer.enabled:
            tracer.add(
                name, self._t0, seconds, len(stack) + 1 - trip.tracer_depth
            )
        if not stack:
            if trip.flight is not None:
                trip.flight.served_s = seconds
            fr._tls.trip = None


class _Tick:
    """``with FLIGHT.background(name)``: one tick of a background loop,
    annotated like a span and remembered for the flights it overlaps."""

    __slots__ = ("name", "t0", "_fr", "_ann")

    def __init__(self, fr: "FlightRecorder", name: str):
        self._fr = fr
        self.name = name

    def __enter__(self) -> "_Tick":
        self._ann = TraceAnnotation("tidbtpu/background/" + self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        self._fr._ticks_open[threading.get_ident()] = self
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._fr._ticks_open.pop(threading.get_ident(), None)
        self._fr._ticks.append((self.name, self.t0, t1))


class FlightRecorder:
    """Always-on per-statement recorder: thread-local current flight,
    finished flights in a bounded ring (oldest evicted). All note_*
    paths are O(1) and lock-free for the current flight (thread-local);
    only the ring append takes the lock."""

    def __init__(self, capacity: int = 256):
        self._tls = threading.local()
        self._lock = racecheck.make_lock("flight.ring")
        self._recent = collections.deque(maxlen=capacity)
        self._qid = itertools.count(1)
        #: finished background ticks (name, start, end on perf_counter)
        #: in closing order, and the open ones by thread
        self._ticks = collections.deque(maxlen=_TICK_RING)
        self._ticks_open: Dict[int, _Tick] = {}

    def set_ring_capacity(self, capacity: int) -> None:
        """Resize the finished-flight ring (newest kept). Load
        harnesses that analyze whole-run timelines (bench --serve-load
        overlap sweeps) size it to the expected flight count first —
        at the 256 default a 64-session run evicts most of its own
        flights before the analysis runs."""
        with self._lock:
            self._recent = collections.deque(
                self._recent, maxlen=max(int(capacity), 1)
            )

    # -- statement scope ----------------------------------------------
    def begin(self, sql: str, conn_id: int = 0) -> QueryFlight:
        trip = getattr(self._tls, "trip", None)
        if trip is not None and trip.flight is None and trip.qid:
            # the statement's own tree: its qid, and its rows so far
            rec = QueryFlight(trip.qid, int(conn_id), str(sql)[:2048])
            rec.spans = trip.rows
        else:
            rec = QueryFlight(next(self._qid), int(conn_id), str(sql)[:2048])
            if trip is not None:
                # a batch's next statement: later spans are its own
                trip.qid, trip.rows = rec.qid, rec.spans
        if trip is not None:
            trip.flight = rec
        self._tls.rec = rec
        for path, _at, seconds in rec.spans:
            # what closed before the flight existed (parse) is its own;
            # the phase counter moved when the span closed
            name = path.rpartition("/")[2]
            if name in _PHASE_SET:
                self._charge(rec, name, seconds)
        # Top SQL attribution (obs/profiler.py): register this thread
        # as a statement context — two dict writes; the digest is
        # computed lazily by the SAMPLER thread, never here, so the
        # always-on path stays O(1). The FULL sql is passed (not the
        # rec's 2048-char display truncation): the digest must match
        # the one statements_summary/note_statement_text compute from
        # the untruncated statement, or long statements fork.
        profiler.begin_task("statement", rec=rec, sql=str(sql))
        return rec

    def current(self) -> Optional[QueryFlight]:
        return getattr(self._tls, "rec", None)

    def finish(self, duration_s: float) -> Optional[QueryFlight]:
        """Close the current flight into the ring and return it (the
        session feeds it to the statement summary / slow log). Returns
        None when no flight is open (nested statement, engine-internal
        session)."""
        rec = self.current()
        self._tls.rec = None
        profiler.end_task()
        if rec is None:
            return None
        rec.duration_s = float(duration_s)
        t1 = time.perf_counter()
        trip = getattr(self._tls, "trip", None)
        rec.background = self._ticks_beside(
            trip.t0 if trip is not None else t1 - rec.duration_s, t1
        )
        _c_queries().inc()
        _h_query_seconds().observe(rec.duration_s)
        with self._lock:
            self._recent.append(rec)
        if TIMELINE.active():
            # one statement span per session thread track, plus a
            # counter-track sample — the timeline moves at statement
            # cadence even when nothing else emits
            TIMELINE.emit_event(
                "statement", rec.sql[:96], rec.start_ts,
                rec.duration_s, track=f"conn-{rec.conn_id}",
                args={
                    "qid": rec.qid, "plan_digest": rec.plan_digest,
                    "plan_cache": rec.plan_cache,
                    "rows_sent": rec.rows_sent,
                },
            )
            TIMELINE.sample_gauges()
        return rec

    def discard(self) -> None:
        """Drop an open flight without recording (statement raised
        before observation; a half-charged timeline would pollute the
        per-digest means)."""
        self._tls.rec = None
        profiler.end_task()

    # -- spans ---------------------------------------------------------
    def span(self, name: str) -> _Span:
        """One boundary, one call: a context manager that times the
        block as the DECLARED span ``name`` nested in the span open on
        this thread (module docstring). Undeclared names raise, as
        ``note_phase`` does."""
        if name not in _SPAN_SET:
            raise ValueError(
                f"undeclared flight span {name!r} (declare it in "
                "tidb_tpu/obs/flight.py SPANS)"
            )
        return _Span(self, name)

    def background(self, name: str) -> _Tick:
        """A context manager around one tick of the background loop
        ``name`` (its thread's name)."""
        return _Tick(self, name)

    def trace_into(self, tracer) -> None:
        """``TRACE``: the spans this thread closes from here on also
        land in ``tracer`` (None detaches), depths counted from the
        span open now."""
        trip = getattr(self._tls, "trip", None)
        if trip is not None:
            trip.tracer = tracer
            trip.tracer_depth = len(trip.stack)

    def _ticks_beside(self, t0: float, t1: float) -> List[list]:
        """[name, seconds] of the background ticks inside (t0, t1)."""
        if not self._ticks and not self._ticks_open:
            return []
        beside: Dict[str, float] = {}
        for tick in list(self._ticks_open.values()):
            if tick.t0 < t1:
                beside[tick.name] = (
                    beside.get(tick.name, 0.0) + t1 - max(tick.t0, t0)
                )
        for name, start, end in reversed(list(self._ticks)):
            if end <= t0:
                break  # closing order: every earlier tick ended before
            if start < t1:
                beside[name] = (
                    beside.get(name, 0.0) + min(end, t1) - max(start, t0)
                )
        return [[name, seconds] for name, seconds in beside.items()]

    def set_live_phase(self, name: str) -> Optional[str]:
        """Mark the phase the current flight's thread is ENTERING
        (the Top SQL sampler's attribution signal); returns the
        previous marker so a bracketing caller can restore it. A
        declared-phase check keeps the marker vocabulary identical to
        the charged one."""
        if name not in _PHASE_SET:
            raise ValueError(
                f"undeclared flight phase {name!r} (declare it in "
                "tidb_tpu/obs/flight.py PHASES)"
            )
        rec = self.current()
        if rec is None:
            return None
        prev = rec.live_phase
        rec.live_phase = name
        return prev

    def restore_live_phase(self, prev: Optional[str]) -> None:
        rec = self.current()
        if rec is not None and prev is not None:
            rec.live_phase = prev

    # -- notes ---------------------------------------------------------
    def note_phase(
        self, name: str, seconds: float, nbytes: int = 0, retries: int = 0
    ) -> None:
        """Charge seconds (and optional bytes/retries) to a DECLARED
        phase of the current flight. Undeclared names raise — the
        failpoint-SITES contract: the registry, not the call site,
        defines the phase vocabulary."""
        if name not in _PHASE_SET:
            raise ValueError(
                f"undeclared flight phase {name!r} (declare it in "
                "tidb_tpu/obs/flight.py PHASES)"
            )
        _c_phase_seconds().labels(phase=name).inc(max(float(seconds), 0.0))
        rec = self.current()
        if rec is not None:
            self._charge(rec, name, seconds, nbytes, retries)

    @staticmethod
    def _charge(
        rec: QueryFlight, name: str, seconds: float, nbytes: int = 0,
        retries: int = 0,
    ) -> None:
        """The flight's half of ``note_phase``."""
        row = rec.phase_row(name)
        row[0] += max(float(seconds), 0.0)
        row[1] += int(nbytes)
        row[2] += int(retries)
        if TIMELINE.active() and seconds > 0:
            # phase charges are noted at the END of the measured wall,
            # so the event window extends backwards by the charge
            TIMELINE.emit_event(
                "phase", name, time.time() - float(seconds),
                float(seconds), track=f"conn-{rec.conn_id}",
                args={"qid": rec.qid},
            )

    def phase_seconds(self, name: str) -> float:
        """Seconds charged so far to ``name`` on the CURRENT flight
        (0.0 when none is open). Lets a caller that brackets a wall
        containing nested charges subtract them — e.g. the session's
        execute window subtracts the compile seconds watched_jit
        charged inside it, so execute and compile stay additive."""
        rec = self.current()
        if rec is None:
            return 0.0
        row = rec.phases.get(name)
        return row[0] if row else 0.0

    def note_plan_cache(self, hit: bool, key=None) -> None:
        """Compiled-plan-cache outcome from the executor; ``key`` (the
        cache key) stamps a short plan digest onto the flight."""
        rec = self.current()
        if rec is None:
            return
        rec.plan_cache = "hit" if hit else "miss"
        if key is not None:
            try:
                rec.plan_digest = "%016x" % (hash(key) & (2 ** 64 - 1))
            except TypeError:
                pass  # unhashable key: keep the outcome, skip the digest

    def note_rows_sent(self, n: int) -> None:
        rec = self.current()
        if rec is not None:
            rec.rows_sent = int(n)

    def note_exchanges(self, count: int, rows: int, nbytes: int) -> None:
        """One executed mesh program's exchanges (planner/physical.py
        reads them beside the program's cardinality scalars)."""
        rec = self.current()
        if rec is not None:
            rec.exchanges += int(count)
            rec.exchange_rows += int(rows)
            rec.exchange_bytes += int(nbytes)

    def note_expansions(self, count: int, rows: int, slots: int) -> None:
        """One executed program's expanding joins (planner/physical.py
        reads them beside the program's cardinality scalars)."""
        rec = self.current()
        if rec is not None:
            rec.join_expansions += int(count)
            rec.join_expand_rows += int(rows)
            rec.join_expand_slots += int(slots)

    def note_groupings(self, count: int, rows: int, groups: int, slots: int) -> None:
        """One executed program's sorted group-bys (planner/physical.py
        reads them beside the program's cardinality scalars)."""
        rec = self.current()
        if rec is not None:
            rec.sorted_groupings += int(count)
            rec.sorted_group_rows += int(rows)
            rec.sorted_groups += int(groups)
            rec.sorted_group_slots += int(slots)

    def note_cardinality(self, est: float, act: float) -> None:
        """Planner-estimated vs observed output rows of a routed
        statement (AQE): feeds the statements_summary est/act
        divergence columns and the tidbtpu_aqe_misestimates_total
        signal behind the cardinality-drift inspection rule."""
        rec = self.current()
        if rec is None:
            return
        rec.est_rows = float(est)
        rec.act_rows = float(act)

    def note_plan_text(self, text: str) -> None:
        rec = self.current()
        if rec is not None and text:
            rec.plan_text = str(text)[:16384]

    def note_engine(self, engine_rec) -> None:
        """Join the engine-watch record (obs/engine_watch.py) into the
        current flight — the statements_summary engine columns."""
        rec = self.current()
        if rec is None or engine_rec is None:
            return
        rec.jit_compilations = int(engine_rec.jit_compilations)
        rec.retraces = int(engine_rec.retraces)
        rec.h2d_bytes = int(engine_rec.h2d_bytes)
        rec.d2h_bytes = int(engine_rec.d2h_bytes)
        rec.device_mem_peak_bytes = int(engine_rec.device_mem_peak_bytes)
        rec.compile_flops = float(
            getattr(engine_rec, "compile_flops", 0.0)
        )
        rec.compile_bytes_accessed = float(
            getattr(engine_rec, "compile_bytes_accessed", 0.0)
        )
        rec.compile_output_bytes = float(
            getattr(engine_rec, "compile_output_bytes", 0.0)
        )

    def note_shuffle_stage(self, stage: dict) -> None:
        """Attribute one DCN shuffle stage's worker-reported stats
        (parallel/dcn.py ``stage`` summary) onto the current flight's
        shuffle phases. Stage retries charge to fragment-dispatch."""
        if not stage:
            return
        self.note_phase(
            "shuffle-produce", stage.get("produce_s", 0.0),
        )
        self.note_phase(
            "shuffle-push", stage.get("encode_s", 0.0),
            nbytes=int(stage.get("bytes_tunneled", 0)),
            retries=int(stage.get("retransmits", 0)),
        )
        self.note_phase("shuffle-wait", stage.get("wait_s", 0.0))
        self.note_phase("shuffle-stage", stage.get("stage_s", 0.0))

    # -- surfaces ------------------------------------------------------
    def rows(self) -> List[dict]:
        """Finished flights, oldest first, as plain dicts (the bench
        --flight-out snapshot; tests)."""
        with self._lock:
            recs = list(self._recent)
        return [
            {
                "qid": r.qid,
                "conn_id": r.conn_id,
                "sql": r.sql,
                "start_ts": r.start_ts,
                "duration_s": r.duration_s,
                "phases": {
                    p: {"seconds": s, "bytes": b, "retries": n}
                    for p, s, b, n in r.timeline()
                },
                "plan_cache": r.plan_cache,
                "rows_sent": r.rows_sent,
                "jit_compilations": r.jit_compilations,
                "retraces": r.retraces,
                "h2d_bytes": r.h2d_bytes,
                "d2h_bytes": r.d2h_bytes,
                "exchanges": r.exchanges,
                "exchange_rows": r.exchange_rows,
                "exchange_bytes": r.exchange_bytes,
                "join_expansions": r.join_expansions,
                "join_expand_rows": r.join_expand_rows,
                "join_expand_slots": r.join_expand_slots,
                "sorted_groupings": r.sorted_groupings,
                "sorted_group_rows": r.sorted_group_rows,
                "sorted_groups": r.sorted_groups,
                "sorted_group_slots": r.sorted_group_slots,
                "device_mem_peak_bytes": r.device_mem_peak_bytes,
                "compile_flops": r.compile_flops,
                "compile_bytes_accessed": r.compile_bytes_accessed,
                "compile_output_bytes": r.compile_output_bytes,
                "plan_captured": bool(r.plan_text),
                "spans": [
                    {"name": p, "start_s": at, "seconds": sec}
                    for p, at, sec in list(r.spans)
                ],
                "served_s": r.served_s,
                "background": [list(b) for b in r.background],
            }
            for r in recs
        ]


FLIGHT = FlightRecorder()


# -- per-peer DCN link health ------------------------------------------------


def _c_link_bytes():
    return REGISTRY.counter(
        "tidbtpu_link_bytes_total",
        "bytes pushed per worker-to-worker tunnel link",
        labels=("src", "dst"),
    )


def _c_link_frames():
    return REGISTRY.counter(
        "tidbtpu_link_frames_total",
        "frames/packets pushed per tunnel link",
        labels=("src", "dst"),
    )


def _c_link_stall_seconds():
    return REGISTRY.counter(
        "tidbtpu_link_stall_seconds",
        "seconds producers spent blocked on tunnel backpressure, per link",
        labels=("src", "dst"),
    )


def _c_link_retransmits():
    return REGISTRY.counter(
        "tidbtpu_link_retransmits_total",
        "packets retransmitted per tunnel link",
        labels=("src", "dst"),
    )


def _g_link_rtt():
    return REGISTRY.gauge(
        "tidbtpu_link_rtt_seconds",
        "handshake-sampled round-trip time per control link",
        labels=("host",),
    )


def _g_link_heartbeat_age():
    return REGISTRY.gauge(
        "tidbtpu_link_heartbeat_age_seconds",
        "seconds since the last successful heartbeat/handshake per host",
        labels=("host",),
    )


def _g_link_clock_offset():
    return REGISTRY.gauge(
        "tidbtpu_link_clock_offset_seconds",
        "handshake-sampled host clock minus coordinator clock (RTT/2 "
        "anchor) per control link — the inspection engine's clock-skew "
        "signal",
        labels=("host",),
    )


class LinkRegistry:
    """Coordinator-side aggregation of per-peer link health.

    Two link kinds:

    - ``control``: coordinator -> worker engine-RPC links. RTT and the
      clock offset come from the connect-time handshake (the PR 5
      clock sampler); heartbeat age tracks the last successful ping
      (HostHeartbeat.beat_once) or handshake.
    - ``tunnel``: worker -> worker shuffle tunnels. Bytes/frames/rows
      pushed, backpressure stall seconds, retransmits and the
      negotiated codec are reported by the owning worker in each
      shuffle reply's ``per_peer`` stats and merged here behind the
      coordinator's exactly-once ledger fence (a retried stage's
      tunnels count once).
    """

    def __init__(self):
        self._lock = racecheck.make_lock("flight.links")
        self._control: Dict[str, dict] = {}
        self._tunnels: Dict[tuple, dict] = {}

    def note_handshake(
        self, host: str, rtt_s: Optional[float], offset_s: Optional[float]
    ) -> None:
        now = time.time()
        with self._lock:
            ent = self._control.setdefault(
                host, {"rtt_s": 0.0, "offset_s": 0.0, "last_seen": now,
                       "alive": True},
            )
            if rtt_s is not None:
                ent["rtt_s"] = float(rtt_s)
                _g_link_rtt().labels(host=host).set(float(rtt_s))
            if offset_s is not None:
                ent["offset_s"] = float(offset_s)
                _g_link_clock_offset().labels(host=host).set(
                    float(offset_s)
                )
            ent["last_seen"] = now
            ent["alive"] = True
        # a fresh handshake IS a successful liveness observation
        _g_link_heartbeat_age().labels(host=host).set(0.0)

    def note_heartbeat(self, host: str, ok: bool) -> None:
        """One liveness observation. The age gauge updates HERE (not
        only in the cluster_links read path) so a /metrics-only
        deployment running the heartbeat loop sees a dead link's age
        grow: a failed beat stamps the time since the last success."""
        now = time.time()
        with self._lock:
            ent = self._control.setdefault(
                host, {"rtt_s": 0.0, "offset_s": 0.0, "last_seen": now,
                       "alive": bool(ok)},
            )
            age = 0.0 if ok else max(now - ent["last_seen"], 0.0)
            if ok:
                ent["last_seen"] = now
            ent["alive"] = bool(ok)
        _g_link_heartbeat_age().labels(host=host).set(age)

    def note_tunnel(self, src: str, dst: str, per_peer: dict) -> None:
        """Fold one worker-reported tunnel sample (a ``per_peer`` row
        from a FENCED shuffle reply) into the (src, dst) link."""
        with self._lock:
            ent = self._tunnels.setdefault(
                (src, dst),
                {"bytes": 0, "frames": 0, "rows": 0, "stalls": 0,
                 "stall_s": 0.0, "retransmits": 0, "codec": "",
                 "last_seen": 0.0},
            )
            ent["bytes"] += int(per_peer.get("bytes", 0))
            ent["frames"] += int(per_peer.get("frames", 0))
            ent["rows"] += int(per_peer.get("rows", 0))
            ent["stalls"] += int(per_peer.get("stalls", 0))
            ent["stall_s"] += float(per_peer.get("stall_s", 0.0))
            ent["retransmits"] += int(per_peer.get("retransmits", 0))
            ent["codec"] = str(per_peer.get("codec") or ent["codec"])
            ent["last_seen"] = time.time()

    def rows(self) -> List[tuple]:
        """information_schema.cluster_links rows: (src, dst, kind,
        alive, rtt_ms, clock_offset_ms, heartbeat_age_s, bytes, frames,
        rows, stall_seconds, retransmits, codec)."""
        now = time.time()
        out: List[tuple] = []
        with self._lock:
            for host in sorted(self._control):
                ent = self._control[host]
                age = max(now - ent["last_seen"], 0.0)
                _g_link_heartbeat_age().labels(host=host).set(age)
                out.append(
                    ("coordinator", host, "control",
                     int(bool(ent["alive"])), ent["rtt_s"] * 1e3,
                     ent["offset_s"] * 1e3, age, 0, 0, 0, 0.0, 0, "")
                )
            for (src, dst) in sorted(self._tunnels):
                ent = self._tunnels[(src, dst)]
                out.append(
                    (src, dst, "tunnel", 1, 0.0, 0.0,
                     max(now - ent["last_seen"], 0.0), ent["bytes"],
                     ent["frames"], ent["rows"], ent["stall_s"],
                     ent["retransmits"], ent["codec"])
                )
        return out

    def snapshot(self) -> List[dict]:
        """The /links endpoint payload (same data as rows(), keyed)."""
        cols = (
            "src", "dst", "kind", "alive", "rtt_ms", "clock_offset_ms",
            "heartbeat_age_s", "bytes", "frames", "rows",
            "stall_seconds", "retransmits", "codec",
        )
        return [dict(zip(cols, r)) for r in self.rows()]

    def reset(self) -> None:
        with self._lock:
            self._control.clear()
            self._tunnels.clear()


LINKS = LinkRegistry()
