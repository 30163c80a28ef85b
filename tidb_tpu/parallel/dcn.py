"""Cross-host DCN fragment scheduler with failure recovery.

Reference: the MPP dispatch triplet — `DispatchMPPTask` fanning
fragments across stores (pkg/store/copr/mpp.go:93), the failed-store
prober quarantining and re-admitting stores (mpp_probe.go:33), and
`ExecutorWithRetry`/`RecoveryHandler` re-running an MPP query on the
survivors (pkg/executor/internal/mpp/recovery_handler.go:26).

TPU-native shape (hierarchical comms):

    coordinator ──plan IR──▶ worker host 0: engine over a local device
        │                        mesh (ICI all_to_all exchanges)
        ├───────plan IR──────▶ worker host 1: same, rows frag-sliced
        ◀──partial agg rows──┘
    final merge + ORDER BY/LIMIT on the coordinator's local engine

planner/fragmenter.py cuts the plan at the topmost Aggregate and slices
one scan per host; each worker reduces its slice to PARTIAL aggregate
rows before anything crosses the inter-host link (partial-agg-before-
DCN), then the coordinator merges partials through the engine's own
final-aggregate path over a Staged batch. Intra-host parallelism stays
on the worker's ICI mesh; the coordinator RPC seam is the host-staged
DCN exchange.

Robustness is part of the subsystem:
- heartbeat liveness per worker host (HostHeartbeat) feeding the same
  FailedEngineProber quarantine/backoff machinery the engine pool uses;
- transport loss during dispatch quarantines the host and re-dispatches
  the fragment onto a survivor (the slice is data-defined, so any host
  can compute any fragment);
- a FragmentLedger built on the DXF subtask-ledger fence
  (dxf/framework.fence_accepts) incorporates each fragment's rows
  exactly once — a late or duplicate delivery after re-dispatch is
  dropped, the work-done-reply-lost ambiguity resolved coordinator-side.

Serving-tier reentrancy (PR 8): the scheduler admits MANY sessions'
queries concurrently. Each worker host gets a small POOL of control
connections (the strict request/response stream invariant holds per
CONNECTION, so k pooled connections serve k concurrent fragments to
one host instead of serializing them onto one socket), qids/staged
nonces come from a locked strictly-unique allocator
(parallel/serving.QidAllocator — qid uniqueness is what fences one
query's shuffle stages and ledger tokens from another's), and an
optional AdmissionController gates query start against the fleet
device-memory budget (session.py consults ``scheduler.admission``
before dispatch).

Failpoint sites: dcn/dispatch, dcn/dispatch-lost, dcn/redispatch,
dcn/heartbeat-timeout, dcn/duplicate-redelivery, dcn/final-stage
(coordinator) and dcn/fragment-execute, dcn/result-send (worker,
server/engine_rpc.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple

from tidb_tpu.dxf.framework import fence_accepts
from tidb_tpu.obs.flight import FLIGHT, LINKS
from tidb_tpu.obs.timeline import TIMELINE
from tidb_tpu.parallel.serving import QidAllocator
from tidb_tpu.planner import logical as L
from tidb_tpu.planner.fragmenter import (
    FragmentPlan,
    ShuffleDAG,
    ShufflePlan,
    choose_edge_modes,
    split_plan,
    split_plan_dag,
    split_plan_shuffle,
)
from tidb_tpu.planner.ir import IR_VERSION, plan_to_ir
from tidb_tpu.server.engine_pool import (
    EngineEndpoint,
    FailedEngineProber,
    ping_endpoint,
)
from tidb_tpu.server.engine_rpc import (
    EngineClient,
    QueryCancelled,
    SchemaOutOfDateError,
)
from tidb_tpu.utils import racecheck
from tidb_tpu.utils.failpoint import inject
from tidb_tpu.utils.metrics import REGISTRY, merge_counter_delta
from tidb_tpu.utils.tracing import Tracer

# strictly-unique under concurrent sessions (see serving.QidAllocator);
# staged nonces start disjoint from streamed.py's and shuffle.py's
_STAGED_NONCE = QidAllocator(start=1 << 20)
_QUERY_ID = QidAllocator(start=1)


# -- telemetry (tidbtpu_dcn_*: exported at /metrics, summarized at /dcn) ----


def _c_dispatches():
    return REGISTRY.counter(
        "tidbtpu_dcn_dispatches", "fragment dispatches", labels=("host",)
    )


def _g_pool_leased_peak():
    return REGISTRY.gauge(
        "tidbtpu_dcn_pool_leased_peak",
        "high-water of concurrently leased control connections per "
        "worker host (>= 2: two queries' fragments genuinely "
        "overlapped on that host)",
        labels=("host",),
    )


def _c_retries():
    return REGISTRY.counter(
        "tidbtpu_dcn_retries", "fragment re-dispatches after a loss"
    )


def _c_quarantines():
    return REGISTRY.counter(
        "tidbtpu_dcn_quarantines", "hosts quarantined", labels=("host",)
    )


def _c_duplicates():
    return REGISTRY.counter(
        "tidbtpu_dcn_duplicates_dropped",
        "late/duplicate fragment deliveries fenced by the ledger",
    )


def _c_bytes_staged():
    return REGISTRY.counter(
        "tidbtpu_dcn_bytes_staged",
        "fragment result bytes staged through the coordinator",
    )


def _c_heartbeat_misses():
    return REGISTRY.counter(
        "tidbtpu_dcn_heartbeat_misses", "missed heartbeats", labels=("host",)
    )


def _h_fragment_seconds():
    return REGISTRY.histogram(
        "tidbtpu_dcn_fragment_seconds", "per-fragment worker execution time"
    )


def _c_shuffle_stages():
    return REGISTRY.counter(
        "tidbtpu_shuffle_stages", "worker-to-worker shuffle stages run"
    )


def _c_shuffle_stage_retries():
    return REGISTRY.counter(
        "tidbtpu_shuffle_stage_retries",
        "shuffle stages re-run on a survivor set after a peer death",
    )


def _c_cancels():
    return REGISTRY.counter(
        "tidbtpu_dcn_cancels_total",
        "fleet-wide cancel_query broadcasts (KILL QUERY / "
        "max_execution_time / propagated statement deadline)",
    )


def _c_retry_backoff():
    return REGISTRY.counter(
        "tidbtpu_dcn_retry_backoff_seconds",
        "jittered exponential backoff slept between stage/fragment "
        "retry rounds (desynchronizes re-dispatch storms)",
    )


def _c_stage_exchanges():
    return REGISTRY.counter(
        "tidbtpu_shuffle_stage_exchanges_total",
        "shuffle DAG stage exchanges run, by kind (the per-edge "
        "cost-model outcome: hash, range, or broadcast)",
        labels=("exchange",),
    )


def _c_stage_sample_seconds():
    return REGISTRY.counter(
        "tidbtpu_shuffle_stage_sample_seconds",
        "coordinator wall spent in range-exchange boundary sampling "
        "rounds (produce-and-cache + merged quantile cut)",
    )


def _c_stage_chained():
    return REGISTRY.counter(
        "tidbtpu_shuffle_stage_chained_total",
        "multi-stage shuffle DAGs executed (stage N's held output fed "
        "stage N+1 without re-scanning base tables)",
    )


def _c_shuffle_result_bytes():
    return REGISTRY.counter(
        "tidbtpu_shuffle_result_bytes",
        "per-partition consumer result bytes returned to the "
        "coordinator (NOT shuffle data — that moves worker-to-worker "
        "and counts under tidbtpu_shuffle_bytes_total)",
    )


def _h_partition_rows():
    return REGISTRY.histogram(
        "tidbtpu_shuffle_partition_rows",
        "rows each shuffle partition's consumer RECEIVED (per "
        "partition per stage) — _sum/_count give the mean partition "
        "load; the max/mean skew ratio renders on the EXPLAIN "
        "ANALYZE DCNShuffle row as skew=",
    )


def _h_filter_selectivity():
    return REGISTRY.histogram(
        "tidbtpu_shuffle_filter_selectivity",
        "observed runtime-filter pass rate per stage (kept/tested "
        "probe-side rows) — low values mean the filter carried its "
        "weight; ~1.0 stages are candidates for the auto cost gate "
        "to stand down (renders as rf= sel_obs on EXPLAIN ANALYZE)",
    )


def _update_host_gauges(endpoints) -> None:
    alive = sum(1 for ep in endpoints if ep.alive)
    REGISTRY.gauge(
        "tidbtpu_dcn_hosts_alive", "worker hosts in rotation"
    ).set(alive)
    REGISTRY.gauge(
        "tidbtpu_dcn_hosts_quarantined", "worker hosts quarantined"
    ).set(len(endpoints) - alive)


class HostHeartbeat:
    """Per-host liveness: ping every alive endpoint on a cadence;
    `miss_threshold` consecutive misses quarantine the host into the
    prober (which owns recovery with exponential backoff). Detection
    and recovery are deliberately split across the two components the
    way the reference splits detect (dispatch/probe failures) from
    recover (mpp_probe.go's prober goroutine)."""

    def __init__(
        self,
        endpoints: List[EngineEndpoint],
        prober: FailedEngineProber,
        interval_s: float = 0.0,
        timeout_s: float = 2.0,
        miss_threshold: int = 2,
    ):
        self.endpoints = endpoints
        self.prober = prober
        self.timeout_s = timeout_s
        self.miss_threshold = miss_threshold
        self._misses: Dict[EngineEndpoint, int] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._interval_s = float(interval_s)
        # serializes retune() against itself (concurrent sysvar SETs
        # from many sessions must not leave two beat threads running)
        self._retune_lock = racecheck.make_lock("dcn.heartbeat")
        if interval_s > 0:
            self._thread = threading.Thread(
                target=self._loop, args=(interval_s, self._stop),
                daemon=True, name="dcn-heartbeat",
            )
            self._thread.start()

    def beat_once(self) -> List[EngineEndpoint]:
        """Ping every alive host; returns hosts quarantined this beat."""
        lost = []
        for ep in list(self.endpoints):
            if not ep.alive:
                continue
            ok = not inject("dcn/heartbeat-timeout") and ping_endpoint(
                ep, timeout_s=self.timeout_s
            )
            # per-link heartbeat age (information_schema.cluster_links)
            LINKS.note_heartbeat(ep.address, ok)
            if ok:
                self._misses[ep] = 0
                continue
            _c_heartbeat_misses().labels(host=ep.address).inc()
            self._misses[ep] = self._misses.get(ep, 0) + 1
            if self._misses[ep] >= self.miss_threshold:
                if self.prober.detect(ep):
                    _c_quarantines().labels(host=ep.address).inc()
                lost.append(ep)
        _update_host_gauges(self.endpoints)
        return lost

    def _loop(self, interval_s: float, stop: threading.Event) -> None:
        # the thread loops on ITS OWN stop event (captured at start),
        # not self._stop: retune() replaces self._stop for the next
        # thread, and an outgoing thread whose join timed out (wedged
        # hosts make one beat exceed it) must still see the event that
        # was set FOR IT — re-reading the attribute would leave it
        # beating forever on a never-set replacement
        while not stop.wait(interval_s):
            try:
                self.beat_once()
            except Exception:
                pass

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def retune(
        self,
        interval_s: Optional[float] = None,
        miss_threshold: Optional[int] = None,
    ) -> None:
        """Live re-tune (the tidb_tpu_heartbeat_* sysvar SET hook): a
        changed miss threshold applies to the next beat; a CHANGED
        interval restarts the beat thread on the new cadence (0 stops
        it — manual beat_once only; an unchanged interval is a no-op,
        not a restart). Serialized: two sessions SETting concurrently
        must not each replace self._stop and leave an orphan thread
        beating on a never-set event."""
        if miss_threshold is not None:
            self.miss_threshold = int(miss_threshold)
        if interval_s is None:
            return
        interval_s = float(interval_s)
        with self._retune_lock:
            if interval_s == self._interval_s:
                return
            self._interval_s = interval_s
            # lock-blocking-ok: stop() joins the outgoing beat thread
            # under the retune lock ON PURPOSE — the join is what
            # guarantees at most one beat thread ever runs, and the
            # lock is leaf-level (beat_once takes no locks of ours)
            self.stop()
            self._stop = threading.Event()
            if interval_s > 0:
                self._thread = threading.Thread(
                    target=self._loop, args=(interval_s, self._stop),
                    daemon=True, name="dcn-heartbeat",
                )
                self._thread.start()


class _EndpointPool:
    """Small pool of control connections to ONE worker host.

    EngineClient's socket protocol is a strict request/response stream,
    so a connection serves one in-flight RPC at a time — but that
    invariant is per CONNECTION, not per host. PR 1-7 kept a single
    connection per host behind a lock, which serialized concurrent
    queries' fragments onto one socket; the serving tier pools up to
    ``size`` connections per endpoint so k sessions' fragments genuinely
    overlap on one worker (the worker side always threaded per
    connection — socketserver.ThreadingTCPServer). Checkout order:
    idle connection, else dial a new one (below the cap), else wait on
    the condition for a checkin. Dead connections (poisoned streams,
    transport loss) are dropped at checkin and their slot freed.
    """

    def __init__(self, ep: EngineEndpoint, timeout_s: float,
                 size: int = 4, on_connect=None):
        self.ep = ep
        self.timeout_s = timeout_s
        self.size = max(int(size), 1)
        self._on_connect = on_connect
        self._cv = racecheck.make_condition("dcn.pool")
        self._idle: List[EngineClient] = []
        self._total = 0

    def _dial(self) -> EngineClient:
        """Connect + handshake OUTSIDE the condition (a slow worker
        must not block other checkouts); the slot was reserved under
        the cv, so release it on failure."""
        try:
            c = EngineClient(
                self.ep.host, self.ep.port, secret=self.ep.secret,
                timeout_s=self.timeout_s,
            )
        except Exception:
            with self._cv:
                self._total -= 1
                self._cv.notify_all()
            raise
        if self._on_connect is not None:
            try:
                self._on_connect(self.ep, c)
            except Exception:
                pass  # telemetry must never fail a checkout
        return c

    def _note_leased(self) -> None:
        """Caller holds the cv. High-water of concurrently leased
        connections to this host — >= 2 is the direct proof that two
        queries' fragments genuinely overlapped on one worker (the
        serve-load acceptance signal; whole-statement flight windows
        overlap even when dispatches serialize)."""
        _g_pool_leased_peak().labels(host=self.ep.address).set_max(
            self._total - len(self._idle)
        )

    def checkout(self) -> EngineClient:
        with self._cv:
            while True:
                while self._idle:
                    c = self._idle.pop()
                    if not c._dead:
                        self._note_leased()
                        return c
                    self._total -= 1
                if self._total < self.size:
                    self._total += 1
                    self._note_leased()
                    break  # reserved a slot: dial outside the cv
                self._cv.wait(0.25)
        return self._dial()

    def checkin(self, conn: EngineClient) -> None:
        with self._cv:
            if conn._dead:
                self._total -= 1
            else:
                self._idle.append(conn)
            self._cv.notify_all()

    def leased(self) -> int:
        """Connections currently checked out — must drain back to 0
        after every query, aborted ones included (the chaos harness's
        leak invariant)."""
        with self._cv:
            return self._total - len(self._idle)

    @contextlib.contextmanager
    def lease(self):
        conn = self.checkout()
        try:
            yield conn
        finally:
            self.checkin(conn)

    def close_idle(self) -> None:
        """Drop every idle connection (quarantine/shutdown). In-flight
        leases keep their connection; a dead worker poisons them on the
        next round trip and checkin frees the slot."""
        with self._cv:
            idle, self._idle = self._idle, []
            self._total -= len(idle)
            self._cv.notify_all()
        for c in idle:
            try:
                c.close()
            except Exception:
                pass


class FragmentLedger:
    """Exactly-once fragment accounting for one query — the DXF
    subtask-ledger pattern (dxf/tasks.py staged-file fences,
    framework.fence_accepts) applied to in-flight MPP fragments. A
    fragment's rows land iff the delivery carries the token of the
    CURRENT attempt while the fragment is still inflight; anything else
    (a zombie host's late reply after re-dispatch, a duplicate
    redelivery) is counted and dropped."""

    def __init__(self, n_fragments: int):
        self._lock = racecheck.make_lock("dcn.ledger")
        self._recs = {
            fid: {"state": "pending", "owner": None, "attempts": 0,
                  "rows": None}
            for fid in range(n_fragments)
        }
        self.duplicates_dropped = 0

    def claim(self, fid: int, host: str) -> str:
        with self._lock:
            rec = self._recs[fid]
            if rec["state"] != "pending":
                raise RuntimeError(f"fragment {fid} is {rec['state']}")
            rec["attempts"] += 1
            rec["state"] = "inflight"
            rec["owner"] = f"{host}#{rec['attempts']}"
            return rec["owner"]

    def release(self, fid: int, token: str) -> None:
        """Transport failure: the attempt is dead, the fragment goes
        back to pending (only the token holder may release)."""
        with self._lock:
            rec = self._recs[fid]
            if rec["state"] == "inflight" and rec["owner"] == token:
                rec["state"] = "pending"
                rec["owner"] = None

    def complete(self, fid: int, token: str, rows: List[tuple]) -> bool:
        with self._lock:
            rec = self._recs[fid]
            if not fence_accepts(rec["owner"], rec["state"], token, "inflight"):
                self.duplicates_dropped += 1
                _c_duplicates().inc()
                return False
            rec["state"] = "done"
            rec["rows"] = rows
        if inject("dcn/duplicate-redelivery"):
            # exercise the fence in vivo: redeliver the same result; the
            # second landing must be dropped
            assert self.complete(fid, token, rows) is False
        return True

    def pending(self) -> List[int]:
        with self._lock:
            return [
                fid for fid, r in self._recs.items()
                if r["state"] == "pending"
            ]

    def attempts(self, fid: int) -> int:
        with self._lock:
            return self._recs[fid]["attempts"]

    def total_retries(self) -> int:
        """Attempts beyond the first, summed over fragments (the
        flight recorder's fragment-dispatch retry count)."""
        with self._lock:
            return sum(
                max(r["attempts"] - 1, 0) for r in self._recs.values()
            )

    def all_done(self) -> bool:
        with self._lock:
            return all(r["state"] == "done" for r in self._recs.values())

    def rows(self) -> List[tuple]:
        """All fragments' rows, fragment order (deterministic)."""
        with self._lock:
            out = []
            for fid in sorted(self._recs):
                out.extend(self._recs[fid]["rows"] or [])
            return out

    def rows_by_fragment(self) -> List[List[tuple]]:
        """Per-fragment row lists, fragment order — the range-exchange
        concat merge needs PARTITION boundaries preserved (partition
        order is the total order; a descending first key concatenates
        them reversed)."""
        with self._lock:
            return [
                list(self._recs[fid]["rows"] or [])
                for fid in sorted(self._recs)
            ]


class DCNFragmentScheduler:
    """Coordinator: split a bound logical plan into per-host fragments,
    dispatch them over the engine-RPC seam, gather partials exactly
    once, and run the final stage on a local engine."""

    def __init__(
        self,
        endpoints: List[Tuple[str, int]],
        secret: Optional[str] = None,
        prober: Optional[FailedEngineProber] = None,
        catalog=None,
        max_attempts: int = 4,
        heartbeat_interval_s: Optional[float] = None,
        heartbeat_miss_threshold: Optional[int] = None,
        dispatch_timeout_s: float = 600.0,
        shuffle_mode: str = "auto",
        shuffle_min_rows: int = 100_000,
        shuffle_dag: str = "auto",
        shuffle_broadcast_rows: int = 0,
        shuffle_sample_k: int = 64,
        shuffle_sample_seed: int = 7,
        shuffle_wait_timeout_s: Optional[float] = None,
        shuffle_packet_rows: Optional[int] = None,
        shuffle_inflight_bytes: Optional[int] = None,
        shuffle_codec: str = "binary",
        shuffle_pipeline: bool = True,
        shuffle_produce_chunks: Optional[int] = None,
        shuffle_skew_ratio: Optional[float] = None,
        shuffle_skew_salt_k: Optional[int] = None,
        aqe_feedback: Optional[bool] = None,
        aqe_replan_ratio: Optional[float] = None,
        runtime_filter: Optional[str] = None,
        rf_bloom_bits: Optional[int] = None,
        rf_inlist_ndv: Optional[int] = None,
        conn_pool_size: int = 4,
        admission=None,
        retry_backoff_s: float = 0.05,
    ):
        if not endpoints:
            raise ValueError("DCN scheduler needs at least one worker host")
        if shuffle_mode not in ("auto", "always", "never"):
            raise ValueError(f"bad shuffle_mode {shuffle_mode!r}")
        if shuffle_dag not in ("auto", "always", "never"):
            raise ValueError(f"bad shuffle_dag {shuffle_dag!r}")
        if shuffle_codec not in ("binary", "json"):
            raise ValueError(f"bad shuffle_codec {shuffle_codec!r}")
        if shuffle_dag == "always" and shuffle_codec == "json":
            # the DAG data plane is binary-only; silently degrading a
            # forced "always" to the single-cut path would make a test
            # or A/B measure the wrong execution path
            raise ValueError(
                "shuffle_dag='always' requires shuffle_codec='binary' "
                "(DAG stages ship columnar frames only)"
            )
        # shuffle DAG policy (PERF_NOTES "Shuffle DAGs"): "auto" runs a
        # multi-stage exchange chain / range ORDER BY only when the
        # sliced side clears shuffle_min_rows (the same bar as the
        # repartition-join policy); "always"/"never" force it (tests,
        # benchmarks). DAG stages need the binary codec.
        self.shuffle_dag = shuffle_dag
        # per-edge broadcast threshold (rows): a join side at most
        # this big may BROADCAST (the other side ships zero bytes) —
        # 0 disables the edge entirely (opt-in until real-hardware
        # numbers calibrate the copy-vs-repartition crossover)
        self.shuffle_broadcast_rows = int(shuffle_broadcast_rows)
        # range-exchange boundary sampling: per-producer sample size
        # and the FIXED seed (same data + same seed = identical
        # boundaries — retries and chaos replays stay deterministic)
        self.shuffle_sample_k = int(shuffle_sample_k)
        self.shuffle_sample_seed = int(shuffle_sample_seed)
        # pipeline=on|off (PERF_NOTES "Shuffle pipelining"): on, workers
        # overlap produce/push/on-arrival-decode/stage within a stage;
        # off is the barrier escape hatch (four sequential phases, like
        # shuffle_codec=json is for the wire format)
        self.shuffle_pipeline = bool(shuffle_pipeline)
        # producer sub-slices per side (None = worker default): row-
        # sliceable sides execute as this many disjoint frag sub-slices
        # so push overlaps the SAME side's remaining produce
        self.shuffle_produce_chunks = shuffle_produce_chunks
        # exchange wire codec (PERF_NOTES "Shuffle wire format"):
        # "binary" ships length-prefixed columnar frames built straight
        # from HostColumn buffers (parallel/wire.py; tunnels still
        # negotiate down per peer for mixed-version fleets); "json" is
        # the row-packet escape hatch
        self.shuffle_codec = shuffle_codec
        # worker-to-worker shuffle policy (PERF_NOTES "Shuffle vs
        # staging"): "auto" uses direct tunnels when coordinator
        # staging is unavailable (the single-host fallback lift) or
        # when neither repartition-join side is small; "always"/"never"
        # force the choice (tests, benchmarks)
        self.shuffle_mode = shuffle_mode
        self.shuffle_min_rows = shuffle_min_rows
        self.shuffle_packet_rows = shuffle_packet_rows
        self.shuffle_inflight_bytes = shuffle_inflight_bytes
        # stage ids must be unique per COORDINATOR INSTANCE: qids
        # restart at 1 after a coordinator restart, and long-lived
        # workers would otherwise serve a previous incarnation's
        # buffered partitions for a colliding (sid, attempt)
        import uuid

        self._sid_prefix = uuid.uuid4().hex[:8]
        self.endpoints = [EngineEndpoint(h, p, secret) for h, p in endpoints]
        self.prober = prober or FailedEngineProber()
        self.max_attempts = max_attempts
        # first dispatch on a fresh worker pays the fragment's XLA
        # compile; the RPC read must outlast it
        self.dispatch_timeout_s = dispatch_timeout_s
        # catalog: schemas/stats for fragment planning and the final
        # stage's local engine (no data required — the final stage's
        # only source is the Staged partials batch)
        if catalog is None:
            from tidb_tpu.storage import Catalog

            catalog = Catalog()
        self.catalog = catalog
        # unset timeout/liveness knobs resolve from the tidb_tpu_*
        # sysvars over this catalog's global store (the admission-knob
        # pattern, AdmissionController.from_sysvars): the 120s WAN
        # default is a CONFIG value, not a constant buried in drivers,
        # and a live SET re-tunes an attached scheduler
        # (session.py SetVariable hook -> retune()).
        from tidb_tpu.utils.sysvar import SysVars

        sv = SysVars(getattr(catalog, "global_sysvars", None))
        if shuffle_wait_timeout_s is None:
            shuffle_wait_timeout_s = float(
                sv.get("tidb_tpu_shuffle_wait_timeout_s")
            )
        if heartbeat_interval_s is None:
            heartbeat_interval_s = float(
                sv.get("tidb_tpu_heartbeat_interval_s")
            )
        if heartbeat_miss_threshold is None:
            heartbeat_miss_threshold = int(
                sv.get("tidb_tpu_heartbeat_miss_threshold")
            )
        # adaptive execution knobs (parallel/aqe.py): skew bar + salt
        # fan-out arm the hash-exchange probe; aqe_feedback seeds the
        # cost model from per-digest observed actuals; the replan
        # ratio gates stage-boundary re-planning. Unset args resolve
        # from the sysvars like the liveness knobs above.
        if shuffle_skew_ratio is None:
            shuffle_skew_ratio = float(
                sv.get("tidb_tpu_shuffle_skew_ratio")
            )
        if shuffle_skew_salt_k is None:
            shuffle_skew_salt_k = int(
                sv.get("tidb_tpu_shuffle_skew_salt_k")
            )
        if aqe_feedback is None:
            aqe_feedback = bool(sv.get("tidb_tpu_aqe_feedback"))
        if aqe_replan_ratio is None:
            aqe_replan_ratio = float(
                sv.get("tidb_tpu_aqe_replan_ratio")
            )
        # runtime filters (PERF_NOTES "PR 19: runtime filters"): the
        # probe round harvests a build-side key summary (bloom /
        # in-list / min-max) and the stage dispatch carries it so
        # probe-side producers drop non-matching rows BEFORE
        # partition+encode. "auto" costs filter build+ship bytes
        # against CARD_FEEDBACK-predicted probe bytes saved;
        # "always"/"off" force the choice (tests, benchmarks).
        if runtime_filter is None:
            runtime_filter = str(sv.get("tidb_tpu_runtime_filter"))
        if runtime_filter not in ("auto", "off", "always"):
            raise ValueError(f"bad runtime_filter {runtime_filter!r}")
        if rf_bloom_bits is None:
            rf_bloom_bits = int(
                sv.get("tidb_tpu_runtime_filter_bloom_bits")
            )
        if rf_inlist_ndv is None:
            rf_inlist_ndv = int(
                sv.get("tidb_tpu_runtime_filter_inlist_ndv")
            )
        self.runtime_filter = runtime_filter
        self.rf_bloom_bits = int(rf_bloom_bits)
        self.rf_inlist_ndv = int(rf_inlist_ndv)
        self.shuffle_skew_ratio = float(shuffle_skew_ratio)
        self.shuffle_skew_salt_k = int(shuffle_skew_salt_k)
        self.aqe_feedback = bool(aqe_feedback)
        self.aqe_replan_ratio = float(aqe_replan_ratio)
        self.shuffle_wait_timeout_s = float(shuffle_wait_timeout_s)
        self.heartbeat = HostHeartbeat(
            self.endpoints, self.prober,
            interval_s=heartbeat_interval_s,
            miss_threshold=heartbeat_miss_threshold,
        )
        # jittered exponential backoff base between stage/fragment
        # retry rounds: a chaos storm quarantining hosts across many
        # concurrent queries must not re-dispatch them in lockstep
        # (synchronized retries re-stampede the survivors)
        self.retry_backoff_s = float(retry_backoff_s)
        from tidb_tpu.planner.physical import PhysicalExecutor

        self._executor = PhysicalExecutor(catalog)
        # coordinator-side trace: remote fragment spans merge here,
        # host-labeled (enable + reset per query to collect)
        self.tracer = Tracer()
        #: telemetry of the most recent fragmented query:
        #: {"qid", "fragments": [{fid, host, attempt, rows, exec_s,
        #:  bytes, spans}]}. Scheduler-global (the /dcn endpoint's
        #: view); concurrent sessions snapshot their OWN query via
        #: last_query_mine() — the thread-local twin — because this
        #: field is overwritten by whichever query finishes last.
        self.last_query: Optional[dict] = None
        self._tls = threading.local()
        self._lock = racecheck.make_lock("dcn.scheduler")
        #: per-host clock offset (host wall clock minus coordinator
        #: wall clock), sampled on each connection's handshake — worker
        #: spans rebase through it instead of the reply-receipt anchor
        self._clock_offsets: Dict[str, float] = {}
        # serving tier: a small control-connection POOL per endpoint
        # (strict request/response per CONNECTION — k pooled
        # connections let k concurrent queries' fragments overlap on
        # one host instead of serializing onto one socket)
        self.conn_pool_size = max(int(conn_pool_size), 1)
        self._pools: Dict[EngineEndpoint, _EndpointPool] = {}
        #: optional serving.AdmissionController: session routing
        #: (session.py _try_dcn_select) gates query start on it —
        #: priority/fairness queue + fleet device-memory budget
        self.admission = admission
        #: optional storage.delta.DeltaReplicator (attach_delta): the
        #: HTAP write path — coordinator DML deltas ship to the fleet
        #: and routed reads snapshot (fold, seq) against it
        self.delta = None
        self._compactor = None
        self._rr = 0

    # -- HTAP delta tier (storage/delta.py) ------------------------------
    def attach_delta(
        self, store, compact_interval_s: float = 0.5,
        compact_depth: int = 32,
    ):
        """Attach a coordinator DeltaStore: routed reads gain delta
        snapshots (freshness modes, worker-side merge) and the
        background delta-compactor starts folding the log into the
        fleet's base blocks. Idempotent."""
        if self.delta is not None:
            return self.delta
        from tidb_tpu.storage.delta import DeltaCompactor, DeltaReplicator

        self.delta = DeltaReplicator(store, self)
        self._compactor = DeltaCompactor(
            self.delta, self.catalog,
            interval_s=compact_interval_s,
            depth_threshold=compact_depth,
        )
        self._compactor.start()
        return self.delta

    def _build_snapshot(self, plan, delta_seq, pins) -> Optional[dict]:
        """The routed snapshot one query's EVERY dispatch carries:
        each scanned table's base version pinned for the whole
        dispatch (a concurrent write + version GC can no longer
        mutate an in-flight routed query's input — fragment slices
        index the base block concatenation, so every fragment must
        read ONE version) plus the delta (fold, seq) window replica
        workers merge. The caller unpins ``pins`` when the query
        completes."""
        from tidb_tpu.storage.delta import scans_in

        tables: Dict[str, int] = {}
        for s in scans_in(plan):
            key = f"{s.db.lower()}.{s.table.lower()}"
            if key in tables:
                continue
            try:
                t = self.catalog.table(s.db, s.table)
            except Exception:
                continue
            v = t.pin_current()
            pins.append((t, v))
            tables[key] = v
        if not tables and self.delta is None:
            return None
        snap = {"tables": tables}
        if self.delta is not None:
            snap.update(self.delta.build_snapshot(delta_seq))
        return snap

    # -- host/connection management ------------------------------------
    def alive_endpoints(self) -> List[EngineEndpoint]:
        return [ep for ep in self.endpoints if ep.alive]

    def _next_alive(self, exclude=()) -> Optional[EngineEndpoint]:
        with self._lock:
            alive = [
                ep for ep in self.endpoints
                if ep.alive and ep not in exclude
            ] or [ep for ep in self.endpoints if ep.alive]
            if not alive:
                return None
            ep = alive[self._rr % len(alive)]
            self._rr += 1
            return ep

    def _pool(self, ep: EngineEndpoint) -> _EndpointPool:
        with self._lock:
            pool = self._pools.get(ep)
            if pool is None:
                pool = self._pools[ep] = _EndpointPool(
                    ep, self.dispatch_timeout_s,
                    size=self.conn_pool_size,
                    on_connect=self._on_connect,
                )
            return pool

    def _on_connect(self, ep: EngineEndpoint, c: EngineClient) -> None:
        """Per-connection handshake telemetry (runs OUTSIDE any pool
        lock): clock-offset sample for span rebasing, and the RTT as
        the control-link health reading (cluster_links, /links)."""
        if c.clock_offset_s is not None:
            self._clock_offsets[ep.address] = c.clock_offset_s
        LINKS.note_handshake(ep.address, c.clock_rtt_s, c.clock_offset_s)

    def close(self) -> None:
        if self._compactor is not None:
            self._compactor.stop()
        self.heartbeat.stop()
        with self._lock:
            pools = list(self._pools.values())
        for pool in pools:
            pool.close_idle()
        self.prober.stop()

    # -- dispatch -------------------------------------------------------
    def _dispatch(self, ep, plan, frag_meta, snap=None):
        """One fragment dispatch on one host. Transport failures raise;
        engine-side execution errors raise RuntimeError (no failover —
        they reproduce everywhere). Returns (cols, rows, resp) — the
        raw response carries the worker's spans and runtime stats."""
        inject("dcn/dispatch")
        _c_dispatches().labels(host=ep.address).inc()
        if inject("dcn/dispatch-lost"):
            raise ConnectionError("failpoint: dispatch lost in transit")
        # pooled control connection: the RPC holds ONE pooled stream,
        # not a per-host lock — concurrent queries' fragments to this
        # host ride sibling connections (serving-tier reentrancy). A
        # transport failure poisons the connection (EngineClient marks
        # _dead) and checkin frees its slot.
        with self._pool(ep).lease() as conn:
            return conn.execute_plan_full(plan, frag=frag_meta, snap=snap)

    def _quarantine(self, ep: EngineEndpoint) -> None:
        self._pool(ep).close_idle()
        # detect() reports whether THIS call made the alive->failed
        # transition: one host death = one quarantine count, no matter
        # how many fragment threads observed it
        if self.prober.detect(ep):
            _c_quarantines().labels(host=ep.address).inc()
        _update_host_gauges(self.endpoints)

    # -- fleet-wide cancellation + deadline propagation -----------------
    @staticmethod
    def _deadline_left(deadline: Optional[float]) -> Optional[float]:
        """Remaining seconds of an absolute time.monotonic deadline —
        what a dispatch carries to the worker (REMAINING time, not a
        wall-clock instant: wall clocks skew across hosts, durations
        do not). Floors at 50ms so an already-expired statement still
        dispatches a frame the worker immediately cancels (keeping the
        abort path uniform) instead of shipping a negative budget."""
        if deadline is None:
            return None
        return max(deadline - time.monotonic(), 0.05)

    def _cancel_fleet(self, qid, sid=None, reason: str = "") -> None:
        """Broadcast cancel_query for ``qid`` to every alive worker —
        the coordinator half of KILL / max_execution_time reaching
        in-flight fragments and shuffle tasks. Dedicated short-lived
        connections: the pooled streams are busy carrying the very
        dispatches being cancelled. One thread per host, joined with a
        bounded cap — a WEDGED host (accepting TCP, not answering:
        exactly the shape cancellation exists for) must not delay the
        healthy hosts' cancel frames by its own timeout, let alone
        serially sum across hosts. Best-effort per host (a dead host
        has nothing to cancel); the propagated dispatch deadline is
        the backstop for hosts the broadcast cannot reach."""
        inject("dcn/cancel")
        _c_cancels().inc()
        if TIMELINE.active():
            TIMELINE.emit_event(
                "fragment", f"cancel q{qid}", time.time(), 0.0,
                track=f"q{qid}", args={"qid": qid, "reason": reason},
            )

        def one(ep):
            try:
                c = EngineClient(
                    ep.host, ep.port, secret=ep.secret, timeout_s=5.0
                )
                try:
                    c.cancel_query(
                        qid, sid=sid, reason=reason,
                        coord=self._sid_prefix,
                    )
                finally:
                    c.close()
            except Exception:
                pass

        threads = [
            threading.Thread(
                target=one, args=(ep,), daemon=True,
                name=f"dcn-cancel-{ep.address}",
            )
            for ep in self.alive_endpoints()
        ]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 5.0
        for t in threads:
            t.join(timeout=max(deadline - time.monotonic(), 0.0))

    def _join_watch(
        self, threads, qid, sid=None, kill_check=None, deadline=None
    ) -> Optional[BaseException]:
        """Join the dispatch threads while watching for a local kill
        or deadline expiry; on the FIRST trigger broadcast the fleet
        cancel (workers abort at their next safepoint, so the joins
        below return promptly) and keep joining. Returns the kill
        exception (to raise after cleanup) or None."""
        killed: Optional[BaseException] = None
        while True:
            alive = [t for t in threads if t.is_alive()]
            if not alive:
                return killed
            if killed is None:
                try:
                    if kill_check is not None:
                        kill_check()
                    if (
                        deadline is not None
                        and time.monotonic() > deadline
                    ):
                        from tidb_tpu.utils.sqlkiller import QueryKilled

                        raise QueryKilled(
                            "query interrupted (statement deadline "
                            "exceeded at the coordinator)"
                        )
                except BaseException as e:
                    killed = e
                    self._cancel_fleet(qid, sid=sid, reason=str(e))
            for t in alive:
                t.join(timeout=0.05)

    def _retry_sleep(self, rnd: int, kill_check=None) -> None:
        """Jittered exponential backoff between retry rounds: base *
        2^rnd scaled by a uniform [0.5, 1.0) draw, capped at 2s — a
        chaos storm failing many queries' stages at once must not
        re-dispatch them in lockstep onto the survivors. Polls the
        kill check so KILL still lands mid-backoff."""
        if self.retry_backoff_s <= 0:
            return
        import random

        d = min(self.retry_backoff_s * (2 ** rnd), 2.0) * (
            0.5 + 0.5 * random.random()
        )
        _c_retry_backoff().inc(d)
        end = time.monotonic() + d
        while True:
            if kill_check is not None:
                kill_check()
            left = end - time.monotonic()
            if left <= 0:
                return
            time.sleep(min(left, 0.05))

    def _classify_reply(
        self, resp, suspects, errs, cancelled, release=None
    ) -> bool:
        """THE worker-reply classification, shared by fragment, sampling and
        DAG-stage dispatch: True = ok (the caller lands the result); a
        deliberate abort (``cancelled`` — fleet cancel / propagated
        deadline: neither an engine error nor a death suspect, PR 10's
        rule) or a retryable stage failure calls ``release`` (the
        ledger-claim return) and records into the caller's
        attempt-scoped lists, returning False; anything else is a
        fatal engine error that reproduces everywhere — raise."""
        if resp.get("ok"):
            return True
        if resp.get("cancelled"):
            if release is not None:
                release()
            with self._lock:
                cancelled.append(str(resp.get("error", "")))
            return False
        if resp.get("retryable"):
            if release is not None:
                release()
            with self._lock:
                suspects.extend(resp.get("suspects") or [])
                errs.append(str(resp.get("error", "")))
            return False
        raise RuntimeError(f"engine error: {resp.get('error', '')}")

    # -- query execution ------------------------------------------------
    def execute_plan(
        self, plan: L.LogicalPlan, cut_hint=None, kill_check=None,
        deadline=None, delta_seq=None, digest=None,
    ) -> Tuple[List[str], List[tuple]]:
        """Run a bound logical plan across the worker hosts. Prefers a
        worker-to-worker shuffle cut when the policy says tunnels beat
        coordinator staging, then the partial-agg staging cut, then
        whole-plan single-host dispatch; every path survives worker
        loss up to max_attempts. ``cut_hint`` is a precomputed
        (kind, cut) from _choose_cut so a caller that already planned
        the route (session SELECT routing) does not pay the planner
        pass twice.

        Fleet-wide cancellation: ``kill_check`` (the session killer's
        check — KILL QUERY and max_execution_time both raise through
        it) is polled while dispatches are in flight; on the first
        raise the coordinator broadcasts ``cancel_query`` to every
        alive worker so in-flight fragments and shuffle tasks abort at
        their next safepoint instead of burning the fleet to
        completion. ``deadline`` (absolute time.monotonic, or None) is
        additionally PROPAGATED: each dispatch carries its remaining
        seconds, so a worker self-cancels even if the coordinator is
        wedged."""
        kind, cut = (
            cut_hint if cut_hint is not None
            else self._choose_cut(plan, digest=digest)
        )
        # routed snapshot: pin every scanned table's base version for
        # the WHOLE query (all fragments of all stages read one base —
        # a concurrent write + version GC cannot mutate an in-flight
        # routed query's input) and carry the delta (fold, seq) window
        pins: List[tuple] = []
        snap = self._build_snapshot(plan, delta_seq, pins)
        try:
            if kind == "dag":
                t0 = time.perf_counter()
                FLIGHT.set_live_phase("fragment-dispatch")
                parts_rows, infos, stages = self._run_dag(
                    cut, kill_check=kill_check, deadline=deadline,
                    snap=snap, digest=digest,
                )
                retries = max(
                    (int(s.get("attempts", 1)) - 1 for s in stages),
                    default=0,
                )
                self._note_dispatch(t0, infos, retries=retries)
                for s in stages:
                    FLIGHT.note_shuffle_stage(s)
                if cut.merge.get("kind") == "concat":
                    return self._concat_merge(cut, parts_rows)
                rows = [r for part in parts_rows for r in part]
                return self._timed_final_stage(cut, rows)
            if kind == "shuffle":
                t0 = time.perf_counter()
                FLIGHT.set_live_phase("fragment-dispatch")
                rows, infos, stage, used = self._run_shuffle(
                    cut, kill_check=kill_check, deadline=deadline,
                    snap=snap, plan=plan, digest=digest,
                )
                self._note_dispatch(
                    t0, infos,
                    retries=max(int(stage.get("attempts", 1)) - 1, 0),
                )
                FLIGHT.note_shuffle_stage(stage)
                # `used` may be a re-planned cut (the salted group-by
                # variant re-merges partials through ITS final-agg
                # builder), so the final stage runs the cut the
                # workers actually executed
                return self._timed_final_stage(used, rows)
            if kind == "frag":
                t0 = time.perf_counter()
                FLIGHT.set_live_phase("fragment-dispatch")
                ledger, infos = self._run_fragments(
                    cut, kill_check=kill_check, deadline=deadline,
                    snap=snap,
                )
                self._note_dispatch(
                    t0, infos, retries=ledger.total_retries()
                )
                # remote engine row work (summed across hosts, like the
                # shuffle phases and the reference's cop-task totals)
                FLIGHT.note_phase(
                    "execute", sum(f.get("exec_s", 0.0) for f in infos)
                )
                return self._timed_final_stage(cut, ledger.rows())
            return self._execute_single(plan, snap=snap)
        finally:
            for t, v in pins:
                t.unpin(v)

    @staticmethod
    def _note_dispatch(t0: float, infos, retries: int) -> None:
        """Flight attribution (obs/flight.py): fragment-dispatch is the
        coordinator-side OVERHEAD — the dispatch+gather wall minus the
        critical-path worker execution it blocks on. The worker time
        itself is charged elsewhere (the shuffle phases, or the frag
        branch's summed execute), so nothing counts twice."""
        wall = time.perf_counter() - t0
        crit = max((f.get("exec_s", 0.0) for f in infos), default=0.0)
        FLIGHT.set_live_phase("execute")  # dispatch window over
        FLIGHT.note_phase(
            "fragment-dispatch", max(wall - crit, 0.0), retries=retries
        )
        # counter tracks move at dispatch cadence too (pool leases /
        # stages buffered peak right here, not at statement close)
        TIMELINE.sample_gauges()

    @staticmethod
    def _worker_mem_peak(infos) -> int:
        """The fleet-eyed device-mem high-water of one query: the max
        of the workers' OWN per-fragment engine-watch peaks shipped in
        the fenced replies. The admission estimate learns from
        max(coordinator peak, this) — a worker-heavier plan (the
        pre-aggregation runs below the exchange) no longer gates on
        the coordinator's smaller final-stage shape (ROADMAP PR 8)."""
        return max(
            (int(f.get("mem_peak", 0)) for f in infos), default=0
        )

    @staticmethod
    @contextlib.contextmanager
    def _final_merge_phase():
        """Charge the enclosed coordinator-local merge work to the
        final-merge flight phase MINUS any jit traces watched_jit
        charges to "compile" inside it, so the two phases stay
        additive — the ONE definition both the plan-based final stage
        and the range-concat merge use."""
        t1 = time.perf_counter()
        c0 = FLIGHT.phase_seconds("compile")
        prev_phase = FLIGHT.set_live_phase("final-merge")
        try:
            yield
        finally:
            FLIGHT.restore_live_phase(prev_phase)
            FLIGHT.note_phase(
                "final-merge",
                (time.perf_counter() - t1)
                - (FLIGHT.phase_seconds("compile") - c0),
            )

    def _timed_final_stage(self, cut, rows):
        """Run the coordinator-local final stage under the final-merge
        phase accounting."""
        with self._final_merge_phase():
            return self._final_stage(cut, rows)

    @staticmethod
    def _delta_lines(infos) -> List[str]:
        """The EXPLAIN ANALYZE DeltaMerge row: summed worker-side
        merge stats of one routed query (delta depth, merged insert
        rows, delete keys filtered) — present only when some fragment
        actually merged buffered deltas."""
        ds = [f.get("delta") for f in infos if f.get("delta")]
        if not ds:
            return []
        return [
            "DeltaMerge depth="
            f"{max(int(d.get('depth', 0)) for d in ds)} "
            f"ins_rows={sum(int(d.get('ins_rows', 0)) for d in ds)} "
            f"delete_keys={max(int(d.get('del_keys', 0)) for d in ds)} "
            f"fragments={len(ds)}"
        ]

    def explain_analyze(
        self, plan: L.LogicalPlan, delta_seq=None, digest=None,
    ) -> Tuple[List[str], List[tuple], List[str]]:
        """Distributed EXPLAIN ANALYZE: run the fragments (or the
        shuffle stage), then the final stage INSTRUMENTED, and merge
        the per-host fragment stats (rows/host, execution times, bytes
        shipped over DCN — plus the Shuffle exchange rows: partition
        bytes over tunnels, stalls, retransmits) into the coordinator's
        plan-tree rows — the reference's cop-task RuntimeStatsColl
        merge, over the engine-RPC seam. Returns (columns, rows, plan
        lines)."""
        kind, cut = self._choose_cut(plan, digest=digest)
        pins: List[tuple] = []
        snap = self._build_snapshot(plan, delta_seq, pins)
        try:
            return self._explain_analyze_inner(
                plan, kind, cut, snap, digest=digest
            )
        finally:
            for t, v in pins:
                t.unpin(v)

    def _explain_analyze_inner(self, plan, kind, cut, snap, digest=None):
        from tidb_tpu.chunk import materialize_rows

        if kind == "dag":
            parts_rows, infos, stages = self._run_dag(
                cut, snap=snap, digest=digest
            )
            pairs = [
                (s, [f for f in infos if f.get("stage", 0) == si])
                for si, s in enumerate(stages)
            ]
            if cut.merge.get("kind") == "concat":
                cols, rows = self._concat_merge(cut, parts_rows)
                lim = cut.merge.get("limit")
                lines = [
                    "RangeConcatMerge stages="
                    f"{len(stages)} reverse="
                    f"{bool(cut.merge.get('reverse'))} "
                    f"limit={lim[0] if lim else 'none'} "
                    f"rows={len(rows)}"
                ]
                from tidb_tpu.planner.physical import (
                    _merge_shuffle_stats,
                )

                for s, fi in pairs:
                    lines = _merge_shuffle_stats(lines, s, fi)
                return cols, rows, lines
            inject("dcn/final-stage")
            rows = [r for part in parts_rows for r in part]
            staged = self._stage_rows(cut, rows)
            final = cut.final_builder(staged)
            out, dicts, lines = self._executor.run_analyze(
                final, shuffle_stats=pairs
            )
            lines = lines + self._delta_lines(infos)
            out_rows = materialize_rows(out, list(final.schema), dicts)
            return [c.name for c in final.schema], out_rows, lines
        if kind == "shuffle":
            rows, infos, stage, used = self._run_shuffle(
                cut, snap=snap, plan=plan, digest=digest
            )
            inject("dcn/final-stage")
            staged = self._stage_rows(used, rows)
            final = used.final_builder(staged)
            out, dicts, lines = self._executor.run_analyze(
                final, shuffle_stats=(stage, infos)
            )
            lines = lines + self._delta_lines(infos)
            out_rows = materialize_rows(out, list(final.schema), dicts)
            return [c.name for c in final.schema], out_rows, lines
        if kind == "single":
            cols, rows = self._execute_single(plan, snap=snap)
            return cols, rows, [
                "SingleHostDispatch (no safe fragment split) "
                f"rows={len(rows)}"
            ]
        frag = cut
        ledger, infos = self._run_fragments(frag, snap=snap)
        inject("dcn/final-stage")
        staged = self._stage_rows(frag, ledger.rows())
        final = frag.final_builder(staged)
        out, dicts, lines = self._executor.run_analyze(
            final, frag_stats=infos
        )
        lines = lines + self._delta_lines(infos)
        out_rows = materialize_rows(out, list(final.schema), dicts)
        return [c.name for c in final.schema], out_rows, lines

    # -- worker-to-worker shuffle stages --------------------------------
    def _choose_cut(self, plan: L.LogicalPlan, digest: Optional[str] = None):
        """One planning pass deciding the execution path — plus the
        AQE feedback seam (parallel/aqe.py): with
        ``tidb_tpu_aqe_feedback=on`` and a digest whose observed
        per-side rows were recorded from an earlier run, the cut is
        re-planned with the MEASURED side estimates; when that changes
        the decision (the shuffle_mode=auto gates or an edge mode),
        the ``feedback`` decision is counted and the cut carries the
        ``adaptive=feedback`` marker into the stage summary."""
        base = self._choose_cut_inner(plan)
        if not self.aqe_feedback or not digest:
            return base
        from tidb_tpu.planner.cardinality import CARD_FEEDBACK

        seeds = CARD_FEEDBACK.sides_for(digest)
        if not seeds:
            return base
        seeded = self._choose_cut_inner(plan, seeds=seeds)
        if self._cut_signature(seeded) != self._cut_signature(base):
            from tidb_tpu.parallel import aqe

            token = aqe.note_decision("feedback")
            if seeded[1] is not None:
                seeded[1]._aqe_tokens = [token]
        return seeded

    @staticmethod
    def _cut_signature(cut) -> tuple:
        """The DECISION content of one planned cut: the path kind plus
        every side's exchange mode — what the feedback seeding must
        have changed for the ``feedback`` decision to count."""
        kind, c = cut
        if kind == "dag":
            return ("dag", tuple(
                tuple(s.mode for s in st.sides) for st in c.stages
            ))
        if kind == "shuffle":
            return ("shuffle", tuple(s.mode for s in c.sides))
        return (kind,)

    @staticmethod
    def _seed_sides(sides, stage_idx: int, seeds, kind: str) -> None:
        """Overwrite static side estimates with recorded actuals
        (keys ``"<kind>:<stage>:<tag>"`` — per-side produced rows from
        the fenced stage stats of this digest's last run). Keys are
        scoped by the cut KIND that executed: a single-stage shuffle
        run's side totals must not seed a DAG candidate's stages (or
        vice versa) — same digest, different relations per side."""
        if not seeds:
            return
        for s in sides:
            v = seeds.get(f"{kind}:{stage_idx}:{s.tag}")
            if v is not None:
                s.est_rows = int(v)

    def _choose_cut_inner(self, plan: L.LogicalPlan, seeds=None):
        """One planning pass deciding the execution path: ("dag",
        ShuffleDAG) | ("shuffle", ShufflePlan) | ("frag",
        FragmentPlan) | ("single", None).

        The shuffle-vs-staging cost model: staging ships each row
        group TWICE through one box (worker->coordinator, then a
        device round trip) but partial aggregation usually shrinks the
        exchange to near-nothing first; tunnels ship pre-join rows
        ONCE, peer to peer, which wins when neither join side is small
        or when no partial-agg cut exists at all (DISTINCT/high-
        cardinality GROUP BY — previously a single-host fallback).

        The DAG tier sits above both: a join feeding a DIFFERENT
        group-key exchange chains two stages (the single-cut group-by
        re-scans unsliced join sides on every host — N x wasted scan
        work), and an ORDER BY (LIMIT) root distributes over a range
        exchange with per-partition top-K. "auto" takes the DAG only
        when the sliced side clears shuffle_min_rows — at small scale
        the extra stage dispatch dominates; shuffle_dag="always"
        forces it (tests, the bench A/B). Each hash join edge then
        runs the per-edge cost model (choose_edge_modes): a side
        under shuffle_broadcast_rows broadcasts while the big side
        ships ZERO bytes."""
        if (
            self.shuffle_mode != "never"
            and self.shuffle_dag != "never"
            and self.shuffle_codec == "binary"
        ):
            dag = split_plan_dag(plan, self.catalog)
            if dag is not None:
                for si, st in enumerate(dag.stages):
                    self._seed_sides(st.sides, si, seeds, "dag")
                    choose_edge_modes(st, self.shuffle_broadcast_rows)
                if self.shuffle_dag == "always":
                    return "dag", dag
                big = max(
                    (
                        s.est_rows
                        for st in dag.stages
                        for s in st.sides
                    ),
                    default=0,
                )
                if big >= self.shuffle_min_rows:
                    return "dag", dag
        sp = None
        if self.shuffle_mode != "never":
            sp = split_plan_shuffle(plan, self.catalog)
        if sp is not None:
            from tidb_tpu.planner.fragmenter import choose_shuffle_modes

            self._seed_sides(sp.sides, 0, seeds, "shuffle")
            choose_shuffle_modes(sp, self.shuffle_broadcast_rows)
            if self.shuffle_mode == "always":
                return "shuffle", sp
            if sp.kind == "join" and min(
                s.est_rows for s in sp.sides
            ) >= self.shuffle_min_rows:
                # neither side small: repartition over tunnels —
                # decided without paying the staging planner's pass
                return "shuffle", sp
            if (
                sp.kind == "join"
                and any(s.mode == "broadcast" for s in sp.sides)
                and max(s.est_rows for s in sp.sides)
                >= self.shuffle_min_rows
            ):
                # one side collapsed under the broadcast bar (static
                # stats, or the AQE feedback seed): broadcast join
                # over tunnels ships the big side ZERO bytes — beats
                # both repartition and the staging cut's re-shipping
                return "shuffle", sp
        frag = split_plan(plan, self.catalog)
        if frag is not None:
            return "frag", frag
        if sp is not None:
            return "shuffle", sp  # lifts the single-host fallback
        return "single", None

    def _plan_shuffle(self, plan: L.LogicalPlan) -> Optional[ShufflePlan]:
        """The ShufflePlan the policy would run, or None (introspection
        helper; the execution paths use _choose_cut directly)."""
        kind, cut = self._choose_cut(plan)
        return cut if kind == "shuffle" else None

    def _run_shuffle(
        self, sp: ShufflePlan, kill_check=None, deadline=None,
        snap=None, plan=None, digest=None,
    ) -> Tuple[List[tuple], List[dict], dict, "ShufflePlan"]:
        """Run one shuffle stage to completion: dispatch a produce+
        consume task per alive host, each host pushing hash partitions
        directly to its peers; on a peer death (transport loss to the
        coordinator, a reported dead tunnel, or a wait timeout) verify
        the suspects, quarantine them, and re-run the WHOLE stage on
        the survivor set at the next attempt — receivers fence stale-
        attempt packets, the per-attempt ledger fences results, so a
        retried stage lands exactly once.

        Adaptive execution (parallel/aqe.py): with
        ``tidb_tpu_shuffle_skew_ratio`` armed, a PROBE round first
        produces-and-caches every side and replies exact
        per-partition histograms + hot keys; the stage then
        dispatches salted (hot partition split across K hosts) or
        broadcast-switched (a collapsed side observed under
        ``shuffle_broadcast_rows``) — the cached produce blocks mean
        the re-planned stage never re-executes the producers.
        Returns (rows, infos, stage summary, the ShufflePlan actually
        executed — the salted group-by variant re-merges through ITS
        final builder)."""
        qid = _QUERY_ID.next()
        sid = f"{self._sid_prefix}-q{qid}"
        ts_entry = self._topsql_entry()  # statement thread: see helper
        stage = {
            "sid": sid, "qid": qid, "kind": sp.kind, "attempts": 0,
            "m": 0, "bytes_tunneled": 0, "rows_tunneled": 0,
            "local_rows": 0, "stalls": 0, "stall_s": 0.0,
            "retransmits": 0,
            "codec": self.shuffle_codec, "encode_s": 0.0,
            "produce_s": 0.0, "wait_s": 0.0, "stage_s": 0.0,
            "scan_rows": 0,
            # what the workers will actually run: the pipeline needs
            # the binary codec, so the json escape hatch forces barrier
            # (mirrors ShuffleWorker.run_task's own gate)
            "pipeline": (
                self.shuffle_pipeline and self.shuffle_codec == "binary"
            ),
            "wait_idle_s": 0.0, "ttff_s": 0.0, "exec_s": 0.0,
        }
        last_err: Optional[str] = None
        # AQE precheck, once per statement: a group-by cut can only
        # act on a probe through its salted partial/final variant —
        # when the aggregate does not decompose (DISTINCT,
        # GROUP_CONCAT) there is NO possible adaptive action, so the
        # probe round (a produce-and-cache pass + an RPC round per
        # attempt) would be pure overhead and is skipped entirely
        salted_sp = None
        if (
            self.shuffle_skew_ratio > 1.0
            and self.shuffle_codec == "binary"
            and sp.kind == "groupby" and plan is not None
        ):
            from tidb_tpu.planner.fragmenter import (
                split_plan_shuffle_salted,
            )

            salted_sp = split_plan_shuffle_salted(plan, self.catalog)
        # runtime-filter candidacy (PR 19, once per statement): the
        # legal build->apply direction plus the coordinator-fixed
        # bloom geometry (every host builds the same shape, so the
        # per-host bitsets OR together in the merge)
        rf_cand = None
        rf_spec = None
        if (
            self.runtime_filter != "off"
            and self.shuffle_codec == "binary"
            and sp.kind == "join"
            and all(s.frag_scan is not None for s in sp.sides)
        ):
            rf_cand = self._rf_candidate(sp)
        if rf_cand is not None:
            from tidb_tpu.parallel.wire import bloom_geometry

            est_b = int(
                next(
                    s for s in sp.sides if s.tag == rf_cand[0]
                ).est_rows or 0
            )
            nbits, kh = bloom_geometry(
                max(est_b, 1), self.rf_bloom_bits
            )
            rf_spec = {
                "bits": int(nbits), "k": int(kh),
                "inlist_ndv": int(self.rf_inlist_ndv),
            }
        # producer partial-agg skip candidacy (the PR 5 "Partial
        # Partial Aggregates" item): plan the partial-agg-free join
        # variant once per statement; the probe's observed group NDV
        # decides whether the partial agg is pure overhead
        aggskip_sp = None
        if (
            self.shuffle_codec == "binary" and plan is not None
            and sp.kind == "join"
            and (self.shuffle_skew_ratio > 1.0 or rf_cand is not None)
        ):
            from tidb_tpu.planner.fragmenter import (
                split_plan_shuffle_aggskip,
            )

            aggskip_sp = split_plan_shuffle_aggskip(plan, self.catalog)
        for rnd in range(self.max_attempts):
            if rnd:
                # jittered exponential backoff before every re-attempt:
                # stage retries across concurrent queries desynchronize
                # instead of stampeding the survivor set together
                self._retry_sleep(rnd - 1, kill_check)
            if not self.alive_endpoints():
                self.prober.probe_once()
            hosts = self.alive_endpoints()
            if not hosts:
                break
            m = len(hosts)
            attempt = rnd + 1
            stage["attempts"] = attempt
            stage["m"] = m
            inject("shuffle/stage")
            _c_shuffle_stages().inc()
            if rnd:
                inject("shuffle/stage-retry")
                _c_shuffle_stage_retries().inc()
            peers = [[ep.host, ep.port] for ep in hosts]
            ledger = FragmentLedger(m)
            infos: List[dict] = []
            suspects: List[str] = []
            errs: List[str] = []
            fatal: List[Exception] = []
            cancelled: List[str] = []
            killed: Optional[BaseException] = None
            # -- AQE probe + re-plan (parallel/aqe.py): the feedback
            # marker from _choose_cut rides along; the probe may add
            # salted / broadcast-switch on top
            used_sp = sp
            salts = None
            tokens = list(getattr(sp, "_aqe_tokens", None) or [])
            probe = None
            rf = None
            probed_tags = None  # None = every side produced-and-cached
            skew_arm = (
                self.shuffle_skew_ratio > 1.0
                and (sp.kind != "groupby" or salted_sp is not None)
            )
            rf_arm = (
                rf_cand is not None
                and m > 1
                and self._rf_probe_worth(sp, rf_cand, m, digest)
            )
            if not skew_arm and aggskip_sp is None and rf_arm:
                # rf-only probe: produce-and-cache just the BUILD
                # side, so the big probe side keeps its pipelined
                # produce->filter->push overlap in the stage round
                probed_tags = {rf_cand[0]}
            if (
                (skew_arm or rf_arm)
                and self.shuffle_codec == "binary"
                and m > 1
                and all(s.frag_scan is not None for s in sp.sides)
            ):
                probe = self._probe_stage(
                    sp, hosts, m, attempt, qid, kill_check, deadline,
                    suspects, errs, snap=snap,
                    rf_spec=rf_spec if rf_arm else None,
                    rf_build_tags=(rf_cand[0],) if rf_arm else (),
                    gcol_by_tag=(
                        {aggskip_sp._aggskip_gtag:
                         aggskip_sp._aggskip_gcol}
                        if aggskip_sp is not None else None
                    ),
                    only_tags=probed_tags,
                )
                if probe is None:
                    # a probe reply was lost: exactly as retryable as
                    # a dispatch loss — verify the suspects, retry the
                    # stage on the survivor set
                    if errs:
                        last_err = errs[0]
                    self._verify_suspects(suspects)
                    continue
                used_sp, salts, toks = self._aqe_decide(
                    plan, sp, probe, m, salted_sp=salted_sp
                )
                tokens = tokens + toks
                # (3) producer partial-agg skip: the probed group NDV
                # approached the side's row count, so the partial agg
                # would barely fold anything — swap to the variant
                # that ships join rows straight to the final agg (a
                # broadcast/salt decision wins the conflict: those
                # re-shape the same sides)
                if (
                    aggskip_sp is not None and used_sp is sp
                    and not salts and not toks
                ):
                    from tidb_tpu.parallel import aqe

                    gtag = aggskip_sp._aggskip_gtag
                    gent = probe.get(gtag) or {}
                    gndv = int(gent.get("gndv", 0) or 0)
                    grows = int(gent.get("rows", 0) or 0)
                    if gndv and grows and gndv >= 0.8 * grows:
                        used_sp = aggskip_sp
                        tokens = tokens + [aqe.note_decision(
                            "partial-agg-skip", f"{gndv}/{grows}"
                        )]
                # (4) runtime filter: merge the per-host build-side
                # filters and attach to the apply side's dispatch
                if rf_arm:
                    rf, rtoks = self._rf_decide(
                        used_sp, probe, m, stage, digest, rf_cand
                    )
                    tokens = tokens + rtoks
            if rf is None:
                # this attempt runs unfiltered (probe stood down, or
                # the merge degraded): a previous attempt's rf= must
                # not linger on the summary — same contract as the
                # adaptive= reflection below
                stage.pop("rf", None)
            stage["kind"] = used_sp.kind
            # reflect THIS attempt's decisions: a retry whose probe
            # stood down (e.g. the survivor set collapsed to m=1) runs
            # the PLAIN cut, so the superseded attempt's tokens must
            # not linger on the summary (adaptive= has to agree with
            # the modes the workers actually ran)
            if tokens:
                stage["adaptive"] = list(tokens)
            else:
                stage.pop("adaptive", None)

            def run_part(i: int, ep: EngineEndpoint, conn: EngineClient):
                token = ledger.claim(i, ep.address)
                task = {
                    "sid": sid, "qid": qid, "attempt": attempt, "m": m,
                    "part": i, "peers": peers, "secret": ep.secret,
                    # cancellation scope: (coordinator instance, qid)
                    # — qids restart with the coordinator, sids don't
                    "coord": self._sid_prefix,
                    # propagated statement deadline: REMAINING seconds
                    # (None = unbounded) — the worker self-cancels its
                    # produce/wait/consume when it expires
                    "deadline_s": self._deadline_left(deadline),
                    "sides": [
                        {
                            "tag": s.tag, "key": s.key,
                            "mode": getattr(s, "mode", "hash"),
                            # salted routing spec (None = plain), and
                            # whether a probe already produced-and-
                            # cached THIS side (the stage round then
                            # reads the held block instead of
                            # re-executing the producer; an rf-only
                            # probe caches just the build side)
                            "salt": (salts or {}).get(s.tag),
                            "probed": (
                                probe is not None
                                and (probed_tags is None
                                     or s.tag in probed_tags)
                            ),
                            # merged runtime filter for the apply
                            # side (None = unfiltered shipping)
                            "rf": (
                                rf["filter"]
                                if rf is not None
                                and s.tag == rf["tag"] else None
                            ),
                            "plan": plan_to_ir(
                                self._rf_pushdown_plan(
                                    s.host_plan(i, m), s.key,
                                    rf["filter"],
                                )
                                if rf is not None
                                and s.tag == rf["tag"]
                                and not (
                                    probe is not None
                                    and (probed_tags is None
                                         or s.tag in probed_tags)
                                )
                                else s.host_plan(i, m)
                            ),
                        }
                        for s in used_sp.sides
                    ],
                    "adaptive": list(tokens) or None,
                    # single-stage tasks drain this query's held
                    # state (the probe round CACHES produce blocks
                    # via _held_put) once the consumer lands — the
                    # chaos harness's held-leak invariant
                    "release_held": True,
                    "consumer": plan_to_ir(used_sp.consumer),
                    "wait_timeout_s": self.shuffle_wait_timeout_s,
                    "packet_rows": self.shuffle_packet_rows,
                    "max_inflight_bytes": self.shuffle_inflight_bytes,
                    "codec": self.shuffle_codec,
                    "pipeline": self.shuffle_pipeline,
                    "produce_chunks": self.shuffle_produce_chunks,
                    "trace": bool(self.tracer.enabled),
                    # opt the worker into timeline event collection
                    # only while a coordinator capture is live
                    "timeline": TIMELINE.active(),
                    # routed snapshot: producers pin this base and
                    # merge the delta window (storage/delta.py)
                    "snap": snap,
                    "topsql": ts_entry,
                }
                t_d0 = time.time()
                try:
                    resp = conn.call(
                        {"v": IR_VERSION, "shuffle_task": task}
                    )
                except (SchemaOutOfDateError, RuntimeError, ValueError,
                        PermissionError):
                    # deterministic client-side failures (oversized
                    # frame, bad auth, stale schema) reproduce on every
                    # host: fatal, same contract as _dispatch
                    raise
                except Exception as e:
                    ledger.release(i, token)
                    with self._lock:
                        suspects.append(ep.address)
                        errs.append(f"{ep.address}: {e}")
                    return
                if not self._classify_reply(
                    resp, suspects, errs, cancelled,
                    release=lambda: ledger.release(i, token),
                ):
                    return
                rows = [tuple(r) for r in resp["rows"]]
                if ledger.complete(i, token, rows):
                    self._note_partition(
                        infos, i, ep, attempt, resp, qid=qid,
                        t_dispatch0=t_d0,
                    )

            def runner(i, ep, conn):
                try:
                    run_part(i, ep, conn)
                except Exception as e:
                    fatal.append(e)

            # a stage's fragments WAIT on each other's frames across
            # hosts, so leasing per-fragment inside the runners allows
            # partial slot allocation across concurrent stages to
            # cycle (stage X holds host A's last slot waiting on its
            # host-B fragment queued behind stage Y, which holds B
            # waiting on A) — broken only by the shuffle wait timeout.
            # Leasing ALL hosts' connections up front, in the fleet's
            # fixed endpoint order, makes acquisition cycle-free: a
            # stage either runs on every host or is still waiting for
            # its FIRST slot, never holding some while blocking on
            # others.
            leases: List[Tuple[EngineEndpoint, EngineClient]] = []
            try:
                try:
                    for ep in hosts:
                        leases.append((ep, self._pool(ep).checkout()))
                except Exception as e:
                    # a checkout failed (endpoint dialed dead): suspect
                    # it and let the retry loop verify/quarantine
                    bad = hosts[len(leases)]
                    with self._lock:
                        suspects.append(bad.address)
                        errs.append(f"{bad.address}: {e}")
                else:
                    threads = [
                        threading.Thread(
                            target=runner, args=(i, ep, conn),
                            daemon=True, name=f"shuffle-q{qid}-p{i}",
                        )
                        for i, (ep, conn) in enumerate(leases)
                    ]
                    for t in threads:
                        t.start()
                    # join while watching for KILL / deadline: the
                    # first trigger broadcasts cancel_query fleet-wide
                    # and the dispatch threads return promptly
                    killed = self._join_watch(
                        threads, qid, sid=sid,
                        kill_check=kill_check, deadline=deadline,
                    )
            finally:
                for ep, conn in leases:
                    self._pool(ep).checkin(conn)
            if fatal:
                raise fatal[0]
            if killed is not None:
                raise killed
            if cancelled:
                # a worker aborted on the propagated deadline before
                # the coordinator's own watch fired (clock margins):
                # same verdict, same exception type as a local kill
                from tidb_tpu.utils.sqlkiller import QueryKilled

                raise QueryKilled(cancelled[0])
            if ledger.all_done():
                infos.sort(key=lambda f: f["fid"])
                self._fold_stage(stage, infos)
                self._record_feedback(digest, [stage], "shuffle")
                lq = {
                    "qid": qid, "fragments": infos,
                    "shuffle": dict(stage),
                    "worker_mem_peak": self._worker_mem_peak(infos),
                }
                with self._lock:
                    self.last_query = lq
                self._tls.last = lq
                _update_host_gauges(self.endpoints)
                return ledger.rows(), infos, stage, used_sp
            if errs:
                last_err = errs[0]
            # verify the suspects before the next attempt: a reported
            # dead tunnel or missing producer is quarantined only when
            # it really stopped answering (a transient loss retries on
            # the same set)
            self._verify_suspects(suspects)
        raise ConnectionError(
            f"shuffle stage {sid} undispatchable after "
            f"{self.max_attempts} attempts ({len(self.endpoints)} hosts, "
            f"{len(self.alive_endpoints())} alive); last error: {last_err}"
        )

    def _verify_suspects(self, suspects) -> None:
        """Quarantine only suspects that REALLY stopped answering (a
        transient loss retries on the same set) — the pre-retry
        verification shared by the shuffle stage, the DAG chain and
        the AQE probe round."""
        by_addr = {ep.address: ep for ep in self.endpoints}
        for addr in sorted(set(suspects)):
            ep = by_addr.get(addr)
            if ep is not None and ep.alive and not ping_endpoint(ep):
                self._quarantine(ep)

    def _record_feedback(self, digest, stage_summaries, kind) -> None:
        """Record one completed routed statement's OBSERVED per-side
        produced rows into the cardinality feedback store (keys
        ``"<kind>:<stage>:<tag>"`` — scoped by the cut kind that
        executed, so a shuffle run's totals never seed a DAG
        candidate's unrelated sides) — the actuals a later run of the
        same digest seeds its cost model from (tidb_tpu_aqe_feedback)."""
        if not digest:
            return
        sides: Dict[str, int] = {}
        for st in stage_summaries:
            si = int(st.get("stage", 0))
            for tag, rows in (st.get("side_rows") or {}).items():
                key = f"{kind}:{si}:{tag}"
                sides[key] = sides.get(key, 0) + int(rows)
            # observed runtime-filter pass rate, per-mille (the
            # selectivity a later run of this digest seeds its
            # emit-or-not cost gate from — _rf_predicted)
            rf = st.get("rf") or {}
            rin = int(rf.get("rows_in", 0) or 0)
            if rin and rf.get("tag") is not None:
                kept = rin - int(rf.get("dropped", 0) or 0)
                sides[f"rf:{kind}:{si}:{rf['tag']}"] = int(
                    round(1000.0 * kept / rin)
                )
        if not sides:
            return
        from tidb_tpu.planner.cardinality import CARD_FEEDBACK

        CARD_FEEDBACK.record(digest, sides=sides)

    # -- shuffle DAGs: topo-ordered multi-stage exchanges ---------------
    @staticmethod
    def merge_boundaries(sample_lists, m: int) -> list:
        """Coordinator half of range-exchange boundary sampling: merge
        every producer's deterministic key sample and cut m-1 quantile
        boundaries (partition p owns keys in (b[p-1], b[p]]). Pure —
        same samples, same boundaries (the determinism the fixed
        sample seed buys end to end). Empty samples (all-NULL or
        empty sides) collapse every row onto partition 0, which is
        still correct, just unbalanced."""
        merged = sorted(v for lst in sample_lists for v in lst)
        if not merged or m <= 1:
            return []
        return [merged[(j * len(merged)) // m] for j in range(1, m)]

    def _stage_task(
        self, dag, si, stage, i, m, attempt, qid, boundaries, peers,
        secret, deadline, snap=None, topsql=None, adaptive=None,
        rf=None, probed_tags=(),
    ) -> dict:
        """The worker task spec for partition ``i`` of DAG stage
        ``si`` — run_task's single-stage spec plus the DAG fields
        (stage index, exchange kind, range boundaries, hold/release
        of the inter-stage held outputs). ``rf``/``probed_tags``
        attach a probed runtime filter exactly like the single-stage
        dispatch (the probe cached the build side under this stage's
        held key, so its producer is not re-executed)."""
        n = len(dag.stages)
        return {
            "sid": f"{self._sid_prefix}-q{qid}-s{si}", "qid": qid,
            "attempt": attempt, "m": m, "part": i, "peers": peers,
            "secret": secret, "coord": self._sid_prefix,
            "deadline_s": self._deadline_left(deadline),
            "stage": si, "n_stages": n,
            "exchange": stage.exchange,
            "adaptive": list(adaptive) if adaptive else None,
            "boundaries": list(boundaries or []),
            "hold_output": si < n - 1,
            "release_held": si == n - 1,
            "sides": [
                {
                    "tag": s.tag, "key": s.key, "mode": s.mode,
                    "probed": s.tag in (probed_tags or ()),
                    "rf": (
                        rf["filter"]
                        if rf is not None and s.tag == rf["tag"]
                        else None
                    ),
                    "plan": plan_to_ir(
                        self._rf_pushdown_plan(
                            s.host_plan(i, m), s.key, rf["filter"]
                        )
                        if rf is not None and s.tag == rf["tag"]
                        and s.tag not in (probed_tags or ())
                        else s.host_plan(i, m)
                    ),
                }
                for s in stage.sides
            ],
            "consumer": plan_to_ir(stage.consumer),
            "wait_timeout_s": self.shuffle_wait_timeout_s,
            "packet_rows": self.shuffle_packet_rows,
            "max_inflight_bytes": self.shuffle_inflight_bytes,
            "codec": "binary",  # DAG stages require the columnar wire
            "pipeline": self.shuffle_pipeline,
            "produce_chunks": self.shuffle_produce_chunks,
            "trace": bool(self.tracer.enabled),
            "timeline": TIMELINE.active(),
            "snap": snap,
            "topsql": topsql,
        }

    def _sample_stage(
        self, si, stage, hosts, m, attempt, qid, kill_check, deadline,
        suspects, errs, snap=None,
    ):
        """Boundary-sampling round of one range stage: every worker
        produces (and CACHES) its side, replies a deterministic key
        sample; the coordinator merges the quantile cut. Returns the
        boundary list, or None when a host failed (suspects/errs
        filled — the caller verifies and retries the whole DAG on the
        survivor set). A boundary-sample loss is exactly as retryable
        as a dispatch loss (shuffle/sample-lost)."""
        side = stage.sides[0]
        t0 = time.perf_counter()
        ts_entry = self._topsql_entry()  # statement thread: see helper
        samples: List[Optional[list]] = [None] * m
        fatal: List[Exception] = []
        cancelled: List[str] = []

        def run_one(i: int, ep: EngineEndpoint, conn: EngineClient):
            spec = {
                "qid": qid, "attempt": attempt, "m": m, "part": i,
                "coord": self._sid_prefix, "stage": si,
                "deadline_s": self._deadline_left(deadline),
                "sample_k": self.shuffle_sample_k,
                "sample_seed": self.shuffle_sample_seed,
                "side": {
                    "tag": side.tag, "key": side.key,
                    "plan": plan_to_ir(side.host_plan(i, m)),
                },
                "snap": snap,
                "topsql": ts_entry,
            }
            try:
                resp = conn.call(
                    {"v": IR_VERSION, "shuffle_sample": spec}
                )
            except (SchemaOutOfDateError, RuntimeError, ValueError,
                    PermissionError):
                raise
            except Exception as e:
                with self._lock:
                    suspects.append(ep.address)
                    errs.append(f"{ep.address}: {e}")
                return
            if not self._classify_reply(
                resp, suspects, errs, cancelled
            ):
                return
            samples[i] = list(resp.get("samples") or [])

        def runner(i, ep, conn):
            try:
                run_one(i, ep, conn)
            except Exception as e:
                fatal.append(e)

        killed = self._leased_rounds(
            hosts, runner, qid, sid=f"{self._sid_prefix}-q{qid}-s{si}",
            kill_check=kill_check, deadline=deadline,
            suspects=suspects, errs=errs,
        )
        _c_stage_sample_seconds().inc(time.perf_counter() - t0)
        if fatal:
            raise fatal[0]
        if killed is not None:
            raise killed
        if cancelled:
            from tidb_tpu.utils.sqlkiller import QueryKilled

            raise QueryKilled(cancelled[0])
        if any(s is None for s in samples):
            return None
        return self.merge_boundaries(
            [s for s in samples if s is not None], m
        )

    def _probe_stage(
        self, sp, hosts, m, attempt, qid, kill_check, deadline,
        suspects, errs, snap=None, stage_idx=0, rf_spec=None,
        rf_build_tags=(), gcol_by_tag=None, only_tags=None,
    ) -> Optional[Dict[int, dict]]:
        """AQE probe round of one hash stage (parallel/aqe.py): every
        worker produces-and-CACHES its sides (ShuffleWorker.run_probe
        — the range-sampling discipline, so the stage round re-reads
        the blocks instead of re-executing the producers) and replies
        exact per-partition row histograms + hottest keys — plus,
        when requested, a runtime filter over the side's key ints
        (``rf_spec`` fixes the bloom geometry coordinator-side so the
        per-host bitsets OR together) and a group-column NDV (the
        partial-agg-skip signal). ``only_tags`` restricts the probe
        to a side subset (an rf-only probe caches just the build side
        so the big probe side keeps its pipelined produce overlap).
        Returns the merged per-side view {tag: {"rows", "part_rows",
        "hot"[, "filters", "gndv"]}}, or None when a host failed
        (suspects filled — the caller verifies and retries on the
        survivor set)."""
        t0 = time.perf_counter()
        ts_entry = self._topsql_entry()  # statement thread: see helper
        replies: List[Optional[list]] = [None] * m
        fatal: List[Exception] = []
        cancelled: List[str] = []

        def run_one(i: int, ep: EngineEndpoint, conn: EngineClient):
            sides = []
            for s in sp.sides:
                if only_tags is not None and s.tag not in only_tags:
                    continue
                sd = {
                    "tag": s.tag, "key": s.key,
                    "plan": plan_to_ir(s.host_plan(i, m)),
                }
                if rf_spec is not None and s.tag in rf_build_tags:
                    sd["rf_build"] = True
                gc = (gcol_by_tag or {}).get(s.tag)
                if gc:
                    sd["gcol"] = gc
                sides.append(sd)
            spec = {
                "qid": qid, "attempt": attempt, "m": m, "part": i,
                "coord": self._sid_prefix, "stage": int(stage_idx),
                "deadline_s": self._deadline_left(deadline),
                "sides": sides,
                "rf": rf_spec,
                "snap": snap,
                "topsql": ts_entry,
            }
            try:
                resp = conn.call(
                    {"v": IR_VERSION, "shuffle_probe": spec}
                )
            except (SchemaOutOfDateError, RuntimeError, ValueError,
                    PermissionError):
                raise
            except Exception as e:
                with self._lock:
                    suspects.append(ep.address)
                    errs.append(f"{ep.address}: {e}")
                return
            if not self._classify_reply(
                resp, suspects, errs, cancelled
            ):
                return
            replies[i] = list(resp.get("sides") or [])

        def runner(i, ep, conn):
            try:
                run_one(i, ep, conn)
            except Exception as e:
                fatal.append(e)

        killed = self._leased_rounds(
            hosts, runner, qid,
            sid=f"{self._sid_prefix}-q{qid}-probe",
            kill_check=kill_check, deadline=deadline,
            suspects=suspects, errs=errs,
        )
        from tidb_tpu.parallel.aqe import _c_probe_seconds

        _c_probe_seconds().inc(time.perf_counter() - t0)
        if fatal:
            raise fatal[0]
        if killed is not None:
            raise killed
        if cancelled:
            from tidb_tpu.utils.sqlkiller import QueryKilled

            raise QueryKilled(cancelled[0])
        if any(r is None for r in replies):
            return None
        merged: Dict[int, dict] = {}
        for r in replies:
            for sd in r:
                tag = int(sd.get("tag", 0))
                ent = merged.setdefault(
                    tag, {"rows": 0, "part_rows": [0] * m, "hot": {}}
                )
                ent["rows"] += int(sd.get("rows", 0))
                for p, n in enumerate(sd.get("part_rows") or ()):
                    if p < m:
                        ent["part_rows"][p] += int(n)
                for kv in sd.get("hot") or ():
                    k, c = int(kv[0]), int(kv[1])
                    ent["hot"][k] = ent["hot"].get(k, 0) + c
                if "filter" in sd:
                    # per-host build-side filters: one entry per host
                    # (merge_runtime_filters ORs same-geometry blooms,
                    # unions in-lists; a malformed entry merges to
                    # None and the stage degrades to unfiltered)
                    ent.setdefault("filters", []).append(
                        sd.get("filter")
                    )
                if "gndv" in sd:
                    # summed per-host LOCAL group NDV: an upper bound
                    # on the global NDV — always CORRECT to act on
                    # (skipping the partial agg never changes results,
                    # it only trades producer CPU against wire bytes)
                    ent["gndv"] = (
                        ent.get("gndv", 0) + int(sd["gndv"])
                    )
        return merged

    #: which side may BUILD a runtime filter the other side tests,
    #: per join kind (build tag -> apply tag): dropping a filtered row
    #: is legal only on the NON-PRESERVED side of the equi-join —
    #: inner/semi filter either direction, left/anti only the right
    #: side (their left rows survive regardless of a match), and
    #: null-aware anti joins are excluded entirely (a dropped NULL /
    #: unmatched right row CHANGES the result there)
    _RF_LEGAL = {
        "inner": {0: 1, 1: 0},
        "left": {0: 1},
        "semi": {0: 1, 1: 0},
        "anti": {0: 1},
    }

    def _rf_candidate(self, sp):
        """(build_tag, apply_tag) for a runtime filter on this hash
        stage, or None when no legal direction exists: two hash-mode
        sides of a supported equi-join kind, building from the
        smaller-estimated legal side (the filter ships per host, so
        the cheap side pays the build)."""
        sides = {s.tag: s for s in sp.sides}
        if len(sides) != 2 or getattr(sp, "join_kind", None) is None:
            return None
        legal = self._RF_LEGAL.get(sp.join_kind or "")
        if not legal:
            return None
        if any(
            getattr(s, "mode", "hash") != "hash" for s in sp.sides
        ):
            return None
        b = min(
            legal, key=lambda t: int(sides[t].est_rows or 0)
        )
        return (b, legal[b])

    def _rf_predicted(self, kind, si, apply_tag, digest):
        """Predicted filter pass rate for this digest's stage/side
        from a PREVIOUS run's observed selectivity (_record_feedback
        stores per-mille kept/tested under ``rf:<kind>:<si>:<tag>``),
        or None when feedback is off / this digest never ran
        filtered."""
        if not (self.aqe_feedback and digest):
            return None
        from tidb_tpu.planner.cardinality import CARD_FEEDBACK

        obs = CARD_FEEDBACK.sides_for(digest) or {}
        v = obs.get(f"rf:{kind}:{si}:{apply_tag}")
        if v is None:
            return None
        return max(0.0, min(1.0, int(v) / 1000.0))

    def _rf_probe_worth(self, sp, cand, m, digest, kind="shuffle",
                        si=0):
        """Whether arming a PROBE round just for a runtime filter
        pays: 'always' forces it; 'auto' requires CARD_FEEDBACK
        evidence from a previous run of this digest that the filter
        won (predicted probe bytes saved clear the estimated filter
        build+ship cost) — without history the probe round itself is
        an unpriced RPC round, so auto stands down rather than tax
        every cold join (the PERF_NOTES PR 19 cost model)."""
        if self.runtime_filter == "always":
            return True
        sel = self._rf_predicted(kind, si, cand[1], digest)
        if sel is None:
            return False
        from tidb_tpu.parallel.wire import RF_MAX_BLOOM_BYTES

        sides = {s.tag: s for s in sp.sides}
        est_probe = int(sides[cand[1]].est_rows or 0)
        est_build = int(sides[cand[0]].est_rows or 0)
        nbytes = min(
            est_build * self.rf_bloom_bits // 8 + 64,
            RF_MAX_BLOOM_BYTES,
        )
        # ~32B/row shipped (a few int64 columns after encode) vs the
        # filter shipped to every host plus one probe RPC round
        return (1.0 - sel) * est_probe * 32.0 > 2.0 * nbytes * m

    def _rf_decide(self, used_sp, probe, m, stage, digest, cand,
                   kind="shuffle", si=0, count=True):
        """Merge the per-host build-side filters and decide emission
        (the declared 'runtime-filter' AQE decision): 'always' forces
        the merged filter onto the apply side; 'auto' costs filter
        ship bytes against predicted probe bytes saved (feedback-
        seeded selectivity when this digest ran before, build-NDV /
        probe-rows otherwise). A lost or corrupt per-host filter
        merges to None and DEGRADES to unfiltered shipping — never
        wrong results. Returns ({"tag", "filter"} or None, tokens);
        ``count=False`` rebuilds the token without re-moving the
        decision counter (DAG retry attempts re-probe to re-cache
        blocks under the new attempt key, but the decision already
        counted — the salting-token fencing discipline)."""
        from tidb_tpu.parallel import aqe
        from tidb_tpu.parallel.wire import (
            merge_runtime_filters,
            runtime_filter_nbytes,
        )

        build_tag, apply_tag = cand
        sides = {s.tag: s for s in used_sp.sides}
        ap = sides.get(apply_tag)
        if ap is None or getattr(ap, "mode", "hash") != "hash":
            # a broadcast-switched edge ships whole copies, not
            # partitions — nothing for a partition filter to drop
            return None, []
        ent = probe.get(build_tag) or {}
        filters = ent.get("filters") or []
        merged = (
            merge_runtime_filters(filters)
            if len(filters) == m else None
        )
        if merged is None:
            return None, []
        nbytes = runtime_filter_nbytes(merged)
        obs = probe.get(apply_tag) or {}
        probe_rows = int(
            obs.get("rows") or int(ap.est_rows or 0)
        )
        sel = self._rf_predicted(kind, si, apply_tag, digest)
        if sel is None:
            sel = min(
                1.0,
                int(merged.get("ndv", 0)) / max(probe_rows, 1),
            )
        if self.runtime_filter != "always":
            saved = (1.0 - sel) * probe_rows * 32.0
            if saved <= 2.0 * nbytes * m:
                return None, []
        detail = f"{merged['kind']}@t{apply_tag}"
        tok = (
            aqe.note_decision("runtime-filter", detail)
            if count else f"runtime-filter:{detail}"
        )
        stage["rf"] = {
            "kind": merged["kind"], "tag": apply_tag,
            "nbytes": int(nbytes),
            "ndv": int(merged.get("ndv", 0)),
            "sel_pred": round(float(sel), 3),
        }
        if merged.get("kind") == "bloom":
            stage["rf"]["bits"] = int(merged.get("bits", 0))
        return {"tag": apply_tag, "filter": merged}, [tok]

    @staticmethod
    def _rf_pushdown_plan(plan_node, key, rf):
        """Push the merged filter's MIN-MAX bounds below the exchange
        into the producer plan (a Selection over the Scan.frag
        slice): rows outside [lo, hi] — and NULL keys, which never
        match the legal apply side — are pruned by the engine's own
        predicate path before they are ever materialized for
        partition+encode. Bounds exist only for order-preserving key
        kinds (INT/BOOL, wire.build_runtime_filter), so a plain
        BETWEEN is exact; any failure falls back to the unwrapped
        plan (the worker-side filter still applies — this is an
        optimization, never a correctness step)."""
        if not isinstance(rf, dict) or "lo" not in rf or "hi" not in rf:
            return plan_node
        try:
            from tidb_tpu.expression.expr import (
                ColumnRef,
                Func,
                Literal,
                bind_expr,
            )
            from tidb_tpu.dtypes import INT64

            types = plan_node.schema.types()
            kt = types.get(key)
            if kt is None:
                return plan_node
            col = ColumnRef(type=kt, name=key)
            pred = Func(type=None, op="and", args=(
                Func(type=None, op="ge", args=(
                    col, Literal(type=INT64, value=int(rf["lo"])),
                )),
                Func(type=None, op="le", args=(
                    col, Literal(type=INT64, value=int(rf["hi"])),
                )),
            ))
            pred = bind_expr(pred, types)
            return L.Selection(plan_node.schema, plan_node, pred)
        except Exception:
            return plan_node

    def _aqe_decide(self, plan, sp, probe, m, salted_sp=None):
        """Turn one probe's merged observations into adaptive
        decisions (parallel/aqe.py). Returns (the ShufflePlan to
        execute, per-tag salt specs or None, decision tokens).
        ``salted_sp`` is the caller's precomputed salted group-by
        variant (_run_shuffle plans it once per statement and skips
        the probe entirely when it is None).

        Priority: a COLLAPSED side broadcast-switches first (zero
        big-side bytes beats any salting), then a partition over
        ``shuffle_skew_ratio`` x mean with identifiable hot keys
        salts — join stages split the hot side and replicate the
        other side's hot keys; group-by stages re-plan to the partial/
        final decomposition so the coordinator re-merges the salted
        partials."""
        from tidb_tpu.parallel import aqe
        from tidb_tpu.parallel.shuffle import mix_hash_np
        from tidb_tpu.planner.fragmenter import (
            choose_shuffle_modes,
            split_plan_shuffle_salted,
        )
        import numpy as np

        tokens: List[str] = []
        # (1) observed collapsed side -> broadcast-switch
        if (
            sp.kind == "join" and len(sp.sides) == 2
            and self.shuffle_broadcast_rows > 0
        ):
            prev = tuple(s.mode for s in sp.sides)
            for s in sp.sides:
                obs = probe.get(s.tag)
                if obs is not None:
                    s.est_rows = int(obs["rows"])
            shape = choose_shuffle_modes(
                sp, self.shuffle_broadcast_rows
            )
            if shape == "broadcast":
                if tuple(s.mode for s in sp.sides) != prev:
                    inject("aqe/replan")
                    tokens.append(
                        aqe.note_decision("broadcast-switch")
                    )
                return sp, None, tokens
        # (2) hot partition -> salting
        if self.shuffle_skew_ratio <= 1.0 or m <= 1:
            return sp, None, tokens
        part_tot = [
            sum(probe[t]["part_rows"][p] for t in probe)
            for p in range(m)
        ]
        total = sum(part_tot)
        mean = total / m if m else 0.0
        if mean <= 0:
            return sp, None, tokens
        hot_p = max(range(m), key=lambda p: part_tot[p])
        if part_tot[hot_p] < self.shuffle_skew_ratio * mean:
            return sp, None, tokens
        # flag the hot keys HOMED on the hot partition with meaningful
        # mass (a partition hot from many distinct keys has no key to
        # salt — splitting by key would not move it)
        counts: Dict[int, int] = {}
        for t in probe:
            for k, c in probe[t]["hot"].items():
                counts[k] = counts.get(k, 0) + c
        flagged = [
            k for k, c in counts.items()
            if c >= 0.5 * mean
            and int(
                mix_hash_np(np.asarray([k], dtype=np.int64))[0]
                % np.int64(m)
            ) == hot_p
        ]
        if not flagged:
            return sp, None, tokens
        k_salt = max(min(self.shuffle_skew_salt_k, m), 2)
        base_salt = {"keys": sorted(flagged), "k": k_salt}
        if sp.kind == "join" and len(sp.sides) == 2:
            # the side carrying the hot mass SPLITS; the other side
            # REPLICATES its hot-key rows to the salted lanes
            mass = {
                s.tag: sum(
                    probe.get(s.tag, {}).get("hot", {}).get(k, 0)
                    for k in flagged
                )
                for s in sp.sides
            }
            split_tag = max(mass, key=lambda t: mass[t])
            if sp.join_kind != "inner" and split_tag != 0:
                # left/semi/anti preserve the LEFT side: replicating
                # it would duplicate preserved rows — skip salting
                return sp, None, tokens
            salts = {
                s.tag: dict(
                    base_salt,
                    role="split" if s.tag == split_tag
                    else "replicate",
                )
                for s in sp.sides
            }
            inject("aqe/replan")
            tokens.append(aqe.note_decision("salted", str(k_salt)))
            return sp, salts, tokens
        if sp.kind == "groupby" and plan is not None:
            # a salted hot group SPLITS across K partitions, so the
            # consumer must produce PARTIAL aggregates and the
            # coordinator re-merges them — the salted plan variant
            # (None when the aggregate does not decompose: skip)
            sp2 = (
                salted_sp if salted_sp is not None
                else split_plan_shuffle_salted(plan, self.catalog)
            )
            if sp2 is None:
                return sp, None, tokens
            salts = {0: dict(base_salt, role="split")}
            inject("aqe/replan")
            tokens.append(aqe.note_decision("salted", str(k_salt)))
            return sp2, salts, tokens
        return sp, None, tokens

    def _leased_rounds(
        self, hosts, runner, qid, sid=None, kill_check=None,
        deadline=None, suspects=None, errs=None,
    ):
        """Lease one control connection per host UP FRONT in fixed
        endpoint order (the cycle-free acquisition discipline of
        _run_shuffle), run ``runner(i, ep, conn)`` per host on named
        threads, and join under the kill/deadline watch. Returns the
        kill exception (to raise after cleanup) or None; a failed
        checkout lands in suspects/errs for the caller's retry loop."""
        leases: List[Tuple[EngineEndpoint, EngineClient]] = []
        killed = None
        try:
            try:
                for ep in hosts:
                    leases.append((ep, self._pool(ep).checkout()))
            except Exception as e:
                bad = hosts[len(leases)]
                with self._lock:
                    if suspects is not None:
                        suspects.append(bad.address)
                    if errs is not None:
                        errs.append(f"{bad.address}: {e}")
            else:
                threads = [
                    threading.Thread(
                        target=runner, args=(i, ep, conn),
                        daemon=True, name=f"dcn-q{qid}-f{i}",
                    )
                    for i, (ep, conn) in enumerate(leases)
                ]
                for t in threads:
                    t.start()
                killed = self._join_watch(
                    threads, qid, sid=sid,
                    kill_check=kill_check, deadline=deadline,
                )
        finally:
            for ep, conn in leases:
                self._pool(ep).checkin(conn)
        return killed

    @staticmethod
    def _fold_stage(stage: dict, infos: List[dict]) -> None:
        """Accumulate the fenced per-partition worker stats into one
        stage summary (the _run_shuffle fold, shared by the DAG).
        Also derives the AQE observability fields: per-side produced
        rows (the feedback actuals), the per-partition received-row
        list and its max/mean skew ratio (the ``skew=`` EXPLAIN
        field + tidbtpu_shuffle_partition_rows histogram — auditable
        even when no salting triggered)."""
        part_recv: Dict[int, int] = {}
        for f in infos:
            stage["bytes_tunneled"] += f["pushed_bytes"]
            stage["rows_tunneled"] += f["pushed_rows"]
            stage["local_rows"] += f["local_rows"]
            stage["stalls"] += f["stalls"]
            stage["stall_s"] += f.get("stall_s", 0.0)
            stage["retransmits"] += f["retransmits"]
            stage["encode_s"] += f.get("encode_s", 0.0)
            stage["produce_s"] += f.get("produce_s", 0.0)
            stage["wait_s"] += f.get("wait_s", 0.0)
            stage["stage_s"] += f.get("stage_s", 0.0)
            stage["wait_idle_s"] += f.get("wait_idle_s", 0.0)
            stage["exec_s"] += f.get("exec_s", 0.0)
            stage["scan_rows"] += int(f.get("scan_rows", 0))
            stage["ttff_s"] = max(
                stage["ttff_s"], f.get("ttff_s", 0.0)
            )
            for t, v in (f.get("side_rows") or {}).items():
                sr = stage.setdefault("side_rows", {})
                sr[str(t)] = sr.get(str(t), 0) + int(v)
            part_recv[int(f["fid"])] = int(f.get("recv_rows", 0))
            if f.get("salted"):
                stage["salted"] = max(
                    int(stage.get("salted", 0)), int(f["salted"])
                )
        pr = [part_recv[k] for k in sorted(part_recv)]
        stage["part_rows"] = pr
        if pr and sum(pr) > 0:
            mean = sum(pr) / len(pr)
            stage["skew"] = round(max(pr) / mean, 2)
            for v in pr:
                _h_partition_rows().observe(float(v))
        # runtime-filter observability (PR 19): observed selectivity =
        # kept/tested probe-side rows, folded fleet-wide; rf_lost
        # counts filter-lost degrades (the chaos site) — renders as
        # rf= ... sel_obs= on the EXPLAIN ANALYZE DCNShuffle row
        rin = sum(int(f.get("rf_rows_in", 0)) for f in infos)
        rdrop = sum(int(f.get("rf_dropped", 0)) for f in infos)
        rlost = sum(int(f.get("rf_lost", 0)) for f in infos)
        if rin or rlost:
            rf = stage.setdefault("rf", {})
            rf["rows_in"] = rin
            rf["dropped"] = rdrop
            if rlost:
                rf["lost"] = rlost
            if rin:
                sel = 1.0 - rdrop / rin
                rf["sel_obs"] = round(sel, 3)
                _h_filter_selectivity().observe(sel)

    def _stage_replan(self, stg, prev_infos) -> List[str]:
        """AQE stage-boundary re-planning (parallel/aqe.py): before
        dispatching a downstream DAG stage, compare the OBSERVED held
        rows of its StageInput sides (already attempt-fenced
        worker-side inputs) against the planner estimate; when a side
        collapsed below ``shuffle_broadcast_rows`` or diverged past
        ``tidb_tpu_aqe_replan_ratio``, re-run choose_edge_modes with
        the observed counts — the switched stage re-plans only this
        downstream edge (held outputs stay where they are; a
        broadcast StageInput side ships each worker's held partition
        to every peer, which IS the full side). Returns the decision
        tokens. A taken decision PERSISTS on the stage across retry
        attempts: the flip mutates the DagStage's side modes in
        place, so a retried attempt re-derives identical modes and
        takes no NEW decision — the stashed token still renders on
        the rebuilt stage summary (adaptive= must agree with the
        modes the workers actually ran; the counter moves once)."""
        persisted = list(getattr(stg, "_aqe_tokens", None) or [])
        if (
            stg.exchange != "hash" or stg.join_kind is None
            or stg.requires_key_partition or len(stg.sides) != 2
            or self.shuffle_broadcast_rows <= 0
        ):
            return persisted
        from tidb_tpu.planner.fragmenter import choose_edge_modes

        updated = False
        for s in stg.sides:
            if not isinstance(s.template, L.StageInput):
                continue
            observed = sum(
                int(f.get("held_rows", 0)) for f in prev_infos
                if int(f.get("stage", -1)) == int(s.template.stage)
            )
            est0 = int(s.est_rows)
            div = (
                max(observed, 1) / max(est0, 1)
                if est0 > 0 else float("inf")
            )
            if (
                observed <= self.shuffle_broadcast_rows
                or div >= self.aqe_replan_ratio
                or div <= 1.0 / self.aqe_replan_ratio
            ):
                s.est_rows = int(observed)
                updated = True
        if not updated:
            return persisted
        prev = tuple(s.mode for s in stg.sides)
        choose_edge_modes(stg, self.shuffle_broadcast_rows)
        if tuple(s.mode for s in stg.sides) == prev:
            return persisted
        inject("aqe/replan")
        from tidb_tpu.parallel import aqe

        stg._aqe_tokens = persisted + [
            aqe.note_decision("broadcast-switch")
        ]
        return list(stg._aqe_tokens)

    def _run_dag(
        self, dag: ShuffleDAG, kill_check=None, deadline=None,
        snap=None, digest=None,
    ) -> Tuple[List[List[tuple]], List[dict], List[dict]]:
        """Run a shuffle DAG to completion: stages execute in topo
        order, each dispatched to every alive host over the
        per-attempt FragmentLedger; range stages run a boundary-
        sampling round first. Stage N's consumer output is HELD on
        its worker as stage N+1's StageInput — a failure anywhere
        restarts the WHOLE chain on the survivor set under a new
        attempt (held outputs of the superseded attempt are fenced by
        the attempt key exactly like stale frames). Deadline and
        cancel propagate through every stage dispatch. Returns
        (last-stage rows per partition, fenced per-partition infos of
        every stage, per-stage summaries)."""
        qid = _QUERY_ID.next()
        ts_entry = self._topsql_entry()  # statement thread: see helper
        n = len(dag.stages)
        if n > 1:
            _c_stage_chained().inc()
        stage_summaries: List[dict] = []
        all_infos: List[dict] = []
        last_err: Optional[str] = None
        try:
            for rnd in range(self.max_attempts):
                if rnd:
                    self._retry_sleep(rnd - 1, kill_check)
                if not self.alive_endpoints():
                    self.prober.probe_once()
                hosts = self.alive_endpoints()
                if not hosts:
                    break
                m = len(hosts)
                attempt = rnd + 1
                peers = [[ep.host, ep.port] for ep in hosts]
                stage_summaries = []
                all_infos = []
                suspects: List[str] = []
                errs: List[str] = []
                parts_rows: Optional[List[List[tuple]]] = None
                for si, stg in enumerate(dag.stages):
                    # AQE: the feedback marker rides stage 0; between
                    # stages, observed held rows may flip the next
                    # edge to broadcast (stage-boundary re-planning)
                    stage_tokens = (
                        list(getattr(dag, "_aqe_tokens", None) or [])
                        if si == 0 else []
                    )
                    if si:
                        stage_tokens += self._stage_replan(
                            stg, all_infos
                        )
                    boundaries = None
                    if stg.exchange == "range":
                        boundaries = self._sample_stage(
                            si, stg, hosts, m, attempt, qid,
                            kill_check, deadline, suspects, errs,
                            snap=snap,
                        )
                        if boundaries is None:
                            break  # suspects filled: verify + retry
                    sid = f"{self._sid_prefix}-q{qid}-s{si}"
                    stage = {
                        "sid": sid, "qid": qid, "kind": "dag",
                        "stage": si, "n_stages": n,
                        "exchange": stg.exchange,
                        # merged quantile boundaries of a range stage
                        # (None for hash): deterministic under the
                        # fixed sample seed — tests assert equality
                        # across runs and retries
                        "boundaries": (
                            list(boundaries)
                            if boundaries is not None else None
                        ),
                        "modes": [s.mode for s in stg.sides],
                        "adaptive": list(stage_tokens),
                        "attempts": attempt, "m": m,
                        "bytes_tunneled": 0, "rows_tunneled": 0,
                        "local_rows": 0, "stalls": 0, "stall_s": 0.0,
                        "retransmits": 0, "codec": "binary",
                        "encode_s": 0.0, "produce_s": 0.0,
                        "wait_s": 0.0, "stage_s": 0.0,
                        "scan_rows": 0,
                        "pipeline": self.shuffle_pipeline,
                        "wait_idle_s": 0.0, "ttff_s": 0.0,
                        "exec_s": 0.0,
                    }
                    # runtime filter on a DAG hash-join stage (PR 19):
                    # probe-and-cache the legal build side, merge the
                    # per-host filters, attach to the apply side. The
                    # DECISION persists on the DagStage across retry
                    # attempts (the _stage_replan token pattern: the
                    # counter moves once) while the probe re-runs per
                    # attempt — held blocks are attempt-fenced, and
                    # deterministic data rebuilds the identical filter.
                    rf_dec = None
                    rf_ptags = ()
                    rf_cand = None
                    if (
                        self.runtime_filter != "off"
                        and stg.exchange == "hash"
                        and m > 1
                        and all(
                            s.frag_scan is not None
                            for s in stg.sides
                        )
                    ):
                        rf_cand = self._rf_candidate(stg)
                    if rf_cand is not None and self._rf_probe_worth(
                        stg, rf_cand, m, digest, kind="dag", si=si
                    ):
                        from tidb_tpu.parallel.wire import (
                            bloom_geometry,
                        )

                        est_b = int(
                            next(
                                s for s in stg.sides
                                if s.tag == rf_cand[0]
                            ).est_rows or 0
                        )
                        nbits, kh = bloom_geometry(
                            max(est_b, 1), self.rf_bloom_bits
                        )
                        probe = self._probe_stage(
                            stg, hosts, m, attempt, qid, kill_check,
                            deadline, suspects, errs, snap=snap,
                            stage_idx=si,
                            rf_spec={
                                "bits": int(nbits), "k": int(kh),
                                "inlist_ndv": int(self.rf_inlist_ndv),
                            },
                            rf_build_tags=(rf_cand[0],),
                            only_tags={rf_cand[0]},
                        )
                        if probe is None:
                            break  # suspects filled: verify + retry
                        persisted_rf = getattr(
                            stg, "_rf_tokens", None
                        )
                        rf_dec, rtoks = self._rf_decide(
                            stg, probe, m, stage, digest, rf_cand,
                            kind="dag", si=si,
                            count=persisted_rf is None,
                        )
                        if rf_dec is not None:
                            rf_ptags = (rf_cand[0],)
                            if persisted_rf is None:
                                stg._rf_tokens = list(rtoks)
                            stage_tokens = (
                                list(stage_tokens)
                                + list(stg._rf_tokens)
                            )
                            stage["adaptive"] = list(stage_tokens)
                        else:
                            # the merge degraded (or auto stood
                            # down): the build side is still cached —
                            # dispatch it as probed so the stage
                            # round reads the held block
                            rf_ptags = (rf_cand[0],)
                            stage.pop("rf", None)
                    inject("shuffle/stage")
                    _c_shuffle_stages().inc()
                    _c_stage_exchanges().labels(
                        exchange=(
                            "broadcast"
                            if any(
                                s.mode == "broadcast"
                                for s in stg.sides
                            )
                            else stg.exchange
                        )
                    ).inc()
                    if rnd:
                        inject("shuffle/stage-retry")
                        _c_shuffle_stage_retries().inc()
                    ledger = FragmentLedger(m)
                    infos: List[dict] = []
                    fatal: List[Exception] = []
                    cancelled: List[str] = []

                    def run_part(i, ep, conn, _si=si, _stg=stg,
                                 _bnd=boundaries, _ledger=ledger,
                                 _infos=infos, _cancelled=cancelled,
                                 _adaptive=tuple(stage_tokens),
                                 _rf=rf_dec, _ptags=rf_ptags):
                        token = _ledger.claim(i, ep.address)
                        task = self._stage_task(
                            dag, _si, _stg, i, m, attempt, qid,
                            _bnd, peers, ep.secret, deadline,
                            snap=snap, topsql=ts_entry,
                            adaptive=_adaptive,
                            rf=_rf, probed_tags=_ptags,
                        )
                        t_d0 = time.time()
                        try:
                            resp = conn.call(
                                {"v": IR_VERSION, "shuffle_task": task}
                            )
                        except (SchemaOutOfDateError, RuntimeError,
                                ValueError, PermissionError):
                            raise
                        except Exception as e:
                            _ledger.release(i, token)
                            with self._lock:
                                suspects.append(ep.address)
                                errs.append(f"{ep.address}: {e}")
                            return
                        if not self._classify_reply(
                            resp, suspects, errs, _cancelled,
                            release=lambda: _ledger.release(i, token),
                        ):
                            return
                        rows = [tuple(r) for r in resp["rows"]]
                        if _ledger.complete(i, token, rows):
                            self._note_partition(
                                _infos, i, ep, attempt, resp,
                                qid=qid, t_dispatch0=t_d0,
                            )

                    def runner(i, ep, conn, _run=run_part,
                               _fatal=fatal):
                        try:
                            _run(i, ep, conn)
                        except Exception as e:
                            _fatal.append(e)

                    killed = self._leased_rounds(
                        hosts, runner, qid, sid=sid,
                        kill_check=kill_check, deadline=deadline,
                        suspects=suspects, errs=errs,
                    )
                    if fatal:
                        raise fatal[0]
                    if killed is not None:
                        raise killed
                    if cancelled:
                        from tidb_tpu.utils.sqlkiller import QueryKilled

                        raise QueryKilled(cancelled[0])
                    if not ledger.all_done():
                        break  # suspects filled: verify + retry
                    infos.sort(key=lambda f: f["fid"])
                    self._fold_stage(stage, infos)
                    stage_summaries.append(stage)
                    all_infos.extend(infos)
                    if si == n - 1:
                        parts_rows = ledger.rows_by_fragment()
                if parts_rows is not None:
                    self._record_feedback(digest, stage_summaries, "dag")
                    lq = {
                        "qid": qid, "fragments": all_infos,
                        "shuffle": self._dag_shuffle_summary(
                            stage_summaries
                        ),
                        "shuffle_stages": stage_summaries,
                        "worker_mem_peak": self._worker_mem_peak(
                            all_infos
                        ),
                    }
                    with self._lock:
                        self.last_query = lq
                    self._tls.last = lq
                    _update_host_gauges(self.endpoints)
                    return parts_rows, all_infos, stage_summaries
                if errs:
                    last_err = errs[0]
                self._verify_suspects(suspects)
        except BaseException:
            # the DAG died mid-chain (kill, fatal engine error): free
            # the workers' held stage outputs now — a best-effort
            # broadcast; unreachable hosts fall back to the bounded
            # held-cap eviction
            self._cancel_fleet(qid, reason="shuffle DAG aborted")
            raise
        self._cancel_fleet(qid, reason="shuffle DAG undispatchable")
        raise ConnectionError(
            f"shuffle DAG q{qid} undispatchable after "
            f"{self.max_attempts} attempts ({len(self.endpoints)} "
            f"hosts, {len(self.alive_endpoints())} alive); "
            f"last error: {last_err}"
        )

    @staticmethod
    def _dag_shuffle_summary(stage_summaries: List[dict]) -> dict:
        """One roll-up of a DAG's stages in the single-stage summary
        shape (statements_summary / slow-log / status consumers read
        ``last_query["shuffle"]`` — additive fields sum, ttff takes
        the max, attempts the max)."""
        out = {
            "kind": "dag", "codec": "binary",
            "n_stages": len(stage_summaries),
            "attempts": 0, "m": 0,
            "bytes_tunneled": 0, "rows_tunneled": 0, "local_rows": 0,
            "stalls": 0, "stall_s": 0.0, "retransmits": 0,
            "encode_s": 0.0, "produce_s": 0.0, "wait_s": 0.0,
            "stage_s": 0.0, "wait_idle_s": 0.0, "ttff_s": 0.0,
            "exec_s": 0.0, "scan_rows": 0, "pipeline": False,
        }
        for s in stage_summaries:
            out["attempts"] = max(out["attempts"], s.get("attempts", 1))
            out["m"] = max(out["m"], s.get("m", 0))
            out["pipeline"] = bool(s.get("pipeline"))
            for k in (
                "bytes_tunneled", "rows_tunneled", "local_rows",
                "stalls", "retransmits", "scan_rows",
            ):
                out[k] += int(s.get(k, 0))
            for k in (
                "stall_s", "encode_s", "produce_s", "wait_s",
                "stage_s", "wait_idle_s", "exec_s",
            ):
                out[k] += float(s.get(k, 0.0))
            out["ttff_s"] = max(out["ttff_s"], s.get("ttff_s", 0.0))
            # AQE roll-up: the union of taken decisions plus the
            # worst per-stage skew ratio (statements_summary / slow-
            # log consumers read this summary shape)
            for tok in s.get("adaptive") or ():
                out.setdefault("adaptive", [])
                if tok not in out["adaptive"]:
                    out["adaptive"].append(tok)
            if s.get("skew"):
                out["skew"] = max(
                    float(out.get("skew", 0.0)), float(s["skew"])
                )
            if s.get("rf"):
                # a filtered stage's rf= renders on the roll-up too
                # (one filtered join per chain in practice)
                out["rf"] = dict(s["rf"])
        return out

    def _concat_merge(self, dag: ShuffleDAG, parts_rows):
        """Order-preserving final merge of a range-exchange DAG: the
        partitions are each sorted and partition ranges are disjoint,
        so the coordinator CONCATENATES them in partition order
        (reversed for a descending first key — NULLs land first ASC /
        last DESC, matching the engine's sort), slices the global
        LIMIT/OFFSET, and runs only the row-wise nodes above the
        limit. No global re-sort."""
        with self._final_merge_phase():
            spec = dag.merge
            seq = (
                list(reversed(parts_rows))
                if spec.get("reverse") else parts_rows
            )
            rows = [r for part in seq for r in part]
            lim = spec.get("limit")
            if lim is not None:
                count, off = lim
                rows = rows[off: off + count]
            above = spec.get("above") or ()
            if above:
                inject("dcn/final-stage")
                from tidb_tpu.chunk import materialize_rows
                from tidb_tpu.parallel.shuffle import (
                    stage_rows_as_batch,
                )

                plan: L.LogicalPlan = stage_rows_as_batch(
                    dag.partial_schema, rows, _STAGED_NONCE.next(),
                    key="dcn-final",
                )
                for node in reversed(above):
                    plan = dataclasses.replace(node, child=plan)
                out, dicts = self._executor.run(plan)
                rows = materialize_rows(out, list(plan.schema), dicts)
                cols = [c.name for c in plan.schema]
            else:
                cols = list(spec.get("columns") or [])
            return cols, rows

    def _note_partition(
        self, infos, part, ep, attempt, resp, qid=None,
        t_dispatch0=None,
    ) -> None:
        """Record one FENCED per-partition shuffle result: counters,
        telemetry, shipped worker registry deltas, the host-labeled
        span merge, and the piggybacked worker timeline events (rebased
        through the handshake clock offset — behind the ledger fence,
        so a retried stage's events land once)."""
        stats = resp.get("stats") or {}
        sh = resp.get("shuffle") or {}
        spans = resp.get("spans") or []
        host = stats.get("host") or ep.address
        exec_s = float(stats.get("exec_s", 0.0))
        nbytes = int(resp.get("_nbytes", 0))
        _c_shuffle_result_bytes().inc(nbytes)
        _h_fragment_seconds().observe(exec_s)
        merge_counter_delta(resp.get("registry"))
        self._merge_tsdb(resp, ep)
        self._merge_topsql(resp, ep)
        self._note_timeline(
            resp, ep, qid=qid, unit=f"p{part}", attempt=attempt,
            t_dispatch0=t_dispatch0,
        )
        info = {
            "fid": part, "host": host, "attempt": attempt,
            "rows": int(stats.get("rows", 0)), "exec_s": exec_s,
            "bytes": nbytes,
            # worker-eyed engine accounting (reply stats): the
            # admission estimate's fleet half + per-fragment compile
            # cost for distributed EXPLAIN ANALYZE
            "mem_peak": int(stats.get("mem_peak_bytes", 0) or 0),
            "compile": stats.get("compile"),
            "pushed_bytes": int(sh.get("pushed_bytes", 0)),
            "pushed_rows": int(sh.get("pushed_rows", 0)),
            "local_rows": int(sh.get("local_rows", 0)),
            "stalls": int(sh.get("stalls", 0)),
            "stall_s": float(sh.get("stall_s", 0.0)),
            "retransmits": int(sh.get("retransmits", 0)),
            "codec": sh.get("codec"),
            "encode_s": float(sh.get("encode_s", 0.0)),
            "produce_s": float(sh.get("produce_s", 0.0)),
            "wait_s": float(sh.get("wait_s", 0.0)),
            "stage_s": float(sh.get("stage_s", 0.0)),
            "pipeline": bool(sh.get("pipeline", False)),
            "wait_idle_s": float(sh.get("wait_idle_s", 0.0)),
            "ttff_s": float(sh.get("ttff_s", 0.0)),
            # shuffle-DAG accounting: stage index/chain length,
            # exchange kind, base-table rows actually scanned (the
            # no-unsliced-re-scan proof), rows held for the next stage
            "stage": int(sh.get("stage", 0)),
            "n_stages": int(sh.get("n_stages", 1)),
            "exchange": sh.get("exchange", "hash"),
            "scan_rows": int(sh.get("scan_rows", 0)),
            "held_rows": int(sh.get("held_rows", 0)),
            "produced_rows": int(sh.get("produced_rows", 0)),
            # AQE accounting: per-side produced rows (feedback
            # actuals), rows this partition received (skew ratio),
            # and the salt fan-out when the stage ran salted
            "side_rows": {
                str(k): int(v)
                for k, v in (sh.get("side_rows") or {}).items()
            },
            "recv_rows": int(sh.get("recv_rows", 0)),
            "salted": int(sh.get("salted", 0)),
            # runtime-filter accounting (PR 19): probe-side rows
            # tested / dropped by the shipped build-side filter, and
            # filter-lost degrades (the chaos site's unfiltered
            # fallback) — folds into the stage rf= observability
            "rf_rows_in": int(sh.get("rf_rows_in", 0)),
            "rf_dropped": int(sh.get("rf_dropped", 0)),
            "rf_lost": int(sh.get("rf_lost", 0)),
            "spans": spans,
        }
        with self._lock:
            infos.append(info)
        # per-peer tunnel health merges once per FENCED reply — the
        # exactly-once ledger means a retried stage's links count once
        for pp in sh.get("per_peer") or ():
            try:
                LINKS.note_tunnel(ep.address, str(pp.get("dst")), pp)
            except Exception:
                pass  # malformed per_peer from a skewed worker
        self._merge_remote_spans(
            spans, host, addr=ep.address, trace_t0=resp.get("trace_t0")
        )

    @staticmethod
    def _topsql_entry():
        """The Top SQL entry every dispatch carries (None while the
        profiler is off — a worker receiving None stops its sampler):
        the fleet config plus THIS statement's digest, so worker-side
        samples attribute to the same digest the coordinator uses.
        Must be computed on the STATEMENT thread (the digest comes
        from its registered flight context), then closed over by the
        dispatch runner threads."""
        from tidb_tpu.obs.profiler import TOPSQL, current_digest

        cfg = TOPSQL.dispatch_config()
        if cfg is None:
            return None
        cfg = dict(cfg)
        cfg["digest"] = current_digest()
        return cfg

    def _merge_topsql(self, resp, ep) -> None:
        """Fold one FENCED reply's piggybacked Top SQL payload
        (per-digest aggregates + collapsed stacks) into the
        coordinator store under this worker's instance label — the
        _merge_tsdb contract: behind the exactly-once ledger fence,
        and telemetry never fails the query."""
        payload = resp.get("topsql")
        if not payload:
            return
        from tidb_tpu.obs.profiler import TOPSQL

        try:
            TOPSQL.store.merge_remote(payload, instance=ep.address)
        except Exception:
            pass

    def _merge_tsdb(self, resp, ep) -> None:
        """Fold one FENCED reply's piggybacked worker metric samples
        into the coordinator time-series store (obs/tsdb.py), rebased
        through this host's handshake clock offset. Behind the
        exactly-once ledger fence like the counter deltas, so a
        retried stage's sample batch lands at most once."""
        rows = resp.get("tsdb")
        if not rows:
            return
        from tidb_tpu.obs.tsdb import TSDB

        try:
            TSDB.merge_remote(
                rows, host=ep.address,
                offset_s=self._clock_offsets.get(ep.address),
            )
        except Exception:
            pass  # telemetry must never fail the query

    def _note_timeline(
        self, resp, ep, qid=None, unit="", attempt=1, t_dispatch0=None,
    ) -> None:
        """Fleet timeline merge for one FENCED reply: the coordinator
        dispatch window (an event the cross-host monotonicity check
        anchors on — worker events must not start before it) plus the
        worker's piggybacked events, rebased through this host's
        handshake-sampled clock offset."""
        if not TIMELINE.active():
            return
        if t_dispatch0 is not None:
            TIMELINE.emit_event(
                "fragment", f"dispatch q{qid}/{unit}", t_dispatch0,
                max(time.time() - t_dispatch0, 0.0),
                track=f"q{qid}",
                args={
                    "qid": qid, "unit": unit, "host": ep.address,
                    "attempt": attempt,
                },
            )
        TIMELINE.merge_remote(
            resp.get("events"), ep.address,
            self._clock_offsets.get(ep.address),
        )

    def _run_fragments(
        self, frag: FragmentPlan, kill_check=None, deadline=None,
        snap=None,
    ) -> Tuple[FragmentLedger, List[dict]]:
        """Dispatch every fragment exactly once onto the alive hosts,
        surviving losses up to max_attempts rounds. Returns the
        completed ledger plus per-fragment telemetry (host, attempt,
        rows, exec_s, bytes, spans) — only FENCED deliveries contribute,
        so a retried fragment's stats and spans appear exactly once."""
        qid = _QUERY_ID.next()
        # computed on the statement thread (the digest lives in ITS
        # flight context), closed over by the dispatch runners
        ts_entry = self._topsql_entry()
        n = max(len(self.alive_endpoints()), 1)
        ledger = FragmentLedger(n)
        infos: List[dict] = []
        last_err: Optional[Exception] = None
        cancelled: List[str] = []
        for _round in range(self.max_attempts):
            pending = ledger.pending()
            if not pending:
                break
            if _round:
                self._retry_sleep(_round - 1, kill_check)
            # quarantined hosts get their recovery shot before the pool
            # is declared exhausted (probe respects backoff)
            if not self.alive_endpoints():
                self.prober.probe_once()
                if not self.alive_endpoints():
                    break
            # assign each pending fragment a host; distinct hosts first,
            # wrap when fragments outnumber survivors
            assignments = []
            taken: List[EngineEndpoint] = []
            for fid in pending:
                ep = self._next_alive(exclude=taken)
                if ep is None:
                    break
                taken.append(ep)
                assignments.append((fid, ep))
            errs: List[Tuple[EngineEndpoint, Exception]] = []

            def run_one(fid: int, ep: EngineEndpoint):
                token = ledger.claim(fid, ep.address)
                if ledger.attempts(fid) > 1:
                    inject("dcn/redispatch")
                    _c_retries().inc()
                meta = {
                    "qid": qid, "fid": fid, "n": n,
                    "attempt": ledger.attempts(fid),
                    # cancellation scope (coordinator instance, qid)
                    "coord": self._sid_prefix,
                    # propagated statement deadline (remaining seconds)
                    "deadline_s": self._deadline_left(deadline),
                    # opt the worker into span collection only when the
                    # coordinator is actually tracing; same opt-in for
                    # timeline event collection
                    "trace": bool(self.tracer.enabled),
                    "timeline": TIMELINE.active(),
                    # Top SQL: profiler config + this statement's
                    # digest for worker-side sample attribution
                    "topsql": ts_entry,
                }
                t_d0 = time.time()
                try:
                    _cols, rows, resp = self._dispatch(
                        ep, frag.host_plan(fid, n), meta, snap=snap
                    )
                except QueryCancelled as e:
                    # deliberate worker-side abort: neither an engine
                    # error (no fatal raise) nor a transport loss (no
                    # quarantine) — before the RuntimeError catch, of
                    # which QueryCancelled is a subclass
                    ledger.release(fid, token)
                    cancelled.append(str(e))
                    return
                except (SchemaOutOfDateError, RuntimeError, ValueError,
                        PermissionError):
                    raise  # deterministic: re-raise to the caller thread
                except Exception as e:  # transport: quarantine + retry
                    ledger.release(fid, token)
                    errs.append((ep, e))
                    return
                if ledger.complete(fid, token, rows):
                    self._note_fragment(
                        infos, fid, ep, meta, resp, t_dispatch0=t_d0
                    )

            fatal: List[Exception] = []

            def runner(fid, ep):
                try:
                    run_one(fid, ep)
                except Exception as e:
                    fatal.append(e)

            threads = [
                threading.Thread(
                    target=runner, args=(fid, ep), daemon=True,
                    name=f"dcn-q{qid}-f{fid}",
                )
                for fid, ep in assignments
            ]
            for t in threads:
                t.start()
            killed = self._join_watch(
                threads, qid, kill_check=kill_check, deadline=deadline
            )
            if fatal:
                raise fatal[0]
            if killed is not None:
                raise killed
            if cancelled:
                from tidb_tpu.utils.sqlkiller import QueryKilled

                raise QueryKilled(cancelled[0])
            for ep, e in errs:
                last_err = e
                self._quarantine(ep)
        if not ledger.all_done():
            raise ConnectionError(
                f"fragments {ledger.pending()} undispatchable after "
                f"{self.max_attempts} rounds "
                f"({len(self.endpoints)} hosts, "
                f"{len(self.alive_endpoints())} alive); last error: "
                f"{last_err}"
            )
        infos.sort(key=lambda f: f["fid"])
        lq = {
            "qid": qid, "fragments": infos,
            "worker_mem_peak": self._worker_mem_peak(infos),
        }
        with self._lock:
            self.last_query = lq
        self._tls.last = lq
        _update_host_gauges(self.endpoints)
        return ledger, infos

    def _note_fragment(
        self, infos, fid, ep, meta, resp, t_dispatch0=None
    ) -> None:
        """Record one FENCED fragment delivery: counters, the per-query
        info list, the host-labeled span merge into the coordinator's
        tracer, and the piggybacked worker timeline events."""
        stats = resp.get("stats") or {}
        spans = resp.get("spans") or []
        host = stats.get("host") or ep.address
        exec_s = float(stats.get("exec_s", 0.0))
        nbytes = int(resp.get("_nbytes", 0))
        _c_bytes_staged().inc(nbytes)
        _h_fragment_seconds().observe(exec_s)
        merge_counter_delta(resp.get("registry"))
        self._merge_tsdb(resp, ep)
        self._merge_topsql(resp, ep)
        self._note_timeline(
            resp, ep, qid=meta.get("qid"), unit=f"f{fid}",
            attempt=meta.get("attempt", 1), t_dispatch0=t_dispatch0,
        )
        info = {
            "fid": fid, "host": host, "attempt": meta["attempt"],
            "rows": int(stats.get("rows", 0)), "exec_s": exec_s,
            "bytes": nbytes, "spans": spans,
            "mem_peak": int(stats.get("mem_peak_bytes", 0) or 0),
            "compile": stats.get("compile"),
        }
        if stats.get("delta"):
            # worker-side delta-merge stats (EXPLAIN ANALYZE DeltaMerge
            # row + the session's routed-stats snapshot)
            info["delta"] = dict(stats["delta"])
        with self._lock:
            infos.append(info)
        self._merge_remote_spans(
            spans, host, addr=ep.address, trace_t0=resp.get("trace_t0")
        )

    def last_query_mine(self) -> Optional[dict]:
        """The most recent query THIS THREAD dispatched. The session
        routing path snapshots runtime stats from here — the global
        ``last_query`` is whichever of N concurrent sessions' queries
        finished last, which would cross-attribute slow-log plan
        captures between sessions."""
        return getattr(self._tls, "last", None)

    def _merge_remote_spans(
        self, spans, host: str, addr: Optional[str] = None,
        trace_t0: Optional[float] = None,
    ) -> None:
        """Rebase worker-clock span offsets onto the coordinator
        timeline. Preferred anchor: the worker ships its tracer's wall
        clock (``trace_t0``) and the handshake sampled this host's
        clock offset (request/reply timestamps, RTT/2 anchor) — span
        starts land at their TRUE coordinator-relative offsets, so
        in-flight overlap between hosts renders faithfully. Fallback
        (offset unsampled / old worker): the reply landed NOW, so the
        spans end here and extend backwards by their own extent."""
        if not self.tracer.enabled:
            return
        base_s = 0.0
        offset = self._clock_offsets.get(addr) if addr else None
        if (
            trace_t0 is not None
            and offset is not None
            and self.tracer.wall_t0 is not None
        ):
            # worker wall clock -> coordinator wall clock -> seconds
            # since the coordinator tracer's reset
            base_s = max(
                float(trace_t0) - float(offset) - self.tracer.wall_t0,
                0.0,
            )
        elif self.tracer._t0 is not None and spans:
            now_rel = time.perf_counter() - self.tracer._t0
            extent = max(float(s[1]) + float(s[2]) for s in spans)
            base_s = max(now_rel - extent, 0.0)
        self.tracer.add_remote(spans, label=host, base_s=base_s)

    def _execute_single(
        self, plan, snap=None
    ) -> Tuple[List[str], List[tuple]]:
        """Whole-plan dispatch onto one host (shapes with no safe
        split): the ExecutorWithRetry loop over survivors."""
        last_err: Optional[Exception] = None
        for _attempt in range(self.max_attempts):
            if not self.alive_endpoints():
                self.prober.probe_once()
            ep = self._next_alive()
            if ep is None:
                break
            try:
                inject("dcn/dispatch")
                _c_dispatches().labels(host=ep.address).inc()
                if inject("dcn/dispatch-lost"):
                    raise ConnectionError("failpoint: dispatch lost in transit")
                # pooled control connection (see _dispatch)
                with self._pool(ep).lease() as conn:
                    return conn.execute_plan(plan, snap=snap)
            except (SchemaOutOfDateError, RuntimeError, ValueError,
                    PermissionError):
                raise
            except Exception as e:
                last_err = e
                self._quarantine(ep)
        raise ConnectionError(
            f"no alive worker host after {self.max_attempts} attempts; "
            f"last error: {last_err}"
        )

    # -- final stage ----------------------------------------------------
    def _stage_rows(self, cut, rows: List[tuple]) -> L.Staged:
        """Stage the gathered partial/partition rows as a device batch
        under the cut's wire schema (the coordinator side of the DCN
        exchange). `cut` is a FragmentPlan or a ShufflePlan — both
        carry partial_schema. Keyed staged input: repeated queries of
        one final-plan shape reuse the compiled final stage instead of
        paying an XLA compile per query (L.Staged.key)."""
        from tidb_tpu.parallel.shuffle import stage_rows_as_batch

        return stage_rows_as_batch(
            cut.partial_schema, rows, _STAGED_NONCE.next(),
            key="dcn-final",
        )

    def _final_stage(self, frag, rows: List[tuple]):
        """Coordinator-side merge: stage the gathered partial rows as a
        device batch and run the final plan (final aggregate + HAVING/
        projections/ORDER BY/LIMIT) through the ordinary engine — the
        root MPP fragment executing at the coordinator. `frag` is a
        FragmentPlan or a ShufflePlan (both carry final_builder)."""
        inject("dcn/final-stage")
        from tidb_tpu.chunk import materialize_rows

        staged = self._stage_rows(frag, rows)
        final = frag.final_builder(staged)
        out, out_dicts = self._executor.run(final)
        out_rows = materialize_rows(out, list(final.schema), out_dicts)
        return [c.name for c in final.schema], out_rows

    def pool_leased(self) -> Dict[str, int]:
        """Per-host count of control connections currently checked out
        — drains to 0 between queries, aborted ones included (the
        chaos harness's connection-leak invariant)."""
        with self._lock:
            pools = dict(self._pools)
        return {ep.address: p.leased() for ep, p in pools.items()}

    # -- status (the /dcn endpoint's payload) ---------------------------
    def status(self) -> dict:
        """Operational snapshot for server/http_status.py's /dcn
        endpoint: host states plus the most recent query's per-fragment
        stats (spans elided — they live in the coordinator tracer)."""
        with self._lock:
            last = self.last_query
        if last is not None:
            summary = {
                "qid": last["qid"],
                "fragments": [
                    {k: v for k, v in f.items() if k != "spans"}
                    for f in last["fragments"]
                ],
            }
            if "shuffle" in last:
                summary["shuffle"] = last["shuffle"]
            last = summary
        quarantined = [
            ep.address for ep in self.prober.failed_endpoints()
        ]
        out = {
            "enabled": True,
            "hosts": [
                {"address": ep.address, "alive": bool(ep.alive)}
                for ep in self.endpoints
            ],
            "alive": len(self.alive_endpoints()),
            "quarantined": quarantined,
            "conn_pool_size": self.conn_pool_size,
            "last_query": last,
        }
        if self.admission is not None:
            # serving-tier admission snapshot rides the same endpoint
            out["admission"] = self.admission.status()
        if self.delta is not None:
            # HTAP delta tier: per-host acked seqs, the acked floor,
            # and the completed fold boundary
            out["delta"] = self.delta.status()
        return out
