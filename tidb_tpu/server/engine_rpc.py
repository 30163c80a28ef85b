"""Frontend <-> device-engine RPC seam over the plan IR.

Reference: the `kv.Client.Send(kv.Request{Data: tipb.DAGRequest})`
contract (pkg/kv/kv.go:523) — the frontend serializes the pushdown plan
and a remote engine executes it, streaming chunks back. unistore proves
the whole SQL stack runs against that seam with an in-process loopback
(`RPCClient.SendRequest`, pkg/store/mockstore/unistore/rpc.go:64).

Here: EngineServer owns the catalog + device engine and serves
length-prefixed frames over TCP; EngineClient serializes a bound
logical plan with planner/ir.py and gets rows back. A frontend process
with no data of its own can plan SQL and execute it on a separate
engine process — the multi-host frontend/engine split.

Two frame types share the stream, discriminated by the first payload
byte: JSON control/plan frames (first byte ``{``) and binary columnar
shuffle frames (parallel/wire.py MAGIC) — the shuffle data plane skips
json.dumps/json.loads entirely. The handshake/ping reply advertises
the server's wire version so peer tunnels negotiate the codec per
connection; JSON row packets remain the mixed-version fallback.

Protocol safety: every request carries a correlation id echoed in the
response (a desynced stream is detected, the connection is poisoned
rather than returning the wrong query's rows); frames are capped; an
optional shared secret authenticates connections (the reference guards
this interior seam with cluster TLS certs — a bearer secret is the
dependency-free analog)."""

from __future__ import annotations

import json
import socket
import socketserver
import struct
import threading
import time as _time
from typing import List, Optional, Tuple

from tidb_tpu.parallel import wire
from tidb_tpu.planner.ir import IR_VERSION, plan_from_ir, plan_to_ir
from tidb_tpu.utils import racecheck

#: hard frame cap — a bogus length header must not buffer gigabytes
MAX_FRAME = 64 << 20


class QueryCancelled(RuntimeError):
    """Worker-side fragment/shuffle-task abort: the coordinator sent a
    ``cancel_query`` frame for this qid (KILL QUERY / statement
    timeout / admission revoke), or the dispatch's propagated deadline
    expired on this host. The reply carries ``cancelled: true`` so the
    coordinator distinguishes a deliberate abort from an engine error
    (no failover, no quarantine — the worker is healthy)."""


class CancelRegistry:
    """Per-server registry of cancelled query ids — the worker half of
    fleet-wide cancellation (reference: MPPTask cancellation via
    CancelMPPQuery, tiflash MPPTaskManager::abortMPPQuery). The cancel
    frame arrives on a DIFFERENT connection than the running dispatch
    (that stream is busy executing), marks the qid here, and every
    execution safepoint (PhysicalExecutor.kill_check, ShuffleWorker
    loop points, ShuffleStore wait aborts) polls it.

    Entries key on (coordinator instance id, qid): qids restart at 1
    after a coordinator restart (and two coordinators may share a
    fleet), so a bare qid cancelled by one incarnation would wrongly
    kill another's query — the same cross-instance collision the
    shuffle sids fence with their uuid prefix (parallel/dcn.py).
    Bounded: old entries age out, which is safe — an entry only
    matters while that exact query's dispatches are in flight."""

    _CAP = 1024

    def __init__(self):
        self._lock = racecheck.make_lock("engine_rpc.cancel")
        # (coord, qid) -> reason (insertion-ordered)
        self._cancelled: "dict" = {}

    def cancel(self, qid, reason: str = "", coord=None) -> None:
        with self._lock:
            self._cancelled[(str(coord), int(qid))] = str(
                reason or "cancelled"
            )
            while len(self._cancelled) > self._CAP:
                self._cancelled.pop(next(iter(self._cancelled)))

    def reason(self, qid, coord=None) -> Optional[str]:
        if qid is None:
            return None
        with self._lock:
            return self._cancelled.get((str(coord), int(qid)))

    def check(self, qid, coord=None) -> None:
        r = self.reason(qid, coord=coord)
        if r is not None:
            raise QueryCancelled(f"query q{qid} cancelled: {r}")


def make_cancel_check(registry: CancelRegistry, qid,
                      deadline_s: Optional[float] = None,
                      coord=None):
    """The worker-side safepoint check for one dispatched fragment or
    shuffle task: raises QueryCancelled when the coordinator cancelled
    this qid OR the dispatch-propagated deadline (``deadline_s``
    REMAINING seconds at dispatch time, converted to a local monotonic
    deadline here — wall clocks skew across hosts, remaining time does
    not) has expired. Plugged into PhysicalExecutor.kill_check and
    sqlkiller.set_current so blocking builtins and chaos hang hooks
    abort at the same safepoints KILL uses locally."""
    deadline = (
        _time.monotonic() + float(deadline_s)
        if deadline_s is not None else None
    )

    def check():
        if registry is not None:
            registry.check(qid, coord=coord)
        if deadline is not None and _time.monotonic() > deadline:
            raise QueryCancelled(
                f"query q{qid} cancelled: dispatch deadline exceeded"
            )

    return check


class _CheckKiller:
    """Adapter exposing a cancel check as the sqlkiller 'current
    killer' protocol (.check()) so utils/sqlkiller.current_check and
    interruptible_sleep observe fragment cancellation on worker
    threads exactly like KILL on session threads."""

    __slots__ = ("check",)

    def __init__(self, check):
        self.check = check


class SchemaOutOfDateError(RuntimeError):
    """The frontend planned against a schema version the engine has
    moved past (or not yet reached) — the analog of the domain schema
    lease check ('Information schema is out of date',
    pkg/domain/domain.go lease validation). The frontend must reload
    schemas and re-plan."""


class DropConnection(BaseException):
    """Raised by a failpoint to simulate abrupt worker death: the
    handler closes the connection WITHOUT a response frame, so the
    coordinator sees a transport loss (the work may or may not have
    happened — exactly the ambiguity fragment re-dispatch fences
    against). BaseException so the generic error-reply catch cannot
    swallow it into a polite error frame."""


def _send_frame(sock, payload: bytes) -> None:
    if len(payload) > MAX_FRAME:
        raise ValueError(f"frame of {len(payload)}B exceeds {MAX_FRAME}B")
    sock.sendall(struct.pack("<I", len(payload)) + payload)


def _recv_frame(sock) -> Optional[bytes]:
    hdr = b""
    while len(hdr) < 4:
        part = sock.recv(4 - len(hdr))
        if not part:
            return None
        hdr += part
    (n,) = struct.unpack("<I", hdr)
    if n > MAX_FRAME:
        raise ValueError(f"frame of {n}B exceeds {MAX_FRAME}B")
    out = b""
    while len(out) < n:
        part = sock.recv(min(1 << 20, n - len(out)))
        if not part:
            return None
        out += part
    return out


class EngineServer:
    """Device-engine side: executes serialized plans over its catalog.
    Each connection gets its own PhysicalExecutor (the per-connection
    Session pattern of server.py — executors' plan caches are not
    thread-safe by design)."""

    def __init__(
        self,
        catalog,
        host: str = "127.0.0.1",
        port: int = 0,
        secret: Optional[str] = None,
        mesh_devices: Optional[int] = None,
        ship_registry: bool = False,
        delta_replica: bool = False,
    ):
        self.catalog = catalog
        self.secret = secret
        # delta_replica: this process holds its OWN copy of the base
        # tables (worker processes — parallel/dcn_worker.py), so
        # coordinator DML reaches it only through delta_sync frames:
        # buffered per table, folded on compact barriers, merged into
        # routed reads (storage/delta.py). In-process servers sharing
        # the coordinator's catalog must NOT set this — their base IS
        # the fresh store, and delta frames ack as no-ops.
        self.delta_state = None
        if delta_replica:
            from tidb_tpu.storage.delta import DeltaReplicaState

            self.delta_state = DeltaReplicaState(catalog)
        # mesh_devices: this engine executes plans SPMD over its local
        # device mesh (intra-host ICI exchanges) — the worker-host shape
        # of the hierarchical DCN scheduler (parallel/dcn.py)
        self.mesh_devices = mesh_devices
        # ship_registry: piggyback this process's counter deltas on
        # fragment/shuffle replies so the coordinator's registry sees
        # fleet-wide engine activity. Worker PROCESSES enable this
        # (parallel/dcn_worker.py); in-process servers must not — they
        # share the coordinator's registry, and shipping would feed the
        # merged increments back into the next delta.
        self.ship_registry = ship_registry
        self._reg_lock = racecheck.make_lock("engine_rpc.registry")
        self._reg_snapshot: dict = {}
        # worker-side metric time-series shipping (obs/tsdb.py): this
        # process samples its OWN registry at a bounded cadence and
        # the pending rows piggyback on the next ship_registry reply —
        # or on a heartbeat ping (the idle-flush), so a worker with no
        # dispatches in flight still reports history. Same at-most-
        # once contract as the counter deltas: the buffer drains into
        # exactly one reply; a reply lost in transit (or fenced as a
        # late duplicate) drops its samples.
        self._tsdb_pending: list = []
        self._tsdb_last = 0.0
        #: min seconds between worker-side sample passes (bounds the
        #: piggyback overhead under rapid dispatch streams)
        self.tsdb_min_interval_s = 1.0
        # worker-to-worker shuffle service: the store this server's
        # shuffle_push frames land in plus the task runner
        # (parallel/shuffle.py); built lazily so plain engine servers
        # pay nothing
        self._shuffle = None
        self._shuffle_lock = racecheck.make_lock("engine_rpc.shuffle_init")
        # fleet-wide cancellation: qids cancelled by coordinator
        # cancel_query frames; every dispatch safepoint polls it
        self.cancels = CancelRegistry()
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                from tidb_tpu.planner.physical import PhysicalExecutor

                executor = PhysicalExecutor(
                    outer.catalog, mesh_devices=outer.mesh_devices
                )
                authed = outer.secret is None
                while True:
                    try:
                        frame = _recv_frame(self.request)
                    except ValueError:
                        return  # oversized frame: drop the connection
                    if frame is None:
                        return
                    req_id = None
                    try:
                        if wire.is_binary_frame(frame):
                            # binary columnar shuffle frame: the data
                            # plane never round-trips through JSON
                            req_id = wire.peek_request_id(frame)
                            if not authed:
                                import hmac

                                try:
                                    frame_auth = wire.peek_auth(frame)
                                except wire.WireFormatError:
                                    frame_auth = None
                                if not hmac.compare_digest(
                                    str(frame_auth or ""), outer.secret
                                ):
                                    _send_frame(
                                        self.request,
                                        json.dumps(
                                            {
                                                "id": req_id, "ok": False,
                                                "error":
                                                    "authentication failed",
                                            }
                                        ).encode(),
                                    )
                                    return
                                authed = True
                            # route off the sid namespace alone: the
                            # delta-sync data plane shares the binary
                            # codec with shuffle but lands in the
                            # replica state, not the shuffle store
                            try:
                                is_delta = wire.peek_sid(
                                    frame
                                ).startswith("delta://")
                            except wire.WireFormatError:
                                is_delta = False
                            if is_delta:
                                resp = outer._delta_sync_binary(frame)
                            else:
                                resp = outer._shuffle_push_binary(frame)
                            _send_frame(self.request, resp)
                            continue
                        t_dec0 = _time.perf_counter()
                        req = json.loads(frame.decode())
                        dec_s = _time.perf_counter() - t_dec0
                        req_id = req.get("id")
                        if not authed:
                            import hmac

                            if not hmac.compare_digest(
                                str(req.get("auth") or ""), outer.secret
                            ):
                                _send_frame(
                                    self.request,
                                    json.dumps(
                                        {
                                            "id": req_id, "ok": False,
                                            "error": "authentication failed",
                                        }
                                    ).encode(),
                                )
                                return
                            authed = True
                        if "shuffle_push" in req:
                            # peer tunnel frame (JSON fallback codec):
                            # a worker pushing one hash partition row
                            # packet of its fragment
                            resp = outer._shuffle_push(req, dec_s)
                        elif "shuffle_task" in req:
                            resp = outer._shuffle_task(req)
                        elif "shuffle_sample" in req:
                            resp = outer._shuffle_sample(req)
                        elif "shuffle_probe" in req:
                            resp = outer._shuffle_probe(req)
                        elif "cancel_query" in req:
                            resp = outer._cancel_query(req)
                        elif "delta_compact" in req:
                            resp = outer._delta_compact(req)
                        elif "delta_status" in req:
                            resp = outer._delta_status(req)
                        elif "engine_status" in req:
                            resp = outer._engine_status(req)
                        elif "plan" not in req:
                            # handshake/ping frame — fine whether or not
                            # this server requires a secret (a secreted
                            # client must interoperate with an open
                            # server). Advertises the binary shuffle
                            # wire version for per-tunnel codec
                            # negotiation.
                            # "ts" is this host's wall clock at reply
                            # build: with the client's send/receive
                            # timestamps it yields the RTT/2-anchored
                            # clock-offset estimate that rebases worker
                            # spans onto the coordinator timeline
                            from tidb_tpu.utils.failpoint import inject

                            ping = {
                                "id": req_id, "ok": True,
                                "wire": wire.WIRE_VERSION,
                                # engine/clock-skew: the chaos
                                # harness shifts this host's
                                # advertised clock so the offset
                                # estimator and span/timeline
                                # rebasing run under skew
                                "ts": _time.time() + float(
                                    inject("engine/clock-skew", 0)
                                    or 0
                                ),
                            }
                            if "topsql" in req:
                                # heartbeat-carried Top SQL profiler
                                # config: workers arm/disarm/re-tune
                                # even with no dispatch in flight
                                outer._apply_topsql(req.get("topsql"))
                            if outer.ship_registry and req.get(
                                "tsdb_flush"
                            ):
                                # idle-flush: a worker with nothing
                                # dispatched still ships its sampled
                                # history on the heartbeat cadence.
                                # Only EXPLICIT flush pings drain the
                                # buffer — every fresh connection
                                # handshakes with this frame shape and
                                # discards the reply, which would
                                # silently eat the pending samples
                                tsdb_rows = outer._tsdb_ship()
                                if tsdb_rows:
                                    ping["tsdb"] = tsdb_rows
                                topsql = outer._topsql_ship()
                                if topsql:
                                    ping["topsql"] = topsql
                            resp = json.dumps(ping).encode()
                        else:
                            resp = outer._execute(executor, req)
                    except DropConnection:
                        # failpoint-simulated worker death: no response
                        # frame — the peer sees the stream close
                        try:
                            self.request.close()
                        except OSError:
                            pass
                        return
                    except Exception as e:
                        err = {
                            "id": req_id, "ok": False,
                            "error": f"{type(e).__name__}: {e}",
                        }
                        if isinstance(e, QueryCancelled):
                            # a deliberate abort, not an engine error:
                            # the coordinator must surface the kill,
                            # never fail over or quarantine
                            err["cancelled"] = True
                        resp = json.dumps(err).encode()
                    try:
                        _send_frame(self.request, resp)
                    except ValueError:
                        # success payload larger than MAX_FRAME: report
                        # instead of dropping the connection silently
                        _send_frame(
                            self.request,
                            json.dumps(
                                {
                                    "id": req_id, "ok": False,
                                    "error": (
                                        f"result exceeds {MAX_FRAME} bytes; "
                                        "narrow the query"
                                    ),
                                }
                            ).encode(),
                        )

        class TCP(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._tcp = TCP((host, port), Handler)
        self.port = self._tcp.server_address[1]

    def _execute(self, executor, req) -> bytes:
        from tidb_tpu.utils.failpoint import inject

        inject("engine/execute")
        from tidb_tpu.chunk import materialize_rows

        frag = req.get("frag")
        if frag is not None:
            # DCN fragment dispatch: a site before execution (dispatch
            # received, about to run — death here loses the fragment
            # cleanly) and one after (dcn/result-send below — death
            # there loses only the REPLY, the duplicate-redelivery case)
            inject("dcn/fragment-execute")
        if req.get("v") != IR_VERSION:
            raise ValueError(f"unsupported IR version {req.get('v')}")
        if "schema_v" in req:
            # schema-lease validation: a plan bound against stale
            # schemas must not execute — name/column resolution could
            # silently hit the wrong physical layout
            engine_v = getattr(self.catalog, "schema_version", 0)
            if int(req["schema_v"]) != int(engine_v):
                raise SchemaOutOfDateError(
                    f"schema out of date: engine at version {engine_v}, "
                    f"client planned at {req['schema_v']}; reload schemas"
                )
        plan = plan_from_ir(req["plan"])
        # snapshot isolation for routed dispatches: pin every scanned
        # table's base version for the WHOLE dispatch (version GC can
        # never collect an in-flight routed query's input) and, on a
        # delta replica, merge the snapshot's buffered deltas into the
        # plan as keyed Staged leaves (storage/delta.py)
        pins: list = []
        delta_stats = None
        snap = req.get("snap")
        conn_executor = executor
        if snap:
            from tidb_tpu.storage import delta as _delta

            plan, hook, delta_stats = _delta.prepare_worker_plan(
                self.catalog, self.delta_state, plan, snap, pins
            )
            if hook is not None:
                executor.table_hook = hook
            if delta_stats is not None and executor.mesh is not None:
                # a merged plan mixes sharded scans with replicated
                # Staged leaves; run it on this connection's plain
                # (single-device) executor — the SPMD mesh program is
                # a scan-throughput optimization, not a correctness
                # requirement
                from tidb_tpu.planner.physical import PhysicalExecutor

                plain = getattr(executor, "_delta_plain", None)
                if plain is None:
                    plain = PhysicalExecutor(self.catalog)
                    executor._delta_plain = plain
                plain.table_hook = executor.table_hook
                executor = plain
        try:
            return self._execute_inner(
                executor, req, plan, frag, delta_stats
            )
        finally:
            # clear BOTH executors' hooks: a merged dispatch swaps to
            # the plain executor but the connection executor's hook was
            # set first — a dangling hook would leak this snapshot's
            # resolution into the next request on this connection
            executor.table_hook = None
            conn_executor.table_hook = None
            for t, v in pins:
                t.unpin(v)

    def _execute_inner(
        self, executor, req, plan, frag, delta_stats
    ) -> bytes:
        from tidb_tpu.chunk import materialize_rows
        from tidb_tpu.utils.failpoint import inject

        tracer = None
        if frag is not None:
            # trace context propagated over the RPC seam: the
            # coordinator's (query id, fragment id) labels every span
            # this worker records, and the spans ship back in the reply
            # for host-labeled merge into the coordinator's Tracer.
            # Span collection is opt-in per dispatch (frag["trace"], set
            # from the coordinator tracer's enabled flag) so untraced
            # production queries pay neither the Tracer nor the span
            # payload in every reply; runtime stats always ship.
            from tidb_tpu.utils.tracing import Tracer

            tracer = Tracer()  # disabled by default: span() is a no-op
            if frag.get("trace"):
                tracer.enabled = True
                tracer.reset()
            ctx = f"q{frag.get('qid')}/f{frag.get('fid')}"
            # per-fragment engine-watch record: this worker's OWN
            # device-mem high-water and compile cost for the slice it
            # ran — shipped in the reply stats so admission estimates
            # learn from worker-eyed peaks (the coordinator-side
            # estimate sees a different, usually smaller, shape)
            from tidb_tpu.obs.engine_watch import (
                ENGINE_WATCH,
                set_cost_wanted,
            )

            ENGINE_WATCH.begin_query(f"frag {ctx}")
            # a timeline-captured dispatch asks this worker to harvest
            # XLA cost analysis for whatever it compiles (thread-scoped)
            set_cost_wanted(bool(frag.get("timeline")))
            # fleet cancellation safepoints: the engine's kill_check
            # polls the cancel registry + the dispatch-propagated
            # deadline, and sqlkiller's thread-local current killer
            # makes interruptible waits (and chaos hang hooks) abort
            # on the same signal
            from tidb_tpu.utils import sqlkiller as _sk

            check = make_cancel_check(
                self.cancels, frag.get("qid"), frag.get("deadline_s"),
                coord=frag.get("coord"),
            )
            executor.kill_check = check
            _sk.set_current(_CheckKiller(check))
            # Top SQL (obs/profiler.py): the dispatch carries the
            # profiler config + the statement digest this fragment
            # belongs to — arm/retune the local sampler and register
            # this handler thread so its samples attribute to that
            # digest (no context, no attribution: a finished or
            # foreign qid can never be charged)
            from tidb_tpu.obs import profiler as _topsql

            ts_cfg = frag.get("topsql")
            self._apply_topsql(ts_cfg)
            ts_prev = _topsql.begin_task(
                "fragment",
                digest=(ts_cfg or {}).get("digest"),
                phase="execute",
            )
            t_exec0 = _time.perf_counter()
            t_wall0 = _time.time()
            try:
                check()
                with tracer.span(f"{ctx}/execute"):
                    batch, dicts = executor.run(plan)
                with tracer.span(f"{ctx}/materialize"):
                    rows = materialize_rows(
                        batch, list(plan.schema), dicts
                    )
            except BaseException:
                ENGINE_WATCH.end_query(
                    _time.perf_counter() - t_exec0
                )
                raise
            finally:
                _topsql.end_task(ts_prev)
                set_cost_wanted(False)
                executor.kill_check = None
                _sk.set_current(None)
            exec_s = _time.perf_counter() - t_exec0
            frag_watch = {
                "mem_peak_bytes": ENGINE_WATCH.current_peak_bytes(),
                "compile": ENGINE_WATCH.current_compile_cost() or None,
            }
            frag_events = None
            if frag.get("timeline"):
                from tidb_tpu.obs.timeline import TimelineBuffer

                tb = TimelineBuffer()
                tb.emit_event(
                    "fragment", f"execute {ctx}", t_wall0, exec_s,
                    track=ctx,
                    args={"attempt": frag.get("attempt", 1)},
                )
                frag_events = tb.events
            ENGINE_WATCH.end_query(exec_s)
        else:
            batch, dicts = executor.run(plan)
            rows = materialize_rows(batch, list(plan.schema), dicts)
        if frag is not None:
            # mid-shuffle worker death AFTER the work, BEFORE the reply:
            # the coordinator must re-dispatch, and its ledger must
            # accept the retry's result exactly once
            inject("dcn/result-send")
        resp = {
            "id": req.get("id"),
            "ok": True,
            "columns": [c.name for c in plan.schema],
            "rows": rows,
        }
        if frag is not None:
            resp["frag"] = frag
            if tracer.enabled:
                resp["spans"] = [
                    [s.name, s.start_s, s.dur_s, s.depth]
                    for s in tracer.spans
                ]
                # this worker's wall clock at tracer reset: with the
                # handshake clock-offset sample the coordinator rebases
                # spans onto its own timeline instead of anchoring at
                # reply receipt
                resp["trace_t0"] = tracer.wall_t0
            # no byte count here: the coordinator measures the actual
            # reply frame length (EngineClient stamps _nbytes), which is
            # what really crossed the DCN link — and avoids serializing
            # the row set twice on the reply hot path
            resp["stats"] = {
                "rows": len(rows),
                "exec_s": exec_s,
                "host": f"{socket.gethostname()}:{self.port}",
                # worker-eyed engine accounting for THIS fragment
                "mem_peak_bytes": frag_watch["mem_peak_bytes"],
                "compile": frag_watch["compile"],
            }
            if delta_stats is not None:
                # this fragment merged buffered deltas: depth / rows /
                # delete keys ride the reply for the coordinator's
                # EXPLAIN ANALYZE DeltaMerge row
                resp["stats"]["delta"] = delta_stats
            if frag_events:
                resp["events"] = frag_events
            if self.ship_registry:
                # fleet observability: this process's counter movement
                # rides the reply; the coordinator merges it behind the
                # ledger fence (at-most-once: a lost/fenced reply drops
                # its delta — see utils/metrics.py fleet-merge notes)
                resp["registry"] = self._registry_delta()
                tsdb_rows = self._tsdb_ship()
                if tsdb_rows:
                    resp["tsdb"] = tsdb_rows
                topsql = self._topsql_ship()
                if topsql:
                    resp["topsql"] = topsql
        return json.dumps(resp).encode()

    # -- worker-to-worker shuffle (parallel/shuffle.py) -----------------
    def shuffle_worker(self):
        with self._shuffle_lock:
            if self._shuffle is None:
                from tidb_tpu.parallel.shuffle import ShuffleWorker

                self._shuffle = ShuffleWorker(
                    self.catalog,
                    self_address=f"{socket.gethostname()}:{self.port}",
                    mesh_devices=self.mesh_devices,
                    delta_state=self.delta_state,
                )
            return self._shuffle

    def _shuffle_push(self, req, decode_s: float = 0.0) -> bytes:
        """A peer worker's JSON-fallback tunnel packet: land the rows
        in the local store (attempt-fenced, seq-deduped) and ack."""
        from tidb_tpu.parallel.shuffle import _c_decode_seconds
        from tidb_tpu.utils.failpoint import inject

        inject("shuffle/recv")
        _c_decode_seconds().labels(codec="json").inc(decode_s)
        p = req["shuffle_push"]
        accepted = self.shuffle_worker().store.push(
            p["sid"], int(p["attempt"]), int(p["m"]), int(p["side"]),
            int(p["sender"]), int(p.get("seq", -1)), p.get("rows"),
            nseq=p.get("nseq"),
        )
        if inject("shuffle/recv-ack-lost"):
            # packet stored, ack lost: the sender retransmits and the
            # seq dedupe drops the duplicate — exactly-once on the wire
            raise DropConnection()
        # shuffle-json-fallback: the tiny control-plane ack stays JSON
        return json.dumps(
            {"id": req.get("id"), "ok": True, "accepted": bool(accepted)}
        ).encode()

    def _shuffle_push_binary(self, frame: bytes) -> bytes:
        """A peer worker's binary columnar tunnel frame, decoded ON
        ARRIVAL (the receive half of the shuffle pipeline — decode
        overlaps the producers still in flight, and ShuffleStore waits
        return already-decoded blocks). The exactly-once fences run
        FIRST, off the header alone (wire.decode_header): a
        stale-attempt or duplicate/retransmitted frame is dropped
        before any column decode work is spent on it — and therefore
        can never double-stage. A frame that fails to decode
        (corruption, version skew inside a negotiated stream — the
        shuffle/decode failpoint injects both) is REJECTED with an
        error reply over the live connection: the sender surfaces it
        as a non-retryable engine error, so a corrupt frame aborts the
        stage instead of masquerading as a peer death and triggering a
        pointless stage retry."""
        from tidb_tpu.parallel.shuffle import (
            _c_decode_on_arrival_seconds,
            _c_decode_seconds,
        )
        from tidb_tpu.utils.failpoint import inject

        inject("shuffle/recv")
        store = self.shuffle_worker().store
        t0 = _time.perf_counter()
        try:
            hdr = wire.decode_header(frame)
            if not hdr["eof"] and not store.admits(
                hdr["sid"], hdr["attempt"], hdr["side"], hdr["sender"],
                hdr["seq"],
            ):
                # fenced from the header — no decode work wasted, and
                # a retransmit can never double-stage
                # shuffle-json-fallback: control-plane ack stays JSON
                return json.dumps(
                    {"id": hdr["id"], "ok": True, "accepted": False}
                ).encode()
            inject("shuffle/decode")
            pkt = wire.decode_frame(frame, header=hdr)
        except Exception as e:
            # shuffle-json-fallback: the error REPLY is control-plane
            return json.dumps(
                {
                    "id": wire.peek_request_id(frame), "ok": False,
                    "error": f"ShuffleDecodeError: {e}",
                }
            ).encode()
        dec_s = _time.perf_counter() - t0
        _c_decode_seconds().labels(codec="binary").inc(dec_s)
        _c_decode_on_arrival_seconds().inc(dec_s)
        payload = pkt["block"]
        accepted = store.push(
            pkt["sid"], pkt["attempt"], pkt["m"], pkt["side"],
            pkt["sender"], pkt["seq"], payload, nseq=pkt["nseq"],
        )
        if inject("shuffle/recv-ack-lost"):
            raise DropConnection()
        # shuffle-json-fallback: the tiny control-plane ack stays JSON
        return json.dumps(
            {"id": pkt["id"], "ok": True, "accepted": bool(accepted)}
        ).encode()

    def _shuffle_task(self, req) -> bytes:
        """One dispatched shuffle stage task: produce + push + wait +
        consume (ShuffleWorker.run_task). Retryable stage failures
        (dead peers, missing producers) reply with a suspect list the
        coordinator verifies before re-running the stage on the
        survivor set."""
        from tidb_tpu.parallel.shuffle import ShuffleAbort
        from tidb_tpu.utils.tracing import Tracer

        if req.get("v") != IR_VERSION:
            raise ValueError(f"unsupported IR version {req.get('v')}")
        spec = req["shuffle_task"]
        if "schema_v" in req:
            engine_v = getattr(self.catalog, "schema_version", 0)
            if int(req["schema_v"]) != int(engine_v):
                raise SchemaOutOfDateError(
                    f"schema out of date: engine at version {engine_v}, "
                    f"client planned at {req['schema_v']}; reload schemas"
                )
        tracer = Tracer()
        if spec.get("trace"):
            tracer.enabled = True
            tracer.reset()
        # per-task engine-watch record: worker-eyed device-mem peak +
        # compile cost ride the reply stats (see _execute)
        from tidb_tpu.obs.engine_watch import (
            ENGINE_WATCH,
            set_cost_wanted,
        )

        ENGINE_WATCH.begin_query(
            f"shuffle {spec.get('sid')}/p{spec.get('part')}"
        )
        set_cost_wanted(bool(spec.get("timeline")))
        # fleet cancellation: the task polls this at its loop points
        # (produce chunks, shipper chunks, store waits, consume) and
        # the thread-local current killer covers interruptible sleeps
        from tidb_tpu.utils import sqlkiller as _sk

        check = make_cancel_check(
            self.cancels, spec.get("qid"), spec.get("deadline_s"),
            coord=spec.get("coord"),
        )
        _sk.set_current(_CheckKiller(check))
        # Top SQL: dispatch-carried config + digest; run_task updates
        # the live phase (produce/push/wait/stage) on this context
        from tidb_tpu.obs import profiler as _topsql

        ts_cfg = spec.get("topsql")
        self._apply_topsql(ts_cfg)
        ts_prev = _topsql.begin_task(
            "shuffle",
            digest=(ts_cfg or {}).get("digest"),
            phase="shuffle-produce",
        )
        t0 = _time.perf_counter()
        try:
            result = self.shuffle_worker().run_task(
                spec, tracer=tracer, cancel_check=check
            )
        except ShuffleAbort as e:
            ENGINE_WATCH.end_query(_time.perf_counter() - t0)
            return json.dumps(
                {
                    "id": req.get("id"), "ok": False, "retryable": "shuffle",
                    "suspects": e.suspects, "error": str(e),
                }
            ).encode()
        except BaseException:
            ENGINE_WATCH.end_query(_time.perf_counter() - t0)
            raise
        finally:
            _topsql.end_task(ts_prev)
            set_cost_wanted(False)
            _sk.set_current(None)
        exec_s = _time.perf_counter() - t0
        task_watch = {
            "mem_peak_bytes": ENGINE_WATCH.current_peak_bytes(),
            "compile": ENGINE_WATCH.current_compile_cost() or None,
        }
        ENGINE_WATCH.end_query(exec_s)
        resp = {
            "id": req.get("id"),
            "ok": True,
            "columns": result["columns"],
            "rows": result["rows"],
            "shuffle": result["shuffle"],
            "stats": {
                # a mid-DAG stage HOLDS its output (rows ship nothing
                # back): report the held partition's row count so
                # per-stage ShuffleExchange rows stay informative
                "rows": len(result["rows"]) or int(
                    result["shuffle"].get("held_rows", 0) or 0
                ),
                "exec_s": exec_s,
                "host": f"{socket.gethostname()}:{self.port}",
                "mem_peak_bytes": task_watch["mem_peak_bytes"],
                "compile": task_watch["compile"],
            },
        }
        if result.get("events"):
            resp["events"] = result["events"]
        if tracer.enabled:
            resp["spans"] = [
                [s.name, s.start_s, s.dur_s, s.depth] for s in tracer.spans
            ]
            resp["trace_t0"] = tracer.wall_t0
        if self.ship_registry:
            resp["registry"] = self._registry_delta()
            tsdb_rows = self._tsdb_ship()
            if tsdb_rows:
                resp["tsdb"] = tsdb_rows
            topsql = self._topsql_ship()
            if topsql:
                resp["topsql"] = topsql
        return json.dumps(resp).encode()

    def _shuffle_sample(self, req) -> bytes:
        """Boundary-sampling round of a range exchange stage
        (ShuffleWorker.run_sample): produce-and-cache this worker's
        side, reply a deterministic key sample for the coordinator's
        merged quantile cut. A lost reply (shuffle/sample-lost) is a
        transport suspect the coordinator verifies like any dispatch
        loss; retryable failures (a held StageInput missing after a
        worker restart) reply with the suspect classification of
        _shuffle_task so the whole DAG retries on the survivor set."""
        from tidb_tpu.parallel.shuffle import ShuffleAbort
        from tidb_tpu.utils import sqlkiller as _sk
        from tidb_tpu.utils.failpoint import inject

        if req.get("v") != IR_VERSION:
            raise ValueError(f"unsupported IR version {req.get('v')}")
        spec = req["shuffle_sample"]
        check = make_cancel_check(
            self.cancels, spec.get("qid"), spec.get("deadline_s"),
            coord=spec.get("coord"),
        )
        _sk.set_current(_CheckKiller(check))
        from tidb_tpu.obs import profiler as _topsql

        ts_cfg = spec.get("topsql")
        self._apply_topsql(ts_cfg)
        ts_prev = _topsql.begin_task(
            "sample",
            digest=(ts_cfg or {}).get("digest"),
            phase="shuffle-produce",
        )
        try:
            result = self.shuffle_worker().run_sample(
                spec, cancel_check=check
            )
        except ShuffleAbort as e:
            return json.dumps(
                {
                    "id": req.get("id"), "ok": False,
                    "retryable": "shuffle", "suspects": e.suspects,
                    "error": str(e),
                }
            ).encode()
        finally:
            _topsql.end_task(ts_prev)
            _sk.set_current(None)
        if inject("shuffle/sample-lost"):
            raise DropConnection()
        return json.dumps(
            {
                "id": req.get("id"), "ok": True,
                "samples": result["samples"], "rows": result["rows"],
            }
        ).encode()

    def _shuffle_probe(self, req) -> bytes:
        """AQE skew/cardinality probe round (ShuffleWorker.run_probe,
        parallel/aqe.py): produce-and-cache every side of a hash
        stage, reply each side's exact per-partition row histogram +
        hottest keys. Classification mirrors _shuffle_sample: a lost reply
        (aqe/probe-lost) is a transport suspect the coordinator
        verifies; retryable failures carry the suspect list."""
        from tidb_tpu.parallel.shuffle import ShuffleAbort
        from tidb_tpu.utils import sqlkiller as _sk
        from tidb_tpu.utils.failpoint import inject

        if req.get("v") != IR_VERSION:
            raise ValueError(f"unsupported IR version {req.get('v')}")
        spec = req["shuffle_probe"]
        check = make_cancel_check(
            self.cancels, spec.get("qid"), spec.get("deadline_s"),
            coord=spec.get("coord"),
        )
        _sk.set_current(_CheckKiller(check))
        from tidb_tpu.obs import profiler as _topsql

        ts_cfg = spec.get("topsql")
        self._apply_topsql(ts_cfg)
        ts_prev = _topsql.begin_task(
            "sample",
            digest=(ts_cfg or {}).get("digest"),
            phase="shuffle-produce",
        )
        try:
            result = self.shuffle_worker().run_probe(
                spec, cancel_check=check
            )
        except ShuffleAbort as e:
            return json.dumps(
                {
                    "id": req.get("id"), "ok": False,
                    "retryable": "shuffle", "suspects": e.suspects,
                    "error": str(e),
                }
            ).encode()
        finally:
            _topsql.end_task(ts_prev)
            _sk.set_current(None)
        if inject("aqe/probe-lost"):
            raise DropConnection()
        return json.dumps(
            {
                "id": req.get("id"), "ok": True,
                "sides": result["sides"],
            }
        ).encode()

    def _cancel_query(self, req) -> bytes:
        """Fleet-wide cancellation, worker half: mark the qid in the
        cancel registry (running fragments/shuffle tasks abort at
        their next safepoint) and free the query's staged shuffle
        buffers NOW — the sid is poisoned so in-flight frames from
        still-pushing peers cannot resurrect an orphan stage record
        (``tidbtpu_shuffle_stages_buffered`` returns to 0 without
        waiting for the eviction window). Held shuffle-DAG blocks of
        the qid drop with it."""
        c = req["cancel_query"]
        self.cancels.cancel(
            c.get("qid"), c.get("reason"), coord=c.get("coord")
        )
        sid = c.get("sid")
        if sid is not None and self._shuffle is not None:
            self._shuffle.store.poison(str(sid))
        if self._shuffle is not None:
            self._shuffle._held_prune(c.get("coord"), c.get("qid"))
        return json.dumps({"id": req.get("id"), "ok": True}).encode()

    # -- delta tier (storage/delta.py) ----------------------------------
    def _delta_sync_binary(self, frame: bytes) -> bytes:
        """One delta-sync frame from the coordinator's replicator:
        decode (binary columnar codec — the delta data plane never
        rides JSON) and buffer it in the replica state, seq-fenced so
        a retransmit can never double-buffer. Servers sharing the
        coordinator's catalog (no replica state) ack without applying:
        their base IS the fresh store. The ``delta/sync-loss``
        failpoint drops the ack AFTER the apply — the chaos frame-loss
        shape the seq fence exists for."""
        from tidb_tpu.utils.failpoint import inject

        try:
            pkt = wire.decode_frame(frame)
        except Exception as e:
            # delta-json-control: the error REPLY is control-plane
            return json.dumps(
                {
                    "id": wire.peek_request_id(frame), "ok": False,
                    "error": f"DeltaDecodeError: {e}",
                }
            ).encode()
        if self.delta_state is not None:
            acked = self.delta_state.apply_frame(pkt)
        else:
            acked = int(pkt["seq"])
        if inject("delta/sync-loss"):
            raise DropConnection()
        # delta-json-control: the tiny ack stays JSON
        return json.dumps(
            {"id": pkt["id"], "ok": True, "acked": acked}
        ).encode()

    def _delta_compact(self, req) -> bytes:
        """Fold barrier: fold buffered deltas <= up_to into the local
        base through the existing columnar write path, retaining the
        previous fold's pinned base version for in-flight snapshots.
        No-op ack on shared-catalog servers and on re-shipped
        barriers (idempotent)."""
        c = req["delta_compact"]
        if self.delta_state is not None:
            acked = self.delta_state.apply_compact(
                int(c["up_to"]), int(c["seq"])
            )
        else:
            acked = int(c["seq"])
        return json.dumps(
            {"id": req.get("id"), "ok": True, "acked": acked}
        ).encode()

    def _delta_status(self, req) -> bytes:
        """Replica-state introspection (tests + chaos invariants)."""
        state = (
            self.delta_state.status()
            if self.delta_state is not None else None
        )
        return json.dumps(
            {"id": req.get("id"), "ok": True, "delta": state}
        ).encode()

    def _engine_status(self, req) -> bytes:
        """Worker introspection frame (tests + chaos invariants): the
        shuffle store's buffered-stage count and the live shuffle
        worker threads on this host — both must return to zero after a
        cancelled or failed stage (the abort-path leak check)."""
        stages = 0
        held = 0
        if self._shuffle is not None:
            stages = self._shuffle.store.buffered_stages()
            held = self._shuffle.held_count()
        shuffle_threads = [
            t.name for t in threading.enumerate()
            if t.is_alive() and t.name.startswith("shuffle-")
        ]
        return json.dumps(
            {
                "id": req.get("id"), "ok": True,
                "stages_buffered": stages,
                "held_outputs": held,
                "shuffle_threads": shuffle_threads,
            }
        ).encode()

    def _registry_delta(self):
        from tidb_tpu.utils.metrics import counter_delta

        with self._reg_lock:
            delta, self._reg_snapshot = counter_delta(self._reg_snapshot)
        return delta

    def _apply_topsql(self, cfg) -> None:
        """Apply a dispatch/ping-carried Top SQL profiler config to
        THIS process's sampler (obs/profiler.py). Worker processes
        only (ship_registry): in-process servers share the
        coordinator's profiler, which the SET GLOBAL hook already
        configured — a second applier would fight it."""
        if not self.ship_registry:
            return
        from tidb_tpu.obs.profiler import TOPSQL

        try:
            TOPSQL.apply_config(cfg)
        except Exception:
            pass  # profiler config must never fail a dispatch

    def _topsql_ship(self):
        """Drain this process's pending Top SQL deltas (collapsed
        stacks + per-digest aggregates) into ONE reply — the
        _tsdb_ship contract: at-most-once, a lost reply drops its
        batch, idle replies stay small."""
        from tidb_tpu.obs.profiler import TOPSQL

        return TOPSQL.store.ship()

    def _tsdb_ship(self):
        """Sample this process's registry (bounded cadence) and drain
        the pending rows into ONE reply: ``[name, [labelnames],
        [labelvalues], ts, value, kind]`` in this worker's wall clock
        (the coordinator rebases through the handshake offset at
        merge). Returns None when nothing is pending — idle pings stay
        small."""
        from tidb_tpu.utils.metrics import sample_rows

        now = _time.time()
        with self._reg_lock:
            if now - self._tsdb_last >= self.tsdb_min_interval_s:
                self._tsdb_last = now
                for name, ln, lv, value, kind in sample_rows():
                    self._tsdb_pending.append(
                        [name, list(ln), list(lv), now, value, kind]
                    )
                if len(self._tsdb_pending) > 8192:
                    # bounded buffer: a coordinator that stopped
                    # draining must not grow worker memory — oldest
                    # samples drop first
                    del self._tsdb_pending[:-8192]
            out, self._tsdb_pending = self._tsdb_pending, []
        return out or None

    def start_background(self) -> threading.Thread:
        th = threading.Thread(
            target=self._tcp.serve_forever, daemon=True,
            name=f"engine-rpc-{self.port}",
        )
        th.start()
        return th

    def shutdown(self) -> None:
        self._tcp.shutdown()
        self._tcp.server_close()


class EngineClient:
    """Frontend side: holds only schemas; data lives on the engine."""

    def __init__(
        self,
        host: str,
        port: int,
        secret: Optional[str] = None,
        timeout_s: float = 60.0,
    ):
        self._sock = socket.create_connection((host, port), timeout=timeout_s)
        self._secret = secret
        self._next_id = 0
        self._dead = False
        #: filled by the eager handshake below: the server's advertised
        #: shuffle wire version (per-tunnel codec negotiation) and a
        #: clock-offset sample — offset = server_ts - (t0 + t1)/2, the
        #: classic request/reply RTT/2 anchor (error bounded by RTT/2).
        #: The DCN scheduler uses the offset to rebase worker span
        #: clocks onto the coordinator timeline.
        self.server_wire = 0
        self.clock_offset_s: Optional[float] = None
        self.clock_rtt_s: Optional[float] = None
        # one eager handshake per connection: authenticates (bad
        # credentials fail at connect), learns the wire version, and
        # samples the peer clock
        try:
            t0 = _time.time()
            resp = self._call({} if secret is None else {"auth": secret})
            t1 = _time.time()
        except Exception:
            self._sock.close()
            raise
        if not resp.get("ok"):
            self._sock.close()
            raise PermissionError(resp.get("error", "auth failed"))
        self.server_wire = int(resp.get("wire", 0))
        ts = resp.get("ts")
        if ts is not None:
            self.clock_rtt_s = t1 - t0
            self.clock_offset_s = float(ts) - (t0 + t1) / 2.0

    def _call(self, req: dict) -> dict:
        """One correlated request/response. Any transport error or id
        mismatch poisons the connection — a desynced stream must never
        hand one query another query's rows."""
        if self._dead:
            raise ConnectionError("engine connection is poisoned; reconnect")
        self._next_id += 1
        req = dict(req)
        req["id"] = self._next_id
        if self._secret is not None:
            req["auth"] = self._secret
        return self._roundtrip(json.dumps(req).encode())

    def _roundtrip(self, payload: bytes) -> dict:
        """Ship one already-encoded frame (its "id" must be
        self._next_id) and read the correlated response."""
        if len(payload) > MAX_FRAME:
            # nothing was written: the stream is still synchronized, so
            # don't poison the connection over a local size check
            raise ValueError(
                f"request of {len(payload)}B exceeds {MAX_FRAME}B"
            )
        try:
            _send_frame(self._sock, payload)
            frame = _recv_frame(self._sock)
        except Exception:
            self._dead = True
            self._sock.close()
            raise
        if frame is None:
            self._dead = True
            raise ConnectionError("engine closed the connection")
        resp = json.loads(frame.decode())
        if isinstance(resp, dict):
            # wire-level reply size: the DCN exchange volume a fragment
            # actually staged through the coordinator
            resp["_nbytes"] = len(frame)
        if resp.get("id") != self._next_id:
            self._dead = True
            self._sock.close()
            raise ConnectionError(
                f"response id {resp.get('id')} != request id {self._next_id}"
            )
        return resp

    def call(self, req: dict) -> dict:
        """One correlated raw request (shuffle task dispatch and other
        non-plan frames); the caller interprets the response dict."""
        return self._call(req)

    def cancel_query(self, qid, sid=None, reason: str = "",
                     coord=None) -> bool:
        """Fleet-wide cancellation, coordinator half: tell this worker
        to abort everything it runs for ``qid`` under coordinator
        instance ``coord`` (and free the stage ``sid``'s shuffle
        buffers). Control-plane frame on THIS connection — callers use
        a dedicated short-lived connection, never a stream with a
        dispatch in flight."""
        resp = self._call(
            {"cancel_query": {
                "qid": qid, "sid": sid, "reason": reason,
                "coord": coord,
            }}
        )
        return bool(resp.get("ok"))

    def engine_status(self) -> dict:
        """Worker introspection (tests + chaos invariants): buffered
        shuffle stages and live shuffle threads on the peer."""
        return self._call({"engine_status": True})

    def shuffle_push(self, packet: dict) -> bool:
        """Push one shuffle partition packet to this peer; returns the
        receiver's accepted flag (False = fenced/deduped, which is fine
        — the data is already accounted for)."""
        resp = self._call({"shuffle_push": packet})
        if not resp.get("ok"):
            raise RuntimeError(
                f"shuffle push rejected: {resp.get('error', '')}"
            )
        return bool(resp.get("accepted"))

    def shuffle_push_encoded(self, payload: bytes) -> bool:
        """shuffle_push over a PRE-ENCODED packet — a binary columnar
        frame (parallel/wire.py) or a `{"shuffle_push": {...}}` JSON
        object: the data plane serializes each packet exactly once (at
        enqueue, where the flow-control window is sized) and the
        correlation id / auth are spliced in at the byte level by the
        shared wire.splice_id_auth helper instead of re-encoding the
        rows on the tunnel thread."""
        return self.shuffle_push_encoded_many([payload])[0]

    def shuffle_push_encoded_many(self, payloads) -> List[bool]:
        """Pipelined shuffle push: write EVERY payload's frame onto the
        socket back to back, THEN read the acks in order — one wire
        round trip amortized over the batch instead of a synchronous
        request/response per packet (the per-frame ack latency was the
        dominant serial tail of a shuffle push stream; the server's
        per-connection loop replies in order, so request pipelining is
        safe). Any transport loss or id mismatch poisons the
        connection; the caller (PeerTunnel) reconnects and retransmits
        the WHOLE unacked batch — the receiver's seq dedupe makes that
        exactly-once."""
        if self._dead:
            raise ConnectionError("engine connection is poisoned; reconnect")
        ids = []
        out = bytearray()
        for payload in payloads:
            self._next_id += 1
            ids.append(self._next_id)
            frame = wire.splice_id_auth(
                payload, self._next_id, self._secret
            )
            if len(frame) > MAX_FRAME:
                raise ValueError(
                    f"request of {len(frame)}B exceeds {MAX_FRAME}B"
                )
            out += struct.pack("<I", len(frame)) + frame
        accepted: List[bool] = []
        try:
            self._sock.sendall(out)
            for want_id in ids:
                frame = _recv_frame(self._sock)
                if frame is None:
                    raise ConnectionError("engine closed the connection")
                resp = json.loads(frame.decode())
                if resp.get("id") != want_id:
                    raise ConnectionError(
                        f"response id {resp.get('id')} != request id "
                        f"{want_id}"
                    )
                if not resp.get("ok"):
                    raise RuntimeError(
                        f"shuffle push rejected: {resp.get('error', '')}"
                    )
                accepted.append(bool(resp.get("accepted")))
        except Exception:
            # transport loss, id desync, OR an engine-side rejection
            # mid-batch (replies for the rest of the batch are still
            # queued on the stream): poison the connection so stale
            # replies can never correlate to later requests
            self._dead = True
            self._sock.close()
            raise
        return accepted

    def delta_sync_encoded(self, payload: bytes) -> int:
        """Ship one pre-encoded binary delta-sync frame
        (storage/delta.py encode_entry_frames); returns the worker's
        acked seq. The correlation id and auth splice in at the byte
        level — the delta data plane serializes each entry exactly
        once, like the shuffle push path."""
        if self._dead:
            raise ConnectionError("engine connection is poisoned; reconnect")
        self._next_id += 1
        frame = wire.splice_id_auth(payload, self._next_id, self._secret)
        resp = self._roundtrip(frame)
        if not resp.get("ok"):
            raise RuntimeError(
                f"delta sync rejected: {resp.get('error', '')}"
            )
        return int(resp.get("acked", 0))

    def execute_plan(
        self, plan, schema_version: Optional[int] = None, frag=None,
        snap=None,
    ) -> Tuple[List[str], List[tuple]]:
        cols, rows, _resp = self.execute_plan_full(
            plan, schema_version=schema_version, frag=frag, snap=snap
        )
        return cols, rows

    def execute_plan_full(
        self, plan, schema_version: Optional[int] = None, frag=None,
        snap=None,
    ) -> Tuple[List[str], List[tuple], dict]:
        """execute_plan plus the raw response — fragment dispatches read
        the worker's span list and runtime stats out of it. ``snap``
        (the routed snapshot: pinned base versions + delta fold/seq)
        rides every dispatch of one query so all its fragments read
        one consistent base."""
        req = {"v": IR_VERSION, "plan": plan_to_ir(plan)}
        if schema_version is not None:
            req["schema_v"] = int(schema_version)
        if snap is not None:
            req["snap"] = snap
        if frag is not None:
            # fragment metadata (query id / fragment id / attempt): the
            # trace context — echoed in the response for the
            # coordinator's ledger, labels the worker's spans, and is
            # visible to the worker-side dcn/* failpoints
            req["frag"] = frag
        resp = self._call(req)
        if not resp.get("ok"):
            err = str(resp.get("error", ""))
            if resp.get("cancelled"):
                # deliberate worker-side abort (fleet cancel /
                # propagated deadline): typed so the scheduler treats
                # it as a kill, never an engine error or a death
                raise QueryCancelled(err)
            if "SchemaOutOfDateError" in err:
                raise SchemaOutOfDateError(err)
            raise RuntimeError(f"engine error: {err}")
        return resp["columns"], [tuple(r) for r in resp["rows"]], resp

    def close(self) -> None:
        self._dead = True
        self._sock.close()
