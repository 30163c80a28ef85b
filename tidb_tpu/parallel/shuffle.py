"""Worker-to-worker DCN shuffle service: the cross-host data plane.

Reference: ExchangeSender/ExchangeReceiver with HashPartition over
MPPDataPacket tunnels (pkg/planner/core/physical_plans.go:1706,
unistore cophandler/mpp_exec.go:597,711) — MPP peers exchange
hash-partitioned chunks DIRECTLY; the coordinator only orchestrates.
PR 1's scheduler staged every inter-host byte through the coordinator
(fine for partial-agg shapes, the wrong cost model for shuffle joins
where neither side is small — ROADMAP; Flare arXiv:1703.08219 and
"Enhancing Computation Pushdown" arXiv:2312.15405 reach the same
conclusion for cloud OLAP pushdown).

This module generalizes the intra-host ICI collectives
(parallel/exchange.py hash_repartition / partition_of with the
`_mix_hash` finalizer) to the DCN tier so the two compose
hierarchically: within a host, rows move over the device mesh's
all_to_all; between hosts, the SAME hash (int keys run the identical
64-bit mix) routes binary columnar frames (parallel/wire.py) over
engine-RPC tunnels (server/engine_rpc.py `shuffle_push` frames). The
producer hashes whole key COLUMNS as numpy arrays and np.takes each
column by partition — HostColumn in, HostColumn out, no Python row
tuples on the hot path; the JSON row-packet codec of PR 3 survives
only as the mixed-version / `shuffle_codec=json` fallback
(partition_rows + _send_stream below).

Pieces, worker side:
- ShuffleStore  — receiver state per (stage, attempt): packet streams
  keyed (side, sender) with per-(fragment, partition, attempt) fences.
  A packet from a superseded attempt is dropped (the stage restarted on
  a survivor set); a duplicate sequence number within an attempt is
  dropped (a retransmit after an ack loss) — the exactly-once
  FragmentLedger discipline (dxf/framework.fence_accepts) applied to
  the data plane, so a re-dispatched fragment never double-delivers.
- PeerTunnel    — sender per peer: a bounded-bytes in-flight window
  (producers block when the window fills: backpressure, counted as
  tunnel stalls), a background sender thread, reconnect + retransmit
  on transport loss (receiver-side dedupe makes retransmit safe).
- ShuffleWorker — one dispatched shuffle task: execute producer side
  plans (SPMD on the local mesh), bucketize rows by key, push
  partitions to peers, wait for the peers' pushes, substitute the
  received partitions for the plan's ShuffleRead leaves, execute the
  consumer plan, reply to the coordinator.

Coordinator-side stage orchestration (tunnel wiring, whole-stage retry
onto the survivor set after a peer death) lives in parallel/dcn.py.

Failpoint sites: shuffle/open, shuffle/recv, shuffle/recv-ack-lost
(server/engine_rpc.py), shuffle/produce, shuffle/push,
shuffle/push-lost, shuffle/wait, shuffle/consume (worker, here) and
shuffle/stage, shuffle/stage-retry (coordinator, parallel/dcn.py).
"""

from __future__ import annotations

import collections
import hashlib
import itertools
import json
import struct
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from tidb_tpu.obs import profiler as topsql
from tidb_tpu.utils import racecheck
from tidb_tpu.utils.failpoint import inject
from tidb_tpu.utils.metrics import REGISTRY

#: receiver cap on concurrently-buffered stages (a runaway backstop,
#: not a working set: completed stages are discarded by run_task as
#: soon as their partition is consumed, so only in-flight queries
#: occupy the window)
_MAX_STAGES = 64

#: default tunnel flow-control window (bytes in flight per peer) and
#: packet granularity; the coordinator can override per stage
DEFAULT_INFLIGHT_BYTES = 4 << 20
DEFAULT_PACKET_ROWS = 2048
#: pipelined producer sub-slices per side (Scan.frag arithmetic):
#: chunk k of n re-frags (i, m) -> (i + k*m, n*m), so encode+push+peer
#: decode of chunk k overlap the device produce of chunk k+1 — the
#: exchange tail after the LAST produce shrinks to one chunk. 2 is the
#: measured sweet spot on CPU dryruns (higher counts starve the
#: shipper thread of the GIL during the rapid-fire sub-dispatches);
#: raise it on real hardware where produce is device-bound.
DEFAULT_PRODUCE_CHUNKS = 2
#: transport retries per packet before the peer is declared dead
PUSH_RETRIES = 3
#: staged-batch nonces for every ShuffleWorker in this process
#: (disjoint from dcn.py's 1<<20 and streamed.py's ranges): shared so
#: two in-process workers can never mint the same nonce — non-keyed
#: Staged plans fingerprint on the nonce alone
_STAGE_NONCES = itertools.count(1 << 24)


# -- telemetry (tidbtpu_shuffle_*) ------------------------------------------


def _c_bytes():
    return REGISTRY.counter(
        "tidbtpu_shuffle_bytes_total",
        "row-packet bytes pushed over worker-to-worker tunnels",
        labels=("src", "dst"),
    )


def _c_rows():
    return REGISTRY.counter(
        "tidbtpu_shuffle_rows_total",
        "rows pushed over worker-to-worker tunnels",
        labels=("src", "dst"),
    )


def _c_stalls():
    return REGISTRY.counter(
        "tidbtpu_shuffle_tunnel_stalls",
        "sends that blocked on the per-peer in-flight byte window",
        labels=("dst",),
    )


def _c_retransmits():
    return REGISTRY.counter(
        "tidbtpu_shuffle_retransmits",
        "packets retransmitted after a tunnel transport loss",
    )


def _c_stale():
    return REGISTRY.counter(
        "tidbtpu_shuffle_stale_dropped",
        "packets fenced out for carrying a superseded stage attempt",
    )


def _c_dups():
    return REGISTRY.counter(
        "tidbtpu_shuffle_duplicates_dropped",
        "duplicate-sequence packets dropped by the receiver dedupe",
    )


def _c_codec_bytes():
    return REGISTRY.counter(
        "tidbtpu_shuffle_codec_bytes",
        "shuffle packet bytes encoded, by wire codec",
        labels=("codec",),
    )


def _c_encode_seconds():
    return REGISTRY.counter(
        "tidbtpu_shuffle_encode_seconds",
        "producer-side packet encode time, by wire codec",
        labels=("codec",),
    )


def _c_decode_seconds():
    return REGISTRY.counter(
        "tidbtpu_shuffle_decode_seconds",
        "receiver-side packet decode time, by wire codec",
        labels=("codec",),
    )


def _c_wait_idle_seconds():
    return REGISTRY.counter(
        "tidbtpu_shuffle_wait_idle_seconds",
        "seconds consumers spent blocked in ShuffleStore waits with "
        "no stream work left to overlap (the barrier cost pipelining "
        "attacks)",
    )


def _c_decode_on_arrival_seconds():
    return REGISTRY.counter(
        "tidbtpu_shuffle_decode_on_arrival_seconds",
        "binary frame decode time spent in the push handler as frames "
        "land (overlapping the producers still in flight), after the "
        "header-only fence check admitted the frame",
    )


def _h_ttff():
    return REGISTRY.histogram(
        "tidbtpu_shuffle_time_to_first_frame_seconds",
        "stage-open to first data frame per (side, sender) stream — "
        "low when producers ship chunk-granularly instead of after the "
        "whole side materializes",
    )


def _c_filter_built():
    return REGISTRY.counter(
        "tidbtpu_shuffle_filter_built_total",
        "runtime filters built from probe-cached build sides, by kind "
        "(bloom / inlist — ISSUE 19 sideways information passing)",
        labels=("kind",),
    )


def _c_filter_bytes():
    return REGISTRY.counter(
        "tidbtpu_shuffle_filter_bytes",
        "runtime filter payload bytes shipped coordinator-ward in "
        "probe replies (the build+ship cost side of the rf cost model)",
    )


def _c_filter_dropped():
    return REGISTRY.counter(
        "tidbtpu_shuffle_filter_dropped_rows_total",
        "probe-side rows dropped by a runtime filter BEFORE "
        "partitioning and encoding (never shipped, never staged)",
    )


def _g_stages_buffered():
    return REGISTRY.gauge(
        "tidbtpu_shuffle_stages_buffered",
        "shuffle stages concurrently buffered in this worker's store — "
        "the serving tier's per-worker concurrency signal (each "
        "in-flight query contributes its own sid-keyed stage)",
    )


# -- host-side hash partitioning --------------------------------------------
#
# The same 64-bit finalizer as parallel/exchange._mix_hash so the two
# shuffle tiers compose: numpy int64 arithmetic has the identical
# wraparound-multiply and arithmetic-shift semantics as the jnp version
# (parity is unit-tested in tests/test_shuffle.py).

_MIX1 = np.int64(-7046029254386353131)  # 0x9E3779B97F4A7C15 as signed
_MIX2 = np.int64(-4658895280553007687)  # 0xBF58476D1CE4E5B9 as signed


def mix_hash_np(x: np.ndarray) -> np.ndarray:
    """exchange._mix_hash over a host numpy int64 array."""
    with np.errstate(over="ignore"):
        h = x.astype(np.int64) * _MIX1
        h = h ^ (h >> 29)
        h = h * _MIX2
        h = h ^ (h >> 32)
    return h & np.int64(0x7FFFFFFFFFFFFFFF)


def _key_to_int(v) -> Optional[int]:
    """Stable int64 image of one key value, identical across worker
    processes (python hash() is salted per process and MUST not be
    used here — two producers disagreeing on a partition would split a
    join key across hosts). None stays None (NULL keys colocate on
    partition 0, like exchange.partition_of)."""
    if v is None:
        return None
    if isinstance(v, bool):
        return int(v)
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, float):
        if v == 0.0:
            v = 0.0  # -0.0 == 0.0 must land together
        if float(v).is_integer() and abs(v) < 2 ** 62:
            return int(v)  # decimal keys decode to integral floats
        (bits,) = struct.unpack("<q", struct.pack("<d", float(v)))
        return bits
    if isinstance(v, str):
        d = hashlib.blake2b(v.encode(), digest_size=8).digest()
        return int.from_bytes(d, "little", signed=True)
    d = hashlib.blake2b(repr(v).encode(), digest_size=8).digest()
    return int.from_bytes(d, "little", signed=True)


def partition_rows(
    rows: List[tuple], key_idx: int, n: int
) -> List[List[tuple]]:
    """Split materialized rows into n hash partitions on column
    `key_idx`. Equal keys always land in one partition; NULL keys all
    go to partition 0 (one group / never match in joins, but must
    colocate) — the host tier of exchange.partition_of."""
    ints = [_key_to_int(r[key_idx]) for r in rows]
    out: List[List[tuple]] = [[] for _ in range(n)]
    if not rows:
        return out
    arr = np.array([0 if i is None else i for i in ints], dtype=np.int64)
    parts = mix_hash_np(arr) % np.int64(n)
    for r, i, p in zip(rows, ints, parts):
        out[0 if i is None else int(p)].append(r)
    return out


# -- receiver: the tunnel endpoint ------------------------------------------


class ShuffleWaitTimeout(TimeoutError):
    def __init__(self, missing: List[str]):
        super().__init__(f"shuffle wait timed out; missing {missing}")
        self.missing = missing


class WaitInterrupted(Exception):
    """wait_side's abort() callback fired: the caller's own producer
    ship failed while the consumer was already waiting (the pipelined
    task overlaps the two), so the wait must hand control back for the
    ship error to surface instead of idling to the stage deadline."""


class _Stream:
    """One (side, sender) packet stream within a stage attempt."""

    __slots__ = ("seqs", "nseq")

    def __init__(self):
        self.seqs: Dict[int, list] = {}
        self.nseq: Optional[int] = None

    def complete(self) -> bool:
        return self.nseq is not None and len(self.seqs) >= self.nseq


class _Stage:
    __slots__ = (
        "attempt", "m", "streams", "waiters", "opened_at", "ttff",
        "vocab",
    )

    def __init__(self, attempt: int, m: int):
        self.attempt = attempt
        self.m = m
        self.streams: Dict[Tuple[int, int], _Stream] = {}
        #: consumer threads blocked in wait() on this stage — never
        #: evict under a waiter's feet
        self.waiters = 0
        self.opened_at = time.monotonic()
        #: (side, sender) -> seconds from stage open to the stream's
        #: first data frame (the pipelining signal: chunk-granular
        #: producers push early, whole-side producers push late)
        self.ttff: Dict[Tuple[int, int], float] = {}
        #: (side, colname) -> running union of string-dictionary
        #: entries, folded in as columnar frames LAND — by the time a
        #: side completes, its unified stage dictionary is one sort
        #: away instead of a full re-scan of every buffered chunk
        self.vocab: Dict[Tuple[int, str], set] = {}


class ShuffleStore:
    """Worker-side receive buffer for pushed shuffle partitions.

    Fencing rules (the FragmentLedger pattern on the data plane):
    - a packet whose attempt is OLDER than the stage's current attempt
      is dropped (the coordinator restarted the stage on a survivor
      set; the old partition map no longer applies);
    - a packet whose attempt is NEWER resets the stage (pushes from a
      fast peer may precede this worker's own task dispatch);
    - within an attempt, a duplicate (side, sender, seq) is dropped —
      retransmits after an ack loss land exactly once.

    Per-QUERY isolation under the concurrent serving tier (PR 8
    audit): stages key on the coordinator's sid, which embeds a
    strictly-unique qid (serving.QidAllocator) under a per-coordinator
    uuid prefix — two concurrent queries (even the same SQL from two
    sessions) can never share a stage record, so a frame admits into
    exactly the stage its producer was dispatched for. The eviction
    window keeps actively-waited stages pinned (waiters counter), so K
    concurrent queries occupy K stage records and complete
    independently; tests/test_race.py hammers K distinct concurrent
    queries through one in-process fleet asserting per-query parity
    and zero stale/duplicate admits.
    """

    #: poisoned-sid memory (cancelled queries): bounded — sids are
    #: strictly unique, so an aged-out entry can only matter if a peer
    #: still pushes a >256-queries-old cancelled stage, which the
    #: eviction window then bounds anyway
    _POISON_CAP = 256

    def __init__(self):
        self._cv = racecheck.make_condition("shuffle.store")
        self._stages: "collections.OrderedDict[str, _Stage]" = (
            collections.OrderedDict()
        )
        self._poisoned: "collections.OrderedDict[str, bool]" = (
            collections.OrderedDict()
        )

    def poison(self, sid: str) -> None:
        """Cancel one stage FOR GOOD: drop its buffered frames and
        refuse to recreate its record — in-flight frames from peers
        that have not yet observed the cancellation land as fenced
        stale drops instead of resurrecting an orphan stage, so the
        buffered-stages gauge returns to zero immediately (the
        fleet-cancellation abort path)."""
        with self._cv:
            self._stages.pop(sid, None)
            self._poisoned[sid] = True
            while len(self._poisoned) > self._POISON_CAP:
                self._poisoned.popitem(last=False)
            _g_stages_buffered().set(len(self._stages))
            self._cv.notify_all()

    def buffered_stages(self) -> int:
        with self._cv:
            return len(self._stages)

    def _stage(self, sid: str, attempt: int, m: int) -> Optional[_Stage]:
        """Stage record for (sid, attempt), fencing stale attempts and
        poisoned (cancelled) sids. Caller holds the condition lock."""
        if sid in self._poisoned:
            return None  # callers count the drop (stale fence)
        st = self._stages.get(sid)
        if st is None or attempt > st.attempt:
            st = _Stage(attempt, m)
            self._stages[sid] = st
            if len(self._stages) > _MAX_STAGES:
                # evict oldest WAITER-FREE stages only: dropping a
                # stage whose consumer is blocked in wait() would fail
                # a query on healthy hosts. With every stage actively
                # waited the map simply grows past the cap (bounded by
                # the number of concurrent tasks).
                excess = len(self._stages) - _MAX_STAGES
                for old_sid in list(self._stages):
                    if excess <= 0:
                        break
                    if old_sid != sid and self._stages[old_sid].waiters == 0:
                        del self._stages[old_sid]
                        excess -= 1
        elif attempt < st.attempt:
            return None
        # LRU touch on EVERY access: an actively-receiving stage must
        # never age out under concurrent stages — only idle/orphan ones
        self._stages.move_to_end(sid)
        return st

    def open(self, sid: str, attempt: int, m: int) -> None:
        inject("shuffle/open")
        with self._cv:
            self._stage(sid, attempt, m)
            # set under the cv: outside it a lost update with a
            # concurrent open/discard leaves the gauge stale
            _g_stages_buffered().set(len(self._stages))

    def discard(self, sid: str) -> None:
        """Drop a stage's buffered rows (called once the consumer has
        read its partition — a retry would run under a NEW attempt,
        which resets the stage anyway, so nothing ever re-reads this
        data). Late peer pushes simply recreate an orphan record that
        ages out of the window."""
        with self._cv:
            self._stages.pop(sid, None)
            _g_stages_buffered().set(len(self._stages))

    def push(
        self,
        sid: str,
        attempt: int,
        m: int,
        side: int,
        sender: int,
        seq: int,
        payload,
        nseq: Optional[int] = None,
    ) -> bool:
        """Land one packet; returns False when fenced (stale attempt)
        or deduped (duplicate seq). `payload` is codec-shaped: a list
        of row tuples (JSON packets) or a decoded columnar HostBlock
        (binary frames) — the store buffers it opaquely and the
        consumer normalizes at staging time, so one stream can even mix
        codecs across senders (mixed-version peers). An EOF packet
        carries payload=None and nseq=<total data packets>."""
        with self._cv:
            st = self._stage(sid, attempt, m)
            if st is None:
                _c_stale().inc()
                return False
            stream = st.streams.setdefault((side, sender), _Stream())
            if payload is None:  # EOF marker — idempotent
                stream.nseq = int(nseq)
                self._cv.notify_all()
                return True
            if seq in stream.seqs:
                _c_dups().inc()
                return False
            stream.seqs[int(seq)] = payload
            if (side, sender) not in st.ttff:
                dt = time.monotonic() - st.opened_at
                st.ttff[(side, sender)] = dt
                _h_ttff().observe(dt)
            cols = getattr(payload, "columns", None)
            if cols is not None:
                # columnar frame: fold its (pruned) string dictionaries
                # into the side's running vocabulary NOW, while other
                # streams are still in flight — incremental staging
                # then unifies with one sort instead of re-walking
                # every buffered chunk after the wait
                for name, col in cols.items():
                    if col.dictionary is not None:
                        st.vocab.setdefault((side, name), set()).update(
                            col.dictionary.tolist()
                        )
            self._cv.notify_all()
            return True

    def admits(
        self, sid: str, attempt: int, side: int, sender: int, seq: int
    ) -> bool:
        """Header-only fence pre-check: would a data frame with this
        route land? False for a superseded attempt or a duplicate seq
        (counted like the push-time fences) — the receive handler asks
        this from decode_header output BEFORE spending decode work on
        the column payload. Purely advisory: push() re-applies the
        fences authoritatively, so a race between two identical
        retransmits still lands exactly once."""
        with self._cv:
            if sid in self._poisoned:
                _c_stale().inc()  # cancelled stage: drop before decode
                return False
            st = self._stages.get(sid)
            if st is None or attempt > st.attempt:
                return True  # new stage / newer attempt: will reset
            if attempt < st.attempt:
                _c_stale().inc()
                return False
            stream = st.streams.get((side, sender))
            if stream is not None and seq in stream.seqs:
                _c_dups().inc()
                return False
            return True

    @staticmethod
    def _senders_of(senders, side, m) -> List[int]:
        """Expected sender set for one side: all m peers unless the
        stage declared otherwise (a "local"-mode side only ever has
        its own host's stream; a broadcast side still has all m)."""
        if senders is None:
            return list(range(m))
        return list(senders.get(side, range(m)))

    def wait(
        self,
        sid: str,
        attempt: int,
        n_sides: int,
        m: int,
        timeout_s: float,
        abort=None,
        senders=None,
    ) -> Dict[int, list]:
        """Block until every (side, sender) stream of the attempt is
        complete; returns side -> payload chunks ordered (sender, seq)
        — a deterministic concatenation order, so per-partition
        execution is reproducible across retries. Raises
        ShuffleWaitTimeout with the missing senders (the coordinator's
        death-suspect list). ``senders`` optionally narrows the
        expected sender set per side (local-mode DAG edges)."""
        inject("shuffle/wait")
        deadline = time.monotonic() + timeout_s

        def missing() -> List[str]:
            st = self._stages.get(sid)
            out = []
            for side in range(n_sides):
                for sender in self._senders_of(senders, side, m):
                    stream = (
                        st.streams.get((side, sender))
                        if st is not None and st.attempt == attempt
                        else None
                    )
                    if stream is None or not stream.complete():
                        out.append(f"side{side}/sender{sender}")
            return out

        with self._cv:
            # pin the stage for the duration of the wait: eviction
            # skips stages with active waiters. pin is None when this
            # attempt is already superseded (the wait can only time
            # out); identity-compare on release — a newer attempt may
            # have replaced the record mid-wait.
            pin = self._stage(sid, attempt, m)
            if pin is not None:
                pin.waiters += 1
            try:
                while True:
                    gone = missing()
                    if not gone:
                        break
                    if abort is not None and abort():
                        # same contract as wait_side: a truthy abort
                        # hands control back (a raising abort — the
                        # fleet-cancel check — propagates directly)
                        raise WaitInterrupted()
                    left = deadline - time.monotonic()
                    if left <= 0:
                        raise ShuffleWaitTimeout(gone)
                    self._cv.wait(min(left, 0.25))
            finally:
                if pin is not None and self._stages.get(sid) is pin:
                    pin.waiters -= 1
            st = self._stages[sid]
            out: Dict[int, list] = {}
            for side in range(n_sides):
                chunks: list = []
                for sender in self._senders_of(senders, side, m):
                    stream = st.streams[(side, sender)]
                    for seq in range(stream.nseq):
                        chunks.append(stream.seqs[seq])
                out[side] = chunks
            return out

    def _side_complete(self, st: Optional[_Stage], attempt, side, m,
                       senders=None):
        if st is None or st.attempt != attempt:
            return False
        for sender in self._senders_of(senders, side, m):
            stream = st.streams.get((side, sender))
            if stream is None or not stream.complete():
                return False
        return True

    def wait_side(
        self,
        sid: str,
        attempt: int,
        pending: List[int],
        m: int,
        deadline: float,
        abort=None,
        senders=None,
    ) -> Tuple[int, list, Dict[str, set]]:
        """Block until ANY side in ``pending`` has all m streams
        complete; returns (side, payload chunks ordered (sender, seq),
        that side's running string vocabularies) — the pipelined
        consumer stages each side the moment it finishes while the
        other side is still in flight, instead of barriering on the
        whole stage like wait(). ``deadline`` is absolute
        (time.monotonic); on expiry raises ShuffleWaitTimeout naming
        every missing stream across the still-pending sides."""
        inject("shuffle/wait")
        with self._cv:
            pin = self._stage(sid, attempt, m)
            if pin is not None:
                pin.waiters += 1
            try:
                while True:
                    st = self._stages.get(sid)
                    for side in pending:
                        if self._side_complete(
                            st, attempt, side, m, senders
                        ):
                            chunks: list = []
                            for sender in self._senders_of(
                                senders, side, m
                            ):
                                stream = st.streams[(side, sender)]
                                for seq in range(stream.nseq):
                                    chunks.append(stream.seqs[seq])
                            vocab = {
                                name: set(v)
                                for (s, name), v in st.vocab.items()
                                if s == side
                            }
                            return side, chunks, vocab
                    if abort is not None and abort():
                        raise WaitInterrupted()
                    left = deadline - time.monotonic()
                    if left <= 0:
                        missing = []
                        for side in pending:
                            for sender in self._senders_of(
                                senders, side, m
                            ):
                                stream = (
                                    st.streams.get((side, sender))
                                    if st is not None
                                    and st.attempt == attempt
                                    else None
                                )
                                if stream is None or not stream.complete():
                                    missing.append(
                                        f"side{side}/sender{sender}"
                                    )
                        raise ShuffleWaitTimeout(missing)
                    self._cv.wait(min(left, 0.25))
            finally:
                if pin is not None and self._stages.get(sid) is pin:
                    pin.waiters -= 1

    def max_ttff(self, sid: str) -> float:
        """Largest stream time-to-first-frame of the stage (0.0 when
        nothing landed) — the straggler signal run_task reports."""
        with self._cv:
            st = self._stages.get(sid)
            if st is None or not st.ttff:
                return 0.0
            return max(st.ttff.values())


# -- sender: per-peer tunnel with flow control ------------------------------


class PeerDeadError(ConnectionError):
    """A tunnel gave up on its peer. `fatal` distinguishes an engine-
    side rejection or encoding error (retrying a HEALTHY peer cannot
    fix it — must surface, not retry) from a transport loss (the peer
    is a death suspect and the stage should retry on survivors)."""

    def __init__(self, address: str, cause: Exception, fatal: bool = False):
        super().__init__(f"shuffle peer {address} unreachable: {cause}")
        self.address = address
        self.cause = cause
        self.fatal = fatal


class PeerTunnel:
    """One worker-to-worker tunnel: a background sender thread drains a
    queue of packets over an EngineClient connection; producers block
    when queued-plus-unacked bytes exceed the window (backpressure —
    counted as tunnel stalls). Transport loss reconnects and
    retransmits the packet (the receiver's seq dedupe makes this safe);
    PUSH_RETRIES consecutive failures declare the peer dead."""

    def __init__(
        self,
        host: str,
        port: int,
        secret: Optional[str],
        src: str,
        max_inflight_bytes: int = DEFAULT_INFLIGHT_BYTES,
        timeout_s: float = 30.0,
        batch_packets: int = 64,
    ):
        self.host, self.port, self.secret = host, port, secret
        self.address = f"{host}:{port}"
        self.src = src
        # packets pipelined onto the wire per ack round trip (the
        # byte window bounds the data volume); 1 = strict stop-and-
        # wait, the pre-pipelining wire discipline the pipeline=off
        # escape hatch preserves
        self.batch_packets = max(int(batch_packets), 1)
        self.max_inflight = int(max_inflight_bytes)
        self.timeout_s = timeout_s
        self.bytes_sent = 0
        self.rows_sent = 0
        self.frames_sent = 0
        self.stalls = 0
        #: cumulative seconds producers spent blocked on this tunnel's
        #: byte window (backpressure stall WALL, not just a count —
        #: information_schema.cluster_links reads this per link)
        self.stall_s = 0.0
        #: individual stall windows as (wall_t0, dur_s) — the timeline
        #: tracer's per-link backpressure events (obs/timeline.py).
        #: Bounded; appended only when a stall actually happened, so
        #: the un-stalled hot path never touches it.
        self.stall_windows: List[Tuple[float, float]] = []
        self.retransmits = 0
        self._cv = racecheck.make_condition("shuffle.tunnel")
        self._q: "collections.deque" = collections.deque()
        self._inflight = 0
        self._dead: Optional[Exception] = None
        self._dead_fatal = False
        self._closing = False
        self._client = None
        self._codec: Optional[str] = None
        self._neg_lock = racecheck.make_lock("shuffle.negotiate")
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=f"shuffle-tx-{self.address}"
        )
        self._thread.start()

    def negotiated_codec(self, preferred: str = "binary") -> str:
        """The wire codec this tunnel may use: "binary" when the peer's
        handshake advertises a compatible wire version, else "json"
        (mixed-version peers keep interoperating through the row-packet
        fallback). Negotiated once per tunnel over a throwaway ping
        connection (the sender thread owns the data connection); an
        unreachable peer answers `preferred` — the first real send will
        surface the death through the normal suspect machinery."""
        if preferred != "binary":
            return "json"
        with self._neg_lock:
            if self._codec is None:
                from tidb_tpu.parallel.wire import WIRE_VERSION
                from tidb_tpu.server.engine_rpc import EngineClient

                try:
                    # lock-blocking-ok: the one-shot negotiation probe
                    # deliberately holds the per-tunnel lock across its
                    # throwaway handshake so racing producers get ONE
                    # answer; the lock is tunnel-private and leaf-level
                    c = EngineClient(
                        self.host, self.port, secret=self.secret,
                        timeout_s=min(self.timeout_s, 10.0),
                    )
                    try:
                        # the connect-time handshake already cached the
                        # peer's advertised wire version
                        peer_wire = int(c.server_wire)
                    finally:
                        c.close()
                    # EXACT version match: decode_frame rejects any
                    # other version, so a skewed peer must degrade to
                    # the JSON fallback, not trade unreadable frames
                    self._codec = (
                        "binary" if peer_wire == WIRE_VERSION else "json"
                    )
                except Exception:
                    self._codec = preferred
            return self._codec

    # -- producer side -------------------------------------------------
    def send(self, packet, nbytes: int, nrows: int) -> None:
        """Enqueue one packet: pre-encoded bytes (the hot path — the
        producer serialized it once and the bytes cross the wire
        verbatim) or a plain dict (tests/tools)."""
        with self._cv:
            stalled = False
            stall_t0 = 0.0
            stall_wall0 = 0.0
            while (
                self._dead is None
                and self._inflight + nbytes > self.max_inflight
                and self._inflight > 0
            ):
                if not stalled:
                    stalled = True
                    stall_t0 = time.perf_counter()
                    stall_wall0 = time.time()
                    self.stalls += 1
                    _c_stalls().labels(dst=self.address).inc()
                self._cv.wait(0.05)
            if stalled:
                dt = time.perf_counter() - stall_t0
                self.stall_s += dt
                if len(self.stall_windows) < 256:
                    self.stall_windows.append((stall_wall0, dt))
                from tidb_tpu.obs.flight import _c_link_stall_seconds

                _c_link_stall_seconds().labels(
                    src=self.src, dst=self.address
                ).inc(dt)
            if self._dead is not None:
                raise PeerDeadError(
                    self.address, self._dead, fatal=self._dead_fatal
                )
            self._inflight += nbytes
            self._q.append((packet, nbytes, nrows))
            self._cv.notify_all()

    def flush(self) -> None:
        """Block until every queued packet is acked; raises if the peer
        died mid-stream."""
        with self._cv:
            while self._dead is None and (self._q or self._inflight):
                self._cv.wait(0.05)
            if self._dead is not None:
                raise PeerDeadError(
                    self.address, self._dead, fatal=self._dead_fatal
                )

    def close(self) -> None:
        with self._cv:
            self._closing = True
            self._cv.notify_all()
        self._thread.join(timeout=10)
        if self._client is not None:
            try:
                self._client.close()
            except Exception:
                pass

    # -- sender thread -------------------------------------------------
    def _connect(self):
        from tidb_tpu.server.engine_rpc import EngineClient

        if self._client is None or self._client._dead:
            self._client = EngineClient(
                self.host, self.port, secret=self.secret,
                timeout_s=self.timeout_s,
            )
        return self._client

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._q and not self._closing and self._dead is None:
                    self._cv.wait(0.05)
                if self._dead is not None or (self._closing and not self._q):
                    return
                # take a RUN of pre-encoded packets and pipeline them
                # onto the wire in ONE write + one in-order ack read —
                # a synchronous round trip per packet made the ack
                # latency the dominant serial tail of a push stream.
                # Packets stay queued until acked (retransmit fodder);
                # plain-dict packets (tests/tools) go one at a time.
                batch = []
                encoded = isinstance(self._q[0][0], (bytes, bytearray))
                for item in self._q:
                    if len(batch) >= self.batch_packets:
                        break
                    if isinstance(
                        item[0], (bytes, bytearray)
                    ) != encoded:
                        break
                    batch.append(item)
                    if not encoded:
                        break
            err: Optional[Exception] = None
            fatal = False
            for attempt in range(PUSH_RETRIES):
                try:
                    for _packet, _nb, _nr in batch:
                        inject("shuffle/push")
                        if inject("shuffle/push-lost"):
                            raise ConnectionError(
                                "failpoint: push lost in transit"
                            )
                    client = self._connect()
                    if encoded:
                        # hot path: pre-encoded at enqueue, sent as-is
                        client.shuffle_push_encoded_many(
                            [bytes(p) for p, _nb, _nr in batch]
                        )
                    else:
                        client.shuffle_push(batch[0][0])
                    err = None
                    break
                except (RuntimeError, ValueError, TypeError) as e:
                    # engine-side rejection or an encoding error — NOT
                    # a transport loss: retrying a healthy peer cannot
                    # fix it, and reporting the peer as a death suspect
                    # would send the coordinator chasing a ghost
                    err, fatal = e, True
                    break
                except Exception as e:
                    err = e
                    if self._client is not None:
                        try:
                            self._client.close()
                        except Exception:
                            pass
                        self._client = None
                    if attempt + 1 < PUSH_RETRIES:
                        # the whole unacked batch retransmits; the
                        # receiver's header dedupe lands each exactly
                        # once
                        self.retransmits += len(batch)
                        _c_retransmits().inc(len(batch))
                        from tidb_tpu.obs.flight import _c_link_retransmits

                        _c_link_retransmits().labels(
                            src=self.src, dst=self.address
                        ).inc(len(batch))
                        time.sleep(0.05 * (attempt + 1))
            with self._cv:
                nbytes_acked = nrows_acked = 0
                for _packet, nbytes, nrows in batch:
                    self._q.popleft()
                    self._inflight -= nbytes
                    nbytes_acked += nbytes
                    nrows_acked += nrows
                if err is not None:
                    self._dead = err
                    self._dead_fatal = fatal
                else:
                    self.bytes_sent += nbytes_acked
                    self.rows_sent += nrows_acked
                    self.frames_sent += len(batch)
                    _c_bytes().labels(src=self.src, dst=self.address).inc(
                        nbytes_acked
                    )
                    _c_rows().labels(src=self.src, dst=self.address).inc(
                        nrows_acked
                    )
                    # per-link health family (information_schema.
                    # cluster_links; counters ship to the coordinator
                    # via the piggybacked registry deltas)
                    from tidb_tpu.obs.flight import (
                        _c_link_bytes,
                        _c_link_frames,
                    )

                    _c_link_bytes().labels(
                        src=self.src, dst=self.address
                    ).inc(nbytes_acked)
                    _c_link_frames().labels(
                        src=self.src, dst=self.address
                    ).inc(len(batch))
                self._cv.notify_all()


# -- the dispatched shuffle task --------------------------------------------


def _payload_rows(p) -> int:
    """Row count of one buffered shuffle payload — a columnar
    HostBlock on the binary path, a plain row list on the JSON
    fallback (the per-partition received-rows accounting feeding the
    skew ratio)."""
    n = getattr(p, "nrows", None)
    return int(n) if n is not None else len(p)


class ShuffleAbort(RuntimeError):
    """Retryable stage failure a worker reports to the coordinator:
    dead peers during push, or producers that never delivered before
    the wait deadline. The coordinator verifies the suspects, then
    re-runs the WHOLE stage (new attempt) on the survivor set."""

    def __init__(self, reason: str, suspects: List[str]):
        super().__init__(f"{reason}; suspects={suspects}")
        self.reason = reason
        self.suspects = suspects


def _substitute_reads(plan, staged_by_tag):
    """Replace every ShuffleRead leaf with its Staged partition batch."""
    import dataclasses

    from tidb_tpu.planner import logical as L

    if isinstance(plan, L.ShuffleRead):
        return staged_by_tag[plan.tag]
    kw = {}
    for attr in ("child", "left", "right"):
        c = getattr(plan, attr, None)
        if c is not None:
            kw[attr] = _substitute_reads(c, staged_by_tag)
    ch = getattr(plan, "children", None)
    if ch:
        kw["children"] = [_substitute_reads(c, staged_by_tag) for c in ch]
    return dataclasses.replace(plan, **kw) if kw else plan


def _slice_producer(plan, k: int, n_chunks: int):
    """Sub-slice a producer side plan for chunk-granular execution:
    the host's fragment scan ``frag=(i, m)`` (rows i::m) becomes
    ``frag=(i + k*m, n_chunks*m)`` — the k-th of n_chunks disjoint
    sub-slices whose union is exactly the host's slice, pure index
    arithmetic through the existing frag machinery. Returns None when
    the plan is not row-sliceable (anything beyond a scan/filter/
    project chain, or no single frag'd scan): aggregates, sorts and
    joins compute over the WHOLE slice and must not be re-run per
    sub-slice."""
    import dataclasses

    from tidb_tpu.planner import logical as L

    scans = []

    def sliceable(p) -> bool:
        if isinstance(p, L.Scan):
            scans.append(p)
            return p.frag is not None
        if isinstance(p, (L.Selection, L.Projection)):
            return sliceable(p.child)
        return False

    if not sliceable(plan) or len(scans) != 1:
        return None
    i, m = scans[0].frag

    def rewrite(p):
        if isinstance(p, L.Scan):
            return dataclasses.replace(
                p, frag=(i + k * m, n_chunks * m)
            )
        return dataclasses.replace(p, child=rewrite(p.child))

    return rewrite(plan)


def _shuffle_read_tags(plan) -> Dict[int, object]:
    """tag -> ShuffleRead node (the consumer's exchange leaves)."""
    from tidb_tpu.planner import logical as L

    out: Dict[int, object] = {}

    def walk(p):
        if isinstance(p, L.ShuffleRead):
            out[p.tag] = p
            return
        for attr in ("child", "left", "right"):
            c = getattr(p, attr, None)
            if c is not None:
                walk(c)
        for c in getattr(p, "children", []) or []:
            walk(c)

    walk(plan)
    return out


def stage_rows_as_batch(schema, rows: List[tuple], nonce: int, key=None):
    """Materialized rows -> a Staged device batch under `schema` (the
    receiving side of any host-level exchange; shared with the
    coordinator's final stage in parallel/dcn.py). With ``key`` the
    staged batch is a runtime input, so repeated final stages of one
    plan shape reuse the compiled program (L.Staged.key)."""
    from tidb_tpu.chunk import (
        HostBlock,
        block_to_batch,
        column_from_values,
        pad_capacity,
    )
    from tidb_tpu.planner import logical as L

    cols = {}
    dicts = {}
    for i, oc in enumerate(schema.cols):
        hc = column_from_values([r[i] for r in rows], oc.type)
        cols[oc.internal] = hc
        if hc.dictionary is not None:
            dicts[oc.internal] = hc.dictionary
    block = HostBlock(cols, len(rows))
    batch = block_to_batch(block, pad_capacity(max(len(rows), 1)))
    return L.Staged(
        schema, batch=batch, dicts=dicts, nonce=nonce, key=key
    )


def stage_payloads_as_batch(schema, payloads: list, nonce: int, key=None):
    """Received shuffle payload chunks -> a Staged device batch by
    COLUMN CONCATENATION: binary frames arrive as decoded HostBlocks
    whose columns concatenate directly (string dictionaries unified
    into one sorted stage-local table, codes re-keyed — join keys
    comparable across senders and sides); JSON row packets take the
    column_from_values slow path per chunk. No per-row Python loop
    touches columnar chunks."""
    from tidb_tpu.chunk import (
        HostBlock,
        block_to_batch,
        column_from_values,
        concat_host_columns,
        pad_capacity,
    )
    from tidb_tpu.planner import logical as L

    per_col: Dict[str, list] = {oc.internal: [] for oc in schema.cols}
    total = 0
    for pl in payloads:
        if isinstance(pl, HostBlock):
            for oc in schema.cols:
                per_col[oc.internal].append(pl.columns[oc.internal])
            total += pl.nrows
        else:  # JSON row packet — the declared fallback's row loop
            for i, oc in enumerate(schema.cols):
                per_col[oc.internal].append(
                    column_from_values([r[i] for r in pl], oc.type)
                )
            total += len(pl)
    cols = {}
    dicts = {}
    for oc in schema.cols:
        hc = concat_host_columns(oc.type, per_col[oc.internal])
        cols[oc.internal] = hc
        if hc.dictionary is not None:
            dicts[oc.internal] = hc.dictionary
    block = HostBlock(cols, total)
    batch = block_to_batch(block, pad_capacity(max(total, 1)))
    return L.Staged(
        schema, batch=batch, dicts=dicts, nonce=nonce, key=key
    )


def stage_payloads_incremental(
    schema, payloads: list, nonce: int, vocab=None, key=None
):
    """Received shuffle payload chunks -> a Staged device batch with
    each output column WRITTEN ONCE (ROADMAP PR 4 item a): the final
    buffers are allocated at tile capacity up front (row counts are
    known from the received frames) and every chunk writes its slice
    directly — no concat-then-pad double copy, no np.concatenate.
    String dictionaries come pre-unioned from the store's running
    per-side vocabularies (``vocab``, folded in as frames ARRIVED), so
    staging sorts once and remaps codes per chunk. JSON row packets
    (mixed-codec peers) normalize per chunk through column_from_values
    — the declared fallback's slow path — contributing their own
    dictionary entries to the union."""
    from tidb_tpu.chunk import (
        HostBlock,
        HostColumn,
        batch_from_padded,
        column_from_values,
        pad_capacity,
    )
    from tidb_tpu.dtypes import Kind
    from tidb_tpu.planner import logical as L

    vocab = {k: set(v) for k, v in (vocab or {}).items()}
    blocks: list = []
    for pl in payloads:
        if isinstance(pl, HostBlock):
            # fold any dictionary entries the running vocab missed
            # (payloads landed via ShuffleStore.push already folded
            # theirs on arrival — these unions are then no-ops over
            # the per-chunk pruned dictionaries, not a row-data scan)
            for cname, col in pl.columns.items():
                if col.dictionary is not None:
                    vocab.setdefault(cname, set()).update(
                        col.dictionary.tolist()
                    )
            blocks.append(pl)
            continue
        cols = {}
        for i, oc in enumerate(schema.cols):
            hc = column_from_values([r[i] for r in pl], oc.type)
            cols[oc.internal] = hc
            if hc.dictionary is not None:
                vocab.setdefault(oc.internal, set()).update(
                    hc.dictionary.tolist()
                )
        blocks.append(HostBlock(cols, len(pl)))
    total = sum(b.nrows for b in blocks)
    cap = pad_capacity(max(total, 1))
    out_cols = {}
    dicts = {}
    for oc in schema.cols:
        name = oc.internal
        valid = np.zeros(cap, dtype=bool)
        if oc.type.kind == Kind.STRING:
            unified = np.array(
                sorted(str(v) for v in vocab.get(name, set())),
                dtype=object,
            )
            lut = {v: i for i, v in enumerate(unified.tolist())}
            data = np.zeros(cap, dtype=np.int32)
            off = 0
            for b in blocks:
                c, n = b.columns[name], b.nrows
                if n:
                    cvalid = np.asarray(c.valid, dtype=bool)
                    if c.dictionary is not None and len(c.dictionary):
                        mapping = np.array(
                            [lut[str(v)] for v in c.dictionary.tolist()],
                            dtype=np.int32,
                        )
                        codes = mapping[
                            np.clip(
                                np.asarray(c.data), 0,
                                len(c.dictionary) - 1,
                            )
                        ]
                    else:
                        codes = np.zeros(n, dtype=np.int32)
                    data[off : off + n] = np.where(cvalid, codes, 0)
                    valid[off : off + n] = cvalid
                off += n
            out_cols[name] = HostColumn(oc.type, data, valid, unified)
            dicts[name] = unified
            continue
        dtype = oc.type.np_dtype
        data = np.zeros(cap, dtype=dtype)
        off = 0
        for b in blocks:
            c, n = b.columns[name], b.nrows
            if n:
                data[off : off + n] = np.asarray(c.data, dtype=dtype)
                valid[off : off + n] = np.asarray(c.valid, dtype=bool)
            off += n
        out_cols[name] = HostColumn(oc.type, data, valid)
    batch = batch_from_padded(out_cols, total)
    return L.Staged(
        schema, batch=batch, dicts=dicts, nonce=nonce, key=key
    )


class ShuffleWorker:
    """Executes one dispatched shuffle task on a worker host. One
    instance per EngineServer; holds the receive store (tunnel
    endpoint) the server's `shuffle_push` frames land in."""

    def __init__(self, catalog, self_address: str = "?", mesh_devices=None,
                 delta_state=None):
        self.catalog = catalog
        self.store = ShuffleStore()
        self.self_address = self_address
        self.mesh_devices = mesh_devices
        # HTAP delta replica state of the owning EngineServer (None on
        # shared-catalog servers): producer plans resolve their routed
        # snapshot against it (storage/delta.py prepare_worker_plan)
        self.delta_state = delta_state
        # PROCESS-wide nonce stream (disjoint from dcn.py's and
        # streamed.py's): nonce-staged plans fingerprint on the nonce
        # alone, so two in-process workers minting from per-instance
        # counters would collide in any process-scoped cache
        self._nonce = _STAGE_NONCES
        # executors persist across tasks so producer plans compile once
        # per (plan, slice) instead of once per dispatch; their plan
        # caches are not thread-safe, so executor phases serialize on
        # this lock (tunnel pushes and the store wait still overlap)
        self._exec_lock = racecheck.make_rlock("shuffle.exec")
        self._producer_exec = None
        self._consumer_exec = None
        # shuffle-DAG held state: (coord, qid, attempt, stage, tag) ->
        # HostBlock. tag=None entries are CONSUMER outputs held between
        # stages (stage N's partition feeds stage N+1's StageInput);
        # tag>=0 entries are range-side produce blocks cached by the
        # sampling round so the stage round ships without re-executing
        # the producer. Pruned when a newer attempt's stage-0 task
        # arrives, when the last stage releases, on cancel, and by the
        # bounded-cap backstop.
        self._held_lock = racecheck.make_lock("shuffle.held")
        self._held: "collections.OrderedDict" = collections.OrderedDict()

    _HELD_CAP = 128

    def _held_put(self, coord, qid, attempt, stage, tag, block) -> None:
        with self._held_lock:
            self._held[(coord, qid, int(attempt), int(stage), tag)] = block
            while len(self._held) > self._HELD_CAP:
                self._held.popitem(last=False)

    def _held_get(self, coord, qid, attempt, stage, tag):
        """Peek (entries live until release/prune: the sampling round
        and the stage round both read the same cached block)."""
        with self._held_lock:
            return self._held.get(
                (coord, qid, int(attempt), int(stage), tag)
            )

    def _held_prune(self, coord, qid, before_attempt=None) -> None:
        """Drop held state for one query — everything (release /
        cancel), or only attempts older than ``before_attempt`` (a
        retried DAG restarts from stage 0; the superseded attempt's
        partitions must not satisfy the new attempt's StageInputs)."""
        with self._held_lock:
            for k in list(self._held):
                if k[0] != coord or k[1] != qid:
                    continue
                if before_attempt is None or k[2] < int(before_attempt):
                    del self._held[k]

    def held_count(self) -> int:
        """Held DAG blocks on this worker (engine_status introspection;
        must drain to zero after a completed or cancelled DAG — the
        chaos harness's held-leak invariant)."""
        with self._held_lock:
            return len(self._held)

    def _side_input_block(self, spec, side, plan, cancel_check=None):
        """The producer input of one DAG side as a complete HostBlock:
        a StageInput leaf reads the held output of an earlier stage
        (missing = this worker restarted mid-DAG -> retryable abort),
        a leaf plan prefers the sampling round's cached produce and
        executes the plan otherwise."""
        from tidb_tpu.chunk import batch_to_block
        from tidb_tpu.planner import logical as L
        from tidb_tpu.planner.physical import PhysicalExecutor

        coord, qid = spec.get("coord"), spec.get("qid")
        attempt, stage = int(spec["attempt"]), int(spec.get("stage", 0))
        tag = int(side["tag"])
        if isinstance(plan, L.StageInput):
            # the mid-DAG re-staging seam (and the worker-kill-between-
            # stages chaos site): stage N's held partition becomes
            # stage N+1's already-sliced producer input — no re-scan
            inject("shuffle/stage-input")
            blk = self._held_get(coord, qid, attempt, plan.stage, None)
            if blk is None:
                raise ShuffleAbort(
                    f"held output of stage {plan.stage} missing "
                    f"(worker restarted mid-DAG?)", [],
                )
            return blk
        blk = self._held_get(coord, qid, attempt, stage, tag)
        if blk is not None:
            return blk
        if cancel_check is not None:
            cancel_check()
        with self._exec_lock:
            if self._producer_exec is None:
                self._producer_exec = PhysicalExecutor(
                    self.catalog, mesh_devices=self.mesh_devices
                )
            batch, dicts = self._run_producer(
                self._producer_exec, plan, side.get("_snap_hook"),
                bool(side.get("_snap_merged")),
            )
            types = {c.internal: c.type for c in plan.schema.cols}
            return batch_to_block(batch, types, dicts)

    def _apply_snap(self, spec, side, plan, pins):
        """Apply the dispatch's routed snapshot to one producer side:
        pin the base versions, rewrite the plan to merge this replica's
        buffered deltas, and stash the resolver hook on the side spec
        for the run sites. No-op without a snapshot."""
        snap = spec.get("snap")
        if not snap:
            return plan
        from tidb_tpu.storage import delta as _delta

        plan2, hook, stats = _delta.prepare_worker_plan(
            self.catalog, self.delta_state, plan, snap, pins
        )
        side["_snap_hook"] = hook
        side["_snap_merged"] = stats is not None
        return plan2

    def _run_producer(self, exec_, plan, hook, merged):
        """One producer-plan execution under the exec lock with the
        snapshot resolver installed. Delta-merged plans mix sharded
        scans with replicated Staged leaves — they run on a plain
        (single-device) executor; the SPMD mesh program is a scan
        throughput optimization, not a correctness requirement."""
        from tidb_tpu.planner.physical import PhysicalExecutor

        with self._exec_lock:
            if merged and self.mesh_devices:
                if getattr(self, "_producer_plain", None) is None:
                    self._producer_plain = PhysicalExecutor(self.catalog)
                exec_ = self._producer_plain
            if hook is not None:
                exec_.table_hook = hook
            try:
                return exec_.run(plan)
            finally:
                exec_.table_hook = None

    def run_sample(self, spec: dict, cancel_check=None) -> dict:
        """Boundary-sampling round of a range exchange stage: produce
        (or read) this worker's side input, CACHE it for the stage
        round (the produce runs once, not twice), and return a
        deterministic sample of the partition key for the
        coordinator-merged quantile cut."""
        from tidb_tpu.parallel.wire import sample_range_keys
        from tidb_tpu.planner.ir import plan_from_ir

        inject("shuffle/sample")
        side = spec["side"]
        plan = plan_from_ir(side["plan"])
        pins: list = []
        try:
            plan = self._apply_snap(spec, side, plan, pins)
            blk = self._side_input_block(spec, side, plan, cancel_check)
        finally:
            for t, v in pins:
                t.unpin(v)
        from tidb_tpu.planner import logical as L

        if not isinstance(plan, L.StageInput):
            self._held_put(
                spec.get("coord"), spec.get("qid"), spec["attempt"],
                spec.get("stage", 0), int(side["tag"]), blk,
            )
        samples = sample_range_keys(
            blk, side["key"], int(spec.get("sample_k") or 64),
            int(spec.get("sample_seed") or 0), int(spec["part"]),
        )
        return {"samples": samples, "rows": blk.nrows}

    def run_probe(self, spec: dict, cancel_check=None) -> dict:
        """AQE skew/cardinality probe of one hash stage (parallel/
        aqe.py): produce (and CACHE, exactly like the range sampling
        round) every side's input, reply each side's EXACT
        per-partition row histogram plus its hottest key values — the
        coordinator sums histograms across producers, detects a
        partition over ``tidb_tpu_shuffle_skew_ratio`` x mean, and
        re-dispatches the stage salted (or broadcast-switched, when a
        side's observed total collapsed). The produce runs ONCE: the
        stage round's sides read the cached blocks through
        _side_input_block.

        Runtime filters (ISSUE 19): when the spec carries an ``rf``
        geometry request, build-flagged sides also reply a compact
        filter over their key domain (bloom / in-list / min-max) plus
        the exact distinct key count — harvested from the SAME keyed-
        int extraction the histogram and hot-key replies use
        (key_ints_valid: each cached block is hashed ONCE). A side
        flagged with a ``gcol`` group column replies its distinct
        group count (``gndv``) for the partial-agg-skip decision."""
        from tidb_tpu.dtypes import Kind
        from tidb_tpu.parallel.wire import (
            build_runtime_filter,
            hot_key_ints_from_ints,
            key_ints_valid,
            partition_histogram_from_ints,
            runtime_filter_nbytes,
        )
        from tidb_tpu.planner import logical as L
        from tidb_tpu.planner.ir import plan_from_ir

        inject("aqe/probe")
        m = int(spec["m"])
        rf_spec = spec.get("rf")
        out = []
        pins: list = []
        try:
            for side in spec["sides"]:
                if cancel_check is not None:
                    cancel_check()
                plan = plan_from_ir(side["plan"])
                plan = self._apply_snap(spec, side, plan, pins)
                blk = self._side_input_block(
                    spec, side, plan, cancel_check
                )
                if not isinstance(plan, L.StageInput):
                    self._held_put(
                        spec.get("coord"), spec.get("qid"),
                        spec["attempt"], spec.get("stage", 0),
                        int(side["tag"]), blk,
                    )
                ints, valid = key_ints_valid(blk, side["key"])
                ent = {
                    "tag": int(side["tag"]),
                    "rows": int(blk.nrows),
                    "part_rows": partition_histogram_from_ints(
                        ints, valid, m
                    ),
                    "hot": hot_key_ints_from_ints(ints, valid),
                }
                if rf_spec and side.get("rf_build"):
                    # min-max bounds are legal only where the key-int
                    # image IS the raw value in logical order
                    kkind = blk.columns[side["key"]].type.kind
                    rf = build_runtime_filter(
                        ints, valid, rf_spec,
                        minmax=kkind in (Kind.INT, Kind.BOOL),
                    )
                    ent["filter"] = rf
                    _c_filter_built().labels(kind=rf["kind"]).inc()
                    _c_filter_bytes().inc(runtime_filter_nbytes(rf))
                gcol = side.get("gcol")
                if gcol and gcol in blk.columns:
                    gints, gvalid = key_ints_valid(blk, gcol)
                    ent["gndv"] = int(len(np.unique(gints[gvalid])))
                out.append(ent)
        finally:
            for t, v in pins:
                t.unpin(v)
        return {"sides": out}

    def _apply_side_filter(self, blk, key, rf, stats, tlock):
        """Apply a broadcast runtime filter to one produced block
        BEFORE partitioning/encoding. The shuffle/filter-lost chaos
        site models a filter lost or corrupted between broadcast and
        application: the side degrades to unfiltered shipping — the
        filter is a pure bytes optimization, never a correctness
        dependency. Stats merge under ``tlock`` (shipper threads and
        the task thread share one stats dict)."""
        from tidb_tpu.parallel.wire import apply_runtime_filter_block

        inject("shuffle/filter")
        if inject("shuffle/filter-lost", False):
            with tlock:
                stats["rf_lost"] = int(stats.get("rf_lost", 0)) + 1
            return blk
        blk2, rows_in, dropped = apply_runtime_filter_block(
            blk, key, rf
        )
        with tlock:
            stats["rf_rows_in"] = (
                int(stats.get("rf_rows_in", 0)) + rows_in
            )
            stats["rf_dropped"] = (
                int(stats.get("rf_dropped", 0)) + dropped
            )
        if dropped:
            _c_filter_dropped().inc(dropped)
        return blk2

    def run_task(self, spec: dict, tracer=None, cancel_check=None) -> dict:
        """The worker half of one shuffle stage. Pipelined (the
        default, ``pipeline=True`` + binary codec): producer sides are
        shipped CHUNK-GRANULARLY on shipper threads — each produced
        block is sliced, hash-partitioned and frame-encoded per packet
        chunk so encode+push (and the peers' on-arrival decode) overlap
        the NEXT side's produce; the consumer then waits PER SIDE
        (ShuffleStore.wait_side) and stages each side the moment its
        streams complete, while the other side is still in flight,
        through the single-write incremental stager. Barrier mode
        (``pipeline=False`` escape hatch, or the JSON codec) keeps the
        four sequential phases of PR 4:

        1. open the receive store for (sid, attempt);
        2. run each producer side plan (this worker's fragment slice),
           bucketize its rows by the partition key, push every
           partition to its owning peer (self partitions short-circuit
           into the local store — no tunnel bytes);
        3. wait for all m producers' streams for OUR partition;
        4. substitute the received partitions for the consumer plan's
           ShuffleRead leaves and execute it.

        Returns {"columns", "rows", "shuffle": {...stats}}; raises
        ShuffleAbort for retryable stage failures and whatever
        ``cancel_check`` raises (fleet-wide cancellation: the check is
        polled at every loop point — produce chunks, shipped
        sub-batches, store waits, consume — and a cancelled task
        poisons its stage so late peer frames cannot resurrect it)."""
        from tidb_tpu.chunk import materialize_rows
        from tidb_tpu.planner.ir import plan_from_ir
        from tidb_tpu.planner.physical import PhysicalExecutor
        from tidb_tpu.server.engine_rpc import QueryCancelled

        sid = spec["sid"]
        attempt = int(spec["attempt"])
        m = int(spec["m"])
        part = int(spec["part"])
        peers = [tuple(p) for p in spec["peers"]]
        secret = spec.get("secret")
        packet_rows = int(spec.get("packet_rows") or DEFAULT_PACKET_ROWS)
        inflight = int(
            spec.get("max_inflight_bytes") or DEFAULT_INFLIGHT_BYTES
        )
        wait_timeout = float(spec.get("wait_timeout_s") or 120.0)
        codec = str(spec.get("codec") or "binary")
        pipeline = (
            bool(spec.get("pipeline", True)) and codec == "binary"
        )
        produce_chunks = max(
            int(spec.get("produce_chunks") or DEFAULT_PRODUCE_CHUNKS), 1
        )
        # shuffle-DAG fields (absent = the single-stage shape): stage
        # index + chain length (telemetry), the exchange kind, range
        # boundaries, and whether this stage's output is HELD for the
        # next stage's StageInput instead of returned to the
        # coordinator
        stage_idx = int(spec.get("stage", 0))
        n_stages = int(spec.get("n_stages", 1))
        exchange = str(spec.get("exchange") or "hash")
        boundaries = spec.get("boundaries") or []
        hold_output = bool(spec.get("hold_output"))
        release_held = bool(spec.get("release_held"))
        coord, qid = spec.get("coord"), spec.get("qid")
        if stage_idx == 0:
            # a retried DAG restarts from stage 0 under a new attempt:
            # the superseded attempt's held partitions must not
            # satisfy the new attempt's StageInputs
            self._held_prune(coord, qid, before_attempt=int(attempt))
        ctx = f"q{spec.get('qid')}/p{part}"
        # fleet timeline capture (obs/timeline.py): when the dispatch
        # asks for it, work windows land in a per-task buffer the reply
        # ships back piggybacked — the coordinator merges them behind
        # the ledger fence and rebases through the handshake clock
        # offset, so a retried stage's events land exactly once
        buf = None
        ev_args = {
            "pipeline": pipeline, "stage": stage_idx,
            "exchange": exchange,
        }
        if spec.get("timeline"):
            from tidb_tpu.obs.timeline import TimelineBuffer

            buf = TimelineBuffer()

        def emit(name: str, t0_wall: float, dur_s: float) -> None:
            if buf is not None:
                buf.emit_event(
                    "shuffle", name, t0_wall, dur_s, track=ctx,
                    args=ev_args,
                )

        self.store.open(sid, attempt, m)
        with self._exec_lock:
            # producer executor: the per-host SPMD engine (scans run
            # over the local device mesh — ICI below, tunnels above)
            if self._producer_exec is None:
                self._producer_exec = PhysicalExecutor(
                    self.catalog, mesh_devices=self.mesh_devices
                )
            producer_exec = self._producer_exec
        tunnels: Dict[int, PeerTunnel] = {}
        tlock = racecheck.make_lock("shuffle.tunnels")  # create + stats
        # adaptive-stage marker (parallel/aqe.py): the coordinator's
        # taken decisions ride the task spec so a worker-side chaos
        # fault can target exactly the window between the re-plan
        # decision and the switched/salted stage's execution
        if spec.get("adaptive"):
            inject("aqe/switched-stage")
        stats = {
            "pushed_bytes": 0, "pushed_rows": 0, "local_rows": 0,
            "stalls": 0, "stall_s": 0.0, "retransmits": 0,
            "produced_rows": 0,
            "stage": stage_idx, "n_stages": n_stages,
            "exchange": exchange, "scan_rows": 0, "held_rows": 0,
            # AQE observability: per-side produced rows (the
            # cardinality feedback's exact actuals), rows this
            # partition RECEIVED (the skew ratio's numerator), and
            # the salt fan-out if this stage ran salted
            "side_rows": {}, "recv_rows": 0, "salted": 0,
            "per_peer": [], "codec": codec, "encode_s": 0.0,
            "pipeline": pipeline, "wait_idle_s": 0.0, "ttff_s": 0.0,
            # flight-recorder phase breakdown (obs/flight.py): engine
            # time below the exchange, total blocked-in-wait wall
            # (nonzero even when overlap hides it — wait_idle_s is the
            # NON-overlapped remainder), and partition staging time
            "produce_s": 0.0, "wait_s": 0.0, "stage_s": 0.0,
        }
        _nullspan = _NullSpan()

        def span(name):
            return tracer.span(name) if tracer is not None else _nullspan

        shippers: List[threading.Thread] = []
        ship_errs: List[Exception] = []
        staged: Dict[int, object] = {}
        snap_pins: List[tuple] = []

        def poll():
            """Wait-abort callback: raises on fleet cancellation, else
            reports whether a shipper failed (the WaitInterrupted
            hand-back)."""
            if cancel_check is not None:
                cancel_check()
            return bool(ship_errs)

        try:
            for side in spec["sides"]:
                if cancel_check is not None:
                    cancel_check()
                # Top SQL live phase (obs/profiler.py): the sampler
                # attributes this thread's instants to the shuffle
                # phase it is inside — a no-op when the engine-RPC
                # handler registered no task context
                topsql.set_task_phase("shuffle-produce")
                tag = int(side["tag"])
                plan = plan_from_ir(side["plan"])
                plan = self._apply_snap(spec, side, plan, snap_pins)
                schema_cols = list(plan.schema)
                inject("shuffle/produce")
                stats["scan_rows"] += self._plan_scan_rows(plan)
                mode = str(
                    side.get("mode")
                    or ("range" if exchange == "range" else "hash")
                )
                from tidb_tpu.planner import logical as _L

                salt = side.get("salt")
                if (
                    salt or mode != "hash"
                    or side.get("probed")
                    or isinstance(plan, _L.StageInput)
                ):
                    # DAG edge over a COMPLETE block: a held stage
                    # output (StageInput), a range side (the sampling
                    # round already produced and cached it), a salted
                    # or merely PROBED side (the skew probe cached the
                    # produce — a plain-hash outcome must still read
                    # the cache, not pay produce twice), or a
                    # broadcast/local edge — partitioned/copied whole,
                    # shipped through the columnar frame path
                    t_prod = time.perf_counter()
                    t_wall = time.time()
                    with span(f"{ctx}/produce#{tag}"):
                        blk = self._side_input_block(
                            spec, side, plan, cancel_check
                        )
                    dt_prod = time.perf_counter() - t_prod
                    stats["produce_s"] += dt_prod
                    emit(f"produce#{tag}", t_wall, dt_prod)
                    stats["produced_rows"] += blk.nrows
                    stats["side_rows"][str(tag)] = int(blk.nrows)
                    if side.get("rf") is not None:
                        # runtime filter over the complete block (the
                        # probe-cached / held / range side shape) —
                        # side_rows above stays the TRUE produce count
                        # (the cardinality feedback's actuals)
                        blk = self._apply_side_filter(
                            blk, side["key"], side["rf"], stats, tlock
                        )
                    t_push = time.perf_counter()
                    t_wall = time.time()
                    topsql.set_task_phase("shuffle-push")
                    with span(f"{ctx}/push#{tag}"):
                        if salt:
                            stats["salted"] = max(
                                stats["salted"],
                                int(salt.get("k", 0)),
                            )
                            self._ship_salted_side(
                                sid, attempt, m, tag, part, blk,
                                schema_cols, salt, side.get("key"),
                                peers, secret, tunnels, tlock,
                                packet_rows, inflight, stats,
                            )
                        else:
                            self._ship_block_side(
                                sid, attempt, m, tag, part, blk,
                                schema_cols, mode, boundaries,
                                side.get("key"), peers, secret,
                                tunnels, tlock, packet_rows, inflight,
                                stats,
                            )
                    emit(
                        f"push#{tag}", t_wall,
                        time.perf_counter() - t_push,
                    )
                    continue
                if codec == "json":
                    # shuffle-json-fallback: the row-packet escape
                    # hatch (shuffle_codec=json) materializes and
                    # partitions Python rows, like PR 3
                    t_prod = time.perf_counter()
                    t_wall = time.time()
                    with span(f"{ctx}/produce#{tag}"):
                        batch, dicts = self._run_producer(
                            producer_exec, plan,
                            side.get("_snap_hook"),
                            bool(side.get("_snap_merged")),
                        )
                    dt_prod = time.perf_counter() - t_prod
                    stats["produce_s"] += dt_prod
                    emit(f"produce#{tag}", t_wall, dt_prod)
                    with self._exec_lock:
                        rows = materialize_rows(batch, schema_cols, dicts)
                    key_idx = [c.internal for c in schema_cols].index(
                        side["key"]
                    )
                    stats["produced_rows"] += len(rows)
                    stats["side_rows"][str(tag)] = len(rows)
                    parts = partition_rows(rows, key_idx, m)
                    t_push = time.perf_counter()
                    t_wall = time.time()
                    topsql.set_task_phase("shuffle-push")
                    with span(f"{ctx}/push#{tag}"):
                        for dest, prows in enumerate(parts):
                            self._send_stream(
                                sid, attempt, m, tag, part, dest, prows,
                                peers, secret, tunnels, tlock,
                                packet_rows, inflight, stats,
                            )
                    emit(
                        f"push#{tag}", t_wall,
                        time.perf_counter() - t_push,
                    )
                    continue
                # binary hot path: keep the engine's own columnar
                # layout end to end — hash the key COLUMN (bit-identical
                # to exchange._mix_hash), np.take each column by
                # partition, frame-encode straight from HostColumn
                from tidb_tpu.chunk import batch_to_block, take_block
                from tidb_tpu.parallel.wire import partition_block

                types = {c.internal: c.type for c in schema_cols}
                if pipeline:
                    # shipper thread fed by a queue of produced
                    # sub-batches: d2h fetch + partition + encode +
                    # push of everything enqueued overlaps BOTH the
                    # same side's next produce chunk and the next
                    # side's produce (and the peers' on-arrival decode
                    # of what we push)
                    import queue as _queue

                    sq: "_queue.Queue" = _queue.Queue()
                    with tlock:
                        stats["_live_shippers"] = (
                            stats.get("_live_shippers", 0) + 1
                        )
                    th = threading.Thread(
                        target=self._ship_side_stream,
                        args=(
                            sid, attempt, m, tag, part, sq,
                            side["key"], schema_cols, peers, secret,
                            tunnels, tlock, packet_rows, inflight,
                            stats, ship_errs, buf, ctx, ev_args,
                            cancel_check,
                            # shipper threads inherit the task's Top
                            # SQL digest (their samples charge the
                            # same statement, phase shuffle-push)
                            topsql.current_digest(),
                            # broadcast runtime filter (None = off):
                            # applied per produced sub-block before
                            # partition/encode
                            side.get("rf"),
                        ),
                        daemon=True,
                        name=f"shuffle-ship-{sid}-s{tag}",
                    )
                    th.start()
                    shippers.append(th)
                    # chunk-granular produce: the side executes as
                    # produce_chunks disjoint frag sub-slices when the
                    # plan is row-sliceable, so push starts after ONE
                    # chunk instead of after the whole side
                    subplans = None
                    if produce_chunks > 1 and not side.get(
                        "_snap_merged"
                    ):
                        # a delta-merged side already carries its frag
                        # slice inside the UnionAll — sub-slicing the
                        # base scan again would desync it from the
                        # staged insert slice
                        cand = [
                            _slice_producer(plan, k, produce_chunks)
                            for k in range(produce_chunks)
                        ]
                        if all(c is not None for c in cand):
                            subplans = cand
                    for sp in (subplans or [plan]):
                        if cancel_check is not None:
                            cancel_check()
                        t_prod = time.perf_counter()
                        t_wall = time.time()
                        with span(f"{ctx}/produce#{tag}"):
                            batch, dicts = self._run_producer(
                                producer_exec, sp,
                                side.get("_snap_hook"),
                                bool(side.get("_snap_merged")),
                            )
                        dt_prod = time.perf_counter() - t_prod
                        stats["produce_s"] += dt_prod
                        emit(f"produce#{tag}", t_wall, dt_prod)
                        sq.put((batch, types, dicts))
                    sq.put(None)  # side EOF sentinel
                    continue
                t_prod = time.perf_counter()
                t_wall = time.time()
                with span(f"{ctx}/produce#{tag}"):
                    batch, dicts = self._run_producer(
                        producer_exec, plan, side.get("_snap_hook"),
                        bool(side.get("_snap_merged")),
                    )
                dt_prod = time.perf_counter() - t_prod
                stats["produce_s"] += dt_prod
                emit(f"produce#{tag}", t_wall, dt_prod)
                block = batch_to_block(batch, types, dicts)
                stats["produced_rows"] += block.nrows
                stats["side_rows"][str(tag)] = int(block.nrows)
                if side.get("rf") is not None:
                    block = self._apply_side_filter(
                        block, side["key"], side["rf"], stats, tlock
                    )
                idxs = partition_block(block, side["key"], m)
                t_push = time.perf_counter()
                t_wall = time.time()
                topsql.set_task_phase("shuffle-push")
                with span(f"{ctx}/push#{tag}"):
                    for dest, idx in enumerate(idxs):
                        self._ship_partition(
                            sid, attempt, m, tag, part, dest,
                            take_block(block, idx), schema_cols, peers,
                            secret, tunnels, tlock, packet_rows,
                            inflight, stats,
                        )
                emit(f"push#{tag}", t_wall, time.perf_counter() - t_push)
            consumer = plan_from_ir(spec["consumer"])
            reads = _shuffle_read_tags(consumer)
            # per-side expected sender sets: a "local" DAG edge only
            # ever has this host's own stream (nothing crosses the
            # wire), every other mode expects all m producers
            senders = {
                int(s["tag"]): (
                    [part]
                    if str(s.get("mode") or "") == "local"
                    else list(range(m))
                )
                for s in spec["sides"]
            }
            if not pipeline:
                # barrier shape: every push acked before the wait
                # opens (shipper threads exist only in pipelined mode,
                # so there are no ship_errs to consult here). Local
                # work is done once the last partition is enqueued, so
                # BOTH the flush block (waiting for peer acks) and the
                # store wait are exchange idle.
                t0 = time.perf_counter()
                t_wall = time.time()
                topsql.set_task_phase("shuffle-wait")
                for t in tunnels.values():
                    t.flush()
                with span(f"{ctx}/wait"):
                    by_side = self.store.wait(
                        sid, attempt, len(spec["sides"]), m,
                        wait_timeout, abort=poll, senders=senders,
                    )
                idle = time.perf_counter() - t0
                emit("wait", t_wall, idle)
                stats["wait_idle_s"] += idle
                stats["wait_s"] += idle
                _c_wait_idle_seconds().inc(idle)
            else:
                # pipelined: the wait/stage loop starts while our OWN
                # shippers are still draining — a side whose streams
                # are all EOF stages (including its h2d move) while the
                # other side is still in flight AND while our outbound
                # tail is still crossing the tunnels. abort() hands
                # control back within a poll tick if a shipper fails,
                # so a dead peer surfaces promptly, not at the wait
                # deadline.
                pending = sorted(int(s["tag"]) for s in spec["sides"])
                waited = 0.0
                while pending:
                    t0 = time.perf_counter()
                    t_wall = time.time()
                    # the timeout budget charges WAITING only: per-side
                    # staging between waits must not burn it (barrier
                    # mode charged wait_timeout purely to its one wait)
                    deadline = time.monotonic() + max(
                        wait_timeout - waited, 0.0
                    )
                    topsql.set_task_phase("shuffle-wait")
                    with span(f"{ctx}/wait"):
                        done, chunks, vocab = self.store.wait_side(
                            sid, attempt, pending, m, deadline,
                            abort=poll, senders=senders,
                        )
                    t1 = time.perf_counter()
                    emit("wait", t_wall, t1 - t0)
                    waited += t1 - t0
                    stats["wait_s"] += t1 - t0
                    # idle = blocked time with our own shippers already
                    # drained (wait wall that overlaps our outbound
                    # push is pipeline WORKING, not idling)
                    with tlock:
                        ship_done = stats.get("_ship_done")
                    idle = (
                        max(0.0, t1 - max(t0, ship_done))
                        if ship_done is not None else 0.0
                    )
                    stats["wait_idle_s"] += idle
                    _c_wait_idle_seconds().inc(idle)
                    pending.remove(done)
                    stats["recv_rows"] += sum(
                        _payload_rows(c) for c in chunks
                    )
                    node = reads.get(done)
                    if node is not None:
                        t_stage = time.perf_counter()
                        t_wall = time.time()
                        topsql.set_task_phase("shuffle-stage")
                        with span(f"{ctx}/stage#{done}"):
                            staged[done] = stage_payloads_incremental(
                                node.schema, chunks,
                                next(self._nonce), vocab=vocab,
                                key=f"shuffle#{done}",
                            )
                        dt_stage = time.perf_counter() - t_stage
                        emit(f"stage#{done}", t_wall, dt_stage)
                        stats["stage_s"] += dt_stage
                for th in shippers:
                    th.join()
                if ship_errs:
                    raise ship_errs[0]
                for t in tunnels.values():
                    t.flush()
        except WaitInterrupted:
            # a shipper failed while we were waiting: surface ITS
            # error with the same classification as the in-try raises (a
            # raise from an except clause skips sibling handlers)
            for th in shippers:
                th.join(timeout=30)
            self.store.discard(sid)
            err = ship_errs[0] if ship_errs else None
            if isinstance(err, QueryCancelled):
                # a cancelled shipper: poison like the direct-cancel
                # path (this raise skips the sibling handlers below)
                self.store.poison(sid)
                self._held_prune(coord, qid)
                raise err
            if isinstance(err, PeerDeadError):
                if err.fatal:
                    raise RuntimeError(
                        f"shuffle push to {err.address} rejected: "
                        f"{err.cause}"
                    ) from err
                raise ShuffleAbort("push failed", [err.address]) from err
            raise err if err is not None else ShuffleAbort(
                "ship interrupted", []
            )
        except ShuffleWaitTimeout as e:
            # missing "sideS/senderJ" -> suspect peer address J
            suspects = sorted(
                {
                    "%s:%s" % peers[int(s.rsplit("sender", 1)[1])]
                    for s in e.missing
                }
            )
            self.store.discard(sid)  # a retry runs under a new attempt
            raise ShuffleAbort("wait timed out", suspects) from e
        except PeerDeadError as e:
            if e.fatal:
                # engine-side rejection/encoding error: surface the
                # REAL cause as a non-retryable engine error
                raise RuntimeError(
                    f"shuffle push to {e.address} rejected: {e.cause}"
                ) from e
            raise ShuffleAbort("push failed", [e.address]) from e
        except QueryCancelled:
            # fleet-wide cancellation reached this task: free the
            # stage's buffers and POISON the sid — frames still in
            # flight from peers that have not seen the cancel land as
            # stale drops instead of resurrecting an orphan record —
            # and drop the query's held DAG blocks
            self.store.poison(sid)
            self._held_prune(coord, qid)
            raise
        finally:
            # release the routed snapshot's base-version pins: GC may
            # collect superseded versions once no dispatch reads them
            for t, v in snap_pins:
                t.unpin(v)
            for th in shippers:
                # an error can escape while shippers run: never close
                # tunnels under an active sender
                th.join(timeout=30)
            for t in tunnels.values():
                t.close()
            # authoritative push stats come from the tunnels (only
            # ACKED packets count — an aborted stream's queued bytes
            # never crossed the link)
            for t in tunnels.values():
                stats["pushed_bytes"] += t.bytes_sent
                stats["pushed_rows"] += t.rows_sent
                stats["stalls"] += t.stalls
                stats["stall_s"] += t.stall_s
                stats["retransmits"] += t.retransmits
                if buf is not None:
                    # backpressure stall windows per link — where a
                    # producer stood blocked on a peer's in-flight
                    # byte window, on the merged fleet timeline
                    for w0, wdur in t.stall_windows:
                        buf.emit_event(
                            "stall", f"stall->{t.address}", w0, wdur,
                            track=ctx, args={"dst": t.address},
                        )
                stats["per_peer"].append(
                    {
                        "dst": t.address, "bytes": t.bytes_sent,
                        "rows": t.rows_sent, "frames": t.frames_sent,
                        "stalls": t.stalls,
                        "stall_s": round(t.stall_s, 6),
                        "retransmits": t.retransmits,
                        "codec": t._codec or stats["codec"],
                    }
                )
        stats["ttff_s"] = self.store.max_ttff(sid)
        stats.pop("_live_shippers", None)
        stats.pop("_ship_done", None)
        # the waits copied the rows out: free the buffered packets NOW
        # so the store holds only in-flight stages, not consumed ones
        self.store.discard(sid)

        if pipeline:
            for tag, node in reads.items():
                if tag not in staged:  # a read with no producer side
                    staged[tag] = stage_payloads_incremental(
                        node.schema, [], next(self._nonce),
                        key=f"shuffle#{tag}",
                    )
        else:
            # barrier escape hatch: the PR 4 stage end to end — bulk
            # concat staging under a fresh nonce (no compiled-consumer
            # reuse; the keyed staged input is incremental-mode
            # machinery)
            t_stage = time.perf_counter()
            t_wall = time.time()
            topsql.set_task_phase("shuffle-stage")
            stats["recv_rows"] += sum(
                _payload_rows(c)
                for payloads in by_side.values() for c in payloads
            )
            staged = {
                tag: stage_payloads_as_batch(
                    node.schema, by_side.get(tag, []),
                    next(self._nonce),
                )
                for tag, node in reads.items()
            }
            dt_stage = time.perf_counter() - t_stage
            emit("stage", t_wall, dt_stage)
            stats["stage_s"] += dt_stage
        inject("shuffle/consume")
        if cancel_check is not None:
            cancel_check()
        topsql.set_task_phase("execute")
        with span(f"{ctx}/consume"), self._exec_lock:
            # consumer executes single-device: its sources are Staged
            # partition batches, not mesh-sharded scans
            if self._consumer_exec is None:
                self._consumer_exec = PhysicalExecutor(self.catalog)
            out, out_dicts = self._consumer_exec.run(
                _substitute_reads(consumer, staged)
            )
            if hold_output:
                # mid-DAG stage: the partition output stays HERE as
                # the next stage's StageInput — nothing but stats
                # returns to the coordinator
                from tidb_tpu.chunk import batch_to_block

                types = {
                    c.internal: c.type for c in consumer.schema.cols
                }
                blk = batch_to_block(out, types, out_dicts)
                self._held_put(
                    coord, qid, attempt, stage_idx, None, blk
                )
                stats["held_rows"] = blk.nrows
                out_rows = []
            else:
                out_rows = materialize_rows(
                    out, list(consumer.schema), out_dicts
                )
        if release_held:
            # last DAG stage done: free every held block of this query
            self._held_prune(coord, qid)
        return {
            "columns": [c.name for c in consumer.schema],
            "rows": out_rows,
            "shuffle": stats,
            # piggybacked timeline events (None when capture is off):
            # the reply ships them, the coordinator merges them behind
            # the exactly-once ledger fence
            "events": buf.events if buf is not None else None,
        }

    def _tunnel_for(
        self, dest, peers, sender, secret, tunnels, tlock, inflight,
        batch_packets: int = 64,
    ) -> PeerTunnel:
        # check-and-create under the shared tunnel lock: the task
        # thread ships complete blocks (probed/held/range sides) WHILE
        # shipper threads stream pipelined sides to the same dests — a
        # racing duplicate PeerTunnel would be overwritten in the dict
        # and its tx thread leak past the task's close
        with tlock:
            if dest not in tunnels:
                host, port = peers[dest]
                # src labeled with THIS worker's dial address
                # (peers[sender]) so tidbtpu_shuffle_bytes_total
                # {src,dst} uses one identity space — a host's inbound
                # and outbound series correlate
                tunnels[dest] = PeerTunnel(
                    host, port, secret,
                    src="%s:%s" % tuple(peers[sender]),
                    max_inflight_bytes=inflight,
                    batch_packets=batch_packets,
                )
            return tunnels[dest]

    def _ship_side_stream(
        self, sid, attempt, m, side, sender, sq, key, schema_cols,
        peers, secret, tunnels, tlock, packet_rows, inflight, stats,
        errs, buf=None, ctx="", ev_args=None, cancel_check=None,
        topsql_digest=None, rf=None,
    ) -> None:
        """Pipelined producer ship (one side, run on a shipper thread,
        fed produced sub-batches through queue ``sq`` until the None
        sentinel): each sub-batch is fetched device->host HERE — the
        d2h move overlaps the next produce chunk — then its partition
        map is computed once and the block walked in packet chunks:
        each chunk is split by destination, frame-encoded and enqueued
        IMMEDIATELY, so every peer's first frame leaves after one
        chunk instead of after the whole side (low time-to-first-frame)
        and destinations interleave fairly. Sequence numbers run
        continuously across sub-batches; EOFs close each stream with
        the true frame count once the sentinel arrives. The whole-side
        row materialization of the barrier path never happens here
        (lint-enforced by check_shuffle_hotpath.py). Self partitions
        land HostBlocks in the local store chunk by chunk; a
        mixed-version peer that negotiated down gets per-chunk JSON
        row packets. Errors land in ``errs`` for the task thread."""
        from tidb_tpu.chunk import (
            batch_to_block,
            block_to_rows,
            slice_block,
            take_block,
        )
        from tidb_tpu.parallel.wire import encode_frame, partition_map

        # shipper threads carry the task's statement digest so the Top
        # SQL sampler attributes their encode/push CPU (and tunnel
        # backpressure stalls) to the same query, phase shuffle-push
        _ts_prev = None
        if topsql_digest:
            _ts_prev = topsql.begin_task(
                "shuffle", digest=topsql_digest, phase="shuffle-push"
            )
        try:
            seqs = [0] * m
            local_rows = 0
            encode_s = 0.0
            produced = 0
            # chunks of packet_rows*m keep per-destination frames near
            # packet_rows rows — framing (and per-frame dictionary/
            # header overhead) comparable to the barrier producer
            step = max(int(packet_rows) * max(m, 1), 1)
            while True:
                item = sq.get()
                if item is None:
                    break
                t_ship0 = time.perf_counter()
                t_ship_wall = time.time()
                batch, types, dicts = item
                block = batch_to_block(batch, types, dicts)
                produced += block.nrows
                if rf is not None:
                    # runtime filter per produced sub-block: dropped
                    # rows are never partitioned, encoded or shipped
                    # (``produced`` above stays the true produce count)
                    block = self._apply_side_filter(
                        block, key, rf, stats, tlock
                    )
                pmap = partition_map(block, key, m)
                for a in range(0, block.nrows, step):
                    if cancel_check is not None:
                        # fleet cancellation: the shipper stops mid-
                        # side — its error lands in ``errs`` and the
                        # waiting consumer's abort poll hands control
                        # back within a tick
                        cancel_check()
                    chunk = slice_block(block, a, a + step)
                    cmap = pmap[a : a + step]
                    for dest in range(m):
                        idx = np.nonzero(cmap == dest)[0]
                        if not len(idx):
                            continue
                        sub = take_block(chunk, idx)
                        seq = seqs[dest]
                        seqs[dest] += 1
                        if dest == sender:
                            self.store.push(
                                sid, attempt, m, side, sender, seq, sub
                            )
                            local_rows += sub.nrows
                            continue
                        tun = self._tunnel_for(
                            dest, peers, secret=secret,
                            sender=sender, tunnels=tunnels,
                            tlock=tlock, inflight=inflight,
                        )
                        if tun.negotiated_codec("binary") != "binary":
                            packet = {
                                "sid": sid, "attempt": attempt, "m": m,
                                "side": side, "sender": sender,
                                "part": dest, "seq": seq,
                                "rows": block_to_rows(sub, schema_cols),
                            }
                            # shuffle-json-fallback: per-chunk row
                            # packet for a peer that negotiated down
                            t0 = time.perf_counter()
                            payload = json.dumps(
                                {"shuffle_push": packet}
                            ).encode()
                            dt = time.perf_counter() - t0
                            encode_s += dt
                            _c_encode_seconds().labels(
                                codec="json"
                            ).inc(dt)
                            _c_codec_bytes().labels(codec="json").inc(
                                len(payload)
                            )
                            tun.send(payload, len(payload), sub.nrows)
                            continue
                        t0 = time.perf_counter()
                        frame = encode_frame(
                            sid, attempt, m, side, sender, dest, seq,
                            sub, schema_cols,
                        )
                        dt = time.perf_counter() - t0
                        encode_s += dt
                        _c_encode_seconds().labels(codec="binary").inc(
                            dt
                        )
                        _c_codec_bytes().labels(codec="binary").inc(
                            len(frame)
                        )
                        tun.send(frame, len(frame), sub.nrows)
                if buf is not None:
                    # one push window per shipped sub-batch: d2h fetch
                    # + partition + encode + enqueue — on the timeline
                    # these windows interleave with the SAME side's
                    # next produce chunk, which is the overlap the
                    # pipelined stage claims
                    buf.emit_event(
                        "shuffle", f"push#{side}", t_ship_wall,
                        time.perf_counter() - t_ship0, track=ctx,
                        args=ev_args,
                    )
            for dest in range(m):
                if dest == sender:
                    self.store.push(
                        sid, attempt, m, side, sender, -1, None,
                        nseq=seqs[dest],
                    )
                    continue
                tun = self._tunnel_for(
                    dest, peers, secret=secret, sender=sender,
                    tunnels=tunnels, tlock=tlock, inflight=inflight,
                )
                if tun.negotiated_codec("binary") != "binary":
                    eof = {
                        "sid": sid, "attempt": attempt, "m": m,
                        "side": side, "sender": sender, "part": dest,
                        "seq": -1, "rows": None, "nseq": seqs[dest],
                    }
                    # shuffle-json-fallback: the row-codec EOF marker
                    payload = json.dumps({"shuffle_push": eof}).encode()
                    tun.send(payload, len(payload), 0)
                else:
                    eof = encode_frame(
                        sid, attempt, m, side, sender, dest, -1, None,
                        schema_cols, nseq=seqs[dest],
                    )
                    tun.send(eof, len(eof), 0)
            with tlock:
                stats["local_rows"] += local_rows
                stats["encode_s"] += encode_s
                stats["produced_rows"] += produced
                stats.setdefault("side_rows", {})[str(side)] = produced
        except Exception as e:
            errs.append(e)
        finally:
            if topsql_digest:
                topsql.end_task(_ts_prev)
            with tlock:
                stats["_live_shippers"] = (
                    stats.get("_live_shippers", 1) - 1
                )
                if stats["_live_shippers"] <= 0:
                    # all sides shipped: wait time past this point is
                    # TRUE consumer idle (nothing left to overlap)
                    stats["_ship_done"] = time.perf_counter()

    def _plan_scan_rows(self, plan) -> int:
        """Base-table rows this plan's scans will read, fragment
        slices honored — the per-host scan-work accounting the DAG A/B
        cites (a chained DAG slices EVERY side; the single-cut
        group-by re-scans unsliced join sides on every host)."""
        from tidb_tpu.planner import logical as L

        total = 0

        def walk(p):
            nonlocal total
            if isinstance(p, L.Scan):
                try:
                    nrows = int(self.catalog.table(p.db, p.table).nrows)
                except Exception:
                    return
                if p.frag is not None:
                    i, mm = p.frag
                    total += len(range(int(i), nrows, int(mm)))
                else:
                    total += nrows
                return
            for attr in ("child", "left", "right"):
                c = getattr(p, attr, None)
                if c is not None:
                    walk(c)
            for c in getattr(p, "children", []) or []:
                walk(c)

        walk(plan)
        return total

    def _ship_block_side(
        self, sid, attempt, m, side, sender, block, schema_cols, mode,
        boundaries, key, peers, secret, tunnels, tlock, packet_rows,
        inflight, stats,
    ) -> None:
        """Ship one COMPLETE columnar side under a DAG edge mode:

        - "local": no exchange at all — the producing host is the
          owning partition (the broadcast join's probe side; zero
          tunnel bytes);
        - "broadcast": the whole side goes to EVERY peer (the small
          join side of a broadcast edge);
        - "range": rows route by sampled key-range boundaries
          (wire.range_partition_map — distributed ORDER BY);
        - "hash": key-hash routing (a held StageInput re-exchange).

        Everything rides the existing columnar frame path
        (_ship_partition: per-chunk binary frames, JSON only for a
        peer that negotiated down)."""
        from tidb_tpu.chunk import take_block
        from tidb_tpu.parallel.wire import (
            partition_block,
            range_partition_map,
        )

        if mode == "local":
            # dest == sender: _ship_partition's self-push path lands
            # the block in the local store with the EOF discipline —
            # ONE definition of the self-push protocol
            self._ship_partition(
                sid, attempt, m, side, sender, sender, block,
                schema_cols, peers, secret, tunnels, tlock,
                packet_rows, inflight, stats,
            )
            return
        if mode == "broadcast":
            for dest in range(m):
                self._ship_partition(
                    sid, attempt, m, side, sender, dest, block,
                    schema_cols, peers, secret, tunnels, tlock,
                    packet_rows, inflight, stats,
                )
            return
        if mode == "range":
            pmap = range_partition_map(block, key, boundaries)
            idxs = [
                np.nonzero(pmap == d)[0] for d in range(m)
            ]
        else:
            idxs = partition_block(block, key, m)
        for dest, idx in enumerate(idxs):
            self._ship_partition(
                sid, attempt, m, side, sender, dest,
                take_block(block, idx), schema_cols, peers, secret,
                tunnels, tlock, packet_rows, inflight, stats,
            )

    def _ship_salted_side(
        self, sid, attempt, m, side, sender, block, schema_cols, salt,
        key, peers, secret, tunnels, tlock, packet_rows, inflight,
        stats,
    ) -> None:
        """Ship one COMPLETE columnar side under a salt spec
        (``{"keys": [key_ints], "k": K, "role": ...}``): the hot
        partition's keys route across their K-wide salted target set
        instead of one home partition.

        - role "split" (the skewed side): each hot-key row goes to ONE
          salted target, round-robin (staggered by sender so m
          producers don't all start on lane 0) — the hot partition's
          work spreads K ways, every row still lands exactly once;
        - role "replicate" (a join's other side): each hot-key row is
          COPIED to all K targets, so every salted lane can match its
          share of the split side (the broadcast-of-hot-keys half of
          skew-salted joins). Unflagged rows keep the plain hash map
          either way."""
        from tidb_tpu.chunk import take_block
        from tidb_tpu.parallel.wire import (
            salted_partition_assign,
            salted_split_map,
        )

        if str(salt.get("role") or "split") == "split":
            pmap = salted_split_map(block, key, m, salt, lane0=sender)
            idxs = [np.nonzero(pmap == d)[0] for d in range(m)]
        else:
            base, flagged, k = salted_partition_assign(
                block, key, m, salt
            )
            idxs = []
            for dest in range(m):
                sel = [np.nonzero((base == dest) & ~flagged)[0]]
                for j in range(k):
                    sel.append(np.nonzero(
                        flagged & ((base + j) % m == dest)
                    )[0])
                idxs.append(np.sort(np.concatenate(sel)))
        for dest, idx in enumerate(idxs):
            self._ship_partition(
                sid, attempt, m, side, sender, dest,
                take_block(block, idx), schema_cols, peers, secret,
                tunnels, tlock, packet_rows, inflight, stats,
            )

    def _ship_partition(
        self, sid, attempt, m, side, sender, dest, block, schema_cols,
        peers, secret, tunnels, tlock, packet_rows, inflight, stats,
    ) -> None:
        """Ship one columnar partition: binary frames seq 0..k-1 then
        the EOF frame, each encoded ONCE here in the producer (the
        encoded bytes size the flow-control window, cross the wire
        verbatim after the tunnel's byte-level id/auth splice, and an
        encoding error fails HERE as a non-retryable engine error, not
        a fake peer death). Self partitions land the HostBlock in the
        local store with NO serialization at all; a mixed-version peer
        whose tunnel negotiates down gets the JSON row packets."""
        from tidb_tpu.chunk import block_to_rows, slice_block
        from tidb_tpu.parallel.wire import encode_frame

        if dest == sender:
            if block.nrows:
                self.store.push(sid, attempt, m, side, sender, 0, block)
                stats["local_rows"] += block.nrows
            self.store.push(
                sid, attempt, m, side, sender, -1, None,
                nseq=1 if block.nrows else 0,
            )
            return
        # barrier escape hatch: strict stop-and-wait acks, the
        # pre-pipelining wire discipline
        tun = self._tunnel_for(
            dest, peers, secret=secret, sender=sender, tunnels=tunnels,
            tlock=tlock, inflight=inflight, batch_packets=1,
        )
        if tun.negotiated_codec("binary") != "binary":
            self._send_stream(
                sid, attempt, m, side, sender, dest,
                block_to_rows(block, schema_cols), peers, secret,
                tunnels, tlock, packet_rows, inflight, stats,
            )
            return
        nchunks = (block.nrows + packet_rows - 1) // packet_rows
        for seq in range(nchunks):
            sub = slice_block(
                block, seq * packet_rows, (seq + 1) * packet_rows
            )
            t0 = time.perf_counter()
            frame = encode_frame(
                sid, attempt, m, side, sender, dest, seq, sub,
                schema_cols,
            )
            dt = time.perf_counter() - t0
            stats["encode_s"] += dt
            _c_encode_seconds().labels(codec="binary").inc(dt)
            _c_codec_bytes().labels(codec="binary").inc(len(frame))
            tun.send(frame, len(frame), sub.nrows)
        eof = encode_frame(
            sid, attempt, m, side, sender, dest, -1, None, schema_cols,
            nseq=nchunks,
        )
        tun.send(eof, len(eof), 0)

    def _send_stream(
        self, sid, attempt, m, side, sender, dest, rows, peers, secret,
        tunnels, tlock, packet_rows, inflight, stats,
    ) -> None:
        """Ship one (side, partition) ROW stream — the JSON fallback
        codec (shuffle_codec=json, or a peer that negotiated down):
        data packets seq 0..k-1 then the EOF marker. Self partitions
        land directly in the local store (no tunnel, no DCN bytes)."""
        local = dest == sender
        if not local:
            # json fallback codec keeps the PR 3 wire discipline:
            # stop-and-wait acks, one packet per round trip
            self._tunnel_for(
                dest, peers, secret=secret, sender=sender,
                tunnels=tunnels, tlock=tlock, inflight=inflight,
                batch_packets=1,
            )
        chunks = [
            rows[a : a + packet_rows]
            for a in range(0, len(rows), packet_rows)
        ]
        for seq, chunk in enumerate(chunks):
            if local:
                self.store.push(
                    sid, attempt, m, side, sender, seq, chunk
                )
                stats["local_rows"] += len(chunk)
                continue
            packet = {
                "sid": sid, "attempt": attempt, "m": m, "side": side,
                "sender": sender, "part": dest, "seq": seq, "rows": chunk,
            }
            # shuffle-json-fallback: serialized ONCE, here in the
            # producer — the bytes size the flow-control window and
            # cross the wire verbatim (wire.splice_id_auth stamps
            # id/auth at the byte level); an unserializable value fails
            # HERE as a non-retryable engine error, not a fake peer
            # death
            t0 = time.perf_counter()
            payload = json.dumps({"shuffle_push": packet}).encode()
            dt = time.perf_counter() - t0
            stats["encode_s"] += dt
            _c_encode_seconds().labels(codec="json").inc(dt)
            _c_codec_bytes().labels(codec="json").inc(len(payload))
            tunnels[dest].send(payload, len(payload), len(chunk))
        if local:
            self.store.push(
                sid, attempt, m, side, sender, -1, None, nseq=len(chunks)
            )
        else:
            eof = {
                "sid": sid, "attempt": attempt, "m": m, "side": side,
                "sender": sender, "part": dest, "seq": -1, "rows": None,
                "nseq": len(chunks),
            }
            # shuffle-json-fallback: the row-codec EOF marker
            payload = json.dumps({"shuffle_push": eof}).encode()
            tunnels[dest].send(payload, len(payload), 0)


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
