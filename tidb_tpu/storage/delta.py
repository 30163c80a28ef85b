"""HTAP delta tier: fleet-replicated writes with snapshot-isolated
delta-merge reads and background compaction.

Reference: TiFlash keeps a delta tree per table — the row-store write
path appends to an in-memory delta layer, analytic reads merge delta +
stable at read time, and a background compaction folds the delta into
the columnar stable layer (PAPER.md; dbms/src/Storages/DeltaMerge in
the reference). Here the coordinator's own table IS the fresh row
store (DML applies write-through, so every local read path keeps its
existing semantics); what the delta tier adds is the ANALYTIC replica
story: the fleet's worker copies were static snapshots loaded at
attach time (the attach_dcn_scheduler contract), so any DML silently
diverged every routed SELECT. Now:

- every Table mutation primitive captures its LOGICAL delta (insert
  row blocks + delete-key sets; whole-rewrite paths capture a reload
  marker) into the catalog's ``DeltaStore`` at a monotonically
  assigned delta-seq;
- a ``DeltaReplicator`` ships the log to the fleet over the
  engine-RPC seam as BINARY columnar frames (parallel/wire.py — the
  delta-sync data plane never touches JSON or materialized rows; the
  check_shuffle_hotpath lint enforces it) with at-most-once seq
  fencing, mirroring the registry-delta / tsdb-row shipping contract;
- routed reads take a snapshot ``(fold, seq)`` — the fold boundary
  pins each worker's base version for the WHOLE dispatch (Table.pin /
  unpin, so version GC can never collect an in-flight routed query's
  input) and the buffered deltas in ``(fold, seq]`` merge INSIDE the
  compiled plan: insert batches become keyed ``L.Staged`` leaves
  (the PR 5 content-keyed fingerprint machinery — merged plans stay
  SharedPlanCache-shareable) unioned above the base scan, delete keys
  become the build side of an anti join (the Flare argument, PAPERS.md:
  the merge is compiled, not an interpreted post-pass);
- a background ``delta-compactor`` daemon folds shipped deltas into
  new columnar base blocks on every worker via the EXISTING
  append_block / delete_where / bump_version path (barriered so every
  worker folds at the same seq boundary — fragment slices index the
  base block concatenation, which must be identical fleet-wide), feeds
  incremental row-count/NDV adjustments into the stats handle, and
  trims the log.

Freshness is a sysvar (``tidb_tpu_read_freshness``): read-your-writes
blocks dispatch until the fleet acks the session's high-water seq;
bounded staleness reads at the fleet's already-acked floor with no
wait ("Fine-Tuning Data Structures for Analytical Query Processing",
PAPERS.md, is the delta-vs-base layout tradeoff this tier encodes).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from tidb_tpu.utils import racecheck
from tidb_tpu.utils.failpoint import inject

#: buffered delta entries per table beyond which the oldest history
#: collapses into one reload marker (bounds coordinator memory when no
#: compactor runs; reload re-ships the base, which is always correct)
MAX_TABLE_DEPTH = 256

#: fold records (base version + folded entries) each worker retains
#: pinned: the CURRENT fold plus the previous one — a query dispatched
#: just before a compaction completes still resolves its snapshot
FOLD_HISTORY = 2

#: delta-sync frame sids are namespaced so the binary-frame router in
#: engine_rpc can split them from shuffle traffic off the header alone
SID_PREFIX = "delta://"


# -- metrics (the `delta` subsystem, scripts/check_metric_names.py) ---------


def _reg():
    from tidb_tpu.utils.metrics import REGISTRY

    return REGISTRY


def _g_depth():
    return _reg().gauge(
        "tidbtpu_delta_depth",
        "buffered delta entries per table (coordinator log)",
        labels=("table",),
    )


def _g_bytes():
    return _reg().gauge(
        "tidbtpu_delta_bytes",
        "approximate bytes buffered in the coordinator delta log",
    )


def _c_batches():
    return _reg().counter(
        "tidbtpu_delta_batches_total",
        "delta entries captured, by kind",
        labels=("kind",),
    )


def _c_sync_frames():
    return _reg().counter(
        "tidbtpu_delta_sync_frames_total",
        "delta-sync frames shipped to workers",
        labels=("host",),
    )


def _c_sync_retrans():
    return _reg().counter(
        "tidbtpu_delta_sync_retransmits_total",
        "delta-sync frames re-shipped after a transport loss",
    )


def _g_sync_lag():
    return _reg().gauge(
        "tidbtpu_delta_sync_lag_entries",
        "coordinator high seq minus this worker's acked seq",
        labels=("host",),
    )


def _c_compactions():
    return _reg().counter(
        "tidbtpu_delta_compactions_total", "completed fold barriers"
    )


def _c_compact_seconds():
    return _reg().counter(
        "tidbtpu_delta_compact_seconds",
        "wall seconds spent in compaction barriers",
    )


def _c_ryw_waits():
    return _reg().counter(
        "tidbtpu_delta_ryw_wait_seconds",
        "seconds routed reads blocked for read-your-writes acks",
    )


def _c_stats_feed():
    return _reg().counter(
        "tidbtpu_delta_stats_adjustments_total",
        "incremental row-count/NDV stats adjustments fed by compaction",
    )


def _c_fold_fallbacks():
    return _reg().counter(
        "tidbtpu_delta_fold_fallbacks_total",
        "worker dispatches whose snapshot fold was unknown (resolved "
        "at the current base instead — degraded consistency window)",
    )


# -- coordinator-side log ---------------------------------------------------


@dataclasses.dataclass
class DeltaEntry:
    """One captured logical mutation. kind:
    - "insert": ``block`` holds the appended rows (storage-name cols);
    - "delete": ``keys`` holds the removed rows' encoded ``key_col``
      values (int64 domain — dates/decimals/dict codes are already
      ints there);
    - "reload": ``blocks`` snapshots the FULL base at capture time
      (whole-rewrite paths: UPDATE rewrites, txn commits, TRUNCATE);
    - "compact": fold barrier — workers fold everything <= ``up_to``
      into their base."""

    seq: int
    db: str
    table: str
    kind: str
    block: Optional[object] = None
    keys: Optional[np.ndarray] = None
    key_col: Optional[str] = None
    blocks: Optional[list] = None
    up_to: int = 0
    nbytes: int = 0
    ts: float = 0.0
    # lazily-encoded wire frames (immutable entries encode once)
    _frames: Optional[List[bytes]] = None


def _block_nbytes(block) -> int:
    n = 0
    for c in block.columns.values():
        n += c.data.nbytes + c.valid.nbytes
    return n


class DeltaStore:
    """Coordinator-side delta log over one catalog. Capture hooks on
    the Table mutation primitives append typed entries here (OUTSIDE
    the table lock — no table<->delta lock-order edge); the replicator
    ships them; the compactor folds + trims."""

    def __init__(self, catalog):
        self.catalog = catalog
        self._lock = racecheck.make_lock("storage.delta")
        self._seq = 0
        self.entries: List[DeltaEntry] = []
        # maintained counters: capture is O(1), never a log scan
        self._depths: Dict[Tuple[str, str], int] = {}
        self._nbytes = 0
        #: highest fold barrier COMPLETED fleet-wide (set by the
        #: replicator after every alive worker acked the fold)
        self.completed_fold_seq = 0
        #: highest seq trim() dropped — a worker acked below this
        #: cannot catch up from the log and takes a full resync
        self.trim_floor = 0

    @classmethod
    def attach(cls, catalog) -> "DeltaStore":
        """Idempotently attach a store to `catalog`: every current
        table gets a capture hook, and catalog.create_table wires
        future ones (storage/catalog.py). Session catalog views
        unwrap to the shared base — one store per store, never per
        session, and the log must never resolve one session's temp
        tables."""
        catalog = getattr(catalog, "_base", catalog)
        store = getattr(catalog, "delta_store", None)
        if store is not None:
            return store
        store = cls(catalog)
        catalog.delta_store = store
        for db in catalog.databases():
            if db.startswith("_") or db == "information_schema":
                continue
            for name in catalog.tables(db):
                try:
                    catalog.table(db, name).delta_log = (store, db)
                except Exception:
                    continue
        return store

    # -- capture (called by Table hooks, outside the table lock) ------
    def _append(self, e: DeltaEntry) -> int:
        inject("delta/capture")
        with self._lock:
            self._seq += 1
            e.seq = self._seq
            e.ts = time.time()
            self.entries.append(e)
            self._nbytes += e.nbytes
            key = (e.db, e.table)
            depth = self._depths.get(key, 0) + (
                1 if e.kind != "compact" else 0
            )
            self._depths[key] = depth
            nbytes = self._nbytes
        _c_batches().labels(kind=e.kind).inc()
        if e.kind != "compact":
            _g_depth().labels(table=f"{e.db}.{e.table}").set(depth)
        _g_bytes().set(nbytes)
        if depth > MAX_TABLE_DEPTH:
            self._collapse(e.db, e.table)
        return e.seq

    def _collapse(self, db: str, table: str) -> None:
        """Cap the per-table log: drop its entries and capture one
        reload marker at the current base (always correct — reload
        re-ships the whole table)."""
        try:
            t = self.catalog.table(db, table)
        except Exception:
            return
        with self._lock:
            kept = []
            for x in self.entries:
                if x.db == db and x.table == table:
                    self._nbytes -= x.nbytes
                else:
                    kept.append(x)
            self.entries = kept
            self._depths[(db, table)] = 0
        self.on_reload(t, db)

    def on_append(self, table, db: str, blocks: list) -> int:
        from tidb_tpu.storage.scan import concat_blocks

        block = concat_blocks(blocks, table.schema.names, table.schema)
        return self._append(DeltaEntry(
            0, db, table.name, "insert", block=block,
            nbytes=_block_nbytes(block),
        ))

    def on_delete(self, table, db: str, keys, key_col) -> int:
        if keys is None or key_col is None:
            return self.on_reload(table, db)
        keys = np.asarray(keys, dtype=np.int64)
        return self._append(DeltaEntry(
            0, db, table.name, "delete", keys=keys, key_col=key_col,
            nbytes=keys.nbytes,
        ))

    def on_reload(self, table, db: str) -> int:
        blocks = list(table.blocks())
        return self._append(DeltaEntry(
            0, db, table.name, "reload", blocks=blocks,
            nbytes=sum(_block_nbytes(b) for b in blocks),
        ))

    def append_compact(self) -> DeltaEntry:
        """Append a fold barrier covering everything captured so far."""
        with self._lock:
            up_to = self._seq
        e = DeltaEntry(0, "", "", "compact", up_to=up_to)
        self._append(e)
        return e

    # -- reads ---------------------------------------------------------
    def high_seq(self) -> int:
        with self._lock:
            return self._seq

    def entries_after(self, seq: int) -> List[DeltaEntry]:
        with self._lock:
            return [e for e in self.entries if e.seq > seq]

    def depth(self, db: str, table: str) -> int:
        with self._lock:
            return self._depths.get((db, table), 0)

    def max_depth(self) -> int:
        with self._lock:
            return max(self._depths.values(), default=0)

    def total_bytes(self) -> int:
        with self._lock:
            return self._nbytes

    def next_seqs(self, n: int) -> int:
        """Allocate n fresh seqs WITHOUT log entries (resync reload
        shipping: each ad-hoc entry needs its own seq or the worker's
        duplicate fence would drop every table after the first).
        Returns the first allocated seq."""
        with self._lock:
            first = self._seq + 1
            self._seq += int(n)
            return first

    def trim(self, up_to: int) -> None:
        """Drop entries <= up_to (their fold completed fleet-wide)."""
        with self._lock:
            kept = []
            for e in self.entries:
                if e.seq <= up_to:
                    self._nbytes -= e.nbytes
                    if e.kind != "compact":
                        k = (e.db, e.table)
                        self._depths[k] = max(
                            self._depths.get(k, 0) - 1, 0
                        )
                else:
                    kept.append(e)
            self.entries = kept
            self.trim_floor = max(self.trim_floor, up_to)
            nbytes = self._nbytes
        _g_bytes().set(nbytes)

    def status(self) -> dict:
        with self._lock:
            return {
                "high_seq": self._seq,
                "entries": len(self.entries),
                "completed_fold_seq": self.completed_fold_seq,
                "bytes": self._nbytes,
            }


# -- wire encoding (binary data plane; no JSON, no row loops) ---------------


def _schema_outcols(table, names=None):
    from tidb_tpu.planner.logical import OutCol

    types = table.schema.types
    return [
        OutCol(None, n, n, types[n])
        for n in (names or table.schema.names)
    ]


def encode_entry_frames(entry: DeltaEntry, table) -> List[bytes]:
    """Encode one log entry as binary delta-sync frames
    (parallel/wire.py columnar codec — the delta data plane ships no
    JSON and materializes no rows; check_shuffle_hotpath enforces).
    Cached on the entry: the log is append-only, so each entry encodes
    exactly once no matter how many workers it ships to."""
    from tidb_tpu.parallel import wire
    from tidb_tpu.storage.scan import concat_blocks

    if entry._frames is not None:
        return entry._frames
    sid = f"{SID_PREFIX}{entry.db}/{entry.table}/{entry.kind}"
    frames: List[bytes] = []
    if entry.kind == "insert":
        frames.append(wire.encode_frame(
            sid, 0, 0, 0, 0, 0, entry.seq, entry.block,
            _schema_outcols(table),
        ))
    elif entry.kind == "delete":
        from tidb_tpu.chunk import HostBlock, HostColumn
        from tidb_tpu.dtypes import INT64

        kb = HostBlock(
            {entry.key_col: HostColumn(
                INT64, entry.keys.astype(np.int64),
                np.ones(len(entry.keys), dtype=bool), None,
            )},
            len(entry.keys),
        )
        from tidb_tpu.planner.logical import OutCol

        frames.append(wire.encode_frame(
            sid, 0, 0, 0, 0, 0, entry.seq, kb,
            [OutCol(None, entry.key_col, entry.key_col, INT64)],
        ))
    elif entry.kind == "reload":
        blocks = entry.blocks or []
        nparts = max(len(blocks), 1)
        if not blocks:
            # empty reload (TRUNCATE): one zero-row frame still carries
            # the part count so the receiver applies the truncation
            blocks = [concat_blocks([], table.schema.names, table.schema)]
        for i, b in enumerate(blocks):
            norm = concat_blocks([b], table.schema.names, table.schema)
            frames.append(wire.encode_frame(
                sid, 0, nparts, 0, 0, i, entry.seq, norm,
                _schema_outcols(table),
            ))
    entry._frames = frames
    return frames


# -- worker-side replica state ----------------------------------------------


@dataclasses.dataclass
class _Fold:
    """One applied fold on this worker's base: the version it
    published and the one it superseded (both pinned while the record
    is retained — in-flight snapshots at older seqs still resolve),
    plus the (seq, entry) list it consumed. Fold records PARTITION the
    seq axis per table: record X holds exactly the entries in
    (previous fold's seq, X.seq], and the live buffer holds everything
    newer — so any snapshot seq maps to one base version plus one
    contiguous merge window."""

    seq: int
    version: int
    prev_version: int
    entries: List[Tuple[int, dict]]


class _TableReplica:
    __slots__ = ("buffered", "folds", "reload_parts")

    def __init__(self):
        # seq -> decoded entry dict; INVARIANT: every seq here is
        # newer than the last fold record's seq
        self.buffered: "OrderedDict[int, dict]" = OrderedDict()
        self.folds: deque = deque()
        # seq -> {part: block} for multi-frame reloads in flight
        self.reload_parts: Dict[int, dict] = {}


class DeltaReplicaState:
    """Worker half of the delta tier: buffers shipped entries per
    table (seq-fenced, at-most-once), folds them into the local base
    (reload markers eagerly on arrival, insert/delete batches on
    compact barriers) via the existing Table write path, and serves
    snapshot merge views to the dispatch execution path. Folds and
    snapshot resolution serialize on one lock, so a resolver can
    never pin a half-applied fold."""

    def __init__(self, catalog):
        self.catalog = catalog
        self._lock = racecheck.make_rlock("storage.delta_replica")
        self._tables: Dict[Tuple[str, str], _TableReplica] = {}
        #: highest GLOBAL seq applied contiguously (acked to the
        #: coordinator; compact barriers advance it too)
        self.acked_seq = 0
        self.folded_seq = 0

    def _rec(self, db: str, table: str) -> _TableReplica:
        key = (db.lower(), table.lower())
        rec = self._tables.get(key)
        if rec is None:
            rec = self._tables[key] = _TableReplica()
        return rec

    def _ensure_table(self, db: str, table: str, block) -> None:
        """A delta frame for a table this replica never loaded: a
        coordinator-side CREATE TABLE after attach. Materialize it
        from the frame's wire schema (column order and logical types
        ride every frame) so the NEW table serves routed reads like
        any loaded one — key metadata stays coordinator-side, which
        only uniqueness re-checks on fold would consume."""
        try:
            self.catalog.table(db, table)
            return
        except Exception:
            pass
        from tidb_tpu.storage.table import TableSchema

        try:
            self.catalog.create_database(db, if_not_exists=True)
            self.catalog.create_table(
                db, table,
                TableSchema(
                    columns=[
                        (n, c.type) for n, c in block.columns.items()
                    ]
                ),
                if_not_exists=True,
            )
        except Exception:
            pass

    def _push_fold(self, t, rec: _TableReplica, fold: _Fold) -> None:
        """Record one fold. BOTH versions arrive ALREADY pinned (the
        pre-image must be pinned BEFORE the fold mutates the table —
        with no GC life window the table keeps only {current, prev,
        pins}, so a fold's 2+ version bumps would collect an unpinned
        pre-image before the record lands)."""
        rec.folds.append(fold)
        while len(rec.folds) > FOLD_HISTORY:
            old = rec.folds.popleft()
            t.unpin(old.version)
            t.unpin(old.prev_version)

    # -- apply (delta_sync frames) ------------------------------------
    def apply_frame(self, pkt: dict) -> int:
        """One decoded delta-sync frame. Returns the acked seq.
        Duplicates/stale seqs drop off the seq fence alone — a
        retransmitted frame can never double-buffer. Reload markers
        (whole-rewrite DML paths) fold EAGERLY: the shipped snapshot
        replaces this replica's base in one fold record, superseding
        the buffered entries it subsumes."""
        inject("delta/apply")
        sid = pkt["sid"]
        assert sid.startswith(SID_PREFIX)
        db, table, kind = sid[len(SID_PREFIX):].split("/", 2)
        seq = int(pkt["seq"])
        with self._lock:
            if seq <= self.acked_seq:
                return self.acked_seq  # duplicate/retransmit: fenced
            rec = self._rec(db, table)
            if kind == "insert":
                self._ensure_table(db, table, pkt["block"])
                rec.buffered[seq] = {"kind": "insert", "block": pkt["block"]}
            elif kind == "delete":
                block = pkt["block"]
                key_col = next(iter(block.columns))
                c = block.columns[key_col]
                rec.buffered[seq] = {
                    "kind": "delete",
                    "keys": np.asarray(c.data, dtype=np.int64),
                    "key_col": key_col,
                }
            elif kind == "reload":
                parts = rec.reload_parts.setdefault(seq, {})
                parts[int(pkt["part"])] = pkt["block"]
                nparts = int(pkt["m"]) or 1
                if len(parts) < nparts:
                    return self.acked_seq  # await remaining parts
                blocks = [parts[i] for i in sorted(parts)]
                del rec.reload_parts[seq]
                self._fold_reload(db, table, rec, seq, blocks)
            self.acked_seq = seq
            return self.acked_seq

    def _fold_reload(self, db, table, rec, seq, blocks) -> None:
        """Eager reload fold (caller holds the lock): the shipped base
        snapshot replaces this replica's blocks — via clear_rows +
        append_block so string dictionaries rebuild/align exactly like
        a fresh load — and the superseded buffered entries move into
        the fold record for snapshots still pinned before it."""
        inject("delta/compact-apply")
        if blocks:
            self._ensure_table(db, table, blocks[0])
        try:
            t = self.catalog.table(db, table)
        except Exception:
            return
        superseded = [
            (s, rec.buffered.pop(s))
            for s in sorted([s for s in rec.buffered if s <= seq])
        ]
        prev = t.pin_current()  # pre-image pinned BEFORE any mutation
        t.clear_rows()
        for b in blocks:
            if b.nrows:
                t.append_block(b)
        v = t.bump_version()
        t.pin(v)
        self._push_fold(t, rec, _Fold(seq, v, prev, superseded))

    # -- fold (compact barrier) ----------------------------------------
    def apply_compact(self, up_to: int, seq: int) -> int:
        """Fold every buffered entry <= up_to into the local base via
        the existing delete_where/append_block/bump_version path, one
        fold record per touched table. Idempotent: a re-shipped
        barrier whose work already happened just acks."""
        with self._lock:
            if seq <= self.folded_seq:
                self.acked_seq = max(self.acked_seq, seq)
                return self.acked_seq
            inject("delta/compact-apply")
            for (db, table), rec in list(self._tables.items()):
                seqs = sorted(s for s in rec.buffered if s <= up_to)
                if not seqs:
                    continue
                try:
                    t = self.catalog.table(db, table)
                except Exception:
                    continue
                entries = [(s, rec.buffered.pop(s)) for s in seqs]
                prev = t.pin_current()  # pinned BEFORE the mutations
                self._fold_into(t, [e for _s, e in entries])
                v = t.bump_version()
                t.pin(v)
                self._push_fold(t, rec, _Fold(up_to, v, prev, entries))
            self.folded_seq = seq
            self.acked_seq = max(self.acked_seq, seq)
            return self.acked_seq

    @staticmethod
    def _fold_into(t, entries: List[dict]) -> None:
        """Apply decoded entries in seq order through the EXISTING
        columnar write path (delete_where masks + append_block) —
        compaction produces ordinary base blocks, indistinguishable
        from a fresh load."""
        for e in entries:
            if e["kind"] == "delete":
                key_col, keys = e["key_col"], e["keys"]
                masks = []
                for b in t.blocks():
                    c = b.columns.get(key_col)
                    if c is None:
                        masks.append(np.ones(b.nrows, dtype=bool))
                        continue
                    dead = np.isin(
                        c.data.astype(np.int64), keys
                    ) & c.valid
                    masks.append(~dead)
                t.delete_where(masks)
            elif e["kind"] == "insert":
                if e["block"].nrows:
                    t.append_block(e["block"])

    # -- snapshot resolution / merge views ------------------------------
    def resolve_base(self, db: str, table: str, snap_seq: int):
        """(base version, fold seq) this worker serves for snapshot
        ``snap_seq``: the newest fold at-or-before it (base includes
        exactly the entries <= that fold). None version = the live
        current version (no folds past the snapshot). Caller holds
        pins via pin_verified."""
        with self._lock:
            rec = self._rec(db, table)
            if not rec.folds or snap_seq >= rec.folds[-1].seq:
                return None, (
                    rec.folds[-1].seq if rec.folds else 0
                )
            base = None
            base_seq = 0
            for f in rec.folds:
                if f.seq <= snap_seq:
                    base, base_seq = f.version, f.seq
            if base is None:
                # older than every retained fold: the oldest record's
                # pre-image is the closest consistent base
                _c_fold_fallbacks().inc()
                return rec.folds[0].prev_version, 0
            return base, base_seq

    def resolve_pinned(self, db: str, table: str, t, snap_seq: int):
        """resolve_base + pin in ONE lock hold: folds serialize on the
        same lock, so the pinned version can neither be superseded nor
        GC'd between resolution and the pin landing. Returns
        (pinned version, base fold seq for the merge window)."""
        with self._lock:
            v, base_seq = self.resolve_base(db, table, snap_seq)
            if v is None:
                return t.pin_current(), base_seq
            t.pin(v)
            return v, base_seq

    def merge_view(self, db: str, table: str, base_seq: int,
                   up_to_seq: int):
        """Net merge inputs for the window ``(base_seq, up_to_seq]``:
        (insert blocks, per-block alive masks, base delete-key array,
        key column, depth). Entries apply in seq order — a delete
        kills earlier pending inserts of the same key; a later
        re-insert survives. Fold records newer than the snapshot
        contribute their RETAINED entries, so a read pinned at an
        older boundary merges exactly what its base lacks."""
        with self._lock:
            rec = self._rec(db, table)
            seqs: List[Tuple[int, dict]] = []
            for f in rec.folds:
                for s, e in f.entries:
                    if base_seq < s <= up_to_seq:
                        seqs.append((s, e))
            for s, e in rec.buffered.items():
                if base_seq < s <= up_to_seq:
                    seqs.append((s, e))
        seqs.sort(key=lambda x: x[0])
        ins_blocks: List = []
        alive: List[np.ndarray] = []
        del_keys: List[np.ndarray] = []
        key_col = None
        depth = 0
        for _s, e in seqs:
            depth += 1
            if e["kind"] == "insert":
                b = e["block"]
                ins_blocks.append(b)
                alive.append(np.ones(b.nrows, dtype=bool))
            elif e["kind"] == "delete":
                key_col = e["key_col"]
                keys = e["keys"]
                del_keys.append(keys)
                for b, m in zip(ins_blocks, alive):
                    c = b.columns.get(key_col)
                    if c is not None:
                        m &= ~(
                            np.isin(c.data.astype(np.int64), keys)
                            & c.valid
                        )
        dk = (
            np.unique(np.concatenate(del_keys))
            if del_keys else None
        )
        return ins_blocks, alive, dk, key_col, depth

    def status(self) -> dict:
        with self._lock:
            return {
                "acked_seq": self.acked_seq,
                "folded_seq": self.folded_seq,
                "tables": {
                    f"{db}.{tb}": {
                        "buffered": len(rec.buffered),
                        "folds": [f.seq for f in rec.folds],
                    }
                    for (db, tb), rec in self._tables.items()
                },
            }


# -- plan merge (delta batches as keyed L.Staged leaves) --------------------


def _staged_from_block(schema, block, dicts, key: str):
    """A keyed Staged leaf over a HostBlock whose columns are already
    named with the schema's internal names. Keyed: the batch is a
    runtime input and the plan-cache fingerprint carries shape + dict
    content (PR 5), so delta growth reuses the compiled merge until
    the capacity tile changes."""
    from tidb_tpu.chunk import block_to_batch, pad_capacity
    from tidb_tpu.planner import logical as L

    batch = block_to_batch(block, pad_capacity(max(block.nrows, 1)))
    return L.Staged(schema, batch=batch, dicts=dicts, nonce=0, key=key)


def merge_scan_plan(plan, view_fn):
    """Rewrite every Scan whose table has a live delta view into the
    compiled merge shape::

        UnionAll
        ├── JoinPlan(anti, on pk)          # base minus delete keys
        │   ├── Scan(base @ pinned fold)   # keeps its frag slice
        │   └── Staged(delete keys, keyed)
        └── Staged(net inserts, keyed)     # frag-sliced like the scan

    ``view_fn(db, table, frag) -> (ins_block, del_keys, key_col,
    depth) | None``; the insert block is already net-of-deletes and
    frag-sliced (fragment slices must partition the delta exactly like
    they partition the base — disjoint per host, covering in union).
    Returns (plan, merged_stats)."""
    import dataclasses as _dc

    from tidb_tpu.dtypes import INT64
    from tidb_tpu.expression.expr import ColumnRef
    from tidb_tpu.planner import logical as L
    from tidb_tpu.planner.logical import OutCol, Schema

    stats = {"depth": 0, "ins_rows": 0, "del_keys": 0}

    def rewrite(p):
        if isinstance(p, L.Scan):
            view = view_fn(p.db, p.table, p.frag)
            if view is None:
                return p
            ins_block, del_keys, key_col, depth = view
            stats["depth"] += depth
            node = p
            schema = p.schema
            if del_keys is not None and len(del_keys):
                stats["del_keys"] += int(len(del_keys))
                if key_col not in p.columns:
                    ktype = INT64
                    schema = Schema(list(p.schema.cols) + [
                        OutCol(p.alias, key_col,
                               f"{p.alias}.{key_col}", ktype)
                    ])
                    node = _dc.replace(
                        p, columns=list(p.columns) + [key_col],
                        schema=schema,
                    )
                kc = next(
                    (c for c in schema.cols if c.name == key_col), None
                )
                ktype = kc.type if kc is not None else INT64
                from tidb_tpu.chunk import HostBlock, HostColumn

                del_int = f"\x01delta.{p.alias}.{key_col}"
                kb = HostBlock(
                    {del_int: HostColumn(
                        INT64, del_keys.astype(np.int64),
                        np.ones(len(del_keys), dtype=bool), None,
                    )},
                    len(del_keys),
                )
                del_schema = Schema(
                    [OutCol(None, del_int, del_int, INT64)]
                )
                staged_del = _staged_from_block(
                    del_schema, kb, {},
                    key=f"delta/{p.db}.{p.table}/del",
                )
                node = L.JoinPlan(
                    schema, "anti", node, staged_del,
                    equi_keys=[(
                        ColumnRef(ktype, f"{p.alias}.{key_col}"),
                        ColumnRef(INT64, del_int),
                    )],
                )
            if ins_block is not None and ins_block.nrows:
                stats["ins_rows"] += int(ins_block.nrows)
                # rename storage columns to the scan's internal names;
                # columns the scan does not read are dropped
                from tidb_tpu.chunk import HostBlock as _HB

                cols = {}
                dicts = {}
                for oc in schema.cols:
                    c = ins_block.columns.get(oc.name)
                    if c is None:
                        import dataclasses as _d2

                        from tidb_tpu.chunk import column_from_values

                        c = column_from_values(
                            [None] * ins_block.nrows, oc.type
                        )
                    cols[oc.internal] = c
                    if c.dictionary is not None:
                        dicts[oc.internal] = c.dictionary
                staged_ins = _staged_from_block(
                    schema, _HB(cols, ins_block.nrows), dicts,
                    key=f"delta/{p.db}.{p.table}/ins",
                )
                node = L.UnionAll(schema, children=[node, staged_ins])
            return node
        for attr in ("child", "left", "right"):
            c = getattr(p, attr, None)
            if c is not None:
                p = _dc.replace(p, **{attr: rewrite(c)})
        kids = getattr(p, "children", None)
        if kids:
            p = _dc.replace(p, children=[rewrite(c) for c in kids])
        return p

    return rewrite(plan), stats


def _slice_net_inserts(ins_blocks, alive, frag, outcols):
    """Net-alive insert rows as ONE block (storage column names,
    string dictionaries UNIFIED across batches — each shipped frame
    carries its own pruned vocabulary), frag-sliced: (idx, n) over the
    alive-row concatenation — the same disjoint cover the base scan's
    slice takes, so each host merges its share of the delta exactly
    once."""
    from tidb_tpu.chunk import HostBlock, concat_host_columns, take_block

    kept = []
    for b, m in zip(ins_blocks, alive):
        if m.all():
            kept.append(b)
        elif m.any():
            kept.append(take_block(b, np.nonzero(m)[0]))
    if not kept:
        return None
    total = sum(b.nrows for b in kept)
    if not total:
        return None
    cols = {
        oc.name: concat_host_columns(
            oc.type, [b.columns[oc.name] for b in kept
                      if oc.name in b.columns]
        )
        for oc in outcols
    }
    block = HostBlock(cols, total)
    if frag is not None:
        fi, fn = int(frag[0]), int(frag[1])
        block = take_block(block, np.arange(fi, block.nrows, fn))
    return block


def scans_in(plan) -> List:
    from tidb_tpu.planner import logical as L

    out = []

    def walk(p):
        if isinstance(p, L.Scan):
            out.append(p)
        for attr in ("child", "left", "right"):
            c = getattr(p, attr, None)
            if c is not None:
                walk(c)
        for c in getattr(p, "children", []) or []:
            walk(c)

    walk(plan)
    return out


def prepare_worker_plan(catalog, state, plan, snap, pins):
    """The worker-dispatch half of snapshot isolation (engine_rpc
    _execute and the shuffle task runner both enter here). Pins every
    scanned table's base version for the WHOLE dispatch and, when this
    process is a delta replica, rewrites the plan to merge buffered
    deltas in ``(fold, seq]``. Returns (plan, table_hook, merge_stats
    or None); the caller unpins ``pins`` after the run."""
    if not snap:
        return plan, None, None
    resolved: Dict[Tuple[str, str], Tuple[object, int]] = {}
    base_seqs: Dict[Tuple[str, str], int] = {}
    merge_stats = None
    seq = int(snap.get("seq") or 0)
    shipped = snap.get("tables") or {}
    for s in scans_in(plan):
        key = (s.db.lower(), s.table.lower())
        if key in resolved:
            continue
        try:
            t = catalog.table(s.db, s.table)
        except Exception:
            continue
        if state is not None:
            v, base_seqs[key] = state.resolve_pinned(
                s.db, s.table, t, seq
            )
        else:
            # shared-catalog servers: the coordinator's pinned version
            # numbers ARE this catalog's — resolve the shipped snapshot
            # so every fragment of the query reads one version even
            # while concurrent writers publish new ones (the unpinned
            # routed-read hole this closes)
            v = shipped.get(f"{s.db.lower()}.{s.table.lower()}")
            if v is None or not t.pin_verified(int(v)):
                v = t.pin_current()
            else:
                v = int(v)
        pins.append((t, v))
        resolved[key] = (t, v)
    if state is not None and seq:
        def view_fn(db, table, frag):
            key = (db.lower(), table.lower())
            ins_blocks, alive, dk, key_col, depth = state.merge_view(
                db, table, base_seqs.get(key, 0), seq
            )
            if depth == 0:
                return None
            t, _v = resolved.get(key, (None, 0))
            if t is None:
                t = catalog.table(db, table)
            block = _slice_net_inserts(
                ins_blocks, alive, frag, _schema_outcols(t)
            )
            if block is None and (dk is None or not len(dk)):
                return None
            return block, dk, key_col, depth

        plan, merge_stats = merge_scan_plan(plan, view_fn)
        if merge_stats["depth"] == 0:
            merge_stats = None

    def table_hook(db, table, _r=resolved, _c=catalog):
        hit = _r.get((db.lower(), table.lower()))
        if hit is not None:
            return hit
        t = _c.table(db, table)
        return t, t.version

    return plan, table_hook, merge_stats


# -- coordinator-side replication + freshness --------------------------------


class DeltaSyncTimeout(RuntimeError):
    """Read-your-writes could not confirm the fleet acked the
    session's high-water seq inside the timeout — surfaced as a
    statement error (never a silent stale read)."""


class DeltaReplicator:
    """Ships the coordinator delta log to the fleet over the
    engine-RPC seam and runs the barriered fold protocol. Owned by a
    DCNFragmentScheduler (attach_delta); duck-typed over its endpoint
    pool so this module never imports parallel/dcn."""

    def __init__(self, store: DeltaStore, scheduler):
        self.store = store
        self.sched = scheduler
        self._lock = racecheck.make_lock("storage.compactor")
        #: endpoint address -> highest seq that worker acked
        self.acked: Dict[str, int] = {}
        #: snapshots never resolve below this: a resync folds the
        #: whole base at fresh pseudo-seqs, so reads at older seqs on
        #: the resync'd worker would fall behind its fold history
        self._min_snapshot_seq = 0

    # -- shipping ------------------------------------------------------
    def _ship_to(self, ep, target_seq: int, kill_check=None) -> int:
        """Ship entries (acked, target] to one endpoint; returns its
        new acked seq. Transport losses retransmit over a fresh pooled
        connection — the worker's seq fence makes that at-most-once."""
        addr = ep.address
        acked = self.acked.get(addr, 0)
        if acked >= target_seq:
            return acked
        entries = [
            e for e in self.store.entries_after(acked)
            if e.seq <= target_seq
        ]
        for attempt in (1, 2):
            try:
                with self.sched._pool(ep).lease() as conn:
                    for e in entries:
                        if e.seq <= self.acked.get(addr, 0):
                            continue
                        if kill_check is not None:
                            kill_check()
                        inject("delta/ship")
                        if e.kind == "compact":
                            resp = conn.call({"delta_compact": {
                                "up_to": e.up_to, "seq": e.seq,
                            }})
                            if not resp.get("ok"):
                                raise RuntimeError(
                                    f"delta_compact rejected: "
                                    f"{resp.get('error', '')}"
                                )
                            self._note_ack(
                                addr, int(resp.get("acked", e.seq))
                            )
                            continue
                        t = self.store.catalog.table(e.db, e.table)
                        for frame in encode_entry_frames(e, t):
                            _c_sync_frames().labels(host=addr).inc()
                            acked_seq = conn.delta_sync_encoded(frame)
                            self._note_ack(addr, acked_seq)
                break
            except (
                ConnectionError, OSError, TimeoutError,
            ):
                if attempt == 2:
                    raise
                _c_sync_retrans().inc()
        return self.acked.get(addr, 0)

    def _note_ack(self, addr: str, acked_seq: int) -> None:
        with self._lock:
            if acked_seq > self.acked.get(addr, 0):
                self.acked[addr] = acked_seq
        _g_sync_lag().labels(host=addr).set(
            max(self.store.high_seq() - acked_seq, 0)
        )

    def _resync_fleet(self, eps) -> None:
        """Full resync: ship ad-hoc reload entries (current
        coordinator base) for every delta-tracked table to EVERY
        alive worker. Triggered when any replica's acked seq fell
        behind the trimmed log (a quarantined worker re-admitted
        after folds). FLEET-WIDE by design: fragment slices index
        each worker's own base block concatenation, so the reload
        fold must land on every base or the slices stop partitioning
        one row set — the already-current workers fold an identical
        image, which is a no-op in content. One FRESH seq per table
        (the duplicate fence keys on the global seq — same-seq
        reloads would silently skip every table after the first);
        reads from here on resolve at-or-past the resync folds."""
        cat = self.store.catalog
        tracked = []
        for db in cat.databases():
            if db.startswith("_"):
                continue
            for name in cat.tables(db):
                t = cat.table(db, name)
                if getattr(t, "delta_log", None) is not None:
                    tracked.append((db, name, t))
        if not tracked:
            high = self.store.high_seq()
            for ep in eps:
                self._note_ack(ep.address, high)
            return
        first = self.store.next_seqs(len(tracked))
        entries = [
            DeltaEntry(
                first + i, db, name, "reload", blocks=list(t.blocks())
            )
            for i, (db, name, t) in enumerate(tracked)
        ]
        for ep in eps:
            with self.sched._pool(ep).lease() as conn:
                for entry, (_db, _name, t) in zip(entries, tracked):
                    for frame in encode_entry_frames(entry, t):
                        _c_sync_frames().labels(host=ep.address).inc()
                        self._note_ack(
                            ep.address, conn.delta_sync_encoded(frame)
                        )
        with self._lock:
            self._min_snapshot_seq = max(
                self._min_snapshot_seq, first + len(tracked) - 1
            )

    def ship_all(self, target_seq=None, kill_check=None,
                 quarantine: bool = False) -> None:
        """Ship pending entries to every alive worker. With
        ``quarantine`` a per-host transport failure quarantines that
        host (the dispatch-path rule: a dead replica must not wedge
        the fleet's freshness) instead of raising."""
        target = (
            self.store.high_seq() if target_seq is None else target_seq
        )
        alive = self.sched.alive_endpoints()
        floor = self.store.trim_floor
        if floor and any(
            self.acked.get(ep.address, 0) < floor for ep in alive
        ):
            # a replica missed trimmed entries: fleet-wide reload
            # resync (bases must stay identical — see _resync_fleet)
            try:
                self._resync_fleet(alive)
            except (ConnectionError, OSError, TimeoutError):
                if not quarantine:
                    raise
        for ep in self.sched.alive_endpoints():
            try:
                self._ship_to(ep, target, kill_check=kill_check)
            except (ConnectionError, OSError, TimeoutError):
                if not quarantine:
                    raise
                try:
                    self.sched._quarantine(ep)
                except Exception:
                    pass

    # -- freshness -----------------------------------------------------
    def floor_seq(self) -> int:
        """Bounded staleness snapshot: the highest seq EVERY alive
        worker already acked (no wait). Never below the completed fold
        boundary — base blocks past a fold cannot be un-merged."""
        alive = self.sched.alive_endpoints()
        with self._lock:
            floor = max(
                min(
                    (self.acked.get(ep.address, 0) for ep in alive),
                    default=0,
                ),
                self._min_snapshot_seq,
            )
        return max(floor, self.store.completed_fold_seq)

    def prepare_read(self, mode: str, hwm: int, kill_check=None,
                     timeout_s: float = 30.0) -> int:
        """Resolve a routed read's snapshot seq by freshness mode.
        read_your_writes ships + blocks until every alive worker acked
        the session's high-water seq; bounded reads at the acked floor
        with zero wait."""
        if mode != "read_your_writes":
            return self.floor_seq()
        t0 = time.perf_counter()
        deadline = time.monotonic() + timeout_s
        try:
            while True:
                # a dead replica quarantines instead of wedging every
                # read-your-writes statement until its timeout
                self.ship_all(
                    target_seq=hwm, kill_check=kill_check,
                    quarantine=True,
                )
                alive = self.sched.alive_endpoints()
                if all(
                    self.acked.get(ep.address, 0) >= hwm
                    for ep in alive
                ):
                    return max(hwm, self.floor_seq())
                if time.monotonic() > deadline:
                    raise DeltaSyncTimeout(
                        f"read-your-writes: fleet did not ack delta "
                        f"seq {hwm} within {timeout_s:g}s"
                    )
                if kill_check is not None:
                    kill_check()
                time.sleep(0.01)
        finally:
            _c_ryw_waits().inc(time.perf_counter() - t0)

    # -- snapshot construction (pins held by the caller) ---------------
    def build_snapshot(self, seq: Optional[int]) -> dict:
        return {
            "seq": int(
                seq if seq is not None else self.floor_seq()
            ),
            "fold": int(self.store.completed_fold_seq),
        }

    # -- compaction (barriered fold) -----------------------------------
    def compact_now(self, kill_check=None, timeout_s: float = 30.0,
                    catalog=None) -> bool:
        """One fold barrier: ship everything, append the compact
        entry, ship it, and wait until EVERY alive worker acked the
        fold (fragment slices index the base concatenation, so folds
        must land fleet-wide before any snapshot reads past them).
        Then trim the log and feed incremental stats. A worker that
        dies mid-barrier QUARANTINES (the fleet absorbs it — fragment
        dispatch stopped trusting it the same moment) and the barrier
        completes on the survivor set; if NO worker survives, the
        round aborts with completed_fold_seq unchanged and the next
        tick retries."""
        t0 = time.perf_counter()
        store = self.store
        high = store.high_seq()
        if high <= store.completed_fold_seq:
            return False
        self.ship_all(kill_check=kill_check, quarantine=True)
        if not self.sched.alive_endpoints():
            return False
        # net per-table adjustments BEFORE trim (stats feed below)
        adjustments = self._net_adjustments(high)
        entry = store.append_compact()
        deadline = time.monotonic() + timeout_s
        while True:
            self.ship_all(
                target_seq=entry.seq, kill_check=kill_check,
                quarantine=True,
            )
            alive = self.sched.alive_endpoints()
            if not alive:
                return False
            if all(
                self.acked.get(ep.address, 0) >= entry.seq
                for ep in alive
            ):
                break
            if time.monotonic() > deadline:
                return False
            time.sleep(0.01)
        with self._lock:
            store.completed_fold_seq = entry.up_to
        store.trim(entry.seq)
        self._feed_stats(adjustments, catalog or store.catalog)
        for db_table in adjustments:
            _g_depth().labels(table=db_table).set(0)
        _c_compactions().inc()
        _c_compact_seconds().inc(time.perf_counter() - t0)
        return True

    def _net_adjustments(self, up_to: int) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for e in self.store.entries_after(0):
            if e.seq > up_to or e.kind == "compact":
                continue
            d = out.setdefault(
                f"{e.db}.{e.table}",
                {"ins": 0, "del": 0, "reload": False, "blocks": []},
            )
            if e.kind == "insert":
                d["ins"] += e.block.nrows
                d["blocks"].append(e.block)
            elif e.kind == "delete":
                d["del"] += len(e.keys)
            else:
                d["reload"] = True
        return out

    def _feed_stats(self, adjustments: Dict[str, dict], catalog) -> None:
        """Incremental stats maintenance: folded row-count deltas and
        per-column NDV bumps land on the existing stats objects
        directly — the auto-analyze ratio still governs full refreshes
        (the modify counters moved at write time), but the planner's
        row counts stop lagging a whole analyze cycle behind the
        delta tier."""
        for db_table, adj in adjustments.items():
            db, table = db_table.split(".", 1)
            try:
                t = catalog.table(db, table)
            except Exception:
                continue
            stats = getattr(t, "stats", None)
            if not stats or adj["reload"]:
                continue
            net = adj["ins"] - adj["del"]
            for col, cs in stats.items():
                cs.row_count = max(cs.row_count + net, 0)
                new_vals = set()
                for b in adj["blocks"]:
                    c = b.columns.get(col)
                    if c is None or not len(c.data):
                        continue
                    vals = c.data[c.valid]
                    if len(vals):
                        new_vals.update(
                            np.unique(vals)[:64].tolist()
                        )
                if new_vals:
                    cs.ndv = max(
                        cs.ndv, min(cs.ndv + len(new_vals), cs.row_count)
                    )
            _c_stats_feed().inc()

    def status(self) -> dict:
        with self._lock:
            acked = dict(self.acked)
        return {
            "acked": acked,
            "floor_seq": self.floor_seq(),
            "completed_fold_seq": self.store.completed_fold_seq,
            "high_seq": self.store.high_seq(),
        }


class DeltaCompactor:
    """Background fold daemon (the delta-compactor of the reference's
    delta tree): folds when the log is deep enough, on a bounded
    cadence. One per attached scheduler; stop() on close."""

    def __init__(self, replicator: DeltaReplicator, catalog,
                 interval_s: float = 0.5, depth_threshold: int = 32):
        self.replicator = replicator
        self.catalog = catalog
        self.interval_s = float(interval_s)
        self.depth_threshold = int(depth_threshold)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def tick(self) -> bool:
        if self.replicator.store.max_depth() < self.depth_threshold:
            return False
        return self.replicator.compact_now(catalog=self.catalog)

    def start(self) -> None:
        if self._thread is not None or self.interval_s <= 0:
            return
        self._stop.clear()

        def loop():
            from tidb_tpu.obs.flight import FLIGHT

            while not self._stop.wait(self.interval_s):
                try:
                    with FLIGHT.background("delta-compactor"):
                        self.tick()
                except Exception:
                    continue  # compaction must never kill the daemon

        self._thread = threading.Thread(
            target=loop, name="delta-compactor", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
