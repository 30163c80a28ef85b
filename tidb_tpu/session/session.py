"""Session: parse -> plan -> execute -> result, plus DDL/DML dispatch.

Reference: pkg/session (session.ExecuteStmt session.go:2001 driving
Compile -> runStmt -> ExecStmt.Exec) and pkg/testkit (TestKit.MustExec /
MustQuery against an embedded store, testkit.go:71) — this class is both:
the embedded single-process session AND the test harness entry point.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from tidb_tpu.chunk import column_from_values, materialize_rows, HostBlock
from tidb_tpu.dtypes import Kind, SQLType
from tidb_tpu.parser import ast, parse
from tidb_tpu.planner import build_query
from tidb_tpu.planner.logical import ExprBinder, Schema
from tidb_tpu.session.ddl import DDLMixin
from tidb_tpu.planner.physical import PhysicalExecutor
from tidb_tpu.storage import Catalog, scan_table
from tidb_tpu.storage.table import TableSchema
from tidb_tpu.storage.scan import clear_scan_cache



@dataclasses.dataclass
class Result:
    columns: List[str]
    rows: List[Tuple]
    affected: int = 0
    elapsed_s: float = 0.0
    types: Optional[List[SQLType]] = None  # per-column, for wire encoding

    def sorted(self) -> List[Tuple]:
        return sorted(self.rows, key=lambda r: tuple((v is None, str(v)) for v in r))


def _walk_dataclasses(obj, fn, _seen=None):
    """Generic pre-order walk over a dataclass tree (lists/tuples/dicts
    descended); fn(node) on every dataclass instance."""
    if _seen is None:
        _seen = set()
    if isinstance(obj, (list, tuple)):
        for x in obj:
            _walk_dataclasses(x, fn, _seen)
        return
    if isinstance(obj, dict):
        for x in obj.values():
            _walk_dataclasses(x, fn, _seen)
        return
    if not dataclasses.is_dataclass(obj) or isinstance(obj, type):
        return
    if id(obj) in _seen:
        return
    _seen.add(id(obj))
    fn(obj)
    for f in dataclasses.fields(obj):
        _walk_dataclasses(getattr(obj, f.name), fn, _seen)


def _count_params(stmt) -> int:
    mx = [-1]

    def see(n):
        idx = getattr(n, "param_index", None)
        if isinstance(n, ast.Const) and idx is not None:
            mx[0] = max(mx[0], idx)

    _walk_dataclasses(stmt, see)
    return mx[0] + 1


def _bind_ast_params(stmt, values) -> None:
    """Write EXECUTE's values into the template's '?' Const nodes (in
    place — the template is session-private)."""

    def see(n):
        idx = getattr(n, "param_index", None)
        if isinstance(n, ast.Const) and idx is not None:
            n.value = values[idx]
            n.type_hint = None

    _walk_dataclasses(stmt, see)


def _collect_param_literals(plan) -> dict:
    """slot -> bound Literal surviving in a logical plan (their types
    drive the host-side encode of later EXECUTE bindings)."""
    from tidb_tpu.expression.expr import Literal as _Lit

    out = {}

    def see(n):
        if isinstance(n, _Lit) and n.param_slot is not None:
            out.setdefault(n.param_slot, n)

    _walk_dataclasses(plan, see)
    return out


def _release_session_locks(base_catalog, conn_id: int) -> None:
    """weakref.finalize hook: a dying session releases its advisory
    locks (MySQL releases GET_LOCK locks on connection end)."""
    cv = getattr(base_catalog, "_user_locks_cv", None)
    reg = getattr(base_catalog, "_user_locks", None)
    if cv is None or reg is None:
        return
    with cv:
        for name in [k for k, v in reg.items() if v[0] == conn_id]:
            del reg[name]
        cv.notify_all()


class _SessionCatalog:
    """Session-scoped catalog view: LOCAL TEMPORARY tables shadow base
    tables by name for this session only (reference:
    pkg/table/temptable/ddl.go — local temp tables live in session
    state, and an infoschema wrapper resolves them before the shared
    schema). Every other attribute (users, sysvars, locks, sequences,
    the `_dbs` map, ...) delegates to the shared base catalog, so
    sessions over the same store still share one authority. Temp
    tables are invisible to `tables()` (SHOW TABLES / BACKUP / dump do
    not see them, matching MySQL) but win name resolution in
    `table()`/`has_table()`."""

    __slots__ = ("_base", "_temp")

    def __init__(self, base):
        object.__setattr__(self, "_base", base)
        object.__setattr__(self, "_temp", {})

    def __getattr__(self, n):
        return getattr(object.__getattribute__(self, "_base"), n)

    def __setattr__(self, n, v):
        setattr(object.__getattribute__(self, "_base"), n, v)

    def table(self, db: str, name: str):
        t = self._temp.get((db.lower(), name.lower()))
        return t if t is not None else self._base.table(db, name)

    def has_table(self, db: str, name: str) -> bool:
        return (db.lower(), name.lower()) in self._temp or (
            self._base.has_table(db, name)
        )

    def create_temp_table(self, db: str, name: str, schema):
        from tidb_tpu.storage.table import Table

        db, name = db.lower(), name.lower()
        if db not in self._base._dbs:
            raise ValueError(f"unknown database {db!r}")
        key = (db, name)
        if key in self._temp:
            raise ValueError(f"temporary table {name!r} exists")
        t = Table(name, schema)
        self._temp[key] = t
        # plan caches key on schema_version: a later DROP must not
        # serve plans compiled against the shadowing temp table
        self._base.schema_version += 1
        return t

    def drop_table(
        self, db: str, name: str, if_exists: bool = False,
        temporary_only: bool = False,
    ) -> None:
        key = (db.lower(), name.lower())
        if key in self._temp:
            del self._temp[key]
            self._base.schema_version += 1
            return
        if temporary_only:
            if if_exists:
                return
            raise ValueError(f"unknown temporary table {db}.{name}")
        self._base.drop_table(db, name, if_exists)


class Session(DDLMixin):
    def __init__(
        self,
        catalog: Optional[Catalog] = None,
        db: str = "test",
        mesh_devices: Optional[int] = None,
        user: str = "root",
    ):
        """mesh_devices=N runs every query as one SPMD shard_map program
        over an N-device mesh (sharded scans, all_to_all exchanges) — the
        MPP mode of the reference (tidb_allow_mpp); None = single device.
        """
        base = catalog or Catalog()
        if isinstance(base, _SessionCatalog):
            base = base._base  # don't stack overlays across sessions
        self.catalog = _SessionCatalog(base)
        self.db = db
        self.user = user
        if not hasattr(self.catalog, "users"):  # pre-UserStore pickles
            from tidb_tpu.utils.privilege import UserStore

            self.catalog.users = UserStore()
        self.executor = PhysicalExecutor(self.catalog, mesh_devices=mesh_devices)
        # cross-host DCN fragment scheduler (parallel/dcn.py): when
        # attached, EXPLAIN ANALYZE routes through the distributed
        # path (per-host fragment rows + Shuffle exchange rows in the
        # plan tree) instead of the local instrumented run
        self.dcn_scheduler = None
        from tidb_tpu.utils import SysVars, Tracer

        self.vars = SysVars(self.catalog.global_sysvars)
        self.tracer = Tracer()
        # Snapshot transaction state (reference: LazyTxn pkg/session/txn.go:50
        # buffering writes in a memdb; here a shadow Table per written table
        # gives read-your-own-writes, and commit swaps blocks in after an
        # optimistic version check — first committer wins, the analog of
        # 2PC prewrite conflict detection).
        self._txn = None
        from tidb_tpu.utils.sqlkiller import SQLKiller

        # KILL QUERY support (reference pkg/util/sqlkiller): executor
        # polls at safepoints; .kill() from any thread aborts the stmt
        self.killer = SQLKiller()
        self.executor.kill_check = self.killer.check
        self.executor.table_hook = self._resolve_table_for_read
        self.last_insert_id = 0
        # prepared statements (reference: pkg/planner/core/plan_cache.go
        # parameterized plans): name -> entry with the parsed template,
        # cached logical plan, and runtime/baked parameter-slot split
        self._prepared = {}
        self.user_vars = {}
        self._last_plan = None
        # stale-read state: per-statement AS OF TIMESTAMP map
        # ((db, table) -> epoch ts) and whether the current top-level
        # statement is read-only (tidb_read_staleness applies only then)
        self._stmt_as_of: dict = {}
        self._stale_ok = False
        # EXECUTE dispatch marker: the depth gate below keeps nested
        # statements (TRACE inner stmt) from clobbering stale-read
        # state, but a prepared statement dispatched via SQL EXECUTE is
        # semantically top-level even at depth 2 — without this flag its
        # AS OF refs would silently read CURRENT data
        self._prepared_dispatch = False
        # RU governance binding (SET RESOURCE GROUP <name>)
        self.resource_group = "default"
        # processlist registry: catalog-wide id -> weakref(Session) so
        # SHOW PROCESSLIST / KILL <id> see every live session over this
        # store without keeping dead ones alive (reference: the server's
        # clientConn registry, pkg/server/server.go)
        import itertools as _it
        import weakref as _wr

        reg = getattr(self.catalog, "_session_registry", None)
        if reg is None:
            # WeakValueDictionary: dead sessions drop out on collection
            # (a server creating one session per request must not grow
            # the registry forever)
            reg = self.catalog._session_registry = _wr.WeakValueDictionary()
            self.catalog._conn_counter = _it.count(1)
        self.conn_id = next(self.catalog._conn_counter)
        reg[self.conn_id] = self
        self._current_stmt: Optional[tuple] = None  # (sql text, t0)
        # per-statement diagnostics area (SHOW WARNINGS): cleared at
        # each non-diagnostic statement, rows are (Level, Code, Message)
        self._warnings: list = []
        self._stmt_count = 0
        import time as _time

        self._start_ts = _time.time()
        self._killed_conn = False  # KILL CONNECTION marks, execute raises
        if not hasattr(self.catalog, "resource_groups"):  # old pickles
            from tidb_tpu.utils.resgroup import ResourceGroupManager

            self.catalog.resource_groups = ResourceGroupManager()

    # -- transaction plumbing ------------------------------------------
    def _resolve_table_for_read(self, db: str, name: str):
        """Returns (table, version) the executor should scan."""
        t = self.catalog.table(db, name)
        key = (db.lower(), name.lower())
        # stale read (reference: sessiontxn staleness providers):
        # AS OF TIMESTAMP on the table ref, else tidb_read_staleness on
        # read-only autocommit statements
        as_of_ts = self._stmt_as_of.get(key)
        if db.lower() in ("information_schema", "metrics_schema"):
            # virtual diagnostic tables are rebuilt fresh per access —
            # staleness would resolve them to their empty version-0
            # state (the reference never applies staleness to
            # memtables; metrics_schema history is time-addressed
            # through its OWN time column, not MVCC)
            if as_of_ts is not None:
                raise ValueError(
                    f"AS OF TIMESTAMP is not supported on "
                    f"{db.lower()} tables"
                )
            return t, t.version
        clamp = False
        if as_of_ts is None and self._txn is None:
            # tidb_snapshot: a session-wide historical read point (the
            # reference rejects writes while it is set — see
            # _resolve_table_for_write); applies to every read until
            # cleared, independent of tidb_read_staleness
            snap = self._tidb_snapshot_ts()
            if snap is not None:
                as_of_ts = snap
        if as_of_ts is None and self._txn is None and self._stale_ok:
            try:
                staleness = int(self.vars.get("tidb_read_staleness") or 0)
            except Exception:
                staleness = 0
            if staleness < 0:
                as_of_ts = time.time() + staleness
                # the reference picks a usable ts inside
                # [now+staleness, now]; a table younger than the window
                # reads its earliest retained state, never errors
                clamp = True
        if as_of_ts is not None:
            if self._txn is not None:
                raise ValueError(
                    "stale read is not allowed inside a transaction"
                )
            return t, t.version_at(as_of_ts, clamp_oldest=clamp)
        if self._txn is None:
            return t, t.version
        if self._rc_isolation() and key not in self._txn["shadows"]:
            # READ COMMITTED provider: every statement reads the newest
            # committed version, not the txn-start snapshot (reference:
            # sessiontxn/isolation/readcommitted.go)
            return t, t.version
        shadow = self._txn["shadows"].get(key)
        if shadow is not None:
            return shadow, shadow.version
        if key not in self._txn["pins"]:
            self._txn["pins"][key] = t.version
            t.pin(t.version)  # GC safepoint: snapshot survives writers
            self._txn.setdefault("pin_objs", []).append((t, t.version))
        pinned = self._txn["pins"][key]
        return t, pinned

    def _tidb_snapshot_ts(self):
        """Epoch ts of the session's tidb_snapshot, or None. Accepts an
        epoch number or a datetime literal in the session time_zone."""
        raw = self.vars.get("tidb_snapshot")
        if raw in (None, "", 0):
            return None
        try:
            return float(raw)
        except (TypeError, ValueError):
            import datetime as _dt

            dt = _dt.datetime.fromisoformat(str(raw))
            if dt.tzinfo is None:
                dt = dt.replace(tzinfo=self._session_tzinfo())
            return dt.timestamp()

    def _resolve_table_for_write(self, db: str, name: str):
        if self._tidb_snapshot_ts() is not None:
            # reference: "can not execute write statement when
            # 'tidb_snapshot' is set"
            raise ValueError(
                "can not execute write statement when 'tidb_snapshot' "
                "is set"
            )
        t = self.catalog.table(db, name)
        if self._txn is None:
            return t
        if self._txn.get("read_only"):
            raise ValueError(
                "cannot execute statement in a READ ONLY transaction"
            )
        key = (db.lower(), name.lower())
        shadow = self._txn["shadows"].get(key)
        if shadow is None:
            from tidb_tpu.storage.table import Table

            pinned = self._txn["pins"].get(key)
            if pinned is None:
                pinned = self._txn["pins"][key] = t.version
                t.pin(t.version)  # survive GC until commit/rollback
                self._txn.setdefault("pin_objs", []).append((t, t.version))
            shadow = Table(t.name, t.schema)
            shadow._versions = {0: list(t.blocks(pinned))}
            shadow.dictionaries = dict(t.dictionaries)
            shadow.indexes = dict(t.indexes)
            shadow.index_states = dict(t.index_states)
            shadow.unique_indexes = set(t.unique_indexes)
            shadow.autoinc_col = t.autoinc_col
            shadow.autoinc_next = t.autoinc_next
            shadow.checks = list(t.checks)
            shadow.fks = list(t.fks)
            shadow.fk_actions = dict(getattr(t, "fk_actions", {}))
            shadow.fk_update_actions = dict(
                getattr(t, "fk_update_actions", {})
            )
            shadow.partition = t.partition
            shadow.defaults = dict(getattr(t, "defaults", None) or {})
            shadow.generated = list(getattr(t, "generated", None) or [])
            self._txn["shadows"][key] = shadow
            # conflict baseline = version at FIRST touch in this txn —
            # a shadow rebuilt after ROLLBACK TO SAVEPOINT must not
            # adopt a newer version (it would mask concurrent commits
            # and overwrite them at commit time)
            self._txn["base_versions"].setdefault(key, pinned)
        return shadow

    # -- pessimistic locking (reference: LockKeys in the pessimistic txn
    # path, pkg/store/driver/txn/txn_driver.go; deadlock detector
    # unistore/tikv/detector.go) --------------------------------------
    def _session_tzinfo(self):
        """tzinfo for the session time_zone sysvar: 'UTC' (default),
        '+HH:MM'/'-HH:MM' offsets, IANA names via zoneinfo, or 'SYSTEM'
        (host local). Unrecognized values raise — silently interpreting
        a literal in the wrong zone would shift every stale read by the
        offset (the silent-wrong-data hazard)."""
        import datetime as _dt

        tz = str(self.vars.get("time_zone") or "UTC").strip()
        up = tz.upper()
        if up in ("UTC", "GMT"):
            return _dt.timezone.utc
        if up == "SYSTEM":
            return _dt.datetime.now().astimezone().tzinfo
        if tz and tz[0] in "+-":
            try:
                hh, _sep, mm = tz[1:].partition(":")
                off = _dt.timedelta(hours=int(hh), minutes=int(mm or 0))
                return _dt.timezone(-off if tz[0] == "-" else off)
            except ValueError:
                raise ValueError(f"Unknown or incorrect time zone: {tz!r}")
        try:
            import zoneinfo

            return zoneinfo.ZoneInfo(tz)
        except Exception:
            raise ValueError(f"Unknown or incorrect time zone: {tz!r}")

    def _collect_as_of(self, s) -> dict:
        """Collect `AS OF TIMESTAMP` table refs across the whole
        statement tree; returns {(db, table): epoch ts}. The resolver is
        keyed by table NAME, so one statement mixing stale and current
        refs of the same table (or two different timestamps) cannot be
        honored — that raises instead of silently resolving both refs
        to one version."""
        out: dict = {}
        plain: set = set()

        def ts_of(expr) -> float:
            v = self._const_value(expr)
            if isinstance(v, (int, float)):
                return float(v)
            if isinstance(v, str):
                try:
                    return float(v)
                except ValueError:
                    import datetime as _dt

                    dt = _dt.datetime.fromisoformat(v)
                    if dt.tzinfo is None:
                        # naive literals resolve in the session
                        # time_zone (default UTC), never the host's —
                        # version_ts is epoch-stamped, so a host-local
                        # interpretation would shift every stale read by
                        # the TZ offset (reference: types.ParseTime with
                        # sessionctx time zone)
                        dt = dt.replace(tzinfo=self._session_tzinfo())
                    return dt.timestamp()
            raise ValueError(
                f"cannot evaluate AS OF TIMESTAMP expression: {expr!r}"
            )

        for ref in ast.iter_table_refs(s):
            key = ((ref.db or self.db).lower(), ref.name.lower())
            if ref.as_of is None:
                plain.add(key)
            else:
                ts = ts_of(ref.as_of)
                if out.get(key, ts) != ts:
                    raise ValueError(
                        f"multiple AS OF TIMESTAMP values for table "
                        f"{key[1]!r} in one statement are not supported"
                    )
                out[key] = ts
        conflict = plain & set(out)
        if conflict:
            raise ValueError(
                "mixing AS OF TIMESTAMP and current-version references "
                f"to the same table {sorted(conflict)[0][1]!r} in one "
                "statement is not supported"
            )
        return out

    def _rc_isolation(self) -> bool:
        # tx_isolation mirrors transaction_isolation on SET (sysvar.py),
        # so one lookup covers both spellings
        try:
            return str(
                self.vars.get("transaction_isolation") or ""
            ).upper() == "READ-COMMITTED"
        except Exception:
            return False

    def _pessimistic(self) -> bool:
        return str(self.vars.get("tidb_txn_mode") or "").lower() == "pessimistic"

    def _lock_manager(self):
        return self.catalog.lock_manager

    def _with_write_locks(self, tables, fn):
        """Run a DML statement holding pessimistic locks on its target
        tables. Explicit transaction: locks persist until COMMIT/
        ROLLBACK and the table's read snapshot advances to the current
        committed version at first lock (the for_update_ts semantics —
        a writer that blocked behind another txn resumes against the
        winner's committed rows, so interleaved writers SERIALIZE
        instead of aborting). Autocommit: the lock spans just this
        statement, closing the read-modify-write race between
        concurrent single-statement writers. A deadlock rolls the whole
        transaction back (InnoDB victim semantics) and re-raises."""
        from tidb_tpu.storage.locks import DeadlockError, next_txn_id

        lm = self._lock_manager()
        try:
            timeout = float(self.vars.get("innodb_lock_wait_timeout") or 50)
        except Exception:
            timeout = 50.0
        keys = [(d.lower(), n.lower()) for d, n in tables]
        if self._txn is not None:
            if not self._pessimistic():
                return fn()  # optimistic txns buffer in shadows, lock-free
            txn_id = self._txn.setdefault("txn_id", next_txn_id())
            locked = self._txn.setdefault("locked", set())
            try:
                for k in keys:
                    if k in locked:
                        continue
                    lm.acquire(
                        txn_id, k, timeout=timeout,
                        kill_check=self.killer.check,
                    )
                    locked.add(k)
                    self._advance_snapshot(k)
            except DeadlockError:
                self._abort_txn()
                raise
            return fn()
        # autocommit (BOTH modes): a statement-scoped table lock — the
        # statement mutates the base table directly, so it must exclude
        # pessimistic lock holders AND committers (which take the same
        # locks in _commit_txn) or its read-modify-write races
        sid = next_txn_id()
        try:
            for k in sorted(keys):
                lm.acquire(
                    sid, k, timeout=timeout, kill_check=self.killer.check
                )
            return fn()
        finally:
            lm.release_all(sid)

    def _advance_snapshot(self, key) -> None:
        """After acquiring a table's pessimistic lock: advance this
        txn's snapshot of it to the CURRENT committed version (nobody
        else can write it while we hold the lock). Skipped once a shadow
        exists — rewriting a table we already wrote would lose our own
        writes; the commit-time version check still guards that case."""
        if self._txn is None or key in self._txn["shadows"]:
            return
        db, name = key
        t = self.catalog.table(db, name)
        cur = t.version
        if self._txn["pins"].get(key) == cur:
            self._txn["base_versions"][key] = cur
            return
        t.pin(cur)
        self._txn.setdefault("pin_objs", []).append((t, cur))
        self._txn["pins"][key] = cur
        self._txn["base_versions"][key] = cur

    def _abort_txn(self) -> None:
        """Roll back the active transaction (deadlock victim path)."""
        txn, self._txn = self._txn, None
        if not txn:
            return
        for t, v in txn.get("pin_objs", []):
            t.unpin(v)
        if txn.get("txn_id"):
            self._lock_manager().release_all(txn["txn_id"])

    def _from_tables(self, ref) -> list:
        """Base (db, table) pairs under a FROM clause (for FOR UPDATE
        locking); subquery refs contribute their inner FROMs."""
        out = []

        def walk(r):
            if r is None:
                return
            if isinstance(r, ast.TableRef):
                try:
                    self.catalog.table(r.db or self.db, r.name)
                except Exception:
                    return  # view / unknown: nothing lockable
                out.append((r.db or self.db, r.name))
            elif isinstance(r, ast.Join):
                walk(r.left)
                walk(r.right)
            elif isinstance(r, ast.SubqueryRef):
                walk(getattr(r.query, "from_", None))

        walk(ref)
        return out

    def _take_outfile(self, s):
        """Pop the INTO OUTFILE path off the statement's final SELECT
        block (unions/CTEs attach it to their last branch)."""
        node = s
        while True:
            if isinstance(node, ast.With):
                node = node.body
            elif isinstance(node, ast.Union):
                node = node.selects[-1]
            elif isinstance(node, ast.SetOp):
                node = node.right
            else:
                break
        f = getattr(node, "outfile", None)
        if f is not None:
            node.outfile = None
        return f

    def _for_update_tables(self, s) -> list:
        """Tables to lock for FOR UPDATE, searching every Select block
        of a query (the parser sets the flag on the inner block of
        WITH/UNION/INTERSECT wrappers)."""
        out = []

        def walk(q):
            if isinstance(q, ast.Select):
                if q.for_update:
                    out.extend(self._from_tables(q.from_))
            elif isinstance(q, ast.Union):
                for sub in q.selects:
                    walk(sub)
            elif isinstance(q, ast.SetOp):
                walk(q.left)
                walk(q.right)
            elif isinstance(q, ast.With):
                for _n, cq in q.ctes:
                    walk(cq)
                walk(q.body)

        walk(s)
        return out

    # -- prepared statements (parameterized plan cache) ----------------
    # Reference: pkg/planner/core/plan_cache.go:231 — EXECUTE reuses the
    # compiled plan with new parameter values bound as runtime inputs.
    # Slots the compiler could not parameterize (LIKE patterns, IN sets,
    # string dictionary lookups, pushed PK ranges, any stage that ran
    # without the parameter scope) register as BAKED: a change in those
    # values replans; changes in runtime slots re-run the same jitted
    # program with new scalars.
    def prepare(self, name: str, sql: str) -> None:
        try:
            stmts = parse(sql)
        except Exception:
            # placeholders in positions the grammar can't hold as
            # expressions (LIMIT ? / OFFSET ?): fall back to textual
            # binding — EXECUTE renders literals into the SQL and runs
            # the statement pipeline (the pre-parameterized behavior)
            from tidb_tpu.server.protocol import count_placeholders

            self._prepared[name.lower()] = {
                "textual": sql,
                "nparams": count_placeholders(sql),
            }
            return
        if len(stmts) != 1:
            raise ValueError("PREPARE expects exactly one statement")
        nparams = _count_params(stmts[0])
        self._prepared[name.lower()] = {
            "ast": stmts[0],
            "nparams": nparams,
            "plan": None,
        }

    def deallocate(self, name: str) -> None:
        if self._prepared.pop(name.lower(), None) is None:
            raise ValueError(f"unknown prepared statement {name}")

    @staticmethod
    def _canonical_param(v):
        """Numeric canonical encoding for a runtime slot binding, or
        None when the value can only bake (strings, NULL, bool)."""
        if isinstance(v, bool) or v is None:
            return None
        if isinstance(v, int):
            return np.asarray(v, dtype=np.int64)
        if isinstance(v, float):
            return np.asarray(v, dtype=np.float64)
        return None

    def execute_prepared(self, name: str, values) -> Result:
        from tidb_tpu.expression.kernels import param_registry
        from tidb_tpu.planner.physical import StaleWidthsError

        ent = self._prepared.get(name.lower())
        if ent is None:
            raise ValueError(f"unknown prepared statement {name}")
        values = list(values)
        if len(values) != ent["nparams"]:
            raise ValueError(
                f"statement expects {ent['nparams']} parameters, "
                f"got {len(values)}"
            )
        if "textual" in ent:
            from tidb_tpu.server.protocol import bind_placeholders

            self._prepared_dispatch = True
            try:
                return self.execute(bind_placeholders(ent["textual"], values))
            finally:
                self._prepared_dispatch = False
        types_sig = tuple(type(v).__name__ for v in values)

        from tidb_tpu.utils.failpoint import inject

        inject("session/execute-prepared")
        # stale-read state for the compiled fast path: no _execute_stmt
        # runs there, so collect AS OF / read-only-ness from the prepared
        # AST here — _fetch_inputs resolves versions through
        # _resolve_table_for_read at run time, which consults this state.
        # An `AS OF TIMESTAMP ?` param is a baked slot, so the fast path
        # only fires when the AST already holds the current value.
        # fast-path eligibility, computed ONCE: the db guard matters
        # because unqualified refs resolve against the CURRENT db at
        # execute time (slow-path semantics), so a USE since planning
        # must force a replan — both for data resolution and for the
        # (db, table)-keyed _stmt_as_of map collected below
        fast_eligible = (
            ent.get("plan") is not None and ent.get("db") == self.db
        )
        if fast_eligible:
            p_ast = ent["ast"]
            if isinstance(p_ast, (ast.Select, ast.Union, ast.With, ast.SetOp)):
                self._stale_ok = True
                # has_as_of is structural (recorded at plan time): the
                # common no-AS-OF EXECUTE skips the AST walk entirely
                self._stmt_as_of = (
                    self._collect_as_of(p_ast)
                    if ent.get("has_as_of") else {}
                )
        # fast path: the held CompiledQuery re-runs with new runtime-slot
        # values as jitted-program inputs — no parse, no plan, no trace
        if (
            fast_eligible
            and ent.get("schema_version") == self.catalog.schema_version
            and ent.get("types_sig") == types_sig
            and all(values[i] == ent["values"][i] for i in ent["baked"])
        ):
            self._enforce_privileges(ent["ast"])
            cq = ent.get("cq")
            # the cq's baked dictionaries key on table versions: reuse
            # only while the fingerprint key (which carries them) holds
            if cq is not None and self.executor._cache_key(ent["plan"]) == ent["ckey"]:
                # same slot set as the slow-path trace: a different
                # params pytree structure would force a jax retrace
                pv = {
                    i: self._canonical_param(values[i])
                    for i in ent["pv_slots"]
                }
                self.executor.param_values = pv
                try:
                    fu = ent.get("for_update") or []
                    run = lambda: self._materialize_prepared(ent, cq)
                    return (
                        self._with_write_locks(fu, run) if fu else run()
                    )
                except StaleWidthsError:
                    ent["plan"] = None  # fall through to replan below
                finally:
                    self.executor.param_values = {}

        # slow path: substitute values into the template and run the
        # full statement pipeline, capturing which slots stayed runtime.
        # Numeric values are offered as runtime bindings during the
        # compile so eligible literals trace as program inputs.
        s = ent["ast"]
        # mesh sessions never thread runtime params (_params() is empty
        # there): every slot bakes and EXECUTE replans per value change
        mesh = self.executor.mesh_n is not None
        _bind_ast_params(s, values)
        self._last_plan = None
        pv = {}
        if not mesh:
            for i, v in enumerate(values):
                c = self._canonical_param(v)
                if c is not None:
                    pv[i] = c
        self.executor.param_values = pv
        self._prepared_dispatch = True
        try:
            with param_registry() as reg:
                r = self._execute_stmt(s)
        finally:
            self._prepared_dispatch = False
            self.executor.param_values = {}
        plan = self._last_plan
        runtime = set()
        cq = ckey = None
        if plan is not None and not mesh:
            lits = _collect_param_literals(plan)
            runtime = (reg.runtime - reg.baked) & set(lits) & set(pv)
            if runtime:
                ckey = self.executor._cache_key(plan)
                cq = self.executor._cache.get(ckey)
        ent.update(
            db=self.db,
            has_as_of=any(
                r.as_of is not None for r in ast.iter_table_refs(s)
            ),
            pv_slots=set(pv),
            plan=plan if (runtime and cq is not None) else None,
            cq=cq,
            ckey=ckey,
            runtime=runtime,
            baked=set(range(ent["nparams"])) - runtime,
            values=list(values),
            types_sig=types_sig,
            schema_version=self.catalog.schema_version,
            for_update=self._for_update_tables(s)
            if isinstance(s, (ast.Select, ast.Union, ast.With, ast.SetOp))
            else [],
        )
        return r

    def _materialize_prepared(self, ent, cq) -> Result:
        pins = []
        try:
            batch, dicts = self.executor._run_pinned(cq, pins)
        finally:
            for t, v in pins:
                t.unpin(v)
        plan = ent["plan"]
        rows = materialize_rows(batch, list(plan.schema), dicts)
        names = [c.name for c in plan.schema]
        return Result(names, rows, types=[c.type for c in plan.schema])

    def _run_txn_control(self, s) -> Result:
        from tidb_tpu.utils import failpoint

        if s.op == "begin":
            failpoint.inject("session/begin-txn")
            if self._txn is not None:
                self._commit_txn()  # MySQL: BEGIN implicitly commits
            self._txn = {
                "pins": {}, "shadows": {}, "base_versions": {},
                "savepoints": [],
                "read_only": bool(getattr(s, "read_only", False)),
            }
        elif s.op == "commit":
            self._commit_txn()
        elif s.op == "rollback":
            self._abort_txn()
        elif s.op == "savepoint":
            # outside a transaction this is a no-op, like MySQL under
            # autocommit (reference: pkg/session savepoint handling,
            # pkg/sessionctx/sessionstates)
            if self._txn is not None:
                sps = self._txn.setdefault("savepoints", [])
                name = s.name.lower()
                # re-declaring a name moves it (MySQL: old one deleted)
                sps[:] = [x for x in sps if x[0] != name]
                sps.append((name, self._txn_snapshot()))
        elif s.op == "rollback_to":
            self._rollback_to_savepoint(s.name.lower())
        elif s.op == "release":
            if self._txn is not None:
                sps = self._txn.get("savepoints", [])
                idx = [i for i, (n, _) in enumerate(sps) if n == s.name.lower()]
                if not idx:
                    raise ValueError(f"SAVEPOINT {s.name} does not exist")
                # TiDB semantics: deletes the named savepoint and every
                # later one; the transaction state is untouched
                del sps[idx[0]:]
        return Result([], [])

    def _txn_snapshot(self) -> dict:
        """Per-shadow restore state for a savepoint: block lists are
        immutable, so capturing them is O(#tables)."""
        return {
            key: (
                list(shadow.blocks()),
                shadow.modify_count,
                dict(shadow.dictionaries),
                shadow.autoinc_next,
            )
            for key, shadow in self._txn["shadows"].items()
        }

    def _rollback_to_savepoint(self, name: str) -> None:
        if self._txn is None:
            raise ValueError(f"SAVEPOINT {name} does not exist")
        sps = self._txn.get("savepoints", [])
        idx = [i for i, (n, _) in enumerate(sps) if n == name]
        if not idx:
            raise ValueError(f"SAVEPOINT {name} does not exist")
        _, snap = sps[idx[0]]
        # the named savepoint survives; later ones are destroyed (MySQL)
        del sps[idx[0] + 1:]
        for key in list(self._txn["shadows"]):
            if key not in snap:
                # table first touched after the savepoint: forget the
                # shadow (reads fall back to the pinned base). pins and
                # base_versions survive — a rebuilt shadow must keep the
                # original snapshot AND conflict baseline
                del self._txn["shadows"][key]
                continue
            shadow = self._txn["shadows"][key]
            blocks, modify, dicts, autoinc = snap[key]
            shadow.replace_blocks(blocks)
            shadow.modify_count = modify
            shadow.dictionaries = dict(dicts)
            shadow.autoinc_next = autoinc
        clear_scan_cache()

    def _commit_txn(self) -> None:
        from tidb_tpu.utils import failpoint

        if self._txn is None:
            return
        txn, self._txn = self._txn, None
        commit_id = None
        try:
            failpoint.inject("session/before-commit")
            # Commit takes the lock-manager locks of every written table
            # (sorted — no lock-order cycles between committers; a
            # pessimistic txn already holds its own, so acquire no-ops).
            # This excludes autocommit writers and pessimistic holders
            # for the whole check+apply span; the catalog commit mutex
            # additionally serializes optimistic committers' check+apply
            # so neither can interleave between the other's check and
            # apply (lost update).
            if txn["shadows"]:
                commit_id = txn.get("txn_id")
                if commit_id is None:
                    from tidb_tpu.storage.locks import next_txn_id

                    commit_id = next_txn_id()
                lm = self._lock_manager()
                try:
                    timeout = float(
                        self.vars.get("innodb_lock_wait_timeout") or 50
                    )
                except Exception:
                    timeout = 50.0
                for k in sorted(txn["shadows"].keys()):
                    lm.acquire(
                        commit_id, k, timeout=timeout,
                        kill_check=self.killer.check,
                    )
            with self.catalog._commit_mu:
                # optimistic conflict check then swap (first committer
                # wins)
                for key, shadow in txn["shadows"].items():
                    db, name = key
                    base = self.catalog.table(db, name)
                    failpoint.inject("session/commit-conflict-check")
                    if base.version != txn["base_versions"][key]:
                        raise RuntimeError(
                            f"write conflict on {db}.{name}: "
                            "table changed since transaction start"
                        )
                failpoint.inject("session/commit-apply")
                for key, shadow in txn["shadows"].items():
                    db, name = key
                    base = self.catalog.table(db, name)
                    # atomic: blocks + dictionaries + allocator swap
                    # under one table-lock acquisition (direct autoinc
                    # assign, not max: the conflict check proved the
                    # base unchanged since first touch, so TRUNCATE's
                    # AUTO_INCREMENT reset survives COMMIT)
                    base.install_commit(
                        shadow.blocks(),
                        shadow.dictionaries,
                        shadow.autoinc_next,
                        shadow.modify_count,
                    )
            if txn["shadows"]:
                clear_scan_cache()
        finally:
            for t, v in txn.get("pin_objs", []):
                t.unpin(v)
            if commit_id is not None or txn.get("txn_id"):
                self._lock_manager().release_all(
                    commit_id if commit_id is not None else txn["txn_id"]
                )

    # ------------------------------------------------------------------
    def _run_admin(self, s) -> Result:
        """ADMIN CHECK TABLE / ADMIN CHECK INDEX / ADMIN SHOW DDL
        (reference: pkg/executor/admin.go:46 — CheckTableExec walks
        every index row-range against the table region; here derived
        per-version indexes make the check a fresh recompute from raw
        block data cross-validated against the cached bookkeeping, plus
        the invariants only the write path normally guards: PK/unique
        key sets, FK closure, partition tagging, dictionary code
        ranges). Inconsistency raises; a clean catalog returns empty."""
        if s.op == "show_ddl":
            # DDL executes synchronously in-process: the job queue is
            # always empty — report the schema version (ShowDDLExec)
            return Result(
                ["SCHEMA_VER", "RUNNING_JOBS", "SELF_ID"],
                [(self.catalog.schema_version, "", "tidb-tpu-0")],
            )
        if s.op == "checksum_table":
            return self._admin_checksum(s)
        if s.op == "check_table_status":
            # MySQL CHECK TABLE: status rows instead of ADMIN CHECK's
            # raise-on-corruption (reference: executor CheckTableExec)
            rows = []
            for db0, name in s.tables:
                db = (db0 or self.db).lower()
                full = f"{db}.{name.lower()}"
                if not self.catalog.has_table(db, name):
                    rows.append((
                        full, "check", "Error",
                        f"Table '{full}' doesn't exist",
                    ))
                    continue
                try:
                    self._run_admin(
                        ast.AdminStmt("check_table", [(db, name)])
                    )
                    rows.append((full, "check", "status", "OK"))
                except Exception as e:
                    rows.append((full, "check", "error", str(e)[:200]))
                    rows.append((full, "check", "error", "Corrupt"))
            return Result(["Table", "Op", "Msg_type", "Msg_text"], rows)
        problems: list = []
        for db0, name in s.tables:
            db = (db0 or self.db).lower()
            # the session's read snapshot (txn pins/shadows, RC), so
            # the FK closure check compares child and parent at ONE
            # consistent point instead of mixed versions
            t, ver = self._resolve_table_for_read(db, name)
            if s.op == "check_index":
                iname = s.index.lower()
                if iname == "primary":
                    cols = list(t.schema.primary_key or [])
                    if not cols:
                        raise ValueError(f"table {name} has no PRIMARY KEY")
                elif iname in t.indexes:
                    if (
                        hasattr(t, "index_state")
                        and t.index_state(iname) != "public"
                    ):
                        raise ValueError(
                            f"index {s.index} is not public yet"
                        )
                    cols = t.indexes[iname]
                else:
                    raise ValueError(f"index {s.index} does not exist")
                unique = iname == "primary" or iname in t.unique_indexes
                problems += self._admin_check_key(
                    t, f"{db}.{name}", iname, cols, unique, ver
                )
            else:
                problems += self._admin_check_table(t, db, name, ver)
        if problems:
            raise ValueError(
                "admin check failed: " + "; ".join(problems[:5])
            )
        return Result([], [])

    def _admin_check_key(self, t, qname, iname, cols, unique, ver) -> list:
        """One key set: fresh duplicate/NULL detection from raw blocks
        + cross-validation of any cached sorted bookkeeping."""
        import numpy as np

        from tidb_tpu.storage.table import Table as _T

        problems = []
        blocks = [
            b for b in t.blocks(ver) if all(c in b.columns for c in cols)
        ]
        if iname == "primary":
            for b in blocks:
                for c in cols:
                    if not bool(b.columns[c].valid.all()):
                        problems.append(
                            f"{qname}: NULL in PRIMARY KEY column {c}"
                        )
                        break
        mats = [m for b in blocks if len(m := _T._key_matrix(b.columns, tuple(cols)))]
        fresh = (
            np.sort(_T._rows_view(np.concatenate(mats))) if mats else None
        )
        if unique and fresh is not None and len(fresh) > 1:
            if bool((fresh[1:] == fresh[:-1]).any()):
                problems.append(
                    f"{qname}: duplicate entries under {iname} "
                    f"({', '.join(cols)})"
                )
        # cached bookkeeping must agree with the fresh recompute
        if len(cols) == 1:
            ent = (getattr(t, "_idx_cache", {}) or {}).get(
                (ver, cols[0])
            )
            if ent is not None:
                svals, perm, nvalid = ent
                data = (
                    np.concatenate([b.columns[cols[0]].data for b in blocks])
                    if blocks else np.zeros(0, dtype=np.int64)
                )
                valid = (
                    np.concatenate([b.columns[cols[0]].valid for b in blocks])
                    if blocks else np.zeros(0, dtype=bool)
                )
                p2 = np.lexsort((data, np.where(valid, 0, 1)))
                if (
                    int(valid.sum()) != nvalid
                    or len(svals) != len(data)
                    or not np.array_equal(data[p2], svals)
                ):
                    problems.append(
                        f"{qname}: cached index {iname} disagrees with "
                        "block data"
                    )
        else:
            hit = (getattr(t, "_comp_cache", {}) or {}).get(tuple(cols))
            if hit is not None and hit[0] == tuple(b.uid for b in blocks):
                cached = hit[1]
                if (cached is None) != (fresh is None) or (
                    fresh is not None
                    and (
                        len(cached) != len(fresh)
                        or not np.array_equal(cached, fresh)
                    )
                ):
                    problems.append(
                        f"{qname}: cached composite view {iname} "
                        "disagrees with block data"
                    )
        return problems

    def _admin_checksum(self, s) -> Result:
        """ADMIN CHECKSUM TABLE t[, ...] — order-independent 64-bit
        checksum per table (reference: AdminChecksumTable,
        pkg/parser/ast/misc.go:2323; TiDB reports crc64-xor over
        encoded KV pairs). Columnar analog: per row, a mix of every
        column's LOGICAL value (dictionary codes hash through the
        dictionary's bytes, so the checksum is stable across dictionary
        remaps and compaction), XOR-folded over rows — the same
        replication-verify use the reference serves."""
        import numpy as np

        def _mix(x):
            # splitmix64 finalizer over uint64 arrays
            x = (x + np.uint64(0x9E3779B97F4A7C15))
            x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            return x ^ (x >> np.uint64(31))

        import zlib

        rows = []
        for db0, name in s.tables:
            db = (db0 or self.db).lower()
            t, ver = self._resolve_table_for_read(db, name)
            total = np.uint64(0)
            nrows = 0
            nbytes = 0
            with np.errstate(over="ignore", invalid="ignore"):
                for b in t.blocks(ver):
                    if b.nrows == 0:
                        continue
                    acc = np.zeros(b.nrows, dtype=np.uint64)
                    for ci, cname in enumerate(t.schema.names):
                        c = b.columns.get(cname)
                        if c is None:
                            continue
                        nbytes += c.data.nbytes
                        if c.dictionary is not None:
                            dh = np.array(
                                [
                                    zlib.crc32(str(v).encode())
                                    for v in c.dictionary
                                ],
                                dtype=np.uint64,
                            ) if len(c.dictionary) else np.zeros(
                                1, dtype=np.uint64
                            )
                            codes = np.clip(
                                c.data.astype(np.int64), 0,
                                max(len(c.dictionary) - 1, 0),
                            )
                            vals = dh[codes]
                        elif c.data.dtype.itemsize == 8:
                            # 8-byte ints AND floats: reinterpret bits —
                            # value-casting floats truncated 1.5 and 1.2
                            # to the same int
                            vals = c.data.view(np.uint64)
                        else:
                            vals = c.data.astype(np.int64).astype(
                                np.uint64
                            )
                        h = _mix(
                            vals + np.uint64((ci + 1) * 0x9E3779B9)
                        )
                        # NULL contributes a fixed marker, not the data
                        h = np.where(
                            c.valid, h, np.uint64(0xDEADBEEF) + np.uint64(ci)
                        )
                        acc = _mix(acc ^ h)
                    total ^= np.bitwise_xor.reduce(acc)
                    nrows += b.nrows
            rows.append((db, name.lower(), int(total), nrows, nbytes))
        return Result(
            ["Db_name", "Table_name", "Checksum_crc64_xor",
             "Total_kvs", "Total_bytes"],
            rows,
        )

    def _admin_check_table(self, t, db, name, ver) -> list:
        import numpy as np

        problems = []
        qname = f"{db}.{name}"
        pk = t.schema.primary_key
        if pk:
            problems += self._admin_check_key(
                t, qname, "primary", list(pk), True, ver
            )
        for iname, cols in t.indexes.items():
            if hasattr(t, "index_state") and t.index_state(iname) != "public":
                continue
            problems += self._admin_check_key(
                t, qname, iname, cols, iname in t.unique_indexes, ver
            )
        # dictionary code ranges
        types = t.schema.types
        for b in t.blocks(ver):
            for cn, c in b.columns.items():
                typ = types.get(cn)
                if typ is None or typ.kind != Kind.STRING:
                    continue
                d = t.dictionaries.get(cn)
                nd = len(d) if d is not None else 0
                codes = c.data[c.valid]
                if len(codes) and (
                    int(codes.min()) < 0 or int(codes.max()) >= nd
                ):
                    problems.append(
                        f"{qname}: string codes out of dictionary range "
                        f"in column {cn}"
                    )
        # FK closure: every non-NULL child value has a parent
        for nm, col, rdb, rtbl, rcol in t.fks:
            try:
                parent = self._column_values(rdb, rtbl, rcol)
            except Exception:
                problems.append(
                    f"{qname}: FK {nm} parent {rdb}.{rtbl} missing"
                )
                continue
            for b in t.blocks(ver):
                c = b.columns.get(col)
                if c is None:
                    continue
                # distinct values only (write-path pattern): decode once,
                # set-difference against the parent set
                dec = c.decode()
                vals = {v for ok, v in zip(c.valid.tolist(), dec) if ok}
                if vals - parent:
                    problems.append(
                        f"{qname}: FK {nm} value without parent in "
                        f"{rdb}.{rtbl}.{rcol}"
                    )
                    break
        # partition tagging: every row sits in the block its tag claims
        if t.partition is not None:
            pcol = t.partition[1]
            for b in t.blocks(ver):
                c = b.columns.get(pcol)
                if c is None:
                    continue
                vals = c.data[c.valid]
                if not len(vals):
                    continue
                try:
                    pids = t.partition_of(vals)
                except ValueError:
                    problems.append(
                        f"{qname}: row outside every partition range"
                    )
                    continue
                # untagged blocks are LEGITIMATE (UPDATE fast paths
                # rebuild without tags; scans always read them) — only
                # a tag that contradicts its rows is corruption
                if b.part_id is not None and bool(
                    (pids != b.part_id).any()
                ):
                    problems.append(
                        f"{qname}: rows tagged partition "
                        f"{b.part_id} belong elsewhere"
                    )
        return problems

    def execute(self, sql: str) -> Result:
        if self._killed_conn:
            raise ConnectionError(
                f"connection {self.conn_id} was killed"
            )
        from tidb_tpu.obs.flight import FLIGHT

        # one span from entry to return: the batch parses once, before
        # its first statement's flight begins, and FLIGHT.begin adopts
        # what closed before it (a nested execute, the prepared-
        # statement rebind, charges the flight that is already open)
        with FLIGHT.span("session"):
            with FLIGHT.span("parse"):
                stmts = parse(sql)
            res = Result([], [])
            for s in stmts:
                if len(stmts) == 1:
                    # per-statement text; multi-statement batches fall
                    # back to AST-type digests rather than
                    # mis-attributing the whole batch text to each
                    try:
                        s._source_sql = sql
                    except Exception:
                        pass
                try:
                    res = self._execute_stmt(s)
                except Exception:
                    from tidb_tpu.utils.metrics import REGISTRY

                    REGISTRY.counter(
                        "tidbtpu_session_statement_errors_total",
                        "failed statements",
                    ).inc()
                    raise
            return res

    # test-kit style helpers (reference pkg/testkit/testkit.go:144,167)
    def must_exec(self, sql: str) -> Result:
        return self.execute(sql)

    def must_query(self, sql: str, expected: Optional[Sequence[Tuple]] = None) -> Result:
        r = self.execute(sql)
        if expected is not None:
            got = [tuple(row) for row in r.rows]
            exp = [tuple(row) for row in expected]
            assert got == exp, f"query mismatch:\n got: {got}\n exp: {exp}"
        return r

    # ------------------------------------------------------------------
    def _execute_stmt(self, s) -> Result:
        from tidb_tpu.utils import failpoint

        t0 = time.perf_counter()
        self._stmt_depth = getattr(self, "_stmt_depth", 0) + 1
        top = self._stmt_depth == 1
        if top:
            self._stmt_count = getattr(self, "_stmt_count", 0) + 1
            if isinstance(s, (ast.Select, ast.Union, ast.With, ast.SetOp)):
                self._select_count = getattr(self, "_select_count", 0) + 1
            # the diagnostics area survives only until the next
            # non-diagnostic statement (MySQL SHOW WARNINGS semantics)
            if not (isinstance(s, ast.Show) and s.what == "warnings"):
                self._warnings = []
            self._current_stmt = (
                getattr(s, "_source_sql", type(s).__name__), time.time()
            )
            # engine watch: per-statement jit/retrace/transfer accounting
            # (information_schema.TPU_ENGINE, obs/engine_watch.py)
            from tidb_tpu.obs.engine_watch import ENGINE_WATCH

            ENGINE_WATCH.begin_query(self._current_stmt[0])
            # flight recorder: always-on per-statement phase timeline
            # (obs/flight.py); begin adopts the batch's parse span
            from tidb_tpu.obs.flight import FLIGHT

            FLIGHT.begin(self._current_stmt[0], self.conn_id)
            from tidb_tpu.utils import sqlkiller as _sk

            # host-side blocking builtins (SLEEP) poll this session's
            # killer via the thread-local — KILL/watchdogs reach them
            _sk.set_current(self.killer)
            # statement priority for the serving tier's admission queue
            # (parallel/serving.py): HIGH_PRIORITY/LOW_PRIORITY on the
            # statement, else the tidb_force_priority sysvar
            self._stmt_priority = self._priority_for(s)
            # throttle waits paid INSIDE the statement (admission
            # queue, dispatch-site RU re-acquire) accumulate here and
            # come off the boundary RU debit — same invariant as the
            # bill_t0 reset below: billing a wait as RU re-overdraws
            # the bucket and the group never converges
            self._bill_exclude_s = 0.0
        bill_t0 = t0
        try:
            if top and self.resource_group != "default":
                # RU governance: block while this session's group has a
                # negative bucket (previous statements overdrew it) —
                # reference: resource-control token-bucket gating.
                # Inside the try: a kill/timeout during the wait must
                # still unwind _stmt_depth or the session is corrupted.
                bill_t0 = None  # a raise mid-wait must not bill the wait
                self.catalog.resource_groups.acquire(
                    self.resource_group, kill_check=self.killer.check
                )
                # billing starts AFTER the gate: charging the throttle
                # wait itself as RU would re-overdraw the bucket and
                # the group would never converge to its fill rate
                bill_t0 = time.perf_counter()
            res = self._execute_stmt_inner(s, bill_t0)
            if isinstance(s, (
                ast.Insert, ast.Update, ast.Delete, ast.LoadData,
                ast.TruncateTable,
            )) or (
                isinstance(s, ast.TxnControl) and s.op == "commit"
            ):
                # read-your-writes high-water: EVERY statement shape
                # that can capture delta entries moves it — txn COMMIT
                # and TRUNCATE land reload markers just like DML
                self._note_delta_hwm()
            self._maybe_auto_analyze(s)
            if top:
                # FOUND_ROWS()/ROW_COUNT() session state (builtin_info.go)
                if isinstance(s, (ast.Select, ast.Union, ast.With, ast.SetOp)):
                    self._found_rows = len(res.rows)
                    self._last_affected = -1
                else:
                    self._last_affected = int(getattr(res, "affected", 0) or 0)
            return res
        except Exception as e:
            # admission rejections/timeouts (serving.AdmissionRejected,
            # duck-typed on the attribute to avoid the import) surface
            # as errors to the client, but the statements_summary row
            # must still land — with the phase breakdown showing the
            # queue-wait that led to the verdict, or an operator can
            # never see WHY the fleet is shedding load. KILLED
            # statements (KILL QUERY / max_execution_time — now
            # cancelled fleet-wide, parallel/dcn.py) land for the same
            # reason: the runaway's phase breakdown and latency are
            # exactly what an operator tuning max_execution_time needs
            from tidb_tpu.utils.sqlkiller import QueryKilled

            if top and (
                getattr(e, "admission_outcome", None)
                or isinstance(e, QueryKilled)
            ):
                try:
                    self._observe_stmt(s, time.perf_counter() - t0)
                except Exception:
                    pass  # observation must never mask the rejection
            raise
        finally:
            self._stmt_depth -= 1
            if top:
                self._current_stmt = None
                from tidb_tpu.obs.engine_watch import ENGINE_WATCH

                ENGINE_WATCH.end_query(time.perf_counter() - t0)
                # error path: _observe_stmt never ran, so an open
                # flight is half-charged — drop it rather than skew
                # the per-digest phase means
                from tidb_tpu.obs.flight import FLIGHT

                FLIGHT.discard()
            if top and bill_t0 is not None:
                try:
                    self.catalog.resource_groups.debit(
                        self.resource_group,
                        max(
                            time.perf_counter() - bill_t0
                            - getattr(self, "_bill_exclude_s", 0.0),
                            0.0,
                        ),
                    )
                except Exception:
                    pass  # billing must never fail the statement

    def _priority_for(self, s) -> str:
        """Admission priority of one statement: the statement's own
        HIGH_PRIORITY/LOW_PRIORITY modifier wins, else the
        tidb_force_priority sysvar maps in (NO_PRIORITY -> medium,
        DELAYED rides with low, like the reference's mysql.Priority
        mapping)."""
        p = getattr(s, "priority", None)
        if p in ("high", "low"):
            return p
        try:
            forced = str(
                self.vars.get("tidb_force_priority") or "NO_PRIORITY"
            ).upper()
        except Exception:
            forced = "NO_PRIORITY"
        return {
            "HIGH_PRIORITY": "high",
            "LOW_PRIORITY": "low",
            "DELAYED": "low",
        }.get(forced, "medium")

    def _maybe_auto_analyze(self, s) -> None:
        """Statement-boundary auto-analyze check (reference: the stats
        handle's modify-counter-driven HandleAutoAnalyze,
        pkg/statistics/handle/autoanalyze/autoanalyze.go:264). Runs only
        after committed DML — inside a transaction the base table hasn't
        changed yet."""
        if self._txn is not None or not isinstance(
            s, (ast.Insert, ast.Update, ast.Delete, ast.LoadData)
        ):
            return
        try:
            if not self.vars.get("tidb_enable_auto_analyze"):
                return
            raw = self.vars.get("tidb_auto_analyze_ratio")
            ratio = 0.5 if raw is None else float(raw)
            from tidb_tpu.stats.handle import maybe_auto_analyze

            t = self.catalog.table(s.db or self.db, s.table)
            maybe_auto_analyze(t, ratio)
        except Exception:
            pass  # stats refresh must never fail the DML

    # -- privilege enforcement -----------------------------------------
    def _check_priv(self, priv: str, db: str, table: str = "*") -> None:
        if not self.catalog.users.check(self.user, priv, db, table):
            raise PermissionError(
                f"{priv.upper()} command denied to user {self.user!r} "
                f"for table {db}.{table}"
            )

    def _require_super(self) -> None:
        if not self.catalog.users.is_super(self.user):
            raise PermissionError(
                f"user {self.user!r} lacks administrative privileges"
            )

    def _require_some_table_priv(
        self, db: str, name: str, what: str, extra: tuple = ()
    ) -> None:
        """MySQL visitInfo rule for metadata statements (SHOW CREATE /
        COLUMNS / INDEX): ANY privilege on the table suffices."""
        if self.catalog.users.is_super(self.user):
            return
        if not any(
            self.catalog.users.check(self.user, p, db.lower(), name.lower())
            for p in ("select", "insert", "update", "delete") + extra
        ):
            raise PermissionError(
                f"{what} denied to user {self.user!r} on {db}.{name}"
            )

    def _ast_tables(self, node, out=None):
        """All TableRefs in a statement tree (generic dataclass walk)."""
        out = [] if out is None else out
        _walk_dataclasses(
            node,
            lambda n: out.append(n) if isinstance(n, ast.TableRef) else None,
        )
        return out

    def _enforce_privileges(self, s) -> None:
        """Statement -> required privileges (reference: the visitor in
        pkg/planner/core/planbuilder.go collecting visitInfo, checked by
        pkg/privilege). Super users skip the walk."""
        users = self.catalog.users
        if users.is_super(self.user):
            return
        if isinstance(s, (ast.Select, ast.Union, ast.With, ast.SetOp, ast.Explain)):
            for tr in self._ast_tables(s):
                db = (tr.db or self.db).lower()
                # CTE names / derived tables aren't catalog tables.
                # Views check SELECT on the VIEW name only — underlying
                # tables were checked against the creator at CREATE VIEW
                # (definer semantics, the MySQL default).
                if self.catalog.has_table(db, tr.name) or (
                    self.catalog.has_view(db, tr.name)
                ):
                    self._check_priv("select", db, tr.name.lower())
            return
        if isinstance(s, (ast.Insert, ast.Update, ast.Delete, ast.LoadData)):
            priv = {
                ast.Insert: "insert",
                ast.Update: "update",
                ast.Delete: "delete",
                ast.LoadData: "insert",
            }[type(s)]
            if isinstance(s, ast.Update) and s.from_refs is not None:
                refs, per = self._update_targets(s)
                for alias in per:
                    tr = refs[alias]
                    self._check_priv(
                        priv, (tr.db or self.db).lower(), tr.name.lower()
                    )
            elif isinstance(s, ast.Delete) and s.targets is not None:
                refs = self._refs_map(s.from_refs)
                for _tdb, name in s.targets:
                    tr = refs.get(name.lower())
                    nm = tr.name.lower() if tr is not None else name.lower()
                    ndb = ((tr.db if tr else None) or self.db).lower()
                    self._check_priv(priv, ndb, nm)
            else:
                self._check_priv(
                    priv, (s.db or self.db).lower(), s.table.lower()
                )
            # any table READ inside the statement (subqueries in VALUES /
            # SET / WHERE) needs SELECT — otherwise INSERT-only users
            # could exfiltrate other tables (or views) through a subquery
            for tr in self._ast_tables(s):
                db = (tr.db or self.db).lower()
                if self.catalog.has_table(db, tr.name) or (
                    self.catalog.has_view(db, tr.name)
                ):
                    self._check_priv("select", db, tr.name.lower())
        elif isinstance(s, ast.CreateTable):
            self._check_priv("create", (s.db or self.db).lower())
            # CTAS reads its source: require SELECT on every table
            # (otherwise a CREATE-only user exfiltrates data)
            if s.as_query is not None:
                for tr in self._ast_tables(s.as_query):
                    db = (tr.db or self.db).lower()
                    if self.catalog.has_table(db, tr.name) or (
                        self.catalog.has_view(db, tr.name)
                    ):
                        self._check_priv("select", db, tr.name.lower())
        elif isinstance(s, ast.DropTable):
            self._check_priv("drop", (s.db or self.db).lower(), s.name.lower())
        elif isinstance(s, ast.TruncateTable):
            # MySQL requires DROP for TRUNCATE (it is DDL)
            self._check_priv("drop", (s.db or self.db).lower(), s.name.lower())
        elif isinstance(s, ast.CreateView):
            self._check_priv("create", (s.db or self.db).lower())
            # the creator must be able to read every source table NOW —
            # later readers of the view inherit this check's result.
            # Bare refs resolve against the VIEW's db, like expansion.
            for tr in self._ast_tables(s.query):
                db = (tr.db or s.db or self.db).lower()
                if self.catalog.has_table(db, tr.name) or (
                    self.catalog.has_view(db, tr.name)
                ):
                    self._check_priv("select", db, tr.name.lower())
        elif isinstance(s, ast.DropView):
            self._check_priv("drop", (s.db or self.db).lower(), s.name.lower())
        elif isinstance(s, ast.AlterTable):
            self._check_priv("alter", (s.db or self.db).lower(), s.name.lower())
            if s.action == "rename":
                # same gate as the RENAME TABLE statement: the operation
                # is identical, so the privilege must be too
                self._check_priv("drop", (s.db or self.db).lower(), s.name.lower())
                self._check_priv("create", (s.db or self.db).lower())
        elif isinstance(s, ast.AdminStmt):
            self._require_super()
        elif isinstance(s, ast.RenameTable):
            # MySQL: ALTER+DROP on the source, CREATE+INSERT on the
            # target; the alter+drop pair is the enforced core here
            for (sdb, sname), (ddb, dname) in s.pairs:
                self._check_priv("alter", (sdb or self.db).lower(), sname.lower())
                self._check_priv("drop", (sdb or self.db).lower(), sname.lower())
                self._check_priv("create", (ddb or self.db).lower())
        elif isinstance(s, (ast.CreateIndex, ast.DropIndex)):
            self._check_priv("index", (s.db or self.db).lower(), s.table.lower())
        elif isinstance(s, (ast.CreateDatabase, ast.DropDatabase)):
            self._check_priv(
                "create" if isinstance(s, ast.CreateDatabase) else "drop",
                s.name.lower(),
            )
        elif isinstance(s, (ast.CreateSequence, ast.DropSequence)):
            self._check_priv(
                "create" if isinstance(s, ast.CreateSequence) else "drop",
                (s.db or self.db).lower(),
            )
        elif isinstance(
            s, (ast.CreateUser, ast.DropUser, ast.GrantStmt, ast.CreateBinding)
        ):
            self._require_super()
        elif isinstance(s, (ast.BackupRestore, ast.BackupLog, ast.RestorePoint)):
            self._require_super()
        elif isinstance(s, ast.ImportInto):
            self._check_priv("insert", (s.db or self.db).lower(), s.table.lower())
        elif isinstance(s, ast.AnalyzeTable):
            self._check_priv("select", (s.db or self.db).lower(), s.name.lower())
        # SHOW / SET / txn control / USE are unrestricted (SHOW GRANTS
        # FOR another user re-checks inside its handler)

    def _seq_func(self, e):
        """Evaluate NEXTVAL/LASTVAL/SETVAL (reference: sequence function
        builtins over pkg/meta/autoid's sequence allocator). LASTVAL is
        per-session per-sequence, like the reference's sessionVars
        SequenceState."""
        op = e.op.lower()
        a = e.args[0] if e.args else None
        if isinstance(a, ast.Name):
            db, name = (a.table or self.db), a.column
        elif isinstance(a, ast.Const) and isinstance(a.value, str):
            db, name = self.db, a.value
        else:
            raise ValueError(f"{op.upper()} needs a sequence name")
        seq = self.catalog.sequence(db, name)
        key = (db.lower(), name.lower())
        lv = getattr(self, "_seq_lastval", None)
        if lv is None:
            lv = self._seq_lastval = {}
        if op == "nextval":
            v = seq.nextval()
            lv[key] = v
            return v
        if op == "lastval":
            return lv.get(key)
        if len(e.args) < 2:
            raise ValueError("SETVAL needs (sequence, value)")
        return seq.setval(self._const_value(e.args[1]))

    def _user_lock_func(self, e):
        """GET_LOCK / RELEASE_LOCK / IS_FREE_LOCK / IS_USED_LOCK /
        RELEASE_ALL_LOCKS — named advisory locks shared by every session
        over the catalog (reference: builtin_miscellaneous.go over the
        advisory-lock table; locks are re-entrant per session and die
        with it). Returns the MySQL int/NULL result."""
        import time as _time

        from tidb_tpu.utils import racecheck

        op = e.op.lower()
        base = getattr(self.catalog, "_base", self.catalog)
        reg = getattr(base, "_user_locks", None)
        if reg is None:
            reg = base._user_locks = {}  # name -> [conn_id, count]
            base._user_locks_cv = racecheck.make_condition(
                "session.user_locks"
            )
        cv = base._user_locks_cv

        def argval(i):
            a = e.args[i]
            if isinstance(a, ast.Const):
                return a.value
            if isinstance(a, ast.Name):
                return a.column
            raise ValueError(f"{op.upper()} needs literal arguments")

        def held_set():
            held = getattr(self, "_held_user_locks", None)
            if held is None:
                held = self._held_user_locks = set()
                import weakref as _wr

                # register exactly once, at first touch of lock state
                _wr.finalize(
                    self, _release_session_locks, base, self.conn_id
                )
            return held

        if op == "release_all_locks":
            held_set()
            with cv:
                n = 0
                for name in [
                    k for k, v in reg.items() if v[0] == self.conn_id
                ]:
                    n += reg[name][1]
                    del reg[name]
                cv.notify_all()
            self._held_user_locks.clear()
            return n
        name = str(argval(0)).lower()
        if op == "get_lock":
            timeout = float(argval(1)) if len(e.args) > 1 else 0.0
            deadline = _time.monotonic() + max(timeout, 0.0)
            with cv:
                while True:
                    holder = reg.get(name)
                    if holder is None or holder[0] == self.conn_id:
                        if holder is None:
                            reg[name] = [self.conn_id, 1]
                        else:
                            holder[1] += 1  # re-entrant
                        held_set().add(name)
                        return 1
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0:
                        return 0
                    self.killer.check()  # KILL / watchdogs abort waits
                    cv.wait(min(remaining, 0.1))
        if op == "release_lock":
            with cv:
                holder = reg.get(name)
                if holder is None:
                    return None  # lock was never held
                if holder[0] != self.conn_id:
                    return 0  # held by another session
                holder[1] -= 1
                if holder[1] <= 0:
                    del reg[name]
                    cv.notify_all()
                return 1
        if op == "is_free_lock":
            with cv:
                return 0 if name in reg else 1
        # is_used_lock: connection id of the holder, or NULL
        with cv:
            holder = reg.get(name)
            return holder[0] if holder is not None else None

    def _resolve_session_funcs(self, node):
        """Fold session-state functions (LAST_INSERT_ID(), DATABASE(),
        CURRENT_USER()) to constants before planning (the reference
        evaluates these against sessionVars, builtin_info.go). Sequence
        functions fold ONCE per statement here — a multi-row SELECT
        NEXTVAL(s) yields one value; per-row advancement applies in
        INSERT ... VALUES via _const_value."""
        if isinstance(node, SQLType):
            return node
        if isinstance(node, ast.Call) and node.op.lower() in (
            "nextval", "lastval", "setval"
        ):
            return ast.Const(self._seq_func(node))
        if isinstance(node, ast.Call) and node.op.lower() in (
            "get_lock", "release_lock", "is_free_lock", "is_used_lock",
            "release_all_locks",
        ):
            return ast.Const(self._user_lock_func(node))
        if isinstance(node, ast.Call) and node.op.lower() == "random_bytes":
            # folded ONCE per statement (like NEXTVAL in SELECT) —
            # documented divergence from MySQL's per-row evaluation
            import os as _os

            n = node.args[0].value if node.args and isinstance(
                node.args[0], ast.Const
            ) else 1
            n = int(n)
            if not (1 <= n <= 1024):
                raise ValueError(
                    "Data length out of range for random_bytes (1..1024)"
                )
            return ast.Const(_os.urandom(n).decode("latin-1"))
        if isinstance(node, ast.UserVarRef):
            return ast.Const(self.user_vars.get(node.name))
        if isinstance(node, ast.Call) and node.op.lower() in (
            "tidb_encode_sql_digest", "tidb_decode_sql_digests",
        ):
            from tidb_tpu.utils.metrics import sql_digest

            op2 = node.op.lower()
            a0 = node.args[0] if node.args else None
            if not isinstance(a0, ast.Const):
                raise ValueError(f"{op2.upper()} supports constant arguments only")
            if a0.value is None:
                return ast.Const(None)
            if op2 == "tidb_encode_sql_digest":
                import hashlib as _h

                return ast.Const(
                    _h.sha256(sql_digest(str(a0.value)).encode()).hexdigest()
                )
            # decode: map digests back to normalized texts via this
            # session's statement summary (reference resolves through
            # the cluster stmt summary tables)
            import json as _json

            try:
                digests = _json.loads(str(a0.value))
            except Exception:
                return ast.Const(None)
            if not isinstance(digests, list):
                return ast.Const(None)
            import hashlib as _h

            from tidb_tpu.utils.metrics import STMT_SUMMARY

            # summary keys ARE the normalized texts (sql_digest);
            # the wire digest is their sha256
            by_digest = {
                _h.sha256(str(norm).encode()).hexdigest(): str(norm)
                for norm, _n, _s, _mx, _sample in STMT_SUMMARY.rows()
            }
            return ast.Const(
                _json.dumps([by_digest.get(str(d)) for d in digests])
            )
        if isinstance(node, ast.Call) and not node.args:
            op = node.op.lower()
            if op == "last_insert_id":
                return ast.Const(int(self.last_insert_id))
            if op in ("database", "schema"):
                return ast.Const(self.db)
            if op in ("current_user", "session_user", "user", "system_user"):
                return ast.Const(f"{self.user}@%")
            if op == "current_role":
                return ast.Const("NONE")
            if op == "tidb_version":
                return ast.Const(
                    f"tidb-tpu {self.vars.get('version')}\n"
                    "Edition: tpu-native (jax/XLA)"
                )
            if op == "connection_id":
                return ast.Const(int(self.conn_id))
            if op == "found_rows":
                return ast.Const(int(getattr(self, "_found_rows", 0)))
            if op == "version":
                return ast.Const(str(self.vars.get("version")))
            if op == "row_count":
                return ast.Const(int(getattr(self, "_last_affected", -1)))
        if dataclasses.is_dataclass(node) and not isinstance(node, type):
            for f in dataclasses.fields(node):
                setattr(
                    node, f.name, self._resolve_session_funcs(getattr(node, f.name))
                )
            return node
        if isinstance(node, list):
            return [self._resolve_session_funcs(x) for x in node]
        if isinstance(node, tuple):
            return tuple(self._resolve_session_funcs(x) for x in node)
        return node

    def _resolve_stmt(self, s):
        """What every statement does before it runs: the killer's
        deadline, the privilege check, AS OF, the session functions
        folded to constants, the executor's memory and streaming knobs.
        Returns the statement as it will run."""
        from tidb_tpu.utils import failpoint

        try:
            limit_ms = int(self.vars.get("max_execution_time") or 0)
        except Exception:
            limit_ms = 0
        if self._stmt_depth == 1:
            # TOP-LEVEL statements only: a nested statement (TRACE's
            # inner stmt, EXECUTE binding) clearing the flag would
            # silently swallow a KILL that landed mid-statement, and
            # would also reset the statement deadline
            self.killer.clear(
                deadline=(
                    time.monotonic() + limit_ms / 1000.0
                ) if limit_ms else 0.0
            )
        failpoint.inject("session/stmt-start")
        self._enforce_privileges(s)
        is_read = isinstance(s, (ast.Select, ast.Union, ast.With, ast.SetOp))
        dispatch = self._stmt_depth == 1 or self._prepared_dispatch
        self._prepared_dispatch = False
        if dispatch:
            # tidb_read_staleness applies to top-level read statements
            # only — the SELECT half of INSERT..SELECT must see fresh
            # data (reference: staleness providers gate on read-only)
            self._stale_ok = is_read
            inner = s
            while isinstance(inner, (ast.Explain, ast.PlanReplayer, ast.Trace)):
                inner = inner.stmt
            if isinstance(inner, (ast.Select, ast.Union, ast.With, ast.SetOp)):
                self._stmt_as_of = self._collect_as_of(inner)
            else:
                self._stmt_as_of = {}
                if any(
                    r.as_of is not None for r in ast.iter_table_refs(inner)
                ):
                    # the reference rejects stale read in DML; silently
                    # reading FRESH data where the user asked for
                    # historical would be worse than an error
                    raise ValueError(
                        "AS OF TIMESTAMP is only allowed in read-only "
                        "statements"
                    )
        if is_read:
            s = self._resolve_session_funcs(s)
        try:
            self.executor.quota_bytes = int(
                self.vars.get("tidb_mem_quota_query") or 0
            )
        except Exception:
            self.executor.quota_bytes = None
        try:
            self.executor.stream_rows = int(
                self.vars.get("tidb_tpu_stream_rows") or 0
            ) or None
        except Exception:
            pass
        return s

    def _execute_stmt_inner(self, s, t0) -> Result:
        from tidb_tpu.utils import failpoint

        if self._stmt_depth == 1:
            from tidb_tpu.obs.flight import FLIGHT

            with FLIGHT.span("resolve"):
                s = self._resolve_stmt(s)
        else:
            s = self._resolve_stmt(s)
        if isinstance(s, (ast.Select, ast.Union, ast.With, ast.SetOp)):
            # SELECT ... INTO OUTFILE (reference: SelectIntoExec,
            # pkg/executor/select_into.go). The clause parses on the
            # last SELECT block of a union chain — hoist it here so set
            # operations write the file too, and existence-check FIRST:
            # a huge query must not run just to fail on the target path
            outfile = self._take_outfile(s)
            if outfile is not None:
                import os as _os

                if _os.path.exists(outfile):
                    raise ValueError(f"File '{outfile}' already exists")
            fu = self._for_update_tables(s)
            if fu:
                # SELECT ... FOR UPDATE (possibly inside WITH/UNION
                # branches): lock the read tables before planning so the
                # snapshot advances under the lock (ref SelectLockExec)
                if self._txn is not None and self._txn.get("read_only"):
                    # MySQL ER_CANT_EXECUTE_IN_READ_ONLY_TRANSACTION:
                    # locking reads count as writes
                    raise ValueError(
                        "cannot execute statement in a READ ONLY "
                        "transaction"
                    )
                r = self._with_write_locks(fu, lambda: self._run_select(s))
            else:
                r = self._run_select(s)
            if outfile is not None:
                # MySQL default format: tab-separated, \N for NULL
                with open(outfile, "w", encoding="utf-8") as f:
                    for row in r.rows:
                        f.write("\t".join(
                            r"\N" if v is None else str(v) for v in row
                        ) + "\n")
                r = Result([], [], affected=len(r.rows))
        elif isinstance(s, ast.CreateTable) and s.as_query is not None:
            # CREATE TABLE ... AS SELECT: schema derived from the query.
            # Existence check FIRST — don't execute a potentially huge
            # query only to throw the result away. Resolve against the
            # catalog the new table will live in: the shared base for a
            # permanent CTAS (a session temp table shadowing the name
            # must neither block nor receive the rows), the session
            # overlay for CREATE TEMPORARY ... AS.
            ctas_cat = (
                self.catalog
                if s.temporary
                else getattr(self.catalog, "_base", self.catalog)
            )
            if (
                s.temporary
                and ((s.db or self.db).lower(), s.name.lower())
                in self.catalog._temp
            ) or (not s.temporary and ctas_cat.has_table(
                s.db or self.db, s.name
            )):
                if s.if_not_exists:
                    return Result([], [])
                raise ValueError(f"table {s.name} exists")
            res = self._run_select(self._resolve_session_funcs(s.as_query))
            from tidb_tpu.dtypes import INT64 as _I

            types = res.types
            if types is None:
                # infer from the first row (tableless SELECTs)
                from tidb_tpu.expression.expr import literal_type

                first = res.rows[0] if res.rows else ()
                types = [
                    literal_type(v) if v is not None else _I for v in first
                ] or [_I] * len(res.columns)
            cols = []
            seen = set()
            for name, typ in zip(res.columns, types):
                n = name.lower()
                if n in seen or not n.isidentifier():
                    n = f"col_{len(cols)}"
                seen.add(n)
                cols.append((n, typ if typ is not None else _I))
            if s.temporary:
                t = self.catalog.create_temp_table(
                    s.db or self.db, s.name, TableSchema(cols)
                )
            else:
                ctas_cat.create_table(
                    s.db or self.db, s.name, TableSchema(cols), False
                )
                t = ctas_cat.table(s.db or self.db, s.name)
            if res.rows:
                t.append_rows([list(r) for r in res.rows])
            clear_scan_cache()
            r = Result([], [], affected=len(res.rows))
        elif isinstance(s, ast.CreateTable) and s.like is not None:
            # CREATE TABLE dst LIKE src (reference: pkg/ddl table.go
            # CreateTableWithLike): clone the full definition via its
            # own rendered DDL — minus FOREIGN KEYs (MySQL parity) and
            # data; defaults and collations follow, AUTO_INCREMENT
            # restarts
            sdb, sname = s.like
            src = self.catalog.table(sdb or self.db, sname)
            from tidb_tpu.tools.dump import create_table_sql

            lines = create_table_sql(src).rstrip(";").split("\n")
            lines = [
                ln for ln in lines if "foreign key" not in ln.lower()
            ]
            # the filtered line may leave a dangling comma on its
            # predecessor; normalize through join/strip
            body = "\n".join(lines)
            body = body.replace(",\n)", "\n)")
            tgt = f"`{s.name.lower()}`"
            ddl = body.replace(f"CREATE TABLE `{src.name}`", "", 1)
            ddl = f"CREATE TABLE {tgt}" + ddl
            if s.if_not_exists and self.catalog.has_table(
                s.db or self.db, s.name
            ):
                r = Result([], [])
            else:
                stmt = parse(ddl)[0]
                stmt = dataclasses.replace(
                    stmt, db=s.db, temporary=s.temporary
                )
                r = self._execute_stmt_inner(stmt, t0)
                nt = (
                    self._resolve_table_for_write(s.db or self.db, s.name)
                    if s.temporary
                    else self.catalog.table(s.db or self.db, s.name)
                )
                nt.defaults = dict(getattr(src, "defaults", {}) or {})
        elif isinstance(s, ast.CreateTable):
            schema = TableSchema(
                [(c.name.lower(), c.type) for c in s.columns],
                primary_key=[c.lower() for c in s.primary_key] or None,
                enums={
                    c.name.lower(): tuple(c.enum_members)
                    for c in s.columns if c.enum_members
                } or None,
                sets={
                    c.name.lower(): tuple(c.set_members)
                    for c in s.columns if c.set_members
                } or None,
                json_cols=tuple(
                    c.name.lower() for c in s.columns if c.is_json
                ),
                not_null=tuple(
                    c.name.lower() for c in s.columns if c.not_null
                ),
            )
            # validate table options BEFORE creating anything — a DDL
            # error must not leave a half-created table behind
            auto = [c for c in s.columns if c.auto_increment]
            if auto and (len(auto) > 1 or auto[0].type.kind != Kind.INT):
                raise ValueError("one integer AUTO_INCREMENT column per table")
            colnames = {c.name.lower() for c in s.columns}
            gen_meta = self._validate_generated(s, auto, colnames)
            for nm, _txt, expr in s.checks:
                from tidb_tpu.utils.checkeval import check_columns

                missing = check_columns(expr) - colnames
                if missing:
                    raise ValueError(
                        f"CHECK {nm!r} references unknown columns "
                        f"{sorted(missing)}"
                    )
            fks_resolved = []
            for nm, col, rdb, rtbl, rcol in s.fks:
                rdb = (rdb or s.db or self.db).lower()
                rtbl, rcol, col = rtbl.lower(), rcol.lower(), col.lower()
                if col not in colnames:
                    raise ValueError(f"FOREIGN KEY column {col!r} unknown")
                if rdb == (s.db or self.db).lower() and rtbl == s.name.lower():
                    if rcol not in colnames:
                        raise ValueError(
                            f"FOREIGN KEY references unknown column {rcol!r}"
                        )
                else:
                    pt = self.catalog.table(rdb, rtbl)  # raises if missing
                    if rcol not in pt.schema.names:
                        raise ValueError(
                            f"FOREIGN KEY references unknown column "
                            f"{rdb}.{rtbl}.{rcol}"
                        )
                fks_resolved.append((nm, col, rdb, rtbl, rcol))
            ttl_opt = None
            if s.ttl is not None:
                tcol, iv, unit = s.ttl
                tcol = tcol.lower()
                ct = schema.types.get(tcol)
                if ct is None or ct.kind not in (Kind.DATE, Kind.DATETIME):
                    raise ValueError(
                        "TTL column must be an existing DATE/DATETIME column"
                    )
                if unit not in ("day", "week", "month", "hour", "minute", "second"):
                    raise ValueError(f"unsupported TTL unit {unit!r}")
                ttl_opt = (tcol, int(iv), unit)
            part_meta = None
            if s.partition is not None:
                part_meta = self._encode_partition(schema, s.partition)
            if s.temporary:
                if s.partition is not None or ttl_opt is not None:
                    raise ValueError(
                        "temporary tables do not support partitioning/TTL"
                    )
                if fks_resolved:
                    # MySQL: FOREIGN KEYs are not supported on temporary
                    # tables (silently dropped there; rejected here)
                    raise ValueError(
                        "temporary tables do not support FOREIGN KEYs"
                    )
                db_l = (s.db or self.db).lower()
                if db_l not in self.catalog._dbs:
                    # IF NOT EXISTS never excuses a bad database name
                    raise ValueError(f"unknown database {db_l!r}")
                t = None
                if (db_l, s.name.lower()) in self.catalog._temp:
                    if not s.if_not_exists:
                        raise ValueError(
                            f"temporary table {s.name!r} exists"
                        )
                else:
                    t = self.catalog.create_temp_table(
                        db_l, s.name, schema
                    )
                if t is not None:
                    for iname, icols, *uq in s.indexes:
                        self._add_index(
                            t, iname, icols, unique=bool(uq and uq[0])
                        )
                    if auto:
                        t.autoinc_col = auto[0].name.lower()
                    t.checks = [(nm, txt) for nm, txt, _e in s.checks]
                    t.defaults = {
                        c.name.lower(): c.default
                        for c in s.columns
                        if c.default is not None
                    }
                    if gen_meta:
                        t.generated = gen_meta
                existed = True  # the permanent-path block below is N/A
                base_cat = None
            else:
                # permanent path: resolve through the BASE catalog — a
                # session temp table may shadow the name, and the new
                # permanent table must not inherit its identity
                base_cat = getattr(self.catalog, "_base", self.catalog)
                existed = (
                    s.if_not_exists
                    and base_cat.has_table(s.db or self.db, s.name)
                )
                self.catalog.create_table(
                    s.db or self.db, s.name, schema, s.if_not_exists
                )
            if not existed:
                # IF NOT EXISTS on a pre-existing table is a full no-op:
                # in-definition indexes must not mutate the live table
                t = base_cat.table(s.db or self.db, s.name)
                for iname, icols, *uq in s.indexes:
                    self._add_index(t, iname, icols, unique=bool(uq and uq[0]))
                if auto:
                    t.autoinc_col = auto[0].name.lower()
                t.ttl = ttl_opt
                t.partition = part_meta
                t.checks = [(nm, txt) for nm, txt, _e in s.checks]
                t.fks = fks_resolved
                t.fk_actions = {
                    nm.lower(): act
                    for nm, act in (getattr(s, "fk_actions", {}) or {}).items()
                    if act != "restrict"
                }
                t.fk_update_actions = {
                    nm.lower(): act
                    for nm, act in (
                        getattr(s, "fk_update_actions", {}) or {}
                    ).items()
                    if act != "restrict"
                }
                t.defaults = {
                    c.name.lower(): c.default
                    for c in s.columns
                    if c.default is not None
                }
                if gen_meta:
                    t.generated = gen_meta
            r = Result([], [])
        elif isinstance(s, ast.CreateIndex):
            failpoint.inject("ddl/create-index")
            t = self.catalog.table(s.db or self.db, s.table)
            if s.name.lower() in t.indexes:
                if not s.if_not_exists:
                    raise ValueError(f"index {s.name} already exists")
            else:
                self._add_index(t, s.name, s.columns, unique=s.unique)
                self.catalog.schema_version += 1
            r = Result([], [])
        elif isinstance(s, ast.DropIndex):
            t = self.catalog.table(s.db or self.db, s.table)
            if s.name.lower() not in t.indexes:
                if not s.if_exists:
                    raise ValueError(f"unknown index {s.name}")
            else:
                del t.indexes[s.name.lower()]
                t.index_states.pop(s.name.lower(), None)
                t.unique_indexes.discard(s.name.lower())
                t.invisible_indexes.discard(s.name.lower())
                t.bump_version()
                self.catalog.schema_version += 1
            r = Result([], [])
        elif isinstance(s, ast.DropTable):
            self.catalog.drop_table(
                s.db or self.db, s.name, s.if_exists,
                temporary_only=s.temporary,
            )
            clear_scan_cache()
            r = Result([], [])
        elif isinstance(s, ast.CreateView):
            db = (s.db or self.db).lower()
            if self.catalog.has_view(db, s.name) and not s.or_replace:
                raise ValueError(f"view {s.name} exists")
            # plan the body NOW so unknown tables/columns, arity and
            # ambiguity surface at CREATE time (MySQL does the same);
            # the stored text is re-planned per use. Qualify bare refs
            # with the view's db first — validation must see the same
            # resolution the expansion path will use (scalar subqueries
            # execute against the session's current db otherwise).
            from tidb_tpu.planner.logical import qualify_view_body

            qualify_view_body(s.query, db)
            plan = build_query(s.query, self.catalog, db, self._scalar_subquery)
            names = [
                c.lower() for c in (s.columns or [])
            ] or [c.name for c in plan.schema.cols]
            if s.columns and len(s.columns) != len(plan.schema.cols):
                raise ValueError(
                    f"view column list has {len(s.columns)} names but "
                    f"SELECT yields {len(plan.schema.cols)} columns"
                )
            if len(set(names)) != len(names):
                raise ValueError("duplicate column name in view")
            self.catalog.create_view(
                db, s.name, s.query_sql, s.columns, s.or_replace
            )
            r = Result([], [])
        elif isinstance(s, ast.DropView):
            self.catalog.drop_view(s.db or self.db, s.name, s.if_exists)
            r = Result([], [])
        elif isinstance(s, ast.AdminStmt):
            r = self._run_admin(s)
        elif isinstance(s, ast.RenameTable):
            failpoint.inject("ddl/rename-table")
            # MySQL RENAME TABLE is atomic across its pairs: validate
            # every source/target first, then move; a later-pair
            # failure rolls earlier moves back
            done = []
            try:
                for (sdb, sname), (ddb, dname) in s.pairs:
                    self.catalog.rename_table(
                        sdb or self.db, sname, ddb or self.db, dname
                    )
                    done.append(((sdb or self.db, sname), (ddb or self.db, dname)))
            except Exception:
                for (sdb, sname), (ddb, dname) in reversed(done):
                    self.catalog.rename_table(ddb, dname, sdb, sname)
                raise
            clear_scan_cache()
            r = Result([], [])
        elif isinstance(s, ast.TruncateTable):
            def _truncate(db=s.db or self.db):
                t = self._resolve_table_for_write(db, s.name)
                children = self._fk_children(db, s.name)
                undo = []
                self._fk_undo_snapshot(undo, t)
                saved_auto = t.autoinc_next
                # truncate FIRST, then referential actions against the
                # post-statement state; any failure (nested RESTRICT)
                # restores every touched table — the statement is atomic
                t.replace_blocks([], modified_rows=t.nrows)
                try:
                    if children:
                        self._enforce_parent_constraints(
                            db, s.name,
                            {c: set() for c in t.schema.names},
                            actions=True, undo=undo,
                        )
                except BaseException:
                    self._fk_undo_restore(undo)
                    t.autoinc_next = saved_auto
                    raise
                t.autoinc_next = 1  # TRUNCATE resets AUTO_INCREMENT (DDL)
                clear_scan_cache()
                return Result([], [])

            r = self._with_write_locks(
                [(s.db or self.db, s.name)], _truncate
            )
        elif isinstance(s, ast.AlterTable):
            failpoint.inject("ddl/alter-table")
            t = self.catalog.table(s.db or self.db, s.name)
            if s.action == "add":
                if getattr(s.column, "generated", None) is not None:
                    self._alter_add_generated(t, s)
                else:
                    default = s.default
                    coerced = None
                    if s.default is not None:
                        # validate the literal BEFORE any mutation — an
                        # invalid default must not leave a half-added
                        # column behind (MySQL: Invalid default value)
                        coerced = self._gen_coerce(
                            s.default, s.column.type
                        )
                        if coerced is None:
                            raise ValueError(
                                "Invalid default value for "
                                f"{s.column.name!r}"
                            )
                        default = coerced
                    if default is None and s.column.not_null:
                        # MySQL fills the type default for NOT NULL adds
                        default = (
                            "" if s.column.type.kind == Kind.STRING else 0
                        )
                    t.alter_add_column(s.column.name, s.column.type, default)
                    if coerced is not None:
                        # the DEFAULT applies to FUTURE inserts too, not
                        # just the backfill of existing rows
                        if not hasattr(t, "defaults"):
                            t.defaults = {}
                        t.defaults[s.column.name.lower()] = coerced
            elif s.action in ("modify", "change"):
                self._run_modify_column(t, s)
            elif s.action == "index_visibility":
                iname = s.col_name.lower()
                if iname not in t.indexes:
                    raise ValueError(f"unknown index {iname!r}")
                if s.new_name == "invisible":
                    t.invisible_indexes.add(iname)
                else:
                    t.invisible_indexes.discard(iname)
                t.bump_version()
            elif s.action == "set_default":
                cn = s.col_name.lower()
                if cn not in t.schema.types:
                    raise ValueError(f"unknown column {cn!r}")
                coerced = self._gen_coerce(s.default, t.schema.types[cn])
                if coerced is None and s.default is not None:
                    raise ValueError(f"Invalid default value for {cn!r}")
                if not hasattr(t, "defaults"):
                    t.defaults = {}
                t.defaults[cn] = coerced
                t.bump_version()
            elif s.action == "drop_default":
                cn = s.col_name.lower()
                if cn not in t.schema.types:
                    raise ValueError(f"unknown column {cn!r}")
                getattr(t, "defaults", {}).pop(cn, None)
                t.bump_version()
            elif s.action == "rename_col":
                self._guard_column_refs(
                    t, s.db or self.db, s.name, s.col_name.lower(), "rename"
                )
                t.alter_rename_column(s.col_name, s.new_name)
            elif s.action == "rename":
                self.catalog.rename_table(
                    s.db or self.db, s.name, s.db or self.db, s.new_name
                )
            elif s.action == "add_partition":
                # reference: pkg/ddl/partition.go onAddTablePartition —
                # metadata-only for RANGE/LIST; bounds encode exactly
                # like CREATE TABLE's (dates->days, decimals->scaled)
                if t.partition is None or t.partition[0] not in (
                    "range", "list",
                ):
                    raise ValueError(
                        "ADD PARTITION requires a RANGE- or "
                        "LIST-partitioned table"
                    )
                enc = self._encode_partition(
                    t.schema, (t.partition[0], t.partition[1], s.partitions)
                )
                t.alter_add_partitions(enc[2])
            elif s.action == "exchange_partition":
                if self._txn is not None:
                    raise ValueError(
                        "partition DDL is not allowed inside a "
                        "transaction; COMMIT first"
                    )
                self._with_write_locks(
                    [
                        (s.db or self.db, s.name),
                        (s.exchange[0] or s.db or self.db, s.exchange[1]),
                    ],
                    lambda: self._run_exchange_partition(t, s),
                )
            elif s.action in ("drop_partition", "truncate_partition"):
                # rows vanish like a DELETE: children's ON DELETE
                # referential actions apply against the post-statement
                # parent values (the TRUNCATE TABLE pattern above);
                # any nested RESTRICT restores every touched table.
                # Rejected inside an explicit transaction: the FK value
                # sets resolve through the session's pinned snapshot, so
                # an in-txn check would validate against pre-drop values
                # (MySQL/TiDB implicitly commit before DDL; erroring is
                # the safe analog for this engine's snapshot txns)
                if self._txn is not None:
                    raise ValueError(
                        "partition DDL is not allowed inside a "
                        "transaction; COMMIT first"
                    )
                db = s.db or self.db

                def _part_ddl(db=db, t=t):
                    children = self._fk_children(db, s.name)
                    undo = []
                    self._fk_undo_snapshot(undo, t)
                    saved_defs = t.partition
                    removed = t.alter_drop_partitions(
                        s.partitions,
                        truncate_only=s.action == "truncate_partition",
                    )
                    try:
                        if children and removed:
                            ref_cols = {
                                rcol
                                for _cd, _ct, _nm, _c, rcol, _a in children
                            }
                            remaining = {
                                rc: self._column_values(db, s.name, rc)
                                for rc in ref_cols
                            }
                            self._enforce_parent_constraints(
                                db, s.name, remaining, actions=True,
                                undo=undo,
                            )
                    except BaseException:
                        self._fk_undo_restore(undo)
                        t.partition = saved_defs  # undo covers blocks only
                        raise

                self._with_write_locks([(db, s.name)], _part_ddl)
            else:
                cn = s.col_name.lower()
                from tidb_tpu.utils.checkeval import check_columns

                for nm, ex in self._check_exprs_for(t):
                    if cn in check_columns(ex):
                        raise ValueError(
                            f"cannot drop column {cn!r}: used by CHECK {nm!r}"
                        )
                for gc, ex in self._gen_exprs_for(t):
                    if cn in check_columns(ex):
                        raise ValueError(
                            f"cannot drop column {cn!r}: used by "
                            f"generated column {gc!r}"
                        )
                for nm, col, rdb, rtbl, rcol in t.fks:
                    if cn == col:
                        raise ValueError(
                            f"cannot drop column {cn!r}: used by "
                            f"FOREIGN KEY {nm!r}"
                        )
                for cdb, ctn, nm, _c, rcol, _act in self._fk_children(
                    s.db or self.db, s.name
                ):
                    if cn == rcol:
                        raise ValueError(
                            f"cannot drop column {cn!r}: referenced by "
                            f"FOREIGN KEY {nm!r} on {cdb}.{ctn}"
                        )
                t.alter_drop_column(s.col_name)
                gen = getattr(t, "generated", None)
                if gen:
                    # dropping a generated column removes its rule
                    t.generated = [g for g in gen if g[0] != cn]
                    t._gen_exprs = None
            self.catalog.schema_version += 1
            clear_scan_cache()
            r = Result([], [])
        elif isinstance(s, ast.MultiAlter):
            # comma-separated ALTER actions (reference:
            # pkg/ddl/multi_schema_change.go — atomic): snapshot every
            # DDL-visible table attribute, apply the specs in order
            # under the table write lock, restore wholesale if any spec
            # fails. Specs whose effects escape the one-table snapshot
            # (RENAME, partition management) are rejected in combination
            # — the reference's multi-schema change restricts the same
            # way (table options/renames don't combine)
            for spec in s.specs:
                act = getattr(spec, "action", None)
                if act in (
                    "rename", "add_partition", "drop_partition",
                    "truncate_partition", "exchange_partition",
                ):
                    raise ValueError(
                        f"ALTER action {act!r} cannot be combined with "
                        "other specs in one statement"
                    )
            t = self.catalog.table(s.db or self.db, s.name)

            def _multi_alter(t=t):
                snap = {
                    "schema": t.schema,
                    "indexes": {k: list(v) for k, v in t.indexes.items()},
                    "unique_indexes": set(t.unique_indexes),
                    "index_states": dict(t.index_states),
                    "defaults": dict(getattr(t, "defaults", {}) or {}),
                    "generated": list(getattr(t, "generated", None) or []),
                    "checks": list(t.checks),
                    "partition": t.partition,
                    "autoinc": (t.autoinc_col, t.autoinc_next),
                    "blocks": list(t.blocks()),
                    "dictionaries": dict(t.dictionaries),
                }
                # nested-statement depth: spec execution must not run
                # the top-level prologue (killer.clear/deadline reset —
                # a KILL landing between specs would be swallowed)
                self._stmt_depth = getattr(self, "_stmt_depth", 0) + 1
                try:
                    for spec in s.specs:
                        self._execute_stmt_inner(spec, t0)
                except BaseException:
                    t.schema = snap["schema"]
                    t.indexes = snap["indexes"]
                    t.unique_indexes = snap["unique_indexes"]
                    t.index_states = snap["index_states"]
                    t.defaults = snap["defaults"]
                    t.generated = snap["generated"]
                    t._gen_exprs = None
                    t.checks = snap["checks"]
                    t.partition = snap["partition"]
                    t.autoinc_col, t.autoinc_next = snap["autoinc"]
                    t.dictionaries = snap["dictionaries"]
                    t.replace_blocks(snap["blocks"], modified_rows=0)
                    self.catalog.schema_version += 1
                    clear_scan_cache()
                    raise
                finally:
                    self._stmt_depth -= 1
                self.catalog.schema_version += 1
                clear_scan_cache()
                return Result([], [])

            r = self._with_write_locks(
                [(s.db or self.db, s.name)], _multi_alter
            )
        elif isinstance(s, ast.CreateBinding):
            self._require_super()
            from tidb_tpu.utils.metrics import sql_digest

            if not hasattr(self.catalog, "bindings"):
                self.catalog.bindings = {}
            digest = sql_digest(s.for_sql)
            if s.drop:
                self.catalog.bindings.pop(digest, None)
            else:
                if not isinstance(parse(s.for_sql)[0], ast.Select):
                    raise ValueError(
                        "bindings currently apply to plain SELECT "
                        "statements only"
                    )
                using = parse(s.using_sql)[0]
                hints = tuple(getattr(using, "hints", ()) or ())
                if not hints:
                    raise ValueError(
                        "CREATE BINDING: the USING statement carries no "
                        "/*+ ... */ hints"
                    )
                self.catalog.bindings[digest] = {
                    "for_sql": s.for_sql,
                    "using_sql": s.using_sql,
                    "hints": hints,
                }
            r = Result([], [])
        elif isinstance(s, ast.BackupRestore):
            failpoint.inject("br/statement")
            from tidb_tpu.storage.persist import load_catalog, save_catalog

            dbs = [s.db] if s.db else None
            # BR operates on the SHARED base catalog: session temp
            # tables must neither ride into backups nor shadow restores
            bcat = getattr(self.catalog, "_base", self.catalog)
            if s.restore:
                load_catalog(s.path, bcat, dbs=dbs)
                clear_scan_cache()
            else:
                save_catalog(bcat, s.path, dbs=dbs, resume=True)
            r = Result([], [])
        elif isinstance(s, ast.BackupLog):
            from tidb_tpu.storage.logbackup import LogBackupTask

            task = getattr(self.catalog, "log_backup", None)
            if s.action == "start":
                if task is not None:
                    raise ValueError("a log backup task is already running")
                task = LogBackupTask(self.catalog, s.uri)
                task.start()
                self.catalog.log_backup = task
                r = Result([], [])
            elif s.action == "stop":
                if task is None:
                    raise ValueError("no log backup task is running")
                task.stop()
                self.catalog.log_backup = None
                r = Result([], [])
            else:  # status
                rows = []
                if task is not None:
                    task.advance()
                    # exact ts, never rounded down: operators feed this
                    # into RESTORE POINT ... UNTIL, and a truncated value
                    # would exclude the newest segment the checkpoint
                    # claims is durable
                    rows.append(("running", task.uri, task.checkpoint_ts))
                r = Result(["state", "storage", "checkpoint_ts"], rows)
        elif isinstance(s, ast.ChangefeedStmt):
            from tidb_tpu.storage.cdc import Changefeed

            # feed lives on the SHARED base catalog (like log backup):
            # session temp tables never enter the stream
            bcat = getattr(self.catalog, "_base", self.catalog)
            feed = getattr(bcat, "changefeed", None)
            if s.action == "start":
                if feed is not None:
                    raise ValueError("a changefeed is already running")
                feed = Changefeed(bcat, s.uri)
                feed.start()
                bcat.changefeed = feed
                r = Result([], [])
            elif s.action == "stop":
                if feed is None:
                    raise ValueError("no changefeed is running")
                feed.stop()
                bcat.changefeed = None
                r = Result([], [])
            else:  # status
                rows = []
                if feed is not None:
                    feed.advance()
                    rows.append((
                        "running", feed.sink_uri, feed.checkpoint_ts,
                        feed.events_emitted,
                    ))
                r = Result(
                    ["state", "sink", "checkpoint_ts", "events"], rows
                )
        elif isinstance(s, ast.RestorePoint):
            from tidb_tpu.storage.logbackup import restore_point_in_time

            n = restore_point_in_time(
                s.uri, getattr(self.catalog, "_base", self.catalog),
                s.until_ts,
            )
            clear_scan_cache()
            r = Result(["tables_restored"], [(n,)])
        elif isinstance(s, ast.ImportInto):
            # distributed chunked import on the DXF (lightning pipeline
            # analog, pkg/disttask/importinto)
            import tidb_tpu.dxf.tasks  # noqa: F401  (register types)
            from tidb_tpu.dxf import TaskManager

            target = self.catalog.table(s.db or self.db, s.table)
            before = target.nrows
            m = TaskManager(self.catalog)
            tid = m.submit(
                "import",
                {
                    "db": (s.db or self.db), "table": s.table,
                    "path": s.path, "sep": s.sep,
                },
            )
            state = m.run_to_completion(tid, executors=4)
            if state != "succeed":
                raise RuntimeError(
                    f"IMPORT INTO failed: {m.tasks[tid]['error']}"
                )
            r = Result([], [], affected=target.nrows - before)
        elif isinstance(s, ast.CreateUser):
            self.catalog.users.create_user(s.name, s.password, s.if_not_exists)
            r = Result([], [])
        elif isinstance(s, ast.DropUser):
            self.catalog.users.drop_user(s.name, s.if_exists)
            r = Result([], [])
        elif isinstance(s, ast.GrantStmt):
            db = s.db if s.db else self.db
            if s.revoke:
                self.catalog.users.revoke(set(s.privs), db, s.table, s.user)
            else:
                self.catalog.users.grant(set(s.privs), db, s.table, s.user)
            r = Result([], [])
        elif isinstance(s, ast.CreateSequence):
            from tidb_tpu.storage.sequence import Sequence

            seq = Sequence(
                s.name.lower(), start=s.start, increment=s.increment,
                minvalue=s.minvalue, maxvalue=s.maxvalue, cycle=s.cycle,
                cache=s.cache,
            )
            self.catalog.create_sequence(
                s.db or self.db, s.name, seq, s.if_not_exists
            )
            r = Result([], [])
        elif isinstance(s, ast.DropSequence):
            self.catalog.drop_sequence(s.db or self.db, s.name, s.if_exists)
            r = Result([], [])
        elif isinstance(s, ast.CreateDatabase):
            self.catalog.create_database(s.name, s.if_not_exists)
            r = Result([], [])
        elif isinstance(s, ast.DropDatabase):
            self.catalog.drop_database(s.name)
            r = Result([], [])
        elif isinstance(s, ast.UseDatabase):
            dbl = s.name.lower()
            if dbl not in (
                "information_schema", "metrics_schema"
            ) and dbl not in [
                d.lower() for d in self.catalog.databases()
            ]:
                raise ValueError(f"unknown database {s.name}")
            self.db = dbl
            r = Result([], [])
        elif isinstance(s, ast.SetNames):
            # connector handshake (reference: pkg/executor/set.go
            # setCharset): latch the character_set_*/collation vars;
            # the engine is utf8mb4-native so this is bookkeeping
            from tidb_tpu.utils import collate as _coll

            cs = s.charset.lower()
            coll = (
                s.collation.lower()
                if s.collation
                else _coll.CHARSET_DEFAULTS.get(cs)
            )
            if coll is None:
                raise ValueError(f"Unknown character set: '{cs}'")
            for v in (
                "character_set_client", "character_set_connection",
                "character_set_results",
            ):
                self.vars.set(v, cs, "session")
            self.vars.set("collation_connection", coll, "session")
            r = Result([], [])
        elif isinstance(s, ast.SetTransaction):
            if s.isolation is not None:
                self.vars.set(
                    "transaction_isolation", s.isolation, s.scope
                )
            if s.access is not None and s.access == "only":
                self.vars.set("transaction_read_only", 1, s.scope)
            elif s.access == "write":
                self.vars.set("transaction_read_only", 0, s.scope)
            r = Result([], [])
        elif isinstance(s, ast.Do):
            # evaluate and discard (side effects like GET_LOCK run)
            q = ast.Select(
                items=[
                    ast.SelectItem(e, alias=f"_do{i}")
                    for i, e in enumerate(s.exprs)
                ],
                from_=None,
            )
            self._run_select(self._resolve_session_funcs(q))
            r = Result([], [])
        elif isinstance(s, ast.Noop):
            r = Result([], [])
        elif isinstance(s, ast.OptimizeTable):
            rows = []
            for db_, name_ in s.tables:
                db_ = db_ or self.db
                self.catalog.table(db_, name_)  # existence check
                self._execute_stmt_inner(
                    ast.AnalyzeTable(db_, name_), t0
                )
                full = f"{db_}.{name_}"
                rows.append((
                    full, "optimize", "note",
                    "Table does not support optimize, doing recreate + "
                    "analyze instead",
                ))
                rows.append((full, "optimize", "status", "OK"))
            r = Result(["Table", "Op", "Msg_type", "Msg_text"], rows)
        elif isinstance(s, ast.Insert):
            r = self._with_write_locks(
                [(s.db or self.db, s.table)], lambda: self._run_insert(s)
            )
        elif isinstance(s, ast.Delete):
            r = self._with_write_locks(
                self._dml_lock_tables(s), lambda: self._run_delete(s)
            )
        elif isinstance(s, ast.Update):
            r = self._with_write_locks(
                self._dml_lock_tables(s), lambda: self._run_update(s)
            )
        elif isinstance(s, ast.Explain):
            r = self._run_explain(s)
        elif isinstance(s, ast.PlanReplayer):
            r = self._run_plan_replayer(s)
        elif isinstance(s, ast.ResourceGroupDDL):
            rg = self.catalog.resource_groups
            if s.action == "create":
                rg.create(
                    s.name, s.ru_per_sec, bool(s.burstable),
                    if_not_exists=s.if_not_exists,
                )
            elif s.action == "alter":
                rg.alter(s.name, s.ru_per_sec, s.burstable)
            else:
                rg.drop(s.name, if_exists=s.if_exists)
            r = Result([], [])
        elif isinstance(s, ast.Kill):
            reg = getattr(self.catalog, "_session_registry", {})
            target = reg.get(s.conn_id)
            if target is None:
                raise ValueError(f"unknown connection id {s.conn_id}")
            # both forms abort the in-flight statement at its next kill
            # safepoint; KILL CONNECTION additionally closes the
            # session — every later execute on it fails (reference:
            # pkg/server kill handling)
            target.killer.kill()
            if not s.query_only:
                target._killed_conn = True
            r = Result([], [])
        elif isinstance(s, ast.SetResourceGroup):
            # validate the group exists before binding
            self.catalog.resource_groups.get(s.name)
            self.resource_group = s.name.lower()
            r = Result([], [])
        elif isinstance(s, ast.Show):
            r = self._run_show(s)
        elif isinstance(s, ast.SetVariable):
            if s.scope == "user":
                self.user_vars[s.name.lstrip("@")] = s.value
            else:
                self.vars.set(s.name, s.value, s.scope)
                if s.name.lower() in (
                    "tidb_server_memory_limit",
                    "tidb_memory_usage_alarm_ratio",
                    "tidb_expensive_query_time_threshold",
                ):
                    # the instance watchdog starts lazily at first touch
                    # of its knobs (memoryusagealarm/servermemorylimit)
                    from tidb_tpu.utils.watchdog import ensure_watchdog

                    ensure_watchdog(self.catalog)
                if s.name.lower() == "tidb_timeline_capture":
                    # the capture gate is engine-wide (one merged
                    # fleet timeline), armed/disarmed by the sysvar
                    from tidb_tpu.obs.timeline import TIMELINE

                    if self.vars.get("tidb_timeline_capture"):
                        TIMELINE.start()
                    else:
                        TIMELINE.stop()
                if s.name.lower().startswith("tidb_tpu_admission_"):
                    # live re-tune of an attached scheduler's running
                    # admission controller (construction-time wiring
                    # is AdmissionController.from_sysvars)
                    sched = getattr(self, "dcn_scheduler", None)
                    adm = getattr(sched, "admission", None)
                    if adm is not None:
                        adm.budget_bytes = int(
                            self.vars.get("tidb_tpu_admission_budget_bytes")
                        )
                        adm.max_queue = int(
                            self.vars.get("tidb_tpu_admission_queue_limit")
                        )
                        adm.starvation_s = float(
                            self.vars.get("tidb_tpu_admission_starvation_s")
                        )
                if s.name.lower().startswith(
                    ("tidb_tpu_shuffle_", "tidb_tpu_heartbeat_",
                     "tidb_tpu_aqe_", "tidb_tpu_runtime_filter")
                ) and s.scope == "global":
                    # live re-tune of an attached scheduler's shuffle
                    # wait timeout and heartbeat liveness knobs (the
                    # admission-knob pattern above; construction-time
                    # wiring is the scheduler ctor's sysvar
                    # resolution). GLOBAL scope only, read through a
                    # session-override-free view: the scheduler is
                    # SHARED by every attached session — one tenant's
                    # session-scoped SET must not re-time the whole
                    # fleet's timeouts
                    sched = getattr(self, "dcn_scheduler", None)
                    if sched is not None:
                        from tidb_tpu.utils.sysvar import SysVars

                        gv = SysVars(self.catalog.global_sysvars)
                        name = s.name.lower()
                        if name.startswith("tidb_tpu_aqe_"):
                            # live re-tune of the AQE knobs (the
                            # shuffle-timeout pattern): feedback
                            # seeding and the replan divergence bar
                            was_fb = sched.aqe_feedback
                            sched.aqe_feedback = bool(
                                gv.get("tidb_tpu_aqe_feedback")
                            )
                            sched.aqe_replan_ratio = float(
                                gv.get("tidb_tpu_aqe_replan_ratio")
                            )
                            if sched.aqe_feedback and not was_fb:
                                # feedback just turned ON: re-seed the
                                # store's est/act pairs from the
                                # statements_summary_history windows
                                # (digests the live summary churned
                                # out keep their divergence signal)
                                from tidb_tpu.planner.cardinality import (
                                    CARD_FEEDBACK,
                                )

                                CARD_FEEDBACK.warm_from_history()
                        elif name.startswith("tidb_tpu_runtime_filter"):
                            # live re-tune of the runtime-filter mode
                            # and geometry knobs (same pattern): the
                            # next probed stage picks them up
                            sched.runtime_filter = str(
                                gv.get("tidb_tpu_runtime_filter")
                            )
                            sched.rf_bloom_bits = int(gv.get(
                                "tidb_tpu_runtime_filter_bloom_bits"
                            ))
                            sched.rf_inlist_ndv = int(gv.get(
                                "tidb_tpu_runtime_filter_inlist_ndv"
                            ))
                        elif name.startswith("tidb_tpu_shuffle_"):
                            sched.shuffle_wait_timeout_s = float(
                                gv.get(
                                    "tidb_tpu_shuffle_wait_timeout_s"
                                )
                            )
                            # skew knobs ride the same family: a SET
                            # arms/retunes the probe live
                            sched.shuffle_skew_ratio = float(
                                gv.get("tidb_tpu_shuffle_skew_ratio")
                            )
                            sched.shuffle_skew_salt_k = int(
                                gv.get("tidb_tpu_shuffle_skew_salt_k")
                            )
                        else:
                            sched.heartbeat.retune(
                                interval_s=float(
                                    gv.get(
                                        "tidb_tpu_heartbeat_interval_s"
                                    )
                                ),
                                miss_threshold=int(gv.get(
                                    "tidb_tpu_heartbeat_miss_threshold"
                                )),
                            )
                if s.name.lower().startswith("tidb_tpu_tsdb_") and \
                        s.scope == "global":
                    # live re-tune of the metric time-series tier
                    # (obs/tsdb.py): the sampler cadence (0 stops the
                    # background thread; statement-close passive ticks
                    # remain) and the retention/downsample ring caps.
                    # GLOBAL scope like the heartbeat knobs — one
                    # store serves every session
                    from tidb_tpu.obs.tsdb import SAMPLER, TSDB
                    from tidb_tpu.utils.sysvar import SysVars

                    gv = SysVars(self.catalog.global_sysvars)
                    if s.name.lower() == "tidb_tpu_tsdb_sample_interval_s":
                        SAMPLER.retune(float(
                            gv.get("tidb_tpu_tsdb_sample_interval_s")
                        ))
                    else:
                        TSDB.retune_retention(
                            retention_points=int(gv.get(
                                "tidb_tpu_tsdb_retention_points"
                            )),
                            downsample_every=int(gv.get(
                                "tidb_tpu_tsdb_downsample_every"
                            )),
                        )
                if s.name.lower() in (
                    "tidb_enable_top_sql",
                    "tidb_top_sql_max_time_series_count",
                    "tidb_top_sql_max_meta_count",
                    "tidb_tpu_topsql_sample_interval_s",
                ) and s.scope == "global":
                    # live wiring of the Top SQL knobs (obs/profiler
                    # .py): enable starts/stops THIS process's sampler
                    # immediately; the caps re-tune the store (the
                    # PR 12 retune pattern). Worker processes pick the
                    # same config up from the next dispatch or
                    # heartbeat ping — the frames carry it. GLOBAL
                    # scope only, read through a session-override-free
                    # view: one fleet profiler serves every session.
                    from tidb_tpu.obs.profiler import TOPSQL
                    from tidb_tpu.utils.sysvar import SysVars

                    TOPSQL.apply_sysvars(
                        SysVars(self.catalog.global_sysvars)
                    )
                if s.name.lower() in (
                    "tidb_stmt_summary_refresh_interval",
                    "tidb_stmt_summary_history_size",
                ):
                    # upgrade the compat knobs to live behavior: the
                    # statements_summary history store rotates on the
                    # refresh interval and keeps history_size windows
                    from tidb_tpu.utils.metrics import STMT_HISTORY

                    try:
                        if s.name.lower().endswith("refresh_interval"):
                            STMT_HISTORY.refresh_interval_s = max(
                                float(self.vars.get(
                                    "tidb_stmt_summary_refresh_interval"
                                )), 0.001,
                            )
                        else:
                            STMT_HISTORY.set_capacity(int(
                                self.vars.get(
                                    "tidb_stmt_summary_history_size"
                                )
                            ))
                    except (TypeError, ValueError):
                        pass  # compat knobs accept any value; only
                        # numeric ones re-tune the store
                if s.name.lower() == "tidb_gc_life_time":
                    # side effect: the storage GC horizon is engine-wide.
                    # The sysvar is GLOBAL-only (set() above enforces
                    # that), so the global store — not a session
                    # override — is the value to apply
                    from tidb_tpu.storage.table import set_gc_life

                    set_gc_life(
                        float(
                            self.vars._globals.get("tidb_gc_life_time", 0)
                        )
                    )
            r = Result([], [])
        elif isinstance(s, ast.PrepareStmt):
            self.prepare(s.name, s.sql)
            r = Result([], [])
        elif isinstance(s, ast.ExecuteStmt):
            vals = []
            for v in s.using:
                if v not in self.user_vars:
                    raise ValueError(f"user variable @{v} is not set")
                vals.append(self.user_vars[v])
            r = self.execute_prepared(s.name, vals)
        elif isinstance(s, ast.DeallocateStmt):
            self.deallocate(s.name)
            r = Result([], [])
        elif isinstance(s, ast.Trace):
            from tidb_tpu.obs.flight import FLIGHT

            # the inner statement's FLIGHT.span calls feed the tracer
            self.tracer.enabled = True
            self.tracer.reset()
            FLIGHT.trace_into(self.tracer)
            try:
                self._execute_stmt(s.stmt)
            finally:
                FLIGHT.trace_into(None)
                self.tracer.enabled = False
            r = Result(["operation", "startTS", "duration"], self.tracer.rows())
        elif isinstance(s, ast.TxnControl):
            r = self._run_txn_control(s)
        elif isinstance(s, ast.AnalyzeTable):
            r = self._run_analyze_table(s)
        elif isinstance(s, ast.LoadData):
            r = self._with_write_locks(
                [(s.db or self.db, s.table)], lambda: self._run_load_data(s)
            )
        else:
            raise ValueError(f"unsupported statement {type(s).__name__}")
        r.elapsed_s = time.perf_counter() - t0
        if self._stmt_depth == 1:
            # nested statements (TRACE's inner stmt) are not re-observed
            self._observe_stmt(s, r.elapsed_s, r)
        return r

    def _observe_stmt(self, s, elapsed_s: float, result=None) -> None:
        """Metrics + flight recorder + slow log + statement summary
        (reference: pkg/metrics collectors, slow_query.go,
        stmtsummary). The finished flight (obs/flight.py) carries the
        phase timeline and engine-watch join into both stores."""
        from tidb_tpu.obs.engine_watch import ENGINE_WATCH
        from tidb_tpu.obs.flight import FLIGHT
        from tidb_tpu.utils.metrics import (
            REGISTRY,
            SLOW_LOG,
            STMT_SUMMARY,
            sql_digest,
        )

        with FLIGHT.span("observe"):
            REGISTRY.counter(
                "tidbtpu_session_statements_total", "statements executed"
            ).inc()
            REGISTRY.histogram(
                "tidbtpu_session_query_duration_seconds", "statement latency"
            ).observe(elapsed_s)
            sql = getattr(s, "_source_sql", None) or type(s).__name__
            FLIGHT.note_engine(ENGINE_WATCH.current())
            if result is not None:
                FLIGHT.note_rows_sent(len(result.rows))
            flight = FLIGHT.finish(elapsed_s)
            digest = sql_digest(sql)  # computed ONCE for both stores
            STMT_SUMMARY.record(sql, elapsed_s, flight=flight, digest=digest)
            # Top SQL digest->text meta (obs/profiler.py): the sampler
            # attributes by 16-hex id; this makes top_sql rows readable.
            # Only while the profiler runs — the meta map must not grow
            # on an unprofiled fleet.
            from tidb_tpu.obs import profiler as _topsql

            if _topsql.TOPSQL.running():
                _topsql.note_statement_text(
                    _topsql.digest_of(digest), digest
                )
            # metric time-series tier: passive tick — with no background
            # sampler armed, history still accretes at statement cadence
            # (bounded by the sampler's passive interval; a no-op when the
            # tidb_tpu_tsdb_sample_interval_s thread owns the cadence)
            from tidb_tpu.obs.tsdb import SAMPLER

            try:
                SAMPLER.maybe_sample()
            except Exception:
                pass  # sampling must never fail the statement
            # slow log: threshold from the sysvar registry (no hardcoded
            # fallback — SYSVAR_DEFS owns the default), gated on the
            # slow_query_log on/off switch like the reference
            try:
                if not bool(self.vars.get("slow_query_log")):
                    return
                thresh_ms = int(self.vars.get("tidb_slow_log_threshold"))
            except Exception:
                return
            if elapsed_s * 1000.0 < thresh_ms:  # 0 = log everything
                return
            phases = ""
            plan_text = ""
            if flight is not None:
                # after the phases, the background ticks that ran
                # beside the statement (obs/flight.py FLIGHT.background)
                phases = " ".join(
                    [f"{p}={sec * 1e3:.3f}ms" for p, sec, _b, _r
                     in flight.timeline()]
                    + [f"beside:{name}={sec * 1e3:.3f}ms"
                       for name, sec in flight.background]
                )
                # tidb_record_plan_in_slow_log gates EVERY capture path,
                # including the instrumented lines an EXPLAIN ANALYZE
                # already stashed on the flight
                if self._record_plan_in_slow_log():
                    plan_text = flight.plan_text or self._capture_slow_plan(s)
                flight.plan_text = plan_text
                if plan_text:
                    from tidb_tpu.obs.flight import _c_slow_captures

                    _c_slow_captures().inc()
            SLOW_LOG.record(
                sql, elapsed_s,
                digest=digest,
                conn_id=self.conn_id,
                phases=phases,
                plan=plan_text,
                log_file=self._slow_log_file(),
            )

    def _record_plan_in_slow_log(self) -> bool:
        try:
            return bool(self.vars.get("tidb_record_plan_in_slow_log"))
        except Exception:
            return False

    def _slow_log_file(self):
        """The tidb_slow_query_file sink path — only when the sysvar
        was EXPLICITLY set (session or global): the reference always
        writes its default file, but an embedded engine spraying
        tidb-slow.log into every caller's CWD is a footgun, so the
        default path is advertised, not armed."""
        sv = self.vars
        if (
            "tidb_slow_query_file" in sv._session
            or "tidb_slow_query_file" in sv._globals
        ):
            return str(sv.get("tidb_slow_query_file")) or None
        return None

    def _capture_slow_plan(self, s) -> str:
        """Plan capture for an over-threshold statement (reference:
        tidb_record_plan_in_slow_log writes the physical plan into the
        slow-log entry; the caller gates on that switch). The captured
        plan is the statement's bound plan tree; when the statement
        rode the DCN scheduler, the distributed stage summary
        SNAPSHOTTED at routing time is appended (same renderer as
        EXPLAIN ANALYZE) so the entry reads like the distributed
        EXPLAIN ANALYZE."""
        if not isinstance(s, (ast.Select, ast.Union, ast.With, ast.SetOp)):
            return ""
        plan = self._last_plan
        if plan is None:
            return ""
        try:
            lines: List[str] = []
            _render_plan(
                plan, 0, lines, catalog=self.catalog,
                resolver=self._resolve_table_for_read,
            )
            if getattr(self, "_last_dcn_routed", False):
                lines.extend(
                    _dcn_runtime_lines(
                        getattr(self, "_last_dcn_snapshot", None)
                    )
                )
            return "\n".join(lines)
        except Exception:
            return ""  # plan capture must never fail the statement

    # ------------------------------------------------------------------
    def _run_show(self, s: ast.Show) -> Result:
        if s.what == "tables":
            # base tables and views interleave in one sorted listing,
            # like MySQL SHOW TABLES
            names = sorted(
                self.catalog.tables(self.db) + self.catalog.views(self.db)
            )
            return Result(["Tables"], [(t,) for t in names])
        if s.what == "databases":
            return Result(["Databases"], [(d,) for d in self.catalog.databases()])
        if s.what == "warnings":
            return Result(
                ["Level", "Code", "Message"], list(self._warnings)
            )
        if s.what == "open_tables":
            return Result(["Database", "Table", "In_use", "Name_locked"], [])
        if s.what == "status":
            # minimal MySQL-compatible status variables (reference:
            # infoschema session_status memtable); monitoring tools read
            # Uptime/Questions/Threads_connected
            import time as _time

            from tidb_tpu.utils.checkeval import sql_like_match
            from tidb_tpu.utils.metrics import REGISTRY as _REG

            pat = s.db or "%"
            uptime = int(_time.time() - getattr(self, "_start_ts", _time.time()))
            reg = getattr(self.catalog, "_session_registry", {})
            alive = sum(1 for cid in list(reg) if reg.get(cid) is not None)
            stats = [
                ("Uptime", uptime),
                ("Threads_connected", max(alive, 1)),
                ("Questions", getattr(self, "_stmt_count", 0)),
                ("Com_select", getattr(self, "_select_count", 0)),
                ("Ssl_cipher", ""),
            ]
            return Result(
                ["Variable_name", "Value"],
                [
                    (k, str(v)) for k, v in stats
                    if sql_like_match(k, pat, ci=True)
                ],
            )
        if s.what == "create_database":
            name = s.db
            if name.lower() not in [
                d.lower() for d in self.catalog.databases()
            ] and name.lower() != "information_schema":
                raise ValueError(f"unknown database {name}")
            return Result(
                ["Database", "Create Database"],
                [(name.lower(),
                  f"CREATE DATABASE `{name.lower()}` "
                  "/*!40100 DEFAULT CHARACTER SET utf8mb4 */")],
            )
        if s.what == "table_status":
            # MySQL SHOW TABLE STATUS (reference: infoschema tables
            # memtable feeding executor/show.go fetchShowTableStatus) —
            # connectors/BI tools read Name/Rows/Engine/Collation
            from tidb_tpu.utils.checkeval import sql_like_match

            pat = s.db or "%"
            cols = [
                "Name", "Engine", "Version", "Row_format", "Rows",
                "Avg_row_length", "Data_length", "Auto_increment",
                "Collation", "Comment",
            ]
            rows = []
            for tn in sorted(self.catalog.tables(self.db)):
                if not sql_like_match(tn, pat, ci=True):
                    continue
                t = self.catalog.table(self.db, tn)
                n = t.nrows
                width = sum(
                    8 if ty.kind != Kind.STRING else 32
                    for _c, ty in t.schema.columns
                )
                rows.append((
                    tn, "tidb_tpu", 10, "Fixed", n, width, n * width,
                    t.autoinc_next if t.autoinc_col else None,
                    "utf8mb4_bin", "",
                ))
            for vn in sorted(self.catalog.views(self.db)):
                if sql_like_match(vn, pat, ci=True):
                    rows.append((
                        vn, None, None, None, None, None, None, None,
                        None, "VIEW",
                    ))
            return Result(cols, rows)
        if s.what == "collation":
            # reference: SHOW COLLATION over the collate registry
            from tidb_tpu.utils import collate as _coll
            from tidb_tpu.utils.checkeval import sql_like_match

            pat = s.db or "%"
            rows = []
            for i, name in enumerate(sorted(_coll._REGISTRY), 1):
                if not sql_like_match(name, pat, ci=True):
                    continue
                rows.append((
                    name, name.split("_")[0], i,
                    "Yes" if name in _coll.CHARSET_DEFAULTS.values() else "",
                    "Yes", 1, "PAD SPACE" if name.endswith("_ci") else "NO PAD",
                ))
            return Result(
                ["Collation", "Charset", "Id", "Default", "Compiled",
                 "Sortlen", "Pad_attribute"], rows,
            )
        if s.what == "charset":
            from tidb_tpu.utils import collate as _coll
            from tidb_tpu.utils.checkeval import sql_like_match

            maxlen = {"utf8mb4": 4, "utf8": 3, "utf8mb3": 3,
                      "latin1": 1, "ascii": 1, "binary": 1}
            pat = s.db or "%"
            rows = [
                (cs, f"{cs} (utf8 internal)", dflt, maxlen.get(cs, 4))
                for cs, dflt in sorted(_coll.CHARSET_DEFAULTS.items())
                if sql_like_match(cs, pat, ci=True)
            ]
            return Result(
                ["Charset", "Description", "Default collation", "Maxlen"],
                rows,
            )
        if s.what == "engines":
            return Result(
                ["Engine", "Support", "Comment", "Transactions", "XA",
                 "Savepoints"],
                [("InnoDB", "DEFAULT",
                  "tidb_tpu columnar XLA engine (InnoDB-compatible surface)",
                  "YES", "NO", "YES")],
            )
        if s.what == "bindings":
            rows = [
                (e["for_sql"], e["using_sql"])
                for e in getattr(self.catalog, "bindings", {}).values()
            ]
            return Result(["Original_sql", "Bind_sql"], rows)
        if s.what == "grants":
            user = (s.db or self.user).lower()
            if user != self.user.lower():
                self._require_super()
            return Result(
                [f"Grants for {user}@%"],
                [(g,) for g in self.catalog.users.show_grants(user)],
            )
        if s.what == "columns":
            db, name = s.db.split(".", 1)
            db = db or self.db
            self._require_some_table_priv(db, name, "SHOW COLUMNS")
            t = self.catalog.table(db, name)
            pk = set(t.schema.primary_key or [])
            uni = {
                t.indexes[i][0] for i in t.unique_indexes if t.indexes.get(i)
            }
            mul = {
                cols[0] for i, cols in t.indexes.items()
                if cols and i not in t.unique_indexes
            }
            dflt = getattr(t, "defaults", None) or {}
            rows = [
                (
                    n,
                    repr(ty).lower(),
                    "NO" if n in pk else "YES",  # PKs are implicitly NOT NULL
                    "PRI" if n in pk else
                    "UNI" if n in uni else "MUL" if n in mul else "",
                    None if dflt.get(n) is None else str(dflt[n]),
                )
                for n, ty in t.schema.columns
            ]
            return Result(
                ["Field", "Type", "Null", "Key", "Default"], rows
            )
        if s.what == "processlist":
            rows = []
            reg = getattr(self.catalog, "_session_registry", {})
            for cid in sorted(reg):
                sess2 = reg.get(cid)  # weak dict: may vanish mid-walk
                if sess2 is None:
                    continue
                cur = sess2._current_stmt
                rows.append(
                    (
                        cid,
                        sess2.user,
                        sess2.db,
                        "Query" if cur is not None else "Sleep",
                        int(time.time() - cur[1]) if cur else 0,
                        str(cur[0])[:100] if cur else None,
                    )
                )
            return Result(
                ["Id", "User", "db", "Command", "Time", "Info"], rows
            )
        if s.what in ("create_table", "create_view"):
            db, name = s.db.split(".", 1)
            db = db or self.db
            self._require_some_table_priv(db, name, "SHOW CREATE")
            if s.what == "create_view":
                vdef = self.catalog.view_def(db, name)
                if vdef is None:
                    raise ValueError(f"unknown view {db}.{name}")
                sql_text, vcols = vdef
                collist = f" ({', '.join(vcols)})" if vcols else ""
                return Result(
                    ["View", "Create View"],
                    [(name.lower(),
                      f"CREATE VIEW `{name.lower()}`{collist} AS {sql_text}")],
                )
            from tidb_tpu.tools.dump import create_table_sql

            t = self.catalog.table(db, name)
            return Result(
                ["Table", "Create Table"],
                [(name.lower(), create_table_sql(t).rstrip(";"))],
            )
        if s.what == "index":
            db, name = s.db.split(".", 1)
            db = db or self.db
            self._require_some_table_priv(
                db, name, "SHOW INDEX", extra=("index",)
            )
            t = self.catalog.table(db, name)
            rows = []
            for i, cn in enumerate(t.schema.primary_key or [], 1):
                rows.append((name, "primary", i, cn, 0))
            for iname in sorted(t.indexes):
                nu = 0 if iname in t.unique_indexes else 1
                for i, cn in enumerate(t.indexes[iname], 1):
                    rows.append((name, iname, i, cn, nu))
            return Result(
                ["Table", "Key_name", "Seq_in_index", "Column_name", "Non_unique"],
                rows,
            )
        # variables
        from tidb_tpu.utils.checkeval import sql_like_match

        pat = s.db
        rows = []
        for name, val in self.vars.all().items():
            if pat is None or sql_like_match(name, pat, ci=True):
                if isinstance(val, bool):
                    val = "ON" if val else "OFF"
                rows.append((name, str(val)))
        return Result(["Variable_name", "Value"], rows)

    def _run_analyze_table(self, s: ast.AnalyzeTable) -> Result:
        from tidb_tpu.stats import analyze_table

        t = self.catalog.table(s.db or self.db, s.name)
        analyze_table(t)
        return Result([], [])

    def _run_load_data(self, s: ast.LoadData) -> Result:
        from tidb_tpu.utils.failpoint import inject

        inject("dml/load")
        t = self._resolve_table_for_write(s.db or self.db, s.table)
        from tidb_tpu.storage.loader import load_file

        constrained = bool(t.checks or t.fks)
        saved = list(t.blocks()) if constrained else None
        n = load_file(t, s.path, sep=s.sep)
        if constrained and n:
            # the bulk loader appends whole blocks — validate the loaded
            # region afterwards and roll the append back on violation
            names = t.schema.names
            loaded = []
            seen = 0
            for b in t.blocks():
                if seen + b.nrows <= sum(x.nrows for x in saved):
                    seen += b.nrows
                    continue
                dec = [b.columns[c].decode() for c in names]
                ok = [b.columns[c].valid for c in names]
                for i in range(b.nrows):
                    loaded.append(
                        [d[i] if o[i] else None for d, o in zip(dec, ok)]
                    )
            try:
                self._enforce_write_constraints(t, s.db or self.db, loaded)
            except Exception:
                t.replace_blocks(saved, modified_rows=n)
                raise
        if n and getattr(t, "generated", None):
            # the bulk loader appends raw blocks; re-evaluate generated
            # columns over the table (values in the file are ignored,
            # like a restore)
            self._recompute_generated(t)
        clear_scan_cache()
        return Result([], [], affected=n)

    def _eval_const_expr(self, e):
        """Host evaluation for tableless SELECTs (reference: expression
        folding in the projection over a one-row dual table)."""
        if isinstance(e, ast.Const):
            return e.value
        if isinstance(e, ast.SysVarRef):
            v = self.vars.get(e.name)
            return ("ON" if v else "OFF") if isinstance(v, bool) else v
        if isinstance(e, ast.SubqueryExpr) and e.modifier is None:
            from tidb_tpu.expression.expr import Literal

            lit = self._scalar_subquery(e.query)
            if lit.type is not None and lit.value is not None:
                from tidb_tpu.dtypes import (
                    Kind as _K, days_to_date, micros_to_datetime,
                    micros_to_time,
                )

                # present temporals for the tableless surface (the
                # BOUND path keeps the typed raw literal)
                if lit.type.kind == _K.DATE:
                    return days_to_date(int(lit.value))
                if lit.type.kind == _K.DATETIME:
                    return micros_to_datetime(int(lit.value))
                if lit.type.kind == _K.TIME:
                    return micros_to_time(int(lit.value))
            return lit.value
        if isinstance(e, ast.Call):
            known = {
                "add", "sub", "mul", "div", "neg", "not", "and", "or",
                "eq", "ne", "lt", "le", "gt", "ge",
                "coalesce", "isnull", "isnotnull", "cast",
                "concat", "concat_ws",
            }
            if e.op not in known:
                return self._device_const_eval(e)
            args = [self._eval_const_expr(a) for a in e.args]
            if any(a is None for a in args) and e.op not in (
                "isnull", "isnotnull", "coalesce", "concat_ws",
            ):
                return None
            import operator as op_

            table = {
                "add": op_.add, "sub": op_.sub, "mul": op_.mul,
                "eq": op_.eq, "ne": op_.ne, "lt": op_.lt, "le": op_.le,
                "gt": op_.gt, "ge": op_.ge,
            }
            _cmp_ops = ("eq", "ne", "lt", "le", "gt", "ge")
            if (
                e.op in ("add", "sub", "mul", "div")
                or (
                    e.op in _cmp_ops
                    and any(isinstance(a, str) for a in args)
                    and any(
                        isinstance(a, (int, float, bool)) for a in args
                    )
                )
            ) and any(isinstance(a, str) for a in args):
                # MySQL coerces a string's numeric prefix in arithmetic
                # and in comparisons against a numeric operand
                from tidb_tpu.expression.expr import _mysql_numeric_prefix

                args = [
                    _mysql_numeric_prefix(a) if isinstance(a, str) else a
                    for a in args
                ]
            if e.op in table:
                return table[e.op](args[0], args[1])
            if e.op == "div":
                return None if args[1] in (0, None) else args[0] / args[1]
            if e.op == "neg":
                return -args[0]
            if e.op == "not":
                return not args[0]
            if e.op in ("and",):
                return bool(args[0]) and bool(args[1])
            if e.op in ("or",):
                return bool(args[0]) or bool(args[1])
            if e.op in ("concat", "concat_ws"):
                def _cs(v):
                    if isinstance(v, bool):
                        return "1" if v else "0"
                    import math as _mf

                    if isinstance(v, float) and _mf.isfinite(v) \
                            and v == int(v):
                        return str(int(v))
                    return str(v)

                if e.op == "concat":
                    return "".join(_cs(a) for a in args)
                sep = args[0]
                if sep is None:
                    return None
                return _cs(sep).join(
                    _cs(a) for a in args[1:] if a is not None
                )
            if e.op == "coalesce":
                return next((a for a in args if a is not None), None)
            if e.op == "isnull":
                return args[0] is None
            if e.op == "isnotnull":
                return args[0] is not None
            if e.op == "cast":
                v = args[0]
                tgt = getattr(e, "cast_type", None)
                if tgt is not None and isinstance(v, str):
                    from tidb_tpu.dtypes import Kind as _K
                    from tidb_tpu.expression.expr import (
                        _mysql_numeric_prefix,
                    )

                    if tgt.kind == _K.INT:
                        f = float(_mysql_numeric_prefix(v))
                        # MySQL rounds half away from zero, string or not
                        import math as _m0

                        return int(
                            _m0.floor(f + 0.5) if f >= 0
                            else _m0.ceil(f - 0.5)
                        )
                    if tgt.kind == _K.FLOAT:
                        return float(_mysql_numeric_prefix(v))
                if tgt is not None and isinstance(v, float) \
                        and not isinstance(v, bool):
                    from tidb_tpu.dtypes import Kind as _K2

                    if tgt.kind == _K2.INT:
                        # MySQL CAST(12.7 AS SIGNED) rounds half away
                        # from zero, not truncates
                        import math as _m

                        return int(
                            _m.floor(v + 0.5) if v >= 0
                            else _m.ceil(v - 0.5)
                        )
                return v
        return self._device_const_eval(e)

    def _device_const_eval(self, e):
        """Evaluate a column-free expression through the engine's own
        kernels on a one-row batch (the dual-table analog; reference:
        TableDual + expression folding)."""
        import jax.numpy as jnp

        from tidb_tpu.chunk import Batch
        from tidb_tpu.dtypes import Kind, days_to_date
        from tidb_tpu.expression.kernels import compile_expr, string_expr
        from tidb_tpu.planner.logical import ExprBinder, Schema

        bound = ExprBinder(Schema([]), self._subq_executor_for_binding()).bind(e)
        b = Batch({}, jnp.ones(1, dtype=bool))
        if bound.type is not None and bound.type.kind == Kind.STRING:
            fn, d = string_expr(bound, {})
            c = fn(b)
            if not bool(c.valid[0]) or not len(d):
                return None
            return str(d[int(c.data[0])])
        c = compile_expr(bound, {})(b)
        if not bool(c.valid[0]):
            return None
        v = c.data[0].item()
        t = bound.type
        if t is None:
            return v
        if t.kind == Kind.DECIMAL:
            return v / 10**t.scale
        if t.kind == Kind.DATE:
            return days_to_date(int(v))
        if t.kind == Kind.DATETIME:
            from tidb_tpu.dtypes import micros_to_datetime

            return micros_to_datetime(int(v))
        if t.kind == Kind.TIME:
            from tidb_tpu.dtypes import micros_to_time

            return micros_to_time(int(v))
        if t.kind == Kind.BOOL:
            return bool(v)
        return v

    def _subq_executor_for_binding(self):
        import dataclasses as _dc

        from tidb_tpu.parser import ast as _ast

        def run(e):
            if isinstance(e, _ast.SubqueryExpr) and e.modifier is None:
                return self._scalar_subquery(e.query)
            if isinstance(e, _ast.SubqueryExpr) and e.modifier in (
                "exists", "not exists",
            ):
                # uncorrelated EXISTS in a scalar (tableless) position:
                # COUNT over a derived table preserves HAVING/LIMIT
                from tidb_tpu.dtypes import BOOL as _BOOL
                from tidb_tpu.expression.expr import Literal as _Lit

                cnt_q = _ast.Select(
                    items=[
                        _ast.SelectItem(_ast.AggCall("count", None), alias="_c")
                    ],
                    from_=_ast.SubqueryRef(
                        _dc.replace(e.query, order_by=[]), "_ex"
                    ),
                )
                n = self._scalar_subquery(cnt_q).value
                hit = (n or 0) > 0
                return _Lit(
                    type=_BOOL,
                    value=hit if e.modifier == "exists" else not hit,
                )
            raise ValueError("IN/EXISTS subquery not supported here")

        return run

    def _run_tableless(self, s: ast.Select) -> Result:
        names = []
        vals = []
        for i, it in enumerate(s.items):
            from tidb_tpu.planner.logical import _display_name

            names.append(it.alias or _display_name(it.expr))
            vals.append(self._eval_const_expr(it.expr))
        rows = [tuple(vals)]
        if s.where is not None and not self._eval_const_expr(s.where):
            rows = []
        if s.limit is not None:
            rows = rows[s.offset or 0 : (s.offset or 0) + s.limit]
        return Result(names, rows)

    # ------------------------------------------------------------------
    # Recursive CTEs: iterative materialization (reference: CTEExec's
    # seed/recursive iteration, pkg/executor/cte.go:70). Each recursive
    # CTE is evaluated to a fixpoint into a scratch catalog table; the
    # body then plans against a plain SELECT over that table.
    @property
    def _CTE_MAX_RECURSION(self) -> int:
        # the real cte_max_recursion_depth sysvar (mysql default 1000)
        try:
            return int(self.vars.get("cte_max_recursion_depth") or 1000)
        except Exception:
            return 1000

    def _run_recursive_with(self, s, outer_ctes=None) -> Result:
        merged = dict(outer_ctes or {})
        scratch: List[Tuple[str, str]] = []
        try:
            for name, q in s.ctes:
                if isinstance(q, ast.Union) and any(
                    _refs_table(sel, name) for sel in q.selects
                ):
                    merged[name] = self._materialize_recursive(
                        name, q, merged, scratch
                    )
                else:
                    merged[name] = q
            return self._run_select(s.body, merged)
        finally:
            for db, t in scratch:
                try:
                    self.catalog.drop_table(db, t, if_exists=True)
                except Exception:
                    pass

    def _materialize_recursive(self, name, q, scope, scratch):
        from tidb_tpu.dtypes import INT64
        from tidb_tpu.storage.table import TableSchema

        seeds = [sel for sel in q.selects if not _refs_table(sel, name)]
        steps = [sel for sel in q.selects if _refs_table(sel, name)]
        if not seeds:
            raise ValueError(f"recursive CTE {name!r} has no seed SELECT")
        seed_ast = seeds[0] if len(seeds) == 1 else ast.Union(seeds, q.all)
        r = self._run_select(seed_ast, dict(scope))
        col_names = list(r.columns)
        types = [
            t if (t is not None and t.kind != Kind.NULL) else INT64
            for t in (r.types or [INT64] * len(col_names))
        ]
        rows = list(r.rows)
        if not q.all:
            seen = set()
            uniq = []
            for row in rows:
                if row not in seen:
                    seen.add(row)
                    uniq.append(row)
            rows = uniq
        else:
            seen = None

        db = "_cte_scratch"
        self.catalog.create_database(db, if_not_exists=True)
        # process-unique scratch names: the scratch database is shared
        # across sessions of one catalog, so a per-session counter would
        # collide under concurrent server connections
        tname = f"{name}_{next(_cte_scratch_seq)}"
        schema = TableSchema(list(zip(col_names, types)))
        tbl = self.catalog.create_table(db, tname, schema)
        scratch.append((db, tname))
        if rows:
            tbl.append_rows(rows)

        # the working (delta) table feeds each recursive step; ONE table
        # reused across iterations (content replacement) so the plan/jit
        # caches hit — a fresh table per iteration would recompile the
        # step program every round
        wname = f"{tname}_w"
        scratch.append((db, wname))
        wt = self.catalog.create_table(db, wname, schema)
        working = rows
        ref_ast = ast.Select(
            items=[
                ast.SelectItem(ast.Name(None, c), alias=c) for c in col_names
            ],
            from_=ast.TableRef(db, wname),
        )
        iters = 0
        while working:
            iters += 1
            if iters > self._CTE_MAX_RECURSION:
                raise ValueError(
                    f"recursive CTE {name!r} exceeded "
                    f"{self._CTE_MAX_RECURSION} iterations"
                )
            from tidb_tpu.utils.failpoint import inject

            inject("cte/iterate")
            wt.clear_rows()
            wt.append_rows(working)
            scope2 = dict(scope)
            scope2[name] = ref_ast
            new_rows = []
            for st in steps:
                r2 = self._run_select(st, scope2)
                new_rows.extend(r2.rows)
            if seen is not None:
                fresh = []
                for row in new_rows:
                    if row not in seen:
                        seen.add(row)
                        fresh.append(row)
                new_rows = fresh
            if new_rows:
                tbl.append_rows(new_rows)
            working = new_rows

        return ast.Select(
            items=[
                ast.SelectItem(ast.Name(None, c), alias=c) for c in col_names
            ],
            from_=ast.TableRef(db, tname),
        )

    # ------------------------------------------------------------------
    def _scalar_subquery(self, q: ast.Select, ctes=None):
        """Execute an uncorrelated scalar subquery; returns a Literal.
        ``ctes`` carries the enclosing WITH scope, if any."""
        from tidb_tpu.expression.expr import Literal

        r = self._run_select(q, ctes)
        if len(r.columns) != 1:
            raise ValueError("scalar subquery must return one column")
        if len(r.rows) == 0:
            return Literal(value=None)
        if len(r.rows) > 1:
            raise ValueError("scalar subquery returned more than one row")
        v = r.rows[0][0]
        t = (r.types[0] if getattr(r, "types", None) else None)
        if t is not None and v is not None:
            from tidb_tpu.dtypes import (
                Kind as _K, date_to_days, datetime_to_micros,
                time_to_micros,
            )

            # temporal results present as strings; re-encode to the raw
            # typed form so the literal composes like a temporal column
            # (a string literal's numeric prefix would turn a datetime
            # into its year under arithmetic)
            if t.kind == _K.DATE and isinstance(v, str):
                return Literal(type=t, value=int(date_to_days(v)))
            if t.kind == _K.DATETIME and isinstance(v, str):
                return Literal(type=t, value=int(datetime_to_micros(v)))
            if t.kind == _K.TIME and isinstance(v, str):
                return Literal(type=t, value=int(time_to_micros(v)))
        return Literal(value=v)

    def _apply_binding(self, s):
        """SQL plan binding: a CREATE BINDING whose normalized digest
        matches this statement injects its hints (reference:
        pkg/bindinfo digest-matched hint sets)."""
        src = getattr(s, "_source_sql", None)
        bindings = getattr(self.catalog, "bindings", None)
        if not src or not bindings or not isinstance(s, ast.Select):
            return s
        from tidb_tpu.utils.metrics import sql_digest

        entry = bindings.get(sql_digest(src))
        if entry is None:
            return s
        s.hints = tuple(entry["hints"]) or s.hints
        from tidb_tpu.utils.metrics import REGISTRY

        REGISTRY.counter(
            "tidbtpu_session_binding_hits_total", "statements matched to bindings"
        ).inc()
        return s

    def _metrics_scan_hint(self, s):
        """Time/label predicate pushdown for metrics_schema scans
        (reference: metrics_schema tables push their time range into
        the Prometheus query — pkg/infoschema/metrics_schema.go). For
        the single-table shape, WHERE conjuncts of the form
        ``time >= / > / <= / < <num>`` and ``<label> = '<lit>'``
        become a tsdb scan hint so the virtual table materializes only
        the covered slice of each retention ring; every predicate is
        STILL evaluated by the executor (the hint is a superset scan,
        never the filter itself), so unpushable conjuncts stay exact.
        Returns (metric, t_lo, t_hi, labels) or None."""
        if not isinstance(s, ast.Select):
            return None
        f = s.from_
        if not isinstance(f, ast.TableRef):
            return None
        if (f.db or self.db).lower() != "metrics_schema":
            return None
        # the hint is thread-wide for the statement's whole build +
        # execute window: if ANY other reference to a metrics_schema
        # table exists (scalar subquery, IN-subquery), an unbounded
        # inner scan of the SAME family would silently inherit the
        # outer bounds — push down only on the strictly single-
        # reference shape
        refs = [
            r for r in ast.iter_table_refs(s)
            if (r.db or self.db).lower() == "metrics_schema"
        ]
        if len(refs) != 1:
            return None
        metric = f.name.lower()
        t_lo = t_hi = None
        labels = {}

        def conjuncts(e):
            if isinstance(e, ast.Call) and e.op == "and":
                for a in e.args:
                    yield from conjuncts(a)
            elif e is not None:
                yield e

        for c in conjuncts(s.where):
            if not (
                isinstance(c, ast.Call) and len(c.args) == 2
                and c.op in ("ge", "gt", "le", "lt", "eq")
            ):
                continue
            lhs, rhs = c.args
            op = c.op
            if isinstance(rhs, ast.Name) and isinstance(lhs, ast.Const):
                # normalize `lit op col` to `col op' lit`
                lhs, rhs = rhs, lhs
                op = {"ge": "le", "gt": "lt", "le": "ge",
                      "lt": "gt", "eq": "eq"}[op]
            if not (
                isinstance(lhs, ast.Name) and isinstance(rhs, ast.Const)
                and rhs.param_index is None
            ):
                continue
            col = lhs.column.lower()
            v = rhs.value
            if col == "time" and isinstance(v, (int, float)):
                if op in ("ge", "gt"):
                    t_lo = float(v) if t_lo is None else max(
                        t_lo, float(v)
                    )
                elif op in ("le", "lt"):
                    t_hi = float(v) if t_hi is None else min(
                        t_hi, float(v)
                    )
                elif op == "eq":
                    t_lo = t_hi = float(v)
            elif (
                op == "eq" and isinstance(v, str)
                and col not in ("time", "instance", "value", "res")
            ):
                labels[col] = v
        if t_lo is None and t_hi is None and not labels:
            return None
        return metric, t_lo, t_hi, labels

    def _run_select(self, s, ctes=None) -> Result:
        if isinstance(s, ast.With) and s.recursive:
            return self._run_recursive_with(s, ctes)
        if isinstance(s, ast.Select) and s.from_ is None:
            return self._run_tableless(s)
        s = self._apply_binding(s)
        # metrics_schema pushdown: park the scan hint on this thread
        # around planning + execution (both resolve the virtual table)
        mhint = self._metrics_scan_hint(s)
        if mhint is not None:
            from tidb_tpu.obs import tsdb as _tsdb

            _tsdb.set_scan_hint(*mhint)
        # per-statement engine hints (session-scoped, reset after)
        old_stream = self.executor.stream_rows
        for name, args in getattr(s, "hints", ()) or ():
            if name == "stream_rows" and args:
                try:
                    self.executor.stream_rows = int(args[0]) or None
                except ValueError:
                    pass
            elif name == "max_execution_time" and args:
                try:
                    import time as _t

                    self.killer.deadline = _t.monotonic() + int(args[0]) / 1000
                except ValueError:
                    pass
        from tidb_tpu.obs.flight import FLIGHT

        try:
            # spans mirror the reference's (session.ExecuteStmt ->
            # Compiler.Compile -> distsql.Select, pkg/util/tracing/util.go:21)
            with FLIGHT.span("plan"):
                plan = build_query(s, self.catalog, self.db, self._scalar_subquery, ctes)
            self._last_plan = plan  # prepared-statement plan capture
            # _source_sql is set only for single-statement texts: a
            # batch's statements would otherwise share one fallback
            # digest and cross-contaminate the cardinality feedback
            # store (no digest = no feedback, routing unaffected)
            routed = self._try_dcn_select(
                plan, sql=getattr(s, "_source_sql", None)
            )
            if routed is not None:
                return routed
            with FLIGHT.span("execute"):
                hs = self._try_host_sorted(plan)
                if hs is not None:
                    return hs
                batch, dicts = self.executor.run(plan)
            with FLIGHT.span("final-merge"):
                rows = materialize_rows(batch, list(plan.schema), dicts)
            names = [c.name for c in plan.schema]
            return Result(names, rows, types=[c.type for c in plan.schema])
        finally:
            self.executor.stream_rows = old_stream
            if mhint is not None:
                from tidb_tpu.obs import tsdb as _tsdb

                _tsdb.clear_scan_hint()

    def _note_delta_hwm(self) -> None:
        """Record this session's high-water delta seq after a DML
        statement — the seq read-your-writes routed reads block on
        (storage/delta.py prepare_read)."""
        ds = getattr(self.catalog, "delta_store", None)
        if ds is not None:
            self._delta_hwm = ds.high_seq()

    def _delta_read_seq(self, sched):
        """Resolve a routed read's delta snapshot seq under the
        session's tidb_tpu_read_freshness mode, or None when the delta
        tier is not in play. read_your_writes ships + blocks until the
        fleet acked this session's high-water seq — a timeout raises
        (never a silent stale read); bounded returns the already-acked
        floor with zero wait."""
        ds = getattr(self.catalog, "delta_store", None)
        repl = getattr(sched, "delta", None)
        if ds is None or repl is None:
            return None
        mode = str(self.vars.get("tidb_tpu_read_freshness"))
        return repl.prepare_read(
            mode,
            hwm=getattr(self, "_delta_hwm", 0),
            kill_check=self.killer.check,
            timeout_s=float(
                self.vars.get("tidb_tpu_delta_sync_timeout_s")
            ),
        )

    #: schemas whose virtual tables reflect THIS process's state — a
    #: plan scanning them must never ship to the worker fleet
    _LOCAL_ONLY_DBS = frozenset(
        {"information_schema", "mysql", "performance_schema",
         "metrics_schema"}
    )

    def _try_dcn_select(self, plan, sql=None):
        """Route a SELECT through the attached DCN fragment scheduler
        (PR 6: attached schedulers execute fragmentable/shuffleable
        statements across the worker fleet, not just EXPLAIN ANALYZE).
        Returns a Result, or None to run locally: unattached, inside a
        transaction or stale read (both need this session's snapshot),
        system-schema scans, and plans the fragmenter declares
        single-host (whole-plan dispatch to a worker would read the
        WORKER's catalog state for shapes the local engine serves
        fine). ``sql`` is the raw statement text; its AQE-feedback
        digest is computed only after the cheap bail-outs — an
        unattached (single-node) deployment must not pay a tokenizer
        pass per SELECT for a route that can never happen."""
        sched = getattr(self, "dcn_scheduler", None)
        self._last_dcn_routed = False
        if sched is None:
            return None
        if self._txn is not None or self._stmt_as_of:
            return None
        from tidb_tpu.planner import logical as L

        def scan_dbs(p, out):
            if isinstance(p, L.Scan):
                out.add(str(p.db).lower())
            for attr in ("child", "left", "right"):
                c = getattr(p, attr, None)
                if c is not None:
                    scan_dbs(c, out)
            for c in getattr(p, "children", []) or []:
                scan_dbs(c, out)
            return out

        dbs = scan_dbs(plan, set())
        # "_"-prefixed dbs are coordinator-internal scratch space
        # (recursive-CTE materialization lands in _cte_scratch) —
        # workers have never heard of them
        if any(db.startswith("_") for db in dbs) or (
            dbs & self._LOCAL_ONLY_DBS
        ):
            return None
        from tidb_tpu.planner.fragmenter import Unschedulable
        from tidb_tpu.utils.metrics import sql_digest as _sqld

        digest = _sqld(sql) if sql else None
        try:
            kind, cut = sched._choose_cut(plan, digest=digest)
        except Unschedulable:
            return None
        if kind == "single":
            return None
        from tidb_tpu.utils.memtrack import QuotaExceeded
        from tidb_tpu.utils.sqlkiller import QueryKilled

        # -- serving-tier admission (parallel/serving.py): gate query
        # START against the fleet device-memory budget, priority/
        # fairness-queued. The plan fingerprint keys the working-set
        # estimate (the engine-watch high-water the same shape reached
        # last time). AdmissionRejected propagates — an overloaded
        # fleet sheds load as a visible MySQL error (never a local
        # fallback), and _execute_stmt still records the summary row.
        ticket = None
        adm = getattr(sched, "admission", None)
        if adm is not None:
            from tidb_tpu.planner.physical import plan_fingerprint

            ticket = adm.admit(
                plan_fingerprint(plan),
                priority=getattr(self, "_stmt_priority", "medium"),
                kill_check=self.killer.check,
            )
            # queue time is a throttle wait, not engine work: exclude
            # it from the boundary RU debit (billing it would drive
            # the group's bucket negative on pure waiting)
            self._bill_exclude_s = getattr(
                self, "_bill_exclude_s", 0.0
            ) + getattr(ticket, "waited_s", 0.0)
        # -- resource-group RU gate at the DISPATCH site: the statement
        # boundary already gated once, but under concurrent sessions
        # the bucket may have been overdrawn while this query sat in
        # the admission queue — re-acquire so CREATE RESOURCE GROUP
        # limits govern what actually reaches the fleet. The wait
        # charges to queue-wait like admission (it IS admission, by
        # RU instead of bytes).
        from tidb_tpu.obs.flight import FLIGHT as _FLIGHT

        rg = getattr(self.catalog, "resource_groups", None)
        throttled = rg is not None and self.resource_group != "default"
        dispatched = False
        try:
            if throttled:
                waited = rg.acquire(
                    self.resource_group, kill_check=self.killer.check
                )
                if waited > 0:
                    _FLIGHT.note_phase("queue-wait", waited)
                    # same exclusion as the admission wait above
                    self._bill_exclude_s = getattr(
                        self, "_bill_exclude_s", 0.0
                    ) + waited
            # HTAP freshness: resolve the delta snapshot seq BEFORE
            # the dispatch try — a read-your-writes ack timeout is a
            # statement error the user sees, never a local fallback
            # masquerading as fleet execution
            delta_seq = self._delta_read_seq(sched)
            try:
                # fleet-wide cancellation: the session killer (KILL
                # QUERY + max_execution_time deadline) is polled while
                # dispatches are in flight and broadcast as
                # cancel_query to the workers on the first raise; the
                # deadline additionally PROPAGATES in each dispatch so
                # workers self-cancel even if the coordinator wedges
                cols, rows = sched.execute_plan(
                    plan, cut_hint=(kind, cut),
                    kill_check=self.killer.check,
                    deadline=self.killer.deadline or None,
                    delta_seq=delta_seq, digest=digest,
                )
                dispatched = True
            except (QueryKilled, QuotaExceeded):
                # deliberate aborts (KILL QUERY / max_execution_time /
                # memory quota) raised during the coordinator-local final
                # stage must surface immediately — re-running the whole
                # statement locally would delay the abort by a full second
                # execution and miscount it as a dispatch failure
                raise
            except Exception:
                # the fleet could not serve it (all workers lost, or a
                # coordinator-only table the workers never loaded): the
                # local engine still can. Data-currency across the fleet
                # remains the attach contract (see attach_dcn_scheduler);
                # this fallback turns hard routing failures into local
                # execution, not silent wrongness.
                from tidb_tpu.utils.metrics import REGISTRY

                REGISTRY.counter(
                    "tidbtpu_session_dcn_route_fallbacks_total",
                    "routed SELECTs that fell back to local execution "
                    "after a fleet dispatch failure",
                ).inc()
                return None
        finally:
            if ticket is not None:
                # feed the OBSERVED engine-watch high-water back as
                # the next admission estimate for this plan shape —
                # but only from a COMPLETED run: a killed or failed
                # dispatch's peak is a truncated partial that would
                # overwrite a learned estimate and let the next N
                # admissions of this shape overcommit the budget
                from tidb_tpu.obs.engine_watch import ENGINE_WATCH

                observed = None
                if dispatched:
                    # fleet-eyed estimate: workers report their OWN
                    # per-fragment device-mem peaks in the fenced
                    # replies (dcn._worker_mem_peak) — a worker-heavier
                    # plan (pre-aggregation below the exchange) must
                    # not learn from the coordinator's smaller
                    # final-stage working set (ROADMAP PR 8 item)
                    mine_fn = getattr(sched, "last_query_mine", None)
                    lqm = (mine_fn() if callable(mine_fn) else None) or {}
                    observed = max(
                        ENGINE_WATCH.current_peak_bytes(),
                        int(lqm.get("worker_mem_peak", 0) or 0),
                    )
                ticket.release(observed_bytes=observed)
        self._last_dcn_routed = True
        # snapshot the runtime stats NOW, from THIS THREAD's query
        # record (last_query is scheduler-global: under concurrent
        # sessions another query may already have overwritten it by
        # the time execute_plan returns). Rendering to text stays lazy
        # — _capture_slow_plan runs only for over-threshold statements.
        mine = getattr(sched, "last_query_mine", None)
        lq = (mine() if callable(mine) else None) or getattr(
            sched, "last_query", None
        ) or {}
        snap = {}
        if lq.get("shuffle"):
            snap["shuffle"] = dict(lq["shuffle"])
        if lq.get("shuffle_stages"):
            snap["shuffle_stages"] = [
                dict(s) for s in lq["shuffle_stages"]
            ]
        if lq.get("fragments"):
            snap["fragments"] = [
                {k: v for k, v in f.items() if k != "spans"}
                for f in lq["fragments"]
            ]
            deltas = [
                f["delta"] for f in lq["fragments"] if f.get("delta")
            ]
            if deltas:
                # worker-side delta-merge stats: the slow-log capture's
                # DeltaMerge row + detail.delta in serve-load
                snap["delta"] = {
                    "depth": max(
                        int(d.get("depth", 0)) for d in deltas
                    ),
                    "ins_rows": sum(
                        int(d.get("ins_rows", 0)) for d in deltas
                    ),
                    "del_keys": max(
                        int(d.get("del_keys", 0)) for d in deltas
                    ),
                }
        self._last_dcn_snapshot = snap
        if throttled:
            # RU debit for the FLEET-specific cost the statement
            # boundary cannot see: the fragment/partition result bytes
            # that crossed the DCN back to this coordinator (1 RU/KiB,
            # utils/resgroup.py). Engine-time RU still bills once at
            # the statement boundary (_execute_stmt's debit) — this
            # site adds bytes only (count_query=False keeps the
            # group's query counter at one per statement), so nothing
            # double-bills.
            nbytes = sum(
                int(f.get("bytes", 0)) for f in snap.get("fragments", ())
            )
            try:
                rg.debit(
                    self.resource_group, 0.0, result_bytes=nbytes,
                    count_query=False,
                )
            except Exception:
                pass  # billing must never fail the statement
        # AQE cardinality accuracy (PR 15): planner estimate vs the
        # observed output rows — statements_summary exposes the
        # per-digest divergence, the misestimate counter feeds the
        # cardinality-drift inspection rule, and the feedback store
        # records the pair for history-seeded planning
        try:
            est = plan.__dict__.get("est")
            if est is None:
                from tidb_tpu.planner.cardinality import est_rows

                est = est_rows(plan, self.catalog)
            _FLIGHT.note_cardinality(float(est), float(len(rows)))
            r = max(len(rows), 1.0) / max(float(est), 1.0)
            div = max(r, 1.0 / r)
            if div >= float(getattr(sched, "aqe_replan_ratio", 4.0)):
                from tidb_tpu.parallel.aqe import _c_misestimates

                _c_misestimates().inc()
            if digest:
                from tidb_tpu.planner.cardinality import CARD_FEEDBACK

                CARD_FEEDBACK.record(
                    digest, est=float(est), act=float(len(rows))
                )
        except Exception:
            pass  # accounting must never fail the statement
        schema_cols = list(plan.schema)
        types = (
            [c.type for c in schema_cols]
            if len(schema_cols) == len(cols) else None
        )
        return Result(cols, rows, types=types)

    def _try_host_sorted(self, plan):
        """Out-of-HBM full ORDER BY (planner/streamed.try_streamed_sort):
        the device pipeline stages sorted-run columns to host RAM and the
        final row order materializes host-side, so the result never needs
        to fit device memory. Returns a Result or None."""
        from tidb_tpu.chunk import HostColumn
        from tidb_tpu.planner.physical import StaleWidthsError
        from tidb_tpu.planner.streamed import try_streamed_sort

        hs = None
        try:
            hs = try_streamed_sort(self.executor, plan)
        except StaleWidthsError:
            try:
                hs = try_streamed_sort(self.executor, plan, conservative=True)
            except StaleWidthsError:
                hs = None
        if hs is None:
            return None
        names_int, cols, _n, sdicts = hs
        from tidb_tpu.chunk import present_temporals

        types = {c.internal: c.type for c in plan.schema}
        decoded = {
            n: present_temporals(HostColumn(
                types[n], cols[n][0], cols[n][1], sdicts.get(n)
            ))
            for n in names_int
        }
        rows = [
            tuple(decoded[n][r] for n in names_int) for r in range(_n)
        ]
        names = [c.name for c in plan.schema]
        return Result(names, rows, types=[c.type for c in plan.schema])

    def _check_exprs_for(self, t):
        exprs = getattr(t, "_check_exprs", None)
        if exprs is None or len(exprs) != len(t.checks):
            from tidb_tpu.parser.sqlparse import parse_expr

            exprs = t._check_exprs = [
                (nm, parse_expr(txt)) for nm, txt in t.checks
            ]
        return exprs

    # -- generated columns ---------------------------------------------
    # Reference: pkg/ddl/generated_column.go:125 (findDependedColumnNames
    # + dependency validation) and pkg/table/tables.go stored-generated
    # evaluation on the write path. Both VIRTUAL and STORED materialize
    # on write here — generated expressions are required deterministic,
    # so eager evaluation is observationally identical; the flag is kept
    # for SHOW CREATE / information_schema fidelity.

    def _column_values(self, db: str, name: str, col: str) -> set:
        """All non-NULL values of a column at this session's read
        snapshot (host decode — constraint batches are small)."""
        t, version = self._resolve_table_for_read(db, name)
        out = set()
        for b in t.blocks(version):
            c = b.columns[col]
            dec = c.decode()
            for ok, v in zip(c.valid, dec):
                if ok:
                    out.add(v)
        return out

    def _enforce_write_constraints(self, t, db: str, rows) -> None:
        """CHECK + child-side FOREIGN KEY validation over fully-formed
        Python rows, BEFORE they are encoded/appended (reference:
        pkg/table/tables.go CheckRowConstraint + FK existence checks in
        the executor's write path). A CHECK passes on TRUE/UNKNOWN and
        fails only on FALSE, per SQL."""
        names = t.schema.names
        if t.checks:
            from tidb_tpu.utils.checkeval import _truth, eval_check

            for nm, ex in self._check_exprs_for(t):
                for r in rows:
                    if _truth(eval_check(ex, dict(zip(names, r)))) is False:
                        raise ValueError(
                            f"CHECK constraint {nm!r} violated"
                        )
        for nm, col, rdb, rtbl, rcol in t.fks:
            i = names.index(col)
            vals = {r[i] for r in rows if r[i] is not None}
            if not vals:
                continue
            parent = self._column_values(rdb, rtbl, rcol)
            if rdb == db.lower() and rtbl == t.name:
                # self-referential FK: keys arriving in this same batch
                # are valid targets (MySQL checks post-statement state)
                j = names.index(rcol)
                parent |= {r[j] for r in rows if r[j] is not None}
            missing = vals - parent
            if missing:
                raise ValueError(
                    f"FOREIGN KEY {nm!r} violated: "
                    f"{sorted(missing)[:3]!r} not in {rdb}.{rtbl}.{rcol}"
                )

    def _fk_children(self, db: str, name: str):
        """[(child_db, child_table, fk_name, fk_col, ref_col)] of every
        FK in the catalog referencing db.name. The reverse map is cached
        on the catalog per schema version — point DML must not pay an
        all-tables walk just to learn there are no FKs."""
        cat = self.catalog
        cache = getattr(cat, "_fk_child_cache", None)
        if cache is None or cache[0] != cat.schema_version:
            rev: dict = {}
            for d in cat.databases():
                for tn in cat.tables(d):
                    t2 = cat.table(d, tn)
                    acts = getattr(t2, "fk_actions", {})
                    for nm, col, rdb, rtbl, rcol in getattr(t2, "fks", ()):
                        rev.setdefault((rdb, rtbl), []).append(
                            (d, tn, nm, col, rcol,
                             acts.get(nm.lower(), "restrict"))
                        )
            cache = cat._fk_child_cache = (cat.schema_version, rev)
        return cache[1].get((db.lower(), name.lower()), [])

    def _fk_undo_snapshot(self, undo, t) -> None:
        """Record a table's pre-statement state once per statement so a
        failure ANYWHERE in a referential-action chain restores every
        touched table (MySQL: the whole statement rolls back)."""
        if undo is not None and all(u[0] is not t for u in undo):
            undo.append((t, list(t.blocks()), dict(t.dictionaries)))

    @staticmethod
    def _fk_undo_restore(undo) -> None:
        for t, blocks, dicts in undo:
            t.replace_blocks(blocks, modified_rows=0)
            t.dictionaries = dicts
        clear_scan_cache()

    def _enforce_parent_constraints(
        self, db: str, name: str, remaining: dict, actions: bool = False,
        _depth: int = 0, undo=None, update_acts: Optional[dict] = None,
    ) -> None:
        """FK enforcement for deletes/updates on an FK parent against
        the post-statement values (``remaining``: ref_col -> value set).
        actions=True (DELETE/TRUNCATE): each child FK's declared
        ON DELETE action applies — RESTRICT raises, CASCADE deletes the
        referencing child rows (recursively), SET NULL nulls the child
        key column. update_acts (UPDATE paths): map of
        (child_db, child_table, fk_name) -> the FK's ON UPDATE action;
        RESTRICT raises, SET NULL nulls, CASCADE is skipped here — the
        caller rewrites child keys from its old->new pairing. Neither
        set: RESTRICT always. Reference: pkg/executor/foreign_key.go
        (FKCascadeExec / FKCheckExec)."""
        if _depth > 10:
            raise ValueError("FOREIGN KEY cascade recursion too deep")
        for cdb, ctn, nm, col, rcol, odel in self._fk_children(db, name):
            if rcol not in remaining:
                continue
            if update_acts is not None:
                act = update_acts.get((cdb, ctn, nm), "restrict")
                if act in ("cascade", "set_null"):
                    # the caller applies both AFTER installing the new
                    # parent image: mutating children pre-install would
                    # be lost for self-FKs (the post-image rows were
                    # computed first) and would leak on a later RESTRICT
                    continue
            elif actions:
                act = odel
            else:
                act = "restrict"
            child_vals = self._column_values(cdb, ctn, col)
            if cdb == db.lower() and ctn == name.lower():
                # self-FK: the child side shrinks with the parent — the
                # caller's remaining set for the fk column is the truth
                child_vals = remaining.get(col, child_vals)
            dangling = child_vals - remaining[rcol]
            if not dangling:
                continue
            if act == "restrict":
                raise ValueError(
                    f"FOREIGN KEY {nm!r} on {cdb}.{ctn} restricts this "
                    f"statement: {sorted(dangling)[:3]!r} still referenced"
                )
            if act == "set_null":
                self._null_child_keys(cdb, ctn, col, dangling, _depth, undo)
            else:  # cascade (delete paths only)
                self._cascade_delete(cdb, ctn, col, dangling, _depth, undo)

    def _child_block_mask(self, block, col, values):
        """Boolean mask of rows whose decoded `col` value is in
        `values` (NULLs never match)."""
        import numpy as np

        c = block.columns[col]
        dec = c.decode()
        hit = np.fromiter(
            (bool(ok) and v in values for ok, v in zip(c.valid, dec)),
            dtype=bool, count=block.nrows,
        )
        return hit

    def _fk_recheck_children(self, cdb, ctn, depth, undo) -> None:
        """After mutating a child (cascade delete / set null), its own
        children may dangle: recurse with the post-mutation value sets
        of every column they reference."""
        ref_cols = {
            rcol2 for _cd, _ct, _nm, _c, rcol2, _a in self._fk_children(cdb, ctn)
        }
        if ref_cols:
            remaining = {
                rc: self._column_values(cdb, ctn, rc) for rc in ref_cols
            }
            self._enforce_parent_constraints(
                cdb, ctn, remaining, actions=True, _depth=depth + 1,
                undo=undo,
            )

    def _null_child_keys(self, cdb, ctn, col, values, depth, undo) -> None:
        """ON DELETE SET NULL: clear the child FK column where it
        referenced a deleted parent key, then re-check the child's own
        children (the nulled column's value set shrank)."""
        t = self._resolve_table_for_write(cdb, ctn)
        self._fk_undo_snapshot(undo, t)
        new_blocks = []
        changed = 0
        for b in t.blocks():
            hit = self._child_block_mask(b, col, values)
            if not hit.any():
                new_blocks.append(b)
                continue
            cols = dict(b.columns)
            c = cols[col]
            cols[col] = dataclasses.replace(c, valid=c.valid & ~hit)
            new_blocks.append(dataclasses.replace(b, columns=cols))
            changed += int(hit.sum())
        if changed:
            t.replace_blocks(new_blocks, modified_rows=changed)
            clear_scan_cache()
            self._fk_recheck_children(cdb, ctn, depth, undo)

    def _fk_upd_acts(self, children) -> dict:
        """(child_db, child_table, fk_name) -> declared ON UPDATE action
        for every child FK. The action dicts are keyed by LOWERCASED fk
        name (session DDL lowers them); looking up with the original-
        case name would silently degrade CASCADE to RESTRICT."""
        out = {}
        for cdb, ctn, nm, _cc, _rc, _a in children:
            ct = self.catalog.table(cdb, ctn)
            out[(cdb, ctn, nm)] = getattr(
                ct, "fk_update_actions", {}
            ).get(nm.lower(), "restrict")
        return out

    def _fk_update_guard(self, t, db, name, names, rows, undo):
        """Parent-key rewrite guard, shared by the single- and
        multi-table UPDATE paths: RESTRICT-checks children against the
        post-image value sets (honoring each FK's ON UPDATE action) and
        returns the post-install cascade/set-null plans."""
        children = self._fk_children(db, name)
        if not children:
            return []
        upd_acts = self._fk_upd_acts(children)
        need = {rc for _, _, _, _, rc, _a in children}
        need |= {
            c for cd, ct, _, c, _, _a in children
            if cd == db.lower() and ct == t.name
        }
        remaining = {
            col: {
                row[names.index(col)] for row in rows
                if row[names.index(col)] is not None
            }
            for col in need
        }
        action_children = [
            c for c in children
            if upd_acts[(c[0], c[1], c[2])] in ("cascade", "set_null")
        ]
        cascade_maps = (
            self._fk_update_plans(
                t, names, rows, action_children, upd_acts, remaining
            )
            if action_children else []
        )
        self._enforce_parent_constraints(
            db, name, remaining, update_acts=upd_acts, undo=undo
        )
        return cascade_maps

    def _apply_fk_update_plans(self, cascade_maps, undo) -> None:
        """Dispatch the post-install child actions from
        _fk_update_plans (shared by the single- and multi-table UPDATE
        paths)."""
        for kind, cdb, ctn, ccol, payload in cascade_maps:
            if kind == "cascade":
                self._cascade_update_child(cdb, ctn, ccol, payload, 0, undo)
            else:  # set_null (incl. cascades whose new key is NULL)
                self._null_child_keys(cdb, ctn, ccol, payload, 0, undo)

    def _cascade_update_child(
        self, cdb, ctn, col, mapping: dict, depth, undo
    ) -> None:
        """ON UPDATE CASCADE: rewrite child FK values old -> new from
        the parent's key rewrite, then RESTRICT-recheck the child's own
        children against its new value sets (a grandchild FK onto the
        rewritten column must still resolve). Reference:
        pkg/executor/foreign_key.go onUpdate cascade."""
        from tidb_tpu.chunk import column_from_values
        from tidb_tpu.utils.failpoint import inject

        inject("fk/cascade-update")
        if not mapping:
            return
        t = self._resolve_table_for_write(cdb, ctn)
        typ = t.schema.types[col]
        if typ.kind == Kind.STRING:
            raise ValueError(
                "ON UPDATE CASCADE is not supported for string FK "
                "columns (dictionary remap); use RESTRICT or SET NULL"
            )
        self._fk_undo_snapshot(undo, t)
        olds = list(mapping)
        enc_old = column_from_values(olds, typ).data
        enc_new = column_from_values([mapping[o] for o in olds], typ).data
        order = np.argsort(enc_old, kind="stable")
        so, sn = enc_old[order], enc_new[order]
        new_blocks = []
        changed = 0
        for b in t.blocks():
            c = b.columns[col]
            pos = np.clip(np.searchsorted(so, c.data), 0, len(so) - 1)
            hit = c.valid & (so[pos] == c.data)
            if not hit.any():
                new_blocks.append(b)
                continue
            data = np.where(hit, sn[pos], c.data).astype(c.data.dtype)
            cols = dict(b.columns)
            cols[col] = dataclasses.replace(c, data=data)
            new_blocks.append(dataclasses.replace(b, columns=cols))
            changed += int(hit.sum())
        if changed:
            t.replace_blocks(new_blocks, modified_rows=changed)
            clear_scan_cache()
            self._fk_recheck_children(cdb, ctn, depth, undo)

    def _cascade_delete(self, cdb, ctn, col, values, depth, undo) -> None:
        """ON DELETE CASCADE: remove child rows referencing deleted
        parent keys (Table.delete_where), then apply the child's own
        ON DELETE actions for its children (recursively)."""
        from tidb_tpu.utils.failpoint import inject

        inject("fk/cascade-delete")
        t = self._resolve_table_for_write(cdb, ctn)
        self._fk_undo_snapshot(undo, t)
        keep_masks = [
            ~self._child_block_mask(b, col, values) for b in t.blocks()
        ]
        if all(m.all() for m in keep_masks):
            return
        t.delete_where(keep_masks)
        clear_scan_cache()
        self._fk_recheck_children(cdb, ctn, depth, undo)

    def _unique_key_sets(self, t):
        """Conflict keys as ordered column tuples: the PK plus every
        UNIQUE index, single- or multi-column — the key set REPLACE INTO
        and ON DUPLICATE KEY resolve against (reference: the unique-key
        list walked by pkg/executor/replace.go removeRow)."""
        out = []
        pk = t.schema.primary_key
        if pk:
            out.append(tuple(pk))
        for iname in sorted(t.unique_indexes):
            c = t.indexes.get(iname)
            if c and tuple(c) not in out:
                out.append(tuple(c))
        return out

    def _unique_key_cols(self, t):
        """Flattened union of all conflict-key columns (any arity)."""
        out = []
        for ks in self._unique_key_sets(t):
            for c in ks:
                if c not in out:
                    out.append(c)
        return out

    def _incoming_key_matrix(self, t, cols, names, rows, ext_state=None):
        """Encode incoming raw rows' key components into the TABLE's
        encoded domain and return (key matrix, all-valid mask) aligned
        to the rows. This is the one place raw SQL values ('1994-01-01',
        Decimal strings, dictionary strings) meet stored encodings —
        comparing raw against decoded was the classic conflict-key bug
        (dates/decimals never matched). Strings map through the table
        dictionary; strings the table has never seen get per-statement
        provisional codes (distinct per distinct string, stable across
        calls via ext_state) so they conflict among themselves but never
        with stored rows."""
        from tidb_tpu.chunk import HostColumn, column_from_values
        from tidb_tpu.dtypes import Kind as _K
        from tidb_tpu.storage.table import Table

        columns = {}
        for c in cols:
            i = names.index(c)
            vals = [r[i] for r in rows]
            typ = t.schema.types[c]
            if typ.kind == _K.STRING:
                lut = None
                if ext_state is not None:
                    # the dictionary lut is per statement, not per call:
                    # row_keys() re-encodes single rows repeatedly and
                    # must not rebuild a large dictionary index each time
                    lut = ext_state.get(("lut", c))
                if lut is None:
                    d = t.dictionaries.get(c)
                    lut = (
                        {str(x): j for j, x in enumerate(d)}
                        if d is not None else {}
                    )
                    if ext_state is not None:
                        ext_state[("lut", c)] = lut
                ext = (
                    ext_state.setdefault(c, {})
                    if ext_state is not None else {}
                )
                codes = np.zeros(len(vals), dtype=np.int64)
                valid = np.zeros(len(vals), dtype=bool)
                for j, v in enumerate(vals):
                    if v is None:
                        continue
                    sv = str(v)
                    valid[j] = True
                    code = lut.get(sv)
                    if code is None:
                        # provisional: above any real int32 code
                        code = ext.setdefault(sv, (1 << 40) + len(ext))
                    codes[j] = code
                columns[c] = HostColumn(typ, codes, valid)
            else:
                columns[c] = column_from_values(vals, typ)
        return Table._key_matrix_full(columns, cols)

    def _incoming_key_views(self, t, key_sets, names, rows, ext_state):
        """Per key set: (per-row structured key view, all-valid mask,
        sorted valid-key array for vectorized membership)."""
        from tidb_tpu.storage.table import Table

        out = {}
        for ks in key_sets:
            mat, allv = self._incoming_key_matrix(
                t, ks, names, rows, ext_state
            )
            view = Table._rows_view(mat)
            out[ks] = (view, allv, np.sort(view[allv]))
        return out

    @staticmethod
    def _block_key_hits(b, ks, sorted_keys):
        """(per-row hit mask, per-row key view, all-valid mask) of one
        stored block against a sorted incoming key array — vectorized
        searchsorted membership in the encoded domain."""
        from tidb_tpu.storage.table import Table

        if any(c not in b.columns for c in ks):
            z = np.zeros(b.nrows, dtype=bool)
            return z, None, z
        bmat, ballv = Table._key_matrix_full(b.columns, ks)
        bview = Table._rows_view(bmat)
        if not len(sorted_keys):
            return np.zeros(b.nrows, dtype=bool), bview, ballv
        pos = np.clip(
            np.searchsorted(sorted_keys, bview), 0, len(sorted_keys) - 1
        )
        hit = ballv & (sorted_keys[pos] == bview)
        return hit, bview, ballv

    def _fill_ignore_null_pk(self, t, names, rows):
        """INSERT IGNORE: a NULL in a PK component (post-autoinc fill)
        takes the column's IMPLICIT default — 0 / '' / zero-temporal —
        so row counts match MySQL (pkg/table/column.go GetZeroValue
        under stmtctx.TruncateAsWarning). Must run BEFORE ON DUPLICATE
        KEY matching: the filled key participates in dup detection (a
        NULL-keyed row can UPDATE the implicit-default row). Kinds with
        no implicit default here drop the row."""
        pk = t.schema.primary_key
        if not pk or not rows:
            return rows
        zero = {
            Kind.INT: 0, Kind.FLOAT: 0.0, Kind.BOOL: False,
            Kind.DECIMAL: 0, Kind.STRING: "", Kind.DATE: 0,
            Kind.DATETIME: 0, Kind.TIME: 0,
        }
        pk_idx = [
            (names.index(c), zero.get(t.schema.types[c].kind))
            for c in pk if c in names
        ]
        fixed = []
        for r in rows:
            if any(r[i] is None and z is None for i, z in pk_idx):
                continue
            if any(r[i] is None for i, _z in pk_idx):
                r = list(r)
                for i, z in pk_idx:
                    if r[i] is None:
                        r[i] = z
                        self._warnings.append((
                            "Warning", 1048,
                            f"Column '{names[i]}' cannot be null",
                        ))
            fixed.append(r)
        return fixed

    def _filter_ignore(self, t, db: str, names, rows, skip_unique=False):
        """INSERT IGNORE: drop (instead of fail) rows that violate a
        CHECK, a FOREIGN KEY, or duplicate a PK/UNIQUE key against
        existing data or earlier rows of the same statement (reference:
        IGNORE handling in the insert executor, pkg/executor/insert.go).
        skip_unique: ON DUPLICATE KEY UPDATE already resolved key
        conflicts — filtering them again would drop the updated rows."""
        from tidb_tpu.utils.checkeval import _truth, eval_check

        checks = self._check_exprs_for(t) if t.checks else []
        fk_parents = []
        for _nm, col, rdb, rtbl, rcol in t.fks:
            parent = self._column_values(rdb, rtbl, rcol)
            self_fk = rdb == db.lower() and rtbl == t.name
            fk_parents.append(
                (names.index(col), parent,
                 names.index(rcol) if self_fk else None)
            )
        key_state = []
        if not skip_unique and rows:
            key_sets = self._unique_key_sets(t)
            inc = self._incoming_key_views(t, key_sets, names, rows, {})
            for ks in key_sets:
                view, allv, _sorted = inc[ks]
                # vectorized membership against the write target's cached
                # sorted composite view (encoded domain on both sides —
                # same data the append-time unique check will consult)
                stored = t._sorted_composite(tuple(ks))
                if stored is not None and len(stored):
                    pos = np.clip(
                        np.searchsorted(stored, view), 0, len(stored) - 1
                    )
                    in_table = allv & (stored[pos] == view)
                else:
                    in_table = np.zeros(len(rows), dtype=bool)
                key_state.append((view, allv, in_table, set()))
        kept = []
        for j, r in enumerate(rows):
            rowd = dict(zip(names, r))
            if any(
                _truth(eval_check(ex, rowd)) is False for _nm, ex in checks
            ):
                continue
            if any(
                r[i] is not None and r[i] not in parent
                for i, parent, _ri in fk_parents
            ):
                continue
            dup = False
            for view, allv, in_table, seen in key_state:
                if allv[j] and (
                    in_table[j] or view[j].tobytes() in seen
                ):
                    dup = True
                    break
            if dup:
                continue
            for view, allv, _in_table, seen in key_state:
                if allv[j]:
                    seen.add(view[j].tobytes())
            for _i, parent, ri in fk_parents:
                # self-FK: a KEPT row's key becomes a valid parent for
                # later rows of this same statement (mirrors the strict
                # path's in-batch semantics)
                if ri is not None and r[ri] is not None:
                    parent.add(r[ri])
            kept.append(r)
        return kept

    @staticmethod
    def _eval_on_dup(assigns, names, old, incoming):
        """One ON DUPLICATE KEY UPDATE application: evaluate assignment
        expressions against the existing row, with VALUES(col) denoting
        the incoming row's value. Later assignments see earlier results
        (MySQL's left-to-right semantics)."""
        from tidb_tpu.utils.checkeval import eval_check

        def subst(e):
            if (
                isinstance(e, ast.Call)
                and e.op == "values"
                and len(e.args) == 1
                and isinstance(e.args[0], ast.Name)
            ):
                return ast.Const(
                    incoming[names.index(e.args[0].column.lower())]
                )
            if isinstance(e, ast.Call):
                return dataclasses.replace(
                    e, args=[subst(a) for a in e.args]
                )
            return e

        from tidb_tpu.utils.checkeval import CheckEvalError

        new = list(old)
        env = dict(zip(names, old))
        for c, e in assigns:
            try:
                v = eval_check(subst(e), env)
            except CheckEvalError as err:
                raise ValueError(
                    "ON DUPLICATE KEY UPDATE supports literals, columns, "
                    f"VALUES(col), arithmetic and comparisons: {err}"
                ) from None
            new[names.index(c)] = v
            env[c] = v
        return new

    def _apply_on_dup(self, t, db: str, names, rows, assigns):
        """Resolve INSERT ... ON DUPLICATE KEY UPDATE into (pending rows
        to append, existing-row keys to delete, update count). Existing
        conflicting rows are fetched, updated, re-appended; statement-
        internal duplicates update the pending row in place (reference:
        pkg/executor/insert.go onDuplicateUpdate)."""
        key_sets = self._unique_key_sets(t)
        assigns = [(c.lower(), e) for c, e in assigns]
        for c, _e in assigns:
            if c not in names:
                raise ValueError(f"unknown column {c!r} in ON DUPLICATE KEY")
        if not key_sets:
            return list(rows), {}, 0
        # encoded-domain keys on BOTH sides: incoming raw values are
        # encoded into the table's domain (dates to day ints, decimals
        # to scaled ints, strings to dictionary codes), stored rows are
        # keyed directly from their encoded blocks — raw-vs-decoded
        # comparison is exactly the mismatch that made typed key
        # components never conflict. ext_state keeps provisional codes
        # for unseen strings stable across the per-row re-encodings of
        # updated rows below.
        ext_state: dict = {}
        inc = self._incoming_key_views(t, key_sets, names, rows, ext_state)

        def inc_key(j, ks):
            view, allv, _s = inc[ks]
            return view[j].tobytes() if allv[j] else None

        def row_keys(row):
            """Encoded keys of one (possibly updated) row, per key set:
            {ks: (bytes key or None, structured scalar or None)}."""
            out = {}
            for ks in key_sets:
                mat, allv = self._incoming_key_matrix(
                    t, ks, names, [row], ext_state
                )
                if allv[0]:
                    from tidb_tpu.storage.table import Table

                    v = Table._rows_view(mat)[0]
                    out[ks] = (v.tobytes(), v)
                else:
                    out[ks] = (None, None)
            return out

        # fetch existing rows that conflict with any incoming key —
        # vectorized encoded-key membership per block; only hit rows get
        # the full decode
        fetched = []
        existing = {ks: {} for ks in key_sets}
        for b in t.blocks():
            hit_any = np.zeros(b.nrows, dtype=bool)
            per_ks = {}
            for ks in key_sets:
                hit, bview, ballv = self._block_key_hits(b, ks, inc[ks][2])
                if bview is not None:
                    per_ks[ks] = (bview, ballv)
                hit_any |= hit
            hits = np.nonzero(hit_any)[0]
            if not len(hits):
                continue
            dec = {c: b.columns[c].decode() for c in names}
            ok = {c: b.columns[c].valid for c in names}
            for i in hits:
                rowv = [dec[c][i] if ok[c][i] else None for c in names]
                idx = len(fetched)
                fetched.append(rowv)
                for ks, (bview, ballv) in per_ks.items():
                    if ballv[i]:
                        existing[ks][bview[i].tobytes()] = idx
        pending, pkey = [], {ks: {} for ks in key_sets}
        # origin: id(pending row) -> [(key col, old value)] of the
        # existing row it replaces — the caller deletes old rows only
        # for pending rows that actually get appended (INSERT IGNORE
        # may drop an updated row; its old row must then survive)
        origin: dict = {}
        n_upd = 0
        consumed = set()
        for j, row in enumerate(rows):
            target = None
            for ks in key_sets:
                v = inc_key(j, ks)
                if v is None:
                    continue
                if v in pkey[ks]:
                    target = ("p", pkey[ks][v])
                    break
                fi = existing[ks].get(v)
                if fi is not None and fi not in consumed:
                    target = ("e", fi)
                    break
            if target is None:
                idx = len(pending)
                pending.append(row)
                for ks in key_sets:
                    v = inc_key(j, ks)
                    if v is not None:
                        pkey[ks][v] = idx
                continue
            n_upd += 1
            if target[0] == "e":
                fi = target[1]
                consumed.add(fi)
                old = fetched[fi]
                new = self._eval_on_dup(assigns, names, old, row)
                old_keys = row_keys(old)
                origin[id(new)] = [
                    (ks, scalar)
                    for ks, (kb, scalar) in old_keys.items()
                    if kb is not None
                ]
                idx = len(pending)
                pending.append(new)
                for ks, (kb, _scalar) in row_keys(new).items():
                    if kb is not None:
                        pkey[ks][kb] = idx
            else:
                pi = target[1]
                old = pending[pi]
                new = self._eval_on_dup(assigns, names, old, row)
                if id(old) in origin:
                    origin[id(new)] = origin.pop(id(old))
                for ks, (kb, _scalar) in row_keys(old).items():
                    if kb is not None and pkey[ks].get(kb) == pi:
                        del pkey[ks][kb]
                pending[pi] = new
                for ks, (kb, _scalar) in row_keys(new).items():
                    if kb is not None:
                        pkey[ks][kb] = pi
        return pending, origin, n_upd

    def _delete_rows_by_keys(self, t, del_keys: dict) -> None:
        """Delete rows matching the given encoded key scalars per key
        set (column tuple) — vectorized searchsorted over each block's
        encoded key view."""
        for cols, values in del_keys.items():
            if not values:
                continue
            tgt = np.sort(np.array(list(values)))
            keep = []
            for b in t.blocks():
                hit, _bview, _ballv = self._block_key_hits(b, cols, tgt)
                keep.append(~hit)
            if any((~m).any() for m in keep):
                t.delete_where(keep)

    def _run_insert(self, s: ast.Insert) -> Result:
        from tidb_tpu.utils.failpoint import inject

        inject("dml/insert")
        t = self._resolve_table_for_write(s.db or self.db, s.table)
        names = t.schema.names
        cols = [c.lower() for c in s.columns] if s.columns else names
        unknown = set(cols) - set(names)
        if unknown:
            raise ValueError(f"unknown columns {sorted(unknown)}")
        rows = []
        if s.query is not None:
            # INSERT ... SELECT: run the source query, map by position
            res = self._run_select(self._resolve_session_funcs(s.query))
            if res.columns and len(res.columns) != len(cols):
                raise ValueError(
                    f"INSERT ... SELECT arity mismatch: {len(res.columns)} "
                    f"columns for {len(cols)} targets"
                )
            dflt = getattr(t, "defaults", None) or {}
            for row in res.rows:
                vals = dict(zip(cols, row))
                rows.append(
                    [vals[n] if n in vals else dflt.get(n) for n in names]
                )
        for row in s.rows:
            if len(row) != len(cols):
                raise ValueError("VALUES arity mismatch")
            vals = {c: self._const_value(v) for c, v in zip(cols, row)}
            dflt = getattr(t, "defaults", None) or {}
            rows.append(
                [vals[n] if n in vals else dflt.get(n) for n in names]
            )
        gen_cols = {c for c, *_ in getattr(t, "generated", None) or []}
        if gen_cols:
            # MySQL: inserting a value into a generated column is only
            # allowed when it is DEFAULT/NULL (computed instead)
            tgt = [(names.index(c), c) for c in gen_cols if c in cols]
            for r in rows:
                for gi, gc in tgt:
                    if r[gi] is not None:
                        raise ValueError(
                            f"the value specified for generated column "
                            f"{gc!r} is not allowed"
                        )
            if s.on_dup:
                self._reject_generated_targets(
                    t, [c.lower() for c, _e in s.on_dup], "assign"
                )
        ac = t.autoinc_col
        if ac is not None:
            ai = names.index(ac)
            explicit = [r[ai] for r in rows if r[ai] is not None]
            if explicit:
                t.observe_autoid(max(explicit))
            missing = [r for r in rows if r[ai] is None]
            if missing:
                start = t.next_autoid(len(missing))
                for k, r in enumerate(missing):
                    r[ai] = start + k
                self.last_insert_id = start
        # generated columns compute over the final base values — before
        # ON DUPLICATE KEY (key lookups may hit an indexed generated
        # column) and re-computed after its assignments below
        self._fill_generated(t, rows)
        # constraints run over the final values (after autoinc fill) and
        # BEFORE the REPLACE delete — a failing row must not leave the
        # statement half-applied
        db = s.db or self.db
        n_upd = 0
        if getattr(s, "ignore", False):
            rows = self._fill_ignore_null_pk(t, names, rows)
        n_incoming = len(rows)
        origin: dict = {}
        if s.on_dup:
            rows, origin, n_upd = self._apply_on_dup(
                t, db, names, rows, s.on_dup
            )
            self._fill_generated(t, rows)
        if getattr(s, "ignore", False):
            before = len(rows)
            rows = self._filter_ignore(
                t, db, names, rows, skip_unique=bool(s.on_dup)
            )
            n_incoming -= before - len(rows)
        else:
            self._enforce_write_constraints(t, db, rows)
        # delete old rows only for updated rows that survived filtering
        # (encoded key scalars, deduped via their byte image — numpy
        # void scalars are not reliably hashable)
        del_keys: dict = {}
        for r in rows:
            for kc, v in origin.get(id(r), ()):
                del_keys.setdefault(kc, {})[v.tobytes()] = v
        del_keys = {kc: list(d.values()) for kc, d in del_keys.items()}
        replace = getattr(s, "replace", False)
        mutates_existing = replace or any(del_keys.values())
        children = (
            self._fk_children(db, s.table) if mutates_existing else []
        )
        saved = (
            (list(t.blocks()), dict(t.dictionaries))
            if mutates_existing else None
        )
        try:
            if replace:
                self._replace_conflicts(t, names, rows)
            if any(del_keys.values()):
                self._delete_rows_by_keys(t, del_keys)
            t.append_rows(rows)
        except Exception:
            if saved is not None:
                t.replace_blocks(saved[0], modified_rows=len(rows))
                t.dictionaries = saved[1]
            raise
        if children:
            # REPLACE / ON DUPLICATE KEY delete or rewrite existing
            # rows: the parent value set may have shrunk — enforce
            # RESTRICT on the post-statement state and roll the whole
            # statement back on violation
            need = {rc for _, _, _, _, rc, _a in children}
            need |= {
                c for cd, ct, _, c, _, _a in children
                if cd == db.lower() and ct == t.name
            }
            remaining = {}
            for col in need:
                vals = set()
                for b in t.blocks():
                    c = b.columns[col]
                    dec = c.decode()
                    for ok, v in zip(c.valid, dec):
                        if ok:
                            vals.add(v)
                remaining[col] = vals
            try:
                self._enforce_parent_constraints(db, s.table, remaining)
            except Exception:
                t.replace_blocks(saved[0], modified_rows=len(rows))
                t.dictionaries = saved[1]
                raise
        clear_scan_cache()
        # MySQL: each plain insert counts 1, each ON DUPLICATE update 2
        # (n_incoming = incoming rows surviving IGNORE; each update
        # consumed one incoming row and counts twice)
        return Result([], [], affected=n_incoming + n_upd)

    def _replace_conflicts(self, t, names, rows) -> None:
        """REPLACE INTO: delete existing rows whose PK or any UNIQUE key
        — single- or multi-column — collides with an incoming row, then
        the normal append inserts the replacements (reference:
        pkg/executor/replace.go — delete then insert under one
        statement). All matching happens in the encoded domain (dates as
        day ints, decimals as scaled ints, strings as dictionary codes),
        vectorized per block."""
        key_sets = self._unique_key_sets(t)
        if not key_sets or not rows:
            return
        ext_state: dict = {}
        # MySQL REPLACE keeps the LAST row when one statement carries
        # duplicate keys — dedupe incoming rows before the append
        for ks in key_sets:
            mat, allv = self._incoming_key_matrix(
                t, ks, names, rows, ext_state
            )
            from tidb_tpu.storage.table import Table

            view = Table._rows_view(mat)
            seen = set()
            kept = []
            for j in range(len(rows) - 1, -1, -1):
                k = view[j].tobytes() if allv[j] else None
                if k is not None and k in seen:
                    continue
                if k is not None:
                    seen.add(k)
                kept.append(rows[j])
            rows[:] = list(reversed(kept))
        for ks in key_sets:
            _mat, allv = self._incoming_key_matrix(
                t, ks, names, rows, ext_state
            )
            from tidb_tpu.storage.table import Table

            srt = np.sort(Table._rows_view(_mat)[allv])
            if not len(srt):
                continue
            keep_masks = []
            for b in t.blocks():
                hit, _bv, _bav = self._block_key_hits(b, ks, srt)
                keep_masks.append(~hit)
            if any((~m).any() for m in keep_masks):
                t.delete_where(keep_masks)

    def _const_value(self, e):
        if isinstance(e, ast.Const):
            return e.value
        if isinstance(e, ast.Call) and e.op == "neg" and isinstance(e.args[0], ast.Const):
            return -e.args[0].value
        if isinstance(e, ast.Call) and e.op.lower() in (
            "nextval", "lastval", "setval"
        ):
            # per-ROW evaluation: INSERT VALUES (nextval(s)), (nextval(s))
            # advances once per row, like the reference
            return self._seq_func(e)
        raise ValueError("INSERT VALUES must be literals")

    def _run_delete(self, s: ast.Delete) -> Result:
        from tidb_tpu.utils.failpoint import inject

        inject("dml/delete")
        if s.targets is not None:
            return self._run_delete_multi(s)
        db = s.db or self.db
        t = self._resolve_table_for_write(db, s.table)
        children = self._fk_children(db, s.table)
        if s.where is None and (s.limit is not None or s.order_by):
            import numpy as np

            masks = [
                np.ones(b.nrows, dtype=bool) for b in t.blocks()
            ]
            masks, affected = self._dml_order_limit_masks(
                t, masks, s.order_by, s.limit
            )
            return self._delete_masked(t, db, s.table, masks, affected)
        if s.where is None:
            affected = t.nrows
            undo = []
            self._fk_undo_snapshot(undo, t)
            t.replace_blocks([], modified_rows=affected)
            try:
                if children:
                    self._enforce_parent_constraints(
                        db, s.table,
                        {c: set() for c in t.schema.names},
                        actions=True, undo=undo,
                    )
            except BaseException:
                self._fk_undo_restore(undo)
                raise
            clear_scan_cache()
            return Result([], [], affected=affected)
        masks, affected = self._eval_where_per_block(t, s.where)
        if s.limit is not None or s.order_by:
            masks, affected = self._dml_order_limit_masks(
                t, masks, s.order_by, s.limit
            )
        return self._delete_masked(t, db, s.table, masks, affected)

    def _delete_masked(
        self, t, db, table_name, masks, affected, undo=None, deferred=None
    ) -> Result:
        """Apply per-block delete masks (True = remove) with the full
        referential-action protocol: compute post-delete remaining value
        sets for FK parents, delete first so cascades see the
        post-statement state, restore every touched table if a nested
        RESTRICT fires.

        Multi-table DELETE passes `undo` (shared restore list) and
        `deferred` (a list collecting referential-action thunks): all
        explicit target deletions then happen BEFORE any cascade runs, so
        a cascade into another target's table can never shift row
        positions a later mask still refers to (positions were captured
        against the pre-statement state)."""
        children = self._fk_children(db, table_name)
        blocks = t.blocks()
        remaining = None
        if children and affected:
            # post-delete values for every column a child references
            # (and, for self-FKs, the child column itself)
            need = {rc for _, _, _, _, rc, _a in children}
            need |= {
                c for cd, ct, _, c, _, _a in children
                if cd == db.lower() and ct == t.name
            }
            remaining = {}
            for col in need:
                vals = set()
                for b, m in zip(blocks, masks):
                    c = b.columns[col]
                    dec = c.decode()
                    for ok, dead, v in zip(c.valid, m, dec):
                        if ok and not dead:
                            vals.add(v)
                remaining[col] = vals
        # delete FIRST so referential actions (incl. self-FK cascades)
        # run against the post-statement state; restore every touched
        # table if a nested RESTRICT fires mid-chain
        shared_undo = undo is not None
        undo = undo if shared_undo else []
        self._fk_undo_snapshot(undo, t)
        t.delete_where([~m for m in masks])

        def actions():
            if children and affected:
                self._enforce_parent_constraints(
                    db, table_name, remaining, actions=True, undo=undo
                )

        if deferred is not None:
            deferred.append(actions)
            return Result([], [], affected=affected)
        try:
            actions()
        except BaseException:
            self._fk_undo_restore(undo)
            raise
        clear_scan_cache()
        return Result([], [], affected=affected)

    def _run_update(self, s: ast.Update) -> Result:
        from tidb_tpu.utils.failpoint import inject

        inject("dml/update")
        if s.from_refs is not None:
            return self._run_update_multi(s)
        t = self._resolve_table_for_write(s.db or self.db, s.table)
        if s.limit is not None or s.order_by:
            # UPDATE ... [ORDER BY] LIMIT: choose the affected rows
            # first, then run a plain keyed UPDATE over them (the
            # columnar fast path and the select-rewrite fallback both
            # consume an ordinary WHERE)
            import numpy as np

            if s.where is not None:
                masks, _n = self._eval_where_per_block(t, s.where)
            else:
                masks = [np.ones(b.nrows, dtype=bool) for b in t.blocks()]
            before = sum(int(m.sum()) for m in masks)
            masks, affected = self._dml_order_limit_masks(
                t, masks, s.order_by, s.limit
            )
            if affected == before:
                # LIMIT did not bind: a plain UPDATE, no rewrite needed
                s = dataclasses.replace(s, order_by=[], limit=None)
                return self._run_update(s)
            pk = t.schema.primary_key
            if not (pk and len(pk) == 1):
                raise ValueError(
                    "UPDATE ... ORDER BY/LIMIT requires a "
                    "single-column PRIMARY KEY"
                )
            pkc = pk[0]
            vals = []
            for b, m in zip(t.blocks(), masks):
                dec = b.columns[pkc].decode()
                vals.extend(dec[i] for i in np.nonzero(m)[0])
            if not vals:
                return Result([], [], affected=0)
            in_pred = ast.Call(
                "in",
                [ast.Name(None, pkc)] + [ast.Const(v) for v in vals],
            )
            s = dataclasses.replace(
                s, where=in_pred, order_by=[], limit=None
            )
        sets = {c.lower(): e for c, e in s.sets}
        self._reject_generated_targets(t, sets, "SET")
        fast = self._try_columnar_update(t, s, sets)
        if fast is not None:
            return fast
        # fallback: evaluate via a SELECT of all columns with updated
        # expressions, then rewrite the table (string-typed SET columns
        # need dictionary merging, which only the append path does).
        alias = t.name
        items = []
        for n, _typ in t.schema.columns:
            if n in sets:
                items.append(ast.SelectItem(sets[n], alias=n))
            else:
                items.append(ast.SelectItem(ast.Name(None, n), alias=n))
        sel = ast.Select(
            items=items,
            from_=ast.TableRef(s.db, s.table, None),
            where=None,
        )
        # rows not matching WHERE keep original values: implement as
        # CASE WHEN where THEN new ELSE old END per updated column
        if s.where is not None:
            new_items = []
            for it in items:
                if it.alias in sets:
                    new_items.append(
                        ast.SelectItem(
                            ast.Call("case", [s.where, it.expr, ast.Name(None, it.alias)]),
                            alias=it.alias,
                        )
                    )
                else:
                    new_items.append(it)
            sel = dataclasses.replace(sel, items=new_items)
        r = self._run_select(sel)
        rows = [list(row) for row in r.rows]
        self._fill_generated(t, rows)
        db = s.db or self.db
        # ``rows`` is the table's complete post-statement image: child
        # FK + CHECK validate the new rows, parent-side constraints
        # validate children against the new value sets (each child FK's
        # ON UPDATE action applies: RESTRICT raises, SET NULL nulls,
        # CASCADE rewrites child keys from the old->new pairing)
        self._enforce_write_constraints(t, db, rows)
        undo: list = []
        cascade_maps = self._fk_update_guard(
            t, db, s.table, t.schema.names, rows, undo
        )
        # count affected
        if s.where is None:
            affected = len(rows)
        else:
            _masks, affected = self._eval_where_per_block(t, s.where)
        saved_blocks = list(t.blocks())
        saved_dicts = dict(t.dictionaries)
        t.replace_blocks([], modified_rows=affected)
        try:
            if rows:
                t.append_rows(rows)
            self._apply_fk_update_plans(cascade_maps, undo)
        except Exception:
            # e.g. the SET created duplicate PK/UNIQUE keys, or a
            # cascade failed downstream — the whole statement rolls
            # back, children included. Undo restores FIRST: a self-FK
            # child snapshot in `undo` was taken post-append, and
            # re-installing it after saved_blocks would resurrect the
            # updated parent image the rollback just removed
            self._fk_undo_restore(undo)
            t.replace_blocks(saved_blocks, modified_rows=affected)
            t.dictionaries = saved_dicts
            raise
        clear_scan_cache()
        return Result([], [], affected=affected)

    def _fk_update_plans(
        self, t, names, rows, action_children, upd_acts, remaining
    ):
        """Post-install child actions for ON UPDATE CASCADE/SET NULL:
        [("cascade", cdb, ctn, child_col, {old: new}) |
         ("set_null", cdb, ctn, child_col, {old values to null})].
        The rewrite SELECT emits rows in scan (block-concatenation)
        order, so pre-image row i corresponds to post-image row i. A
        length mismatch, or one old key paired with TWO different
        outcomes (rewritten in one parent row, kept or rewritten
        differently in another — possible only when the referenced
        column is not unique), aborts rather than guessing. A cascade
        whose new key is NULL becomes a SET NULL on the child (writing
        the encoded null sentinel with valid=True would fabricate key
        0)."""
        old_cols: dict = {}
        for rc in {c[4] for c in action_children}:
            vals: list = []
            for b in t.blocks():
                hc = b.columns[rc]
                dec = hc.decode()
                vals.extend(
                    dec[i] if hc.valid[i] else None
                    for i in range(b.nrows)
                )
            old_cols[rc] = vals
        out = []
        for cdb, ctn, nm, ccol, rcol, _odel in action_children:
            act = upd_acts[(cdb, ctn, nm)]
            olds = old_cols[rcol]
            if act == "set_null":
                dangling = {o for o in olds if o is not None} - remaining[
                    rcol
                ]
                if dangling:
                    out.append(("set_null", cdb, ctn, ccol, dangling))
                continue
            if len(olds) != len(rows):
                raise ValueError(
                    "ON UPDATE CASCADE: cannot align pre/post images "
                    f"for {rcol!r} (row set changed size)"
                )
            idx = names.index(rcol)
            pairs: dict = {}
            for old, row in zip(olds, rows):
                if old is None:
                    continue
                pairs.setdefault(old, set()).add(row[idx])
            mapping: dict = {}
            null_olds: set = set()
            for old, news in pairs.items():
                if len(news) > 1:
                    raise ValueError(
                        f"ON UPDATE CASCADE: ambiguous rewrite of "
                        f"{rcol!r} value {old!r}"
                    )
                new = next(iter(news))
                if new is None:
                    null_olds.add(old)
                elif new != old:
                    mapping[old] = new
            if mapping:
                out.append(("cascade", cdb, ctn, ccol, mapping))
            if null_olds:
                out.append(("set_null", cdb, ctn, ccol, null_olds))
        return out

    def _try_columnar_update(self, t, s: ast.Update, sets) -> Optional[Result]:
        """Block-targeted columnar UPDATE: scatter new values for the SET
        columns into copies of only the touched blocks — O(touched data),
        not a whole-table rewrite through Python rows (reference: the
        write path touches only affected keys, pkg/executor/update.go).
        String SET columns stay columnar when every SET expression is a
        constant already present in the column's dictionary (the common
        `SET status = 'done'` shape): the scatter writes dictionary
        codes, no remap. A constant the dictionary has never seen needs
        the sorted-merge remap — that falls back to the rewrite path."""
        types = t.schema.types
        if any(c not in types for c in sets):
            return None
        str_codes = {}
        for c, e in sets.items():
            if types[c].kind != Kind.STRING:
                continue
            try:
                v = self._const_value(e)
            except Exception:
                return None  # non-literal string SET: rewrite path
            d = t.dictionaries.get(c)
            if not isinstance(v, str) or d is None or not len(d):
                return None
            pos = int(np.searchsorted(d, v))
            if pos >= len(d) or str(d[pos]) != v:
                return None  # unseen value: needs a dictionary remap
            str_codes[c] = pos
        if s.where is None or not t.blocks():
            return None
        relevant: set = set()
        if t.checks:
            from tidb_tpu.utils.checkeval import check_columns

            for _nm, ex in self._check_exprs_for(t):
                relevant |= check_columns(ex)
        relevant |= {col for _nm, col, *_ in t.fks}
        relevant |= {
            rc for _, _, _, _, rc, _a in
            self._fk_children(s.db or self.db, s.table)
        }
        # generated-column dependencies: a SET on a base column must
        # recompute dependents, which needs the full-row rewrite path
        if getattr(t, "generated", None):
            from tidb_tpu.utils.checkeval import check_columns

            for _col, ex in self._gen_exprs_for(t):
                relevant |= check_columns(ex)
        # PK/UNIQUE columns: the scatter path bypasses append-time
        # uniqueness checks, so key-touching SETs take the rewrite path
        relevant |= set(self._unique_key_cols(t))
        if relevant & set(sets):
            # a constrained column is being SET: constraint checks need
            # fully-formed rows — use the rewrite path, which
            # materializes them anyway
            return None
        try:
            masks, affected = self._eval_where_per_block(t, s.where)
        except Exception:
            return None
        if affected == 0:
            return Result([], [], affected=0)
        # new values for matching rows only, cast to the column type,
        # in scan (block-concatenation) order. Constant string SETs
        # don't need the SELECT at all: their dictionary code scatters
        # directly.
        set_cols = [c for c in sets if c not in str_codes]
        new_data = {}
        new_valid = {}
        if set_cols:
            items = [
                ast.SelectItem(
                    ast.Call("cast", [sets[c]], types[c]), alias=f"_s{i}"
                )
                for i, c in enumerate(set_cols)
            ]
            sel = ast.Select(
                items=items,
                from_=ast.TableRef(s.db, s.table, None),
                where=s.where,
            )
            db = s.db or self.db
            try:
                plan = build_query(
                    sel, self.catalog, db, self._scalar_subquery
                )
                batch, _dicts = self.executor.run(plan)
            except Exception:
                return None
            rv = np.asarray(batch.row_valid)
            order = np.nonzero(rv)[0]
            internals = [c.internal for c in plan.schema.cols]
            for c, internal in zip(set_cols, internals):
                dc = batch.cols[internal]
                new_data[c] = np.asarray(dc.data)[order]
                new_valid[c] = np.asarray(dc.valid)[order]
            if len(order) != affected:
                return None  # alignment lost — fall back
        new_blocks = []
        consumed = 0
        for block, m in zip(t.blocks(), masks):
            hit = int(m.sum())
            if hit == 0:
                new_blocks.append(block)
                continue
            pos = np.nonzero(m)[0]
            cols = dict(block.columns)
            for c in set_cols:
                src = block.columns[c]
                data = src.data.copy()
                valid = src.valid.copy()
                data[pos] = new_data[c][consumed : consumed + hit].astype(
                    data.dtype
                )
                valid[pos] = new_valid[c][consumed : consumed + hit]
                cols[c] = dataclasses.replace(src, data=data, valid=valid)
            for c, code in str_codes.items():
                src = block.columns[c]
                data = src.data.copy()
                valid = src.valid.copy()
                data[pos] = np.asarray(code, dtype=data.dtype)
                valid[pos] = True
                cols[c] = dataclasses.replace(src, data=data, valid=valid)
            consumed += hit
            new_blocks.append(
                HostBlock(cols, block.nrows, part_id=block.part_id)
            )
        t.replace_blocks(new_blocks, modified_rows=affected)
        clear_scan_cache()
        return Result([], [], affected=affected)

    def _dml_order_limit_masks(self, t, masks, order_by, limit):
        """Restrict per-block DML masks (True = affected) to the first
        `limit` matching rows ordered by `order_by` (MySQL single-table
        UPDATE/DELETE ... ORDER BY ... LIMIT). Order keys must be plain
        columns; NULLs sort first ascending (MySQL). Returns (masks,
        affected)."""
        import numpy as np

        blocks = t.blocks()
        total = sum(int(m.sum()) for m in masks)
        if total == 0 or limit is None or total <= limit:
            # ORDER BY without a binding LIMIT changes nothing
            return masks, total
        bi = np.concatenate([
            np.full(int(m.sum()), i, dtype=np.int64)
            for i, m in enumerate(masks)
        ])
        ri = np.concatenate([np.nonzero(m)[0] for m in masks])
        if order_by:
            # vectorized direction+null key transforms (the
            # executor/sort.py convention: NULLs first ascending, last
            # descending), encoded domain — dictionaries are sorted so
            # string codes order binary-lexicographically
            keys = []  # np.lexsort order: LAST array is primary
            for ob in order_by:
                if not isinstance(ob.expr, ast.Name) or ob.expr.table:
                    raise ValueError(
                        "DELETE/UPDATE ... ORDER BY supports plain "
                        "column names"
                    )
                cn = ob.expr.column.lower()
                if cn not in t.schema.types:
                    raise ValueError(f"unknown column {cn!r}")
                data = np.concatenate([
                    np.asarray(
                        b.columns[cn].data, dtype=np.float64
                    )[m]
                    for b, m in zip(blocks, masks)
                ])
                valid = np.concatenate([
                    b.columns[cn].valid[m]
                    for b, m in zip(blocks, masks)
                ])
                if ob.desc:
                    nullk = (~valid).astype(np.int8)  # NULLs last
                    valk = np.where(valid, -data, 0.0)
                else:
                    nullk = valid.astype(np.int8)  # NULLs first
                    valk = np.where(valid, data, 0.0)
                keys.append((nullk, valk))
            operands = []
            for nullk, valk in reversed(keys):
                operands.append(valk)
                operands.append(nullk)
            order = np.lexsort(operands)
        else:
            order = np.arange(len(bi))
        take = order[:limit]
        out = []
        for i, m in enumerate(masks):
            nm = np.zeros_like(m)
            mine = take[bi[take] == i]
            nm[ri[mine]] = True
            out.append(nm)
        return out, int(len(take))

    def _eval_where_per_block(self, t, where):
        """Evaluate WHERE over each block on host via a filtered scan;
        returns per-block keep masks for matching rows + count."""
        sel = ast.Select(
            items=[ast.SelectItem(where, alias="_m")],
            from_=ast.TableRef(None, t.name, None),
        )
        # plan against this table's db: resolve by search
        db = next(d for d in self.catalog.databases() if self.catalog.has_table(d, t.name))
        plan = build_query(sel, self.catalog, db, self._scalar_subquery)
        batch, dicts = self.executor.run(plan)
        internal = plan.schema.cols[0].internal
        c = batch.cols[internal]
        m = np.asarray(c.data & c.valid & batch.row_valid)
        # batch rows follow block concatenation order
        masks = []
        off = 0
        for b in t.blocks():
            masks.append(m[off : off + b.nrows].astype(bool))
            off += b.nrows
        return masks, int(m[: off].sum())

    # -- multi-table DML -----------------------------------------------
    def _dml_lock_tables(self, s) -> list:
        """(db, table) write-lock list of an UPDATE/DELETE — the target
        tables, resolving multi-table forms through their from_refs."""
        if isinstance(s, ast.Update) and s.from_refs is not None:
            refs, per = self._update_targets(s)
            return [
                ((refs[a].db or self.db), refs[a].name) for a in per
            ]
        if isinstance(s, ast.Delete) and s.targets is not None:
            refs = self._refs_map(s.from_refs)
            out = []
            for tdb, name in s.targets:
                tr = refs.get(name.lower())
                if tr is not None:
                    out.append(((tr.db or self.db), tr.name))
                else:
                    out.append((tdb or self.db, name))
            return out
        return [(s.db or self.db, s.table)]

    def _refs_map(self, refs) -> dict:
        """alias (lowercased) -> TableRef for every TOP-LEVEL base table
        of a from_refs join tree. Does not descend into derived tables
        (SubqueryRef) — tables inside them are legal row sources but
        never DML targets or SET-column binding candidates."""
        out = {}

        def walk(node):
            if isinstance(node, ast.TableRef):
                out[(node.alias or node.name).lower()] = node
            elif isinstance(node, ast.Join):
                walk(node.left)
                walk(node.right)
            # SubqueryRef: stop

        walk(refs)
        return out

    def _update_targets(self, s: ast.Update):
        """Resolve the SET list of a multi-table UPDATE: returns
        {alias: [(column, expr)]} with unqualified columns bound to the
        unique base table that has them (reference: buildUpdateLists'
        column resolution, pkg/planner/core/logical_plan_builder.go)."""
        refs = self._refs_map(s.from_refs)
        per: dict = {}
        for col, e in s.sets:
            if "." in col:
                alias, c = col.split(".", 1)
                alias = alias.lower()
                if alias not in refs:
                    raise ValueError(f"unknown table {alias!r} in UPDATE SET")
            else:
                cands = []
                for a, tr in refs.items():
                    db = (tr.db or self.db).lower()
                    if self.catalog.has_table(db, tr.name):
                        t = self.catalog.table(db, tr.name)
                        if col.lower() in t.schema.types:
                            cands.append(a)
                if len(cands) != 1:
                    raise ValueError(
                        f"column {col!r} in UPDATE SET is "
                        + ("ambiguous" if cands else "unknown")
                    )
                alias, c = cands[0], col
            per.setdefault(alias, []).append((c.lower(), e))
        return refs, per

    def _run_update_multi(self, s: ast.Update) -> Result:
        """UPDATE over a joined row source (UPDATE t1 JOIN t2 ...). One
        SELECT over the join computes, per matched row, each target
        table's scan-order row handle (the virtual _tidb_rowid column)
        plus the SET expressions evaluated in join scope; each target row
        is then updated once — the first matching join row wins, MySQL's
        multiple-match rule (reference: pkg/executor/update.go dupKey
        handling). The table rewrite reuses the single-table fallback
        protocol: full row image, constraint + FK validation, atomic
        replace with rollback."""
        from tidb_tpu.planner.logical import ROWID_NAME, expose_rowid

        refs, per = self._update_targets(s)
        aliases = list(per)
        items = []
        for i, alias in enumerate(aliases):
            tr = refs[alias]
            db = (tr.db or self.db).lower()
            t = self.catalog.table(db, tr.name)
            items.append(
                ast.SelectItem(ast.Name(alias, ROWID_NAME), alias=f"_h{i}")
            )
            for j, (c, e) in enumerate(per[alias]):
                typ = t.schema.types.get(c)
                if typ is None:
                    raise ValueError(f"unknown column {alias}.{c}")
                if typ.kind != Kind.STRING:
                    # cast to the column type on device; string values
                    # come back as Python strings and re-encode on append
                    e = ast.Call("cast", [e], typ)
                items.append(ast.SelectItem(e, alias=f"_v{i}_{j}"))
        sel = ast.Select(items=items, from_=s.from_refs, where=s.where)
        with expose_rowid(aliases):
            r = self._run_select(sel)

        # column offsets of each target's handle/value slots in the rows
        offs = {}
        pos = 0
        for i, alias in enumerate(aliases):
            offs[alias] = pos
            pos += 1 + len(per[alias])

        affected = 0
        # statement-level rollback state: a failure on the SECOND target
        # must also restore the first target and its FK cascades (the
        # statement is atomic across every table it touches)
        stmt_undo: list = []
        saved: list = []  # (table, blocks, dicts, modified_rows)
        try:
            for alias in aliases:
                tr = refs[alias]
                db = (tr.db or self.db).lower()
                t = self._resolve_table_for_write(db, tr.name)
                base = offs[alias]
                nsets = len(per[alias])
                new_by_handle: dict = {}
                for row in r.rows:
                    h = row[base]
                    if h is None or h in new_by_handle:
                        continue  # no-match (outer join) / first match wins
                    new_by_handle[int(h)] = row[base + 1 : base + 1 + nsets]
                if not new_by_handle:
                    continue
                # full decoded row image with new values applied at handles
                names = t.schema.names
                cidx = {n: k for k, n in enumerate(names)}
                rows = []
                for b in t.blocks():
                    decs = [b.columns[n].decode() for n in names]
                    vals = [b.columns[n].valid for n in names]
                    for k in range(b.nrows):
                        rows.append(
                            [
                                decs[c][k] if vals[c][k] else None
                                for c in range(len(names))
                            ]
                        )
                for h, new in new_by_handle.items():
                    if not (0 <= h < len(rows)):
                        raise ValueError(f"stale row handle {h} in UPDATE")
                    for (c, _e), v in zip(per[alias], new):
                        rows[h][cidx[c]] = v
                self._reject_generated_targets(
                    t, [c for c, _e in per[alias]], "SET"
                )
                self._fill_generated(t, rows)
                self._enforce_write_constraints(t, db, rows)
                # rows[] was built FROM t.blocks() in scan order, so the
                # pre/post alignment the guard needs is exact
                cascade_maps = self._fk_update_guard(
                    t, db, tr.name, names, rows, stmt_undo
                )
                saved.append(
                    (t, list(t.blocks()), dict(t.dictionaries),
                     len(new_by_handle))
                )
                t.replace_blocks([], modified_rows=len(new_by_handle))
                if rows:
                    t.append_rows(rows)
                self._apply_fk_update_plans(cascade_maps, stmt_undo)
                affected += len(new_by_handle)
        except Exception:
            # undo first (child snapshots may be post-append), then the
            # targets in reverse order — see _run_update's ordering note
            self._fk_undo_restore(stmt_undo)
            for t2, blocks2, dicts2, mod2 in reversed(saved):
                t2.replace_blocks(blocks2, modified_rows=mod2)
                t2.dictionaries = dicts2
            raise
        clear_scan_cache()
        return Result([], [], affected=affected)

    def _run_delete_multi(self, s: ast.Delete) -> Result:
        """DELETE t1[, t2] FROM <join> / DELETE FROM t USING <join>: one
        SELECT over the join collects each target's matched row handles;
        each target then runs the same masked-delete + referential-action
        protocol as single-table DELETE (reference: buildDelete's
        multi-table path, pkg/planner/core/logical_plan_builder.go)."""
        from tidb_tpu.planner.logical import ROWID_NAME, expose_rowid

        refs = self._refs_map(s.from_refs)
        resolved = []
        for tdb, name in s.targets:
            alias = name.lower()
            if alias not in refs:
                # target named by real table name while FROM uses aliases
                cands = [
                    a for a, tr in refs.items()
                    if tr.name.lower() == alias
                    and (tdb is None or (tr.db or self.db).lower() == tdb.lower())
                ]
                if len(cands) != 1:
                    raise ValueError(f"unknown DELETE target {name!r}")
                alias = cands[0]
            resolved.append(alias)
        # the same table listed twice deletes once
        seen = set()
        resolved = [a for a in resolved if not (a in seen or seen.add(a))]
        items = [
            ast.SelectItem(
                ast.Name(a, ROWID_NAME), alias=f"_h{i}"
            )
            for i, a in enumerate(resolved)
        ]
        sel = ast.Select(items=items, from_=s.from_refs, where=s.where)
        with expose_rowid(resolved):
            r = self._run_select(sel)

        # Phase A: all explicit target deletions against pre-statement
        # row positions; Phase B: referential actions afterwards, so a
        # cascade into a later target's table can't shift its handles.
        total = 0
        undo: list = []
        deferred: list = []
        try:
            for i, alias in enumerate(resolved):
                tr = refs[alias]
                db = (tr.db or self.db).lower()
                t = self._resolve_table_for_write(db, tr.name)
                handles = {
                    int(row[i]) for row in r.rows if row[i] is not None
                }
                if not handles:
                    continue
                hs = np.fromiter(handles, dtype=np.int64)
                masks = []
                base = 0
                for b in t.blocks():
                    m = np.zeros(b.nrows, dtype=bool)
                    local = hs[(hs >= base) & (hs < base + b.nrows)] - base
                    m[local] = True
                    masks.append(m)
                    base += b.nrows
                self._delete_masked(
                    t, db, tr.name, masks, len(handles),
                    undo=undo, deferred=deferred,
                )
                total += len(handles)
            for actions in deferred:
                actions()
        except BaseException:
            self._fk_undo_restore(undo)
            raise
        clear_scan_cache()
        return Result([], [], affected=total)

    # ------------------------------------------------------------------
    def _run_plan_replayer(self, s: ast.PlanReplayer) -> Result:
        """PLAN REPLAYER DUMP EXPLAIN <stmt>: zip of schema DDL, stats,
        variables, bindings, the SQL and its EXPLAIN (reference:
        optimizor/plan_replayer.go). Returns the zip path."""
        from tidb_tpu.utils.planreplayer import dump_plan_replayer

        explain = self._run_explain(ast.Explain(s.stmt))
        tables: list = []
        for ref in ast.iter_table_refs(s.stmt):
            key = ((ref.db or self.db).lower(), ref.name.lower())
            if key not in tables and self.catalog.has_table(*key):
                tables.append(key)
        fn = dump_plan_replayer(self, s.sql_text, tables, explain.rows)
        return Result(["File"], [(fn,)])

    def attach_dcn_scheduler(self, scheduler) -> None:
        """Attach a DCNFragmentScheduler: EXPLAIN ANALYZE of session
        statements routes through scheduler.explain_analyze (the
        distributed plan tree — per-host fragment rows, Shuffle
        exchange rows), and fragmentable/shuffleable SELECTs execute
        across the worker fleet (PR 6, _try_dcn_select). CONTRACT:
        attaching asserts the workers hold copies of the scanned user
        tables as of the deterministic load (dcn_worker's model);
        with the HTAP delta tier enabled (tidb_tpu_delta_store, the
        default) coordinator DML captures into the catalog's
        DeltaStore, replicates to delta-replica workers over the
        engine-RPC seam, and routed reads merge a snapshot-isolated
        (fold, seq) window under the tidb_tpu_read_freshness mode —
        so writes no longer silently diverge routed SELECTs.
        Transactions, stale reads, system schemas and internal dbs
        always run locally, and a fleet dispatch failure falls back
        to local execution. Pass None to detach."""
        self.dcn_scheduler = scheduler
        if scheduler is None:
            return
        try:
            enabled = str(
                self.vars.get("tidb_tpu_delta_store")
            ).lower() not in ("0", "off", "false")
        except KeyError:
            enabled = True
        if not enabled or not hasattr(scheduler, "attach_delta"):
            return
        from tidb_tpu.storage.delta import DeltaStore

        store = DeltaStore.attach(self.catalog)
        scheduler.attach_delta(
            store,
            compact_interval_s=float(
                self.vars.get("tidb_tpu_delta_compact_interval_s")
            ),
            compact_depth=int(
                self.vars.get("tidb_tpu_delta_compact_depth")
            ),
        )

    def _run_explain(self, s: ast.Explain) -> Result:
        if not isinstance(s.stmt, (ast.Select, ast.Union, ast.With)):
            raise ValueError("EXPLAIN supports SELECT/UNION/WITH")
        plan = build_query(s.stmt, self.catalog, self.db, self._scalar_subquery)
        if s.analyze:
            from tidb_tpu.obs.flight import FLIGHT

            sched = getattr(self, "dcn_scheduler", None)
            if sched is not None:
                from tidb_tpu.planner.fragmenter import Unschedulable

                try:
                    from tidb_tpu.utils.metrics import sql_digest

                    _cols, _rows, lines = sched.explain_analyze(
                        plan, delta_seq=self._delta_read_seq(sched),
                        # the INNER statement's digest: feedback-seeded
                        # planning applies to EXPLAIN ANALYZE too, so
                        # the adaptive= marker is inspectable
                        digest=sql_digest(
                            getattr(s.stmt, "_source_sql", None) or ""
                        ),
                    )
                    lines = lines + _compile_cost_lines()
                    # the instrumented lines ARE the plan capture: an
                    # over-threshold EXPLAIN ANALYZE's slow-log entry
                    # carries the genuine distributed EXPLAIN ANALYZE
                    FLIGHT.note_plan_text("\n".join(lines))
                    return Result(["plan"], [(l,) for l in lines])
                except Unschedulable:
                    # plans that cannot cross the engine seam at all
                    # (GROUP_CONCAT host-assisted shapes) fall back to
                    # the local instrumented run
                    pass
            _out, _dicts, lines = self.executor.run_analyze(plan)
            lines = lines + _compile_cost_lines(self.executor, plan)
            FLIGHT.note_plan_text("\n".join(lines))
            return Result(["plan"], [(l,) for l in lines])
        from tidb_tpu.planner.cardinality import est_rows

        est_rows(plan, self.catalog)  # annotates .est per node
        lines = []
        # prune display must resolve versions the way execution will
        # (txn pins / stale reads), or EXPLAIN disagrees with the run
        _render_plan(
            plan, 0, lines, catalog=self.catalog,
            resolver=self._resolve_table_for_read,
        )
        return Result(["plan"], [(l,) for l in lines])


def _compile_cost_lines(executor=None, plan=None) -> List[str]:
    """EXPLAIN ANALYZE compile row: the statement's summed XLA compile
    cost analysis (obs/engine_watch.py — flops, bytes accessed, output
    bytes harvested from the lowered programs this statement compiled).
    The instrumented EXPLAIN ANALYZE run itself executes EAGER (no
    jit), so when this statement compiled nothing the row falls back
    to the PLAN SIGNATURE's cached per-digest cost (``cached=1``) —
    the warm-plan case where the interesting compile already happened.
    Empty when neither exists: the row reports measured analyses,
    never an estimate."""
    from tidb_tpu.obs.engine_watch import ENGINE_WATCH

    cost = ENGINE_WATCH.current_compile_cost()
    cached = False
    if not cost and executor is not None and plan is not None:
        try:
            sig = executor.watch_sig(executor._cache_key(plan))
            for phase in ("steady", "discover"):
                c = ENGINE_WATCH.cost_for_sig((phase, sig))
                if c:
                    cost, cached = dict(c), True
                    break
        except Exception:
            cost = {}
    if not cost:
        return []
    head = (
        "XLACompile cached=1" if cached
        else f"XLACompile compiles={int(cost.get('compiles', 0))}"
    )
    parts = [head]
    for key in ("flops", "bytes_accessed", "output_bytes"):
        if key in cost:
            parts.append(f"{key}={cost[key]:.0f}")
    return [" ".join(parts)]


def _dcn_runtime_lines(lq) -> List[str]:
    """Distributed runtime summary of one routed query's stats
    snapshot ({"shuffle": ..., "fragments": [...]}), appended to
    slow-log plan captures so an over-threshold DCN statement's entry
    reads like its distributed EXPLAIN ANALYZE without re-running the
    query instrumented. Rendered LAZILY (the capture path only) by
    the SAME functions EXPLAIN ANALYZE uses (planner/physical.py
    _merge_shuffle_stats/_merge_frag_stats over an empty tree) — one
    DCNShuffle/Fragment# grammar, never two."""
    from tidb_tpu.planner.physical import (
        _merge_frag_stats,
        _merge_shuffle_stats,
    )

    lq = lq or {}
    delta_lines = []
    if lq.get("delta"):
        d = lq["delta"]
        delta_lines = [
            f"DeltaMerge depth={int(d.get('depth', 0))} "
            f"ins_rows={int(d.get('ins_rows', 0))} "
            f"delete_keys={int(d.get('del_keys', 0))}"
        ]
    if lq.get("shuffle_stages"):
        # shuffle DAG: one DCNShuffle row PER STAGE (stage=i/n,
        # exchange kind, per-stage phase seconds), same grammar
        lines: List[str] = []
        frags = lq.get("fragments") or []
        for si, stage in enumerate(lq["shuffle_stages"]):
            lines = _merge_shuffle_stats(
                lines, stage,
                [f for f in frags if f.get("stage", 0) == si],
            )
        return lines + delta_lines
    if lq.get("shuffle"):
        return _merge_shuffle_stats(
            [], lq["shuffle"], lq.get("fragments") or []
        ) + delta_lines
    if lq.get("fragments"):
        return _merge_frag_stats([], lq["fragments"]) + delta_lines
    return delta_lines


_cte_scratch_seq = itertools.count(1)


def _refs_table(node, name: str) -> bool:
    """Does this AST subtree reference table ``name`` (unqualified)?"""
    import dataclasses as _dc

    if isinstance(node, ast.TableRef):
        if node.db is None and node.name.lower() == name.lower():
            return True
    if _dc.is_dataclass(node) and not isinstance(node, type):
        for f in _dc.fields(node):
            if _refs_table(getattr(node, f.name), name):
                return True
    elif isinstance(node, (list, tuple)):
        return any(_refs_table(x, name) for x in node)
    return False


def _render_plan(plan, depth, out: List[str], catalog=None, resolver=None):
    from tidb_tpu.planner import logical as L

    pad = "  " * depth
    name = type(plan).__name__
    detail = ""
    if isinstance(plan, L.Scan):
        detail = f" table={plan.db}.{plan.table} cols={len(plan.columns)}"
    elif isinstance(plan, L.Selection):
        detail = f" pred={plan.predicate!r}"
        if catalog is not None and isinstance(plan.child, L.Scan):
            from tidb_tpu.planner.physical import _extract_pk_range

            r = _extract_pk_range(
                plan.predicate,
                plan.child,
                lambda db, tb: (catalog.table(db, tb), 0),
            )
            if r is not None:
                col, lo, hi = r
                detail += (
                    f" access=IndexRangeScan({col} in [{lo}, {hi}])"
                )
            else:
                from tidb_tpu.planner.physical import _extract_index_merge

                mr = _extract_index_merge(
                    plan.predicate,
                    plan.child,
                    lambda db, tb: (catalog.table(db, tb), 0),
                )
                if mr is not None:
                    def b(v, open_s):
                        return open_s if abs(v) >= (1 << 62) else v

                    spans = " | ".join(
                        f"{c}[{b(lo, '-inf')},{b(hi, 'inf')}]"
                        for c, lo, hi in mr
                    )
                    detail += f" access=IndexMerge(union: {spans})"
            from tidb_tpu.planner.physical import _prune_partitions

            def _res(db, tb):
                if resolver is not None:
                    return resolver(db, tb)
                t2 = catalog.table(db, tb)
                return t2, t2.version

            pp = _prune_partitions(plan.predicate, plan.child, _res)
            if pp is not None:
                t2, v2 = _res(plan.child.db, plan.child.table)
                defs2 = t2.partition_defs_at(v2)
                names = (
                    [f"p{i}" for i in range(int(defs2[2]))]
                    if defs2[0] == "hash"
                    else [n for n, _u in defs2[2]]
                )
                detail += (
                    " partitions="
                    + "[" + ",".join(names[i] for i in pp) + "]"
                )
    elif isinstance(plan, L.Aggregate):
        detail = f" groups={[n for n, _ in plan.group_exprs]} aggs={[f'{f}({n})' for n, f, _, _ in plan.aggs]}"
    elif isinstance(plan, L.JoinPlan):
        detail = f" kind={plan.kind} keys={len(plan.equi_keys)}"
        if plan.broadcast:
            detail += f" broadcast={plan.broadcast}"
    elif isinstance(plan, L.Sort):
        detail = f" keys={len(plan.keys)}"
    elif isinstance(plan, L.Limit):
        detail = f" limit={plan.count} offset={plan.offset}"
    elif isinstance(plan, L.Projection):
        detail = f" exprs={[n for n, _ in plan.exprs]}{' +base' if plan.additive else ''}"
    est = getattr(plan, "est", None)
    if est is not None:
        detail += f" est={est:.0f}"
    out.append(pad + name + detail)
    for attr in ("child", "left", "right"):
        c = getattr(plan, attr, None)
        if c is not None:
            _render_plan(c, depth + 1, out, catalog=catalog, resolver=resolver)
    for c in getattr(plan, "children", []) or []:
        _render_plan(c, depth + 1, out, catalog=catalog, resolver=resolver)
