"""Physical compilation + execution of logical plans.

Reference: pkg/executor/builder.go (executorBuilder.build) + unistore's
closure executor (cophandler/closure_exec.go:165,470) which fuses a whole
DAG into one callable — here the whole plan compiles into ONE jitted XLA
program per (plan fingerprint, capacity vector), the TPU-native answer to
the reference's volcano iterator tree, and the engine side of its plan
cache (pkg/planner/core/plan_cache.go:231).

Execution is two-phase:

1. **Discovery (eager)**: the plan function runs op-by-op with a default
   capacity vector; every Aggregate/Join node reports its true output
   cardinality. Overflows bump that node's capacity tile and re-run.
2. **Steady state (jitted)**: the discovered capacities are frozen and
   the whole plan becomes one jit-compiled program over the scan batches.
   Each run still returns the cardinality scalars; if data growth makes a
   node overflow its tile, execution transparently falls back to
   discovery and re-jits at the larger tile.

Dynamic result sizes are thereby handled with static shapes only —
SURVEY.md §7 "hard parts" #3/#7.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.chunk import Batch, DevCol, pad_capacity
from tidb_tpu.dtypes import Kind
from tidb_tpu.executor import (
    AggDesc,
    equi_join,
    filter_batch,
    group_aggregate,
    limit_op,
    order_by,
)
from tidb_tpu.executor.aggregate import WIDTH_STALE as _WIDTH_STALE
from tidb_tpu.executor.join import expansion_ledger
from tidb_tpu.executor.sortops import grouping_ledger
from tidb_tpu.expression import compile_expr
from tidb_tpu.expression.expr import ColumnRef, Expr
from tidb_tpu.obs.engine_watch import ENGINE_WATCH, watched_jit
from tidb_tpu.obs.flight import FLIGHT
from tidb_tpu.planner import logical as L
from tidb_tpu.storage import scan_table
from tidb_tpu.utils import racecheck

Dicts = Dict[str, np.ndarray]
# node function: (inputs by scan id, caps by node id) -> (batch, needs dict)
PlanFn = Callable[[Dict[int, Batch], Dict[int, int]], Tuple[Batch, Dict[int, jax.Array]]]


class ExecError(RuntimeError):
    pass


class StaleWidthsError(RuntimeError):
    """A compiled program's baked key-width bounds no longer cover the
    data (rows grew past the bounds observed at compile time). The
    executor recompiles the plan against fresh Table.col_bounds."""


# reserved dicts-map key prefix for integer-column value bounds (column
# names never contain NUL); see Table.col_bounds and _key_width
_BOUNDS_PREFIX = "\x00b\x00"
# reserved prefix marking a column as unique-valued (single-column PK /
# unique index at scan, GROUP BY key of a single-key aggregate). Joins
# use it to prove a 1:1 build side (dense unique join); it survives
# row-filtering operators and is stripped where rows can duplicate.
_UNIQ_PREFIX = "\x00u\x00"


def _strip_uniq(dicts: Dicts) -> Dicts:
    return {k: v for k, v in dicts.items() if not k.startswith(_UNIQ_PREFIX)}


def _merge_join_dicts(ldicts: Dicts, rdicts: Dicts, lu: bool, ru: bool) -> Dicts:
    """Join output dictionaries with SELECTIVE uniqueness survival: an
    inner join duplicates one side's rows only when the OTHER side's
    equi key repeats, so a provably-unique build key (ru for the left
    side's entries, lu for the right's) preserves that side's
    uniqueness proofs. Keeps chained star joins (Q5's
    region->nation->supplier->lineitem) on the dense 1:1 join path
    instead of degrading to probe-chain hashing after the first hop."""
    out: Dicts = {}
    for k, v in ldicts.items():
        if k.startswith(_UNIQ_PREFIX) and not ru:
            continue
        out[k] = v
    for k, v in rdicts.items():
        if k.startswith(_UNIQ_PREFIX) and not lu:
            continue
        out[k] = v
    return out


class _LazyBounds:
    """Deferred Table.col_bounds lookup pinned to a (table, col, version):
    scans emit one per integer column, but the min/max host pass only
    runs if a packed-aggregation or dense-join site consumes it (the
    Table caches the result per version for repeat consumers)."""

    __slots__ = ("table", "col", "version", "nid")

    def __init__(self, table, col, version, nid=None):
        self.table = table
        self.col = col
        self.version = version
        # scan node id: lets consumers that bake these bounds register a
        # fetch-time re-check against the scan's resolved version
        self.nid = nid

    def get(self):
        return self.table.col_bounds(self.col, self.version)


class _GridBounds:
    """A key's bounds as a DISTINCT publishes them: on `_grid`, not the
    data set's own extremes. Which values of a column a distinct keeps
    (the returned orders of a set of sales) is the data set's, and a
    dense join above it bakes the bounds it is handed, so exact ones
    would make a program per data set (ROADMAP M1). Resolved lazily,
    like the storage bounds it widens."""

    __slots__ = ("entry",)

    def __init__(self, entry):
        self.entry = entry

    def get(self):
        cb = _resolve_bounds(self.entry)
        return None if cb is None else _grid(int(cb[0]), int(cb[1]))


def _grid(lo: int, hi: int) -> Tuple[int, int]:
    """[lo, hi] widened to what two data sets of one scale share: the
    lower bound down to a multiple of the power of two over the span,
    the upper one to the last value as many bits then hold."""
    step = (hi - lo + 1).bit_length()
    lo = (lo >> step) << step
    return lo, lo + (1 << (hi - lo + 1).bit_length()) - 1


def _resolve_bounds(entry):
    if entry is None:
        return None
    if isinstance(entry, (_LazyBounds, _GridBounds)):
        return entry.get()
    return entry


def _stale_only(total):
    """Pass the WIDTH_STALE sentinel through a needs slot, 0 otherwise."""
    return jnp.where(total >= _WIDTH_STALE, total, jnp.int64(0))


@dataclasses.dataclass
class ScanSite:
    node_id: int
    db: str
    table: str
    alias: str
    columns: List[str]
    # PK range pushdown (reference: point_get.go:132 + pkg/util/ranger):
    # (pk column, lo, hi) in raw encoded units — the fetch gathers only
    # matching rows via the table's sorted index instead of a full scan
    pk_range: Optional[Tuple[str, int, int]] = None
    # partition pruning (reference partitionProcessor,
    # pkg/planner/core/rule_partition_processor.go): partition ids the
    # predicate can reach; None = all partitions scan
    partitions: Optional[Tuple[int, ...]] = None
    # index-merge UNION reader (pkg/executor/index_merge_reader.go:88):
    # OR-of-indexable-ranges — the fetch unions each range's sorted-
    # index row ids (dedup via np.unique) and gathers once; the
    # original predicate still filters, so over-approximation is safe
    merge_ranges: Optional[Tuple[Tuple[str, int, int], ...]] = None
    # cross-host fragment slice (idx, n): this engine scans only every
    # n-th row starting at idx (planner/fragmenter.py dispatch)
    frag: Optional[Tuple[int, int]] = None


@dataclasses.dataclass
class CompiledQuery:
    fn: PlanFn
    scans: List[ScanSite]
    sized_nodes: List[int]  # node ids with a capacity knob
    default_caps: Dict[int, int]
    out_dicts: Dicts
    # (node id, Staged.key): keyed staged batches fed as runtime inputs
    # per run — the shuffle consumer's stage partitions, so one compile
    # serves every stage of the plan shape
    staged_sites: List[Tuple[int, str]] = dataclasses.field(
        default_factory=list
    )
    # steady state: the last discovered caps, a warm-start hint for
    # discovery
    caps: Optional[Dict[int, int]] = None
    # the CONSISTENT steady snapshot: (jitted, caps, input_shape_key)
    # published as ONE atomic tuple after the post-discovery
    # verification run passes. Concurrent executors sharing this cq
    # (the cross-session plan cache) read the tuple, never the loose
    # hint above — a reader pairing thread A's program with thread B's
    # caps could accept a silently-truncated output (the program's true
    # cardinalities are checked against the caps IT was compiled for).
    steady: Optional[tuple] = None
    # set when a post-shrink steady run overflowed (e.g. a probe chain no
    # longer fit the smaller hash table): discovery stops shrinking caps
    # for this plan so grow/shrink cannot oscillate
    no_shrink: bool = False
    # mesh mode: distribution of the root output ('shard' = row-partitioned
    # over the mesh axis, 'repl' = identical on every device)
    out_tag: str = "shard"
    # per sized-node estimated row width in bytes (for quota admission)
    widths: Dict[int, int] = dataclasses.field(default_factory=dict)
    # (scan node id, column) pairs whose validity was folded into the row
    # mask because the column held no NULLs at compile time; re-checked
    # at fetch, violation -> StaleWidthsError recompile
    nonnull: List[Tuple[int, str]] = dataclasses.field(default_factory=list)
    # (scan node id, column, lo, hi): compile-time column bounds that
    # proved a decimal SUM safe for single-lane int64 accumulation
    # (AggDesc.wide narrowing); re-checked at fetch like nonnull —
    # growth past the baked interval recompiles, never silently wraps
    bound_checks: List[Tuple[int, str, int, int]] = dataclasses.field(
        default_factory=list
    )
    # sized nodes that are an exchange's bucket tile (mesh mode): a
    # bump of one is counted as an exchange overflow retry
    exchange_nids: frozenset = frozenset()
    # sized nodes that are an expanding join's output tile (a build key
    # that is not unique): a bump of one is counted as an expansion
    # overflow retry
    expand_nids: set = dataclasses.field(default_factory=set)
    # sized node -> the smallest tile discovery shrinks it to. A tile
    # that follows a count of a few dozen rows makes one program for a
    # data set with 40 of them and another for one with 70; where a
    # small tile buys nothing the node keeps this many slots
    floors: Dict[int, int] = dataclasses.field(default_factory=dict)
    # the output's first compaction tile, from the root's estimated
    # rows (default_caps holds the knobs' first tiles, from the
    # estimates of theirs)
    first_out_cap: int = 0
    # plan signature for the engine watch: a second jit trace for the
    # same sig is a retrace (obs/engine_watch.py)
    sig: Optional[object] = None

    @property
    def first_caps(self) -> Dict[int, int]:
        """Every knob's first tile: default_caps, under the name the
        benchmark's tests read it by."""
        return self.default_caps



def _schema_width(schema) -> int:
    """Bytes per row of a plan schema (data + validity per column)."""
    total = 1  # row_valid bit (byte on device)
    for c in schema:
        try:
            total += c.type.np_dtype.itemsize + 1
        except Exception:
            total += 9
    return total

def plan_fingerprint(plan: L.LogicalPlan) -> str:
    """Deterministic structural key for the plan cache."""
    parts: List[str] = []

    def walk(p):
        parts.append(type(p).__name__)
        if isinstance(p, L.Scan):
            parts.append(f"{p.db}.{p.table} as {p.alias} {sorted(p.columns)}")
            if p.frag is not None:
                # two hosts' fragment plans differ ONLY in the slice —
                # without this the plan cache would serve host 0's scan
                # to host 1
                parts.append(f"frag{p.frag[0]}/{p.frag[1]}")
        elif isinstance(p, L.Selection):
            parts.append(repr(p.predicate))
        elif isinstance(p, L.Projection):
            parts.append(repr(p.exprs) + str(p.additive))
        elif isinstance(p, L.Aggregate):
            parts.append(repr(p.group_exprs) + repr(p.aggs))
        elif isinstance(p, L.JoinPlan):
            parts.append(
                p.kind
                + repr(p.equi_keys)
                + repr(p.residual)
                + str(p.null_aware)
                + str(p.broadcast)
                # mark joins: the mark column name is part of the output
                # schema — without it two same-shaped IN-subqueries would
                # collide in the subtree memo (_build)
                + str(getattr(p, "mark_name", None))
                # what a mesh join's exchange carries: two joins alike
                # but for their readers must not share one subtree
                + repr(p.needs and [sorted(side) for side in p.needs])
            )
        elif isinstance(p, L.Sort):
            parts.append(repr(p.keys))
        elif isinstance(p, L.Window):
            parts.append(
                repr(p.partition_exprs) + repr(p.order_exprs) + repr(p.descs)
            )
        elif isinstance(p, L.Limit):
            parts.append(f"{p.count},{p.offset}")
        elif isinstance(p, L.Staged):
            if p.key is not None:
                # keyed staged input: the batch is a runtime input, so
                # the fingerprint carries everything the compiled
                # program bakes in — shape (capacity + column dtypes)
                # and string dictionary CONTENT (key-alignment LUTs are
                # compile-time) — and two stages with matching shapes
                # share one program
                import hashlib as _hashlib

                b = p.batch
                # LOGICAL types (kind + scale) must key too: two
                # DECIMAL scales share one int64 physical dtype but
                # compile scale-dependent programs, and scan-free
                # staged plans carry no schema-version entries to
                # catch an ALTER
                ltypes = {
                    c.internal: c.type
                    for c in getattr(p.schema, "cols", [])
                }

                def lsig(n):
                    t = ltypes.get(n)
                    return (
                        f"{t.kind.name}s{t.scale}"
                        if t is not None else "?"
                    )

                colsig = ",".join(
                    f"{n}:{dc.data.dtype.str}:{lsig(n)}"
                    for n, dc in sorted(b.cols.items())
                )
                dsig = ";".join(
                    n + "="
                    + _hashlib.blake2b(
                        "\x00".join(map(str, d.tolist())).encode(),
                        digest_size=8,
                    ).hexdigest()
                    for n, d in sorted((p.dicts or {}).items())
                    if d is not None
                )
                parts.append(
                    f"staged@{p.key}#cap{b.capacity}#{colsig}#{dsig}"
                )
            else:
                parts.append(f"staged#{p.nonce}")
        kids = _plan_children(p)
        # child count disambiguates flat vs nested n-ary nodes
        # (UnionAll([U([A,B]),C]) vs UnionAll([U([A,B,C])]))
        parts.append(f"#{len(kids)}")
        for c in kids:
            walk(c)

    walk(plan)
    return "|".join(parts)


def _plan_shareable(plan: L.LogicalPlan) -> bool:
    """Whether a compiled plan may cross the executor boundary via the
    process-wide SharedPlanCache. Only DATA-INDEPENDENT compiles may: a
    non-keyed Staged leaf bakes its batch into the compiled closure
    under a nonce-only fingerprint, and nonces are unique per
    ALLOCATOR, not per process — two in-process shuffle workers mint
    the same nonce and would serve each other's baked partitions as
    results. Keyed Staged leaves are fine: their batches are runtime
    inputs (staged_sites) and their fingerprints carry shape + dict
    content. Scans are fine: data is resolved from the RUNNING
    executor's catalog per run, and baked string LUTs are keyed by the
    table's process-unique uid + version."""
    if isinstance(plan, L.Staged) and plan.key is None:
        return False
    return all(_plan_shareable(c) for c in _plan_children(plan))


def _worth_sharing(plan) -> bool:
    """Subtrees worth memoizing for common-subtree sharing: a join or
    aggregate anywhere beneath (cheap nodes cost less than the
    fingerprint), and never a bare Scan root (pending pushdown state)."""
    if isinstance(plan, L.Scan):
        return False

    def heavy(p):
        if isinstance(p, (L.JoinPlan, L.Aggregate, L.Window, L.Sort)):
            return True
        return any(heavy(c) for c in _plan_children(p))

    return heavy(plan)


def _share_result(fn, registry=None):
    """Per-trace result memo: when the same compiled subtree fn is
    invoked twice with the same (inputs, caps) — two call sites sharing
    one memo entry — the second call returns the FIRST call's traced
    arrays, so the jaxpr (and the compiled program) contains one copy
    of the subtree's work. Keyed by inputs-dict identity (fresh per
    trace/execution) + the static caps; holds only the latest entry."""
    memo: list = []

    def shared(inputs, caps):
        # (registered in the compiler's _share_memos; the root fn wipes
        # every memo after each invocation — see compile())
        capskey = tuple(sorted(caps.items()))
        for (kin, kcaps), v in memo:
            if kin is inputs and kcaps == capskey:
                return v
        v = fn(inputs, caps)
        del memo[:]
        memo.append(((inputs, capskey), v))
        return v

    if registry is not None:
        registry.append(memo)
    return shared


def _plan_children(p) -> List[L.LogicalPlan]:
    out = []
    for attr in ("child", "left", "right"):
        c = getattr(p, attr, None)
        if c is not None:
            out.append(c)
    out.extend(getattr(p, "children", []) or [])
    return out


def _staged_inputs(plan) -> Optional[Dict[str, "Batch"]]:
    """Staged.key -> batch for every keyed staged node in the plan —
    the runtime inputs a cached compile of this plan shape consumes
    (None when the plan has none, the overwhelmingly common case)."""
    out: Dict[str, Batch] = {}

    def walk(p):
        if isinstance(p, L.Staged) and p.key is not None:
            out[p.key] = p.batch
        for c in _plan_children(p):
            walk(c)

    walk(plan)
    return out or None



def _prune_partitions(pred, scan: "L.Scan", resolver):
    """Partition ids of `scan`'s table the predicate can reach, or None
    (all). Range partitioning prunes by bound comparison against the
    VALUES LESS THAN ladder; hash partitioning prunes on equality.
    Reference: partitionProcessor (rule_partition_processor.go)."""
    if "_tidb_rowid" in scan.columns:
        # multi-table DML handle scans: row ids address the FULL block
        # concatenation, so the scan must never see a partition subset
        return None
    try:
        t, _v = resolver(scan.db, scan.table)
    except Exception:
        return None
    # defs at the SNAPSHOT version: a pinned reader must prune with the
    # ladder its blocks were tagged under, not post-ALTER defs
    try:
        part = t.partition_defs_at(_v)
    except AttributeError:
        part = getattr(t, "partition", None)
    if part is None or pred is None:
        return None
    pcol = part[1]
    r = _extract_col_range(pred, scan, t, pcol, open_ok=True)
    if r is None:
        return None
    _col, lo, hi = r
    if lo is not None and hi is not None and lo > hi:
        return ()
    nparts = (
        int(part[2]) if part[0] == "hash" else len(part[2])
    )
    if part[0] == "list":
        keep = []
        for i, (_n, vals) in enumerate(part[2]):
            hit = any(
                v is not None
                and (lo is None or v >= lo)
                and (hi is None or v <= hi)
                for v in vals
            )
            if hit:
                keep.append(i)
        return None if len(keep) == nparts else tuple(keep)
    if part[0] == "hash":
        # hash pruning needs a small CLOSED range (point lookups mostly)
        n = int(part[2])
        if lo is None or hi is None or hi - lo + 1 >= n:
            return None
        return tuple(sorted({(v % n + n) % n for v in range(lo, hi + 1)}))
    uppers = [u for _n, u in part[2]]
    keep = []
    lower = None
    for i, u in enumerate(uppers):
        # partition i holds [lower, u)
        p_lo = lower
        p_hi = None if u is None else u - 1
        lo_ok = lo is None or p_hi is None or lo <= p_hi
        hi_ok = hi is None or p_lo is None or hi >= p_lo
        if lo_ok and hi_ok:
            keep.append(i)
        lower = u
    if len(keep) == nparts:
        return None
    return tuple(keep)


def _extract_pk_range(pred, scan: "L.Scan", resolver):
    """Predicate -> (col, lo, hi) raw-encoded range over the best access
    path: the single-column PK or any single-leading-column secondary
    index whose column is bounded on both sides by the predicate (the
    point-get / IndexRangeScan case, pkg/executor/point_get.go:132 +
    pkg/util/ranger). When several candidates qualify the narrowest
    range wins. Remaining conjuncts still filter the fetched batch, so
    over-extraction is impossible."""
    if "_tidb_rowid" in scan.columns:
        # DML handle scans address full-scan row positions; an index
        # range fetch would renumber them
        return None
    try:
        t, _v = resolver(scan.db, scan.table)
    except Exception:
        return None
    candidates = _index_candidates(t)
    best = None
    for col in candidates:
        r = _extract_col_range(pred, scan, t, col)
        if r is None:
            continue
        width = r[2] - r[1]
        if best is None or width < best[0]:
            best = (width, r)
    return best[1] if best else None


def _index_candidates(t) -> list:
    """Single-column access paths: the one-column PK plus leading
    columns of PUBLIC indexes (shared by range and merge extraction)."""
    candidates = []
    pk = t.schema.primary_key
    if pk and len(pk) == 1:
        candidates.append(pk[0])
    idx_map = (
        t.public_indexes()
        if hasattr(t, "public_indexes")
        else getattr(t, "indexes", {})
    )
    for icols in idx_map.values():
        if icols and icols[0] not in candidates:
            candidates.append(icols[0])
    return candidates


def _extract_index_merge(pred, scan: "L.Scan", resolver):
    """OR-of-indexable-ranges -> tuple of (col, lo, hi) whose UNION
    covers every accepting row (the IndexMerge union reader,
    pkg/executor/index_merge_reader.go:88). Sound because each
    disjunct's range over-approximates that disjunct and the original
    predicate re-filters the fetched batch; extraction fails — full
    scan — if ANY disjunct is not range-expressible on an indexed
    column (a non-indexable disjunct could accept rows outside every
    range). AND-of-ranges (intersection) needs no special reader here:
    the single-range path takes the narrowest conjunct and the filter
    applies the rest."""
    from tidb_tpu.expression.expr import Func

    if "_tidb_rowid" in scan.columns:
        return None
    try:
        t, _v = resolver(scan.db, scan.table)
    except Exception:
        return None
    candidates = _index_candidates(t)
    if not candidates:
        return None

    def conjs(e):
        if isinstance(e, Func) and e.op == "and":
            return conjs(e.args[0]) + conjs(e.args[1])
        return [e]

    def disjuncts(e):
        if isinstance(e, Func) and e.op == "or":
            return disjuncts(e.args[0]) + disjuncts(e.args[1])
        return [e]

    # one OR-shaped conjunct suffices: the other conjuncts only filter
    # further, so the union over this OR stays a superset of the result
    for c in conjs(pred):
        ds = disjuncts(c)
        if len(ds) < 2:
            continue
        ranges = []
        for d in ds:
            best = None
            for col in candidates:
                r = _extract_col_range(d, scan, t, col, open_ok=True)
                if r is not None:
                    # open sides take FULL int64 extremes — the
                    # union reader must never under-approximate, and
                    # values beyond any smaller sentinel would be
                    # silently excluded by the inclusive range fetch
                    col_, lo, hi = r
                    lo = -(1 << 63) if lo is None else lo
                    hi = (1 << 63) - 1 if hi is None else hi
                    width = hi - lo
                    if best is None or width < best[0]:
                        best = (width, (col_, lo, hi))
            if best is None:
                ranges = None
                break
            ranges.append(best[1])
        if ranges:
            return tuple(ranges)
    return None


def _extract_col_range(pred, scan: "L.Scan", t, pkcol: str, open_ok=False):
    typ = t.schema.types.get(pkcol)
    if typ is None or typ.kind not in (
        Kind.INT, Kind.DATE, Kind.DECIMAL, Kind.DATETIME,
    ):
        return None
    internal = f"{scan.alias}.{pkcol}"
    from tidb_tpu.expression.expr import ColumnRef, Func, Literal

    def conjuncts(e):
        if isinstance(e, Func) and e.op == "and":
            return conjuncts(e.args[0]) + conjuncts(e.args[1])
        return [e]

    import math

    def scaled(v):
        """Literal -> exact value in raw encoded units (float; fractional
        when the literal falls between representable values). DATE/
        DATETIME literals may still carry their source string (typed
        temporal literals skip the string-vs-temporal coercion)."""
        if isinstance(v, str) and typ.kind in (Kind.DATE, Kind.DATETIME):
            from tidb_tpu.dtypes import date_to_days, datetime_to_micros

            try:
                if typ.kind == Kind.DATE:
                    return float(date_to_days(v))
                return float(datetime_to_micros(v))
            except Exception:
                return None
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            return None
        if typ.kind == Kind.DECIMAL:
            return float(v) * 10**typ.scale
        return float(v)

    lo, hi = None, None
    flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq"}

    def bound_hi(x, strict):
        # col < x  ->  col <= ceil(x)-1 ; col <= x -> col <= floor(x)
        return int(math.ceil(x)) - 1 if strict else int(math.floor(x))

    def bound_lo(x, strict):
        # col > x  ->  col >= floor(x)+1 ; col >= x -> col >= ceil(x)
        return int(math.floor(x)) + 1 if strict else int(math.ceil(x))

    for c in conjuncts(pred):
        if not (isinstance(c, Func) and len(c.args) >= 2):
            continue
        op = c.op
        a, b = c.args[0], c.args[1]
        if op == "between" and len(c.args) == 3:
            if (
                isinstance(a, ColumnRef)
                and a.name == internal
                and isinstance(c.args[1], Literal)
                and isinstance(c.args[2], Literal)
            ):
                from tidb_tpu.expression.kernels import baked_value

                x, y = scaled(baked_value(c.args[1])), scaled(
                    baked_value(c.args[2])
                )
                if x is not None and y is not None:
                    xl, yh = bound_lo(x, False), bound_hi(y, False)
                    lo = xl if lo is None else max(lo, xl)
                    hi = yh if hi is None else min(hi, yh)
            continue
        if op not in ("eq", "lt", "le", "gt", "ge"):
            continue
        if isinstance(a, ColumnRef) and a.name == internal and isinstance(b, Literal):
            pass
        elif isinstance(b, ColumnRef) and b.name == internal and isinstance(a, Literal):
            a, b, op = b, a, flip[op]
        else:
            continue
        from tidb_tpu.expression.kernels import baked_value

        x = scaled(baked_value(b))
        if x is None:
            continue
        if op == "eq":
            if x != int(x):
                return (pkcol, 1, 0)  # empty range: no integer equals x
            xi = int(x)
            lo = xi if lo is None else max(lo, xi)
            hi = xi if hi is None else min(hi, xi)
        elif op in ("lt", "le"):
            y = bound_hi(x, op == "lt")
            hi = y if hi is None else min(hi, y)
        else:
            y = bound_lo(x, op == "gt")
            lo = y if lo is None else max(lo, y)
    if not open_ok and (lo is None or hi is None):
        return None
    if lo is None and hi is None:
        return None
    return (pkcol, lo, hi)



def _expr_abs_bound(e: Expr, dicts: Dicts):
    """Max-abs of an expression's SCALED integer representation via
    interval arithmetic over storage column bounds, or None (unbounded /
    unsupported shape). Returns (bound, [contributing _LazyBounds]).
    Sound only while every referenced column stays inside its
    compile-time bounds — callers must register a fetch-time re-check
    for each returned entry (CompiledQuery.bound_checks)."""
    import math

    from tidb_tpu.expression.expr import Func, Literal

    kind = e.type.kind if e.type is not None else None
    if kind not in (Kind.INT, Kind.DECIMAL, Kind.BOOL):
        return None
    scale = e.type.scale if kind == Kind.DECIMAL else 0
    if isinstance(e, ColumnRef):
        entry = dicts.get(_BOUNDS_PREFIX + e.name)
        cb = _resolve_bounds(entry)
        if cb is None or not isinstance(entry, _LazyBounds):
            return None
        return (max(abs(int(cb[0])), abs(int(cb[1]))), [entry])
    if isinstance(e, Literal):
        if e.param_slot is not None:
            return None  # value changes per EXECUTE; no static bound
        v = e.value
        if isinstance(v, bool):
            v = int(v)
        if not isinstance(v, (int, float)):
            return None
        return (int(math.ceil(abs(v) * 10 ** scale)) + 1, [])
    if isinstance(e, Func) and e.op in ("add", "sub", "mul", "neg"):
        subs = [_expr_abs_bound(a, dicts) for a in e.args]
        if any(s is None for s in subs):
            return None
        if e.op == "neg":
            return subs[0]
        (b1, c1), (b2, c2) = subs

        def sc(a):
            return (
                a.type.scale
                if a.type is not None and a.type.kind == Kind.DECIMAL
                else 0
            )

        s1, s2 = sc(e.args[0]), sc(e.args[1])
        if e.op == "mul":
            # scaled product == product of scaled operands at result
            # scale s1+s2; a result rescaled DOWN is only smaller
            return (b1 * b2, c1 + c2)
        if scale < max(s1, s2):
            return None  # add/sub never narrows scale; bail if odd
        return (b1 * 10 ** (scale - s1) + b2 * 10 ** (scale - s2), c1 + c2)
    return None


def build_agg_parts(plan: "L.Aggregate", dicts, compiler=None):
    """Compile an Aggregate node's pieces: (key fns, key names, packed key
    widths, AggDescs). Shared by the in-plan aggregation node and the
    streamed (chunked) execution path. With a compiler, wide decimal
    sums whose arguments are provably small (interval arithmetic over
    storage bounds) drop to single-lane int64 accumulation, halving the
    reduction passes; the proof is re-checked at every fetch."""
    key_fns = [compile_expr(e, dicts) for _, e in plan.group_exprs]
    key_names = [n for n, _ in plan.group_exprs]
    key_widths = [_key_width(e, dicts) for _, e in plan.group_exprs]
    # collation-correct grouping: dict-coded string keys under a CI
    # collation group by their dense collation RANK (equal-under-
    # collation entries share a rank) instead of the binary dict code,
    # so GROUP BY name merges 'Ann'/'ANN' under *_ci like MySQL
    # (reference pkg/util/collate/collate.go:66 — Key() drives hash).
    # agg_out_dicts applies the matching rank->representative dict.
    for i, (_n, e) in enumerate(plan.group_exprs):
        lr = _collation_rank(e, dicts)
        if lr is None:
            continue
        key_fns[i] = _rank_wrap(key_fns[i], jnp.asarray(lr[0]))
        key_widths[i] = (max(1, int(len(lr[1])).bit_length()), 0)
    descs = []
    for name, func, arg, distinct in plan.aggs:
        fn = compile_expr(arg, dicts) if arg is not None else None
        scale = (
            arg.type.scale
            if arg is not None and arg.type.kind == Kind.DECIMAL
            else 0
        )
        # scale-4+ decimal products (price*(1-disc)*(1+tax)) overflow
        # int64 accumulation at SF100 row counts: use the dual-lane
        # wide accumulator (AggDesc.wide)
        wide = func in ("sum", "avg") and scale >= 4
        pack_bound = None
        if func in ("sum", "avg") and compiler is not None and arg is not None:
            r = _expr_abs_bound(arg, dicts)
            # 2^31 rows is past any single-program tile (int32 row
            # indexing); bound * 2^31 < 2^62 proves no int64 wraparound.
            # The same proof funds the packed (sum,count) single-pass
            # reduction (AggDesc.pack_bound) for ALL integer sums —
            # re-verified against live storage bounds at every fetch.
            # A wider proven bound keeps the wide accumulator and still
            # tells the kernel how many digits the sum's lanes need.
            if r is not None and r[0] < (1 << 62) and all(
                lb.nid is not None for lb in r[1]
            ):
                for lb in r[1]:
                    cb = lb.get()
                    compiler.bound_checks.append(
                        (lb.nid, lb.col, int(cb[0]), int(cb[1]))
                    )
                wide = wide and r[0] >= (1 << 31)
                # the bias the program bakes: the next 2**k - 1 at or
                # above the bound, which packs in as many bits and does
                # not change with a data set's largest value
                pack_bound = (1 << int(r[0]).bit_length()) - 1
        # DISTINCT is a no-op for min/max (duplicate-insensitive); for
        # sum/avg/count the kernel dedupes via representative-row masks
        # (executor/aggregate._distinct_reps)
        d = bool(distinct) and func in ("sum", "avg", "count") and arg is not None
        # MIN/MAX over CI-collated strings must order by collation, not
        # binary code: compose cmp_rank*D + code so the int reduction
        # picks the collation extreme; AggDesc.post decodes the winning
        # member's original dict code (output dict unchanged). COUNT
        # (DISTINCT s) dedupes by equality class; plain COUNT reads
        # only validity and `first` is a row passthrough — both keep
        # raw codes.
        post = None
        if func in ("min", "max") and arg is not None:
            cw = _collation_compose(arg, dicts)
            if cw is not None:
                fn, post = cw[0](fn), cw[1]
        elif func == "count" and distinct and arg is not None:
            lr = _collation_rank(arg, dicts)
            if lr is not None:
                fn = _rank_wrap(fn, jnp.asarray(lr[0]))
        descs.append(
            AggDesc(
                func, fn, name, distinct=d, arg_scale=scale, wide=wide,
                post=post, pack_bound=pack_bound,
            )
        )
    return key_fns, key_names, key_widths, descs


def _collation_compose(e: Expr, dicts):
    """For a CI-collated dict-coded string expr: (wrapper making the
    compiled fn yield cmp_rank*D + code, post decoding code) so MIN/MAX
    order by collation while returning a real dictionary code. None
    when binary / no dictionary."""
    if e.type is None or e.type.kind != Kind.STRING or not e.type.collation:
        return None
    from tidb_tpu.utils import collate as _coll

    if _coll.is_binary(e.type.collation):
        return None
    d = _expr_dict(e, dicts)
    if d is None or not len(d):
        return None
    from tidb_tpu.expression.kernels import _collation_rank_lut

    cr, _keys, _kf = _collation_rank_lut(d, e.type.collation)
    D = int(len(d))

    def wrap(fn):
        def composed(b: Batch) -> DevCol:
            c = fn(b)
            code = jnp.clip(c.data.astype(jnp.int64), 0, D - 1)
            return DevCol(cr[code] * D + code, c.valid)

        return composed

    return wrap, (lambda v: v % D)


def _collation_rank(e: Expr, dicts):
    """(jnp rank LUT, representative dict) for a dict-coded string expr
    under a non-binary collation; None when binary/no dictionary."""
    if e.type is None or e.type.kind != Kind.STRING or not e.type.collation:
        return None
    from tidb_tpu.utils import collate as _coll

    if _coll.is_binary(e.type.collation):
        return None
    d = _expr_dict(e, dicts)
    if d is None:
        return None
    lr = _coll.rank_lut(d, e.type.collation)
    if lr is None or len(lr[0]) == 0:
        return None
    return lr  # (np lut, rep) — callers upload the LUT only when used


def _rank_wrap(fn, jlut):
    def wrapped(b: Batch) -> DevCol:
        c = fn(b)
        return DevCol(
            jlut[jnp.clip(c.data, 0, jlut.shape[0] - 1)], c.valid
        )

    return wrapped



def agg_out_dicts(plan: "L.Aggregate", dicts) -> Dicts:
    """Dictionaries surviving an aggregation: group keys and
    min/max/first outputs over dictionary-coded columns."""
    out_dicts: Dicts = {}
    for (kname, e) in plan.group_exprs:
        d = _expr_dict(e, dicts)
        if d is not None:
            # CI-collated keys group (and emit codes) in rank space:
            # publish the matching rank->representative dictionary
            # (build_agg_parts applies the mirror-image rank LUT)
            lr = _collation_rank(e, dicts)
            out_dicts[kname] = d if lr is None else lr[1]
            if lr is not None:
                continue  # code bounds describe the pre-rank codes
        if isinstance(e, ColumnRef):
            cb = dicts.get(_BOUNDS_PREFIX + e.name)
            if cb is not None:
                # a distinct's keys on the shared grid; an aggregate with
                # functions keeps the column's own (Q18's semi join reads
                # them: its program is left as it was)
                out_dicts[_BOUNDS_PREFIX + kname] = (
                    cb if plan.aggs else _GridBounds(cb)
                )
    if len(plan.group_exprs) == 1:
        # a single GROUP BY key is unique in the aggregate's output
        out_dicts[_UNIQ_PREFIX + plan.group_exprs[0][0]] = True
    for (name, func, arg, _d) in plan.aggs:
        if func in ("min", "max", "first") and arg is not None:
            # min/max decode back to original dict codes (AggDesc.post),
            # so the original dictionary stays correct under CI too
            d = _expr_dict(arg, dicts)
            if d is not None:
                out_dicts[name] = d
            elif isinstance(arg, ColumnRef):
                # one of the column's own values: its bounds hold
                cb = dicts.get(_BOUNDS_PREFIX + arg.name)
                if cb is not None:
                    out_dicts[_BOUNDS_PREFIX + name] = cb
    return out_dicts


class PlanCompiler:
    """Builds the pure plan function; dictionaries and LUTs are resolved
    at build time (they change only with table versions).

    With instrument=True every node is wrapped with wall-time + row-count
    probes (forces per-op sync — diagnostic mode only): the engine side
    of EXPLAIN ANALYZE (reference RuntimeStatsColl,
    pkg/util/execdetails/execdetails.go:1273)."""

    def __init__(
        self, catalog, instrument: bool = False, resolver=None,
        mesh_n: Optional[int] = None, conservative: bool = False,
    ):
        # conservative=True drops every runtime-verified compile-time
        # assumption (int-column bounds, unique marks, NULL-free folding,
        # assumed top-k widths): the executor's stale-retry loop falls
        # back to it when assumptions keep failing (e.g. a duplicate in a
        # column the planner believed unique), guaranteeing termination.
        self.conservative = conservative
        self.catalog = catalog
        self.resolver = resolver or (
            lambda db, tbl: (catalog.table(db, tbl), catalog.table(db, tbl).version)
        )
        self._next_id = 0
        self.scans: List[ScanSite] = []
        #: (node id, Staged.key) for keyed staged inputs: the executor
        #: feeds these batches at run time like scan inputs
        self.staged_sites: List[Tuple[int, str]] = []
        self.sized: List[int] = []
        self.defaults: Dict[int, int] = {}
        # estimated bytes per row of each sized node's output schema:
        # quota admission pre-accounts cap x width before any launch
        # (pkg/util/memory/tracker.go:74 as admission control)
        self.widths: Dict[int, int] = {}
        self.instrument = instrument
        self.nonnull: List[Tuple[int, str]] = []
        self.bound_checks: List[Tuple[int, str, int, int]] = []
        # fingerprint -> (shared fn, dicts, tag): see _build
        self._subtree_memo: dict = {}
        self._share_memos: list = []  # per-trace result memos to wipe
        self.node_labels: List[Tuple[int, int, str]] = []  # (nid, depth, label)
        self.stats: Dict[int, Dict[str, float]] = {}
        self._depth = 0
        # mesh mode: plan functions run per-shard inside shard_map over a
        # mesh_n-device axis. Every node output carries a distribution tag
        # ('shard' = row-partitioned over the mesh, 'repl' = identical on
        # every device); _tag holds the tag of the most recently built
        # node (stack discipline: a parent reads it right after building
        # each child). The mapping mirrors the reference's MPP task types
        # (pkg/planner/core/fragment.go:47): sharded scan fragments,
        # exchange at aggregation/join boundaries, singleton (gathered)
        # fragments for order-sensitive operators.
        self.mesh_n = mesh_n
        self._tag = "shard"
        # sized nodes that are an exchange's bucket tile (mesh mode)
        self.exchange_nids: set = set()
        # sized nodes that are an expanding join's output tile; filled
        # while the program is traced (the executor picks the path)
        self.expand_nids: set = set()
        # sized node -> its smallest tile (CompiledQuery.floors); the
        # output's compaction tile has one like any join's
        self.floors: Dict[int, int] = {_OUT_NODE: _FEW_ROWS}

    def fresh_id(self) -> int:
        self._next_id += 1
        return self._next_id

    def _stale_sentinel_node(self, props) -> Optional[int]:
        """Semi/anti/mark joins have no output-capacity knob, so a dense
        build side gets a dedicated sized node whose `needs` carries only
        the WIDTH_STALE sentinel (0 otherwise) back to the discovery
        loop."""
        if props[0] is None:
            return None
        nid = self.fresh_id()
        self.sized.append(nid)
        self.defaults[nid] = 16
        self.widths[nid] = 8
        return nid

    def _first_tile(self, plan: L.LogicalPlan, parts: int = 1) -> int:
        """A knob's first tile from the planner's estimate of `plan`'s
        rows (exact table counts and ANALYZE's statistics,
        planner/cardinality.py), a `parts`-th of them, with a quarter
        of room for the estimate's error and a partition's unevenness,
        to the next tile. Where the estimates hold, the first program a
        statement compiles is already its steady one
        (PhysicalExecutor._steady_first); a tile too small retries at
        the exact need like any other."""
        from tidb_tpu.planner import cardinality as C

        return _cap_tile(int(1.25 * C.est_rows(plan, self.catalog)) // parts + 1)

    def _exchange_knob(self, plan: L.LogicalPlan, sides) -> int:
        """The sized node of an exchange of `sides` (a join's pair, a
        sort's child): B, the rows one shard may send one other, first
        sized for the larger side spread over the n x n buckets.
        Discovery shrinks it to the fullest bucket, or retries once at
        the exact need."""
        nid = self.fresh_id()
        self.sized.append(nid)
        self.exchange_nids.add(nid)
        self.widths[nid] = _schema_width(plan.schema)
        self.defaults[nid] = max(
            self._first_tile(side, self.mesh_n**2) for side in sides
        )
        return nid

    def _join_knob(self, plan: L.LogicalPlan, first: int = 0) -> int:
        """The sized node of a join's output tile, starting at `first`
        (0: at the probe's tile, known when the program is traced).
        Like a sorted group table it keeps _FEW_ROWS slots however few
        rows the data puts in it, so that no program is shaped by such
        a count."""
        nid = self.fresh_id()
        self.sized.append(nid)
        self.widths[nid] = _schema_width(plan.schema)
        self.defaults[nid] = max(first, _FEW_ROWS) if first else 0
        self.floors[nid] = _FEW_ROWS
        return nid

    def _gathered(self, fn, tag):
        """Wrap fn so its output is replicated on every device (the
        reference's PassThrough/singleton exchange)."""
        if self.mesh_n is None or tag == "repl":
            return fn
        from tidb_tpu.parallel import broadcast_gather

        def g(inputs, caps):
            b, needs = fn(inputs, caps)
            return broadcast_gather(b), needs

        return g

    def _gather_child(self, child):
        """Singleton-fragment transition for order-sensitive operators
        (Sort/Window/Limit): gather the child, mark output replicated."""
        child = self._gathered(child, self._tag)
        if self.mesh_n:
            self._tag = "repl"
        return child

    def _build(self, plan: L.LogicalPlan):
        # Common-subtree sharing: structurally identical subtrees that
        # contain a join or aggregate (inlined WITH/CTE references used
        # from several IN-subqueries — Q95's ws_wh shape) compile ONCE;
        # the second call site reuses the first's traced result, so the
        # XLA program contains one copy of the work. (Reference: CTE
        # materialization, pkg/planner/core/logical_plan_builder.go
        # buildWith — there a disk spool, here graph sharing inside one
        # program.) Bare scans never memoize: their build consumes the
        # caller's pending range/partition pushdown state.
        fp = None
        if _worth_sharing(plan):
            fp = plan_fingerprint(plan)
            hit = self._subtree_memo.get(fp)
            if hit is not None:
                fn, dicts, tag = hit
                self._tag = tag
                return fn, dicts
        nid = self.fresh_id()
        label = _node_label(plan)
        self.node_labels.append((nid, self._depth, label))
        self._depth += 1
        fn, dicts = self._build_node(plan)
        self._depth -= 1
        fn = _scoped(scope_name(label, nid), fn)
        if self.instrument:
            fn = self._wrap(nid, fn)
        if fp is not None:
            fn = _share_result(fn, registry=self._share_memos)
            self._subtree_memo[fp] = (fn, dicts, self._tag)
        if self._depth == 0 and self._share_memos:
            # root of the build (compile() and the streamed pipeline
            # builder both enter here at depth 0): wipe every per-trace
            # result memo after each invocation — a retained entry would
            # pin the previous run's input batches or leak tracers
            inner, memos = fn, list(self._share_memos)

            def fn(inputs, caps, _f=inner, _m=memos):
                try:
                    return _f(inputs, caps)
                finally:
                    for mm in _m:
                        del mm[:]
        return fn, dicts

    def _wrap(self, nid: int, fn):
        stats = self.stats

        def timed(inputs, caps):
            import time as _time

            t0 = _time.perf_counter()
            batch, needs = fn(inputs, caps)
            jax.block_until_ready(batch.row_valid)
            el = _time.perf_counter() - t0
            rows = int(jnp.sum(batch.row_valid.astype(jnp.int32)))
            st = stats.setdefault(nid, {"time_s": 0.0, "rows": 0, "calls": 0})
            st["time_s"] += el
            st["rows"] = rows
            st["calls"] += 1
            return batch, needs

        return timed

    def compile(self, plan: L.LogicalPlan) -> CompiledQuery:
        self._tag = "shard"
        fn, dicts = self._build(plan)
        # bounds/uniqueness entries are compile-time plumbing; result
        # consumers (materialization, the RPC seam) expect name ->
        # dictionary only (all reserved prefixes start with NUL)
        out = {k: v for k, v in dicts.items() if not k.startswith("\x00")}
        return CompiledQuery(
            fn=fn,
            out_tag=self._tag,
            scans=self.scans,
            staged_sites=list(self.staged_sites),
            sized_nodes=self.sized,
            default_caps=dict(self.defaults),
            out_dicts=out,
            widths=dict(self.widths),
            nonnull=list(self.nonnull),
            bound_checks=list(self.bound_checks),
            exchange_nids=frozenset(self.exchange_nids),
            expand_nids=self.expand_nids,
            floors=dict(self.floors),
            first_out_cap=self._first_tile(plan),
        )

    # ------------------------------------------------------------------
    def _build_node(self, plan: L.LogicalPlan):
        if isinstance(plan, L.OneRow):

            def fn_one(inputs, caps):
                rv = jnp.zeros(256, dtype=bool).at[0].set(True)
                return Batch({}, rv), {}

            self._tag = "repl"
            return fn_one, {}

        if isinstance(plan, L.Staged):
            batch = plan.batch
            sdicts = dict(plan.dicts or {})
            self._tag = "repl"
            if plan.key is not None:
                # runtime staged input: the executor feeds the batch
                # per run (PhysicalExecutor collects keyed Staged
                # nodes), so the cached program never pins stage data
                # and fresh data reuses the compile
                nid = self.fresh_id()
                self.staged_sites.append((nid, plan.key))

                def fn_staged_input(inputs, caps, _nid=nid):
                    return inputs[_nid], {}

                return fn_staged_input, sdicts

            def fn_staged(inputs, caps, _b=batch):
                return _b, {}

            return fn_staged, sdicts

        if isinstance(plan, L.Scan):
            nid = self.fresh_id()
            parts = getattr(self, "_pending_parts", None)
            self._pending_parts = None
            self.scans.append(
                ScanSite(
                    nid, plan.db, plan.table, plan.alias, plan.columns,
                    pk_range=getattr(self, "_pending_range", None),
                    partitions=parts,
                    merge_ranges=getattr(self, "_pending_merge", None),
                    frag=plan.frag,
                )
            )
            self._pending_merge = None
            if parts is not None and self.node_labels:
                # surface pruning in EXPLAIN: the Scan is a leaf, so its
                # label is the most recently appended
                lnid, ldepth, ltext = self.node_labels[-1]
                self.node_labels[-1] = (
                    lnid, ldepth, ltext + f" partitions={list(parts)}"
                )
            t, _v = self.resolver(plan.db, plan.table)
            dicts = {
                f"{plan.alias}.{n}": d
                for n, d in t.dictionaries.items()
                if n in plan.columns
            }
            # integer-column value bounds ride the dicts map under a
            # reserved key (columns can't contain NUL): they give the
            # packed-aggregation paths sound static widths for int keys.
            # Programs verify them at run time (aggregate._pack_keys), so
            # jit reuse across versions stays sound after data growth.
            # Entries are lazy (resolved by _resolve_bounds at the group/
            # join key that consumes them): a wide scan never pays the
            # full-column min/max host pass for unused columns.
            if not self.conservative:
                for n in plan.columns:
                    dicts[_BOUNDS_PREFIX + f"{plan.alias}.{n}"] = _LazyBounds(
                        t, n, _v, nid
                    )
            pk = t.schema.primary_key
            uniq_cols = set([pk[0]] if pk and len(pk) == 1 else [])
            for iname in t.unique_indexes:
                # a unique index not yet PUBLIC may still cover
                # unvalidated duplicate rows: no uniqueness proofs
                if hasattr(t, "index_state") and t.index_state(iname) != "public":
                    continue
                icols = t.indexes.get(iname) or []
                if len(icols) == 1:
                    uniq_cols.add(icols[0])
            if self.conservative:
                uniq_cols = set()
            for n in plan.columns:
                if n in uniq_cols:
                    dicts[_UNIQ_PREFIX + f"{plan.alias}.{n}"] = True
            alias = plan.alias
            # NULL-free columns: fold the per-column validity mask into
            # the row mask so XLA constant-folds every downstream
            # `valid & ...` (measured ~25% of Q1's memory traffic was
            # validity loads/ANDs over columns that never hold NULLs).
            # The assumption is re-checked host-side at every fetch
            # (_run_pinned) and a violation recompiles via the stale path.
            # A small table that holds a NULL anywhere is a table of
            # nullable columns: that another of them holds none today
            # (a 30-row dimension, 4 % NULL a column) is the data's
            # accident, and a program that folded it would be another
            # program for the next data set. Folding saves nothing
            # there; such a table keeps every validity mask.
            nullable_few = t.nrows < _FEW_ROWS and any(
                t.col_has_nulls(n, _v) for n in t.schema.names
            )
            nonnull = [] if self.conservative or nullable_few else [
                n for n in plan.columns if not t.col_has_nulls(n, _v)
            ]
            self.nonnull.extend((nid, n) for n in nonnull)
            nonnull_set = frozenset(nonnull)

            def fn_scan(inputs, caps, _nid=nid, _alias=alias, _nn=nonnull_set):
                raw = inputs[_nid]
                return (
                    Batch(
                        {
                            f"{_alias}.{n}": (
                                DevCol(c.data, raw.row_valid)
                                if n in _nn
                                else c
                            )
                            for n, c in raw.cols.items()
                        },
                        raw.row_valid,
                    ),
                    {},
                )

            self._tag = "shard"
            return fn_scan, dicts

        if isinstance(plan, L.Selection):
            if (
                isinstance(plan.child, L.Aggregate)
                and plan.child.group_exprs
                and not plan.child.gc_meta
            ):
                names = {n for n, _ in plan.child.group_exprs} | {
                    n for n, _f, _a, _d in plan.child.aggs
                }
                pc = _bound_pred_cols(plan.predicate)
                if pc is not None and pc <= names:
                    # HAVING fusion: evaluate the predicate inside the
                    # aggregation kernel — the dense path then compacts
                    # only surviving groups, so the discovered output
                    # tile (and every downstream operator's capacity)
                    # shrinks to the survivor count
                    return self._build_aggregate(
                        plan.child, post_pred=plan.predicate
                    )
            if isinstance(plan.child, L.Scan) and not self.mesh_n:
                self._pending_range = _extract_pk_range(
                    plan.predicate, plan.child, self.resolver
                )
                if self._pending_range is None:
                    self._pending_merge = _extract_index_merge(
                        plan.predicate, plan.child, self.resolver
                    )
            if isinstance(plan.child, L.Scan):
                self._pending_parts = _prune_partitions(
                    plan.predicate, plan.child, self.resolver
                )
            child, dicts = self._build(plan.child)
            self._pending_range = None
            self._pending_merge = None
            self._pending_parts = None
            pred = compile_expr(plan.predicate, dicts)

            def fn_sel(inputs, caps):
                b, needs = child(inputs, caps)
                return filter_batch(b, pred), needs

            return fn_sel, dicts

        if isinstance(plan, L.Projection):
            child, dicts = self._build(plan.child)
            exprs = [(n, compile_expr(e, dicts)) for n, e in plan.exprs]
            out_dicts: Dicts = dict(dicts) if plan.additive else {}
            for n, e in plan.exprs:
                d = _expr_dict(e, dicts)
                if d is not None:
                    out_dicts[n] = d
                if isinstance(e, ColumnRef):
                    cb = dicts.get(_BOUNDS_PREFIX + e.name)
                    if cb is not None:
                        out_dicts[_BOUNDS_PREFIX + n] = cb
                    if dicts.get(_UNIQ_PREFIX + e.name):
                        out_dicts[_UNIQ_PREFIX + n] = True
            additive = plan.additive

            def fn_proj(inputs, caps):
                b, needs = child(inputs, caps)
                cols = dict(b.cols) if additive else {}
                for n, f in exprs:
                    cols[n] = f(b)
                return Batch(cols, b.row_valid), needs

            return fn_proj, out_dicts

        if isinstance(plan, L.Aggregate):
            return self._build_aggregate(plan)

        if isinstance(plan, L.JoinPlan):
            return self._build_join(plan)

        if isinstance(plan, L.Sort):
            child, dicts = self._build(plan.child)
            key_fns = [compile_expr(e, dicts) for e, _ in plan.keys]
            descs = [d for _, d in plan.keys]
            if self.mesh_n and self._tag == "shard":
                # distributed sample sort (no whole-dataset gather): rows
                # range-partition by sampled splitters of the first key,
                # each shard sorts locally, and shard-major array order
                # IS the total order (the output compaction is stable).
                # Replaces the round-1 broadcast_gather Sort path
                # (reference: sortexec multi-way merge over partitions;
                # VERDICT round-1 weak #2).
                mesh_n = self.mesh_n
                nid = self._exchange_knob(plan, (plan.child,))
                # the exchange allocates an (n, B) send buffer + an n*B
                # receive batch per device: account ~n tiles of width,
                # not one (memory-quota admission honesty)
                self.widths[nid] *= mesh_n
                first_fn, first_desc = key_fns[0], descs[0]

                def fn_dsort(inputs, caps):
                    from tidb_tpu.parallel import range_repartition

                    b, needs = child(inputs, caps)
                    k0 = first_fn(b)
                    data = k0.data
                    if data.dtype == jnp.bool_:
                        data = data.astype(jnp.int32)
                    dird = (-data if first_desc else data).astype(jnp.float64)
                    # MySQL null order: first ASC, last DESC — rank NULLs
                    # at the matching infinity so they colocate in the
                    # end bucket (float64 ranking: equal keys always map
                    # to equal ranks, so ties never split across shards)
                    null_rank = -jnp.inf if not first_desc else jnp.inf
                    isnull = b.row_valid & ~k0.valid
                    rank = jnp.where(isnull, null_rank, dird)
                    B = caps[nid]
                    ex, dropped, xneed = range_repartition(
                        b, rank, mesh_n, B, "d"
                    )
                    needs = dict(needs)
                    # xneed is the exact per-bucket requirement in BOTH
                    # directions: discovery shrinks an over-provisioned
                    # tile toward rows/n and grows an overflowed one to
                    # the true hot-bucket size in one step
                    needs[nid] = xneed
                    return order_by(ex, key_fns, descs), needs

                # output stays sharded (range-partitioned + locally
                # sorted = totally ordered in shard-major array order)
                return fn_dsort, dicts
            child = self._gather_child(child)

            def fn_sort(inputs, caps):
                b, needs = child(inputs, caps)
                return order_by(b, key_fns, descs), needs

            return fn_sort, dicts

        if isinstance(plan, L.Window):
            from tidb_tpu.executor.window import WindowDesc, window_op

            child, dicts = self._build(plan.child)
            child = self._gather_child(child)
            part_fns = [compile_expr(e, dicts) for e in plan.partition_exprs]
            order_fns = [compile_expr(e, dicts) for e, _ in plan.order_exprs]
            order_descs = [d for _, d in plan.order_exprs]
            wdescs = []
            out_dicts = dict(dicts)
            for name, func, arg, offset, running, frame in plan.descs:
                fn = compile_expr(arg, dicts) if arg is not None else None
                scale = (
                    arg.type.scale
                    if arg is not None and arg.type.kind == Kind.DECIMAL
                    else 0
                )
                wdescs.append(
                    WindowDesc(func, fn, name, offset, scale, running, frame)
                )
                if func in ("lag", "lead", "min", "max") and arg is not None:
                    d = _expr_dict(arg, dicts)
                    if d is not None:
                        out_dicts[name] = d

            def fn_win(inputs, caps):
                b, needs = child(inputs, caps)
                return (
                    window_op(b, part_fns, order_fns, order_descs, wdescs),
                    needs,
                )

            return fn_win, out_dicts

        if isinstance(plan, L.Limit):
            if isinstance(plan.child, L.Sort) and plan.count is not None:
                return self._build_topn(plan)
            child, dicts = self._build(plan.child)
            child = self._gather_child(child)
            k, off = plan.count, plan.offset

            def fn_lim(inputs, caps):
                b, needs = child(inputs, caps)
                return limit_op(b, k, off), needs

            return fn_lim, dicts

        if isinstance(plan, L.UnionAll):
            built, ctags = [], []
            for c in plan.children:
                built.append(self._build(c))
                ctags.append(self._tag)
            if self.mesh_n and not all(t == "shard" for t in ctags):
                # mixed distribution: gather everything, emit replicated
                built = [
                    (self._gathered(f, t), d)
                    for (f, d), t in zip(built, ctags)
                ]
                self._tag = "repl"
            else:
                self._tag = "shard" if self.mesh_n else self._tag
            fns = [f for f, _ in built]
            child_dicts = [d for _, d in built]
            internals = [c.internal for c in plan.schema.cols]
            types = {c.internal: c.type for c in plan.schema.cols}
            # merge dictionaries per string output column; per-child LUTs
            out_dicts: Dicts = {}
            luts: Dict[str, List[Optional[jax.Array]]] = {}
            for name in internals:
                if types[name].kind != Kind.STRING:
                    continue
                ds = [cd.get(name) for cd in child_dicts]
                merged = np.array(
                    sorted({s for d in ds if d is not None for s in d.tolist()}),
                    dtype=object,
                )
                out_dicts[name] = merged
                luts[name] = [
                    jnp.asarray(
                        np.searchsorted(merged, d).astype(np.int32)
                        if d is not None and len(d)
                        else np.zeros(1, np.int32)
                    )
                    for d in ds
                ]

            def fn_union(inputs, caps):
                needs: Dict[int, jax.Array] = {}
                batches = []
                for f in fns:
                    b, n = f(inputs, caps)
                    needs.update(n)
                    batches.append(b)
                cols = {}
                for name in internals:
                    datas, valids = [], []
                    for ci, b in enumerate(batches):
                        c = b.cols[name]
                        d = c.data
                        if name in luts:
                            lut = luts[name][ci]
                            d = lut[jnp.clip(d, 0, lut.shape[0] - 1)]
                        datas.append(d)
                        valids.append(c.valid)
                    cols[name] = DevCol(
                        jnp.concatenate(datas), jnp.concatenate(valids)
                    )
                rv = jnp.concatenate([b.row_valid for b in batches])
                return Batch(cols, rv), needs

            return fn_union, out_dicts

        raise ExecError(f"no physical impl for {type(plan).__name__}")

    # ------------------------------------------------------------------
    def _build_aggregate(self, plan: L.Aggregate, post_pred=None):
        child, dicts = self._build(plan.child)
        child_tag = self._tag
        nid = self.fresh_id()
        self.sized.append(nid)
        self.defaults[nid] = self._first_tile(plan)
        self.widths[nid] = _schema_width(plan.schema)
        key_fns, key_names, key_widths, descs = build_agg_parts(
            plan, dicts, compiler=self
        )
        scalar = not plan.group_exprs
        if not scalar and not _dense_keys(key_widths):
            # sorted groups: the table is the output tile and nothing
            # else, so one of a few dozen slots is no cheaper than one
            # of _FEW_ROWS, and does not follow the group count
            self.floors[nid] = _FEW_ROWS
            self.defaults[nid] = max(self.defaults[nid], _FEW_ROWS)
        agg_names = [(n, f) for n, f, _a, _d in plan.aggs]
        mesh_n = self.mesh_n if child_tag == "shard" else None
        post_fn = (
            compile_expr(post_pred, agg_out_dicts(plan, dicts))
            if post_pred is not None
            else None
        )
        if mesh_n:
            # partial agg per shard -> all_to_all of group rows -> final
            # agg; groups end hash-sharded (keyed) / replicated (scalar)
            self._tag = "repl" if scalar else "shard"

        def fn_agg(inputs, caps):
            b, needs = child(inputs, caps)
            cap = caps[nid]
            if mesh_n:
                from tidb_tpu.parallel import distributed_group_aggregate

                out, total, dropped, xneed = distributed_group_aggregate(
                    b, key_fns, descs, cap, mesh_n,
                    key_names=key_names, key_widths=key_widths,
                )
                ngroups = jnp.maximum(
                    total, (dropped > 0).astype(total.dtype) * xneed
                )
                if post_fn is not None:
                    # distributed path: the fused HAVING applies as a
                    # row mask on the final (hash-sharded) groups —
                    # semantically the Selection node it replaced
                    c = post_fn(out)
                    out = Batch(
                        out.cols,
                        out.row_valid & c.valid & (c.data != 0),
                    )
            else:
                out, ngroups = group_aggregate(
                    b, key_fns, descs, cap, key_names,
                    key_widths=key_widths, post_filter=post_fn,
                )
            if scalar:
                # MySQL: scalar aggregation over empty input yields one
                # row: COUNT=0 valid, others NULL (branchless form).
                empty = ngroups == 0
                first = jnp.zeros(out.capacity, dtype=bool).at[0].set(True)
                rv = jnp.where(empty, first, out.row_valid)
                cols = {}
                for name, func in agg_names:
                    c = out.cols[name]
                    if func == "count":
                        cols[name] = DevCol(
                            jnp.where(empty, jnp.zeros_like(c.data), c.data),
                            jnp.where(empty, first, c.valid),
                        )
                    else:
                        cols[name] = DevCol(
                            c.data, jnp.where(empty, jnp.zeros_like(c.valid), c.valid)
                        )
                out = Batch(cols, rv)
            needs = dict(needs)
            needs[nid] = ngroups
            return out, needs

        return fn_agg, agg_out_dicts(plan, dicts)

    # ------------------------------------------------------------------
    def _topn_widths(self, keys, dicts):
        """Per-key (bit width, bias) for the packed top-k encoding, or
        None when the keys don't pack into <= 62 bits. Unlike the
        aggregation widths, integer-typed keys WITHOUT bounds (e.g. SUM
        outputs) get an assumed 40-bit width — runtime-verified, and
        dropped by the conservative recompile if values exceed it."""
        out = []
        total = 0
        for e, _d in keys:
            w = _key_width(e, dicts)
            if w is None and not self.conservative:
                kind = e.type.kind if e.type is not None else None
                if kind in (Kind.INT, Kind.DECIMAL, Kind.DATETIME, Kind.TIME):
                    w = (40, 1 << 39)  # covers |v| < 2^39
            if w is None:
                return None
            total += w[0]
            out.append(w)
        return out if total <= 62 else None

    def _build_topn(self, plan: L.Limit):
        """ORDER BY ... LIMIT n without sorting the dataset.

        Fast path: when every sort key packs into one int64 (dictionary
        codes, dates, bounded/assumed-width ints — desc keys keep their
        limb, asc keys flip it, so bigger packed == earlier row and
        MySQL NULL ordering falls out of the 0-limb), the top (n+offset)
        rows come from ONE jax.lax.top_k over the packed key: O(rows log
        n) and no gather of the full dataset. On a mesh each shard
        top-k's locally, only the n-row tiles all_gather, and a final
        top-k runs on mesh x n rows (reference: TopNExec pushed to each
        region + root merge, pkg/executor/sortexec/topn.go:31).

        Fallback (unpackable keys): full local sort + head tile, same
        shard/merge structure."""
        sort = plan.child
        inner, dicts = self._build(sort.child)
        if self._tag != "shard":
            inner = self._gathered(inner, self._tag)
            self._tag = "repl"
        key_fns = [compile_expr(e, dicts) for e, _ in sort.keys]
        descs = [d for _, d in sort.keys]
        n = plan.count + (plan.offset or 0)
        k, off = plan.count, plan.offset
        mesh_on = bool(self.mesh_n) and self._tag == "shard"
        if self.mesh_n:
            self._tag = "repl"

        widths = self._topn_widths(sort.keys, dicts) if n <= 4096 else None
        if widths is not None:
            total_bits = sum(w for w, _b in widths)
            snid = self.fresh_id()
            self.sized.append(snid)
            self.defaults[snid] = 16
            self.widths[snid] = 8

            def pack(b):
                packed = jnp.zeros(b.capacity, dtype=jnp.int64)
                stale = jnp.zeros((), dtype=bool)
                offb = total_bits
                for (w, bias), f, d in zip(widths, key_fns, descs):
                    offb -= w
                    kcol = f(b)
                    limb = jnp.where(
                        kcol.valid,
                        kcol.data.astype(jnp.int64) + (bias + 1),
                        0,
                    )
                    bad = kcol.valid & ((limb < 1) | (limb > ((1 << w) - 1)))
                    stale = stale | jnp.any(b.row_valid & bad)
                    enc = limb if d else ((1 << w) - 1) - limb
                    packed = packed | (enc << offb)
                # invalid rows sink below every real row (packed >= 0)
                return jnp.where(b.row_valid, packed, -1), stale

            def take(b, packed, kk):
                _vals, idx = jax.lax.top_k(packed, kk)
                cols = {
                    nm: DevCol(c.data[idx], c.valid[idx])
                    for nm, c in b.cols.items()
                }
                return Batch(cols, b.row_valid[idx])

            def fn_topk(inputs, caps):
                b, needs = inner(inputs, caps)
                packed, stale = pack(b)
                head = take(b, packed, min(n, b.capacity))
                if mesh_on:
                    from tidb_tpu.parallel import broadcast_gather

                    head = broadcast_gather(head)
                    p2, st2 = pack(head)
                    stale = stale | st2
                    head = take(head, p2, min(n, head.capacity))
                needs = dict(needs)
                needs[snid] = jnp.where(
                    stale, jnp.int64(_WIDTH_STALE), jnp.int64(0)
                )
                return limit_op(head, k, off), needs

            return fn_topk, dicts

        tile = pad_capacity(max(n, 1), floor=32)

        def fn_topn(inputs, caps):
            b, needs = inner(inputs, caps)
            b = order_by(b, key_fns, descs)
            # top-n per shard: sorted order puts valid rows first, so a
            # static head slice after masking rows past n is exact
            keep = jnp.cumsum(b.row_valid.astype(jnp.int32)) <= n
            t = min(tile, b.capacity)
            head = Batch(
                {
                    nm: DevCol(c.data[:t], c.valid[:t] & keep[:t])
                    for nm, c in b.cols.items()
                },
                b.row_valid[:t] & keep[:t],
            )
            if mesh_on:
                from tidb_tpu.parallel import broadcast_gather

                head = broadcast_gather(head)
                head = order_by(head, key_fns, descs)
            return limit_op(head, k, off), needs

        return fn_topn, dicts

    # ------------------------------------------------------------------
    def _build_join(self, plan: L.JoinPlan):
        left, ldicts = self._build(plan.left)
        ltag = self._tag
        right, rdicts = self._build(plan.right)
        rtag = self._tag
        dicts = {**ldicts, **rdicts}
        mesh = self.mesh_n

        def _gather_both():
            nonlocal left, right, ltag, rtag
            left = self._gathered(left, ltag)
            right = self._gathered(right, rtag)
            ltag = rtag = "repl"
            self._tag = "repl"

        if plan.kind == "cross":
            if mesh:
                _gather_both()
            res = compile_expr(plan.residual, dicts) if plan.residual is not None else None

            def fn_cross(inputs, caps):
                lb, n1 = left(inputs, caps)
                rb, n2 = right(inputs, caps)
                out, _total = _cross_join(lb, rb)
                if res is not None:
                    out = filter_batch(out, res)
                return out, {**n1, **n2}

            return fn_cross, _strip_uniq(dicts)

        lkeys, rkeys = [], []
        for le, re_ in plan.equi_keys:
            lf, rf = _align_key_fns(le, re_, ldicts, rdicts)
            lkeys.append(lf)
            rkeys.append(rf)
        lprops = rprops = ((None, False))
        chosen = None
        if len(lkeys) == 1:
            lkey, rkey = lkeys[0], rkeys[0]
            verify = None
            le0, re0 = plan.equi_keys[0]
            lprops = _join_key_props(le0, ldicts)
            rprops = _join_key_props(re0, rdicts)
        else:
            if plan.kind not in ("inner", "semi", "anti", "left"):
                raise ExecError("multi-key outer join not yet supported")
            # multi-key inner join: when one pair's key is provably
            # unique on its side, join on THAT pair alone (dense 1:1
            # path) and let the verify filter apply the remaining
            # equalities post-join — the unique key already guarantees
            # <= 1 match per probe row, so no hash-combine collisions
            # and no probe-chain expansion. (Q5's customer join:
            # c_custkey unique, c_nationkey = s_nationkey demoted.)
            # Semi/anti joins use the same trick but can't swap sides,
            # so only BUILD-side (right) uniqueness qualifies.
            if plan.kind in ("inner", "semi", "anti"):
                for i, (le0, re0) in enumerate(plan.equi_keys):
                    lp = _join_key_props(le0, ldicts)
                    rp = _join_key_props(re0, rdicts)
                    if rp[1] or (plan.kind == "inner" and lp[1]):
                        chosen, lprops, rprops = i, lp, rp
                        break
            if chosen is not None:
                lkey, rkey = lkeys[chosen], rkeys[chosen]
                # the join itself enforces the chosen pair's equality
                # exactly (dense 1:1 / searchsorted, runtime-verified):
                # verify only the demoted pairs
                verify = (
                    [f for j, f in enumerate(lkeys) if j != chosen],
                    [f for j, f in enumerate(rkeys) if j != chosen],
                )
            else:
                lkey = _hash_combine(lkeys)
                rkey = _hash_combine(rkeys)
                verify = (lkeys, rkeys)

        kind = plan.kind
        null_aware = plan.null_aware
        res = compile_expr(plan.residual, dicts) if plan.residual is not None else None

        if kind == "mark":
            # mark join: probe rows survive, gaining a boolean IN/EXISTS
            # result column (three-valued under null_aware — the IN
            # semantics; two-valued for EXISTS)
            if verify is not None or res is not None:
                raise ExecError(
                    "mark join supports a single equality key and no "
                    "residual conditions"
                )
            mark = getattr(plan, "mark_name", None) or plan.schema.cols[-1].internal
            three = null_aware
            if mesh:
                # replicate the build side: every shard marks its own
                # probe rows against the full build set
                right = self._gathered(right, rtag)
                rtag = "repl"
                self._tag = ltag
            snid = self._stale_sentinel_node(rprops)

            def fn_mark(inputs, caps):
                lb, n1 = left(inputs, caps)
                rb, n2 = right(inputs, caps)
                out, t = equi_join(
                    rb, lb, rkey, lkey, 0, "mark",
                    mark_name=mark, mark_three_valued=three,
                    build_bounds=rprops[0],
                )
                needs = {**n1, **n2}
                if snid is not None:
                    needs[snid] = _stale_only(t)
                return out, needs

            return fn_mark, {**ldicts}

        if kind in ("semi", "anti"):
            if verify is None and res is None:
                part_nid = None
                build_sharded = rtag == "shard"
                if mesh:
                    if ltag == "shard" and rtag == "shard" and plan.broadcast == "right":
                        right = self._gathered(right, rtag)
                        rtag, build_sharded = "repl", False
                    if ltag == "repl" and rtag == "shard":
                        # replicated probe vs sharded build: gather build
                        right = self._gathered(right, rtag)
                        rtag, build_sharded = "repl", False
                    if ltag == "shard" and rtag == "shard":
                        # repartition both sides on the join key so equal
                        # keys colocate (MPP HashPartition exchange)
                        part_nid = self._exchange_knob(plan, (plan.left, plan.right))
                    self._tag = ltag

                snid = self._stale_sentinel_node(rprops)

                def fn_semi(inputs, caps):
                    lb, n1 = left(inputs, caps)
                    rb, n2 = right(inputs, caps)
                    needs = {**n1, **n2}
                    if part_nid is not None:
                        from tidb_tpu.parallel import repartition_pair

                        B = caps[part_nid]
                        lb, rb, _drp, xneed = repartition_pair(
                            lb, rb, lkey, rkey, mesh, B, keep=plan.needs
                        )
                        # the TRUE per-bucket need, in both directions:
                        # a hot key costs ONE recompile at the exact
                        # size, not a doubling ladder, and the first
                        # tile shrinks to what the data fills
                        needs[part_nid] = xneed
                    out, _t = equi_join(
                        rb, lb, rkey, lkey, 0, kind, build_bounds=rprops[0]
                    )
                    if snid is not None:
                        needs[snid] = _stale_only(_t)
                    if null_aware and kind == "anti":
                        bk = rkey(rb)
                        has_null = jnp.any(~bk.valid & rb.row_valid)
                        # NULL NOT IN (an empty set) is true
                        has_rows = jnp.any(rb.row_valid)
                        if mesh and build_sharded:
                            has_null = jax.lax.pmax(has_null, "d")
                            has_rows = jax.lax.pmax(has_rows, "d")
                        pk = lkey(out)
                        out = Batch(
                            out.cols,
                            out.row_valid & ~has_null & (pk.valid | ~has_rows),
                        )
                    return out, needs

                return fn_semi, {**ldicts}

            # Multi-key semi/anti with a provably-unique build pair:
            # probe-aligned 1:1 lookup on that pair, demoted equalities
            # and any residual verified on the gathered build row — one
            # build pass + one probe pass, no expansion, no row-id
            # re-join (the expand path below cost Q5's customer-semi
            # rewrite 0.14s/run at SF1 before this).
            if (
                chosen is not None
                or (verify is None and res is not None and rprops[1])
            ) and not (null_aware and kind == "anti"):
                # (second disjunct: single-key correlated EXISTS whose
                # build side is unique — same lookup, no demoted pairs)
                from tidb_tpu.executor.join import gather_cols, lookup_build_rows

                part_nid = None
                if mesh:
                    if rtag == "shard" and (
                        ltag == "repl"
                        or (ltag == "shard" and plan.broadcast == "right")
                    ):
                        right = self._gathered(right, rtag)
                        rtag = "repl"
                    if ltag == "shard" and rtag == "shard":
                        part_nid = self._exchange_knob(plan, (plan.left, plan.right))
                    self._tag = ltag
                # the sorted lookup's stale source is a runtime
                # uniqueness violation, not just outgrown bounds — the
                # sentinel is needed whenever either assumption is baked
                snid = self._stale_sentinel_node(
                    rprops if rprops[0] is not None else ((0, 0), True)
                )
                lks_rks = verify

                def fn_semi_lookup(inputs, caps):
                    lb, n1 = left(inputs, caps)
                    rb, n2 = right(inputs, caps)
                    needs = {**n1, **n2}
                    if part_nid is not None:
                        from tidb_tpu.parallel import repartition_pair

                        B = caps[part_nid]
                        lb, rb, _drp, xneed = repartition_pair(
                            lb, rb, lkey, rkey, mesh, B, keep=plan.needs
                        )
                        needs[part_nid] = xneed
                    brow, matched, stale = lookup_build_rows(
                        rb, lb, rkey, lkey, build_bounds=rprops[0]
                    )
                    # joined namespace, probe-aligned: verify fns and the
                    # residual see probe cols + the matched build row's
                    # cols (junk where unmatched — masked right after),
                    # those they read, moved by one stacked gather
                    bcols = gather_cols(
                        rb, brow, plan.needs and plan.needs[1]
                    )
                    bb = Batch(
                        {
                            **lb.cols,
                            **{
                                n: DevCol(c.data, c.valid & matched)
                                for n, c in bcols.items()
                            },
                        },
                        lb.row_valid,
                    )
                    ok = matched
                    if lks_rks is not None:
                        for lf2, rf2 in zip(*lks_rks):
                            a, c = lf2(bb), rf2(bb)
                            ok = ok & (a.data == c.data) & a.valid & c.valid
                    if res is not None:
                        r = res(bb)
                        ok = ok & r.data & r.valid
                    keep = ok if kind == "semi" else ~ok
                    out = Batch(lb.cols, lb.row_valid & keep)
                    if snid is not None:
                        needs[snid] = jnp.where(
                            stale, jnp.int64(_WIDTH_STALE), jnp.int64(0)
                        )
                    return out, needs

                return fn_semi_lookup, {**ldicts}

            # Semi/anti with multiple keys and/or a residual predicate
            # (correlated EXISTS): hash-combined keys can collide and
            # residuals need both sides' columns, so expand via an inner
            # join carrying a probe row id, verify every key pair exactly,
            # apply the residual, then mask the probe batch by surviving
            # row ids (an exact single-key semi join).
            if null_aware:
                raise ExecError("null-aware multi-key anti join not supported")
            if mesh:
                # row-id re-join must see both sides whole: run replicated
                _gather_both()
            nid = self._join_knob(plan)
            lks_rks = verify

            def fn_semi_multi(inputs, caps):
                lb, n1 = left(inputs, caps)
                rb, n2 = right(inputs, caps)
                rid = jnp.arange(lb.capacity, dtype=jnp.int64)
                lb2 = Batch(
                    {**lb.cols, "_srowid": DevCol(rid, lb.row_valid)},
                    lb.row_valid,
                )
                cap = caps[nid] or pad_capacity(max(lb.capacity, 1024))
                j, total = equi_join(rb, lb2, rkey, lkey, cap, "inner")
                if lks_rks is not None:
                    lks, rks = lks_rks

                    def vf(bb):
                        ok = jnp.ones(bb.capacity, dtype=bool)
                        for lf2, rf2 in zip(lks, rks):
                            a, c = lf2(bb), rf2(bb)
                            ok = ok & (a.data == c.data) & a.valid & c.valid
                        return DevCol(ok, jnp.ones(bb.capacity, dtype=bool))

                    j = filter_batch(j, vf)
                if res is not None:
                    j = filter_batch(j, res)
                ridc = lambda b: b.cols["_srowid"]
                out, _t = equi_join(j, lb2, ridc, ridc, 0, kind)
                out = Batch(
                    {k: v for k, v in out.cols.items() if k != "_srowid"},
                    out.row_valid,
                )
                needs = {**n1, **n2}
                needs[nid] = total
                return out, needs

            return fn_semi_multi, {**ldicts}

        if kind == "left" and (verify is not None or res is not None):
            # LEFT join with multiple equi keys and/or an ON-residual.
            # Hash-combined keys collide and a post-join residual filter
            # would wrongly drop NULL-extended rows, so: (1) inner-join
            # with a probe row id, verifying every key pair exactly and
            # applying the residual to matched pairs only, then (2) LEFT
            # join the original probe against the survivors on row id —
            # exact single-key — so unmatched probe rows NULL-extend.
            # Reference: ON-clause vs WHERE-clause semantics in
            # pkg/planner/core/logical_plan_builder.go (outer join ON
            # conditions never filter the outer side).
            if mesh:
                _gather_both()
            nid = self._join_knob(plan)
            nid2 = self._join_knob(plan)
            lks_rks = verify

            def fn_left_multi(inputs, caps):
                lb, n1 = left(inputs, caps)
                rb, n2 = right(inputs, caps)
                rid = jnp.arange(lb.capacity, dtype=jnp.int64)
                lb2 = Batch(
                    {**lb.cols, "_lrowid": DevCol(rid, lb.row_valid)},
                    lb.row_valid,
                )
                cap = caps[nid] or pad_capacity(max(lb.capacity, 1024))
                j, total = equi_join(rb, lb2, rkey, lkey, cap, "inner")
                if lks_rks is not None:
                    lks, rks = lks_rks

                    def vf(bb):
                        ok = jnp.ones(bb.capacity, dtype=bool)
                        for lf2, rf2 in zip(lks, rks):
                            a, c = lf2(bb), rf2(bb)
                            ok = ok & (a.data == c.data) & a.valid & c.valid
                        return DevCol(ok, jnp.ones(bb.capacity, dtype=bool))

                    j = filter_batch(j, vf)
                if res is not None:
                    j = filter_batch(j, res)
                rnames = set(rb.cols)
                j2 = Batch(
                    {
                        k: v
                        for k, v in j.cols.items()
                        if k in rnames or k == "_lrowid"
                    },
                    j.row_valid,
                )
                ridc = lambda b: b.cols["_lrowid"]
                cap2 = caps[nid2] or pad_capacity(max(lb.capacity, 1024))
                out, total2 = equi_join(j2, lb2, ridc, ridc, cap2, "left")
                out = Batch(
                    {k: v for k, v in out.cols.items() if k != "_lrowid"},
                    out.row_valid,
                )
                needs = {**n1, **n2}
                needs[nid] = total
                needs[nid2] = total2
                return out, needs

            return fn_left_multi, _strip_uniq(dicts)

        part_nid = None
        forced_swap = False
        if mesh and ltag == "shard" and rtag == "shard":
            # cost-based broadcast: replicate the estimated-small side
            # (all_gather of it) instead of all_to_all on both sides
            bc = plan.broadcast
            if bc == "right":
                right = self._gathered(right, rtag)
                rtag = "repl"
            elif bc == "left" and kind == "inner":
                left = self._gathered(left, ltag)
                ltag = "repl"
        if mesh:
            if ltag == "repl" and rtag == "shard":
                if kind == "inner":
                    # broadcast-style: replicated left is the build side
                    forced_swap = True
                    self._tag = "shard"
                else:
                    # outer probe must see every build row: gather build
                    right = self._gathered(right, rtag)
                    rtag = "repl"
                    self._tag = "repl"
            elif ltag == "shard" and rtag == "shard":
                part_nid = self._exchange_knob(plan, (plan.left, plan.right))
                self._tag = "shard"
            else:
                # rtag repl: build side already everywhere (broadcast join)
                self._tag = ltag
        # one device: resolved at first execution from the probe's tile;
        # a mesh: from the estimate, a shard's share where it is sharded
        nid = self._join_knob(
            plan,
            self._first_tile(
                plan, self.mesh_n if mesh and self._tag == "shard" else 1
            ),
        )

        def fn_join(inputs, caps):
            lb, n1 = left(inputs, caps)
            rb, n2 = right(inputs, caps)
            needs = {**n1, **n2}
            if part_nid is not None:
                from tidb_tpu.parallel import repartition_pair

                B = caps[part_nid]
                lb, rb, _drp, xneed = repartition_pair(
                    lb, rb, lkey, rkey, mesh, B, keep=plan.needs
                )
                needs[part_nid] = xneed
            build_b, probe_b, build_k, probe_k = rb, lb, rkey, lkey
            build_props = rprops
            # what the join, its verify and residual filters and its
            # readers use of (probe, build): all the join emits
            keep = plan.needs
            if forced_swap or (
                kind == "inner" and not mesh and lb.capacity < rb.capacity
            ):
                build_b, probe_b, build_k, probe_k = lb, rb, lkey, rkey
                build_props = lprops
                keep = keep and keep[::-1]
            cap = caps[nid] or pad_capacity(max(probe_b.capacity, 1024))
            with expansion_ledger() as expanded:
                out, total = equi_join(
                    build_b, probe_b, build_k, probe_k, cap, kind,
                    build_bounds=build_props[0], build_unique=build_props[1],
                    keep=keep,
                )
            if expanded:
                # the executor took the expanding path: this tile is
                # what an overflow of it retries at
                self.expand_nids.add(nid)
            if verify is not None:
                lk, rk = verify

                def vf(b):
                    ok = jnp.ones(b.capacity, dtype=bool)
                    vv = jnp.ones(b.capacity, dtype=bool)
                    for lf2, rf2 in zip(lk, rk):
                        a, c = lf2(b), rf2(b)
                        ok = ok & (a.data == c.data)
                        vv = vv & a.valid & c.valid
                    return DevCol(ok, vv)

                out = filter_batch(out, vf)
            if res is not None:
                out = filter_batch(out, res)
            needs[nid] = total
            return out, needs

        if kind == "inner" and (len(plan.equi_keys) == 1 or chosen is not None):
            # inner join keyed (or chosen-keyed) on a single pair: a
            # unique build key can't duplicate the other side's rows, so
            # that side's uniqueness survives; the verify filter for
            # demoted pairs only drops rows and can't duplicate either
            return fn_join, _merge_join_dicts(
                ldicts, rdicts, lprops[1], rprops[1]
            )
        return fn_join, _strip_uniq(dicts)


# ---------------------------------------------------------------------------
# Executor: discovery loop + jit cache
# ---------------------------------------------------------------------------

_MAX_JOIN_CAP = 1 << 26


# Rows below which a tile or a table is "a few": one 8 x 128 vector
# register of 32-bit lanes. Nothing on the chip is cheaper for being
# smaller than that, so no program is shaped by a count below it.
_FEW_ROWS = 1024


def _dense_keys(key_widths) -> bool:
    """Whether group keys of these packed widths take the executor's
    dense (masked) aggregation (executor/aggregate.group_aggregate)."""
    from tidb_tpu.executor.aggregate import _DENSE_BITS

    return all(w is not None for w in key_widths) and (
        sum(w for w, _b in key_widths) <= _DENSE_BITS
    )


def _cap_tile(n: int) -> int:
    """Power-of-two tile >= n for capacity knobs (floor 16 — unlike batch
    tiles, small group/join tables benefit from staying small; group
    slot counts derived from these are used as bitmask moduli)."""
    return pad_capacity(n, floor=16, pow2=True)


class SharedPlanCache:
    """Process-wide compiled-plan cache shared across sessions.

    Every PhysicalExecutor keeps its private LRU (below), but executors
    are per-session / per-connection, so under the serving tier N
    concurrent sessions would otherwise each pay the XLA compile for
    the SAME plan shape — the dominant cost "Accelerating Presto with
    GPUs" (PAPERS.md) identifies at high concurrency. This cache is the
    cross-session tier: keyed exactly like the private LRU (plan
    fingerprint + per-scan table-uid/versions — which already folds in
    the PR 5 keyed-staged fingerprints: staged capacity, logical
    dtypes, dictionary content) plus the executor's mesh width (a mesh
    program is not a single-device program). An executor consults it on
    a private miss and publishes after a compile; entries remember
    their creating executor so CROSS-session reuse is observable
    (tidbtpu_executor_shared_plan_cache_cross_session_hits_total — the
    bench --serve-load acceptance signal).

    Sharing one CompiledQuery across concurrent executors is safe
    because the steady state is published as one atomic tuple
    (CompiledQuery.steady) and everything else on the dataclass is
    written once at compile time.

    Entries are WEAK references: a shared entry lives exactly as long
    as at least one executor still holds the CompiledQuery in its
    private LRU. That is the serving scenario (concurrent sessions
    reuse each other's live compiles) without the pathology of a
    strong process-global cache — compiled closures capture table
    readers, so a strong cache would pin whole dead catalogs (every
    test's, every closed connection's) for the life of the process.

    Misses are SINGLEFLIGHT: the first executor to miss a key CLAIMS
    it and compiles; concurrent requesters of the same key wait for
    that one publish instead of stampeding N identical compiles — the
    flash-crowd case (64 sessions, one dashboard query) pays one
    compile, and every waiter lands a (cross-session) hit. A claimant
    that fails releases the claim (abandon, via the caller's finally),
    and a bounded wait means a wedged claimant degrades a waiter to
    compiling itself, never to hanging."""

    def __init__(self):
        import weakref as _wr

        self._cv = racecheck.make_condition("executor.plan_cache")
        self._map: "_wr.WeakValueDictionary" = _wr.WeakValueDictionary()
        #: in-flight compiles: (mesh_n, key) -> claiming owner
        self._pending: Dict[tuple, int] = {}

    def get(self, mesh_n, key: tuple, owner: int, wait_s: float = 120.0):
        """A hit returns the CompiledQuery. A miss returns None and
        CLAIMS the key — the caller MUST publish via put() or release
        via abandon() (exception paths). If another executor holds the
        claim, block for its publish (same-key waits cannot deadlock:
        a claimant never re-enters get() for the key it holds)."""
        from tidb_tpu.utils.metrics import REGISTRY

        k = (mesh_n, key)
        deadline = None
        cq = None
        with self._cv:
            while True:
                cq = self._map.get(k)
                if cq is not None:
                    break
                claimant = self._pending.get(k)
                if claimant is None:
                    self._pending[k] = owner
                    break
                now = time.monotonic()
                if deadline is None:
                    deadline = now + wait_s
                if now >= deadline:
                    # claimant wedged: compile ourselves (duplicate
                    # work, never wrong). No claim taken — the original
                    # one stands until its publish/abandon.
                    break
                self._cv.wait(min(deadline - now, 0.1))
        if cq is None:
            REGISTRY.counter(
                "tidbtpu_executor_shared_plan_cache_misses_total",
                "shared plan-cache lookups that missed",
            ).inc()
            return None
        REGISTRY.counter(
            "tidbtpu_executor_shared_plan_cache_hits_total",
            "compiles avoided via the cross-session plan cache",
        ).inc()
        if getattr(cq, "shared_owner", None) != owner:
            REGISTRY.counter(
                "tidbtpu_executor_shared_plan_cache_cross_session_hits_total",
                "shared plan-cache hits on a plan another session compiled",
            ).inc()
        return cq

    def put(self, mesh_n, key: tuple, cq, owner: int) -> None:
        cq.shared_owner = owner  # creator id: cross-session accounting
        with self._cv:
            self._map[(mesh_n, key)] = cq
            self._pending.pop((mesh_n, key), None)
            self._cv.notify_all()

    def abandon(self, mesh_n, key: tuple, owner: int) -> None:
        """A claimant's compile failed: release the claim (only the
        claiming owner's — a waiter that timed out and then failed must
        not free someone else's live claim) so waiters stop waiting and
        the next requester claims."""
        with self._cv:
            if self._pending.get((mesh_n, key)) == owner:
                del self._pending[(mesh_n, key)]
                self._cv.notify_all()

    def invalidate(self, mesh_n, key: tuple) -> None:
        """Drop one entry (StaleWidthsError: the compiled program's
        baked bounds no longer cover the data — every session must
        recompile, not just the one that noticed)."""
        with self._cv:
            self._map.pop((mesh_n, key), None)

    def clear(self) -> None:
        with self._cv:
            self._map.clear()
            self._pending.clear()
            self._cv.notify_all()


SHARED_PLAN_CACHE = SharedPlanCache()


class PhysicalExecutor:
    """Runs compiled plans. With mesh_devices=N, every plan compiles to a
    single shard_map program over an N-device mesh: scans row-sharded
    (the Region data-parallel analog), aggregation/joins exchanged via
    all_to_all/all_gather collectives (the MPP HashPartition/Broadcast
    exchanges, pkg/store/mockstore/unistore/cophandler/mpp_exec.go:597),
    order-sensitive operators on gathered singleton fragments."""

    def __init__(self, catalog, mesh_devices: Optional[int] = None):
        self.catalog = catalog
        # fingerprint + versions -> CompiledQuery; ordered dict used as an
        # LRU (move-to-end on hit, evict oldest past capacity) like the
        # reference's plan-cache LRU (pkg/planner/core/plan_cache_lru.go)
        from collections import OrderedDict

        self._cache: "OrderedDict[tuple, CompiledQuery]" = OrderedDict()
        # session hook: (db, table) -> (Table, version) — lets snapshot
        # transactions pin versions / substitute shadow tables.
        self.table_hook = None
        # per-query device-memory budget in bytes (tidb_mem_quota_query);
        # session refreshes it per statement. None/0 = unlimited.
        self.quota_bytes = None
        # aggregate inputs execute chunked through host RAM when the scan
        # working set overruns device memory (tidb_tpu_stream_rows):
        # -1 = auto (bytes-based vs the device budget), >0 = explicit row
        # threshold, None/0 = never stream
        self.stream_rows = -1
        # kill safepoint hook (utils/sqlkiller): raises to abort
        self.kill_check = None
        # prepared-statement parameter bindings for the CURRENT statement
        # (slot -> numpy scalar in physical encoding); the session sets
        # them before run(). Empty for plain statements.
        self.param_values: Dict[int, object] = {}
        self.mesh = None
        self.mesh_n = mesh_devices
        if mesh_devices:
            from tidb_tpu.parallel.mesh import shared_mesh

            self.mesh = shared_mesh(mesh_devices)

    def _resolve(self, db: str, table: str):
        if self.table_hook is not None:
            return self.table_hook(db, table)
        t = self.catalog.table(db, table)
        return t, t.version

    def _params(self) -> Dict[int, "jax.Array"]:
        """Current prepared-statement bindings as device scalars (the
        second argument of every compiled program). Mesh programs never
        see runtime parameters (values are baked there)."""
        if not self.param_values or self.mesh is not None:
            return {}
        return {k: jnp.asarray(v) for k, v in self.param_values.items()}

    @staticmethod
    def watch_sig(key: tuple) -> tuple:
        """Version-independent plan signature for the engine watch's
        retrace accounting: _cache_key is (deliberately) version-keyed
        for plans over string columns, but a recompile of the same
        logical plan driven by data growth IS the retrace the watch
        exists to count — so the signature drops the version column."""
        fp, versions = key
        return (fp, tuple(v[:3] for v in versions))

    def _cache_key(self, plan: L.LogicalPlan) -> tuple:
        fp = plan_fingerprint(plan)
        versions = []

        def walk(p):
            if isinstance(p, L.Scan):
                t, v = self._resolve(p.db, p.table)
                # compiled plans bake in dictionary LUTs, so plans over
                # string columns are version-keyed; string-free scans
                # compile version-independent programs (data is re-fetched
                # every run) — iterative workloads (recursive CTEs, DML
                # loops) then reuse the jit instead of recompiling
                types = t.schema.types
                has_str = any(
                    types.get(c) is not None and types[c].kind == Kind.STRING
                    for c in p.columns
                )
                versions.append(
                    (p.db, p.table, getattr(t, "uid", None) or id(t), v if has_str else -1)
                )
            for c in _plan_children(p):
                walk(c)

        walk(plan)
        return (fp, tuple(versions))

    def _fetch_inputs(
        self, cq: CompiledQuery, mesh=None, pins=None, resolved=None,
        staged=None,
    ) -> Dict[int, Batch]:
        inputs = {}
        for nid, skey in cq.staged_sites:
            if staged is None or skey not in staged:
                raise ExecError(
                    f"keyed staged input {skey!r} missing at run time"
                )
            inputs[nid] = staged[skey]
        for s in cq.scans:
            t, v = self._resolve(s.db, s.table)
            if pins is not None:
                # hold the snapshot for this statement: concurrent
                # committers bump versions and GC old ones; an unpinned
                # in-flight read racing 2+ commits would KeyError.
                # pin-then-verify closes the resolve/pin window: once a
                # pin lands on a still-present version, GC keeps it.
                for _ in range(8):
                    if t.pin_verified(v):
                        break
                    t, v = self._resolve(s.db, s.table)
                else:
                    raise ExecError(f"snapshot of {s.db}.{s.table} vanished")
                pins.append((t, v))
            if resolved is not None:
                resolved[s.node_id] = (t, v)
            narrowed = (
                fetch_site_rows(t, s, v)
                # a fragment slice addresses the FULL block concatenation:
                # index-narrowed gathers would re-number rows and break
                # the disjoint per-host cover
                if mesh is None and s.frag is None
                else None
            )
            if narrowed is not None:
                inputs[s.node_id] = narrowed
            else:
                batch, _d = scan_table(
                    t, s.columns, version=v, mesh=mesh,
                    partitions=s.partitions, frag=s.frag,
                )
                inputs[s.node_id] = batch
        return inputs

    def _make_program(self, cq: CompiledQuery, frozen_caps: Dict[int, int]):
        """The whole-query callable over (inputs, params): plain plan fn
        on one device, or the shard_map-wrapped SPMD program on a mesh
        (the entire fragment tree is ONE collective XLA program —
        exchanges are all_to_all/all_gather inside, not RPCs). `params`
        is the prepared-statement parameter dict (slot -> scalar array),
        made visible to compiled literal readers during tracing; empty
        for plain statements, and always empty on a mesh (the session
        bakes parameter values into mesh plans)."""
        fn = cq.fn
        if self.mesh is None:
            from tidb_tpu.expression.kernels import param_scope

            def prog(i, p, _f=fn, _c=frozen_caps):
                with param_scope(p), expansion_ledger() as expanded, \
                        grouping_ledger() as grouped:
                    b, needs = _f(i, _c)
                return b, _with_groupings(
                    _with_expansions(needs, expanded), grouped
                )

            return prog
        from jax.sharding import PartitionSpec as P

        from tidb_tpu.parallel.mesh import pmax, reshard, shard_map

        from tidb_tpu.parallel.exchange import sent_ledger

        n = self.mesh_n

        def local(i, _f=fn, _c=frozen_caps):
            with sent_ledger() as sent, expansion_ledger() as expanded, \
                    grouping_ledger() as grouped:
                b, needs = _f(i, _c)
            # pmax proves replication of the cardinality scalars to
            # shard_map AND takes the per-shard max for sizing knobs
            needs = {k: pmax(v, "d") for k, v in needs.items()}
            # a shard's expanding joins, summed over the shards
            needs = _with_expansions(
                needs, [(jax.lax.psum(r, "d"), s * n) for r, s in expanded]
            )
            # and its sorted group-bys
            needs = _with_groupings(
                needs,
                [
                    (jax.lax.psum(r, "d"), jax.lax.psum(g, "d"), s * n)
                    for r, g, s in grouped
                ],
            )
            # what the program's exchanges sent, beside them: how many
            # there are, their rows and their cross-chip bytes
            needs[_EXCHANGES] = jnp.int64(len(sent))
            needs[_EXCHANGE_ROWS] = sum((r for r, _ in sent), jnp.int64(0))
            needs[_EXCHANGE_BYTES] = sum((b for _, b in sent), jnp.int64(0))
            return b, needs

        sm = shard_map(
            local, mesh=self.mesh, in_specs=(P("d"),), out_specs=(P("d"), P())
        )
        if cq.out_tag == "repl":
            from jax.sharding import NamedSharding

            repl = NamedSharding(self.mesh, P())

            def run_repl(i, _p=None):
                b, needs = sm(i)
                # replicated output: every shard emitted an identical full
                # copy; reshard (so the slice is legal for any mesh size)
                # and keep the first copy
                b = jax.tree.map(
                    lambda a: reshard(a, repl)[: a.shape[0] // n], b
                )
                return b, needs

            return run_repl
        return lambda i, _p=None, _sm=sm: _sm(i)

    def _admit(self, cq: CompiledQuery, inputs, caps) -> None:
        """Quota admission: pre-account every static buffer (scan batches
        + sized-node tiles) against tidb_mem_quota_query BEFORE launching.
        The reference escalates via ActionOnExceed (spill/cancel,
        pkg/util/memory/action.go:30); with static shapes the whole
        footprint is known up front, so over-quota queries are rejected
        with a tracker report instead of being killed mid-flight."""
        quota = self.quota_bytes
        # working-set estimate (inputs + operator tiles) — always
        # computed: the instance watchdog ranks sessions by it when the
        # server memory limit is breached (servermemorylimit.go:51)
        ws = 0
        for _nid, b in inputs.items():
            nb = b.capacity
            for dc in b.cols.values():
                nb += b.capacity * (dc.data.dtype.itemsize + 1)
            ws += nb
        # an operator's tile is allocated on every shard of a mesh
        shards = self.mesh_n or 1
        for nid, cap in caps.items():
            ws += 2 * cap * cq.widths.get(nid, 64) * shards
        self.last_working_set = ws
        ENGINE_WATCH.note_device_mem(ws)
        if not quota:
            return
        from tidb_tpu.utils.failpoint import inject
        from tidb_tpu.utils.memtrack import MemoryTracker, QuotaExceeded

        inject("executor/admission")
        root = MemoryTracker("query", quota_bytes=int(quota))
        scans = root.child("scans")
        nodes = root.child("operators")
        try:
            for nid, b in inputs.items():
                nb = b.capacity
                for dc in b.cols.values():
                    nb += b.capacity * (dc.data.dtype.itemsize + 1)
                scans.child(f"scan#{nid}").consume(nb)
            for nid, cap in caps.items():
                w = cq.widths.get(nid, 64)
                # keyed group tables allocate 2x slots; exchanges double-
                # buffer: a conservative 2x multiplier covers both
                nodes.child(f"node#{nid}").consume(2 * cap * w * shards)
        except QuotaExceeded as e:
            report = "\n".join(root.report())
            raise ExecError(
                f"memory quota exceeded ({e}); tracker report:\n{report}"
            ) from None

    def _discover(
        self, cq: CompiledQuery, inputs, jit: bool = True
    ) -> Tuple[Batch, Dict[int, int]]:
        """Find the capacity vector. Each iteration compiles the whole plan
        at the candidate caps and fetches only the cardinality scalars in a
        single device->host round trip (small transfers are latency-bound:
        8 bytes cost about what 32MB does). jit=False
        runs op-by-op for the instrumented EXPLAIN ANALYZE path."""
        from tidb_tpu.utils import failpoint

        failpoint.inject("executor/before-discover")
        caps = dict(cq.caps or cq.default_caps)
        # tiles no run has proven: all of them on a first discovery
        # (the planner's estimates, and 0 for a join re-joined on a row
        # id), none once an execution has left its caps
        defaulted = [] if cq.caps else list(caps)
        for nid, c in caps.items():
            if c == 0:  # such a join starts at the dominant input tile
                d = _join_default(inputs, cq)
                if jit and self.mesh_n:
                    d = _cap_tile(max(d // self.mesh_n, 1024))
                caps[nid] = d
        if self.quota_bytes and defaulted:
            # under a memory quota, DEFAULT tiles must not fail
            # admission on their own: start small enough to fit and let
            # the overflow loop grow each knob only as the data proves
            # necessary — every growth re-admits, so a genuinely
            # over-quota cardinality still errors with the tracker
            # report (reference: quota actions escalate before failing,
            # pkg/util/memory/action.go). Only guesses are clamped (the
            # planner's estimates, _join_default) — capacities a
            # previous execution DISCOVERED are known-needed;
            # re-clamping them would force a re-discovery launch on
            # every run
            share = max(int(self.quota_bytes) // (4 * len(caps)), 1)
            for nid in defaulted:
                w = cq.widths.get(nid, 64)
                lim = _cap_tile(
                    max(share // (2 * max(w, 1) * (self.mesh_n or 1)), 1024)
                )
                if caps[nid] > lim:
                    caps[nid] = lim
        from tidb_tpu.utils.sqlkiller import current_check

        while True:
            if self.kill_check is not None:
                self.kill_check()
            else:
                # no explicitly-wired killer (worker-side producer/
                # consumer executors shared across shuffle tasks): the
                # thread-local current killer — set per dispatched
                # fragment/shuffle task around execution — makes
                # fleet-wide cancellation land at the same safepoint
                current_check()
            self._admit(cq, inputs, caps)
            frozen = dict(caps)
            if jit:
                jitted = watched_jit(
                    self._make_program(cq, frozen), sig=("discover", cq.sig)
                )
            else:
                # eager single-device path (EXPLAIN ANALYZE instrumentation)
                fn = cq.fn
                jitted = lambda i, _p, _f=fn, _c=frozen: _f(i, _c)
            with FLIGHT.span("dispatch"):
                with FLIGHT.span("launch"):
                    out, needs = jitted(inputs, self._params())
                with FLIGHT.span("enqueue-fetch"):
                    _enqueue_fetch(needs)
            with FLIGHT.span("device-wait"):
                jax.block_until_ready((needs, out))
            with FLIGHT.span("fetch"):
                needs_host = _take_program_stats(jax.device_get(needs))
            bumped = False
            for nid, true_n in needs_host.items():
                n = int(true_n)
                if n >= _WIDTH_STALE:
                    # baked packed-key bounds no longer cover the data:
                    # capacity bumps can't fix this — recompile the plan
                    # against fresh Table.col_bounds (run()'s retry loop)
                    raise StaleWidthsError()
                if n > caps[nid]:
                    failpoint.inject("executor/cap-overflow")
                    if nid in cq.exchange_nids:
                        from tidb_tpu.utils.metrics import REGISTRY

                        REGISTRY.counter(
                            "tidbtpu_executor_exchange_overflow_retries_total",
                            "whole-program recompiles because an "
                            "exchange's bucket tile overflowed",
                        ).inc()
                    if nid in cq.expand_nids:
                        _expand_overflow_retries().inc()
                    caps[nid] = _cap_tile(n)
                    if caps[nid] > _MAX_JOIN_CAP:
                        raise ExecError(f"result too large at node {nid}: {n} rows")
                    bumped = True
            if not bumped:
                # shrink every knob to the tight tile of its true
                # cardinality: small group tables unlock the scatter-free
                # masked aggregation path, and join/exchange tiles stop
                # inheriting the (huge) default of their input capacity
                if not cq.no_shrink:
                    for nid, true_n in needs_host.items():
                        if nid in caps:
                            tile = max(
                                _cap_tile(int(true_n)), cq.floors.get(nid, 0)
                            )
                            caps[nid] = min(caps[nid], tile)
                return out, caps

    def run(self, plan: L.LogicalPlan) -> Tuple[Batch, Dicts]:
        from tidb_tpu.planner.hostagg import try_host_agg
        from tidb_tpu.planner.streamed import try_partitioned, try_streamed
        from tidb_tpu.utils.metrics import REGISTRY

        # keyed staged inputs (shuffle consumers, the DCN final stage):
        # their batches are fed at run time through _run_pinned — the
        # streamed/partitioned re-chunkers compile their own pipelines
        # and never feed staged sites, so keyed plans must take the
        # compiled path only (their sources are already resident device
        # batches; there is nothing to page in anyway)
        staged = _staged_inputs(plan)
        # stale-width retry: programs bake integer key bounds as static
        # widths and verify them at run time; growth past them recompiles
        # against fresh bounds. The last attempts compile conservatively
        # (no runtime-verified assumptions) so even an assumption the
        # data permanently violates terminates.
        for _stale_attempt in range(4):
            conservative = _stale_attempt >= 2
            try:
                hosted = try_host_agg(self, plan)
                if hosted is not None:
                    return hosted
                if staged is None:
                    streamed = try_streamed(
                        self, plan, conservative=conservative
                    )
                    if streamed is not None:
                        return streamed
                    parted = try_partitioned(
                        self, plan, conservative=conservative
                    )
                    if parted is not None:
                        return parted

                # the compiled plan for this shape: the key, the
                # session's cache, the shared one, or a new compile
                with FLIGHT.span("plan-cache"):
                    key = self._cache_key(plan)
                    cq = None if conservative else self._cache.get(key)
                    shareable = not conservative and _plan_shareable(plan)
                    claimed = False
                    if cq is None and shareable:
                        # cross-session tier: another session/connection may
                        # already have compiled this exact plan shape (the
                        # serving-tier reuse — one compile serves the
                        # fleet). A miss CLAIMS the key (singleflight):
                        # publish or abandon below, or waiters stall
                        cq = SHARED_PLAN_CACHE.get(
                            self.mesh_n, key, owner=id(self)
                        )
                        claimed = cq is None
                        if cq is not None:
                            # imported entries honor the same LRU bound as
                            # compiles, or cross-session hits would grow
                            # the private cache without limit
                            while len(self._cache) >= 256:
                                self._cache.popitem(last=False)
                            self._cache[key] = cq
                    # flight recorder: plan-cache outcome + plan digest for
                    # the statements_summary attribution (obs/flight.py)
                    FLIGHT.note_plan_cache(cq is not None, key=key)
                    if cq is not None:
                        self._cache.move_to_end(key)
                        REGISTRY.counter("tidbtpu_executor_plan_cache_hits_total").inc()
                    else:
                        REGISTRY.counter("tidbtpu_executor_plan_cache_misses_total").inc()
                        try:
                            compiler = PlanCompiler(
                                self.catalog, resolver=self._resolve,
                                mesh_n=self.mesh_n, conservative=conservative,
                            )
                            cq = compiler.compile(plan)
                            cq.sig = self.watch_sig(key)
                        except BaseException:
                            if claimed:
                                SHARED_PLAN_CACHE.abandon(
                                    self.mesh_n, key, id(self)
                                )
                            raise
                        while len(self._cache) >= 256:
                            self._cache.popitem(last=False)
                        self._cache[key] = cq
                        if shareable:
                            SHARED_PLAN_CACHE.put(
                                self.mesh_n, key, cq, owner=id(self)
                            )

                pins = []
                try:
                    return self._run_pinned(cq, pins, staged=staged)
                except ExecError as e:
                    # quota admission rejected the unpaged plan: retry
                    # with streaming FORCED — the aggregate's own
                    # working set fit the budget, but join tiles above
                    # it did not (the reference escalates the same way:
                    # memory-tracker pressure triggers spill actions,
                    # pkg/util/memory/action.go). Keyed staged plans
                    # never stream (see above): for them the quota
                    # rejection surfaces as-is.
                    if staged is None and "memory quota exceeded" in str(e):
                        forced = try_streamed(
                            self, plan, conservative=conservative,
                            force=True,
                        )
                        if forced is None:
                            forced = try_partitioned(
                                self, plan, conservative=conservative,
                                force=True,
                            )
                        if forced is not None:
                            return forced
                    raise
                finally:
                    for t, v in pins:
                        t.unpin(v)
            except StaleWidthsError:
                key = self._cache_key(plan)
                self._cache.pop(key, None)
                # stale widths are a property of the PLAN, not of this
                # executor: evict the shared entry too, or every other
                # session keeps re-importing the stale program
                SHARED_PLAN_CACHE.invalidate(self.mesh_n, key)
                sp = getattr(self, "_stream_plans", {})
                for k in [k for k in sp if k[0] == key]:
                    sp.pop(k, None)
        raise ExecError("packed key widths did not stabilize after recompiles")

    def _steady_at(self, cq: CompiledQuery, caps, out_cap: int, inputs):
        """Compile the steady program at `caps` and run it once:
        (callable, output, cardinalities on the host)."""
        program = self._make_program(cq, dict(caps))
        jitted = watched_jit(
            lambda i, pv, _p=program, _oc=out_cap: _steady_step(
                _p, _oc, i, pv, mesh=self.mesh
            ),
            sig=("steady", cq.sig),
        )
        out, needs_host = _launch_and_fetch(jitted, inputs, self._params())
        return jitted, out, needs_host

    def _steady_first(self, cq: CompiledQuery, inputs, shape_key):
        """A plan's first execution where every knob has a first tile
        without a run, from the planner's estimates
        (PlanCompiler._first_tile; a join re-joined on a row id has
        none): compile the STEADY program at them and run it, in the
        discover program's place. It returns the knobs' true
        cardinalities like any steady run. Nothing overflowed and no
        tile is more than twice its tight one: that program is
        published as the steady one and the statement has compiled one
        whole program, not two (on the v5e host a mesh Q5's take 90 s
        each, PERF.md PR 29). A looser tile: the tight tiles are known,
        the steady program is compiled at them, and discovery is
        skipped all the same. An overflow, which cuts short what lies
        downstream of it: the discover loop, as before.

        Returns (output, None, 0) when the first program was kept,
        (None, tight caps, tight output tile) when it ran clean and is
        to be tightened, (None, None, 0) when discovery has to run."""
        first = cq.default_caps
        first_out = cq.first_out_cap
        if cq.caps or not first_out or not all(first.values()):
            return None, None, 0
        from tidb_tpu.utils.metrics import REGISTRY

        outcomes = REGISTRY.counter(
            "tidbtpu_executor_steady_first_total",
            "plans first compiled as their steady program at "
            "estimated tiles: kept, tightened (recompiled at the tight "
            "tiles, no discovery), overflowed (discovery ran)",
            labels=("outcome",),
        )
        from tidb_tpu.utils import failpoint

        # a plan's first program, in the discover program's place
        failpoint.inject("executor/before-discover")
        if self.kill_check is not None:
            self.kill_check()
        else:
            # worker-side executors: the thread's current killer, at
            # the safepoint _discover gives them
            from tidb_tpu.utils.sqlkiller import current_check

            current_check()
        try:
            self._admit(cq, inputs, first)
        except ExecError:
            # estimated tiles must not fail admission on their own:
            # discovery starts them small enough and grows them as the
            # data proves (_discover)
            return None, None, 0
        jitted, out, needs_host = self._steady_at(cq, first, first_out, inputs)
        full = {**first, _OUT_NODE: first_out}
        if _overflowed(needs_host, full):
            if any(int(n) >= _WIDTH_STALE for n in needs_host.values()):
                # baked key bounds no longer cover the data: no tile fixes that
                raise StaleWidthsError()
            outcomes.labels(outcome="overflowed").inc()
            for nid in cq.expand_nids:
                if int(needs_host.get(nid, 0)) > full.get(nid, 0):
                    _expand_overflow_retries().inc()
            # discovery starts from what this run saw (a lower bound
            # downstream of the overflow), not from the estimates again
            cq.caps = {
                nid: max(cap, _cap_tile(int(needs_host.get(nid, 0))))
                for nid, cap in first.items()
            }
            return None, None, 0
        tight = {
            nid: max(_cap_tile(int(needs_host[nid])), cq.floors.get(nid, 0))
            if nid in needs_host else cap
            for nid, cap in full.items()
        }
        if all(cap <= 2 * tight[nid] for nid, cap in full.items()):
            outcomes.labels(outcome="kept").inc()
            cq.caps = dict(full)
            cq.steady = (jitted, full, shape_key)
            return out, None, 0
        outcomes.labels(outcome="tightened").inc()
        out_cap = tight.pop(_OUT_NODE)
        return None, tight, out_cap

    def _run_pinned(
        self, cq: CompiledQuery, pins, staged=None
    ) -> Tuple[Batch, Dicts]:
        resolved = {}
        with FLIGHT.span("inputs"):
            inputs = self._fetch_inputs(
                cq, mesh=self.mesh, pins=pins, resolved=resolved,
                staged=staged,
            )
        # compile-time NULL-free assumptions: columns whose validity mask
        # was folded away must still be NULL-free at the fetched version
        # (host-side O(1) after the table's per-version cache warms)
        for nid, col in cq.nonnull:
            t, v = resolved[nid]
            if t.col_has_nulls(col, v):
                raise StaleWidthsError()
        # compile-time bounds that narrowed a wide sum: the fetched
        # version must still fit the baked interval or single-lane
        # accumulation could silently wrap — recompile instead
        for nid, col, lo, hi in cq.bound_checks:
            t, v = resolved[nid]
            cb = t.col_bounds(col, v)
            if cb is not None and (cb[0] < lo or cb[1] > hi):
                raise StaleWidthsError()
        shape_key = tuple(sorted((nid, b.capacity) for nid, b in inputs.items()))

        # the steady snapshot is read as ONE tuple: under the shared
        # cross-session plan cache, another executor may republish it
        # concurrently, and a (program, caps) pair from two different
        # publishes could accept a truncated output
        st = cq.steady
        if st is not None and st[2] == shape_key:
            st_jitted, st_caps, _sk = st
            out, needs_host = _launch_and_fetch(
                st_jitted, inputs, self._params()
            )
            if not _overflowed(needs_host, st_caps):
                return out, cq.out_dicts
            # data grew past a tile: rediscover (drop the snapshot only
            # if it is still the one that overflowed)
            if cq.steady is st:
                cq.steady = None

        out, caps, out_cap = self._steady_first(cq, inputs, shape_key)
        if out is not None:
            return out, cq.out_dicts  # the first program is the steady one
        for _attempt in range(8):
            if caps is None:
                out, caps = self._discover(cq, inputs)
                nvalid = int(jax.device_get(_count_valid(out.row_valid)))
                out_cap = min(
                    max(_cap_tile(nvalid), cq.floors.get(_OUT_NODE, 0)),
                    out.capacity,
                )
            full_caps = dict(caps)
            full_caps[_OUT_NODE] = out_cap
            cq.caps = dict(full_caps)  # warm-start hint for _discover
            # compile + run the steady program now so every later run is a
            # single launch + single fetch
            jitted, out, needs_host = self._steady_at(cq, caps, out_cap, inputs)
            if not _overflowed(needs_host, full_caps):
                # verified: publish the consistent snapshot atomically
                cq.steady = (jitted, full_caps, shape_key)
                return out, cq.out_dicts
            # the post-shrink steady run overflowed: stop shrinking this
            # plan's caps and rediscover from the grown values
            cq.no_shrink = True
            for nid, n in needs_host.items():
                if nid in caps and int(n) > caps[nid]:
                    caps[nid] = _cap_tile(int(n))
            cq.caps = dict(caps)
            caps = None
        raise ExecError("capacity discovery did not converge")

    def run_analyze(
        self, plan: L.LogicalPlan, frag_stats=None, shuffle_stats=None
    ) -> Tuple[Batch, Dicts, List[str]]:
        """EXPLAIN ANALYZE: instrumented single run with per-node stats.

        `frag_stats` is the distributed case (parallel/dcn.py): per-host
        fragment runtime stats gathered from the worker replies, merged
        into the plan-tree rows beneath the Staged exchange node the way
        the reference merges cop-task RuntimeStatsColl into the
        coordinator's plan tree. `shuffle_stats` is the worker-to-worker
        shuffle case: a (stage summary, per-partition infos) pair whose
        Shuffle exchange rows render the same way."""
        from tidb_tpu.planner.hostagg import _find_gc_agg, try_host_agg

        if _find_gc_agg(plan) is not None:
            # GROUP_CONCAT aggregates execute host-assisted — per-node
            # device instrumentation doesn't apply; report the plan shape
            # with timing of the whole statement instead of crashing in
            # the device compiler (which has no string-concat kernel)
            import time as _time

            t0 = _time.perf_counter()
            out, dicts = try_host_agg(self, plan)
            dt = (_time.perf_counter() - t0) * 1000
            lines = [
                f"HostAssistedAggregate(GROUP_CONCAT)  time={dt:.2f}ms "
                "(per-node stats unavailable on the host-assisted path)"
            ]
            return out, dicts, lines
        compiler = PlanCompiler(self.catalog, instrument=True, resolver=self._resolve)
        cq = compiler.compile(plan)
        # unsharded: eager single-device (keyed staged batches fed like
        # the run() path)
        inputs = self._fetch_inputs(cq, staged=_staged_inputs(plan))
        out, _caps = self._discover(cq, inputs, jit=False)
        lines = []
        for nid, depth, label in compiler.node_labels:
            st = compiler.stats.get(nid)
            suffix = (
                f"  rows={st['rows']} time={st['time_s']*1000:.2f}ms calls={st['calls']}"
                if st
                else ""
            )
            lines.append("  " * depth + label + suffix)
        if frag_stats:
            lines = _merge_frag_stats(lines, frag_stats)
        if shuffle_stats:
            if isinstance(shuffle_stats, list):
                # shuffle DAG: one (stage summary, infos) pair per
                # exchange stage, rendered topo-order under the
                # Staged node with the same grammar (each insert
                # lands directly below the anchor, so reversed
                # iteration leaves stage 0 on top)
                for stage, infos in reversed(shuffle_stats):
                    lines = _merge_shuffle_stats(lines, stage, infos)
            else:
                lines = _merge_shuffle_stats(lines, *shuffle_stats)
        return out, cq.out_dicts, lines


def _merge_frag_stats(lines: List[str], frag_stats) -> List[str]:
    """Insert per-host fragment rows into an EXPLAIN ANALYZE plan tree
    beneath the Staged node (the DCN exchange's coordinator side): one
    summary row (time min/avg/max across hosts, total rows and bytes
    shipped) plus one row per fragment (rows/host, execution time,
    bytes). The distributed analog of the reference's cop-task rows."""
    frags = sorted(frag_stats, key=lambda f: f.get("fid", 0))
    times = [float(f.get("exec_s", 0.0)) for f in frags] or [0.0]
    hosts = sorted({f.get("host", "?") for f in frags})
    total_bytes = sum(int(f.get("bytes", 0)) for f in frags)
    total_rows = sum(int(f.get("rows", 0)) for f in frags)
    summary = (
        f"DCNFragments fragments={len(frags)} hosts={len(hosts)} "
        f"rows={total_rows} bytes_shipped={total_bytes} "
        f"time min={min(times)*1000:.2f}ms "
        f"avg={(sum(times)/len(times))*1000:.2f}ms "
        f"max={max(times)*1000:.2f}ms"
    )
    summary += _compile_cost_suffix(frags)
    per_frag = [
        (
            f"Fragment#{f.get('fid')} host={f.get('host', '?')} "
            f"attempt={f.get('attempt', 1)} rows={f.get('rows', 0)} "
            f"time={float(f.get('exec_s', 0.0))*1000:.2f}ms "
            f"bytes={f.get('bytes', 0)}"
        )
        for f in frags
    ]
    return _insert_below_staged(lines, summary, per_frag)


def _compile_cost_suffix(frags) -> str:
    """Worker-reported XLA compile cost summed across the fenced
    fragment replies (obs/engine_watch.py harvest, shipped in reply
    stats) — rendered on the exchange summary row when any worker
    actually compiled during this statement. Empty on warm runs."""
    flops = sum(
        float((f.get("compile") or {}).get("flops", 0.0)) for f in frags
    )
    nbytes = sum(
        float((f.get("compile") or {}).get("bytes_accessed", 0.0))
        for f in frags
    )
    if not flops and not nbytes:
        return ""
    return (
        f" compile_flops={flops:.0f} compile_bytes_accessed={nbytes:.0f}"
    )


def _insert_below_staged(
    lines: List[str], summary: str, rows: List[str]
) -> List[str]:
    """Splice an exchange block (one summary line + indented per-unit
    rows) beneath the plan tree's Staged node — the coordinator side
    of any DCN exchange. Shared by the fragment and shuffle renderers
    so the anchor/indent rules never diverge."""
    idx = next(
        (i for i, ln in enumerate(lines) if ln.lstrip().startswith("Staged")),
        None,
    )
    if idx is None:
        pad = ""
        insert_at = len(lines)
    else:
        pad = " " * (len(lines[idx]) - len(lines[idx].lstrip()) + 2)
        insert_at = idx + 1
    block = [pad + summary] + [pad + "  " + r for r in rows]
    return lines[:insert_at] + block + lines[insert_at:]


def _merge_shuffle_stats(lines: List[str], stage, infos) -> List[str]:
    """Insert the worker-to-worker shuffle exchange rows into an
    EXPLAIN ANALYZE plan tree beneath the Staged node: one DCNShuffle
    summary (partition count, attempts, tunnel bytes/rows, stalls,
    retransmits) plus one ShuffleExchange row per partition — the MPP
    ExchangeSender/ExchangeReceiver rows of the reference's plan tree,
    rendered coordinator-side from the fenced task replies."""
    frags = sorted(infos, key=lambda f: f.get("fid", 0))
    hosts = sorted({f.get("host", "?") for f in frags})
    total_rows = sum(int(f.get("rows", 0)) for f in frags)
    # overlap: the share of total worker stage time NOT spent blocked
    # idle in the store waits — the pipelining win made visible (a
    # barrier stage idles through the whole exchange; a pipelined one
    # decodes/stages on arrival while producers still run)
    total_exec = float(stage.get("exec_s", 0.0)) or sum(
        float(f.get("exec_s", 0.0)) for f in frags
    )
    idle = float(stage.get("wait_idle_s", 0.0))
    overlap = max(0.0, 1.0 - idle / total_exec) if total_exec > 0 else 0.0
    # shuffle-DAG stages additionally carry their chain position, the
    # exchange kind chosen per edge (hash | range | broadcast), and
    # the per-stage produce/wait/stage phase seconds
    dag_bits = ""
    if "exchange" in stage:
        modes = stage.get("modes") or ()
        exch = (
            "broadcast"
            if "broadcast" in modes
            else stage.get("exchange", "hash")
        )
        pos = (
            f"stage={int(stage.get('stage', 0)) + 1}/"
            f"{int(stage.get('n_stages', 1))} "
            if "stage" in stage else ""
        )
        dag_bits = (
            pos
            + f"exchange={exch} "
            f"produce={float(stage.get('produce_s', 0.0))*1000:.2f}ms "
            f"wait={float(stage.get('wait_s', 0.0))*1000:.2f}ms "
            f"stage_s={float(stage.get('stage_s', 0.0))*1000:.2f}ms "
        )
    # AQE (parallel/aqe.py): every taken adaptive decision renders on
    # the exchange row (adaptive=salted:3|broadcast-switch|feedback),
    # and the per-partition received-row skew ratio renders whenever
    # partition counts exist — detection stays auditable even when
    # nothing triggered
    aqe_bits = ""
    if stage.get("skew"):
        aqe_bits += f" skew={float(stage['skew']):.2f}"
    if stage.get("adaptive"):
        aqe_bits += f" adaptive={'|'.join(stage['adaptive'])}"
    # runtime filter (PR 19): kind + bloom geometry + predicted vs
    # OBSERVED selectivity (kept/tested probe rows — the auto cost
    # gate's feedback signal), and filter-lost degrade counts
    rf = stage.get("rf")
    if rf:
        aqe_bits += f" rf={rf.get('kind', '?')}"
        if rf.get("bits"):
            aqe_bits += f":{int(rf['bits'])}b"
        if rf.get("sel_pred") is not None:
            aqe_bits += f" sel_pred={float(rf['sel_pred']):.3f}"
        if rf.get("sel_obs") is not None:
            aqe_bits += f" sel_obs={float(rf['sel_obs']):.3f}"
        if rf.get("lost"):
            aqe_bits += f" rf_lost={int(rf['lost'])}"
    summary = (
        f"DCNShuffle kind={stage.get('kind')} "
        + dag_bits
        + f"partitions={stage.get('m')} hosts={len(hosts)} "
        f"attempts={stage.get('attempts')} rows={total_rows} "
        f"bytes_tunneled={stage.get('bytes_tunneled')} "
        f"rows_tunneled={stage.get('rows_tunneled')} "
        f"local_rows={stage.get('local_rows')} "
        f"stalls={stage.get('stalls')} "
        f"retransmits={stage.get('retransmits')} "
        f"codec={stage.get('codec', 'json')} "
        f"encode={float(stage.get('encode_s', 0.0))*1000:.2f}ms "
        f"pipeline={'on' if stage.get('pipeline') else 'off'} "
        f"overlap={overlap*100:.0f}% "
        f"wait_idle={idle*1000:.2f}ms "
        f"ttff={float(stage.get('ttff_s', 0.0))*1000:.2f}ms"
        + aqe_bits
    )
    summary += _compile_cost_suffix(frags)
    per_part = [
        (
            f"ShuffleExchange part={f.get('fid')} "
            f"host={f.get('host', '?')} attempt={f.get('attempt', 1)} "
            f"rows={f.get('rows', 0)} "
            f"time={float(f.get('exec_s', 0.0))*1000:.2f}ms "
            f"pushed={f.get('pushed_bytes', 0)}B "
            f"stalls={f.get('stalls', 0)} "
            f"wait_idle={float(f.get('wait_idle_s', 0.0))*1000:.2f}ms "
            f"ttff={float(f.get('ttff_s', 0.0))*1000:.2f}ms"
        )
        for f in frags
    ]
    return _insert_below_staged(lines, summary, per_part)


# pseudo node id for the final output's compaction capacity
_OUT_NODE = -1
# pseudo node ids of what a mesh program's exchanges sent (never knobs)
_EXCHANGES, _EXCHANGE_ROWS, _EXCHANGE_BYTES = -2, -3, -4
# and of what a program's expanding joins emitted: how many there are,
# their true output rows, their output tiles' slots
_EXPANSIONS, _EXPAND_ROWS, _EXPAND_SLOTS = -5, -6, -7
# and of what its sorted group-bys did: how many there are, the valid
# rows that entered them, the groups they found, their tables' slots
_GROUPINGS, _GROUP_ROWS, _GROUPS, _GROUP_SLOTS = -8, -9, -10, -11


def _expand_overflow_retries():
    from tidb_tpu.utils.metrics import REGISTRY

    return REGISTRY.counter(
        "tidbtpu_executor_join_expand_overflow_retries_total",
        "whole-program recompiles because an expanding join's output "
        "tile overflowed",
    )


def _with_expansions(needs: dict, expanded: list) -> dict:
    """The program's cardinality scalars with what its expanding joins
    (executor/join.expansion_ledger: one (rows, slots) a join) emitted
    beside them: the same fetch brings both."""
    if not expanded:
        return needs
    needs = dict(needs)
    needs[_EXPANSIONS] = jnp.int64(len(expanded))
    needs[_EXPAND_ROWS] = sum((r for r, _ in expanded), jnp.int64(0))
    needs[_EXPAND_SLOTS] = jnp.int64(sum(s for _, s in expanded))
    return needs


def _with_groupings(needs: dict, grouped: list) -> dict:
    """The program's cardinality scalars with what its sorted group-bys
    (executor/sortops.grouping_ledger: one (rows, groups, slots) a
    group-by) did beside them: the same fetch brings both."""
    if not grouped:
        return needs
    needs = dict(needs)
    needs[_GROUPINGS] = jnp.int64(len(grouped))
    needs[_GROUP_ROWS] = sum((r for r, _g, _s in grouped), jnp.int64(0))
    needs[_GROUPS] = sum((g for _r, g, _s in grouped), jnp.int64(0))
    needs[_GROUP_SLOTS] = jnp.int64(sum(s for _r, _g, s in grouped))
    return needs


def _take_program_stats(needs_host: dict) -> dict:
    """Take what the exchanges sent, what the expanding joins emitted
    and what the sorted group-bys did out of a program's fetched
    scalars, onto the statement's flight and the registry; what is
    left are the cardinalities of the knobs."""
    if not any(k in needs_host for k in (_EXCHANGES, _EXPANSIONS, _GROUPINGS)):
        return needs_host
    needs_host = dict(needs_host)
    from tidb_tpu.utils.metrics import REGISTRY

    if _EXCHANGES in needs_host:
        count, rows, nbytes = (
            int(needs_host.pop(k))
            for k in (_EXCHANGES, _EXCHANGE_ROWS, _EXCHANGE_BYTES)
        )
        REGISTRY.counter(
            "tidbtpu_executor_exchange_rows_total",
            "valid rows the executed mesh programs' exchanges sent, "
            "summed over shards",
        ).inc(rows)
        REGISTRY.counter(
            "tidbtpu_executor_exchange_bytes_total",
            "bytes those rows must carry between chips: rows x the "
            "travelling columns' logical width x the share that leaves "
            "its chip",
        ).inc(nbytes)
        FLIGHT.note_exchanges(count, rows, nbytes)
    if _EXPANSIONS in needs_host:
        count, rows, slots = (
            int(needs_host.pop(k))
            for k in (_EXPANSIONS, _EXPAND_ROWS, _EXPAND_SLOTS)
        )
        REGISTRY.counter(
            "tidbtpu_executor_join_expand_rows_total",
            "rows the executed programs' expanding joins had to emit "
            "(their true output, also where a tile overflowed)",
        ).inc(rows)
        FLIGHT.note_expansions(count, rows, slots)
    if _GROUPINGS in needs_host:
        count, rows, groups, slots = (
            int(needs_host.pop(k))
            for k in (_GROUPINGS, _GROUP_ROWS, _GROUPS, _GROUP_SLOTS)
        )
        REGISTRY.counter(
            "tidbtpu_executor_sorted_group_rows_total",
            "valid rows that entered the executed programs' sorted "
            "group-bys",
        ).inc(rows)
        FLIGHT.note_groupings(count, rows, groups, slots)
    return needs_host


def _steady_step(program, out_cap, inputs, params=None, mesh=None):
    """Steady-state whole-query program: plan (possibly a shard_map SPMD
    program) + output compaction + output cardinality, in one XLA launch.
    Compaction runs on the global (post-shard_map) arrays; on a mesh the
    result is resharded to replicated first (the compaction gather is not
    expressible over a row-sharded operand)."""
    out, needs = program(inputs, params)
    needs = dict(needs)
    needs[_OUT_NODE] = _count_valid(out.row_valid)
    if mesh is not None:
        # replicated whether or not it is compacted: every process of a
        # mesh that spans hosts fetches the answer, and an output tile
        # from an estimate (_steady_first) may be the whole capacity
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tidb_tpu.parallel.mesh import reshard

        repl = NamedSharding(mesh, P())
        out = jax.tree.map(lambda a: reshard(a, repl), out)
    if out_cap < out.capacity:
        out = _compact_impl(out, out_cap)
    return out, needs


def _enqueue_fetch(tree) -> None:
    """Queue the device->host copies of ``tree`` behind the program
    that produces it, as ``jax.device_get`` does on entry. Waiting for
    the program first and asking for the copies afterwards puts a host
    round trip between the program's end and the transfer's start:
    1 ms a statement, 1 % of the SF10 scan cell's rate (PERF.md, PR 27)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        leaf.copy_to_host_async()


def _launch_and_fetch(jitted, inputs, params):
    """One launch, one fetch, each boundary its own span: ``dispatch``
    until the program and the copies of what it will produce are
    enqueued, in two parts: ``launch``, the jitted call (a first call
    traces and compiles inside it), and ``enqueue-fetch``, the copies
    queued behind it; ``device-wait`` until the program has
    finished, ``fetch`` for the ONE device->host round trip of output
    batch + cardinality scalars together, which also warms each
    array's host-value cache so the session's materialization re-reads
    are free. Returns (out, needs on the host)."""
    with FLIGHT.span("dispatch"):
        with FLIGHT.span("launch"):
            out, needs = jitted(inputs, params)
        with FLIGHT.span("enqueue-fetch"):
            _enqueue_fetch((needs, out))
    with FLIGHT.span("device-wait"):
        jax.block_until_ready((needs, out))
    with FLIGHT.span("fetch"):
        needs_host = _take_program_stats(jax.device_get((needs, out))[0])
        ENGINE_WATCH.d2h_batch(out)
    return out, needs_host


def _overflowed(needs_host: Dict[int, np.ndarray], caps: Dict[int, int]) -> bool:
    for nid, true_n in needs_host.items():
        cap = caps.get(nid, 0)
        if cap and int(true_n) > cap:
            return True
    return False


def fetch_site_rows(t, site, version):
    """Narrowed host fetch for one scan site: PK range or index-merge
    union (shared by PhysicalExecutor._fetch_inputs and the streamed
    path's _fetch_resident — one implementation, no drift). Returns a
    device Batch or None when the site has no narrowing."""
    from tidb_tpu.chunk import block_to_batch

    if site.pk_range is not None:
        col, lo, hi = site.pk_range
        idx = t.range_rows(col, lo, hi, version=version)
        return block_to_batch(t.gather_rows(idx, site.columns, version=version))
    if getattr(site, "merge_ranges", None) is not None:
        ids = [
            t.range_rows(col, lo, hi, version=version)
            for col, lo, hi in site.merge_ranges
        ]
        idx = np.unique(np.concatenate(ids))
        return block_to_batch(t.gather_rows(idx, site.columns, version=version))
    return None


def _join_default(inputs, cq) -> int:
    return pad_capacity(max([b.capacity for b in inputs.values()] + [1024]))


# ---------------------------------------------------------------------------
# shared helpers (also used by PlanCompiler)
# ---------------------------------------------------------------------------


def scope_name(label: str, nid: int) -> str:
    """The name an operator's HLO ops carry on the device (``op_name``
    holds the stack of them, innermost last): what EXPLAIN prints for
    the node, cut to a length a trace viewer shows and kept free of the
    stack's own separator, then ``#`` and the node id."""
    return f"{label[:64].replace('/', '|')}#{nid}"


def _scoped(scope: str, fn: PlanFn) -> PlanFn:
    def scoped(inputs, caps):
        with jax.named_scope(scope):
            return fn(inputs, caps)

    return scoped


def _node_label(plan: L.LogicalPlan) -> str:
    name = type(plan).__name__
    if isinstance(plan, L.Scan):
        return f"Scan table={plan.db}.{plan.table} cols={len(plan.columns)}"
    if isinstance(plan, L.Selection):
        return f"Selection pred={plan.predicate!r}"
    if isinstance(plan, L.Aggregate):
        return (
            f"Aggregate groups={[n for n, _ in plan.group_exprs]} "
            f"aggs={[f'{f}({n})' for n, f, _, _ in plan.aggs]}"
        )
    if isinstance(plan, L.JoinPlan):
        return f"Join kind={plan.kind} keys={len(plan.equi_keys)}"
    if isinstance(plan, L.Sort):
        return f"Sort keys={len(plan.keys)}"
    if isinstance(plan, L.Window):
        return f"Window funcs={[d[1] for d in plan.descs]} parts={len(plan.partition_exprs)}"
    if isinstance(plan, L.Limit):
        return f"Limit limit={plan.count} offset={plan.offset}"
    if isinstance(plan, L.Projection):
        return (
            f"Projection exprs={[n for n, _ in plan.exprs]}"
            + (" +base" if plan.additive else "")
        )
    if isinstance(plan, L.UnionAll):
        return f"UnionAll branches={len(plan.children)}"
    return name


@jax.jit
def _count_valid(row_valid: jax.Array) -> jax.Array:
    return jnp.sum(row_valid.astype(jnp.int64))


def _compact_impl(batch: Batch, out_cap: int) -> Batch:
    """Stable-partition valid rows to the front and slice to out_cap —
    runs on device so only pad_capacity(true rows) transfers to host."""
    from tidb_tpu.executor.sortops import compaction_index

    sel, filled = compaction_index(batch.row_valid, out_cap)
    cols = {
        n: DevCol(c.data[sel], c.valid[sel]) for n, c in batch.cols.items()
    }
    return Batch(cols, filled)


def _bound_pred_cols(e):
    """Column names referenced by a bound predicate, or None when the
    tree contains nodes other than ColumnRef/Func/Literal (bail from
    HAVING fusion rather than guess)."""
    from tidb_tpu.expression.expr import Func, Literal

    out: set = set()

    def walk(x):
        if isinstance(x, ColumnRef):
            out.add(x.name)
        elif isinstance(x, Func):
            for a in x.args:
                if isinstance(a, (ColumnRef, Func, Literal)):
                    walk(a)
                elif isinstance(a, Expr):
                    raise _PredBail
        elif not isinstance(x, Literal):
            raise _PredBail

    try:
        walk(e)
    except _PredBail:
        return None
    return out


class _PredBail(Exception):
    pass


def _key_width(e: Expr, dicts: Dicts):
    """(bit width, bias) of a group key's packed encoding when a sound
    static bound exists (enables the scatter-free packed aggregation
    path); None otherwise. Integer-typed plain columns take their width
    from the storage layer's value bounds (Table.col_bounds, riding the
    dicts map), widened to what two data sets of one scale share: the
    lower bound down to a multiple of the power of two over the span,
    as many bits as then hold the upper one (one more than the exact
    span's at most). A program bakes the bias, so an exact one
    (-min(o_totalprice)) made Q18 a program per data set (PERF.md
    PR 35). The kernel verifies the widened bounds at run time, so
    growth past them re-plans instead of mis-grouping."""
    kind = e.type.kind if e.type is not None else None
    if kind == Kind.STRING:
        d = _expr_dict(e, dicts)
        if d is None:
            return None
        return (max(1, int(len(d)).bit_length()), 0)
    if isinstance(e, ColumnRef):
        cb = _resolve_bounds(dicts.get(_BOUNDS_PREFIX + e.name))
        if cb is not None:
            lo, hi = _grid(int(cb[0]), int(cb[1]))
            w = (hi - lo).bit_length()
            if w <= 40:
                return (w, -lo)
    if kind == Kind.DATE:
        return (33, 1 << 31)
    if kind == Kind.BOOL:
        return (2, 0)
    return None


def _expr_dict(e: Expr, dicts: Dicts) -> Optional[np.ndarray]:
    """Dictionary of a string-valued output expr (shared with the
    compiler's string_expr so codes and dictionary always agree)."""
    if e.type is None or e.type.kind != Kind.STRING:
        return None
    from tidb_tpu.expression.kernels import expr_dictionary

    return expr_dictionary(e, dicts)


def _join_key_props(e: Expr, dicts: Dicts):
    """(bounds, unique) of a join key column for the dense join paths.
    STRING keys are excluded: their codes are remapped into a merged
    dictionary by _align_key_fns, so the storage-level code bounds no
    longer describe the values the kernel sees."""
    if not isinstance(e, ColumnRef):
        return (None, False)
    if e.type is not None and e.type.kind == Kind.STRING:
        return (None, False)
    return (
        _resolve_bounds(dicts.get(_BOUNDS_PREFIX + e.name)),
        bool(dicts.get(_UNIQ_PREFIX + e.name)),
    )


def _align_key_fns(le: Expr, re_: Expr, ldicts: Dicts, rdicts: Dicts):
    """Compile join key exprs; for STRING keys, remap both sides' codes
    into a merged dictionary so integer equality == string equality."""
    if le.type is not None and le.type.kind == Kind.STRING:
        if not isinstance(le, ColumnRef) or not isinstance(re_, ColumnRef):
            raise ExecError("string join keys must be plain columns")
        ld = ldicts.get(le.name)
        rd = rdicts.get(re_.name)
        if ld is None or rd is None:
            raise ExecError("string join keys need dictionaries")
        # collation coercion: a CI collation on EITHER side makes the
        # join key CI — merge in sort-KEY space so equal-under-collation
        # values land on equal merged codes (collate.go Key() semantics)
        from tidb_tpu.utils import collate as _coll

        coll = le.type.collation or (
            re_.type.collation if re_.type is not None else None
        )
        _m, ll, lr = _coll.merge_rank_luts(ld, rd, coll)
        lut_l = jnp.asarray(np.asarray(ll, dtype=np.int32))
        lut_r = jnp.asarray(np.asarray(lr, dtype=np.int32))
        lname, rname = le.name, re_.name

        def _mapped(c: DevCol, lut) -> DevCol:
            if lut.shape[0] == 0:
                # an EMPTY dictionary (a 0-row shuffle partition's
                # staged side): no valid rows exist, so any constant
                # key works — never index into a size-0 LUT
                return DevCol(
                    jnp.zeros(c.data.shape, dtype=jnp.int32), c.valid
                )
            return DevCol(lut[jnp.clip(c.data, 0, lut.shape[0] - 1)], c.valid)

        def lf(b: Batch) -> DevCol:
            return _mapped(b.cols[lname], lut_l)

        def rf(b: Batch) -> DevCol:
            return _mapped(b.cols[rname], lut_r)

        return lf, rf
    lfn = compile_expr(le, ldicts)
    rfn = compile_expr(re_, rdicts)
    return lfn, rfn


def _hash_combine(key_fns):
    def f(b: Batch) -> DevCol:
        h = jnp.zeros(b.capacity, dtype=jnp.int64)
        valid = jnp.ones(b.capacity, dtype=bool)
        for fn in key_fns:
            c = fn(b)
            k = c.data.astype(jnp.int64)
            h = (h * jnp.int64(-7046029254386353131)) ^ (
                k + jnp.int64(-9061461749304837403) + (h << 6) + (h >> 2)
            )
            valid = valid & c.valid
        return DevCol(h, valid)

    return f


def _cross_join(left: Batch, right: Batch):
    """Nested-loop cross join via broadcast (small sides only)."""
    lcap, rcap = left.capacity, right.capacity
    if lcap * rcap > (1 << 24):
        raise ExecError("cross join too large")
    li = jnp.repeat(jnp.arange(lcap), rcap)
    ri = jnp.tile(jnp.arange(rcap), lcap)
    cols = {}
    for n, c in left.cols.items():
        cols[n] = DevCol(c.data[li], c.valid[li])
    for n, c in right.cols.items():
        cols[n] = DevCol(c.data[ri], c.valid[ri])
    rv = left.row_valid[li] & right.row_valid[ri]
    total = jnp.sum(rv.astype(jnp.int64))
    return Batch(cols, rv), total
