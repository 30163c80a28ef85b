"""Cardinality estimation: the stats -> planner loop.

Reference: pkg/planner/cardinality/selectivity.go (predicate selectivity
from histograms/TopN/NDV), pkg/statistics/histogram.go. ANALYZE stores
exact per-column stats on the table (tidb_tpu/stats/collect.py); this
module consumes them to estimate row counts of logical subtrees. The
estimates drive join ordering, broadcast-vs-repartition exchange choice
(pkg/planner/core/exhaust_physical_plans.go MPP join picks), and the
est-rows column of EXPLAIN (pkg/planner/core/explain.go).

Without ANALYZE the estimator falls back to the reference's pseudo
selectivities (pseudoEqualRate 1/1000, pseudoLessRate 1/3 in
pkg/statistics/table.go) softened for tiny tables.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import numpy as np

from tidb_tpu.dtypes import Kind, SQLType
from tidb_tpu.expression.expr import ColumnRef, Func, Literal

# pseudo selectivities (reference pkg/statistics/table.go pseudo rates)
SEL_EQ_DEFAULT = 0.05
SEL_RANGE_DEFAULT = 1.0 / 3.0
SEL_LIKE_PREFIX = 0.05
SEL_LIKE_CONTAINS = 0.10
SEL_DEFAULT = 0.25

# mesh exchange choice: a build side at most this many rows is cheaper to
# broadcast (all_gather of the small side) than to all_to_all both sides
BROADCAST_ROW_LIMIT = 65536


class StatsMap:
    """internal column name -> (ColumnStats|None, SQLType, table_rows)."""

    def __init__(self):
        self.cols: Dict[str, Tuple[object, SQLType, int]] = {}

    def add(self, name, stats, typ, table_rows):
        self.cols[name] = (stats, typ, table_rows)

    def stats_of(self, e) -> Optional[Tuple[object, SQLType, int]]:
        if isinstance(e, ColumnRef) and e.name in self.cols:
            return self.cols[e.name]
        return None

    def ndv_of(self, e) -> Optional[int]:
        got = self.stats_of(e)
        if got is None or got[0] is None:
            return None
        return max(int(got[0].ndv), 1)


def gather_stats(plan, catalog) -> StatsMap:
    """Collect column stats reachable from the plan's scans, following
    pass-through projection renames (derived tables / CTE wrappers)."""
    from tidb_tpu.planner import logical as L

    smap = StatsMap()

    def walk(p):
        for c in _children(p):
            walk(c)
        if isinstance(p, L.Scan):
            try:
                t = catalog.table(p.db, p.table)
            except Exception:
                return
            tstats = getattr(t, "stats", None) or {}
            types = dict(t.schema.columns)
            for c in p.columns:
                smap.add(
                    f"{p.alias}.{c}", tstats.get(c), types.get(c), t.nrows
                )
        elif isinstance(p, L.Projection):
            for name, e in p.exprs:
                if isinstance(e, ColumnRef) and e.name in smap.cols:
                    smap.cols[name] = smap.cols[e.name]

    walk(plan)
    return smap


def _children(p):
    out = []
    for attr in ("child", "left", "right"):
        c = getattr(p, attr, None)
        if c is not None:
            out.append(c)
    out.extend(getattr(p, "children", []) or [])
    return out


# ---------------------------------------------------------------------------
# predicate selectivity
# ---------------------------------------------------------------------------


def _encode_literal(value, typ: Optional[SQLType]):
    """Literal -> the column's raw on-device encoding (scaled decimal)."""
    if value is None or typ is None:
        return None
    if typ.kind == Kind.DECIMAL and isinstance(value, (int, float)):
        return round(float(value) * 10**typ.scale)
    if typ.kind == Kind.DATE and isinstance(value, str):
        try:
            from tidb_tpu.dtypes import date_to_days

            return int(date_to_days(value))
        except Exception:
            return None
    if typ.kind == Kind.DATETIME and isinstance(value, str):
        try:
            from tidb_tpu.dtypes import datetime_to_micros

            return int(datetime_to_micros(value))
        except Exception:
            return None
    if isinstance(value, (int, float)):
        return value
    return None  # strings handled via TopN only


def _col_lit(e: Func):
    """Match col-vs-literal in either order; returns (col, lit, flipped)."""
    a, b = e.args[0], e.args[1]
    if isinstance(a, ColumnRef) and isinstance(b, Literal):
        return a, b, False
    if isinstance(b, ColumnRef) and isinstance(a, Literal):
        return b, a, True
    return None, None, False


_FLIP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq", "ne": "ne"}


def selectivity(e, smap: StatsMap) -> float:
    """P(row passes predicate); independence assumed across conjuncts
    (the reference does the same absent multi-column stats)."""
    if e is None:
        return 1.0
    if isinstance(e, Literal):
        if e.value is None:
            return 0.0
        return 1.0 if e.value else 0.0
    if not isinstance(e, Func):
        return SEL_DEFAULT
    op = e.op
    if op == "and":
        # intersect range predicates on the same column before falling
        # back to the independence product — `d >= a AND d < b` is one
        # interval, not two independent 1/3s (reference: range building
        # in pkg/util/ranger feeding histogram row counts)
        conj = _flatten_and(e)
        ranges: Dict[str, list] = {}
        rest = []
        for c in conj:
            m = _range_bound(c, smap)
            if m is None:
                rest.append(c)
                continue
            col, kind, frac = m
            lo, hi = ranges.get(col, (0.0, 1.0))
            if kind == "lo":
                lo = max(lo, frac)
            else:
                hi = min(hi, frac)
            ranges[col] = [lo, hi]
        sel = 1.0
        for lo, hi in ranges.values():
            sel *= max(0.0, hi - lo)
        for c in rest:
            sel *= selectivity(c, smap)
        return sel
    if op == "or":
        s1 = selectivity(e.args[0], smap)
        s2 = selectivity(e.args[1], smap)
        return min(1.0, s1 + s2 - s1 * s2)
    if op == "not":
        return max(0.0, 1.0 - selectivity(e.args[0], smap))
    if op in ("eq", "ne", "lt", "le", "gt", "ge") and len(e.args) == 2:
        col, lit, flipped = _col_lit(e)
        if col is None:
            if op == "eq":
                # col = col (join-ish residual): 1/max ndv if known
                n1, n2 = smap.ndv_of(e.args[0]), smap.ndv_of(e.args[1])
                n = max(n1 or 0, n2 or 0)
                return 1.0 / n if n else SEL_EQ_DEFAULT
            if op in ("lt", "le", "gt", "ge"):
                p = _col_above_col(e.args[0], e.args[1], smap)
                if p is not None:
                    return p if op in ("gt", "ge") else 1.0 - p
            return SEL_RANGE_DEFAULT
        got = smap.stats_of(col)
        if got is None or got[0] is None:
            return SEL_EQ_DEFAULT if op in ("eq", "ne") else SEL_RANGE_DEFAULT
        st, typ, _rows = got
        total = max(st.row_count - st.null_count, 1)
        if op in ("eq", "ne"):
            sel = None
            for v, f in st.topn or []:
                if v == lit.value:
                    sel = f / total
                    break
            if sel is None:
                sel = 1.0 / max(st.ndv, 1)
            return min(1.0, sel) if op == "eq" else max(0.0, 1.0 - sel)
        x = _encode_literal(lit.value, typ)
        if x is None:
            return SEL_RANGE_DEFAULT
        real_op = _FLIP[op] if flipped else op
        frac = _hist_le_frac(st, x)
        if real_op in ("lt", "le"):
            return frac
        return max(0.0, 1.0 - frac)
    if op == "between" and len(e.args) == 3:
        col = e.args[0]
        got = smap.stats_of(col)
        if (
            got is not None
            and got[0] is not None
            and isinstance(e.args[1], Literal)
            and isinstance(e.args[2], Literal)
        ):
            st, typ, _rows = got
            lo = _encode_literal(e.args[1].value, typ)
            hi = _encode_literal(e.args[2].value, typ)
            if lo is not None and hi is not None:
                return max(0.0, _hist_le_frac(st, hi) - _hist_le_frac(st, lo - 1))
        return SEL_RANGE_DEFAULT / 2
    if op == "in":
        col = e.args[0]
        k = len(e.args) - 1
        ndv = smap.ndv_of(col)
        if ndv:
            return min(1.0, k / ndv)
        return min(1.0, k * SEL_EQ_DEFAULT)
    if op == "like":
        if isinstance(e.args[1], Literal) and isinstance(e.args[1].value, str):
            pat = e.args[1].value
            return SEL_LIKE_CONTAINS if pat.startswith("%") else SEL_LIKE_PREFIX
        return SEL_LIKE_CONTAINS
    if op in ("isnull",):
        got = smap.stats_of(e.args[0])
        if got is not None and got[0] is not None:
            st = got[0]
            return st.null_count / max(st.row_count, 1)
        return 0.02
    if op in ("isnotnull",):
        return 1.0 - selectivity(Func(e.type, "isnull", e.args), smap)
    return SEL_DEFAULT


def _col_above_col(a, b, smap: StatsMap) -> Optional[float]:
    """P(a > b) for two columns of one type that both have histograms
    (`l_receiptdate > l_commitdate`), the columns taken as independent:
    b's histogram read at the two edges of each of a's equal-depth
    buckets. None where either has none."""
    sa, sb = smap.stats_of(a), smap.stats_of(b)
    if sa is None or sb is None or sa[0] is None or sb[0] is None:
        return None
    if sa[1] != sb[1] or sa[1] is None or sa[1].kind not in (
        Kind.INT, Kind.DATE, Kind.DECIMAL
    ):
        return None
    hi = np.asarray(sa[0].bounds)
    if hi.size == 0 or np.asarray(sb[0].bounds).size == 0:
        return None
    lo = np.concatenate([hi[:1], hi[:-1]])
    below = [
        (_hist_le_frac(sb[0], x) + _hist_le_frac(sb[0], y)) / 2
        for x, y in zip(lo, hi)
    ]
    return float(np.mean(below))


def _flatten_and(e):
    if isinstance(e, Func) and e.op == "and":
        return _flatten_and(e.args[0]) + _flatten_and(e.args[1])
    return [e]


def _range_bound(e, smap: StatsMap):
    """Match a histogram-estimable one-sided range predicate; returns
    (column name, 'lo'|'hi', P(col <= bound)) or None."""
    if not (isinstance(e, Func) and e.op in ("lt", "le", "gt", "ge")):
        return None
    col, lit, flipped = _col_lit(e)
    if col is None:
        return None
    got = smap.stats_of(col)
    if got is None or got[0] is None:
        return None
    st, typ, _rows = got
    x = _encode_literal(lit.value, typ)
    if x is None:
        return None
    op = _FLIP[e.op] if flipped else e.op
    frac = _hist_le_frac(st, x)
    if op in ("lt", "le"):
        return col.name, "hi", frac
    return col.name, "lo", frac


def _hist_le_frac(st, x) -> float:
    """P(col <= x) from the equal-depth histogram bounds."""
    bounds = np.asarray(st.bounds)
    if bounds.size == 0:
        return SEL_RANGE_DEFAULT
    pos = int(np.searchsorted(bounds, x, side="right"))
    frac = pos / bounds.size
    lo = st.min_val
    if lo is not None and isinstance(lo, (int, float)) and x < lo:
        return 0.0
    return min(1.0, max(0.0, frac))


# ---------------------------------------------------------------------------
# row-count estimation over the logical tree
# ---------------------------------------------------------------------------


def est_rows(plan, catalog, smap: Optional[StatsMap] = None) -> float:
    """Estimate output rows; annotates every node with ``.est`` for
    EXPLAIN (the reference's estRows column). Annotations double as a
    memo: repeated estimation over shared subtrees during join building
    returns the cached value instead of re-walking (keeps planning O(k)
    in the number of joins, not O(k^2))."""
    from tidb_tpu.planner import logical as L

    if smap is None:
        smap = gather_stats(plan, catalog)

    def walk(p) -> float:
        cached = p.__dict__.get("est")
        if cached is not None:
            return cached
        if isinstance(p, L.Scan):
            try:
                n = float(catalog.table(p.db, p.table).nrows)
            except Exception:
                n = 1000.0
        elif isinstance(p, L.Selection):
            n = walk(p.child) * selectivity(p.predicate, smap)
        elif isinstance(p, L.JoinPlan):
            nl, nr = walk(p.left), walk(p.right)
            n = est_join(nl, nr, p.equi_keys, p.kind, smap)
            if p.residual is not None:
                n *= selectivity(p.residual, smap)
        elif isinstance(p, L.Aggregate):
            c = walk(p.child)
            if not p.group_exprs:
                n = 1.0
            else:
                ndv = 1.0
                known = True
                for _nm, ge in p.group_exprs:
                    gn = smap.ndv_of(ge)
                    if gn is None:
                        known = False
                        break
                    ndv *= gn
                # unknown group NDV: sqrt heuristic keeps it sublinear
                n = min(c, ndv) if known else min(c, max(1.0, math.sqrt(c) * 8))
        elif isinstance(p, L.Limit):
            n = min(walk(p.child), float(p.count))
        elif isinstance(p, L.Projection):
            n = walk(p.child)
        else:
            cs = _children(p)
            n = max((walk(c) for c in cs), default=1.0)
        p.est = max(n, 0.0)
        return p.est

    return walk(plan)


# ---------------------------------------------------------------------------
# history-seeded cardinality feedback (AQE, PR 15)
# ---------------------------------------------------------------------------


class CardinalityFeedback:
    """Per-digest OBSERVED cardinalities fed back into planning — the
    learned half of the cost model (the PR 8 admission-mem-estimate
    pattern applied to row counts). The DCN scheduler records each
    routed statement's per-side produced rows (exact, from the fenced
    worker stage stats) plus the root est/act pair; the next run of
    the same digest, with ``tidb_tpu_aqe_feedback=on``, seeds
    ``ShuffleSide.est_rows`` from the recorded actuals so
    ``shuffle_mode=auto`` gates and ``choose_edge_modes`` start from
    measured rather than static stats (parallel/dcn.py _choose_cut).

    ``warm_from_history`` re-seeds the store from
    information_schema.statements_summary_history rows after a
    restart of the live summary — the trajectories the StmtHistory
    fold-in keeps for exactly the digests the live map churned out.
    Bounded: oldest digest evicted past ``capacity``."""

    def __init__(self, capacity: int = 512):
        from tidb_tpu.utils import racecheck

        self._lock = racecheck.make_lock("planner.card_feedback")
        self._capacity = int(capacity)
        # digest -> {"sides": {tag: rows}, "est": float, "act": float,
        #            "n": int}
        self._map: Dict[str, dict] = {}

    def record(
        self, digest: str, est: float = 0.0, act: float = 0.0,
        sides: Optional[Dict[str, int]] = None,
    ) -> None:
        """``sides`` keys are ``"<kind>:<stage>:<tag>"`` — per-side
        produced rows from the fenced stage stats, scoped by the cut
        kind that executed (dcn._record_feedback)."""
        if not digest:
            return
        with self._lock:
            ent = self._map.pop(digest, None)
            if ent is None:
                ent = {"sides": {}, "est": 0.0, "act": 0.0, "n": 0}
            if sides:
                for tag, rows in sides.items():
                    ent["sides"][str(tag)] = int(rows)
            if est or act:
                ent["est"] = float(est)
                ent["act"] = float(act)
            ent["n"] += 1
            self._map[digest] = ent  # re-insert: LRU-ish recency
            while len(self._map) > self._capacity:
                self._map.pop(next(iter(self._map)))

    def sides_for(self, digest: str) -> Optional[Dict[int, int]]:
        """Observed per-side produced rows of this digest's last run,
        or None when nothing was recorded."""
        with self._lock:
            ent = self._map.get(digest)
            return dict(ent["sides"]) if ent and ent["sides"] else None

    def est_act(self, digest: str) -> Optional[Tuple[float, float]]:
        with self._lock:
            ent = self._map.get(digest)
            if ent is None or not (ent["est"] or ent["act"]):
                return None
            return ent["est"], ent["act"]

    def warm_from_history(self, history=None) -> int:
        """Seed root est/act pairs from statements_summary_history
        rows (per-side detail does not survive the summary fold, so
        warmed digests seed the divergence only). Returns the number
        of digests seeded."""
        if history is None:
            from tidb_tpu.utils.metrics import STMT_HISTORY

            history = STMT_HISTORY
        n = 0
        for _b, _e, row in history.rows():
            est = float(row.get("est_rows", 0.0) or 0.0)
            act = float(row.get("act_rows", 0.0) or 0.0)
            digest = row.get("digest_text", "")
            if digest and (est or act):
                self.record(digest, est=est, act=act)
                n += 1
        return n

    def reset(self) -> None:
        with self._lock:
            self._map.clear()


#: process-wide feedback store (one cost model per coordinator, like
#: the shared plan cache); tests construct private instances
CARD_FEEDBACK = CardinalityFeedback()


def est_join(nl: float, nr: float, equi_keys, kind: str, smap: StatsMap) -> float:
    if kind == "cross" or not equi_keys:
        return nl * nr
    denom = 1.0
    for le, re_ in equi_keys:
        n1 = smap.ndv_of(le)
        n2 = smap.ndv_of(re_)
        if n1 or n2:
            denom *= max(n1 or 1, n2 or 1)
        else:
            denom *= max(min(nl, nr), 1.0)
    n = nl * nr / max(denom, 1.0)
    if kind in ("semi", "anti"):
        n = min(n, nl)
    if kind == "left":
        n = max(n, nl)
    return max(n, 1.0)
