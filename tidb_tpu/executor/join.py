"""Equi-joins with static shapes: sort the build side, binary-search from
the probe side, expand matches into a fixed-capacity output.

Reference: HashJoinExec with build/probe workers
(pkg/executor/join/join.go:125,117,91) and the row-emit strategies in
join/joiner.go (inner, left outer, semi, anti). A device hash table needs
dynamic shapes, so the TPU formulation is:

  build:  sort build rows by key (lax.sort, invalid/NULL keys sink)
  probe:  lo/hi = searchsorted(build_keys, probe_key, left/right)
          counts = hi - lo                      (0 for NULL/invalid)
  expand: out_slot j -> probe row = searchsorted(cumsum(counts), j, right)
          build row  = lo[probe] + (j - cum[probe-1])

Everything is a fixed-size gather/scan; the true match total is returned
so the host retries at the next output-capacity tile on overflow — the
static-shape analog of the reference's spillable hashRowContainer
(join/hash_table.go).

Join types: inner, left (outer), semi, anti. Semi/anti never expand —
they just mask probe rows, like the reference's semi joiners.

Multi-column keys are packed into one i64 by the planner (dictionary codes
and small ints shift-packed); collisions are impossible because pack
layouts are chosen from column value ranges.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from tidb_tpu.chunk import Batch, DevCol
from tidb_tpu.executor.aggregate import WIDTH_STALE

ExprFn = Callable[[Batch], DevCol]

_TRACING = threading.local()


@contextlib.contextmanager
def expansion_ledger():
    """What the expanding joins traced inside emit: one (rows, slots)
    pair a join, `rows` the int64 scalar of its true output rows and
    `slots` its output tile. The program opens it around the plan and
    returns the sums beside its cardinality scalars
    (planner/physical.py), so a statement's flight can say how many rows
    its many-to-many joins made and how full their tiles were. One
    opened inside another (the planner's, around a single join) hands
    what it holds to the outer one when it closes."""
    prev = getattr(_TRACING, "expansions", None)
    _TRACING.expansions = out = []
    try:
        yield out
    finally:
        _TRACING.expansions = prev
        if prev is not None:
            prev.extend(out)


def _note_expansion(total: jax.Array, slots: int) -> None:
    """Count one traced expanding join (once per join per traced
    program, as the compactions are counted) and put it on the open
    ledger."""
    from tidb_tpu.utils.metrics import REGISTRY

    REGISTRY.counter(
        "tidbtpu_executor_join_expansions_total",
        "inner/left joins traced on the expanding path: a build key "
        "that is not unique, so every probe row emits one row a match "
        "into an output tile sized by discovery",
    ).inc()
    ledger = getattr(_TRACING, "expansions", None)
    if ledger is not None:
        ledger.append((total, int(slots)))


@contextlib.contextmanager
def residual_ledger():
    """What the semi and anti joins with a residual (a correlated
    EXISTS or NOT EXISTS: planner/physical.py) traced inside tested:
    one (pairs, kept) a join, the scalars of the valid pairs the
    residual was evaluated on and of those it passed. Opened and
    returned as expansion_ledger is."""
    prev = getattr(_TRACING, "residuals", None)
    _TRACING.residuals = out = []
    try:
        yield out
    finally:
        _TRACING.residuals = prev
        if prev is not None:
            prev.extend(out)


def note_residual(kind: str, pairs: jax.Array, kept: jax.Array) -> None:
    """Count one traced semi or anti join with a residual (once per
    join per traced program) and put its pairs on the open ledger."""
    from tidb_tpu.utils.metrics import REGISTRY

    REGISTRY.counter(
        "tidbtpu_executor_residual_joins_total",
        "semi/anti joins traced with a residual: an inner expansion "
        "carrying the probe's row id, the residual over its pairs, a "
        "semi or anti join back on the row id",
        labels=("kind",),
    ).labels(kind=kind).inc()
    ledger = getattr(_TRACING, "residuals", None)
    if ledger is not None:
        ledger.append((pairs, kept))


def _use_merge_probe(m: int) -> bool:
    """Replace per-row binary search with sortops.merge_searchsorted
    for large probe sides: searchsorted's log N rounds of random gather
    measured 161ms at 1M probes on the v5e vs ~15ms for the three
    regular sorts of the merge formulation. Below the cutoff the extra
    sorts don't pay."""
    return m >= 4096


def _probe_lo_hi(skey, pkey, need_hi: bool):
    """(lo, hi) insertion bounds of each probe key in the sorted build
    keys — jnp.searchsorted for small probes, merge sorts for large. hi
    comes from the run-end table (one reversed cummin) instead of a
    second search."""
    if not _use_merge_probe(pkey.shape[0]):
        lo = jnp.searchsorted(skey, pkey, side="left")
        hi = jnp.searchsorted(skey, pkey, side="right") if need_hi else None
        return lo, hi
    from tidb_tpu.executor.sortops import (
        gather_rows, merge_searchsorted, run_ends,
    )

    n = skey.shape[0]
    # int64 whatever the keys' width (narrowed keys are uint32)
    lo = merge_searchsorted(skey, pkey, side="left").astype(jnp.int64)
    if not need_hi:
        return lo, None
    # hi differs from lo only where the probe key occurs in skey; the
    # run of equal values starting at lo then ends at run_ends[lo]: the
    # key and the run's end at lo by one stacked gather
    lo_c = jnp.clip(lo, 0, n - 1)
    hit = (lo < n) & (skey[lo_c] == pkey)
    hi = jnp.where(hit, run_ends(skey)[lo_c], lo)
    return lo, hi


def _sort_build(bkey, bvalid, bcap: int, kbits: Optional[int] = None):
    """Build side sorted by key, NULL/invalid rows last: (skey carrying
    the sentinel, the largest value of its width, on invalid rows,
    svalid, perm). One packed unstable sort of (key, invalid flag, row
    id): three key limbs for a full int64 key, two for a key narrowed to
    `kbits` bits (_narrow_keys; sortops module docstring: compile time
    follows key limbs)."""
    from tidb_tpu.executor.sortops import (
        int_from_sort_bits, int_sort_bits, sort_rows, unpack_lex,
    )

    skey = jnp.where(bvalid, bkey, _sentinel(bkey.dtype, kbits))
    comp = int_sort_bits(skey) if kbits is None else (skey, kbits)
    ops, where, perm = sort_rows([comp, (~bvalid, 1)], bcap)
    return (
        int_from_sort_bits(unpack_lex(ops, where, 0), bkey.dtype),
        unpack_lex(ops, where, 1) == 0,
        perm,
    )


def _sentinel(dtype, kbits: Optional[int]):
    """The key an invalid build row carries: it sorts last."""
    if kbits is None:
        return jnp.iinfo(dtype).max
    return jnp.asarray((1 << kbits) - 1, dtype)


def _narrow_keys(bkey, bvalid, pkey, pvalid, build_bounds):
    """Both sides' keys as uint32 offsets, where the build key's static
    bounds (Table.col_bounds via the planner) lie between two multiples
    of 2**31: (bkey, bvalid, pkey, pvalid, stale, kbits); without such
    bounds the keys as they came, stale False and kbits None. A sort's
    compile time on the
    v5e grows with the square of its 32-bit key limbs and its run time
    with their number: an order number below 60,000 sorts in 16 bits,
    not 64. A valid build key outside the bounds means the data outgrew
    them: `stale`, the WIDTH_STALE contract of the dense paths. A probe
    key outside them matches nothing, as it must: it takes the value
    one below the invalid build rows' sentinel, which no build key has."""
    asis = bkey, bvalid, pkey, pvalid, False, None
    if build_bounds is None:
        return asis
    # the bounds widened to what does not change with a data set's own
    # smallest and largest key: from the multiple of 2**31 below the
    # lower one, as many bits as hold the upper one
    lo = (int(build_bounds[0]) >> 31) << 31
    if not lo <= int(build_bounds[1]) < lo + (1 << 31) - 2:
        return asis
    from tidb_tpu.executor.sortops import bits_for

    kbits = bits_for(int(build_bounds[1]) - lo + 3)
    hi = lo + (1 << kbits) - 3
    bin_ = bvalid & (bkey >= lo) & (bkey <= hi)
    stale = jnp.any(bvalid & ~bin_)
    pin = pvalid & (pkey >= lo) & (pkey <= hi)
    b32 = jnp.where(bin_, bkey - lo, (1 << kbits) - 1).astype(jnp.uint32)
    p32 = jnp.where(pin, pkey - lo, (1 << kbits) - 2).astype(jnp.uint32)
    return b32, bin_, p32, pin, stale, kbits


def _keys_of(batch: Batch, key_fn: ExprFn) -> Tuple[jax.Array, jax.Array]:
    k = key_fn(batch)
    valid = k.valid & batch.row_valid
    return k.data.astype(jnp.int64), valid


def _dense_span(build_bounds, bcap: int, pcap: int) -> Optional[int]:
    """Static dense-table span for a bounded build key, or None when the
    domain is too large/sparse for direct indexing to pay off.

    The dense table builds via scatter — the v5e runs large scatters
    serially while lax.sort runs regular strided passes, so dense only
    pays for small builds: past 65,536 build rows the callers sort."""
    if build_bounds is None or bcap > (1 << 16):
        return None
    lo, hi = build_bounds
    span = int(hi) - int(lo) + 1
    if span <= 0 or span > (1 << 24) or span > 4 * (bcap + pcap):
        return None
    return span


def _dense_build(bkey, bvalid, lo: int, hi: int, span: int):
    """(build offsets with OOB -> span, in-range mask, stale scalar).
    Bounds are compile-time constants from Table.col_bounds; a valid
    build key outside them means the data grew past the baked bounds —
    reported via the WIDTH_STALE sentinel so the host recompiles (the
    same contract as aggregate._pack_keys). Probe keys outside the
    bounds simply never match, which is already correct."""
    bin_ = bvalid & (bkey >= lo) & (bkey <= hi)
    stale = jnp.any(bvalid & ~bin_)
    boff = jnp.where(bin_, bkey - lo, span)
    return boff, bin_, stale


def _dense_unique_lookup(bkey, bvalid, lo: int, hi: int, span: int,
                         bcap: int, pkey, pvalid):
    """Dense direct-index lookup into a planner-proven-unique build key:
    (brow, matched, stale) probe-aligned; stale on outgrown bounds or a
    uniqueness violation (cnt > 1). Shared by equi_join's inner/left
    unique path and lookup_build_rows."""
    boff, _bin, stale = _dense_build(bkey, bvalid, lo, hi, span)
    rows = jnp.arange(bcap, dtype=jnp.int32)
    rowtab = (
        jnp.full(span, -1, dtype=jnp.int32).at[boff].max(rows, mode="drop")
    )
    cnt = (
        jnp.zeros(span, dtype=jnp.int32)
        .at[boff]
        .add(jnp.int32(1), mode="drop")
    )
    stale = stale | jnp.any(cnt > 1)
    pin = pvalid & (pkey >= lo) & (pkey <= hi)
    poff = jnp.clip(pkey - lo, 0, span - 1)
    brow_ = rowtab[jnp.where(pin, poff, 0)]
    matched = pin & (brow_ >= 0)
    return jnp.clip(brow_, 0, bcap - 1), matched, stale


def _sorted_unique_lookup(bkey, bvalid, bcap: int, pkey, pvalid,
                          build_bounds=None):
    """Sorted 1:1 lookup into a planner-proven-unique build key:
    (brow, matched, stale) probe-aligned. ONE searchsorted + one gather
    of the sorted build's (key, validity, row) at lo (uniqueness makes
    `hi` redundant: a hit is an equality at lo). Keys narrowed by the
    build's static bounds where it has them (_narrow_keys).
    stale must be the build-side adjacent-duplicate check — a
    probe-derived hi-lo>1 would also fire on garbage probe lanes equal
    to the invalid-row sentinel run, and a spurious stale is a
    recompile livelock."""
    from tidb_tpu.executor.sortops import gather_rows

    bkey, bvalid, pkey, pvalid, outgrown, kbits = _narrow_keys(
        bkey, bvalid, pkey, pvalid, build_bounds
    )
    skey, svalid, sperm = _sort_build(bkey, bvalid, bcap, kbits)
    lo, _hi = _probe_lo_hi(skey, pkey, need_hi=False)
    with jax.named_scope("lookup"):
        (key_at, brow), (valid_at,) = gather_rows(
            [skey, sperm], [svalid], jnp.clip(lo, 0, bcap - 1)
        )
    matched = pvalid & (lo < bcap) & valid_at & (key_at == pkey)
    stale = jnp.any(svalid[1:] & (skey[1:] == skey[:-1])) | outgrown
    return brow, matched, stale


def lookup_build_rows(
    build: Batch,
    probe: Batch,
    build_key: ExprFn,
    probe_key: ExprFn,
    build_bounds: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Probe-aligned row lookup into a build side the planner proved
    UNIQUE on the key: returns (brow, matched, stale) where brow[i] is
    the build row matching probe row i (clipped junk where unmatched),
    matched is the probe-aligned hit mask, and stale flags a broken
    compile-time assumption (bounds outgrown / uniqueness violated) for
    the WIDTH_STALE recompile contract. One table build + one probe
    pass — no expansion, no cumsum; the primitive behind multi-key
    semi/anti joins with a unique pair (planner demotes the remaining
    equalities to a verify mask over the gathered build columns)."""
    bkey, bvalid = _keys_of(build, build_key)
    pkey, pvalid = _keys_of(probe, probe_key)
    bcap = build.capacity
    span = _dense_span(build_bounds, bcap, probe.capacity)
    if span is not None:
        lo, hi = build_bounds
        brow, matched, stale = _dense_unique_lookup(
            bkey, bvalid, lo, hi, span, bcap, pkey, pvalid
        )
        return brow, matched, stale
    return _sorted_unique_lookup(
        bkey, bvalid, bcap, pkey, pvalid, build_bounds
    )


def gather_cols(batch: Batch, index: jax.Array, names=None) -> Dict[str, DevCol]:
    """The columns of `batch` (those in `names`; None: all) at rows
    `index`, moved by ONE gather of their stacked lanes
    (sortops.gather_rows)."""
    from tidb_tpu.executor.sortops import gather_rows

    cols = {n: c for n, c in batch.cols.items() if names is None or n in names}
    data, flag = gather_rows(
        [c.data for c in cols.values()], [c.valid for c in cols.values()], index
    )
    return {n: DevCol(d, v) for n, d, v in zip(cols, data, flag)}


def _emit(
    probe: Batch,
    build: Batch,
    prow: Optional[jax.Array],
    brow: jax.Array,
    out_valid: jax.Array,
    bmatched: Optional[jax.Array],
    probe_prefix: str,
    build_prefix: str,
    keep=None,
    aligned: bool = False,
) -> Batch:
    """The output tile of an inner/left join, a side by ONE gather of
    its columns' stacked lanes (sortops.gather_rows): slot j holds probe
    row prow[j] (None: the probe tile as it stands, slot j = row j)
    beside build row brow[j]; build columns are NULL where ~bmatched (a
    left join's unmatched row; None: none is). `aligned`: brow and
    bmatched are indexed by probe row, as a unique lookup gives them,
    and move through prow as lanes beside the probe's columns. `keep`:
    the (probe, build) column names something reads; None emits every
    column. Every column is invalid where ~out_valid; the data there is
    whatever the gather met."""
    from tidb_tpu.executor.sortops import gather_rows

    pnames, bnames = keep if keep is not None else (None, None)
    pcols = {
        n: c for n, c in probe.cols.items() if pnames is None or n in pnames
    }
    pdata = [c.data for c in pcols.values()]
    pflag = [c.valid for c in pcols.values()]
    if prow is not None:
        if aligned:
            pdata.append(brow)
            if bmatched is not None:
                pflag.append(bmatched)
        pdata, pflag = gather_rows(pdata, pflag, prow)
        if aligned:
            brow = pdata.pop()
            if bmatched is not None:
                bmatched = pflag.pop()
    bcols = gather_cols(build, brow, bnames)
    bvalid = out_valid if bmatched is None else out_valid & bmatched
    cols: Dict[str, DevCol] = {}
    for name, d, v in zip(pcols, pdata, pflag):
        cols[probe_prefix + name] = DevCol(d, v & out_valid)
    for name, c in bcols.items():
        cols[build_prefix + name] = DevCol(c.data, c.valid & bvalid)
    return Batch(cols, out_valid)


def equi_join(
    build: Batch,
    probe: Batch,
    build_key: ExprFn,
    probe_key: ExprFn,
    out_capacity: int,
    join_type: str = "inner",
    build_prefix: str = "",
    probe_prefix: str = "",
    mark_name: str = "_mark",
    mark_three_valued: bool = True,
    build_bounds: Optional[Tuple[int, int]] = None,
    build_unique: bool = False,
    keep=None,
) -> Tuple[Batch, jax.Array]:
    """Returns (joined batch, true output row count).

    For semi/anti the result is the probe batch with a refined row_valid
    (and the true surviving row count); out_capacity is ignored.
    For left joins, unmatched probe rows emit once with NULL build columns.

    build_bounds: static (min, max) of the build key (Table.col_bounds
    via the planner) — enables dense direct indexing instead of
    sort + searchsorted: existence scatters for semi/anti/mark, and a
    1:1 row table for inner/left when the planner proves the build key
    unique (build_unique: PK / unique index / GROUP BY output).
    Both bounds and uniqueness are runtime-verified; violations report
    the WIDTH_STALE sentinel in place of the row count and the executor
    recompiles with fresh bounds.

    keep: the (probe, build) column names the join's readers use
    (JoinPlan.needs through the planner): an inner/left join emits only
    those, since a side's columns move as ONE stacked operand from which
    XLA can drop no unread column. None emits every column."""

    from tidb_tpu.utils.failpoint import inject

    inject("executor/join")
    bkey, bvalid = _keys_of(build, build_key)
    pkey, pvalid = _keys_of(probe, probe_key)
    bcap = build.capacity
    span = _dense_span(build_bounds, bcap, probe.capacity)

    if join_type in ("semi", "anti", "mark") and span is not None:
        lo, hi = build_bounds
        boff, _bin, stale = _dense_build(bkey, bvalid, lo, hi, span)
        occ = jnp.zeros(span, dtype=bool).at[boff].set(True, mode="drop")
        pin = pvalid & (pkey >= lo) & (pkey <= hi)
        poff = jnp.clip(pkey - lo, 0, span - 1)
        matched = pin & occ[jnp.where(pin, poff, 0)]
        if join_type == "mark":
            build_has_null = jnp.any(build.row_valid & ~bvalid)
            build_empty = ~jnp.any(build.row_valid)
            if mark_three_valued:
                mvalid = probe.row_valid & (
                    matched | build_empty | (pvalid & ~build_has_null)
                )
            else:
                mvalid = probe.row_valid
            cols = dict(probe.cols)
            cols[mark_name] = DevCol(matched, mvalid)
            out = Batch(cols, probe.row_valid)
        else:
            keep = (
                matched
                if join_type == "semi"
                else (~matched & probe.row_valid & pvalid)
            )
            if join_type == "anti":
                keep = keep | (~pvalid & probe.row_valid)
            out = Batch(probe.cols, probe.row_valid & keep)
        total = jnp.sum(out.row_valid.astype(jnp.int64))
        return out, jnp.where(stale, jnp.int64(WIDTH_STALE), total)

    if join_type in ("inner", "left") and build_unique:
        if span is not None:
            lo, hi = build_bounds
            brow, matched, stale = _dense_unique_lookup(
                bkey, bvalid, lo, hi, span, bcap, pkey, pvalid
            )
        else:
            # unique build without a usable dense span (domain too
            # large/sparse, or a build past 65,536 rows): sorted lookup —
            # sort the build once, one searchsorted per probe, still 1:1
            # probe-aligned with NO expansion pass (vs the generic
            # expand path below that pays cumsum + output re-gather)
            brow, matched, stale = _sorted_unique_lookup(
                bkey, bvalid, bcap, pkey, pvalid, build_bounds
            )
        # 1:1 with the probe side: the output IS the probe batch (same
        # capacity, row_valid refined) plus gathered build columns — no
        # expansion pass. When capacity discovery has shrunk the output
        # tile below the probe tile (selective join), compact surviving
        # rows into it so downstream operators (and the memory budget)
        # pay for matches, not for the probe capacity.
        inner = join_type == "inner"
        out_valid = probe.row_valid & matched if inner else probe.row_valid
        total = jnp.sum(out_valid.astype(jnp.int64))
        total = jnp.where(stale, jnp.int64(WIDTH_STALE), total)
        if not 0 < out_capacity < probe.capacity:
            out = _emit(
                probe, build, None, brow, out_valid,
                None if inner else matched, probe_prefix, build_prefix, keep,
            )
            return out, total
        from tidb_tpu.executor.sortops import compaction_index
        from tidb_tpu.utils.metrics import REGISTRY

        # counted while the program is traced: once per join per
        # compiled program
        REGISTRY.counter(
            "tidbtpu_executor_join_compactions_total",
            "unique-build joins traced with an output tile smaller "
            "than the probe tile (one compaction index + two gathers)",
        ).inc()
        with jax.named_scope("compact"):
            # slot j <- probe row sel[j], in probe order: the probe's
            # columns, and beside them the lookup's build row (and a
            # left join's match flag), by one gather through sel; the
            # build's columns by one more, at out_capacity rows,
            # through the build row that one brought. Slots from `total`
            # on hold some dropped row's data under valid == False. The
            # tile's validity is an iota compare, not the index's
            # `filled`: that one hangs on the sort's output and the v5e
            # compiler fuses it into a column's gather, 24 ms dearer at
            # 2,097,152 rows (PR 28).
            sel, _filled = compaction_index(out_valid, out_capacity)
            rv = jnp.arange(out_capacity) < jnp.minimum(total, out_capacity)
            out = _emit(
                probe, build, sel, brow, rv, None if inner else matched,
                probe_prefix, build_prefix, keep, aligned=True,
            )
        return out, total

    # no dense table from here on: the build is sorted, on keys narrowed
    # by its static bounds where it has them. What the narrowing drops
    # (a key outside the bounds) matches nothing, so the rows' own
    # validity (pvalid, bvalid) still decides every NULL below.
    skeys_b, sbvalid, skeys_p, spvalid, outgrown, kbits = _narrow_keys(
        bkey, bvalid, pkey, pvalid, build_bounds
    )

    def _or_stale(total):
        if outgrown is False:
            return total
        return jnp.where(outgrown, jnp.int64(WIDTH_STALE), total)

    if join_type in ("semi", "anti", "mark"):
        skey = jax.lax.sort(
            [jnp.where(sbvalid, skeys_b, _sentinel(skeys_b.dtype, kbits))],
            is_stable=False,
        )[0]
        # existence needs no run length: the key at lo is the probe's
        lo, _hi = _probe_lo_hi(skey, skeys_p, need_hi=False)
        matched = spvalid & (lo < bcap) & (
            skey[jnp.clip(lo, 0, bcap - 1)] == skeys_p
        )
        if join_type == "mark":
            # mark join: every probe row survives and gains a boolean
            # column holding the (three-valued) IN/EXISTS result — the
            # reference's mark join for subqueries in value positions
            # (expression_rewriter.go's LeftOuterSemiJoin). With
            # mark_three_valued (IN semantics): no-match is NULL when
            # the probe key is NULL or the build side contains a NULL.
            build_has_null = jnp.any(build.row_valid & ~bvalid)
            build_empty = ~jnp.any(build.row_valid)
            if mark_three_valued:
                # x IN (empty set) is FALSE even for NULL x (MySQL);
                # otherwise a no-match is NULL when the probe key is
                # NULL or the build side contains a NULL
                mvalid = probe.row_valid & (
                    matched | build_empty | (pvalid & ~build_has_null)
                )
            else:  # EXISTS: always two-valued
                mvalid = probe.row_valid
            cols = dict(probe.cols)
            cols[mark_name] = DevCol(matched, mvalid)
            out = Batch(cols, probe.row_valid)
            return out, _or_stale(jnp.sum(out.row_valid.astype(jnp.int64)))
        keep = matched if join_type == "semi" else (~matched & probe.row_valid & pvalid)
        if join_type == "anti":
            # NULL probe key in NOT IN/anti: row never matches but with a
            # NULL key the comparison is NULL -> row is dropped too (the
            # null-aware anti-join case, reference join/joiner.go). Plain
            # NOT EXISTS keeps it; planner selects via null_aware flag.
            keep = keep | (~pvalid & probe.row_valid)
        out = Batch(probe.cols, probe.row_valid & keep)
        return out, _or_stale(jnp.sum(out.row_valid.astype(jnp.int64)))

    # ---- inner / left: sort build side, carry permutation ----
    skey, _svalid, sperm = _sort_build(skeys_b, sbvalid, bcap, kbits)

    with jax.named_scope("expand"):
        with jax.named_scope("search"):
            lo, hi = _probe_lo_hi(skey, skeys_p, need_hi=True)
            counts = jnp.where(spvalid & probe.row_valid, hi - lo, 0)
            if join_type == "left":
                emit = jnp.where(probe.row_valid, jnp.maximum(counts, 1), 0)
            else:
                emit = counts

            # the true total in 64 bits; the running count in 32: where
            # the total fits the tile (below 2**31 slots) no prefix
            # passes it, and where it does not the tile is thrown away
            # and the statement retried at the total. An int64 cumsum
            # over 786,432 rows takes the v5e compiler 48 s, an int32
            # one 2 s (described v5e:2x2, PR 33), and the slot search
            # sorts one limb a key for it.
            total = jnp.sum(emit.astype(jnp.int64))
            cum = jnp.cumsum(emit.astype(jnp.int32))
            # out slot j -> probe row
            slots = jnp.arange(out_capacity, dtype=jnp.int32)
            if _use_merge_probe(out_capacity):
                from tidb_tpu.executor.sortops import merge_searchsorted

                prow = merge_searchsorted(cum, slots, side="right")
            else:
                prow = jnp.searchsorted(cum, slots, side="right")
        _note_expansion(total, out_capacity)
        with jax.named_scope("gather"):
            # slot j of probe row p holds sorted build row lo[p] + (j -
            # the slots before p's): one int32 a probe row says where a
            # row's matches start, and every slot reads it by ONE gather
            # (at 16,777,216 slots a plain gather is 144 ms on the v5e,
            # an int64 one 348: cum, emit, lo and counts each took one)
            prow_c = jnp.clip(prow, 0, probe.capacity - 1)
            first = (lo - (cum - emit)).astype(jnp.int32)
            out_valid = slots < total
            brow = sperm[jnp.clip(slots + first[prow_c], 0, bcap - 1)]
            # a left join's unmatched row emits one slot of NULLs
            bmatched = None if join_type == "inner" else (counts > 0)[prow_c]

            out = _emit(
                probe, build, prow_c, brow, out_valid, bmatched,
                probe_prefix, build_prefix, keep,
            )
    return out, _or_stale(total)
