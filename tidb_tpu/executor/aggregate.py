"""Group aggregation with static shapes: one lowering per operator shape,
chosen from what is known when the program is traced (key widths and
counts), never from the platform or the environment.

Reference: the parallel hash aggregate with partial/final workers
(pkg/executor/aggregate/agg_hash_executor.go:60-91) and StreamAggExec
(agg_stream_executor.go:32). The reference builds a dynamic hash table;
a static-shape program has three shapes instead:

  no keys                  one group at slot 0, one full-array
                           reduction a lane (`_scalar_backend`)
  keys packing into        slot id == packed key over a dense domain of
  _DENSE_BITS bits or      at most 128 slots, one fused masked reduction
  fewer (Q1's flags)       per (slot, lane) (`_masked_backend`), then a
                           cumsum compaction of the occupied slots
  any other keys           sort rows by key, reduce runs by cumulative
                           sums and segmented scans
                           (`sortops.sort_group_aggregate`)

A scatter costs the v5e ~69 ns an update, serially; that is why no
shape above is built on one (sortops.py's header has the measurements).

DISTINCT sum/count/avg dedupe their (group, value) pairs with the one
hash table that is left, `group_assign`, a data-parallel claim loop over
a fixed power-of-two slot array:

  1. every row hashes its key to a slot,
  2. unassigned rows scatter-min their row id into the slot (the smallest
     row id claims it),
  3. rows whose key equals the claimer's key adopt the slot; the rest
     linear-probe to the next slot and repeat.

All rows of one key follow the same probe sequence, so each key settles
on exactly one slot and the loop runs for ~the longest probe chain.

Every shape returns the true group count; a count above the output tile
(or a pair table that overflowed its probe limit) makes the host bump
the capacity tile and re-jit — the analog of the reference's spill
escalation (aggregate/agg_spill.go), replaced by recompile-at-larger-
tile. The partial/final split of the reference maps to per-device local
aggregation followed by an all_to_all repartition of group keys and a
final aggregation (parallel/fragment.py), mirroring agg partial workers
-> shuffle -> final workers.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from tidb_tpu.chunk import Batch, DevCol, pad_capacity

ExprFn = Callable[[Batch], DevCol]

# linear-probe bound per table size; beyond this the table is declared
# full and the host retries at the next tile
_MAX_PROBES = 64

# widest packed key domain the dense path takes: 2**7 = 128 slots, the
# most that the masked reductions unroll over (Q1's two flags pack into
# 4 bits). Wider keys sort.
_DENSE_BITS = 7

# reported in place of the group count when a row's key falls outside the
# compile-time-baked packed-key bounds (int-column widths come from
# Table.col_bounds and data may have grown since): the executor recompiles
# the plan with fresh bounds (physical.StaleWidthsError) instead of
# bumping capacity tiles
WIDTH_STALE = 1 << 60


def _pack_keys(keys, key_widths, row_valid):
    """Pack key columns into one int64 (biased limbs, 0 = NULL) and
    verify every valid row's limb fits its baked width. Returns
    (packed [cap] int64, stale bool scalar)."""
    cap = row_valid.shape[0]
    packed = jnp.zeros(cap, dtype=jnp.int64)
    stale = jnp.zeros((), dtype=bool)
    off = 0
    for (w, b), k in zip(key_widths, keys):
        limb = jnp.where(k.valid, k.data.astype(jnp.int64) + (b + 1), 0)
        bad = k.valid & ((limb < 1) | (limb > ((1 << w) - 1)))
        stale = stale | jnp.any(row_valid & bad)
        packed = packed | (limb << off)
        off += w
    return packed, stale


@dataclasses.dataclass(frozen=True)
class AggDesc:
    """An aggregate: func in {sum,count,avg,min,max,first}, over arg_fn.

    count with arg_fn=None is COUNT(*). ``sum_as_float`` forces float
    accumulation (AVG over ints / DOUBLE sums).
    """

    func: str
    arg: Optional[ExprFn]
    out_name: str
    distinct: bool = False
    # decimal scale of the argument: AVG divides the float result by
    # 10**arg_scale to return true values (SUM keeps the scaled int,
    # typed DECIMAL(scale) by the planner).
    arg_scale: int = 0
    # wide accumulation for overflow-prone decimal sums (scale >= 4
    # products): the scaled-i64 argument is split into 30-bit lo and
    # high limbs, each summed exactly in int64 (safe to 2^31 rows of
    # 2^47-scale values), then recombined in float64 — no silent int64
    # wraparound at TPC-H SF100 scale. Reference: MyDecimal's 30-digit
    # fixed-point accumulators (pkg/types/mydecimal.go:236).
    wide: bool = False
    # post-reduction decode applied to min/max results (e.g. CI-collated
    # string MIN composes rank*D+code so the reduction orders by
    # collation; post extracts the original dict code). Skipped at the
    # partial stage of a split aggregation — only the final stage
    # decodes (parallel/fragment._partial_descs).
    post: Optional[Callable] = None
    # proven per-row |value| bound of an integer sum/avg argument
    # (interval arithmetic over storage bounds, re-verified at every
    # fetch via CompiledQuery.bound_checks): lets the kernel pack the
    # (sum, count) lane pair into ONE biased int64 reduction —
    # (value + bound) << count_bits | 1 — halving the reduction passes
    # (one lane instead of two, whatever the reducer).
    pack_bound: Optional[int] = None


def _next_pow2(n: int) -> int:
    return pad_capacity(n, floor=1, pow2=True)


def _key_components(k: DevCol):
    """(comparison components, hash int) of one group key column.

    Comparison components are compared with `==` in the claim loop, so
    they must (a) be canonical — equal SQL values compare equal — and
    (b) always terminate — no NaN != NaN. Floats are compared DIRECTLY as
    floats (bit extraction is impossible on TPU: the x64 rewrite
    implements neither f64 bitcast nor frexp, and its f64 is a float-pair
    emulation without full IEEE range), with NaN zeroed out and carried
    as a separate boolean component. The hash int for floats combines a
    clipped fixed-point projection with approximate mantissa/exponent
    projections — hash collisions only lengthen probe chains, never
    merge groups.
    """
    d = k.data
    if jnp.issubdtype(d.dtype, jnp.floating):
        dd = jnp.where(d == 0, jnp.zeros_like(d), d)  # -0.0 -> +0.0
        nanf = jnp.isnan(dd) & k.valid
        dd = jnp.where(nanf | ~k.valid, jnp.zeros_like(dd), dd)
        lim = 9.0e15  # stays exactly convertible to int64 after *1024
        hv = (jnp.clip(dd, -lim, lim) * 1024.0).astype(jnp.int64)
        # hv quantizes to 2^-10 within +-9e15; the mantissa (hm) and
        # exponent (he) projections keep values that clip/quantize
        # identically on separate probe chains; log2/exp2 are approximate
        # on TPU's f64 emulation, which is fine for a hash — the exact ==
        # compare guards correctness, collisions only lengthen probes
        a = jnp.abs(dd)
        e = jnp.log2(jnp.where(a > 0, a, 1.0))
        ef = jnp.floor(jnp.where(jnp.isfinite(e), e, 0.0))
        m = dd * jnp.exp2(-ef)
        m = jnp.where(jnp.isfinite(m), m, jnp.zeros_like(m))
        hm = (jnp.clip(m, -4.0, 4.0) * (2.0**40)).astype(jnp.int64)
        he = ef.astype(jnp.int64)
        h = (
            hv
            ^ jnp.asarray(_mix64(hm.astype(jnp.uint64))).astype(jnp.int64)
            ^ (he * jnp.int64(-7046029254386353131))  # 0x9E3779B97F4A7C15
        )
        h = h + nanf.astype(jnp.int64)
        return [dd, nanf], h
    vbd = jnp.where(k.valid, d.astype(jnp.int64), jnp.int64(0))
    return [vbd], vbd


def _mix64(x: jax.Array) -> jax.Array:
    """splitmix64 finalizer (public-domain constant mix)."""
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> 31)


def group_assign(
    keys: Sequence[DevCol], row_valid: jax.Array, slots: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Assign each valid row a slot in a `slots`-entry table by group key.

    Returns (seg [cap] int32 — slot per row, `slots` for dropped/invalid
    rows; claimer [slots] int32 — first (min) row id per occupied slot,
    cap for empty; ngroups scalar; overflow bool scalar).
    """
    cap = row_valid.shape[0]
    # per-key canonical components (zeroed for NULL) + validity, compared
    # separately with == — packing value+null into one int64 would wrap
    # mod 2^64 and merge keys that differ only in the top bit
    vbs = []
    h = jnp.zeros(cap, dtype=jnp.uint64)
    for k in keys:
        comps, hash_int = _key_components(k)
        vbs.append((comps, k.valid))
        h = _mix64(h + hash_int.astype(jnp.uint64) * 2 + k.valid)
    slot0 = (h & jnp.uint64(slots - 1)).astype(jnp.int32)
    row_id = jnp.arange(cap, dtype=jnp.int32)
    max_iters = min(slots, _MAX_PROBES)

    # Claim values encode (iteration, row id) as it*cap + row_id so that a
    # group arriving at a slot in a LATER iteration can never steal a slot
    # an earlier group already settled on (plain min-row-id would let a
    # lower row id overwrite an established claim and merge two groups).
    sentinel = jnp.int64((max_iters + 1) * cap)

    def cond(state):
        _claim, assigned, _probe, it = state
        return (it < max_iters) & jnp.any(row_valid & (assigned < 0))

    def body(state):
        claim, assigned, probe, it = state
        unassigned = row_valid & (assigned < 0)
        slot = (slot0 + probe) & (slots - 1)
        target = jnp.where(unassigned, slot, slots)
        val = it.astype(jnp.int64) * cap + row_id
        claim = claim.at[target].min(val, mode="drop")
        claimer_v = claim[slot]
        claimer = (claimer_v % cap).astype(jnp.int32)
        cl = jnp.minimum(claimer, cap - 1)
        same = claimer_v < sentinel
        for comps, kvalid in vbs:
            for c in comps:
                same = same & (c[cl] == c)
            same = same & (kvalid[cl] == kvalid)
        newly = unassigned & same
        assigned = jnp.where(newly, slot, assigned)
        probe = jnp.where(unassigned & ~same, probe + 1, probe)
        return claim, assigned, probe, it + 1

    # seed the carries from a varying input so the loop works unchanged
    # inside shard_map (fresh constants would be replicated and clash with
    # the varying carry outputs)
    z = jnp.min(row_valid.astype(jnp.int32)) * 0
    claim0 = jnp.full(slots + 1, sentinel, dtype=jnp.int64) + z
    assigned0 = jnp.full(cap, -1, dtype=jnp.int32) + z
    probe0 = jnp.zeros(cap, dtype=jnp.int32) + z
    claim, assigned, _probe, _it = jax.lax.while_loop(
        cond, body, (claim0, assigned0, probe0, jnp.int32(0) + z)
    )
    claimer_v = claim[:slots]
    occupied = claimer_v < sentinel
    claimer = jnp.where(
        occupied, (claimer_v % cap).astype(jnp.int32), jnp.int32(cap)
    )
    ngroups = jnp.sum(occupied.astype(jnp.int64))
    overflow = jnp.any(row_valid & (assigned < 0))
    seg = jnp.where(row_valid & (assigned >= 0), assigned, slots)
    return seg, claimer, ngroups, overflow


def _packs(a: AggDesc, col, cap: int) -> bool:
    """Whether a sum/avg lane qualifies for the packed (sum, count)
    single reduction: proven per-row bound, integer data, and the
    biased sum + count bits fit int64 at this batch capacity."""
    return (
        a.pack_bound is not None
        and not a.wide
        and col is not None
        and not jnp.issubdtype(col.data.dtype, jnp.floating)
        and (2 * a.pack_bound).bit_length() + 2 * int(cap).bit_length() <= 62
    )


def _dense_compact_group_aggregate(
    batch, keys, key_widths, aggs, arg_cols, slots, dense_bits,
    key_names, reps, fold_distinct_overflow, post_filter=None,
):
    """Aggregation over the full dense packed-key domain (slot id ==
    packed key, at most 2**_DENSE_BITS slots: no assignment pass at
    all), every lane a fused masked reduction per slot, followed by a
    cumsum compaction of occupied slots into the `slots` output tile.
    Reports the true group count — when it exceeds `slots` the host
    bumps the capacity knob and re-jits exactly like the sorted path
    (results here stay correct regardless; only the compaction tile was
    too small)."""
    cap = batch.capacity
    dense = 1 << dense_bits
    packed, stale = _pack_keys(keys, key_widths, batch.row_valid)
    # invalid / stale-width rows -> `dense`: no slot's mask matches them
    # (and the `first` scatter below drops the out-of-range index)
    seg = jnp.where(
        batch.row_valid & (packed < dense), packed, dense
    ).astype(jnp.int32)

    # a segment scatter costs the v5e ~45x a fused masked reduction at
    # small domains (measured 64ms vs 1.4ms per lane at 1M rows)
    red = _masked_backend(seg, dense)

    # occupancy anchor: with a fused HAVING, a packed sum/avg lane whose
    # contribution mask IS the row mask (nonnull-folded column — object
    # identity is the trace-time proof) already carries the per-group
    # row count, so the dedicated occupancy lane can be skipped: its
    # output column's validity (count > 0) IS `occupied`.
    anchor = None
    if post_filter is not None and not any(a.func == "first" for a in aggs):
        for i, (a, ac) in enumerate(zip(aggs, arg_cols)):
            if (
                a.func in ("sum", "avg")
                and ac is not None
                and _packs(a, ac, cap)
                and ac.valid is batch.row_valid
                and not (reps and i in reps)
            ):
                anchor = a.out_name
                break
    if anchor is not None:
        occupied = jnp.ones(dense, dtype=bool)
        ngroups = None  # derived from the anchor lane below
    else:
        occ_n = red(
            "sum",
            batch.row_valid.astype(jnp.int64),
            batch.row_valid,
            jnp.int64(0),
        )
        occupied = occ_n > 0
        ngroups = jnp.sum(occupied.astype(jnp.int64))
        ngroups = jnp.where(stale, jnp.int64(WIDTH_STALE), ngroups)

    # dense-domain key reconstruction
    sid = jnp.arange(dense, dtype=jnp.int64)
    out_cols = {}
    off = 0
    for name, k, (w, b) in zip(key_names, keys, key_widths):
        limb = (sid >> off) & ((1 << w) - 1)
        off += w
        kv = (limb != 0) & occupied
        kd = (limb - (b + 1)).astype(k.data.dtype)
        out_cols[name] = DevCol(jnp.where(kv, kd, jnp.zeros_like(kd)), kv)

    claimer = None
    if any(a.func == "first" for a in aggs):
        claimer = (
            jnp.full(dense, cap, dtype=jnp.int32)
            .at[seg]
            .min(jnp.arange(cap, dtype=jnp.int32), mode="drop")
        )
    cl = (
        jnp.minimum(claimer, cap - 1)
        if claimer is not None
        else jnp.zeros(dense, dtype=jnp.int32)
    )

    wide = _run_aggs(
        batch, aggs, arg_cols, seg, dense, occupied, cl, out_cols, red,
        reps=reps,
    )

    if post_filter is not None:
        # fused HAVING: evaluate the predicate over the DENSE domain and
        # compact only surviving groups — the reported group count (and
        # therefore the discovered output tile) shrinks to the survivor
        # count, collapsing every downstream operator's capacity. The
        # aggregation itself lives in the dense domain, so a small
        # output tile never loses groups. (Reference: HAVING lowers to
        # a Selection above the agg, pkg/planner/core — here the dense
        # layout makes fusing it strictly cheaper.)
        occ_true = (
            wide.cols[anchor].valid if anchor is not None else wide.row_valid
        )
        c = post_filter(wide)
        keep = occ_true & c.valid & (c.data != 0)
        occupied = keep
        ngroups = jnp.where(
            stale,
            jnp.int64(WIDTH_STALE),
            jnp.sum(keep.astype(jnp.int64)),
        )
        wide = Batch(wide.cols, keep)

    # compact occupied dense slots into the output tile, in slot-id
    # (ascending key) order
    pos = jnp.where(
        occupied, jnp.cumsum(occupied.astype(jnp.int32)) - 1, slots
    )
    cols = {}
    for name, c in wide.cols.items():
        nd = jnp.zeros(slots, dtype=c.data.dtype).at[pos].set(
            c.data, mode="drop"
        )
        nv = jnp.zeros(slots, dtype=bool).at[pos].set(c.valid, mode="drop")
        cols[name] = DevCol(nd, nv)
    row_valid = jnp.arange(slots) < jnp.minimum(ngroups, slots)
    return Batch(cols, row_valid), fold_distinct_overflow(ngroups)


def _needs_rep(a: AggDesc) -> bool:
    """DISTINCT changes the result only for sum/avg/count (min/max/first
    are duplicate-insensitive, reference pkg/executor/aggfuncs)."""
    return a.distinct and a.func in ("sum", "avg", "count") and a.arg is not None


def _distinct_reps(keys, aggs, arg_cols, row_valid, slots):
    """Per-DISTINCT-agg representative-row masks: one second claim-loop
    pass per distinct argument over (group keys + argument) dedupes the
    (group, value) pairs; the pair slot's claiming row is the single
    contributor. Returns ({agg index: bool mask}, overflow | None).
    The reference dedupes with per-group hash sets inside each agg
    function's update path (pkg/executor/aggfuncs count distinct); here
    the dedup is one more data-parallel probe loop, so the whole
    DISTINCT aggregation stays a single fused XLA program."""
    reps = {}
    over = None
    cap = row_valid.shape[0]
    rid = jnp.arange(cap, dtype=jnp.int32)
    for i, (a, col) in enumerate(zip(aggs, arg_cols)):
        if not _needs_rep(a) or col is None:
            continue
        pseg, pclaimer, _png, pover = group_assign(
            list(keys) + [col], row_valid, slots
        )
        cl = pclaimer[jnp.minimum(pseg, slots - 1)]
        reps[i] = (pseg < slots) & (cl == rid)
        over = pover if over is None else (over | pover)
    return reps, over


def group_aggregate(
    batch: Batch,
    key_fns: Sequence[ExprFn],
    aggs: Sequence[AggDesc],
    group_capacity: int,
    key_names: Optional[Sequence[str]] = None,
    key_widths: Optional[Sequence[Optional[Tuple[int, int]]]] = None,
    post_filter: Optional[Callable[[Batch], DevCol]] = None,
) -> Tuple[Batch, jax.Array]:
    """Returns (group batch, reported group count).

    The group batch has one row per group; its capacity is the power of
    two at or above max(group_capacity, 16) for keyed aggregation and
    group_capacity for scalar — callers must size overflow checks from
    the RETURNED batch's capacity. Key columns first (named key_names or
    k0..kn), then one agg column each. The reported count is the true
    group count, a value above the output capacity when the tile
    overflowed (host: bump the tile and re-jit), or WIDTH_STALE when
    baked key bounds no longer cover the data (host: recompile with
    fresh bounds).

    key_widths: per-key (bit width, bias) for keys whose packed encoding
    ``data + bias + 1`` (0 = NULL) provably fits the width. Keys that
    all have one and pack into _DENSE_BITS bits or fewer take the dense
    path; any other keys are sorted, packed by the widths they have.
    """

    from tidb_tpu.utils.failpoint import inject

    inject("executor/aggregate")
    cap = batch.capacity
    key_names = list(key_names or [f"k{i}" for i in range(len(key_fns))])

    # fused HAVING (post_filter): the dense path compacts only
    # surviving groups (capacity win); every other path masks the
    # output rows — reported counts stay PRE-filter there because the
    # output tile must still hold every group.
    def _mask_post(out, ng):
        if post_filter is None:
            return out, ng
        c = post_filter(out)
        keep = out.row_valid & c.valid & (c.data != 0)
        return Batch(out.cols, keep), ng

    keys = [fn(batch) for fn in key_fns]
    arg_cols = [a.arg(batch) if a.arg is not None else None for a in aggs]

    # DISTINCT dedup masks (and their pair-table overflow, folded into the
    # reported group count so the host's capacity-discovery loop retries
    # at a larger tile when distinct pairs outgrow the table).
    # The pair table shares the group-capacity knob: when distinct pairs
    # far outnumber groups the group table grows along with the pair
    # table (wasted slots of the same order as the pair table itself, and
    # the output tile re-shrinks after discovery) — accepted coupling to
    # keep one capacity signal per plan node; only the multi-distinct
    # kernel path pays it (single DISTINCT uses the stacked rewrite with
    # independently-sized nodes, planner/logical._expand_distinct_aggs).
    reps: dict = {}
    dover = None
    pair_slots = _next_pow2(max(2 * group_capacity, 16))
    if any(_needs_rep(a) for a in aggs):
        reps, dover = _distinct_reps(
            keys, aggs, arg_cols, batch.row_valid, pair_slots
        )

    def fold_distinct_overflow(ngroups):
        if dover is None:
            return ngroups
        return jnp.maximum(
            ngroups,
            jnp.where(dover, jnp.int64(pair_slots + 1), jnp.int64(0)),
        )

    if keys:
        # Output tile is 1x the capacity knob: neither shape is a hash
        # table that needs load-factor headroom, and downstream
        # operators (sorts especially) pay per-capacity for every pass.
        slots = _next_pow2(max(group_capacity, 16))
        dense_bits = (
            sum(w for w, _b in key_widths)
            if key_widths is not None and all(w is not None for w in key_widths)
            else None
        )
        if dense_bits is not None and dense_bits <= _DENSE_BITS:
            return _dense_compact_group_aggregate(
                batch, keys, key_widths, aggs, arg_cols, slots, dense_bits,
                key_names, reps, fold_distinct_overflow,
                post_filter=post_filter,
            )
        from tidb_tpu.executor.sortops import sort_group_aggregate

        out, ngroups = sort_group_aggregate(
            batch, keys, aggs, arg_cols, slots, key_names, reps=reps,
            key_widths=key_widths,
        )
        return _mask_post(out, fold_distinct_overflow(ngroups))

    # scalar aggregation: one group at slot 0
    slots = group_capacity
    any_valid = jnp.any(batch.row_valid)
    seg = jnp.where(batch.row_valid, 0, slots)
    first_valid = jnp.argmax(batch.row_valid).astype(jnp.int32)
    claimer = (
        jnp.full(slots, cap, dtype=jnp.int32)
        .at[0]
        .set(jnp.where(any_valid, first_valid, cap))
    )
    group_valid = claimer < cap
    ngroups = jnp.sum(group_valid.astype(jnp.int64))
    out = _run_aggs(
        batch, aggs, arg_cols, seg, slots, group_valid,
        jnp.minimum(claimer, cap - 1), {}, _scalar_backend(slots), reps=reps,
    )
    return _mask_post(out, fold_distinct_overflow(ngroups))


_REDUCE = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}


def _scalar_backend(slots):
    """Scalar (no GROUP BY) reductions: exactly one group lives at slot
    0, so each lane is ONE fused full-array reduction."""

    def red(op, vals, contrib, ident):
        top = _REDUCE[op](jnp.where(contrib, vals, ident))
        out = jnp.full((slots,), ident, dtype=top.dtype)
        return out.at[0].set(top)

    return red


def _masked_backend(seg, slots):
    """Aggregate reductions as fused masked full-array reductions, one
    accumulator per (slot, agg) — scatter-free; unrolled over the slots,
    so for the dense path's few (at most 2**_DENSE_BITS) only. The
    optimization barrier pins the reduction inputs: without it XLA fuses
    the producer expression tree (decimal products, filters) into EVERY
    per-slot reduction, recomputing it slots*aggs times — measured 35x
    slowdown on whole-query Q1."""

    def red(op, vals, contrib, ident):
        f = _REDUCE[op]
        vals, contrib = jax.lax.optimization_barrier((vals, contrib))
        return jnp.stack(
            [f(jnp.where(contrib & (seg == s), vals, ident)) for s in range(slots)]
        )

    return red


class _SortedReducer:
    """Reduction backend over a group-sorted permutation (sortops): sums
    and counts are cumulative-sum differences at segment ends; min/max are
    segmented scans. Same-op sum lanes of one dtype class are stacked
    into a single [cap, L] row-gather + axis-0 cumsum, so the whole
    aggregate costs one gather pass + one scan per dtype class instead of
    one scatter per lane (TPU scatter: ~45x a scan at 1M rows)."""

    def __init__(self, perm, valid_s, boundary, starts, ends, cap):
        self.perm = perm
        self.valid_s = valid_s
        self.boundary = boundary
        self.starts = starts  # clamped to [0, cap-1]
        self.ends = ends
        self.cap = cap
        self.has_rows = ends > starts

    def exec_all(self, reqs):
        from tidb_tpu.executor.sortops import _seg_scan

        results: list = [None] * len(reqs)
        ends_i = jnp.clip(self.ends - 1, 0, self.cap - 1)
        # --- stack sum lanes by accumulation dtype ---
        groups: dict = {}
        for i, (op, vals, contrib, ident) in enumerate(reqs):
            if op == "sum":
                acc = (
                    jnp.float64
                    if jnp.issubdtype(vals.dtype, jnp.floating)
                    else jnp.int64
                )
                groups.setdefault(acc, []).append((i, vals, contrib))
        for acc, lanes in groups.items():
            vm = jnp.stack(
                [
                    jnp.where(c, v, jnp.zeros((), v.dtype)).astype(acc)
                    for _i, v, c in lanes
                ],
                axis=1,
            )
            vs = vm[self.perm]  # one row-gather for every lane
            cs = jnp.cumsum(vs, axis=0)
            hi = cs[ends_i]
            lo = jnp.where(
                (self.starts > 0)[:, None],
                cs[jnp.maximum(self.starts - 1, 0)],
                jnp.zeros((), acc),
            )
            total = jnp.where(self.has_rows[:, None], hi - lo, jnp.zeros((), acc))
            for j, (i, v, _c) in enumerate(lanes):
                out_dtype = (
                    v.dtype if jnp.issubdtype(v.dtype, jnp.floating) else jnp.int64
                )
                results[i] = total[:, j].astype(out_dtype)
        # --- min/max lanes: segmented scan each ---
        for i, (op, vals, contrib, ident) in enumerate(reqs):
            if op == "sum":
                continue
            f = jnp.maximum if op == "max" else jnp.minimum
            z = jnp.where(contrib, vals, ident)[self.perm]
            z = jnp.where(self.valid_s, z, ident)
            s = _seg_scan(z, self.boundary, f)
            results[i] = jnp.where(self.has_rows, s[ends_i], ident)
        return results

    def __call__(self, op, vals, contrib, ident):
        return self.exec_all([(op, vals, contrib, ident)])[0]


def _run_sorted_aggs(
    batch, aggs, arg_cols, perm, valid_s, boundary, starts_c, ends,
    group_valid, out_cols, reps=None,
):
    """Bridge sortops.sort_group_aggregate into _run_aggs: contributions
    stay in original row order (the reducer permutes them), `first`
    reads the claiming row — the segment's first row, whose original id
    is perm[start]."""
    red = _SortedReducer(
        perm, valid_s, boundary, starts_c, ends, batch.capacity
    )
    cl = jnp.minimum(perm[starts_c], batch.capacity - 1)
    slots = starts_c.shape[0]
    # seg only feeds srow_valid (seg < slots) and the ones template here:
    # encode plain row validity in it
    seg = jnp.where(batch.row_valid, 0, slots).astype(jnp.int32)
    return _run_aggs(
        batch, aggs, arg_cols, seg, slots, group_valid, cl, out_cols, red,
        reps=reps,
    )


def _run_aggs(
    batch, aggs, arg_cols, seg, slots, group_valid, cl, out_cols, red,
    reps=None,
):
    """Compute all aggregates into the slot table. One implementation of
    the MySQL aggregate semantics (NULL rules, AVG decimal scale),
    parameterized over the reducer `red(op, vals, contrib, ident)` ->
    [slots] (_scalar_backend, _masked_backend, _SortedReducer). `reps`
    maps agg index to a DISTINCT representative-row mask
    (_distinct_reps). Runs in three phases — collect reduction requests,
    execute them (all at once where the reducer has `exec_all`), then
    assemble output columns — so independent lanes share passes."""
    srow_valid = seg < slots
    ones = jnp.ones_like(seg, dtype=jnp.int64)
    reqs = []

    def req(op, vals, contrib, ident):
        reqs.append((op, vals, contrib, ident))
        return len(reqs) - 1

    assemble = []  # callables taking the executed results list

    def emit(name, fn):
        assemble.append((name, fn))

    for i, (a, col) in enumerate(zip(aggs, arg_cols)):
        if a.func == "count" and col is None:
            rid = req("sum", ones, srow_valid, jnp.int64(0))
            emit(a.out_name, lambda R, rid=rid: DevCol(R[rid], group_valid))
            continue

        data = col.data
        if data.dtype == jnp.bool_ and a.func in ("sum", "avg", "min", "max"):
            # SUM(bool_expr) etc.: MySQL treats booleans as 0/1 ints
            data = data.astype(jnp.int64)
        valid = col.valid & srow_valid
        if reps and i in reps:
            valid = valid & reps[i]
        if a.func == "count":
            rid = req("sum", ones, valid, jnp.int64(0))
            emit(a.out_name, lambda R, rid=rid: DevCol(R[rid], group_valid))
        elif a.func in ("sum", "avg"):
            if a.wide and not jnp.issubdtype(data.dtype, jnp.floating):
                d64 = data.astype(jnp.int64)
                lo = d64 & jnp.int64((1 << 30) - 1)
                hi = d64 >> 30  # arithmetic shift: hi*2^30 + lo == d64
                rlo = req("sum", lo, valid, jnp.int64(0))
                rhi = req("sum", hi, valid, jnp.int64(0))

                def mk_s(R, rlo=rlo, rhi=rhi):
                    return R[rhi].astype(jnp.float64) * float(1 << 30) + R[
                        rlo
                    ].astype(jnp.float64)

            elif _packs(a, col, batch.capacity):
                # packed (sum, count) single reduction: values biased
                # non-negative so the count rides the low bits with no
                # carry; bound re-verified at fetch (AggDesc.pack_bound)
                cb = int(batch.capacity).bit_length()
                bias = int(a.pack_bound)
                d64 = data.astype(jnp.int64)
                pv = ((d64 + bias) << cb) | 1
                rp = req("sum", pv, valid, jnp.int64(0))
                mask = jnp.int64((1 << cb) - 1)

                def mk_s(R, rp=rp, cb=cb, bias=bias, mask=mask):
                    return (R[rp] >> cb) - bias * (R[rp] & mask)

                def mk_cnt(R, rp=rp, mask=mask):
                    return R[rp] & mask

                if a.func == "sum":

                    def fin(R, mk_s=mk_s, mk_cnt=mk_cnt):
                        cnt = mk_cnt(R)
                        return DevCol(mk_s(R), (cnt > 0) & group_valid)

                else:
                    scale = a.arg_scale

                    def fin(R, mk_s=mk_s, mk_cnt=mk_cnt, scale=scale):
                        cnt = mk_cnt(R)
                        denom = jnp.where(cnt == 0, 1, cnt).astype(
                            jnp.float64
                        )
                        if scale:
                            denom = denom * (10**scale)
                        return DevCol(
                            mk_s(R).astype(jnp.float64) / denom,
                            (cnt > 0) & group_valid,
                        )

                emit(a.out_name, fin)
                continue
            else:
                rs = req("sum", data, valid, jnp.zeros((), data.dtype))

                def mk_s(R, rs=rs):
                    return R[rs]

            rc = req("sum", ones, valid, jnp.int64(0))

            def mk_cnt(R, rc=rc):
                return R[rc]

            if a.func == "sum":

                def fin(R, mk_s=mk_s, mk_cnt=mk_cnt):
                    cnt = mk_cnt(R)
                    # SUM over an all-NULL / empty group is NULL (MySQL)
                    return DevCol(mk_s(R), (cnt > 0) & group_valid)

            else:
                scale = a.arg_scale

                def fin(R, mk_s=mk_s, mk_cnt=mk_cnt, scale=scale):
                    cnt = mk_cnt(R)
                    denom = jnp.where(cnt == 0, 1, cnt).astype(jnp.float64)
                    if scale:
                        # DECIMAL data is in scaled units whether the
                        # device dtype is int64 or (wide-sum) float64 —
                        # always descale by 10^scale
                        denom = denom * (10**scale)
                    return DevCol(
                        mk_s(R).astype(jnp.float64) / denom,
                        (cnt > 0) & group_valid,
                    )

            emit(a.out_name, fin)
        elif a.func in ("min", "max"):
            ident = _type_max(data.dtype) if a.func == "min" else _type_min(data.dtype)
            rs = req(a.func, data, valid, ident)
            rc = req("sum", ones, valid, jnp.int64(0))
            emit(
                a.out_name,
                lambda R, rs=rs, rc=rc, p=a.post: DevCol(
                    p(R[rs]) if p is not None else R[rs],
                    (R[rc] > 0) & group_valid,
                ),
            )
        elif a.func == "first":
            d = data[cl]
            out_cols[a.out_name] = DevCol(d, col.valid[cl] & group_valid)
        else:
            raise NotImplementedError(f"agg func {a.func!r}")

    exec_all = getattr(red, "exec_all", None)
    results = (
        exec_all(reqs) if exec_all is not None
        else [red(op, v, c, ident) for (op, v, c, ident) in reqs]
    )
    for name, fn in assemble:
        out_cols[name] = fn(results)
    return Batch(out_cols, group_valid)


def _type_max(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype=dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype=dtype)


def _type_min(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype=dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype=dtype)
