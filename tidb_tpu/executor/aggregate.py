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
  _DENSE_BITS bits or      at most 128 slots: every integer sum and count
  fewer (Q1's flags)       of the aggregate rides ONE exact contraction
                           of byte digits against the one-hot of the
                           slot ids (`_DenseReducer`; min/max and
                           floating sums: one masked reduction per
                           (slot, lane), `_masked_backend`), then a
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
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from tidb_tpu.chunk import Batch, DevCol, pad_capacity

ExprFn = Callable[[Batch], DevCol]

# linear-probe bound per table size; beyond this the table is declared
# full and the host retries at the next tile
_MAX_PROBES = 64

# widest packed key domain the dense path takes: 2**7 = 128 slots, the
# most that the masked reductions unroll over and one MXU tile of one-hot
# rows (Q1's two flags pack into 4 bits). Wider keys sort.
_DENSE_BITS = 7

# rows of one block of the dense contraction: a digit in [-128, 127]
# times a 0/1 one-hot, summed into int32, stays exact while
# 128 * block < 2**31; 2**14 to 2**18 time alike on the v5e, 2**20 three
# times slower (PERF.md PR 34)
_CONTRACT_BLOCK = 1 << 16
# rows of one statically sliced piece, whose digits are made, contracted
# (its blocks one batched dot) and dead before the next piece's: bounds
# the program's temporaries whatever the tile
_CONTRACT_PIECE = 1 << 23

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
    # (one lane instead of two: the scalar and the sorted reducer). The
    # dense reducer reads it as the lane's width: a sum rides as many
    # byte digits as bound.bit_length() + 1 bits need, a `wide` sum's
    # high part as many as the bits past its low 30.
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
    all), every integer sum and count one contraction (_DenseReducer),
    followed by a cumsum compaction of occupied slots into the `slots`
    output tile. Reports the true group count — when it exceeds `slots`
    the host bumps the capacity knob and re-jits exactly like the sorted
    path (results here stay correct regardless; only the compaction tile
    was too small)."""
    cap = batch.capacity
    dense = 1 << dense_bits
    packed, stale = _pack_keys(keys, key_widths, batch.row_valid)
    # invalid / stale-width rows -> `dense`: no slot's one-hot row or
    # mask matches them (and the `first` scatter below drops the
    # out-of-range index)
    seg = jnp.where(
        batch.row_valid & (packed < dense), packed, dense
    ).astype(jnp.int32)

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

    # a slot is occupied where its row count is positive: that count is
    # one more lane of the aggregate's contraction (count(*)'s own, where
    # the statement has one), so _run_aggs derives group validity itself
    wide = _run_aggs(
        batch, aggs, arg_cols, seg, dense, None, cl, {},
        _DenseReducer(seg, dense), reps=reps,
    )
    occupied = wide.row_valid
    ngroups = jnp.sum(occupied.astype(jnp.int64))
    ngroups = jnp.where(stale, jnp.int64(WIDTH_STALE), ngroups)

    # dense-domain key reconstruction
    sid = jnp.arange(dense, dtype=jnp.int64)
    key_cols = {}
    off = 0
    for name, k, (w, b) in zip(key_names, keys, key_widths):
        limb = (sid >> off) & ((1 << w) - 1)
        off += w
        kv = (limb != 0) & occupied
        kd = (limb - (b + 1)).astype(k.data.dtype)
        key_cols[name] = DevCol(jnp.where(kv, kd, jnp.zeros_like(kd)), kv)
    wide = Batch({**key_cols, **wide.cols}, occupied)

    if post_filter is not None:
        # fused HAVING: evaluate the predicate over the DENSE domain and
        # compact only surviving groups — the reported group count (and
        # therefore the discovered output tile) shrinks to the survivor
        # count, collapsing every downstream operator's capacity. The
        # aggregation itself lives in the dense domain, so a small
        # output tile never loses groups. (Reference: HAVING lowers to
        # a Selection above the agg, pkg/planner/core — here the dense
        # layout makes fusing it strictly cheaper.)
        c = post_filter(wide)
        occupied = occupied & c.valid & (c.data != 0)
        ngroups = jnp.where(
            stale,
            jnp.int64(WIDTH_STALE),
            jnp.sum(occupied.astype(jnp.int64)),
        )
        wide = Batch(wide.cols, occupied)

    cols = _compact_slots(wide.cols, occupied, slots)
    row_valid = jnp.arange(slots) < jnp.minimum(ngroups, slots)
    return Batch(cols, row_valid), fold_distinct_overflow(ngroups)


def _compact_slots(cols, occupied, slots):
    """Compact the occupied slots of a dense domain into a tile of
    `slots` rows, in slot-id (ascending key) order: output row j takes
    the one slot whose rank among the occupied is j, by select and sum
    over the (at most 128 x 128) pairs. Not `.at[pos].set(...,
    mode="drop")`: on the v5e that scatter of an int64 column returned
    Q1's first group 2**31 - 655,360 short on two of four SF10 data
    sets (PERF.md PR 34), where the dense-domain values before it were
    exact. `chip_smoke.py` runs this function on the chip against numpy."""
    pos = jnp.where(
        occupied, jnp.cumsum(occupied.astype(jnp.int32)) - 1, slots
    )
    take = pos[None, :] == jnp.arange(slots, dtype=jnp.int32)[:, None]
    out = {}
    for name, c in cols.items():
        nd = jnp.sum(
            jnp.where(take, c.data[None, :], jnp.zeros((), c.data.dtype)),
            axis=1,
        ).astype(c.data.dtype)
        nv = jnp.any(take & c.valid[None, :], axis=1)
        out[name] = DevCol(nd, nv)
    return out


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


class _Req(NamedTuple):
    """One reduction _run_aggs asks of its reducer: `op` in {sum, min,
    max} over `vals` where `contrib`, `ident` elsewhere."""

    op: str
    vals: jax.Array
    contrib: jax.Array
    ident: jax.Array
    # signed bits that hold every value of an integer `vals`, as far as
    # is known when the program is traced (a proven bound, the wide
    # split, a count's 0/1; else the dtype's)
    bits: int = 64
    # what `contrib` was made of, where _run_aggs made it anew for each
    # aggregate: (id(validity array), id(DISTINCT representatives)).
    # A reducer that merges requests compares this; None: the object
    mask: tuple | None = None


def _int_bits(dtype) -> int:
    """Signed bits that hold every value of an integer dtype."""
    if dtype == jnp.bool_:
        return 2
    unsigned = jnp.issubdtype(dtype, jnp.unsignedinteger)
    return min(64, dtype.itemsize * 8 + int(unsigned))


def _masked_backend(seg, slots):
    """Aggregate reductions as fused masked full-array reductions, one
    accumulator per (slot, agg) — scatter-free; unrolled over the slots,
    so for the dense path's few (at most 2**_DENSE_BITS) only, and there
    for what digits cannot carry exactly: min, max and floating sums.
    The optimization barrier pins the reduction inputs: without it XLA
    fuses the producer expression tree (decimal products, filters) into
    EVERY per-slot reduction, recomputing it slots*aggs times — measured
    35x slowdown on whole-query Q1."""

    def red(op, vals, contrib, ident):
        f = _REDUCE[op]
        vals, contrib = jax.lax.optimization_barrier((vals, contrib))
        return jnp.stack(
            [f(jnp.where(contrib & (seg == s), vals, ident)) for s in range(slots)]
        )

    return red


def _byte_digits(vals, bits: int):
    """Digits d[i] in [-128, 127], least significant first, of an
    integer array whose values fit `bits` signed bits, with
    sum(d[i] * 256**i) == vals (mod 2**64 at 64 bits): the bytes of
    vals + 0x80..80, each less 128, under a signed top byte. Exact in
    int8, the narrowest type the MXU multiplies."""
    nl = 1 if bits <= 8 else min(8, -(-(bits + 1) // 8))
    v = vals.astype(jnp.int64) + sum(128 << (8 * i) for i in range(nl - 1))
    words = (v.astype(jnp.uint32), (v >> 32).astype(jnp.uint32))
    out = []
    for i in range(nl):
        b = ((words[i // 4] >> (8 * (i % 4))) & 0xFF).astype(jnp.int32)
        out.append((b ^ 0x80 if i == nl - 1 else b) - 128)
    return out


class _DenseReducer:
    """Reduction backend over the dense key domain (seg in [0, dense),
    `dense` for a row of no slot). ALL integer sums and counts of an
    aggregate are one exact contraction over the row axis: requests of
    the same values under the same mask (_Req.mask) are one lane, merged
    here and nowhere else; a lane is as many int8 byte digits as its
    statically known width needs (_byte_digits), laid out [digits,
    rows] with rows minor, and the MXU contracts them against
    the one-hot of seg, [dense, rows] x [digits, rows] -> [dense,
    digits], in row blocks whose int32 partials cannot overflow; the
    partials are widened to int64, summed, and the digits recombined by
    shifts into the int64 sum a masked reduction gives (mod 2**64). The
    v5e has no native int64: the masked form wrote every request as an
    int64 array and passed over it once a slot (Q1 at SF10: 16 requests
    of 512 MB x 16 slots, 169 ms; PERF.md PR 34). min, max and floating
    sums, which digits cannot carry, keep the masked form."""

    # a count is one shared one-digit lane here, so _run_aggs asks for
    # (sum, count) pairs and never packs a count into its sum's lane
    shares_counts = True

    def __init__(self, seg, dense):
        self.seg = seg
        self.dense = dense
        self.masked = _masked_backend(seg, dense)

    def exec_all(self, reqs):
        results: list = [None] * len(reqs)
        lanes: dict = {}  # (id(vals), mask) -> indices into reqs
        for i, r in enumerate(reqs):
            if r.op == "sum" and not jnp.issubdtype(r.vals.dtype, jnp.floating):
                mask = r.mask if r.mask is not None else (id(r.contrib),)
                lanes.setdefault((id(r.vals), mask), []).append(i)
            else:
                results[i] = self.masked(r.op, r.vals, r.contrib, r.ident)
        if lanes:
            with jax.named_scope("contract"):
                sums = self._contract(
                    [
                        reqs[ix[0]]._replace(
                            bits=min(reqs[i].bits for i in ix), mask=mask
                        )
                        for (_vals, mask), ix in lanes.items()
                    ]
                )
            for total, ix in zip(sums, lanes.values()):
                for i in ix:
                    results[i] = total
        return results

    def _contract(self, lanes):
        from tidb_tpu.utils.metrics import REGISTRY

        cap = self.seg.shape[0]
        slot = jnp.arange(self.dense, dtype=jnp.int32)[None, :, None]
        contribs = {r.mask: r.contrib for r in lanes}  # one a distinct mask
        sums = None
        for at in range(0, cap, _CONTRACT_PIECE):
            rows = slice(at, min(at + _CONTRACT_PIECE, cap))
            keep = [c[rows] for c in contribs.values()]
            if sums is not None:
                # a piece's digits wait for the sums of the piece before
                # (every digit reads its mask): one piece's temporaries
                # are live at a time, not as many as the scheduler likes
                sums, keep = jax.lax.optimization_barrier((sums, keep))
            keep = dict(zip(contribs, keep))
            digits, spans = [], []
            for r in lanes:
                ds = _byte_digits(r.vals[rows], r.bits)
                spans.append((len(digits), len(ds)))
                digits += [
                    jnp.where(keep[r.mask], d, 0).astype(jnp.int8)
                    for d in ds
                ]
            seg, x = self.seg[rows], jnp.stack(digits)
            # the ladder's tiles (2**k, 3 * 2**(k-1)) are whole blocks,
            # or one short one; any other length is padded to them
            block = min(_CONTRACT_BLOCK, seg.shape[0])
            pad = -seg.shape[0] % block
            if pad:
                seg = jnp.pad(seg, (0, pad), constant_values=self.dense)
                x = jnp.pad(x, ((0, 0), (0, pad)))
            part = jax.lax.dot_general(
                (seg.reshape(-1, 1, block) == slot).astype(jnp.int8),
                x.reshape(len(digits), -1, block),
                (((2,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.int32,
            )  # [blocks, dense, digits]
            part = part.astype(jnp.int64).sum(axis=0)
            sums = part if sums is None else sums + part
        REGISTRY.counter(
            "tidbtpu_executor_dense_contractions_total",
            "aggregates over a dense key domain whose integer sums and "
            "counts ride one digit contraction, in traced programs",
        ).inc()
        REGISTRY.counter(
            "tidbtpu_executor_dense_contraction_limbs_total",
            "int8 digit rows of those contractions, after requests of "
            "the same (values, mask) were merged",
        ).inc(len(digits))
        return [
            sum(sums[:, at + i] << (8 * i) for i in range(n))
            for at, n in spans
        ]


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
        for i, (op, vals, contrib, *_rest) in enumerate(reqs):
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
        for i, (op, vals, contrib, ident, *_rest) in enumerate(reqs):
            if op == "sum":
                continue
            f = jnp.maximum if op == "max" else jnp.minimum
            z = jnp.where(contrib, vals, ident)[self.perm]
            z = jnp.where(self.valid_s, z, ident)
            s = _seg_scan(z, self.boundary, f)
            results[i] = jnp.where(self.has_rows, s[ends_i], ident)
        return results

    def __call__(self, op, vals, contrib, ident):
        return self.exec_all([_Req(op, vals, contrib, ident)])[0]


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
    [slots] (_scalar_backend, _DenseReducer, _SortedReducer). `reps`
    maps agg index to a DISTINCT representative-row mask
    (_distinct_reps). Runs in three phases — collect reduction requests
    (_Req), execute them (all at once where the reducer has `exec_all`),
    then assemble output columns — so independent lanes share passes.
    `group_valid` None: a slot is a group where it holds a row, counted
    by one more request."""
    srow_valid = seg < slots
    ones = jnp.ones_like(seg, dtype=jnp.int64)
    reqs = []

    def req(op, vals, contrib, ident, bits=64, mask=None):
        reqs.append(_Req(op, vals, contrib, ident, bits, mask))
        return len(reqs) - 1

    def count(contrib, mask=None):
        return req("sum", ones, contrib, jnp.int64(0), 2, mask)

    assemble = []  # callables taking the executed results and group_valid

    def emit(name, fn):
        assemble.append((name, fn))

    r_rows = count(srow_valid) if group_valid is None else None

    for i, (a, col) in enumerate(zip(aggs, arg_cols)):
        if a.func == "count" and col is None:
            rid = count(srow_valid)
            emit(a.out_name, lambda R, gv, rid=rid: DevCol(R[rid], gv))
            continue

        data = col.data
        bits = _int_bits(data.dtype)
        if data.dtype == jnp.bool_ and a.func in ("sum", "avg", "min", "max"):
            # SUM(bool_expr) etc.: MySQL treats booleans as 0/1 ints
            data = data.astype(jnp.int64)
        rep = reps[i] if reps and i in reps else None
        valid = col.valid & srow_valid
        if rep is not None:
            valid = valid & rep
        # what `valid` is made of (arg_cols and reps outlive the ids): a
        # reducer that merges requests compares this, `valid` being new
        mask = (id(col.valid), id(rep))
        if a.func == "count":
            rid = count(valid, mask)
            emit(a.out_name, lambda R, gv, rid=rid: DevCol(R[rid], gv))
        elif a.func in ("sum", "avg"):
            if a.pack_bound is not None:
                bits = min(bits, int(a.pack_bound).bit_length() + 1)
            mk_cnt = None  # the packed lane brings its own
            if a.wide and not jnp.issubdtype(data.dtype, jnp.floating):
                d64 = data.astype(jnp.int64)
                lo = d64 & jnp.int64((1 << 30) - 1)
                hi = d64 >> 30  # arithmetic shift: hi*2^30 + lo == d64
                rlo = req("sum", lo, valid, jnp.int64(0), 31, mask)
                rhi = req("sum", hi, valid, jnp.int64(0), max(bits - 30, 2), mask)

                def mk_s(R, rlo=rlo, rhi=rhi):
                    return R[rhi].astype(jnp.float64) * float(1 << 30) + R[
                        rlo
                    ].astype(jnp.float64)

            elif not getattr(red, "shares_counts", False) and _packs(
                a, col, batch.capacity
            ):
                # packed (sum, count) single reduction: values biased
                # non-negative so the count rides the low bits with no
                # carry; bound re-verified at fetch (AggDesc.pack_bound)
                cb = int(batch.capacity).bit_length()
                bias = int(a.pack_bound)
                d64 = data.astype(jnp.int64)
                pv = ((d64 + bias) << cb) | 1
                rp = req("sum", pv, valid, jnp.int64(0), mask=mask)
                mask = jnp.int64((1 << cb) - 1)

                def mk_s(R, rp=rp, cb=cb, bias=bias, mask=mask):
                    return (R[rp] >> cb) - bias * (R[rp] & mask)

                def mk_cnt(R, rp=rp, mask=mask):
                    return R[rp] & mask

            else:
                rs = req(
                    "sum", data, valid, jnp.zeros((), data.dtype), bits, mask
                )

                def mk_s(R, rs=rs):
                    return R[rs]

            if mk_cnt is None:
                rc = count(valid, mask)

                def mk_cnt(R, rc=rc):
                    return R[rc]

            if a.func == "sum":

                def fin(R, gv, mk_s=mk_s, mk_cnt=mk_cnt):
                    cnt = mk_cnt(R)
                    # SUM over an all-NULL / empty group is NULL (MySQL)
                    return DevCol(mk_s(R), (cnt > 0) & gv)

            else:
                scale = a.arg_scale

                def fin(R, gv, mk_s=mk_s, mk_cnt=mk_cnt, scale=scale):
                    cnt = mk_cnt(R)
                    denom = jnp.where(cnt == 0, 1, cnt).astype(jnp.float64)
                    if scale:
                        # DECIMAL data is in scaled units whether the
                        # device dtype is int64 or (wide-sum) float64 —
                        # always descale by 10^scale
                        denom = denom * (10**scale)
                    return DevCol(
                        mk_s(R).astype(jnp.float64) / denom,
                        (cnt > 0) & gv,
                    )

            emit(a.out_name, fin)
        elif a.func in ("min", "max"):
            ident = _type_max(data.dtype) if a.func == "min" else _type_min(data.dtype)
            rs = req(a.func, data, valid, ident, mask=mask)
            rc = count(valid, mask)
            emit(
                a.out_name,
                lambda R, gv, rs=rs, rc=rc, p=a.post: DevCol(
                    p(R[rs]) if p is not None else R[rs], (R[rc] > 0) & gv
                ),
            )
        elif a.func == "first":
            emit(
                a.out_name,
                lambda R, gv, d=data[cl], v=col.valid[cl]: DevCol(d, v & gv),
            )
        else:
            raise NotImplementedError(f"agg func {a.func!r}")

    exec_all = getattr(red, "exec_all", None)
    results = exec_all(reqs) if exec_all is not None else [red(*r[:4]) for r in reqs]
    if group_valid is None:
        group_valid = results[r_rows] > 0
    for name, fn in assemble:
        out_cols[name] = fn(results, group_valid)
    return Batch(out_cols, group_valid)


def _type_max(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype=dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype=dtype)


def _type_min(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype=dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype=dtype)
