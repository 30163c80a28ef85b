"""Hash-based group aggregation with static shapes (no sort).

Reference: the parallel hash aggregate with partial/final workers
(pkg/executor/aggregate/agg_hash_executor.go:60-91) and StreamAggExec
(agg_stream_executor.go:32). The reference builds a dynamic hash table;
TPU needs static shapes, so the table is a fixed power-of-two slot array
(2x the group-capacity knob) built with a data-parallel claim loop:

  1. every row hashes its group key to a slot,
  2. unassigned rows scatter-min their row id into the slot (the smallest
     row id claims it),
  3. rows whose key equals the claimer's key adopt the slot; the rest
     linear-probe to the next slot and repeat.

All rows of one key follow the same probe sequence, so each group settles
on exactly one slot and the loop runs for ~the longest probe chain (a few
memory-bound passes) instead of a full bitonic sort of the batch
(O(n log^2 n) on TPU, the reason the sort-based first cut was slow).
Aggregation is then jax.ops.segment_* straight into the slot array —
segment ops do not need sorted input.

The kernel returns the true group count; table overflow (unassigned rows
after the probe limit) reports slots+1 so the host bumps the capacity
tile and re-jits — the analog of the reference's spill escalation
(aggregate/agg_spill.go), replaced by recompile-at-larger-tile. The
partial/final split of the reference maps to per-device local aggregation
followed by an all_to_all repartition of group keys and a final
aggregation (parallel/fragment.py), mirroring agg partial workers ->
shuffle -> final workers.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from tidb_tpu.chunk import Batch, DevCol, pad_capacity
from tidb_tpu.utils.backend import is_tpu as _is_tpu

ExprFn = Callable[[Batch], DevCol]

# linear-probe bound per table size; beyond this the table is declared
# full and the host retries at the next tile
_MAX_PROBES = 64

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
    # (one segment scatter instead of two on CPU; one lane instead of
    # two on the masked/TPU backends).
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


def _packed_group_assign(
    keys: Sequence[DevCol],
    key_widths: Sequence[Tuple[int, int]],
    row_valid: jax.Array,
    slots: int,
):
    """Scatter/gather-free group assignment for keys that pack losslessly
    into one int64 (dict-coded strings, dates, bools — widths are static,
    sound bounds from the planner).

    Discovers the distinct packed values with a min-above reduction loop
    (one full reduction per group — TPU reductions are fast; TPU random
    scatter/gather is not), then derives segment ids by comparing against
    the sorted distinct table. Returns (seg, uniq, count, overflow) where
    uniq is the sorted packed-key table for key-column reconstruction.
    """
    cap = row_valid.shape[0]
    sent = jnp.int64(2**63 - 1)
    packed, stale = _pack_keys(keys, key_widths, row_valid)
    packed = jnp.where(row_valid, packed, sent)

    def cond(s):
        return ~s[-1]

    def body(s):
        uniq, count, prev, over, _stop = s
        cur = jnp.min(jnp.where(packed > prev, packed, sent))
        found = cur < sent
        room = count < slots
        take = found & room
        uniq = uniq.at[jnp.where(take, count, slots)].set(cur, mode="drop")
        count = count + take.astype(jnp.int32)
        prev = jnp.where(found, cur, prev)
        over = over | (found & ~room)
        stop = ~take
        return uniq, count, prev, over, stop

    z = jnp.min(row_valid.astype(jnp.int32)) * 0  # varying seed (shard_map)
    uniq0 = jnp.full(slots + 1, sent, dtype=jnp.int64) + z
    state = (
        uniq0,
        jnp.int32(0) + z,
        jnp.int64(-1) + z,
        (z == 1),
        (z == 1),
    )
    uniq, count, _prev, over, _stop = jax.lax.while_loop(cond, body, state)
    uniq = uniq[:slots]
    eq = packed[:, None] == uniq[None, :]
    seg = jnp.argmax(eq, axis=1).astype(jnp.int32)
    # mask with row_valid too: invalid rows carry the sentinel, which
    # also fills unclaimed uniq slots and would otherwise match one
    seg = jnp.where(row_valid & jnp.any(eq, axis=1), seg, slots)
    return seg, uniq, count, over, stale


def _prefix_sum(mask):
    """int32 inclusive prefix sum of a bool mask; routes through the
    Pallas streaming-scan kernel when opted in (TIDB_TPU_PALLAS=1 on
    TPU, or interpret mode under TIDB_TPU_PALLAS_INTERPRET=1). An
    opted-in kernel that fails raises: the jnp form never answers in
    its place."""
    from tidb_tpu.executor.pallas_kernels import (
        pallas_interpret, prefix_sum_i32,
    )

    interp = pallas_interpret()
    if interp is not None:
        return prefix_sum_i32(mask, interpret=interp)
    return jnp.cumsum(mask.astype(jnp.int32))


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
    """Aggregation over the full dense packed-key domain followed by a
    cumsum compaction of occupied slots into the `slots` output tile.
    For high-cardinality keys the claim loop needs O(probe-chain) full
    scatter passes; this costs one segment scatter per agg over the dense
    domain plus ~2 passes per output column to compact. Reports the true
    group count — when it exceeds `slots` the host bumps the capacity
    knob and re-jits exactly like the probed paths (results here stay
    correct regardless; only the compaction tile was too small)."""
    cap = batch.capacity
    dense = 1 << dense_bits
    packed, stale = _pack_keys(keys, key_widths, batch.row_valid)
    # invalid / stale-width rows -> `dense`, out of range for every
    # dense-domain scatter below (scatter drops OOB indices under jit)
    seg = jnp.where(
        batch.row_valid & (packed < dense), packed, dense
    ).astype(jnp.int32)

    # TPU: a segment scatter costs ~45x a fused masked reduction at small
    # domains (measured 64ms vs 1.4ms per lane at 1M rows) — route the
    # reductions through the masked backend whenever the dense domain is
    # small enough for full unrolling
    red = _pick_backend(seg, dense)

    # occupancy anchor: with a fused HAVING, a packed sum/avg lane whose
    # contribution mask IS the row mask (nonnull-folded column — object
    # identity is the trace-time proof) already carries the per-group
    # row count, so the dedicated occupancy scatter can be skipped: its
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
        if red is not None:
            occ_n = red(
                "sum",
                batch.row_valid.astype(jnp.int64),
                batch.row_valid,
                jnp.int64(0),
            )
        else:
            occ_n = jax.ops.segment_sum(
                batch.row_valid.astype(jnp.int64), seg, num_segments=dense
            )
        occupied = occ_n > 0
        from tidb_tpu.executor.fastreduce import count as _fr_count

        ngroups = _fr_count(occupied)
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
        reps=reps, num_segments=dense,
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
        from tidb_tpu.executor.fastreduce import count as _fr_count2

        ngroups = jnp.where(
            stale, jnp.int64(WIDTH_STALE), _fr_count2(keep)
        )
        wide = Batch(wide.cols, keep)

    # compact occupied dense slots into the output tile, in slot-id
    # (ascending key) order (int32 cumsum: dense <= 2^23 and a 34MB
    # serial chain runs ~1.6x faster than the 67MB int64 one on CPU).
    # Opt-in TPU path: the Pallas streaming prefix sum does the scan in
    # ONE sequential-grid pass vs XLA's log-depth multi-pass lowering.
    pos = jnp.where(
        occupied, _prefix_sum(occupied) - 1, slots
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

    The group batch has one row per group; its capacity depends on the
    path (2*group_capacity hash-slot tile for the probed keyed paths,
    1x for dense compaction, group_capacity for scalar) — callers must
    size overflow checks from the RETURNED batch's capacity, never from
    a 2x assumption. Key columns first (named key_names or k0..kn),
    then one agg column each. The reported count is the true group
    count, a value above the output capacity when the table overflowed
    (host: bump the tile and re-jit), or WIDTH_STALE when baked key
    bounds no longer cover the data (host: recompile with fresh bounds).

    key_widths: per-key (bit width, bias) for keys whose packed encoding
    ``data + bias + 1`` (0 = NULL) provably fits the width — enables the
    scatter-free packed fast path when all keys qualify and the widths
    sum to <= 62 bits.
    """

    from tidb_tpu.utils.failpoint import inject

    inject("executor/aggregate")
    cap = batch.capacity
    key_names = list(key_names or [f"k{i}" for i in range(len(key_fns))])

    # fused HAVING (post_filter): the dense path compacts only
    # surviving groups (capacity win); every other path masks the
    # output rows — reported counts stay PRE-filter there because the
    # group/hash tables must still hold every group.
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

    widths_ok = (
        keys
        and key_widths is not None
        and all(w is not None for w in key_widths)
        and sum(w for w, _b in key_widths) <= 62
    )
    dense_bits = sum(w for w, _b in key_widths) if widths_ok else 99
    packable = widths_ok and group_capacity <= 256

    # TPU (or TIDB_TPU_SORT_AGG=1): keyed aggregation by lexicographic
    # sort (sortops) — the probed hash paths below are built on scatter
    # and per-group reduction loops, both serial on TPU. The dense path
    # keeps priority while its domain fits the masked-reduction unroll.
    from tidb_tpu.utils.backend import sort_path_preference

    _pref = sort_path_preference()
    use_sorted = keys and (
        _pref == "force" or (_is_tpu() and _pref != "avoid")
    )
    dense_ok = (
        widths_ok
        and dense_bits <= 26  # 2^26 domain = 536MB/lane: SF10 orderkeys
        # stay on the dense path (the claim loop's serial probe passes
        # are catastrophic at 60M rows); the 4*cap guard below still
        # bounds the domain-to-batch waste
        and (1 << dense_bits) <= max(4 * cap, 1 << 16)
    )
    if use_sorted and not (dense_ok and dense_bits <= 7):
        from tidb_tpu.executor.sortops import sort_group_aggregate

        slots = _next_pow2(max(group_capacity, 16))
        out, ngroups = sort_group_aggregate(
            batch, keys, aggs, arg_cols, slots, key_names, reps=reps,
            key_widths=key_widths,
        )
        return _mask_post(out, fold_distinct_overflow(ngroups))

    if dense_ok:
        # the whole packed-key domain fits a dense table (and is not
        # wildly sparser than the batch): slot id == packed key, so
        # assignment needs no probe loop at all — one segment scatter
        # per agg plus a cumsum compaction into the output tile. The
        # probed paths below cost one full-array pass PER GROUP (packed
        # loop) or per probe-chain step (claim loop). Output tile is 1x
        # the capacity knob (not the hash paths' 2x): compaction needs no
        # load-factor headroom, and downstream operators (sorts
        # especially) pay per-capacity for every pass.
        slots = _next_pow2(max(group_capacity, 16))
        return _dense_compact_group_aggregate(
            batch, keys, key_widths, aggs, arg_cols, slots, dense_bits,
            key_names, reps, fold_distinct_overflow,
            post_filter=post_filter,
        )

    if packable:
        slots = _next_pow2(max(2 * group_capacity, 16))
        seg, uniq, count, over, stale = _packed_group_assign(
            keys, key_widths, batch.row_valid, slots
        )
        ngroups = jnp.where(over, jnp.int64(slots + 1), count.astype(jnp.int64))
        ngroups = jnp.where(stale, jnp.int64(WIDTH_STALE), ngroups)
        occupied = jnp.arange(slots) < count
        group_valid = occupied
        # reconstruct key columns arithmetically from the packed table
        out_cols = {}
        off = 0
        for name, k, (w, b) in zip(key_names, keys, key_widths):
            limb = (uniq >> off) & ((1 << w) - 1)
            off += w
            kv = (limb != 0) & occupied
            kd = (limb - (b + 1)).astype(k.data.dtype)
            out_cols[name] = DevCol(jnp.where(kv, kd, jnp.zeros_like(kd)), kv)
        # 'first' needs a representative row per group: min row id per slot
        claimer = None
        if any(a.func == "first" for a in aggs):
            claimer = (
                jnp.full(slots + 1, cap, dtype=jnp.int32)
                .at[seg]
                .min(jnp.arange(cap, dtype=jnp.int32), mode="drop")[:slots]
            )
        cl = (
            jnp.minimum(claimer, cap - 1)
            if claimer is not None
            else jnp.zeros(slots, dtype=jnp.int32)
        )
        red = _pick_backend(seg, slots)
        out = _run_aggs(
            batch, aggs, arg_cols, seg, slots, group_valid, cl, out_cols, red,
            reps=reps,
        )
        return _mask_post(out, fold_distinct_overflow(ngroups))

    if keys:
        slots = _next_pow2(max(2 * group_capacity, 16))
        seg, claimer, true_ng, overflow = group_assign(
            keys, batch.row_valid, slots
        )
        ngroups = jnp.where(overflow, jnp.int64(slots + 1), true_ng)
        occupied = claimer < cap
        red = _pick_backend(seg, slots)
    else:
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
        occupied = claimer < cap
        ngroups = jnp.sum(occupied.astype(jnp.int64))
        red = _scalar_backend(slots)

    group_valid = occupied
    cl = jnp.minimum(claimer, cap - 1)

    # --- group key columns: value at the first (claiming) row ---
    out_cols = {}
    for name, k in zip(key_names, keys):
        kd = k.data[cl]
        kv = k.valid[cl] & group_valid
        out_cols[name] = DevCol(jnp.where(group_valid, kd, jnp.zeros_like(kd)), kv)

    return _mask_post(
        _run_aggs(
            batch, aggs, arg_cols, seg, slots, group_valid, cl, out_cols, red,
            reps=reps,
        ),
        fold_distinct_overflow(ngroups),
    )


def _scalar_backend(slots):
    """Scalar (no GROUP BY) reductions: exactly one group lives at slot
    0, so each lane is ONE full-array reduction. On CPU the reduction
    routes through fastreduce (XLA:CPU lowers reduces with fused
    producers to scalar loops — the two-stage GEMV is 10-45x faster,
    measured); TPU keeps the fused jnp reduction, which is optimal
    there."""
    from tidb_tpu.executor import fastreduce as FR

    fast = FR.use_fast()
    ops = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}

    def red(op, vals, contrib, ident):
        if fast and op == "sum":
            if jnp.issubdtype(vals.dtype, jnp.floating):
                top = FR.sum_f64(vals, contrib).astype(vals.dtype)
            else:
                top = FR.sum_i64(vals, contrib)
        else:
            top = ops[op](jnp.where(contrib, vals, ident))
        out = jnp.full((slots,), ident, dtype=top.dtype)
        return out.at[0].set(top)

    return red


def _masked_backend(seg, slots):
    """Aggregate reductions as fused masked full-array reductions, one
    accumulator per (slot, agg) — scatter-free. TPU scatter costs ~20x a
    fused masked reduction at small slot counts, so this is the fast path
    there when the slot table is small. The optimization barrier pins the
    reduction inputs: without it XLA fuses the producer expression tree
    (decimal products, filters, the claim loop) into EVERY per-slot
    reduction, recomputing it slots*aggs times — measured 35x slowdown on
    whole-query Q1."""
    ops = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}

    def red(op, vals, contrib, ident):
        f = ops[op]
        vals, contrib = jax.lax.optimization_barrier((vals, contrib))
        return jnp.stack(
            [f(jnp.where(contrib & (seg == s), vals, ident)) for s in range(slots)]
        )

    return red


def _pick_backend(seg, slots):
    """Small slot tables: masked reductions on TPU (scatter there costs
    ~20x a fused reduction), segment_* scatter elsewhere (CPU XLA lowers
    segment_sum to a fast serial scatter; the masked path is ~20x slower
    there even with the barrier). Large tables: always segment.
    TIDB_TPU_FORCE_MASKED=1 forces the masked path so the CPU test suite
    can exercise the TPU lowering's numerics."""
    import os

    forced = os.environ.get("TIDB_TPU_FORCE_MASKED") == "1"
    if slots <= 128 and (forced or _is_tpu()):
        return _masked_backend(seg, slots)
    return None


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


def _try_pallas_slot_sums(aggs, arg_cols, seg, slots, srow_valid, reps):
    """Opt-in (TIDB_TPU_PALLAS=1) one-pass slot accumulation for the
    non-wide SUM/COUNT/AVG aggregates: stacks their (value, contrib)
    pairs and calls the Pallas kernel once. Returns {lane index ->
    (sum f32 [slots], count i64-ish)} keyed by agg index, or None when
    not opted in (the jnp path runs as before); an opted-in kernel that
    fails to import, lower or run raises. float32 accumulation:
    experimental, see pallas_kernels.py numerics note."""
    from tidb_tpu.executor.pallas_kernels import (
        pallas_interpret,
        slot_sums_f32,
    )

    interp = pallas_interpret()
    if interp is None or slots > 128:
        return None
    lanes = []  # (agg index, kind: 'cnt'|'sum', values, contrib)
    for i, (a, col) in enumerate(zip(aggs, arg_cols)):
        if a.func not in ("count", "sum", "avg") or a.wide:
            continue
        if col is None:
            lanes.append((i, "cnt", jnp.ones_like(seg, jnp.float32), srow_valid))
            continue
        contrib = col.valid & srow_valid
        if reps and i in reps:
            contrib = contrib & reps[i]
        if a.func in ("sum", "avg"):
            lanes.append((i, "sum", col.data.astype(jnp.float32), contrib))
        if a.func in ("count", "avg"):
            lanes.append((i, "cnt", jnp.ones_like(seg, jnp.float32), contrib))
    if not lanes:
        return None
    vals = jnp.stack([v for _i, _k, v, _c in lanes])
    contribs = jnp.stack([c for _i, _k, _v, c in lanes])
    sums = slot_sums_f32(
        vals, contribs, seg.astype(jnp.int32), slots, interpret=interp
    )
    out = {}
    for lane, (i, kind, _v, _c) in enumerate(lanes):
        out.setdefault(i, {})[kind] = sums[lane]
    return out


_SEG_OPS = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}


def _exec_reqs(reqs, red, seg, slots, num_segments):
    """Execute a list of (op, vals, contrib, ident) reduction requests,
    one segment scatter per lane. (Stacking same-op lanes into one
    [n, L] scatter was measured 2x SLOWER on CPU XLA: the stack
    materializes an n x L intermediate because producers don't fuse into
    scatter operands, costing more traffic than the shared seg reads
    save.)"""
    if red is not None:
        batch_exec = getattr(red, "exec_all", None)
        if batch_exec is not None:
            return batch_exec(reqs)
        return [red(op, v, c, i) for (op, v, c, i) in reqs]
    ns = (slots + 1) if num_segments is None else num_segments
    return [
        _SEG_OPS[op](jnp.where(c, v, ident), seg, num_segments=ns)[:slots]
        for (op, v, c, ident) in reqs
    ]


def _run_aggs(
    batch, aggs, arg_cols, seg, slots, group_valid, cl, out_cols, red=None,
    reps=None, num_segments=None,
):
    """Compute all aggregates into the slot table. One implementation of
    the MySQL aggregate semantics (NULL rules, AVG decimal scale),
    parameterized over the reduction backend. `reps` maps agg index to a
    DISTINCT representative-row mask (_distinct_reps). Runs in three
    phases — collect reduction requests, execute them (batched), then
    assemble output columns — so independent lanes share scatter passes."""
    srow_valid = seg < slots
    ones = jnp.ones_like(seg, dtype=jnp.int64)
    # the pallas slot kernel accumulates BY seg value — meaningless under
    # the sorted reducer, whose seg only encodes row validity
    pallas_pre = None
    if not isinstance(red, _SortedReducer):
        pallas_pre = _try_pallas_slot_sums(
            aggs, arg_cols, seg, slots, srow_valid, reps
        )
    reqs = []

    def req(op, vals, contrib, ident):
        reqs.append((op, vals, contrib, ident))
        return len(reqs) - 1

    assemble = []  # callables taking the executed results list

    def emit(name, fn):
        assemble.append((name, fn))

    for i, (a, col) in enumerate(zip(aggs, arg_cols)):
        pre = (pallas_pre or {}).get(i)
        if a.func == "count" and col is None:
            if pre is not None:
                s = jnp.round(pre["cnt"]).astype(jnp.int64)
                out_cols[a.out_name] = DevCol(s, group_valid)
            else:
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
            if pre is not None:
                s = jnp.round(pre["cnt"]).astype(jnp.int64)
                out_cols[a.out_name] = DevCol(s, group_valid)
            else:
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

            elif pre is not None:
                ps = pre["sum"]
                s_pre = (
                    jnp.round(ps).astype(data.dtype)
                    if not jnp.issubdtype(data.dtype, jnp.floating)
                    else ps.astype(data.dtype)
                )

                def mk_s(R, s_pre=s_pre):
                    return s_pre

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

            if pre is not None and "cnt" in pre:
                cnt_pre = jnp.round(pre["cnt"]).astype(jnp.int64)

                def mk_cnt(R, cnt_pre=cnt_pre):
                    return cnt_pre

            else:
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

    results = _exec_reqs(reqs, red, seg, slots, num_segments)
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
