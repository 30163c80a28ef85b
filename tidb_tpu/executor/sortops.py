"""Sort-based relational kernels: the TPU-native answer to hash tables.

TPU microbenchmarks (scripts/microbench_agg.py, TPU v5e, 1M rows) put the
primitive costs at:

    lax.sort (1-3 operands)      ~3-6 ms      regular strided passes
    cumsum / segmented scan      ~3 ms        regular
    row-gather [N, L] matrix     ~4 ms        amortizes over lanes
    masked reduction (<=128)     ~1.4 ms      one int64 lane, 64 slots, 1M rows;
                                              16 lanes x 16 slots at 67M rows took
                                              169 ms (PERF.md PR 34: integer sums
                                              over a dense domain contract instead)
    jnp.searchsorted (N probes)  ~160 ms      log N rounds of random gather
    segment_sum scatter          ~64 ms       serialized scatter
    scatter-min                  ~130 ms      serialized scatter

so anything built on scatter or per-row binary search is 20-50x slower
than a formulation built on sort + prefix scan. The reference's hash
aggregate (pkg/executor/aggregate/agg_hash_executor.go) and hash join
(pkg/executor/join/hash_table.go) therefore map to SORTS here, not to
device hash tables:

  - group-by = lexicographic sort of key components with the row id as
    the final key, segment boundaries from adjacent-row comparison,
    aggregates as cumulative-sum differences at segment ends;
  - searchsorted(a, q) for large q = one merged sort of a ++ q plus a
    rank subtraction, then one pack-sort to restore query order — three
    regular sorts instead of len(q) binary searches.

Both keep every op regular (sorts, scans, small gathers), report true
cardinalities for the host's capacity-discovery protocol, and compile to
a single fused XLA program like the rest of the engine.

Every sort here is UNSTABLE over PACKED keys (pack_lex). The v5e
compiler's time for one lax.sort grows about quadratically with the
number of 32-bit key limbs its comparator reads and hardly at all with
the length (measured for v5e:2x2, 6,291,456 rows: one i32 key 7 s, one
i64 key 27 s, i32+i32 27 s, i64+i32 59 s, i64+i64 105 s; an int8 flag
costs a whole limb, and is_stable=True adds a hidden iota key — the
seed's aggregation sort of five int8 keys + row id took 524 s). So key
components are bit-packed into as few words as hold them, and the row
id rides as the last packed component, which makes the order total and
stability moot.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from tidb_tpu.chunk import Batch, DevCol

_I64_MAX = jnp.iinfo(jnp.int64).max

_TRACING = threading.local()


@contextlib.contextmanager
def grouping_ledger():
    """What the sorted group-bys traced inside did: one (rows, groups,
    slots) a group-by, `rows` the scalar of the valid rows that entered
    it, `groups` that of the groups it found and `slots` its group
    table. The program opens it around the plan and returns the sums
    beside its cardinality scalars (planner/physical.py), as it does
    the expanding joins' (join.expansion_ledger)."""
    prev = getattr(_TRACING, "groupings", None)
    _TRACING.groupings = out = []
    try:
        yield out
    finally:
        _TRACING.groupings = prev
        if prev is not None:
            prev.extend(out)


def _note_grouping(rows: jax.Array, groups: jax.Array, slots: int) -> None:
    """Count one traced sorted group-by (once per aggregate per traced
    program, as the dense contractions are counted) and put it on the
    open ledger."""
    from tidb_tpu.utils.metrics import REGISTRY

    REGISTRY.counter(
        "tidbtpu_executor_sorted_groupings_total",
        "keyed aggregates traced on the sorted path: a packed key wider "
        "than the dense domain, so the rows are sorted by it and "
        "reduced between the segment boundaries",
    ).inc()
    ledger = getattr(_TRACING, "groupings", None)
    if ledger is not None:
        ledger.append((rows, groups, int(slots)))


def bits_for(n: int) -> int:
    """Bits that hold every value in [0, n)."""
    return max(int(n - 1).bit_length(), 1)


def pack_lex(comps):
    """Pack lexicographic sort components into key operands.

    comps: [(array, bits)] most-significant first. An array with a
    `bits` holds unsigned values below 2**bits (any int/bool dtype,
    bits <= 64); bits=None marks an operand that sorts as it is (a
    float: the v5e compiler cannot bitcast 64-bit floats to integers).
    Each run of packable components forms one bit string, cut into
    ceil(total/32) uint32 limbs (a component may straddle limbs; spare
    bits pad the top of the first). Returns (operands most-significant
    first, where): where[i] places component i for unpack_lex."""
    operands: list = []
    where: list = [None] * len(comps)

    def flush(run):
        total = sum(bits for _i, _a, bits in run)
        nlimbs = -(-total // 32)
        off = total
        offs = []
        for i, _a, bits in run:
            off -= bits
            offs.append(off)
            where[i] = (len(operands), nlimbs, off, bits)
        for j in range(nlimbs - 1, -1, -1):  # j = limb index from the end
            lo = 32 * j
            word = None
            for (_i, arr, bits), off in zip(run, offs):
                if off >= lo + 32 or off + bits <= lo:
                    continue
                v = arr.astype(jnp.uint64)
                v = (
                    v << jnp.uint64(off - lo) if off >= lo
                    else v >> jnp.uint64(lo - off)
                )
                part = (v & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
                word = part if word is None else word | part
            operands.append(word)

    run: list = []
    for i, (arr, bits) in enumerate(comps):
        if bits is None:
            if run:
                flush(run)
                run = []
            where[i] = (len(operands), 1, 0, None)
            operands.append(arr)
        else:
            run.append((i, arr, bits))
    if run:
        flush(run)
    return operands, where


def unpack_lex(operands, where, i):
    """Component i of (sorted) pack_lex operands: uint64 for a packed
    component, the operand itself for a bits=None one."""
    first, nlimbs, off, bits = where[i]
    if bits is None:
        return operands[first]
    out = None
    for j in range(nlimbs):
        lo = 32 * j
        if off >= lo + 32 or off + bits <= lo:
            continue
        v = operands[first + nlimbs - 1 - j].astype(jnp.uint64)
        v = v >> jnp.uint64(off - lo) if off >= lo else v << jnp.uint64(lo - off)
        out = v if out is None else out | v
    return out & jnp.uint64((1 << bits) - 1)


def sort_lex(comps):
    """Unstable ascending sort of packed components; pass a unique last
    component (the row id) for a total order. Returns the sorted
    operands and the `where` table for unpack_lex."""
    operands, where = pack_lex(comps)
    out = jax.lax.sort(operands, num_keys=len(operands), is_stable=False)
    return list(out), where


def sort_rows(comps, cap: int):
    """sort_lex with the row id appended as the last component: a total
    order in which ties keep row order. Returns (sorted operands,
    where, perm) — perm[j] is the original row now at position j."""
    comps = list(comps) + [(jnp.arange(cap, dtype=jnp.int32), bits_for(cap))]
    ops, where = sort_lex(comps)
    return ops, where, unpack_lex(ops, where, len(comps) - 1).astype(jnp.int32)


def compaction_index(valid: jax.Array, out_cap: int):
    """(sel, filled): sel[j] is the row of the j-th True of `valid`, in
    row order, for j < out_cap; filled[j] is False where `valid` holds
    fewer than j + 1 Trues (sel[j] then names some invalid row). The
    engine's one way to compact a tile: ONE single-limb sort (validity
    bit + row id), after which every column moves by a gather of
    out_cap rows - a scatter pays per INPUT row and the v5e runs it
    serially (69 ns a row against a gather's 7-8, PERF.md PR 28)."""
    ops, where, perm = sort_rows([(~valid, 1)], valid.shape[0])
    return perm[:out_cap], unpack_lex(ops, where, 0)[:out_cap] == 0


def pack_bits(bit0: jax.Array, flags) -> jax.Array:
    """A u32 word a row: `bit0` (a u32 of 0 or 1), then one bit a flag."""
    word = bit0
    for i, flag in enumerate(flags):
        word = word | (flag.astype(jnp.uint32) << (1 + i))
    return word


def to_lanes(arr: jax.Array):
    """A column's values as u32 lanes, exactly: one lane for 32 bits or
    fewer (a narrow value widened first), the high and the low limb of
    a 64-bit integer. None for a float64, which the v5e compiler cannot
    bitcast: it travels beside the lanes."""
    dt = arr.dtype
    if dt == jnp.float64:
        return None
    if dt.itemsize == 8:
        u = jax.lax.bitcast_convert_type(arr, jnp.uint64)
        return [(u >> jnp.uint64(32)).astype(jnp.uint32), u.astype(jnp.uint32)]
    if dt == jnp.bool_ or jnp.issubdtype(dt, jnp.unsignedinteger):
        return [arr.astype(jnp.uint32)]
    wide = jnp.int32 if jnp.issubdtype(dt, jnp.integer) else jnp.float32
    return [jax.lax.bitcast_convert_type(arr.astype(wide), jnp.uint32)]


def from_lanes(lanes, dtype) -> jax.Array:
    """Inverse of to_lanes."""
    dt = jnp.dtype(dtype)
    if dt.itemsize == 8:
        hi, lo = (x.astype(jnp.uint64) for x in lanes)
        return jax.lax.bitcast_convert_type((hi << jnp.uint64(32)) | lo, dt)
    (u,) = lanes
    if dt == jnp.bool_ or jnp.issubdtype(dt, jnp.unsignedinteger):
        return u.astype(dt)
    wide = jnp.int32 if jnp.issubdtype(dt, jnp.integer) else jnp.float32
    return jax.lax.bitcast_convert_type(u, wide).astype(dt)


def gather_rows(datas, flags, index: jax.Array):
    """([d[index] for d in datas], [f[index] for f in flags]), bit for
    bit, by ONE gather: the engine's one way to move the rows of a tile.
    On the v5e a gather pays per OUTPUT row and no more for a row of
    many lanes than for a row of one (5 ns at 524,288 rows with one u32
    lane or eleven, PERF.md PR 30), so every array that moves through
    the same index rides as u32 lanes of one stacked operand: an
    integer, bool or float32 array as its exact limbs (to_lanes), the
    boolean flags as the bits of u32 words, 32 a word. A float64 array
    takes a gather of its own through the same index (to_lanes says
    why). Index values past the tile are clipped, as plain indexing
    clips them. Pass only arrays something reads: a stacked operand
    keeps every lane alive where XLA drops an unread column's own
    gather and the chain that made it (PERF.md PR 30).

    Counted while the program is traced: once per call per compiled
    program."""
    from tidb_tpu.utils.metrics import REGISTRY

    datas, flags = list(datas), list(flags)
    lanes, spans = [], []
    for d in datas:
        limbs = to_lanes(d)
        spans.append(None if limbs is None else (len(lanes), len(limbs)))
        lanes += limbs or []
    words_at = len(lanes)
    lanes += [
        pack_bits(flags[at].astype(jnp.uint32), flags[at + 1:at + 32])
        for at in range(0, len(flags), 32)
    ]
    if lanes:
        REGISTRY.counter(
            "tidbtpu_executor_stacked_gathers_total",
            "row gathers of stacked u32 lanes (sortops.gather_rows) in "
            "traced programs",
        ).inc()
        got = jnp.stack(lanes)[:, index]
    out = [
        d[index] if span is None
        else from_lanes([got[span[0] + k] for k in range(span[1])], d.dtype)
        for d, span in zip(datas, spans)
    ]
    bits = [
        ((got[words_at + i // 32] >> (i % 32)) & 1) != 0
        for i in range(len(flags))
    ]
    return out, bits


def int_sort_bits(d: jax.Array):
    """(unsigned order-preserving image of an integer/bool array, bits):
    the value less its dtype's minimum."""
    if d.dtype == jnp.bool_:
        return d, 1
    nbits = d.dtype.itemsize * 8
    if jnp.issubdtype(d.dtype, jnp.unsignedinteger):
        return d, nbits
    u = jax.lax.bitcast_convert_type(d, jnp.dtype(f"uint{nbits}"))
    return u ^ u.dtype.type(1 << (nbits - 1)), nbits


def int_from_sort_bits(u: jax.Array, dtype):
    """Inverse of int_sort_bits for a uint64-held image."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bool_:
        return u != 0
    nbits = dtype.itemsize * 8
    if jnp.issubdtype(dtype, jnp.unsignedinteger):
        return u.astype(dtype)
    w = u.astype(jnp.dtype(f"uint{nbits}"))
    return jax.lax.bitcast_convert_type(w ^ w.dtype.type(1 << (nbits - 1)), dtype)


def merge_searchsorted(
    sorted_keys: jax.Array, queries: jax.Array, side: str
) -> jax.Array:
    """jnp.searchsorted(sorted_keys, queries, side) computed with sorts.

    For each query q: side='left' returns #keys < q, side='right'
    #keys <= q. The merged sort's tie tag orders queries before (left)
    or after (right) equal keys; a query's insertion point is then its
    merged position minus its rank among queries. A final single-operand
    sort of packed (query id, result) pairs restores query order without
    a scatter. Exact for full-range int64 keys.
    """
    n = sorted_keys.shape[0]
    m = queries.shape[0]
    tq = 0 if side == "left" else 1
    tk = 1 - tq
    keys = jnp.concatenate([sorted_keys, queries])
    # (tag, query id) share one word behind the key: equal (key, tag)
    # non-query entries are identical, so the unstable order is moot
    tagq = jnp.concatenate(
        [
            jnp.full(n, tk << 31, dtype=jnp.uint32),
            jnp.uint32(tq << 31) | jnp.arange(m, dtype=jnp.uint32),
        ]
    )
    _sk, stq = jax.lax.sort([keys, tagq], num_keys=2, is_stable=False)
    is_q = (stq >> jnp.uint32(31)) == tq
    sq = (stq & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
    nq_incl = jnp.cumsum(is_q.astype(jnp.int32))
    res = jnp.arange(n + m, dtype=jnp.int32) - (nq_incl - 1)
    packed = jnp.where(
        is_q,
        (sq.astype(jnp.int64) << 32) | res.astype(jnp.int64),
        _I64_MAX,
    )
    back = jax.lax.sort([packed], num_keys=1, is_stable=False)[0][:m]
    return (back & jnp.int64(0xFFFFFFFF)).astype(queries.dtype)


def run_ends(sorted_keys: jax.Array) -> jax.Array:
    """For each position j of a sorted array: the end (exclusive) of the
    run of values equal to sorted_keys[j] — a reversed running min of
    run-boundary positions. With this, hi = run_ends[lo] replaces the
    second (side='right') searchsorted of an equi-probe. The running min
    is _seg_scan's rolled loop: lax.cummin over 786,432 int32 takes the
    v5e compiler 37 s, this 1.5 s (described v5e:2x2, PR 33)."""
    n = sorted_keys.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    nxt = jnp.where(
        jnp.concatenate(
            [sorted_keys[1:] != sorted_keys[:-1], jnp.ones(1, dtype=bool)]
        ),
        idx + 1,
        n,
    )
    whole = jnp.zeros(n, dtype=bool)  # one segment: a plain scan
    return jnp.flip(_seg_scan(jnp.flip(nxt), whole, jnp.minimum))


def _seg_scan(vals: jax.Array, boundary: jax.Array, op) -> jax.Array:
    """Inclusive segmented scan: runs of rows between boundary flags are
    scanned independently. The standard segmented-scan semiring over
    (value, started-a-new-segment) pairs, stepped by doubling strides in
    ONE rolled loop: lax.associative_scan unrolls its 2*log2(n) levels
    into the program, and the v5e compiler had not finished that at
    6,291,456 rows after 15 minutes; this loop compiles in 3 s."""
    n = vals.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    def step(k, st):
        v, f = st
        d = jnp.int32(1) << k
        ok = idx >= d
        pv, pf = jnp.roll(v, d), jnp.roll(f, d)
        return jnp.where(ok & ~f, op(pv, v), v), f | (ok & pf)

    v, _f = jax.lax.fori_loop(0, bits_for(n), step, (vals, boundary))
    return v


def _key_comps(k: DevCol, width):
    """Sort components of one key column, NULLs last: [(~valid, 1 bit),
    (data image, bits), (nan flag, 1 bit for floats)], a decoder
    (`get(j)` = the column's j-th sorted component -> the column's
    dtype), and a per-row mask of values outside a planner-baked width.
    Equal SQL values give equal component tuples (NULL data zeroed,
    -0.0 folded to +0.0, NaN zeroed and carried as a flag). `width` = (w, b) from the planner
    (values + b + 1 fit w bits, aggregate._pack_keys' convention) packs
    integer data tight; without it the dtype's own width is used. A
    float sorts as its own operand."""
    d = k.data
    inv = (~k.valid, 1)
    if jnp.issubdtype(d.dtype, jnp.floating):
        dd = jnp.where(d == 0, jnp.zeros_like(d), d)
        nanf = jnp.isnan(dd) & k.valid
        dd = jnp.where(nanf | ~k.valid, jnp.zeros_like(dd), dd)
        return (
            [inv, (dd, None), (nanf, 1)],
            lambda get: jnp.where(get(2) != 0, jnp.nan, get(1)),
            None,
        )
    if width is not None and d.dtype != jnp.bool_:
        w, b = width
        v = d.astype(jnp.int64) + b
        bad = k.valid & ((v < 0) | (v > (1 << w) - 2))
        v = jnp.where(k.valid & ~bad, v, 0)
        return (
            [inv, (v, w)],
            lambda get: (get(1).astype(jnp.int64) - b).astype(d.dtype),
            bad,
        )
    vd = jnp.where(k.valid, d, jnp.zeros_like(d))
    return (
        [inv, int_sort_bits(vd)],
        lambda get: int_from_sort_bits(get(1), d.dtype),
        None,
    )


def sort_group_aggregate(
    batch: Batch,
    keys: Sequence[DevCol],
    aggs,
    arg_cols,
    slots: int,
    key_names: Sequence[str],
    reps=None,
    key_widths=None,
) -> Tuple[Batch, jax.Array]:
    """Keyed aggregation by lexicographic sort, where the reference has
    a hash table (see module docstring). Returns (group batch with
    capacity `slots`, true group count) under the same overflow protocol
    as group_aggregate: a count above `slots` makes the host bump the
    capacity knob and re-jit; results in the returned batch are correct
    whenever the count fits. A valid key outside its planner-baked
    width (`key_widths`) reports aggregate.WIDTH_STALE instead, and the
    host recompiles against fresh bounds.

    Groups come out in ascending key order (NULLs last) — a stable,
    mesh-friendly order that downstream distributed merges rely on.
    DISTINCT rep masks (`reps`, in original row order) are permuted
    through the sort like every other contribution mask.
    """
    from tidb_tpu.executor.aggregate import WIDTH_STALE, _run_sorted_aggs

    cap = batch.capacity
    widths = key_widths if key_widths is not None else [None] * len(keys)
    comps: list = [(~batch.row_valid, 1)]
    key_at: list = []  # per key: (index of its first component, decoder)
    stale = jnp.zeros((), dtype=bool)
    for k, width in zip(keys, widths):
        kc, decode, bad = _key_comps(k, width)
        key_at.append((len(comps), decode))
        comps.extend(kc)
        if bad is not None:
            stale = stale | jnp.any(batch.row_valid & bad)
    rowid_at = len(comps)
    # the sort, the boundaries and the keys at the segment starts
    with jax.named_scope("group"), jax.named_scope("sort"):
        sorted_ops, where, perm = sort_rows(comps, cap)
        valid_s = unpack_lex(sorted_ops, where, 0) == 0  # invalid rows sort last

        # a new group starts where any key bit differs from the row before:
        # compare whole operands, the row id's bits (the tail of the last
        # packed run) shifted out
        rid_first, rid_limbs, _off, rid_bits = where[rowid_at]
        diff = jnp.zeros(cap, dtype=bool).at[0].set(True)
        for oi, op in enumerate(sorted_ops):
            if oi >= rid_first:
                tail = rid_bits - 32 * (rid_first + rid_limbs - 1 - oi)
                if tail >= 32:
                    continue  # nothing but row-id bits in this limb
                if tail > 0:
                    op = op >> jnp.uint32(tail)
            diff = diff | jnp.concatenate(
                [jnp.ones(1, dtype=bool), op[1:] != op[:-1]]
            )
        boundary = valid_s & diff
        ngroups = jnp.sum(boundary.astype(jnp.int64))
        nvalid = jnp.sum(valid_s.astype(jnp.int32))

        # segment start positions, compacted into the `slots` tile by a sort
        # (scatter-free); ends follow by shifting, the last real group ending
        # at nvalid
        spos = jnp.where(boundary, jnp.arange(cap, dtype=jnp.int32), cap)
        if slots > cap:
            spos = jnp.concatenate(
                [spos, jnp.full(slots - cap, cap, dtype=jnp.int32)]
            )
        starts = jax.lax.sort([spos], num_keys=1, is_stable=False)[0][:slots]
        ends = jnp.minimum(
            jnp.concatenate([starts[1:], jnp.full(1, cap, dtype=jnp.int32)]),
            nvalid,
        )
        group_valid = jnp.arange(slots) < jnp.minimum(ngroups, slots)
        starts_c = jnp.minimum(starts, cap - 1)

        # key output columns: component values at segment starts
        at_starts = [op[starts_c] for op in sorted_ops]
        out_cols = {}
        for name, (ci, decode) in zip(key_names, key_at):
            kv = (unpack_lex(at_starts, where, ci) == 0) & group_valid
            kd = decode(lambda j, ci=ci: unpack_lex(at_starts, where, ci + j))
            out_cols[name] = DevCol(jnp.where(group_valid, kd, jnp.zeros_like(kd)), kv)

    # the stacked gather through the permutation, the cumulative sums
    # cut at the segment ends, the segmented scans
    with jax.named_scope("group"), jax.named_scope("reduce"):
        out = _run_sorted_aggs(
            batch, aggs, arg_cols, perm, valid_s, boundary,
            starts_c, ends, group_valid, out_cols, reps=reps,
        )
    _note_grouping(nvalid.astype(jnp.int64), ngroups, slots)
    return out, jnp.where(stale, jnp.int64(WIDTH_STALE), ngroups)
