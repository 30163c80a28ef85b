"""Window functions with static shapes.

Reference: WindowExec (pkg/executor/window.go:32) and PipelinedWindowExec
(pipelined_window.go:38); the reference parallelizes via ShuffleExec
hash-repartitioning partitions to workers (shuffle.go:56-86). On TPU one
lax.sort by (partition, order) keys + segment-indexed prefix ops handles
every partition simultaneously — the shuffle is unnecessary on one chip
and becomes hash_repartition over the mesh for the distributed case.

Supported: row_number, rank, dense_rank, lag, lead, and sum/count/avg/
min/max as window aggregates — over the whole partition without ORDER BY,
or as running (rows unbounded-preceding..current) with ORDER BY.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from tidb_tpu.chunk import Batch, DevCol

ExprFn = Callable[[Batch], DevCol]


@dataclasses.dataclass(frozen=True)
class WindowDesc:
    func: str  # row_number|rank|dense_rank|lag|lead|sum|count|avg|min|max
    arg: Optional[ExprFn]
    out_name: str
    offset: int = 1  # for lag/lead
    arg_scale: int = 0
    # True when the OVER clause has ORDER BY: aggregate becomes a running
    # (rows unbounded-preceding..current) computation, else whole-partition.
    running: bool = False
    # explicit ROWS frame (lo, hi) row offsets relative to the current
    # row, None = unbounded side; overrides `running` when present.
    # Computed as differences of global prefix sums clamped to the
    # partition bounds — one cumsum serves every row's window
    # (reference: per-frame re-aggregation in pkg/executor/window.go
    # slidingWindowAggFunc; prefix-sum differencing is the O(1)-per-row
    # TPU form).
    frame: Optional[tuple] = None


def _seg_gather(values, seg, first_idx):
    return values[first_idx[seg]]


def _lex_searchsorted(S, K, s_t, k_t, side: str):
    """Vectorized binary search over rows sorted lexicographically by
    (S, K): per-target insertion points for (s_t, k_t). jnp.searchsorted
    is single-key only; this is the same O(n log n) ladder of gathers,
    which tiles fine on TPU."""
    n = S.shape[0]
    lo = jnp.zeros(s_t.shape, dtype=jnp.int32)
    hi = jnp.full(s_t.shape, n, dtype=jnp.int32)
    for _ in range(max(int(n).bit_length(), 1)):
        mid = (lo + hi) // 2
        midc = jnp.clip(mid, 0, n - 1)
        sm, km = S[midc], K[midc]
        if side == "left":
            go = (sm < s_t) | ((sm == s_t) & (km < k_t))
        else:
            go = (sm < s_t) | ((sm == s_t) & (km <= k_t))
        go = go & (lo < hi)
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(go, hi, mid)
    return lo


def window_op(
    batch: Batch,
    part_fns: Sequence[ExprFn],
    order_fns: Sequence[ExprFn],
    order_descs: Sequence[bool],
    descs: Sequence[WindowDesc],
) -> Batch:
    cap = batch.capacity
    idx32 = jnp.arange(cap, dtype=jnp.int32)

    # ---- global sort by (valid, partition keys, order keys) ----
    operands: List[jax.Array] = [~batch.row_valid]
    n_part_ops = 0
    for fn in part_fns:
        k = fn(batch)
        operands.append(~k.valid)
        operands.append(jnp.where(k.valid, k.data, jnp.zeros_like(k.data)))
        n_part_ops += 2
    for fn, desc in zip(order_fns, order_descs):
        k = fn(batch)
        valid = k.valid
        nullk = ~valid if desc else valid
        data = k.data
        if jnp.issubdtype(data.dtype, jnp.floating):
            d = -data if desc else data
        elif data.dtype == jnp.bool_:
            d = data ^ desc
        else:
            d = -data.astype(jnp.int64) if desc else data.astype(jnp.int64)
        operands.append(nullk)
        operands.append(jnp.where(valid, d, jnp.zeros_like(d)))
    sorted_ops = jax.lax.sort(operands + [idx32], num_keys=len(operands))
    perm = sorted_ops[-1]
    srow_valid = ~sorted_ops[0]

    # partition segment ids over the sorted order
    part_change = jnp.zeros(cap, dtype=bool).at[0].set(True)
    for i in range(1, 1 + n_part_ops):
        arr = sorted_ops[i]
        part_change = part_change | (arr != jnp.roll(arr, 1))
    part_change = part_change.at[0].set(True)
    seg = jnp.cumsum((part_change & srow_valid).astype(jnp.int32)) - 1
    seg = jnp.where(srow_valid, seg, cap)  # invalid rows -> overflow seg

    # peer-group change (partition change OR any order key change)
    peer_change = part_change
    for i in range(1 + n_part_ops, len(operands)):
        arr = sorted_ops[i]
        peer_change = peer_change | (arr != jnp.roll(arr, 1))
    peer_change = peer_change.at[0].set(True)

    num_segments = cap + 1
    first_idx = (
        jnp.full(num_segments, cap - 1, dtype=jnp.int32)
        .at[seg]
        .min(idx32, mode="drop")
    )
    seg_c = jnp.clip(seg, 0, cap)

    # shared per-sort-order arrays (computed once, used by several
    # window functions): last row index per partition, peer-group ids,
    # last row index per peer group, and each peer group's start index
    idx64 = jnp.arange(cap, dtype=jnp.int64)
    last_idx = (
        jnp.full(cap + 1, 0, dtype=jnp.int64)
        .at[jnp.where(srow_valid, seg_c, cap)]
        .max(idx64, mode="drop")[seg_c]
    )
    pg = jnp.cumsum(peer_change.astype(jnp.int64))
    pgc = jnp.clip(pg, 0, cap)
    peer_last = (
        jnp.full(cap + 1, 0, dtype=jnp.int64)
        .at[jnp.where(srow_valid, pgc, cap)]
        .max(idx64, mode="drop")[pgc]
    )
    peer_start = jax.lax.cummax(jnp.where(peer_change, idx64, 0))
    aux = {
        "last_idx": last_idx, "peer_last": peer_last,
        "peer_start": peer_start,
    }
    if len(order_fns) == 1:
        # normalized (ascending-monotone) order key for RANGE value
        # frames: DESC keys were pre-negated above, so value deltas keep
        # their sign; NULL keys collapse to -inf (all NULLs are peers
        # and any offset window over a NULL row spans exactly the NULLs)
        base = 1 + n_part_ops
        nullk_s = sorted_ops[base].astype(bool)
        kvalid = ~nullk_s if order_descs[0] else nullk_s
        kv = sorted_ops[base + 1].astype(jnp.float64)
        # NULLs must keep the per-partition key array MONOTONE for the
        # binary search: they sort first under ASC (-inf) but LAST
        # under DESC (+inf in the negated domain)
        ninf = jnp.inf if order_descs[0] else -jnp.inf
        aux["range_key"] = jnp.where(kvalid, kv, ninf)

    new_cols = {}
    inv = jnp.zeros(cap, dtype=jnp.int32).at[perm].set(idx32)
    for d in descs:
        col = _compute(
            d, batch, perm, srow_valid, seg_c, first_idx, peer_change, cap,
            aux,
        )
        # scatter back to original row positions
        new_cols[d.out_name] = DevCol(col.data[inv], col.valid[inv])

    cols = dict(batch.cols)
    cols.update(new_cols)
    return Batch(cols, batch.row_valid)


def _compute(
    d: WindowDesc, batch, perm, srow_valid, seg, first_idx, peer_change,
    cap, aux,
):
    idx = jnp.arange(cap, dtype=jnp.int64)
    pos = idx - first_idx[seg]
    if d.func == "row_number":
        return DevCol(pos + 1, srow_valid)
    if d.func == "rank":
        return DevCol(aux["peer_start"] - first_idx[seg] + 1, srow_valid)
    if d.func == "dense_rank":
        c = jnp.cumsum(peer_change.astype(jnp.int64))
        return DevCol(c - c[first_idx[seg]] + 1, srow_valid)

    if d.func in ("ntile", "percent_rank", "cume_dist"):
        nrows = aux["last_idx"] - first_idx[seg] + 1
        if d.func == "ntile":
            n = jnp.int64(d.offset)
            # MySQL: first (rows % n) buckets get one extra row
            base = nrows // n
            rem = nrows % n
            big = rem * (base + 1)
            bucket = jnp.where(
                pos < big,
                pos // jnp.maximum(base + 1, 1),
                rem + (pos - big) // jnp.maximum(base, 1),
            )
            return DevCol(bucket + 1, srow_valid)
        if d.func == "percent_rank":
            rank = aux["peer_start"] - first_idx[seg] + 1
            denom = jnp.maximum(nrows - 1, 1).astype(jnp.float64)
            return DevCol(
                (rank - 1).astype(jnp.float64) / denom, srow_valid
            )
        # cume_dist: peers' LAST position / partition rows
        return DevCol(
            (aux["peer_last"] - first_idx[seg] + 1).astype(jnp.float64)
            / jnp.maximum(nrows, 1).astype(jnp.float64),
            srow_valid,
        )

    if d.arg is None:  # COUNT(*) OVER ...
        data = jnp.ones(cap, dtype=jnp.int64)
        valid = srow_valid
    else:
        arg = d.arg(batch)
        data = arg.data[perm]
        valid = arg.valid[perm] & srow_valid

    if d.func in ("lag", "lead"):
        off = d.offset if d.func == "lag" else -d.offset
        src = jnp.clip(idx - off, 0, cap - 1)
        same_seg = seg[src] == seg
        in_range = (idx - off >= 0) & (idx - off < cap)
        ok = same_seg & in_range & srow_valid
        return DevCol(
            jnp.where(ok, data[src], jnp.zeros_like(data[src])),
            ok & valid[src],
        )

    if d.func in ("first_value", "last_value", "nth_value"):
        # MySQL default frame is RANGE UNBOUNDED PRECEDING..CURRENT ROW:
        # the frame ends at the current row's LAST PEER
        peer_last = aux["peer_last"]
        if d.func == "first_value":
            src = first_idx[seg].astype(jnp.int64)
        elif d.func == "nth_value":
            # NULL until the nth row has entered the frame
            src = first_idx[seg].astype(jnp.int64) + (d.offset - 1)
        else:
            src = peer_last
        ok = srow_valid & (src <= peer_last) & (src >= 0)
        srcc = jnp.clip(src, 0, cap - 1)
        return DevCol(
            jnp.where(ok, data[srcc], jnp.zeros_like(data[srcc])),
            ok & valid[srcc],
        )

    # whole-partition aggregates via segment reduce; running variants via
    # prefix ops offset by the segment start.
    zero = jnp.zeros((), dtype=data.dtype)
    if d.func in ("sum", "avg", "count"):
        contrib = (
            valid.astype(jnp.int64)
            if d.func == "count"
            else jnp.where(valid, data, zero)
        )
        if d.frame is not None:
            idx32 = jnp.arange(cap, dtype=jnp.int32)
            start = first_idx[seg]
            last_idx = (
                jnp.zeros(cap + 1, dtype=jnp.int32)
                .at[seg]
                .max(idx32, mode="drop")
            )
            end = last_idx[seg]
            if len(d.frame) == 3:
                # RANGE value frame: bounds are the row positions whose
                # ORDER BY key falls within [key+lo_off, key+hi_off],
                # found by lexicographic (partition, key) binary search
                # over the sorted arrays (searchsorted has no multi-key
                # form). Reference: pkg/executor/window.go range frames.
                _tag, flo, fhi = d.frame
                k = aux["range_key"]
                if flo is None:
                    loi = start
                else:
                    t_lo = k if flo == "cur" else k + flo
                    loi = _lex_searchsorted(
                        seg, k, seg, t_lo, side="left"
                    ).astype(jnp.int32)
                if fhi is None:
                    hii = end
                else:
                    t_hi = k if fhi == "cur" else k + fhi
                    hii = (
                        _lex_searchsorted(seg, k, seg, t_hi, side="right")
                        - 1
                    ).astype(jnp.int32)
                loi = jnp.maximum(loi, start)
                hii = jnp.minimum(hii, end)
            else:
                lo, hi = d.frame
                loi = start if lo is None else jnp.maximum(idx32 + lo, start)
                hii = end if hi is None else jnp.minimum(idx32 + hi, end)
            empty = hii < loi
            c = jnp.cumsum(contrib)
            cnt_c = jnp.cumsum(valid.astype(jnp.int64))

            def rng(pref, a, b):
                left = jnp.where(
                    a > 0, pref[jnp.clip(a - 1, 0, cap - 1)], 0
                )
                return pref[jnp.clip(b, 0, cap - 1)] - left

            run = jnp.where(empty, 0, rng(c, loi, hii))
            cnt = jnp.where(empty, 0, rng(cnt_c, loi, hii))
        elif d.running:
            c = jnp.cumsum(contrib)
            run = c - jnp.where(first_idx[seg] > 0, c[jnp.clip(first_idx[seg] - 1, 0, cap - 1)], 0)
            cnt_c = jnp.cumsum(valid.astype(jnp.int64))
            cnt = cnt_c - jnp.where(first_idx[seg] > 0, cnt_c[jnp.clip(first_idx[seg] - 1, 0, cap - 1)], 0)
        else:
            s = jax.ops.segment_sum(contrib, seg, num_segments=cap + 1)
            run = s[seg]
            cn = jax.ops.segment_sum(valid.astype(jnp.int64), seg, num_segments=cap + 1)
            cnt = cn[seg]
        if d.func == "count":
            return DevCol(cnt if d.running else run, srow_valid)
        if d.func == "sum":
            return DevCol(run, srow_valid & (cnt > 0))
        denom = jnp.where(cnt == 0, 1, cnt).astype(jnp.float64)
        if d.arg_scale:
            denom = denom * (10**d.arg_scale)
        return DevCol(run.astype(jnp.float64) / denom, srow_valid & (cnt > 0))
    if d.func in ("min", "max"):
        big = _sentinel(data.dtype, d.func == "min")
        masked = jnp.where(valid, data, big)
        if d.running:
            op = jnp.minimum if d.func == "min" else jnp.maximum

            # segmented scan: the accumulator resets at every partition
            # boundary
            from tidb_tpu.executor.sortops import _seg_scan

            seg_start = first_idx[seg] == jnp.arange(cap, dtype=jnp.int32)
            run = _seg_scan(masked, seg_start, op)
            cnt = jnp.cumsum(valid.astype(jnp.int64))
            cnt = cnt - jnp.where(first_idx[seg] > 0, cnt[jnp.clip(first_idx[seg] - 1, 0, cap - 1)], 0)
        else:
            red = jax.ops.segment_min if d.func == "min" else jax.ops.segment_max
            s = red(masked, seg, num_segments=cap + 1)
            run = s[seg]
            cn = jax.ops.segment_sum(valid.astype(jnp.int64), seg, num_segments=cap + 1)
            cnt = cn[seg]
        return DevCol(run, srow_valid & (cnt > 0))
    raise NotImplementedError(f"window func {d.func}")


def _sentinel(dtype, is_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if is_min else -jnp.inf, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if is_min else info.min, dtype=dtype)
