"""Distributed plan fragments: partial/final aggregation and joins.

Reference: the MPP fragment execution model — plans cut at exchange
boundaries (pkg/planner/core/fragment.go:47,149), HashAgg split into
partial and final stages across the shuffle (the reference does the same
split *within* one node via partial/final workers,
aggregate/agg_hash_executor.go:60-91; MPP does it across nodes), and
shuffled hash join (join keys hash-partitioned to colocate).

Everything here runs inside shard_map over the mesh axis. The composition

    scan shard -> filter -> partial agg -> all_to_all -> final agg

is the TPU rendering of TiDB's canonical MPP pipeline
TableScan -> Selection -> HashAgg(partial) -> ExchangeSender(hash) ->
ExchangeReceiver -> HashAgg(final).
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from tidb_tpu.chunk import Batch, DevCol
from tidb_tpu.executor.aggregate import AggDesc, group_aggregate
from tidb_tpu.executor.join import equi_join
from tidb_tpu.parallel.exchange import broadcast_gather, hash_repartition
from tidb_tpu.parallel.mesh import pmax

ExprFn = Callable[[Batch], DevCol]


def _colfn(name: str) -> ExprFn:
    return lambda b: b.cols[name]


def _combined_key_hash(cols, cap: int) -> DevCol:
    """Order-sensitive hash of several key columns for exchange routing.
    NULLs are canonicalized (data zeroed, validity mixed in) so equal SQL
    keys — including NULL keys, whose stored data is unspecified — hash
    identically on every device; otherwise a NULL-key group would split
    across devices and emit duplicate result rows."""
    h = jnp.zeros(cap, dtype=jnp.int64)
    for c in cols:
        hv = jnp.where(c.valid, c.data.astype(jnp.int64), jnp.int64(0))
        h = h * jnp.int64(1000003) ^ (hv * 2 + c.valid)
    return DevCol(h, jnp.ones(cap, dtype=jnp.bool_))


def _partial_descs(
    aggs: Sequence[AggDesc],
) -> Tuple[List[AggDesc], List[Tuple[str, str, List[str], int, object]]]:
    """Split aggregates into partial-stage descriptors and final-stage
    combine rules: (final func name, out name, partial col names, scale,
    post-decode callable or None)."""
    partial: List[AggDesc] = []
    final: List[Tuple[str, str, List[str], int, object]] = []
    for i, a in enumerate(aggs):
        if a.func == "count":
            pname = f"_p{i}"
            partial.append(AggDesc("count", a.arg, pname))
            final.append(("sum", a.out_name, [pname], 0, None))
        elif a.func == "sum":
            pname = f"_p{i}"
            # pack_bound holds at the partial stage (per-row bound);
            # the FINAL stage sums partial sums, whose bound is not
            # per-row — it stays unpacked (default None)
            partial.append(
                AggDesc(
                    "sum", a.arg, pname, wide=a.wide,
                    pack_bound=a.pack_bound,
                )
            )
            final.append(("sum", a.out_name, [pname], 0, None))
        elif a.func == "first":
            pname = f"_p{i}"
            partial.append(AggDesc("first", a.arg, pname))
            final.append(("first", a.out_name, [pname], 0, None))
        elif a.func in ("min", "max"):
            # the partial stage keeps encoded values (a.post decodes
            # e.g. CI-string rank*D+code back to a dict code); only the
            # FINAL reduction decodes, so cross-chunk combines still
            # order by the encoded comparison key
            pname = f"_p{i}"
            partial.append(AggDesc(a.func, a.arg, pname))
            final.append((a.func, a.out_name, [pname], 0, a.post))
        elif a.func == "avg":
            sname, cname = f"_ps{i}", f"_pc{i}"
            partial.append(
                AggDesc(
                    "sum", a.arg, sname, wide=a.wide,
                    pack_bound=a.pack_bound,
                )
            )
            partial.append(AggDesc("count", a.arg, cname))
            final.append(("avg2", a.out_name, [sname, cname], a.arg_scale, None))
        else:
            raise NotImplementedError(f"distributed agg {a.func}")
    return partial, final


def build_final_stage(key_names, final):
    """Final-merge stage descriptors shared by the distributed (mesh)
    and streamed (chunked) aggregation paths: key column readers, final
    AggDescs (avg split into sum+count), and post-division rules."""
    fkeys = [_colfn(n) for n in key_names]
    fdescs: List[AggDesc] = []
    post_avg: List[Tuple[str, str, str, int]] = []
    for func, out, pnames, scale, post in final:
        if func == "avg2":
            fdescs.append(AggDesc("sum", _colfn(pnames[0]), f"_fs_{out}"))
            fdescs.append(AggDesc("sum", _colfn(pnames[1]), f"_fc_{out}"))
            post_avg.append((out, f"_fs_{out}", f"_fc_{out}", scale))
        else:
            fdescs.append(AggDesc(func, _colfn(pnames[0]), out, post=post))
    return fkeys, fdescs, post_avg


def apply_post_avg(cols, post_avg):
    """AVG = SUM(partial sums) / SUM(partial counts), descaled for
    decimal args; drops the helper columns."""
    for out, sn, cn, scale in post_avg:
        s, c = cols[sn], cols[cn]
        denom = jnp.where(c.data == 0, 1, c.data).astype(jnp.float64)
        if scale:
            denom = denom * (10**scale)
        cols[out] = DevCol(
            s.data.astype(jnp.float64) / denom, s.valid & (c.data > 0)
        )
    for _out, sn, cn, _ in post_avg:
        cols.pop(sn, None)
        cols.pop(cn, None)
    return cols


def distributed_group_aggregate(
    local: Batch,
    key_fns: Sequence[ExprFn],
    aggs: Sequence[AggDesc],
    group_capacity: int,
    n_devices: int,
    axis: str = "d",
    key_names: Optional[Sequence[str]] = None,
    key_widths=None,
) -> Tuple[Batch, jax.Array, jax.Array]:
    """Partial agg on each shard, hash-exchange of group rows, final agg.
    Result: each device holds a disjoint subset of groups (hash-sharded)
    in a slot table of 2*group_capacity rows (group_aggregate's keyed
    output capacity; the exchange buckets stay group_capacity per device,
    overfills are counted in `dropped`). Returns (local result batch,
    global group count upper bound, dropped row count from the
    exchange)."""
    key_names = list(key_names or [f"k{i}" for i in range(len(key_fns))])

    if any(a.distinct for a in aggs):
        # DISTINCT defeats the partial/final decomposition (partial sums
        # of duplicated values can't be deduped after the fact). Instead
        # colocate each group wholly on one device by hash-repartitioning
        # the RAW rows on the group keys, then run the full aggregation
        # (with its claim-loop dedup) locally — the reference's
        # ExchangePartition-then-complete-agg MPP mode
        # (pkg/planner/core "1-phase" agg under MPP).
        if key_fns:

            def exch_rows_key(b: Batch) -> DevCol:
                return _combined_key_hash(
                    [fn(b) for fn in key_fns], b.capacity
                )

            B = max(group_capacity, (2 * local.capacity) // n_devices, 16)
            exchanged, dropped, need = hash_repartition(
                local, exch_rows_key, n_devices, B, axis
            )
            fin, ng = group_aggregate(
                exchanged, key_fns, aggs, group_capacity, key_names,
                key_widths=key_widths,
            )
            return (
                Batch(dict(fin.cols), fin.row_valid),
                jax.lax.psum(ng, axis),
                dropped,
                need,
            )
        # scalar DISTINCT: every device needs every row to dedupe
        # globally — gather, compute replicated
        gathered = broadcast_gather(local, axis)
        fin, ng = group_aggregate(
            gathered, key_fns, aggs, group_capacity, key_names,
            key_widths=key_widths,
        )
        return (
            Batch(dict(fin.cols), fin.row_valid),
            pmax(ng, axis),
            jnp.zeros((), jnp.int64),
            jnp.zeros((), jnp.int64),
        )

    partial, final = _partial_descs(aggs)

    # part_ng carries the partial stage's overflow signal (a count above
    # its output tile when the table overflowed); folded into the group-count
    # bound below so the host retries at a larger tile instead of
    # silently losing the unassigned rows' contributions
    part_batch, part_ng = group_aggregate(
        local, key_fns, partial, group_capacity, key_names, key_widths=key_widths
    )

    if key_fns:
        # exchange partial groups so equal keys colocate
        def exch_key(b: Batch) -> DevCol:
            return _combined_key_hash(
                [b.cols[kn] for kn in key_names], b.capacity
            )

        exchanged, dropped, need = hash_repartition(
            part_batch, exch_key, n_devices, group_capacity, axis
        )
    else:
        # scalar agg: all partials to device 0 conceptually == all_gather
        exchanged = broadcast_gather(part_batch, axis)
        dropped = jnp.zeros((), jnp.int64)
        need = jnp.zeros((), jnp.int64)

    fkeys, fdescs, post_avg = build_final_stage(key_names, final)
    fin, ng = group_aggregate(
        exchanged, fkeys, fdescs, group_capacity, key_names, key_widths=key_widths
    )
    cols = apply_post_avg(dict(fin.cols), post_avg)

    if not key_fns:
        # scalar: every device now has all partials; result is replicated —
        # keep it valid only on one logical row (row 0 of each shard; host
        # reads shard 0).
        pass

    # pmax (not psum) for the scalar case: the broadcast made every shard
    # compute the same single group; pmax also proves replication to jax.
    total_groups = jax.lax.psum(ng, axis) if key_fns else pmax(ng, axis)
    # a partial-stage overflow anywhere (part_ng above the partial output
    # tile, hence above the capacity knob) must surface to the host even
    # though the final stage fit
    total_groups = jnp.maximum(total_groups, pmax(part_ng, axis))
    return Batch(cols, fin.row_valid), total_groups, dropped, need


def repartition_pair(
    left: Batch,
    right: Batch,
    left_key: ExprFn,
    right_key: ExprFn,
    n_devices: int,
    bucket_capacity: int,
    axis: str = "d",
    keep=None,
) -> Tuple[Batch, Batch, jax.Array, jax.Array]:
    """Hash-partition both join sides on their keys so equal keys
    colocate (the MPP HashPartition exchange applied to a join pair).
    Returns (left', right', global dropped rows, true per-bucket need
    over BOTH sides — the retry-at-exact-size signal). The single
    shared composition used by both partitioned_join and the planner.

    `keep`: the (left, right) column names the join and its readers
    use (JoinPlan.needs); the other columns stay behind. The exchange
    moves a row's columns as lanes of one operand, from which XLA can
    drop no dead column, nor then the work below that made it: 60 ms of
    a mesh Q5's 209 on the v5e (PERF.md, PR 30)."""
    if keep is not None:
        left, right = (
            Batch({c: v for c, v in b.cols.items() if c in k}, b.row_valid)
            for b, k in zip((left, right), keep)
        )
    lex, d1, n1 = hash_repartition(left, left_key, n_devices, bucket_capacity, axis)
    rex, d2, n2 = hash_repartition(right, right_key, n_devices, bucket_capacity, axis)
    return lex, rex, d1 + d2, jnp.maximum(n1, n2)


def partitioned_join(
    left: Batch,
    right: Batch,
    left_key: ExprFn,
    right_key: ExprFn,
    n_devices: int,
    bucket_capacity: int,
    out_capacity: int,
    join_type: str = "inner",
    axis: str = "d",
) -> Tuple[Batch, jax.Array, jax.Array]:
    """Shuffled hash join: both sides hash-partitioned on the join key so
    matching rows colocate, then a local join per device (the reference's
    HashPartition MPP join). Returns (local join result, global true
    output count, dropped exchange rows)."""
    lex, rex, dropped, _need = repartition_pair(
        left, right, left_key, right_key, n_devices, bucket_capacity, axis
    )
    out, total = equi_join(
        rex, lex, right_key_after(right_key), left_key_after(left_key),
        out_capacity, join_type,
    )
    return out, jax.lax.psum(total, axis), dropped


def left_key_after(key_fn: ExprFn) -> ExprFn:
    # keys are recomputable on the exchanged batch (same column names)
    return key_fn


def right_key_after(key_fn: ExprFn) -> ExprFn:
    return key_fn


def broadcast_join(
    build: Batch,
    probe: Batch,
    build_key: ExprFn,
    probe_key: ExprFn,
    out_capacity: int,
    join_type: str = "inner",
    axis: str = "d",
) -> Tuple[Batch, jax.Array]:
    """Broadcast the (small) build side to every device, join locally with
    the probe shard (the reference's Broadcast MPP join for small tables).
    """
    full_build = broadcast_gather(build, axis)
    out, total = equi_join(
        full_build, probe, build_key, probe_key, out_capacity, join_type
    )
    return out, jax.lax.psum(total, axis)
