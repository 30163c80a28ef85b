"""Exchange operators: the MPP shuffle as XLA collectives.

Reference: ExchangeSender/ExchangeReceiver with HashPartition / Broadcast /
PassThrough types (pkg/planner/core/physical_plans.go:1706, executed by
unistore's exchSenderExec/exchRecvExec over MPPDataPacket tunnels,
cophandler/mpp_exec.go:597,711). The TPU formulation (SURVEY.md §2.7 —
"the single most important mapping"):

  HashPartition  -> per-device bucketization + lax.all_to_all over ICI
  Broadcast      -> lax.all_gather of the (small) side
  PassThrough    -> identity (results collected at the root host)

All functions here run INSIDE shard_map: they see the per-device shard of
a row-sharded Batch and use collectives over the mesh axis. Buckets have
a static per-destination capacity; the true sent-row count is psum'd and
returned so the host can detect overflow and retry at a larger tile
(same pattern as the single-chip operators).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from tidb_tpu.chunk import Batch, DevCol

ExprFn = Callable[[Batch], DevCol]

_TRACING = threading.local()


@contextlib.contextmanager
def sent_ledger():
    """What the exchanges traced inside send: one (rows, bytes) pair of
    replicated int64 scalars an exchange. The mesh program opens it
    around the plan and returns the sums beside its cardinality scalars
    (planner/physical.py), so a statement's flight can say what crossed
    the chips."""
    prev = getattr(_TRACING, "sent", None)
    _TRACING.sent = out = []
    try:
        yield out
    finally:
        _TRACING.sent = prev


def _note_exchange(
    kind: str, batch: Batch, rows: jax.Array, hops: Tuple[int, int]
) -> None:
    """Count one traced exchange (once per exchange per traced program,
    as the joins count their compactions) and put what it sends on the
    open ledger. `rows`: the valid rows sent, summed over the shards.
    Bytes are the least any implementation moves between chips: those
    rows times the logical width of the columns that travel (a value's
    bytes; validity bits, padding and bucket slack are not counted)
    times `hops` (a fraction), the other chips a row must reach:
    (n-1)/n of them on average under a hash or range partition, n-1
    under a broadcast."""
    from tidb_tpu.utils.metrics import REGISTRY

    REGISTRY.counter(
        "tidbtpu_executor_exchanges_total",
        "exchanges in traced mesh programs, by kind",
        labels=("kind",),
    ).labels(kind=kind).inc()
    sent = getattr(_TRACING, "sent", None)
    if sent is not None:
        width = sum(c.data.dtype.itemsize for c in batch.cols.values())
        rows = rows.astype(jnp.int64)
        sent.append((rows, rows * (width * hops[0]) // hops[1]))

_MIX = jnp.int64(-7046029254386353131)  # 0x9E3779B97F4A7C15 as signed


def _mix_hash(x: jax.Array) -> jax.Array:
    """64-bit finalizer so small consecutive keys spread across devices."""
    h = x.astype(jnp.int64) * _MIX
    h = h ^ (h >> 29)
    h = h * jnp.int64(-4658895280553007687)  # 0xBF58476D1CE4E5B9
    h = h ^ (h >> 32)
    return h & jnp.int64(0x7FFFFFFFFFFFFFFF)


def partition_of(key: DevCol, n: int) -> jax.Array:
    """Destination device for each row; NULL keys all go to device 0
    (they form one group / never match in joins, but must colocate)."""
    h = _mix_hash(key.data) % n
    return jnp.where(key.valid, h, 0)


def hash_repartition(
    batch: Batch,
    key_fn: ExprFn,
    n_devices: int,
    bucket_capacity: int,
    axis: str = "d",
) -> Tuple[Batch, jax.Array, jax.Array]:
    """Redistribute rows so equal keys colocate. Per-shard view:

    1. target[i] = mix(key[i]) % n                  (hash partition fn)
    2. sort (target, row id): perm, and where each bucket starts
    3. gather the [n, B] send buffer through perm: slot s of bucket b
       is row perm[start[b] + s] (rows past a bucket's B slots drop)
    4. lax.all_to_all exchanges bucket j to device j
    5. flatten received [n, B] to a new local batch of capacity n*B

    Returns (new local batch, global dropped rows, true per-bucket
    need) — nonzero drop means retry at `need` (see exchange_by_target).
    """

    from tidb_tpu.utils.failpoint import inject

    inject("exchange/repartition")
    n = n_devices
    k = key_fn(batch)
    target = partition_of(k, n)
    # invalid rows go to a virtual overflow bucket n (never sent)
    target = jnp.where(batch.row_valid, target, n)
    return exchange_by_target(batch, target, n, bucket_capacity, axis)


def range_repartition(
    batch: Batch,
    rank_vals: jax.Array,
    n_devices: int,
    bucket_capacity: int,
    axis: str = "d",
) -> Tuple[Batch, jax.Array, jax.Array]:
    """Range-partition rows by a scalar ranking value using sampled
    splitters: device i receives every row whose rank falls in the i-th
    global range, so locally sorted shards concatenate to a total order
    — the distributed ORDER BY exchange (reference: range-partitioned
    ShuffleExec + the external-sort splitter pass in
    pkg/lightning/backend/external; classic sample sort).

    Splitters are computed collectively (identical on every device):
    each shard contributes n evenly-spaced local quantiles of its valid
    ranks; the gathered candidates' global quantiles become the n-1 cut
    points. Equal ranks always land in one bucket (ties stay local)."""

    from tidb_tpu.utils.failpoint import inject

    inject("exchange/range-repartition")
    n = n_devices
    cap = batch.capacity
    v = jnp.where(batch.row_valid, rank_vals, jnp.inf)
    srt = jnp.sort(v)
    nvalid = jnp.sum(batch.row_valid.astype(jnp.int32))
    pos = jnp.clip((jnp.arange(1, n + 1) * nvalid) // (n + 1), 0, cap - 1)
    samples = srt[pos]
    allsamp = jnp.sort(jax.lax.all_gather(samples, axis).reshape(-1))
    m = allsamp.shape[0]
    spos = jnp.clip((jnp.arange(1, n) * m) // n, 0, m - 1)
    splitters = allsamp[spos]
    target = jnp.searchsorted(splitters, rank_vals, side="right").astype(
        jnp.int32
    )
    target = jnp.where(batch.row_valid, target, n)
    return exchange_by_target(
        batch, target, n, bucket_capacity, axis, kind="range"
    )


def exchange_by_target(
    batch: Batch,
    target: jax.Array,
    n: int,
    bucket_capacity: int,
    axis: str = "d",
    kind: str = "hash",
) -> Tuple[Batch, jax.Array, jax.Array]:
    """all_to_all exchange of rows to explicit per-row target devices
    (bucket n = drop). Shared by hash and range repartition.

    Returns (new local batch, globally dropped rows, TRUE per-bucket
    need): `need` is the fullest (source, destination) bucket of this
    data, the region-balance analog (pkg/store/copr/batch_coprocessor.go
    balances tasks by actual region sizes), exact in both directions: on
    overflow the host retries at exactly `need` instead of doubling
    blindly, so a hot key costs ONE recompile, not log2(hot/B), and an
    over-provisioned first tile shrinks to it; in steady state the
    plan-cache keeps the discovered capacity and nothing recompiles.

    On the device trace the stages sit in `exchange/sort` (the order of
    the rows by bucket), `exchange/pack` (the send buffers) and
    `exchange/all-to-all` inside the operator's scope
    (scripts/trace_by_scope.py sums them)."""
    B = bucket_capacity
    cap = batch.capacity

    from tidb_tpu.executor.sortops import (
        bits_for, from_lanes, pack_bits, sort_rows, to_lanes, unpack_lex,
    )
    from tidb_tpu.parallel.mesh import pmax

    with jax.named_scope("exchange"):
        with jax.named_scope("sort"):
            # (destination, row id) packed into one key word: rows keep
            # their order inside a bucket (sortops: compile time follows
            # key limbs)
            ops, where, perm = sort_rows(
                [(jnp.clip(target, 0, n), bits_for(n + 1))], cap
            )
            sorted_t = unpack_lex(ops, where, 0).astype(jnp.int32)
            start = jnp.searchsorted(
                sorted_t, jnp.arange(n + 1, dtype=jnp.int32)
            ).astype(jnp.int32)
            # this shard's bucket sizes (start deltas), of which a
            # bucket sends its first B rows; the fullest bucket of any
            # shard is what B has to hold
            local_counts = start[1 : n + 1] - start[:n]
            kept = jnp.minimum(local_counts, B)
            sent = jnp.sum(kept.astype(jnp.int64))
            valid_rows = jnp.sum((target < n).astype(jnp.int64))
            all_valid = jax.lax.psum(valid_rows, axis)
            dropped = all_valid - jax.lax.psum(sent, axis)
            need = pmax(jnp.max(local_counts).astype(jnp.int64), axis)
        _note_exchange(kind, batch, all_valid, (n - 1, n))

        names = list(batch.cols)
        with jax.named_scope("pack"):
            # Slot s of bucket b is row perm[start[b] + s] where the
            # bucket holds more than s rows, and empty otherwise: the
            # buffer's row index is n slices of perm, and the buffer a
            # gather through it. A scatter pays 69 ns an INPUT row on
            # the v5e, serially; a gather 5 ns an output row, and no
            # more for a row of many lanes than for a row of one. So
            # all that a row carries travels as u32 lanes of ONE
            # operand, moved by one gather and one all-to-all: 2.9 ms
            # for five int64 columns of 524,288 rows against 228 ms for
            # a scatter and 42 ms for a gather a column (PERF.md, PR
            # 30). The row's presence and every column's validity are
            # the bits of lanes too, 31 columns a word: nothing 8 bits
            # wide is moved, which the v5e compiler takes 10-15 s to
            # compile into a scatter (PERF.md, PR 29).
            padded = jnp.concatenate([perm, jnp.zeros((B,), perm.dtype)])
            row = jnp.stack([
                jax.lax.dynamic_slice_in_dim(padded, start[b], B)
                for b in range(n)
            ])
            filled = jnp.arange(B, dtype=jnp.int32)[None, :] < kept[:, None]
            present = jnp.ones((cap,), dtype=jnp.uint32)
            lanes = [
                pack_bits(
                    present, [batch.cols[c].valid for c in names[at:at + 31]]
                )
                for at in range(0, max(len(names), 1), 31)
            ]
            span, apart = {}, {}
            for c in names:
                limbs = to_lanes(batch.cols[c].data)
                if limbs is None:
                    apart[c] = jnp.where(filled, batch.cols[c].data[row], 0)
                else:
                    span[c] = (len(lanes), len(lanes) + len(limbs))
                    lanes += limbs
            send = jnp.where(filled, jnp.stack(lanes)[:, row], 0)
        with jax.named_scope("all-to-all"):
            got = jax.lax.all_to_all(send, axis, 1, 1).reshape(-1, n * B)
            rv = (got[0] & 1) != 0
            new_cols = {}
            for i, name in enumerate(names):
                if name in apart:
                    d = jax.lax.all_to_all(apart[name], axis, 0, 0).reshape(n * B)
                else:
                    lo, hi = span[name]
                    d = from_lanes(
                        [got[k] for k in range(lo, hi)],
                        batch.cols[name].data.dtype,
                    )
                v = ((got[i // 31] >> (1 + i % 31)) & 1) != 0
                new_cols[name] = DevCol(d, v)
    return Batch(new_cols, rv), dropped, need


def broadcast_gather(batch: Batch, axis: str = "d") -> Batch:
    """Broadcast exchange: every device receives all rows (for small
    build sides of joins — the reference's Broadcast ExchangeType)."""

    from tidb_tpu.utils.failpoint import inject

    inject("exchange/gather")

    def gather(arr: jax.Array) -> jax.Array:
        g = jax.lax.all_gather(arr, axis)  # [n, cap]
        return g.reshape(-1)

    n = jax.lax.axis_size(axis)
    rows = jax.lax.psum(jnp.sum(batch.row_valid.astype(jnp.int64)), axis)
    _note_exchange("broadcast", batch, rows, (n - 1, 1))
    with jax.named_scope("broadcast"), jax.named_scope("all-gather"):
        cols = {
            name: DevCol(gather(c.data), gather(c.valid))
            for name, c in batch.cols.items()
        }
        return Batch(cols, gather(batch.row_valid))
