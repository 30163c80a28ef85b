"""Streamed (paged) aggregation: the spill analog.

Reference: the engine never requires a table to fit one buffer — blocking
operators spill to disk (pkg/executor/aggregate/agg_spill.go, sortexec
spill, pkg/util/paging/paging.go progressive paging). On TPU the scarce
resource is HBM and the staging medium is host RAM: when an aggregation's
input table exceeds the device tile budget, the pre-aggregation pipeline
(scan -> filter -> project) runs CHUNK BY CHUNK on device, each chunk is
partially aggregated (the same partial/final split the mesh path uses
across devices — here applied across time), only the tiny partial group
rows accumulate on device, and one final aggregation merges them.

The streamed Aggregate's result is injected back into the plan as a
Staged node, and the remainder of the plan (HAVING / ORDER BY / joins
above the aggregate) executes normally — so any plan shape whose large
table feeds an aggregation benefits, not just bare GROUP BY queries.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.chunk import Batch, DevCol, HostBlock, block_to_batch, pad_capacity
from tidb_tpu.executor.aggregate import (
    WIDTH_STALE,
    AggDesc,
    _next_pow2,
    group_aggregate,
)
from tidb_tpu.parallel.fragment import (
    _partial_descs,
    apply_post_avg,
    build_final_stage,
)
from tidb_tpu.planner import logical as L

_STAGED_NONCE = [0]


def _collect_pipeline_scans(p, scans, flags, chunkable=True) -> bool:
    """Walk a streaming pipeline (Selection/Projection chains over Scans
    composed with equi-joins) collecting (scan, chunkable) pairs.

    A scan is CHUNKABLE when splitting it into row chunks and unioning
    the per-chunk pipeline outputs equals running the whole pipeline:
    either side of an inner join distributes over row-union, but only
    the probe (left) side of left/semi/anti/mark joins does — chunking
    the build side would change unmatched-row semantics per chunk.
    Returns False when the subtree contains anything else (the shape
    doesn't stream)."""
    while isinstance(p, (L.Selection, L.Projection)):
        p = p.child
    if isinstance(p, L.Staged):
        # an already-staged (streamed lower aggregate) result: resident
        # and closed over by the compiled pipeline — a valid leaf, never
        # the chunked side. Lets a SECOND aggregate above a staged one
        # stream too (Q18's outer GROUP BY over the HAVING subquery).
        return True
    if isinstance(p, L.Scan):
        if p.frag is not None:
            # cross-host fragment slices (fragmenter.py) pin their row
            # numbering to the whole-plan fetch path; the streamed
            # re-chunkers don't know the slice and would scan full tables
            return False
        scans.append(p)
        flags.append(chunkable)
        return True
    if isinstance(p, L.JoinPlan):
        if p.kind == "inner":
            return _collect_pipeline_scans(
                p.left, scans, flags, chunkable
            ) and _collect_pipeline_scans(p.right, scans, flags, chunkable)
        if p.kind in ("left", "semi", "anti", "mark"):
            return _collect_pipeline_scans(
                p.left, scans, flags, chunkable
            ) and _collect_pipeline_scans(p.right, scans, flags, False)
    return False


def _pipeline_below(plan) -> Optional[Tuple[L.Aggregate, list, list]]:
    """Find the lowest Aggregate whose input subtree is a streaming
    pipeline. Returns (agg_node, scans, chunkable_flags) or None."""
    found = None

    def walk(p):
        nonlocal found
        for c in _children(p):
            walk(c)
        if found is None and isinstance(p, L.Aggregate):
            scans, flags = [], []
            if _collect_pipeline_scans(p.child, scans, flags) and scans:
                found = (p, scans, flags)

    walk(plan)
    return found


def _pick_big_scan(executor, scans, flags):
    """(index, (table, version) list) of the largest chunkable scan."""
    resolved = [executor._resolve(s.db, s.table) for s in scans]
    big_i = None
    for i, ok in enumerate(flags):
        if ok and (
            big_i is None or resolved[i][0].nrows > resolved[big_i][0].nrows
        ):
            big_i = i
    return big_i, resolved


def _stream_sizing(executor, scans, resolved, big_i, threshold, force=False):
    """(chunk_rows, should_stream, ctx): budget math shared by the agg
    and sort streaming paths. Auto mode streams when the whole working
    set (big scan + resident sides, times an intermediates multiplier)
    overruns the device budget, and sizes chunks from the budget
    REMAINING after the resident sides. Explicit thresholds chunk at
    that row count. `ctx` carries the computed (budget, others_bytes,
    rb) so callers (the device-resident gate) never re-derive them.
    force: stream even when this aggregate's own working set fits —
    the quota-admission retry path, where the WHOLE plan (join tiles
    above this aggregate) blew the budget."""
    t, v = resolved[big_i]
    big = scans[big_i]
    budget = _device_budget()
    # the admission quota (tidb_mem_quota_query) caps the working set
    # below physical memory: streaming must engage at the quota, not at
    # HBM exhaustion, or small-quota queries die at admission instead
    # of spilling (reference: spill triggers on the memory tracker's
    # quota, pkg/executor/aggregate/agg_spill.go)
    q = getattr(executor, "quota_bytes", None)
    if q:
        budget = min(budget, int(q))
    rb = _row_bytes(t, v, big.columns)
    others_bytes = sum(
        ot.nrows * _row_bytes(ot, ov, s.columns)
        for i, (s, (ot, ov)) in enumerate(zip(scans, resolved))
        if i != big_i
    )
    ctx = {"budget": budget, "others_bytes": others_bytes, "rb": rb}
    if others_bytes * 4 > budget:
        # resident join sides don't fit: run unpaged
        return None, False, ctx
    # intermediates multiplier: a single-scan plan (scan->filter->proj->
    # agg, no join sides) keeps only a couple of row-width temporaries
    # live in the fused program. Join plans materialize gathered
    # columns per probe stage — a deep chain (TPC-H Q5's 6-way) peaks
    # far above 4x: the round-5 hardware run at est. 10.6GB against a
    # 13.6GB budget crashed the TPU worker, so join plans hold 6x and
    # stream (device-resident when the raw columns fit) instead of
    # gambling the whole worker on resident execution
    mult = 2 if others_bytes == 0 and len(scans) == 1 else 6
    if threshold == -1 or force:
        if not force and (t.nrows * rb + others_bytes) * mult <= budget:
            return None, False, ctx
        avail = max(budget - 4 * others_bytes, budget // 8)
        chunk_rows = max(1 << 14, min(1 << 24, _pow2_floor(avail // (4 * rb))))
        if force and chunk_rows * rb * 4 > budget:
            # even one minimal chunk overruns the quota: streaming
            # cannot save this query — let admission's rejection stand
            return None, False, ctx
    else:
        if t.nrows <= threshold:
            return None, False, ctx
        chunk_rows = max(int(threshold), 1)
    return chunk_rows, True, ctx


def _fetch_resident(executor, site, st, sv):
    """One resident (non-chunked) site's device batch, honoring PK-range
    pushdown like PhysicalExecutor._fetch_inputs."""
    from tidb_tpu.storage import scan_table

    from tidb_tpu.planner.physical import fetch_site_rows

    narrowed = fetch_site_rows(st, site, sv)
    if narrowed is not None:
        return narrowed
    batch, _d = scan_table(
        st, site.columns, version=sv, partitions=site.partitions
    )
    return batch


def _expr_column_refs(e, out) -> None:
    """Collect ColumnRef names from an expression tree."""
    from tidb_tpu.expression.expr import ColumnRef

    if isinstance(e, ColumnRef):
        out.add(e.name)
        return
    if dataclasses.is_dataclass(e):
        for f in dataclasses.fields(e):
            val = getattr(e, f.name)
            for item in val if isinstance(val, (list, tuple)) else [val]:
                if dataclasses.is_dataclass(item):
                    _expr_column_refs(item, out)


def _children(p):
    out = []
    for attr in ("child", "left", "right"):
        c = getattr(p, attr, None)
        if c is not None:
            out.append(c)
    out.extend(getattr(p, "children", []) or [])
    return out


def _replace_node(plan, target, repl):
    if plan is target:
        return repl
    kw = {}
    for attr in ("child", "left", "right"):
        c = getattr(plan, attr, None)
        if c is not None:
            kw[attr] = _replace_node(c, target, repl)
    ch = getattr(plan, "children", None)
    if ch:
        kw["children"] = [_replace_node(c, target, repl) for c in ch]
    if not kw:
        return plan
    return dataclasses.replace(plan, **kw)


def _chunk_blocks(table, version, columns, chunk_rows: int, partitions=None):
    """Yield HostBlocks of <= chunk_rows rows over the table's blocks
    (numpy views — no copies until device transfer)."""
    for b in table.blocks(version, partitions=partitions):
        n = b.nrows
        for a in range(0, n, chunk_rows):
            z = min(a + chunk_rows, n)
            cols = {
                name: dataclasses.replace(
                    c, data=c.data[a:z], valid=c.valid[a:z]
                )
                for name, c in b.columns.items()
                if name in columns
            }
            yield HostBlock(cols, z - a)


# HBM per chip by device_kind, for a runtime that reports no
# bytes_limit. Budgeted at 85% of physical to leave runtime headroom.
_HBM_BY_KIND = {
    "TPU v5 lite": 16 << 30,   # v5e (one core per chip)
    "TPU v4": 32 << 30,        # megacore: one device per chip
    "TPU v4 lite": 8 << 30,    # v4i
    # v2/v3 expose each CORE as a device with half the chip's HBM
    "TPU v3": 16 << 30,
    "TPU v2": 8 << 30,
}


def _device_budget() -> int:
    """Device memory available for one query's working set. On a TPU
    the runtime's own bytes_limit; a runtime that reports none is
    looked up by device_kind, and a kind this table does not know is an
    error — guessing a size either forces resident data onto the
    streamed path or admits a working set the chip cannot hold. The CPU
    backend (tests) stages through host RAM past a fixed 4GB budget."""
    from tidb_tpu.utils.backend import is_tpu

    if not is_tpu():
        return 4 << 30
    d = jax.local_devices()[0]
    ms = d.memory_stats()
    if ms and ms.get("bytes_limit"):
        return int(ms["bytes_limit"])
    if d.device_kind not in _HBM_BY_KIND:
        raise RuntimeError(
            f"device reports no memory limit and its kind "
            f"{d.device_kind!r} is not in streamed._HBM_BY_KIND"
        )
    return int(_HBM_BY_KIND[d.device_kind] * 0.85)


def _row_bytes(table, version, columns) -> int:
    """Estimated device bytes per scanned row (data + validity mask)."""
    total = 0
    for b in table.blocks(version):
        for name in columns:
            c = b.columns.get(name)
            total += (c.data.dtype.itemsize if c is not None else 8) + 1
        break
    return max(total, 9)


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


class _StreamPlan:
    """Cached compiled artifacts for one streamed plan: the pre-agg
    pipeline (which may contain joins: the big scan streams through in
    chunks while the other scans' batches stay device-resident) + agg
    descriptors, and jitted chunk/final programs keyed by the capacity
    vector so repeated executes and same-shape chunks reuse one XLA
    compilation."""

    def __init__(self, pipe_fn, dicts, big_site, other_sites, sized,
                 key_fns, key_names, key_widths, partial, final, nonnull=()):
        self.pipe_fn = pipe_fn
        self.dicts = dicts
        self.big_site = big_site
        self.other_sites = other_sites
        self.sized = list(sized)  # pipeline capacity-knob node ids (joins)
        self.nonnull = list(nonnull)
        self.key_fns = key_fns
        self.key_names = key_names
        self.key_widths = key_widths
        self.partial = partial
        self.final = final
        self.jits = {}
        self.caps = None  # sticky discovered pipeline capacities
        self.sig = None  # plan signature for the engine watch

    def chunk_step(self, cap: int, caps: dict):
        key = ("partial", cap, tuple(sorted(caps.items())))
        j = self.jits.get(key)
        if j is None:
            from tidb_tpu.expression.kernels import param_scope
            from tidb_tpu.obs.engine_watch import watched_jit

            frozen = dict(caps)

            def step(inputs, params, _cap=cap, _caps=frozen):
                with param_scope(params):
                    piped, needs = self.pipe_fn(inputs, _caps)
                    out, ng = group_aggregate(
                        piped, self.key_fns, self.partial, _cap,
                        self.key_names, key_widths=self.key_widths,
                    )
                return out, ng, needs

            j = self.jits[key] = watched_jit(
                step, sig=("stream-partial", self.sig)
            )
        return j

    def final_step(self, fcap: int):
        j = self.jits.get(("final", fcap))
        if j is None:
            from tidb_tpu.obs.engine_watch import watched_jit

            fkeys, fdescs, post_avg = build_final_stage(
                self.key_names, self.final
            )

            def step(combined, _cap=fcap, _keys=fkeys, _descs=fdescs):
                return group_aggregate(
                    combined, _keys, _descs, _cap, self.key_names,
                    key_widths=self.key_widths,
                )

            j = self.jits[("final", fcap)] = (
                watched_jit(step, sig=("stream-final", self.sig)), post_avg
            )
        return j


def _stream_plan(executor, plan, agg, big_scan, conservative=False):
    from tidb_tpu.planner.physical import PlanCompiler, build_agg_parts

    cache = getattr(executor, "_stream_plans", None)
    if cache is None:
        cache = executor._stream_plans = {}
    # the big-scan identity is part of the key: table growth can flip
    # which scan streams, and a stale entry would pin/load the wrong one
    key = (
        executor._cache_key(plan),
        (big_scan.db, big_scan.table, big_scan.alias),
        conservative,
    )
    if key in cache:
        return cache[key]
    while len(cache) >= 32:
        cache.pop(next(iter(cache)))
    # compile the pre-aggregation pipeline once; the big scan's site is
    # fed one chunk at a time, every other site its full batch
    comp = PlanCompiler(
        executor.catalog, resolver=executor._resolve,
        conservative=conservative,
    )
    pipe_fn, dicts = comp._build(agg.child)
    entry = None
    big_site = next(
        (
            s
            for s in comp.scans
            if (s.db, s.table, s.alias)
            == (big_scan.db, big_scan.table, big_scan.alias)
        ),
        None,
    )
    if big_site is not None and big_site.pk_range is None:
        others = [s for s in comp.scans if s is not big_site]
        key_fns, key_names, key_widths, descs = build_agg_parts(agg, dicts)
        if not any(a.distinct for a in descs):
            # DISTINCT can't be split into partial sums across chunks
            # (dedup must see all rows of a group at once): run unpaged
            partial, final = _partial_descs(descs)
            entry = _StreamPlan(
                pipe_fn, dicts, big_site, others, comp.sized,
                key_fns, key_names, key_widths,
                partial, final, nonnull=comp.nonnull,
            )
            entry.sig = (executor.watch_sig(key[0]), key[1])
    cache[key] = entry
    return entry


def try_streamed(
    executor, plan, conservative=False, force=False
) -> Optional[Tuple[Batch, dict]]:
    """Execute `plan` with a streamed aggregate when it qualifies:
    single-device, lowest Aggregate over a streaming pipeline
    (Selection/Projection chains + equi-joins over scans), with the
    largest chunkable table too big for the device. The big scan streams
    through the whole pipeline (including joins against the resident
    small sides) chunk by chunk — the TPU analog of the reference's
    spill-to-disk join/agg executors. stream_rows: -1 = auto (stream
    when the working set overruns the device memory budget), >0 =
    explicit row threshold, 0/None = never stream."""
    threshold = getattr(executor, "stream_rows", None)
    if not threshold or executor.mesh is not None:
        return None
    m = _pipeline_below(plan)
    if m is None:
        return None
    agg, scans, flags = m

    # the streamed scan: largest chunkable table
    big_i, resolved = _pick_big_scan(executor, scans, flags)
    if big_i is None:
        return None
    big_scan = scans[big_i]
    t, v = resolved[big_i]
    chunk_rows, should, sizing = _stream_sizing(
        executor, scans, resolved, big_i, threshold, force=force
    )
    if not should:
        return None

    from tidb_tpu.planner.physical import StaleWidthsError, agg_out_dicts
    from tidb_tpu.utils.failpoint import inject

    inject("executor/stream-start")
    sp = _stream_plan(executor, plan, agg, big_scan, conservative=conservative)
    if sp is None:
        return None
    key_fns, key_names, key_widths, dicts = (
        sp.key_fns, sp.key_names, sp.key_widths, sp.dicts
    )

    # pin one snapshot of every scanned table for the whole statement
    pins = []
    try:
        site_tables = {}
        for s in [sp.big_site] + sp.other_sites:
            st, sv = executor._resolve(s.db, s.table)
            for _ in range(8):
                if st.pin_verified(sv):
                    break
                st, sv = executor._resolve(s.db, s.table)
            else:
                return None  # snapshot churned away repeatedly: unpaged
            pins.append((st, sv))
            site_tables[s.node_id] = (st, sv)
        t, v = site_tables[sp.big_site.node_id]
        # NULL-free folding assumptions must hold at the pinned versions
        for nid, coln in sp.nonnull:
            st, sv = site_tables.get(nid, (None, None))
            if st is not None and st.col_has_nulls(coln, sv):
                raise StaleWidthsError()
        # resident small-side batches, fetched once (device-cached)
        inputs_base = {}
        for s in sp.other_sites:
            st, sv = site_tables[s.node_id]
            inputs_base[s.node_id] = _fetch_resident(executor, s, st, sv)

        # one fixed tile for every chunk: all chunks share one compiled
        # program (the last, shorter chunk pads up to the same tile)
        chunk_tile = pad_capacity(chunk_rows)

        # device-resident streaming: when the big table's RAW columns
        # fit comfortably in the budget but the per-chunk pipeline's
        # intermediates are what forced streaming, transfer the table
        # ONCE (through the scan cache — repeats re-use it) and slice
        # chunk windows on device. Streaming then bounds COMPUTE
        # intermediates without re-paying host->device per execute.
        # The reference's paging equally re-reads from the store, not
        # from the client (pkg/store/copr paging). A small admission
        # quota caps sizing's budget, so quota-forced streaming keeps
        # chunking from host — the quota's purpose.
        big_bytes = t.nrows * sizing["rb"]
        device_resident = (
            big_bytes * 2.5 + sizing["others_bytes"] * 4
            <= sizing["budget"]
        )

        def feeds():
            if device_resident:
                from tidb_tpu.storage import scan_table

                full, _fd = scan_table(
                    t, big_scan.columns, version=v,
                    partitions=sp.big_site.partitions,
                )
                cap = full.capacity
                for a in range(0, cap, chunk_tile):
                    inject("executor/stream-chunk")
                    inject("executor/stream-chunk-device")
                    z = min(a + chunk_tile, cap)
                    pad = chunk_tile - (z - a)
                    cols = {}
                    for name, c in full.cols.items():
                        d, vl = c.data[a:z], c.valid[a:z]
                        if pad:
                            d = jnp.pad(d, (0, pad))
                            vl = jnp.pad(vl, (0, pad))
                        cols[name] = DevCol(d, vl)
                    rv = full.row_valid[a:z]
                    if pad:
                        rv = jnp.pad(rv, (0, pad))
                    inputs = dict(inputs_base)
                    inputs[sp.big_site.node_id] = Batch(cols, rv)
                    yield inputs
                return
            for hb in _chunk_blocks(
                t, v, sp.big_site.columns, chunk_rows,
                partitions=sp.big_site.partitions,
            ):
                inject("executor/stream-chunk")
                chunk = block_to_batch(hb, capacity=chunk_tile)
                inputs = dict(inputs_base)
                inputs[sp.big_site.node_id] = chunk
                yield inputs

        partial_batches, cap = _drain_partials(
            executor, sp, feeds(), key_fns, default_tile=chunk_tile
        )
    finally:
        for pt, pv in pins:
            pt.unpin(pv)

    return _finalize_partials(
        executor, plan, agg, sp, partial_batches, cap, dicts, key_fns
    )


def _drain_partials(executor, sp, feeds, key_fns, default_tile):
    """Run the compiled pipeline + partial aggregation over each input
    feed (one chunk or one hash partition), growing capacity knobs on
    overflow exactly like the discovery loop. Returns (partial batches,
    final partial-table cap)."""
    from tidb_tpu.planner.physical import StaleWidthsError

    cap = 1024
    caps = dict(sp.caps) if sp.caps else {
        nid: default_tile for nid in sp.sized
    }
    partial_batches: List[Batch] = []
    for inputs in feeds:
        if executor.kill_check is not None:
            executor.kill_check()
        for _retry in range(24):
            out, ng, needs = sp.chunk_step(cap, caps)(
                inputs, executor._params()
            )
            got = jax.device_get((ng, needs))
            ngi = int(got[0])
            if ngi >= WIDTH_STALE:
                raise StaleWidthsError()
            bumped = False
            for nid, n in got[1].items():
                n = int(n)
                if n >= WIDTH_STALE:
                    raise StaleWidthsError()
                if nid in caps and n > caps[nid]:
                    caps[nid] = pad_capacity(n, floor=16, pow2=True)
                    bumped = True
            if bumped:
                continue
            # overflow whenever the true group count exceeds the
            # batch the kernel emitted (tile size differs by path:
            # 2x cap for hash tables, 1x for dense compaction)
            if key_fns and ngi > out.capacity:
                cap = cap * 2  # partial table overflowed: retry bigger
                continue
            break
        else:
            raise StaleWidthsError()  # capacities never converged
        partial_batches.append(out)
    sp.caps = dict(caps)  # discovered capacities stick for reuse
    return partial_batches, cap


def _finalize_partials(
    executor, plan, agg, sp, partial_batches, cap, dicts, key_fns
):
    """Merge partial aggregates into the final stage, inject the result
    as a Staged node, and run the remainder of the plan."""
    from tidb_tpu.planner.physical import StaleWidthsError, agg_out_dicts

    combined = _concat_batches(partial_batches)

    # final merge: shared with the mesh path's final stage (fragment.py)
    fcap = max(cap, 1024)
    while True:
        jfin, post_avg = sp.final_step(fcap)
        fin, ng = jfin(combined)
        ngi = int(jax.device_get(ng))
        if ngi >= WIDTH_STALE:
            raise StaleWidthsError()
        if sp.key_names and ngi > fin.capacity:
            fcap *= 2
            continue
        break

    cols = apply_post_avg(dict(fin.cols), post_avg)
    result = Batch(
        {n: cols[n] for n in [c.internal for c in agg.schema]}, fin.row_valid
    )

    if not key_fns:
        # scalar aggregate over possibly-empty input: ensure one row
        # (COUNT=0, others NULL) like the in-plan aggregation node
        any_group = jnp.any(result.row_valid)
        first = jnp.zeros(result.capacity, dtype=bool).at[0].set(True)
        rv = jnp.where(any_group, result.row_valid, first)
        cols2 = {}
        agg_funcs = {n: f for n, f, _a, _d in agg.aggs}
        for n, c in result.cols.items():
            if agg_funcs.get(n) == "count":
                cols2[n] = DevCol(
                    jnp.where(any_group, c.data, jnp.zeros_like(c.data)),
                    jnp.where(any_group, c.valid, first),
                )
            else:
                cols2[n] = DevCol(
                    c.data, jnp.where(any_group, c.valid, jnp.zeros_like(c.valid))
                )
        result = Batch(cols2, rv)

    _STAGED_NONCE[0] += 1
    staged = L.Staged(
        agg.schema,
        batch=result,
        dicts=agg_out_dicts(agg, dicts),
        nonce=_STAGED_NONCE[0],
    )
    if plan is agg:
        new_plan = staged
    else:
        new_plan = _replace_node(plan, agg, staged)
    return executor.run(new_plan)


def _trace_col(p, name: str):
    """Descend Selection/Projection/Join chains to the Scan producing
    internal column `name`; returns (scan, bare column) or None (the
    column is computed, not a bare scan column)."""
    from tidb_tpu.expression.expr import ColumnRef

    while True:
        if isinstance(p, L.Selection):
            p = p.child
            continue
        if isinstance(p, L.Projection):
            m = dict(p.exprs)
            e = m.get(name)
            if e is None:
                if p.additive:
                    p = p.child
                    continue
                return None
            if isinstance(e, ColumnRef):
                name = e.name
                p = p.child
                continue
            return None
        if isinstance(p, L.Scan):
            pref = p.alias + "."
            if name.startswith(pref) and name[len(pref):] in p.columns:
                return p, name[len(pref):]
            return None
        if isinstance(p, L.JoinPlan):
            hit = _trace_col(p.left, name)
            return hit if hit is not None else _trace_col(p.right, name)
        return None


def _derive_partition_cols(p, big_aliases: set, out: dict) -> bool:
    """Walk the join tree assigning one hash-partition column to every
    big scan via the equi keys of joins whose BOTH subtrees hold big
    scans (the grace-hash co-partitioning condition). Returns False when
    any such join cannot be co-partitioned (non-equi, null-aware NOT IN,
    or a key that does not trace to a bare big-scan column)."""
    from tidb_tpu.expression.expr import ColumnRef

    def walk(p) -> Optional[set]:
        if isinstance(p, (L.Selection, L.Projection)):
            return walk(p.child)
        if isinstance(p, L.Scan):
            return {p.alias} if p.alias in big_aliases else set()
        if isinstance(p, L.Staged):
            return set()
        if isinstance(p, L.JoinPlan):
            lb = walk(p.left)
            rb = walk(p.right)
            if lb is None or rb is None:
                return None
            if lb and rb:
                if (
                    p.null_aware
                    or not p.equi_keys
                    or p.kind not in ("inner", "left", "semi", "anti", "mark")
                ):
                    return None
                lk, rk = p.equi_keys[0]
                if not (
                    isinstance(lk, ColumnRef) and isinstance(rk, ColumnRef)
                ):
                    return None
                for key, side, bigs in ((lk, p.left, lb), (rk, p.right, rb)):
                    hit = _trace_col(side, key.name)
                    if hit is None:
                        return None
                    scan, col = hit
                    if scan.alias not in big_aliases:
                        # the join key lives on a small scan while this
                        # subtree holds a DIFFERENT big one: that big is
                        # not co-partitioned by this join
                        return None
                    if out.get(scan.alias, col) != col:
                        return None  # conflicting partition columns
                    out[scan.alias] = col
            elif rb and not lb and p.kind in ("left", "anti", "mark"):
                # partitioned bigs ONLY on the build side while the
                # PRESERVED/probe side is resident (replicated to every
                # partition feed): an unmatched probe row would be
                # left-NULL/anti-emitted once PER FEED — duplicated
                # results. (inner/semi stay correct: a probe row's
                # matches all live in one partition; cross unions
                # cleanly; bigs-on-probe-side is fine for every kind.)
                return None
            return lb | rb
        return None

    return walk(p) is not None


def _partition_assignment(t, v, col: str, K: int, partitions=None):
    """Per-block (stable partition-sorted row order, K+1 slice starts,
    per-partition counts): ONE argsort pass per block yields every
    partition's row indices as a slice — gathering K partitions costs
    O(N log N) total, not K full scans. NULLs land in partition 0 (they
    never equi-match, and probe-side NULL rows must still appear exactly
    once)."""
    out = []
    for b in t.blocks(v, partitions=partitions):
        hc = b.columns.get(col)
        if hc is None:
            part = np.zeros(b.nrows, dtype=np.int64)
        else:
            vals = hc.data
            if np.issubdtype(vals.dtype, np.floating):
                v64 = vals.astype(np.float64, copy=True)
                v64[v64 == 0.0] = 0.0  # -0.0 equi-matches 0.0
                vals = v64.view(np.int64)
            h = vals.astype(np.uint64, copy=False) * np.uint64(
                0x9E3779B97F4A7C15
            )
            part = ((h >> np.uint64(33)) % np.uint64(K)).astype(np.int64)
            part[~hc.valid] = 0
        order = np.argsort(part, kind="stable")
        counts = np.bincount(part, minlength=K)
        starts = np.concatenate([[0], np.cumsum(counts)])
        out.append((order, starts, counts))
    return out


def _gather_partition(t, v, columns, assign, k, partitions=None) -> HostBlock:
    """One hash partition of a table as a single HostBlock (slicing the
    precomputed partition-sorted order)."""
    cols: dict = {c: ([], []) for c in columns}
    dicts: dict = {}
    n = 0
    for b, (order, starts, _counts) in zip(
        t.blocks(v, partitions=partitions), assign
    ):
        idx = order[starts[k]:starts[k + 1]]
        n += len(idx)
        for c in columns:
            hc = b.columns.get(c)
            if hc is None:
                cols[c][0].append(np.zeros(len(idx), dtype=np.int64))
                cols[c][1].append(np.zeros(len(idx), dtype=bool))
            else:
                cols[c][0].append(hc.data[idx])
                cols[c][1].append(hc.valid[idx])
                if hc.dictionary is not None:
                    dicts[c] = hc.dictionary
    from tidb_tpu.chunk import HostColumn

    types = t.schema.types
    built = {
        c: HostColumn(
            types[c],
            np.concatenate(d) if d else np.zeros(0, dtype=np.int64),
            np.concatenate(vm) if vm else np.zeros(0, dtype=bool),
            dicts.get(c),
        )
        for c, (d, vm) in cols.items()
    }
    return HostBlock(built, n)


def try_partitioned(
    executor, plan, conservative=False, force=False
) -> Optional[Tuple[Batch, dict]]:
    """Grace-hash spill: when TWO OR MORE pipeline tables exceed the
    memory budget (lineitem self-joins in EXISTS chains, partsupp
    vs partsupp minima), hash-partition every big table on its equi-join
    key into K co-partitions, run the whole compiled pipeline + partial
    aggregation once per partition, and final-merge — the TPU analog of
    the reference's spill-to-disk partitioned hash join
    (pkg/executor/join hash_table spill). Single-big shapes use
    try_streamed (row chunking, no key requirement); this path needs
    key co-location, which row chunks cannot give the build side."""
    threshold = getattr(executor, "stream_rows", None)
    if not threshold or executor.mesh is not None:
        return None
    m = _pipeline_below(plan)
    if m is None:
        return None
    agg, scans, flags = m
    if any(s.alias is None for s in scans):
        return None
    budget = _device_budget()
    q = getattr(executor, "quota_bytes", None)
    if q:
        budget = min(budget, int(q))
    resolved = [executor._resolve(s.db, s.table) for s in scans]
    sizes = [
        t.nrows * _row_bytes(t, v, s.columns)
        for s, (t, v) in zip(scans, resolved)
    ]
    # auto mode: a table is "big" when its working set overruns the
    # budget. force mode (the unpaged plan ALREADY failed admission):
    # partition anything that meaningfully contributes, since join tiles
    # — not raw scan bytes — blew the budget
    bar = budget // 8 if force else budget // 4
    bigs = [i for i, sz in enumerate(sizes) if sz > bar]
    if len(bigs) < 2:
        return None  # zero/one big side: try_streamed's territory
    big_aliases = {scans[i].alias for i in bigs}
    partcols: dict = {}
    if not _derive_partition_cols(agg.child, big_aliases, partcols):
        return None
    if set(partcols) != big_aliases:
        return None  # some big scan never meets another big via a key
    # partition hashing happens on the RAW stored representation, so all
    # co-partitioned keys must share one representation:
    # - dictionary codes are per-table (self-joins share one dict; a
    #   cross-table string key would split equal values), and
    # - numeric keys must agree on (kind, scale): the compare kernels
    #   rescale decimal(10,2) vs decimal(10,4) to match, but raw scaled
    #   ints 500 vs 50000 hash apart.
    # Decline rather than silently drop matches.
    key_types = set()
    for i in bigs:
        t_i, _v_i = resolved[i]
        col = partcols[scans[i].alias]
        ty = t_i.schema.types[col]
        key_types.add((ty.kind, ty.scale))
        if (
            t_i.dictionaries.get(col) is not None
            and len({scans[j].table.lower() for j in bigs}) > 1
        ):
            return None
    if len(key_types) > 1:
        return None
    big_bytes = sum(sizes[i] for i in bigs)
    K = 2
    while K < 64 and (big_bytes * 4) // K > budget:
        K *= 2

    from tidb_tpu.planner.physical import StaleWidthsError
    from tidb_tpu.utils.failpoint import inject

    sp = _stream_plan(
        executor, plan, agg, scans[bigs[0]], conservative=conservative
    )
    if sp is None:
        return None
    all_sites = [sp.big_site] + sp.other_sites
    if any(
        s.pk_range is not None
        for s in all_sites
        if s.alias in partcols
    ):
        return None  # index-range pushdown on a partitioned site
    dicts, key_fns = sp.dicts, sp.key_fns

    pins = []
    try:
        site_tables = {}
        for s in all_sites:
            st, sv = executor._resolve(s.db, s.table)
            for _ in range(8):
                if st.pin_verified(sv):
                    break
                st, sv = executor._resolve(s.db, s.table)
            else:
                return None
            pins.append((st, sv))
            site_tables[s.node_id] = (st, sv)
        for nid, coln in sp.nonnull:
            st, sv = site_tables.get(nid, (None, None))
            if st is not None and st.col_has_nulls(coln, sv):
                raise StaleWidthsError()

        # per-site partition assignment + tile (max partition size)
        assigns = {}
        tiles = {}
        resident = {}
        part_bytes = 0
        for s in all_sites:
            st, sv = site_tables[s.node_id]
            if s.alias in partcols:
                a = _partition_assignment(
                    st, sv, partcols[s.alias], K, partitions=s.partitions
                )
                counts = np.zeros(K, dtype=np.int64)
                for _order, _starts, c in a:
                    counts += c
                assigns[s.node_id] = a
                tiles[s.node_id] = pad_capacity(int(counts.max()) or 1)
                part_bytes += tiles[s.node_id] * _row_bytes(
                    st, sv, s.columns
                )
        # key skew check: a hot key can put ~everything in one partition
        # — running that would silently defeat the quota; decline and
        # let admission's rejection (with its tracker report) stand
        if part_bytes * 4 > budget * 2:
            return None
        for s in all_sites:
            st, sv = site_tables[s.node_id]
            if s.alias not in partcols:
                resident[s.node_id] = _fetch_resident(executor, s, st, sv)

        inject("executor/partition-start")  # the path is committed

        def feeds():
            for k in range(K):
                inject("executor/partition-feed")
                inputs = dict(resident)
                for s in all_sites:
                    if s.node_id in assigns:
                        st, sv = site_tables[s.node_id]
                        hb = _gather_partition(
                            st, sv, s.columns, assigns[s.node_id], k,
                            partitions=s.partitions,
                        )
                        inputs[s.node_id] = block_to_batch(
                            hb, capacity=tiles[s.node_id]
                        )
                yield inputs

        partial_batches, cap = _drain_partials(
            executor, sp, feeds(), key_fns,
            default_tile=max(tiles.values()),
        )
    finally:
        for pt, pv in pins:
            pt.unpin(pv)

    return _finalize_partials(
        executor, plan, agg, sp, partial_batches, cap, dicts, key_fns
    )


class _SortStreamPlan:
    """Cached compiled artifacts for one streamed full ORDER BY: the
    chunked pipeline, its sort-key expressions, and jitted chunk
    programs — repeated executes reuse one XLA compilation, and the
    discovered capacity vector sticks across executes."""

    def __init__(self, pipe_fn, dicts, big_site, other_sites, sized,
                 key_fns, nonnull):
        self.pipe_fn = pipe_fn
        self.dicts = dicts
        self.big_site = big_site
        self.other_sites = other_sites
        self.sized = list(sized)
        self.key_fns = key_fns
        self.nonnull = list(nonnull)
        self.jits = {}
        self.caps = None
        self.sig = None  # plan signature for the engine watch


def _sort_stream_plan(executor, plan, sort, big_scan, conservative=False):
    from tidb_tpu.expression import compile_expr
    from tidb_tpu.planner.physical import PlanCompiler

    cache = getattr(executor, "_stream_plans", None)
    if cache is None:
        cache = executor._stream_plans = {}
    key = (
        executor._cache_key(plan),
        ("sort", big_scan.db, big_scan.table, big_scan.alias),
        conservative,
    )
    if key in cache:
        return cache[key]
    while len(cache) >= 32:
        cache.pop(next(iter(cache)))
    entry = None
    # compile the whole plan MINUS the Sort: projections above it apply
    # per chunk; the host merge only reorders rows. Sort keys must still
    # be computable on that pipeline's output — a pruning projection
    # above the Sort may have dropped a hidden ORDER BY column, in which
    # case this path declines (the in-device path still handles it).
    inner_plan = _replace_node(plan, sort, sort.child)
    schema_names = {c.internal for c in inner_plan.schema}
    refs = set()
    for e, _d in sort.keys:
        _expr_column_refs(e, refs)
    if refs <= schema_names:
        comp = PlanCompiler(
            executor.catalog, resolver=executor._resolve,
            conservative=conservative,
        )
        pipe_fn, dicts = comp._build(inner_plan)
        big_site = next(
            (
                s
                for s in comp.scans
                if (s.db, s.table, s.alias)
                == (big_scan.db, big_scan.table, big_scan.alias)
            ),
            None,
        )
        if big_site is not None and big_site.pk_range is None:
            key_fns = [compile_expr(e, dicts) for e, _ in sort.keys]
            entry = _SortStreamPlan(
                pipe_fn, dicts, big_site,
                [s for s in comp.scans if s is not big_site],
                comp.sized, key_fns, comp.nonnull,
            )
            entry.sig = (executor.watch_sig(key[0]), key[1])
    cache[key] = entry
    return entry


def try_streamed_sort(executor, plan, conservative=False):
    """Out-of-HBM full ORDER BY: when the ROOT of a plan is a Sort (with
    optional Projections above) over a streaming pipeline whose big scan
    exceeds the device budget, the pipeline runs chunk-by-chunk on
    device, each chunk's (pre-sorted) key+payload columns stage to host
    RAM, and the host merges the sorted runs into the final row order.
    Returns (column internal names, ordered numpy column dict, row
    count) or None. Reference: sortexec's disk-spill partitions + merge
    (pkg/executor/sortexec/sort_partition.go) — here HBM is the scarce
    buffer and host RAM the staging medium.

    LIMIT shapes never reach this path (the packed top-k keeps them
    in-device); this is for full-result sorts whose OUTPUT itself
    exceeds device memory, so rows are delivered host-side."""
    threshold = getattr(executor, "stream_rows", None)
    if not threshold or executor.mesh is not None:
        return None
    # peel Projections above the root Sort; the peeled projections apply
    # per chunk (inner_plan below), so sort keys referencing columns THEY
    # prune are checked against the pipeline schema before engaging
    node = plan
    while isinstance(node, L.Projection):
        node = node.child
    if not isinstance(node, L.Sort):
        return None
    sort = node
    scans, flags = [], []
    if not _collect_pipeline_scans(sort.child, scans, flags) or not scans:
        return None
    big_i, resolved = _pick_big_scan(executor, scans, flags)
    if big_i is None:
        return None
    big_scan = scans[big_i]
    chunk_rows, should, _sz = _stream_sizing(
        executor, scans, resolved, big_i, threshold
    )
    if not should:
        return None

    from tidb_tpu.planner.physical import StaleWidthsError
    from tidb_tpu.utils.failpoint import inject

    inject("executor/stream-sort")
    sp = _sort_stream_plan(
        executor, plan, sort, big_scan, conservative=conservative
    )
    if sp is None:
        return None
    big_site = sp.big_site
    key_descs = [d for _, d in sort.keys]
    out_names = [c.internal for c in plan.schema]

    pins = []
    try:
        site_tables = {}
        for s in [sp.big_site] + sp.other_sites:
            st, sv = executor._resolve(s.db, s.table)
            for _ in range(8):
                if st.pin_verified(sv):
                    break
                st, sv = executor._resolve(s.db, s.table)
            else:
                return None
            pins.append((st, sv))
            site_tables[s.node_id] = (st, sv)
        t, v = site_tables[big_site.node_id]
        for nid, coln in sp.nonnull:
            st, sv = site_tables.get(nid, (None, None))
            if st is not None and st.col_has_nulls(coln, sv):
                raise StaleWidthsError()
        inputs_base = {}
        for s in sp.other_sites:
            st, sv = site_tables[s.node_id]
            inputs_base[s.node_id] = _fetch_resident(executor, s, st, sv)

        chunk_tile = pad_capacity(chunk_rows)
        caps = dict(sp.caps) if sp.caps else {
            nid: chunk_tile for nid in sp.sized
        }
        host_runs = []  # per chunk: (row mask, key arrays, col arrays)

        def step_for(caps_t):
            j = sp.jits.get(caps_t)
            if j is None:
                from tidb_tpu.expression.kernels import param_scope
                from tidb_tpu.obs.engine_watch import watched_jit

                frozen = dict(caps)

                def step(inputs, params, _caps=frozen):
                    with param_scope(params):
                        b, needs = sp.pipe_fn(inputs, _caps)
                        keys = [f(b) for f in sp.key_fns]
                    return b, keys, needs

                j = sp.jits[caps_t] = watched_jit(
                    step, sig=("stream-sort-chunk", sp.sig)
                )
            return j

        for hb in _chunk_blocks(
            t, v, big_site.columns, chunk_rows,
            partitions=big_site.partitions,
        ):
            inject("executor/stream-chunk")
            if executor.kill_check is not None:
                executor.kill_check()
            chunk = block_to_batch(hb, capacity=chunk_tile)
            inputs = dict(inputs_base)
            inputs[big_site.node_id] = chunk
            for _retry in range(24):
                b, keys, needs = step_for(tuple(sorted(caps.items())))(
                    inputs, executor._params()
                )
                needs_host = jax.device_get(needs)
                bumped = False
                for nid, n in needs_host.items():
                    n = int(n)
                    if n >= WIDTH_STALE:
                        raise StaleWidthsError()
                    if nid in caps and n > caps[nid]:
                        caps[nid] = pad_capacity(n, floor=16, pow2=True)
                        bumped = True
                if not bumped:
                    break
            else:
                raise StaleWidthsError()
            # stage this chunk's valid rows to host RAM
            rv, kd, cd = jax.device_get(
                (
                    b.row_valid,
                    [(k.data, k.valid) for k in keys],
                    {
                        n: (b.cols[n].data, b.cols[n].valid)
                        for n in out_names
                    },
                )
            )
            host_runs.append((rv, kd, cd))
        sp.caps = dict(caps)  # discovered capacities stick for reuse
    finally:
        for pt, pv in pins:
            pt.unpin(pv)

    # host merge: stable lexsort over the staged runs (numpy's C sort —
    # the "disk merge" analog with host RAM as the spill medium)
    mask = np.concatenate([r[0] for r in host_runs])
    sort_cols = []
    for ki in range(len(sp.key_fns)):
        kdat = np.concatenate([r[1][ki][0] for r in host_runs])[mask]
        kval = np.concatenate([r[1][ki][1] for r in host_runs])[mask]
        sort_cols.append((kdat, kval))
    order = np.arange(int(mask.sum()))
    # np.lexsort sorts by its LAST array first: build
    # [val_kN, rank_kN, ..., val_k0, rank_k0] so key 0's NULL-rank is
    # most significant, then key 0's value, then key 1... Each key gets
    # an explicit NULL-rank array (MySQL: NULLs first asc, last desc) —
    # no in-band sentinel values that could collide with real data.
    lex = []
    for (kdat, kval), desc in zip(sort_cols, key_descs):
        if desc:
            rank = np.where(kval, 0, 1)  # NULLs last
            val = -kdat.astype(np.float64) if np.issubdtype(
                kdat.dtype, np.floating
            ) else -kdat.astype(np.int64)
        else:
            rank = np.where(kval, 1, 0)  # NULLs first
            val = kdat
        val = np.where(kval, val, 0)
        lex = [val, rank] + lex
    if lex:
        order = np.lexsort(lex)
    cols = {}
    for n in out_names:
        dat = np.concatenate([r[2][n][0] for r in host_runs])[mask][order]
        val = np.concatenate([r[2][n][1] for r in host_runs])[mask][order]
        cols[n] = (dat, val)
    return out_names, cols, int(mask.sum()), sp.dicts


def _concat_batches(batches: List[Batch]) -> Batch:
    if len(batches) == 1:
        return batches[0]
    names = list(batches[0].cols)
    cols = {}
    for n in names:
        cols[n] = DevCol(
            jnp.concatenate([b.cols[n].data for b in batches]),
            jnp.concatenate([b.cols[n].valid for b in batches]),
        )
    rv = jnp.concatenate([b.row_valid for b in batches])
    return Batch(cols, rv)
