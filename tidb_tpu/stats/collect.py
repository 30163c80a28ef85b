"""Statistics collection on device.

Reference: pkg/statistics — equal-depth Histogram (histogram.go:51),
TopN + CMSketch (cmsketch.go:536,54), FMSketch NDV (fmsketch.go:55),
collected by ANALYZE pushdown (ReqTypeAnalyze). On TPU the whole column
is resident, so exact computation replaces sketching: one lax.sort gives
NDV (change flags), the equal-depth histogram (quantile bounds) and TopN
(segment counts + top_k) in a single fused program. Sampling-based
collectors (row_sampler.go) become unnecessary below HBM scale; chunked
variants are the planned path for >HBM tables.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.dtypes import Kind
from tidb_tpu.storage.scan import scan_table

N_BUCKETS = 64
N_TOPN = 16

#: above this many rows ANALYZE samples instead of sorting the full
#: column — the reference's row_sampler.go sampling regime; exact stats
#: below it. One device sort of the full column per column is fine at
#: millions of rows but superlinear pain at SF10+ (23 columns x 64M
#: sorts measured ~19min on the CPU fallback).
SAMPLE_CAP = int(os.environ.get("TIDB_TPU_ANALYZE_SAMPLE", str(2 << 20)))


@dataclasses.dataclass
class ColumnStats:
    row_count: int
    null_count: int
    ndv: int
    # equal-depth histogram: upper bounds per bucket + per-bucket count
    bounds: np.ndarray
    bucket_counts: np.ndarray
    topn: List[Tuple[object, int]]  # decoded (value, count)
    min_val: Optional[object] = None
    max_val: Optional[object] = None

    def selectivity_eq(self) -> float:
        """Average rows per distinct value / total (reference
        cardinality.selectivity baseline 1/NDV)."""
        if self.ndv <= 0:
            return 1.0
        return 1.0 / self.ndv


#: the smallest tile the statistics kernel runs on. A cold server
#: compiled one kernel a (table size, storage dtype): 16 programs of
#: about 10 s each on the v5e for TPC-H's eight tables, the largest
#: single part of every first run's set-up (PERF.md section 7). Columns
#: are widened to int64 and padded with invalid rows to the power of two
#: at or over their rows, at least this many: three programs at SF1.
_STATS_FLOOR = 1 << 18


@functools.partial(jax.jit, static_argnums=3)
def _padded(data, valid, row_valid, cap: int):
    if not jnp.issubdtype(data.dtype, jnp.floating):
        data = data.astype(jnp.int64)
    pad = ((0, cap - data.shape[0]),)
    return jnp.pad(data, pad), jnp.pad(valid, pad), jnp.pad(row_valid, pad)


def _stats_input(data, valid, row_valid):
    """The kernel's arguments in the few shapes it is compiled for."""
    n = data.shape[0]
    cap = max(_STATS_FLOOR, 1 << max(n - 1, 0).bit_length())
    return _padded(data, valid, row_valid, cap)


@jax.jit
def _column_stats_kernel(data, valid, row_valid):
    cap = data.shape[0]
    ok = valid & row_valid
    nulls = jnp.sum((row_valid & ~valid).astype(jnp.int64))
    count = jnp.sum(ok.astype(jnp.int64))
    big = jnp.iinfo(jnp.int64).max if not jnp.issubdtype(data.dtype, jnp.floating) else jnp.inf
    key = jnp.where(ok, data.astype(jnp.float64) if jnp.issubdtype(data.dtype, jnp.floating) else data.astype(jnp.int64), big)
    s = jax.lax.sort([key], is_stable=False)[0]
    # distinct change flags among valid prefix
    idx = jnp.arange(cap)
    is_valid_pos = idx < count
    changed = (s != jnp.roll(s, 1)) | (idx == 0)
    ndv = jnp.sum((changed & is_valid_pos).astype(jnp.int64))
    # equal-depth bounds: value at ceil((b+1)*count/N)-1
    pos = jnp.clip((jnp.arange(N_BUCKETS) + 1) * count // N_BUCKETS - 1, 0, cap - 1)
    bounds = s[pos]
    bcounts = jnp.diff(jnp.concatenate([jnp.zeros(1, jnp.int64), (jnp.arange(N_BUCKETS) + 1) * count // N_BUCKETS]))
    # top-N by frequency: segment ids over sorted values
    seg = jnp.cumsum(changed.astype(jnp.int64)) - 1
    seg = jnp.where(is_valid_pos, seg, cap)
    freq = jax.ops.segment_sum(is_valid_pos.astype(jnp.int64), seg.astype(jnp.int32), num_segments=cap + 1)[:cap]
    # singleton count: values seen exactly once — feeds the Haas-Stokes
    # NDV scale-up when these stats come from a sample
    f1 = jnp.sum((freq == 1).astype(jnp.int64))
    first_idx = (
        jnp.full(cap + 1, cap - 1, dtype=jnp.int32)
        .at[seg.astype(jnp.int32)]
        .min(jnp.arange(cap, dtype=jnp.int32), mode="drop")[:cap]
    )
    # counts fit int32 (cap < 2^31): the v5e compiler's time for a
    # top_k/sort follows the key's 32-bit limbs (executor/sortops.py)
    topf, topi = jax.lax.top_k(freq.astype(jnp.int32), N_TOPN)
    topf = topf.astype(jnp.int64)
    top_vals = s[first_idx[topi]]
    mn = s[0]
    mx = s[jnp.clip(count - 1, 0, cap - 1)]
    return nulls, count, ndv, bounds, bcounts, topf, top_vals, mn, mx, f1


def analyze_table(table, columns=None) -> Dict[str, ColumnStats]:
    """ANALYZE TABLE: exact per-column stats, stored on the table
    (reference: stats tables mysql.stats_histograms etc. via the stats
    handle, pkg/statistics/handle). `columns` restricts the pass (the
    DXF distributed-analyze subtask shape: one column per subtask)."""
    from tidb_tpu.utils.failpoint import inject

    inject("stats/analyze")
    if columns is not None and not columns:
        return dict(getattr(table, "stats", None) or {})  # nothing to do
    stats: Dict[str, ColumnStats] = {}
    # pin ONE version for the whole pass: a concurrent DELETE between
    # the nrows computation and a later column's concat would otherwise
    # shrink the arrays under sample_idx (IndexError), and a concurrent
    # INSERT would silently sample different physical rows per column
    version = table.pin_current()
    try:
        return _analyze_at_version(table, version, columns, stats)
    finally:
        table.unpin(version)


def _analyze_at_version(table, version, columns, stats):
    blocks = table.blocks(version)
    nrows = sum(b.nrows for b in blocks)
    sampled = nrows > SAMPLE_CAP
    if sampled:
        # one shared uniform sample of row positions across all columns
        # (deterministic per table version, so repeat ANALYZE agrees)
        rng = np.random.default_rng(
            (getattr(table, "uid", 0) * 1_000_003 + version) & 0x7FFFFFFF
        )
        sample_idx = np.sort(rng.choice(nrows, SAMPLE_CAP, replace=False))
        ratio = nrows / SAMPLE_CAP
    for name, typ in table.schema.columns:
        if columns is not None and name not in columns:
            continue
        if sampled:
            # gather ONLY the sampled rows per block (sample_idx is
            # sorted; split it into per-block ranges) — concatenating
            # whole columns first would copy O(total rows) per column
            # at exactly the scale that triggers sampling
            data_parts, valid_parts = [], []
            off = 0
            lo = 0
            for b in blocks:
                hi = np.searchsorted(sample_idx, off + b.nrows)
                local = sample_idx[lo:hi] - off
                hc = b.columns.get(name)
                if hc is None:
                    # block predates ALTER ADD COLUMN: reads see NULL
                    data_parts.append(np.zeros(len(local), dtype=np.int64))
                    valid_parts.append(np.zeros(len(local), dtype=bool))
                else:
                    data_parts.append(hc.data[local])
                    valid_parts.append(hc.valid[local])
                off += b.nrows
                lo = hi
            data_h = np.concatenate(data_parts)
            valid_h = np.concatenate(valid_parts)
            # decode through the PINNED blocks' dictionary, not the live
            # table dict: a concurrent append can grow-and-remap the
            # sorted dictionary, shifting the codes these blocks hold
            pinned_dict = next(
                (
                    b.columns[name].dictionary
                    for b in blocks
                    if name in b.columns
                    and b.columns[name].dictionary is not None
                ),
                None,
            )
            dicts = {name: pinned_dict} if pinned_dict is not None else {}
            nulls, count, ndv, bounds, bcounts, topf, top_vals, mn, mx, f1 = (
                _column_stats_kernel(*_stats_input(
                    data_h, valid_h, np.ones(len(data_h), dtype=bool)
                ))
            )
        else:
            batch, dicts = scan_table(table, [name], version=version)
            col = batch.cols[name]
            nulls, count, ndv, bounds, bcounts, topf, top_vals, mn, mx, f1 = (
                _column_stats_kernel(
                    *_stats_input(col.data, col.valid, batch.row_valid)
                )
            )
        count_i = int(count)
        dictionary = dicts.get(name)

        def decode(v):
            if count_i == 0:
                return None
            if typ.kind == Kind.STRING and dictionary is not None and len(dictionary):
                code = int(v)
                if 0 <= code < len(dictionary):
                    return str(dictionary[code])
                return None
            if typ.kind == Kind.DECIMAL:
                return int(v) / 10**typ.scale
            if typ.kind == Kind.FLOAT:
                return float(v)
            return int(v)

        if sampled:
            # scale sample counts to the table; NDV via first-order
            # Haas-Stokes: D = d + (N/n - 1) * f1, clamped to [d, N]
            # (reference estimator role: FMSketch/row sampling,
            # pkg/statistics/fmsketch.go + row_sampler.go)
            d = int(ndv)
            est_ndv = min(
                max(d, int(d + (ratio - 1.0) * int(f1))), nrows
            )
            topn = [
                (decode(v), int(round(int(f) * ratio)))
                for v, f in zip(np.asarray(top_vals), np.asarray(topf))
                if int(f) > 0
            ]
            stats[name] = ColumnStats(
                row_count=nrows,
                null_count=int(round(int(nulls) * ratio)),
                ndv=est_ndv,
                bounds=np.asarray(bounds),
                bucket_counts=(
                    np.asarray(bcounts).astype(np.float64) * ratio
                ).astype(np.int64),
                topn=topn,
                min_val=decode(mn),
                max_val=decode(mx),
            )
        else:
            topn = [
                (decode(v), int(f))
                for v, f in zip(np.asarray(top_vals), np.asarray(topf))
                if int(f) > 0
            ]
            stats[name] = ColumnStats(
                row_count=count_i + int(nulls),
                null_count=int(nulls),
                ndv=int(ndv),
                bounds=np.asarray(bounds),
                bucket_counts=np.asarray(bcounts),
                topn=topn,
                min_val=decode(mn),
                max_val=decode(mx),
            )
    # merge + publish under the table lock: concurrent per-column
    # analyze subtasks (DXF distributed analyze) must not lose each
    # other's columns in a read-modify-write race
    with table._lock:
        if columns is not None:
            merged = dict(getattr(table, "stats", None) or {})
            merged.update(stats)
            table.stats = merged
        else:
            table.stats = stats
        table.stats_version = version  # the version these stats reflect
        # reset the auto-analyze counter (manual ANALYZE counts too)
        table.analyzed_modify = getattr(table, "modify_count", 0)
    return table.stats
