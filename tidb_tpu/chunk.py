"""Columnar batches: host side (numpy) and device side (jax pytrees).

Reference: pkg/util/chunk — Apache Arrow-format Chunk (chunk.go:34) with
Column{nullBitmap, offsets, data} (column.go:63) and a sel vector. The TPU
design keeps the same information with static shapes:

- ``HostColumn``: numpy data + bool validity (+ sorted string dictionary).
- ``HostBlock``: a set of named HostColumns with a row count — the unit of
  storage (a table partition holds a list of blocks).
- ``DevCol`` / ``Batch``: jax pytrees. ``Batch.row_valid`` plays the role of
  the reference's sel vector: filters do not compact, they mask. Row
  capacity is padded to a fixed tile ladder so XLA compiles one program per
  (plan, shape bucket) — the analog of the reference's plan cache
  (pkg/planner/core/plan_cache.go:231) interacting with paging sizes
  (pkg/util/paging/paging.go:25).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.dtypes import Kind, SQLType

# Fixed tile ladder (rows). Mirrors the reference's paging growth
# 128 -> 50k (pkg/util/paging/paging.go:25-28) but with powers of two so a
# handful of compiled programs cover all sizes.
_MIN_CAPACITY = 256


def pad_capacity(n: int, floor: int = _MIN_CAPACITY, pow2: bool = False) -> int:
    """Smallest tile >= n on the engine's tiling ladder (>= floor).

    Batch tiles use half-steps (.., 2^k, 3*2^(k-1), 2^(k+1), ..): pure
    power-of-two padding wastes up to 50% of every full-array pass (TPC-H
    SF1 lineitem is 6.0M rows — 8.39M padded vs 6.29M with half-steps).
    pow2=True restricts to powers of two for sizes used as bitmask moduli
    (hash-table slot counts, exchange buckets)."""
    cap = floor
    while cap < n:
        half = cap + cap // 2
        if not pow2 and cap % 2 == 0 and half >= n:
            return half
        cap *= 2
    return cap


@dataclasses.dataclass
class HostColumn:
    """Numpy-backed column. ``dictionary`` is present iff type is STRING;
    it is sorted, so code order == binary collation order."""

    type: SQLType
    data: np.ndarray
    valid: np.ndarray
    dictionary: Optional[np.ndarray] = None  # np.array of str objects

    def __post_init__(self) -> None:
        assert self.data.shape == self.valid.shape

    def __len__(self) -> int:
        return len(self.data)

    def decode(self) -> np.ndarray:
        """Materialize logical values (object array with None for NULL).
        Vectorized — the reference streams chunks to the wire without a
        per-row interpreter (pkg/server/conn.go writeChunks:2286); a
        Python per-row loop here dominated large result sets."""
        n = len(self.data)
        out = np.empty(n, dtype=object)
        if self.type.kind == Kind.STRING:
            if self.dictionary is not None and len(self.dictionary):
                codes = np.clip(self.data, 0, len(self.dictionary) - 1)
                out[:] = self.dictionary[codes]
            else:
                out[:] = ""
        elif self.type.kind == Kind.DECIMAL:
            out[:] = (self.data / (10 ** self.type.scale)).tolist()
        elif self.type.kind == Kind.BOOL:
            out[:] = self.data.astype(bool).tolist()
        elif self.type.kind == Kind.FLOAT:
            out[:] = self.data.astype(np.float64).tolist()
        else:
            out[:] = self.data.astype(np.int64).tolist()
        out[~self.valid] = None
        return out


def encode_strings(values: List[Optional[str]]) -> HostColumn:
    """Dictionary-encode a string column. The dictionary is sorted so that
    integer code comparisons implement binary-collation string comparisons
    on device (reference collation engine: pkg/util/collate)."""
    valid = np.array([v is not None for v in values], dtype=bool)
    present = sorted({v for v in values if v is not None})
    dictionary = np.array(present, dtype=object)
    lookup = {v: i for i, v in enumerate(present)}
    codes = np.array([lookup[v] if v is not None else 0 for v in values], dtype=np.int32)
    from tidb_tpu.dtypes import STRING

    return HostColumn(STRING, codes, valid, dictionary)


def column_from_values(values: List, typ: SQLType) -> HostColumn:
    if typ.kind == Kind.STRING:
        return encode_strings(values)
    valid = np.array([v is not None for v in values], dtype=bool)
    if typ.kind == Kind.DECIMAL:
        data = np.array(
            [round(float(v) * 10**typ.scale) if v is not None else 0 for v in values],
            dtype=np.int64,
        )
    elif typ.kind == Kind.DATE:
        from tidb_tpu.dtypes import date_to_days

        data = np.array(
            [date_to_days(v) if isinstance(v, str) else (v or 0) for v in values],
            dtype=np.int32,
        )
    elif typ.kind == Kind.DATETIME:
        from tidb_tpu.dtypes import datetime_to_micros

        data = np.array(
            [
                datetime_to_micros(v) if isinstance(v, str) else (v or 0)
                for v in values
            ],
            dtype=np.int64,
        )
    elif typ.kind == Kind.TIME:
        from tidb_tpu.dtypes import time_to_micros

        data = np.array(
            [
                time_to_micros(v) if isinstance(v, str) else (v or 0)
                for v in values
            ],
            dtype=np.int64,
        )
    else:
        data = np.array([v if v is not None else 0 for v in values], dtype=typ.np_dtype)
    return HostColumn(typ, data, valid)


_block_uid = itertools.count(1)


@dataclasses.dataclass
class HostBlock:
    """A batch of rows on the host: the storage unit of a table partition."""

    columns: Dict[str, HostColumn]
    nrows: int
    # partition id for blocks of a partitioned table (Table.split_by_
    # partition tags appends); None = unpartitioned
    part_id: Optional[int] = None
    # process-unique immutable-block identity: version deltas (log
    # backup) diff block lists by uid instead of object identity, which
    # GC could recycle
    uid: int = dataclasses.field(default_factory=lambda: next(_block_uid))

    @staticmethod
    def from_columns(columns: Dict[str, HostColumn]) -> "HostBlock":
        n = len(next(iter(columns.values()))) if columns else 0
        for c in columns.values():
            assert len(c) == n
        return HostBlock(columns, n)


def take_block(block: HostBlock, idx: np.ndarray) -> HostBlock:
    """Rows of a block selected by index array, column-wise (one
    ``np.take`` per column — the vectorized partition split of the
    shuffle producer; no Python row loop)."""
    cols = {
        n: HostColumn(c.type, c.data[idx], c.valid[idx], c.dictionary)
        for n, c in block.columns.items()
    }
    return HostBlock(cols, len(idx))


def slice_block(block: HostBlock, a: int, b: int) -> HostBlock:
    """Contiguous row range [a, b) of a block as numpy views (packet
    chunking on the shuffle send path — zero-copy)."""
    b = min(b, block.nrows)
    cols = {
        n: HostColumn(c.type, c.data[a:b], c.valid[a:b], c.dictionary)
        for n, c in block.columns.items()
    }
    return HostBlock(cols, max(b - a, 0))


def concat_host_columns(typ: SQLType, chunks: List[HostColumn]) -> HostColumn:
    """Concatenate column chunks into one HostColumn. For strings the
    chunks' per-batch dictionaries are unified into ONE sorted
    stage-local dictionary and every chunk's codes are re-keyed against
    it — dictionary codes become comparable across senders and across
    exchange sides (code order still == binary collation order), which
    is what makes string join keys shuffle-safe (ROADMAP item c)."""
    if typ.kind != Kind.STRING:
        if not chunks:
            return HostColumn(
                typ,
                np.zeros(0, dtype=typ.np_dtype),
                np.zeros(0, dtype=bool),
            )
        data = np.concatenate(
            [np.asarray(c.data, dtype=typ.np_dtype) for c in chunks]
        )
        valid = np.concatenate(
            [np.asarray(c.valid, dtype=bool) for c in chunks]
        )
        return HostColumn(typ, data, valid)
    vocab = set()
    for c in chunks:
        if c.dictionary is not None:
            vocab.update(str(s) for s in c.dictionary.tolist())
    unified = np.array(sorted(vocab), dtype=object)
    lut = {v: i for i, v in enumerate(unified.tolist())}
    datas, valids = [], []
    for c in chunks:
        valid = np.asarray(c.valid, dtype=bool)
        if c.dictionary is not None and len(c.dictionary):
            mapping = np.array(
                [lut[str(v)] for v in c.dictionary.tolist()],
                dtype=np.int32,
            )
            codes = mapping[
                np.clip(np.asarray(c.data), 0, len(c.dictionary) - 1)
            ]
        else:
            codes = np.zeros(len(c.data), dtype=np.int32)
        datas.append(np.where(valid, codes, 0).astype(np.int32))
        valids.append(valid)
    data = (
        np.concatenate(datas) if datas else np.zeros(0, dtype=np.int32)
    )
    valid = (
        np.concatenate(valids) if valids else np.zeros(0, dtype=bool)
    )
    return HostColumn(typ, data, valid, unified)


# ---------------------------------------------------------------------------
# Device side
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DevCol:
    data: jax.Array
    valid: jax.Array  # bool, True = not NULL


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Batch:
    """Device-side batch: dict of columns + row validity (the sel vector)."""

    cols: Dict[str, DevCol]
    row_valid: jax.Array  # bool [capacity]

    @property
    def capacity(self) -> int:
        return self.row_valid.shape[0]

    def with_cols(self, cols: Dict[str, DevCol]) -> "Batch":
        return Batch(cols, self.row_valid)

    def nrows(self) -> jax.Array:
        return jnp.sum(self.row_valid.astype(jnp.int32))


def block_to_batch(
    block: HostBlock, capacity: Optional[int] = None, sharding=None
) -> Batch:
    """Pad a host block to a static tile and move it to device layout:
    the default device, or with `sharding` each shard of the padded host
    array straight to its own device (no whole copy on one device, no
    slicing program per shape)."""
    from tidb_tpu.obs.engine_watch import ENGINE_WATCH

    cap = capacity or pad_capacity(block.nrows)
    pad = cap - block.nrows
    if sharding is None:
        to_device = jnp.asarray
    else:
        to_device = lambda a: jax.device_put(a, sharding)  # noqa: E731
    cols = {}
    h2d = cap  # the row-validity mask ships too
    for name, col in block.columns.items():
        data = np.pad(col.data, (0, pad))
        valid = np.pad(col.valid, (0, pad))
        h2d += data.nbytes + valid.nbytes
        cols[name] = DevCol(to_device(data), to_device(valid))
    row_valid = np.zeros(cap, dtype=bool)
    row_valid[: block.nrows] = True
    ENGINE_WATCH.note_h2d(h2d)
    return Batch(cols, to_device(row_valid))


def batch_from_padded(
    columns: Dict[str, HostColumn], nrows: int
) -> Batch:
    """Device batch from host columns ALREADY sized to the target tile
    capacity — the zero-extra-copy staging seam (ROADMAP PR 4 item a):
    the incremental shuffle stager writes each received chunk straight
    into capacity-sized buffers, so there is no concat-then-pad double
    copy here, just the h2d move. Every column must share one length
    (the capacity); rows past ``nrows`` are pad."""
    caps = {len(c.data) for c in columns.values()}
    assert len(caps) == 1, f"ragged staged columns: {sorted(caps)}"
    cap = caps.pop()
    assert nrows <= cap
    from tidb_tpu.obs.engine_watch import ENGINE_WATCH

    cols = {}
    h2d = cap  # the row-validity mask ships too
    for name, col in columns.items():
        h2d += col.data.nbytes + col.valid.nbytes
        cols[name] = DevCol(jnp.asarray(col.data), jnp.asarray(col.valid))
    row_valid = np.zeros(cap, dtype=bool)
    row_valid[:nrows] = True
    ENGINE_WATCH.note_h2d(h2d)
    return Batch(cols, jnp.asarray(row_valid))


def present_temporals(col: "HostColumn"):
    """decode() + MySQL string presentation for temporal kinds — the
    user-facing result seam (decode() itself stays raw ints for
    internal consumers). Vectorized via numpy datetime64 for
    DATE/DATETIME; TIME (rare in results) loops only over its rows."""
    k = col.type.kind
    if k not in (Kind.DATE, Kind.DATETIME, Kind.TIME):
        return col.decode()
    n = len(col.data)
    out = np.empty(n, dtype=object)
    if n == 0:
        # np.datetime_as_string rejects zero-size arrays; a 0-row
        # shuffle partition legitimately presents an empty column
        return out
    if k == Kind.DATE:
        out[:] = np.datetime_as_string(
            col.data.astype("datetime64[D]"), unit="D"
        )
    elif k == Kind.DATETIME:
        micros = col.data.astype(np.int64)
        secs = np.datetime_as_string(
            (micros // 1_000_000).astype("datetime64[s]"), unit="s"
        )
        secs = np.char.replace(secs, "T", " ")
        frac = micros % 1_000_000
        out[:] = secs
        nz = frac != 0
        if nz.any():
            from tidb_tpu.dtypes import micros_to_datetime

            idx = np.nonzero(nz)[0]
            for i in idx:
                out[i] = micros_to_datetime(int(micros[i]))
    else:
        from tidb_tpu.dtypes import micros_to_time

        out[:] = [micros_to_time(int(v)) for v in col.data]
    out[~col.valid] = None
    return out


def materialize_rows(batch, schema_cols, dicts):
    """Device batch -> python row tuples for a plan schema (one fetch,
    vectorized decode). The single implementation behind the session's
    result materialization and the engine-RPC response encoder.
    Temporal columns present as MySQL-formatted strings HERE — the
    user-facing seam — while decode() stays raw (day/micros ints) for
    internal consumers (oracles, dump, CDC diffing)."""
    types = {c.internal: c.type for c in schema_cols}
    return block_to_rows(batch_to_block(batch, types, dicts), schema_cols)


def block_to_rows(block: HostBlock, schema_cols) -> List[tuple]:
    """Host block -> presented python row tuples (the row half of
    materialize_rows, reusable for blocks that never touched a device —
    the shuffle producer's JSON fallback for mixed-version peers)."""
    internals = [c.internal for c in schema_cols]
    decoded = {
        i: present_temporals(block.columns[i]) for i in internals
    }
    return [
        tuple(decoded[i][r] for i in internals) for r in range(block.nrows)
    ]


def batch_to_block(
    batch: Batch, types: Dict[str, SQLType], dicts: Dict[str, Optional[np.ndarray]]
) -> HostBlock:
    """Pull a device batch back to host and compact out invalid rows.

    Fetches everything in ONE device->host transfer (device->host round
    trips are latency-bound, so N column-wise pulls would cost N round
    trips)."""
    fetched = jax.device_get(
        (batch.row_valid, {n: (dc.data, dc.valid) for n, dc in batch.cols.items()})
    )
    row_valid, host_cols = fetched
    idx = np.nonzero(np.asarray(row_valid))[0]
    cols = {}
    for name, (data, valid) in host_cols.items():
        if name not in types:
            # additive projections keep base columns in the runtime
            # batch; only the plan schema's columns materialize (matters
            # for additive-rooted fragment plans over the RPC seam)
            continue
        cols[name] = HostColumn(
            types[name], np.asarray(data)[idx], np.asarray(valid)[idx], dicts.get(name)
        )
    return HostBlock(cols, len(idx))
