"""Table scan: host blocks -> device Batch, with a device-resident cache.

Reference: TableReaderExecutor (pkg/executor/table_reader.go:135) issuing
coprocessor scans per Region with the copr response cache
(pkg/store/copr/coprocessor_cache.go:32). TPU analog: concatenate the
table's blocks for the requested columns, pad to the capacity tile, move
to device once, and cache keyed by (table version, columns, capacity) —
re-scans of an unchanged table are free, which is the dominant pattern in
analytics. Column pruning happens here (only requested columns transfer).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from tidb_tpu.chunk import Batch, HostBlock, HostColumn, block_to_batch, pad_capacity
from tidb_tpu.storage.table import Table

# (table uid, version, cols, capacity, sharding) -> Batch. Keyed by the
# process-unique Table.uid (NOT id(): CPython reuses freed addresses, and
# a drop/create cycle would alias a new table onto stale device arrays).
# LRU-bounded; inserting a new version evicts older versions of the same
# table (the copr-cache invalidation analog).
from collections import OrderedDict

_scan_cache: "OrderedDict[tuple, Batch]" = OrderedDict()
_SCAN_CACHE_MAX = 64


def clear_scan_cache() -> None:
    _scan_cache.clear()


def concat_blocks(blocks, columns: Sequence[str], schema=None) -> HostBlock:
    if not blocks:
        types = schema.types if schema is not None else {}
        cols = {
            name: HostColumn(
                types[name],
                np.zeros(0, dtype=types[name].np_dtype),
                np.zeros(0, dtype=bool),
                np.array([], dtype=object) if types[name].is_string else None,
            )
            for name in columns
        }
        return HostBlock(cols, 0)
    cols = {}
    types = schema.types if schema is not None else {}
    for name in columns:
        have = [b for b in blocks if name in b.columns]
        first = have[0].columns[name] if have else None
        typ = first.type if first is not None else types[name]

        def col_of(b):
            c = b.columns.get(name)
            if c is not None:
                return c.data, c.valid
            # block predates ALTER ADD COLUMN: reads see NULL
            return (
                np.zeros(b.nrows, dtype=typ.np_dtype),
                np.zeros(b.nrows, dtype=bool),
            )

        parts = [col_of(b) for b in blocks]
        data = np.concatenate([d for d, _ in parts])
        valid = np.concatenate([v for _, v in parts])
        cols[name] = HostColumn(
            typ, data, valid, first.dictionary if first is not None else None
        )
    return HostBlock(cols, sum(b.nrows for b in blocks))


def scan_table(
    table: Table,
    columns: Sequence[str],
    capacity: Optional[int] = None,
    version: Optional[int] = None,
    mesh=None,
    partitions=None,
    frag=None,
) -> Tuple[Batch, Dict[str, np.ndarray]]:
    """Returns (device batch, dictionaries for the scanned columns).

    With a mesh, the batch is placed row-sharded over the mesh axis (the
    Region data-parallel scan analog, SURVEY.md §2.7) and the capacity is
    padded to a multiple of the mesh size; cached per (version, columns,
    capacity, mesh). frag=(idx, n) scans only every n-th row starting at
    idx of the version's block concatenation — the cross-host fragment
    slice (disjoint over idx, covering in union; planner/fragmenter.py)."""
    from tidb_tpu.utils.failpoint import inject

    inject("storage/scan")
    v = table.version if version is None else version
    cols = tuple(columns)
    if frag is not None and "_tidb_rowid" in cols:
        # rowid handles address the FULL block concatenation; a sliced
        # scan would mislabel slice-local positions as global handles
        # and DML masks would hit the wrong rows
        raise ValueError("fragment scans cannot expose _tidb_rowid")
    blocks = table.blocks(v, partitions=partitions)
    n = sum(b.nrows for b in blocks)
    if frag is not None:
        fi, fn = int(frag[0]), int(frag[1])
        n = max((n - fi + fn - 1) // fn, 0) if fn > 0 else n
    cap = capacity or pad_capacity(n)
    mesh_n = None
    if mesh is not None:
        mesh_n = int(mesh.devices.size)
        if cap % mesh_n:
            # equal per-shard tiles for any mesh size (a doubling loop
            # would never terminate for non-power-of-two meshes)
            cap = mesh_n * pad_capacity(-(-cap // mesh_n), floor=32)
    uid = getattr(table, "uid", None) or id(table)
    pkey = tuple(sorted(partitions)) if partitions is not None else None
    fkey = (int(frag[0]), int(frag[1])) if frag is not None else None
    key = (uid, v, cols, cap, mesh_n, pkey, fkey)
    dicts = {c: table.dictionaries[c] for c in cols if c in table.dictionaries}
    if key in _scan_cache:
        _scan_cache.move_to_end(key)
        return _scan_cache[key], dicts
    rowid = [c for c in cols if c == "_tidb_rowid"]
    block = concat_blocks(
        blocks, [c for c in cols if c != "_tidb_rowid"], table.schema
    )
    if frag is not None:
        import dataclasses as _dc

        fi, fn = int(frag[0]), int(frag[1])
        block = HostBlock(
            {
                name: _dc.replace(c, data=c.data[fi::fn], valid=c.valid[fi::fn])
                for name, c in block.columns.items()
            },
            len(range(fi, block.nrows, fn)),
        )
    if rowid:
        # virtual scan-order row handle (multi-table DML): position in
        # the version's block concatenation — the same coordinates
        # delete_where / columnar-update masks address
        from tidb_tpu.chunk import HostColumn
        from tidb_tpu.dtypes import INT64

        block.columns["_tidb_rowid"] = HostColumn(
            INT64,
            np.arange(block.nrows, dtype=np.int64),
            np.ones(block.nrows, dtype=bool),
            None,
        )
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding

        from tidb_tpu.parallel.mesh import batch_spec

        sharding = NamedSharding(mesh, batch_spec())
    batch = block_to_batch(block, cap, sharding=sharding)
    # drop cached batches of older versions of this table
    for k in [k for k in _scan_cache if k[0] == uid and k[1] != v]:
        del _scan_cache[k]
    while len(_scan_cache) >= _SCAN_CACHE_MAX:
        _scan_cache.popitem(last=False)
    _scan_cache[key] = batch
    return batch, dicts
