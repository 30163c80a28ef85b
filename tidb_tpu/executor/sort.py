"""ORDER BY / TOP-N / LIMIT with static shapes.

Reference: SortExec's parallel multi-way merge sort
(pkg/executor/sortexec/sort.go:38, parallel_sort_worker.go:31), TopNExec
(topn.go:31) and LimitExec (executor.go:1307). On TPU a single lax.sort
over the whole tile replaces the worker/merge machinery (the sort network
is the parallelism); TopN = sort + limit mask; spill never happens on
device — oversized sorts are partitioned across the mesh and merged
(parallel/exchange.py), or staged through host RAM.

Sort keys encode direction and MySQL null ordering (NULLs first ASC,
last DESC) by key transforms, so one ascending lax.sort handles all.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from tidb_tpu.chunk import Batch, DevCol

ExprFn = Callable[[Batch], DevCol]


def _directional_comps(batch: Batch, key_fns, descs) -> list:
    """(unsigned image, bits) sort components implementing direction +
    null order for sortops.sort_lex. Invalid rows always sink to the
    end."""
    from tidb_tpu.executor.sortops import int_sort_bits

    comps = [(~batch.row_valid, 1)]
    for fn, desc in zip(key_fns, descs):
        k = fn(batch)
        valid = k.valid & batch.row_valid
        # MySQL: NULLs sort first ascending, last descending. An
        # ascending sort puts 0 before 1, so NULL rows need null_key 0
        # for ASC (valid) and 1 for DESC (~valid).
        comps.append((~valid if desc else valid, 1))
        data = jnp.where(valid, k.data, jnp.zeros_like(k.data))
        if jnp.issubdtype(data.dtype, jnp.floating):
            # a float sorts as its own operand (NaNs last either way)
            comps.append((-data if desc else data, None))
        else:
            u, bits = int_sort_bits(data)
            comps.append((~u if desc and bits > 1 else u ^ desc, bits))
    return comps


def sort_permutation(batch: Batch, key_fns, descs) -> jax.Array:
    """Row permutation of ORDER BY: one packed unstable sort whose last
    component is the row id, so ties keep row order (sortops module
    docstring: compile time follows key limbs)."""
    from tidb_tpu.executor.sortops import sort_rows

    comps = _directional_comps(batch, key_fns, descs)
    return sort_rows(comps, batch.capacity)[2]


def order_by(batch: Batch, key_fns, descs) -> Batch:
    """Fully sort the batch (valid rows first, in key order)."""

    from tidb_tpu.utils.failpoint import inject

    inject("executor/sort")
    perm = sort_permutation(batch, key_fns, descs)
    cols = {n: DevCol(c.data[perm], c.valid[perm]) for n, c in batch.cols.items()}
    return Batch(cols, batch.row_valid[perm])


def limit(batch: Batch, k: int, offset: int = 0) -> Batch:
    """Keep rows [offset, offset+k) in current row order (LimitExec)."""
    pos = jnp.cumsum(batch.row_valid.astype(jnp.int64)) - 1  # rank of each valid row
    keep = batch.row_valid & (pos >= offset) & (pos < offset + k)
    return Batch(batch.cols, keep)


def top_n(batch: Batch, key_fns, descs, k: int, offset: int = 0) -> Batch:
    """ORDER BY ... LIMIT k: sort then mask (TopNExec topn.go:31)."""
    return limit(order_by(batch, key_fns, descs), k, offset)
