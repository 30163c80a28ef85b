"""What the traced statements' flights say their programs' semi and
anti joins with a residual did (`residual_joins`, `residual_pairs`,
`residual_kept`: tidb_tpu/obs/flight.py), for the per-layer readers,
and the least bytes such a join moves, for a roofline share of it. A
program from before those keys, and a statement without a correlated
EXISTS or NOT EXISTS whose subquery has a residual, give the readers
nothing to read: None."""

from __future__ import annotations

from readers import traced


def tested(run) -> list:
    """The flights of the traced statements that ran a semi or anti
    join with a residual."""
    flights = [s["flight"] for s in traced(run) if s["flight"] is not None]
    return [f for f in flights if f.get("residual_joins")]


def least_bytes(build_rows: int, build_bytes: int, pairs: int, pair_bytes: int) -> int:
    """The bytes one semi or anti join with a residual cannot avoid
    moving: its build side's key and the columns its residual reads
    (`build_bytes` a row) read once, and its valid pairs (`pair_bytes`
    a pair: the probe's row id and the columns the residual compares)
    written once. Its device seconds over these bytes at the chip's HBM
    bandwidth give the join's share of its roofline; the seconds come
    from the trace's `residual` scopes (scripts/trace_by_scope.py),
    which benchmarks/trace_reduce.py does not read yet."""
    return int(build_rows) * int(build_bytes) + int(pairs) * int(pair_bytes)
