"""What the traced statements' flights say their programs' expanding
joins did (`join_expansions`, `join_expand_rows`, `join_expand_slots`:
tidb_tpu/obs/flight.py), for the per-layer readers. A program from
before those keys, and a statement none of whose joins expands (every
build side unique), give the readers nothing to read: None."""

from __future__ import annotations

from readers import traced


def expanding(run) -> list:
    """The flights of the traced statements that ran an expanding join."""
    flights = [s["flight"] for s in traced(run) if s["flight"] is not None]
    return [f for f in flights if f.get("join_expansions")]
