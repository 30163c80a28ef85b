"""What the traced statements' flights say their programs' sorted
group-bys did (`sorted_groupings`, `sorted_group_rows`, `sorted_groups`,
`sorted_group_slots`: tidb_tpu/obs/flight.py), for the per-layer
readers. A program from before those keys, and a statement whose
aggregates are all dense or scalar, give the readers nothing to read:
None."""

from __future__ import annotations

from readers import traced


def grouping(run) -> list:
    """The flights of the traced statements that ran a sorted group-by."""
    flights = [s["flight"] for s in traced(run) if s["flight"] is not None]
    return [f for f in flights if f.get("sorted_groupings")]
