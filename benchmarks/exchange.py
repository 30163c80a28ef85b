"""What the traced statements' flights say their mesh programs
exchanged between chips (`exchanges`, `exchange_rows`,
`exchange_bytes`: tidb_tpu/obs/flight.py), for the per-layer readers.
A program from before those keys, and a one-device server, whose
statements exchange nothing, give the readers nothing to read: None."""

from __future__ import annotations

import statistics

from readers import traced


def exchanging(run) -> list:
    """The flights of the traced statements that ran an exchange."""
    flights = [s["flight"] for s in traced(run) if s["flight"] is not None]
    return [f for f in flights if f.get("exchanges")]


def mean_per_stmt(run, key: str, scale: float = 1.0):
    values = [f[key] * scale for f in exchanging(run)]
    return statistics.fmean(values) if values else None
