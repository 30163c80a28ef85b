"""The per-layer readers. A metric is `layers/<metric>.py` with a
`read(run)` of its own, or `layers/<metric>.json`, which names one of
these functions as `reader` and gives its other parameters. A reader
gets the run's record and returns its number, or None where it finds
nothing to read; the harness then leaves the metric out of the line.

The record: `setup` (seconds of each part, compile activity), `window`
(seconds, compilations), `statements` (one per statement of the window:
name, latency_s, error, traced, flight = the program's own span of it),
`trace` (trace_reduce's numbers, `--trace 1` only), `bytes` (least
bytes per statement name), `peaks` (this device's row of peaks.json)."""

from __future__ import annotations

import statistics


def answered(run) -> list:
    return [s for s in run["statements"] if s["error"] is None]


def class_median_ms(run, statement: str):
    """Median client-side latency of one statement class."""
    values = [s["latency_s"] for s in answered(run) if s["name"] == statement]
    return 1e3 * statistics.median(values) if values else None


def setup_part(run, part: str):
    """Seconds of one part of set-up."""
    return run["setup"].get(part)


def flight_phase_mean_ms(run, phases) -> float | None:
    """Mean per statement of the named flight phases' seconds."""
    values = [
        sum(s["flight"]["phases"].get(p, {}).get("seconds", 0.0) for p in phases)
        for s in answered(run) if s["flight"] is not None
    ]
    return 1e3 * statistics.fmean(values) if values else None


def traced(run) -> list:
    return [s for s in answered(run) if s["traced"]]
