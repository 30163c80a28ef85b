"""The program's own spans, for the per-layer readers. Since PR 27 a
flight (`run["statements"][i]["flight"]`) carries `spans`: one row per
boundary the program timed itself, `{"name": "stmt/session/execute/
dispatch", "start_s", "seconds"}`, nested by path under the
served statement's root (`stmt`: command packet read to answer
written), with `served_s` (the root's seconds) and `background`
(`[name, seconds]` of the background ticks that ran beside it). A
program from before that has none: every reader here then finds
nothing and returns None."""

from __future__ import annotations

import statistics

from readers import answered


def has_spans(statement) -> bool:
    """The statement's flight has spans and a closed root."""
    flight = statement["flight"]
    return bool(flight is not None and flight.get("spans") and flight.get("served_s"))


def spanned(run) -> list:
    return [s for s in answered(run) if has_spans(s)]


def _ends(path: str, suffix: str) -> bool:
    return path == suffix or path.endswith("/" + suffix)


def seconds_of(flight, suffix: str) -> float:
    """Seconds of the flight's spans whose path ends in `suffix`."""
    return sum(row["seconds"] for row in flight["spans"] if _ends(row["name"], suffix))


def self_seconds_of(flight, suffix: str) -> float:
    """Seconds of the spans ending in `suffix` less those of the spans
    nested directly in them (a span is nested in the longest path that
    is a prefix of its own)."""
    paths = {row["name"] for row in flight["spans"]}
    total = 0.0
    for row in flight["spans"]:
        if _ends(row["name"], suffix):
            total += row["seconds"]
            continue
        above = [p for p in paths if row["name"].startswith(p + "/")]
        if above and _ends(max(above, key=len), suffix):
            total -= row["seconds"]
    return total


def mean_ms(run, suffix: str, of=seconds_of):
    """Mean per statement of the spans ending in `suffix`."""
    values = [of(s["flight"], suffix) for s in spanned(run)]
    return 1e3 * statistics.fmean(values) if values else None


def background_seconds(flight) -> float:
    return sum(seconds for _name, seconds in flight.get("background") or ())


def tail(run):
    """The answered statement whose latency exceeds its class's median
    by most, and that excess in seconds; (None, None) without one."""
    by_class: dict = {}
    for s in answered(run):
        by_class.setdefault(s["name"], []).append(s["latency_s"])
    medians = {name: statistics.median(values) for name, values in by_class.items()}
    worst = max(answered(run), key=lambda s: s["latency_s"] - medians[s["name"]], default=None)
    if worst is None:
        return None, None
    return worst, worst["latency_s"] - medians[worst["name"]]


def class_median_seconds(run, statement: str, suffix: str):
    values = [seconds_of(s["flight"], suffix) for s in spanned(run) if s["name"] == statement]
    return statistics.median(values) if values else None
