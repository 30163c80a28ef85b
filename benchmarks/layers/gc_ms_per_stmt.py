"""Mean per statement of the flight's `gc_s`: seconds of Python's
collector, on any thread, while the statement's root span was open."""

import statistics

import served_path


def read(run):
    flights = [s["flight"] for s in served_path.named(run)]
    if not flights:
        return None
    return 1e3 * statistics.fmean(f["gc_s"] for f in flights)
