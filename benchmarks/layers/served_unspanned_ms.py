"""Mean per statement of the root `stmt` span less the union of its
leaf spans: the served path's time that no span names."""

import statistics

import served_path


def read(run):
    flights = [s["flight"] for s in served_path.named(run)]
    if not flights:
        return None
    return 1e3 * statistics.fmean(served_path.unspanned_seconds(f) for f in flights)
