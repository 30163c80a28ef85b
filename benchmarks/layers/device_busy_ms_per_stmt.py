"""Device-busy milliseconds per statement of the traced part."""

from readers import traced


def read(run):
    n = len(traced(run))
    if run["trace"] is None or not n:
        return None
    return 1e3 * run["trace"]["busy_s"] / n
