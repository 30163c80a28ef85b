"""XLA module executions on the device per statement of the traced part."""

from readers import traced


def read(run):
    n = len(traced(run))
    if run["trace"] is None or not n:
        return None
    return run["trace"]["launches"] / n
