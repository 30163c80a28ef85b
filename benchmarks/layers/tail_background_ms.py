"""Seconds of background ticks beside the farthest-off statement."""

import spans


def read(run):
    worst, _excess = spans.tail(run)
    if worst is None or not spans.has_spans(worst):
        return None
    return 1e3 * spans.background_seconds(worst["flight"])
