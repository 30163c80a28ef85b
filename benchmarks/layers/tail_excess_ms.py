"""The largest, over the window's answered statements, of latency less
its class's median: how far off the farthest statement was."""

import spans


def read(run):
    _worst, excess = spans.tail(run)
    return None if excess is None else 1e3 * excess
