"""The farthest-off statement's `device-wait` less its class's median
`device-wait`: whether the excess was spent waiting for the device or
on the host."""

import spans


def read(run):
    worst, _excess = spans.tail(run)
    if worst is None or not spans.has_spans(worst):
        return None
    median = spans.class_median_seconds(run, worst["name"], "execute/device-wait")
    return 1e3 * (spans.seconds_of(worst["flight"], "execute/device-wait") - median)
