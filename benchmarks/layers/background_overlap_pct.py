"""Seconds of background ticks that ran beside the statements, over the
statements' served seconds, in per cent."""

import spans


def read(run):
    flights = [s["flight"] for s in spans.spanned(run)]
    served = sum(f["served_s"] for f in flights)
    if not served:
        return None
    return 100.0 * sum(spans.background_seconds(f) for f in flights) / served
