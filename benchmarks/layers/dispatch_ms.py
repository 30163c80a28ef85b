"""Mean per statement of `execute/dispatch`: the jitted call until it
returns (the enqueue)."""

import spans


def read(run):
    return spans.mean_ms(run, "execute/dispatch")
