"""Mean per statement of `execute/device-wait`: block_until_ready on
the launched program's outputs."""

import spans


def read(run):
    return spans.mean_ms(run, "execute/device-wait")
