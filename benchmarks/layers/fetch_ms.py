"""Mean per statement of `execute/fetch`: the one device_get of the
output batch and the cardinality scalars."""

import spans


def read(run):
    return spans.mean_ms(run, "execute/fetch")
