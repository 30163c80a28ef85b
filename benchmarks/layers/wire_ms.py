"""Mean per statement of client latency less the program's own wall for
it (its flight): socket, protocol parse and the text rendering of rows."""

import statistics

from readers import answered


def read(run):
    values = [
        s["latency_s"] - s["flight"]["duration_s"]
        for s in answered(run) if s["flight"] is not None
    ]
    return 1e3 * statistics.fmean(values) if values else None
