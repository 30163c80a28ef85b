"""Mean per statement of client latency less the served statement's
root span: the load generator's own parsing and the loopback, what no
change to the program moves."""

import statistics

import spans


def read(run):
    values = [s["latency_s"] - s["flight"]["served_s"] for s in spans.spanned(run)]
    return 1e3 * statistics.fmean(values) if values else None
