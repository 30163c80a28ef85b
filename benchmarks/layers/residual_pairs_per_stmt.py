"""Valid pairs that a traced statement's semi and anti joins with a
residual tested it on, summed over them."""

import statistics

import residual


def read(run):
    values = [f["residual_pairs"] for f in residual.tested(run)]
    return statistics.fmean(values) if values else None
