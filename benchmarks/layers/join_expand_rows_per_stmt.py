"""Rows a traced statement's expanding joins emitted, summed over them."""

import statistics

import expansion


def read(run):
    values = [f["join_expand_rows"] for f in expansion.expanding(run)]
    return statistics.fmean(values) if values else None
