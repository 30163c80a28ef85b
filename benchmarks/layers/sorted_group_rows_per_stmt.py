"""Valid rows that entered a traced statement's sorted group-bys,
summed over them."""

import statistics

import grouping


def read(run):
    values = [f["sorted_group_rows"] for f in grouping.grouping(run)]
    return statistics.fmean(values) if values else None
