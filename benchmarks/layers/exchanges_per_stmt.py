"""Exchanges (hash or range partitions, broadcasts) the mesh program of
a traced statement ran."""

import exchange


def read(run):
    return exchange.mean_per_stmt(run, "exchanges")
