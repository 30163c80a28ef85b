"""Valid rows a traced statement's exchanges sent, summed over the shards."""

import exchange


def read(run):
    return exchange.mean_per_stmt(run, "exchange_rows")
