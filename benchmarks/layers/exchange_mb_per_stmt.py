"""Megabytes a traced statement's exchanged rows must carry between
chips (ici.py says what is counted)."""

import exchange


def read(run):
    return exchange.mean_per_stmt(run, "exchange_bytes", 1e-6)
