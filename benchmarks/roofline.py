"""The least a statement's bytes cost: its base-table columns read once.

The byte count is a function of the query's column list
(`queries/<q>.json`) and of the loaded schema (rows x bytes of a value
as loaded), not of the plan: it reads the same work whatever implements
it. A statement that reads a column twice, sorts it or spills still
counts it once, so the share says how far the whole statement is from a
single streaming pass at the chip's peak."""

from __future__ import annotations


def statement_bytes(reads: dict, row_counts: dict, width_bytes) -> int:
    """`reads`: {table: [column, ...]}; `width_bytes(table, column)`."""
    return sum(
        row_counts[table] * width_bytes(table, column)
        for table, columns in reads.items() for column in columns
    )


def hbm_share_pct(total_bytes: float, busy_s: float, peak_bytes_per_s: float):
    """Percent of the HBM roofline: least seconds over device-busy seconds."""
    if busy_s <= 0 or total_bytes <= 0:
        return None
    return 100.0 * (total_bytes / peak_bytes_per_s) / busy_s
