"""The least an exchange's bytes cost: its rows once over the chips'
interconnect.

Rows that a hash or range partition sends are spread over the `chips`
destinations, the sender among them, so (chips - 1) / chips of them
leave their chip; a broadcast sends every row to each other chip. A
row carries the values of the columns that travel: validity bits, the
send buffers' padding and the bucket tiles' slack are the
implementation's and are not counted. The program counts the same on
each statement's flight (`exchange_rows`, `exchange_bytes`);
`tests/test_server_mesh.py` holds it to this function on a join whose
rows are counted by hand."""

from __future__ import annotations


def partition_bytes(rows: int, row_bytes: int, chips: int) -> float:
    """Bytes a hash or range partition of `rows` rows moves between chips."""
    return rows * row_bytes * (chips - 1) / chips


def broadcast_bytes(rows: int, row_bytes: int, chips: int) -> float:
    """Bytes a broadcast of `rows` rows moves between chips."""
    return rows * row_bytes * (chips - 1)


def ici_share_pct(total_bytes: float, chips: int, busy_s: float, peak_bytes_per_s: float):
    """Percent of the interconnect's roofline: the seconds one chip's
    share of the bytes would take at its ICI peak, over the seconds the
    chips were busy (the mean over them, as trace_reduce gives it)."""
    if busy_s <= 0 or total_bytes <= 0 or chips < 2:
        return None
    return 100.0 * (total_bytes / chips / peak_bytes_per_s) / busy_s
