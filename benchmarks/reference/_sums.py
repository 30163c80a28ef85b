"""Sums for the references, in the precision asked for.

`exact` is the reference: 64-bit integers (checked to fit). `float32`
is the control's: the nearest precision below the float64 that the
configuration states for wide sums, put in the program's place; it is
never the reference."""

from __future__ import annotations

import numpy as np

PRECISIONS = ("exact", "float32")


def total(values: np.ndarray, precision: str) -> int:
    """Sum of an integer column (scaled decimals are integers)."""
    if precision == "float32":
        return int(np.sum(values.astype(np.float32), dtype=np.float32))
    if precision != "exact":
        raise ValueError(precision)
    if values.size and float(np.abs(values).max()) * values.size >= 2.0**63:
        return sum(int(v) for v in values.tolist())  # never at these scales
    return int(values.sum(dtype=np.int64))


def product(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return a.astype(np.float32) * b.astype(np.float32)
    return a.astype(np.int64) * b.astype(np.int64)
