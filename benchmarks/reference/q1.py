"""TPC-H Q1 (DELTA = 90) over the host columns. Sums are exact 64-bit
integers of the scaled decimals; averages are their float64 quotients."""

import numpy as np

from _sums import product, total

KINDS = ("str", "str", "dec2", "dec2", "wide4", "wide6",
         "float", "float", "float", "int")
_CUTOFF = int(np.datetime64("1998-12-01", "D").astype(np.int64)) - 90


def expected(data, precision="exact"):
    keep = data.col("lineitem", "l_shipdate") <= _CUTOFF
    rf = data.col("lineitem", "l_returnflag")[keep]
    ls = data.col("lineitem", "l_linestatus")[keep]
    qty = data.col("lineitem", "l_quantity")[keep]
    price = data.col("lineitem", "l_extendedprice")[keep]
    disc = data.col("lineitem", "l_discount")[keep]
    tax = data.col("lineitem", "l_tax")[keep]
    rf_names = data.dictionary("lineitem", "l_returnflag")
    ls_names = data.dictionary("lineitem", "l_linestatus")
    disc_price = product(price, 100 - disc, precision)
    charge = product(disc_price, 100 + tax, precision)
    rows = []
    for r in range(len(rf_names)):
        for s in range(len(ls_names)):
            g = (rf == r) & (ls == s)
            n = int(g.sum())
            if not n:
                continue
            sum_qty = total(qty[g], precision)
            sum_base = total(price[g], precision)
            sum_disc = total(disc[g], precision)
            rows.append((
                str(rf_names[r]), str(ls_names[s]), sum_qty, sum_base,
                total(disc_price[g], precision), total(charge[g], precision),
                sum_qty / n / 100.0, sum_base / n / 100.0, sum_disc / n / 100.0, n,
            ))
    return sorted(rows, key=lambda t: t[:2])
