"""TPC-H Q18 (QUANTITY = 300) over the host columns: the orders whose
lines sum to more than 300 units, with their customer, the 100 of
highest total price. Per-order sums are exact (under 2**24 hundredths
in either precision). Rows that tie on total price and date could come
in any order; the reference breaks such a tie by order key, and a run
that meets one would read `cells_wrong` (PERF.md gives the odds)."""

import numpy as np

KINDS = ("str", "int", "int", "str", "dec2", "dec2")
_THRESHOLD = 300_00
_LIMIT = 100


def expected(data, precision="exact"):
    col = data.col
    weights = col("lineitem", "l_quantity").astype(
        np.float32 if precision == "float32" else np.float64)
    sums = np.bincount(col("lineitem", "l_orderkey"), weights=weights).astype(np.int64)
    o_key = col("orders", "o_orderkey")
    in_range = o_key < len(sums)
    picked = np.nonzero(in_range)[0][sums[o_key[in_range]] > _THRESHOLD]
    total, date = col("orders", "o_totalprice")[picked], col("orders", "o_orderdate")[picked]
    order = np.lexsort((o_key[picked], date, -total))[:_LIMIT]
    picked = picked[order]
    if not len(picked):
        raise AssertionError("Q18's reference is empty: the comparison would be vacuous")
    c_key = col("customer", "c_custkey")
    row_of = np.full(int(c_key.max()) + 1, -1, dtype=np.int64)
    row_of[c_key] = np.arange(len(c_key))
    cust = col("orders", "o_custkey")[picked]
    names = data.dictionary("customer", "c_name")[col("customer", "c_name")[row_of[cust]]]
    dates = col("orders", "o_orderdate")[picked].astype("datetime64[D]").astype(str)
    return [
        (str(names[i]), int(cust[i]), int(o_key[picked[i]]), str(dates[i]),
         int(col("orders", "o_totalprice")[picked[i]]), int(sums[o_key[picked[i]]]))
        for i in range(len(picked))
    ]
