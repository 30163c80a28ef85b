"""TPC-H Q21 (NATION = 'SAUDI ARABIA') over the host columns, from the
query's text.

For every order: the set of its suppliers, and the set of its late
suppliers (those of its lines with l_receiptdate > l_commitdate), as
distinct (order, supplier) pairs. A late line l1 of an 'F' order from a
Saudi supplier counts where its order has a supplier other than
l1's (the EXISTS over l2) and no late supplier other than l1's (the
NOT EXISTS over l3; l1's own supplier is in the late set, so the set
holds it alone). Then count(*) by s_name, ORDER BY numwait DESC,
s_name, LIMIT 100. Both cells are exact.

Precisions other than "exact" are controls (benchmarks/control_q21.py):

- `float32`: the same answer, since nothing here is summed in a
  floating type; it says nothing for Q21;
- `exists_any_line`: the EXISTS without its residual, so any other
  line of the order satisfies it, whatever its supplier;
- `not_exists_any_line`: the NOT EXISTS without l3's lateness filter,
  so any line of another supplier defeats it.

`extra`: lineitem rows a write added, as {column: values} with the
four columns read here, appended to the loaded ones."""

import numpy as np

KINDS = ("str", "int")
NATION = "SAUDI ARABIA"
LIMIT = 100
PRECISIONS = ("exact", "float32", "exists_any_line", "not_exists_any_line")
_READS = ("l_orderkey", "l_suppkey", "l_receiptdate", "l_commitdate")


def _distinct_per_order(order, supp, n_orders):
    """How many distinct suppliers each order key has among the rows."""
    pairs = np.unique(np.stack([order, supp]), axis=1)
    return np.bincount(pairs[0], minlength=n_orders)


def expected(data, precision="exact", extra=None):
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")

    def col(name):
        values = data.col("lineitem", name)
        if extra is None:
            return values
        return np.concatenate([values, np.asarray(extra[name], dtype=values.dtype)])

    order, supp, receipt, commit = (col(name) for name in _READS)
    late = receipt > commit
    n_orders = int(order.max()) + 1
    suppliers = _distinct_per_order(order, supp, n_orders)
    late_suppliers = _distinct_per_order(order[late], supp[late], n_orders)
    lines = np.bincount(order, minlength=n_orders)

    o_key = data.col("orders", "o_orderkey")
    status = data.dictionary("orders", "o_orderstatus")[data.col("orders", "o_orderstatus")]
    finished = np.zeros(n_orders, dtype=bool)
    in_range = o_key < n_orders
    finished[o_key[in_range]] = status[in_range] == "F"

    n_key = data.col("nation", "n_nationkey")
    n_name = data.dictionary("nation", "n_name")[data.col("nation", "n_name")]
    s_key = data.col("supplier", "s_suppkey")
    saudi = np.zeros(int(max(s_key.max(), supp.max())) + 1, dtype=bool)
    saudi[s_key] = np.isin(data.col("supplier", "s_nationkey"), n_key[n_name == NATION])

    if precision == "exists_any_line":
        exists = lines[order] >= 2
    else:
        exists = suppliers[order] >= 2
    if precision == "not_exists_any_line":
        none_other = suppliers[order] == 1
    else:
        none_other = late_suppliers[order] == 1
    counted = late & finished[order] & saudi[supp] & exists & none_other

    numwait = np.bincount(supp[counted], minlength=len(saudi))
    waiting = np.nonzero(numwait)[0]
    row_of = np.full(len(saudi), -1, dtype=np.int64)
    row_of[s_key] = np.arange(len(s_key))
    names = data.dictionary("supplier", "s_name")[data.col("supplier", "s_name")[row_of[waiting]]]
    ranked = sorted(zip(names.tolist(), numwait[waiting].tolist()), key=lambda r: (-r[1], r[0]))
    if precision == "exact" and not ranked:
        raise AssertionError("Q21's reference is empty: the comparison would be vacuous")
    return [(str(name), int(n)) for name, n in ranked[:LIMIT]]
