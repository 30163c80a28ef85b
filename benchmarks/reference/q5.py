"""TPC-H Q5 (ASIA, 1994) over the host columns: dense keys make every
join an array lookup. The revenue is an exact integer sum at scale 4."""

import numpy as np

from _sums import product, total

KINDS = ("str", "wide4")
_D0 = int(np.datetime64("1994-01-01", "D").astype(np.int64))
_D1 = int(np.datetime64("1995-01-01", "D").astype(np.int64))


def _lookup(keys, values, size, fill):
    table = np.full(size, fill, dtype=np.int64)
    table[keys] = values
    return table


def expected(data, precision="exact"):
    col = data.col
    r_names = data.dictionary("region", "r_name")
    asia_regions = col("region", "r_regionkey")[r_names[col("region", "r_name")] == "ASIA"]
    n_key, n_region = col("nation", "n_nationkey"), col("nation", "n_regionkey")
    n_names = data.dictionary("nation", "n_name")[col("nation", "n_name")]
    nation_in = _lookup(n_key, np.isin(n_region, asia_regions), int(n_key.max()) + 1, 0).astype(bool)

    c_key = col("customer", "c_custkey")
    cust_nation = _lookup(c_key, col("customer", "c_nationkey"), int(c_key.max()) + 1, -1)
    s_key = col("supplier", "s_suppkey")
    supp_nation = _lookup(s_key, col("supplier", "s_nationkey"), int(s_key.max()) + 1, -2)

    o_key, o_date = col("orders", "o_orderkey"), col("orders", "o_orderdate")
    in_year = (o_date >= _D0) & (o_date < _D1)
    l_okey, l_skey = col("lineitem", "l_orderkey"), col("lineitem", "l_suppkey")
    size = int(max(o_key.max(), l_okey.max())) + 1
    order_cust = _lookup(o_key[in_year], col("orders", "o_custkey")[in_year], size, 0)
    cn = cust_nation[order_cust[l_okey]]  # customer key 0 is no customer: nation -1
    sn = supp_nation[np.clip(l_skey, 0, len(supp_nation) - 1)]
    m = (cn == sn) & (sn >= 0)
    m &= nation_in[np.clip(sn, 0, len(nation_in) - 1)]
    revenue = product(col("lineitem", "l_extendedprice")[m],
                      100 - col("lineitem", "l_discount")[m], precision)
    nation = sn[m]
    rows = []
    for key, name in zip(n_key, n_names):
        g = nation == key
        if g.any():
            rows.append((str(name), total(revenue[g], precision)))
    if not rows:
        raise AssertionError("Q5's reference is empty: the comparison would be vacuous")
    return sorted(rows, key=lambda t: -t[1])
