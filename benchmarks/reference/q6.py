"""TPC-H Q6 at the validation values (1994, discount 0.06 +- 0.01,
quantity < 24) over the host columns: scaled integers, exact."""

import numpy as np

from _sums import product, total

KINDS = ("dec4",)
_D0 = int(np.datetime64("1994-01-01", "D").astype(np.int64))
_D1 = int(np.datetime64("1995-01-01", "D").astype(np.int64))


def expected(data, precision="exact", extra=None):
    """`extra`: rows a write added, as {column: array}, appended to the
    loaded columns (the read-back check)."""

    def col(name):
        base = data.col("lineitem", name)
        return base if extra is None else np.concatenate([base, np.asarray(extra[name], base.dtype)])

    ship, disc, qty = col("l_shipdate"), col("l_discount"), col("l_quantity")
    m = (ship >= _D0) & (ship < _D1) & (disc >= 5) & (disc <= 7) & (qty < 2400)
    return [(total(product(col("l_extendedprice")[m], disc[m], precision), precision),)]
