"""TPC-DS Q95 (1999-02, IL, pri) over the host columns, from the
query's text: every join is made, as pairs of row numbers, and every
NULL is SQL's (a NULL key joins nothing, a NULL warehouse is in no `<>`
pair, a sum skips NULLs and is NULL over none; an empty qualifying set
gives `0, NULL, NULL`).

The CTE `ws_wh` is the self-join of `web_sales` on the order number
(an order of k items gives k x k pairs) filtered by `<>` on the
warehouse; `IN (select ws_order_number from ws_wh)` keeps the orders
that are in it; the second IN keeps those that `web_returns` joined to
`ws_wh` names. Both sums are DECIMAL(7,2), exact in whole cents.

`precision="float32"` is the control's: every amount held as a float32
of dollars and added one after the other in float32, as an engine
without exact decimals would, then rounded to cents."""

import numpy as np

KINDS = ("int", "dec2", "dec2")
_D0 = int(np.datetime64("1999-02-01", "D").astype(np.int64))
_D1 = _D0 + 60
_READ = ("ws_order_number", "ws_warehouse_sk", "ws_ship_date_sk", "ws_ship_addr_sk",
         "ws_web_site_sk", "ws_ext_ship_cost", "ws_net_profit")


def join_pairs(left, right):
    """Every (left row, right row) whose keys are equal and not NULL.
    `left`, `right`: (values, valid)."""
    (lkey, lvalid), (rkey, rvalid) = left, right
    rrows = np.nonzero(rvalid)[0]
    rrows = rrows[np.argsort(rkey[rrows], kind="stable")]
    sorted_keys = rkey[rrows]
    lrows = np.nonzero(lvalid)[0]
    lo = np.searchsorted(sorted_keys, lkey[lrows], side="left")
    hi = np.searchsorted(sorted_keys, lkey[lrows], side="right")
    counts = hi - lo
    first = np.cumsum(counts) - counts
    li = np.repeat(lrows, counts)
    ri = rrows[np.repeat(lo - first, counts) + np.arange(int(counts.sum()), dtype=np.int64)]
    return li, ri


def _is_in(values, valid, members):
    """`x IN (set)` is TRUE: x is not NULL and equals a member."""
    return valid & np.isin(values, members)


def _sum(cents, valid, precision):
    cents = cents[valid]
    if not len(cents):
        return None
    if precision == "float32":
        dollars = (cents / 100.0).astype(np.float32)
        return int(round(float(np.cumsum(dollars, dtype=np.float32)[-1]) * 100.0))
    if precision != "exact":
        raise ValueError(precision)
    return int(cents.sum(dtype=np.int64))


def expected(data, precision="exact", extra=None):
    def sales(name):
        values, valid = data.col("web_sales", name), data.valid("web_sales", name)
        if extra is not None:
            more = np.asarray(extra[name], dtype=values.dtype)
            values = np.concatenate([values, more])
            valid = np.concatenate([valid, np.ones(len(more), dtype=bool)])
        return values, valid

    ws = {name: sales(name) for name in _READ}
    order, warehouse = ws["ws_order_number"], ws["ws_warehouse_sk"]

    # with ws_wh as (select ws1.ws_order_number, ... where ws1.ws_order_number =
    # ws2.ws_order_number and ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
    i1, i2 = join_pairs(order, order)
    differ = (warehouse[1][i1] & warehouse[1][i2]
              & (warehouse[0][i1] != warehouse[0][i2]))
    ws_wh_order = order[0][i1[differ]]  # ws_wh.ws_order_number, one a pair
    ws_wh = (ws_wh_order, np.ones(len(ws_wh_order), dtype=bool))

    # ws_order_number in (select ws_order_number from ws_wh)
    keep = _is_in(order[0], order[1], ws_wh_order)
    # ... in (select wr_order_number from web_returns, ws_wh
    #         where wr_order_number = ws_wh.ws_order_number)
    wr = (data.col("web_returns", "wr_order_number"), data.valid("web_returns", "wr_order_number"))
    wi, _ = join_pairs(wr, ws_wh)
    keep &= _is_in(order[0], order[1], wr[0][wi])

    def dimension(table, key, column, test):
        values, valid = data.col(table, column), data.valid(table, column)
        rows = valid & test(values, table, column)
        return (data.col(table, key)[rows], data.valid(table, key)[rows])

    def equals(word):
        def test(values, table, column):
            words = data.dictionary(table, column)
            return words[np.clip(values, 0, len(words) - 1)] == word
        return test

    for fact, dim in (
        ("ws_ship_date_sk", dimension("date_dim", "d_date_sk", "d_date",
                                      lambda v, *_: (v >= _D0) & (v <= _D1))),
        ("ws_ship_addr_sk", dimension("customer_address", "ca_address_sk", "ca_state", equals("IL"))),
        ("ws_web_site_sk", dimension("web_site", "web_site_sk", "web_company_name", equals("pri"))),
    ):
        rows = np.nonzero(keep)[0]
        li, _ = join_pairs((ws[fact][0][rows], ws[fact][1][rows]), dim)
        # the dimension's key is its primary key: a row joins once at most
        keep = np.zeros_like(keep)
        keep[rows[li]] = True
        if len(li) != int(keep.sum()):
            raise AssertionError(f"{fact}: the dimension's key is not unique")

    if not keep.any():
        return [(0, None, None)]
    return [(
        len(np.unique(order[0][keep])),
        _sum(ws["ws_ext_ship_cost"][0][keep], ws["ws_ext_ship_cost"][1][keep], precision),
        _sum(ws["ws_net_profit"][0][keep], ws["ws_net_profit"][1][keep], precision),
    )]
