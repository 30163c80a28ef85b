"""TPC-DS subset: the tables Q95 exercises + a TOY generator.

Reference ladder config #5 (BASELINE.md): TPC-DS Q95 — correlated
subqueries + multi-join over web_sales / web_returns / date_dim /
customer_address / web_site. `load_tpcds` is a toy population for the
repo's own tests and `bench.py`, not the specification's: 72,000
`web_sales` rows a scale factor where Table 3-2 has 719,384, about 3
items an order where dsdgen draws 8 to 16 (the self-join of Q95 expands
x3 here and x12.5 there), seven of the 34 columns, a 400-day `date_dim`,
no NULL anywhere. Correctness is checked against a numpy oracle over the
SAME generated data. The specification's population, its plain
reference and the benchmark cell `tpcds_sf1.q95` live under
`benchmarks/` (`datagen/tpcds.py`, `reference/q95.py`;
`tests/test_tpcds_deployment.py`).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from tidb_tpu.chunk import HostBlock, HostColumn
from tidb_tpu.dtypes import DATE, DECIMAL, INT64, STRING, date_to_days
from tidb_tpu.storage import Catalog, TableSchema

_STATES = ["IL", "CA", "TX", "NY", "WA", "GA", "OH", "MI"]
_COMPANIES = ["pri", "ese", "anti", "ought", "able", "cally"]


def _col_i(vals):
    a = np.asarray(vals, dtype=np.int64)
    return HostColumn(INT64, a, np.ones(len(a), dtype=bool))


def _col_dec(vals, scale=2):
    a = np.round(np.asarray(vals, dtype=np.float64) * 10**scale).astype(np.int64)
    return HostColumn(DECIMAL(scale), a, np.ones(len(a), dtype=bool))


def _col_s(vals):
    from tidb_tpu.chunk import encode_strings

    return encode_strings([str(v) for v in vals])


def _col_d(days):
    a = np.asarray(days, dtype=np.int32)
    return HostColumn(DATE, a, np.ones(len(a), dtype=bool))


def load_tpcds(catalog: Catalog, sf: float = 0.01, seed: int = 7) -> Dict[str, int]:
    """Populate the Q95 table subset at roughly `sf` scale (web_sales
    ~ 72k rows/sf). Returns per-table row counts."""
    rng = np.random.default_rng(seed)
    n_sales = max(int(72_000 * sf), 500)
    n_orders = max(n_sales // 3, 50)  # ~3 line items per order
    n_addr = max(int(1000 * sf * 50), 100)
    n_sites = 12
    n_dates = 400  # covers 1999 H1 + slack
    d0 = int(date_to_days("1999-01-01"))

    counts = {}

    def put(name, schema_cols, cols, pk=None):
        t = catalog.create_table(
            "test", name, TableSchema(schema_cols, primary_key=pk),
            if_not_exists=False,
        )
        t.append_block(HostBlock.from_columns(cols))
        counts[name] = t.nrows

    # date_dim: d_date_sk is days since a base; d_date the DATE value
    put(
        "date_dim",
        [("d_date_sk", INT64), ("d_date", DATE)],
        {
            "d_date_sk": _col_i(np.arange(n_dates) + 1000),
            "d_date": _col_d(d0 - 30 + np.arange(n_dates)),
        },
        pk=["d_date_sk"],
    )

    put(
        "customer_address",
        [("ca_address_sk", INT64), ("ca_state", STRING)],
        {
            "ca_address_sk": _col_i(np.arange(n_addr)),
            "ca_state": _col_s(rng.choice(_STATES, n_addr)),
        },
        pk=["ca_address_sk"],
    )

    put(
        "web_site",
        [("web_site_sk", INT64), ("web_company_name", STRING)],
        {
            "web_site_sk": _col_i(np.arange(n_sites)),
            "web_company_name": _col_s(
                [_COMPANIES[i % len(_COMPANIES)] for i in range(n_sites)]
            ),
        },
        pk=["web_site_sk"],
    )

    order_no = rng.integers(0, n_orders, n_sales)
    # most orders ship from one warehouse; ~25% of rows get a second
    wh_of_order = rng.integers(0, 5, n_orders)
    warehouse = wh_of_order[order_no].copy()
    multi = rng.random(n_sales) < 0.25
    warehouse[multi] = (warehouse[multi] + 1 + rng.integers(0, 3, multi.sum())) % 6
    put(
        "web_sales",
        [
            ("ws_order_number", INT64), ("ws_warehouse_sk", INT64),
            ("ws_ship_date_sk", INT64), ("ws_ship_addr_sk", INT64),
            ("ws_web_site_sk", INT64), ("ws_ext_ship_cost", DECIMAL(2)),
            ("ws_net_profit", DECIMAL(2)),
        ],
        {
            "ws_order_number": _col_i(order_no),
            "ws_warehouse_sk": _col_i(warehouse),
            "ws_ship_date_sk": _col_i(rng.integers(1000, 1000 + n_dates, n_sales)),
            "ws_ship_addr_sk": _col_i(rng.integers(0, n_addr, n_sales)),
            "ws_web_site_sk": _col_i(rng.integers(0, n_sites, n_sales)),
            "ws_ext_ship_cost": _col_dec(rng.uniform(1, 200, n_sales)),
            "ws_net_profit": _col_dec(rng.uniform(-100, 300, n_sales)),
        },
    )

    n_ret = max(n_sales // 6, 30)
    put(
        "web_returns",
        [("wr_order_number", INT64)],
        {"wr_order_number": _col_i(rng.integers(0, n_orders, n_ret))},
    )
    return counts


def _specification_q95() -> str:
    """The one Q95 text of the repo: `benchmarks/queries/q95.sql`, the
    specification's query95.tpl at its qualification values (MySQL's
    dialect only where it differs: backtick aliases, `+ interval 60
    day`). It runs over this toy schema as over the specification's."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmarks", "queries", "q95.sql")) as f:
        return f.read()


Q95_SQL = _specification_q95()


def numpy_q95(catalog: Catalog):
    """Oracle over the generated blocks (pure numpy)."""

    def arr(table, col):
        t = catalog.table("test", table)
        b = t.blocks()[0]
        c = b.columns[col]
        if c.dictionary is not None:
            return c.dictionary[np.clip(c.data, 0, len(c.dictionary) - 1)]
        return c.data

    ws_order = arr("web_sales", "ws_order_number").astype(np.int64)
    ws_wh = arr("web_sales", "ws_warehouse_sk").astype(np.int64)
    ws_date = arr("web_sales", "ws_ship_date_sk").astype(np.int64)
    ws_addr = arr("web_sales", "ws_ship_addr_sk").astype(np.int64)
    ws_site = arr("web_sales", "ws_web_site_sk").astype(np.int64)
    ws_cost = arr("web_sales", "ws_ext_ship_cost").astype(np.int64)  # scaled
    ws_profit = arr("web_sales", "ws_net_profit").astype(np.int64)

    # ws_wh: orders shipping from >1 warehouse
    import collections

    whs = collections.defaultdict(set)
    for o, w in zip(ws_order, ws_wh):
        whs[int(o)].add(int(w))
    multi_orders = {o for o, s in whs.items() if len(s) > 1}

    wr_orders = set(arr("web_returns", "wr_order_number").astype(np.int64).tolist())
    returned_multi = multi_orders & wr_orders

    d_sk = arr("date_dim", "d_date_sk").astype(np.int64)
    d_date = arr("date_dim", "d_date").astype(np.int64)
    lo = date_to_days("1999-02-01")
    hi = lo + 60
    ok_sk = set(d_sk[(d_date >= lo) & (d_date <= hi)].tolist())

    ca_sk = arr("customer_address", "ca_address_sk").astype(np.int64)
    ca_state = arr("customer_address", "ca_state")
    il = set(ca_sk[ca_state == "IL"].tolist())

    site_sk = arr("web_site", "web_site_sk").astype(np.int64)
    company = arr("web_site", "web_company_name")
    pri = set(site_sk[company == "pri"].tolist())

    mask = np.array(
        [
            (int(d) in ok_sk) and (int(a) in il) and (int(s) in pri)
            and (int(o) in multi_orders) and (int(o) in returned_multi)
            for d, a, s, o in zip(ws_date, ws_addr, ws_site, ws_order)
        ]
    )
    if not mask.any():
        return (0, None, None)
    cnt = len(set(ws_order[mask].tolist()))
    return (
        cnt,
        round(float(ws_cost[mask].sum()) / 100, 2),
        round(float(ws_profit[mask].sum()) / 100, 2),
    )
