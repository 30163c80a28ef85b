"""Loader `tpcds`: a TPC-DS deployment brought up as `tidb_server.main`
brings a server up, with the benchmark's own population
(`datagen/tpcds.py`, made from the seed) bulk-loaded into its catalog,
NULLs included. `loaders/tpch.py`'s surface; the one file that knows how
to reach the program for a TPC-DS configuration.

From the program it takes the bootstrap, the server and the catalog's
bulk-load surface. The data is the benchmark's: the program is handed
the arrays and their validity, the references read them."""

from __future__ import annotations

import importlib.util
import os

import numpy as np

import tidb_tpu  # noqa: F401  (enables x64; the repo wants it before any other JAX use)

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


datagen = _load("bench_datagen_tpcds", os.path.join(os.path.dirname(HERE), "datagen", "tpcds.py"))
# how a run reads the program's flights and compile counter is the TPC-H loader's
_tpch = _load("bench_loaders_tpch_for_tpcds", os.path.join(HERE, "tpch.py"))
flight_rows, keep_flights, compilations = _tpch.flight_rows, _tpch.keep_flights, _tpch.compilations

DATABASE = "tpcds"
PRIMARY_KEYS = {  # the specification's single-column primary keys
    "date_dim": ["d_date_sk"], "customer_address": ["ca_address_sk"], "web_site": ["web_site_sk"],
}


class HostData:
    """The generated columns as plain numpy arrays, for the references."""

    def __init__(self, tables: dict):
        self._tables = tables

    def col(self, table: str, column: str) -> np.ndarray:
        return self._tables[table][column].data

    def valid(self, table: str, column: str) -> np.ndarray:
        """False where the value is NULL."""
        return self._tables[table][column].valid

    def dictionary(self, table: str, column: str) -> np.ndarray:
        """The strings behind a dictionary-coded column's codes."""
        return self._tables[table][column].dictionary

    def width_bytes(self, table: str, column: str) -> int:
        """Bytes one value of the column takes as loaded (codes for strings)."""
        return int(self.col(table, column).dtype.itemsize)


def bulk_load(catalog, tables: dict) -> None:
    from tidb_tpu.chunk import HostBlock, HostColumn
    from tidb_tpu.dtypes import DATE, DECIMAL, INT64, STRING
    from tidb_tpu.storage import TableSchema

    types = {"int": INT64, "dec2": DECIMAL(2), "date": DATE, "str": STRING}
    catalog.create_database(DATABASE, if_not_exists=True)
    for name, columns in tables.items():
        block = HostBlock.from_columns({
            c: HostColumn(types[col.kind], col.data, col.valid, col.dictionary)
            for c, col in columns.items()
        })
        schema = TableSchema([(c, types[col.kind]) for c, col in columns.items()],
                             primary_key=PRIMARY_KEYS.get(name))
        table = catalog.create_table(DATABASE, name, schema)
        table.dictionaries.update(  # sorted already: no merge needed on a new table
            {c: col.dictionary for c, col in columns.items() if col.dictionary is not None})
        table.replace_blocks([block])


def first_key(data: HostData, table: str, key: str, column: str, word: str) -> int:
    """The key of the first row whose `column` is `word`."""
    words = data.dictionary(table, column)
    hit = data.valid(table, column) & (
        words[np.clip(data.col(table, column), 0, len(words) - 1)] == word)
    return int(data.col(table, key)[np.nonzero(hit)[0][0]])


def _money(cents: int) -> str:
    return f"{cents // 100}.{cents % 100:02d}"


class Deployment:
    def __init__(self, config: dict, seed: int, scale_factor: float):
        import tidb_server
        from tidb_tpu.utils.config import Config

        self.config = config
        tables = datagen.generate(scale_factor, seed)
        self.catalog, self.server = tidb_server.bootstrap(Config().override(port=0))
        bulk_load(self.catalog, tables)
        self.data = HostData(tables)

    def row_counts(self) -> dict:
        return {t: int(self.catalog.table(DATABASE, t).nrows) for t in self.config["row_counts"]}

    def start(self) -> int:
        self.server.start_background()
        return self.server.port

    def prelude(self) -> list:
        """Statements a new connection sends first."""
        return [f"use {DATABASE}"]

    def analyze_statements(self) -> list:
        return [f"analyze table {t}" for t in self.config["row_counts"]]

    def write_for_readback(self) -> dict:
        """The acknowledged write of the guarantee check: five web_sales
        rows inside Q95's predicates (shipped in its window to an IL
        address through a `pri` site), onto an order web_returns already
        names, from two warehouses, so that the order is in `ws_wh`
        whatever it was before and both of Q95's sums move. As SQL and
        as the columns the reference appends (`extra`)."""
        data = self.data
        order = int(data.col("web_returns", "wr_order_number")[0])
        address = first_key(data, "customer_address", "ca_address_sk", "ca_state", "IL")
        site = first_key(data, "web_site", "web_site_sk", "web_company_name", "pri")
        ship0 = int(np.datetime64("1999-02-10", "D").astype(np.int64)) + datagen.JULIAN_OF_EPOCH
        top_item = int(data.col("web_sales", "ws_item_sk").max())
        names = list(self.catalog.table(DATABASE, "web_sales").schema.names)
        rows = []
        for i in range(5):
            rows.append({
                "ws_sold_date_sk": ship0 - 30, "ws_sold_time_sk": 1, "ws_ship_date_sk": ship0 + i,
                "ws_item_sk": top_item + 1 + i, "ws_bill_customer_sk": 1, "ws_bill_cdemo_sk": 1,
                "ws_bill_hdemo_sk": 1, "ws_bill_addr_sk": address, "ws_ship_customer_sk": 1,
                "ws_ship_cdemo_sk": 1, "ws_ship_hdemo_sk": 1, "ws_ship_addr_sk": address,
                "ws_web_page_sk": 1, "ws_web_site_sk": site, "ws_ship_mode_sk": 1,
                "ws_warehouse_sk": 1 + i % 2, "ws_promo_sk": 1, "ws_order_number": order,
                "ws_quantity": 10, "ws_wholesale_cost": 50_00, "ws_list_price": 100_00,
                "ws_sales_price": 80_00, "ws_ext_discount_amt": 200_00, "ws_ext_sales_price": 800_00,
                "ws_ext_wholesale_cost": 500_00, "ws_ext_list_price": 1000_00, "ws_ext_tax": 40_00,
                "ws_coupon_amt": 0, "ws_ext_ship_cost": 123_45 + 101 * i, "ws_net_paid": 800_00,
                "ws_net_paid_inc_tax": 840_00, "ws_net_paid_inc_ship": 923_45 + 101 * i,
                "ws_net_paid_inc_ship_tax": 963_45 + 101 * i, "ws_net_profit": 300_00 + 7 * i,
            })
        kinds = {c: data._tables["web_sales"][c].kind for c in names}
        values = ", ".join(
            "(" + ", ".join(_money(r[c]) if kinds[c] == "dec2" else str(r[c]) for c in names) + ")"
            for r in rows)
        extra = {k: [r[k] for r in rows] for k in rows[0]}
        return {"sql": f"insert into web_sales values {values}", "query": "q95", "extra": extra}

    def shutdown(self) -> None:
        self.server.shutdown()
