"""Loader `tpch`: a TPC-H deployment brought up as `tidb_server.main`
brings a server up, with the benchmark's own population (`datagen/tpch.py`,
made from the seed) bulk-loaded into its catalog. This is the one file
that knows how to reach the program for a TPC-H configuration; a
configuration of another benchmark names another loader beside it (see
README.md).

From the program it takes the bootstrap, the server and the catalog's
bulk-load surface. The data is the benchmark's: the program is handed
the arrays, the references read them."""

from __future__ import annotations

import importlib.util
import os

import numpy as np

import tidb_tpu  # noqa: F401  (enables x64; the repo wants it before any other JAX use)

_spec = importlib.util.spec_from_file_location(
    "bench_datagen_tpch",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "datagen", "tpch.py"))
datagen = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(datagen)

DATABASE = "tpch"
PRIMARY_KEYS = {  # the specification's single-column primary keys
    "region": ["r_regionkey"], "nation": ["n_nationkey"], "part": ["p_partkey"],
    "supplier": ["s_suppkey"], "customer": ["c_custkey"], "orders": ["o_orderkey"],
}


class HostData:
    """The generated columns as plain numpy arrays, for the references."""

    def __init__(self, tables: dict):
        self._tables = tables

    def col(self, table: str, column: str) -> np.ndarray:
        return self._tables[table][column].data

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
            c: HostColumn(types[col.kind], col.data, np.ones(len(col.data), bool), col.dictionary)
            for c, col in columns.items()
        })
        schema = TableSchema([(c, types[col.kind]) for c, col in columns.items()],
                             primary_key=PRIMARY_KEYS.get(name))
        table = catalog.create_table(DATABASE, name, schema)
        table.dictionaries.update(  # sorted already: no merge needed on a new table
            {c: col.dictionary for c, col in columns.items() if col.dictionary is not None})
        table.replace_blocks([block])


class Deployment:
    def __init__(self, config: dict, seed: int, scale_factor: float):
        import tidb_server
        from tidb_tpu.utils.config import Config

        self.config = config
        # the configuration's own scale gets its published lineitem count;
        # a rehearsal at another scale takes the count that comes
        target = config["row_counts"]["lineitem"] if scale_factor == config["scale_factor"] else None
        tables = datagen.generate(scale_factor, seed, lineitem_rows=target)
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
        """The acknowledged write of the guarantee check: five lineitem
        rows inside Q6's predicate, as SQL and as the columns a
        reference appends (`extra`), with the query that reads them."""
        shipmode = self.data.dictionary("lineitem", "l_shipmode")[0]
        instruct = self.data.dictionary("lineitem", "l_shipinstruct")[0]
        comment = self.data.dictionary("lineitem", "l_comment")[0]
        top = int(self.data.col("lineitem", "l_orderkey").max())
        ship0 = int(np.datetime64("1994-06-10", "D").astype(np.int64))
        rows = [
            {"l_orderkey": top + 1 + i, "l_quantity": (10 + i) * 100,
             "l_extendedprice": 1000_00 + 37 * i, "l_discount": 5 + i % 3,
             "l_tax": 2, "l_shipdate": ship0 + i}
            for i in range(5)
        ]
        values = ", ".join(
            f"({r['l_orderkey']}, {1 + i}, {1 + i}, 1, {r['l_quantity'] // 100}.00, "
            f"{r['l_extendedprice'] // 100}.{r['l_extendedprice'] % 100:02d}, "
            f"0.0{r['l_discount']}, 0.0{r['l_tax']}, 'N', 'O', '1994-06-1{i}', "
            f"'1994-06-2{i}', '1994-07-0{i + 1}', '{instruct}', '{shipmode}', '{comment}')"
            for i, r in enumerate(rows)
        )
        extra = {k: [r[k] for r in rows] for k in rows[0]}
        return {"sql": f"insert into lineitem values {values}", "query": "q6", "extra": extra}

    def shutdown(self) -> None:
        self.server.shutdown()


def flight_rows() -> list:
    """The program's per-statement spans (obs/flight.py), oldest first."""
    from tidb_tpu.obs.flight import FLIGHT

    return FLIGHT.rows()


def keep_flights(n: int) -> None:
    from tidb_tpu.obs.flight import FLIGHT

    FLIGHT.set_ring_capacity(n)


def compilations() -> float:
    """Programs the engine has jit-compiled so far (its own counter)."""
    from tidb_tpu.utils.metrics import REGISTRY

    name = "tidbtpu_engine_jit_compilations"
    return float(sum(
        v for n, _kind, v in REGISTRY.rows() if n == name or n.startswith(name + "{")
    ))
