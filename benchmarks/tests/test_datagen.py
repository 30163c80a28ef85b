"""The benchmark's own TPC-H population: what clause 4.2.3 fixes, kept."""

import importlib.util
import os

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location("datagen_tpch", os.path.join(BENCH, "datagen", "tpch.py"))
datagen = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(datagen)


@pytest.fixture(scope="module")
def tables():
    return datagen.generate(0.02, 2**31 + 17, lineitem_rows=120_100)


def col(tables, table, column):
    return tables[table][column].data


def test_cardinalities_and_the_lineitem_target(tables):
    counts = {t: len(next(iter(c.values())).data) for t, c in tables.items()}
    assert counts == {"region": 5, "nation": 25, "supplier": 200, "part": 4000, "partsupp": 16000,
                      "customer": 3000, "lineitem": 120_100, "orders": 30_000}
    for columns in tables.values():
        assert len({len(c.data) for c in columns.values()}) == 1


def test_orders_have_one_to_seven_lines_on_sparse_keys_and_no_third_customer(tables):
    keys, lines = np.unique(col(tables, "lineitem", "l_orderkey"), return_counts=True)
    assert np.array_equal(keys, col(tables, "orders", "o_orderkey"))
    assert lines.min() == 1 and lines.max() == 7 and np.bincount(lines)[1:].min() > 3500
    assert (keys % 32 < 8).all()
    starts = np.cumsum(lines) - lines
    assert np.array_equal(col(tables, "lineitem", "l_linenumber")[starts], np.ones(len(keys)))
    assert (col(tables, "orders", "o_custkey") % 3 != 0).all()


def test_values_derived_as_the_specification_derives_them(tables):
    li = {c: v.data for c, v in tables["lineitem"].items()}
    retail = dict(zip(col(tables, "part", "p_partkey").tolist(), col(tables, "part", "p_retailprice").tolist()))
    assert retail[1] == 90100 and retail[4000] == 90000 + 400 + 0
    head = slice(0, 500)
    assert li["l_extendedprice"][head].tolist() == [
        q // 100 * retail[p] for q, p in zip(li["l_quantity"][head].tolist(), li["l_partkey"][head].tolist())]
    pairs = set(zip(col(tables, "partsupp", "ps_partkey").tolist(), col(tables, "partsupp", "ps_suppkey").tolist()))
    assert set(zip(li["l_partkey"].tolist(), li["l_suppkey"].tolist())) <= pairs
    charged = li["l_extendedprice"] * (100 - li["l_discount"]) // 100 * (100 + li["l_tax"]) // 100
    _keys, first = np.unique(li["l_orderkey"], return_index=True)
    assert np.array_equal(np.add.reduceat(charged, first), col(tables, "orders", "o_totalprice"))
    assert ((li["l_shipdate"] > datagen.CURRENT) == (tables["lineitem"]["l_linestatus"].dictionary[
        li["l_linestatus"]] == "O")).all()
    flags = tables["lineitem"]["l_returnflag"].dictionary[li["l_returnflag"]]
    assert ((li["l_receiptdate"] > datagen.CURRENT) == (flags == "N")).all()
    assert ((li["l_receiptdate"] - li["l_shipdate"]) >= 1).all()


def test_dictionaries_are_sorted_and_codes_in_range(tables):
    for columns in tables.values():
        for column in columns.values():
            if column.kind == "str":
                words = column.dictionary.tolist()
                assert words == sorted(words) and column.data.dtype == np.int32
                assert 0 <= column.data.min() and column.data.max() < len(words)


def test_the_seed_makes_the_data():
    a, b, c = (datagen.generate(0.01, s) for s in (3_000_000_001, 3_000_000_001, 3_000_000_002))
    same = lambda x, y: all(np.array_equal(x[t][k].data, y[t][k].data) for t in x for k in x[t])
    assert same(a, b) and len(a["lineitem"]["l_orderkey"].data) != len(c["lineitem"]["l_orderkey"].data)
