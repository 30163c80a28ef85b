"""The deployment `tpch_sf1_q18` at a small size, on the CPU: the served
TPC-H Q18 against its plain reference (benchmarks/reference/q18.py) on
three seeds, one program for every data set, the cell's rehearsal, what
the sorted group-bys leave on the statement's flight and in the
registry, the group key the planner narrows, and answers that
`checks.py` must judge not correct. The whole file runs in about a
minute."""

import hashlib
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _path in (os.path.join(BENCH, "reference"), BENCH):  # the references' own helpers
    if _path not in sys.path:
        sys.path.insert(0, _path)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


loader = _load("loaders/tpch.py", "t18_loader_tpch")
reference = _load("reference/q18.py", "t18_reference_q18")
checks = _load("checks.py", "t18_checks")
with open(os.path.join(BENCH, "configs", "tpch_sf1_q18.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "queries", "q18.sql")) as f:
    Q18 = " ".join(f.read().split())
SF, SEEDS = 0.05, (1, 2, 2**31 + 38)


def serve(seed, sf=SF):
    """(host data, session) of one population, loaded as the benchmark
    loads it and ANALYZEd."""
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog

    tables = loader.datagen.generate(sf, seed)
    catalog = Catalog()
    loader.bulk_load(catalog, tables)
    session = Session(catalog, db=loader.DATABASE)
    for table in tables:
        session.execute(f"analyze table {table}")
    return loader.HostData(tables), session


@pytest.fixture(scope="module")
def served():
    return [serve(seed) for seed in SEEDS]


def wire_rows(result):
    """A result's rows as the text protocol carries them."""
    return [tuple(None if v is None else str(v) for v in row) for row in result.rows]


def judged(rows, want):
    tally = checks.Tally()
    tally.answers += 1
    checks.judge_rows(reference.KINDS, rows, want, tally)
    return tally


# ---- the served statement ---------------------------------------------


def test_the_configuration_is_tpch_sf1s_rows_with_q18s_guarantees():
    with open(os.path.join(BENCH, "configs", "tpch_sf1.json")) as f:
        sf1 = json.load(f)
    assert CONFIG["row_counts"] == sf1["row_counts"] and CONFIG["scale_factor"] == 1
    assert CONFIG["chips"] == 1 and CONFIG["reduced"] == ["scale_factor"]
    assert len(CONFIG["source"]) <= 200 and "2.4.18" in CONFIG["source"]
    assert reference.KINDS == ("str", "int", "int", "str", "dec2", "dec2")


@pytest.mark.parametrize("which", [0, 1, 2])
def test_served_q18_is_the_references_answer_cell_for_cell(served, which):
    data, session = served[which]
    want = reference.expected(data)
    assert 0 < len(want) <= 100
    tally = judged(wire_rows(session.execute(Q18)), want)
    assert tally.correct() and tally.values["cells_wrong"] == 0, tally.first_wrong


def test_a_cent_off_a_dropped_order_and_a_stale_read_are_not_correct(served):
    data, session = served[0]
    want = reference.expected(data)
    rows = wire_rows(session.execute(Q18))
    assert judged(rows, want).correct()
    # one order's total price a cent off
    off = list(rows)
    price = off[1][4]
    off[1] = off[1][:4] + (price[:-1] + ("1" if price[-1] != "1" else "2"),) + off[1][5:]
    tally = judged(off, want)
    assert not tally.correct() and tally.values["cells_wrong"] == 1
    # one qualifying order dropped: every later row is another order's
    tally = judged(rows[:1] + rows[2:], want)
    assert not tally.correct() and tally.values["cells_wrong"] >= len(reference.KINDS)
    # the read-back answered from the snapshot before the write
    q6 = _load("reference/q6.py", "t18_reference_q6")
    extra = {"l_quantity": [1000], "l_extendedprice": [1000_00], "l_discount": [6],
             "l_shipdate": [int(np.datetime64("1994-06-10", "D").astype(np.int64))]}
    stale = checks.Tally()
    stale.answers += 1
    checks.judge_rows(q6.KINDS, checks.render_rows(q6.KINDS, q6.expected(data)),
                      q6.expected(data, extra=extra), stale, "readback_wrong")
    assert not stale.correct() and stale.values["readback_wrong"] >= 1


# ---- one program for every data set -----------------------------------


def test_two_seeds_share_their_programs():
    """Nothing of a data set's values is baked into Q18's programs: two
    populations of one size lower to the same programs, list for list
    (the packed key's bias was -min(o_totalprice): PERF.md PR 35)."""
    import jax

    from tidb_tpu.planner import physical

    hashes = {}  # seed -> the programs' hashes, in the order compiled
    real = physical.watched_jit

    def hashing(seed):
        def wj(fn, sig=None, **kw):
            inner = real(fn, sig=sig, **kw)

            def call(*a, **k):
                text = jax.jit(fn).lower(*a, **k).as_text()
                hashes.setdefault(seed, []).append(
                    (sig[0], hashlib.sha256(text.encode()).hexdigest()))
                return inner(*a, **k)

            return call
        return wj

    try:
        for seed in (1, 2):
            _data, session = serve(seed)
            physical.watched_jit = hashing(seed)  # after ANALYZE's programs
            session.execute(Q18)
            session.execute(Q18)
    finally:
        physical.watched_jit = real
    assert hashes[1] == hashes[2]
    # at most two whole programs a data set, none of them a discovery
    compiled = list(dict.fromkeys(hashes[1]))
    assert len(compiled) <= 2 and {kind for kind, _h in compiled} == {"steady"}


def test_key_widths_do_not_follow_a_data_sets_own_bounds():
    from tidb_tpu.expression.expr import ColumnRef
    from tidb_tpu.dtypes import INT64
    from tidb_tpu.planner.physical import _BOUNDS_PREFIX, _key_width

    col = ColumnRef(type=INT64, name="t.c")

    def width(lo, hi):
        return _key_width(col, {_BOUNDS_PREFIX + "t.c": (lo, hi)})

    # two seeds' o_totalprice at SF 0.05: one width, one bias
    assert width(86891, 52_000_000) == width(87076, 51_900_000) == (26, 0)
    # a surrogate key from 1: as before
    assert width(1, 6_000_000) == (23, 0)
    # every value still fits: value + bias + 1 in [1, 2**w - 1]
    for lo, hi in ((86891, 52_000_000), (-5, 3), (1000, 1003), (7, 7), (-(2**39), 2**39)):
        got = width(lo, hi)
        if got is not None:
            w, bias = got
            assert 1 <= lo + bias + 1 and hi + bias + 1 <= (1 << w) - 1, (lo, hi, got)
            assert w <= (hi - lo + 1).bit_length() + 1


# ---- the narrowed group key -------------------------------------------


def test_q18s_outer_group_by_sorts_the_order_key_alone(served):
    """o_orderkey determines the four other keys (orders' primary key,
    the join's equality, customer's primary key): they are read off a
    group's first row (planner/logical.py narrow_group_keys)."""
    _data, session = served[0]
    plan = "\n".join(r[0] for r in session.execute("explain " + Q18).rows)
    assert "Aggregate groups=['_g2'] aggs=['sum(_a0)', 'first(_g0)', 'first(_g1)'" in plan
    # a key from a table without a primary key determines nothing
    plan = "\n".join(r[0] for r in session.execute(
        "explain select l_orderkey, l_linenumber, count(*) from lineitem "
        "group by l_orderkey, l_linenumber").rows)
    assert "groups=['_g0', '_g1']" in plan
    # the narrowed group-by answers as the full one does
    got = session.execute(
        "select o_orderkey, o_orderdate, c_name, count(*) from orders, customer, lineitem "
        "where o_custkey = c_custkey and l_orderkey = o_orderkey and o_orderkey < 200 "
        "group by o_orderkey, o_orderdate, c_name order by o_orderkey").rows
    want = session.execute(
        "select o_orderkey, min(o_orderdate), min(c_name), count(*) from orders, customer, lineitem "
        "where o_custkey = c_custkey and l_orderkey = o_orderkey and o_orderkey < 200 "
        "group by o_orderkey order by o_orderkey").rows
    assert len(got) > 10 and [tuple(r) for r in got] == [tuple(r) for r in want]


# ---- spans and counters of the sorted group-by ------------------------


def test_flight_and_registry_count_the_sorted_group_bys_of_one_q18(served):
    from tidb_tpu.obs.flight import FLIGHT
    from tidb_tpu.utils.metrics import REGISTRY

    def counter(name):
        return sum(v for n, _kind, v in REGISTRY.rows() if n == name)

    data, session = served[1]
    session.execute(Q18)  # steady by now: one program a statement
    names = ("tidbtpu_executor_sorted_groupings_total", "tidbtpu_executor_sorted_group_rows_total")
    before = {n: counter(n) for n in names}
    session.execute(Q18)
    flight = FLIGHT.rows()[-1]
    l_key = data.col("lineitem", "l_orderkey")
    sums = np.bincount(l_key, weights=data.col("lineitem", "l_quantity")).astype(np.int64)
    qualifying = np.nonzero(sums > 300_00)[0]
    lines_of_qualifying = int(np.isin(l_key, qualifying).sum())
    # the plan has two sorted aggregates: every line by its order, then
    # the qualifying orders' lines by the order again
    assert flight["sorted_groupings"] == 2
    assert flight["sorted_group_rows"] == len(l_key) + lines_of_qualifying
    assert flight["sorted_groups"] == len(np.unique(l_key)) + len(qualifying)
    assert flight["sorted_groups"] <= flight["sorted_group_slots"] < 4 * flight["sorted_groups"]
    assert counter(names[1]) - before[names[1]] == flight["sorted_group_rows"]
    # counted while a program is traced: the steady statement traces none
    assert counter(names[0]) == before[names[0]] >= 2


def test_the_sorted_group_by_opens_its_two_scopes_inside_the_operators():
    import jax

    from tidb_tpu.chunk import Batch, DevCol
    from tidb_tpu.executor import AggDesc, group_aggregate

    n = 4096
    keys = jax.numpy.arange(n, dtype=jax.numpy.int64) % 1000
    ones = jax.numpy.ones(n, dtype=bool)
    batch = Batch({"k": DevCol(keys, ones), "v": DevCol(keys, ones)}, ones)

    def agg(b):
        with jax.named_scope("Aggregate#1"):
            return group_aggregate(
                b, [lambda x: x.cols["k"]], [AggDesc("sum", lambda x: x.cols["v"], "s")],
                1024, key_names=["k"], key_widths=[(11, 0)])

    text = jax.jit(agg).lower(batch).as_text(debug_info=True)
    assert "Aggregate#1/group/sort/" in text and "Aggregate#1/group/reduce/" in text
    out, ngroups = jax.jit(agg)(batch)
    assert int(ngroups) == 1000 and int(out.cols["s"].data[3]) == 3 * 5  # rows 3, 1003, ... 4003


# ---- the cell's rehearsal ---------------------------------------------


def test_the_benchmarks_rehearsal_of_the_cell_is_correct():
    """`run.py --workload tpch_sf1_q18.q18 --rehearse-cpu-sf 0.01`, the
    whole command on the CPU: served, judged, read back."""
    import run as harness

    from tidb_tpu.obs.flight import FLIGHT

    args = harness.parse_args(["--workload", "tpch_sf1_q18.q18", "--seed", "1", "--seconds", "1",
                               "--trace", "1", "--rehearse-cpu-sf", "0.01"])
    try:
        result, judged_ = harness.run_cell(args)
    finally:
        FLIGHT.set_ring_capacity(256)  # the harness keeps a whole run's flights
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["cells_wrong"] == [0, 0]
    assert result["checks"]["readback_wrong"] == [0, 0]
    assert {"q18_ms", "sorted_group_rows_per_stmt", "sorted_group_fill_pct"} <= set(
        result["rehearsed_metrics"])
    assert list(judged_["statements"]) == ["q18"] and judged_["write"]["query"] == "q6"
