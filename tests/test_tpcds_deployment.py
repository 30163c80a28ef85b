"""The deployment `tpcds_sf1` at a small size, on the CPU: the
population's shapes (benchmarks/datagen/tpcds.py), the served Query 95
against its plain reference (benchmarks/reference/q95.py), the reference
against a brute-force evaluation of the query's text, NULLs that decide
rows, the empty answer, and what the expanding joins leave on the
statement's flight and in the registry. The whole file runs in under a
minute."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


datagen = _load("datagen/tpcds.py", "t_datagen_tpcds")
reference = _load("reference/q95.py", "t_reference_q95")
loader = _load("loaders/tpcds.py", "t_loader_tpcds")
with open(os.path.join(BENCH, "configs", "tpcds_sf1.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "queries", "q95.sql")) as f:
    Q95 = " ".join(f.read().split())
SF, SEED = 0.02, 2**31 + 23


@pytest.fixture(scope="module")
def tables():
    return datagen.generate(SF, SEED)


def col(tables, table, column):
    return tables[table][column]


# ---- the population ---------------------------------------------------


def test_sf1_counts_come_from_the_generators_arithmetic_and_are_the_configurations():
    size = datagen.sizes(1)
    assert {t: size[t] for t in CONFIG["row_counts"] if t != "web_returns"} == {
        "web_sales": 719_384, "date_dim": 73_049, "customer_address": 50_000, "web_site": 30}
    assert datagen.ROWS_SF1 == CONFIG["row_counts"]
    # 60,000 orders of 8-16 items: the published count is within a per cent of the mean
    assert abs(size["orders"] * 12 - 719_384) < 7_200
    assert round(datagen.RETURN_SHARE * 719_384) == 71_763


def test_every_column_of_the_five_tables_at_its_published_type(tables):
    assert {t: len(c) for t, c in tables.items()} == {
        "web_sales": 34, "web_returns": 24, "date_dim": 28, "customer_address": 13, "web_site": 26}
    for columns in tables.values():
        assert len({len(c.data) for c in columns.values()}) == 1
        for c in columns.values():
            assert c.kind in ("int", "dec2", "date", "str") and c.valid.dtype == bool
            assert len(c.valid) == len(c.data) and not c.data[~c.valid].any()
    assert col(tables, "web_sales", "ws_ext_ship_cost").kind == "dec2"
    assert col(tables, "date_dim", "d_date").kind == "date"
    assert col(tables, "customer_address", "ca_state").kind == "str"


def test_orders_have_8_to_16_items_uniform_each_item_once(tables):
    order = col(tables, "web_sales", "ws_order_number").data
    orders, items = np.unique(order, return_counts=True)
    assert len(orders) == datagen.sizes(SF)["orders"] and orders[0] == 1
    assert items.min() == 8 and items.max() == 16
    assert np.bincount(items)[8:].min() > len(orders) / 9 * 0.7
    pair = order * 10**6 + col(tables, "web_sales", "ws_item_sk").data
    assert len(np.unique(pair)) == len(pair)


def test_warehouse_site_and_ship_date_are_per_item_the_address_per_order(tables):
    ws = tables["web_sales"]
    order = ws["ws_order_number"].data

    def distinct_per_order(name):
        c = ws[name]
        pairs = np.unique(np.stack([order[c.valid], c.data[c.valid]]), axis=1)
        return np.bincount(pairs[0])[1:]

    assert distinct_per_order("ws_warehouse_sk").mean() > 3  # 5 warehouses over 8-16 items
    assert distinct_per_order("ws_web_site_sk").mean() > 5
    assert distinct_per_order("ws_ship_date_sk").mean() > 7
    assert distinct_per_order("ws_ship_addr_sk").max() == 1
    assert distinct_per_order("ws_sold_date_sk").max() == 1
    assert set(np.unique(ws["ws_warehouse_sk"].data[ws["ws_warehouse_sk"].valid])) == {1, 2, 3, 4, 5}


def test_null_shares_and_the_keys_that_are_never_null(tables):
    for table, never in (("web_sales", ("ws_item_sk", "ws_order_number")),
                         ("web_returns", ("wr_item_sk", "wr_order_number"))):
        for name, c in tables[table].items():
            share = 1 - c.valid.mean()
            if name in never:
                assert share == 0, name
            else:
                assert 0.03 < share < 0.06, (name, share)
    assert all(c.valid.all() for c in tables["date_dim"].values())
    assert tables["customer_address"]["ca_address_sk"].valid.all()
    assert 0.02 < 1 - tables["customer_address"]["ca_state"].valid.mean() < 0.07


def test_dates_are_julian_day_numbers_and_ships_follow_sales_by_1_to_120_days(tables):
    dd, ws = tables["date_dim"], tables["web_sales"]
    assert dd["d_date_sk"].data[0] == 2_415_022 and len(dd["d_date_sk"].data) == 73_049
    assert str(dd["d_date"].data[0].astype("datetime64[D]")) == "1900-01-02"
    assert str(dd["d_date"].data[-1].astype("datetime64[D]")) == "2100-01-01"
    assert np.array_equal(dd["d_date_sk"].data - datagen.JULIAN_OF_EPOCH, dd["d_date"].data)
    at = np.searchsorted(dd["d_date"].data, np.datetime64("1999-02-01", "D").astype(np.int64))
    assert (dd["d_year"].data[at], dd["d_moy"].data[at], dd["d_dom"].data[at]) == (1999, 2, 1)
    both = ws["ws_sold_date_sk"].valid & ws["ws_ship_date_sk"].valid
    lag = ws["ws_ship_date_sk"].data[both] - ws["ws_sold_date_sk"].data[both]
    assert lag.min() == 1 and lag.max() == 120
    sold = ws["ws_sold_date_sk"].data[ws["ws_sold_date_sk"].valid] - datagen.JULIAN_OF_EPOCH
    years = sold.astype("datetime64[D]").astype("datetime64[Y]").astype(int) + 1970
    assert set(np.unique(years)) == {1998, 1999, 2000, 2001, 2002}


def test_keys_join_and_a_return_names_a_sale(tables):
    ws, wr = tables["web_sales"], tables["web_returns"]
    for fact, dim, key in (("ws_ship_date_sk", "date_dim", "d_date_sk"),
                           ("ws_ship_addr_sk", "customer_address", "ca_address_sk"),
                           ("ws_web_site_sk", "web_site", "web_site_sk")):
        c = ws[fact]
        assert np.isin(c.data[c.valid], tables[dim][key].data).all(), fact
    assert abs(len(wr["wr_item_sk"].data) / len(ws["ws_item_sk"].data) - 0.0998) < 0.001
    sale = ws["ws_order_number"].data * 10**6 + ws["ws_item_sk"].data
    back = wr["wr_order_number"].data * 10**6 + wr["wr_item_sk"].data
    assert np.isin(back, sale).all() and len(np.unique(back)) == len(back)


def test_company_names_and_states_are_dsdgens(tables):
    site, ca = tables["web_site"], tables["customer_address"]
    names = site["web_company_name"]
    assert set(names.dictionary) == set(datagen.SYLLABLES)
    assert "pri" in set(names.dictionary[names.data[names.valid]])
    states = ca["ca_state"]
    seen = states.dictionary[states.data[states.valid]]
    share = {s: (seen == s).mean() for s in ("TX", "IL", "DE")}
    assert share["TX"] > share["IL"] > share["DE"]  # by the states' counties: 254, 102, 3
    assert abs(share["IL"] - 102 / 3141) < 0.02


# ---- the reference, against the query's text --------------------------


class Columns:
    """loaders/tpcds.py's HostData over hand-made columns."""

    def __init__(self, tables):
        self._tables = tables

    def col(self, table, column):
        return self._tables[table][column][0]

    def valid(self, table, column):
        return self._tables[table][column][1]

    def dictionary(self, table, column):
        return self._tables[table][column][2]


def brute_force(data, extra=None):
    """Query 95 as its text reads, row by row in Python: None is NULL."""
    def rows(table, names):
        cols = []
        for name in names:
            values, valid = data.col(table, name), data.valid(table, name)
            words = data.dictionary(table, name) if len(data._tables[table][name]) > 2 else None
            cols.append([None if not ok else (words[v] if words is not None else int(v))
                         for v, ok in zip(values, valid)])
        return list(zip(*cols))

    sales = rows("web_sales", reference._READ)
    if extra is not None:
        sales += list(zip(*(extra[name] for name in reference._READ)))
    ws_wh = [a[0] for a in sales for b in sales
             if a[0] is not None and a[0] == b[0]
             and a[1] is not None and b[1] is not None and a[1] != b[1]]
    returned = [r[0] for r in rows("web_returns", ["wr_order_number"])
                for o in ws_wh if r[0] is not None and r[0] == o]
    dates = {k for k, d in rows("date_dim", ["d_date_sk", "d_date"])
             if d is not None and reference._D0 <= d <= reference._D1}
    addresses = {k for k, s in rows("customer_address", ["ca_address_sk", "ca_state"]) if s == "IL"}
    sites = {k for k, n in rows("web_site", ["web_site_sk", "web_company_name"]) if n == "pri"}
    keep = [s for s in sales
            if s[2] in dates and s[3] in addresses and s[4] in sites
            and s[0] in set(ws_wh) and s[0] in set(returned)]
    if not keep:
        return [(0, None, None)]
    total = lambda i: sum(s[i] for s in keep if s[i] is not None) if any(  # noqa: E731
        s[i] is not None for s in keep) else None
    return [(len({s[0] for s in keep}), total(5), total(6))]


def small_world(rng, n_sales=300, null_share=0.15):
    """A few hundred rows with every NULL the text can meet, dense
    enough that rows qualify."""
    def ints(lo, hi, n, nulls=True):
        valid = rng.random(n) >= null_share if nulls else np.ones(n, bool)
        return (np.where(valid, rng.integers(lo, hi + 1, n), 0).astype(np.int64), valid)

    def words(universe, n):
        valid = rng.random(n) >= null_share
        return (rng.integers(0, len(universe), n).astype(np.int32), valid,
                np.array(universe, dtype=object))

    d0 = reference._D0
    return Columns({
        "web_sales": {
            "ws_order_number": ints(1, 40, n_sales, nulls=False),
            "ws_warehouse_sk": ints(1, 3, n_sales),
            "ws_ship_date_sk": ints(1, 8, n_sales),
            "ws_ship_addr_sk": ints(1, 6, n_sales),
            "ws_web_site_sk": ints(1, 4, n_sales),
            "ws_ext_ship_cost": ints(1, 99_999, n_sales),
            "ws_net_profit": ints(-50_000, 50_000, n_sales),
        },
        "web_returns": {"wr_order_number": ints(1, 60, 25)},
        "date_dim": {"d_date_sk": (np.arange(1, 9, dtype=np.int64), np.ones(8, bool)),
                     "d_date": (np.array([d0 - 1, d0, d0 + 1, d0 + 30, d0 + 60, d0 + 61, d0 + 400, d0 + 5],
                                         dtype=np.int32), np.ones(8, bool))},
        "customer_address": {"ca_address_sk": (np.arange(1, 7, dtype=np.int64), np.ones(6, bool)),
                             "ca_state": words(["IL", "TX"], 6)},
        "web_site": {"web_site_sk": (np.arange(1, 5, dtype=np.int64), np.ones(4, bool)),
                     "web_company_name": words(["ought", "pri"], 4)},
    })


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_reference_is_the_querys_text_row_by_row(seed):
    data = small_world(np.random.default_rng(seed))
    want = brute_force(data)
    assert reference.expected(data) == want
    extra = {name: [7, 1, 2, 1, 1, 500, -20][i:i + 1] * 2 for i, name in enumerate(reference._READ)}
    extra["ws_warehouse_sk"] = [1, 2]
    assert reference.expected(data, extra=extra) == brute_force(data, extra)
    if seed == 1:
        assert want[0][0] > 0  # the case is not vacuous


def test_reference_answers_zero_null_null_over_an_empty_qualifying_set():
    data = small_world(np.random.default_rng(5))
    data._tables["web_returns"]["wr_order_number"] = (np.zeros(3, np.int64), np.zeros(3, bool))
    assert reference.expected(data) == brute_force(data) == [(0, None, None)]


def test_float32_control_differs_once_the_sums_pass_float32s_digits():
    data = small_world(np.random.default_rng(1), n_sales=4000, null_share=0.02)
    data._tables["web_sales"]["ws_ext_ship_cost"] = (
        data.col("web_sales", "ws_ext_ship_cost") * 37 + 1_000_003, data.valid("web_sales", "ws_ext_ship_cost"))
    exact, low = reference.expected(data), reference.expected(data, precision="float32")
    assert exact[0][0] == low[0][0] and exact[0][1] != low[0][1]


# ---- the served statement ---------------------------------------------


def serve(tables):
    """The loader's bulk load into a fresh catalog, ANALYZE, a session."""
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog

    catalog = Catalog()
    loader.bulk_load(catalog, tables)
    session = Session(catalog, db=loader.DATABASE)
    for table in tables:
        session.execute(f"analyze table {table}")
    return session


def as_reference_rows(result):
    def cents(v):
        return None if v is None else int(round(float(v) * 100))

    return [(int(r[0]), cents(r[1]), cents(r[2])) for r in result.rows]


def deciding_nulls(tables):
    """The population with one order rebuilt so that three NULLs and a
    single warehouse each decide a row: order A's only second warehouse
    becomes NULL (A leaves `ws_wh`), order B keeps two warehouses but
    its one qualifying row loses its ship date, order C keeps both and
    qualifies. All three are returned and ship to IL through `pri`."""
    ws = {name: datagen.Column(c.kind, c.data.copy(), c.dictionary, c.valid.copy())
          for name, c in tables["web_sales"].items()}
    tables = dict(tables, web_sales=ws)
    data = loader.HostData(tables)
    address = loader.first_key(data, "customer_address", "ca_address_sk", "ca_state", "IL")
    site = loader.first_key(data, "web_site", "web_site_sk", "web_company_name", "pri")
    ship = int(np.datetime64("1999-02-20", "D").astype(np.int64)) + datagen.JULIAN_OF_EPOCH
    orders = np.unique(tables["web_returns"]["wr_order_number"].data)[:3]
    for which, order in zip("ABC", orders):
        rows = np.nonzero(ws["ws_order_number"].data == order)[0]
        for name, value in (("ws_ship_addr_sk", address), ("ws_web_site_sk", site)):
            ws[name].data[rows], ws[name].valid[rows] = value, True
        ws["ws_ship_date_sk"].data[rows], ws["ws_ship_date_sk"].valid[rows] = ship + 400, True
        ws["ws_ship_date_sk"].data[rows[0]] = ship  # one row of the order qualifies
        ws["ws_warehouse_sk"].data[rows], ws["ws_warehouse_sk"].valid[rows] = 1, True
        ws["ws_warehouse_sk"].data[rows[1]] = 2
        for name in ("ws_ext_ship_cost", "ws_net_profit"):
            ws[name].valid[rows[0]] = True
        if which == "A":
            ws["ws_warehouse_sk"].data[rows[1]], ws["ws_warehouse_sk"].valid[rows[1]] = 0, False
        if which == "B":
            ws["ws_ship_date_sk"].data[rows[0]], ws["ws_ship_date_sk"].valid[rows[0]] = 0, False
    return tables, [int(o) for o in orders]


@pytest.fixture(scope="module")
def served():
    """One session a population: (tables, session), three seeds, the
    third rebuilt by `deciding_nulls`."""
    out = []
    for seed in (SEED, 7):
        t = datagen.generate(SF, seed)
        out.append((t, serve(t)))
    t, _orders = deciding_nulls(datagen.generate(SF, 11))
    out.append((t, serve(t)))
    return out


@pytest.mark.parametrize("which", [0, 1, 2])
def test_served_q95_is_the_references_answer(served, which):
    tables, session = served[which]
    want = reference.expected(loader.HostData(tables))
    assert as_reference_rows(session.execute(Q95)) == want
    if which == 2:
        assert want[0][0] >= 1


def test_a_null_warehouse_a_null_ship_date_and_a_single_warehouse_each_decide_a_row():
    tables, (a, b, c) = deciding_nulls(datagen.generate(SF, 11))
    ws = tables["web_sales"]
    assert kept_orders(tables, (a, b, c)) == {c}
    # give each NULL a value in turn: the order it kept out comes back
    rows_a = np.nonzero(ws["ws_order_number"].data == a)[0]
    assert not ws["ws_warehouse_sk"].valid[rows_a[1]]
    ws["ws_warehouse_sk"].data[rows_a[1]], ws["ws_warehouse_sk"].valid[rows_a[1]] = 2, True
    assert kept_orders(tables, (a, b, c)) == {a, c}
    rows_b = np.nonzero(ws["ws_order_number"].data == b)[0]
    assert not ws["ws_ship_date_sk"].valid[rows_b[0]]
    ws["ws_ship_date_sk"].data[rows_b[0]] = ws["ws_ship_date_sk"].data[rows_a[0]]
    ws["ws_ship_date_sk"].valid[rows_b[0]] = True
    assert kept_orders(tables, (a, b, c)) == {a, b, c}
    # and with one warehouse only, an order is in no <> pair
    rows_c = np.nonzero(ws["ws_order_number"].data == c)[0]
    ws["ws_warehouse_sk"].data[rows_c] = 1
    assert kept_orders(tables, (a, b, c)) == {a, b}


def kept_orders(tables, orders):
    """Which of `orders` the text keeps, by the reference on each alone."""
    kept = set()
    for order in orders:
        mask = tables["web_sales"]["ws_order_number"].data == order
        alone = dict(tables, web_sales={
            n: datagen.Column(c.kind, c.data[mask], c.dictionary, c.valid[mask])
            for n, c in tables["web_sales"].items()})
        if reference.expected(loader.HostData(alone))[0][0]:
            kept.add(int(order))
    return kept


def test_served_q95_over_an_empty_qualifying_set_is_zero_null_null():
    tables = datagen.generate(0.01, 5)
    wr = tables["web_returns"]["wr_order_number"]
    tables["web_returns"] = dict(tables["web_returns"], wr_order_number=datagen.Column(
        wr.kind, np.zeros_like(wr.data), None, np.zeros_like(wr.valid)))
    session = serve(tables)
    assert reference.expected(loader.HostData(tables)) == [(0, None, None)]
    assert [tuple(r) for r in session.execute(Q95).rows] == [(0, None, None)]


def test_flight_and_registry_count_the_expansions_of_one_q95(served):
    from tidb_tpu.obs.flight import FLIGHT
    from tidb_tpu.utils.metrics import REGISTRY

    def counter(name):
        return sum(v for n, _kind, v in REGISTRY.rows() if n == name)

    tables, session = served[0]
    session.execute(Q95)  # steady by now: one program a statement
    before = {n: counter(n) for n in (
        "tidbtpu_executor_join_expansions_total", "tidbtpu_executor_join_expand_rows_total",
        "tidbtpu_executor_join_expand_overflow_retries_total")}
    session.execute(Q95)
    flight = FLIGHT.rows()[-1]
    order = tables["web_sales"]["ws_order_number"].data
    self_join = int((np.bincount(order).astype(np.int64) ** 2).sum())
    assert flight["join_expansions"] == 2
    assert self_join < flight["join_expand_rows"] < 2.2 * self_join
    assert flight["join_expand_rows"] <= flight["join_expand_slots"] < 4 * flight["join_expand_rows"]
    assert counter("tidbtpu_executor_join_expand_rows_total") - before[
        "tidbtpu_executor_join_expand_rows_total"] == flight["join_expand_rows"]
    # counted while a program is traced: the steady statement traces none
    assert counter("tidbtpu_executor_join_expansions_total") == before[
        "tidbtpu_executor_join_expansions_total"] >= 2
    # the first program, at the estimated tiles, overflowed at this size
    assert before["tidbtpu_executor_join_expand_overflow_retries_total"] >= 1


def test_two_seeds_share_one_program():
    """Nothing of a data set's values is baked into Q95's program: two
    populations of one size lower to the same steady program."""
    import hashlib

    import jax

    from tidb_tpu.planner import physical

    hashes = {}  # seed -> the steady programs' hashes, in the order compiled
    real = physical.watched_jit

    def hashing(seed):
        def wj(fn, sig=None, **kw):
            inner = real(fn, sig=sig, **kw)

            def call(*a, **k):
                if isinstance(sig, tuple) and sig[0] == "steady":
                    text = jax.jit(fn).lower(*a, **k).as_text()
                    hashes.setdefault(seed, []).append(hashlib.sha256(text.encode()).hexdigest())
                return inner(*a, **k)

            return call
        return wj

    try:
        for seed in (101, 202):  # 101 draws no NULL web_company_name, 202 does
            physical.watched_jit = hashing(seed)
            session = serve(datagen.generate(0.01, seed))
            session.execute(Q95)
            session.execute(Q95)
    finally:
        physical.watched_jit = real
    # the published program, compiled last. (At this size the first one, at
    # the estimated tiles, overflows, and its 65,536-row tile takes the dense
    # join path, which still bakes a build side's exact bounds: ROADMAP M1.)
    assert hashes[101][-1] == hashes[202][-1]


def test_the_benchmarks_rehearsal_of_the_cell_is_correct():
    """`run.py --workload tpcds_sf1.q95 --rehearse-cpu-sf 0.01`, the whole
    command on the CPU: served, judged, read back."""
    for path in (os.path.join(BENCH, "reference"), BENCH):
        if path not in sys.path:
            sys.path.insert(0, path)
    import run as harness

    from tidb_tpu.obs.flight import FLIGHT

    args = harness.parse_args(["--workload", "tpcds_sf1.q95", "--seed", "5", "--seconds", "1",
                               "--rehearse-cpu-sf", "0.01"])
    try:
        result, judged = harness.run_cell(args)
    finally:
        FLIGHT.set_ring_capacity(256)  # the harness keeps a whole run's flights
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["readback_wrong"] == [0, 0]
    assert judged["write"]["query"] == "q95"
