"""The deployment `tpch_sf1_q21` at a small size, on the CPU: the served
TPC-H Q21 against its plain reference (benchmarks/reference/q21.py) on
three seeds and on hand-made rows that each decide a row, the controls
that must fail, one program for every data set, the cold pass at its
first tiles, what the semi and anti joins with a residual leave on the
statement's flight, in the registry and in the HLO scopes, and the
cell's rehearsal. The whole file runs in about two minutes."""

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


loader = _load("loaders/tpch.py", "t21_loader_tpch")
reference = _load("reference/q21.py", "t21_reference_q21")
checks = _load("checks.py", "t21_checks")
with open(os.path.join(BENCH, "configs", "tpch_sf1_q21.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "queries", "q21.sql")) as f:
    Q21 = " ".join(f.read().split())
SF, SEEDS = 0.05, (1, 2, 2**31 + 21)
Column = loader.datagen.Column


def session_over(tables):
    """A session over `tables` (loaded as the benchmark loads them),
    ANALYZEd."""
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog

    catalog = Catalog()
    loader.bulk_load(catalog, tables)
    session = Session(catalog, db=loader.DATABASE)
    for table in tables:
        session.execute(f"analyze table {table}")
    return session


def serve(seed, sf=SF):
    """(host data, session) of one population."""
    tables = loader.datagen.generate(sf, seed)
    return loader.HostData(tables), session_over(tables)


@pytest.fixture(scope="module")
def served():
    return [serve(seed) for seed in SEEDS]


def wire_rows(result):
    """A result's rows as the text protocol carries them."""
    return [tuple(None if v is None else str(v) for v in row) for row in result.rows]


def judged(rows, want, wrong="cells_wrong"):
    tally = checks.Tally()
    tally.answers += 1
    checks.judge_rows(reference.KINDS, rows, want, tally, wrong)
    return tally


def counter(name, **labels):
    from tidb_tpu.utils.metrics import REGISTRY

    if labels:
        block = "{" + ",".join(f'{k}="{v}"' for k, v in labels.items()) + "}"
        name += block
    return sum(v for n, _kind, v in REGISTRY.rows() if n == name)


# ---- the configuration and the served statement -----------------------


def test_the_configuration_is_tpch_sf1s_rows_with_q21s_guarantees():
    with open(os.path.join(BENCH, "configs", "tpch_sf1.json")) as f:
        sf1 = json.load(f)
    with open(os.path.join(BENCH, "configs", "tpch_sf1_q18.json")) as f:
        q18 = json.load(f)
    assert CONFIG["row_counts"] == sf1["row_counts"] and CONFIG["scale_factor"] == 1
    assert CONFIG["row_counts"]["lineitem"] == 6_001_215 and len(CONFIG["row_counts"]) == 8
    assert CONFIG["chips"] == 1 and CONFIG["reduced"] == ["scale_factor"]
    assert CONFIG["loader"] == "tpch" and CONFIG["layout"] == sf1["layout"]
    assert len(CONFIG["source"]) <= 200 and "2.4.21" in CONFIG["source"]
    assert "SAUDI ARABIA" in CONFIG["source"]
    assert CONFIG["guarantees"]["read_your_writes"] == q18["guarantees"]["read_your_writes"]
    assert "s_name str, count(*) int" in CONFIG["guarantees"]["answers"]
    assert CONFIG["assumed"][:3] == q18["assumed"][:3]
    assert reference.KINDS == ("str", "int")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == "tpch_sf1_q21.q21"]
    assert cell["chips"] == 1 and cell["traffic"] == "q21"
    for name in ("q21_ms", "residual_pairs_per_stmt", "residual_pass_pct"):
        (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
        assert entry["workloads"] == ["tpch_sf1_q21.q21"]


@pytest.mark.parametrize("which", [0, 1, 2])
def test_served_q21_is_the_references_answer_cell_for_cell(served, which):
    data, session = served[which]
    want = reference.expected(data)
    assert 0 < len(want) <= 100
    tally = judged(wire_rows(session.execute(Q21)), want)
    assert tally.correct() and tally.values["cells_wrong"] == 0, tally.first_wrong


def _hand_made():
    """Five suppliers (1, 2, 3 Saudi; 4 Saudi; 5 of another nation) and
    orders that each decide a row of the answer:

    - 10 'F': lines of 1 (late), 2 (on time): 1 waits alone -> counts;
    - 11 'F': lines of 1 (late), 2 (late): both late -> neither counts;
    - 12 'F': two lines of 3 (late, late): no other supplier -> none;
    - 13 'O': lines of 1 (late), 2: not finished -> none;
    - 14 'F': lines of 4 (late), 4 (on time), 2 (on time): 4 counts,
      its second line being of the same supplier does not defeat it;
    - 15 'F': lines of 5 (late), 1 (on time): 5 is not Saudi -> none;
    - 16 'F': lines of 1 (on time), 3 (late), 2 (on time): 3 counts;
    - 17 'F': lines of 1 (late), 1 (late), 2 (on time): counts twice."""
    def col(kind, values, dictionary=None):
        if dictionary is not None:
            return Column(kind, np.asarray(values, dtype=np.int32), np.asarray(dictionary, dtype=object))
        return Column(kind, np.asarray(values, dtype=np.int64 if kind == "int" else np.int32))

    day = int(np.datetime64("1995-01-10", "D").astype(np.int64))
    lines = [  # order, supplier, late
        (10, 1, True), (10, 2, False), (11, 1, True), (11, 2, True), (12, 3, True), (12, 3, True),
        (13, 1, True), (13, 2, False), (14, 4, True), (14, 4, False), (14, 2, False),
        (15, 5, True), (15, 1, False), (16, 1, False), (16, 3, True), (16, 2, False),
        (17, 1, True), (17, 1, True), (17, 2, False),
    ]
    names = [f"Supplier#{i:09d}" for i in range(1, 6)]
    return {
        "nation": {"n_nationkey": col("int", [20, 7]), "n_name": col("str", [1, 0], ["GERMANY", "SAUDI ARABIA"])},
        "supplier": {"s_suppkey": col("int", [1, 2, 3, 4, 5]), "s_name": col("str", [0, 1, 2, 3, 4], names),
                     "s_nationkey": col("int", [20, 20, 20, 20, 7])},
        "orders": {"o_orderkey": col("int", range(10, 18)),
                   "o_orderstatus": col("str", [0, 0, 0, 1, 0, 0, 0, 0], ["F", "O"])},
        "lineitem": {"l_orderkey": col("int", [o for o, _s, _l in lines]),
                     "l_suppkey": col("int", [s for _o, s, _l in lines]),
                     "l_commitdate": col("date", [day] * len(lines)),
                     "l_receiptdate": col("date", [day + (3 if late else -3) for _o, _s, late in lines])},
    }


def test_hand_made_rows_each_decide_a_row_as_the_text_says():
    tables = _hand_made()
    data, session = loader.HostData(tables), session_over(tables)
    want = [("Supplier#000000001", 3), ("Supplier#000000003", 1), ("Supplier#000000004", 1)]
    assert reference.expected(data) == want
    assert judged(wire_rows(session.execute(Q21)), want).correct()
    # the controls each change a row here: order 12's lone supplier
    # satisfies the EXISTS without its residual; any line of another
    # supplier, late or not, defeats the NOT EXISTS without its filter
    assert reference.expected(data, precision="exists_any_line") == [
        ("Supplier#000000001", 3), ("Supplier#000000003", 3), ("Supplier#000000004", 1)]
    assert reference.expected(data, precision="not_exists_any_line") == []


def test_the_controls_and_the_stale_read_are_not_correct(served):
    data, _session = served[0]
    want = reference.expected(data)
    # float32 says nothing here: Q21 counts and sums nothing
    f32 = checks.render_rows(reference.KINDS, reference.expected(data, precision="float32"))
    assert judged(f32, want).correct()
    late_filter_dropped = reference.expected(data, precision="not_exists_any_line")
    tally = judged(checks.render_rows(reference.KINDS, late_filter_dropped), want)
    assert not tally.correct() and tally.values["cells_wrong"] >= len(want)
    # the EXISTS without its residual differs only where an order's
    # lines are all one supplier's: on some seed of three it shows
    wrong = [
        judged(checks.render_rows(reference.KINDS, reference.expected(d, precision="exists_any_line")),
               reference.expected(d)).values["cells_wrong"]
        for d, _s in served
    ]
    assert max(wrong) > 0
    # the read-back answered from the snapshot before the write (the
    # tpch loader's write and its reader, Q6)
    q6 = _load("reference/q6.py", "t21_reference_q6")
    dep = loader.Deployment.__new__(loader.Deployment)
    dep.data = data
    write = dep.write_for_readback()
    assert write["query"] == "q6"
    stale = checks.Tally()
    stale.answers += 1
    checks.judge_rows(q6.KINDS, checks.render_rows(q6.KINDS, q6.expected(data)),
                      q6.expected(data, extra=write["extra"]), stale, "readback_wrong")
    assert not stale.correct() and stale.values["readback_wrong"] >= 1


def test_the_reference_reads_lineitem_rows_a_write_added():
    tables = _hand_made()
    data = loader.HostData(tables)
    day = int(np.datetime64("1995-01-10", "D").astype(np.int64))
    # a late line of supplier 2 on order 10: supplier 1 no longer waits alone there
    extra = {"l_orderkey": [10], "l_suppkey": [2], "l_receiptdate": [day + 1], "l_commitdate": [day]}
    assert reference.expected(data, extra=extra) == [
        ("Supplier#000000001", 2), ("Supplier#000000003", 1), ("Supplier#000000004", 1)]


# ---- the planner's estimate of a column against a column ---------------


def test_a_column_against_a_column_is_estimated_from_both_histograms(served):
    """`l_receiptdate > l_commitdate` from the two columns' histograms,
    the columns taken as independent (about a half here), where it was
    the pseudo rate 1/3; `<` is its complement; a column without
    statistics keeps the pseudo rate."""
    from tidb_tpu.dtypes import DATE
    from tidb_tpu.expression.expr import ColumnRef, Func
    from tidb_tpu.planner import cardinality as C

    _data, session = served[0]
    table = session.catalog.table(loader.DATABASE, "lineitem")
    smap = C.StatsMap()
    for name in ("l_receiptdate", "l_commitdate", "l_shipdate"):
        smap.add(f"l.{name}", table.stats.get(name), DATE, table.nrows)
    smap.add("l.no_stats", None, DATE, table.nrows)
    receipt, commit, ship, bare = (
        ColumnRef(type=DATE, name=f"l.{n}")
        for n in ("l_receiptdate", "l_commitdate", "l_shipdate", "no_stats"))
    above = C.selectivity(Func(None, "gt", [receipt, commit]), smap)
    assert 0.4 < above < 0.7
    assert C.selectivity(Func(None, "lt", [receipt, commit]), smap) == pytest.approx(1 - above)
    assert C.selectivity(Func(None, "ge", [ship, commit]), smap) != C.SEL_RANGE_DEFAULT
    assert C.selectivity(Func(None, "gt", [receipt, bare]), smap) == C.SEL_RANGE_DEFAULT


# ---- what the semi and anti joins with a residual leave behind --------


def _pairs(data):
    """(pairs, kept) of the EXISTS and of the NOT EXISTS, from the host
    columns: each probe row meets every line (or late line) of its
    order; the residual passes those of another supplier."""
    col = data.col
    order, supp = col("lineitem", "l_orderkey"), col("lineitem", "l_suppkey")
    late = col("lineitem", "l_receiptdate") > col("lineitem", "l_commitdate")
    n = int(order.max()) + 1
    status = data.dictionary("orders", "o_orderstatus")[col("orders", "o_orderstatus")]
    finished = np.zeros(n, dtype=bool)
    finished[col("orders", "o_orderkey")] = status == "F"
    saudi = np.zeros(int(supp.max()) + 1, dtype=bool)
    saudi[col("supplier", "s_suppkey")] = col("supplier", "s_nationkey") == 20
    probe = np.nonzero(late & finished[order] & saudi[supp])[0]

    def meet(rows, build):
        by_order = {}
        for o, s_ in zip(order[build], supp[build]):
            by_order.setdefault(int(o), []).append(int(s_))
        pairs = kept = 0
        survivors = []
        for r in rows:
            others = by_order.get(int(order[r]), [])
            pairs += len(others)
            k = sum(1 for s_ in others if s_ != supp[r])
            kept += k
            survivors.append(k > 0)
        return pairs, kept, np.asarray(survivors, dtype=bool)

    p1, k1, passed = meet(probe, np.arange(len(order)))
    p2, k2, _ = meet(probe[passed], np.nonzero(late)[0])
    return p1 + p2, k1 + k2


def test_flight_and_registry_count_the_residual_joins_of_one_q21(served):
    from tidb_tpu.obs.flight import FLIGHT

    data, session = served[1]
    plan = [r[0].strip() for r in session.execute("explain " + Q21).rows]
    joins = [line.split(" est=")[0] for line in plan if line.startswith("JoinPlan")]
    # the NOT EXISTS over the EXISTS over the 'F' orders' semi join
    assert joins[:3] == ["JoinPlan kind=anti keys=1", "JoinPlan kind=semi keys=1",
                         "JoinPlan kind=semi keys=1"]
    session.execute(Q21)  # steady by now: one program a statement
    names = ('tidbtpu_executor_residual_joins_total', 'tidbtpu_executor_residual_pairs_total')
    before = (counter(names[0], kind="semi"), counter(names[0], kind="anti"), counter(names[1]))
    session.execute(Q21)
    flight = FLIGHT.rows()[-1]
    pairs, kept = _pairs(data)
    assert flight["residual_joins"] == 2
    assert (flight["residual_pairs"], flight["residual_kept"]) == (pairs, kept)
    assert 0 < kept < pairs
    # every tested pair is a row the two expansions emitted
    assert flight["join_expansions"] == 2 and flight["join_expand_rows"] == pairs
    assert counter(names[1]) - before[2] == pairs
    # counted while a program is traced: the steady statement traces none
    assert (counter(names[0], kind="semi"), counter(names[0], kind="anti")) == before[:2]
    assert before[0] >= 1 and before[1] >= 1


def _lowered(session, sql, debug_info=False):
    """[(kind, lowered text)] of the programs a statement compiles."""
    import jax

    from tidb_tpu.planner import physical

    texts = []
    real = physical.watched_jit

    def wj(fn, sig=None, **kw):
        inner = real(fn, sig=sig, **kw)

        def call(*a, **k):
            texts.append((sig[0], jax.jit(fn).lower(*a, **k).as_text(debug_info=debug_info)))
            return inner(*a, **k)

        return call

    physical.watched_jit = wj
    try:
        session.execute(sql)
    finally:
        physical.watched_jit = real
    return texts


# ---- one program for every data set, and the cold pass ----------------


def _cold_q21(seed, sf=SF):
    """Serve one cold Q21 over a fresh population: (the caps of each
    steady program compiled, in order, with the needs its run fetched;
    the pairs knobs' first tiles the planner gave; the lowered hash of
    every program; the answer's wire rows; host data)."""
    import jax

    from tidb_tpu.planner import physical

    data, session = serve(seed, sf)
    runs, firsts, hashes = [], [], []
    real_steady, real_first, real_wj = (
        physical.PhysicalExecutor._steady_at, physical.PlanCompiler._pairs_first_tile,
        physical.watched_jit)

    def steady_at(self, cq, caps, out_cap, inputs):
        got = real_steady(self, cq, caps, out_cap, inputs)
        runs.append((dict(caps), sorted(cq.expand_nids), {k: int(v) for k, v in got[2].items()}))
        return got

    def first_tile(self, plan):
        tile = real_first(self, plan)
        firsts.append(tile)
        return tile

    def wj(fn, sig=None, **kw):
        inner = real_wj(fn, sig=sig, **kw)

        def call(*a, **k):
            text = jax.jit(fn).lower(*a, **k).as_text()
            hashes.append((sig[0], hashlib.sha256(text.encode()).hexdigest()))
            return inner(*a, **k)

        return call

    physical.PhysicalExecutor._steady_at = steady_at
    physical.PlanCompiler._pairs_first_tile = first_tile
    physical.watched_jit = wj
    try:
        rows = wire_rows(session.execute(Q21))
    finally:
        physical.PhysicalExecutor._steady_at = real_steady
        physical.PlanCompiler._pairs_first_tile = real_first
        physical.watched_jit = real_wj
    return runs, firsts, hashes, rows, data


@pytest.fixture(scope="module")
def cold():
    return {seed: _cold_q21(seed) for seed in SEEDS}


def test_every_seeds_first_program_is_one_program(cold):
    """Nothing of a data set's values is baked into Q21's program: the
    first whole program, compiled at the planner's tiles before any run,
    lowers to the same text on every seed. (A later program is compiled
    at the tiles the seed's own counts need; seeds share it where those
    fall in one power-of-two step.)"""
    firsts = {seed: got[2][0] for seed, got in cold.items()}
    assert {kind for kind, _h in firsts.values()} == {"steady"}
    assert len(set(firsts.values())) == 1, firsts
    assert len({tuple(got[1]) for got in cold.values()}) == 1


def test_the_cold_pass_starts_the_pairs_at_the_planners_estimate(cold):
    """The two residual joins' pairs tiles start from the planner's
    estimate of the inner join of their sides (not at the probe's tile,
    which left the first program to a discovery run): the first program
    is a steady one at those tiles; a tile its data outgrows is counted
    as one expansion overflow retry, and the answer is the reference's."""
    from tidb_tpu.planner.physical import _FEW_ROWS, _cap_tile

    for seed, (runs, firsts, hashes, rows, data) in cold.items():
        assert len(firsts) == 2 and all(t >= 16 for t in firsts)
        caps, expand, needs = runs[0]
        assert len(expand) == 2 and sorted(caps[n] for n in expand) == sorted(firsts)
        assert judged(rows, reference.expected(data)).correct(), seed
        if any(needs.get(n, 0) > caps[n] for n in caps):
            # an overflowed first program: the steady one again at the
            # tight tiles its run saw, with no discover program between
            assert [kind for kind, _h in hashes] == ["steady", "steady"], seed
            assert all(runs[1][0][n] == max(_cap_tile(needs[n]), _FEW_ROWS) for n in expand)


def test_an_overflowed_pairs_tile_is_counted_once_a_cold_pass():
    name = "tidbtpu_executor_join_expand_overflow_retries_total"
    before = counter(name)
    runs, _firsts, _hashes, rows, data = _cold_q21(SEEDS[0])
    caps, expand, needs = runs[0]
    overflowed = sum(1 for n in expand if needs.get(n, 0) > caps[n])
    assert counter(name) - before == overflowed
    assert judged(rows, reference.expected(data)).correct()


def test_the_residual_path_opens_its_three_scopes_inside_the_join():
    tables = loader.datagen.generate(0.01, 7)
    session = session_over(tables)
    ((_kind, text), *_rest) = _lowered(session, Q21, debug_info=True)
    for scope in ("residual/expand/", "residual/filter/", "residual/rejoin/"):
        assert f"kind=semi keys=1#" in text and scope in text, scope
    # the expansion's own scopes sit inside residual/expand
    assert "residual/expand/expand/search/" in text and "residual/expand/expand/gather/" in text


def test_a_plan_without_a_residual_join_reports_none(served):
    from tidb_tpu.obs.flight import FLIGHT

    _data, session = served[0]
    session.execute("select count(*) from lineitem l1 where exists "
                    "(select * from orders where o_orderkey = l1.l_orderkey)")
    flight = FLIGHT.rows()[-1]
    assert (flight["residual_joins"], flight["residual_pairs"], flight["residual_kept"]) == (0, 0, 0)


# ---- the cell's rehearsal ---------------------------------------------


def test_the_benchmarks_rehearsal_and_controls_of_the_cell():
    """`control_q21.py --workload tpch_sf1_q21.q21 --rehearse-cpu-sf
    0.02`: the whole command on the CPU, served, judged, read back, the
    new metrics read, and every required control not correct."""
    import control_q21
    import run as harness

    from tidb_tpu.obs.flight import FLIGHT

    args = harness.parse_args(["--workload", "tpch_sf1_q21.q21", "--seed", "3100000031",
                               "--seconds", "1", "--trace", "1", "--rehearse-cpu-sf", "0.02"])
    try:
        result, judged_ = harness.run_cell(args)
    finally:
        FLIGHT.set_ring_capacity(256)  # the harness keeps a whole run's flights
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["cells_wrong"] == [0, 0]
    assert result["checks"]["readback_wrong"] == [0, 0]
    assert {"q21_ms", "residual_pairs_per_stmt", "residual_pass_pct"} <= set(
        result["rehearsed_metrics"])
    assert list(judged_["statements"]) == ["q21"] and judged_["write"]["query"] == "q6"
    controls = control_q21.judge_controls(judged_)
    assert not controls["not_exists_any_line"]["correct"]
    assert not controls["stale_read"]["correct"]
    assert controls["float32"]["correct"]
    assert set(control_q21.REQUIRED) | {"exists_any_line"} <= set(controls)


def test_the_residual_readers_read_the_flights_and_nothing_without_them():
    import importlib

    residual = importlib.import_module("residual")
    pairs = _load("layers/residual_pairs_per_stmt.py", "t21_layer_pairs")
    share = _load("layers/residual_pass_pct.py", "t21_layer_pass")

    def stmt(flight):
        return {"name": "q21", "latency_s": 0.1, "error": None, "traced": True, "flight": flight}

    run = {"statements": [stmt({"residual_joins": 2, "residual_pairs": 100, "residual_kept": 75}),
                          stmt({"residual_joins": 2, "residual_pairs": 300, "residual_kept": 225})]}
    assert pairs.read(run) == 200 and share.read(run) == 75.0
    # a program from before the keys, and a statement without such a join
    for flight in ({}, {"residual_joins": 0, "residual_pairs": 0, "residual_kept": 0}):
        assert pairs.read({"statements": [stmt(flight)]}) is None
        assert share.read({"statements": [stmt(flight)]}) is None
    assert residual.least_bytes(10, 16, 4, 24) == 10 * 16 + 4 * 24
