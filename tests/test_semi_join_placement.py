"""An uncorrelated `IN (subquery)` over ONE relation of a comma-join is a
reducing edge of the join order (planner/logical.py `_in_reducers`,
`_reorder_joins`): placed by `cardinality`'s estimates beside the inner
joins, early where it is estimated to leave strictly fewer rows than
every join would, over the finished join tree otherwise.

EXPLAIN of the benchmark's Q18 and Q95, answers against a plain Python
reference (NULLs on either side, an empty subquery, a row-value left
side, a left side that spans relations, NOT IN beside it), and the
counter `tidbtpu_planner_semi_join_placements_total{placed}`."""

import importlib.util
import itertools
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _path in (os.path.join(BENCH, "reference"), BENCH):  # the loaders' own helpers
    if _path not in sys.path:
        sys.path.insert(0, _path)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sql(name):
    with open(os.path.join(BENCH, "queries", name + ".sql")) as f:
        return " ".join(f.read().split())


Q18, Q95 = _sql("q18"), _sql("q95")
COUNTER = "tidbtpu_planner_semi_join_placements_total"


def placements():
    """{placed: count} of the registry's counter, as it stands."""
    from tidb_tpu.utils.metrics import REGISTRY

    out = {"early": 0, "last": 0}
    for name, _kind, value in REGISTRY.rows():
        if name.startswith(COUNTER):
            for placed in out:
                if f'placed="{placed}"' in name:
                    out[placed] += int(value)
    return out


def moved(before):
    now = placements()
    return {k: now[k] - before[k] for k in now}


def explain(session, sql):
    return [r[0] for r in session.execute("explain " + sql).rows]


def joins_above(lines, table):
    """For every join whose build side scans `table`: the tables scanned
    by the build sides of the joins above it, nearest first."""
    nodes = [(len(t) - len(t.lstrip()), t.strip()) for t in lines]

    def subtree(i):
        return list(itertools.takewhile(lambda j: nodes[j][0] > nodes[i][0], range(i + 1, len(nodes))))

    def build_scans(i):
        kids = [j for j in subtree(i) if nodes[j][0] == nodes[i][0] + 2]
        return {nodes[j][1].split("table=")[1].split()[0].split(".")[-1]
                for j in [kids[1]] + subtree(kids[1]) if nodes[j][1].startswith("Scan ")}

    out = []
    for i, (indent, text) in enumerate(nodes):
        if not text.startswith("JoinPlan") or table not in build_scans(i):
            continue
        above, level = [], indent
        for j in range(i - 1, -1, -1):
            if nodes[j][0] < level:
                level = nodes[j][0]
                if nodes[j][1].startswith("JoinPlan"):
                    above.append(build_scans(j))
        out.append(above)
    return out


# ---- the benchmark's statements ---------------------------------------


@pytest.fixture(scope="module")
def tpch():
    """TPC-H at SF 0.01, the benchmark's population (seed 1 has four
    qualifying orders), ANALYZEd: (host data, session)."""
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog

    loader = _load("loaders/tpch.py", "sjp_loader_tpch")
    tables = loader.datagen.generate(0.01, 1)
    catalog = Catalog()
    loader.bulk_load(catalog, tables)
    session = Session(catalog, db=loader.DATABASE)
    for table in tables:
        session.execute(f"analyze table {table}")
    return loader.HostData(tables), session


@pytest.fixture(scope="module")
def tpcds():
    """TPC-DS's web channel at SF 0.05, seed 101, ANALYZEd: a session."""
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog

    loader = _load("loaders/tpcds.py", "sjp_loader_tpcds")
    tables = loader.datagen.generate(0.05, 101)
    catalog = Catalog()
    loader.bulk_load(catalog, tables)
    session = Session(catalog, db=loader.DATABASE)
    for table in tables:
        session.execute(f"analyze table {table}")
    return session


def test_q18s_semi_join_runs_under_the_join_with_lineitem(tpch):
    _data, session = tpch
    lines = explain(session, Q18)
    semi = [i for i, t in enumerate(lines) if "JoinPlan kind=semi" in t]
    assert len(semi) == 1
    # the semi join's probe side is orders x customer; lineitem is joined above it
    (above,) = [a for a in joins_above(lines, "lineitem") if a]
    assert above == [{"lineitem"}]
    inner = [i for i, t in enumerate(lines) if "JoinPlan kind=inner" in t]
    assert inner[0] < semi[0] < inner[1]
    assert lines[semi[0] + 1].strip().startswith("JoinPlan kind=inner")
    scans = [t.strip().split()[1] for t in lines if t.strip().startswith("Scan ")]
    assert scans == ["table=tpch.customer", "table=tpch.orders", "table=tpch.lineitem", "table=tpch.lineitem"]


# PR 35's EXPLAIN of `benchmarks/queries/q95.sql` over this population,
# kept as it was: both IN subqueries are estimated to keep every probe
# row, so they stay over the finished join tree, in the statement's order
Q95_PARENT = """\
Projection exprs=['order count', 'total shipping cost', 'total net profit'] est=1
  Limit limit=100 offset=0 est=1
    Sort keys=1 est=1
      Projection exprs=['order count', 'total shipping cost', 'total net profit'] +base est=1
        Aggregate groups=[] aggs=['count(_a0)', 'sum(_a1)', 'sum(_a2)'] est=1
          Aggregate groups=['_dx'] aggs=['sum(_p0)', 'sum(_p1)'] est=37
            JoinPlan kind=semi keys=1 est=37
              JoinPlan kind=semi keys=1 est=37
                JoinPlan kind=semi keys=1 est=37
                  JoinPlan kind=semi keys=1 broadcast=right est=213
                    JoinPlan kind=semi keys=1 broadcast=right est=5958
                      Scan table=tpcds.web_sales cols=6 est=35751
                      Selection pred=eq(web_site.web_company_name, 'pri') est=5
                        Scan table=tpcds.web_site cols=2 est=30
                    Selection pred=eq(customer_address.ca_state, 'IL') est=89
                      Scan table=tpcds.customer_address cols=2 est=2500
                  Selection pred=and(ge(date_dim.d_date, 10623), le(date_dim.d_date, add(cast('1999-2-01'), 60))) est=12555
                    Scan table=tpcds.date_dim cols=2 est=73049
                Projection exprs=['ws_order_number'] est=142015
                  Projection exprs=['ws_order_number'] +base est=142015
                    Projection exprs=['ws_wh.ws_order_number'] est=142015
                      Projection exprs=['ws_order_number'] est=142015
                        Projection exprs=['ws_order_number'] +base est=142015
                          Selection pred=ne(ws1.ws_warehouse_sk, ws2.ws_warehouse_sk) est=142015
                            JoinPlan kind=inner keys=1 est=426045
                              Scan table=tpcds.web_sales cols=2 est=35751
                              Scan table=tpcds.web_sales cols=2 est=35751
              Projection exprs=['wr_order_number'] est=168808
                Projection exprs=['wr_order_number'] +base est=168808
                  JoinPlan kind=inner keys=1 broadcast=left est=168808
                    Scan table=tpcds.web_returns cols=1 est=3566
                    Projection exprs=['ws_wh.ws_order_number'] est=142015
                      Projection exprs=['ws_order_number'] est=142015
                        Projection exprs=['ws_order_number'] +base est=142015
                          Selection pred=ne(ws1.ws_warehouse_sk, ws2.ws_warehouse_sk) est=142015
                            JoinPlan kind=inner keys=1 est=426045
                              Scan table=tpcds.web_sales cols=2 est=35751
                              Scan table=tpcds.web_sales cols=2 est=35751"""


# The same statement since the semi joins' build sides are read as sets
# (`_set_dedupe`): the self-join's inputs are distinct on (order,
# warehouse) under both INs, and web_returns and ws_wh's output on the
# order number under the second, whose join then reads its build only
# for a match and is a semi join. The INs stay where PR 35 put them
Q95_NOW = """\
Projection exprs=['order count', 'total shipping cost', 'total net profit'] est=1
  Limit limit=100 offset=0 est=1
    Sort keys=1 est=1
      Projection exprs=['order count', 'total shipping cost', 'total net profit'] +base est=1
        Aggregate groups=[] aggs=['count(_a0)', 'sum(_a1)', 'sum(_a2)'] est=1
          Aggregate groups=['_dx'] aggs=['sum(_p0)', 'sum(_p1)'] est=26
            JoinPlan kind=semi keys=1 est=26
              JoinPlan kind=semi keys=1 est=37
                JoinPlan kind=semi keys=1 est=37
                  JoinPlan kind=semi keys=1 broadcast=right est=213
                    JoinPlan kind=semi keys=1 broadcast=right est=5958
                      Scan table=tpcds.web_sales cols=6 est=35751
                      Selection pred=eq(web_site.web_company_name, 'pri') est=5
                        Scan table=tpcds.web_site cols=2 est=30
                    Selection pred=eq(customer_address.ca_state, 'IL') est=89
                      Scan table=tpcds.customer_address cols=2 est=2500
                  Selection pred=and(ge(date_dim.d_date, 10623), le(date_dim.d_date, add(cast('1999-2-01'), 60))) est=12555
                    Scan table=tpcds.date_dim cols=2 est=73049
                Projection exprs=['ws_order_number'] est=25000
                  Projection exprs=['ws_order_number'] +base est=25000
                    Projection exprs=['ws_wh.ws_order_number'] est=25000
                      Projection exprs=['ws_order_number'] est=25000
                        Projection exprs=['ws_order_number'] +base est=25000
                          Selection pred=ne(ws1.ws_warehouse_sk, ws2.ws_warehouse_sk) est=25000
                            JoinPlan kind=inner keys=1 est=75000
                              Aggregate groups=['ws1.ws_warehouse_sk', 'ws1.ws_order_number'] aggs=[] est=15000
                                Scan table=tpcds.web_sales cols=2 est=35751
                              Aggregate groups=['ws2.ws_warehouse_sk', 'ws2.ws_order_number'] aggs=[] est=15000
                                Scan table=tpcds.web_sales cols=2 est=35751
              Projection exprs=['wr_order_number'] est=2117
                Projection exprs=['wr_order_number'] +base est=2117
                  JoinPlan kind=semi keys=1 est=2117
                    Aggregate groups=['web_returns.wr_order_number'] aggs=[] est=2117
                      Scan table=tpcds.web_returns cols=1 est=3566
                    Aggregate groups=['ws_wh.ws_order_number'] aggs=[] est=3000
                      Projection exprs=['ws_wh.ws_order_number'] est=25000
                        Projection exprs=['ws_order_number'] est=25000
                          Projection exprs=['ws_order_number'] +base est=25000
                            Selection pred=ne(ws1.ws_warehouse_sk, ws2.ws_warehouse_sk) est=25000
                              JoinPlan kind=inner keys=1 est=75000
                                Aggregate groups=['ws1.ws_warehouse_sk', 'ws1.ws_order_number'] aggs=[] est=15000
                                  Scan table=tpcds.web_sales cols=2 est=35751
                                Aggregate groups=['ws2.ws_warehouse_sk', 'ws2.ws_order_number'] aggs=[] est=15000
                                  Scan table=tpcds.web_sales cols=2 est=35751"""


def test_q95s_plan_reads_as_the_parents(tpcds):
    lines = explain(tpcds, Q95)
    assert "\n".join(lines) == Q95_NOW
    # where the INs stand is the parent's: the plan down to their builds
    outer = Q95_PARENT.splitlines()
    assert [t.split(" est=")[0] for t in lines[:18]] == [t.split(" est=")[0] for t in outer[:18]]


@pytest.mark.parametrize("statement, want", [("q18", {"early": 1, "last": 0}), ("q95", {"early": 0, "last": 2})])
def test_a_planned_statement_counts_each_reducer_once(tpch, tpcds, statement, want):
    before = placements()
    if statement == "q18":
        data, session = tpch
        reference = _load("reference/q18.py", "sjp_reference_q18")
        checks = _load("checks.py", "sjp_checks")
        rows = [tuple(None if v is None else str(v) for v in row) for row in session.execute(Q18).rows]
        tally = checks.Tally()
        tally.answers += 1
        checks.judge_rows(reference.KINDS, rows, reference.expected(data), tally)
        assert len(rows) == 4 and tally.correct(), tally.first_wrong
    else:
        explain(tpcds, Q95)
    assert moved(before) == want


# ---- answers against a plain reference --------------------------------

BIG = [(i, None if i % 7 == 0 else i % 40, i % 20, i % 9) for i in range(400)]  # id, k, g, v
DIM = [(g, f"d{g}") for g in range(20)]  # g, name
MID = [(k, k * 3 % 11) for k in range(0, 80, 2)] + [(None, 5)]  # k, w
PICK = [(3,), (4,), (12,), (None,), (77,)]  # k
PAIRS = [(3, 3), (4, 5), (12, 12), (None, 4), (24, None), (24, 4)]  # k, g
WIDE = [(k % 6,) for k in range(300)]  # k: many rows, six values


@pytest.fixture(scope="module")
def sess():
    from tidb_tpu.session import Session

    s = Session()
    s.must_exec("create database if not exists sjp")
    s.must_exec("use sjp")

    def fill(name, cols, rows):
        s.must_exec(f"create table {name} ({cols})")
        for at in range(0, len(rows), 100):
            values = ", ".join(
                "(" + ", ".join("null" if v is None else repr(v) for v in row) + ")"
                for row in rows[at:at + 100])
            s.must_exec(f"insert into {name} values {values}")
        s.must_exec(f"analyze table {name}")

    fill("big", "id int, k int, g int, v int", BIG)
    fill("dim", "g int, name varchar(8)", DIM)
    fill("mid", "k int, w int", MID)
    fill("pick", "k int", PICK)
    fill("pairs", "k int, g int", PAIRS)
    fill("wide", "k int", WIDE)
    s.must_exec("create table nothing (k int)")
    s.must_exec("analyze table nothing")
    return s


def sql_in(value, members):
    """SQL's three-valued `value IN members`: True, False or None."""
    if not members:
        return False
    if value is None:
        return None
    if value in members:
        return True
    return None if None in members else False


def row_in(values, members):
    """`(a, b) IN (rows)`: true where one row equals it column for column."""
    return any(all(v is not None and m is not None and v == m for v, m in zip(values, row)) for row in members)


def _not(three):
    return None if three is None else not three


def big_dim():
    return [(b, d) for b in BIG for d in DIM if b[2] == d[0]]


def big_dim_mid():
    return [(b, d, m) for b, d in big_dim() for m in MID if b[1] is not None and b[1] == m[0]]


PICKED = [k for (k,) in PICK]
WIDE_KEYS = [k for (k,) in WIDE]
CASES = {
    # the reducer sits on the start relation and keeps 3 of its 20 rows: before the join
    "two_relations_early": (
        "select big.id, dim.name from big, dim where big.g = dim.g and dim.g in (select k from pick)",
        lambda: [(b[0], d[1]) for b, d in big_dim() if sql_in(d[0], PICKED)],
        {"early": 1, "last": 0}, {"pick": [[{"big"}]]}),
    # on the relation joined last, nothing is left to join: over the join tree
    "two_relations_last": (
        "select big.id, dim.name from big, dim where big.g = dim.g and big.k in (select k from pick)",
        lambda: [(b[0], d[1]) for b, d in big_dim() if sql_in(b[1], PICKED)],
        {"early": 0, "last": 1}, {"pick": [[]]}),
    # dim, then big, then the reducer on big (NULLs on both sides of it), then mid
    "three_relations_early": (
        "select big.id, dim.name, mid.w from big, dim, mid where big.g = dim.g and big.k = mid.k "
        "and big.k in (select k from pick)",
        lambda: [(b[0], d[1], m[1]) for b, d, m in big_dim_mid() if sql_in(b[1], PICKED)],
        {"early": 1, "last": 0}, {"pick": [[{"mid"}]]}),
    "three_relations_last": (
        "select big.id, dim.name, mid.w from big, dim, mid where big.g = dim.g and big.k = mid.k "
        "and mid.w in (select k from pick)",
        lambda: [(b[0], d[1], m[1]) for b, d, m in big_dim_mid() if sql_in(m[1], PICKED)],
        {"early": 0, "last": 1}, {"pick": [[]]}),
    # 300 build rows of six values are estimated to keep every probe row,
    # which is still fewer than the join with big would leave: before it
    "a_reducer_that_keeps_every_row_runs_before_an_expanding_join": (
        "select big.id, mid.w from big, mid where big.k = mid.k and mid.k in (select k from wide)",
        lambda: [(b[0], m[1]) for b in BIG for m in MID
                 if b[1] is not None and b[1] == m[0] and sql_in(m[0], WIDE_KEYS)],
        {"early": 1, "last": 0}, {"wide": [[{"big"}]]}),
    # Q95's shape: the same reducer beside a join that is estimated to
    # leave fewer rows than it: the join goes first, the reducer stays last
    "a_reducer_dearer_than_the_join_left_stays_last": (
        "select big.id, mid.w from big, dim, mid where big.g = dim.g and dim.name = 'd4' "
        "and big.k = mid.k and mid.w = 1 and big.k in (select k from wide)",
        lambda: [(b[0], m[1]) for b, d, m in big_dim_mid()
                 if d[1] == "d4" and m[1] == 1 and sql_in(b[1], WIDE_KEYS)],
        {"early": 0, "last": 1}, {"wide": [[]]}),
    "an_empty_subquery_keeps_nothing": (
        "select big.id, dim.name from big, dim where big.g = dim.g and dim.g in (select k from nothing)",
        lambda: [],
        {"early": 1, "last": 0}, {"nothing": [[{"big"}]]}),
    "a_row_value_left_side": (
        "select big.id, mid.w from big, dim, mid where big.g = dim.g and big.k = mid.k "
        "and (big.k, big.g) in (select k, g from pairs)",
        lambda: [(b[0], m[1]) for b, _d, m in big_dim_mid() if row_in((b[1], b[2]), PAIRS)],
        {"early": 1, "last": 0}, {"pairs": [[{"mid"}]]}),
    # a row value over two relations is no reducer: today's path, uncounted
    "a_row_value_that_spans_two_relations_stays_last": (
        "select big.id, mid.w from big, dim, mid where big.g = dim.g and big.k = mid.k "
        "and (big.k, dim.g) in (select k, g from pairs)",
        lambda: [(b[0], m[1]) for b, d, m in big_dim_mid() if row_in((b[1], d[0]), PAIRS)],
        {"early": 0, "last": 0}, {"pairs": [[]]}),
    "an_expression_that_spans_two_relations_stays_last": (
        "select big.id, dim.name from big, dim, mid where big.g = dim.g and big.k = mid.k "
        "and big.k + dim.g in (select k from pick)",
        lambda: [(b[0], d[1]) for b, d, _m in big_dim_mid() if sql_in(b[1] + d[0], PICKED)],
        {"early": 0, "last": 0}, {"pick": [[]]}),
    # NOT IN keeps its null-aware anti join over the join tree; the IN beside it moves
    "not_in_beside_it": (
        "select big.id, mid.w from big, dim, mid where big.g = dim.g and big.k = mid.k "
        "and big.v not in (select k from pick where k is not null) and big.k in (select k from pick)",
        lambda: [(b[0], m[1]) for b, _d, m in big_dim_mid()
                 if _not(sql_in(b[3], [3, 4, 12, 77])) and sql_in(b[1], PICKED)],
        {"early": 1, "last": 0}, {"pick": [[], [{"mid"}]]}),
    "not_in_over_a_null_keeps_nothing": (
        "select big.id from big, dim where big.g = dim.g and dim.g in (select k from pick) "
        "and big.v not in (select k from pick)",
        lambda: [],
        {"early": 1, "last": 0}, {"pick": [[{"big"}], []]}),
    # two reducers on one relation: each is placed by its own estimate
    "two_reducers": (
        "select big.id, mid.w from big, dim, mid where big.g = dim.g and big.k = mid.k "
        "and big.k in (select k from pick) and big.g in (select g from pairs)",
        lambda: [(b[0], m[1]) for b, _d, m in big_dim_mid()
                 if sql_in(b[1], PICKED) and sql_in(b[2], [g for _k, g in PAIRS])],
        {"early": 2, "last": 0}, {"pick": [[{"mid"}]], "pairs": [[{"mid"}]]}),
    # no equality links the relations: the cross join is the candidate, and the reducer is under it
    "before_a_cross_join": (
        "select dim.name, mid.w from dim, mid where dim.g in (select k from pick)",
        lambda: [(d[1], m[1]) for d in DIM for m in MID if sql_in(d[0], PICKED)],
        {"early": 1, "last": 0}, {"pick": [[{"mid"}]]}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_an_in_over_a_join_answers_as_the_plain_reference(sess, case):
    sql, reference, counted, where = CASES[case]
    before = placements()
    got = sorted(tuple(r) for r in sess.must_query(sql).rows)
    assert moved(before) == counted
    want = sorted(reference())
    assert got == want
    if case not in ("an_empty_subquery_keeps_nothing", "not_in_over_a_null_keeps_nothing"):
        assert want  # no vacuous comparison
    lines = explain(sess, sql)
    for table, above in where.items():
        found = sorted(([sorted(s) for s in a] for a in joins_above(lines, table)), key=repr)
        # the joins above a subquery's semi join that bring in a FROM relation
        found = [[s for s in a if s and not set(s) <= {"pick", "pairs", "wide", "nothing"}] for a in found]
        assert found == sorted(([sorted(s) for s in a] for a in above), key=repr), "\n".join(lines)


def test_a_where_over_one_relation_keeps_its_path(sess):
    before = placements()
    got = sorted(r[0] for r in sess.must_query("select id from big where k in (select k from pick)").rows)
    assert got == sorted(b[0] for b in BIG if sql_in(b[1], PICKED)) and got
    assert moved(before) == {"early": 0, "last": 0}


def test_a_plan_cache_hit_plans_nothing_and_counts_nothing(sess):
    sess.must_exec(
        "prepare sjp from 'select big.id from big, dim where big.g = dim.g "
        "and dim.g in (select k from pick) and big.id > ?'")

    def run(bound):
        sess.must_exec(f"set @a = {bound}")
        return sorted(r[0] for r in sess.must_query("execute sjp using @a").rows)

    def want(bound):
        return sorted(b[0] for b, d in big_dim() if sql_in(d[0], PICKED) and b[0] > bound)

    before = placements()
    assert run(100) == want(100)
    assert moved(before) == {"early": 1, "last": 0}  # planned once
    before = placements()
    assert run(250) == want(250) and want(250)
    assert moved(before) == {"early": 0, "last": 0}  # the cached plan ran


@pytest.mark.parametrize("where", [
    "dim.g in (select k from pick)",  # only a reducer: the join order starts from dim all the same
    "dim.g in (select k from pick) and big.g = dim.g and big.id < 50",
    "big.g = dim.g and big.id < 50",  # no subquery: the reordering alone
])
def test_a_star_lists_the_from_clauses_columns_in_its_order(sess, where):
    """`big` is the larger relation, so the join order starts from
    `dim`; `*` still reads big's columns first."""
    result = sess.must_query(f"select * from big, dim where {where}")
    assert list(result.columns) == ["id", "k", "g", "v", "g", "name"]
    keep = (lambda b, d: sql_in(d[0], PICKED)) if "pick" in where else (lambda b, d: True)
    join = (lambda b, d: b[2] == d[0] and b[0] < 50) if "big.g" in where else (lambda b, d: True)
    want = sorted((b + d for b in BIG for d in DIM if keep(b, d) and join(b, d)), key=repr)
    assert sorted((tuple(r) for r in result.rows), key=repr) == want and want
