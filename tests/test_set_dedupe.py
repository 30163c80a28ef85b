"""A semi or anti join reads its build side as a set
(planner/logical.py `_set_build`, `_set_dedupe`): an inner join under
it that the estimates say expands joins inputs made distinct on the
columns read of them above, unless an input is already unique on them
or one of those columns compares equal where it is not identical
(FLOAT, a case-insensitive string).

Answers against a plain Python reference (IN, NOT IN, EXISTS, a
correlated NOT EXISTS with a residual, NULLs in keys and predicates, an
empty build), the cases where no distinct goes in, and the counter
`tidbtpu_planner_set_dedupes_total` over the benchmark's Q95, Q18 and Q5."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _path in (os.path.join(BENCH, "reference"), BENCH):  # the loaders' own helpers
    if _path not in sys.path:
        sys.path.insert(0, _path)

COUNTER = "tidbtpu_planner_set_dedupes_total"


def dedupes():
    from tidb_tpu.utils.metrics import REGISTRY

    return sum(int(v) for name, _kind, v in REGISTRY.rows() if name == COUNTER)


def explain(session, sql):
    return [r[0] for r in session.execute("explain " + sql).rows]


# ---- the data: lines of orders (o) shipped from warehouses (w) ----------

LINES = [
    (i, None if i % 11 == 0 else i % 37, None if i % 13 == 0 else i % 5, 0.5 + i % 3, "aAb"[i % 3])
    for i in range(300)
]  # id (PRIMARY KEY), o, w, f (DOUBLE), c (case-insensitive)
PROBE = [(i, None if i % 9 == 0 else i - 5, None if i % 8 == 0 else i % 7) for i in range(60)]  # id, o, w
WHS = [(w,) for w in range(5)]  # w: one row a warehouse


@pytest.fixture(scope="module")
def sess():
    from tidb_tpu.session import Session

    s = Session()
    s.must_exec("create database if not exists sdd")
    s.must_exec("use sdd")

    def fill(name, cols, rows):
        s.must_exec(f"create table {name} ({cols})")
        for at in range(0, len(rows), 100):
            values = ", ".join(
                "(" + ", ".join("null" if v is None else repr(v) for v in row) + ")"
                for row in rows[at:at + 100])
            s.must_exec(f"insert into {name} values {values}")
        s.must_exec(f"analyze table {name}")

    fill("sales", "id int primary key, o int, w int, f double, c varchar(8) collate utf8mb4_general_ci", LINES)
    fill("probe", "id int, o int, w int", PROBE)
    fill("whs", "w int", WHS)
    return s


def pairs(pred=lambda a, b: True):
    """(l1, l2) of lines joined on o (NULL matches nothing) where pred holds."""
    return [(a, b) for a in LINES for b in LINES if a[1] is not None and a[1] == b[1] and pred(a, b)]


def ne(x, y):
    """SQL's `x <> y` as a filter: NULL on either side keeps nothing."""
    return x is not None and y is not None and x != y


def sql_in(value, members):
    """SQL's three-valued `value IN members`: True, False or None."""
    members = list(members)
    if not members:
        return False
    if value is None:
        return None
    if value in members:
        return True
    return None if None in members else False


TWO_WAREHOUSES = {a[1] for a, b in pairs(lambda a, b: ne(a[2], b[2]))}
CASES = {
    # Q95's shape: the orders shipped from two warehouses
    "in_over_a_self_join": (
        "select id from probe where o in (select l1.o from sales l1, sales l2 where l1.o = l2.o and l1.w <> l2.w)",
        lambda: [(p[0],) for p in PROBE if sql_in(p[1], TWO_WAREHOUSES)],
        2),
    # the <> as the join's own residual, not a filter above it
    "in_with_a_residual_on_the_join": (
        "select id from probe where o in (select l1.o from sales l1 join sales l2 on l1.o = l2.o and l1.w <> l2.w)",
        lambda: [(p[0],) for p in PROBE if sql_in(p[1], TWO_WAREHOUSES)],
        2),
    "not_in_over_a_self_join": (
        "select id from probe where o not in (select l1.o from sales l1, sales l2 where l1.o = l2.o and l1.w <> l2.w)",
        lambda: [(p[0],) for p in PROBE if sql_in(p[1], TWO_WAREHOUSES) is False],
        2),
    # the build's column is NULL on some rows: NULL IN (...) and x IN (..., NULL)
    "in_over_a_nullable_column": (
        "select id from probe where w in (select l1.w from sales l1, sales l2 where l1.o = l2.o and l2.w = 3)",
        lambda: [(p[0],) for p in PROBE if sql_in(p[2], {a[2] for a, b in pairs(lambda a, b: b[2] == 3)})],
        2),
    "not_in_over_a_nullable_column_keeps_nothing": (
        "select id from probe where w not in (select l1.w from sales l1, sales l2 where l1.o = l2.o)",
        lambda: [(p[0],) for p in PROBE if sql_in(p[2], {a[2] for a, b in pairs()}) is False],
        2),
    "a_row_value_in": (
        "select id from probe where (o, w) in (select l1.o, l2.w from sales l1, sales l2 where l1.o = l2.o and l1.w <> l2.w)",
        lambda: [(p[0],) for p in PROBE
                 if p[1] is not None and p[2] is not None
                 and (p[1], p[2]) in {(a[1], b[2]) for a, b in pairs(lambda a, b: ne(a[2], b[2]))}],
        2),
    "exists": (
        "select id from probe p where exists (select 1 from sales l1, sales l2 "
        "where l1.o = l2.o and l1.w <> l2.w and l1.o = p.o)",
        lambda: [(p[0],) for p in PROBE if p[1] in TWO_WAREHOUSES],
        2),
    # a residual over both sides: l2.w, read above the join, is a column of the set
    "correlated_not_exists_with_a_residual": (
        "select id from probe p where not exists (select 1 from sales l1, sales l2 "
        "where l1.o = l2.o and l1.o = p.o and l2.w > p.w)",
        lambda: [(p[0],) for p in PROBE
                 if not any(a[1] == p[1] and b[2] is not None and p[2] is not None and b[2] > p[2] for a, b in pairs())],
        2),
    "an_empty_build": (
        "select id from probe where o in (select l1.o from sales l1, sales l2 where l1.o = l2.o and l1.o + l2.o < 0)",
        lambda: [],
        2),
    "not_in_an_empty_build_keeps_every_row": (
        "select id from probe where o not in (select l1.o from sales l1, sales l2 where l1.o = l2.o and l1.o + l2.o < 0)",
        lambda: [(p[0],) for p in PROBE],
        2),
    # ---- no distinct goes in ----
    # each side is read on (o, id): the primary key is among them
    "an_input_already_unique_on_its_primary_key": (
        "select id from probe where o in (select l1.o from sales l1, sales l2 where l1.o = l2.o and l1.id <> l2.id)",
        lambda: [(p[0],) for p in PROBE if sql_in(p[1], {a[1] for a, b in pairs(lambda a, b: a[0] != b[0])})],
        0),
    "inputs_already_unique_as_group_by_outputs": (
        "select id from probe where o in (select g1.o from (select o, w from sales group by o, w) g1, "
        "(select o, w from sales group by o, w) g2 where g1.o = g2.o and g1.w <> g2.w)",
        lambda: [(p[0],) for p in PROBE if sql_in(p[1], TWO_WAREHOUSES)],
        0),
    # five warehouses of one row each: lines x whs keeps lines' rows
    "a_join_not_estimated_to_expand": (
        "select id from probe where o in (select l.o from sales l, whs where l.w = whs.w)",
        lambda: [(p[0],) for p in PROBE if sql_in(p[1], {a[1] for a in LINES if a[2] is not None})],
        0),
    "a_float_column_read_above": (
        "select id from probe where o in (select l1.o from sales l1, sales l2 where l1.o = l2.o and l1.f <> l2.f)",
        lambda: [(p[0],) for p in PROBE if sql_in(p[1], {a[1] for a, b in pairs(lambda a, b: a[3] != b[3])})],
        0),
    "a_case_insensitive_string_read_above": (
        "select id from probe where o in (select l1.o from sales l1, sales l2 where l1.o = l2.o and l1.c <> l2.c)",
        lambda: [(p[0],) for p in PROBE
                 if sql_in(p[1], {a[1] for a, b in pairs(lambda a, b: a[4].lower() != b[4].lower())})],
        0),
    # a SUM reads how many rows the join made
    "a_sum_inside_the_build": (
        "select id from probe where o in (select l1.o from sales l1, sales l2 where l1.o = l2.o "
        "group by l1.o having sum(l2.w) > 60)",
        lambda: [(p[0],) for p in PROBE if sql_in(p[1], {
            o for o in {a[1] for a, _b in pairs()}
            if sum(b[2] for a, b in pairs() if a[1] == o and b[2] is not None) > 60})],
        0),
    "a_plain_inner_join_outside_any_subquery": (
        "select l1.id, l2.w from sales l1, sales l2 where l1.o = l2.o and l1.w <> l2.w",
        lambda: [(a[0], b[2]) for a, b in pairs(lambda a, b: ne(a[2], b[2]))],
        0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_build_read_as_a_set_answers_as_the_plain_reference(sess, case):
    sql, reference, distincts = CASES[case]
    before = dedupes()
    got = sorted(tuple(r) for r in sess.must_query(sql).rows)
    assert dedupes() - before == distincts
    want = sorted(reference())
    assert got == want
    if case not in ("an_empty_build", "not_in_over_a_nullable_column_keeps_nothing"):
        assert want  # no vacuous comparison
    lines = explain(sess, sql)
    assert sum(t.strip().startswith("Aggregate") and "aggs=[]" in t for t in lines) >= distincts, "\n".join(lines)


def test_the_distincts_stand_under_the_self_join(sess):
    lines = [t.strip() for t in explain(sess, CASES["in_over_a_self_join"][0])]
    join = next(i for i, t in enumerate(lines) if t.startswith("JoinPlan kind=inner"))
    assert lines[join + 1].startswith("Aggregate groups=['l1.o', 'l1.w'] aggs=[]"), "\n".join(lines)
    assert lines[join + 2].startswith("Scan table=sdd.sales")
    assert lines[join + 3].startswith("Aggregate groups=['l2.o', 'l2.w'] aggs=[]"), "\n".join(lines)


def test_a_plan_cache_hit_counts_nothing(sess):
    sess.must_exec(
        "prepare sdd from 'select id from probe where o in (select l1.o from sales l1, sales l2 "
        "where l1.o = l2.o and l1.w <> l2.w) and id > ?'")

    def run(bound):
        sess.must_exec(f"set @a = {bound}")
        return sorted(r[0] for r in sess.must_query("execute sdd using @a").rows)

    def want(bound):
        return sorted(p[0] for p in PROBE if sql_in(p[1], TWO_WAREHOUSES) and p[0] > bound)

    before = dedupes()
    assert run(10) == want(10)
    assert dedupes() - before == 2  # planned once
    before = dedupes()
    assert run(20) == want(20) and want(20)
    assert dedupes() - before == 0  # the cached plan ran


# ---- the benchmark's statements -----------------------------------------


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sql(name):
    with open(os.path.join(BENCH, "queries", name + ".sql")) as f:
        return " ".join(f.read().split())


def _analyzed(loader_path, name, sf, seed):
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog

    loader = _load(loader_path, name)
    tables = loader.datagen.generate(sf, seed)
    catalog = Catalog()
    loader.bulk_load(catalog, tables)
    session = Session(catalog, db=loader.DATABASE)
    for table in tables:
        session.execute(f"analyze table {table}")
    return session


@pytest.mark.parametrize("statement, want", [("q95", 6), ("q18", 0), ("q5", 0)])
def test_the_benchmarks_statements_count_their_distincts(statement, want):
    """Q95's two INs: the self-join's two inputs under each, web_returns
    and ws_wh's output under the second (six, the CTE planned twice);
    Q18's IN is a HAVING over a group-by with no join under it, Q5 has
    no subquery."""
    if statement == "q95":
        session = _analyzed("loaders/tpcds.py", "sdd_loader_tpcds", 0.01, 101)
    else:
        session = _analyzed("loaders/tpch.py", f"sdd_loader_{statement}", 0.01, 1)
    before = dedupes()
    lines = explain(session, _sql(statement))
    assert dedupes() - before == want
    assert sum(t.strip().startswith("Aggregate") and "aggs=[]" in t for t in lines) == want
