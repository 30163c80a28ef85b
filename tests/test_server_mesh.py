"""A served mesh: `tidb_server.bootstrap` with `mesh_devices=4` over
four of the forced host devices, driven over a real socket at TPC-H
SF 0.01 (the benchmark's own population, loaders and references, as the
cell `tpch_sf1_mesh4.join_q5` uses them at SF 1).

Answers match the plain references and a one-device server cell for
cell, the float32 references fail, the write is read back from sharded
residency, the steady Q5 moves rows with an all-to-all, the columns sit
on four devices, and the exchange's counters say what a numpy count of
the rows says."""

import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SF, SEED, WIDTH = 0.01, 2900000029, 4
STATEMENTS = ("q5", "q1", "q6")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def bench():
    """The benchmark's harness, client, checks and loaders, by path."""
    import json

    added = [p for p in (BENCH, os.path.join(BENCH, "reference")) if p not in sys.path]
    sys.path.extend(added)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    files = {c["name"]: os.path.join(ROOT, c["file"]) for c in spec["configs"]}
    with open(files["tpch_sf1_mesh4"]) as f:
        mesh_config = json.load(f)
    with open(files["tpch_sf1"]) as f:
        one_config = json.load(f)
    assert mesh_config["mesh_devices"] == WIDTH and "mesh_devices" not in one_config
    harness = _load(os.path.join(BENCH, "run.py"), "bench_run_for_mesh_tests")
    import checks
    import mysql_client

    class B:
        pass

    b = B()
    b.harness, b.checks, b.client = harness, checks, mysql_client.MysqlClient
    b.mesh_loader = _load(os.path.join(BENCH, "loaders", "tpch_mesh.py"), "bench_tpch_mesh")
    b.mesh_config, b.one_config = mesh_config, one_config
    b.statements = {name: harness.Statement(name) for name in STATEMENTS}
    yield b
    for p in added:
        sys.path.remove(p)


def _serve(dep, client_cls):
    port = dep.start()
    client = client_cls(port)
    for sql in dep.prelude() + dep.analyze_statements():
        client.query(sql)
    return client


@pytest.fixture(scope="module")
def mesh(bench):
    """(deployment, client) of the four-device server, ANALYZEd."""
    dep = bench.mesh_loader.Deployment(bench.mesh_config, SEED, SF)
    client = _serve(dep, bench.client)
    yield dep, client
    client.close()
    dep.shutdown()


@pytest.fixture(scope="module")
def one(bench):
    """The same rows from the same seed behind a one-device server."""
    dep = bench.mesh_loader.tpch.Deployment(bench.one_config, SEED, SF)
    client = _serve(dep, bench.client)
    yield dep, client
    client.close()
    dep.shutdown()


def _last_flight(sql):
    from tidb_tpu.obs.flight import FLIGHT

    return [f for f in FLIGHT.rows() if f["sql"] == sql[:2048]][-1]


def test_sessions_share_one_mesh_of_four_devices(mesh):
    from tidb_tpu.parallel.mesh import shared_mesh
    from tidb_tpu.session import Session

    dep, _client = mesh
    assert dep.server.mesh_devices == WIDTH
    a = Session(dep.server.catalog, mesh_devices=dep.server.mesh_devices).executor
    b = Session(dep.server.catalog, mesh_devices=dep.server.mesh_devices).executor
    assert a.mesh_n == WIDTH and a.mesh is b.mesh is shared_mesh(WIDTH)
    assert len({d.id for d in a.mesh.devices.flat}) == WIDTH


@pytest.mark.parametrize("name", STATEMENTS)
def test_mesh_server_answers_as_the_reference_and_as_one_device(bench, mesh, one, name):
    st = bench.statements[name]
    got = mesh[1].query(st.sql)
    tally = bench.checks.Tally()
    tally.answers += 1
    st.judge(got, st.reference.expected(mesh[0].data), tally)
    assert tally.correct(), tally.report()
    single = one[1].query(st.sql)
    assert len(got) == len(single)
    for kind, cells in zip(st.reference.KINDS, zip(*[zip(r, s) for r, s in zip(got, single)])):
        for g, s in cells:
            if kind.startswith(("wide", "float")):
                # another summation order on four shards
                assert float(g) == pytest.approx(float(s), rel=1e-9), (name, kind)
            else:
                assert g == s, (name, kind)


@pytest.mark.parametrize("quantity", [300, 250])
def test_a_q18_on_the_mesh_server_answers_as_one_device(bench, mesh, one, quantity):
    """Q18's semi join sits inside the join order (PR 36): the mesh
    repartitions or gathers its sides where it stands. This population
    has no order over 300 (both servers answer nothing); 68 are over 250."""
    sql = bench.harness.Statement("q18").sql
    assert "> 300" in sql
    sql = sql.replace("> 300", f"> {quantity}")
    plan = [row[0] for row in mesh[1].query("explain " + sql)]
    semi = [i for i, line in enumerate(plan) if "JoinPlan kind=semi" in line]
    inner = [i for i, line in enumerate(plan) if "JoinPlan kind=inner" in line]
    assert len(semi) == 1 and inner[0] < semi[0] < inner[1], plan
    got, single = mesh[1].query(sql), one[1].query(sql)
    assert got == single
    data = mesh[0].data
    sums = np.bincount(data.col("lineitem", "l_orderkey"), weights=data.col("lineitem", "l_quantity"))
    orders = np.nonzero(sums > quantity * 100)[0]
    assert sorted(int(row[2]) for row in got) == sorted(orders.tolist())
    assert (len(orders) > 0) == (quantity == 250)


@pytest.mark.parametrize("name", STATEMENTS)
def test_the_float32_reference_fails_on_the_mesh_server_s_data(bench, mesh, name):
    st = bench.statements[name]
    low = bench.checks.Tally()
    low.answers += 1
    rendered = bench.checks.render_rows(
        st.reference.KINDS, st.reference.expected(mesh[0].data, precision="float32"))
    st.judge(rendered, st.reference.expected(mesh[0].data), low)
    assert not low.correct(), low.report()


def test_an_acknowledged_insert_is_read_back_from_sharded_residency(bench, mesh):
    dep, client = mesh
    write = dep.write_for_readback()
    reader = bench.statements[write["query"]]
    before = client.query(reader.sql)
    client.query(write["sql"])
    got = client.query(reader.sql)
    assert got != before
    tally = bench.checks.Tally()
    tally.answers += 1
    reader.judge(got, reader.reference.expected(dep.data, extra=write["extra"]), tally,
                 wrong="readback_wrong")
    assert tally.correct(), tally.report()
    stale = bench.checks.Tally()
    stale.answers += 1
    reader.judge(before, reader.reference.expected(dep.data, extra=write["extra"]), stale,
                 wrong="readback_wrong")
    assert not stale.correct()


def _steady_q5(dep, sql):
    """(executor, compiled plan) of a session that ran Q5 on the mesh."""
    from tidb_tpu.session import Session

    sess = Session(dep.server.catalog, db="tpch", mesh_devices=WIDTH)
    sess.execute(sql)
    (cq,) = sess.executor._cache.values()
    assert cq.steady is not None
    return sess.executor, cq


def _scatter_scopes(lowered):
    """{operator scope: most slots} of every scatter in a program's
    StableHLO, printed with debug_info."""
    import re

    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', lowered, re.M))
    scopes = {}
    for slots, loc in re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \([^)]*\) -> tensor<(\d+)x\w+> loc\((#loc\d+)\)',
        lowered, re.S,
    ):
        scopes[named[loc]] = max(scopes.get(named[loc], 0), int(slots))
    return scopes


def test_the_steady_q5_holds_an_all_to_all(bench, mesh):
    import jax

    from tidb_tpu.planner import physical

    dep, client = mesh
    sql = bench.statements["q5"].sql
    client.query(sql)
    executor, cq = _steady_q5(dep, sql)
    caps = dict(cq.steady[1])
    caps.pop(physical._OUT_NODE)
    assert cq.exchange_nids and cq.exchange_nids <= set(caps)
    inputs = executor._fetch_inputs(cq, mesh=executor.mesh)
    text = jax.jit(executor._make_program(cq, caps)).lower(inputs, {}).compile().as_text()
    assert "all-to-all" in text
    lowered = jax.jit(executor._make_program(cq, caps)).lower(inputs, {}).as_text(debug_info=True)
    for scope in ("exchange/sort", "exchange/pack", "exchange/all-to-all", "broadcast/all-gather"):
        assert scope in lowered, scope
    # the send buffers are gathers (PERF.md, PR 30: their scatters were
    # 321 ms of a 455-ms statement on the v5e). What scatters is a dense
    # table of a few slots: the nation and region lookups, the groups
    scopes = _scatter_scopes(lowered)
    assert scopes and not [s for s in scopes if "exchange" in s], scopes
    assert all(slots <= 32 for slots in scopes.values()), scopes


def test_a_scanned_column_has_a_shard_on_each_of_four_devices(mesh):
    from tidb_tpu.parallel.mesh import shared_mesh
    from tidb_tpu.storage import scan_table

    dep, _client = mesh
    batch, _dicts = scan_table(
        dep.server.catalog.table("tpch", "lineitem"), ["l_orderkey", "l_quantity"],
        mesh=shared_mesh(WIDTH))
    for name, col in batch.cols.items():
        shards = col.data.addressable_shards
        assert len({s.device.id for s in shards}) == len(shards) == WIDTH, name
        assert {tuple(s.data.shape) for s in shards} == {(col.data.shape[0] // WIDTH,)}


@pytest.mark.parametrize("which", ["mesh", "one"])
def test_the_flight_says_what_was_exchanged(bench, mesh, one, which):
    sql = bench.statements["q5"].sql
    client = {"mesh": mesh, "one": one}[which][1]
    client.query(sql)
    client.query(sql)
    flight = _last_flight(sql)
    if which == "mesh":
        assert flight["exchanges"] >= 1 and flight["exchange_rows"] > 0
        assert 0 < flight["exchange_bytes"] <= flight["exchange_rows"] * 64 * (WIDTH - 1)
    else:
        assert (flight["exchanges"], flight["exchange_rows"], flight["exchange_bytes"]) == (0, 0, 0)


def test_a_second_connection_compiles_and_uploads_nothing(bench, mesh):
    dep, client = mesh
    sql = bench.statements["q5"].sql
    client.query(sql)
    first = client.query(sql)
    before = bench.mesh_loader.compilations()
    second = bench.client(dep.server.port)
    try:
        for prelude in dep.prelude():
            second.query(prelude)
        assert second.query(sql) == first
    finally:
        second.close()
    flight = _last_flight(sql)
    assert bench.mesh_loader.compilations() == before
    assert flight["jit_compilations"] == 0 and flight["h2d_bytes"] == 0
    assert flight["plan_cache"] == "hit" and flight["exchanges"] >= 1


@pytest.mark.parametrize("build", ["Server", "bootstrap"])
def test_more_devices_than_jax_sees_is_refused_at_start_up(build):
    import jax

    import tidb_server
    from tidb_tpu.server import Server
    from tidb_tpu.storage import Catalog
    from tidb_tpu.utils.config import Config

    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError, match=f"mesh of {too_many} devices"):
        if build == "Server":
            Server(Catalog(), port=0, mesh_devices=too_many)
        else:
            # before any load: the TPC-H bootstrap would take seconds
            tidb_server.bootstrap(Config().override(port=0, mesh_devices=too_many), tpch_sf=1)


@pytest.mark.parametrize("statement", ["explain", "trace", "analyze"])
def test_explain_trace_and_analyze_are_served_from_the_mesh(bench, mesh, statement):
    _dep, client = mesh
    sql = bench.statements["q5"].sql
    if statement == "explain":
        text = "\n".join(row[0] for row in client.query("explain " + sql))
        assert "JoinPlan" in text and "broadcast=" in text
    elif statement == "trace":
        spans = "\n".join(str(row) for row in client.query("trace " + sql))
        for name in ("dispatch", "device-wait", "fetch"):
            assert name in spans, name
    else:
        client.query("analyze table orders")
        assert client.query(sql)


def test_the_watchdog_ranks_a_mesh_session(bench, mesh):
    from tidb_tpu.session import Session
    from tidb_tpu.utils.watchdog import ensure_watchdog

    dep, _client = mesh
    sess = Session(dep.server.catalog, db="tpch", mesh_devices=WIDTH)
    # a plan no connection has run: this session is admitted with its
    # inputs and its tiles, each tile counted on the four shards
    sess.execute("select count(*) from orders join customer on o_custkey = c_custkey"
                 " where c_acctbal > 100")
    inputs = sum(
        dep.server.catalog.table("tpch", t).nrows for t in ("orders", "customer"))
    assert sess.executor.last_working_set > inputs
    watchdog = ensure_watchdog(dep.server.catalog)
    before = watchdog.samples
    watchdog.sample()
    assert watchdog.samples > before and watchdog.top_consumer() is None


# ---------------------------------------------------------------------------
# the counters against a hand count
# ---------------------------------------------------------------------------

ROWS_A, ROWS_B = 6000, 5000


@pytest.fixture(scope="module")
def two_tables():
    """A mesh server over a(k, v), b(k, w): no statistics, both sides
    too alike in size for a broadcast, so the join repartitions both."""
    from tidb_tpu.bench.serve_load import MysqlClient
    from tidb_tpu.chunk import HostBlock, HostColumn
    from tidb_tpu.dtypes import INT64
    from tidb_tpu.server import Server
    from tidb_tpu.storage import Catalog, TableSchema

    rng = np.random.default_rng(29)
    tables = {
        "a": {"k": rng.integers(0, 4000, ROWS_A), "v": rng.integers(0, 100, ROWS_A)},
        "b": {"k": rng.integers(0, 4000, ROWS_B), "w": rng.integers(0, 100, ROWS_B)},
    }
    catalog = Catalog()
    catalog.create_database("hand", if_not_exists=True)
    for name, cols in tables.items():
        block = HostBlock.from_columns({
            c: HostColumn(INT64, data.astype(np.int64), np.ones(len(data), bool), None)
            for c, data in cols.items()})
        table = catalog.create_table("hand", name, TableSchema([(c, INT64) for c in cols]))
        table.replace_blocks([block])
    server = Server(catalog, port=0, mesh_devices=WIDTH)
    server.start_background()
    client = MysqlClient(server.port)
    client.query("use hand")
    yield tables, client
    client.close()
    server.shutdown()


@pytest.mark.parametrize("floor", [-1, 39, 79])
def test_exchange_counters_equal_a_numpy_count(two_tables, floor):
    """Rows: the valid rows entering each of the join's two
    repartitions. Bytes: those rows x the two int64 columns that travel
    with them x 3/4, as benchmarks/ici.py reckons."""
    from tidb_tpu.utils.metrics import REGISTRY

    ici = _load(os.path.join(BENCH, "ici.py"), "bench_ici_for_mesh_tests")
    tables, client = two_tables
    # ON conjuncts of one side filter below the join, and so below its exchange
    sql = f"select a.k, v, w from a join b on a.k = b.k and v > {floor} and w > {floor}"
    want = np.sum(
        (tables["a"]["v"] > floor)[:, None] & (tables["b"]["w"] > floor)[None, :]
        & (tables["a"]["k"][:, None] == tables["b"]["k"][None, :]))
    assert len(client.query(sql)) == want  # discover and steady: two programs
    counters = ("tidbtpu_executor_exchange_rows_total", "tidbtpu_executor_exchange_bytes_total")
    before = [REGISTRY.counter(name).value for name in counters]
    assert len(client.query(sql)) == want  # the steady program alone
    flight = _last_flight(sql)
    rows = int(np.sum(tables["a"]["v"] > floor) + np.sum(tables["b"]["w"] > floor))
    nbytes = ici.partition_bytes(rows, 2 * 8, WIDTH)
    assert nbytes == rows * 16 * 3 / 4
    assert (flight["exchanges"], flight["exchange_rows"], flight["exchange_bytes"]) == (2, rows, nbytes)
    after = [REGISTRY.counter(name).value for name in counters]
    assert [a - b for a, b in zip(after, before)] == [rows, nbytes]


def test_a_join_s_own_keys_stay_behind_its_parent_s_exchange(two_tables):
    """a x b emits a.k, a.v, b.k, b.w; the join above reads a.v, b.k and
    b.w of it. Column pruning says so on the plan (JoinPlan.needs) and
    the upper join's exchange moves those three, not four: a row's
    columns travel as lanes of one operand, out of which XLA drops no
    dead one (PERF.md, PR 30)."""
    ici = _load(os.path.join(BENCH, "ici.py"), "bench_ici_for_mesh_tests")
    tables, client = two_tables
    a, b = tables["a"], tables["b"]
    sql = ("select a.v, c.v from a join b on a.k = b.k "
           "join a c on b.w + 4000 * (b.k % 2) = c.k")
    lower = np.argwhere(a["k"][:, None] == b["k"][None, :])
    upper = (b["w"] + 4000 * (b["k"] % 2))[:, None] == a["k"][None, :]
    want = sorted(
        (int(a["v"][i]), int(a["v"][m])) for i, j in lower for m in np.nonzero(upper[j])[0])
    for _ in range(2):  # the second is the steady program alone
        got = client.query(sql)
    assert sorted((int(x), int(y)) for x, y in got) == want
    flight = _last_flight(sql)
    sides = [(ROWS_A, 2), (ROWS_B, 2), (len(lower), 3), (ROWS_A, 2)]
    assert (flight["exchanges"], flight["exchange_rows"]) == (4, sum(rows for rows, _ in sides))
    assert flight["exchange_bytes"] == sum(
        ici.partition_bytes(rows, cols * 8, WIDTH) for rows, cols in sides)


# ---------------------------------------------------------------------------
# a mesh plan's first program: the steady one, at estimated tiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("outcome, sql, compiles", [
    # one group, one row out: the estimates hold, one whole program
    ("kept", "select count(*), sum(v) from a where v > 10", 1),
    # a third of the rows estimated, 1 % pass: recompiled at the tight
    # tiles the first run returned, with no discover program between
    ("tightened", "select k, v from a where v > 98", 2),
    # every row passes a filter estimated at a third: the first
    # program overflows its output tile and discovery runs as before
    ("overflowed", "select k, v from a where v >= 0", 3),
])
def test_a_mesh_plan_compiles_its_steady_program_first(two_tables, outcome, sql, compiles):
    from tidb_tpu.utils.metrics import REGISTRY

    tables, client = two_tables
    counter = REGISTRY.counter(
        "tidbtpu_executor_steady_first_total", labels=("outcome",)).labels(outcome=outcome)
    before = counter.value
    got = client.query(sql)
    flight = _last_flight(sql)
    assert counter.value == before + 1
    assert flight["jit_compilations"] == compiles
    v = tables["a"]["v"]
    if outcome == "kept":
        assert [tuple(int(x) for x in got[0])] == [(int(np.sum(v > 10)), int(v[v > 10].sum()))]
    else:
        keep = v > 98 if outcome == "tightened" else v >= 0
        want = sorted(zip(tables["a"]["k"][keep].tolist(), v[keep].tolist()))
        assert sorted((int(k), int(x)) for k, x in got) == want
    assert client.query(sql) == got
    assert _last_flight(sql)["jit_compilations"] == 0
