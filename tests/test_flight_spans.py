"""FLIGHT.span (obs/flight.py): one call per boundary. The spans of a
served statement nest and add up, charge the phases they are named
after, lie on the profiler's clock under the flight's qid, name the
program and its operators on the device, and carry what ran beside
the statement."""

import glob
import os
import threading
import time

import pytest

from tidb_tpu.obs.flight import FLIGHT, SPANS, FlightRecorder
from tidb_tpu.session import Session
from tidb_tpu.storage import Catalog

JOIN_AGG = (
    "select t.a, sum(u.c) from t join u on t.a = u.a"
    " where u.c > 5 group by t.a"
)


def _load(execute):
    execute("create table t (a bigint, b bigint)")
    execute("insert into t values (1, 2), (3, 4), (5, 6)")
    execute("create table u (a bigint, c bigint)")
    execute("insert into u values (1, 20), (3, 40)")


@pytest.fixture()
def served():
    """(query, server): a client on a real socket of a Server."""
    from tidb_tpu.bench.serve_load import MysqlClient
    from tidb_tpu.server import Server

    srv = Server(Catalog(), port=0)
    srv.start_background()
    client = MysqlClient(srv.port)
    try:
        _load(client.query)
        yield client.query, srv
    finally:
        client.close()
        srv.shutdown()


def _by_path(flight):
    return {s["name"]: s for s in flight["spans"]}


def _last_qid():
    rows = FLIGHT.rows()
    return rows[-1]["qid"] if rows else 0


def _flights_since(qid):
    """The flights finished after ``qid`` was the newest (the ring is
    bounded: its length says nothing once it is full)."""
    return [f for f in FLIGHT.rows() if f["qid"] > qid]


def _parent(path):
    """The path of the span a path nests in: the longest declared
    prefix (``wire/write`` holds a slash of its own)."""
    for name in SPANS:
        if path.endswith("/" + name):
            return path[: -len(name) - 1]
    return None


def _assert_additive(flight, eps=1e-9):
    """Every span lies inside a span of its parent's path, and the
    children of a path never exceed it (a path may repeat: a cold
    statement dispatches, and compiles, twice)."""
    by_path, children = {}, {}
    for span in flight["spans"]:
        by_path.setdefault(span["name"], []).append(span)
    for path, spans in by_path.items():
        parent = _parent(path)
        if parent is None:
            continue
        assert parent in by_path, (path, sorted(by_path))
        for span in spans:
            assert any(
                span["start_s"] >= p["start_s"] - eps
                and span["start_s"] + span["seconds"]
                <= p["start_s"] + p["seconds"] + eps
                for p in by_path[parent]
            ), path
            children[parent] = children.get(parent, 0.0) + span["seconds"]
    for parent, total in children.items():
        assert total <= sum(p["seconds"] for p in by_path[parent]) + eps, parent


class TestSpanTree:
    def test_served_statement_has_the_whole_tree(self, served):
        query, _srv = served
        query(JOIN_AGG)  # discover + steady compile
        query(JOIN_AGG)
        flight = FLIGHT.rows()[-1]
        assert flight["sql"] == JOIN_AGG
        spans = _by_path(flight)
        assert set(spans) == {
            "stmt", "stmt/session", "stmt/session/parse",
            "stmt/session/resolve",
            "stmt/session/plan", "stmt/session/execute",
            "stmt/session/execute/plan-cache",
            "stmt/session/execute/inputs", "stmt/session/execute/dispatch",
            "stmt/session/execute/dispatch/launch",
            "stmt/session/execute/dispatch/enqueue-fetch",
            "stmt/session/execute/device-wait", "stmt/session/execute/fetch",
            "stmt/session/final-merge", "stmt/session/observe",
            "stmt/wire/write",
        }
        _assert_additive(flight)
        root = spans["stmt"]
        assert root["start_s"] == 0.0
        assert flight["served_s"] == root["seconds"]
        # start_ts / duration_s stay where they were taken: the flight
        # begins after the parse and ends before observe and the write
        assert flight["duration_s"] < spans["stmt/session"]["seconds"]
        assert set(spans["stmt"]) == {"name", "start_s", "seconds"}

    def test_phases_are_what_the_spans_charged(self, served):
        query, _srv = served
        query(JOIN_AGG)  # this one compiles inside execute/dispatch
        cold = FLIGHT.rows()[-1]
        query(JOIN_AGG)
        warm = FLIGHT.rows()[-1]
        for flight in (cold, warm):
            spans, ph = _by_path(flight), flight["phases"]
            for phase in ("parse", "plan", "final-merge"):
                assert ph[phase]["seconds"] == spans["stmt/session/" + phase]["seconds"]
            compiled = sum(
                s["seconds"] for s in flight["spans"]
                if s["name"].endswith("/compile")
            )
            assert ph.get("compile", {"seconds": 0.0})["seconds"] == pytest.approx(
                compiled, abs=1e-12
            )
            # compile is taken out of execute: the two stay additive
            assert ph["execute"]["seconds"] == pytest.approx(
                spans["stmt/session/execute"]["seconds"] - compiled, abs=1e-9
            )
            _assert_additive(flight)
        assert cold["jit_compilations"] >= 1 and "compile" in cold["phases"]
        assert any(p.endswith("/dispatch/launch/compile") for p in _by_path(cold))
        assert "compile" not in warm["phases"]

    def test_dispatch_is_the_launch_then_the_queued_copies(self, served):
        """``dispatch`` holds two spans one after the other: ``launch``
        (the jitted call) and ``enqueue-fetch`` (the copies queued
        behind the program); ``dispatch_ms`` keeps its meaning."""
        query, _srv = served
        query(JOIN_AGG)
        query(JOIN_AGG)
        spans = _by_path(FLIGHT.rows()[-1])
        dispatch = spans["stmt/session/execute/dispatch"]
        launch = spans["stmt/session/execute/dispatch/launch"]
        queued = spans["stmt/session/execute/dispatch/enqueue-fetch"]
        assert launch["start_s"] + launch["seconds"] <= queued["start_s"]
        assert launch["seconds"] + queued["seconds"] <= dispatch["seconds"]
        # the statement's own Python has names: resolving and the plan
        # cache, each a leaf where it belongs
        for path in ("stmt/session/resolve", "stmt/session/execute/plan-cache"):
            assert not any(p.startswith(path + "/") for p in spans), path
        assert spans["stmt/session/resolve"]["start_s"] < spans["stmt/session/plan"]["start_s"]
        cache = spans["stmt/session/execute/plan-cache"]
        assert cache["start_s"] + cache["seconds"] <= dispatch["start_s"]

    def test_a_nested_statement_resolves_nothing(self):
        """TRACE's inner statement runs nested: its rows start at
        ``plan``, as the outer statement's ``resolve`` is the top
        statement's."""
        sess = Session(Catalog())
        _load(sess.execute)
        sess.execute(JOIN_AGG)
        rows = sess.execute("trace " + JOIN_AGG).rows
        names = [row[0].strip() for row in rows]
        assert "resolve" not in names
        assert names[0] == "plan" and {"plan-cache", "launch"} <= set(names)
        spans = _by_path(FLIGHT.rows()[-1])
        assert "session/resolve" in spans

    def test_session_is_the_root_without_a_server(self):
        sess = Session(Catalog())
        _load(sess.execute)
        sess.execute(JOIN_AGG)
        sess.execute(JOIN_AGG)
        flight = FLIGHT.rows()[-1]
        spans = _by_path(flight)
        assert "session" in spans and "stmt" not in spans
        assert flight["served_s"] == spans["session"]["seconds"]
        assert spans["session/parse"]["seconds"] == flight["phases"]["parse"]["seconds"]
        _assert_additive(flight)

    def test_failed_statement_leaves_no_open_span(self):
        sess = Session(Catalog())
        before = len(FLIGHT.rows())
        with pytest.raises(Exception):
            sess.execute("select * from no_such_table_for_spans")
        assert FLIGHT.current() is None
        assert getattr(FLIGHT._tls, "trip", None) is None
        assert len(FLIGHT.rows()) == before
        sess.execute("select 1")  # the next statement starts a clean tree
        assert _by_path(FLIGHT.rows()[-1])["session"]["start_s"] == 0.0

    def test_prepared_execute_hangs_from_one_stmt_root(self, served):
        """COM_STMT_EXECUTE opens ``stmt`` as COM_QUERY does: the write
        is on the flight the statement began, under its qid."""
        from test_server import PreparedClient

        _query, srv = served
        client = PreparedClient(srv.port)
        try:
            sid, _n = client.prepare("select a, b from t where a = ?")
            before = _last_qid()
            assert client.execute(sid, [3])["rows"] == [(3, 4)]
        finally:
            client.close()
        flights = _flights_since(before)
        assert len(flights) == 1
        spans = _by_path(flights[0])
        assert {"stmt", "stmt/wire/write"} <= set(spans)
        assert all(path.startswith("stmt") for path in spans)
        assert flights[0]["served_s"] == spans["stmt"]["seconds"]
        _assert_additive(flights[0])

    def test_batch_statements_keep_their_own_spans(self):
        """One parse, charged to the first statement; each flight owns
        the spans that closed while it was the trip's flight."""
        sess = Session(Catalog())
        before = _last_qid()
        sess.execute("select 1; select 2")
        first, second = _flights_since(before)
        assert first["qid"] != second["qid"]
        assert "parse" in first["phases"] and "parse" not in second["phases"]
        a, b = _by_path(first), _by_path(second)
        assert "session/parse" in a and "session" not in a
        assert "session/parse" not in b and "session" in b
        assert first["served_s"] == 0.0
        assert second["served_s"] == b["session"]["seconds"]

    def test_only_a_statement_root_draws_a_qid(self):
        """A span with nothing above it that is no statement's root (a
        worker's compile) is annotated with qid 0 and kept nowhere: the
        flights' qids do not skip."""
        f = FlightRecorder()
        with f.span("compile"):
            with f.span("dispatch"):
                pass
        with f.span("wire/write"):
            pass
        with f.span("session"):
            rec = f.begin("select 1")
            f.finish(0.0)
        assert rec.qid == 1
        assert [row[0] for row in rec.spans] == ["session"]
        assert f.rows()[-1]["served_s"] == rec.served_s > 0

    def test_undeclared_span_is_rejected(self):
        f = FlightRecorder()
        with pytest.raises(ValueError, match="undeclared flight span"):
            f.span("no-such-span")

    def test_span_outside_any_statement_still_counts_its_phase(self):
        """A compile on a thread with no flight (a worker, ANALYZE's
        kernels) charges the phase counter as note_phase always did."""
        from tidb_tpu.obs.flight import _c_phase_seconds

        f = FlightRecorder()
        series = _c_phase_seconds().labels(phase="compile")
        before = series.value
        with f.span("compile") as span:
            time.sleep(0.002)
        assert span.seconds >= 0.002
        assert series.value - before == pytest.approx(span.seconds)
        assert getattr(f._tls, "trip", None) is None


class TestCollector:
    """Python's collector on every flight (obs/flight.py ``_on_gc``):
    a pass inside a statement's root span shows in its ``gc_s`` and
    ``gc_passes``; a statement with none reads 0."""

    def test_a_pass_inside_the_root_is_on_the_flight(self, served):
        import gc

        from tidb_tpu.utils import failpoint

        query, _srv = served
        query(JOIN_AGG)
        gc.disable()  # no pass but the one the hook forces
        try:
            failpoint.enable("server/dispatch-query", lambda: gc.collect())
            try:
                query(JOIN_AGG)
            finally:
                failpoint.disable("server/dispatch-query")
            collected = FLIGHT.rows()[-1]
            query(JOIN_AGG)
            quiet = FLIGHT.rows()[-1]
        finally:
            gc.enable()
        assert collected["sql"] == quiet["sql"] == JOIN_AGG
        assert collected["gc_passes"][2] >= 1 and collected["gc_s"] > 0
        assert collected["gc_s"] < collected["served_s"]
        assert quiet["gc_s"] == 0.0 and quiet["gc_passes"] == [0, 0, 0]

    def test_one_hook_counts_every_generation(self):
        import gc

        from tidb_tpu.obs import flight

        assert gc.callbacks.count(flight._on_gc) == 1
        before = list(flight._GC)
        gc.collect(0)
        gc.collect(1)
        gc.collect(2)
        after = flight._GC
        assert [after[i] - before[i] for i in (1, 2, 3)] == [1, 1, 1]
        assert after[0] > before[0]

    def test_a_flight_reads_no_pass_until_its_root_closes(self):
        f = FlightRecorder()
        with f.span("session"):
            rec = f.begin("select 1")
            f.finish(0.0)
            assert rec.gc_s == 0.0 and rec.gc_passes == [0, 0, 0]
        assert f.rows()[-1]["gc_s"] >= 0.0
        assert len(f.rows()[-1]["gc_passes"]) == 3


class TestProfilerClock:
    def test_annotations_match_the_flight_spans(self, served, tmp_path):
        """(a) under jax.profiler the served statement leaves
        ``tidbtpu/`` annotations that match the flight's spans by name,
        qid, nesting and seconds, and the executable's host-side
        execute event lies inside ``execute/dispatch``."""
        import jax
        from jax.profiler import ProfileData

        query, _srv = served
        query(JOIN_AGG)
        query(JOIN_AGG)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            query(JOIN_AGG)
            query(JOIN_AGG)
            import gc

            gc.collect()  # a pass of generation 2, on this thread
        finally:
            jax.profiler.stop_trace()
        flights = [f for f in FLIGHT.rows() if f["sql"] == JOIN_AGG][-2:]
        found = glob.glob(
            os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True
        )
        assert found
        data = ProfileData.from_file(found[-1])
        notes, launches, passes = {}, [], []  # (qid, path) -> (line, start, end)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    start, end = e.start_ns, e.start_ns + e.duration_ns
                    stats = dict(e.stats)
                    if e.name == "tidbtpu/gc/gen2":
                        passes.append((start, end))
                    if e.name.startswith("tidbtpu/") and "qid" in stats:
                        qid = stats["qid"]
                        notes[(qid, e.name[len("tidbtpu/"):])] = (
                            line.name, start, end
                        )
                    elif e.name == "PjitFunction(steady)" or e.name.endswith(
                        "Executable::Execute"
                    ):
                        launches.append((line.name, e.name, start, end))
        for flight in flights:
            qid = flight["qid"]
            root = notes[(qid, "stmt")]
            assert {p for q, p in notes if q == qid} == set(_by_path(flight))
            for path, span in _by_path(flight).items():
                line, start, end = notes[(qid, path)]
                assert line == root[0]  # the statement's own thread
                # the annotation brackets the perf_counter pair: a few
                # microseconds wider, on the same clock from the root
                assert (end - start) / 1e9 == pytest.approx(
                    span["seconds"], abs=2e-3
                )
                assert (start - root[1]) / 1e9 == pytest.approx(
                    span["start_s"], abs=2e-3
                )
                parent = _parent(path)
                if parent is not None:
                    _l, p_start, p_end = notes[(qid, parent)]
                    assert p_start <= start and end <= p_end, path
        assert passes and all(end > start for start, end in passes)
        # every launch of the statement's program lies inside a dispatch
        steady = [x for x in launches if x[1] == "PjitFunction(steady)"]
        executes = [x for x in launches if x[1].endswith("Executable::Execute")]
        assert len(steady) >= 2 and executes
        dispatches = [
            v for (q, p), v in notes.items() if p.endswith("execute/dispatch")
        ]
        for line, _name, start, end in steady + executes:
            if line != dispatches[0][0]:
                continue  # another thread's program (none expected)
            assert any(d[1] <= start and end <= d[2] for d in dispatches), _name


class TestNamesOnTheDevice:
    def test_hlo_carries_operator_scopes_and_module_kind(self, monkeypatch):
        """(c) the compiled HLO of a join + aggregate plan carries each
        operator's ``label#nid`` in ``op_name`` (innermost last), and
        the module is named after the kind in watched_jit's sig."""
        import jax

        from tidb_tpu.planner.physical import PlanCompiler, scope_name

        real_jit, calls = jax.jit, []

        def spy_jit(fn, **kw):
            jitted = real_jit(fn, **kw)

            def call(*a, **k):
                calls.append((getattr(fn, "__name__", ""), jitted, a, k))
                return jitted(*a, **k)

            call.lower = jitted.lower
            return call

        sess = Session(Catalog())
        _load(sess.execute)
        monkeypatch.setattr(jax, "jit", spy_jit)
        sess.execute(JOIN_AGG)
        monkeypatch.undo()
        kinds = [name for name, *_ in calls]
        # the join expands (neither key is unique) and the aggregate is
        # sorted: every knob has a first tile, so the first program may be
        # the steady one (PR 33); else discovery runs before it
        assert "steady" in kinds and set(kinds) <= {"discover", "steady"}
        _name, jitted, a, k = [c for c in calls if c[0] == "steady"][-1]
        hlo = jitted.lower(*a, **k).compile().as_text()
        assert hlo.startswith("HloModule jit_steady")
        compiler = PlanCompiler(sess.catalog, resolver=sess.executor._resolve)
        compiler.compile(sess._last_plan)
        scopes = {
            label.split(" ")[0]: scope_name(label, nid).replace("'", "\\'")
            for nid, _depth, label in compiler.node_labels
        }
        assert {"Join", "Aggregate", "Selection"} <= set(scopes)
        # operators that emit work carry their scope; the join's ops
        # lie under the aggregate's, as the plan nests
        assert scopes["Join"] in hlo and scopes["Selection"] in hlo
        assert f"{scopes['Aggregate']}/{scopes['Join']}/" in hlo
        assert f"{scopes['Join']}/{scopes['Selection']}/" in hlo

    def test_scope_name_is_one_short_segment(self):
        from tidb_tpu.planner.physical import scope_name

        name = scope_name("Selection pred=div(a, b) / " + "x" * 200, 7)
        assert "/" not in name and name.endswith("#7") and len(name) <= 66

    @pytest.mark.parametrize(
        "kind", ["steady", "discover", "stream-partial", "stream-final",
                 "stream-sort-chunk"],
    )
    def test_watched_jit_names_the_module_by_kind(self, kind, monkeypatch):
        import jax
        import jax.numpy as jnp

        from tidb_tpu.obs.engine_watch import watched_jit

        real_jit, seen = jax.jit, []
        monkeypatch.setattr(
            jax, "jit", lambda fn, **kw: seen.append(fn) or real_jit(fn, **kw)
        )
        wrapped = watched_jit(lambda x: x + 1, sig=(kind, ("test-sig", kind)))
        monkeypatch.undo()
        assert int(wrapped(jnp.int32(1))) == 2
        name = kind.replace("-", "_")
        assert seen[0].__name__ == name
        assert real_jit(seen[0]).lower(jnp.int32(1)).as_text().startswith(
            f"module @jit_{name}"
        )


class TestBackground:
    def test_overlapping_tick_shows_on_the_flight_and_in_the_slow_log(self):
        """(d) a tick that overlaps a statement is in its ``background``
        and after the phases of its slow-log line; one that ended
        before the statement is in neither."""
        from tidb_tpu.utils.metrics import SLOW_LOG

        sess = Session(Catalog())
        _load(sess.execute)
        sess.execute("set tidb_slow_log_threshold = 0")
        with FLIGHT.background("ttl-worker"):
            pass  # over before the statement starts
        inside, release = threading.Event(), threading.Event()

        def tick():
            with FLIGHT.background("stats-auto-analyze"):
                inside.set()
                release.wait(10)

        th = threading.Thread(target=tick)
        th.start()
        try:
            assert inside.wait(10)
            sess.execute(JOIN_AGG)
        finally:
            release.set()
            th.join(10)
        assert not th.is_alive()
        flight = FLIGHT.rows()[-1]
        beside = dict(flight["background"])
        assert set(beside) == {"stats-auto-analyze"}
        # open all through the statement: from the root's start to finish
        assert 0 < beside["stats-auto-analyze"] <= flight["served_s"]
        assert beside["stats-auto-analyze"] >= flight["duration_s"]
        phases = SLOW_LOG.rows()[-1][5]
        assert "execute=" in phases
        assert phases.index("beside:stats-auto-analyze=") > phases.index("execute=")
        assert "ttl-worker" not in phases
        # once closed, the tick is found by the flights it overlapped only
        sess.execute(JOIN_AGG)
        assert FLIGHT.rows()[-1]["background"] == []

    def test_closed_tick_overlap_is_clipped_to_the_statement(self):
        f = FlightRecorder()
        f._ticks.append(("a", 1.0, 2.0))   # before
        f._ticks.append(("b", 2.5, 3.5))   # straddles the start
        f._ticks.append(("b", 3.6, 3.8))   # inside
        f._ticks.append(("c", 4.5, 6.0))   # straddles the end
        beside = dict(f._ticks_beside(3.0, 5.0))
        assert beside == pytest.approx({"b": 0.5 + 0.2, "c": 0.5})

    @pytest.mark.parametrize("loop", ["stats-auto-analyze", "ttl-worker",
                                      "watchdog-instance"])
    def test_server_loops_tick_inside_background(self, loop):
        """The loops bootstrap/serve_forever start run each tick inside
        FLIGHT.background under their thread's name."""
        from tidb_tpu.stats.handle import StatsHandle
        from tidb_tpu.utils.ttl import TTLWorker
        from tidb_tpu.utils.watchdog import InstanceWatchdog

        catalog = Catalog()
        FLIGHT._ticks.clear()
        if loop == "watchdog-instance":
            worker = InstanceWatchdog(catalog, interval=0.01)
            stop = worker.stop_flag.set
        else:
            cls = StatsHandle if loop == "stats-auto-analyze" else TTLWorker
            worker = cls(catalog, interval_s=0.01)
            stop = worker.stop
        worker.start()
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not any(
                name == loop for name, _a, _b in list(FLIGHT._ticks)
            ):
                time.sleep(0.01)
        finally:
            stop()
        assert any(name == loop for name, _a, _b in list(FLIGHT._ticks))


def test_span_call_is_cheap_without_a_profiler():
    """Nineteen spans a statement: bounded loosely here (a shared CPU);
    PERF.md holds the measured cost."""
    f = FlightRecorder()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with f.span("stmt"):
            with f.span("session"):
                pass
    per_span = (time.perf_counter() - t0) / (2 * n)
    assert per_span < 100e-6
