"""Metrics, slow-query log, and statement summary.

Reference: pkg/metrics (Prometheus collectors), slow log read back as
INFORMATION_SCHEMA.SLOW_QUERY (pkg/executor/slow_query.go), and
per-digest statement summary (statement_summary.go:73). VERDICT round-1
missing #9. Round-2 additions: gauges, metric labels, exposition-format
round trip, /dcn, and the live /status connection count.
"""

import json
import re
import urllib.request

import pytest

from tidb_tpu.session import Session
from tidb_tpu.storage import Catalog
from tidb_tpu.utils.metrics import REGISTRY, Registry, sql_digest


@pytest.fixture()
def sess():
    return Session(Catalog())


def test_sql_digest_normalizes_literals():
    a = sql_digest("SELECT * FROM t WHERE a = 5 AND s = 'x'")
    b = sql_digest("select  *  from t where a = 99 and s = 'zzz'")
    assert a == b
    assert "?" in a and "5" not in a


def test_statement_summary_aggregates(sess):
    # distinctive shape so the digest is unique even though the summary
    # registry is process-global across the test suite
    sess.execute("create table obs_t (a bigint, bb bigint)")
    sess.execute("insert into obs_t values (1, 7),(2, 8)")
    for i in range(3):
        sess.execute(f"select sum(a + bb) from obs_t where a > {i}")
    digest = sql_digest("select sum(a + bb) from obs_t where a > 0")
    r = sess.must_query(
        "select exec_count from information_schema.statements_summary "
        f"where digest_text = '{digest}'"
    )
    assert r.rows and r.rows[0][0] >= 3  # three literals, one digest


def test_slow_log_threshold(sess):
    sess.execute("create table t (a bigint)")
    sess.execute("insert into t values (1)")
    sess.execute("set tidb_slow_log_threshold = 0")  # log everything
    sess.execute("select count(*) from t")
    r = sess.must_query(
        "select count(*) from information_schema.slow_query "
        "where query like 'select count%'"
    )
    assert r.rows[0][0] >= 1
    # high threshold: fast statements stay out
    sess.execute("set tidb_slow_log_threshold = 2000000")
    before = sess.must_query(
        "select count(*) from information_schema.slow_query"
    ).rows[0][0]
    sess.execute("select count(*) from t")
    after = sess.must_query(
        "select count(*) from information_schema.slow_query"
    ).rows[0][0]
    assert after == before


def test_metrics_counters_and_prometheus_render(sess):
    sess.execute("create table t (a bigint)")
    sess.execute("insert into t values (1)")
    sess.execute("select a from t")
    sess.execute("select a from t")  # plan cache hit
    r = sess.must_query(
        "select value from information_schema.metrics "
        "where name = 'tidbtpu_executor_plan_cache_hits_total'"
    )
    assert r.rows and r.rows[0][0] >= 1
    text = REGISTRY.render()
    assert "# TYPE tidbtpu_session_statements_total counter" in text
    assert "tidbtpu_session_query_duration_seconds_count" in text


class TestGaugesAndLabels:
    """Satellite: Gauge (set/inc/dec) + metric labels with correct
    Prometheus text exposition, on a private Registry so the assertions
    are exact."""

    def test_gauge_set_inc_dec(self):
        reg = Registry()
        g = reg.gauge("tidbtpu_test_pool_size", "g")
        g.set(5)
        g.inc(2)
        g.dec(3)
        assert g.value == 4
        g.set_max(2)
        assert g.value == 4  # high-water keeps the max
        g.set_max(9)
        assert g.value == 9
        assert ("tidbtpu_test_pool_size", "gauge", 9.0) in reg.rows()
        assert "# TYPE tidbtpu_test_pool_size gauge" in reg.render()

    def test_labeled_counter_children_and_escaping(self):
        reg = Registry()
        c = reg.counter("tidbtpu_test_dispatches", "d", labels=("host",))
        c.labels(host="h1").inc()
        c.labels(host="h1").inc()
        c.labels(host='we"ird\\h').inc()
        text = reg.render()
        assert 'tidbtpu_test_dispatches{host="h1"} 2' in text
        assert 'tidbtpu_test_dispatches{host="we\\"ird\\\\h"} 1' in text
        names = [n for n, _k, _v in reg.rows()]
        assert 'tidbtpu_test_dispatches{host="h1"}' in names

    def test_labeled_histogram_cumulative_buckets(self):
        reg = Registry()
        h = reg.histogram("tidbtpu_test_lat_seconds", "h", labels=("op",))
        h.labels(op="scan").observe(0.003)
        h.labels(op="scan").observe(0.004)
        h.labels(op="scan").observe(5.0)
        text = reg.render()
        # cumulative le buckets: 0.001 -> 0, 0.005 -> 2, ..., 10 -> 3
        assert 'tidbtpu_test_lat_seconds_bucket{op="scan",le="0.001"} 0' in text
        assert 'tidbtpu_test_lat_seconds_bucket{op="scan",le="0.005"} 2' in text
        assert 'tidbtpu_test_lat_seconds_bucket{op="scan",le="10"} 3' in text
        assert 'tidbtpu_test_lat_seconds_bucket{op="scan",le="+Inf"} 3' in text
        assert 'tidbtpu_test_lat_seconds_count{op="scan"} 3' in text

    def test_unknown_label_names_rejected(self):
        reg = Registry()
        c = reg.counter("tidbtpu_test_labeled", "c", labels=("host",))
        with pytest.raises(ValueError, match="unknown label"):
            c.labels(host="h1", port=8080)

    def test_full_precision_exposition(self):
        """Byte-scale counters must not lose low-order increments to %g
        (rate() over scrapes would read zero between 1e5-sized jumps)."""
        reg = Registry()
        c = reg.counter("tidbtpu_test_bytes", "b")
        c.inc(10_737_418_240)  # 10 GiB
        c.inc(65_536)
        assert "tidbtpu_test_bytes 10737483776" in reg.render()

    def test_kind_and_label_conflicts_rejected(self):
        reg = Registry()
        reg.counter("tidbtpu_test_thing", "c")
        with pytest.raises(ValueError):
            reg.gauge("tidbtpu_test_thing", "g")
        with pytest.raises(ValueError):
            reg.counter("tidbtpu_test_thing", "c", labels=("x",))

    def test_registry_rows_contract_unchanged(self):
        """The information_schema METRICS contract: (name, kind, value)
        triplets, histograms exploded into _count/_sum."""
        reg = Registry()
        reg.counter("tidbtpu_test_c", "c").inc(3)
        reg.histogram("tidbtpu_test_h", "h").observe(0.5)
        rows = dict((n, (k, v)) for n, k, v in reg.rows())
        assert rows["tidbtpu_test_c"] == ("counter", 3.0)
        assert rows["tidbtpu_test_h_count"] == ("histogram", 1.0)
        assert rows["tidbtpu_test_h_sum"] == ("histogram", 0.5)


#: one Prometheus text-format sample line
_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s(-?[0-9.e+-]+|NaN)$"
)


class TestHTTPStatus:
    """Side HTTP port: /status /metrics /schema /settings /dcn
    (reference pkg/server/http_status.go)."""

    @pytest.fixture()
    def srv(self):
        import time

        from tidb_tpu.server.http_status import StatusServer
        from tidb_tpu.session.session import Session
        from tidb_tpu.storage import Catalog

        cat = Catalog()
        s = Session(catalog=cat)
        s.execute("create table t (a int primary key, b varchar(8))")
        s.execute("insert into t values (1,'x')")
        srv = StatusServer(cat, port=0, connections=lambda: 7)
        srv.start_background()
        time.sleep(0.1)
        yield srv
        srv.shutdown()

    def _get(self, srv, path):
        return urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=10
        ).read().decode()

    def test_status_reports_live_connections(self, srv):
        body = json.loads(self._get(srv, "/status"))
        assert "tidb-tpu" in body["version"]
        # satellite: no longer hardcoded 0 — wired from the provider
        assert body["connections"] == 7

    def test_metrics_prometheus_text(self, srv):
        body = self._get(srv, "/metrics")
        assert "tidbtpu_" in body and "# TYPE" in body

    def test_metrics_exposition_round_trip(self, srv):
        """Every /metrics line parses as Prometheus text format, every
        histogram's le buckets are cumulative and end at +Inf==count."""
        body = self._get(srv, "/metrics")
        buckets = {}
        counts = {}
        for line in body.strip().splitlines():
            if line.startswith("#"):
                assert re.match(
                    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                    r"(counter|gauge|histogram)$", line
                ), line
                continue
            m = _SAMPLE.match(line)
            assert m, f"unparseable exposition line: {line!r}"
            name, lb, val = m.group(1), m.group(2) or "", m.group(3)
            if name.endswith("_bucket"):
                le = re.search(r'le="([^"]+)"', lb).group(1)
                rest = re.sub(r',?le="[^"]+"', "", lb)
                series = name + ("" if rest == "{}" else rest)
                buckets.setdefault(series, []).append((le, float(val)))
            elif name.endswith("_count"):
                counts[name[: -len("_count")] + lb] = float(val)
        assert buckets, "no histograms exposed"
        for series, bs in buckets.items():
            vals = [v for _le, v in bs]
            assert vals == sorted(vals), f"non-cumulative buckets: {series}"
            les = [le for le, _v in bs]
            assert les[-1] == "+Inf"
            base = series.replace("_bucket", "")
            assert counts.get(base) == vals[-1], series

    def test_schema_endpoints(self, srv):
        assert json.loads(self._get(srv, "/schema"))["test"] == ["t"]
        t = json.loads(self._get(srv, "/schema/test/t"))
        assert t["primary_key"] == ["a"] and t["rows"] == 1

    def test_settings(self, srv):
        assert "tidb_mem_quota_query" in json.loads(self._get(srv, "/settings"))

    def test_dcn_endpoint_unattached(self, srv):
        assert json.loads(self._get(srv, "/dcn")) == {"enabled": False}

    def test_dcn_endpoint_attached(self, srv):
        srv.attach_dcn(lambda: {"enabled": True, "alive": 2})
        body = json.loads(self._get(srv, "/dcn"))
        assert body["enabled"] is True and body["alive"] == 2


class TestSqlDigestInLists:
    """Satellite: IN-lists of literals collapse to one digest element
    (reference digester behavior) so statements_summary does not
    fragment per literal count."""

    def test_in_list_lengths_share_a_digest(self):
        a = sql_digest("select * from t where a in (1, 2, 3)")
        b = sql_digest("select * from t where a in (9)")
        c = sql_digest("select * from t where a in (1,2,3,4,5,6,7,8)")
        assert a == b == c
        assert "( ... )" in a

    def test_string_literals_collapse_too(self):
        a = sql_digest("select 1 from t where s in ('x', 'y')")
        b = sql_digest("select 1 from t where s in ('zzz')")
        assert a == b

    def test_not_in_and_surrounding_structure_kept(self):
        a = sql_digest("select 1 from t where a not in (1, 2) and b = 3")
        assert "not in ( ... )" in a and "b = ?" in a

    def test_subquery_and_mixed_lists_do_not_collapse(self):
        sub = sql_digest("select 1 from t where a in (select a from u)")
        assert "..." not in sub
        mixed = sql_digest("select 1 from t where a in (1, b)")
        assert "..." not in mixed  # non-literal member: structure kept

    def test_summary_rows_do_not_fragment(self, sess):
        sess.execute("create table obs_inl (a bigint)")
        sess.execute("insert into obs_inl values (1),(2),(3)")
        sess.execute("select count(*) from obs_inl where a in (1)")
        sess.execute("select count(*) from obs_inl where a in (1, 2)")
        sess.execute("select count(*) from obs_inl where a in (1, 2, 3)")
        r = sess.must_query(
            "select exec_count from information_schema.statements_summary"
            " where digest_text like '%obs_inl where a in ( ... )'"
        )
        assert len(r.rows) == 1 and r.rows[0][0] >= 3


class TestStreamingHistogram:
    """Satellite: the statements_summary percentile estimator."""

    def test_quantiles_monotone_and_ordered(self):
        from tidb_tpu.utils.metrics import StreamingHistogram

        h = StreamingHistogram("t")
        import random

        rnd = random.Random(7)
        for _ in range(500):
            h.observe(rnd.uniform(0.0005, 1.5))
        qs = [h.quantile(q) for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0)]
        assert qs == sorted(qs)
        assert h.quantile(0.99) >= h.quantile(0.5) > 0

    def test_quantile_brackets_true_value(self):
        from tidb_tpu.utils.metrics import Histogram, StreamingHistogram

        h = StreamingHistogram("t")
        for _ in range(100):
            h.observe(0.01)  # all in the (0.005, 0.02] bucket
        for q in (0.1, 0.5, 0.9):
            assert 0.005 <= h.quantile(q) <= 0.02
        # interpolation is linear in rank within the bucket
        assert h.quantile(0.9) > h.quantile(0.1)
        assert tuple(StreamingHistogram.BUCKETS) == tuple(Histogram.BUCKETS)

    def test_empty_and_overflow(self):
        from tidb_tpu.utils.metrics import StreamingHistogram

        h = StreamingHistogram("t")
        assert h.quantile(0.5) == 0.0
        h.observe(100.0)  # beyond the last bucket edge
        assert h.quantile(0.5) >= StreamingHistogram.BUCKETS[-1]


class TestFlightRecorder:
    """Tentpole: always-on per-query phase timelines (obs/flight.py)."""

    def test_ring_bounds(self):
        from tidb_tpu.obs.flight import FlightRecorder

        f = FlightRecorder(capacity=8)
        for i in range(50):
            f.begin(f"select {i}")
            f.note_phase("parse", 0.001)
            f.finish(0.01)
        rows = f.rows()
        assert len(rows) == 8
        # oldest evicted: the survivors are the last 8
        assert [r["sql"] for r in rows] == [
            f"select {i}" for i in range(42, 50)
        ]

    def test_thread_safety_under_concurrent_sessions(self):
        """Each thread's notes land on ITS flight (thread-local
        current record), and concurrent finishes never corrupt the
        ring."""
        import threading

        from tidb_tpu.obs.flight import FlightRecorder

        f = FlightRecorder(capacity=4096)
        errs = []

        def worker(k):
            try:
                for i in range(50):
                    f.begin(f"w{k}", conn_id=k)
                    f.note_phase("execute", 0.001 * (k + 1))
                    f.note_phase("plan", 0.0001)
                    rec = f.finish(0.01)
                    assert rec is not None and rec.conn_id == k
                    assert rec.phases["execute"][0] == pytest.approx(
                        0.001 * (k + 1)
                    )
            except Exception as e:  # pragma: no cover - failure path
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(k,)) for k in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        rows = f.rows()
        assert len(rows) == 8 * 50
        by_conn = {}
        for r in rows:
            by_conn.setdefault(r["conn_id"], []).append(r)
        assert all(len(v) == 50 for v in by_conn.values())

    def test_session_statement_lands_phases_and_engine_join(self):
        """A real statement's flight carries parse/plan/execute phases
        and the engine-watch join, and statements_summary's joined
        columns (p50<=p99, jit compilations, plan-cache attribution)
        reflect it."""
        from tidb_tpu.utils.metrics import STMT_SUMMARY, sql_digest

        sess = Session(Catalog())
        sess.execute("create table obs_fl (a bigint, b bigint)")
        sess.execute("insert into obs_fl values (1, 2),(3, 4)")
        for _ in range(3):  # identical text: repeats hit the plan cache
            sess.execute("select sum(a * b) from obs_fl where a > 0")
        d = sql_digest("select sum(a * b) from obs_fl where a > 0")
        ent = next(
            e for e in STMT_SUMMARY.rows_full() if e["digest_text"] == d
        )
        assert ent["exec_count"] >= 3
        assert 0 < ent["p50_latency"] <= ent["p95_latency"] <= ent["p99_latency"]
        ph = ent["phases"]
        for phase in ("parse", "plan", "execute"):
            assert ph[phase][0] > 0, phase
        # engine-watch join: the first execution compiled
        assert ent["jit_compilations"] >= 1
        assert ent["plan_cache_hits"] + ent["plan_cache_misses"] >= 3
        assert ent["plan_cache_hits"] >= 1  # repeats reuse the plan
        assert ent["rows_sent"] >= 3
        assert ent["plan_digest"]
        # the same breakdown through the SQL surface
        r = sess.must_query(
            "select p50_latency, p99_latency, avg_execute,"
            " plan_cache_hits, jit_compilations from"
            f" information_schema.statements_summary"
            f" where digest_text = '{d}'"
        )
        p50, p99, avg_exec, hits, jit = r.rows[0]
        assert 0 < p50 <= p99 and avg_exec > 0
        assert hits >= 1 and jit >= 1

    def test_trace_spans_and_flight_phases_agree(self):
        """TRACE rows and the flight recorder's phases come from the
        same FLIGHT.span call: the traced statement's plan / execute
        rows equal its flight's plan / execute phases (no compile ran,
        so execute has nothing taken out), and the rows nest as the
        spans do."""
        from tidb_tpu.obs.flight import FLIGHT

        sess = Session(Catalog())
        sess.execute("create table obs_tr (a bigint)")
        sess.execute("insert into obs_tr values (1),(2)")
        sess.execute("select sum(a) from obs_tr")  # pre-compile
        r = sess.execute("trace select sum(a) from obs_tr")
        flight = FLIGHT.rows()[-1]
        assert flight["sql"].startswith("trace ")
        spans = sess.tracer.totals_by_name()
        ph = flight["phases"]
        assert spans["plan"] == ph["plan"]["seconds"]
        assert spans["execute"] == ph["execute"]["seconds"]
        assert spans["final-merge"] == ph["final-merge"]["seconds"]
        ops = [row[0] for row in r.rows]
        assert ops[:2] == ["plan", "execute"] and ops[-1] == "final-merge"
        assert {"  inputs", "  dispatch", "  device-wait", "  fetch"} <= set(ops)

    def test_error_statement_discards_open_flight(self):
        from tidb_tpu.obs.flight import FLIGHT

        sess = Session(Catalog())
        with pytest.raises(Exception):
            sess.execute("select * from obs_no_such_table_xyz")
        assert FLIGHT.current() is None  # not leaked into the next stmt


class TestSlowQueryCapture:
    """Tentpole surface 2: slow_query grows the phase timeline + plan
    capture, honoring slow_query_log / tidb_slow_log_threshold /
    tidb_record_plan_in_slow_log / tidb_slow_query_file."""

    def test_phase_timeline_and_plan_columns(self, sess):
        sess.execute("create table obs_sq (a bigint)")
        sess.execute("insert into obs_sq values (1),(2)")
        sess.execute("set tidb_slow_log_threshold = 0")
        sess.execute("select count(*) from obs_sq where a > 0")
        r = sess.must_query(
            "select query, phases, plan, conn_id from"
            " information_schema.slow_query"
            " where query like '%obs_sq where a > 0'"
        )
        assert r.rows
        _q, phases, plan, conn_id = r.rows[-1]
        assert "execute=" in phases and "plan=" in phases
        assert "obs_sq" in plan  # captured plan tree scans the table
        assert conn_id == sess.conn_id

    def test_slow_query_log_switch_gates(self, sess):
        sess.execute("create table obs_sq2 (a bigint)")
        sess.execute("insert into obs_sq2 values (1)")
        sess.execute("set tidb_slow_log_threshold = 0")
        sess.execute("set slow_query_log = 0")
        sess.execute("select count(*) from obs_sq2")
        r = sess.must_query(
            "select count(*) from information_schema.slow_query"
            " where query like '%obs_sq2'"
        )
        assert r.rows[0][0] == 0
        sess.execute("set slow_query_log = 1")
        sess.execute("select count(*) from obs_sq2")
        r = sess.must_query(
            "select count(*) from information_schema.slow_query"
            " where query like '%obs_sq2'"
        )
        assert r.rows[0][0] >= 1

    def test_record_plan_switch(self, sess):
        sess.execute("create table obs_sq3 (a bigint)")
        sess.execute("insert into obs_sq3 values (1)")
        sess.execute("set tidb_slow_log_threshold = 0")
        sess.execute("set tidb_record_plan_in_slow_log = 0")
        sess.execute("select count(*) from obs_sq3")
        r = sess.must_query(
            "select plan from information_schema.slow_query"
            " where query like '%obs_sq3'"
        )
        assert r.rows and r.rows[-1][0] == ""
        # the switch gates the EXPLAIN ANALYZE capture path too (the
        # instrumented lines stashed on the flight, not just the
        # rendered plan tree)
        sess.execute("explain analyze select count(*) from obs_sq3")
        r = sess.must_query(
            "select plan from information_schema.slow_query"
            " where query like 'explain analyze%obs_sq3'"
        )
        assert r.rows and r.rows[-1][0] == ""

    def test_dcn_routing_guards_local_only_scans(self):
        """An attached scheduler must never see plans that scan
        coordinator-only state: system schemas and '_'-prefixed
        internal dbs (recursive-CTE scratch) run locally."""
        sess = Session(Catalog())
        sess.execute("create table obs_rt (a bigint)")
        sess.execute("insert into obs_rt values (1),(2)")

        class TripwireSched:
            def _choose_cut(self, plan, digest=None):  # pragma: no cover - tripwire
                raise AssertionError(
                    "local-only statement offered to the fleet"
                )

        sess.attach_dcn_scheduler(TripwireSched())
        try:
            r = sess.execute(
                "select count(*) from information_schema.tables"
            )
            assert r.rows
            r = sess.execute(
                "with recursive nums(n) as (select 1 union all"
                " select n + 1 from nums where n < 3)"
                " select count(*) from nums"
            )
            assert r.rows == [(3,)]
        finally:
            sess.attach_dcn_scheduler(None)

    def test_dcn_routing_falls_back_locally_on_fleet_failure(self):
        """A fleet that cannot serve a routed SELECT (all workers
        lost, a coordinator-only table) must not fail the statement:
        the local engine takes over, counted under the fallback
        metric."""
        from tidb_tpu.utils.metrics import REGISTRY

        sess = Session(Catalog())
        sess.execute("create table obs_fb (a bigint)")
        sess.execute("insert into obs_fb values (1),(2),(3)")

        class DeadFleetSched:
            def _choose_cut(self, plan, digest=None):
                return "frag", object()

            def execute_plan(self, plan, cut_hint=None):
                raise ConnectionError("no alive worker host")

        sess.attach_dcn_scheduler(DeadFleetSched())
        try:
            before = REGISTRY.counter(
                "tidbtpu_session_dcn_route_fallbacks_total"
            ).value
            r = sess.execute("select count(*) from obs_fb")
            assert r.rows == [(3,)]  # served locally
            after = REGISTRY.counter(
                "tidbtpu_session_dcn_route_fallbacks_total"
            ).value
            assert after == before + 1
        finally:
            sess.attach_dcn_scheduler(None)

    def test_slow_query_file_sink(self, sess, tmp_path):
        path = tmp_path / "slow.log"
        sess.execute("create table obs_sq4 (a bigint)")
        sess.execute("insert into obs_sq4 values (1)")
        sess.execute("set tidb_slow_log_threshold = 0")
        sess.execute(f"set tidb_slow_query_file = '{path}'")
        sess.execute("select count(*) from obs_sq4")
        text = path.read_text()
        assert "# Time:" in text and "# Query_time:" in text
        assert "# Phases:" in text and "# Plan:" in text
        assert "select count(*) from obs_sq4;" in text

    def test_explain_analyze_text_captured(self, sess):
        """An over-threshold EXPLAIN ANALYZE's slow-log entry carries
        the instrumented plan lines themselves."""
        sess.execute("create table obs_sq5 (a bigint)")
        sess.execute("insert into obs_sq5 values (1),(2),(3)")
        sess.execute("set tidb_slow_log_threshold = 0")
        sess.execute("explain analyze select count(*) from obs_sq5")
        r = sess.must_query(
            "select plan from information_schema.slow_query"
            " where query like 'explain analyze%obs_sq5'"
        )
        assert r.rows
        plan = r.rows[-1][0]
        # run_analyze lines carry runtime stats, not just the tree
        assert "Aggregate" in plan and "time=" in plan


def test_links_endpoint_and_cluster_links_table():
    """Tentpole surface 3: /links + information_schema.cluster_links
    read the link registry (control-link health populated here via the
    registry API; the multihost dryrun exercises the real handshake
    and tunnel merges)."""
    import time as _time

    from tidb_tpu.obs.flight import LINKS
    from tidb_tpu.server.http_status import StatusServer

    LINKS.note_handshake("127.0.0.1:9999", rtt_s=0.002, offset_s=0.0001)
    LINKS.note_tunnel(
        "127.0.0.1:9999", "127.0.0.1:9998",
        {"bytes": 1024, "frames": 3, "rows": 10, "stalls": 1,
         "stall_s": 0.5, "retransmits": 2, "codec": "binary"},
    )
    cat = Catalog()
    sess = Session(cat)
    r = sess.must_query(
        "select src, dst, kind, rtt_ms, stall_seconds, retransmits,"
        " codec from information_schema.cluster_links"
        " where dst like '127.0.0.1:999%'"
    )
    by_kind = {row[2]: row for row in r.rows}
    assert by_kind["control"][1] == "127.0.0.1:9999"
    assert by_kind["control"][3] == pytest.approx(2.0)  # rtt ms
    assert by_kind["tunnel"][4] == pytest.approx(0.5)   # stall seconds
    assert by_kind["tunnel"][5] == 2 and by_kind["tunnel"][6] == "binary"

    srv = StatusServer(cat, port=0)
    srv.start_background()
    try:
        _time.sleep(0.1)
        body = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/links", timeout=10
            ).read().decode()
        )
        links = body["links"]
        assert any(
            l["kind"] == "tunnel" and l["stall_seconds"] > 0
            for l in links
        )
        assert any(
            l["kind"] == "control" and l["rtt_ms"] > 0 for l in links
        )
    finally:
        srv.shutdown()


def test_mysql_server_connection_count():
    """The MySQL-protocol server counts live connections and the status
    port reports them (satellite: /status hardcoded 0)."""
    import socket
    import time

    from tidb_tpu.server.server import Server

    srv = Server(port=0, status_port=0)
    srv.start_background()
    try:
        time.sleep(0.2)
        assert srv.connections == 0
        conns = [
            socket.create_connection(("127.0.0.1", srv.port), timeout=5)
            for _ in range(3)
        ]
        try:
            for c in conns:
                c.recv(4096)  # handshake arrived: the server counted us
            deadline = time.time() + 5
            while srv.connections != 3 and time.time() < deadline:
                time.sleep(0.05)
            assert srv.connections == 3
            body = json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.status_server.port}/status",
                    timeout=10,
                ).read().decode()
            )
            assert body["connections"] == 3
        finally:
            for c in conns:
                c.close()
        deadline = time.time() + 5
        while srv.connections != 0 and time.time() < deadline:
            time.sleep(0.05)
        assert srv.connections == 0
    finally:
        srv.shutdown()
