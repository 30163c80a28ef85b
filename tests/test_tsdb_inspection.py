"""PR 12: metric time-series store (obs/tsdb.py), metrics_schema
virtual tables with predicate pushdown, statements_summary_history,
and the inspection engine (obs/inspection.py).

Reference: pkg/infoschema/metrics_schema.go (Prometheus history as SQL)
and pkg/executor/inspection_result.go (rules reading it back). The
chaos-driven acceptance tier (fault class -> finding) also lives here
over the in-process fleet; the 2-process dryrun is in
test_multihost.py.
"""

import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from tidb_tpu.obs.tsdb import (
    SAMPLER,
    TSDB,
    TimeSeriesStore,
    TsdbSampler,
    clear_scan_hint,
    scan_hint_for,
    set_scan_hint,
)
from tidb_tpu.utils import racecheck
from tidb_tpu.utils.metrics import (
    REGISTRY,
    Registry,
    StmtHistory,
    StmtSummary,
    sample_rows,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def sess():
    from tidb_tpu.session import Session

    s = Session()
    s.execute("create table t (a int, b varchar(8))")
    s.execute("insert into t values (1,'x'),(2,'y'),(3,'x')")
    return s


# ---------------------------------------------------------------------------
# store unit tier
# ---------------------------------------------------------------------------


class TestTimeSeriesStore:
    def test_sample_rows_covers_all_kinds(self):
        reg = Registry()
        reg.counter("tidbtpu_session_statements_total").inc(3)
        reg.gauge("tidbtpu_dcn_hosts_alive").set(2)
        reg.histogram("tidbtpu_flight_query_seconds").observe(0.5)
        reg.counter(
            "tidbtpu_dcn_dispatches", labels=("host",)
        ).labels(host="w1").inc()
        rows = {(r[0], r[2]): (r[3], r[4]) for r in sample_rows(reg)}
        assert rows[("tidbtpu_session_statements_total", ())] == (
            3.0, "counter"
        )
        assert rows[("tidbtpu_dcn_hosts_alive", ())] == (2.0, "gauge")
        # histograms decompose into count/sum stat series
        assert rows[
            ("tidbtpu_flight_query_seconds", ("count",))
        ] == (1.0, "histogram")
        assert rows[
            ("tidbtpu_flight_query_seconds", ("sum",))
        ] == (0.5, "histogram")
        assert rows[("tidbtpu_dcn_dispatches", ("w1",))][0] == 1.0

    def test_retention_ring_and_downsample_bounds(self):
        store = TimeSeriesStore(
            retention_points=8, downsample_every=4
        )
        reg = Registry()
        c = reg.counter("tidbtpu_session_statements_total")
        for i in range(64):
            c.inc()
            store.sample_registry(registry=reg, now=1000.0 + i)
        key = (
            "tidbtpu_session_statements_total", "coordinator", (), (),
        )
        s = store._series[key]
        assert len(s.raw) == 8          # raw ring bounded
        assert len(s.coarse) <= 8       # coarse ring bounded
        # counters downsample to the LAST cumulative value of the fold
        pts = store.query("tidbtpu_session_statements_total")
        raw = [p for p in pts if p[4] == "raw"]
        ds = [p for p in pts if p[4] == "ds"]
        assert len(raw) == 8 and ds
        assert raw[-1][3] == 64.0
        # downsampled values are cumulative (monotone) too
        assert [p[3] for p in ds] == sorted(p[3] for p in ds)
        # total memory stays bounded no matter how many samples landed
        assert store.point_count() <= 16

    def test_gauge_downsample_keeps_mean(self):
        store = TimeSeriesStore(retention_points=4, downsample_every=4)
        reg = Registry()
        g = reg.gauge("tidbtpu_dcn_hosts_alive")
        vals = [0.0, 4.0, 0.0, 4.0, 1.0, 1.0, 1.0, 1.0]
        for i, v in enumerate(vals):
            g.set(v)
            store.sample_registry(registry=reg, now=2000.0 + i)
        ds = [
            p for p in store.query("tidbtpu_dcn_hosts_alive")
            if p[4] == "ds"
        ]
        assert ds and ds[0][3] == pytest.approx(2.0)  # mean of 0,4,0,4

    def test_eviction_counter_moves_on_coarse_overflow(self):
        from tidb_tpu.obs.tsdb import _c_evicted

        store = TimeSeriesStore(retention_points=4, downsample_every=1)
        reg = Registry()
        g = reg.gauge("tidbtpu_dcn_hosts_alive")
        before = _c_evicted().value
        for i in range(32):
            g.set(i)
            store.sample_registry(registry=reg, now=3000.0 + i)
        # downsample_every=1: every raw eviction becomes a coarse
        # point; coarse cap 4 -> overflow beyond 8 retained points
        assert _c_evicted().value > before
        assert store.point_count() <= 8

    def test_series_cap_bounds_label_blowup(self):
        store = TimeSeriesStore(retention_points=8, max_series=16)
        reg = Registry()
        fam = reg.counter(
            "tidbtpu_dcn_dispatches", labels=("host",)
        )
        for i in range(64):
            fam.labels(host=f"w{i}").inc()
        store.sample_registry(registry=reg, now=4000.0)
        assert store.series_count() <= 16
        assert store.series_cap_drops > 0

    def test_query_time_and_label_pushdown(self):
        store = TimeSeriesStore(retention_points=32)
        reg = Registry()
        fam = reg.counter(
            "tidbtpu_dcn_dispatches", labels=("host",)
        )
        fam.labels(host="w1").inc()
        fam.labels(host="w2").inc()
        for i in range(10):
            store.sample_registry(registry=reg, now=5000.0 + i)
        allpts = store.query("tidbtpu_dcn_dispatches")
        assert len(allpts) == 20
        bounded = store.query(
            "tidbtpu_dcn_dispatches", t_lo=5007.0, t_hi=5008.5
        )
        assert len(bounded) == 4  # 2 hosts x samples 5007, 5008
        w1 = store.query(
            "tidbtpu_dcn_dispatches", labels={"host": "w1"}
        )
        assert len(w1) == 10
        assert all(lv == ("w1",) for _t, _h, lv, _v, _r in w1)

    def test_merge_remote_rebases_filters_and_survives_garbage(self):
        store = TimeSeriesStore()
        rows = [
            ["tidbtpu_shuffle_bytes_total", [], [], 1000.0, 5.0,
             "counter"],
            ["not_ours_metric", [], [], 1000.0, 1.0, "counter"],
            ["tidbtpu_shuffle_bytes_total", "garbage"],  # malformed
        ]
        n = store.merge_remote(rows, host="w1:1", offset_s=2.0)
        assert n == 1
        pts = store.query("tidbtpu_shuffle_bytes_total")
        assert pts == [(998.0, "w1:1", (), 5.0, "raw")]

    def test_retune_retention_shrinks_live_series(self):
        store = TimeSeriesStore(retention_points=32)
        reg = Registry()
        g = reg.gauge("tidbtpu_dcn_hosts_alive")
        for i in range(32):
            g.set(i)
            store.sample_registry(registry=reg, now=6000.0 + i)
        store.retune_retention(retention_points=8)
        key = ("tidbtpu_dcn_hosts_alive", "coordinator", (), ())
        assert len(store._series[key].raw) == 8
        # the shrink folded the overflow through downsampling
        assert any(
            p[4] == "ds"
            for p in store.query("tidbtpu_dcn_hosts_alive")
        )


class TestSampler:
    def test_passive_tick_spacing_and_background_retune(self):
        store = TimeSeriesStore()
        sampler = TsdbSampler(store, passive_interval_s=3600.0)
        assert sampler.maybe_sample(now=10.0) is True
        assert sampler.maybe_sample(now=11.0) is False  # too soon
        # background thread: starts, samples, stops on retune(0)
        sampler.retune(0.01)
        try:
            assert sampler.interval_s() == 0.01
            # the thread owns the cadence: passive ticks are no-ops
            assert sampler.maybe_sample(now=1e12) is False
            deadline = time.monotonic() + 10
            base = store.point_count()
            while store.point_count() <= base:
                assert time.monotonic() < deadline, "sampler idle"
                time.sleep(0.02)
        finally:
            sampler.stop()
        assert sampler.interval_s() == 0.0
        assert not [
            t for t in threading.enumerate()
            if t.name == "obs-tsdb-sampler" and t.is_alive()
        ]

    def test_tick_feeds_timeline_counter_tracks(self):
        """ISSUE 12 satellite: while a capture is live, the tsdb
        cadence samples the 'C' counter tracks — gauge movement
        BETWEEN statements lands in the trace instead of flatlining
        until the next statement close."""
        from tidb_tpu.obs.timeline import TIMELINE

        REGISTRY.gauge(
            "tidbtpu_admission_queue_depth",
            "queries waiting for admission",
        ).set(7)
        sampler = TsdbSampler(TimeSeriesStore())
        TIMELINE.start()
        try:
            sampler.sample_once()
            counters = [
                e for e in TIMELINE.events()
                if e[0] == "C"
                and e[2] == "tidbtpu_admission_queue_depth"
            ]
            assert counters and counters[-1][4] == 7.0
        finally:
            TIMELINE.stop()
            TIMELINE.clear()


# ---------------------------------------------------------------------------
# SQL surface: metrics_schema + pushdown + statements_summary_history
# ---------------------------------------------------------------------------


class TestMetricsSchemaSQL:
    def test_select_with_time_pushdown(self, sess):
        sess.execute("select count(*) from t")
        t_mid = time.time()
        SAMPLER.sample_once(now=t_mid - 30.0)
        SAMPLER.sample_once(now=t_mid)
        # `last_scan_points` is the store's LAST scan, whoever made it: a
        # loop of a server another test of this process left up can scan
        # between the statement and the read (seen in the driver's run of
        # PR 29 and in PR 33's). Three tries; one clean one is the proof.
        for _attempt in range(3):
            r = sess.must_query(
                "select time, instance, value from "
                "metrics_schema.tidbtpu_session_statements_total "
                f"where time >= {t_mid - 1.0}"
            )
            # the pushdown reached the store: only the bounded slice was
            # materialized, not the whole ring (read the scan gauge BEFORE
            # the unbounded count query overwrites it)
            bounded = TSDB.last_scan_points
            total = len(TSDB.query("tidbtpu_session_statements_total"))
            if bounded < total:
                break
        assert r.rows and all(row[0] >= t_mid - 1.0 for row in r.rows)
        assert all(row[1] == "coordinator" for row in r.rows)
        assert bounded < total

    def test_label_columns_and_label_pushdown(self, sess):
        REGISTRY.counter(
            "tidbtpu_dcn_dispatches", "fragment dispatches",
            labels=("host",),
        ).labels(host="w1:9").inc()
        SAMPLER.sample_once()
        r = sess.must_query(
            "select host, value from "
            "metrics_schema.tidbtpu_dcn_dispatches "
            "where host = 'w1:9'"
        )
        assert r.rows and all(row[0] == "w1:9" for row in r.rows)

    def test_histogram_family_has_stat_column(self, sess):
        sess.execute("select count(*) from t")
        SAMPLER.sample_once()
        r = sess.must_query(
            "select stat, value from "
            "metrics_schema.tidbtpu_session_query_duration_seconds "
            "where stat = 'count'"
        )
        assert r.rows and all(row[0] == "count" for row in r.rows)

    def test_unknown_family_and_show_tables(self, sess):
        with pytest.raises(ValueError, match="metrics_schema"):
            sess.execute(
                "select * from metrics_schema.tidbtpu_nope_nothing"
            )
        SAMPLER.sample_once()
        sess.execute("use metrics_schema")
        rows = {r[0] for r in sess.execute("show tables").rows}
        assert "tidbtpu_session_statements_total" in rows

    def test_scan_hint_is_thread_local_and_metric_scoped(self):
        set_scan_hint("tidbtpu_x_y", t_lo=1.0)
        try:
            assert scan_hint_for("tidbtpu_x_y") == (1.0, None, {})
            assert scan_hint_for("tidbtpu_other_z") is None
            seen = []
            t = threading.Thread(
                target=lambda: seen.append(
                    scan_hint_for("tidbtpu_x_y")
                ),
                daemon=True, name="obs-hint-probe",
            )
            t.start()
            t.join()
            assert seen == [None]
        finally:
            clear_scan_hint()

    def test_no_hint_bleed_into_same_family_subquery(self, sess):
        """A statement referencing the family TWICE (scalar subquery)
        must not push the outer bounds down — the inner unbounded
        aggregate would silently inherit them and compute over the
        sliced history."""
        t0 = time.time()
        sess.execute("select count(*) from t")
        SAMPLER.sample_once(now=t0 - 50.0)
        sess.execute("select count(*) from t")
        sess.execute("select count(*) from t")
        SAMPLER.sample_once(now=t0)
        r = sess.must_query(
            "select value from "
            "metrics_schema.tidbtpu_session_statements_total "
            f"where time >= {t0 - 1.0} and value > ("
            "select min(value) from "
            "metrics_schema.tidbtpu_session_statements_total)"
        )
        # the inner min spans the FULL history (smaller than any
        # in-window value), so the bounded outer rows all qualify; a
        # hint bleed would bound the inner min to the newest sample
        # and return nothing
        assert r.rows

    def test_downsampled_histogram_stats_stay_cumulative(self):
        store = TimeSeriesStore(retention_points=4, downsample_every=4)
        reg = Registry()
        h = reg.histogram("tidbtpu_flight_query_seconds")
        for i in range(8):
            h.observe(1.0)
            store.sample_registry(registry=reg, now=7000.0 + i)
        ds = [
            p for p in store.query(
                "tidbtpu_flight_query_seconds",
                labels={"stat": "count"},
            )
            if p[4] == "ds"
        ]
        # cumulative count at the fold boundary, NOT the fold mean
        # (the mean would under-read and inflate window deltas that
        # straddle the coarse->raw boundary)
        assert ds and ds[0][3] == 4.0

    def test_predicates_stay_exact_beyond_the_hint(self, sess):
        """The hint is a superset scan, never the filter: a predicate
        the store cannot push (value comparison) still filters."""
        SAMPLER.sample_once()
        r = sess.must_query(
            "select value from "
            "metrics_schema.tidbtpu_session_statements_total "
            "where value < -1"
        )
        assert r.rows == []


class TestStatementsSummaryHistory:
    def test_windows_survive_eviction_boundary(self):
        """ISSUE 12 acceptance: >= 2 windows per digest across an
        eviction boundary — the AQE trajectory must not vanish when
        the live summary churns."""
        summ = StmtSummary(capacity=2)
        hist = StmtHistory(max_windows=8, refresh_interval_s=3600.0)
        summ.history = hist
        summ.record("select a from q1", 0.1)
        summ.record("select a from q2", 0.1)
        summ.record("select a from q2", 0.2)
        hist.rotate(summ, now=100.0)          # window 1: q1 live
        # a new digest evicts q1 (least-executed) from the live map
        summ.record("select a from q3", 0.1)
        digests = {d for d, *_ in summ.rows()}
        assert not any("q1" in d for d in digests)  # evicted
        hist.rotate(summ, now=200.0)          # window 2: q1 via evict
        q1 = [
            (b, e, r) for b, e, r in hist.rows() if "q1" in
            r["digest_text"]
        ]
        assert len(q1) >= 2
        # the eviction snapshot kept the aggregates
        assert all(r["exec_count"] == 1 for _b, _e, r in q1)

    def test_window_capacity_and_maybe_rotate(self):
        summ = StmtSummary(capacity=8)
        hist = StmtHistory(max_windows=2, refresh_interval_s=50.0)
        summ.record("select 1 from w", 0.1)
        assert hist.maybe_rotate(summ, now=hist._open_t0 + 1) is False
        assert hist.maybe_rotate(summ, now=hist._open_t0 + 60) is True
        for i in range(4):
            hist.rotate(summ, now=1000.0 + i)
        assert len(hist._windows) == 2  # bounded

    def test_infoschema_table_serves_history(self, sess):
        from tidb_tpu.utils.metrics import STMT_HISTORY, STMT_SUMMARY

        sess.execute("select a, b from t where a = 1")
        STMT_HISTORY.rotate(STMT_SUMMARY)
        r = sess.must_query(
            "select digest_text, exec_count from "
            "information_schema.statements_summary_history "
            "where digest_text like '%from t where%'"
        )
        assert r.rows and all(row[1] >= 1 for row in r.rows)


# ---------------------------------------------------------------------------
# inspection engine
# ---------------------------------------------------------------------------


def _feed(store, name, lnames, lvalues, series, kind="counter",
          host="coordinator"):
    """Feed (ts, value) points for one series through the public
    merge path."""
    store.merge_remote(
        [[name, list(lnames), list(lvalues), t, v, kind]
         for t, v in series],
        host=host,
    )


class TestInspectionRules:
    def _engine(self):
        from tidb_tpu.obs.inspection import InspectionEngine

        store = TimeSeriesStore()
        return store, InspectionEngine(store)

    def test_healthy_history_yields_no_findings(self):
        store, eng = self._engine()
        _feed(store, "tidbtpu_dcn_retries", (), (),
              [(100.0, 5.0), (200.0, 5.0)])
        _feed(store, "tidbtpu_link_heartbeat_age_seconds", ("host",),
              ("w1",), [(100.0, 0.0), (200.0, 0.01)], kind="gauge")
        assert eng.run(t_lo=50.0, t_hi=250.0) == []

    def test_heartbeat_gap_and_miss_escalation(self):
        store, eng = self._engine()
        _feed(store, "tidbtpu_link_heartbeat_age_seconds", ("host",),
              ("w1",), [(100.0, 0.0), (150.0, 4.0)], kind="gauge")
        fs = eng.run(t_lo=50.0, t_hi=200.0)
        gap = [f for f in fs if f.rule == "heartbeat-gap"]
        assert gap and gap[0].item == "w1"
        assert gap[0].severity == "warning"
        assert 100.0 <= gap[0].t0 <= gap[0].t1 <= 150.0
        # repeated misses on THE SAME host escalate it; another
        # host's misses must not (severity is per-host evidence)
        _feed(store, "tidbtpu_dcn_heartbeat_misses", ("host",),
              ("w2",), [(100.0, 0.0), (150.0, 5.0)])
        fs = eng.run(t_lo=50.0, t_hi=200.0)
        w1 = [f for f in fs if f.rule == "heartbeat-gap"
              and f.item == "w1" and "age" in f.detail]
        assert w1 and w1[0].severity == "warning"
        _feed(store, "tidbtpu_dcn_heartbeat_misses", ("host",),
              ("w1",), [(100.0, 0.0), (150.0, 2.0)])
        fs = eng.run(t_lo=50.0, t_hi=200.0)
        w1 = [f for f in fs if f.rule == "heartbeat-gap"
              and f.item == "w1" and "age" in f.detail]
        assert w1 and w1[0].severity == "critical"

    def test_retry_storm_thresholds_and_evidence_window(self):
        store, eng = self._engine()
        _feed(store, "tidbtpu_dcn_retries", (), (),
              [(100.0, 0.0), (150.0, 2.0), (200.0, 2.0)])
        fs = eng.run(t_lo=50.0, t_hi=250.0)
        storm = [f for f in fs if f.rule == "retry-storm"]
        assert storm and storm[0].severity == "warning"
        # evidence brackets the movement, not the whole window
        assert storm[0].t0 == 100.0 and storm[0].t1 == 200.0
        _feed(store, "tidbtpu_shuffle_stage_retries", (), (),
              [(100.0, 0.0), (180.0, 10.0)])
        fs = eng.run(t_lo=50.0, t_hi=250.0)
        storm = [f for f in fs if f.rule == "retry-storm"]
        assert storm[0].severity == "critical"

    def test_counter_born_inside_window_counts_from_zero(self):
        store, eng = self._engine()
        _feed(store, "tidbtpu_shuffle_retransmits", (), (),
              [(150.0, 3.0)])
        fs = eng.run(t_lo=100.0, t_hi=200.0)
        assert any(
            f.rule == "shuffle-retransmit-storm" for f in fs
        )

    def test_preexisting_counter_standing_value_is_not_an_increase(
        self
    ):
        store, eng = self._engine()
        _feed(store, "tidbtpu_shuffle_retransmits", (), (),
              [(50.0, 100.0), (150.0, 100.0)])
        fs = eng.run(t_lo=100.0, t_hi=200.0)
        assert not any(
            f.rule == "shuffle-retransmit-storm" for f in fs
        )

    def test_clock_skew_and_tunnel_backpressure(self):
        store, eng = self._engine()
        _feed(store, "tidbtpu_link_clock_offset_seconds", ("host",),
              ("w2",), [(100.0, -3.0)], kind="gauge")
        _feed(store, "tidbtpu_link_stall_seconds", ("src", "dst"),
              ("a:1", "b:2"), [(100.0, 0.0), (150.0, 0.8)])
        fs = eng.run(t_lo=50.0, t_hi=200.0)
        rules = {f.rule: f for f in fs}
        assert rules["clock-skew"].severity == "critical"
        assert rules["clock-skew"].item == "w2"
        assert rules["tunnel-backpressure"].item == "a:1->b:2"

    def test_admission_starvation_and_plan_cache_thrash(self):
        store, eng = self._engine()
        # histogram stat series: 4 waits totalling 8s -> mean 2s
        _feed(store, "tidbtpu_admission_queue_wait_seconds",
              ("stat",), ("sum",), [(100.0, 0.0), (150.0, 8.0)],
              kind="histogram")
        _feed(store, "tidbtpu_admission_queue_wait_seconds",
              ("stat",), ("count",), [(100.0, 0.0), (150.0, 4.0)],
              kind="histogram")
        _feed(store, "tidbtpu_admission_outcomes_total",
              ("outcome",), ("reject",), [(100.0, 0.0), (150.0, 2.0)])
        _feed(store, "tidbtpu_executor_plan_cache_misses_total", (),
              (), [(100.0, 0.0), (150.0, 20.0)])
        _feed(store, "tidbtpu_executor_plan_cache_hits_total", (),
              (), [(100.0, 0.0), (150.0, 2.0)])
        fs = eng.run(t_lo=50.0, t_hi=200.0)
        rules = {f.rule for f in fs}
        assert "admission-starvation" in rules
        assert "plan-cache-thrash" in rules
        rejects = [
            f for f in fs if f.rule == "admission-starvation"
            and f.item == "reject"
        ]
        assert rejects and rejects[0].severity == "critical"

    def test_quarantine_flap(self):
        store, eng = self._engine()
        _feed(store, "tidbtpu_dcn_quarantines", ("host",), ("w1",),
              [(100.0, 0.0), (150.0, 2.0)])
        _feed(store, "tidbtpu_dcn_readmissions_total", ("host",),
              ("w1",), [(100.0, 0.0), (160.0, 2.0)])
        fs = eng.run(t_lo=50.0, t_hi=200.0)
        flap = [f for f in fs if f.rule == "quarantine-flap"]
        assert flap and flap[0].item == "w1"
        assert flap[0].severity == "critical"

    def test_undeclared_metric_read_raises_and_is_reported(self):
        from tidb_tpu.obs import inspection as insp

        store, eng = self._engine()

        @insp.rule("x-test-rogue", metrics=("tidbtpu_dcn_retries",))
        def _rogue(ctx):
            return ctx.series("tidbtpu_shuffle_retransmits")

        try:
            fs = eng.run(rules=["x-test-rogue"])
            assert fs and fs[0].severity == "critical"
            assert "undeclared metric" in fs[0].detail
        finally:
            del insp.RULES["x-test-rogue"]

    def test_rule_registry_rejects_duplicates_and_empty_metrics(self):
        from tidb_tpu.obs import inspection as insp

        with pytest.raises(ValueError, match="duplicate"):
            insp.rule("retry-storm", metrics=("tidbtpu_dcn_retries",))(
                lambda ctx: []
            )
        with pytest.raises(ValueError, match="no metrics"):
            insp.rule("x-test-empty", metrics=())(lambda ctx: [])

    def test_match_chaos_findings_window_overlap(self):
        from tidb_tpu.obs.inspection import (
            Finding,
            match_chaos_findings,
        )

        f = Finding("clock-skew", "w1", "critical", 3.0, "", "",
                    100.0, 110.0)
        assert match_chaos_findings(
            ["clock-skew"], [f], window=(105.0, 120.0)
        ) == {"clock-skew": True}
        assert match_chaos_findings(
            ["clock-skew"], [f], window=(200.0, 210.0)
        ) == {"clock-skew": False}
        # classes with no declared signature assert nothing
        assert match_chaos_findings(
            ["frame-delay"], [], window=(0.0, 1.0)
        ) == {"frame-delay": True}


# ---------------------------------------------------------------------------
# worker sample shipping (in-process half; the 2-process dryrun is in
# test_multihost.py)
# ---------------------------------------------------------------------------


class TestWorkerSampleShipping:
    def test_tsdb_ship_drains_exactly_once(self, sess):
        from tidb_tpu.server.engine_rpc import EngineServer

        srv = EngineServer(sess.catalog, port=0, ship_registry=True)
        srv.start_background()
        try:
            srv.tsdb_min_interval_s = 0.0
            first = srv._tsdb_ship()
            assert first
            srv.tsdb_min_interval_s = 3600.0
            # nothing new sampled and the buffer was drained: the same
            # batch can never ride two replies
            assert srv._tsdb_ship() is None
        finally:
            srv.shutdown()

    def test_ping_idle_flush_merges_host_history(self, sess):
        """The heartbeat idle-flush: an idle worker's samples reach
        the coordinator store via ping_endpoint, labeled by the
        worker's address, without any dispatch in flight."""
        from tidb_tpu.server.engine_pool import (
            EngineEndpoint,
            ping_endpoint,
        )
        from tidb_tpu.server.engine_rpc import EngineServer

        srv = EngineServer(sess.catalog, port=0, ship_registry=True)
        srv.start_background()
        srv.tsdb_min_interval_s = 0.0
        ep = EngineEndpoint("127.0.0.1", srv.port)
        try:
            before = {
                k for k in TSDB._series if k[1] == ep.address
            }
            assert ping_endpoint(ep) is True
            after = {k for k in TSDB._series if k[1] == ep.address}
            assert after - before  # worker-host series landed
        finally:
            srv.shutdown()

    def test_fenced_merge_never_duplicates_a_sample_batch(self, sess):
        """dcn/duplicate-redelivery: every completion is immediately
        redelivered; the ledger fences the second landing, so a
        reply's sample batch lands AT MOST ONCE — no exact-duplicate
        (metric, ts, labels, value) points for the worker host."""
        from tidb_tpu.parallel.dcn import DCNFragmentScheduler
        from tidb_tpu.parser.sqlparse import parse
        from tidb_tpu.planner.logical import build_query
        from tidb_tpu.server.engine_rpc import EngineServer
        from tidb_tpu.utils import failpoint

        srv = EngineServer(sess.catalog, port=0, ship_registry=True)
        srv.tsdb_min_interval_s = 0.0
        srv.start_background()
        failpoint.enable("dcn/duplicate-redelivery", True)
        sched = DCNFragmentScheduler(
            [("127.0.0.1", srv.port)], catalog=sess.catalog
        )
        try:
            plan = build_query(
                parse("select b, count(*) from t group by b order by b")[0],
                sess.catalog, "test", sess._scalar_subquery,
            )
            _cols, rows = sched.execute_plan(plan)
            assert rows  # parity is covered elsewhere; landing matters
            host = f"127.0.0.1:{srv.port}"
            pts = []
            for key, s in TSDB._series.items():
                if key[1] != host:
                    continue
                pts.extend(
                    (key[0], key[3], t, v) for t, v in s.raw
                )
            assert pts, "worker samples should have merged"
            assert len(pts) == len(set(pts)), (
                "duplicate-redelivered reply's sample batch merged "
                "twice"
            )
        finally:
            failpoint.disable("dcn/duplicate-redelivery")
            sched.close()
            srv.shutdown()


# ---------------------------------------------------------------------------
# racecheck stress (ISSUE 12 satellite)
# ---------------------------------------------------------------------------


@pytest.fixture()
def racecheck_on():
    racecheck.enable()
    racecheck.reset()
    try:
        yield
    finally:
        racecheck.disable()
        racecheck.reset()


class TestRacecheckStress:
    def test_metric_hammer_concurrent_with_sampling_and_eviction(
        self, racecheck_on
    ):
        """8 threads hammer labeled metrics while a sampler thread
        samples + evicts under order-tracked locks; retention bounds
        hold throughout and no lock-order inversion raises."""
        reg = Registry()
        store = TimeSeriesStore(
            retention_points=8, downsample_every=2, max_series=256
        )
        stop = threading.Event()
        errors = []

        def hammer(idx):
            fam = reg.counter(
                "tidbtpu_dcn_dispatches", labels=("host",)
            )
            h = reg.histogram("tidbtpu_flight_query_seconds")
            g = reg.gauge("tidbtpu_dcn_hosts_alive")
            i = 0
            try:
                while not stop.is_set():
                    fam.labels(host=f"w{idx}").inc()
                    h.observe(0.001 * i)
                    g.set(i % 5)
                    i += 1
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def sample_loop():
            now = 1000.0
            try:
                while not stop.is_set():
                    store.sample_registry(registry=reg, now=now)
                    now += 1.0
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [
            threading.Thread(
                target=hammer, args=(i,), daemon=True,
                name=f"obs-hammer-{i}",
            )
            for i in range(8)
        ] + [
            threading.Thread(
                target=sample_loop, daemon=True, name="obs-sampler",
            )
        ]
        for t in threads:
            t.start()
        time.sleep(0.4)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors
        assert not [t for t in threads if t.is_alive()]
        # retention bounds held under the hammer: <= 2 rings per series
        assert store.point_count() <= store.series_count() * 16
        # the tsdb lock class participated in the tracked run
        assert "obs.tsdb" in racecheck.seen_classes()

    def test_query_concurrent_with_retune(self, racecheck_on):
        store = TimeSeriesStore(retention_points=64)
        reg = Registry()
        g = reg.gauge("tidbtpu_dcn_hosts_alive")
        stop = threading.Event()
        errors = []

        def writer():
            now = 0.0
            try:
                while not stop.is_set():
                    g.set(now)
                    store.sample_registry(registry=reg, now=now)
                    now += 1.0
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def retuner():
            try:
                while not stop.is_set():
                    store.retune_retention(retention_points=8)
                    store.retune_retention(retention_points=64)
                    store.query("tidbtpu_dcn_hosts_alive", t_lo=5.0)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        ts = [
            threading.Thread(
                target=writer, daemon=True, name="obs-writer"
            ),
            threading.Thread(
                target=retuner, daemon=True, name="obs-retuner"
            ),
        ]
        for t in ts:
            t.start()
        time.sleep(0.25)
        stop.set()
        for t in ts:
            t.join(timeout=10)
        assert not errors, errors


# ---------------------------------------------------------------------------
# chaos -> inspection acceptance (in-process fleet)
# ---------------------------------------------------------------------------


def test_chaos_fault_classes_surface_as_findings():
    """ISSUE 12 acceptance: a seeded chaos run with worker-crash +
    frame-drop + clock-skew episodes yields an inspection finding per
    fault class whose evidence window overlaps the episode —
    deterministic under schedule replay (re-running an episode's
    schedule reproduces its match verdict; schedule generation itself
    is seed-pure, tests/test_chaos.py)."""
    from tidb_tpu.chaos import ChaosHarness
    from tidb_tpu.chaos.schedule import Episode, Fault
    from tidb_tpu.obs.inspection import (
        match_chaos_findings,
        run_inspection,
    )

    episodes = [
        Episode(0, 0, (Fault("worker-crash", "shuffle/recv", "drop",
                             n=2),)),
        Episode(1, 2, (Fault("frame-drop", "shuffle/push-lost",
                             "window-error", n=3),)),
        Episode(2, 1, (Fault("clock-skew", "engine/clock-skew",
                             "value", param=3.0),)),
        # replay of the clock-skew episode: the same schedule must
        # reproduce the same verdict
        Episode(3, 1, (Fault("clock-skew", "engine/clock-skew",
                             "value", param=3.0),)),
    ]
    verdicts = []
    with ChaosHarness(seed=12, wait_timeout_s=2.0) as h:
        for ep in episodes:
            violations, _wall = h.run_episode(ep)
            assert violations == [], violations
            t0, t1 = h.last_window
            findings = run_inspection(t_lo=t0 - 0.01, t_hi=t1 + 0.01)
            classes = tuple(f.cls for f in ep.faults)
            m = match_chaos_findings(classes, findings, window=(t0, t1))
            assert all(m.values()), (classes, m, [
                (f.rule, f.t0, f.t1) for f in findings
            ])
            verdicts.append(m)
    assert verdicts[2] == verdicts[3]  # replay determinism


# ---------------------------------------------------------------------------
# check_inspection_rules lint: seeded violations
# ---------------------------------------------------------------------------


LINT = os.path.join(REPO, "scripts", "check_inspection_rules.py")

_FLIGHT_STUB = 'PHASES = (\n    "parse",\n    "compile",\n)\n'

_METRICS_STUB = textwrap.dedent(
    '''
    from x import REGISTRY

    REGISTRY.counter("tidbtpu_dcn_retries", "r")
    REGISTRY.gauge("tidbtpu_link_heartbeat_age_seconds", "a")
    '''
)


def _lint_tree(tmp_path, inspection_src):
    obs = tmp_path / "tidb_tpu" / "obs"
    obs.mkdir(parents=True)
    (obs / "flight.py").write_text(_FLIGHT_STUB)
    (obs / "inspection.py").write_text(textwrap.dedent(inspection_src))
    (tmp_path / "tidb_tpu" / "engine.py").write_text(_METRICS_STUB)
    return subprocess.run(
        [sys.executable, LINT, str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )


class TestInspectionRulesLint:
    def test_clean_tree_passes(self, tmp_path):
        proc = _lint_tree(
            tmp_path,
            '''
            @rule("ok", metrics=("tidbtpu_dcn_retries",),
                  phases=("compile",))
            def _ok(ctx):
                return []
            ''',
        )
        assert proc.returncode == 0, proc.stdout

    def test_head_is_clean(self):
        proc = subprocess.run(
            [sys.executable, LINT, REPO], capture_output=True,
            text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout

    def test_bad_convention_and_undeclared_subsystem(self, tmp_path):
        proc = _lint_tree(
            tmp_path,
            '''
            @rule("bad", metrics=("tidb_tpu-wrong",))
            def _bad(ctx):
                return []

            @rule("bad2", metrics=("tidbtpu_nosuchsub_x",))
            def _bad2(ctx):
                return []
            ''',
        )
        assert proc.returncode == 1
        assert "violating the tidbtpu_<subsystem>_<name>" in proc.stdout
        assert "undeclared subsystem 'nosuchsub'" in proc.stdout

    def test_dead_metric_declaration_fails(self, tmp_path):
        proc = _lint_tree(
            tmp_path,
            '''
            @rule("dead", metrics=("tidbtpu_dcn_never_registered",))
            def _dead(ctx):
                return []
            ''',
        )
        assert proc.returncode == 1
        assert "dead rule declaration" in proc.stdout

    def test_undeclared_phase_and_empty_metrics_fail(self, tmp_path):
        proc = _lint_tree(
            tmp_path,
            '''
            @rule("p", metrics=("tidbtpu_dcn_retries",),
                  phases=("warp-drive",))
            def _p(ctx):
                return []

            @rule("empty", metrics=())
            def _empty(ctx):
                return []
            ''',
        )
        assert proc.returncode == 1
        assert "undeclared flight phase 'warp-drive'" in proc.stdout
        assert "declares no metrics" in proc.stdout

    def test_duplicate_rule_names_fail(self, tmp_path):
        proc = _lint_tree(
            tmp_path,
            '''
            @rule("twice", metrics=("tidbtpu_dcn_retries",))
            def _a(ctx):
                return []

            @rule("twice", metrics=("tidbtpu_dcn_retries",))
            def _b(ctx):
                return []
            ''',
        )
        assert proc.returncode == 1
        assert "duplicate inspection rule 'twice'" in proc.stdout


# ---------------------------------------------------------------------------
# HTTP endpoints
# ---------------------------------------------------------------------------


def test_http_tsdb_and_inspection_endpoints(sess):
    import json
    import urllib.request

    from tidb_tpu.server.http_status import StatusServer

    SAMPLER.sample_once()
    http = StatusServer(sess.catalog, port=0)
    http.start_background()
    try:
        base = f"http://127.0.0.1:{http.port}"
        tsdb = json.loads(
            urllib.request.urlopen(f"{base}/tsdb", timeout=10)
            .read().decode()
        )
        assert tsdb["series"] > 0 and tsdb["points"] > 0
        assert (
            "tidbtpu_session_statements_total" in tsdb["families"]
        )
        one = json.loads(
            urllib.request.urlopen(
                f"{base}/tsdb?metric="
                "tidbtpu_session_statements_total",
                timeout=10,
            ).read().decode()
        )
        assert one["points"]
        insp = json.loads(
            urllib.request.urlopen(f"{base}/inspection", timeout=10)
            .read().decode()
        )
        assert "findings" in insp
    finally:
        http.shutdown()
