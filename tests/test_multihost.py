"""Multi-host (DCN analog) tests: 2 processes x 4 virtual CPU devices.

Two complementary shapes (both 2-process x 4-device dryruns):

1. multi-controller SPMD — both processes run the same program over one
   8-device global mesh via jax.distributed (coordinator = PD analog);
   collectives ride the inter-process transport (DCN on real slices).
2. coordinator/worker MPP — the DCN fragment scheduler
   (parallel/dcn.py) dispatches per-host fragment plans over the
   engine-RPC seam to two worker processes, each executing SPMD on its
   own 4-device mesh (hierarchical shuffle: ICI within the host,
   host-staged exchange between), with partial-agg-before-DCN and
   failure recovery (kill-one-worker retry parity below).

Reference: cross-store MPP dispatch over gRPC (pkg/store/copr/mpp.go:93)
with PD-coordinated membership, and the MPP recovery loop
(pkg/executor/internal/mpp/recovery_handler.go:26).
"""

import os
import re
import socket
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

#: the TPC-H subset both dryruns assert parity on: scalar aggregate
#: (Q6 shape), grouped aggregate with avg (Q1 shape), join + group-by
#: (Q4/Q18 shape), top-k group-by
TPCH_QUERIES = [
    "select sum(l_extendedprice * l_discount) from lineitem "
    "where l_discount between 0.05 and 0.07 and l_quantity < 24",
    "select l_returnflag, l_linestatus, sum(l_quantity), "
    "sum(l_extendedprice), avg(l_discount), count(*) from lineitem "
    "where l_shipdate <= date '1998-09-02' "
    "group by l_returnflag, l_linestatus "
    "order by l_returnflag, l_linestatus",
    "select o_orderpriority, count(*) from orders join lineitem "
    "on o_orderkey = l_orderkey where l_quantity < 10 "
    "group by o_orderpriority order by o_orderpriority",
    "select l_suppkey, count(*) from lineitem group by l_suppkey "
    "order by count(*) desc, l_suppkey limit 5",
]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _worker_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the pytest process forces an 8-device host platform (conftest);
    # each worker must contribute exactly 4
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return env


def test_two_process_mesh_sql_parity():
    worker = os.path.join(HERE, "_multihost_worker.py")
    coord = f"127.0.0.1:{_free_port()}"
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), "2", coord],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=_worker_env(),
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-4000:]}"
        assert "MULTIHOST_OK" in out, out[-2000:]


# ---------------------------------------------------------------------------
# DCN fragment scheduler dryruns (coordinator here, 2 worker processes)
# ---------------------------------------------------------------------------


def _spawn_dcn_worker(extra=()):
    p = subprocess.Popen(
        [
            sys.executable, "-m", "tidb_tpu.parallel.dcn_worker",
            "--cpu", "--port", "0", "--mesh-devices", "4",
            "--tpch-sf", "0.002", "--seed", "3",
            "--tables", "orders,lineitem", *extra,
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=_worker_env(),
        cwd=REPO,
    )
    line = p.stdout.readline()
    m = re.match(r"DCN_WORKER_READY port=(\d+)", line)
    if not m:
        rest = ""
        try:
            rest, _ = p.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
        raise AssertionError(f"worker not ready: {line!r}\n{rest[-3000:]}")
    return p, int(m.group(1))


@pytest.fixture()
def tpch_single():
    """Single-process reference session over the same deterministic
    data every worker loads."""
    from tidb_tpu.bench import load_tpch
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog

    cat = Catalog()
    load_tpch(cat, sf=0.002, seed=3, tables=["orders", "lineitem"])
    return Session(cat, db="tpch")


def _plan(sess, q):
    from tidb_tpu.parser.sqlparse import parse
    from tidb_tpu.planner.logical import build_query

    return build_query(
        parse(q)[0], sess.catalog, "tpch", sess._scalar_subquery
    )


def test_dcn_fragment_scheduler_tpch_parity(tpch_single):
    """2-process x 4-device dryrun: the TPC-H subset runs through the
    cross-host fragment scheduler with results identical to
    single-process execution."""
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker()
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=tpch_single.catalog,
    )
    try:
        for q in TPCH_QUERIES:
            exp = tpch_single.must_query(q).rows
            _cols, got = sched.execute_plan(_plan(tpch_single, q))
            assert got == exp, f"{q}\n got={got}\n exp={exp}"
        # every query fanned out: both hosts stayed in rotation
        assert len(sched.alive_endpoints()) == 2
    finally:
        sched.close()
        for w in (w1, w2):
            w.kill()


def test_dcn_explain_analyze_and_metrics(tpch_single):
    """Distributed EXPLAIN ANALYZE on the 2-process x 4-device dryrun:
    the plan tree carries per-host fragment rows with nonzero execution
    times and DCN byte counts, and /metrics afterwards exposes the
    tidbtpu_dcn_* counters plus tidbtpu_engine_jit_compilations
    consistent with the run."""
    import json
    import urllib.request

    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.server.http_status import StatusServer
    from tidb_tpu.utils.metrics import REGISTRY

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker()
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=tpch_single.catalog,
    )
    http = StatusServer(tpch_single.catalog, port=0, dcn=sched)
    http.start_background()
    dispatches0 = sum(
        v for n, _k, v in REGISTRY.rows()
        if n.startswith("tidbtpu_dcn_dispatches")
    )
    try:
        q = TPCH_QUERIES[1]  # grouped aggregate with avg (Q1 shape)
        exp = tpch_single.must_query(q).rows
        _cols, rows, lines = sched.explain_analyze(_plan(tpch_single, q))
        assert rows == exp  # the instrumented run still returns parity
        text = "\n".join(lines)
        assert "DCNFragments fragments=2 hosts=2" in text
        frag_lines = [
            ln for ln in lines if ln.lstrip().startswith("Fragment#")
        ]
        assert len(frag_lines) == 2
        for ln in frag_lines:
            m = re.search(
                r"host=(\S+) attempt=1 rows=(\d+) "
                r"time=([0-9.]+)ms bytes=(\d+)", ln
            )
            assert m, ln
            assert float(m.group(3)) > 0  # nonzero per-host exec time
            assert int(m.group(4)) > 0    # nonzero DCN byte count
        # the two fragments ran on distinct worker hosts
        assert len({re.search(r"host=(\S+)", ln).group(1)
                    for ln in frag_lines}) == 2
        # min/avg/max across hosts + total bytes shipped in the summary
        assert re.search(
            r"bytes_shipped=[1-9]\d* time min=[0-9.]+ms "
            r"avg=[0-9.]+ms max=[0-9.]+ms", text
        )

        # /metrics after the run: dcn counters + engine jit accounting
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{http.port}/metrics", timeout=10
        ).read().decode()
        assert "tidbtpu_dcn_dispatches" in body
        assert "tidbtpu_dcn_bytes_staged" in body
        jit = re.search(
            r"^tidbtpu_engine_jit_compilations (\d+)", body, re.M
        )
        assert jit and int(jit.group(1)) > 0
        dispatches1 = sum(
            v for n, _k, v in REGISTRY.rows()
            if n.startswith("tidbtpu_dcn_dispatches")
        )
        assert dispatches1 >= dispatches0 + 2  # both fragments dispatched
        # /dcn: per-fragment stats of the run we just made
        dcn = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{http.port}/dcn", timeout=10
        ).read().decode())
        assert dcn["alive"] == 2
        assert [f["fid"] for f in dcn["last_query"]["fragments"]] == [0, 1]
    finally:
        http.shutdown()
        sched.close()
        for w in (w1, w2):
            w.kill()


def _counter_total(prefix):
    from tidb_tpu.utils.metrics import REGISTRY

    return sum(
        v for n, _k, v in REGISTRY.rows() if n.startswith(prefix)
    )


#: joins and distinct group-bys routed over worker-to-worker tunnels
SHUFFLE_QUERIES = [
    # repartition join: orders join lineitem, neither side small
    TPCH_QUERIES[2],
    # fragment-sliced GROUP BY with DISTINCT (the old single-host
    # fallback): complete groups per partition
    "select o_orderpriority, count(distinct o_custkey) from orders "
    "group by o_orderpriority order by o_orderpriority",
]

#: STRING-keyed repartition join (un-gated by the binary columnar wire
#: format: values hash stably, receivers re-key dictionary codes into a
#: stage-local unified dictionary). Filters keep the F/O status match
#: explosion small at SF 0.002.
STRING_KEY_JOIN = (
    "select o_orderstatus, count(*) from orders join lineitem "
    "on o_orderstatus = l_linestatus "
    "where o_totalprice > 150000 and l_quantity >= 47 "
    "group by o_orderstatus order by o_orderstatus"
)


def test_dcn_shuffle_repartition_join_parity(tpch_single):
    """2-process x 4-device dryrun of the worker-to-worker shuffle
    service: repartition join + distinct GROUP BY + STRING-keyed join
    run with results identical to single-process execution, the
    shuffled bytes provably BYPASS the coordinator —
    tidbtpu_shuffle_bytes_total (incremented only in the worker
    processes, shipped back via the piggybacked registry deltas) grows,
    while tidbtpu_dcn_bytes_staged does not move at all — and the
    binary columnar wire codec puts <= 0.5x the JSON codec's bytes on
    the tunnels for the same query at row-level result parity."""
    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker()
    try:
        _shuffle_codec_ab_body(tpch_single, p1, p2)
    finally:
        for w in (w1, w2):
            w.kill()


def _shuffle_codec_ab_body(tpch_single, p1, p2):
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler

    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=tpch_single.catalog,
        shuffle_mode="always",
    )
    staged0 = _counter_total("tidbtpu_dcn_bytes_staged")
    shuffled0 = _counter_total("tidbtpu_shuffle_bytes_total")
    bytes_binary = {}
    try:
        for q in SHUFFLE_QUERIES + [STRING_KEY_JOIN]:
            exp = tpch_single.must_query(q).rows
            _cols, got = sched.execute_plan(_plan(tpch_single, q))
            assert got == exp, f"{q}\n got={got}\n exp={exp}"
            bytes_binary[q] = sched.last_query["shuffle"]["bytes_tunneled"]
            assert sched.last_query["shuffle"]["codec"] == "binary"
        # the string-keyed join really rode the shuffle path (no
        # single-host fallback) and really exchanged partition data
        assert sched.last_query["shuffle"]["kind"] == "join"
        assert bytes_binary[STRING_KEY_JOIN] > 0
        last = sched.last_query
        assert last["shuffle"]["m"] == 2
        assert last["shuffle"]["bytes_tunneled"] > 0
        # the acceptance criterion: inter-worker data rode the tunnels,
        # not the coordinator
        staged1 = _counter_total("tidbtpu_dcn_bytes_staged")
        shuffled1 = _counter_total("tidbtpu_shuffle_bytes_total")
        assert shuffled1 > shuffled0  # fleet counters merged from replies
        assert staged1 == staged0
        # per-partition results DID return to the coordinator (they are
        # final rows, not exchange data) under their own counter
        assert _counter_total("tidbtpu_shuffle_result_bytes") > 0
        assert len(sched.alive_endpoints()) == 2
    finally:
        sched.close()

    # codec A/B on the same workers: the JSON escape hatch gives the
    # same rows while the binary codec's tunnel bytes are <= 0.5x
    sched_json = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=tpch_single.catalog,
        shuffle_mode="always",
        shuffle_codec="json",
    )
    try:
        q = SHUFFLE_QUERIES[0]
        exp = tpch_single.must_query(q).rows
        _cols, got = sched_json.execute_plan(_plan(tpch_single, q))
        assert got == exp  # row-level cross-codec parity
        bytes_json = sched_json.last_query["shuffle"]["bytes_tunneled"]
        assert sched_json.last_query["shuffle"]["codec"] == "json"
        assert bytes_json > 0
        assert bytes_binary[q] <= 0.5 * bytes_json, (
            f"binary codec shipped {bytes_binary[q]}B vs JSON "
            f"{bytes_json}B — expected <= 0.5x"
        )
    finally:
        sched_json.close()


def test_dcn_flight_recorder_surfaces(tpch_single, tmp_path):
    """PR 6 acceptance: a 2-process x 4-device shuffle dryrun driven
    through the SESSION (an attached scheduler now routes fragmentable
    SELECTs across the fleet, not just EXPLAIN ANALYZE) lands all
    three flight-recorder surfaces:

    - statements_summary rows with NON-ZERO shuffle-wait phase time
      and p99 >= p50 (the per-digest streaming histogram);
    - slow_query rows carrying captured EXPLAIN ANALYZE text (the
      instrumented lines for an over-threshold EXPLAIN ANALYZE, the
      plan tree + distributed runtime summary for a routed SELECT),
      also written to the tidb_slow_query_file sink;
    - cluster_links rows with per-peer RTT and stall seconds.
    """
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.utils.metrics import STMT_SUMMARY, sql_digest

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker()
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=tpch_single.catalog,
        shuffle_mode="always",
    )
    sess = tpch_single
    q = SHUFFLE_QUERIES[0]
    exp = sess.must_query(q).rows  # local reference BEFORE attaching
    sess.attach_dcn_scheduler(sched)
    try:
        sess.execute("set tidb_slow_log_threshold = 0")
        slow_file = tmp_path / "slow.log"
        sess.execute(f"set tidb_slow_query_file = '{slow_file}'")
        for _ in range(3):
            r = sess.execute(q)
            assert r.rows == exp  # scheduler-routed result parity

        # -- statements_summary: shuffle phases + percentiles ----------
        d = sql_digest(q)
        ent = next(
            e for e in STMT_SUMMARY.rows_full() if e["digest_text"] == d
        )
        assert ent["phases"]["shuffle-wait"][0] > 0
        assert ent["phases"]["shuffle-produce"][0] > 0
        assert ent["phases"]["shuffle-push"][1] > 0  # tunneled bytes
        assert ent["phases"]["fragment-dispatch"][0] > 0
        assert ent["p99_latency"] >= ent["p50_latency"] > 0
        r = sess.must_query(
            "select avg_shuffle_wait, p50_latency, p99_latency,"
            " shuffle_bytes from information_schema.statements_summary"
            f" where digest_text = '{d}'"
        )
        avg_wait, p50, p99, sbytes = r.rows[0]
        assert avg_wait > 0 and p99 >= p50 > 0 and sbytes > 0

        # -- slow_query: captured EXPLAIN ANALYZE / plan text ----------
        sess.execute(f"explain analyze {q}")
        r = sess.must_query(
            "select query, plan from information_schema.slow_query"
            " where plan != ''"
        )
        routed_plans = [p for (txt, p) in r.rows if txt == q]
        assert routed_plans and any(
            "DCNShuffle" in p for p in routed_plans
        ), "routed SELECT's capture lacks the distributed summary"
        ea_plans = [
            p for (txt, p) in r.rows if txt == f"explain analyze {q}"
        ]
        assert ea_plans and any("DCNShuffle" in p for p in ea_plans), (
            "EXPLAIN ANALYZE capture is not the instrumented text"
        )
        text = slow_file.read_text()
        assert "# Query_time:" in text and "# Phases:" in text
        assert "# Plan: " in text and "DCNShuffle" in text

        # -- cluster_links: per-peer link health -----------------------
        sched.heartbeat.beat_once()
        r = sess.must_query(
            "select kind, dst, rtt_ms, heartbeat_age_s, stall_seconds,"
            " bytes, frames, codec from"
            " information_schema.cluster_links"
        )
        controls = [row for row in r.rows if row[0] == "control"]
        tunnels = [row for row in r.rows if row[0] == "tunnel"]
        worker_addrs = {f"127.0.0.1:{p1}", f"127.0.0.1:{p2}"}
        assert worker_addrs <= {row[1] for row in controls}
        assert any(row[2] > 0 for row in controls)  # handshake RTT
        assert all(row[3] >= 0 for row in controls)  # heartbeat age
        # worker-to-worker tunnels merged from fenced shuffle replies:
        # real bytes/frames per link, stall seconds present (>= 0)
        assert any(
            row[1] in worker_addrs and row[5] > 0 and row[6] > 0
            for row in tunnels
        )
        assert all(row[4] >= 0.0 for row in tunnels)
        assert any(row[7] == "binary" for row in tunnels)
    finally:
        sess.attach_dcn_scheduler(None)
        sched.close()
        for w in (w1, w2):
            w.kill()


def test_dcn_metrics_schema_fleet_history(tpch_single):
    """PR 12 acceptance: the 2-process x 4-device dryrun accretes
    SQL-queryable metric HISTORY for the whole fleet. Worker processes
    sample their own registries and ship the rows piggybacked on
    fenced shuffle replies (plus the heartbeat idle-flush);
    `SELECT ... FROM metrics_schema.tidbtpu_shuffle_codec_bytes WHERE
    time >= ...` then returns sampled points for BOTH worker hosts
    with the codec label column intact, under bounded store memory,
    with the time predicate pushed into the retention rings."""
    import time as _time

    from tidb_tpu.obs.tsdb import TSDB
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker()
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=tpch_single.catalog,
        shuffle_mode="always",
    )
    sess = tpch_single
    t_run0 = _time.time()
    try:
        q = SHUFFLE_QUERIES[0]
        exp = sess.must_query(q).rows
        for _ in range(2):
            # >= 2 rounds spaced past the worker's sample cadence so
            # each host ships at least two time points (history, not
            # a single snapshot)
            _cols, got = sched.execute_plan(_plan(sess, q))
            assert got == exp
            _time.sleep(1.1)
        # the heartbeat idle-flush: pending worker samples land even
        # with no dispatch in flight
        sched.heartbeat.beat_once()

        worker_addrs = {f"127.0.0.1:{p1}", f"127.0.0.1:{p2}"}
        r = sess.must_query(
            "select time, instance, codec, value from "
            "metrics_schema.tidbtpu_shuffle_codec_bytes "
            f"where time >= {t_run0 - 5.0}"
        )
        assert r.rows, "no sampled shuffle history reached the store"
        hosts = {row[1] for row in r.rows}
        assert worker_addrs <= hosts, (
            f"history missing a worker host: {hosts}"
        )
        # label columns intact: the codec label survives as a column
        assert {row[2] for row in r.rows} <= {"binary", "json"}
        assert all(row[3] > 0 for row in r.rows)
        # both hosts shipped HISTORY (>= 2 distinct sample times)
        for addr in worker_addrs:
            times = {row[0] for row in r.rows if row[1] == addr}
            assert len(times) >= 2, (
                f"{addr} shipped {len(times)} sample time(s)"
            )
        # the time predicate genuinely pushed into the store: a
        # future-bounded scan materializes ZERO points while the
        # unbounded family is non-empty (were the session's hint
        # extraction deleted, the store would materialize everything
        # and last_scan_points would equal the total)
        r = sess.must_query(
            "select time from "
            "metrics_schema.tidbtpu_shuffle_codec_bytes "
            f"where time >= {t_run0 + 10 ** 6}"
        )
        assert r.rows == []
        assert TSDB.last_scan_points == 0
        assert len(TSDB.query("tidbtpu_shuffle_codec_bytes")) > 0
        # bounded memory: every ring respects the retention caps
        cap = 2 * TSDB.retention_points
        assert TSDB.point_count() <= TSDB.series_count() * cap
    finally:
        sched.close()
        for w in (w1, w2):
            w.kill()


def test_dcn_many_session_serving_dryrun(tpch_single):
    """PR 8 serving tier: a 2-process x 4-device fleet serves 8+
    CONCURRENT session threads (each session its own Session object
    over the shared catalog, scheduler attached, admission-gated).
    Asserts per-session result parity for a mixed short/scan workload
    (HIGH_PRIORITY grouped aggregate + LOW_PRIORITY repartition join),
    that every statement was admitted through the controller, and that
    the cross-session compiled-plan cache was actually hit (> 0) — the
    per-connection worker executors and pooled control connections
    mean two sessions' identical fragments reuse one compile."""
    import threading

    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.parallel.serving import AdmissionController
    from tidb_tpu.session import Session

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker()
    admission = AdmissionController(queue_timeout_s=300.0)
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=tpch_single.catalog,
        shuffle_min_rows=1,  # joins ride the tunnels even at dryrun SF
        admission=admission,
    )
    short_q = (
        "select high_priority l_returnflag, count(*), sum(l_quantity) "
        "from lineitem group by l_returnflag order by l_returnflag"
    )
    scan_q = (
        "select low_priority o_orderpriority, count(*), "
        "sum(l_extendedprice) from orders join lineitem "
        "on o_orderkey = l_orderkey where l_quantity < 24 "
        "group by o_orderpriority order by o_orderpriority"
    )
    exp_short = tpch_single.must_query(short_q).rows
    exp_scan = tpch_single.must_query(scan_q).rows
    hits0 = _counter_total(
        "tidbtpu_executor_shared_plan_cache_cross_session_hits_total"
    )
    errors, done = [], []

    def session_thread(i):
        try:
            sess = Session(tpch_single.catalog, db="tpch")
            sess.attach_dcn_scheduler(sched)
            for rnd in range(2):
                q, exp = (
                    (scan_q, exp_scan) if (i + rnd) % 4 == 0
                    else (short_q, exp_short)
                )
                r = sess.execute(q)
                assert r.rows == exp, (
                    f"session {i} round {rnd} parity broke"
                )
            done.append(i)
        except Exception as e:
            errors.append((i, f"{type(e).__name__}: {e}"))

    threads = [
        threading.Thread(target=session_thread, args=(i,), daemon=True)
        for i in range(8)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=480)
        hung = [t.name for t in threads if t.is_alive()]
        assert not hung, f"session threads hung: {hung}"
        assert not errors, f"serving dryrun failed: {errors[:3]}"
        assert sorted(done) == list(range(8))
        # no statement dodged the gate, none was shed on a healthy fleet
        outcomes = admission.status()["outcomes"]
        assert outcomes["admit"] >= 16, outcomes
        assert outcomes["reject"] == 0 and outcomes["timeout"] == 0
        # cross-session compile reuse really happened (worker-side
        # counters ship back on the fenced replies; coordinator-side
        # final stages share through the same cache)
        hits1 = _counter_total(
            "tidbtpu_executor_shared_plan_cache_cross_session_hits_total"
        )
        assert hits1 > hits0, (
            "no cross-session shared-plan-cache hits under 8 sessions"
        )
        assert len(sched.alive_endpoints()) == 2
    finally:
        sched.close()
        for w in (w1, w2):
            w.kill()


def test_dcn_timeline_trace_cross_host(tpch_single):
    """PR 9 acceptance: a 2-process x 4-device shuffle dryrun captured
    by the fleet timeline tracer produces a VALID Chrome trace with:

    - process tracks for the coordinator AND both worker hosts (worker
      events ship piggybacked on the fenced replies);
    - clock-offset monotonicity: no worker event starts before its
      fragment's dispatch event on the rebased coordinator timeline;
    - the overlap proof: pipelined tasks' produce/push windows overlap
      in time, the barrier escape hatch's do not;
    - compile events carrying non-empty XLA cost_analysis attributes,
      and the per-digest cost columns populated in statements_summary.
    """
    import json as _json

    from tidb_tpu.obs.timeline import (
        TIMELINE,
        shuffle_overlap_report,
    )
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.planner.physical import SHARED_PLAN_CACHE

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker()
    worker_addrs = {f"127.0.0.1:{p1}", f"127.0.0.1:{p2}"}
    q = SHUFFLE_QUERIES[0]
    exp = tpch_single.must_query(q).rows
    # the compile-event assertion needs a REAL coordinator compile
    # under capture: an earlier test in the session may still pin this
    # final-stage shape in the process-wide shared plan cache (weak
    # entries live as long as any executor's LRU does), which would
    # make the fresh scheduler import instead of compile
    SHARED_PLAN_CACHE._map.clear()
    TIMELINE.start(capacity=65536)
    try:
        for pipeline in (True, False):
            sched = DCNFragmentScheduler(
                [("127.0.0.1", p1), ("127.0.0.1", p2)],
                catalog=tpch_single.catalog,
                shuffle_mode="always",
                shuffle_pipeline=pipeline,
            )
            try:
                for _ in range(2):
                    _cols, got = sched.execute_plan(
                        _plan(tpch_single, q)
                    )
                    assert got == exp
            finally:
                sched.close()
        TIMELINE.stop()

        # -- valid Chrome trace JSON with both hosts' process tracks --
        trace = _json.loads(
            _json.dumps(TIMELINE.dump())  # round-trips (serializable)
        )
        evs = trace["traceEvents"]
        procs = {
            e["args"]["name"]
            for e in evs
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        assert "coordinator" in procs
        assert worker_addrs <= procs, (
            f"missing worker process tracks: {procs}"
        )
        for e in evs:
            if e.get("ph") == "X":
                assert isinstance(e["ts"], float) and e["ts"] >= 0
                assert isinstance(e["dur"], float) and e["dur"] >= 0
                assert e["cat"] and e["name"] and e["pid"]

        # -- clock-offset monotonicity --------------------------------
        raw = TIMELINE.events()
        dispatches = {}
        for ph, cat, name, t0, dur, host, track, args in raw:
            if ph == "X" and cat == "fragment" and args and (
                name.startswith("dispatch")
            ):
                key = (args["host"], f"q{args['qid']}/{args['unit']}")
                dispatches[key] = min(
                    dispatches.get(key, t0), t0
                )
        assert dispatches, "no coordinator dispatch events captured"
        checked = 0
        for ph, cat, name, t0, dur, host, track, args in raw:
            if ph != "X" or host not in worker_addrs:
                continue
            if cat not in ("shuffle", "fragment"):
                continue
            d0 = dispatches.get((host, track))
            if d0 is None:
                continue
            checked += 1
            assert t0 >= d0 - 0.05, (
                f"worker event {name} on {host}/{track} starts "
                f"{d0 - t0:.3f}s before its dispatch (clock rebase "
                "broke monotonicity)"
            )
        assert checked > 0, "no worker events matched a dispatch"

        # -- overlap: pipelined yes, barrier no -----------------------
        rep = shuffle_overlap_report(raw)
        pipe_overlap = max(
            (r["produce_push_overlap_s"]
             for r in rep.values() if r["pipeline"]),
            default=0.0,
        )
        barrier_tracks = [
            r for r in rep.values()
            if not r["pipeline"] and r["push_windows"]
        ]
        assert pipe_overlap > 0.0, (
            f"pipelined produce/push windows never overlapped: {rep}"
        )
        # tolerance: event windows mix a wall-clock start with a
        # perf_counter duration, so strictly-sequential barrier phases
        # can show microsecond-scale numeric overlap — anything at ms
        # scale would be REAL overlap and a bug
        assert barrier_tracks and all(
            r["produce_push_overlap_s"] < 0.005 for r in barrier_tracks
        ), f"barrier stage shows overlap: {rep}"

        # -- compile events carry cost analysis -----------------------
        compile_costs = [
            (args or {}).get("cost_analysis")
            for ph, cat, name, t0, dur, host, track, args in raw
            if ph == "X" and cat == "compile"
        ]
        assert any(
            c and c.get("flops", 0) > 0 for c in compile_costs
        ), "no compile event carries non-empty cost_analysis"
    finally:
        TIMELINE.stop()
        TIMELINE.clear()
        for w in (w1, w2):
            w.kill()


def test_dcn_worker_death_mid_shuffle_retry_parity(tpch_single):
    """Failpoint-killed worker MID-SHUFFLE with PIPELINING ON: worker 2
    hard-exits on the first partition packet a peer pushes to it (the
    shuffle/recv site), mid-way through the survivor's chunk-granular
    pipelined push with frames already decoded-on-arrival on both ends.
    Worker 1's tunnel reports the dead peer, the coordinator verifies
    and quarantines it, re-runs the WHOLE stage on the survivor set
    (attempt 2, m=1 — upstream partitions re-shuffled to the
    survivors), the dead attempt's partially-decoded stage is fenced
    out by the attempt bump, and the rerun still matches the reference
    exactly once."""
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.server.engine_pool import FailedEngineProber

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker(
        ["--die-on-fragment", "1", "--die-at", "shuffle-recv"]
    )
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=tpch_single.catalog,
        shuffle_mode="always",
        shuffle_pipeline=True,  # explicit: retry parity WITH overlap
        shuffle_wait_timeout_s=20.0,
        prober=FailedEngineProber(initial_backoff_s=60),
    )
    try:
        q = SHUFFLE_QUERIES[0]
        exp = tpch_single.must_query(q).rows
        _cols, got = sched.execute_plan(_plan(tpch_single, q))
        assert got == exp, f"\n got={got}\n exp={exp}"
        # the stage really retried on the survivor set, pipelined
        assert sched.last_query["shuffle"]["attempts"] >= 2
        assert sched.last_query["shuffle"]["m"] == 1
        assert sched.last_query["shuffle"]["pipeline"] is True
        assert [e.port for e in sched.prober.failed_endpoints()] == [p2]
        w2.wait(timeout=30)
        assert w2.returncode == 3
        # the survivor keeps serving shuffle stages alone
        q2 = SHUFFLE_QUERIES[1]
        exp2 = tpch_single.must_query(q2).rows
        _cols, got2 = sched.execute_plan(_plan(tpch_single, q2))
        assert got2 == exp2
    finally:
        sched.close()
        for w in (w1, w2):
            w.kill()


def test_dcn_worker_death_mid_query_retry_parity(tpch_single):
    """Failpoint-killed worker mid-query: worker 2 hard-exits AFTER
    computing its first fragment but BEFORE replying (the
    dcn/result-send site — work done, reply lost). The coordinator must
    quarantine it, re-dispatch the fragment onto the survivor, and
    still return correct results exactly once."""
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.server.engine_pool import FailedEngineProber

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker(
        ["--die-on-fragment", "1", "--die-at", "result-send"]
    )
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=tpch_single.catalog,
        prober=FailedEngineProber(initial_backoff_s=60),
    )
    try:
        q = TPCH_QUERIES[2]  # join + group-by
        exp = tpch_single.must_query(q).rows
        _cols, got = sched.execute_plan(_plan(tpch_single, q))
        assert got == exp, f"\n got={got}\n exp={exp}"
        # the dead worker was quarantined, and really died via os._exit
        assert [e.port for e in sched.prober.failed_endpoints()] == [p2]
        w2.wait(timeout=30)
        assert w2.returncode == 3
        # the survivor keeps serving (fewer fragments per query)
        q2 = TPCH_QUERIES[0]
        exp2 = tpch_single.must_query(q2).rows
        _cols, got2 = sched.execute_plan(_plan(tpch_single, q2))
        assert got2 == exp2
    finally:
        sched.close()
        for w in (w1, w2):
            w.kill()


def test_dcn_fleet_cancellation_kill_and_max_execution_time(tpch_single):
    """ISSUE 10 acceptance: KILL and max_execution_time on a routed
    query cancel WORKER-SIDE fragments and shuffle tasks. Both workers
    are armed with a worker-side hang failpoint (shuffle/produce
    sleeps 30s via --chaos-spec); the kill must broadcast cancel_query
    so worker task threads exit and staged buffers free LONG before
    the hang would, and the killed statement's flight record still
    lands in statements_summary with its phase breakdown."""
    import json as _json
    import threading
    import time

    from tidb_tpu.chaos.schedule import Fault
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.server.engine_rpc import EngineClient
    from tidb_tpu.utils.metrics import STMT_SUMMARY, sql_digest

    # a 2-hit hang window per worker: the KILL statement consumes the
    # first hit, the max_execution_time statement the second, and the
    # final parity query runs against healthy workers
    spec = _json.dumps([
        Fault("worker-hang", "shuffle/produce", "hang", n=2,
              param=30.0).to_dict(),
    ])
    w1, p1 = _spawn_dcn_worker(["--chaos-spec", spec])
    w2, p2 = _spawn_dcn_worker(["--chaos-spec", spec])
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=tpch_single.catalog,
        shuffle_mode="always",
        shuffle_wait_timeout_s=60.0,
    )
    sess = tpch_single
    q = SHUFFLE_QUERIES[0]
    sess.attach_dcn_scheduler(sched)

    def assert_workers_clean():
        """Worker task threads exited and staged buffers freed —
        polled over the engine_status introspection frame."""
        deadline = time.monotonic() + 10.0
        while True:
            states = []
            for port in (p1, p2):
                c = EngineClient("127.0.0.1", port, timeout_s=5.0)
                try:
                    states.append(c.engine_status())
                finally:
                    c.close()
            if all(
                st["stages_buffered"] == 0
                and not st["shuffle_threads"]
                for st in states
            ):
                return
            if time.monotonic() > deadline:
                raise AssertionError(
                    f"worker-side work outlived the kill: {states}"
                )
            time.sleep(0.1)

    try:
        # -- KILL QUERY mid-hang ---------------------------------------
        errors = []

        def runner():
            try:
                sess.execute(q)
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")

        t = threading.Thread(target=runner, daemon=True)
        t0 = time.monotonic()
        t.start()
        # kill only once the dispatch REACHED the workers (their
        # stores opened a stage record) — a blind sleep races worker
        # startup and can kill before/never-reaching the hung produce
        wait_deadline = time.monotonic() + 30.0
        while time.monotonic() < wait_deadline:
            opened = 0
            for port in (p1, p2):
                c = EngineClient("127.0.0.1", port, timeout_s=5.0)
                try:
                    opened += c.engine_status()["stages_buffered"]
                finally:
                    c.close()
            if opened >= 2:
                break
            time.sleep(0.1)
        assert opened >= 2, "dispatch never reached the workers"
        time.sleep(0.3)  # both tasks are in the hung produce now
        sess.killer.kill()
        t.join(timeout=30)
        assert not t.is_alive(), "killed statement never returned"
        wall = time.monotonic() - t0
        assert errors and "interrupted" in errors[0], errors
        assert wall < 25.0, (
            f"kill took {wall:.1f}s — the 30s worker hang was not "
            "cancelled"
        )
        assert_workers_clean()
        # the killed statement's flight record landed, phases intact
        ent = next(
            e for e in STMT_SUMMARY.rows_full()
            if e["digest_text"] == sql_digest(q)
        )
        assert ent["exec_count"] >= 1
        assert ent["max_latency"] > 0  # the wait it paid is visible
        assert "parse" in ent["phases"] and "plan" in ent["phases"]

        # -- max_execution_time mid-hang -------------------------------
        # (the second --chaos-spec hang hit arms each worker's n=1
        # once; re-arm by statement: the deadline also PROPAGATES so
        # the worker self-cancels even without the coordinator watch)
        sess.execute("set max_execution_time = 1200")
        t0 = time.monotonic()
        try:
            sess.execute(q)
            raise AssertionError("max_execution_time never fired")
        except Exception as e:
            assert "interrupted" in str(e), e
        wall = time.monotonic() - t0
        assert wall < 20.0, f"deadline abort took {wall:.1f}s"
        sess.execute("set max_execution_time = 0")
        assert_workers_clean()
        # the fleet is healthy after both aborts: same query, parity
        exp = None
        sess.attach_dcn_scheduler(None)
        exp = sess.must_query(q).rows
        sess.attach_dcn_scheduler(sched)
        r = sess.execute(q)
        assert r.rows == exp
    finally:
        sess.attach_dcn_scheduler(None)
        sched.close()
        for w in (w1, w2):
            w.kill()


def test_dcn_multihost_chaos_composed_faults(tpch_single):
    """ISSUE 10 acceptance: a seeded chaos schedule composing crash +
    hang + frame loss over the 2-process dryrun — worker 1 hard-exits
    (os._exit) on a pushed frame, worker 0 hangs a produce and drops
    frames probabilistically — passes all fleet invariants with exact
    row parity, and the same seed replays the same fault schedule
    deterministically."""
    import json as _json
    import time

    from tidb_tpu.chaos.schedule import generate_worker_specs
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.server.engine_pool import FailedEngineProber
    from tidb_tpu.server.engine_rpc import EngineClient

    SEED = 1310
    specs = generate_worker_specs(SEED, 2)
    assert specs == generate_worker_specs(SEED, 2)  # replayable
    classes = {f["cls"] for spec in specs for f in spec}
    assert {"worker-crash", "worker-hang", "frame-drop"} <= classes
    workers, ports = [], []
    for spec in specs:
        w, p = _spawn_dcn_worker(
            ["--chaos-spec", _json.dumps(spec)]
        )
        workers.append(w)
        ports.append(p)
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p) for p in ports],
        catalog=tpch_single.catalog,
        shuffle_mode="always",
        shuffle_wait_timeout_s=15.0,
        retry_backoff_s=0.05,
        prober=FailedEngineProber(initial_backoff_s=60),
    )
    t0 = time.monotonic()
    try:
        for q in SHUFFLE_QUERIES:
            exp = tpch_single.must_query(q).rows
            _cols, got = sched.execute_plan(_plan(tpch_single, q))
            assert got == exp, (
                f"chaos parity broke (seed {SEED}):\n got={got}\n"
                f" exp={exp}"
            )
        # the crash CLASS really fired: the last worker died via
        # os._exit(3) and was quarantined; survivors carried parity
        workers[-1].wait(timeout=30)
        assert workers[-1].returncode == 3
        assert [e.port for e in sched.prober.failed_endpoints()] == (
            [ports[-1]]
        )
        # bounded recovery wall for the whole composed run
        assert time.monotonic() - t0 < 120.0
        # no leaked coordinator-side leases, no orphaned buffers on
        # the SURVIVING worker
        assert all(v == 0 for v in sched.pool_leased().values())
        c = EngineClient("127.0.0.1", ports[0], timeout_s=5.0)
        try:
            st = c.engine_status()
        finally:
            c.close()
        assert st["stages_buffered"] == 0
        assert not st["shuffle_threads"]
    finally:
        sched.close()
        for w in workers:
            w.kill()


#: the ISSUE 11 acceptance shape: join -> RE-KEYED GROUP BY (the group
#: key is not a join key, and the DISTINCT makes the aggregate
#: non-decomposable — the single-cut group-by re-scans the unsliced
#: orders side on every host) -> ORDER BY LIMIT (a range exchange with
#: per-partition top-K)
DAG_QUERY = (
    "select o_orderpriority, count(distinct l_suppkey), "
    "sum(l_extendedprice) from orders join lineitem "
    "on o_orderkey = l_orderkey group by o_orderpriority "
    "order by sum(l_extendedprice) desc limit 3"
)


def test_dcn_shuffle_dag_tpch_parity(tpch_single):
    """ISSUE 11 acceptance: the join -> re-keyed GROUP BY -> ORDER BY
    LIMIT query executes as >= 2 chained shuffle stages on the
    2-process dryrun with BOTH join sides fragment-sliced — per-host
    scanned base rows ~ total/N, vs the single-cut group-by baseline
    that re-scans the whole unsliced orders side on every host — the
    range exchange returns exact global order at row parity, the
    exchange bytes bypass the coordinator (staged-delta invariant),
    and the sampled boundaries are deterministic across runs."""
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.server.engine_rpc import EngineClient

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker()
    cat = tpch_single.catalog
    n_orders = cat.table("tpch", "orders").nrows
    n_lineitem = cat.table("tpch", "lineitem").nrows
    total = n_orders + n_lineitem
    exp = tpch_single.must_query(DAG_QUERY).rows
    plan = _plan(tpch_single, DAG_QUERY)

    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=cat, shuffle_mode="always", shuffle_dag="always",
    )
    try:
        # the planner really chained stages: hash join -> hash re-key
        # -> range order-by
        kind, cut = sched._choose_cut(plan)
        assert kind == "dag"
        assert [s.exchange for s in cut.stages] == [
            "hash", "hash", "range",
        ]
        staged0 = _counter_total("tidbtpu_dcn_bytes_staged")
        _cols, got = sched.execute_plan(plan, cut_hint=(kind, cut))
        # exact global order parity against local execution (the
        # order-preserving concat, not a coordinator re-sort)
        assert got == exp, f"\n got={got}\n exp={exp}"
        # exchange data rode worker-to-worker tunnels, NOT the
        # coordinator (the staged-delta invariant of PR 3, now held
        # across a 3-stage chain)
        assert _counter_total("tidbtpu_dcn_bytes_staged") == staged0
        stages = sched.last_query["shuffle_stages"]
        frags = sched.last_query["fragments"]
        assert [s["stage"] for s in stages] == [0, 1, 2]
        # BOTH join sides fragment-sliced: each host scanned ~ total/2
        # base rows in stage 0 and NOTHING after (stages 1-2 re-stage
        # held outputs)
        for f in [f for f in frags if f["stage"] == 0]:
            assert abs(f["scan_rows"] - total / 2) <= 2, f
        assert all(
            f["scan_rows"] == 0 for f in frags if f["stage"] > 0
        )
        # per-partition top-K: the range stage shipped at most K rows
        # per partition
        for f in [f for f in frags if f["stage"] == 2]:
            assert f["rows"] <= 3
        # boundary-sampling determinism: a second run cuts the SAME
        # boundaries (fixed sample seed)
        b1 = stages[2]["boundaries"]
        sched.execute_plan(plan, cut_hint=(kind, cut))
        b2 = sched.last_query["shuffle_stages"][2]["boundaries"]
        assert b1 == b2 and b1  # non-trivial and identical
        # no held stage outputs or buffered stages linger on workers
        for port in (p1, p2):
            c = EngineClient("127.0.0.1", port, timeout_s=10.0)
            try:
                st = c.engine_status()
            finally:
                c.close()
            assert st["stages_buffered"] == 0
            assert st["held_outputs"] == 0
    finally:
        sched.close()

    # the single-cut BASELINE (shuffle_dag="never"): the DISTINCT
    # group-by cut slices only lineitem — every host re-scans the
    # whole orders side (the N x wasted scan work the DAG removes)
    sched2 = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=cat, shuffle_mode="always", shuffle_dag="never",
    )
    try:
        kind2, cut2 = sched2._choose_cut(plan)
        assert kind2 == "shuffle" and cut2.kind == "groupby"
        _cols, got2 = sched2.execute_plan(plan, cut_hint=(kind2, cut2))
        assert got2 == exp
        for f in sched2.last_query["fragments"]:
            # per-host scan = its lineitem slice + ALL of orders
            assert abs(
                f["scan_rows"] - (n_lineitem / 2 + n_orders)
            ) <= 2, f
    finally:
        sched2.close()
        for w in (w1, w2):
            w.kill()


def test_dcn_multihost_chaos_interstage_kill(tpch_single):
    """ISSUE 11 chaos acceptance: a composed-fault episode killing a
    worker BETWEEN stage N and stage N+1 of the DAG (os._exit the
    first time it reads a held StageInput, while every worker also
    drops pushed frames probabilistically). The coordinator must
    quarantine the dead worker, restart the WHOLE chain on the
    survivor under a new attempt (the superseded attempt's held
    partitions are fenced by the attempt key), and still return exact
    parity — with no leaked held outputs, buffers, threads, or
    leases."""
    import json as _json
    import time

    from tidb_tpu.chaos.schedule import generate_interstage_kill_specs
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.server.engine_pool import FailedEngineProber
    from tidb_tpu.server.engine_rpc import EngineClient

    SEED = 2718
    specs = generate_interstage_kill_specs(SEED, 2)
    assert specs == generate_interstage_kill_specs(SEED, 2)
    assert specs[-1][-1]["site"] == "shuffle/stage-input"
    assert specs[-1][-1]["kind"] == "exit"
    workers, ports = [], []
    for spec in specs:
        w, p = _spawn_dcn_worker(["--chaos-spec", _json.dumps(spec)])
        workers.append(w)
        ports.append(p)
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p) for p in ports],
        catalog=tpch_single.catalog,
        shuffle_mode="always", shuffle_dag="always",
        shuffle_wait_timeout_s=15.0,
        retry_backoff_s=0.05,
        prober=FailedEngineProber(initial_backoff_s=60),
    )
    t0 = time.monotonic()
    try:
        exp = tpch_single.must_query(DAG_QUERY).rows
        _cols, got = sched.execute_plan(_plan(tpch_single, DAG_QUERY))
        assert got == exp, (
            f"interstage-kill parity broke (seed {SEED}):\n"
            f" got={got}\n exp={exp}"
        )
        # the kill really happened BETWEEN stages: worker 2 died via
        # os._exit(3) on the stage-input site and was quarantined
        workers[-1].wait(timeout=30)
        assert workers[-1].returncode == 3
        assert [e.port for e in sched.prober.failed_endpoints()] == (
            [ports[-1]]
        )
        # the chain retried on the survivor set
        assert any(
            s["attempts"] >= 2
            for s in sched.last_query["shuffle_stages"]
        )
        assert time.monotonic() - t0 < 120.0
        # invariant audit on the survivor: nothing leaked
        assert all(v == 0 for v in sched.pool_leased().values())
        c = EngineClient("127.0.0.1", ports[0], timeout_s=5.0)
        try:
            st = c.engine_status()
        finally:
            c.close()
        assert st["stages_buffered"] == 0
        assert st["held_outputs"] == 0
        assert not st["shuffle_threads"]
    finally:
        sched.close()
        for w in workers:
            w.kill()


def test_dcn_delta_writes_mid_run_freshness_modes(tpch_single):
    """HTAP delta tier on the REAL 2-process x 4-device dryrun
    (workers are delta replicas): coordinator writes land mid-run —
    INSERT/DELETE on a loaded table plus a table the workers never
    loaded — and routed SELECTs honor both freshness modes with zero
    local fallbacks and exact parity against a full local reload."""
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.session import Session

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker()
    sess = tpch_single
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=sess.catalog,
    )
    sess.attach_dcn_scheduler(sched)
    fb0 = _counter_total("tidbtpu_session_dcn_route_fallbacks")
    q_orders = (
        "select o_orderstatus, count(*), sum(o_shippriority) "
        "from orders group by o_orderstatus order by o_orderstatus"
    )
    q_hot = "select count(*), sum(v) from hot_writes"
    try:
        base = sess.must_query(q_orders).rows
        assert sess._last_dcn_routed

        # writes land mid-run: a loaded table takes typed deltas, a
        # NEW table materializes on the replicas from the sync frames
        sess.execute(
            "insert into orders values "
            "(4000001, 1, 'O', 123.45, '1995-01-01', '1-URGENT', 7, 'dx'),"
            "(4000002, 2, 'F', 456.78, '1996-02-02', '2-HIGH', 7, 'dx')"
        )
        sess.execute("delete from orders where o_orderkey = 4000002")
        sess.execute(
            "create table hot_writes (k bigint primary key, v bigint)"
        )
        sess.execute("insert into hot_writes values (1, 10), (2, 20)")

        # read-your-writes: every committed write visible, routed
        fresh = Session(sess.catalog, db="tpch")
        for q in (q_orders, q_hot):
            got = sess.execute(q)
            assert got.rows == fresh.execute(q).rows, q
            assert sess._last_dcn_routed, q
        assert got.rows == [(2, 30)]  # q_hot: exact committed image

        # bounded staleness: still routed, zero waits — and because
        # the read-your-writes reads above already shipped the log,
        # the acked floor covers every write
        sess.execute("set tidb_tpu_read_freshness = 'bounded'")
        w0 = _counter_total("tidbtpu_delta_ryw_wait_seconds")
        for q in (q_orders, q_hot):
            got = sess.execute(q)
            assert got.rows == fresh.execute(q).rows, q
            assert sess._last_dcn_routed, q
        assert _counter_total("tidbtpu_delta_ryw_wait_seconds") == w0

        # bounded lags behind an unshipped write (staleness is real,
        # not a fresh read in disguise)...
        sess.execute("insert into hot_writes values (3, 30)")
        assert sess.execute(q_hot).rows == [(2, 30)]
        assert sess._last_dcn_routed
        # ...until read-your-writes ships + waits
        sess.execute("set tidb_tpu_read_freshness = 'read_your_writes'")
        assert sess.execute(q_hot).rows == [(3, 60)]
        assert sess._last_dcn_routed

        # a compaction barrier folds the deltas into BOTH worker
        # processes' base blocks; parity holds after
        assert sched.delta.compact_now(catalog=sess.catalog)
        post = sess.execute(q_orders)
        assert post.rows == fresh.execute(q_orders).rows
        assert sess._last_dcn_routed
        assert post.rows != base  # the writes are visible in the fold
        assert sess.execute(q_hot).rows == [(3, 60)]

        # ZERO local fallbacks across the whole scenario
        assert _counter_total(
            "tidbtpu_session_dcn_route_fallbacks"
        ) == fb0
    finally:
        sess.attach_dcn_scheduler(None)
        sched.close()
        for w in (w1, w2):
            w.kill()

def test_dcn_topsql_fleet_attribution(tpch_single):
    """PR 14 acceptance: with ``tidb_enable_top_sql = ON`` the
    2-process x 4-device dryrun attributes sampled CPU per statement
    digest on EVERY host — workers arm their samplers from the
    dispatch-carried config, attribute task samples to the dispatched
    digest (so a finished/foreign qid can never be charged), and ship
    windows + collapsed stacks piggybacked on the fenced replies.
    information_schema.top_sql then shows per-instance rows for both
    workers, the tsdb series carry clock-rebased worker windows, and
    the merged /profile export is non-empty."""
    import time as _time

    from tidb_tpu.obs.profiler import OTHERS_DIGEST, TOPSQL, digest_of
    from tidb_tpu.obs.tsdb import TSDB
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.utils.metrics import sql_digest

    w1, p1 = _spawn_dcn_worker()
    w2, p2 = _spawn_dcn_worker()
    sess = tpch_single
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p1), ("127.0.0.1", p2)],
        catalog=sess.catalog,
        shuffle_mode="always",
    )
    sess.attach_dcn_scheduler(sched)
    TOPSQL.store.reset()
    t_run0 = _time.time()
    worker_addrs = {f"127.0.0.1:{p1}", f"127.0.0.1:{p2}"}
    try:
        sess.execute("set global tidb_enable_top_sql = ON")
        assert TOPSQL.running()
        q = SHUFFLE_QUERIES[0]
        exp = sess.must_query(q).rows
        # several rounds so worker samplers (armed by the FIRST
        # dispatch's config) accumulate samples on later tasks
        for _ in range(4):
            got = sess.execute(q)
            assert [tuple(r) for r in got.rows] == exp
        # the heartbeat idle-flush ships anything still pending
        sched.heartbeat.beat_once()

        rows = sess.execute(
            "select rank, instance, digest, cpu_ms, device_ms, "
            "stall_ms, samples from information_schema.top_sql "
            "order by rank, instance"
        ).rows
        assert rows
        hosts = {r[1] for r in rows}
        assert worker_addrs <= hosts, (
            f"top_sql missing a worker instance: {hosts}"
        )
        assert "coordinator" in hosts
        # every worker row carries real sampled attribution
        for r in rows:
            if r[1] in worker_addrs:
                assert r[6] > 0  # samples
                assert r[3] + r[4] + r[5] > 0  # cpu+device+stall ms

        # zero attribution to finished/foreign qids: workers learn
        # digests ONLY from dispatches, so every worker-side digest
        # must be one this coordinator actually ran (or the fold-in
        # aggregate) — a foreign coordinator's digest cannot appear
        ran = {
            digest_of(sql_digest(stmt))
            for stmt in (q, "set global tidb_enable_top_sql = ON")
        }
        for r in TOPSQL.store.rows():
            if r["instance"] in worker_addrs:
                assert r["digest"] in ran | {OTHERS_DIGEST}, (
                    f"foreign digest {r['digest']} attributed on "
                    f"{r['instance']}"
                )

        # worker windows reached the tsdb CLOCK-REBASED: every stored
        # point of the topsql families sits inside the run's
        # coordinator-clock window (a skewed/unrebased worker stamp
        # would land outside)
        pts = [
            (t, host)
            for t, host, _lv, _v, _res in TSDB.query(
                "tidbtpu_topsql_cpu_seconds"
            )
            if host in worker_addrs
        ]
        assert pts, "no worker topsql series reached the tsdb"
        now = _time.time()
        for t, host in pts:
            assert t_run0 - 30 <= t <= now + 30, (
                f"unrebased worker window ts {t} from {host}"
            )

        # the /profile export half: fleet-merged collapsed stacks are
        # non-empty and include worker-shipped towers
        merged = TOPSQL.store.collapsed()
        assert merged
        for addr in worker_addrs:
            assert TOPSQL.store.collapsed(instance=addr), (
                f"no collapsed stacks shipped from {addr}"
            )
        for line in merged:
            stack, count = line.rsplit(" ", 1)
            assert int(count) >= 1 and ";" in stack
    finally:
        sess.execute("set global tidb_enable_top_sql = OFF")
        sess.attach_dcn_scheduler(None)
        TOPSQL.store.reset()
        sched.close()
        for w in (w1, w2):
            w.kill()


def test_dcn_aqe_replan_crash_retry_parity(tpch_single):
    """ISSUE 15 chaos acceptance (replan-crash): worker 2 hard-exits
    (os._exit) the first time an ADAPTIVE stage task reaches it — the
    window between the coordinator's re-plan decision (a probe-observed
    collapsed join side switching repartition to broadcast) and the
    switched stage's completion — while both workers also drop a
    seeded fraction of pushed frames. The coordinator must quarantine
    the dead worker and retry the WHOLE stage, probe round included,
    on the survivor set (m=1: the probe gate stands down, the stage
    runs plain) with exact row parity and the adaptive decision
    counted from the first attempt."""
    import json as _json

    from tidb_tpu.chaos.schedule import generate_replan_kill_specs
    from tidb_tpu.parallel import aqe
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.server.engine_pool import FailedEngineProber

    SEED = 1501
    specs = generate_replan_kill_specs(SEED, 2)
    assert specs == generate_replan_kill_specs(SEED, 2)  # replayable
    assert any(
        f["site"] == "aqe/switched-stage" and f["kind"] == "exit"
        for f in specs[-1]
    )
    workers, ports = [], []
    for spec in specs:
        w, p = _spawn_dcn_worker(["--chaos-spec", _json.dumps(spec)])
        workers.append(w)
        ports.append(p)
    # static est (orders at full table size) says repartition; the
    # o_custkey filter collapses the observed side under the bar, so
    # the probe's broadcast-switch decision targets worker 2 with an
    # adaptive stage task — its armed exit fires exactly there
    orders_rows = tpch_single.catalog.table("tpch", "orders").nrows
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p) for p in ports],
        catalog=tpch_single.catalog,
        shuffle_mode="always",
        shuffle_dag="never",
        shuffle_skew_ratio=1.5,
        shuffle_broadcast_rows=max(orders_rows // 4, 64),
        # the killed worker dies BEFORE producing, so the survivor
        # detects the loss only by wait expiry (the serve-load 10s
        # loopback stance) — the healthy retry is m=1 and never waits
        shuffle_wait_timeout_s=10.0,
        prober=FailedEngineProber(initial_backoff_s=60),
    )
    try:
        q = (
            "select count(*), sum(l_quantity) from lineitem "
            "join orders on l_orderkey = o_orderkey "
            "where o_custkey < 5"
        )
        exp = tpch_single.must_query(q).rows
        before = aqe.decision_counts().get("broadcast-switch", 0.0)
        _cols, got = sched.execute_plan(_plan(tpch_single, q))
        assert got == exp, f"\n got={got}\n exp={exp}"
        st = sched.last_query["shuffle"]
        # the whole stage retried on the survivor set after the kill
        assert st["attempts"] >= 2
        assert st["m"] == 1
        # the decision genuinely fired before the crash
        assert aqe.decision_counts().get(
            "broadcast-switch", 0.0
        ) >= before + 1
        # ...but the m=1 retry ran the PLAIN cut: the superseded
        # attempt's token must not linger on the reported summary
        # (adaptive= has to agree with what the survivor actually
        # ran; the counter above is the record that it fired)
        assert not st.get("adaptive")
        assert [e.port for e in sched.prober.failed_endpoints()] == [
            ports[-1]
        ]
        workers[-1].wait(timeout=30)
        assert workers[-1].returncode == 3
        # the survivor keeps serving adaptive-eligible queries alone
        _cols, got2 = sched.execute_plan(_plan(tpch_single, q))
        assert got2 == exp
    finally:
        sched.close()
        for w in workers:
            w.kill()


def test_dcn_runtime_filter_crash_retry_parity(tpch_single):
    """ISSUE 19 chaos acceptance (filter-crash): worker 2 hard-exits
    (os._exit) the first time the broadcast runtime filter reaches its
    produce path — the window between the coordinator's probe-round
    merge + broadcast and the filtered stage's completion — while both
    workers also drop a seeded fraction of pushed frames. The
    coordinator must quarantine the dead worker and retry the whole
    stage on the survivor set (m=1: the filter stands down, the stage
    ships unfiltered) with exact row parity and no stale rf= on the
    reported summary."""
    import json as _json

    from tidb_tpu.chaos.schedule import generate_filter_kill_specs
    from tidb_tpu.parallel import aqe
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.server.engine_pool import FailedEngineProber

    SEED = 1901
    specs = generate_filter_kill_specs(SEED, 2)
    assert specs == generate_filter_kill_specs(SEED, 2)  # replayable
    assert any(
        f["site"] == "shuffle/filter" and f["kind"] == "exit"
        for f in specs[-1]
    )
    workers, ports = [], []
    for spec in specs:
        w, p = _spawn_dcn_worker(["--chaos-spec", _json.dumps(spec)])
        workers.append(w)
        ports.append(p)
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p) for p in ports],
        catalog=tpch_single.catalog,
        shuffle_mode="always",
        shuffle_dag="never",
        runtime_filter="always",
        # the killed worker dies mid-produce, so the survivor detects
        # the loss only by wait expiry; the healthy retry never waits
        shuffle_wait_timeout_s=10.0,
        prober=FailedEngineProber(initial_backoff_s=60),
    )
    try:
        q = (
            "select count(*), sum(l_quantity) from lineitem "
            "join orders on l_orderkey = o_orderkey "
            "where o_custkey < 5"
        )
        exp = tpch_single.must_query(q).rows
        before = aqe.decision_counts().get("runtime-filter", 0.0)
        _cols, got = sched.execute_plan(_plan(tpch_single, q))
        assert got == exp, f"\n got={got}\n exp={exp}"
        st = sched.last_query["shuffle"]
        # the whole stage retried on the survivor set after the kill
        assert st["attempts"] >= 2
        assert st["m"] == 1
        # the decision genuinely fired before the crash...
        assert aqe.decision_counts().get(
            "runtime-filter", 0.0
        ) >= before + 1
        # ...but the m=1 retry stood the filter down: the superseded
        # attempt's rf must not linger on the reported summary
        assert "rf" not in st
        assert [e.port for e in sched.prober.failed_endpoints()] == [
            ports[-1]
        ]
        workers[-1].wait(timeout=30)
        assert workers[-1].returncode == 3
        # the survivor keeps serving filter-eligible queries alone
        _cols, got2 = sched.execute_plan(_plan(tpch_single, q))
        assert got2 == exp
    finally:
        sched.close()
        for w in workers:
            w.kill()
