"""Chaos fleet tests: deterministic fault schedules, composed-fault
episodes over the in-process 2-server fleet, fleet-wide cancellation,
abort-path resource cleanup, quarantine-rejoin visibility, and the
failpoint-coverage sweep.

Reference: the prober/quarantine/cancel loop (mpp_probe.go, MPPTask
cancellation) exercised under COMPOSED faults instead of one
hand-armed failpoint at a time (ISSUE 10)."""

import subprocess
import sys
import threading
import time

import pytest

from tidb_tpu.utils import failpoint, racecheck


@pytest.fixture()
def racecheck_on():
    racecheck.enable()
    racecheck.reset()
    try:
        yield
    finally:
        racecheck.disable()
        racecheck.reset()


# ---------------------------------------------------------------------------
# schedules: pure functions of the seed
# ---------------------------------------------------------------------------


class TestSchedule:
    def test_same_seed_identical_schedule(self):
        from tidb_tpu.chaos import ChaosSchedule

        a = ChaosSchedule.generate(42, 12, 4)
        b = ChaosSchedule.generate(42, 12, 4)
        assert a == b  # dataclass equality: byte-identical replay
        assert a != ChaosSchedule.generate(43, 12, 4)
        # composed: some episode carries more than one fault
        assert any(len(ep.faults) > 1 for ep in a.episodes)

    def test_worker_specs_deterministic_and_composed(self):
        from tidb_tpu.chaos.schedule import generate_worker_specs

        a = generate_worker_specs(7, 2)
        assert a == generate_worker_specs(7, 2)
        classes = {f["cls"] for spec in a for f in spec}
        # the acceptance triple: crash + hang + frame loss composed
        assert {"worker-crash", "worker-hang", "frame-drop"} <= classes

    def test_undeclared_class_rejected(self):
        from tidb_tpu.chaos import ChaosSchedule

        with pytest.raises(ValueError, match="undeclared fault class"):
            ChaosSchedule.generate(1, 1, 1, classes=["nope"])

    def test_faults_roundtrip_json(self):
        import json

        from tidb_tpu.chaos import ChaosSchedule
        from tidb_tpu.chaos.schedule import Fault

        sched = ChaosSchedule.generate(5, 6, 4)
        for ep in sched.episodes:
            for f in ep.faults:
                assert Fault.from_dict(
                    json.loads(json.dumps(f.to_dict()))
                ) == f


class TestSeededActions:
    def test_seeded_fire_pattern_replays(self):
        # test-local site: declared at runtime, named via a variable
        # (a literal enable() of a non-SITES name fails the
        # check_failpoints lint by design)
        site = "chaostest/seeded"
        failpoint.declare(site)

        def pattern():
            hits = []
            failpoint.enable(
                site, failpoint.seeded(99, 0.3, lambda: hits.append(1))
            )
            try:
                out = []
                for _ in range(50):
                    n0 = len(hits)
                    failpoint.inject(site)
                    out.append(len(hits) > n0)
                return out
            finally:
                failpoint.disable(site)

        a, b = pattern(), pattern()
        assert a == b  # the same seed draws the same sequence
        assert any(a) and not all(a)

    def test_times_window_heals(self):
        site = "chaostest/window"
        failpoint.declare(site)
        failpoint.enable(
            site, failpoint.times(3, ConnectionError("chaos"))
        )
        try:
            fired = 0
            for _ in range(6):
                try:
                    failpoint.inject(site)
                except ConnectionError:
                    fired += 1
            assert fired == 3  # the window ends: the fault heals
        finally:
            failpoint.disable(site)


# ---------------------------------------------------------------------------
# the in-process fleet: composed episodes + cancellation + cleanup
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet():
    """One in-process 2-server fleet shared by the module's episode,
    cancellation, and cleanup tests (compiles amortize)."""
    from tidb_tpu.chaos import ChaosHarness

    h = ChaosHarness(seed=3, wait_timeout_s=2.0, max_wall_s=45.0)
    try:
        yield h
    finally:
        h.close()


def test_chaos_episodes_all_invariants_hold(fleet):
    """Seeded composed-fault episodes (crash + hang + frame loss and
    friends) against the live fleet: every episode must end with exact
    row parity, drained admission budget, zero buffered shuffle
    stages, zero leased connections, and no leaked threads."""
    report = fleet.run(4)
    assert report.episodes == 4
    assert report.violations == [], report.violations
    assert sum(report.faults.values()) >= 4
    assert report.to_dict()["recovery_wall_p95_s"] < 45.0


def test_worker_hang_recovers_via_stage_retry(fleet):
    """A hung producer (hang > wait timeout) forces the suspect/verify
    path: the peer times out, the suspect pings ALIVE (no quarantine),
    and the stage retries to parity — with the retry visible at the
    shuffle/stage-retry site and the jittered backoff counter."""
    from tidb_tpu.chaos.schedule import Fault, arm_spec, disarm
    from tidb_tpu.utils.metrics import REGISTRY

    retries = []
    failpoint.enable("shuffle/stage-retry", lambda: retries.append(1))
    backoff0 = sum(
        v for n, _k, v in REGISTRY.rows()
        if n.startswith("tidbtpu_dcn_retry_backoff_seconds")
    )
    armed = arm_spec([
        Fault("worker-hang", "shuffle/produce", "hang", n=1, param=3.0),
    ])
    try:
        _cols, got = fleet.sched.execute_plan(fleet.plans[0])
        assert got == fleet.expected[0]
    finally:
        disarm(armed)
        failpoint.disable("shuffle/stage-retry")
    assert retries, "hang never forced a stage retry"
    backoff1 = sum(
        v for n, _k, v in REGISTRY.rows()
        if n.startswith("tidbtpu_dcn_retry_backoff_seconds")
    )
    assert backoff1 > backoff0, "retry skipped the jittered backoff"
    assert fleet.check_invariants("hang-retry") == []


def test_kill_cancels_worker_side_work(fleet):
    """KILL while a shuffle task hangs: the coordinator broadcasts
    cancel_query (the dcn/cancel site), worker task threads exit,
    staged buffers are freed, pooled connections drain — and the
    fleet serves the next query at parity."""
    from tidb_tpu.chaos.schedule import Fault, arm_spec, disarm
    from tidb_tpu.utils.sqlkiller import QueryKilled, SQLKiller

    cancels = []
    failpoint.enable("dcn/cancel", lambda: cancels.append(1))
    killer = SQLKiller()
    armed = arm_spec([
        Fault("worker-hang", "shuffle/produce", "hang", n=1,
              param=30.0),
    ])
    threading.Timer(0.8, killer.kill).start()
    t0 = time.monotonic()
    try:
        with pytest.raises(QueryKilled):
            fleet.sched.execute_plan(
                fleet.plans[0], kill_check=killer.check
            )
    finally:
        disarm(armed)
        failpoint.disable("dcn/cancel")
    # the kill aborted a 30s hang promptly (not at a timeout)
    assert time.monotonic() - t0 < 10.0
    assert cancels, "no cancel_query broadcast"
    assert fleet.check_invariants("kill") == []
    _cols, got = fleet.sched.execute_plan(fleet.plans[0])
    assert got == fleet.expected[0]


def test_deadline_propagates_to_workers(fleet):
    """max_execution_time shape: the dispatch carries REMAINING
    seconds, so the worker self-cancels its hung task even though the
    coordinator also watches — either side's trigger ends the query
    as a kill, never an engine error or quarantine."""
    from tidb_tpu.chaos.schedule import Fault, arm_spec, disarm
    from tidb_tpu.utils.sqlkiller import QueryKilled, SQLKiller

    killer = SQLKiller()
    killer.deadline = time.monotonic() + 1.0
    armed = arm_spec([
        Fault("worker-hang", "shuffle/produce", "hang", n=1,
              param=30.0),
    ])
    t0 = time.monotonic()
    try:
        with pytest.raises(QueryKilled):
            fleet.sched.execute_plan(
                fleet.plans[2], kill_check=killer.check,
                deadline=killer.deadline,
            )
    finally:
        disarm(armed)
    assert time.monotonic() - t0 < 10.0
    assert fleet.check_invariants("deadline") == []
    assert len(fleet.sched.alive_endpoints()) == 2  # nobody blamed


def test_abort_path_cleanup_under_racecheck(fleet, racecheck_on):
    """ISSUE 10 satellite: after a cancelled stage, the ShuffleStore
    holds ZERO buffered stages, the endpoint pools' leased counts are
     0, and no shuffle-* task/shipper/tunnel thread outlives the query
    — with every swept lock order-tracked (racecheck on)."""
    from tidb_tpu.chaos.schedule import Fault, arm_spec, disarm
    from tidb_tpu.utils.sqlkiller import QueryKilled, SQLKiller

    killer = SQLKiller()
    armed = arm_spec([
        Fault("worker-hang", "shuffle/produce", "hang", n=1,
              param=30.0),
    ])
    threading.Timer(0.6, killer.kill).start()
    try:
        with pytest.raises(QueryKilled):
            fleet.sched.execute_plan(
                fleet.plans[0], kill_check=killer.check
            )
    finally:
        disarm(armed)
    # explicit, named asserts (the satellite's list), not just the
    # bundled invariant audit
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        stages = [
            s._shuffle.store.buffered_stages()
            for s in fleet.servers if s._shuffle is not None
        ]
        leased = fleet.sched.pool_leased()
        threads = [
            t.name for t in threading.enumerate()
            if t.is_alive() and t.name.startswith(
                ("shuffle-q", "shuffle-ship", "shuffle-tx")
            )
        ]
        if (
            all(v == 0 for v in stages)
            and all(v == 0 for v in leased.values())
            and not threads
        ):
            break
        time.sleep(0.02)
    assert all(v == 0 for v in stages), f"buffered stages leak: {stages}"
    assert all(v == 0 for v in leased.values()), f"leases leak: {leased}"
    assert not threads, f"threads outlived the query: {threads}"
    # per-query lock instances (ledger, tunnels) were constructed
    # AFTER enable() and so ran order-tracked through the abort (the
    # module fixture's store cv predates enable() — the full-suite
    # tracking of that class lives in tests/test_race.py)
    seen = racecheck.seen_classes()
    assert {"dcn.ledger", "shuffle.tunnel"} <= seen, seen


# ---------------------------------------------------------------------------
# quarantine-rejoin visibility (ISSUE 10 satellite)
# ---------------------------------------------------------------------------


def test_readmission_counted_and_rejoined_host_used():
    """A killed-then-restarted worker must be USED again: quarantine
    was already counted; now the prober's re-admission lands
    tidbtpu_dcn_readmissions_total{host}, a timeline admission event,
    and a later stage really dispatches to the recovered host."""
    from tidb_tpu.obs.timeline import TIMELINE
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.parser.sqlparse import parse
    from tidb_tpu.planner.logical import build_query
    from tidb_tpu.server.engine_pool import FailedEngineProber
    from tidb_tpu.server.engine_rpc import EngineServer
    from tidb_tpu.session.session import Session
    from tidb_tpu.utils.metrics import REGISTRY

    def reg_total(prefix):
        return sum(
            v for n, _k, v in REGISTRY.rows() if n.startswith(prefix)
        )

    sess = Session()
    sess.execute("create table t (a int, b varchar(8))")
    sess.execute(
        "insert into t values (1,'x'),(2,'y'),(3,'x'),(2,'x'),(7,'y')"
    )
    q = "select b, count(*) from t group by b order by b"
    exp = sess.must_query(q).rows
    plan = build_query(
        parse(q)[0], sess.catalog, "test", sess._scalar_subquery
    )
    servers = [EngineServer(sess.catalog, port=0) for _ in range(2)]
    for s in servers:
        s.start_background()
    ports = [s.port for s in servers]
    sched = DCNFragmentScheduler(
        [("127.0.0.1", p) for p in ports],
        catalog=sess.catalog,
        prober=FailedEngineProber(initial_backoff_s=0.05),
    )
    TIMELINE.start()
    try:
        assert sched.execute_plan(plan)[1] == exp
        # kill worker 1 for real (its port is freed). In-process,
        # shutdown() stops the LISTENER but not already-established
        # handler threads — drop the pooled idle connections so the
        # next dispatch must redial the dead port (a real crash kills
        # both at once), then route: the dial failure quarantines it
        servers[1].shutdown()
        sched._pool(sched.endpoints[1]).close_idle()
        assert sched.execute_plan(plan)[1] == exp
        dead = [ep for ep in sched.endpoints if not ep.alive]
        assert [ep.port for ep in dead] == [ports[1]]
        readmits0 = reg_total("tidbtpu_dcn_readmissions_total")
        # restart a worker on the SAME port and give the prober its
        # recovery shot (backoff 50ms)
        servers[1] = EngineServer(
            sess.catalog, port=ports[1]
        )
        servers[1].start_background()
        time.sleep(0.1)
        recovered = sched.prober.probe_once()
        assert [ep.port for ep in recovered] == [ports[1]]
        assert reg_total("tidbtpu_dcn_readmissions_total") == readmits0 + 1
        # the readmit landed on the timeline's admission track
        assert any(
            cat == "admission" and name.startswith("readmit")
            for _ph, cat, name, *_rest in TIMELINE.events()
        )
        # ... and the recovered host is actually USED by a later stage
        host = f"127.0.0.1:{ports[1]}"
        d0 = REGISTRY.counter(
            "tidbtpu_dcn_dispatches", "fragment dispatches",
            labels=("host",),
        ).labels(host=host).value
        assert sched.execute_plan(plan)[1] == exp
        d1 = REGISTRY.counter(
            "tidbtpu_dcn_dispatches", "fragment dispatches",
            labels=("host",),
        ).labels(host=host).value
        assert d1 > d0, "recovered host never dispatched to again"
    finally:
        TIMELINE.stop()
        TIMELINE.clear()
        sched.close()
        for s in servers:
            try:
                s.shutdown()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# sysvar knobs (ISSUE 10 satellite)
# ---------------------------------------------------------------------------


def test_dcn_sysvars_construct_and_live_retune():
    """tidb_tpu_shuffle_wait_timeout_s / heartbeat interval / miss
    threshold: the scheduler ctor resolves unset args from the
    catalog's sysvars, and a live SET on a session with an attached
    scheduler re-tunes the running instance (the PR 9 admission-knob
    pattern)."""
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.server.engine_rpc import EngineServer
    from tidb_tpu.session.session import Session

    sess = Session()
    sess.execute("set global tidb_tpu_shuffle_wait_timeout_s = 33")
    sess.execute("set global tidb_tpu_heartbeat_miss_threshold = 5")
    srv = EngineServer(sess.catalog, port=0)
    srv.start_background()
    sched = DCNFragmentScheduler(
        [("127.0.0.1", srv.port)], catalog=sess.catalog
    )
    try:
        assert sched.shuffle_wait_timeout_s == 33.0
        assert sched.heartbeat.miss_threshold == 5
        sess.attach_dcn_scheduler(sched)
        # a SESSION-scoped SET must not silently half-apply: the knobs
        # are declared GLOBAL-only (the scheduler is shared by every
        # attached session), so it errors loudly
        with pytest.raises(Exception, match="global"):
            sess.execute("set tidb_tpu_shuffle_wait_timeout_s = 7")
        assert sched.shuffle_wait_timeout_s == 33.0
        sess.execute("set global tidb_tpu_shuffle_wait_timeout_s = 7")
        sess.execute("set global tidb_tpu_heartbeat_miss_threshold = 3")
        assert sched.shuffle_wait_timeout_s == 7.0
        assert sched.heartbeat.miss_threshold == 3
        # interval retune spins the beat thread up and down (an
        # unchanged interval is a no-op, not a restart)
        sess.execute("set global tidb_tpu_heartbeat_interval_s = 0.05")
        t = sched.heartbeat._thread
        assert t is not None
        sess.execute("set global tidb_tpu_heartbeat_miss_threshold = 4")
        assert sched.heartbeat._thread is t  # not restarted
        sess.execute("set global tidb_tpu_heartbeat_interval_s = 0")
        assert sched.heartbeat._thread is None
    finally:
        sess.attach_dcn_scheduler(None)
        sched.close()
        srv.shutdown()


# ---------------------------------------------------------------------------
# the failpoint-coverage sweep + lint (ISSUE 10 satellite)
# ---------------------------------------------------------------------------


def test_failpoint_site_sweep(tmp_path):
    """Every swept site FIRES under its declared workload — the
    runtime half of check_failpoint_coverage.py (a site whose
    workload stops traversing it fails here, not in a stale
    comment)."""
    from tidb_tpu.chaos.sweep import run_sweep, sweep_sites
    from tidb_tpu.session.session import Session

    assert len(set(sweep_sites())) == len(sweep_sites()) > 40
    sess = Session()
    counts = run_sweep(sess, str(tmp_path))
    dead = sorted(s for s, c in counts.items() if c == 0)
    assert not dead, f"swept sites never fired: {dead}"


def test_failpoint_coverage_lint(tmp_path):
    """HEAD is clean; a fixture tree with an unreferenced site
    fails."""
    import os
    import shutil

    sys.path.insert(0, "scripts")
    import check_failpoint_coverage as lint

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert lint.check(repo) == []

    # fixture: one declared site, no tests/, no chaos/ references
    fx = tmp_path / "fx"
    (fx / "tidb_tpu" / "utils").mkdir(parents=True)
    (fx / "tests").mkdir()
    shutil.copy(
        os.path.join(repo, "tidb_tpu", "utils", "racecheck.py"),
        fx / "tidb_tpu" / "utils" / "racecheck.py",
    )
    (fx / "tidb_tpu" / "utils" / "failpoint.py").write_text(
        "SITES = frozenset({'lonely/site'})\n"
    )
    bad = lint.check(str(fx))
    assert len(bad) == 1 and "lonely/site" in bad[0][2]


def test_chaos_spec_arms_worker_process(tmp_path):
    """dcn_worker --chaos-spec arms the schedule's faults in a real
    worker process (the multihost chaos dryrun's mechanism): a worker
    armed with an exit fault on its handshake... is overkill here —
    instead prove the spec path end to end with a benign clock-skew
    fault and read the skew back through the handshake."""
    import json
    import os
    import re

    from tidb_tpu.chaos.schedule import Fault
    from tidb_tpu.server.engine_rpc import EngineClient

    spec = json.dumps([
        Fault("clock-skew", "engine/clock-skew", "value",
              param=120.0).to_dict()
    ])
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    p = subprocess.Popen(
        [sys.executable, "-m", "tidb_tpu.parallel.dcn_worker",
         "--cpu", "--port", "0", "--chaos-spec", spec],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    try:
        line = p.stdout.readline()
        m = re.match(r"DCN_WORKER_READY port=(\d+)", line)
        assert m, line
        c = EngineClient("127.0.0.1", int(m.group(1)))
        try:
            # the armed skew shifts the advertised clock ~120s
            assert c.clock_offset_s is not None
            assert 110.0 < c.clock_offset_s < 130.0
        finally:
            c.close()
    finally:
        p.kill()
