"""Many-connection MySQL-protocol load driver for the serving tier.

The acceptance harness for PR 8 (``bench.py --serve-load``): N
concurrent MySQL-protocol sessions drive a mixed short/scan workload
through one coordinator Server whose sessions route fragmentable
SELECTs across a 2-process worker fleet (parallel/dcn.py), gated by the
admission controller (parallel/serving.py). It measures and asserts the
serving-tier claims end to end:

- **exact per-query row parity** — every statement's result is checked
  against a locally-computed reference (text-protocol rendering and
  all);
- **fragments genuinely overlap on the fleet** — measured from the
  flight-recorder timelines (obs/flight.py): the maximum number of
  DCN-routed flights from DISTINCT connections whose [start, end]
  windows intersect must be >= 2 (PR 1-7 serialized per host, so this
  could never exceed 1 dispatch per host at a time);
- **cross-session compiled-plan reuse** — the shared plan cache's
  cross-session hit counter must move (coordinator final stages and the
  workers' per-connection executors both share compiles now);
- **p50/p99 latency + fleet queries/sec** per workload class
  (interactive statements carry HIGH_PRIORITY, scans LOW_PRIORITY, so
  the admission queue orders them);
- **kill-a-worker-under-load** — one worker process is hard-killed
  mid-run; every in-flight statement must still complete correctly via
  the existing quarantine/re-dispatch/stage-retry machinery (plus the
  session's local fallback for statements whose dispatch window
  straddled the death).

Client side: a minimal raw-socket MySQL 4.1 text-protocol client (the
tests/test_server.py MiniClient shape) — no external driver, per the
no-new-dependencies rule.
"""

from __future__ import annotations

import json
import os
import re
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

#: workload classes: (name, priority modifier, SQL). The short class is
#: a fragmentable grouped aggregate (interactive shape); the scan class
#: is a repartition join (neither side small — the shuffle data plane).
SHORT_SQL = (
    "select high_priority l_returnflag, count(*), sum(l_quantity) "
    "from lineitem group by l_returnflag order by l_returnflag"
)
SCAN_SQL = (
    "select low_priority o_orderpriority, count(*), sum(l_extendedprice) "
    "from orders join lineitem on o_orderkey = l_orderkey "
    "where l_quantity < 24 "
    "group by o_orderpriority order by o_orderpriority"
)


class MysqlClient:
    """Just enough MySQL client: handshake + COM_QUERY text results."""

    def __init__(self, port: int, timeout_s: float = 600.0):
        from tidb_tpu.server import protocol as P

        self._P = P
        self.sock = socket.create_connection(
            ("127.0.0.1", port), timeout=timeout_s
        )
        self.io = P.PacketIO(self.sock)
        greeting = self.io.read_packet()
        if not greeting or greeting[0] != 0x0A:
            raise ConnectionError("expected handshake v10")
        caps = P.CLIENT_PROTOCOL_41 | P.CLIENT_SECURE_CONNECTION
        body = (
            struct.pack("<I", caps) + struct.pack("<I", 1 << 24)
            + bytes([0xFF]) + b"\x00" * 23 + b"root\x00" + bytes([0])
        )
        self.io.write_packet(body)
        ok = self.io.read_packet()
        if not ok or ok[0] != 0x00:
            raise ConnectionError(f"auth failed: {ok!r}")

    def _lenenc(self, data: bytes, pos: int) -> Tuple[int, int]:
        v = data[pos]
        if v < 251:
            return v, pos + 1
        if v == 0xFC:
            return struct.unpack_from("<H", data, pos + 1)[0], pos + 3
        if v == 0xFD:
            return int.from_bytes(data[pos + 1:pos + 4], "little"), pos + 4
        return struct.unpack_from("<Q", data, pos + 1)[0], pos + 9

    def query(self, sql: str) -> List[tuple]:
        """Run one statement; returns text-protocol rows. Server-side
        errors raise RuntimeError carrying the MySQL errno."""
        self.io.reset_seq()
        self.io.write_packet(b"\x03" + sql.encode())
        first = self.io.read_packet()
        if first is None:
            raise ConnectionError("server closed the connection")
        if first[0] == 0xFF:
            errno = struct.unpack_from("<H", first, 1)[0]
            raise RuntimeError(
                f"server error {errno}: {first[9:].decode(errors='replace')}"
            )
        if first[0] == 0x00:
            return []
        ncols, _ = self._lenenc(first, 0)
        for _ in range(ncols):
            self.io.read_packet()  # column definitions
        eof = self.io.read_packet()
        assert eof[0] == 0xFE
        rows: List[tuple] = []
        while True:
            pkt = self.io.read_packet()
            if pkt[0] == 0xFE and len(pkt) < 9:
                break
            row: list = []
            pos = 0
            while pos < len(pkt):
                if pkt[pos] == 0xFB:
                    row.append(None)
                    pos += 1
                else:
                    ln, pos = self._lenenc(pkt, pos)
                    row.append(pkt[pos:pos + ln].decode())
                    pos += ln
            rows.append(tuple(row))
        return rows

    def close(self) -> None:
        try:
            self.io.reset_seq()
            self.io.write_packet(b"\x01")
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


def _spawn_worker(sf: float, seed: int) -> Tuple[subprocess.Popen, int]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = subprocess.Popen(
        [
            sys.executable, "-m", "tidb_tpu.parallel.dcn_worker",
            "--cpu", "--port", "0", "--mesh-devices", "4",
            "--tpch-sf", str(sf), "--seed", str(seed),
            "--tables", "orders,lineitem",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env,
    )
    line = p.stdout.readline()
    m = re.match(r"DCN_WORKER_READY port=(\d+)", line)
    if not m:
        try:
            rest, _ = p.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
            rest = ""
        raise RuntimeError(f"worker not ready: {line!r}\n{rest[-3000:]}")
    return p, int(m.group(1))


def _text_rows(result) -> List[tuple]:
    """Render a session Result the way the text protocol will, so the
    parity check compares byte-identical strings (decimals, dates,
    NULLs)."""
    from tidb_tpu.server import protocol as P

    types = getattr(result, "types", None) or [None] * len(result.columns)

    def txt(v, t):
        fv = P.format_value(v, t)
        if fv is None:
            return None
        return fv.decode() if isinstance(fv, bytes) else str(fv)

    out = []
    for row in result.rows:
        out.append(tuple(txt(v, t) for v, t in zip(row, types)))
    return out


def _pct(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def _counter_total(prefix: str) -> float:
    from tidb_tpu.utils.metrics import REGISTRY

    return sum(v for n, _k, v in REGISTRY.rows() if n.startswith(prefix))


def _flight_overlap(routed_flights: List[dict]) -> int:
    """Maximum number of concurrently-executing DCN-routed statements
    from DISTINCT connections, from the flight timelines: sweep the
    [start_ts, start_ts + duration] windows of every flight that
    charged fragment-dispatch time."""
    events: List[Tuple[float, int, int]] = []
    for f in routed_flights:
        t0 = f["start_ts"]
        t1 = t0 + f["duration_s"]
        events.append((t0, 1, f["conn_id"]))
        events.append((t1, -1, f["conn_id"]))
    events.sort()
    live: Dict[int, int] = {}
    best = 0
    for _ts, delta, conn in events:
        live[conn] = live.get(conn, 0) + delta
        if live[conn] <= 0:
            live.pop(conn, None)
        best = max(best, len(live))
    return best


def run_serve_load(args) -> int:
    """The --serve-load scenario (invoked from bench.py). Returns the
    process exit code; prints the one-line JSON result."""
    os.environ["JAX_PLATFORMS"] = "cpu"

    from tidb_tpu.bench import load_tpch
    from tidb_tpu.obs.flight import FLIGHT
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.parallel.serving import AdmissionController
    from tidb_tpu.server import Server
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog

    sf = args.sf if args.sf <= 1.0 else 0.005
    seed = 3
    sessions = max(int(args.serve_sessions), 1)
    stmts_per_session = max(int(args.serve_statements), 1)
    nworkers = max(int(args.serve_workers), 1)

    workers: List[subprocess.Popen] = []
    server = None
    sched = None
    try:
        ports = []
        for _ in range(nworkers):
            p, port = _spawn_worker(sf, seed)
            workers.append(p)
            ports.append(port)

        cat = Catalog()
        load_tpch(cat, sf=sf, seed=seed, tables=["orders", "lineitem"])
        ref = Session(cat, db="tpch")
        expected = {
            "short": _text_rows(ref.execute(SHORT_SQL)),
            "scan": _text_rows(ref.execute(SCAN_SQL)),
        }
        # --write-mix (the HTAP delta tier, storage/delta.py): a
        # concurrent writer session streams INSERTs into a table the
        # workers have NEVER loaded (the delta tier materializes it on
        # the replicas from the sync frames), verifying read-your-
        # writes after every commit, while reader sessions run the
        # same aggregate under both freshness modes — detail.delta
        # stamps depth, per-host sync lag, and the RYW-vs-bounded p99s
        write_mix = bool(getattr(args, "write_mix", False))
        if write_mix:
            ref.execute(
                "create table serve_writes (k bigint primary key, "
                "v bigint)"
            )

        # --timeline-out: capture the whole load run's fleet timeline
        # (worker events ride the fenced replies; admission waits and
        # statement spans land coordinator-side)
        timeline_path = getattr(args, "timeline_out", None)
        if timeline_path:
            from tidb_tpu.obs.timeline import TIMELINE

            TIMELINE.start()

        # Top SQL (obs/profiler.py) runs ON for the whole load phase —
        # the point of a continuous profiler is that serving traffic
        # can afford it; the A/B pairs below MEASURE that claim and
        # detail.topsql fails the run if profiler-on p50 regresses >5%
        from tidb_tpu.obs.profiler import TOPSQL

        cat.global_sysvars["tidb_enable_top_sql"] = True
        TOPSQL.store.reset()

        # metric time-series cadence for the run: the inspection stamp
        # (detail.inspection / --inspect-out) reads this history, and
        # worker samples ride the fenced replies + heartbeat flushes
        from tidb_tpu.obs.tsdb import SAMPLER, TSDB

        t_inspect0 = time.time()
        TSDB.sample_registry(now=t_inspect0)
        SAMPLER.retune(0.5)

        # admission knobs come from the tidb_-style sysvars (ROADMAP
        # PR 8 item); the bench's --serve-budget-mb overrides the
        # budget the way a SET GLOBAL would
        from tidb_tpu.utils.sysvar import SysVars

        TOPSQL.apply_sysvars(SysVars(cat.global_sysvars))
        admission = AdmissionController.from_sysvars(
            SysVars(cat.global_sysvars),
            budget_bytes=int(args.serve_budget_mb) << 20,
            queue_timeout_s=600.0,
        )
        # loopback-scale shuffle wait via the SYSVAR, not a hardcoded
        # ctor arg (same config plane a SET GLOBAL uses; an operator's
        # pre-set global wins over the driver's loopback default). The
        # WAN-scale 120s default makes kill-a-worker recovery
        # minutes-long here — every straddled stage's SURVIVOR sits
        # out the full wait for the dead peer's frames before its
        # retryable reply, and under 64 sessions those waits stack. On
        # loopback a healthy side arrives in milliseconds, so 10s is
        # already three orders of magnitude of slack.
        cat.global_sysvars.setdefault(
            "tidb_tpu_shuffle_wait_timeout_s", 10.0
        )
        sched = DCNFragmentScheduler(
            [("127.0.0.1", pt) for pt in ports],
            catalog=cat,
            # route joins over worker-to-worker tunnels even at dryrun
            # scale; grouped aggregates take the partial-agg frag cut
            shuffle_min_rows=1,
            dispatch_timeout_s=180.0,
            conn_pool_size=int(args.serve_pool_size),
            admission=admission,
        )
        server = Server(cat, port=0, dcn_scheduler=sched)
        server.start_background()

        before = {
            p: _counter_total(p)
            for p in (
                "tidbtpu_executor_shared_plan_cache_cross_session_hits_total",
                "tidbtpu_executor_shared_plan_cache_hits_total",
                "tidbtpu_session_dcn_route_fallbacks_total",
                "tidbtpu_dcn_retries",
                "tidbtpu_dcn_quarantines",
                "tidbtpu_shuffle_stage_retries",
            )
        }
        adm_before = dict(admission.status()["outcomes"])
        # the overlap sweep reads the WHOLE run's flight timelines:
        # size the ring so the default 256 cap doesn't evict early
        # flights mid-run (64 sessions x 7 statements is ~450 flights)
        FLIGHT.set_ring_capacity(
            sessions * (stmts_per_session + 2) + 64
        )
        flights_before = len(FLIGHT.rows())

        from tidb_tpu.utils import racecheck

        lock = racecheck.make_lock("serving.load")
        lat: Dict[str, List[float]] = (
            {"ryw": [], "bounded": []}
            if write_mix else {"short": [], "scan": []}
        )
        errors: List[str] = []
        started = threading.Barrier(sessions + 1)
        kill_at = threading.Event()

        WMIX_SQL = "select count(*), sum(v) from serve_writes"
        writer_done = threading.Event()

        def write_mix_thread(idx: int):
            c = MysqlClient(server.port)
            c.query("use tpch")
            started.wait(timeout=120)
            if idx == 0:
                # THE writer: interleave commits with read-your-writes
                # self-verification — acks are contiguous seqs, so a
                # session that waits for its own high-water observes
                # every earlier commit too
                inserted = 0
                try:
                    for k in range(stmts_per_session):
                        c.query(
                            "insert into serve_writes values "
                            f"({10 ** 9 + 2 * k}, {k}), "
                            f"({10 ** 9 + 2 * k + 1}, {k})"
                        )
                        inserted += 2
                        t0 = time.perf_counter()
                        rows = c.query(WMIX_SQL)
                        dt = time.perf_counter() - t0
                        n = int(rows[0][0])
                        with lock:
                            if n != inserted:
                                errors.append(
                                    f"writer stmt {k}: read-your-"
                                    f"writes stale: saw {n} rows, "
                                    f"committed {inserted}"
                                )
                            lat["ryw"].append(dt)
                        if k == 0:
                            kill_at.set()
                finally:
                    writer_done.set()
                    c.close()
                return
            mode = "bounded" if idx % 2 else "ryw"
            if mode == "bounded":
                c.query("set tidb_tpu_read_freshness = 'bounded'")
            last_n = -1
            for k in range(stmts_per_session):
                t0 = time.perf_counter()
                rows = c.query(WMIX_SQL)
                dt = time.perf_counter() - t0
                n = int(rows[0][0])
                with lock:
                    if n < last_n:
                        errors.append(
                            f"session {idx} ({mode}): count went "
                            f"backwards {last_n} -> {n}"
                        )
                    lat[mode].append(dt)
                last_n = n
            c.close()

        def client_thread(idx: int):
            if write_mix:
                try:
                    write_mix_thread(idx)
                except Exception as e:
                    with lock:
                        errors.append(
                            f"session {idx}: {type(e).__name__}: {e}"
                        )
                    writer_done.set()
                return
            try:
                c = MysqlClient(server.port)
                c.query("use tpch")
                started.wait(timeout=120)
                for k in range(stmts_per_session):
                    # mixed workload: every 4th statement is the
                    # LOW_PRIORITY scan, the rest HIGH_PRIORITY shorts
                    cls = "scan" if (idx + k) % 4 == 0 else "short"
                    sql = SCAN_SQL if cls == "scan" else SHORT_SQL
                    t0 = time.perf_counter()
                    rows = c.query(sql)
                    dt = time.perf_counter() - t0
                    if rows != expected[cls]:
                        with lock:
                            errors.append(
                                f"session {idx} stmt {k} ({cls}): "
                                f"parity broke: {rows[:3]} != "
                                f"{expected[cls][:3]}"
                            )
                        return
                    with lock:
                        lat[cls].append(dt)
                    if k == 0:
                        kill_at.set()  # load is flowing: arm the kill
                c.close()
            except Exception as e:
                with lock:
                    errors.append(f"session {idx}: {type(e).__name__}: {e}")

        threads = [
            threading.Thread(
                target=client_thread, args=(i,), daemon=True,
                name=f"serve-client-{i}",
            )
            for i in range(sessions)
        ]
        for t in threads:
            t.start()
        try:
            started.wait(timeout=120)
        except threading.BrokenBarrierError:
            # a client died before reaching the barrier (its error is
            # recorded): every other waiter unblocks broken — proceed
            # so the run still emits its JSON result with the
            # per-session errors instead of crashing the harness
            pass
        t_load0 = time.perf_counter()

        killed_worker = None
        if args.serve_kill_worker and len(workers) > 1:
            # kill one worker while the fleet is under load: the prober
            # quarantines it, in-flight fragments re-dispatch onto the
            # survivors (stage retries for shuffles), and any statement
            # whose dispatch straddled the death falls back local —
            # every statement still answers correctly
            kill_at.wait(timeout=300)
            time.sleep(0.5)
            killed_worker = len(workers) - 1
            workers[killed_worker].kill()

        for t in threads:
            t.join(timeout=1800)
        hung = [t.name for t in threads if t.is_alive()]
        wall = time.perf_counter() - t_load0

        total_stmts = sum(len(v) for v in lat.values())
        for v in lat.values():
            v.sort()

        # overlap from the flight timelines: routed flights only
        flights = FLIGHT.rows()[flights_before:]
        routed = [
            f for f in flights if "fragment-dispatch" in f["phases"]
        ]
        overlap = _flight_overlap(routed)
        # the DIRECT dispatch-overlap proof: the per-host pool's
        # high-water of concurrently leased control connections —
        # whole-statement flight windows intersect even when
        # dispatches serialize onto one stream, this gauge cannot
        from tidb_tpu.utils.metrics import REGISTRY

        pool_peak = int(max(
            (
                v for n, _k, v in REGISTRY.rows()
                if n.startswith("tidbtpu_dcn_pool_leased_peak")
            ),
            default=0,
        ))

        delta = {p: _counter_total(p) - v for p, v in before.items()}
        adm_after = admission.status()["outcomes"]
        adm_delta = {
            k: int(adm_after[k] - adm_before.get(k, 0)) for k in adm_after
        }

        # -- detail.topsql: attribution from the load phase + the
        # measured sampler overhead. Top digests snapshot FIRST (the
        # A/B pairs below toggle the profiler and would dilute them).
        prof_rows = TOPSQL.store.rows()
        fleet: Dict[str, dict] = {}
        for r in prof_rows:
            ent = fleet.setdefault(r["digest"], {
                "digest": r["digest"], "digest_text": "",
                "cpu_ms": 0.0, "device_ms": 0.0, "stall_ms": 0.0,
                "samples": 0, "instances": [],
            })
            ent["cpu_ms"] += r["cpu_s"] * 1e3
            ent["device_ms"] += r["device_s"] * 1e3
            ent["stall_ms"] += r["stall_s"] * 1e3
            ent["samples"] += r["samples"]
            ent["instances"].append(r["instance"])
            ent["digest_text"] = ent["digest_text"] or r["digest_text"]
        top_digests = sorted(
            fleet.values(), key=lambda e: -e["cpu_ms"]
        )[:3]
        for e in top_digests:
            e["cpu_ms"] = round(e["cpu_ms"], 2)
            e["device_ms"] = round(e["device_ms"], 2)
            e["stall_ms"] = round(e["stall_ms"], 2)
            e["instances"] = sorted(set(e["instances"]))
        ts_status = TOPSQL.store.status()
        flame_lines = len(TOPSQL.store.collapsed())

        # sampler overhead A/B: one session, interleaved ON/OFF pairs
        # of the short statement (the dispatch carries the toggle to
        # the workers, so BOTH tiers' samplers flip per batch) —
        # medians over pairs, same discipline as the pipeline A/B
        ab_pairs = 8
        ab_k = 3
        lat_ab = {"on": [], "off": []}
        abc = MysqlClient(server.port)
        abc.query("use tpch")
        abc.query(SHORT_SQL)  # warm the compiled path once
        for _pair in range(ab_pairs):
            for mode in ("on", "off"):
                if mode == "on":
                    TOPSQL.apply_sysvars(SysVars(cat.global_sysvars))
                else:
                    TOPSQL.stop()
                for _ in range(ab_k):
                    t0 = time.perf_counter()
                    abc.query(SHORT_SQL)
                    lat_ab[mode].append(time.perf_counter() - t0)
        abc.close()
        TOPSQL.stop()
        for v in lat_ab.values():
            v.sort()
        p50_on = _pct(lat_ab["on"], 0.50)
        p50_off = _pct(lat_ab["off"], 0.50)
        overhead_pct = (
            (p50_on - p50_off) / p50_off * 100.0 if p50_off > 0 else 0.0
        )
        topsql_detail = {
            "top_digests": top_digests,
            "digests_tracked": ts_status["digests"],
            "dropped_samples": ts_status["dropped"],
            "flamegraph_stacks": flame_lines,
            "ab_pairs": ab_pairs,
            "ab_statements_per_mode": ab_pairs * ab_k,
            "p50_on_s": round(p50_on, 4),
            "p50_off_s": round(p50_off, 4),
            "sampler_overhead_pct": round(overhead_pct, 2),
        }

        ok = not errors and not hung and total_stmts == (
            sessions * stmts_per_session
        )
        checks = {
            "parity_all_statements": not errors,
            "all_sessions_finished": not hung,
            "overlap_ge_2": overlap >= 2 and pool_peak >= 2,
            "cross_session_plan_cache_hits": delta[
                "tidbtpu_executor_shared_plan_cache_cross_session_hits_total"
            ] > 0,
            # the continuous-profiler claim, MEASURED: profiler-on p50
            # within 5% of profiler-off over the interleaved pairs
            "topsql_overhead_lt_5pct": overhead_pct < 5.0,
            # and the attribution actually landed under load
            "topsql_attributed": bool(top_digests),
        }
        delta_detail = None
        if write_mix:
            # post-hoc full-reload parity: a FRESH local session reads
            # the coordinator base directly; one last routed read-your-
            # writes statement (its own commit orders it after every
            # writer commit) must match it exactly
            final = MysqlClient(server.port)
            final.query("use tpch")
            final.query(
                "insert into serve_writes values (999999999, -1)"
            )
            routed_rows = final.query(WMIX_SQL)
            final.close()
            reload_rows = _text_rows(
                Session(cat, db="tpch").execute(WMIX_SQL)
            )
            parity = [tuple(r) for r in routed_rows] == [
                tuple(r) for r in reload_rows
            ]
            checks["write_mix_reload_parity"] = parity
            checks.pop("cross_session_plan_cache_hits", None)
            ds = getattr(cat, "delta_store", None)
            repl = getattr(sched, "delta", None)
            lag = {}
            if ds is not None and repl is not None:
                high = ds.high_seq()
                lag = {
                    host: int(high - acked)
                    for host, acked in repl.status()["acked"].items()
                }
            delta_detail = {
                "depth": ds.status()["entries"] if ds else 0,
                "high_seq": ds.high_seq() if ds else 0,
                "completed_fold_seq": (
                    ds.completed_fold_seq if ds else 0
                ),
                "sync_lag": lag,
                "ryw_p50_s": round(_pct(lat["ryw"], 0.50), 4),
                "ryw_p99_s": round(_pct(lat["ryw"], 0.99), 4),
                "bounded_p50_s": round(_pct(lat["bounded"], 0.50), 4),
                "bounded_p99_s": round(_pct(lat["bounded"], 0.99), 4),
                "reload_parity": parity,
            }
        result = {
            "metric": f"serve_load_{sessions}sess_queries_per_sec",
            "value": round(total_stmts / max(wall, 1e-9), 2),
            "unit": "queries/s",
            "vs_baseline": 0,
            "detail": {
                "backend": "cpu",
                "scenario": "serve_load",
                "ok": bool(ok and all(checks.values())),
                "checks": checks,
                "sessions": sessions,
                "statements_per_session": stmts_per_session,
                "statements_completed": total_stmts,
                "workers": nworkers,
                "killed_worker_under_load": killed_worker is not None,
                "sf": sf,
                "wall_seconds": round(wall, 3),
                "latency_s": {
                    cls: {
                        "n": len(v),
                        "p50": round(_pct(v, 0.50), 4),
                        "p99": round(_pct(v, 0.99), 4),
                        "max": round(v[-1], 4) if v else 0.0,
                    }
                    for cls, v in lat.items()
                },
                "fleet_overlap_max_concurrent_routed": overlap,
                "pool_leased_peak_per_host": pool_peak,
                "routed_statements": len(routed),
                "admission_outcomes": adm_delta,
                "admission": admission.status(),
                "counters": {k: round(v, 1) for k, v in delta.items()},
                "errors": errors[:10],
                "hung_sessions": hung,
                "write_mix": write_mix,
                "topsql": topsql_detail,
                "platform": "cpu",
            },
        }
        if delta_detail is not None:
            result["detail"]["delta"] = delta_detail
        if timeline_path:
            from tidb_tpu.obs.timeline import TIMELINE

            TIMELINE.stop()
            trace = TIMELINE.dump()
            with open(timeline_path, "w") as f:
                json.dump(trace, f)
            result["detail"]["timeline"] = {
                "hosts": trace["otherData"]["hosts"],
                "events": len(trace["traceEvents"]),
                "path": timeline_path,
            }
        # inspection stamp over the run's window: under a worker kill
        # the findings narrate the incident (heartbeat gap / retry
        # storm), under a clean run they should be quiet
        SAMPLER.stop()
        t_inspect1 = time.time()
        TSDB.sample_registry(now=t_inspect1)
        from tidb_tpu.obs.inspection import (
            inspection_detail,
            write_inspect_out,
        )

        inspection = inspection_detail(
            t_lo=t_inspect0, t_hi=t_inspect1
        )
        result["detail"]["inspection"] = inspection
        write_inspect_out(getattr(args, "inspect_out", None), inspection)
        print(json.dumps(result))
        return 0 if result["detail"]["ok"] else 1
    finally:
        try:
            from tidb_tpu.obs.tsdb import SAMPLER as _S

            _S.stop()  # idempotent; error paths must not leak the thread
        except Exception:
            pass
        try:
            from tidb_tpu.obs.profiler import TOPSQL as _T

            _T.stop()  # the profiler is process-global too
        except Exception:
            pass
        if server is not None:
            try:
                server.shutdown()
            except Exception:
                pass
        if sched is not None:
            try:
                sched.close()
            except Exception:
                pass
        for p in workers:
            p.kill()
