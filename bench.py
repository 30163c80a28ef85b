"""Benchmark: TPC-H on the device engine vs a vectorized-numpy CPU baseline.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference baseline (BASELINE.md) is TiDB's own embedded CPU engine
(unistore/mocktikv vectorized coprocessor); a vectorized numpy
implementation of the same query over the same data stands in for it
here (same columnar layout, single CPU core — generous to the baseline
since numpy's C kernels are at least as fast as the Go engine's
per-chunk loops).

One process, one measurement: the run measures on what `jax.devices()`
gives, names platform / device kind / device count in its output, and
exits non-zero when it finds no TPU — unless `--cpu` asks for the
explicit CPU run (which sets JAX_PLATFORMS=cpu before JAX loads). No
probe child, no retry, no CPU fallback, no replay of an older capture.
The fleet scenarios (--multihost-shuffle, --skew, --order-by, --chaos,
--serve-load) are CPU data-plane rehearsals by design: they set
JAX_PLATFORMS=cpu before touching JAX, spawn `dcn_worker --cpu`
processes and stamp "platform": "cpu" into their result.

Usage: python bench.py [--sf 1.0] [--query q1|q6|q18] [--repeat 5] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

Q1_SQL = (
    "select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
    "sum(l_extendedprice) as sum_base_price, "
    "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
    "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, "
    "avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price, "
    "avg(l_discount) as avg_disc, count(*) as count_order "
    "from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day "
    "group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"
)
Q6_SQL = (
    "select sum(l_extendedprice * l_discount) as revenue from lineitem "
    "where l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01' "
    "and l_discount between 0.05 and 0.07 and l_quantity < 24"
)
Q18_SQL = (
    "select o_orderkey, sum(l_quantity) from lineitem, orders "
    "where o_orderkey = l_orderkey "
    "group by o_orderkey having sum(l_quantity) > 300 "
    "order by sum(l_quantity) desc limit 100"
)
Q5_SQL = (
    "select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue "
    "from customer, orders, lineitem, supplier, nation, region "
    "where c_custkey = o_custkey and l_orderkey = o_orderkey "
    "and l_suppkey = s_suppkey and c_nationkey = s_nationkey "
    "and s_nationkey = n_nationkey and n_regionkey = r_regionkey "
    "and r_name = 'ASIA' "
    "and o_orderdate >= date '1994-01-01' and o_orderdate < date '1995-01-01' "
    "group by n_name order by revenue desc"
)
QUERIES = {"q1": Q1_SQL, "q5": Q5_SQL, "q6": Q6_SQL, "q18": Q18_SQL}
# ladder #5: TPC-DS Q95 (correlated subqueries + multi-join)
_TABLES = {
    "q1": ["orders", "lineitem"],
    "q6": ["orders", "lineitem"],
    "q18": ["orders", "lineitem"],
    "q5": ["orders", "lineitem", "customer", "supplier", "nation", "region"],
}


# ---------------------------------------------------------------------------
# numpy oracle/baseline kernels (child-side)
# ---------------------------------------------------------------------------


def numpy_q1(np, blk, cutoff):
    ship = blk["l_shipdate"]
    m = ship <= cutoff
    rf = blk["l_returnflag"][m].astype(np.int64)
    ls = blk["l_linestatus"][m].astype(np.int64)
    qty = blk["l_quantity"][m]
    price = blk["l_extendedprice"][m]
    disc = blk["l_discount"][m]
    tax = blk["l_tax"][m]
    key = rf * 2 + ls
    nk = 6
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    out = {
        "sum_qty": np.bincount(key, qty, minlength=nk),
        "sum_base": np.bincount(key, price, minlength=nk),
        "sum_disc": np.bincount(key, disc_price, minlength=nk),
        "sum_charge": np.bincount(key, charge, minlength=nk),
        "cnt": np.bincount(key, minlength=nk),
    }
    out["avg_qty"] = out["sum_qty"] / np.maximum(out["cnt"], 1)
    out["avg_base"] = out["sum_base"] / np.maximum(out["cnt"], 1)
    return out


def numpy_q6(np, blk, d0, d1):
    ship = blk["l_shipdate"]
    m = (
        (ship >= d0)
        & (ship < d1)
        & (blk["l_discount"] >= 5)
        & (blk["l_discount"] <= 7)
        & (blk["l_quantity"] < 2400)
    )
    return (blk["l_extendedprice"][m] * blk["l_discount"][m]).sum()


def numpy_q18(np, blk, thresh):
    ok = blk["l_orderkey"]
    qty = blk["l_quantity"]
    sums = np.bincount(ok, qty)
    big = np.nonzero(sums > thresh)[0]
    return big, sums[big]


def numpy_q5(np, cat, d0, d1):
    """Vectorized Q5 over raw columns (dense 1..N keys -> array lookups)."""

    def cols(t):
        tt = cat.table("tpch", t)
        b = tt.blocks()[0]
        return {n: c for n, c in b.columns.items()}

    reg = cols("region")
    nat = cols("nation")
    cust = cols("customer")
    supp = cols("supplier")
    orders = cols("orders")
    li = cols("lineitem")
    asia_code = np.searchsorted(
        np.asarray(reg["r_name"].dictionary, dtype=object), "ASIA"
    )
    asia = set(reg["r_regionkey"].data[reg["r_name"].data == asia_code].tolist())
    nat_in = np.array([rk in asia for rk in nat["n_regionkey"].data])
    n_nat = len(nat_in)
    cust_nation = np.zeros(int(cust["c_custkey"].data.max()) + 1, dtype=np.int64)
    cust_nation[cust["c_custkey"].data] = cust["c_nationkey"].data
    supp_nation = np.zeros(int(supp["s_suppkey"].data.max()) + 1, dtype=np.int64)
    supp_nation[supp["s_suppkey"].data] = supp["s_nationkey"].data
    om = (orders["o_orderdate"].data >= d0) & (orders["o_orderdate"].data < d1)
    ord_cust = np.zeros(int(orders["o_orderkey"].data.max()) + 2, dtype=np.int64)
    ord_ok = np.zeros(int(orders["o_orderkey"].data.max()) + 2, dtype=bool)
    ord_cust[orders["o_orderkey"].data[om]] = orders["o_custkey"].data[om]
    ord_ok[orders["o_orderkey"].data[om]] = True
    lo = li["l_orderkey"].data
    ls = li["l_suppkey"].data
    cn = cust_nation[ord_cust[lo]]
    sn = supp_nation[ls]
    m = ord_ok[lo] & (cn == sn) & nat_in[np.clip(sn, 0, n_nat - 1)]
    rev = li["l_extendedprice"].data[m] * (100 - li["l_discount"].data[m])
    return np.bincount(sn[m], rev, minlength=n_nat)


# ---------------------------------------------------------------------------
# child: actually measure (imports jax via tidb_tpu)
# ---------------------------------------------------------------------------


def _phase(name: str) -> None:
    """Per-phase progress marker on stderr, flushed immediately, so a
    run cut at its time limit shows the LAST phase reached."""
    print(f"[phase {time.strftime('%H:%M:%S')}] {name}", file=sys.stderr, flush=True)


def _metrics_snapshot() -> dict:
    """{metric_name: (kind, value)} view of the engine registry."""
    from tidb_tpu.utils.metrics import REGISTRY

    return {name: (kind, val) for name, kind, val in REGISTRY.rows()}


def _metrics_delta(before: dict, after: dict) -> dict:
    """Registry movement across the benchmarked query: what the engine
    actually did (jit compiles, retraces, transfer bytes, cache hits)
    alongside the latency headline. Counters/histograms report the
    delta; gauges (e.g. device-mem high-water — a lifetime max that may
    not move during the measured window) report their absolute value."""
    out = {}
    for name, (kind, v) in sorted(after.items()):
        if kind == "gauge":
            if v:
                out[name] = round(v, 6)
            continue
        d = v - before.get(name, ("", 0.0))[1]
        if d:
            out[name] = round(d, 6)
    return out


def _emit_metrics(args, result, before: dict, after=None) -> None:
    """Stamp the per-query registry delta into result.detail and, with
    --metrics-out, snapshot it to a JSON file next to the bench output."""
    delta = _metrics_delta(before, after if after is not None else _metrics_snapshot())
    result.setdefault("detail", {})["engine_metrics"] = delta
    if getattr(args, "metrics_out", None):
        with open(args.metrics_out, "w") as f:
            json.dump(
                {
                    "query": args.query,
                    "sf": args.sf,
                    "metrics_delta": delta,
                },
                f, indent=1,
            )
    _write_flight_out(args)
    _write_timeline_out(args)


def _start_timeline(args) -> bool:
    """Arm the fleet timeline tracer when --timeline-out asked for a
    capture (any bench mode). Returns whether a capture is live."""
    if not getattr(args, "timeline_out", None):
        return False
    from tidb_tpu.obs.timeline import TIMELINE

    TIMELINE.start()
    return True


def _write_timeline_out(args) -> None:
    """--timeline-out: dump the captured fleet timeline as Chrome
    trace-event JSON (open the file in Perfetto / chrome://tracing).
    One process track per host, thread tracks per session/worker task,
    counter tracks from the sampled gauges."""
    path = getattr(args, "timeline_out", None)
    if not path:
        return
    from tidb_tpu.obs.timeline import TIMELINE

    TIMELINE.stop()
    with open(path, "w") as f:
        json.dump(TIMELINE.dump(), f)


def _write_flight_out(args) -> None:
    """--flight-out: snapshot the flight recorder's view of the bench
    run — per-query phase timelines, the per-digest statements summary
    (percentiles + mean phase breakdown + engine columns) and the DCN
    link registry — to a JSON file. The same breakdown
    information_schema serves, captured for the bench ladder."""
    path = getattr(args, "flight_out", None)
    if not path:
        return
    from tidb_tpu.obs.flight import FLIGHT, LINKS
    from tidb_tpu.utils.metrics import STMT_SUMMARY

    with open(path, "w") as f:
        json.dump(
            {
                "flights": FLIGHT.rows(),
                "statements": STMT_SUMMARY.rows_full(),
                "links": LINKS.snapshot(),
            },
            f, indent=1,
        )


def measure(args) -> int:
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before JAX loads

    _start_timeline(args)

    import numpy as np

    _phase("import tidb_tpu/jax")
    from tidb_tpu.bench import load_tpch
    from tidb_tpu.dtypes import date_to_days
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog

    import jax

    from tidb_tpu.utils.backend import enable_compile_cache, is_tpu

    _phase("backend init (devices query)")
    dev0 = jax.devices()[0]
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(jax.devices()),
    }
    if not is_tpu() and not args.cpu:
        print(
            f"bench.py: no TPU (found {device}); pass --cpu for the "
            "explicit CPU run",
            file=sys.stderr,
        )
        return 1
    cache_dir = enable_compile_cache()
    _phase(f"backend ready: {device}, compile cache {cache_dir}")

    cat = Catalog()
    t0 = time.perf_counter()
    if args.query == "q95":
        from tidb_tpu.bench.tpcds import Q95_SQL, load_tpcds, numpy_q95

        load_tpcds(cat, sf=args.sf, seed=1)
        gen_s = time.perf_counter() - t0
        sess = Session(cat, db="test")
        # benchmark machines have tens of GB of device/host memory; the
        # conservative 8GB default admission quota is for servers
        sess.execute(f"set tidb_mem_quota_query = {64 << 30}")
        nrows = cat.table("test", "web_sales").nrows
        sql = Q95_SQL
        m0 = _metrics_snapshot()
        sess.execute(sql)  # warmup
        times = []
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            sess.execute(sql)
            times.append(time.perf_counter() - t0)
        dev_s = float(np.median(times))
        m_after = _metrics_snapshot()  # before the baseline, like tpch
        base_times = []
        for _ in range(min(max(args.repeat, 2), 3)):
            t0 = time.perf_counter()
            numpy_q95(cat)
            base_times.append(time.perf_counter() - t0)
        base_s = float(np.median(base_times))
        value = nrows / dev_s
        baseline = nrows / base_s
        result = {
            "metric": f"tpcds_q95_sf{args.sf:g}_rows_per_sec",
            "value": round(value, 1),
            "unit": "rows/s",
            "vs_baseline": round(value / baseline, 3),
            "detail": {
                "rows": nrows,
                "device_median_s": round(dev_s, 4),
                "numpy_baseline_s": round(base_s, 4),
                "datagen_s": round(gen_s, 2),
                "repeat": args.repeat,
                "device": device,
            },
        }
        _emit_metrics(args, result, m0, m_after)
        print(json.dumps(result))
        return 0
    tables = _TABLES[args.query]
    _phase("datagen")
    load_tpch(cat, sf=args.sf, tables=tables, seed=1)
    gen_s = time.perf_counter() - t0
    sess = Session(cat, db="tpch")
    sess.execute(f"set tidb_mem_quota_query = {64 << 30}")
    _phase("analyze tables")
    for tname in tables:
        # reference benchmark methodology: ANALYZE before measuring so
        # the CBO sizes join tiles from real stats
        sess.execute(f"analyze table {tname}")
    li = cat.table("tpch", "lineitem")
    nrows = li.nrows

    sql = QUERIES[args.query]

    # device engine (includes host->device on first run; cached after)
    _phase("warmup execute (h2d + discovery + first jit)")
    m0 = _metrics_snapshot()
    sess.execute(sql)  # warmup: compile + scan cache
    _phase("steady-state runs")
    times = []
    for _ in range(args.repeat):
        t0 = time.perf_counter()
        sess.execute(sql)
        times.append(time.perf_counter() - t0)
    dev_s = float(np.median(times))
    m_after = _metrics_snapshot()
    _phase("numpy baseline")

    # numpy baseline over the same host-resident columns
    blk = {}
    b = li.blocks()[0]
    for c in (
        "l_shipdate l_returnflag l_linestatus l_quantity l_extendedprice "
        "l_discount l_tax l_orderkey".split()
    ):
        blk[c] = b.columns[c].data
    base_times = []
    cutoff = int(date_to_days("1998-12-01")) - 90
    d0, d1 = int(date_to_days("1994-01-01")), int(date_to_days("1995-01-01"))
    for _ in range(min(max(args.repeat, 2), 3)):
        t0 = time.perf_counter()
        if args.query == "q1":
            numpy_q1(np, blk, cutoff)
        elif args.query == "q6":
            numpy_q6(np, blk, d0, d1)
        elif args.query == "q5":
            numpy_q5(np, cat, d0, d1)
        else:
            numpy_q18(np, blk, 30000)
        base_times.append(time.perf_counter() - t0)
    base_s = float(np.median(base_times))

    value = nrows / dev_s
    baseline = nrows / base_s
    result = {
        "metric": f"tpch_{args.query}_sf{args.sf:g}_rows_per_sec",
        "value": round(value, 1),
        "unit": "rows/s",
        "vs_baseline": round(value / baseline, 3),
        "detail": {
            "rows": nrows,
            "device_median_s": round(dev_s, 4),
            "numpy_baseline_s": round(base_s, 4),
            "datagen_s": round(gen_s, 2),
            "repeat": args.repeat,
            "device": device,
        },
    }
    _emit_metrics(args, result, m0, m_after)
    print(json.dumps(result))
    return 0


def _write_out(args, result) -> None:
    """--out: also write the result JSON to a file."""
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


def measure_multihost_shuffle(args) -> int:
    """Multihost shuffle-join scenario: a 2-worker x 4-device CPU
    dryrun runs one repartition-join query BOTH ways — partial-agg
    staging through the coordinator vs direct worker-to-worker tunnels
    — and records where the inter-host bytes actually went
    (bytes_over_coordinator vs bytes_over_tunnels) alongside the
    timings. This is a DATA-PLANE benchmark, deliberately CPU (the
    workers are `dcn_worker --cpu` subprocesses; the result says
    "platform": "cpu")."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import re
    import statistics

    timeline_on = _start_timeline(args)

    from tidb_tpu.bench import load_tpch
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.parser.sqlparse import parse
    from tidb_tpu.planner.logical import build_query
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog
    from tidb_tpu.utils.metrics import REGISTRY

    # 2 CPU worker processes can't chew SF10: cap the dryrun scale
    sf = args.sf if args.sf <= 1.0 else 0.02
    seed = 3
    workers = []
    try:
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        if getattr(args, "racecheck", False):
            # the whole data plane (ShuffleStore cv, tunnel cv, exec
            # rlock, metrics) runs order-tracked in the workers: a
            # clean capture PROVES no lock-order inversion fired under
            # real produce/push/decode/stage interleaving
            env["TIDB_TPU_RACECHECK"] = "1"
        ports = []
        for _ in range(2):
            p = subprocess.Popen(
                [
                    sys.executable, "-m", "tidb_tpu.parallel.dcn_worker",
                    "--cpu", "--port", "0", "--mesh-devices", "4",
                    "--tpch-sf", str(sf), "--seed", str(seed),
                    "--tables", "orders,lineitem",
                ],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env,
            )
            workers.append(p)
            line = p.stdout.readline()
            m = re.match(r"DCN_WORKER_READY port=(\d+)", line)
            if not m:
                # drain the merged stdout/stderr so a startup crash
                # (jax init, import error) is diagnosable
                try:
                    rest, _ = p.communicate(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    rest = ""
                raise RuntimeError(
                    f"worker not ready: {line!r}\n{rest[-3000:]}"
                )
            ports.append(int(m.group(1)))

        cat = Catalog()
        load_tpch(cat, sf=sf, seed=seed, tables=["orders", "lineitem"])
        sess = Session(cat, db="tpch")
        # a true repartition-join shape: neither side pre-aggregates
        # below the join (Q18's planner rewrites the agg under the
        # join, which removes the shuffle cut entirely)
        sql = (
            "select o_orderpriority, count(*), sum(l_extendedprice) "
            "from orders join lineitem on o_orderkey = l_orderkey "
            "where l_quantity < 24 "
            "group by o_orderpriority order by o_orderpriority"
        )
        plan = build_query(
            parse(sql)[0], cat, "tpch", sess._scalar_subquery
        )

        def _reg_total(prefix):
            return sum(
                v for n, _k, v in REGISTRY.rows() if n.startswith(prefix)
            )

        def run_mode(mode, codec="binary", pipeline=True):
            sched = DCNFragmentScheduler(
                [("127.0.0.1", pt) for pt in ports],
                catalog=cat, shuffle_mode=mode, shuffle_codec=codec,
                shuffle_pipeline=pipeline,
            )
            try:
                # one untimed warmup: the workers' persistent executors
                # pay the producer/consumer XLA compile here, so the
                # timed repeats (and the mode/pipeline A/Bs) compare
                # steady-state data-plane behavior, not compile order
                sched.execute_plan(plan)
                before = {
                    p: _reg_total(p)
                    for p in (
                        "tidbtpu_dcn_bytes_staged",
                        "tidbtpu_shuffle_bytes_total",
                        "tidbtpu_shuffle_encode_seconds",
                        "tidbtpu_shuffle_decode_seconds",
                        "tidbtpu_shuffle_wait_idle_seconds",
                    )
                }
                times, rows = [], []
                rows_tunneled = 0
                ttff = 0.0
                stage_walls = []
                for _ in range(max(args.repeat, 1)):
                    t0 = time.perf_counter()
                    _cols, out = sched.execute_plan(plan)
                    times.append(time.perf_counter() - t0)
                    rows = out
                    if mode != "never":
                        # summed across repeats — the byte counters
                        # below accumulate across repeats too
                        lq = sched.last_query or {}
                        sh = lq.get("shuffle", {})
                        rows_tunneled += sh.get("rows_tunneled", 0)
                        ttff = max(ttff, sh.get("ttff_s", 0.0))
                        # the shuffle STAGE wall-clock: the slowest
                        # partition's produce+push+wait+stage+consume
                        # on the workers (excludes dispatch RPC and the
                        # coordinator's final merge, identical in both
                        # pipeline modes)
                        stage_walls.append(max(
                            (f.get("exec_s", 0.0)
                             for f in lq.get("fragments", [])),
                            default=0.0,
                        ))
                delta = {
                    p: _reg_total(p) - v0 for p, v0 in before.items()
                }
                tunneled = delta["tidbtpu_shuffle_bytes_total"]
                return {
                    "seconds": statistics.median(times),
                    "stage_seconds": (
                        statistics.median(stage_walls)
                        if stage_walls else None
                    ),
                    "rows": len(rows),
                    "codec": codec if mode != "never" else None,
                    "pipeline": pipeline if mode != "never" else None,
                    "bytes_over_coordinator":
                        delta["tidbtpu_dcn_bytes_staged"],
                    "bytes_over_tunnels": tunneled,
                    # wire efficiency of the exchange codec (the A/B
                    # PERF_NOTES "Shuffle wire format" cites): counters
                    # ship back from the worker processes via the
                    # piggybacked registry deltas
                    "bytes_per_row": (
                        round(tunneled / rows_tunneled, 2)
                        if rows_tunneled else None
                    ),
                    "encode_seconds": round(
                        delta["tidbtpu_shuffle_encode_seconds"], 6
                    ),
                    "decode_seconds": round(
                        delta["tidbtpu_shuffle_decode_seconds"], 6
                    ),
                    "wait_idle_seconds": round(
                        delta["tidbtpu_shuffle_wait_idle_seconds"], 6
                    ),
                    "time_to_first_frame_seconds": round(ttff, 6),
                    "rows_tunneled": rows_tunneled,
                    "result": rows,
                }
            finally:
                sched.close()

        staged = run_mode("never")
        tunnel = run_mode("always")                  # binary, pipelined
        barrier = run_mode("always", pipeline=False)  # pipeline A/B ref
        tunnel_json = run_mode("always", codec="json")    # A/B reference

        def run_pipeline_pairs(pairs):
            """Interleaved pipelined/barrier timing pairs on two live
            schedulers: block-sequential A/B timing is dominated by
            system drift at this stage scale (~10^-1 s); alternating
            runs sample the same machine state for both modes."""
            scheds = {
                mode: DCNFragmentScheduler(
                    [("127.0.0.1", pt) for pt in ports],
                    catalog=cat, shuffle_mode="always",
                    shuffle_pipeline=(mode == "pipelined"),
                )
                for mode in ("pipelined", "barrier")
            }
            out = {
                mode: {"wall": [], "stage": [], "idle": 0.0, "ttff": 0.0}
                for mode in scheds
            }
            try:
                for sched in scheds.values():  # warm both
                    sched.execute_plan(plan)
                for _ in range(pairs):
                    for mode, sched in scheds.items():
                        t0 = time.perf_counter()
                        _cols, res = sched.execute_plan(plan)
                        out[mode]["wall"].append(
                            time.perf_counter() - t0
                        )
                        assert res == staged["result"], (
                            f"pipeline A/B parity broke ({mode})"
                        )
                        lq = sched.last_query or {}
                        sh = lq.get("shuffle", {})
                        out[mode]["stage"].append(max(
                            (f.get("exec_s", 0.0)
                             for f in lq.get("fragments", [])),
                            default=0.0,
                        ))
                        out[mode]["idle"] += sh.get("wait_idle_s", 0.0)
                        out[mode]["ttff"] = max(
                            out[mode]["ttff"], sh.get("ttff_s", 0.0)
                        )
            finally:
                for sched in scheds.values():
                    sched.close()
            return out

        def run_dag_ab(pairs):
            """Shuffle-DAG A/B (ISSUE 11): the join -> RE-KEYED
            DISTINCT group-by -> ORDER BY LIMIT query runs CHAINED
            (hash join stage -> held-output re-key stage -> range
            top-K stage; both sides fragment-sliced) vs the SINGLE-CUT
            group-by baseline (only lineitem sliced — every host
            re-scans the whole orders side). Interleaved pairs, same
            workers; reports wall + per-host scanned base rows +
            per-host produced exchange bytes."""
            dag_sql = (
                "select o_orderpriority, count(distinct l_suppkey), "
                "sum(l_extendedprice) from orders join lineitem "
                "on o_orderkey = l_orderkey group by o_orderpriority "
                "order by sum(l_extendedprice) desc limit 3"
            )
            dag_plan = build_query(
                parse(dag_sql)[0], cat, "tpch", sess._scalar_subquery
            )
            scheds = {
                "chained": DCNFragmentScheduler(
                    [("127.0.0.1", pt) for pt in ports],
                    catalog=cat, shuffle_mode="always",
                    shuffle_dag="always",
                ),
                "single_cut": DCNFragmentScheduler(
                    [("127.0.0.1", pt) for pt in ports],
                    catalog=cat, shuffle_mode="always",
                    shuffle_dag="never",
                ),
            }
            out = {
                mode: {
                    "wall": [], "scan_rows_per_host": 0,
                    "bytes_per_host": 0, "stages": 0,
                }
                for mode in scheds
            }

            def scan_bytes_per_host(sched):
                """Per-host base-table PRODUCE bytes of this
                scheduler's chosen cut: every Scan it executes per
                host (sliced scans read nrows/2, re-scanned unsliced
                sides read ALL nrows on EVERY host) times the pruned
                column set at 8 B/col — the scan-work cost the
                chained DAG removes, priced from the plan the
                scheduler actually picked."""
                from tidb_tpu.planner import logical as L

                kind, cut2 = sched._choose_cut(dag_plan)
                sides = (
                    [s for st in cut2.stages for s in st.sides]
                    if kind == "dag" else list(cut2.sides)
                )
                total = 0.0
                for s in sides:
                    if s.frag_scan is None:
                        continue  # re-staged held output: no scan
                    scans = []

                    def walk(p):
                        if isinstance(p, L.Scan):
                            scans.append(p)
                            return
                        for a in ("child", "left", "right"):
                            c = getattr(p, a, None)
                            if c is not None:
                                walk(c)
                        for c in getattr(p, "children", []) or []:
                            walk(c)

                    walk(s.template)
                    for sc in scans:
                        nrows = cat.table(sc.db, sc.table).nrows
                        share = nrows / 2 if sc is s.frag_scan else nrows
                        total += share * 8 * len(sc.columns)
                return int(total)

            ref = None
            try:
                for sched in scheds.values():  # warm (XLA compiles)
                    sched.execute_plan(dag_plan)
                for _ in range(pairs):
                    for mode, sched in scheds.items():
                        t0 = time.perf_counter()
                        _cols, res = sched.execute_plan(dag_plan)
                        out[mode]["wall"].append(
                            time.perf_counter() - t0
                        )
                        if ref is None:
                            ref = res
                        assert res == ref, f"dag A/B parity broke ({mode})"
                        lq = sched.last_query or {}
                        frags = lq.get("fragments", [])
                        by_host = {}
                        for f in frags:
                            h = by_host.setdefault(
                                f.get("host"), [0, 0]
                            )
                            h[0] += int(f.get("scan_rows", 0))
                            h[1] += int(f.get("pushed_bytes", 0))
                        if by_host:
                            out[mode]["scan_rows_per_host"] = max(
                                v[0] for v in by_host.values()
                            )
                            out[mode]["bytes_per_host"] = max(
                                v[1] for v in by_host.values()
                            )
                        out[mode]["stages"] = len(
                            lq.get("shuffle_stages")
                            or ([lq["shuffle"]] if lq.get("shuffle")
                                else [])
                        )
            finally:
                for sched in scheds.values():
                    sched.close()
            ch, sc = out["chained"], out["single_cut"]
            produce_ch = scan_bytes_per_host(scheds["chained"])
            produce_sc = scan_bytes_per_host(scheds["single_cut"])
            return {
                "pairs": pairs,
                "query": dag_sql,
                # per-host base-table produce bytes (pruned columns x
                # slice share): the chained DAG slices BOTH sides; the
                # single cut re-scans the whole unsliced orders side
                # on every host
                "produce_bytes_per_host_chained": produce_ch,
                "produce_bytes_per_host_single_cut": produce_sc,
                "produce_bytes_ratio": round(
                    produce_sc / max(produce_ch, 1), 4
                ),
                "seconds_chained": round(
                    statistics.median(ch["wall"]), 6
                ),
                "seconds_single_cut": round(
                    statistics.median(sc["wall"]), 6
                ),
                "speedup": round(
                    statistics.median(sc["wall"])
                    / max(statistics.median(ch["wall"]), 1e-9), 4
                ),
                "stages_chained": ch["stages"],
                "stages_single_cut": sc["stages"],
                # the headline: scanned base rows per host — the
                # chained DAG slices BOTH sides (~ total/N per host);
                # the single cut re-scans the unsliced orders side on
                # every host
                "scan_rows_per_host_chained": ch["scan_rows_per_host"],
                "scan_rows_per_host_single_cut":
                    sc["scan_rows_per_host"],
                "scan_rows_ratio": round(
                    sc["scan_rows_per_host"]
                    / max(ch["scan_rows_per_host"], 1), 4
                ),
                "bytes_per_host_chained": ch["bytes_per_host"],
                "bytes_per_host_single_cut": sc["bytes_per_host"],
            }

        # flight-recorder attribution through the session routing path
        # (PR 6): the SAME query executed as SQL with the scheduler
        # ATTACHED — statements_summary picks up the worker-reported
        # shuffle phase breakdown, and --flight-out snapshots it
        def run_flight_attributed():
            from tidb_tpu.utils.metrics import STMT_SUMMARY, sql_digest

            sched = DCNFragmentScheduler(
                [("127.0.0.1", pt) for pt in ports],
                catalog=cat, shuffle_mode="always",
            )
            sess.attach_dcn_scheduler(sched)
            try:
                for _ in range(max(args.repeat, 2)):
                    sess.execute(sql)
            finally:
                sess.attach_dcn_scheduler(None)
                sched.close()
            ent = next(
                (
                    e for e in STMT_SUMMARY.rows_full()
                    if e["digest_text"] == sql_digest(sql)
                ),
                None,
            )
            if ent is None:
                return None
            n = max(ent["exec_count"], 1)
            return {
                "exec_count": ent["exec_count"],
                "p50_latency_s": round(ent["p50_latency"], 6),
                "p99_latency_s": round(ent["p99_latency"], 6),
                "avg_phase_seconds": {
                    p: round(v[0] / n, 6)
                    for p, v in sorted(ent["phases"].items())
                },
                "shuffle_bytes": ent["phases"].get(
                    "shuffle-push", (0.0, 0, 0)
                )[1],
                "rows_sent": ent["rows_sent"],
            }

        flight_breakdown = run_flight_attributed()

        def run_feedback_pair():
            """AQE feedback warm/cold pair (ISSUE 15): a join whose
            filtered side collapses far below its static catalog
            estimate runs twice under tidb_tpu_aqe_feedback=on — the
            COLD run plans from static stats (repartition) and
            records the observed side rows; the WARM run's cost model
            seeds from those actuals and switches the edge to
            broadcast (adaptive=feedback, fewer tunnel bytes)."""
            from tidb_tpu.parallel import aqe
            from tidb_tpu.planner.cardinality import CARD_FEEDBACK
            from tidb_tpu.utils.metrics import sql_digest

            q = (
                "select count(*), sum(l_quantity) from lineitem "
                "join orders on l_orderkey = o_orderkey "
                "where o_custkey < 5"
            )
            digest = sql_digest(q)
            CARD_FEEDBACK.reset()
            fb_plan = build_query(
                parse(q)[0], cat, "tpch", sess._scalar_subquery
            )
            sched = DCNFragmentScheduler(
                [("127.0.0.1", pt) for pt in ports],
                catalog=cat, shuffle_mode="always",
                shuffle_dag="never", aqe_feedback=True,
                shuffle_broadcast_rows=max(
                    int(cat.table("tpch", "orders").nrows * 0.2), 64
                ),
            )
            out = {}
            try:
                sched.execute_plan(fb_plan)  # compile warmup
                d0 = aqe.decision_counts().get("feedback", 0.0)
                ref = None
                for phase in ("cold", "warm"):
                    kind, cut = sched._choose_cut(
                        fb_plan, digest=digest
                    )
                    t0 = time.perf_counter()
                    _c, rows = sched.execute_plan(
                        fb_plan, cut_hint=(kind, cut), digest=digest
                    )
                    st = (sched.last_query_mine() or {}).get(
                        "shuffle", {}
                    )
                    if ref is None:
                        ref = rows
                    assert rows == ref, "feedback pair parity broke"
                    out[phase] = {
                        "seconds": round(time.perf_counter() - t0, 6),
                        "modes": [s.mode for s in cut.sides],
                        "adaptive": list(st.get("adaptive") or []),
                        "bytes_tunneled": st.get("bytes_tunneled"),
                    }
                out["feedback_decisions"] = (
                    aqe.decision_counts().get("feedback", 0.0) - d0
                )
                out["changed"] = (
                    out["cold"]["modes"] != out["warm"]["modes"]
                )
                return out
            finally:
                sched.close()

        def run_rf_pairs(pairs):
            """Runtime-filter on/off pairs (ISSUE 19): a repartition
            join whose build side (orders, o_custkey < 5) rejects
            nearly every probe-side lineitem row runs INTERLEAVED on
            two live schedulers — runtime_filter=always vs off — so
            both arms sample the same machine state. The filtered arm
            pays a build-side probe round and the filter broadcast;
            it saves the dropped rows' partition+encode+tunnel bytes.
            Exact row parity is asserted every pair."""
            q = (
                "select count(*), sum(l_extendedprice) from lineitem "
                "join orders on l_orderkey = o_orderkey "
                "where o_custkey < 5"
            )
            rf_plan = build_query(
                parse(q)[0], cat, "tpch", sess._scalar_subquery
            )
            scheds = {
                arm: DCNFragmentScheduler(
                    [("127.0.0.1", pt) for pt in ports],
                    catalog=cat, shuffle_mode="always",
                    shuffle_dag="never",
                    runtime_filter=(
                        "always" if arm == "filtered" else "off"
                    ),
                )
                for arm in ("filtered", "unfiltered")
            }
            out = {
                arm: {"wall": [], "bytes": [], "encode": [],
                      "stage": []}
                for arm in scheds
            }
            rf_info = {}
            try:
                for sched in scheds.values():  # compile warmup
                    sched.execute_plan(rf_plan)
                ref = None
                for _ in range(pairs):
                    for arm, sched in scheds.items():
                        e0 = _reg_total(
                            "tidbtpu_shuffle_encode_seconds"
                        )
                        t0 = time.perf_counter()
                        _c, rows = sched.execute_plan(rf_plan)
                        wall = time.perf_counter() - t0
                        if ref is None:
                            ref = rows
                        assert rows == ref, "rf pair parity broke"
                        lq = sched.last_query_mine() or {}
                        st = lq.get("shuffle", {})
                        rec = out[arm]
                        rec["wall"].append(wall)
                        rec["bytes"].append(
                            st.get("bytes_tunneled", 0)
                        )
                        rec["encode"].append(
                            _reg_total(
                                "tidbtpu_shuffle_encode_seconds"
                            ) - e0
                        )
                        rec["stage"].append(max(
                            (f.get("exec_s", 0.0)
                             for f in lq.get("fragments", [])),
                            default=0.0,
                        ))
                        if arm == "filtered" and st.get("rf"):
                            rf_info = dict(st["rf"])
                f, u = out["filtered"], out["unfiltered"]
                med = statistics.median
                return {
                    "pairs": pairs,
                    "filter_kind": rf_info.get("kind"),
                    "filter_bytes": rf_info.get("nbytes"),
                    # observed keep-rate at the producers (the rf=
                    # sel_obs EXPLAIN field): what fraction of probe
                    # rows the build side actually admitted
                    "observed_selectivity": rf_info.get("sel_obs"),
                    "rows_dropped": rf_info.get("dropped"),
                    "bytes_filtered": med(f["bytes"]),
                    "bytes_unfiltered": med(u["bytes"]),
                    "bytes_ratio": round(
                        med(u["bytes"]) / max(med(f["bytes"]), 1), 4
                    ),
                    "encode_seconds_filtered": round(
                        med(f["encode"]), 6
                    ),
                    "encode_seconds_unfiltered": round(
                        med(u["encode"]), 6
                    ),
                    "stage_seconds_filtered": round(
                        med(f["stage"]), 6
                    ),
                    "stage_seconds_unfiltered": round(
                        med(u["stage"]), 6
                    ),
                    "seconds_filtered": round(med(f["wall"]), 6),
                    "seconds_unfiltered": round(med(u["wall"]), 6),
                    "speedup": round(
                        med(u["wall"]) / max(med(f["wall"]), 1e-9), 4
                    ),
                }
            finally:
                for sched in scheds.values():
                    sched.close()

        feedback_ab = run_feedback_pair()
        runtime_filter_ab = run_rf_pairs(pairs=max(args.repeat, 5))

        ab = run_pipeline_pairs(pairs=max(args.repeat, 5))
        dag_ab = run_dag_ab(pairs=max(args.repeat, 3))
        assert tunnel["result"] == staged["result"], "mode parity broke"
        assert tunnel_json["result"] == staged["result"], (
            "codec parity broke"
        )
        assert barrier["result"] == staged["result"], (
            "pipeline parity broke"
        )
        # pipelined vs barrier A/B (PERF_NOTES "Shuffle pipelining"):
        # same query, same codec, same workers — only the stage shape
        # differs (overlapped produce/push/decode/stage vs the four
        # sequential phases). Row counts must match exactly; tunnel
        # bytes track closely (chunked frames re-prune dictionaries
        # per chunk, so a small delta is framing overhead, not data).
        assert barrier["rows_tunneled"] == tunnel["rows_tunneled"], (
            "pipeline row parity broke"
        )
        pipe, barr = ab["pipelined"], ab["barrier"]
        pipeline_ab = {
            # stage wall-clock (the slowest worker partition's whole
            # produce->push->wait->stage->consume): what pipelining
            # actually restructures — end-to-end seconds additionally
            # carry the dispatch RPC + coordinator final merge common
            # to both modes. Medians over interleaved pairs.
            "pairs": len(pipe["wall"]),
            "stage_seconds_pipelined": round(
                statistics.median(pipe["stage"]), 6
            ),
            "stage_seconds_barrier": round(
                statistics.median(barr["stage"]), 6
            ),
            "stage_speedup": round(
                statistics.median(barr["stage"])
                / max(statistics.median(pipe["stage"]), 1e-9), 4
            ),
            "seconds_pipelined": round(
                statistics.median(pipe["wall"]), 6
            ),
            "seconds_barrier": round(
                statistics.median(barr["wall"]), 6
            ),
            "speedup": round(
                statistics.median(barr["wall"])
                / max(statistics.median(pipe["wall"]), 1e-9), 4
            ),
            "wait_idle_pipelined_s": round(pipe["idle"], 6),
            "wait_idle_barrier_s": round(barr["idle"], 6),
            "ttff_pipelined_s": round(pipe["ttff"], 6),
            "ttff_barrier_s": round(barr["ttff"], 6),
            "rows_tunneled": tunnel["rows_tunneled"],
            "bytes_pipelined": tunnel["bytes_over_tunnels"],
            "bytes_barrier": barrier["bytes_over_tunnels"],
        }
        codec_ab = {
            "bytes_binary": tunnel["bytes_over_tunnels"],
            "bytes_json": tunnel_json["bytes_over_tunnels"],
            "bytes_ratio": round(
                tunnel["bytes_over_tunnels"]
                / max(tunnel_json["bytes_over_tunnels"], 1), 4
            ),
            "encode_seconds_binary": tunnel["encode_seconds"],
            "encode_seconds_json": tunnel_json["encode_seconds"],
            "decode_seconds_binary": tunnel["decode_seconds"],
            "decode_seconds_json": tunnel_json["decode_seconds"],
        }
        nrows_lineitem = cat.table("tpch", "lineitem").nrows
        result = {
            "metric": f"multihost_shuffle_join_sf{sf:g}_rows_per_sec",
            "value": round(nrows_lineitem / tunnel["seconds"], 2),
            "unit": "rows/s",
            "vs_baseline": round(
                staged["seconds"] / tunnel["seconds"], 4
            ),
            "detail": {
                "backend": "cpu",
                "scenario": "multihost_shuffle",
                "workers": 2,
                "mesh_devices": 4,
                "sf": sf,
                "repeat": args.repeat,
                "staged": {
                    k: v for k, v in staged.items() if k != "result"
                },
                "tunneled": {
                    k: v for k, v in tunnel.items() if k != "result"
                },
                "tunneled_barrier": {
                    k: v for k, v in barrier.items() if k != "result"
                },
                "tunneled_json": {
                    k: v for k, v in tunnel_json.items() if k != "result"
                },
                "codec_ab": codec_ab,
                "pipeline_ab": pipeline_ab,
                # ISSUE 11: chained shuffle DAG vs single-cut re-scan
                # (wall + per-host scanned rows + exchange bytes)
                "dag_ab": dag_ab,
                # --racecheck: workers ran with TIDB_TPU_RACECHECK=1
                # (order-tracked locks); a worker inversion raises and
                # fails the run, so True here means the data plane ran
                # clean under the detector
                "racecheck": bool(getattr(args, "racecheck", False)),
                # the flight recorder's per-digest view of this query
                # (phase means, percentiles) — the information_schema.
                # statements_summary breakdown as the bench sees it
                "flight": flight_breakdown,
                # ISSUE 15: AQE feedback warm/cold pair — the warm
                # run's seeded cost model flips repartition to
                # broadcast (adaptive=feedback)
                "feedback_ab": feedback_ab,
                # ISSUE 19: runtime-filter on/off pairs — build-side
                # key summary drops probe rows before partition+encode
                # (tunnel bytes, encode CPU, observed selectivity)
                "runtime_filter_ab": runtime_filter_ab,
                "platform": "cpu",
            },
        }
        if timeline_on:
            # the trace PROVES the overlap claim: pipelined tasks'
            # produce/push windows intersect, the barrier escape
            # hatch's do not (per-track report from the captured
            # worker events, PERF_NOTES "reading a timeline")
            from tidb_tpu.obs.timeline import (
                TIMELINE,
                shuffle_overlap_report,
            )

            rep = shuffle_overlap_report(TIMELINE.events())
            result["detail"]["timeline"] = {
                "hosts": TIMELINE.dump()["otherData"]["hosts"],
                "events": len(TIMELINE),
                "produce_push_overlap_s_pipelined": round(max(
                    (r["produce_push_overlap_s"]
                     for r in rep.values() if r["pipeline"]),
                    default=0.0,
                ), 6),
                "produce_push_overlap_s_barrier": round(max(
                    (r["produce_push_overlap_s"]
                     for r in rep.values() if not r["pipeline"]),
                    default=0.0,
                ), 6),
            }
    finally:
        for p in workers:
            p.kill()
    _write_flight_out(args)
    _write_timeline_out(args)
    rc = 0
    _write_out(args, result)
    print(json.dumps(result))
    return rc


def measure_skew(args) -> int:
    """AQE skew ladder (ISSUE 15): a zipf-keyed join+group-by runs at
    2-3 skew exponents over a 4-server in-process fleet, interleaved
    A/B with salting armed (tidb_tpu_shuffle_skew_ratio) vs off, at
    EXACT row parity both arms. Stamps detail.aqe per rung: walls,
    max-partition received rows (the skew the salting removed),
    decisions taken. CPU data-plane scenario (in-process servers: the
    fleet shares one catalog; XLA consumer work releases the GIL, so
    hot-partition serialization is real); the result says
    "platform": "cpu"."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import statistics

    import numpy as np

    from tidb_tpu.parallel import aqe
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.parser.sqlparse import parse
    from tidb_tpu.planner.logical import build_query
    from tidb_tpu.server.engine_rpc import EngineServer
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog

    n_rows = int(50_000 * (args.sf if args.sf <= 1.0 else 0.5))
    n_keys = max(n_rows // 50, 16)
    m_hosts = 4
    rungs = (1.1, 1.5, 2.0)
    cat = Catalog()
    sess = Session(cat, db="test")
    rng = np.random.default_rng(7)
    sess.execute("create table skew_dim (k int, g int)")
    sess.execute(
        "insert into skew_dim values "
        + ",".join(f"({k},{k % 16})" for k in range(n_keys))
    )
    ladder = {}
    servers = [EngineServer(cat, port=0) for _ in range(m_hosts)]
    for s in servers:
        s.start_background()
    try:
        for z in rungs:
            # zipf-ranked keys: rank r gets mass ~ 1/r^z (clipped to
            # the key domain); z=2.0 puts ~half the rows on rank 1
            ranks = np.minimum(
                rng.zipf(z, size=n_rows), n_keys
            ).astype(np.int64) - 1
            tbl = f"skew_f_{int(z * 10)}"
            sess.execute(f"create table {tbl} (k int, v int)")
            vals = ",".join(
                f"({int(k)},{i % 97})" for i, k in enumerate(ranks)
            )
            sess.execute(f"insert into {tbl} values {vals}")
            q = (
                f"select g, count(*), sum(v) from {tbl} f "
                "join skew_dim d on f.k = d.k "
                "group by g order by g"
            )
            plan = build_query(
                parse(q)[0], cat, "test", sess._scalar_subquery
            )
            mk = lambda ratio: DCNFragmentScheduler(
                [("127.0.0.1", s.port) for s in servers],
                catalog=cat, shuffle_mode="always",
                shuffle_dag="never", shuffle_wait_timeout_s=60.0,
                shuffle_skew_ratio=ratio, shuffle_skew_salt_k=4,
            )
            scheds = {"salted": mk(1.5), "plain": mk(0.0)}
            entry = {}
            try:
                for arm in scheds.values():
                    arm.execute_plan(plan)  # compile warmup
                walls = {"salted": [], "plain": []}
                stats = {}
                ref = None
                d0 = aqe.decision_counts().get("salted", 0.0)
                for _ in range(max(args.repeat, 3)):
                    for arm, sched in scheds.items():  # interleaved
                        t0 = time.perf_counter()
                        _c, rows = sched.execute_plan(plan)
                        walls[arm].append(time.perf_counter() - t0)
                        if ref is None:
                            ref = rows
                        assert rows == ref, f"z={z} {arm} parity broke"
                        st = (sched.last_query_mine() or {}).get(
                            "shuffle", {}
                        )
                        stats[arm] = st
                for arm in scheds:
                    st = stats[arm]
                    entry[arm] = {
                        "seconds": round(
                            statistics.median(walls[arm]), 6
                        ),
                        "max_partition_rows": max(
                            st.get("part_rows") or [0]
                        ),
                        "skew": st.get("skew"),
                        "adaptive": list(st.get("adaptive") or []),
                        "salt_k": st.get("salted", 0),
                    }
                entry["salted_decisions"] = (
                    aqe.decision_counts().get("salted", 0.0) - d0
                )
                entry["speedup"] = round(
                    entry["plain"]["seconds"]
                    / max(entry["salted"]["seconds"], 1e-9), 4
                )
                entry["rows"] = len(ref)
                entry["query"] = q
            finally:
                for sched in scheds.values():
                    sched.close()
            ladder[f"z{z:g}"] = entry
    finally:
        for s in servers:
            s.shutdown()
    top = ladder[f"z{rungs[-1]:g}"]
    result = {
        "metric": f"aqe_skew_salting_n{n_rows}_rows_per_sec",
        "value": round(n_rows / top["salted"]["seconds"], 2),
        "unit": "rows/s",
        "vs_baseline": top["speedup"],
        "detail": {
            "backend": "cpu",
            "scenario": "aqe_skew_salting",
            "servers": m_hosts,
            "rows": n_rows,
            "keys": n_keys,
            "repeat": args.repeat,
            "aqe": ladder,
            "platform": "cpu",
        },
    }
    rc = 0
    _write_out(args, result)
    print(json.dumps(result))
    return rc


def measure_order_by(args) -> int:
    """Distributed ORDER BY ladder (ISSUE 11): range-partitioned
    exchanges vs the coordinator-sort baseline on a 2-worker x
    4-device CPU dryrun. Each rung runs one ORDER BY (LIMIT) query
    both ways — shuffle_dag="always" (boundary-sampled range exchange,
    per-partition sort/top-K, order-preserving concat) vs
    shuffle_mode="never" (the fragment cut ships EVERY row to the
    coordinator, which re-sorts) — at exact row parity, recording
    walls, rows shipped to the coordinator, and per-partition top-K
    row caps. CPU data-plane scenario; the result says
    "platform": "cpu"."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import re
    import statistics

    from tidb_tpu.bench import load_tpch
    from tidb_tpu.parallel.dcn import DCNFragmentScheduler
    from tidb_tpu.parser.sqlparse import parse
    from tidb_tpu.planner.logical import build_query
    from tidb_tpu.session import Session
    from tidb_tpu.storage import Catalog
    from tidb_tpu.utils.metrics import REGISTRY

    sf = args.sf if args.sf <= 1.0 else 0.02
    seed = 3
    workers = []
    try:
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        ports = []
        for _ in range(2):
            p = subprocess.Popen(
                [
                    sys.executable, "-m", "tidb_tpu.parallel.dcn_worker",
                    "--cpu", "--port", "0", "--mesh-devices", "4",
                    "--tpch-sf", str(sf), "--seed", str(seed),
                    "--tables", "orders,lineitem",
                ],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env,
            )
            workers.append(p)
            line = p.stdout.readline()
            m = re.match(r"DCN_WORKER_READY port=(\d+)", line)
            if not m:
                try:
                    rest, _ = p.communicate(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    rest = ""
                raise RuntimeError(
                    f"worker not ready: {line!r}\n{rest[-3000:]}"
                )
            ports.append(int(m.group(1)))

        cat = Catalog()
        load_tpch(cat, sf=sf, seed=seed, tables=["orders", "lineitem"])
        sess = Session(cat, db="tpch")
        #: the ladder: top-K, aggregate-then-order, and a full sort
        RUNGS = [
            ("topk",
             "select l_orderkey, l_extendedprice from lineitem "
             "order by l_extendedprice desc limit 100"),
            ("agg_topk",
             "select l_suppkey, count(*), sum(l_quantity) from "
             "lineitem group by l_suppkey "
             "order by sum(l_quantity) desc limit 10"),
            ("full_sort",
             "select l_extendedprice, l_orderkey from lineitem "
             "order by l_extendedprice"),
        ]

        def _reg_total(prefix):
            return sum(
                v for n, _k, v in REGISTRY.rows() if n.startswith(prefix)
            )

        def run_rung(name, sql):
            plan = build_query(
                parse(sql)[0], cat, "tpch", sess._scalar_subquery
            )
            scheds = {
                "range": DCNFragmentScheduler(
                    [("127.0.0.1", pt) for pt in ports],
                    catalog=cat, shuffle_mode="always",
                    shuffle_dag="always",
                ),
                "staged": DCNFragmentScheduler(
                    [("127.0.0.1", pt) for pt in ports],
                    catalog=cat, shuffle_mode="never",
                    shuffle_dag="never",
                ),
            }
            out = {}
            try:
                kind, cut = scheds["range"]._choose_cut(plan)
                assert kind == "dag", (
                    f"rung {name} did not plan a range DAG ({kind})"
                )
                ref = None
                for mode, sched in scheds.items():
                    sched.execute_plan(plan)  # warm the compiles
                    staged0 = _reg_total("tidbtpu_dcn_bytes_staged")
                    walls = []
                    rows = []
                    for _ in range(max(args.repeat, 3)):
                        t0 = time.perf_counter()
                        _cols, rows = sched.execute_plan(plan)
                        walls.append(time.perf_counter() - t0)
                    if ref is None:
                        ref = rows
                    assert rows == ref, f"rung {name} parity broke"
                    lq = sched.last_query or {}
                    entry = {
                        "seconds": round(statistics.median(walls), 6),
                        "rows": len(rows),
                        "bytes_over_coordinator": _reg_total(
                            "tidbtpu_dcn_bytes_staged"
                        ) - staged0,
                    }
                    if mode == "range":
                        st = (lq.get("shuffle_stages") or [{}])[-1]
                        frags = lq.get("fragments", [])
                        last_stage = st.get("stage", 0)
                        entry["boundaries"] = st.get("boundaries")
                        entry["max_partition_rows"] = max(
                            (
                                f.get("rows", 0) for f in frags
                                if f.get("stage", 0) == last_stage
                            ),
                            default=0,
                        )
                    out[mode] = entry
            finally:
                for sched in scheds.values():
                    sched.close()
            out["speedup_vs_staged"] = round(
                out["staged"]["seconds"]
                / max(out["range"]["seconds"], 1e-9), 4
            )
            out["query"] = sql
            return name, out

        ladder = dict(run_rung(n, s) for n, s in RUNGS)
        nrows = cat.table("tpch", "lineitem").nrows
        result = {
            "metric": f"order_by_range_exchange_sf{sf:g}_rows_per_sec",
            "value": round(
                nrows / ladder["topk"]["range"]["seconds"], 2
            ),
            "unit": "rows/s",
            "vs_baseline": ladder["topk"]["speedup_vs_staged"],
            "detail": {
                "backend": "cpu",
                "scenario": "order_by_range_exchange",
                "workers": 2,
                "mesh_devices": 4,
                "sf": sf,
                "repeat": args.repeat,
                "order_by": ladder,
                "platform": "cpu",
            },
        }
    finally:
        for p in workers:
            p.kill()
    rc = 0
    _write_out(args, result)
    print(json.dumps(result))
    return rc


def _write_inspect_out(args, detail: dict) -> None:
    """--inspect-out: snapshot detail.inspection to a JSON file."""
    from tidb_tpu.obs.inspection import write_inspect_out

    write_inspect_out(getattr(args, "inspect_out", None), detail)


def measure_chaos(args) -> int:
    """Chaos robustness scenario: N seeded composed-fault episodes
    (worker crash / hang / frame loss / delay / slow peer / tunnel
    partition / clock skew) against an in-process 2-server fleet
    running --multihost-shuffle-shaped workloads (repartition joins +
    distinct group-bys over the tunnels, grouped aggregates over the
    partial-agg cut), with the fleet invariants audited after EVERY
    episode. Stamps detail.chaos — episodes, faults injected,
    invariant violations (0 is the bar), recovery-wall p50/p95 — so
    the robustness trajectory is benchable like perf: a regression
    that slows recovery or leaks a buffer moves a number here."""
    os.environ["JAX_PLATFORMS"] = "cpu"

    from tidb_tpu.chaos import ChaosHarness

    episodes = max(int(args.chaos_episodes), 1)
    seed = int(args.chaos_seed)
    false_positive = None
    with ChaosHarness(seed=seed, wait_timeout_s=2.0) as h:
        # false-positive guard FIRST: a fault-free calibration episode
        # must not yield a critical inspection finding — a diagnosis
        # tier that alarms on a healthy fleet fails the bench before
        # any chaos is injected
        baseline_viol, (b0, b1) = h.baseline_episode()
        from tidb_tpu.obs.inspection import run_inspection

        baseline_critical = [
            f.to_dict() for f in run_inspection(t_lo=b0, t_hi=b1)
            if f.severity == "critical"
        ]
        if baseline_critical:
            false_positive = baseline_critical
        # the headline wall starts AFTER calibration: the episodes/s
        # metric must stay comparable with pre-PR-12 captures that
        # had no baseline episode or inspection pass in the window
        t0 = time.time()
        rep = h.run(episodes)
    wall = time.time() - t0
    detail = rep.to_dict()
    if baseline_viol:
        # a fleet invariant breached with NOTHING injected is a
        # stronger red flag than the same breach under faults: count
        # it into the run's violation total (which fails the bench)
        detail["invariant_violations"] += len(baseline_viol)
        detail["violations"] = (
            list(baseline_viol) + list(detail["violations"])
        )
    from tidb_tpu.obs.inspection import inspection_detail

    inspection = inspection_detail(windows=rep.windows)
    inspection["baseline_critical"] = false_positive or []
    inspection["baseline_violations"] = list(baseline_viol)
    _write_inspect_out(args, inspection)
    result = {
        "metric": f"chaos_episodes_seed{seed}_per_sec",
        "value": round(episodes / max(wall, 1e-9), 4),
        "unit": "episodes/s",
        "detail": {
            "backend": "cpu",
            "scenario": "chaos",
            "workers": 2,
            "wall_seconds": round(wall, 3),
            "chaos": detail,
            "inspection": inspection,
            "platform": "cpu",
        },
    }
    rc = 0
    _write_out(args, result)
    if detail["invariant_violations"]:
        # a violated invariant fails the run loudly — AFTER the
        # capture is written (the violating run's record is exactly
        # the artifact a robustness regression needs)
        rc = 1
    if false_positive:
        # the false-positive guard: a CRITICAL inspection finding over
        # the fault-free calibration window means the diagnosis tier
        # alarms on a healthy fleet — fail loudly, after the capture
        print(json.dumps({
            "inspection_false_positive": false_positive
        }), file=sys.stderr)
        rc = 1
    print(json.dumps(result))
    return rc


def main() -> int:
    ap = argparse.ArgumentParser()
    # SF10 default: BASELINE.md's ladder runs SF10-SF100 and the north
    # star is SF100 rows/sec/chip.
    ap.add_argument("--sf", type=float, default=10.0)
    ap.add_argument("--query", default="q1", choices=sorted(QUERIES) + ["q95"])
    # 3 repeats (median)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--quick", action="store_true", help="sf=0.01 sanity run")
    ap.add_argument("--cpu", action="store_true",
                    help="the explicit CPU run (sets JAX_PLATFORMS=cpu); "
                    "without it a run that finds no TPU exits non-zero")
    ap.add_argument("--out", default=None,
                    help="also write the result JSON to this file")
    ap.add_argument(
        "--metrics-out", default=None, metavar="FILE",
        help="snapshot the engine-metrics registry delta across the "
        "benchmarked query (jit compiles, retraces, transfer bytes, "
        "tidbtpu_* counters) to this JSON file; the delta is also "
        "stamped into detail.engine_metrics of the result",
    )
    ap.add_argument(
        "--flight-out", default=None, metavar="FILE",
        help="snapshot the query flight recorder after the run — "
        "per-query phase timelines, the per-digest statements summary "
        "(p50/p95/p99 + mean phase breakdown + engine columns) and the "
        "DCN link registry — to this JSON file (the information_schema "
        "breakdown, captured for the bench ladder)",
    )
    ap.add_argument(
        "--timeline-out", default=None, metavar="FILE",
        help="capture the fleet timeline across the run and write it "
        "as Chrome trace-event JSON (open in Perfetto / "
        "chrome://tracing): one process track per host, thread tracks "
        "per session/worker task, counter tracks from existing gauges;"
        " works in every mode incl. --serve-load and "
        "--multihost-shuffle (worker events ship back on the fenced "
        "replies, rebased through the handshake clock offsets)",
    )
    ap.add_argument(
        "--inspect-out", default=None, metavar="FILE",
        help="with --chaos or --serve-load: run the inspection engine "
        "(information_schema.inspection_result's evaluator, "
        "obs/inspection.py) over the run's sampled metric history and "
        "write the findings + evidence windows to this JSON file; "
        "detail.inspection is stamped either way. --chaos additionally "
        "exits nonzero on a critical finding over its fault-free "
        "calibration episode (false-positive guard)",
    )
    ap.add_argument(
        "--multihost-shuffle", action="store_true",
        help="run the 2-worker DCN shuffle-join dryrun instead of the "
        "single-engine ladder: measures a repartition-join query "
        "(orders JOIN lineitem GROUP BY o_orderpriority — Q18 itself "
        "pre-aggregates below the join, which removes the shuffle cut) "
        "with partial-agg coordinator staging vs direct worker-to-"
        "worker tunnels and records bytes_over_coordinator vs "
        "bytes_over_tunnels, plus the binary-vs-JSON shuffle wire "
        "codec A/B (bytes per row, encode/decode seconds — "
        "detail.codec_ab) (CPU data-plane scenario; SF capped at "
        "0.02 unless --sf <= 1)",
    )
    ap.add_argument(
        "--skew", action="store_true",
        help="AQE skew ladder (ISSUE 15): zipf-keyed join+group-by at "
        "3 skew exponents over a 4-server in-process fleet, "
        "interleaved A/B with hot-key salting armed vs off at exact "
        "row parity; stamps detail.aqe (walls, max-partition rows, "
        "decisions taken)",
    )
    ap.add_argument(
        "--order-by", action="store_true",
        help="run the distributed ORDER BY range-exchange ladder "
        "instead of the single-engine ladder: top-K / aggregate-then-"
        "order / full-sort queries each run range-partitioned "
        "(boundary-sampled exchange, per-partition sort with pushed-"
        "down top-K, order-preserving concat) vs the coordinator-sort "
        "baseline at exact parity; stamps detail.order_by (CPU "
        "data-plane scenario; SF capped at 0.02 unless --sf <= 1)",
    )
    ap.add_argument(
        "--serve-load", action="store_true",
        help="run the serving-tier load scenario instead of the "
        "single-engine ladder: N concurrent MySQL-protocol sessions "
        "(--serve-sessions) drive a mixed HIGH_PRIORITY/LOW_PRIORITY "
        "workload through one coordinator Server routing across a "
        "worker fleet with admission control; reports p50/p99 latency "
        "per class + fleet queries/sec, proves >= 2 sessions' "
        "fragments overlap (flight timelines), shared-plan-cache "
        "cross-session hits > 0, and kill-a-worker-under-load "
        "recovery (CPU data-plane scenario)",
    )
    ap.add_argument("--serve-sessions", type=int, default=64,
                    help="concurrent MySQL-protocol sessions (>= 64 "
                    "for the acceptance run)")
    ap.add_argument("--serve-statements", type=int, default=6,
                    help="statements per session")
    ap.add_argument("--serve-workers", type=int, default=2,
                    help="worker processes in the fleet")
    ap.add_argument("--serve-pool-size", type=int, default=4,
                    help="control connections per worker host")
    ap.add_argument("--serve-budget-mb", type=int, default=2048,
                    help="fleet device-memory admission budget (MiB)")
    ap.add_argument("--write-mix", action="store_true",
                    help="with --serve-load: a concurrent writer "
                    "session streams INSERTs through the HTAP delta "
                    "tier (read-your-writes verified per commit) while "
                    "reader sessions run both freshness modes; stamps "
                    "detail.delta (depth, per-host sync lag, "
                    "read-your-writes vs bounded-staleness p99)")
    ap.add_argument("--serve-kill-worker", action="store_true",
                    default=True,
                    help="hard-kill one worker mid-load (default on; "
                    "--no-serve-kill-worker disables)")
    ap.add_argument("--no-serve-kill-worker", dest="serve_kill_worker",
                    action="store_false")
    ap.add_argument(
        "--chaos", action="store_true",
        help="run the chaos robustness scenario instead of the "
        "single-engine ladder: N seeded composed-fault episodes "
        "(crash/hang/frame loss/delay/slow peer/tunnel partition/"
        "clock skew) over an in-process 2-server fleet, auditing "
        "fleet invariants after every episode; stamps detail.chaos "
        "(episodes, faults, invariant violations, recovery-wall "
        "p50/p95). A violated invariant exits nonzero.",
    )
    ap.add_argument("--chaos-episodes", type=int, default=20,
                    help="episodes per chaos run")
    ap.add_argument("--chaos-seed", type=int, default=1,
                    help="schedule seed (the same seed replays the "
                    "same fault schedule exactly)")
    ap.add_argument(
        "--racecheck", action="store_true",
        help="with --multihost-shuffle: run the worker processes under "
        "TIDB_TPU_RACECHECK=1 (order-tracked locks, utils/racecheck.py)"
        " and stamp detail.racecheck so the capture proves the data "
        "plane ran clean under the lock-order detector",
    )
    args = ap.parse_args()
    if args.quick:
        args.sf = 0.01
    if args.serve_load:
        from tidb_tpu.bench.serve_load import run_serve_load

        # the serving scenario picks its own dryrun scale cap
        if args.sf == 10.0:  # the ladder default is not a dryrun scale
            args.sf = 0.005
        return run_serve_load(args)
    if args.chaos:
        return measure_chaos(args)
    if args.multihost_shuffle:
        return measure_multihost_shuffle(args)
    if args.skew:
        return measure_skew(args)
    if args.order_by:
        return measure_order_by(args)

    return measure(args)


if __name__ == "__main__":
    sys.exit(main())
