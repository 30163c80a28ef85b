#!/usr/bin/env python
"""Chip smoke: the system's main path, once, on the TPU, checked.

One process, one chip (`python chip_smoke.py`): requires a TPU, builds
the TPC-H SF1 catalog with the bootstrap `tidb_server.main` uses, starts
`tidb_tpu.server.Server` on an ephemeral port in a thread of this same
process, and drives it over a real socket with the dependency-free
MySQL client: ANALYZE, then Q6, Q1, Q18, Q5 cold (with compile) then
warm, each answer compared with the numpy oracle of bench.py, then an
INSERT that is acknowledged and must be read back by Q6. Before that,
in seconds, the dense aggregate's slot compaction alone against numpy
(`compaction_phase`; alone: `python -c "import chip_smoke as c;
c.compaction_phase(1)"`).

`--mesh 4` (four chips, run by hand) runs ONLY the multi-chip path and
what it is compared with: Q1, Q18, Q5 over a socket of the server that
`tidb_server.bootstrap` builds with `mesh_devices=4`, against a
one-device server on the same catalog and the oracle, shows that
scanned columns hold one shard on each of four devices, and that the
compiled repartition join contains an all-to-all.

Every line of output is one JSON object; the last is
{"ok": true, "device": {"platform", "kind", "count"}}. Any failed check
raises, so the script exits non-zero at once and prints no such line.
It sets no JAX_PLATFORMS, starts no child process, calls no git, needs
no network, and makes all data from --seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from decimal import Decimal


TABLES = (
    "lineitem", "orders", "customer", "supplier", "nation", "region",
    "part", "partsupp",
)


def require(ok, *what) -> None:
    """A check that raises (an `assert` would vanish under -O)."""
    if not ok:
        raise AssertionError(what)


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


class CompileMeter:
    """XLA compile activity from JAX's own monitoring events: seconds
    inside backend compile (persistent-cache retrieval included),
    compile requests, and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as M

        self.secs = 0.0
        self.requests = 0
        self.cache_hits = 0
        M.register_event_duration_secs_listener(self._on_duration)
        M.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.requests += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.secs, self.requests, self.cache_hits)

    def since(self, snap) -> dict:
        return {
            "backend_compile_s": round(self.secs - snap[0], 3),
            "compile_requests": self.requests - snap[1],
            "persistent_cache_hits": self.cache_hits - snap[2],
        }


class LoweringCounter:
    """Which lowering a statement's trace went through: counts calls of
    the formulations' entry points while programs are traced
    (tracing runs in this process, on the serving thread)."""

    SITES = {
        "sorted_agg": ("tidb_tpu.executor.sortops", "sort_group_aggregate"),
        "dense_reducer": ("tidb_tpu.executor.aggregate", "_DenseReducer"),
        "merge_probe": ("tidb_tpu.executor.sortops", "merge_searchsorted"),
        "sorted_join_build": ("tidb_tpu.executor.join", "_sort_build"),
        # one per unique-build join that emits a smaller tile than it
        # probes, and one for the steady program's own output
        "gather_compact": ("tidb_tpu.executor.sortops", "compaction_index"),
        # one a side of every inner/left join's output tile, and one
        # for a sorted unique lookup's reads
        "stacked_gather": ("tidb_tpu.executor.sortops", "gather_rows"),
    }

    def __init__(self):
        import importlib

        self.counts = {k: 0 for k in self.SITES}
        for key, (mod, name) in self.SITES.items():
            m = importlib.import_module(mod)
            setattr(m, name, self._wrap(key, getattr(m, name)))

    def _wrap(self, key, fn):
        def counted(*a, **k):
            self.counts[key] += 1
            return fn(*a, **k)

        return counted

    def take(self) -> dict:
        out, self.counts = self.counts, {k: 0 for k in self.SITES}
        return {k: v for k, v in out.items() if v}


def jit_compilations() -> float:
    from tidb_tpu.utils.metrics import REGISTRY

    return sum(
        v for name, _kind, v in REGISTRY.rows()
        if name == "tidbtpu_engine_jit_compilations"
    )


# ---------------------------------------------------------------------------
# oracle: bench.py's numpy kernels over the host-resident columns
# ---------------------------------------------------------------------------


class Oracle:
    def __init__(self, cat):
        import numpy as np

        import bench
        from tidb_tpu.dtypes import date_to_days

        self.np, self.bench, self.cat = np, bench, cat
        self._li = (None, None)  # (table version, concatenated columns)
        self.cutoff = int(date_to_days("1998-12-01")) - 90
        self.d0 = int(date_to_days("1994-01-01"))
        self.d1 = int(date_to_days("1995-01-01"))
        self.wide_dev = 0.0  # largest deviation seen in _wide_sum

    def _wide_sum(self, got: int, want: int, what) -> None:
        """A decimal sum the engine accumulates in float64 by design
        (AggDesc.wide: products of scale >= 4). The TPU emulates float64
        short of 53 bits, and a mesh adds per-shard partials, so the
        last digits may differ from the exact integer sum: relative
        1e-13 — any lost or doubled row is orders of magnitude more."""
        dev = abs(got - want) / max(abs(want), 1)
        self.wide_dev = max(self.wide_dev, dev)
        require(dev <= 1e-13, (what, got, want, dev))

    def _lineitem(self) -> dict:
        np = self.np
        table = self.cat.table("tpch", "lineitem")
        if self._li[0] != table.version:
            blocks = table.blocks()
            cols = (
                "l_shipdate l_returnflag l_linestatus l_quantity "
                "l_extendedprice l_discount l_tax l_orderkey".split()
            )
            self._li = (table.version, {
                c: np.concatenate([b.columns[c].data for b in blocks])
                for c in cols
            })
        return self._li[1]

    def q6(self) -> int:
        return int(self.bench.numpy_q6(self.np, self._lineitem(), self.d0, self.d1))

    def check_q6(self, rows, want=None) -> int:
        want = self.q6() if want is None else want
        require(len(rows) == 1, rows)
        got = int(Decimal(rows[0][0]) * 10**4)
        require(got == want, ("q6", got, want))
        return want

    def check_q1(self, rows) -> None:
        np = self.np
        li = self.cat.table("tpch", "lineitem")
        want = self.bench.numpy_q1(np, self._lineitem(), self.cutoff)
        rf_codes = {v: i for i, v in enumerate(li.dictionaries["l_returnflag"])}
        ls_codes = {v: i for i, v in enumerate(li.dictionaries["l_linestatus"])}
        groups = int((want["cnt"] > 0).sum())
        require(len(rows) == groups, ("q1 groups", len(rows), groups))
        require([r[:2] for r in rows] == sorted(r[:2] for r in rows), "q1 order")
        for r in rows:
            k = rf_codes[r[0]] * 2 + ls_codes[r[1]]
            # integer-accumulated sums are exact; the charge sum passes
            # 2^53 at SF1 and the averages are floats: relative 1e-9
            require(int(Decimal(r[2]) * 100) == int(want["sum_qty"][k]), ("q1 qty", r))
            require(int(Decimal(r[3]) * 100) == int(want["sum_base"][k]), ("q1 base", r))
            self._wide_sum(
                int(Decimal(r[4]) * 10**4), int(want["sum_disc"][k]), ("q1 disc", r)
            )
            for got, exp in (
                (float(r[5]) * 1e6, want["sum_charge"][k]),
                (float(r[6]) * 100, want["avg_qty"][k]),
                (float(r[7]) * 100, want["avg_base"][k]),
            ):
                require(abs(got - exp) <= 1e-9 * abs(exp), ("q1 float", r, exp))
            require(int(r[9]) == int(want["cnt"][k]), ("q1 count", r))

    def check_q18(self, rows) -> None:
        keys, sums = self.bench.numpy_q18(self.np, self._lineitem(), 30000)
        want = {int(k): int(s) for k, s in zip(keys, sums)}
        require(want, "q18 oracle is empty: the check would be vacuous")
        got = [(int(r[0]), int(Decimal(r[1]) * 100)) for r in rows]
        require(len(got) == min(len(want), 100), ("q18 rows", len(got), len(want)))
        require(all(want.get(k) == s for k, s in got), "q18 pair not in oracle")
        require([s for _k, s in got] == sorted(want.values(), reverse=True)[:100])

    def check_q5(self, rows) -> None:
        np = self.np
        rev = self.bench.numpy_q5(np, self.cat, self.d0, self.d1)
        nat = self.cat.table("tpch", "nation").blocks()[0].columns
        names = np.asarray(nat["n_name"].dictionary, dtype=object)[nat["n_name"].data]
        want = sorted(
            (
                (str(names[i]), int(rev[k]))
                for i, k in enumerate(nat["n_nationkey"].data)
                if rev[k] > 0
            ),
            key=lambda t: -t[1],
        )
        got = [(r[0], int(Decimal(r[1]) * 10**4)) for r in rows]
        require(want and [g[0] for g in got] == [w[0] for w in want], ("q5", got, want))
        for (name, g), (_n, w) in zip(got, want):
            self._wide_sum(g, w, ("q5", name))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def compaction_phase(seed: int) -> None:
    """The last step of a dense-domain aggregate alone, against numpy:
    16 slots of int64 values handed in as parameters, the occupied ones
    (Q1's four first, then drawn) compacted into a 16-row tile, so 12
    indices are out of range. `aggregate._compact_slots`, the select and
    sum the executor runs, must be exact. The int64 `.at[pos].set(...,
    mode="drop")` it replaced returned one element wrong inside Q1's SF10
    program (PERF.md PR 34) and is reported beside it, not required: the
    bare kernel has not shown the fault, and nothing runs it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tidb_tpu.chunk import DevCol
    from tidb_tpu.executor.aggregate import _compact_slots

    slots = 16

    def select_sum(vals, occupied):
        c = _compact_slots({"v": DevCol(vals, occupied)}, occupied, slots)["v"]
        return c.data, c.valid

    def drop_scatter(vals, occupied):
        pos = jnp.where(occupied, jnp.cumsum(occupied.astype(jnp.int32)) - 1, slots)
        return (jnp.zeros(slots, jnp.int64).at[pos].set(vals, mode="drop"),
                jnp.zeros(slots, bool).at[pos].set(occupied, mode="drop"))

    forms = {"select_sum": jax.jit(select_sum), "drop_scatter": jax.jit(drop_scatter)}
    rng = np.random.default_rng(seed)
    q1 = np.zeros(slots, bool)
    q1[[5, 6, 7, 10]] = True  # (A,F) (N,F) (R,F) (N,O) as (flag+1) | (status+1) << 2
    wrong = dict.fromkeys(forms, 0)
    trials = 32
    for t in range(trials):
        occupied = q1 if t == 0 else rng.random(slots) < rng.random()
        # sums of Q1's size at SF10, halves that straddle 2**31, the extremes
        vals = rng.integers(-(1 << 62), 1 << 62, slots, dtype=np.int64)
        vals[::4] = rng.integers(1 << 47, 1 << 50, len(vals[::4]), dtype=np.int64)
        vals[1::4] = (vals[1::4] & ~np.int64(0xFFFFFFFF)) | np.int64((1 << 31) - 655_360)
        vals[int(rng.integers(slots))] = np.iinfo(np.int64).min
        vals[int(rng.integers(slots))] = np.iinfo(np.int64).max
        k = int(occupied.sum())
        want = np.zeros(slots, np.int64)
        want[:k] = vals[occupied]
        for name, fn in forms.items():
            data, valid = fn(jnp.asarray(vals), jnp.asarray(occupied))
            ok = np.array_equal(np.asarray(data)[:k], want[:k]) and np.array_equal(
                np.asarray(valid), np.arange(slots) < k)
            wrong[name] += not ok
    emit(phase="compaction", slots=slots, trials=trials,
         select_sum_wrong=wrong["select_sum"], drop_scatter_wrong=wrong["drop_scatter"])
    require(wrong["select_sum"] == 0, "the executor's slot compaction is wrong", wrong)


def serve_phase(args, meter: CompileMeter, lowerings: LoweringCounter) -> None:
    import jax

    import bench
    import tidb_server
    from tidb_tpu.bench.serve_load import MysqlClient
    from tidb_tpu.planner.streamed import _device_budget
    from tidb_tpu.utils.config import Config

    t0 = time.perf_counter()
    cat, srv = tidb_server.bootstrap(
        Config().override(port=0), tpch_sf=args.sf, seed=args.seed
    )
    li = cat.table("tpch", "lineitem")
    emit(phase="load", sf=args.sf, seed=args.seed, lineitem_rows=li.nrows,
         orders_rows=cat.table("tpch", "orders").nrows,
         seconds=round(time.perf_counter() - t0, 2),
         device_budget_bytes=_device_budget())
    srv.start_background()
    oracle = Oracle(cat)
    checks = {
        "q6": oracle.check_q6, "q1": oracle.check_q1,
        "q18": oracle.check_q18, "q5": oracle.check_q5,
    }
    client = MysqlClient(srv.port, timeout_s=1100.0)
    try:
        client.query("use tpch")
        # what a user does after a bulk load, and what bench.py does:
        # without statistics the planner over-sizes Q5's joins past the
        # device budget and the streamed path answers instead of the
        # resident one. Every table, so the server's own auto-analyze
        # (a background thread, 30 s ticks) finds nothing left to do in
        # the middle of the run.
        snap, t0 = meter.snapshot(), time.perf_counter()
        for table in TABLES:
            client.query(f"analyze table {table}")
        emit(phase="serve", statement="analyze", tables=len(TABLES),
             seconds=round(time.perf_counter() - t0, 2), **meter.since(snap))

        def run(name, sql, check, passno):
            snap, jits = meter.snapshot(), jit_compilations()
            t0 = time.perf_counter()
            rows = client.query(sql)  # returns when the rows are fetched
            secs = time.perf_counter() - t0
            check(rows)  # the oracle runs outside the timing
            stat = meter.since(snap)
            stat["engine_jit_compilations"] = int(jit_compilations() - jits)
            emit(phase="serve", statement=name, passno=passno,
                 seconds=round(secs, 4), rows=len(rows), correct=True,
                 lowerings=lowerings.take(), **stat)
            return stat

        for passno in ("cold", "warm"):
            for name in ("q6", "q1", "q18", "q5"):
                stat = run(name, bench.QUERIES[name], checks[name], passno)
                if passno == "warm":
                    # the engine's own count decides; JAX's process-wide
                    # compile requests are printed beside it (a
                    # background thread's would land there too)
                    require(stat["engine_jit_compilations"] == 0, (name, stat))

        # one acknowledged write, read back: rows inside Q6's predicate
        before = oracle.q6()
        sm, si = li.dictionaries["l_shipmode"][0], li.dictionaries["l_shipinstruct"][0]
        new = [
            (6_000_001 + i, 1 + i, 1 + i, 1, 10 + i, 1000_00 + 37 * i, 5 + i % 3, 2)
            for i in range(5)
        ]
        values = ", ".join(
            f"({ok}, {pk}, {sk}, {ln}, {q}.00, {p // 100}.{p % 100:02d}, "
            f"0.0{d}, 0.0{t}, 'N', 'O', '1994-06-1{i}', '1994-06-2{i}', "
            f"'1994-07-0{i + 1}', '{sm}', '{si}')"
            for i, (ok, pk, sk, ln, q, p, d, t) in enumerate(new)
        )
        t0 = time.perf_counter()
        client.query(f"insert into lineitem values {values}")
        emit(phase="serve", statement="insert", rows_inserted=len(new),
             seconds=round(time.perf_counter() - t0, 4), acknowledged=True)
        want = before + sum(p * d for (_o, _p, _s, _l, _q, p, d, _t) in new)
        require(oracle.q6() == want, "oracle on the changed table")
        run("q6_after_insert", bench.QUERIES["q6"],
            lambda rows: oracle.check_q6(rows, want), "read-back")
    finally:
        client.close()
        srv.shutdown()
    ms = jax.devices()[0].memory_stats() or {}
    emit(phase="serve", peak_bytes_in_use=ms.get("peak_bytes_in_use"),
         bytes_limit=ms.get("bytes_limit"),
         wide_sum_max_rel_dev=oracle.wide_dev)


def mesh_phase(args, meter: CompileMeter) -> None:
    """Four chips: Q1/Q18/Q5 over a socket of the server that
    `tidb_server.bootstrap` builds with `mesh_devices`, against a
    one-device server on the same catalog and the oracle; shard
    placement; all-to-all in the compiled join."""
    import jax

    import bench
    import tidb_server
    import tidb_tpu.obs.engine_watch as EW
    import tidb_tpu.planner.physical as PH
    from tidb_tpu.bench.serve_load import MysqlClient
    from tidb_tpu.parallel.mesh import shared_mesh
    from tidb_tpu.server import Server
    from tidb_tpu.storage import scan_table
    from tidb_tpu.utils.config import Config

    n = args.mesh
    t0 = time.perf_counter()
    cat, meshed = tidb_server.bootstrap(
        Config().override(port=0, mesh_devices=n), tpch_sf=args.sf, seed=args.seed
    )
    require(meshed.mesh_devices == n, meshed.mesh_devices)
    emit(phase="load", sf=args.sf, seed=args.seed,
         lineitem_rows=cat.table("tpch", "lineitem").nrows,
         seconds=round(time.perf_counter() - t0, 2))
    single = Server(cat, port=0)
    oracle = Oracle(cat)
    checks = {"q1": oracle.check_q1, "q18": oracle.check_q18, "q5": oracle.check_q5}

    # keep each steady program of the mesh server (callable + inputs)
    # so its compiled text can be read back after the run
    steady: dict = {}
    current = [None]
    real_jit = EW.watched_jit

    def keeping_jit(fn, sig=None, **kw):
        run = real_jit(fn, sig=sig, **kw)
        if not (isinstance(sig, tuple) and sig[0] == "steady"):
            return run

        def call(*a):
            if current[0] is not None:
                steady[current[0]] = (fn, a)
            return run(*a)

        return call

    # the executor binds the name at import, the streamed paths on use
    EW.watched_jit = PH.watched_jit = keeping_jit
    clients = {}
    try:
        for label, srv in (("single", single), ("mesh", meshed)):
            srv.start_background()
            clients[label] = MysqlClient(srv.port, timeout_s=1100.0)
            clients[label].query("use tpch")
            clients[label].query(f"set tidb_mem_quota_query = {64 << 30}")
        snap, t0 = meter.snapshot(), time.perf_counter()
        for table in TABLES:  # statistics live on the catalog: both servers plan from them
            clients["single"].query(f"analyze table {table}")
        emit(phase="mesh", statement="analyze", tables=len(TABLES),
             seconds=round(time.perf_counter() - t0, 2), **meter.since(snap))
        for name in ("q1", "q18", "q5"):
            sql = bench.QUERIES[name]
            out = {}
            for label in ("single", "mesh"):
                current[0] = name if label == "mesh" else None
                snap = meter.snapshot()
                t0 = time.perf_counter()
                rows = clients[label].query(sql)
                cold = time.perf_counter() - t0
                t0 = time.perf_counter()
                rows2 = clients[label].query(sql)
                warm = time.perf_counter() - t0
                require(rows == rows2, (name, label, "cold != warm"))
                out[label] = rows
                checks[name](rows)
                emit(phase="mesh", statement=name, server=label,
                     devices=n if label == "mesh" else 1,
                     cold_s=round(cold, 4), warm_s=round(warm, 4),
                     rows=len(rows), correct=True,
                     wide_sum_max_rel_dev=oracle.wide_dev, **meter.since(snap))
            same_rows(name, out["mesh"], out["single"])
    finally:
        current[0] = None
        EW.watched_jit = PH.watched_jit = real_jit
        for client in clients.values():
            client.close()
        meshed.shutdown()
        single.shutdown()

    # the work is really spread: one addressable shard of each scanned
    # column on each of n distinct devices (the server's one mesh and
    # its resident shards)
    batch, _d = scan_table(
        cat.table("tpch", "lineitem"), ["l_orderkey", "l_quantity"],
        mesh=shared_mesh(n),
    )
    for cname, col in batch.cols.items():
        devs = sorted(str(s.device) for s in col.data.addressable_shards)
        shapes = {tuple(s.data.shape) for s in col.data.addressable_shards}
        require(len(set(devs)) == n and len(devs) == n, (cname, devs))
        require(shapes == {(col.data.shape[0] // n,)}, (cname, shapes))
        emit(phase="mesh", column=cname, shard_devices=devs,
             shard_rows=col.data.shape[0] // n)

    # the repartition join (Q18: lineitem x orders on the order key)
    # compiled for the mesh moves rows with an all-to-all
    fn, a = steady["q18"]
    txt = jax.jit(fn).lower(*a).compile().as_text()
    n_a2a = txt.count("all-to-all")
    require(n_a2a > 0, "no all-to-all in the compiled Q18 mesh program")
    emit(phase="mesh", statement="q18", all_to_all_ops=n_a2a,
         all_gather_ops=txt.count("all-gather"))


def same_rows(name, a, b) -> None:
    """Mesh answer == one-device answer, as text off the wire: exact
    but for the float-accumulated columns (another summation order:
    relative 1e-9) and for which of several tied orders Q18's LIMIT
    keeps (its sums must still agree)."""
    if name == "q18":
        a, b = [r[1:] for r in a], [r[1:] for r in b]
    require(len(a) == len(b), (name, len(a), len(b)))
    for ra, rb in zip(a, b):
        for x, y in zip(ra, rb):
            if x != y:
                require(x is not None and y is not None, (name, ra, rb))
                require(abs(float(x) - float(y)) <= 1e-9 * abs(float(y)), (name, ra, rb))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1; 10 by hand)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run ONLY the N-chip mesh phase and its comparison")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    from importlib import metadata

    import jaxlib

    import tidb_tpu  # noqa: F401  (enables x64 for the engine)
    from tidb_tpu.utils.backend import enable_compile_cache

    cache_dir = enable_compile_cache()
    from jax.extend import backend as _jb

    emit(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=metadata.version("libtpu"),
         platform_version=_jb.get_backend().platform_version,
         device_kind=dev.device_kind, devices=len(jax.devices()),
         compile_cache_dir=cache_dir)
    meter = CompileMeter()
    t0 = time.perf_counter()
    if args.mesh:
        require(len(jax.devices()) >= args.mesh, (len(jax.devices()), args.mesh))
        mesh_phase(args, meter)
        count = args.mesh
    else:
        compaction_phase(args.seed)
        serve_phase(args, meter, LoweringCounter())
        count = len(jax.devices())
    total = meter.since((0.0, 0, 0))
    emit(phase="done", seconds=round(time.perf_counter() - t0, 1), **total)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": count},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
