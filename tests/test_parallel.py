"""Mesh runtime tests on the virtual 8-device CPU mesh (reference model:
unistore's in-proc MPP exchange tests — full shuffle without a cluster,
SURVEY.md §4 "multi-node without a cluster")."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tidb_tpu.chunk import Batch, DevCol, HostBlock, block_to_batch, column_from_values
from tidb_tpu.dtypes import INT64
from tidb_tpu.executor import AggDesc, group_aggregate
from tidb_tpu.parallel import (
    broadcast_join,
    distributed_group_aggregate,
    hash_repartition,
    make_mesh,
    partitioned_join,
    shard_batch,
)
from tidb_tpu.parallel.mesh import shard_map

N = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N
    return make_mesh(N)


def make_global_batch(n_rows, n_keys, seed=0, cap_per_dev=256):
    rng = np.random.default_rng(seed)
    g = rng.integers(0, n_keys, n_rows).astype(np.int64)
    v = rng.integers(0, 100, n_rows).astype(np.int64)
    block = HostBlock.from_columns(
        {
            "g": column_from_values(g.tolist(), INT64),
            "v": column_from_values(v.tolist(), INT64),
        }
    )
    batch = block_to_batch(block, cap_per_dev * N)
    return batch, g, v


def colfn(n):
    return lambda b: b.cols[n]


class TestRepartition:
    def test_preserves_rows_and_colocates(self, mesh):
        batch, g, v = make_global_batch(1000, 16)
        sharded = shard_batch(batch, mesh)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("d"), out_specs=(P("d"), P())
        )
        def step(b):
            out, dropped, need = hash_repartition(b, colfn("g"), N, 512)
            return out, dropped

        out, dropped = step(sharded)
        assert int(dropped) == 0
        rv = np.asarray(out.row_valid)
        gd = np.asarray(out.cols["g"].data)
        vd = np.asarray(out.cols["v"].data)
        # all rows survive with their values
        got = sorted(zip(gd[rv].tolist(), vd[rv].tolist()))
        exp = sorted(zip(g.tolist(), v.tolist()))
        assert got == exp
        # equal keys land on one device
        per_dev = np.asarray(out.row_valid).reshape(N, -1)
        gd2 = gd.reshape(N, -1)
        seen = {}
        for d in range(N):
            for key in np.unique(gd2[d][per_dev[d]]):
                assert seen.setdefault(int(key), d) == d

    def test_overflow_detected(self, mesh):
        batch, g, v = make_global_batch(1000, 1)  # all rows to one device
        sharded = shard_batch(batch, mesh)

        def step(bucket):
            @jax.jit
            @functools.partial(
                shard_map,
                mesh=mesh, in_specs=P("d"), out_specs=(P("d"), P(), P()),
            )
            def run(b):
                return hash_repartition(b, colfn("g"), N, bucket)

            return run(sharded)

        _out, dropped, need = step(64)
        assert int(dropped) > 0
        # the region-balance analog: the exchange reports the TRUE
        # hot-bucket size, the most one shard sends one other (the
        # first three of the eight 256-row shards are full), so the
        # host retries at the exact capacity, and nothing is dropped
        assert int(need) == 256
        out, dropped, need = step(int(need))
        assert int(dropped) == 0 and int(need) == 256
        assert int(np.sum(np.asarray(out.row_valid))) == 1000


    @pytest.mark.parametrize("ncols", [3, 40])
    def test_narrow_columns_and_nulls_cross_the_exchange(self, mesh, ncols):
        """The send buffers hold nothing under 32 bits (the v5e compiler
        takes 10-15 s for every large 8-bit scatter, PERF.md PR 29):
        validity travels as bits of u32 words, 31 columns a word, and a
        bool or int8 column widened. What arrives is what was sent."""
        rng = np.random.default_rng(ncols)
        rows, cap = 1500, 256 * N
        kinds = [np.int64, np.bool_, np.int8, np.float64, np.int32]
        host = {"g": (rng.integers(0, 50, cap).astype(np.int64), rng.random(cap) < 0.9)}
        for i in range(ncols - 1):
            dt = kinds[i % len(kinds)]
            data = (rng.random(cap) < 0.5) if dt == np.bool_ else (
                rng.integers(-100, 100, cap).astype(dt))
            host[f"c{i}"] = (data, rng.random(cap) < 0.8)
        row_valid = np.arange(cap) < rows
        batch = Batch(
            {n: DevCol(jnp.asarray(d), jnp.asarray(v)) for n, (d, v) in host.items()},
            jnp.asarray(row_valid),
        )
        step = jax.jit(shard_map(
            lambda b: hash_repartition(b, colfn("g"), N, 128)[:2],
            mesh=mesh, in_specs=P("d"), out_specs=(P("d"), P()),
        ))
        sharded = shard_batch(batch, mesh)
        out, dropped = step(sharded)
        assert int(dropped) == 0
        rv = np.asarray(out.row_valid)
        assert int(rv.sum()) == rows

        def as_rows(cols, mask):
            arrays = []
            for n in host:
                d, v = (np.asarray(x)[mask] for x in cols[n])
                assert np.asarray(cols[n][0]).dtype == host[n][0].dtype, n
                arrays += [np.where(v, d, 0).astype(np.float64), v.astype(np.float64)]
            return sorted(map(tuple, np.stack(arrays, axis=1).tolist()))

        got = as_rows({n: (c.data, c.valid) for n, c in out.cols.items()}, rv)
        assert got == as_rows(host, row_valid)
        # the send buffers are gathers through the bucket sort's
        # permutation: a scatter pays 69 ns an input row on the v5e
        # (PERF.md, PR 30)
        assert "stablehlo.scatter" not in step.lower(sharded).as_text()


def _np_mix_hash(x):
    """exchange._mix_hash on the host: wrapping int64 arithmetic."""
    with np.errstate(over="ignore"):
        h = x.astype(np.int64) * np.int64(-7046029254386353131)
        h = h ^ (h >> 29)
        h = h * np.int64(-4658895280553007687)
        h = h ^ (h >> 32)
    return h & np.int64(0x7FFFFFFFFFFFFFFF)


def _np_range_targets(rank, row_valid, n):
    """range_repartition's sample sort on the host: n local quantiles a
    shard, the gathered candidates' n-1 global cut points."""
    cap = rank.shape[0] // n
    samples = []
    for s in range(n):
        ok = row_valid[s * cap:(s + 1) * cap]
        srt = np.sort(np.where(ok, rank[s * cap:(s + 1) * cap], np.inf))
        pos = np.clip((np.arange(1, n + 1) * int(ok.sum())) // (n + 1), 0, cap - 1)
        samples.append(srt[pos])
    allsamp = np.sort(np.concatenate(samples))
    m = allsamp.shape[0]
    splitters = allsamp[np.clip((np.arange(1, n) * m) // n, 0, m - 1)]
    return np.where(row_valid, np.searchsorted(splitters, rank, side="right"), n)


def _np_exchange(host, row_valid, target, n, B):
    """The exchange in plain numpy. Device d receives, from each source
    shard s in turn, the rows s holds for d in row order, in B slots:
    the first B of them, then empty slots (row not valid, every column
    NULL, data zero). Returns (columns, row_valid, dropped, need), the
    arrays as the n devices' buffers end to end."""
    cap = row_valid.shape[0] // n
    rv = np.zeros((n, n, B), dtype=bool)
    cols = {
        name: (np.zeros((n, n, B), dtype=d.dtype), np.zeros((n, n, B), dtype=bool))
        for name, (d, _v) in host.items()
    }
    dropped = need = 0
    for s in range(n):
        rows = np.arange(s * cap, (s + 1) * cap)
        for d in range(n):
            mine = rows[target[rows] == d]
            need = max(need, len(mine))
            dropped += max(len(mine) - B, 0)
            mine = mine[:B]
            rv[d, s, :len(mine)] = True
            for name, (data, valid) in host.items():
                cols[name][0][d, s, :len(mine)] = data[mine]
                cols[name][1][d, s, :len(mine)] = valid[mine]
    flat = {name: (d.reshape(-1), v.reshape(-1)) for name, (d, v) in cols.items()}
    return flat, rv.reshape(-1), dropped, need


EXCHANGE_CASES = {
    # name: (bucket tile, share of the rows on one hot key, shard emptied)
    "balanced": (64, 0.0, None),
    "hot-key-overflows-the-tile": (64, 0.6, None),
    "an-empty-shard": (64, 0.0, 3),
    "tile-larger-than-any-bucket-and-the-shard": (512, 0.0, None),
}


@pytest.mark.parametrize("kind", ["hash", "range"])
@pytest.mark.parametrize("case", sorted(EXCHANGE_CASES))
def test_the_exchange_is_its_numpy_reference(mesh, case, kind):
    """Every slot of every device's receive buffer, `dropped` and `need`
    against _np_exchange: rows in source-then-row order, a row past its
    bucket's tile dropped and counted, NULL keys on device 0, empty
    slots all zero and not valid."""
    from tidb_tpu.parallel.exchange import range_repartition

    B, hot, empty = EXCHANGE_CASES[case]
    rng = np.random.default_rng(len(case))
    cap = 256 * N
    key = rng.integers(0, 1000, cap).astype(np.int64)
    key[rng.random(cap) < hot] = 7
    rank = np.where(rng.random(cap) < hot, 0.5, rng.random(cap))
    row_valid = rng.random(cap) < 0.7
    if empty is not None:
        row_valid[empty * 256:(empty + 1) * 256] = False
    host = {
        "k": (key, rng.random(cap) < 0.9),
        "r": (rank, np.ones(cap, dtype=bool)),
        "f": (rng.random(cap) < 0.5, rng.random(cap) < 0.8),
        "t": (rng.integers(-100, 100, cap).astype(np.int8), rng.random(cap) < 0.8),
        "i": (rng.integers(-1 << 30, 1 << 30, cap).astype(np.int32), rng.random(cap) < 0.8),
    }
    if kind == "hash":
        target = np.where(host["k"][1], _np_mix_hash(key) % N, 0)
        target = np.where(row_valid, target, N)
    else:
        target = _np_range_targets(rank, row_valid, N)

    def fn(b):
        if kind == "hash":
            return hash_repartition(b, colfn("k"), N, B)
        return range_repartition(b, b.cols["r"].data, N, B)

    batch = Batch(
        {n: DevCol(jnp.asarray(d), jnp.asarray(v)) for n, (d, v) in host.items()},
        jnp.asarray(row_valid),
    )
    step = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=P("d"), out_specs=(P("d"), P(), P())
    ))
    out, dropped, need = step(shard_batch(batch, mesh))
    want, want_rv, want_dropped, want_need = _np_exchange(host, row_valid, target, N, B)
    assert (int(dropped), int(need)) == (want_dropped, want_need)
    assert (want_dropped > 0) == (hot > 0)
    np.testing.assert_array_equal(np.asarray(out.row_valid), want_rv)
    for name, (data, valid) in want.items():
        got = out.cols[name]
        assert np.asarray(got.data).dtype == data.dtype, name
        np.testing.assert_array_equal(np.asarray(got.valid), valid, err_msg=name)
        np.testing.assert_array_equal(np.asarray(got.data), data, err_msg=name)


class TestDistributedAgg:
    def test_matches_single_device(self, mesh):
        batch, g, v = make_global_batch(2000, 23, seed=3)
        sharded = shard_batch(batch, mesh)
        aggs = [
            AggDesc("sum", colfn("v"), "s"),
            AggDesc("count", None, "c"),
            AggDesc("avg", colfn("v"), "m"),
            AggDesc("min", colfn("v"), "lo"),
            AggDesc("max", colfn("v"), "hi"),
        ]

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("d"), out_specs=(P("d"), P(), P())
        )
        def step(b):
            out, ng, dropped, _need = distributed_group_aggregate(
                b, [colfn("g")], aggs, 256, N, key_names=["g"]
            )
            return out, ng, dropped

        out, ng, dropped = step(sharded)
        assert int(dropped) == 0
        rv = np.asarray(out.row_valid)
        rows = {}
        for i in np.nonzero(rv)[0]:
            key = int(np.asarray(out.cols["g"].data)[i])
            assert key not in rows, "group split across devices!"
            rows[key] = (
                int(np.asarray(out.cols["s"].data)[i]),
                int(np.asarray(out.cols["c"].data)[i]),
                float(np.asarray(out.cols["m"].data)[i]),
                int(np.asarray(out.cols["lo"].data)[i]),
                int(np.asarray(out.cols["hi"].data)[i]),
            )
        # golden
        exp = {}
        for key in np.unique(g):
            m = g == key
            exp[int(key)] = (
                int(v[m].sum()), int(m.sum()), float(v[m].mean()),
                int(v[m].min()), int(v[m].max()),
            )
        assert rows == exp

    def test_scalar_agg(self, mesh):
        batch, g, v = make_global_batch(500, 5, seed=4)
        sharded = shard_batch(batch, mesh)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=P("d"), out_specs=(P("d"), P(), P())
        )
        def step(b):
            return distributed_group_aggregate(b, [], [AggDesc("sum", colfn("v"), "s")], 64, N)[:3]

        out, _ng, _dropped = step(sharded)
        # replicated result: read shard 0 row 0
        assert int(np.asarray(out.cols["s"].data)[0]) == int(v.sum())


class TestDistributedJoin:
    def _sides(self, seed=5):
        rng = np.random.default_rng(seed)
        bk = np.arange(64, dtype=np.int64)
        bv = rng.integers(0, 1000, 64).astype(np.int64)
        pk = rng.integers(0, 96, 800).astype(np.int64)
        pv = rng.integers(0, 1000, 800).astype(np.int64)
        build = block_to_batch(
            HostBlock.from_columns(
                {"bk": column_from_values(bk.tolist(), INT64),
                 "bv": column_from_values(bv.tolist(), INT64)}
            ),
            32 * N,
        )
        probe = block_to_batch(
            HostBlock.from_columns(
                {"pk": column_from_values(pk.tolist(), INT64),
                 "pv": column_from_values(pv.tolist(), INT64)}
            ),
            128 * N,
        )
        expected = sorted(
            (int(k), int(pv[i]), int(bv[k]))
            for i, k in enumerate(pk)
            if k < 64
        )
        return build, probe, expected

    def test_partitioned_join(self, mesh):
        build, probe, expected = self._sides()
        sb, sp = shard_batch(build, mesh), shard_batch(probe, mesh)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P("d"), P("d")), out_specs=(P("d"), P(), P())
        )
        def step(b, p):
            return partitioned_join(
                p, b, colfn("pk"), colfn("bk"), N, 1024, 1024, "inner"
            )

        out, total, dropped = step(sb, sp)
        assert int(dropped) == 0
        assert int(total) == len(expected)
        rv = np.asarray(out.row_valid)
        got = sorted(
            zip(
                np.asarray(out.cols["pk"].data)[rv].tolist(),
                np.asarray(out.cols["pv"].data)[rv].tolist(),
                np.asarray(out.cols["bv"].data)[rv].tolist(),
            )
        )
        assert got == expected

    def test_broadcast_join(self, mesh):
        build, probe, expected = self._sides(seed=6)
        sb, sp = shard_batch(build, mesh), shard_batch(probe, mesh)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P("d"), P("d")), out_specs=(P("d"), P())
        )
        def step(b, p):
            return broadcast_join(b, p, colfn("bk"), colfn("pk"), 1024, "inner")

        out, total = step(sb, sp)
        assert int(total) == len(expected)
        rv = np.asarray(out.row_valid)
        got = sorted(
            zip(
                np.asarray(out.cols["pk"].data)[rv].tolist(),
                np.asarray(out.cols["pv"].data)[rv].tolist(),
                np.asarray(out.cols["bv"].data)[rv].tolist(),
            )
        )
        assert got == expected


class TestDistributedSort:
    """Sample-sort ORDER BY over the mesh: range-partition by sampled
    splitters + local sort; per-device memory stays O(rows/n) instead of
    the round-1 whole-dataset gather (reference: sortexec partition
    merge; VERDICT round-1 weak #2)."""

    def _pair(self, rows):
        from tidb_tpu.session.session import Session

        sm, s1 = Session(mesh_devices=8), Session()
        for s in (sm, s1):
            s.execute("create table t (a int, w int, c varchar(8))")
            s.execute("insert into t values " + ",".join(rows))
        return sm, s1

    def test_parity_with_nulls_desc_strings(self):
        import random

        random.seed(5)
        rows = [
            f"({random.choice(['null'] + [str(random.randint(-500, 500))])},"
            f"{random.randint(0, 99)},'s{random.randint(0, 40)}')"
            for _ in range(2500)
        ]
        sm, s1 = self._pair(rows)
        for q in [
            "select a, w from t order by a, w",
            "select a, w from t order by a desc, w desc",
            "select c, a from t order by c, a",
            "select a, w, c from t order by w desc, a, c",
        ]:
            assert sm.execute(q).rows == s1.execute(q).rows, q

    def test_no_gather_in_sharded_sort_plan(self):
        """The mesh Sort on sharded input must range-exchange, not
        broadcast_gather (memory contract)."""
        from tidb_tpu.session.session import Session
        from tidb_tpu.utils import failpoint

        sm = Session(mesh_devices=8)
        sm.execute("create table t (a int)")
        sm.execute(
            "insert into t values " + ",".join(f"({i % 97})" for i in range(1000))
        )
        seen = []
        failpoint.enable("exchange/range-repartition", lambda: seen.append("range"))
        failpoint.enable("exchange/gather", lambda: seen.append("gather"))
        try:
            sm.execute("select a from t order by a")
        finally:
            failpoint.disable("exchange/range-repartition")
            failpoint.disable("exchange/gather")
        assert "range" in seen and "gather" not in seen

    def test_skewed_keys_converge(self):
        # every row shares one key: one bucket takes everything — the
        # drop-retry loop must converge, and ties must not reorder
        from tidb_tpu.session.session import Session

        sm, s1 = Session(mesh_devices=8), Session()
        for s in (sm, s1):
            s.execute("create table t (a int, b int)")
            s.execute(
                "insert into t values "
                + ",".join(f"(7,{i})" for i in range(900))
            )
        q = "select a, b from t order by a, b"
        assert sm.execute(q).rows == s1.execute(q).rows
