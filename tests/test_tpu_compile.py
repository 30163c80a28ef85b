"""The chip's compiler, asked here without the chip.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached (`v5e:2x2`). These tests compile — never
run — the executor's lowerings at TPC-H SF1 shapes, so a refusal (a
64-bit op the X64 rewriter lacks, a program past 16 GB) or a compile
too slow for a cold server start shows here at no chip time. Nothing is
steered: the executor picks its kernels from the shapes it is handed,
and these are the chip's.

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture — never at import,
never in conftest.py, never autouse — everything compiles in the test's
own process, the persistent compile cache is off around the compiles,
and all of it lives in this ONE file (another file could land on another
xdist worker, whose fixture would then fail to load the library).

Compile seconds for v5e:2x2 as measured in the sandbox are in CHANGES.md
(PR 23). Tests whose compile passes a quarter of a minute are `slow`.
"""

import time

import numpy as np
import pytest

LINEITEM_SF1 = 6_001_215
ORDERS_SF1 = 1_500_000


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _widen(tree, rows: int, sharding):
    """Shapes of a pytree of [n, ...] arrays, widened to `rows` rows."""
    import jax

    return jax.tree.map(
        lambda x: _sds((rows,) + tuple(x.shape[1:]), x.dtype, sharding), tree
    )


def _batch(cols: dict, rows: int, sharding):
    """A Batch of shapes: cols = {name: dtype}."""
    from tidb_tpu.chunk import Batch, DevCol

    return Batch(
        {
            n: DevCol(_sds((rows,), dt, sharding), _sds((rows,), np.bool_, sharding))
            for n, dt in cols.items()
        },
        _sds((rows,), np.bool_, sharding),
    )


def _compile(fn, *args):
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _fits_v5e(compiled):
    ma = compiled.memory_analysis()
    total = (
        ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes
    )
    assert total < 16 << 30, total
    return ma


# ---------------------------------------------------------------------------
# the flagship fragment, and Q1's aggregation through the dense reducer
# ---------------------------------------------------------------------------


def _q1_fragment(one_chip):
    import __graft_entry__ as g
    from tidb_tpu.chunk import pad_capacity

    fn, (batch,) = g.entry()
    return fn, _widen(batch, pad_capacity(LINEITEM_SF1), one_chip)


def test_q1_fragment_compiles(one_chip):
    fn, batch = _q1_fragment(one_chip)
    compiled, _s = _compile(fn, batch)
    _fits_v5e(compiled)


LINEITEM_SF10_TILE = 67_108_864  # pad_capacity(59,986,052), `tpch_sf10.scan`'s


@pytest.mark.parametrize("rows", [LINEITEM_SF1, LINEITEM_SF10_TILE], ids=["sf1", "sf10"])
def test_dense_reducer_q1_shape_compiles(one_chip, monkeypatch, rows):
    """Q1's real plan carries planner widths for its two dictionary
    keys, so the 4-bit dense domain reduces through
    aggregate._DenseReducer: the integer sums and the count ride ONE
    digit contraction (a convolution a block of rows), the
    min, which digits cannot carry, the masked reductions. At SF10's
    tile the whole aggregation must leave the chip's memory to the
    tables: temporaries under 2 GB."""
    import tidb_tpu.executor.aggregate as A
    from tidb_tpu.chunk import pad_capacity

    contracted, masked = [], []
    real_contract = A._DenseReducer._contract
    real_masked = A._masked_backend
    monkeypatch.setattr(
        A._DenseReducer, "_contract",
        lambda self, lanes: contracted.append(len(lanes)) or real_contract(self, lanes),
    )

    def spy_masked(seg, slots):
        red = real_masked(seg, slots)
        return lambda op, *a: masked.append(op) or red(op, *a)

    monkeypatch.setattr(A, "_masked_backend", spy_masked)
    batch = _batch(
        {"l_returnflag": np.int32, "l_linestatus": np.int32,
         "l_quantity": np.int64, "l_extendedprice": np.int64},
        pad_capacity(rows), one_chip,
    )
    sf10 = rows == LINEITEM_SF10_TILE
    # the bounds the planner proves for the two columns (13 and 24 bits)
    aggs = [
        A.AggDesc("sum", lambda x: x.cols["l_quantity"], "sum_qty", pack_bound=8191),
        A.AggDesc("avg", lambda x: x.cols["l_quantity"], "avg_qty", pack_bound=8191),
        A.AggDesc("sum", lambda x: x.cols["l_extendedprice"], "sum_base",
                  pack_bound=(1 << 24) - 1),
        A.AggDesc("count", None, "count_order"),
    ] + ([] if sf10 else [A.AggDesc("min", lambda x: x.cols["l_quantity"], "min_qty")])

    def q1_agg(b):
        return A.group_aggregate(
            b,
            [lambda x: x.cols["l_returnflag"], lambda x: x.cols["l_linestatus"]],
            aggs,
            16, key_names=["l_returnflag", "l_linestatus"],
            key_widths=[(2, 0), (2, 0)],  # 3 and 2 dictionary codes
        )

    compiled, _s = _compile(q1_agg, batch)
    # one contraction of four lanes: the two columns, their validity's
    # count, the row count (sum_qty and avg_qty are one lane, count(*)
    # and the occupancy another)
    assert contracted == [5] and masked == ([] if sf10 else ["min"])
    ma = _fits_v5e(compiled)
    # one batched dot a piece of 2**23 rows
    assert compiled.as_text().count(" convolution(") == -(-batch.capacity // (1 << 23))
    if sf10:
        assert ma.temp_size_in_bytes < 2 << 30, ma.temp_size_in_bytes


def test_dense_reducer_q1_real_lanes_sf10_temporaries(one_chip, monkeypatch):
    """Q1's own filter and eight aggregates with the planner's bounds
    (the decimal products, the wide sum_charge) at SF10's tile: 17
    requests, 10 lanes, 22 digit rows. Each piece's digits wait for the
    piece before (_DenseReducer._contract's barrier), which is what
    bounds the temporaries: 3.20 GiB by this compiler, 4.73 GiB without
    the chain (PERF.md PR 34). Losing it fails here."""
    import jax.numpy as jnp

    import tidb_tpu.executor.aggregate as A
    from tidb_tpu.chunk import Batch, DevCol

    contracted = []
    real_contract = A._DenseReducer._contract
    monkeypatch.setattr(
        A._DenseReducer, "_contract",
        lambda self, lanes: contracted.append(
            (len(lanes), sum(len(A._byte_digits(r.vals[:1], r.bits)) for r in lanes))
        ) or real_contract(self, lanes),
    )
    batch = _batch(
        {"l_returnflag": np.int32, "l_linestatus": np.int32, "l_shipdate": np.int32,
         "l_quantity": np.int64, "l_extendedprice": np.int64,
         "l_discount": np.int64, "l_tax": np.int64},
        LINEITEM_SF10_TILE, one_chip,
    )

    def col(n):
        return lambda b: b.cols[n]

    def disc_price(b):
        p, d = b.cols["l_extendedprice"], b.cols["l_discount"]
        return DevCol(p.data * (100 - d.data), p.valid & d.valid)

    def charge(b):
        dp, t = disc_price(b), b.cols["l_tax"]
        return DevCol(dp.data * (100 + t.data), dp.valid & t.valid)

    aggs = [
        A.AggDesc("sum", col("l_quantity"), "sum_qty", arg_scale=2, pack_bound=8191),
        A.AggDesc("sum", col("l_extendedprice"), "sum_base_price", arg_scale=2,
                  pack_bound=(1 << 24) - 1),
        A.AggDesc("sum", disc_price, "sum_disc_price", arg_scale=4,
                  pack_bound=(1 << 31) - 1),
        A.AggDesc("sum", charge, "sum_charge", arg_scale=6, wide=True,
                  pack_bound=(1 << 39) - 1),
        A.AggDesc("avg", col("l_quantity"), "avg_qty", arg_scale=2, pack_bound=8191),
        A.AggDesc("avg", col("l_extendedprice"), "avg_price", arg_scale=2,
                  pack_bound=(1 << 24) - 1),
        A.AggDesc("avg", col("l_discount"), "avg_disc", arg_scale=2, pack_bound=15),
        A.AggDesc("count", None, "count_order"),
    ]

    def q1(b):
        keep = b.row_valid & (b.cols["l_shipdate"].data <= jnp.int32(10471))
        # as the scan hands them over: NOT NULL columns share one validity
        cols = {n: DevCol(c.data, b.row_valid) for n, c in b.cols.items()}
        return A.group_aggregate(
            Batch(cols, keep), [col("l_returnflag"), col("l_linestatus")], aggs,
            16, key_names=["l_returnflag", "l_linestatus"],
            key_widths=[(2, 0), (2, 0)],
        )

    compiled, _s = _compile(q1, batch)
    assert contracted == [(10, 22)]
    ma = _fits_v5e(compiled)
    assert ma.temp_size_in_bytes < int(3.5 * 2**30), ma.temp_size_in_bytes


# ---------------------------------------------------------------------------
# sorted aggregation (Q18's SF1 shape: 6M rows -> 1.5M groups)
# ---------------------------------------------------------------------------


def _q18_aggregation(one_chip, key_widths):
    from tidb_tpu.chunk import pad_capacity
    from tidb_tpu.executor import AggDesc, group_aggregate

    batch = _batch(
        {"l_orderkey": np.int64, "l_quantity": np.int64},
        pad_capacity(LINEITEM_SF1), one_chip,
    )

    def agg(b):
        out, ngroups = group_aggregate(
            b, [lambda x: x.cols["l_orderkey"]],
            [AggDesc("sum", lambda x: x.cols["l_quantity"], "sum_qty")],
            pad_capacity(ORDERS_SF1), key_names=["l_orderkey"],
            key_widths=key_widths,
        )
        return out, ngroups

    return agg, batch


def _sort_signatures(fn, *args):
    """[(operand dtypes, num_keys, is_stable)] of every sort traced."""
    import jax

    found = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "sort":
                found.append((
                    tuple(str(v.aval.dtype) for v in e.invars),
                    e.params["num_keys"], e.params["is_stable"],
                ))
            for p in e.params.values():
                for sub in p if isinstance(p, (list, tuple)) else [p]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_sorted_aggregation_q18_shape_compiles(one_chip):
    """The lowering that took 434 s in the seed (five int8 keys + row id,
    stable). With planner widths the whole key — row validity, key
    validity, 24 key bits, 23 row-id bits — is two uint32 limbs."""
    # l_orderkey in [1, 6,000,000]: 24 bits hold value + bias + 1
    agg, batch = _q18_aggregation(one_chip, [(24, 0)])
    sorts = _sort_signatures(agg, batch)
    assert sorts[0] == (("uint32", "uint32"), 2, False), sorts
    assert all(not stable for _d, _k, stable in sorts), sorts
    compiled, secs = _compile(agg, batch)
    _fits_v5e(compiled)
    assert secs < 150, f"sorted aggregation took {secs:.0f}s to compile"


@pytest.mark.slow
def test_sorted_aggregation_without_widths_compiles(one_chip):
    """No planner bounds: the int64 key keeps all 64 bits (3 limbs)."""
    agg, batch = _q18_aggregation(one_chip, None)
    assert _sort_signatures(agg, batch)[0][1] == 3
    compiled, _s = _compile(agg, batch)
    _fits_v5e(compiled)


def test_segmented_scan_compiles(one_chip):
    """min/max on the sorted path: the rolled doubling loop (the
    unrolled lax.associative_scan had not compiled after 15 minutes)."""
    import jax.numpy as jnp

    from tidb_tpu.chunk import pad_capacity
    from tidb_tpu.executor.sortops import _seg_scan

    n = pad_capacity(LINEITEM_SF1)
    compiled, secs = _compile(
        lambda v, b: _seg_scan(v, b, jnp.maximum),
        _sds((n,), np.int64, one_chip), _sds((n,), np.bool_, one_chip),
    )
    assert "while" in compiled.as_text()
    assert secs < 60, f"segmented scan took {secs:.0f}s to compile"


# ---------------------------------------------------------------------------
# merge-probe / sorted-lookup joins (Q5's SF1 shapes) — slow: each
# program holds three 3-limb sorts
# ---------------------------------------------------------------------------


def _q5_join_sides(one_chip):
    from tidb_tpu.chunk import pad_capacity

    orders = _batch(
        {"o_orderkey": np.int64, "o_custkey": np.int64},
        pad_capacity(ORDERS_SF1), one_chip,
    )
    lineitem = _batch(
        {"l_orderkey": np.int64, "l_extendedprice": np.int64},
        pad_capacity(LINEITEM_SF1), one_chip,
    )
    return orders, lineitem


@pytest.mark.slow
def test_merge_probe_join_q5_shape_compiles(one_chip):
    from tidb_tpu.chunk import pad_capacity
    from tidb_tpu.executor.join import _use_merge_probe, equi_join

    assert _use_merge_probe(pad_capacity(LINEITEM_SF1))
    orders, lineitem = _q5_join_sides(one_chip)
    compiled, _s = _compile(
        lambda b, p: equi_join(
            b, p, lambda x: x.cols["o_orderkey"], lambda x: x.cols["l_orderkey"],
            pad_capacity(LINEITEM_SF1), "inner",
        ),
        orders, lineitem,
    )
    _fits_v5e(compiled)


@pytest.mark.slow
def test_sorted_unique_lookup_q5_shape_compiles(one_chip):
    from tidb_tpu.executor.join import lookup_build_rows

    orders, lineitem = _q5_join_sides(one_chip)
    compiled, _s = _compile(
        lambda b, p: lookup_build_rows(
            b, p, lambda x: x.cols["o_orderkey"], lambda x: x.cols["l_orderkey"],
            build_bounds=(1, 6_000_000),  # past 2**16 build rows: sorted anyway
        ),
        orders, lineitem,
    )
    _fits_v5e(compiled)


@pytest.mark.slow
def test_unique_join_compaction_q5_shape_compiles(one_chip):
    """Q5's largest join at SF1: 6,291,456 probe rows into the
    discovered 2,097,152-row tile, four output columns. 55 s here (the
    lookup's three-limb sorts; the compaction adds one single-limb
    sort). The compiled program holds no scatter: the index is a sort,
    and a side's columns move by ONE gather of the output tile's rows
    (their u32 limbs and validity bits stacked as lanes), where PR 28
    had a gather a column and a validity array."""
    from tidb_tpu.chunk import pad_capacity
    from tidb_tpu.executor.join import equi_join

    orders = _batch(
        {"o_orderkey": np.int64, "o_custkey": np.int64},
        pad_capacity(ORDERS_SF1), one_chip,
    )
    lineitem = _batch(
        {n: np.int64 for n in
         ("l_orderkey", "l_suppkey", "l_extendedprice", "l_discount")},
        pad_capacity(LINEITEM_SF1), one_chip,
    )
    keep = (("l_suppkey", "l_extendedprice", "l_discount"), ("o_custkey",))

    def join(b, p):
        return equi_join(
            b, p, lambda x: x.cols["o_orderkey"], lambda x: x.cols["l_orderkey"],
            1 << 21, "inner", build_unique=True, keep=keep,
        )

    compiled, _s = _compile(join, orders, lineitem)
    _fits_v5e(compiled)
    text = compiled.as_text()
    assert "scatter" not in text
    gathers = [
        ln for ln in text.splitlines()
        if " gather(" in ln and "/compact/gather" in ln
    ]
    assert 2 <= len(gathers) <= 3, gathers


# ---------------------------------------------------------------------------
# four devices: the mesh repartition join carries an all-to-all
# ---------------------------------------------------------------------------


def test_mesh_repartition_join_has_all_to_all(topo):
    """The hash-partition shuffle of the north star, compiled for the
    2x2 v5e mesh. Small tiles: what is asserted is the collective, and
    the sorts of a 4096-row tile compile in seconds."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tidb_tpu.parallel import partitioned_join
    from tidb_tpu.parallel.mesh import AXIS, pmax, shard_map

    n = len(topo.devices)
    assert n == 4
    mesh = Mesh(np.array(topo.devices), (AXIS,))
    rows = NamedSharding(mesh, P(AXIS))
    orders = _batch({"o_orderkey": np.int64, "o_custkey": np.int64}, 1024 * n, rows)
    lineitem = _batch({"l_orderkey": np.int64, "l_qty": np.int64}, 4096 * n, rows)

    def local(o, li):
        out, total, dropped = partitioned_join(
            li, o, lambda b: b.cols["l_orderkey"], lambda b: b.cols["o_orderkey"],
            n, 2048, 4096, "inner",
        )
        # every mesh program ends by taking the max of its int64
        # cardinality scalars over the axis: the TPU compiler lowers only
        # SUM all-reduces of int64, which mesh.pmax works around
        return out, pmax(total, AXIS), dropped

    step = shard_map(
        local, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(), P()),
    )
    compiled, _s = _compile(step, orders, lineitem)
    assert "all-to-all" in compiled.as_text()
    assert len(jax.tree.leaves(compiled.input_shardings)[0].device_set) == n
