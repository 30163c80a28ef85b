"""Numerics of the executor's lowerings, run here on the CPU.

The executor lowers an operator one way, chosen from the shapes and
widths it is handed (7 bits of packed key or fewer: a dense domain, its
integer sums one digit contraction, min/max/float masked reductions;
other keys: sorted; probes of 4,096 and more: merge; builds over 65,536:
sorted lookup), so what runs here is what the chip runs. Each test
compares a formulation with numpy (or a loop over the rows) on the same
batch, at the operator level — no planner. What the chip's compiler
makes of the same code is tests/test_tpu_compile.py's business; what
the chip answers is chip_smoke.py's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tidb_tpu.chunk import Batch, HostBlock, block_to_batch, column_from_values
from tidb_tpu.dtypes import FLOAT64, INT64


def _mk(cols: dict, capacity: int) -> Batch:
    return block_to_batch(
        HostBlock.from_columns(
            {k: column_from_values(v, t) for k, (v, t) in cols.items()}
        ),
        capacity,
    )


def _col(name):
    return lambda b: b.cols[name]


def _rows(batch: Batch, names) -> list:
    """Valid rows as sorted tuples (None for NULL), floats rounded."""
    rv = np.asarray(batch.row_valid)
    out = []
    for i in np.nonzero(rv)[0]:
        row = []
        for n in names:
            c = batch.cols[n]
            v = np.asarray(c.data)[i]
            ok = bool(np.asarray(c.valid)[i])
            row.append(None if not ok else "nan" if v != v else round(float(v), 6))
        out.append(tuple(row))
    return sorted(out, key=lambda r: tuple((x is None, str(x)) for x in r))


# ---------------------------------------------------------------------------
# key packing
# ---------------------------------------------------------------------------


def test_pack_lex_roundtrip_order_and_limbs():
    from tidb_tpu.executor.sortops import bits_for, pack_lex, sort_lex, unpack_lex

    rng = np.random.default_rng(1)
    n = 5000
    comps = [
        (jnp.asarray(rng.integers(0, 2, n)), 1),
        (jnp.asarray(rng.integers(0, 2**40, n)), 40),      # straddles limbs
        (jnp.asarray(rng.integers(0, 3, n).astype(np.float64)), None),  # as is
        (jnp.asarray(rng.integers(0, 2, n)), 1),
        (jnp.asarray(rng.integers(0, 2**63, n, dtype=np.uint64)), 64),
        (jnp.arange(n, dtype=jnp.int32), bits_for(n)),
    ]
    limbs, where = pack_lex(comps)
    # 41 bits -> 2 limbs, the float itself, 1+64+13 bits -> 3 limbs
    assert [str(x.dtype) for x in limbs] == ["uint32"] * 2 + ["float64"] + ["uint32"] * 3
    for i, (a, _bits) in enumerate(comps):
        assert (np.asarray(unpack_lex(limbs, where, i)) == np.asarray(a)).all(), i
    sorted_ops, where = sort_lex(comps)
    perm = np.asarray(unpack_lex(sorted_ops, where, len(comps) - 1)).astype(np.int64)
    want = np.lexsort(tuple(np.asarray(a) for a, _b in comps[::-1]))
    assert (perm == want).all()


@pytest.mark.parametrize("dtype", [np.int8, np.int32, np.int64, np.uint16, np.bool_])
def test_int_sort_bits_keep_order_and_invert(dtype):
    from tidb_tpu.executor.sortops import int_from_sort_bits, int_sort_bits

    rng = np.random.default_rng(2)
    if dtype == np.bool_:
        d = rng.random(500) < 0.5
    else:
        info = np.iinfo(dtype)
        d = rng.integers(info.min, info.max, 500, dtype=dtype, endpoint=True)
    u, bits = int_sort_bits(jnp.asarray(d))
    assert bits == (1 if dtype == np.bool_ else np.dtype(dtype).itemsize * 8)
    un = np.asarray(u).astype(np.uint64)
    assert (np.argsort(un, kind="stable") == np.argsort(d, kind="stable")).all()
    back = int_from_sort_bits(jnp.asarray(un), dtype)
    assert (np.asarray(back) == d).all()


@pytest.mark.parametrize("n", [1, 2, 7, 1000, 4096, 100001])
def test_seg_scan_matches_associative_scan(n):
    from tidb_tpu.executor.sortops import _seg_scan

    rng = np.random.default_rng(n)
    v = jnp.asarray(rng.integers(-1000, 1000, n))
    b = jnp.asarray(rng.random(n) < 0.1)
    for op in (jnp.maximum, jnp.minimum):
        def combine(x, y, op=op):
            return jnp.where(y[1], y[0], op(x[0], y[0])), x[1] | y[1]

        want, _ = jax.lax.associative_scan(combine, (v, b))
        assert (np.asarray(_seg_scan(v, b, op)) == np.asarray(want)).all()


@pytest.mark.parametrize("side", ["left", "right"])
def test_merge_searchsorted_matches_jnp(side):
    from tidb_tpu.executor.sortops import merge_searchsorted

    rng = np.random.default_rng(3)
    keys = jnp.asarray(np.sort(rng.integers(-50, 50, 700)))
    q = jnp.asarray(rng.integers(-60, 60, 900))
    got = merge_searchsorted(keys, q, side)
    assert (np.asarray(got) == np.asarray(jnp.searchsorted(keys, q, side=side))).all()


# ---------------------------------------------------------------------------
# group_aggregate's three shapes (scalar, dense + masked, sorted) vs numpy
# ---------------------------------------------------------------------------


def _agg_batch():
    rng = np.random.default_rng(4)
    n = 3000
    k1 = [None if rng.random() < 0.05 else int(x) for x in rng.integers(-40, 40, n)]
    k2 = [None if rng.random() < 0.05 else int(x) for x in rng.integers(0, 3, n)]
    kf = [
        None if r < 0.05 else float("nan") if r < 0.08
        else -0.0 if x == 0 and r > 0.5 else float(x) * 0.5
        for r, x in zip(rng.random(n), rng.integers(-4, 4, n))
    ]
    v = [None if rng.random() < 0.1 else int(x) for x in rng.integers(-10**6, 10**6, n)]
    d = [None if rng.random() < 0.1 else int(x) for x in rng.integers(0, 6, n)]
    f = [float(x) for x in rng.normal(size=n)]
    batch = _mk(
        {"k1": (k1, INT64), "k2": (k2, INT64), "kf": (kf, FLOAT64),
         "v": (v, INT64), "d": (d, INT64), "f": (f, FLOAT64)},
        4096,
    )
    # some invalid rows in the middle of the tile
    rv = np.asarray(batch.row_valid).copy()
    rv[::17] = False
    return Batch(batch.cols, jnp.asarray(rv))


def _cells(batch: Batch, names) -> list:
    """Valid rows as tuples of exact host values: None for NULL, "nan",
    int or float (-0.0 as 0.0)."""
    cols = [
        (np.asarray(batch.cols[n].data), np.asarray(batch.cols[n].valid))
        for n in names
    ]

    def cell(data, valid, i):
        x = data[i].item()
        return (
            None if not valid[i] else "nan" if x != x
            else x + 0.0 if isinstance(x, float) else x
        )

    return [
        tuple(cell(data, valid, i) for data, valid in cols)
        for i in np.nonzero(np.asarray(batch.row_valid))[0]
    ]


def _reference_groups(batch: Batch, keys, aggs) -> list:
    """GROUP BY in plain Python over the host's values. `aggs` are
    (func, column or None, distinct). MySQL's rules: NULLs (and NaNs,
    and the two zeros) of a key are one group; an aggregate skips NULL
    arguments, and is NULL over none, except COUNT."""
    import math

    args = sorted({col for _f, col, _d in aggs if col is not None})
    table = _cells(batch, list(keys) + args)  # one tuple a valid row
    at = {col: len(keys) + j for j, col in enumerate(args)}
    groups: dict = {}
    for r in table:
        groups.setdefault(r[: len(keys)], []).append(r)
    rows = []
    for key, members in groups.items():
        row = list(key)
        for func, col, distinct in aggs:
            if col is None:
                row.append(len(members))
                continue
            vals = [r[at[col]] for r in members if r[at[col]] is not None]
            if distinct:
                vals = sorted(set(vals))
            total = math.fsum(vals) if vals and isinstance(vals[0], float) else sum(vals)
            row.append(
                len(vals) if func == "count"
                else None if not vals
                else total if func == "sum"
                else total / len(vals) if func == "avg"
                else min(vals) if func == "min" else max(vals)
            )
        rows.append(tuple(row))
    return rows


def _assert_same_groups(got: list, want: list, nkeys: int):
    """Same groups, cell for cell: keys, integers and NULLs exactly, a
    float within the benchmark's own float_rel_dev limit (1e-10): a sum
    accumulated in another order is the same sum."""
    def order(r):
        return tuple((x is None, str(x)) for x in r[:nkeys])

    got, want = sorted(got, key=order), sorted(want, key=order)
    assert [r[:nkeys] for r in got] == [r[:nkeys] for r in want]
    for g, w in zip(got, want):
        for x, y in zip(g[nkeys:], w[nkeys:]):
            if isinstance(y, float) and isinstance(x, float):
                assert abs(x - y) <= 1e-10 * max(abs(y), 1.0), (g, w)
            else:
                assert x == y and type(x) is type(y), (g, w)


def _agg_descs(specs):
    from tidb_tpu.executor import AggDesc

    return [
        AggDesc(func, None if col is None else _col(col), f"a{i}", distinct=distinct)
        for i, (func, col, distinct) in enumerate(specs)
    ]


_PLAIN_AGGS = [
    ("sum", "v", False), ("count", None, False), ("count", "v", False),
    ("min", "v", False), ("max", "f", False), ("avg", "v", False),
]


@pytest.mark.parametrize(
    "keys,widths",
    [
        (["k1"], None),
        (["k1"], [(8, 40)]),            # planner width: value + 40 + 1 < 2**8
        (["k1", "k2"], [(8, 40), (3, 0)]),
        (["k1", "k2"], [None, (3, 0)]),
        (["kf"], None),                 # float key: NaN group, -0.0 == 0.0
        (["k2", "kf", "k1"], None),
    ],
)
def test_sorted_aggregation_matches_default_path(keys, widths):
    """Keyed aggregation past the dense domain: rows sorted by key,
    runs reduced by cumulative sums and segmented scans, against the
    reference GROUP BY."""
    from tidb_tpu.executor import group_aggregate

    batch = _agg_batch()
    aggs = _agg_descs(_PLAIN_AGGS)
    out, ng = jax.jit(
        lambda b: group_aggregate(
            b, [_col(k) for k in keys], aggs, 4096, key_names=keys,
            key_widths=widths,
        )
    )(batch)
    want = _reference_groups(batch, keys, _PLAIN_AGGS)
    assert int(ng) == len(want)
    _assert_same_groups(
        _cells(out, keys + [a.out_name for a in aggs]), want, len(keys)
    )


_SHAPES = {
    # name: (keys, key_widths, group_capacity, the dense path?)
    "scalar": ([], None, 1, False),
    "dense 3-bit key": (["k2"], [(3, 0)], 16, True),
    "8-bit key": (["k1"], [(8, 40)], 4096, False),
    "two keys": (["k1", "k2"], [(8, 40), (3, 0)], 4096, False),
    "float key": (["kf"], None, 4096, False),  # NaN group, -0.0 == 0.0
    "no widths": (["k2", "k1"], None, 4096, False),
}


@pytest.mark.parametrize("mode", ["plain", "post_filter", "distinct sum"])
@pytest.mark.parametrize("shape", list(_SHAPES))
def test_group_aggregate_is_its_numpy_reference(shape, mode, monkeypatch):
    """Every shape group_aggregate lowers, picked from keys and widths
    alone (no keys: one full-array reduction a lane; 7 bits or fewer:
    dense domain, integer sums and counts one digit contraction, min,
    max and the float sum masked; any other keys: sorted), plain,
    under a fused HAVING, and with DISTINCT aggregates (the claim-loop
    pair table), against a GROUP BY in plain Python: same groups, same
    NULLs, integers exact, float sums within 1e-10."""
    import tidb_tpu.executor.aggregate as A
    import tidb_tpu.executor.sortops as S
    from tidb_tpu.chunk import DevCol

    keys, widths, capacity, dense = _SHAPES[shape]
    took = []
    for module, name in ((A, "_DenseReducer"), (A, "_scalar_backend"),
                         (S, "sort_group_aggregate")):
        real = getattr(module, name)
        monkeypatch.setattr(
            module, name,
            lambda *a, _n=name, _f=real, **k: took.append(_n) or _f(*a, **k),
        )
    specs = _PLAIN_AGGS + [("sum", "f", False)]
    if mode == "distinct sum":
        specs = specs + [("sum", "d", True), ("count", "d", True), ("avg", "d", True)]
    aggs = _agg_descs(specs)
    post = None
    if mode == "post_filter":  # HAVING sum(v) < 0: a NULL sum is dropped
        def post(out):
            return DevCol(out.cols["a0"].data < 0, out.cols["a0"].valid)

    batch = _agg_batch()
    out, ng = jax.jit(
        lambda b: A.group_aggregate(
            b, [_col(k) for k in keys], aggs, capacity, key_names=keys,
            key_widths=widths, post_filter=post,
        )
    )(batch)
    assert took == [
        "_scalar_backend" if not keys
        else "_DenseReducer" if dense else "sort_group_aggregate"
    ]
    groups = _reference_groups(batch, keys, specs)
    want = [
        r for r in groups
        if post is None or (r[len(keys)] is not None and r[len(keys)] < 0)
    ]
    assert want and (post is None or not keys or len(want) < len(groups))
    # the dense path compacts the survivors and reports them; the others
    # mask them and report what their tile had to hold
    assert int(ng) == (len(want) if dense else len(groups))
    assert out.capacity == max(capacity, 16) if keys else out.capacity == capacity
    _assert_same_groups(
        _cells(out, keys + [a.out_name for a in aggs]), want, len(keys)
    )


def test_sorted_aggregation_reports_stale_widths():
    """A valid key outside its planner-baked width must surface as
    WIDTH_STALE (the host recompiles), never as a wrong group."""
    from tidb_tpu.executor import AggDesc, group_aggregate
    from tidb_tpu.executor.aggregate import WIDTH_STALE

    batch = _mk({"k": ([1, 2, 300, 2], INT64), "v": ([1, 1, 1, 1], INT64)}, 1024)
    _out, ng = jax.jit(
        lambda b: group_aggregate(
            b, [_col("k")], [AggDesc("sum", _col("v"), "s")], 1024,
            key_names=["k"], key_widths=[(8, 0)],  # holds 0..254
        )
    )(batch)
    assert int(ng) >= WIDTH_STALE


def _spy_dense(monkeypatch):
    """(lanes of each contraction, ops of each masked reduction) as the
    dense reducer runs them."""
    import tidb_tpu.executor.aggregate as A

    contracted, masked = [], []
    real_contract, real_masked = A._DenseReducer._contract, A._masked_backend
    monkeypatch.setattr(
        A._DenseReducer, "_contract",
        lambda self, lanes: contracted.append(lanes) or real_contract(self, lanes),
    )

    def spy_masked(seg, slots):
        red = real_masked(seg, slots)
        return lambda op, vals, *a: masked.append((op, str(vals.dtype))) or red(op, vals, *a)

    monkeypatch.setattr(A, "_masked_backend", spy_masked)
    return contracted, masked


def test_small_dense_domain_contracts_integer_sums(monkeypatch):
    """The dense rule: integer sums and counts of one aggregate are ONE
    contraction, requests of the same (values, mask) one lane; min, max
    and floating sums keep the masked reductions."""
    import tidb_tpu.executor.aggregate as A
    from tidb_tpu.utils.metrics import REGISTRY

    contracted, masked = _spy_dense(monkeypatch)
    ran = REGISTRY.counter("tidbtpu_executor_dense_contractions_total")
    limbs = REGISTRY.counter("tidbtpu_executor_dense_contraction_limbs_total")
    ran0, limbs0 = ran.value, limbs.value
    batch = _agg_batch()
    aggs = [
        A.AggDesc("sum", _col("v"), "s", pack_bound=(1 << 20) - 1),
        A.AggDesc("avg", _col("v"), "a", pack_bound=(1 << 20) - 1),
        A.AggDesc("count", _col("v"), "cv"),
        A.AggDesc("count", None, "c"),
        A.AggDesc("min", _col("v"), "lo"),
        A.AggDesc("sum", _col("f"), "sf"),
    ]
    out, ng = jax.jit(
        lambda b: A.group_aggregate(
            b, [_col("k2")], aggs, 16, key_names=["k2"], key_widths=[(3, 0)]
        )
    )(batch)
    # v under its mask (sum, avg), that mask's count (sum, avg, count(v),
    # min), f's count, the row count (count(*), occupancy): four lanes,
    # 21 bits of v in three digits and three counts of one
    assert [len(lanes) for lanes in contracted] == [4]
    assert sorted(r.bits for r in contracted[0]) == [2, 2, 2, 21]
    assert ran.value - ran0 == 1 and limbs.value - limbs0 == 6
    assert masked == [("min", "int64"), ("sum", "float64")]
    assert int(ng) == 4  # 0, 1, 2 and NULL
    k2 = np.asarray(batch.cols["k2"].data)
    ok = np.asarray(batch.row_valid) & np.asarray(batch.cols["k2"].valid)
    vv = np.asarray(batch.cols["v"].valid)
    v = np.asarray(batch.cols["v"].data)
    for key, s, c, cv, lo in _rows(out, ["k2", "s", "c", "cv", "lo"]):
        m = (ok & (k2 == key)) if key is not None else (
            np.asarray(batch.row_valid) & ~np.asarray(batch.cols["k2"].valid)
        )
        assert c == m.sum() and cv == (m & vv).sum()
        assert s == v[m & vv].sum() and lo == v[m & vv].min()


def _lane(rng, n, bits, null_share=0.0):
    """(int64 values of `bits` signed bits, contribution mask, bits)."""
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    vals = rng.integers(lo, hi, n, dtype=np.int64, endpoint=True)
    vals[:4] = [lo, hi, lo, hi][: min(4, n)]  # the extremes, twice: 64 bits wrap
    return vals, rng.random(n) >= null_share, bits


_REDUCER_CASES = {
    # name: (rows, dense, piece, block, lanes as (bits, share of NULLs))
    "negative values, each width": (5000, 16, 1 << 23, 1 << 16, [(b, 0.1) for b in (2, 8, 9, 14, 25, 32, 39, 63)]),
    "int64 extremes wrap mod 2**64": (3000, 16, 1 << 23, 1 << 16, [(64, 0.0), (64, 0.3)]),
    "an unbounded lane beside a 1-bit one": (3000, 16, 1 << 23, 1 << 16, [(64, 0.1), (2, 0.1)]),
    "all-NULL lane and empty groups": (3000, 16, 1 << 23, 1 << 16, [(14, 1.0), (31, 0.0)]),
    "a tile below one block": (100, 16, 1 << 23, 1 << 16, [(31, 0.1), (8, 0.0)]),
    "whole blocks, whole pieces": (4096, 16, 2048, 512, [(31, 0.1), (64, 0.0)]),
    "not a multiple of the block": (5000, 16, 2048, 512, [(31, 0.1), (64, 0.0), (2, 0.5)]),
    "a last piece below one block": (4196, 16, 2048, 512, [(31, 0.1), (64, 0.0)]),
    "dense 2": (3000, 2, 4096, 1024, [(25, 0.1), (64, 0.1)]),
    "dense 128": (9000, 128, 8192, 4096, [(25, 0.1), (64, 0.1)]),
}


@pytest.mark.parametrize("case", list(_REDUCER_CASES))
def test_dense_reducer_is_numpy_int64(case, monkeypatch):
    """The reducer alone against numpy's int64 sums (which wrap mod
    2**64 as jnp.sum's do): every slot of the domain, occupied or not;
    rows of the out-of-domain slot `dense` counted nowhere."""
    import tidb_tpu.executor.aggregate as A

    rows, dense, piece, block, spec = _REDUCER_CASES[case]
    monkeypatch.setattr(A, "_CONTRACT_BLOCK", block)
    monkeypatch.setattr(A, "_CONTRACT_PIECE", piece)
    rng = np.random.default_rng(len(case))
    # slots 1 and dense - 1 stay empty; a tenth of the rows are of no slot
    seg = rng.choice([s for s in range(dense + 1) if s not in (1, dense - 1)] + [dense] * (dense // 8 + 1), rows)
    if case.startswith("all-NULL"):
        seg[seg == 3] = dense
    lanes = [_lane(rng, rows, bits, nulls) for bits, nulls in spec]

    def run(seg, lanes):
        red = A._DenseReducer(seg, dense)
        return red.exec_all(
            [A._Req("sum", v, c, jnp.int64(0), bits) for v, c, bits in lanes]
        )

    got = run(
        jnp.asarray(seg, jnp.int32),
        [(jnp.asarray(v), jnp.asarray(c), b) for v, c, b in lanes],
    )
    for (v, c, _b), g in zip(lanes, got):
        want = np.array(
            [v[c & (seg == s)].sum(dtype=np.int64) for s in range(dense)]
        )
        assert g.dtype == jnp.int64 and (np.asarray(g) == want).all(), case


def test_dense_reducer_wide_lanes_equal_the_masked_form(monkeypatch):
    """A wide sum's lo and hi lanes through the contraction are the
    masked reductions' sums bit for bit, so the float64 that mk_s makes
    of them is the same float64; and a narrower dtype's lane comes back
    as int64 (the sorted reducer's rule)."""
    import tidb_tpu.executor.aggregate as A

    monkeypatch.setattr(A, "_CONTRACT_BLOCK", 1024)
    monkeypatch.setattr(A, "_CONTRACT_PIECE", 4096)
    rng = np.random.default_rng(34)
    n, dense = 7000, 16
    seg = jnp.asarray(rng.integers(0, dense + 1, n), jnp.int32)
    d64 = jnp.asarray(rng.integers(-(1 << 38), 1 << 38, n, dtype=np.int64))
    ok = jnp.asarray(rng.random(n) < 0.9)
    lo, hi = d64 & ((1 << 30) - 1), d64 >> 30
    i32 = jnp.asarray(rng.integers(-(1 << 31), (1 << 31) - 1, n, dtype=np.int64).astype(np.int32))
    reqs = [
        A._Req("sum", lo, ok, jnp.int64(0), 31),
        A._Req("sum", hi, ok, jnp.int64(0), 10),
        A._Req("sum", i32, ok, jnp.int32(0), 32),
    ]
    got = A._DenseReducer(seg, dense).exec_all(reqs)
    masked = A._masked_backend(seg, dense)
    for r, g in zip(reqs, got):
        want = masked("sum", r.vals.astype(jnp.int64), r.contrib, jnp.int64(0))
        assert g.dtype == jnp.int64 and (np.asarray(g) == np.asarray(want)).all()


# ---------------------------------------------------------------------------
# sorted join build + merge probe, sorted unique lookup vs a loop over rows
# ---------------------------------------------------------------------------


def _join_sides():
    rng = np.random.default_rng(5)
    nb, npr = 600, 5000
    bk = [None if rng.random() < 0.05 else int(x) for x in rng.integers(0, 400, nb)]
    pk = [None if rng.random() < 0.05 else int(x) for x in rng.integers(-20, 450, npr)]
    build = _mk({"bk": (bk, INT64), "bv": (list(range(nb)), INT64)}, 1024)
    probe = _mk({"pk": (pk, INT64), "pv": (list(range(npr)), INT64)}, 8192)
    return build, probe, bk, pk


@pytest.mark.parametrize("join_type", ["inner", "left", "semi", "anti"])
def test_merge_probe_join_matches_default(join_type):
    """A probe tile of 4,096 rows and more takes the merge probe (and
    the expansion its merge_searchsorted); the rows are those of a
    nested loop. anti is null-aware: a NULL probe key survives."""
    from tidb_tpu.executor.join import _use_merge_probe, equi_join

    build, probe, bk, pk = _join_sides()
    assert _use_merge_probe(probe.capacity) and not _use_merge_probe(build.capacity)
    rows_of = {}
    for j, k in enumerate(bk):
        if k is not None:
            rows_of.setdefault(k, []).append(j)
    want = []
    for i, k in enumerate(pk):
        hits = rows_of.get(k, [])
        if join_type in ("inner", "left"):
            want += [(k, i, bk[j], j) for j in hits]
            if join_type == "left" and not hits:
                want.append((k, i, None, None))
        elif (join_type == "semi") == bool(hits):
            want.append((k, i))
    out, total = jax.jit(
        lambda b, p: equi_join(b, p, _col("bk"), _col("pk"), 16384, join_type)
    )(build, probe)
    names = ["pk", "pv"] + (["bk", "bv"] if join_type in ("inner", "left") else [])
    assert int(total) == len(want)
    assert sorted(_cells(out, names), key=repr) == sorted(want, key=repr)


def test_sorted_unique_lookup_matches_dense():
    """A unique build of 65,536 rows or fewer with planner bounds is a
    dense table; past that capacity (or without bounds) it is sorted
    and probed. Same build rows, same answer: numpy's."""
    import tidb_tpu.executor.join as J

    rng = np.random.default_rng(6)
    bk = rng.permutation(3000)[:2000].tolist()  # unique build keys
    pk = [None if rng.random() < 0.05 else int(x) for x in rng.integers(-5, 3100, 5000)]
    probe = _mk({"pk": (pk, INT64)}, 8192)
    row_of = {k: j for j, k in enumerate(bk)}
    want = np.full(8192, -1)
    want[: len(pk)] = [row_of.get(k, -1) for k in pk]

    for bcap, span in ((2048, 3000), (1 << 17, None)):
        assert J._dense_span((0, 2999), bcap, probe.capacity) == span
        build = _mk({"bk": (bk, INT64)}, bcap)
        brow, matched, stale = jax.jit(
            lambda b, p: J.lookup_build_rows(
                b, p, _col("bk"), _col("pk"), build_bounds=(0, 2999)
            )
        )(build, probe)
        assert not bool(stale)
        assert (np.where(np.asarray(matched), np.asarray(brow), -1) == want).all()


# ---------------------------------------------------------------------------
# unique-build join into an output tile smaller than the probe tile:
# one compaction index, then gathers (PR 28)
# ---------------------------------------------------------------------------

_PCAP, _OCAP = 4096, 1024


# data columns beyond the (int64, int64, bool) a side starts with: the
# narrow and the floating dtypes, float64 among them (it cannot ride as
# lanes), and int32 columns enough that the probe side's validity bits
# (36 columns and, in a left join, the match flag) need two u32 words
_EXTRA_BUILD = [("b8", np.int8), ("bf32", np.float32), ("bd", np.float64)]
_EXTRA_PROBE = [("p8", np.int8), ("pf32", np.float32), ("pd", np.float64)] + [
    (f"w{i}", np.int32) for i in range(30)
]
_JOIN_NAMES = (
    ["pk", "pv", "pb"] + [n for n, _ in _EXTRA_PROBE]
    + ["bk", "bv", "bf"] + [n for n, _ in _EXTRA_BUILD]
)


def _extra_cols(rng, specs, n, cap):
    """({name: DevCol}, {name: python values, None for NULL}) of random
    columns of the given numpy dtypes: n rows, one in ten NULL."""
    from tidb_tpu.chunk import DevCol

    cols, values = {}, {}
    for name, dt in specs:
        if np.issubdtype(dt, np.floating):
            arr = (rng.standard_normal(n) * 1e6).astype(dt)
        else:
            info = np.iinfo(dt)
            arr = rng.integers(info.min, info.max, n, endpoint=True).astype(dt)
        ok = rng.random(n) >= 0.1
        data, valid = np.zeros(cap, dtype=dt), np.zeros(cap, dtype=bool)
        data[:n], valid[:n] = arr, ok
        cols[name] = DevCol(jnp.asarray(data), jnp.asarray(valid))
        values[name] = [a.item() if o else None for a, o in zip(arr, ok)]
    return cols, values


def _unique_join_sides(join_type, fill, bcap=2048):
    """(build, probe, expected rows in probe order). NULL keys on both
    sides, NULL values, invalid probe rows scattered through the tile,
    a 64-bit, a bool, an int8, a float32 and a float64 column a side,
    36 columns on the probe's; the probe's row_valid is thinned until
    the join emits exactly the count `fill` asks for."""
    from tidb_tpu.dtypes import BOOL

    rng = np.random.default_rng(28)
    nb, npr = 2000, 4000
    bk = rng.permutation(3000)[:nb].tolist()
    for i in rng.choice(nb, 20, replace=False):
        bk[i] = None
    bv = [None if rng.random() < 0.1 else int(x)
          for x in rng.integers(-(1 << 62), 1 << 62, nb)]
    bf = [None if rng.random() < 0.1 else bool(x) for x in rng.integers(0, 2, nb)]
    pk = [None if rng.random() < 0.05 else int(x)
          for x in rng.integers(-5, 3100, npr)]
    pv = [None if rng.random() < 0.1 else int(x)
          for x in rng.integers(-(1 << 62), 1 << 62, npr)]
    pb = [None if rng.random() < 0.1 else bool(x) for x in rng.integers(0, 2, npr)]
    by_key = {k: i for i, k in enumerate(bk) if k is not None}

    def emits(i):
        return join_type == "left" or by_key.get(pk[i]) is not None

    alive = rng.random(npr) < 0.9
    want_n = {"under": 700, "full": _OCAP, "over": 1500}[fill]
    emitting = [i for i in range(npr) if alive[i] and emits(i)]
    assert len(emitting) >= want_n, (join_type, len(emitting))
    for i in rng.permutation(emitting)[: len(emitting) - want_n]:
        alive[i] = False

    bx_cols, bx = _extra_cols(rng, _EXTRA_BUILD, nb, bcap)
    px_cols, px = _extra_cols(rng, _EXTRA_PROBE, npr, _PCAP)
    expected = []
    for i in np.nonzero(alive)[0]:
        j = by_key.get(pk[i])
        if j is None and join_type == "inner":
            continue
        build_vals = (
            (None,) * (3 + len(bx)) if j is None
            else (bk[j], bv[j], bf[j]) + tuple(v[j] for v in bx.values())
        )
        expected.append(
            (pk[i], pv[i], pb[i]) + tuple(v[i] for v in px.values()) + build_vals
        )
    assert len(expected) == want_n

    build = _mk({"bk": (bk, INT64), "bv": (bv, INT64), "bf": (bf, BOOL)}, bcap)
    probe = _mk({"pk": (pk, INT64), "pv": (pv, INT64), "pb": (pb, BOOL)}, _PCAP)
    rv = np.zeros(_PCAP, dtype=bool)
    rv[:npr] = alive
    return (
        Batch({**build.cols, **bx_cols}, build.row_valid),
        Batch({**probe.cols, **px_cols}, jnp.asarray(rv)),
        expected,
    )


@pytest.mark.parametrize("fill", ["under", "full", "over"])
@pytest.mark.parametrize("lookup", ["dense", "sorted"])
@pytest.mark.parametrize("join_type", ["inner", "left"])
def test_unique_join_compacts_like_a_plain_join(join_type, lookup, fill):
    """Same rows, same (probe) order, same validity as a loop over the
    probe rows; an overflowing tile holds the first out_capacity rows
    and reports the true total; no column is valid past the rows. The
    build's capacity picks the lookup: a dense table up to 65,536 rows,
    sorted past them (Q5's orders at SF1), the planner's bounds given
    either way."""
    from tidb_tpu.executor.join import _dense_span, equi_join

    bcap = 2048 if lookup == "dense" else 1 << 17
    build, probe, expected = _unique_join_sides(join_type, fill, bcap)
    assert (_dense_span((0, 2999), bcap, _PCAP) is None) == (lookup == "sorted")
    out, total = jax.jit(
        lambda b, p: equi_join(
            b, p, _col("bk"), _col("pk"), _OCAP, join_type,
            build_bounds=(0, 2999), build_unique=True,
        )
    )(build, probe)
    assert out.capacity == _OCAP and int(total) == len(expected)
    n = min(len(expected), _OCAP)
    assert np.asarray(out.row_valid).tolist() == [True] * n + [False] * (_OCAP - n)
    names = _JOIN_NAMES
    assert sorted(out.cols) == sorted(names)
    data = {c: np.asarray(out.cols[c].data) for c in names}
    valid = {c: np.asarray(out.cols[c].valid) for c in names}
    assert data["pv"].dtype == np.int64 and data["pb"].dtype == np.bool_
    for c, dt in _EXTRA_PROBE + _EXTRA_BUILD:
        assert data[c].dtype == dt, c
    got = [
        tuple(data[c][j].item() if valid[c][j] else None for c in names)
        for j in range(n)
    ]
    assert got == expected[:n]
    for c in names:
        assert not valid[c][n:].any(), c


def _lowered(fn, *args):
    """(StableHLO op histogram, text with each op's scope stack)."""
    import collections
    import re

    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    return collections.Counter(re.findall(r"stablehlo\.(\w+)", text)), text


def _ops_under(text, op, scope):
    """Scope stacks (op_name) of the `stablehlo.<op>` ops lowered under a
    named scope, from _lowered's text: an op line names a location
    alias, the alias's definition holds the op_name."""
    import re

    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    at = re.findall(rf'"?stablehlo\.{op}"?\(.*loc\((#loc\d+)\)$', text, re.M)
    return [names[a] for a in at if scope in names.get(a, "")]


def test_unique_join_compaction_holds_no_scatter():
    """The branch pays per OUTPUT row, and a gathered row costs the v5e
    the same with one lane or eleven: one sort for the index, then ONE
    gather a side of its columns' stacked lanes, where PR 28 had one a
    column and one a validity array (55 % of a Q5's device time,
    PERF.md PR 32). A scatter pays per probe row and the v5e runs it
    serially (2.46 s of Q5's 3.17 s at SF1, PERF.md PR 28). Sorted
    lookup (no bounds), as Q5's sizes take: the dense table build is a
    scatter of its own, per BUILD row."""
    from tidb_tpu.executor.join import equi_join
    from tidb_tpu.utils.metrics import REGISTRY

    def join(out_capacity):
        return lambda b, p: equi_join(
            b, p, _col("bk"), _col("pk"), out_capacity, "inner",
            build_unique=True, keep=(("pk", "pv", "pb"), ("bk", "bv", "bf")),
        )

    build, probe, _expected = _unique_join_sides("inner", "under")
    engaged = REGISTRY.counter("tidbtpu_executor_join_compactions_total")
    stacked = REGISTRY.counter("tidbtpu_executor_stacked_gathers_total")
    before, at = engaged.value, stacked.value
    ops, text = _lowered(join(_OCAP), build, probe)
    assert "scatter" not in ops
    # six int columns and their validity: a gather a side
    under_compact = _ops_under(text, "gather", "/compact/")
    assert 2 <= len(under_compact) <= 3, under_compact
    assert engaged.value == before + 1  # once per traced program
    # the lookup's read and the two of the compaction, a side each
    assert stacked.value == at + 3
    # the probe tile as it stands: nothing to compact, nothing counted,
    # and the probe side does not move (the lookup's read, the build's)
    _lowered(join(_PCAP), build, probe)
    assert engaged.value == before + 1 and stacked.value == at + 5


_GATHER_DTYPES = [
    np.int64, np.uint64, np.int32, np.uint32, np.int16, np.int8, np.uint8,
    np.bool_, np.float32, np.float64,
]


def _gather_index(kind, cap, m, rng):
    if kind == "monotone":
        return np.sort(rng.choice(cap, m, replace=False)).astype(np.int32)
    if kind == "random":
        return rng.permutation(cap)[:m].astype(np.int32)
    if kind == "repeated":
        return rng.integers(0, 7, m).astype(np.int32)
    return rng.integers(0, 3 * cap, m).astype(np.int64)  # clipped to the tile


@pytest.mark.parametrize(
    "index", ["monotone", "random", "repeated", "out-of-tile"]
)
@pytest.mark.parametrize("dtype", _GATHER_DTYPES, ids=lambda d: np.dtype(d).name)
def test_gather_rows_is_its_numpy_reference(dtype, index):
    """sortops.gather_rows moves what it is handed bit for bit: two
    arrays of the dtype (and an int64 beside them, so limbs of unlike
    widths share the operand), 40 flags (two words), through every kind
    of index; an index past the tile reads its last row, as np.take
    with mode="clip" does. A float64 rides beside the lanes."""
    from tidb_tpu.executor.sortops import gather_rows

    rng = np.random.default_rng(32)
    cap, m = 1000, 777

    def draw(dt):
        if dt == np.bool_:
            return rng.random(cap) < 0.5
        bits = rng.integers(0, 256, cap * np.dtype(dt).itemsize, dtype=np.uint8)
        arr = bits.view(dt)
        if np.issubdtype(dt, np.floating):  # every bit pattern but NaN's
            arr = np.where(np.isnan(arr), dt(-0.0), arr)
        return arr.astype(dt)

    datas = [draw(dtype), draw(np.int64), draw(dtype)]
    flags = [rng.random(cap) < 0.5 for _ in range(40)]
    idx = _gather_index(index, cap, m, rng)
    got_d, got_f = jax.jit(gather_rows)(
        [jnp.asarray(d) for d in datas], [jnp.asarray(f) for f in flags],
        jnp.asarray(idx),
    )
    assert len(got_d) == 3 and len(got_f) == 40
    for g, d in zip(got_d, datas):
        want = np.take(d, idx, mode="clip")
        g = np.asarray(g)
        assert g.dtype == d.dtype
        assert g.tobytes() == want.tobytes()
    for g, f in zip(got_f, flags):
        assert np.asarray(g).dtype == np.bool_
        assert (np.asarray(g) == np.take(f, idx, mode="clip")).all()


def test_gather_rows_of_nothing_gathers_nothing():
    from tidb_tpu.executor.sortops import gather_rows
    from tidb_tpu.utils.metrics import REGISTRY

    stacked = REGISTRY.counter("tidbtpu_executor_stacked_gathers_total")
    at = stacked.value
    ops, _ = _lowered(lambda i: gather_rows([], [], i), jnp.arange(8))
    assert "gather" not in ops and stacked.value == at


@pytest.mark.parametrize("path", ["compact", "in-place", "expand"])
@pytest.mark.parametrize("join_type", ["inner", "left"])
def test_a_join_emits_only_what_its_readers_read(join_type, path):
    """With `keep` (JoinPlan.needs through the planner) the output batch
    holds exactly those names, with the cells it holds without; with
    keep=None, every column of both sides. A stacked operand keeps every
    lane alive, so what nothing reads must not be stacked (PERF.md, PR
    30: 147 ms against 87 in one join until pruned)."""
    from tidb_tpu.executor.join import equi_join

    build, probe, _expected = _unique_join_sides(join_type, "under")
    cap = {"compact": _OCAP, "in-place": _PCAP, "expand": _PCAP}[path]

    def join(keep):
        return jax.jit(lambda b, p: equi_join(
            b, p, _col("bk"), _col("pk"), cap, join_type,
            build_unique=path != "expand", keep=keep,
        ))(build, probe)

    (full, n_full), (some, n_some) = join(None), join((("pk", "pd"), ("bv",)))
    assert sorted(full.cols) == sorted(_JOIN_NAMES)
    assert sorted(some.cols) == ["bv", "pd", "pk"]
    assert int(n_full) == int(n_some) == 700
    assert (np.asarray(full.row_valid) == np.asarray(some.row_valid)).all()
    for c in some.cols:
        ok = np.asarray(some.cols[c].valid)
        assert (ok == np.asarray(full.cols[c].valid)).all()
        got, want = np.asarray(some.cols[c].data), np.asarray(full.cols[c].data)
        assert (got[ok] == want[ok]).all()
    # a side nothing reads is not gathered at all
    none, _n = join(((), ()))
    assert not none.cols and int(_n) == 700


def test_compact_impl_lowers_as_before_the_shared_index():
    """planner/physical.py:_compact_impl ends every steady program; it
    now takes its permutation from sortops.compaction_index and must
    lower to the ops it lowered to when it held the sort itself."""
    from tidb_tpu.chunk import DevCol
    from tidb_tpu.executor.sortops import sort_rows, unpack_lex
    from tidb_tpu.planner.physical import _compact_impl

    def before(batch, out_cap):
        ops, where, perm = sort_rows([(~batch.row_valid, 1)], batch.capacity)
        perm = perm[:out_cap]
        cols = {
            n: DevCol(c.data[perm], c.valid[perm]) for n, c in batch.cols.items()
        }
        return Batch(cols, unpack_lex(ops, where, 0)[:out_cap] == 0)

    _build, probe, _expected = _unique_join_sides("inner", "under")
    was, _ = _lowered(lambda b: before(b, 256), probe)
    now, _ = _lowered(lambda b: _compact_impl(b, 256), probe)
    assert now == was and now["sort"] == 1 and "scatter" not in now
    got, want = jax.jit(lambda b: _compact_impl(b, 256))(probe), before(probe, 256)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (np.asarray(a) == np.asarray(b)).all()


# ---------------------------------------------------------------------------
# ORDER BY through the packed sort
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("descs", [(False, False), (True, False), (False, True), (True, True)])
def test_sort_permutation_matches_numpy(descs):
    from tidb_tpu.executor.sort import sort_permutation

    rng = np.random.default_rng(7)
    n = 2000
    a = [None if rng.random() < 0.1 else int(x) for x in rng.integers(-5, 5, n)]
    f = [None if rng.random() < 0.1 else float(x) for x in rng.integers(-3, 3, n) * 0.25]
    batch = _mk({"a": (a, INT64), "f": (f, FLOAT64)}, 2048)
    perm = np.asarray(
        jax.jit(lambda b: sort_permutation(b, [_col("a"), _col("f")], list(descs)))(batch)
    )[:n]

    def key(i):
        out = []
        for v, desc in ((a[i], descs[0]), (f[i], descs[1])):
            # MySQL: NULLs first ascending, last descending
            out.append((v is None) if desc else (v is not None))
            out.append(0 if v is None else (-v if desc else v))
        return tuple(out) + (i,)

    assert perm.tolist() == sorted(range(n), key=key)


# ---------------------------------------------------------------------------
# decimal column vs float literal: exact, not float-space
# ---------------------------------------------------------------------------


def test_decimal_vs_float_literal_compares_scaled_integers():
    """`l_discount >= 0.05` in float space (col / 100.0 >= 0.05) leans on
    float64 division being exact to the last bit; the TPU's emulated
    float64 is not, and the v5e dropped every 0.05 row of Q6 (PR 23).
    The predicate must compile to integer compares only."""
    from tidb_tpu.dtypes import DECIMAL
    from tidb_tpu.expression import ColumnRef, Func, Literal, bind_expr, compile_expr

    types = {"d": DECIMAL(2)}
    batch = _mk({"d": ([0.05, 0.06, 0.07, 0.04, None], DECIMAL(2))}, 1024)
    pred = bind_expr(
        Func(op="and", args=(
            Func(op="ge", args=(ColumnRef(name="d"), Literal(value=0.05))),
            Func(op="le", args=(ColumnRef(name="d"), Literal(value=0.07))),
        )),
        types,
    )
    fn = compile_expr(pred, {})
    out = fn(batch)
    keep = np.asarray(out.data & out.valid & batch.row_valid)
    assert keep[:5].tolist() == [True, True, True, False, False]
    prims = {e.primitive.name for e in jax.make_jaxpr(lambda b: fn(b).data)(batch).eqns}
    assert "div" not in prims, prims


# ---------------------------------------------------------------------------
# mesh: a 64-bit max as two 32-bit all-reduces
# ---------------------------------------------------------------------------


def test_pmax_int64_matches_max():
    """The TPU compiler lowers only SUM all-reduces of int64, so
    mesh.pmax splits the word; the engine's overflow sentinel
    (WIDTH_STALE = 2^60) must survive it."""
    from jax.sharding import PartitionSpec as P

    from tidb_tpu.parallel.mesh import make_mesh, pmax, shard_map

    mesh = make_mesh(8)
    f = jax.jit(
        shard_map(lambda x: pmax(x[0], "d"), mesh=mesh, in_specs=P("d"), out_specs=P())
    )
    for vals in (
        [5, -3, 1 << 60, (1 << 60) + 7, -(1 << 40), 2**31, 2**32 - 1, 0],
        [-(i + 1) * (1 << 33) - 5 for i in range(8)],
        [7] * 8,
    ):
        a = np.asarray(vals, dtype=np.int64)
        assert int(f(jnp.asarray(a))) == a.max()
