"""Pallas slot-table aggregation kernel (opt-in; interpret-mode tests).

Each case runs in a clean CPU child (its own env switches the opt-in
flags on before tidb_tpu imports) and runs the kernel in interpret
mode against the float64 jnp oracle. The compiled (interpret=False)
forms are compiled for the v5e in tests/test_tpu_compile.py.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = textwrap.dedent(
    """
    import sys; sys.path.insert(0, REPO_PATH)
    import tidb_tpu
    import numpy as np, jax.numpy as jnp
    from tidb_tpu.executor.pallas_kernels import (
        slot_sums_f32, slot_sums_reference,
    )

    rng = np.random.default_rng(7)
    for (A, N, S) in [(1, 100, 4), (4, 3000, 8), (10, 5000, 12), (2, 1024, 6)]:
        vals = jnp.asarray(rng.integers(0, 100, (A, N)).astype(np.float32))
        contrib = jnp.asarray(rng.random((A, N)) < 0.8)
        # seg includes the overflow slot S (dropped rows)
        seg = jnp.asarray(rng.integers(0, S + 1, N).astype(np.int32))
        got = slot_sums_f32(vals, contrib, seg, S, interpret=True)
        exp = slot_sums_reference(vals, contrib, seg, S).astype(jnp.float32)
        assert got.shape == (A, S), got.shape
        assert bool(jnp.allclose(got, exp, rtol=1e-6)), (A, N, S)
    # exact counting: values=1 contributions count rows per slot exactly
    ones = jnp.ones((1, 4096), jnp.float32)
    contrib = jnp.ones((1, 4096), bool)
    seg = jnp.asarray((np.arange(4096) % 3).astype(np.int32))
    got = slot_sums_f32(ones, contrib, seg, 3, interpret=True)
    assert got.tolist() == [[1366.0, 1365.0, 1365.0]], got.tolist()
    print("PALLAS_OK")
    """
)


def test_slot_sums_interpret_matches_oracle():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", CHILD.replace("REPO_PATH", repr(REPO))],
        capture_output=True, text=True, timeout=600, cwd="/tmp", env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PALLAS_OK" in out.stdout


def test_disabled_by_default():
    from tidb_tpu.executor.pallas_kernels import pallas_enabled

    assert not pallas_enabled()


SQL_CHILD = textwrap.dedent(
    """
    import sys; sys.path.insert(0, REPO_PATH)
    import tidb_tpu
    from tidb_tpu.session.session import Session

    s = Session()
    s.execute("create table t (g int, v int, f double)")
    s.execute(
        "insert into t values "
        + ",".join(
            f"({i % 5},{i},{i / 4})" for i in range(2000)
        )
    )
    r = s.execute(
        "select g, count(*), sum(v), avg(f) from t group by g order by g"
    )
    exp = []
    for g in range(5):
        xs = [i for i in range(2000) if i % 5 == g]
        exp.append((g, len(xs), sum(xs), sum(i / 4 for i in xs) / len(xs)))
    for got, want in zip(r.rows, exp):
        assert got[0] == want[0] and got[1] == want[1], (got, want)
        assert abs(got[2] - want[2]) <= abs(want[2]) * 1e-6, (got, want)
        assert abs(got[3] - want[3]) <= abs(want[3]) * 1e-5, (got, want)
    print("PALLAS_SQL_OK")
    """
)


def test_enabled_path_through_sql():
    """TIDB_TPU_PALLAS=1 (+interpret escape hatch off-TPU) routes
    SUM/COUNT/AVG slot accumulation through the kernel; group results
    match the exact expectations within f32 tolerance."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["TIDB_TPU_PALLAS"] = "1"
    env["TIDB_TPU_PALLAS_INTERPRET"] = "1"
    out = subprocess.run(
        [sys.executable, "-c", SQL_CHILD.replace("REPO_PATH", repr(REPO))],
        capture_output=True, text=True, timeout=600, cwd="/tmp", env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PALLAS_SQL_OK" in out.stdout


class TestPrefixSum:
    """Kernel #2: streaming prefix sum (interpret-mode parity; the
    compiled form runs on the chip in chip_smoke.py's kernels phase and
    scripts/pallas_validate.py). Same clean-child pattern as the
    slot-sum tests."""

    CHILD2 = textwrap.dedent(
        """
        import sys; sys.path.insert(0, REPO_PATH)
        import tidb_tpu
        import numpy as np, jax.numpy as jnp
        from tidb_tpu.executor.pallas_kernels import (
            prefix_sum_i32, prefix_sum_reference,
        )

        rng = np.random.default_rng(11)
        for n in (100, 1024, 3001, 5000, 8192):
            x = jnp.asarray(rng.random(n) < 0.3)
            got = prefix_sum_i32(x, interpret=True)
            want = prefix_sum_reference(x)
            assert got.shape == want.shape, (got.shape, want.shape)
            assert (np.asarray(got) == np.asarray(want)).all(), n
        xi = jnp.asarray(rng.integers(0, 5, 3001).astype(np.int32))
        assert (
            np.asarray(prefix_sum_i32(xi, interpret=True))
            == np.asarray(prefix_sum_reference(xi))
        ).all()
        print("PREFIX_OK")
        """
    )

    def test_parity(self):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-c",
             self.CHILD2.replace("REPO_PATH", repr(REPO))],
            capture_output=True, text=True, timeout=600, cwd="/tmp",
            env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "PREFIX_OK" in out.stdout

    def test_dense_compaction_uses_kernel(self):
        # end-to-end in a clean child: dense-path GROUP BY compacts
        # identically with the Pallas scan (interpret) and jnp
        child = textwrap.dedent(
            """
            import sys; sys.path.insert(0, REPO_PATH)
            import os
            import tidb_tpu
            from tidb_tpu.session import Session

            def run():
                s = Session()
                s.execute("create table t (k int, v int)")
                rows = ", ".join(f"({i % 97}, {i})" for i in range(500))
                s.execute(f"insert into t values {rows}")
                return s.execute(
                    "select k, sum(v) from t group by k order by k"
                ).rows

            base = run()
            os.environ["TIDB_TPU_PALLAS"] = "1"
            os.environ["TIDB_TPU_PALLAS_INTERPRET"] = "1"
            assert run() == base
            print("COMPACT_OK")
            """
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-c", child.replace("REPO_PATH", repr(REPO))],
            capture_output=True, text=True, timeout=600, cwd="/tmp",
            env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "COMPACT_OK" in out.stdout
