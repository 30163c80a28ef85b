"""Profile any ladder query: compile vs steady-state split + EXPLAIN.

One process, on what jax.devices() gives (JAX_PLATFORMS=cpu for a CPU run).

Usage: python scripts/profile_query.py {q1|q5|q6|q18|q95} [sf] [--explain]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from tidb_tpu.utils.backend import backend_label

import bench as B
from tidb_tpu.bench import load_tpch
from tidb_tpu.session import Session
from tidb_tpu.storage import Catalog


def main():
    pos = [a for a in sys.argv[1:] if not a.startswith("--")]
    q = pos[0] if pos else "q18"
    sf = float(pos[1]) if len(pos) > 1 else 1.0
    print("backend:", backend_label(), flush=True)
    cat = Catalog()
    t0 = time.perf_counter()
    if q == "q95":
        from tidb_tpu.bench.tpcds import Q95_SQL, load_tpcds

        load_tpcds(cat, sf=sf, seed=1)
        tables, sql, db = [], Q95_SQL, "test"
    else:
        tables, sql, db = B._TABLES[q], B.QUERIES[q], "tpch"
        load_tpch(cat, sf=sf, tables=tables, seed=1)
    print(f"datagen: {time.perf_counter()-t0:.2f}s", flush=True)
    sess = Session(cat, db=db)
    sess.execute(f"set tidb_mem_quota_query = {64 << 30}")
    t0 = time.perf_counter()
    for t in tables:
        sess.execute(f"analyze table {t}")
    print(f"analyze: {time.perf_counter()-t0:.2f}s", flush=True)
    if "--explain" in sys.argv:
        for row in sess.execute("explain " + sql).rows:
            print("  ", row[0], flush=True)
    t0 = time.perf_counter()
    r = sess.execute(sql)
    print(f"first execute: {time.perf_counter()-t0:.2f}s ({len(r.rows)} rows)",
          flush=True)
    times = []
    for i in range(3):
        t0 = time.perf_counter()
        sess.execute(sql)
        times.append(time.perf_counter() - t0)
    print("steady:", " ".join(f"{t:.3f}s" for t in times), flush=True)


if __name__ == "__main__":
    main()
