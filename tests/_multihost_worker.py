"""Worker process for the 2-host DCN bring-up test (spawned by
test_multihost.py). Each process contributes 4 virtual CPU devices to a
global 8-device mesh; the same SQL runs through the mesh session and
must match the single-device answer computed locally.

Usage: python _multihost_worker.py <process_id> <num_processes> <coordinator>
"""

import os
import sys

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
coord = sys.argv[3]

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# distributed bring-up MUST precede anything that initializes the XLA
# backend — including the tidb_tpu import chain (x64 flag warmup)
jax.distributed.initialize(
    coordinator_address=coord, num_processes=nproc, process_id=pid
)
assert jax.process_count() == nproc, jax.process_count()
assert len(jax.devices()) == 4 * nproc, len(jax.devices())

from tidb_tpu.bench import load_tpch  # noqa: E402
from tidb_tpu.session import Session  # noqa: E402
from tidb_tpu.storage import Catalog  # noqa: E402

# identical deterministic data in every process (multi-controller SPMD:
# each host holds the full host-side table; device placement shards it)
cat = Catalog()
load_tpch(cat, sf=0.002, seed=3, tables=["orders", "lineitem"])
single = Session(cat, db="tpch")
msess = Session(cat, db="tpch", mesh_devices=4 * nproc)

QUERIES = [
    "select count(*), sum(l_extendedprice), min(l_shipdate) from lineitem "
    "where l_discount <= 0.05",
    "select l_returnflag, count(*), sum(l_quantity) from lineitem "
    "group by l_returnflag order by l_returnflag",
    "select o_orderpriority, count(*) from orders join lineitem "
    "on o_orderkey = l_orderkey where l_quantity < 10 "
    "group by o_orderpriority order by o_orderpriority",
    "select l_suppkey, count(*) from lineitem group by l_suppkey "
    "order by count(*) desc, l_suppkey limit 5",
]

for q in QUERIES:
    a = single.must_query(q).rows
    b = msess.must_query(q).rows
    assert a == b, f"process {pid} mismatch on {q!r}:\n single={a}\n mesh={b}"

print(f"MULTIHOST_OK process={pid} devices={len(jax.devices())}")
