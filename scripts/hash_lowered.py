"""sha256 of the lowered StableHLO (no debug locations) of every program
`planner/physical.py` jit-compiles for Q1, Q5, Q6, Q18 at SF 0.01 on one
device and for Q5 on a 4-device CPU mesh, as JSON on stdout: the proof that
a refactor leaves the programs alone (PR 30, PR 31). About 30 s on the CPU.

    python scripts/hash_lowered.py . > /root/scratch/change.json
    mkdir -p /root/scratch/parent && git archive HEAD | tar -x -C /root/scratch/parent
    python scripts/hash_lowered.py /root/scratch/parent > /root/scratch/parent.json
    cmp /root/scratch/parent.json /root/scratch/change.json

A tree from before PR 31 asked the platform for its kernels and needs
steering first; `CHANGES.md`'s PR 31 entry has the three lines.

With `--q95 <sf> <seed> [<seed> ...]` after the tree it hashes instead
the programs of TPC-DS Q95 (`tpcds_sf1`'s population at that scale
factor) for each seed, under the labels `q95:<seed>`: the proof that
one program serves every data set (PR 33). Two seeds' programs are the
same where their lists are equal. `--q18 <sf> <seed> [<seed> ...]` does
the same for TPC-H Q18 over `tpch_sf1`'s population, under `q18:<seed>`
(PR 35)."""
import hashlib, json, os, sys

repo = os.path.abspath(sys.argv[1])
PER_SEED = sys.argv[2][2:] if sys.argv[2:3] in (["--q95"], ["--q18"]) else None
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.chdir(repo)
sys.path.insert(0, repo)
sys.path.insert(0, os.path.join(repo, "benchmarks"))
sys.path.insert(0, os.path.join(repo, "benchmarks", "reference"))

import importlib.util
import tidb_tpu  # noqa
import jax
from tidb_tpu.planner import physical

records = []
label = [None]
real_wj = physical.watched_jit


def wj(fn, sig=None, **kw):
    inner = real_wj(fn, sig=sig, **kw)
    kind = sig[0] if isinstance(sig, tuple) else "fn"

    def call(*a, **k):
        text = jax.jit(fn).lower(*a, **k).as_text()
        records.append((label[0], kind, hashlib.sha256(text.encode()).hexdigest(), len(text)))
        return inner(*a, **k)

    return call


physical.watched_jit = wj


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


B = os.path.join(repo, "benchmarks")
spec = json.load(open(os.path.join(repo, "BENCHMARK.json")))


def config(name):
    (entry,) = [c for c in spec["configs"] if c["name"] == name]
    return json.load(open(os.path.join(repo, entry["file"])))


one_cfg, mesh_cfg = config("tpch_sf1"), config("tpch_sf1_mesh4")
mesh_loader = load(os.path.join(B, "loaders", "tpch_mesh.py"), "bench_tpch_mesh")
SF, SEED = 0.01, 3100000031


def sql_of(name):
    return " ".join(open(os.path.join(B, "queries", name + ".sql")).read().split())


from tidb_tpu.session import Session

if PER_SEED:
    SF = float(sys.argv[3])
    if PER_SEED == "q95":
        tpcds = load(os.path.join(B, "loaders", "tpcds.py"), "bench_tpcds")
        dep_cls, cfg, db = tpcds.Deployment, config("tpcds_sf1"), "tpcds"
    else:
        dep_cls, cfg, db = mesh_loader.tpch.Deployment, one_cfg, "tpch"
    runs = [(f"{PER_SEED}:{seed}", dep_cls, cfg, None, (PER_SEED,), int(seed), db)
            for seed in sys.argv[4:]]
else:
    runs = [
        ("one", mesh_loader.tpch.Deployment, one_cfg, None, ("q1", "q5", "q6", "q18"), SEED, "tpch"),
        ("mesh4", mesh_loader.Deployment, mesh_cfg, 4, ("q5",), SEED, "tpch"),
    ]
for which, dep_cls, cfg, width, stmts, seed, db in runs:
    dep = dep_cls(cfg, seed, SF)
    sess = Session(dep.server.catalog, db=db, **({"mesh_devices": width} if width else {}))
    label[0] = which + ":setup"
    print(which, "analyze", file=sys.stderr, flush=True)
    for s in dep.analyze_statements():
        sess.execute(s)
    for q in stmts:
        label[0] = which if PER_SEED else f"{which}:{q}"
        print(label[0], file=sys.stderr, flush=True)
        r1 = sess.execute(sql_of(q))
        r2 = sess.execute(sql_of(q))  # steady

out = {}
for lab, kind, h, n in records:
    if lab.endswith(":setup"):
        continue
    out.setdefault(lab, []).append({"kind": kind, "sha256": h, "chars": n})
json.dump(out, sys.stdout, indent=1)
print()
sys.stdout.flush()
os._exit(0)
