"""Microbenchmark Q1's grouped sums at the shape of `tpch_sf10.scan` on
the live backend: 67,108,864 rows, a 4-bit dense key domain (16 slots),
Q1's seven distinct lanes at the widths the planner proves for them.

    chiprun -- python scripts/microbench_reductions.py          # time
    python scripts/microbench_reductions.py --describe          # compile for a described v5e, no chip (HLO under chiprun_out/hlo)
    MB_N=1048576 JAX_PLATFORMS=cpu python scripts/microbench_reductions.py   # exactness at a small size
    chiprun -- python scripts/microbench_reductions.py --q1     # Q1's filter + aggregation through the program

Forms (each returns [dense, lanes] int64 and is checked against numpy):

  parent     the masked form as PR 33 ran it: 16 requests (sum+count
             pairs, the wide lo/hi/count, one packed lane, count(*),
             occupancy), each pinned as an int64 array and reduced by 16
             masked reductions
  int8       the seven distinct lanes as signed byte digits, int8, one
             one-hot contraction into int32 over row blocks
  bf16       the same digits as bfloat16 into float32, blocks of 65,536
  masked_u16 the control without the MXU: the seven lanes as 16-bit
             limbs in int32, masked block sums in int32, 16 slots each

The MXU forms come whole-tile and in statically sliced pieces (`chunk`)
of 2**23 rows, blocks of 2**16 one batched dot. `shipped` is what the
program runs (`aggregate._DenseReducer`: int8 digits, those pieces and
blocks), timed through the same harness. The forms that lost (rows
folded to fill the MXU, a rolled loop over blocks, one plain dot a
piece, digits left in their uint32 words and bitcast, other piece and
block sizes) are out of the script; PERF.md PR 34 keeps their readings.
A limb must be exact in the type the MXU multiplies: a float32 operand
at the default precision may be multiplied as bfloat16 passes, so 16-bit
limbs as float32 are exact only where the compiler happens to keep them
so (the form labelled "exactness not guaranteed": exact on the v5e under
this jaxlib, PERF.md PR 34; nothing promises it). MB_FORMS names the
forms to run, comma-separated.
"""

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DESCRIBE = "--describe" in sys.argv
if DESCRIBE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

import tidb_tpu  # noqa: F401  (x64 on)
import tidb_tpu.executor.aggregate as A

N = int(os.environ.get("MB_N", str(67_108_864)))
DENSE = int(os.environ.get("MB_DENSE", "16"))
FORMS = os.environ.get("MB_FORMS", "").split(",") if os.environ.get("MB_FORMS") else None
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out")

# Q1's distinct lanes: (name, signed bits the planner proves, [lo, hi) drawn)
LANES = [
    ("qty", 14, (100, 5001)),  # pack_bound 8191
    ("base_price", 25, (90_000, 10_495_001)),  # pack_bound 2**24 - 1
    ("disc_price", 32, (0, 1_150_000_000)),  # pack_bound 2**31 - 1
    ("charge_lo", 31, (0, 1 << 30)),  # the wide split's low 30 bits
    ("charge_hi", 8, (-3, 117)),  # the wide split's high part
    ("discount", 5, (0, 11)),  # pack_bound 15
    ("count", 2, (1, 2)),  # ones under the mask
]
L = len(LANES)


def make_inputs(key):
    ks = jax.random.split(key, L + 2)
    # six of the sixteen slots hold rows (Q1: four), 2 % of the rows are out of the domain
    seg = jax.random.randint(ks[0], (N,), 0, 6, dtype=jnp.int32) * 3
    seg = jnp.where(jax.random.uniform(ks[1], (N,)) < 0.98, seg, DENSE)
    lanes = tuple(
        jax.random.randint(ks[2 + i], (N,), lo, hi, dtype=jnp.int64)
        for i, (_n, _b, (lo, hi)) in enumerate(LANES)
    )
    return seg, lanes


def reference(seg, lanes):
    seg = np.asarray(seg)
    out = np.zeros((DENSE, L), dtype=np.int64)
    for j, v in enumerate(lanes):
        v = np.asarray(v)
        for s in range(DENSE):
            out[s, j] = v[seg == s].sum(dtype=np.int64)
    return out


# ---------------------------------------------------------------------------
# digits
# ---------------------------------------------------------------------------


def byte_digits(v, bits, balanced):
    """Byte digits of an int64 array whose values fit `bits` signed bits,
    least significant first, with sum(d[i] * 256**i) == v. balanced:
    every digit in [-128, 127] (an int8 operand); else the low digits in
    [0, 255] and the top one signed (exact in bfloat16)."""
    if balanced:
        nl = 1 if bits <= 8 else min(8, -(-(bits + 1) // 8))
        c = sum(128 << (8 * i) for i in range(nl - 1))
        v = v + jnp.int64(c)
    else:
        nl = min(8, -(-bits // 8))
    words = (v.astype(jnp.uint32), (v >> 32).astype(jnp.uint32))
    out = []
    for i in range(nl):
        b = ((words[i // 4] >> (8 * (i % 4))) & 0xFF).astype(jnp.int32)
        if i == nl - 1:
            b = (b ^ 0x80) - 128  # the top byte, as signed
        elif balanced:
            b = b - 128
        out.append(b)
    return out


def digit_rows(lanes, balanced, dt):
    rows = []
    spans = []
    for (_n, bits, _r), v in zip(LANES, lanes):
        d = byte_digits(v, bits, balanced)
        spans.append((len(rows), len(d)))
        rows += [x.astype(dt) for x in d]
    return jnp.stack(rows), spans


def recombine(sums, spans):
    """[dense, limbs] int64 digit sums -> [dense, lanes] int64."""
    cols = []
    for at, n in spans:
        t = jnp.zeros(sums.shape[0], jnp.int64)
        for i in range(n):
            t = t + (sums[:, at + i] << (8 * i))
        cols.append(t)
    return jnp.stack(cols, axis=1)


# ---------------------------------------------------------------------------
# contractions
# ---------------------------------------------------------------------------


def contract_batched(seg, digits, block, dt, acc):
    """[limbs, rows] x one-hot [dense, rows] in row blocks, one batched
    dot_general; partials widened to int64."""
    n = seg.shape[0]
    block = math.gcd(n, block)
    nb = n // block
    oh = (
        seg.reshape(nb, 1, block) == jnp.arange(DENSE, dtype=jnp.int32)[None, :, None]
    ).astype(dt)
    x = digits.reshape(digits.shape[0], nb, block)
    part = jax.lax.dot_general(
        oh, x, (((2,), (2,)), ((0,), (1,))), preferred_element_type=acc
    )  # [nb, dense, limbs]
    return part.astype(jnp.int64).sum(axis=0)


def form_mxu(balanced, dt, acc, block, chunk=None):
    """chunk: rows of one statically sliced piece, digits made and
    contracted piece by piece (bounds the temporaries)."""

    def f(seg, lanes):
        n = seg.shape[0]
        step = math.gcd(n, chunk) if chunk else n
        total = None
        for at in range(0, n, step):
            digits, spans = digit_rows(
                [v[at:at + step] for v in lanes], balanced, dt
            )
            part = contract_batched(seg[at:at + step], digits, block, dt, acc)
            total = part if total is None else total + part
        return recombine(total, spans)

    return f


def form_parent(seg, lanes):
    """PR 33's sixteen requests through the masked backend."""
    red = A._masked_backend(seg, DENSE)
    qty, base, disc, lo, hi, dsc, ones = lanes
    mask = seg < DENSE
    packed = ((dsc + 15) << 28) | 1
    reqs = [qty, ones, base, ones, disc, ones, lo, hi, ones, qty, ones, base,
            ones, packed, ones, mask.astype(jnp.int64)]
    got = [red("sum", v, mask, jnp.int64(0)) for v in reqs]
    dsum = (got[13] >> 28) - 15 * (got[13] & ((1 << 28) - 1))
    return jnp.stack([got[0], got[2], got[4], got[6], got[7], dsum, got[14]], axis=1)


def form_masked_u16(seg, lanes):
    """No MXU: 16-bit limbs in int32, block sums of 32,768 rows exact in
    int32, one masked reduction a (slot, limb), in pieces of 2**23 rows."""
    n = seg.shape[0]
    step = math.gcd(n, 1 << 23)
    block = math.gcd(step, 32768)
    cols = [jnp.zeros(DENSE, jnp.int64) for _ in lanes]
    for at in range(0, n, step):
        segb = seg[at:at + step].reshape(-1, block)
        for j, ((_n, bits, _r), v) in enumerate(zip(LANES, lanes)):
            v = v[at:at + step]
            nl = -(-bits // 16)
            words = (v.astype(jnp.uint32), (v >> 32).astype(jnp.uint32))
            for i in range(nl):
                b = ((words[i // 2] >> (16 * (i % 2))) & 0xFFFF).astype(jnp.int32)
                if i == nl - 1:
                    b = (b ^ 0x8000) - 0x8000
                b = jax.lax.optimization_barrier(b).reshape(-1, block)
                per = jnp.stack([
                    jnp.sum(jnp.where(segb == s, b, 0), axis=1).astype(jnp.int64).sum()
                    for s in range(DENSE)
                ])
                cols[j] = cols[j] + (per << (16 * i))
    return jnp.stack(cols, axis=1)


def form_f32_default_inexact(seg, lanes):
    """The trap: 16-bit limbs as float32 at the default precision."""
    n = seg.shape[0]
    block = math.gcd(n, 128)
    oh = (seg.reshape(-1, 1, block) == jnp.arange(DENSE)[None, :, None]).astype(jnp.float32)
    cols = []
    for v in lanes:
        tot = jnp.zeros(DENSE, jnp.int64)
        for shift in (0, 16, 32):
            limb = (v >> shift) & 0xFFFF if shift < 32 else v >> shift
            limb = limb.astype(jnp.float32).reshape(-1, 1, block)
            part = jax.lax.dot_general(oh, limb, (((2,), (2,)), ((0,), (0,))))
            tot = tot + (part[:, :, 0].astype(jnp.int64).sum(axis=0) << shift)
        cols.append(tot)
    return jnp.stack(cols, axis=1)


def form_shipped(seg, lanes):
    red = A._DenseReducer(seg, DENSE)
    mask = seg < DENSE
    reqs = [
        A._Req("sum", v, mask, jnp.int64(0), bits)
        for v, (_n, bits, _r) in zip(lanes, LANES)
    ]
    return jnp.stack(red.exec_all(reqs), axis=1)


I8 = dict(balanced=True, dt=jnp.int8, acc=jnp.int32)
BF = dict(balanced=False, dt=jnp.bfloat16, acc=jnp.float32)
ALL_FORMS = {
    "parent": form_parent,
    # the whole tile at once: [limbs, rows] stacked, its blocks one batched dot
    "int8 batched 2^16": form_mxu(block=1 << 16, **I8),
    # pieces of 2**23 rows, statically sliced, a piece's blocks one batched dot
    "int8 chunk 2^23 batched 2^16": form_mxu(block=1 << 16, chunk=1 << 23, **I8),
    "bf16 chunk 2^23 batched 2^16": form_mxu(block=1 << 16, chunk=1 << 23, **BF),
    "masked_u16": form_masked_u16,
    "f32 default precision (exactness not guaranteed)": form_f32_default_inexact,
}
if hasattr(A, "_DenseReducer"):  # a tree from before PR 34 has none
    ALL_FORMS["shipped"] = form_shipped


def digits_only(balanced, dt):
    def f(seg, lanes):
        return digit_rows(lanes, balanced, dt)[0]

    return f


def q1_fragment():
    """Q1's filter and aggregation as the planner hands them to
    `group_aggregate` (same AggDescs: bounds, the wide sum_charge), on
    MB_N synthetic rows: what `Aggregate#4` costs with its producers."""
    from tidb_tpu.chunk import Batch, DevCol

    def make(key):
        ks = jax.random.split(key, 7)
        rv = jnp.arange(N) < int(N * 0.894)  # SF10: 59,986,052 of 67,108,864
        ri = lambda k, lo, hi, dt=jnp.int64: jax.random.randint(k, (N,), lo, hi, dtype=dt)
        cols = {
            "l_returnflag": ri(ks[0], 0, 3, jnp.int32),
            "l_linestatus": ri(ks[1], 0, 2, jnp.int32),
            "l_quantity": ri(ks[2], 1, 51) * 100,
            "l_extendedprice": ri(ks[3], 90_000, 10_495_001),
            "l_discount": ri(ks[4], 0, 11),
            "l_tax": ri(ks[5], 0, 9),
            "l_shipdate": ri(ks[6], 8036, 10562, jnp.int32),
        }
        return Batch({n: DevCol(d, rv) for n, d in cols.items()}, rv)

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
        A.AggDesc("sum", col("l_extendedprice"), "sum_base_price", arg_scale=2, pack_bound=(1 << 24) - 1),
        A.AggDesc("sum", disc_price, "sum_disc_price", arg_scale=4, pack_bound=(1 << 31) - 1),
        A.AggDesc("sum", charge, "sum_charge", arg_scale=6, wide=True, pack_bound=(1 << 39) - 1),
        A.AggDesc("avg", col("l_quantity"), "avg_qty", arg_scale=2, pack_bound=8191),
        A.AggDesc("avg", col("l_extendedprice"), "avg_price", arg_scale=2, pack_bound=(1 << 24) - 1),
        A.AggDesc("avg", col("l_discount"), "avg_disc", arg_scale=2, pack_bound=15),
        A.AggDesc("count", None, "count_order"),
    ]

    def q1(b):
        keep = b.row_valid & (b.cols["l_shipdate"].data <= 10471)
        b = Batch(b.cols, keep)
        return A.group_aggregate(
            b, [col("l_returnflag"), col("l_linestatus")], aggs, 16,
            key_names=["l_returnflag", "l_linestatus"], key_widths=[(2, 0), (2, 0)],
        )

    batch = jax.jit(make)(jax.random.PRNGKey(34))
    jax.block_until_ready(batch)
    fn = jax.jit(q1)
    t0 = time.perf_counter()
    out, ng = jax.block_until_ready(fn(batch))
    cs = time.perf_counter() - t0
    ts = []
    for _ in range(7):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(batch))
        ts.append(time.perf_counter() - t0)
    rows = int(ng)
    got = {n: np.asarray(c.data)[:rows].tolist() for n, c in out.cols.items()}
    line = (f"q1 fragment ({os.path.abspath(A.__file__)}) rows {N}: "
            f"{np.median(ts) * 1e3:.2f} ms (min {min(ts) * 1e3:.2f}, first call {cs:.1f} s) groups {rows}")
    print(line, flush=True)
    print(json.dumps(got, sort_keys=True), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "microbench_reductions.txt"), "a") as fh:
        fh.write(line + "\n" + json.dumps(got, sort_keys=True) + "\n")


def describe():
    """Compile every form at MB_N rows for a described v5e: temporaries,
    compile seconds, and the optimised HLO under chiprun_out/hlo."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    seg = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one)
    lanes = tuple(jax.ShapeDtypeStruct((N,), jnp.int64, sharding=one) for _ in LANES)
    hlo = os.path.join(OUT, "hlo")
    os.makedirs(hlo, exist_ok=True)
    for name, f in ALL_FORMS.items():
        if FORMS and name not in FORMS:
            continue
        t0 = time.perf_counter()
        try:
            c = jax.jit(f).lower(seg, lanes).compile()
        except Exception as e:  # noqa: BLE001 - report and go on
            print(f"{name:28s} REFUSED {str(e)[:300]}", flush=True)
            continue
        ma = c.memory_analysis()
        path = os.path.join(hlo, name.replace(" ", "_").replace("^", "") + ".txt")
        with open(path, "w") as fh:
            fh.write(c.as_text())
        print(f"{name:28s} compile {time.perf_counter() - t0:6.1f} s  temp "
              f"{ma.temp_size_in_bytes / 2**30:6.2f} GiB  -> {path}", flush=True)


def main():
    from tidb_tpu.utils.backend import backend_label

    print("backend:", backend_label(), "rows", N, "dense", DENSE, flush=True)
    seg, lanes = jax.jit(make_inputs)(jax.random.PRNGKey(int(os.environ.get("MB_SEED", "34"))))
    jax.block_until_ready(lanes)
    t0 = time.perf_counter()
    want = reference(seg, lanes)
    print(f"numpy reference {time.perf_counter() - t0:.1f} s", flush=True)
    lines = []
    timed = dict(ALL_FORMS)
    timed["digits only int8"] = digits_only(True, jnp.int8)
    timed["digits only bf16"] = digits_only(False, jnp.bfloat16)
    for name, f in timed.items():
        if FORMS and name not in FORMS:
            continue
        try:
            t0 = time.perf_counter()
            fn = jax.jit(f)
            got = jax.block_until_ready(fn(seg, lanes))
            cs = time.perf_counter() - t0
            ts = []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(seg, lanes))
                ts.append(time.perf_counter() - t0)
        except Exception as e:  # noqa: BLE001 - report and go on
            lines.append(f"{name:28s} FAILED {str(e)[:300]}")
            print(lines[-1], flush=True)
            continue
        exact = (
            "" if name.startswith("digits") else
            "exact" if np.array_equal(np.asarray(got), want) else "NOT EXACT"
        )
        lines.append(
            f"{name:28s} {np.median(ts) * 1e3:9.2f} ms  (min {min(ts) * 1e3:8.2f}, "
            f"first call {cs:6.1f} s)  {exact}"
        )
        print(lines[-1], flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "microbench_reductions.txt"), "a") as fh:
        fh.write(f"# rows {N} dense {DENSE} backend {backend_label()}\n")
        fh.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    if DESCRIBE:
        describe()
    elif "--q1" in sys.argv:
        q1_fragment()
    else:
        main()
