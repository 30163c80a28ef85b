"""Microbenchmark tile compaction on the live backend (PERF.md, PR 28).

A unique-build join whose discovered output tile is smaller than its
probe tile moves `out_cap` of `cap` rows to the front, in row order.
Isolates, at Q5's SF1 shapes (6,291,456 -> 2,097,152 and 2,097,152 ->
262,144 on one chip, 1,572,864 -> 524,288 on a shard of four):

  index    sortops.compaction_index (one single-limb sort) against the
           generic expand path's slot-to-row map,
           merge_searchsorted(cumsum(valid), arange(out_cap), "right")
  columns  one gather a column through that index (u32, bool, int64,
           and the four (int64 data, valid) pairs a Q5 join emits)
           against one row-gather of the columns stacked as u32 lanes,
           (by hand in both orientations, and as sortops.gather_rows,
           the helper the joins call), and against the scatter a column
           the join used to pay
  lookup   (PR 32) the sorted unique lookup's reads at `lo`: the sorted
           build's validity, key (compared with the probe's) and row,
           one gather each as the compiler fuses them, against the four
           stacked lanes of one gather_rows and the compare after it;
           `lo` monotone, as Q5's probe in l_orderkey order gives it,
           and random
  lanes    (PR 32) a stacked gather by the lanes it carries, one to ten
  dense    (PR 32) the dense unique lookup's row-table read as a 1-D
           gather and as a one-lane row gather

    chiprun -- python scripts/microbench_compact.py [compact lookup lanes dense]

`exchange-pack` (PERF.md, PR 30) isolates the send buffers of
`parallel/exchange.exchange_by_target` at the mesh Q5's three SF1
shapes (`cap` rows a shard, `n` destinations, `B` slots a bucket), with
five and with three int64 columns and their validity word: slot
`b * B + s` holds row `perm[start[b] + s]` of the `(destination, row)`
sort, so the buffer is

  scatter   the form deleted: `arr[perm]`, then one scatter a column
  gather    one gather a column through the composed index
            `perm[start[b] + s]`, itself a gather of `n * B` from perm
            (or `n` slices of the padded perm)
  slices    `arr[perm]` a column, then `n` dynamic slices of `B` rows
  stacked   the columns' u32 limbs and the word as one [cap, lanes]
            operand (or [lanes, cap]) moved by ONE row-gather, unpacked
            to int64 again on the far side of the all-to-all

    chiprun -- python scripts/microbench_compact.py exchange-pack
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.executor.sortops import (
    bits_for,
    compaction_index,
    from_lanes,
    gather_rows,
    merge_searchsorted,
    sort_rows,
    to_lanes,
    unpack_lex,
)
from tidb_tpu.utils.backend import backend_label, enable_compile_cache

jax.config.update("jax_enable_x64", True)

# Join#7 and Join#6 on one chip, Join#7 on a shard of four
SHAPES = [
    (6_291_456, 2_097_152, 0.29), (2_097_152, 262_144, 0.11),
    (1_572_864, 524_288, 0.29),
]
# (probe rows, build rows) of Join#6's sorted unique lookup: one chip, a shard
LOOKUP_SHAPES = [(2_097_152, 2_097_152), (524_288, 524_288)]
# (cap, n, B): Join#6's lineitem and orders sides, Join#5's (PERF.md, PR 29)
PACK_SHAPES = [(524_288, 4, 131_072), (393_216, 4, 131_072), (65_536, 4, 16_384)]


def timeit(name, fn, *args, reps=5):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    ms = (time.perf_counter() - t0) / reps * 1000
    print(f"{name:64s} {ms:9.3f} ms   (first call {first:6.1f} s)", flush=True)
    return out


def merge_index(valid, out_cap):
    cum = jnp.cumsum(valid.astype(jnp.int32))
    return merge_searchsorted(cum, jnp.arange(out_cap, dtype=jnp.int32), "right")


def compact():
    rng = np.random.default_rng(28)
    for cap, out_cap, density in SHAPES:
        tag = f"{cap} -> {out_cap}"
        valid = jnp.asarray(rng.random(cap) < density)
        want = np.nonzero(np.asarray(valid))[0][:out_cap]
        sel = timeit(
            f"index  sort   {tag}",
            jax.jit(lambda v: compaction_index(v, out_cap)[0]), valid,
        )
        got = timeit(
            f"index  merge  {tag}",
            jax.jit(lambda v: merge_index(v, out_cap)), valid,
        )
        n = len(want)
        assert (np.asarray(sel)[:n] == want).all()
        assert (np.asarray(got)[:n] == want).all()

        d64 = [jnp.asarray(rng.integers(0, 1 << 40, cap)) for _ in range(4)]
        ok = [jnp.asarray(rng.random(cap) < 0.99) for _ in range(4)]
        d32 = d64[0].astype(jnp.uint32)
        timeit(f"gather u32           {tag}", jax.jit(lambda a, s: a[s]), d32, sel)
        timeit(f"gather bool          {tag}", jax.jit(lambda a, s: a[s]), ok[0], sel)
        timeit(f"gather int64         {tag}", jax.jit(lambda a, s: a[s]), d64[0], sel)
        timeit(
            f"gather 4 x (int64, bool), one a column  {tag}",
            jax.jit(lambda ds, vs, s: ([a[s] for a in ds], [a[s] for a in vs])),
            d64, ok, sel,
        )

        def lanes(ds, vs):
            """The columns as u32 lanes: two per int64, one of valid bits."""
            out = []
            for a in ds:
                u = jax.lax.bitcast_convert_type(a, jnp.uint64)
                out += [(u >> jnp.uint64(32)).astype(jnp.uint32),
                        u.astype(jnp.uint32)]
            bits = jnp.zeros(cap, dtype=jnp.uint32)
            for i, a in enumerate(vs):
                bits = bits | (a.astype(jnp.uint32) << jnp.uint32(i))
            return out + [bits]

        timeit(
            f"gather 4 x (int64, bool), stacked [cap, 9] u32 rows  {tag}",
            jax.jit(lambda ds, vs, s: jnp.stack(lanes(ds, vs), axis=1)[s]),
            d64, ok, sel,
        )
        timeit(
            f"gather 4 x (int64, bool), stacked [9, cap] u32 lanes {tag}",
            jax.jit(lambda ds, vs, s: jnp.stack(lanes(ds, vs), axis=0)[:, s]),
            d64, ok, sel,
        )

        got = timeit(
            f"gather 4 x (int64, bool), sortops.gather_rows  {tag}",
            jax.jit(gather_rows), d64, ok, sel,
        )
        for have, src in zip(got[0] + got[1], d64 + ok):
            assert (np.asarray(have) == np.asarray(src)[np.asarray(sel)]).all()

        def scatter(a, v):
            pos = jnp.where(v, jnp.cumsum(v) - 1, out_cap)
            return jnp.zeros(out_cap, a.dtype).at[pos].set(a, mode="drop")

        timeit(f"scatter int64 (the form deleted)  {tag}",
               jax.jit(scatter), d64[0], valid, reps=2)


def lookup():
    rng = np.random.default_rng(32)
    for m, bcap in LOOKUP_SHAPES:
        skey = jnp.asarray(np.sort(rng.choice(1 << 40, bcap, replace=False)))
        svalid = jnp.asarray(np.arange(bcap) < int(bcap * 0.72))
        sperm = jnp.asarray(rng.permutation(bcap).astype(np.int32))
        for order in ("monotone", "random"):
            at = rng.integers(0, bcap, m).astype(np.int32)
            lo = jnp.asarray(np.sort(at) if order == "monotone" else at)
            # three probes in four hit, as keys drawn from the build do
            pkey = jnp.where(
                jnp.asarray(rng.random(m) < 0.75), skey[lo], jnp.int64(-1)
            )
            tag = f"{m} probes of {bcap}, lo {order}"

            def three(skey, svalid, sperm, lo, pkey):
                return sperm[lo], svalid[lo] & (skey[lo] == pkey)

            def four_lanes(skey, svalid, sperm, lo, pkey):
                (key_at, brow), (valid_at,) = gather_rows(
                    [skey, sperm], [svalid], lo
                )
                return brow, valid_at & (key_at == pkey)

            want = timeit(f"lookup  three gathers, compare fused  {tag}",
                          jax.jit(three), skey, svalid, sperm, lo, pkey)
            got = timeit(f"lookup  four stacked lanes, compare after  {tag}",
                         jax.jit(four_lanes), skey, svalid, sperm, lo, pkey)
            for w, g in zip(want, got):
                assert (np.asarray(w) == np.asarray(g)).all()


def lanes():
    """What a stacked gather costs by the lanes it carries, at Join#7's
    one-chip shape: the 10-lane probe side read 27 ns a row where four
    lanes of a 2,097,152-row source read 4.5 (PERF.md, PR 32)."""
    rng = np.random.default_rng(33)
    cap, out_cap, density = SHAPES[0]
    sel = jnp.asarray(
        np.nonzero(rng.random(cap) < density)[0][:out_cap].astype(np.int32)
    )
    sel = jnp.concatenate([sel, jnp.zeros(out_cap - sel.shape[0], jnp.int32)])
    cols = [jnp.asarray(rng.integers(0, 1 << 32, cap, dtype=np.uint32))
            for _ in range(10)]
    for k in (1, 2, 3, 4, 6, 10):
        timeit(
            f"gather_rows  {k} u32 lanes ({k * cap * 4 >> 20} MiB)  {cap} -> {out_cap}",
            jax.jit(lambda ds, s: gather_rows(ds, [], s)[0]), cols[:k], sel,
        )
    timeit(
        f"gather_rows  10 u32 lanes as 5 gathers of 2  {cap} -> {out_cap}",
        jax.jit(lambda ds, s: [
            gather_rows(ds[i:i + 2], [], s)[0] for i in range(0, 10, 2)
        ]), cols, sel,
    )


def dense():
    """Join#7's dense-table read: 6,291,456 probe rows into a row table
    of 10,000 build keys (44.9 ms of a one-chip Q5), as the plain 1-D
    gather the join holds and as a one-lane row gather either way up."""
    rng = np.random.default_rng(34)
    for m, span in ((6_291_456, 10_000), (1_572_864, 10_000)):
        tab = jnp.asarray(rng.permutation(span).astype(np.int32))
        off = jnp.asarray(rng.integers(0, span, m).astype(np.int32))
        tag = f"{m} probes of a {span}-entry table"
        want = timeit(f"dense  tab[off]  {tag}", jax.jit(lambda t, o: t[o]), tab, off)
        for name, fn in (
            ("tab[None, :][:, off]", lambda t, o: t[None, :][:, o][0]),
            ("tab[:, None][off]", lambda t, o: t[:, None][o][:, 0]),
            ("gather_rows([tab])", lambda t, o: gather_rows([t], [], o)[0][0]),
        ):
            got = timeit(f"dense  {name}  {tag}", jax.jit(fn), tab, off)
            assert (np.asarray(got) == np.asarray(want)).all()


def exchange_pack():
    rng = np.random.default_rng(30)
    for cap, n, B in PACK_SHAPES:
        # six rows in ten are valid and spread evenly: the buckets fill
        # to 0.6 of a tight tile, as Join#6's do (77-81 k of 131,072)
        target = jnp.asarray(
            np.where(rng.random(cap) < 0.6, rng.integers(0, n, cap), n).astype(np.int32)
        )

        def prelude(target):
            ops, where, perm = sort_rows([(target, bits_for(n + 1))], cap)
            sorted_t = unpack_lex(ops, where, 0).astype(jnp.int32)
            start = jnp.searchsorted(sorted_t, jnp.arange(n + 1, dtype=jnp.int32))
            return perm, sorted_t, start.astype(jnp.int32)

        perm, sorted_t, start = timeit(
            f"sort (destination, row)  cap {cap}", jax.jit(prelude), target
        )

        def slots(start):
            s = jnp.arange(B, dtype=jnp.int32)
            count = jnp.minimum(start[1:] - start[:n], B)
            filled = s[None, :] < count[:, None]
            return jnp.minimum(start[:n, None] + s[None, :], cap - 1), filled

        def index_gather(perm, start):
            pos, filled = slots(start)
            return perm[pos], filled

        def index_slices(perm, start):
            padded = jnp.concatenate([perm, jnp.zeros(B, perm.dtype)])
            return sliced(padded, start), slots(start)[1]

        def sliced(padded, start):
            return jnp.stack([
                jax.lax.dynamic_slice_in_dim(padded, start[b], B) for b in range(n)
            ])

        def masked(filled, x):
            return jnp.where(filled.reshape(filled.shape + (1,) * (x.ndim - 2)), x, 0)

        def by_scatter(perm, sorted_t, start, arrs):
            slot = jnp.arange(cap, dtype=jnp.int32) - start[jnp.clip(sorted_t, 0, n)]
            fits = (slot < B) & (sorted_t < n)
            at = jnp.where(
                fits, jnp.clip(sorted_t, 0, n - 1) * B + jnp.clip(slot, 0, B - 1), n * B
            )
            return [
                jnp.zeros(n * B, a.dtype).at[at].set(a[perm], mode="drop").reshape(n, B)
                for a in arrs
            ]

        def by_gather(index):
            def run(perm, sorted_t, start, arrs):
                idx, filled = index(perm, start)
                return [masked(filled, a[idx]) for a in arrs]
            return run

        def by_gather_unmasked(perm, sorted_t, start, arrs):
            idx, _filled = index_gather(perm, start)
            return [a[idx] for a in arrs]

        def by_slices(perm, sorted_t, start, arrs):
            filled = slots(start)[1]
            return [
                masked(filled, sliced(
                    jnp.concatenate([a[perm], jnp.zeros(B, a.dtype)]), start))
                for a in arrs
            ]

        def stacked(axis, index=index_gather, unpack=True):
            def run(perm, sorted_t, start, arrs):
                lanes = [l for a in arrs[:-1] for l in to_lanes(a)] + [arrs[-1]]
                idx, filled = index(perm, start)
                if axis == 1:
                    got = masked(filled, jnp.stack(lanes, axis=1)[idx])  # [n, B, L]
                    got = [got[..., i] for i in range(len(lanes))]
                else:
                    got = jnp.stack(lanes, axis=0)[:, idx]  # [L, n, B]
                    got = [masked(filled, got[i]) for i in range(len(lanes))]
                if not unpack:
                    return got
                return [
                    from_lanes(got[2 * i:2 * i + 2], jnp.int64)
                    for i in range(len(arrs) - 1)
                ] + [got[-1]]
            return run

        def stacked_slices(perm, sorted_t, start, arrs):
            lanes = [l for a in arrs[:-1] for l in to_lanes(a)] + [arrs[-1]]
            rows = jnp.stack(lanes, axis=1)[perm]
            rows = jnp.concatenate([rows, jnp.zeros((B, len(lanes)), rows.dtype)])
            got = masked(slots(start)[1], jnp.stack([
                jax.lax.dynamic_slice_in_dim(rows, start[b], B) for b in range(n)
            ]))
            return [
                from_lanes([got[..., 2 * i], got[..., 2 * i + 1]], jnp.int64)
                for i in range(len(arrs) - 1)
            ] + [got[..., -1]]

        for k in (5, 3):
            tag = f"{k} x int64 + word  cap {cap} n {n} B {B}"
            arrs = [jnp.asarray(rng.integers(-(1 << 40), 1 << 40, cap)) for _ in range(k)]
            arrs.append(jnp.asarray(rng.integers(0, 1 << (k + 1), cap).astype(np.uint32)))
            want = timeit(f"scatter a column (the form deleted)  {tag}",
                          jax.jit(by_scatter), perm, sorted_t, start, arrs, reps=2)
            forms = [
                ("gather a column, index perm[start[b]+s]", by_gather(index_gather)),
                ("gather a column, index by slices of perm", by_gather(index_slices)),
                ("gather a column, no empty-slot select", by_gather_unmasked),
                ("arr[perm] a column, then n slices", by_slices),
                ("stacked [cap, lanes] row-gather", stacked(1)),
                ("stacked [cap, lanes], index by slices", stacked(1, index_slices)),
                ("stacked [cap, lanes], limbs not rejoined", stacked(1, unpack=False)),
                ("stacked [lanes, cap] lane-gather", stacked(0)),
                ("stacked [cap, lanes][perm], then n slices", stacked_slices),
            ]
            for name, fn in forms:
                got = timeit(f"{name}  {tag}", jax.jit(fn), perm, sorted_t, start, arrs)
                if "no empty-slot" in name or "not rejoined" in name:
                    continue
                for w, g in zip(want, got):
                    assert (np.asarray(w) == np.asarray(g)).all(), name


if __name__ == "__main__":
    enable_compile_cache()
    print("backend:", backend_label(), flush=True)
    modes = {"compact": compact, "lookup": lookup, "lanes": lanes,
             "dense": dense, "exchange-pack": exchange_pack}
    for mode in sys.argv[1:] or ["compact", "lookup"]:
        modes[mode]()
