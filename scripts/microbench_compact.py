"""Microbenchmark tile compaction on the live backend (PERF.md, PR 28).

A unique-build join whose discovered output tile is smaller than its
probe tile moves `out_cap` of `cap` rows to the front, in row order.
Isolates, at Q5's two SF1 shapes (6,291,456 -> 2,097,152 and
2,097,152 -> 262,144):

  index    sortops.compaction_index (one single-limb sort) against the
           generic expand path's slot-to-row map,
           merge_searchsorted(cumsum(valid), arange(out_cap), "right")
  columns  one gather a column through that index (u32, bool, int64,
           and the four (int64 data, valid) pairs a Q5 join emits)
           against one row-gather of the columns stacked as u32 lanes,
           and against the scatter a column the join used to pay

    chiprun -- python scripts/microbench_compact.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.executor.sortops import compaction_index, merge_searchsorted
from tidb_tpu.utils.backend import backend_label, enable_compile_cache

jax.config.update("jax_enable_x64", True)

SHAPES = [(6_291_456, 2_097_152, 0.29), (2_097_152, 262_144, 0.11)]


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


def main():
    enable_compile_cache()
    print("backend:", backend_label(), flush=True)
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

        def scatter(a, v):
            pos = jnp.where(v, jnp.cumsum(v) - 1, out_cap)
            return jnp.zeros(out_cap, a.dtype).at[pos].set(a, mode="drop")

        timeit(f"scatter int64 (the form deleted)  {tag}",
               jax.jit(scatter), d64[0], valid, reps=2)


if __name__ == "__main__":
    main()
