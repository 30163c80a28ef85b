"""On-chip Pallas validation: one process, on what jax.devices() gives.

Runs both kernels compiled (NOT interpret mode), checks numerics
against the jnp oracles, and times them (host clock around
block_until_ready) against the forms the engine uses by default on TPU.
Prints one JSON object; writes no record. A kernel that fails to lower
or run raises. Run it on the chip: `chiprun -- python scripts/pallas_validate.py`.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from tidb_tpu.executor.pallas_kernels import slot_sums_f32, slot_sums_reference

N = int(os.environ.get("PV_N", str(6_000_000)))
SLOTS = 8
LANES = 8

_dev = jax.devices()[0]
out = {
    "device": {
        "platform": _dev.platform, "kind": _dev.device_kind,
        "count": len(jax.devices()),
    },
    "n": N, "slots": SLOTS, "lanes": LANES,
}
print("device:", out["device"], flush=True)

rng = np.random.default_rng(0)
# f32-exact magnitudes (the kernel's contract: sums < 2^24 per slot
# would be bit-exact; realistic magnitudes check tolerance instead)
vals = jnp.asarray(rng.integers(0, 1000, (LANES, N)), dtype=jnp.float32)
contrib = jnp.asarray(rng.random((LANES, N)) < 0.9)
seg = jnp.asarray(rng.integers(0, SLOTS, N), dtype=jnp.int32)


def timed(fn, *args, reps=5):
    """Median host-clock ms around work that ends in block_until_ready
    (device compute, not the result transfer)."""
    r = jax.block_until_ready(fn(*args))  # compile + sync
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return jax.device_get(r), float(np.median(ts)) * 1e3


kernel_out, kernel_ms = timed(
    lambda v, c, s: slot_sums_f32(v, c, s, SLOTS), vals, contrib, seg
)
ref_out, ref_ms = timed(
    jax.jit(lambda v, c, s: slot_sums_reference(v, c, s, SLOTS)),
    vals, contrib, seg,
)


# the masked per-slot backend shape (engine default on TPU)
@jax.jit
def masked(v, c, s):
    outs = []
    for lane in range(LANES):
        outs.append(
            jnp.stack(
                [
                    jnp.sum(jnp.where(c[lane] & (s == k), v[lane], 0.0))
                    for k in range(SLOTS)
                ]
            )
        )
    return jnp.stack(outs)


_m, masked_ms = timed(masked, vals, contrib, seg)

# ---- kernel #2: streaming prefix sum vs XLA cumsum -----------------
from tidb_tpu.executor.pallas_kernels import prefix_sum_i32

PN = int(os.environ.get("PV_PN", str(8_388_608)))
mask = jnp.asarray(rng.random(PN) < 0.3)
ps_out, ps_ms = timed(lambda m: prefix_sum_i32(m), mask)
xla_out, xla_ms = timed(
    jax.jit(lambda m: jnp.cumsum(m.astype(jnp.int32))), mask
)
prefix_ok = bool((np.asarray(ps_out) == np.asarray(xla_out)).all())
out.update(
    {
        "prefix_n": PN,
        "prefix_kernel_ms": round(ps_ms, 3),
        "prefix_xla_cumsum_ms": round(xla_ms, 3),
        "prefix_numerics_ok": prefix_ok,
        "prefix_kernel_beats_xla": bool(ps_ms < xla_ms),
    }
)
print("prefix sum:", ps_ms, "ms vs xla", xla_ms, "ms, ok:", prefix_ok,
      flush=True)

ref64 = np.asarray(ref_out)
got = np.asarray(kernel_out)
rel = np.abs(got - ref64) / np.maximum(np.abs(ref64), 1.0)
max_rel, num_ok = float(rel.max()), bool(rel.max() < 1e-5)
out.update(
    {
        "kernel_ms": round(kernel_ms, 3),
        "masked_backend_ms": round(masked_ms, 3),
        "jnp_onehot_ms": round(ref_ms, 3),
        "max_rel_err_vs_f64": max_rel,
        "numerics_ok": num_ok,
        "kernel_beats_masked": bool(kernel_ms < masked_ms),
    }
)
print(json.dumps(out, indent=1), flush=True)
