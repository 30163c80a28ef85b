"""Pallas TPU kernels for aggregation hot loops (opt-in).

The engine's default lowering leaves fusion to XLA, which already fuses
scan→filter→project→reduce chains well. The one shape XLA lowers
sub-optimally is the small-slot-table aggregation (`_masked_backend` in
executor/aggregate.py): S slots × A aggregates become S·A separate
full-array masked reductions — up to ~60 HBM passes for TPC-H Q1.
This kernel computes the whole [A, S] slot table in ONE pass over the
rows: grid over row tiles, VMEM accumulators, one-hot dot per tile
(reference hot loop: the per-group accumulation inside
pkg/executor/aggregate/agg_hash_partial_worker.go).

Numerics: accumulation is float32 inside the kernel — exact only for
integer magnitudes below 2^24 per accumulator (f32 mantissa), NOT
bit-identical to the engine's float64/int64 semantics. The kernel is
therefore **opt-in** (`TIDB_TPU_PALLAS=1`): aggregate._run_aggs routes
non-wide SUM/COUNT/AVG slot accumulation through it when enabled,
while the jnp path answers everywhere else, and every use is
verified against the float64 oracle in interpret mode
(tests/test_pallas.py). Both kernels compile for the v5e in
tests/test_tpu_compile.py and run compiled against their references in
chip_smoke.py's kernels phase; the flag defaults off.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

#: row-tile size per grid step (lane-width multiple)
TILE = 1024


def pallas_enabled() -> bool:
    return os.environ.get("TIDB_TPU_PALLAS", "0") == "1"


def pallas_interpret() -> "bool | None":
    """How an opted-in call site runs the kernels: None = not at all
    (flag off, or a non-TPU backend without the interpret hatch — the
    kernels only lower for TPU), False = compiled on the TPU, True =
    interpret mode (TIDB_TPU_PALLAS_INTERPRET=1, CPU tests only).
    Interpret mode on a TPU device is an error, never a quiet
    substitute for the compiled kernel."""
    from tidb_tpu.utils.backend import is_tpu

    if not pallas_enabled():
        return None
    interp = os.environ.get("TIDB_TPU_PALLAS_INTERPRET") == "1"
    if is_tpu():
        if interp:
            raise RuntimeError(
                "TIDB_TPU_PALLAS_INTERPRET=1 is for CPU tests; on a TPU "
                "device the kernels run compiled — unset it"
            )
        return False
    return True if interp else None


def _slot_sums_kernel(slots, vals_ref, seg_ref, out_ref):
    """One grid step: out[A, S] += vals[A, T] @ onehot(seg)[T, S].

    The one-hot is built IN-KERNEL from the tile's seg ids (iota
    compare), so only vals (4·A B/row) and seg (4 B/row) cross HBM —
    one true pass. The matmul runs on the MXU; dropped rows (seg
    outside [0, S)) produce all-zero one-hot columns.
    """
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:, :] = jnp.zeros_like(out_ref)

    seg = seg_ref[0, :]  # [T]
    onehot = (
        seg[:, None]
        == jax.lax.broadcasted_iota(seg.dtype, (seg.shape[0], slots), 1)
    ).astype(jnp.float32)
    out_ref[:, :] += jnp.dot(
        vals_ref[:, :], onehot, preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("slots", "interpret"))
def slot_sums_f32(values, contrib, seg, slots: int, interpret: bool = False):
    """[A, N] values + [A, N] contrib masks + [N] slot ids -> [A, slots]
    float32 sums, one pass over the rows.

    Rows with seg outside [0, slots) are dropped (the engine's overflow
    slot convention)."""
    from jax.experimental import pallas as pl

    a, n = values.shape
    pad = (-n) % TILE
    if pad:
        values = jnp.pad(values, ((0, 0), (0, pad)))
        contrib = jnp.pad(contrib, ((0, 0), (0, pad)))
        seg = jnp.pad(seg, (0, pad), constant_values=slots)
    n_padded = n + pad
    grid = n_padded // TILE

    import functools as _ft

    masked = jnp.where(contrib, values.astype(jnp.float32), 0.0)
    seg2d = seg.astype(jnp.int32).reshape(1, n_padded)

    return pl.pallas_call(
        _ft.partial(_slot_sums_kernel, slots),
        out_shape=jax.ShapeDtypeStruct((a, slots), jnp.float32),
        grid=(grid,),
        # index-map literals MUST be i32-typed: under the engine's
        # jax_enable_x64 a plain 0 traces as i64 and the Mosaic module
        # gets a mixed (i64, i32) index function, which it rejects
        in_specs=[
            pl.BlockSpec((a, TILE), lambda i: (jnp.int32(0), i)),
            pl.BlockSpec((1, TILE), lambda i: (jnp.int32(0), i)),
        ],
        out_specs=pl.BlockSpec(
            (a, slots), lambda i: (jnp.int32(0), jnp.int32(0))
        ),
        interpret=interpret,
    )(masked, seg2d)


def slot_sums_reference(values, contrib, seg, slots: int):
    """jnp oracle with identical drop semantics (float64 accumulate)."""
    masked = jnp.where(contrib, values.astype(jnp.float64), 0.0)
    onehot = (
        seg[:, None] == jnp.arange(slots, dtype=seg.dtype)[None, :]
    ).astype(jnp.float64)
    return masked @ onehot


# ---------------------------------------------------------------------------
# kernel #2: streaming prefix sum (compaction positions)
# ---------------------------------------------------------------------------
# The dense aggregation path compacts surviving groups with
# `cumsum(occupied)` over the whole dense domain (executor/aggregate.py
# _dense_compact_group_aggregate) — up to 2^23 elements per statement.
# XLA lowers big cumsums to a log-depth associative scan: ~2·log2(n)
# full HBM passes (≈46 passes at 8M). A TPU Pallas grid is SEQUENTIAL,
# so a running carry in SMEM turns the scan into ONE pass: each tile
# cumsums in VMEM (VPU), adds the carry, and forwards carry+tile_total.
# Expected hardware delta (a written claim, not measured;
# scripts/pallas_validate.py times both forms on the chip): ~10-20x for
# the scan op at 8M rows (one 34MB pass vs tens).
# Reference seam: the spill/compaction machinery this accelerates is
# the analog of pkg/util/chunk row-container compaction.


#: prefix-scan block geometry: each grid step scans R_SCAN x C_SCAN =
#: 128K elements, so 8M elements need only 64 sequential steps (the
#: first cut used 1024-wide tiles -> 8192 steps whose fixed per-step
#: cost ate the one-pass win: 736ms, barely under XLA's 756ms).
R_SCAN = 128
C_SCAN = 1024


def _prefix_sum_kernel(x_ref, out_ref, carry_ref):
    """Hierarchical in-block inclusive scan, all on the MXU:
    1. scan each row of the [R, C] block:    t @ upper_C   (R*C^2 MACs)
    2. exclusive-scan the R row totals:      totals @ strict_upper_R
    3. add row offsets + the running SMEM carry from earlier blocks.

    Mosaic has no cumsum lowering and no dynamic_slice (round-5
    hardware validation), so scans are triangular matmuls and totals
    are full sums — nothing indexes an array element. f32 is exact
    here: block sums <= R*C = 2^17 << 2^24 for 0/1 mask inputs."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[0] = jnp.int32(0)

    t = x_ref[:, :].astype(jnp.float32)  # [R, C]
    r, c = t.shape
    rowi = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    coli = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    upper_c = (rowi <= coli).astype(jnp.float32)
    # HIGHEST on both matmuls: default MXU bf16 input truncation
    # rounds values above 256, and the contract covers small ints
    # (per-block sums < 2^24), not just 0/1 masks
    row_scan = jnp.dot(
        t, upper_c, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    totals = jnp.sum(t, axis=1)  # [R]
    ri = jax.lax.broadcasted_iota(jnp.int32, (r, r), 0)
    rj = jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
    strict_upper_r = (ri < rj).astype(jnp.float32)
    # HIGHEST precision: the MXU's default bf16 input truncation
    # rounds totals above 256 (e.g. 300 needs 9 mantissa bits) — the
    # round-5 hardware run caught exactly that (interpret passed,
    # hardware diverged). The 0/1-input matmul above is bf16-exact.
    offs = jnp.dot(
        totals[None, :], strict_upper_r,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(r)  # exclusive row offsets
    block = (row_scan + offs[:, None]).astype(jnp.int32)
    out_ref[:, :] = block + carry_ref[0]
    carry_ref[0] = carry_ref[0] + jnp.sum(t).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def prefix_sum_i32(x, interpret: bool = False):
    """Inclusive int32 prefix sum over a 1-D bool/small-int array in
    ONE sequential-grid pass (running carry in SMEM scratch). The
    in-block scan accumulates in f32 on the MXU, exact while per-BLOCK
    sums stay below 2^24 — blocks are R_SCAN*C_SCAN = 131072 elements,
    so values up to ~128 are safe; the engine's only use is 0/1
    compaction masks, far inside the bound."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = x.shape[0]
    xi = x.astype(jnp.int32)
    block = R_SCAN * C_SCAN
    pad = (-n) % block
    if pad:
        xi = jnp.pad(xi, (0, pad))
    npad = n + pad
    rows = npad // C_SCAN
    out = pl.pallas_call(
        _prefix_sum_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, C_SCAN), jnp.int32),
        grid=(rows // R_SCAN,),
        in_specs=[pl.BlockSpec((R_SCAN, C_SCAN),
                               lambda i: (i, jnp.int32(0)))],
        out_specs=pl.BlockSpec((R_SCAN, C_SCAN),
                               lambda i: (i, jnp.int32(0))),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(xi.reshape(rows, C_SCAN))
    return out.reshape(npad)[:n]


def prefix_sum_reference(x):
    return jnp.cumsum(x.astype(jnp.int32))
