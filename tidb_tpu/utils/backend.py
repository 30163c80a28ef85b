"""Backend questions the engine asks of JAX, in one place.

The program runs in two ways: on the CPU for tests (the caller sets
JAX_PLATFORMS=cpu, plus XLA_FLAGS=--xla_force_host_platform_device_count=N
for mesh paths) and on the TPU, one process per chip. Nothing here
chooses between them or falls back from one to the other: a device
query that fails is an error.
"""

from __future__ import annotations

import os

_IS_TPU: bool | None = None


def is_tpu() -> bool:
    """True when the default JAX device is a TPU. THE gate for every
    TPU-only lowering (sorted aggregation, masked reductions, merge
    probe): gates ask this, never `jax.default_backend()` string
    compares (scripts/check_backend_gates.py). Cached: the backend
    never changes inside a process."""
    global _IS_TPU
    if _IS_TPU is None:
        import jax

        _IS_TPU = jax.devices()[0].platform == "tpu"
    return _IS_TPU


def backend_label() -> str:
    """Platform line for benches/profilers."""
    import jax

    return jax.devices()[0].platform


def sort_path_preference() -> str:
    """One switch for every sort-vs-scatter formulation gate:
    TIDB_TPU_SORT_AGG=1 -> 'force' (CPU tests cover the TPU lowering),
    =0 -> 'avoid' (TPU opt-out escape hatch), unset -> 'auto' (backend
    decides). Gates combine this with is_tpu() and their own size
    thresholds, but the env-var policy lives here only."""
    v = os.environ.get("TIDB_TPU_SORT_AGG")
    return "force" if v == "1" else "avoid" if v == "0" else "auto"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. JAX_COMPILATION_CACHE_DIR, when set, places it (JAX reads
    the variable itself; no directory is set in code); otherwise it is
    <checkout>/.jax_cache, computed from this package's location — the
    path is part of the cache key, so it must never move. Called by
    every process entry (tidb_server, bench, chip_smoke, dcn_worker)."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        cache_dir = env_dir
    else:
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        cache_dir = os.path.join(os.path.dirname(pkg), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir
