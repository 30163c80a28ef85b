"""Backend questions the engine asks of JAX, in one place.

The program is one program on two backends: the CPU for tests (the
caller sets JAX_PLATFORMS=cpu, plus
XLA_FLAGS=--xla_force_host_platform_device_count=N for mesh paths) and
the TPU, one process per chip or per mesh. The executor lowers every
operator the same way on both (its kernels are chosen from shapes and
widths, executor/aggregate.py and executor/join.py); nothing here
chooses between backends or falls back from one to the other: a device
query that fails is an error.
"""

from __future__ import annotations

import os

_IS_TPU: bool | None = None


def is_tpu() -> bool:
    """True when the default JAX device is a TPU: a fact about the
    device (how much memory it has, planner/streamed._device_budget;
    whether a bench found its chip), never a choice of kernel —
    scripts/check_backend_gates.py keeps it, `jax.default_backend()`
    and the environment out of tidb_tpu/executor/. Cached: the backend
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
