"""Tier-1 gate for scripts/check_backend_gates.py: the repo stays free
of raw `== "tpu"` backend string compares (utils/backend.is_tpu() is
the one sanctioned check), of imports of JAX's private package, of
device queries swallowed by a catch-all handler, and of an executor
that asks the backend or the environment which kernel to take."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "scripts", "check_backend_gates.py")


def test_repo_is_clean():
    proc = subprocess.run(
        [sys.executable, LINT, REPO], capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 0, (
        f"backend-gate violations:\n{proc.stdout}{proc.stderr}"
    )


def test_lint_catches_violations(tmp_path):
    pkg = tmp_path / "tidb_tpu"
    pkg.mkdir()
    (pkg / "bad_gate.py").write_text(
        'import jax\n'
        'ON_TPU = jax.default_backend() == "tpu"\n'   # rule 1  # backend-gate-ok
        'OTHER = backend != "tpu"\n'                  # rule 2
        'OK = backend == "tpu"  # backend-gate-ok\n'  # pragma exempts
    )
    asks = (
        'import os\n'
        'import jax\n'
        'from tidb_tpu.utils.backend import is_tpu\n'
        'def pick(m):\n'
        '    if is_tpu():\n'                              # rule 5
        '        return "merge"\n'
        '    if jax.default_backend() != "cpu":\n'        # rule 5
        '        return "merge"\n'
        '    if os.environ.get("TIDB_TPU_X") == "1":\n'   # rule 5
        '        return "merge"\n'
        '    return "merge" if m >= 4096 else "search"\n' # a size: fine
    )
    (pkg / "executor").mkdir()
    (pkg / "executor" / "bad_kernel_gate.py").write_text(asks)
    (pkg / "planner_like.py").write_text(asks)  # rule 5 is executor-only
    (tmp_path / "outside.py").write_text(
        'x = store == "tpu"\n'  # outside tidb_tpu/: rule 2 not applied
    )
    proc = subprocess.run(
        [sys.executable, LINT, str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "bad_gate.py:2" in proc.stdout
    assert "bad_gate.py:3" in proc.stdout
    assert "bad_gate.py:4" not in proc.stdout
    assert "outside.py" not in proc.stdout
    for line in (5, 7, 9):
        assert f"bad_kernel_gate.py:{line}:" in proc.stdout, line
    assert "bad_kernel_gate.py:11" not in proc.stdout
    assert "bad_kernel_gate.py:3" not in proc.stdout  # the import alone asks nothing
    assert "planner_like.py" not in proc.stdout


def test_lint_catches_private_jax_and_swallowed_queries(tmp_path):
    pkg = tmp_path / "tidb_tpu"
    pkg.mkdir()
    private = "jax." + "_src"  # halves: a grep of the tree stays clean
    (pkg / "bad_private.py").write_text(
        f"from {private} import xla_bridge\n"       # rule 3
        "import jax\n"
        "try:\n"
        "    n = len(jax.devices())\n"              # rule 4
        "except Exception:\n"
        "    n = 0\n"
        "try:\n"
        "    m = jax.local_devices()[0].memory_stats()\n"  # rule 4, twice
        "except (ValueError, BaseException):\n"
        "    m = None\n"
        "try:\n"
        "    k = jax.devices()[0].device_kind\n"    # narrow handler: fine
        "except IndexError:\n"
        "    k = None\n"
    )
    (tmp_path / "outside.py").write_text(
        f"import {private}.xla_bridge\n"            # rule 3 applies everywhere
        "try:\n"
        "    import jax; jax.devices()\n"           # rule 4 is engine-only
        "except Exception:\n"
        "    pass\n"
    )
    proc = subprocess.run(
        [sys.executable, LINT, str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    out = proc.stdout
    assert "bad_private.py:1" in out and "outside.py:1" in out
    assert "bad_private.py:4" in out and out.count("bad_private.py:8") == 2
    assert "bad_private.py:12" not in out and "outside.py:3" not in out


def test_device_budget_raises_on_unknown_kind(monkeypatch):
    """A TPU whose runtime reports no memory limit is looked up by kind;
    a kind the table does not know is an error, never a guessed size."""
    import jax
    import pytest

    import tidb_tpu.utils.backend as backend
    from tidb_tpu.planner import streamed

    class Dev:
        platform = "tpu"

        def __init__(self, kind, stats):
            self.device_kind, self._stats = kind, stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(backend, "_IS_TPU", True)
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev("TPU v9 mega", None)])
    with pytest.raises(RuntimeError, match="TPU v9 mega"):
        streamed._device_budget()
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev("TPU v5 lite", None)])
    assert streamed._device_budget() == int((16 << 30) * 0.85)
    monkeypatch.setattr(
        jax, "local_devices", lambda: [Dev("TPU v9 mega", {"bytes_limit": 123})]
    )
    assert streamed._device_budget() == 123
    monkeypatch.setattr(backend, "_IS_TPU", False)
    assert streamed._device_budget() == 4 << 30


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR places the cache (no directory is set
    in code); unset, it is <checkout>/.jax_cache."""
    import jax

    from tidb_tpu.utils.backend import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_is_tpu_is_importable_and_boolean():
    from tidb_tpu.utils.backend import is_tpu

    assert is_tpu() in (True, False)
