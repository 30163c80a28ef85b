"""Test bootstrap: run everything on a virtual 8-device CPU mesh.

The reference's tests run the whole engine against an embedded unistore
(pkg/testkit/mockstore.go:49) so no real cluster is needed; our analog is
JAX CPU with xla_force_host_platform_device_count=8 so multi-chip sharding
paths execute without TPU hardware. Must be set before jax imports.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: scale-tier tests (SF0.1+ TPC-H parity, forced-spill runs); "
        "skipped unless RUN_SLOW=1 or -m slow",
    )


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest

    if os.environ.get("RUN_SLOW") == "1" or "slow" in config.getoption("-m", ""):
        return
    skip = _pytest.mark.skip(reason="scale tier: set RUN_SLOW=1 or -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
