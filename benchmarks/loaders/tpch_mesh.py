"""Loader `tpch_mesh`: a TPC-H deployment row-sharded over the chips of
one host. `loaders/tpch.py` in everything but the server: it is brought
up through `tidb_server.bootstrap` with the configuration's
`mesh_devices`, so every connection's session runs its statements as
one SPMD program over one mesh (the program's MPP mode).

A program from before `Server` took `mesh_devices` would accept the
setting (`Config` declares the field) and serve from one chip: this
loader refuses before datagen where `Server` has no such parameter, and
after the start checks the width of a session's mesh.

`run.py` loads this module before JAX starts its backend, so a CPU
rehearsal (`JAX_PLATFORMS=cpu`, set by `--rehearse-cpu-sf`) is given as
many host devices here as the widest configuration of this loader asks
for."""

from __future__ import annotations

import importlib.util
import inspect
import os

_REHEARSAL_DEVICES = 4
if os.environ.get("JAX_PLATFORMS") == "cpu":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            f"{_flags} --xla_force_host_platform_device_count={_REHEARSAL_DEVICES}".strip())

_spec = importlib.util.spec_from_file_location(
    "bench_loaders_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpch)

DATABASE = tpch.DATABASE
HostData = tpch.HostData
flight_rows = tpch.flight_rows
keep_flights = tpch.keep_flights
compilations = tpch.compilations


class MeshRefused(RuntimeError):
    """The program cannot serve the mesh the configuration states."""


def served_mesh_width(server) -> int:
    """The width of the mesh a new connection's session would run on
    (1 for a one-device session)."""
    from tidb_tpu.session import Session

    return int(Session(server.catalog, mesh_devices=server.mesh_devices).executor.mesh_n or 1)


class Deployment(tpch.Deployment):
    def __init__(self, config: dict, seed: int, scale_factor: float):
        import tidb_server
        from tidb_tpu.server import Server
        from tidb_tpu.utils.config import Config

        width = int(config["mesh_devices"])
        if "mesh_devices" not in inspect.signature(Server.__init__).parameters:
            raise MeshRefused(
                "this program's Server takes no mesh_devices: it would serve "
                f"{config['name']} from one chip under {width} chips' name")
        self.config = config
        # the mesh first: a host with fewer devices fails here, before datagen
        self.catalog, self.server = tidb_server.bootstrap(
            Config().override(port=0, mesh_devices=width))
        got = served_mesh_width(self.server)
        if got != width:
            raise MeshRefused(f"sessions run on {got} device(s), the configuration says {width}")
        target = config["row_counts"]["lineitem"] if scale_factor == config["scale_factor"] else None
        tables = tpch.datagen.generate(scale_factor, seed, lineitem_rows=target)
        tpch.bulk_load(self.catalog, tables)
        self.data = HostData(tables)
