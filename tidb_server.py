#!/usr/bin/env python
"""tidb_tpu server binary.

Reference: cmd/tidb-server/main.go — flags (main.go:200-262), TOML config
(pkg/config/config.go, loaded by InitializeConfig main.go:275), store
registry (registerStores main.go:397), server start (createServer
main.go:895), graceful shutdown (main.go:330-341). Layers: built-in
defaults <- --config TOML <- CLI flags. With --path the catalog loads
from the snapshot directory on boot and persists back on shutdown
(the durability story; storage/persist.py).
"""

from __future__ import annotations

import argparse
import os
import signal
import sys


def bootstrap(cfg, tpch_sf=None, seed: int = 0, status_port=None):
    """Build the catalog and the MySQL server the way the binary does:
    compile cache, snapshot load, config variables, optional TPC-H
    bootstrap, watchdog. Returns (catalog, server); the caller runs
    serve_forever() (main) or start_background() (chip_smoke.py)."""
    from tidb_tpu.server import Server
    from tidb_tpu.storage import Catalog
    from tidb_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    if cfg.mesh_devices:
        # before any load: a mesh wider than the devices JAX sees is an
        # error, never a narrower server under the same name
        from tidb_tpu.parallel.mesh import shared_mesh

        shared_mesh(cfg.mesh_devices)
    catalog = Catalog()
    if cfg.path and os.path.exists(os.path.join(cfg.path, "manifest.json")):
        from tidb_tpu.storage.persist import load_catalog

        print(f"loading catalog from {cfg.path} ...", flush=True)
        load_catalog(cfg.path, catalog)
    cfg.apply_variables(catalog)
    if tpch_sf:
        from tidb_tpu.bench import load_tpch

        print(f"generating TPC-H sf={tpch_sf} ...", flush=True)
        load_tpch(catalog, sf=tpch_sf, seed=seed)

    sp = status_port if status_port is not None else cfg.status_port
    srv = Server(catalog, host=cfg.host, port=cfg.port, status_port=sp,
                 mesh_devices=cfg.mesh_devices)
    srv.stats_handle.interval_s = cfg.auto_analyze_interval_s
    from tidb_tpu.utils.watchdog import ensure_watchdog

    ensure_watchdog(catalog)  # memory alarm / expensive-query / mem-limit
    return catalog, srv


def main() -> int:
    ap = argparse.ArgumentParser(description="TPU-native MySQL-compatible SQL engine")
    ap.add_argument("--config", default=None, metavar="FILE",
                    help="TOML config file (pkg/config analog)")
    ap.add_argument("--host", default=None)
    ap.add_argument("-P", "--port", type=int, default=None)
    ap.add_argument("--path", default=None,
                    help="persistence dir: load on boot, snapshot on shutdown")
    ap.add_argument("--status-port", type=int, default=None,
                    help="HTTP status/metrics port (reference :10080)")
    ap.add_argument("--store", default=None, choices=["tpu"],
                    help="storage/compute engine (TPU device engine)")
    ap.add_argument("--mesh-devices", type=int, default=None, metavar="N",
                    help="serve every statement as one SPMD program over a "
                         "mesh of N devices (MPP mode); more than JAX sees "
                         "is an error at start-up")
    ap.add_argument("--tpch", type=float, default=None, metavar="SF",
                    help="bootstrap with TPC-H data at scale factor SF")
    args = ap.parse_args()

    from tidb_tpu.utils.config import Config

    cfg = Config.from_toml(args.config) if args.config else Config()
    cfg = cfg.override(
        host=args.host, port=args.port, path=args.path, store=args.store,
        mesh_devices=args.mesh_devices,
    )
    catalog, srv = bootstrap(cfg, tpch_sf=args.tpch, status_port=args.status_port)
    print(
        f"tidb_tpu listening on {cfg.host}:{srv.port} (store={cfg.store}"
        + (f", mesh-devices={cfg.mesh_devices})" if cfg.mesh_devices else ")"),
        flush=True,
    )

    def on_sigterm(*_):
        # TCPServer.shutdown() blocks until serve_forever() returns, and
        # the signal handler runs ON serve_forever's thread — stop the
        # accept loop from a helper thread; the main thread then falls
        # out of serve_forever() and persists below
        import threading

        threading.Thread(target=srv.shutdown, daemon=True).start()

    signal.signal(signal.SIGTERM, on_sigterm)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        srv.shutdown()
    if cfg.path:
        from tidb_tpu.storage.persist import save_catalog

        print(f"snapshotting catalog to {cfg.path} ...", flush=True)
        save_catalog(catalog, cfg.path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
