"""Worker-host process for the DCN fragment scheduler.

One worker = one EngineServer over a local catalog, executing dispatched
fragment plans SPMD on its own device mesh (intra-host ICI exchanges).
Every worker of a job loads identical deterministic data, so any host
can compute any fragment slice — which is what makes re-dispatch onto
survivors correct (parallel/dcn.py).

Run as a module:

    python -m tidb_tpu.parallel.dcn_worker \
        --cpu --port 0 --mesh-devices 4 --tpch-sf 0.002 --seed 3 \
        --tables orders,lineitem

Prints ``DCN_WORKER_READY port=<p>`` on stdout once serving; the parent
reads the line to learn the bound port.

Fault injection for the kill-one-worker tests: --die-on-fragment K
arms the worker-side dcn failpoints so the process hard-exits
(os._exit — no reply frame, no cleanup: real crash semantics) on its
K-th fragment execution; --die-at picks the site: ``execute`` (before
the work — the fragment is simply lost) or ``result-send`` (after the
work, before the reply — the duplicate-redelivery hazard the
coordinator ledger must fence)."""

from __future__ import annotations

import argparse
import os
import sys


def _cpu_rehearsal_env(local_devices: int) -> None:
    """--cpu: a CPU data-plane rehearsal on `local_devices` virtual
    devices. Must run BEFORE anything imports jax (tidb_tpu's import
    chain does)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={local_devices}"
        ).strip()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--secret", default=None)
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="intra-host mesh width; 0 = single device")
    ap.add_argument("--cpu", action="store_true",
                    help="CPU rehearsal: JAX_PLATFORMS=cpu with "
                    "--mesh-devices virtual devices. WITHOUT this flag "
                    "the worker opens the default backend and owns "
                    "every device it sees for its lifetime (a chip "
                    "belongs to one process: start one worker per "
                    "chip set, and never from a parent that has "
                    "touched JAX)")
    ap.add_argument("--tpch-sf", type=float, default=0.0,
                    help="load TPC-H at this scale factor into db 'tpch'")
    ap.add_argument("--tables", default="orders,lineitem")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--die-on-fragment", type=int, default=0,
                    help="hard-exit on the K-th hit of the --die-at site")
    ap.add_argument("--die-at",
                    choices=["execute", "result-send", "shuffle-push",
                             "shuffle-recv"],
                    default="execute",
                    help="where to die: fragment execute / reply send, "
                    "or mid-shuffle while pushing a partition packet "
                    "(shuffle-push) / receiving one (shuffle-recv)")
    ap.add_argument("--chaos-spec", default=None,
                    help="JSON list of chaos Fault dicts "
                    "(tidb_tpu/chaos/schedule.py) armed at startup — "
                    "the multihost chaos dryrun's per-worker fault "
                    "schedule (crash/hang/frame-loss composed, "
                    "deterministic per seed)")
    args = ap.parse_args(argv)

    if args.cpu:
        _cpu_rehearsal_env(max(args.mesh_devices, 1))

    from tidb_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()

    from tidb_tpu.server.engine_rpc import EngineServer
    from tidb_tpu.storage import Catalog
    from tidb_tpu.utils import failpoint

    cat = Catalog()
    if args.tpch_sf > 0:
        from tidb_tpu.bench import load_tpch

        load_tpch(
            cat, sf=args.tpch_sf, seed=args.seed,
            tables=[t for t in args.tables.split(",") if t],
        )

    if args.chaos_spec:
        import json

        from tidb_tpu.chaos.schedule import arm_spec

        arm_spec(json.loads(args.chaos_spec))

    if args.die_on_fragment > 0:
        site = {
            "execute": "dcn/fragment-execute",
            "result-send": "dcn/result-send",
            "shuffle-push": "shuffle/push",
            "shuffle-recv": "shuffle/recv",
        }[args.die_at]
        failpoint.enable(
            site,
            failpoint.after_n(
                args.die_on_fragment, lambda: os._exit(3)
            ),
        )

    srv = EngineServer(
        cat, host=args.host, port=args.port, secret=args.secret,
        mesh_devices=args.mesh_devices or None,
        # worker PROCESS: piggyback this registry's counter deltas on
        # fragment/shuffle replies so the coordinator /metrics reflects
        # fleet-wide engine activity (never set in-process — see
        # EngineServer.ship_registry)
        ship_registry=True,
        # worker PROCESS holds its OWN base-table copies: coordinator
        # DML reaches it only through delta_sync frames, buffered and
        # folded by the replica state (never set in-process — see
        # EngineServer delta_replica)
        delta_replica=True,
    )
    print(f"DCN_WORKER_READY port={srv.port}", flush=True)
    try:
        srv._tcp.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        srv.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
