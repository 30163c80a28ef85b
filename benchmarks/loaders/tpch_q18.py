"""Loader `tpch_q18`: `loaders/tpch.py` in everything, for a program
whose flights count their sorted group-bys (`sorted_groupings`,
tidb_tpu/obs/flight.py).

A program from before that field serves Q18 rightly, but compiles it
anew for every data set, three whole programs and 205 s of compile a
seed: its run of the cell took 367 s where 360 are allowed, with ten
of ANALYZE's sixteen programs read from a cache (my chip run, PR 35;
PERF.md section 6). This loader refuses such a program before datagen,
with one line that says why, so that its run fails in seconds and not
at the limit."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_loaders_tpch", os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpch)

DATABASE = tpch.DATABASE
HostData = tpch.HostData
flight_rows = tpch.flight_rows
keep_flights = tpch.keep_flights
compilations = tpch.compilations


class GroupingRefused(RuntimeError):
    """The program's flights do not count its sorted group-bys."""


def counts_sorted_groupings() -> bool:
    from tidb_tpu.obs.flight import QueryFlight

    return "sorted_groupings" in QueryFlight.__slots__


class Deployment(tpch.Deployment):
    def __init__(self, config: dict, seed: int, scale_factor: float):
        if not counts_sorted_groupings():
            raise GroupingRefused(
                f"this program's flights have no sorted_groupings: it compiles Q18 anew for "
                f"every data set and cannot run {config['name']} inside a run's limit")
        super().__init__(config, seed, scale_factor)
