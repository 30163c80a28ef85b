"""The metrics of the mesh cell: the byte function on a hand count, the
five readers on a run record kept from a CPU rehearsal of
`tpch_sf1_mesh4.join_q5` at SF 0.01 (testdata/; its times are not the
device's, its shape is) and on records of a one-device server, and the
loader's refusal of a program that cannot serve a mesh."""

import copy
import importlib.util
import json
import os

import pytest

import ici
import run as harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "testdata", "run_record_mesh_q5_cpu.json")) as f:
    MESH = json.load(f)
with open(os.path.join(BENCH, "testdata", "run_record_scan_cpu.json")) as f:
    ONE_DEVICE = json.load(f)
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    SPEC = json.load(f)
with open(os.path.join(BENCH, "peaks.json")) as f:
    V5E = json.load(f)["TPU v5 lite"]
CELL = "tpch_sf1_mesh4.join_q5"
COUNTED = ("exchanges_per_stmt", "exchange_rows_per_stmt", "exchange_mb_per_stmt")
NEW = COUNTED + ("mesh_q5_ms", "stmt_ici_roofline")


def on_the_chip(run, busy_s=2.0):
    """The record as a traced run on four v5e chips would carry it."""
    run = copy.deepcopy(run)
    run["trace"] = {"busy_s": busy_s, "window_s": 2.1, "launches": 4, "devices": 4}
    run["peaks"] = dict(V5E)
    return run


def test_the_byte_function_on_a_hand_count():
    # 1,000 rows of two int64 columns over four chips: a quarter stays
    assert ici.partition_bytes(1000, 16, 4) == 12000
    assert ici.partition_bytes(1000, 16, 1) == 0
    # a broadcast of 10 rows of one int64 reaches three other chips
    assert ici.broadcast_bytes(10, 8, 4) == 240
    # 4 chips x 200 GB/s for 2 busy seconds could carry 1.6e12 bytes
    assert ici.ici_share_pct(1.6e10, 4, 2.0, 200e9) == pytest.approx(1.0)
    assert ici.ici_share_pct(0, 4, 2.0, 200e9) is None
    assert ici.ici_share_pct(1e9, 1, 2.0, 200e9) is None
    with open(os.path.join(BENCH, "peaks_ici.json")) as f:
        for kind, row in json.load(f).items():
            assert kind in json.load(open(os.path.join(BENCH, "peaks.json")))
            assert row["source"] and row["ici_bytes_per_s"] * 8 == row["ici_bits_per_s"]


def test_the_new_metrics_are_declared_for_the_mesh_cell_alone():
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW:
        assert declared[name]["workloads"] == [CELL], name
    cell = [c for c in SPEC["workloads"] if c["name"] == CELL][0]
    assert cell["chips"] == 4 and cell["traffic"] == "join_q5"
    assert sum(c["chips"] == 4 for c in SPEC["workloads"]) == 1


def test_the_readers_on_the_recorded_mesh_run():
    traced = [s for s in MESH["statements"] if s["traced"]]
    assert len(traced) >= 4 and MESH["cell"]["name"] == CELL
    flights = [s["flight"] for s in traced]
    n = len(flights)
    read = lambda name, run=MESH: harness.read_layer(name, run)  # noqa: E731
    assert read("exchanges_per_stmt") == sum(f["exchanges"] for f in flights) / n >= 1
    assert read("exchange_rows_per_stmt") == sum(f["exchange_rows"] for f in flights) / n > 0
    assert read("exchange_mb_per_stmt") == pytest.approx(
        sum(f["exchange_bytes"] for f in flights) / n / 1e6)
    assert read("mesh_q5_ms") == harness.read_layer("q5_ms", MESH) > 0
    # a rehearsal has no trace and no peaks: no share of a roofline
    assert read("stmt_ici_roofline") is None
    chip = on_the_chip(MESH, busy_s=2.0)
    total = sum(f["exchange_bytes"] for f in flights)
    assert read("stmt_ici_roofline", chip) == pytest.approx(100 * total / 4 / 200e9 / 2.0)
    assert 0 < read("stmt_ici_roofline", chip) < 100
    chip["peaks"]["hbm_bytes_per_s"] = 1.0  # a device peaks_ici.json does not list
    assert read("stmt_ici_roofline", chip) is None


@pytest.mark.parametrize("record", ["no_keys", "zeros", "no_flight"])
def test_a_one_device_record_reads_as_nothing(record):
    """The parent's flights have no exchange keys, a one-device
    server's hold zeros: nothing to read, and nothing raised."""
    run = on_the_chip(ONE_DEVICE)
    for statement in run["statements"]:
        statement["traced"] = True
        if record == "zeros":
            statement["flight"].update(exchanges=0, exchange_rows=0, exchange_bytes=0)
        elif record == "no_flight":
            statement["flight"] = None
    for name in COUNTED + ("stmt_ici_roofline",):
        assert harness.read_layer(name, run) is None, name


def test_the_loader_refuses_a_server_that_takes_no_mesh(monkeypatch):
    """On the parent commit `Config` would accept `mesh_devices` and
    `bootstrap` would ignore it: the loader refuses before datagen."""
    import tidb_tpu.server as server_module

    spec = importlib.util.spec_from_file_location(
        "bench_tpch_mesh_under_test", os.path.join(BENCH, "loaders", "tpch_mesh.py"))
    loader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loader)

    class OldServer:
        def __init__(self, catalog=None, host="127.0.0.1", port=4000, status_port=None,
                     dcn_scheduler=None):
            raise AssertionError("the loader brought a server up")

    def no_datagen(*_a, **_kw):
        raise AssertionError("datagen ran before the refusal")

    monkeypatch.setattr(server_module, "Server", OldServer)
    monkeypatch.setattr(loader.tpch.datagen, "generate", no_datagen)
    with open(os.path.join(BENCH, "configs", "tpch_sf1_mesh4.json")) as f:
        config = json.load(f)
    with pytest.raises(loader.MeshRefused, match="takes no mesh_devices"):
        loader.Deployment(config, 1, 0.01)
