"""The per-layer metrics that read the served path's named spans and
the collector's seconds (served_path.py): `enqueue_fetch_ms`, `served_unspanned_ms`,
`gc_ms_per_stmt`, `tail_gc_ms`, on a hand-made record whose answers can
be worked out on paper, and None on a record of a program from before
(testdata/run_record_scan_cpu.json)."""

import json
import os

import pytest

import run as harness
import served_path

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "testdata", "run_record_scan_cpu.json")) as f:
    RECORDED = json.load(f)
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    SPEC = json.load(f)
NEW = {
    "enqueue_fetch_ms": ("physical executor", "stmts_per_s"),
    "served_unspanned_ms": ("session and planner", "stmts_per_s"),
    "gc_ms_per_stmt": ("process entry", "stmts_per_s"),
    "tail_gc_ms": ("statement class", "stmt_p95_ms"),
}


def _flight(wait_ms: float, gc_ms: float, passes=(0, 0, 0)):
    """A served statement laid out in milliseconds, with self time at
    three depths: stmt 0-1 and 18-18.5 (+ `wait_ms` - 6 beyond 14),
    session 1-2 and 17-18, execute 5-6 and 14-15, and dispatch's own
    half millisecond after its two leaves."""
    more = wait_ms - 6.0
    rows = [
        ("stmt", 0, 20 + more),
        ("stmt/session", 1, 18 + more),
        ("stmt/session/parse", 2, 3),
        ("stmt/session/plan", 3, 5),
        ("stmt/session/execute", 5, 15 + more),
        ("stmt/session/execute/dispatch", 6, 8),
        ("stmt/session/execute/dispatch/launch", 6, 7),
        ("stmt/session/execute/dispatch/enqueue-fetch", 7, 7.5),
        ("stmt/session/execute/device-wait", 8, 14 + more),
        ("stmt/session/observe", 15 + more, 17 + more),
        ("stmt/wire/write", 18.5 + more, 20 + more),
    ]
    return {
        "spans": [{"name": n, "start_s": a / 1e3, "seconds": (b - a) / 1e3} for n, a, b in rows],
        "served_s": (20 + more) / 1e3, "background": [],
        "gc_s": gc_ms / 1e3, "gc_passes": list(passes),
    }


def _statement(latency_ms, flight):
    return {"name": "q", "latency_s": latency_ms / 1e3, "error": None, "traced": False,
            "flight": flight}


def test_on_paper():
    flights = [_flight(6.0, 0.0), _flight(6.0, 4.0, (2, 0, 0)), _flight(41.0, 35.0, (1, 0, 1))]
    # leaves: parse 1, plan 2, launch 1, enqueue-fetch 0.5, device-wait
    # 6, observe 2, write 1.5 = 14 of 20 ms; the other 6 are self time
    assert served_path.unspanned_seconds(flights[0]) == pytest.approx(6e-3)
    assert served_path.unspanned_seconds(flights[2]) == pytest.approx(6e-3)
    run = {"statements": [
        _statement(21.0, flights[0]), _statement(21.5, flights[1]), _statement(56.0, flights[2]),
        dict(_statement(9000.0, None), error="failed"),  # a failed statement is no tail
    ]}
    read = lambda name: harness.read_layer(name, run)  # noqa: E731
    assert read("enqueue_fetch_ms") == pytest.approx(0.5)
    assert read("served_unspanned_ms") == pytest.approx(6.0)
    assert read("gc_ms_per_stmt") == pytest.approx((0.0 + 4.0 + 35.0) / 3)
    assert read("tail_gc_ms") == pytest.approx(35.0)  # the 56-ms statement's
    # dispatch_ms keeps its meaning: the two halves and dispatch's own
    assert harness.read_layer("dispatch_ms", run) == pytest.approx(2.0)


def test_a_repeated_path_and_a_root_of_its_own():
    """A cold statement dispatches twice: each dispatch's leaves count
    once, by their intervals and not by their names."""
    flight = _flight(6.0, 0.0)
    flight["spans"].append({"name": "stmt/session/execute/dispatch", "start_s": 14.2e-3,
                            "seconds": 0.6e-3})
    flight["spans"].append({"name": "stmt/session/execute/dispatch/launch",
                            "start_s": 14.3e-3, "seconds": 0.4e-3})
    # the second dispatch lies in execute's own 14-15: 0.4 of it is a leaf
    assert served_path.unspanned_seconds(flight) == pytest.approx(5.6e-3)


def test_a_program_from_before_reads_nothing():
    for name in NEW:
        assert harness.read_layer(name, RECORDED) is None, name
    # a flight with the spans of before and no collector keys is not read
    bare = _flight(6.0, 0.0)
    del bare["gc_s"], bare["gc_passes"]
    run = {"statements": [_statement(21.0, bare)]}
    for name in NEW:
        assert harness.read_layer(name, run) is None, name


def test_the_four_are_declared_for_every_cell():
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    layers = {m["layer"] for m in SPEC["per_layer"]}
    for name, (layer, moves) in NEW.items():
        entry = declared[name]
        assert entry["layer"] == layer and entry["moves"] == moves, name
        assert "workloads" not in entry and entry["unit"] == "ms", name
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py")), name
    assert set(v[0] for v in NEW.values()) <= layers
