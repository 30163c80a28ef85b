"""The per-layer metrics that read the program's own spans (spans.py,
PR 27): on a small hand-made record whose answers can be worked out on
paper, on a run record kept from a CPU rehearsal of the scan cell
(testdata/; its times are not the device's, its shape is), and on a
record of a program that writes no spans."""

import copy
import json
import os

import pytest

import run as harness
import spans

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "testdata", "run_record_scan_cpu.json")) as f:
    RECORDED = json.load(f)
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    SPEC = json.load(f)
NEW = [
    "wire_write_ms", "client_ms", "observe_ms", "session_self_ms", "inputs_ms",
    "dispatch_ms", "device_wait_ms", "fetch_ms", "final_merge_ms",
    "background_overlap_pct", "tail_excess_ms", "tail_excess_device_wait_ms",
    "tail_background_ms",
]


def _flight(ms: dict, background=()):
    """A flight whose spans are laid as the program lays them, from the
    milliseconds of each leaf and 1 ms of self time in session, execute
    and stmt."""
    rows, at = [], 1.0  # stmt's own first millisecond

    def leaf(path, length):
        nonlocal at
        rows.append({"name": path, "start_s": at / 1e3, "seconds": length / 1e3})
        at += length

    session_at = at
    at += 1.0  # session's self time
    leaf("stmt/session/parse", ms["parse"])
    leaf("stmt/session/plan", ms["plan"])
    execute_at = at
    at += 1.0  # execute's self time
    for name in ("inputs", "dispatch", "device-wait", "fetch"):
        leaf("stmt/session/execute/" + name, ms[name])
    rows.append({"name": "stmt/session/execute", "start_s": execute_at / 1e3,
                 "seconds": (at - execute_at) / 1e3})
    leaf("stmt/session/final-merge", ms["final-merge"])
    leaf("stmt/session/observe", ms["observe"])
    rows.append({"name": "stmt/session", "start_s": session_at / 1e3,
                 "seconds": (at - session_at) / 1e3})
    leaf("stmt/wire/write", ms["write"])
    rows.append({"name": "stmt", "start_s": 0.0, "seconds": at / 1e3})
    execute_s = (ms["inputs"] + ms["dispatch"] + ms["device-wait"] + ms["fetch"] + 1.0) / 1e3
    return {
        "spans": rows, "served_s": at / 1e3, "background": [list(b) for b in background],
        "duration_s": (ms["plan"] + ms["final-merge"]) / 1e3 + execute_s,
        "phases": {"execute": {"seconds": execute_s}, "final-merge": {"seconds": ms["final-merge"] / 1e3}},
    }


def _statement(name, latency_ms, flight):
    return {"name": name, "latency_s": latency_ms / 1e3, "error": None, "traced": False, "flight": flight}


BASE = {"parse": 0.5, "plan": 1.5, "inputs": 0.25, "dispatch": 0.75, "device-wait": 90.0,
        "fetch": 2.0, "final-merge": 0.5, "observe": 0.25, "write": 0.75}


def test_on_paper():
    far = dict(BASE, **{"device-wait": 95.0, "observe": 4.25})
    flights = [_flight(BASE), _flight(BASE), _flight(far, [("stats-auto-analyze", 0.003), ("ttl-worker", 0.001)])]
    served = [1e3 * f["served_s"] for f in flights]
    assert served[0] == pytest.approx(99.5) and served[2] == pytest.approx(108.5)
    run = {"statements": [
        _statement("q", served[0] + 0.5, flights[0]), _statement("q", served[1] + 0.5, flights[1]),
        _statement("q", served[2] + 1.0, flights[2]),
        dict(_statement("q", 5000.0, None), error="failed"),  # a failed statement is no tail
    ]}
    read = lambda name: harness.read_layer(name, run)  # noqa: E731
    assert read("wire_write_ms") == pytest.approx(0.75)
    assert read("client_ms") == pytest.approx((0.5 + 0.5 + 1.0) / 3)
    assert read("observe_ms") == pytest.approx((0.25 + 0.25 + 4.25) / 3)
    assert read("session_self_ms") == pytest.approx(1.0)
    assert read("inputs_ms") == pytest.approx(0.25)
    assert read("dispatch_ms") == pytest.approx(0.75)
    assert read("device_wait_ms") == pytest.approx((90 + 90 + 95) / 3)
    assert read("fetch_ms") == pytest.approx(2.0)
    assert read("final_merge_ms") == pytest.approx(0.5)
    assert read("background_overlap_pct") == pytest.approx(100 * 4.0 / sum(served))
    assert read("tail_excess_ms") == pytest.approx(served[2] + 1.0 - (served[0] + 0.5))
    assert read("tail_excess_device_wait_ms") == pytest.approx(5.0)
    assert read("tail_background_ms") == pytest.approx(4.0)
    # self time is the span less what nests directly in it
    assert spans.self_seconds_of(flights[0], "execute") == pytest.approx(1e-3)
    assert spans.self_seconds_of(flights[0], "stmt") == pytest.approx(1e-3)


def test_every_new_metric_is_declared_and_reads_the_recorded_run():
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    values = {}
    for name in NEW:
        assert name in declared and "workloads" not in declared[name], name
        values[name] = harness.read_layer(name, RECORDED)
        assert values[name] is not None, name
    old = {name: harness.read_layer(name, RECORDED) for name in ("execute_ms", "wire_ms", "plan_ms")}
    # the four parts of execute leave its own Python: the plan-cache
    # lookup and the streamed and host paths' checks before the inputs
    parts = sum(values[k] for k in ("inputs_ms", "dispatch_ms", "device_wait_ms", "fetch_ms"))
    assert 0 <= old["execute_ms"] - parts < 1.0
    # wire_ms, from outside, is latency less the flight's own wall: the
    # client, the write, observe, the parse (before the flight begins)
    # and the edges of stmt and session around them
    outside = values["wire_write_ms"] + values["observe_ms"] + values["client_ms"]
    rest = old["wire_ms"] - outside - spans.mean_ms(RECORDED, "parse")
    assert 0 <= rest < values["session_self_ms"] + spans.mean_ms(RECORDED, "stmt", of=spans.self_seconds_of)
    assert values["tail_excess_ms"] >= 0 and values["background_overlap_pct"] >= 0


def test_a_program_without_spans_reads_as_nothing():
    """The parent commit's flights have no `spans`: the readers return
    None and do not raise; the client-side tail metric still reads."""
    run = copy.deepcopy(RECORDED)
    for statement in run["statements"]:
        for key in ("spans", "served_s", "background"):
            statement["flight"].pop(key)
    for name in NEW:
        value = harness.read_layer(name, run)
        if name in ("tail_excess_ms", "final_merge_ms"):
            assert value is not None
        else:
            assert value is None, name
    run["statements"][0]["flight"] = None
    for name in NEW:
        harness.read_layer(name, run)
