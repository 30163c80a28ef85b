"""The metrics of the Q18 cell: the two readers of the flights' sorted
group-bys on hand-made run records, what they read from a program
without those fields (the parent's: nothing, and no error), how the
cell is declared, and the controls of `correct` on a rehearsal of the
cell on the CPU at SF 0.01 (about 20 s)."""

import json
import os

import control
import run as harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELL = "tpch_sf1_q18.q18"
NEW = ("q18_ms", "sorted_group_rows_per_stmt", "sorted_group_fill_pct")


def record(flights):
    return {"statements": [
        {"name": "q18", "error": None, "traced": traced, "latency_s": latency, "flight": flight}
        for traced, latency, flight in flights]}


def test_the_readers_read_the_flights_groupings_and_nothing_from_a_program_without_them():
    flight = {"sorted_groupings": 2, "sorted_group_rows": 6_001_600, "sorted_groups": 1_500_060,
              "sorted_group_slots": 2_098_176}
    run = record([(True, 0.5, dict(flight)),
                  (True, 0.7, dict(flight, sorted_group_rows=6_001_800, sorted_groups=1_500_100)),
                  (False, 9.0, dict(flight, sorted_group_rows=1))])
    assert harness.read_layer("sorted_group_rows_per_stmt", run) == 6_001_700
    assert harness.read_layer("sorted_group_fill_pct", run) == 100.0 * 3_000_160 / 4_196_352
    assert harness.read_layer("q18_ms", run) == 700.0
    # the parent's flights have no such keys; a dense or scalar statement's read 0
    for other in ({"phases": {}}, dict(flight, sorted_groupings=0), None):
        for s in run["statements"]:
            s["flight"] = other
        assert harness.read_layer("sorted_group_rows_per_stmt", run) is None
        assert harness.read_layer("sorted_group_fill_pct", run) is None


def test_the_new_metrics_are_declared_for_the_q18_cell_alone_and_last():
    names = [m["name"] for m in SPEC["per_layer"]]
    assert tuple(names[-3:]) == NEW
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW:
        assert declared[name]["workloads"] == [CELL], name
    assert declared["q18_ms"]["moves"] == "stmt_p95_ms"
    assert declared["sorted_group_fill_pct"]["unit"] == "%"
    assert SPEC["workloads"][-1] == {**SPEC["workloads"][-1], "name": CELL, "chips": 1,
                                     "config": "tpch_sf1_q18", "traffic": "q18"}
    assert SPEC["configs"][-1]["name"] == "tpch_sf1_q18"
    with open(os.path.join(BENCH, "traffic", "q18.json")) as f:
        assert json.load(f) == {"loop": "closed", "clients": 1, "statements": ["q18"],
                                "trace_seconds": 3}


def test_a_sound_rehearsal_is_correct_and_a_stale_read_is_not():
    """Every cell of Q18 is exact, and each order's sum stays under 2**24
    hundredths, so a float32 reference equals the exact one: that
    control says nothing here (PERF.md section 6, PR 35). The stale
    read does."""
    args = harness.parse_args(["--workload", CELL, "--seed", "1", "--seconds", "1",
                               "--trace", "1", "--rehearse-cpu-sf", "0.01"])
    result, judged = harness.run_cell(args)
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0
    assert set(NEW) <= set(result["rehearsed_metrics"])
    controls = control.judge_controls(judged)
    assert not controls["stale_read"]["correct"]
    assert controls["stale_read"]["checks"]["readback_wrong"][0] > 0
    assert controls["float32"]["checks"]["cells_wrong"][0] == 0


def test_a_q18_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The server renders every fifth DECIMAL cell with its last digit
    changed: the whole run, judged as always, is not correct."""
    from tidb_tpu.server import protocol

    real, calls = protocol.format_value, [0]

    def altered(v, t):
        out = real(v, t)
        calls[0] += 1
        if out and calls[0] % 5 == 0 and out[-1:].isdigit() and b"." in out:
            out = out[:-1] + (b"1" if out[-1:] != b"1" else b"2")
        return out

    monkeypatch.setattr(protocol, "format_value", altered)
    args = harness.parse_args(["--workload", CELL, "--seed", "1", "--seconds", "1",
                               "--rehearse-cpu-sf", "0.01"])
    result, _judged = harness.run_cell(args)
    assert not result["correct"] and result["checks"]["cells_wrong"][0] > 0
