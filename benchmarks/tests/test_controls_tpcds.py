"""`correct` has been shown to fail for the cell `tpcds_sf1.q95`: a
rehearsal of the whole command on the CPU at SF 0.01 is `correct`, and
the controls put in the program's place are not. The stale read fails
at the rehearsal's size. The float32 control cannot fail there (Q95's
sums are exact DECIMAL(7,2) sums over 0-2 rows at SF 0.01: float32 holds
them to the cent), so it is judged where the cell runs: on the SF1
population, by the references alone (numpy, a few seconds, no engine)."""

import importlib.util
import os

import pytest

import checks
import control
import run as harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tpcds_sf1.q95"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def rehearse(seed=5, seconds=1.0, trace=0):
    args = harness.parse_args([
        "--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace), "--rehearse-cpu-sf", "0.01",
    ])
    return harness.run_cell(args)


@pytest.fixture(scope="module")
def rehearsal():
    return rehearse()


def test_rehearsal_is_correct_and_reads_its_write_back(rehearsal):
    result, judged = rehearsal
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["checks"]["readback_wrong"] == [0, 0]
    # the write moved the answer: the read-back was not the snapshot's
    reader, data = judged["reader"], judged["data"]
    before = reader.reference.expected(data)
    after = reader.reference.expected(data, extra=judged["write"]["extra"])
    assert before != after and after[0][1] is not None and after[0][2] is not None


@pytest.mark.parametrize("seed", [5, 2**31 + 9, 77])
def test_stale_read_control_is_not_correct(rehearsal, seed):
    judged = rehearsal[1] if seed == 5 else rehearse(seed)[1]
    controls = control.judge_controls(judged)
    assert not controls["stale_read"]["correct"]
    assert controls["stale_read"]["checks"]["readback_wrong"][0] > 0


@pytest.mark.parametrize("seed", [3000000019, 12])
def test_float32_control_is_not_correct_on_the_sf1_population(seed):
    datagen = _load("datagen/tpcds.py", "c_datagen_tpcds")
    reference = _load("reference/q95.py", "c_reference_q95")
    loader = _load("loaders/tpcds.py", "c_loader_tpcds")
    data = loader.HostData(datagen.generate(1, seed))
    exact = reference.expected(data)
    low = reference.expected(data, precision="float32")
    assert exact[0][0] > 20  # some dozens of orders qualify at SF1
    tally = checks.Tally()
    tally.answers += 1
    checks.judge_rows(reference.KINDS, checks.render_rows(reference.KINDS, low), exact, tally)
    assert not tally.correct() and tally.values["cells_wrong"] > 0
    same = checks.Tally()
    same.answers += 1
    checks.judge_rows(reference.KINDS, checks.render_rows(reference.KINDS, exact), exact, same)
    assert same.correct()


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The server renders every DECIMAL cell with its last digit
    changed: the whole run, judged as always, is not correct."""
    from tidb_tpu.server import protocol

    real = protocol.format_value

    def altered(v, t):
        out = real(v, t)
        if out and out[-1:].isdigit() and b"." in out:
            out = out[:-1] + (b"1" if out[-1:] != b"1" else b"2")
        return out

    monkeypatch.setattr(protocol, "format_value", altered)
    result, _judged = rehearse(seed=2**31 + 9)
    assert not result["correct"]
    assert result["checks"]["cells_wrong"][0] + result["checks"]["readback_wrong"][0] > 0


def test_layer_readers_read_the_flights_expansions_and_nothing_from_a_program_without_them():
    flight = {"join_expansions": 2, "join_expand_rows": 300, "join_expand_slots": 400}
    run = {"statements": [
        {"name": "q95", "error": None, "traced": True, "latency_s": 0.5, "flight": dict(flight)},
        {"name": "q95", "error": None, "traced": True, "latency_s": 0.7,
         "flight": {"join_expansions": 2, "join_expand_rows": 500, "join_expand_slots": 400}},
        {"name": "q95", "error": None, "traced": False, "latency_s": 9.0, "flight": dict(flight)},
    ]}
    assert harness.read_layer("join_expand_rows_per_stmt", run) == 400
    assert harness.read_layer("join_expand_fill_pct", run) == 100.0
    assert harness.read_layer("q95_ms", run) == 700.0
    for s in run["statements"]:  # the parent's flights: no such keys
        s["flight"] = {"phases": {}}
    assert harness.read_layer("join_expand_rows_per_stmt", run) is None
    assert harness.read_layer("join_expand_fill_pct", run) is None


def test_at_sf1_every_knob_of_q95_has_its_first_tile_without_a_run(monkeypatch):
    """The cold run fits its 360 s only if Q95 compiles ONE whole
    program: the planner's estimates have to give the two expansions'
    tiles (16,777,216 and 8,388,608 slots over 9.02 M and 7.9 M rows),
    so that the first program is the steady one. Planned at SF1 on the
    CPU (loaded and ANALYZEd, never executed)."""
    from tidb_tpu.planner import physical
    from tidb_tpu.session import Session

    loader = _load("loaders/tpcds.py", "c_loader_tpcds_sf1")
    seen = {}

    class Planned(Exception):
        pass

    def capture(self, cq, inputs, shape_key):
        seen["first"], seen["out"], seen["floors"] = dict(cq.first_caps), cq.first_out_cap, cq.floors
        raise Planned()

    monkeypatch.setattr(physical.PhysicalExecutor, "_steady_first", capture)
    import json

    with open(os.path.join(BENCH, "configs", "tpcds_sf1.json")) as f:
        config = json.load(f)
    dep = loader.Deployment(config, 3000000019, 1)
    assert dep.row_counts() == config["row_counts"]
    session = Session(dep.catalog, db=loader.DATABASE)
    for sql in dep.analyze_statements():
        session.execute(sql)
    with open(os.path.join(BENCH, "queries", "q95.sql")) as f:
        with pytest.raises(Planned):
            session.execute(" ".join(f.read().split()))
    tiles = sorted(seen["first"].values())
    assert tiles[-2:] == [8_388_608, 16_777_216] and seen["out"] == 16
    assert all(t in (16, 1024) for t in tiles[:-2]) and 1024 in seen["floors"].values()
