"""`correct` has been shown to fail: the controls put in the program's
place, and the timed path broken underneath a whole run. Each case is
one rehearsal of the command on the CPU at SF 0.01 (about 15 s)."""

import pytest

import control
import run as harness

CELLS = ("tpch_sf1.join_q5", "tpch_sf10.scan")


def rehearse(workload, seed=5, seconds=1.0):
    args = harness.parse_args([
        "--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
        "--rehearse-cpu-sf", "0.01",
    ])
    return harness.run_cell(args)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_both_controls_are_not(workload):
    result, judged = rehearse(workload)
    assert result["correct"] and result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    controls = control.judge_controls(judged)
    assert not controls["float32"]["correct"]
    assert not controls["stale_read"]["correct"]
    assert controls["stale_read"]["checks"]["readback_wrong"][0] > 0
    low = controls["float32"]["checks"]
    assert low["wide_sum_rel_dev"][0] > 100 * low["wide_sum_rel_dev"][1]


@pytest.mark.parametrize("workload", CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(workload, monkeypatch):
    """The server renders every fifth cell, where it is a DECIMAL, with its last
    digit changed: the whole run, judged as always, is not correct."""
    from tidb_tpu.server import protocol

    real, calls = protocol.format_value, [0]

    def altered(v, t):
        out = real(v, t)
        calls[0] += 1
        if out and calls[0] % 5 == 0 and out[-1:].isdigit() and b"." in out:
            out = out[:-1] + (b"1" if out[-1:] != b"1" else b"2")
        return out

    monkeypatch.setattr(protocol, "format_value", altered)
    result, _judged = rehearse(workload)
    assert not result["correct"]
    wide, limit = result["checks"]["wide_sum_rel_dev"]
    assert result["checks"]["cells_wrong"][0] > 0 or wide > limit


def test_a_write_that_is_acknowledged_and_dropped_is_not_correct(monkeypatch):
    """The server acknowledges the INSERT and does not apply it: the
    read-back answers from the old snapshot."""
    from tidb_tpu.session import Session

    real = Session.execute

    def dropping(self, sql, *a, **kw):
        if sql.lstrip().lower().startswith("insert into"):
            sql = "select 1 from nation where 1 = 0"
        return real(self, sql, *a, **kw)

    monkeypatch.setattr(Session, "execute", dropping)
    result, _judged = rehearse("tpch_sf10.scan")
    assert not result["correct"] and result["checks"]["readback_wrong"][0] > 0


def test_without_a_tpu_the_command_refuses_and_prints_no_result(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    code = harness.main(["--workload", "tpch_sf1.join_q5", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert code != 0 and "correct" not in out.out and "no TPU" in out.err
