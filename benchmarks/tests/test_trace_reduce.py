"""The reduction from a trace to device numbers: on a hand-made trace
whose answers can be worked out on paper, and on the small trace
recorded on the chip (testdata/, cut by trim_trace.py)."""

import os

import pytest

import trace_reduce

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "testdata", "join_q18_q5.xplane.pb")
MS = 1e6  # ns


def test_busy_union_self_time_gaps_and_launches_on_paper():
    trace = {
        "devices": {"/device:TPU:0": {
            # a while of 40 ms with two ops nested in it (10 + 20), then
            # an op of 30 ms that overlaps nothing, 20 ms later
            "ops": [
                ("%while.1 = s32[] while(s32[] %a)", 10 * MS, 50 * MS),
                ("%sort.2 = u32[8]{0:T(1024)} sort(u32[8]{0} %x)", 10 * MS, 20 * MS),
                ("%fusion.3 = u32[8]{0} fusion(u32[8]{0} %y), kind=kLoop, calls=%f", 25 * MS, 45 * MS),
                ("%sort.2 = u32[8]{0:T(1024)} sort(u32[8]{0} %x)", 70 * MS, 100 * MS),
            ],
            "modules": [("jit_a(1)", 10 * MS, 50 * MS), ("jit_b(2)", 70 * MS, 100 * MS),
                        ("jit_c(3)", 500 * MS, 600 * MS)],  # outside the window
        }},
        "spans": [("stmt/a/outside_flight", 0.0, 60 * MS), ("stmt/b/outside_flight", 60 * MS, 110 * MS)],
    }
    flight = [("stmt/a/flight/execute", 5 * MS, 55 * MS)]
    got = trace_reduce.reduce_trace(trace, flight)
    assert got["window_s"] == pytest.approx(0.110)
    assert got["busy_s"] == pytest.approx(0.070)  # 40 + 30, the nested ops counted once
    assert got["launches"] == 2
    ops = dict(got["device_ops"])
    assert ops["sort.2 sort u32[8]"] == pytest.approx(0.040)  # 10 nested + 30 alone
    assert ops["fusion.3 fusion kLoop u32[8]"] == pytest.approx(0.020)
    assert ops["while.1 while s32[]"] == pytest.approx(0.010)  # 40 less its children
    gaps = dict(got["idle_gaps"])
    # 0-10 ms lies in a's flight, 50-70 has its middle at 60 (b), 100-110 in b
    assert gaps["stmt/a/flight/execute"] == pytest.approx(0.010)
    assert gaps["stmt/b/outside_flight"] == pytest.approx(0.030)
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - got["busy_s"])


def test_a_trace_with_no_device_op_gives_nothing():
    empty = {"devices": {"/device:TPU:0": {"ops": [], "modules": []}}, "spans": []}
    assert trace_reduce.reduce_trace(empty) is None
    assert trace_reduce.reduce_trace({"devices": {}, "spans": [("stmt/a", 0.0, 1.0)]}) is None


def test_two_devices_are_averaged():
    def dev(a, b):
        return {"ops": [("%x = s32[] add(s32[] %a)", a, b)], "modules": [("m", a, b)]}

    trace = {"devices": {"/device:TPU:0": dev(0.0, 40 * MS), "/device:TPU:1": dev(0.0, 20 * MS)},
             "spans": [("stmt/a", 0.0, 100 * MS)]}
    got = trace_reduce.reduce_trace(trace)
    assert got["devices"] == 2 and got["busy_s"] == pytest.approx(0.030) and got["launches"] == 1


def test_the_recorded_trace_of_a_q18_q5_cycle_on_the_chip():
    trace = trace_reduce.load(RECORDED)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert [s[0] for s in trace["spans"]] == ["stmt/q18", "stmt/q5"]
    got = trace_reduce.reduce_trace(trace)
    assert got["launches"] == 2  # one program a statement
    assert got["window_s"] == pytest.approx(3.672502993)
    assert got["busy_s"] == pytest.approx(3.644865742)  # the async line is not counted twice
    assert 100 * (1 - got["busy_s"] / got["window_s"]) == pytest.approx(0.7525, abs=1e-3)
    name, seconds = got["device_ops"][0]
    assert name.startswith("fusion.40 fusion") and seconds == pytest.approx(0.556446005)
    assert len(got["device_ops"]) == 10 and {g[0] for g in got["idle_gaps"]} == {"stmt/q18", "stmt/q5"}


def test_op_label_keeps_name_opcode_kind_and_shape():
    text = ("%fusion.40 = (u32[2097152]{0:T(1024)S(1)}, u32[2097152]{0:T(1024)S(1)}) "
            "fusion(u32[2097152]{0:T(1024)S(1)} %b, s32[6291456]{0:T(1024)} %c), "
            "kind=kCustom, calls=%fused_computation")
    assert trace_reduce.op_label(text) == "fusion.40 fusion kCustom (u32[2097152], u32[2097152])"
    assert trace_reduce.op_label("jit_traced(123)") == "jit_traced(123)"
