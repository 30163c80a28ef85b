"""scripts/trace_by_scope.py --idle: the device's idle cut at the edges
of the program's annotations and each piece given to the innermost span
covering it, by overlap and not by the gap's midpoint."""

import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "trace_by_scope", os.path.join(REPO, "scripts", "trace_by_scope.py")
)
tbs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tbs)

MS = 1e6  # ns


def _statement(qid, t0, wait):
    """A served statement's annotations: stmt 0-20 ms after ``t0``,
    session 1-19, execute 4-18, dispatch/launch 5-6, device-wait from
    ``wait`` to 17, and wire/write 19-20."""
    at = lambda a, b: (t0 + a * MS, t0 + b * MS)  # noqa: E731
    return [
        ("stmt", *at(0, 20), qid),
        ("stmt/session", *at(1, 19), qid),
        ("stmt/session/execute", *at(4, 18), qid),
        ("stmt/session/execute/dispatch", *at(5, 6), qid),
        ("stmt/session/execute/dispatch/launch", *at(5, 6), qid),
        ("stmt/session/execute/device-wait", *at(wait, 17), qid),
        ("stmt/wire/write", *at(19, 20), qid),
    ]


def test_a_gap_is_cut_by_overlap_where_its_midpoint_would_name_one_span():
    notes = _statement(1, 0.0, 7) + _statement(2, 30 * MS, 7)
    # one gap from the first statement's device-wait end (16 ms into it)
    # to its launch in the second (35 ms): its midpoint, 25.5 ms, lies
    # between the statements, which would take all 19 ms
    got = tbs.apportion([(16 * MS, 35 * MS)], notes)
    assert got == pytest.approx({
        "stmt/session/execute/device-wait": 1 * MS,  # 16-17
        "stmt/session/execute": 2 * MS,              # 17-18 and 34-35
        "stmt/session": 4 * MS,                      # 18-19 and 31-34
        "stmt/wire/write": 1 * MS,                   # 19-20
        tbs.BETWEEN: 10 * MS,                        # 20-30
        "stmt/self": 1 * MS,                         # 30-31
    })
    assert sum(got.values()) == pytest.approx(19 * MS)


def test_every_piece_sums_to_the_idle_and_a_collector_pass_takes_its_piece():
    notes = _statement(7, 0.0, 6) + [
        ("gc/gen2", 8 * MS, 9 * MS, 0),          # a pass on any thread
        ("background/ttl-worker", 2 * MS, 3 * MS, 0),  # no statement's span
    ]
    idle = [(-5 * MS, 0.5 * MS), (6 * MS, 10 * MS), (17.5 * MS, 25 * MS)]
    got = tbs.apportion(idle, notes)
    assert got == pytest.approx({
        tbs.BETWEEN: 5 * MS + 5 * MS,      # before the root, after it
        "stmt/self": 0.5 * MS,
        "stmt/session/execute/device-wait": 2 * MS + 1 * MS,  # 6-8, 9-10
        tbs.GC: 1 * MS,
        "stmt/session/execute": 0.5 * MS,  # 17.5-18
        "stmt/session": 1 * MS,            # 18-19
        "stmt/wire/write": 1 * MS,         # 19-20
    })
    assert sum(got.values()) == pytest.approx(sum(b - a for a, b in idle))


def test_idle_of_is_the_complement_of_the_ops_in_the_window():
    ops = [("a", 2.0, 4.0), ("b", 3.0, 5.0), ("c", 7.0, 8.0), ("d", 11.0, 12.0)]
    assert tbs.idle_of(ops, 0.0, 10.0) == [(0.0, 2.0), (5.0, 7.0), (8.0, 10.0)]
    assert tbs.idle_of([], 1.0, 2.0) == [(1.0, 2.0)]
