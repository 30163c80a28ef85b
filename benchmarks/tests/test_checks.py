"""The comparison: each kind of cell, row counts, and the rendering
that puts a reference in the program's place."""

import checks


def tally_of(kinds, got, want):
    tally = checks.Tally()
    tally.answers = 1
    checks.judge_rows(kinds, got, want, tally)
    return tally


def test_equal_rows_are_correct():
    kinds = ("str", "int", "dec2", "wide4", "float")
    want = [("A", 7, 12345, 10**15 + 1, 0.5)]
    tally = tally_of(kinds, checks.render_rows(kinds, want), want)
    assert tally.correct() and tally.values["wide_sum_rel_dev"] == 0


def test_exact_kinds_count_each_wrong_cell():
    kinds = ("str", "int", "dec2")
    tally = tally_of(kinds, [("B", "8", "123.46")], [("A", 7, 12345)])
    assert tally.values["cells_wrong"] == 3 and not tally.correct()


def test_decimal_text_forms_are_the_same_number():
    assert tally_of(("dec2",), [("252686.0",)], [(25268600,)]).correct()


def test_wide_sum_is_held_to_its_limit_not_to_equality():
    want = 5 * 10**14
    near = tally_of(("wide4",), [(str((want + 2) / 10**4),)], [(want,)])
    assert near.correct() and 0 < near.values["wide_sum_rel_dev"] < 1e-13
    far = tally_of(("wide4",), [(str((want + 10**8) / 10**4),)], [(want,)])
    assert not far.correct()


def test_missing_and_extra_rows_are_wrong():
    assert tally_of(("int",), [], [(1,)]).values["cells_wrong"] == 1
    assert tally_of(("int",), [("1",), ("2",)], [(1,)]).values["cells_wrong"] == 1


def test_unparsable_cell_is_wrong_and_null_matches_only_null():
    assert tally_of(("dec2",), [("n/a",)], [(1,)]).values["cells_wrong"] == 1
    assert tally_of(("int",), [(None,)], [(1,)]).values["cells_wrong"] == 1
    assert tally_of(("int",), [(None,)], [(None,)]).correct()


def test_no_answer_judged_is_not_correct():
    assert not checks.Tally().correct()
