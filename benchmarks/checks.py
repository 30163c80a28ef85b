"""The comparison that decides `correct`.

A reference (`reference/<query>.py`) turns the seeded host columns into
the rows a statement must return, each column tagged with a kind:

- `str`, `int`: equal, cell for cell;
- `dec<N>`: a DECIMAL of scale N the engine accumulates on integers:
  equal as scaled integers;
- `wide<N>`: a DECIMAL sum the engine accumulates in float64 by design
  (products of scale >= 4): relative deviation from the exact integer
  sum, held to `wide_sum_rel_dev`'s limit;
- `float`: a DOUBLE (an average): relative deviation, held to
  `float_rel_dev`'s limit.

Every answer received in the window is judged; a run's numbers are the
totals and the maxima over them. The limits and the readings they were
set from are in PERF.md section 2."""

from __future__ import annotations

from decimal import Decimal, InvalidOperation

# name -> limit. Counts are exact comparisons (limit 0). The two
# deviations lie between the program's largest reading over its seeds
# and the float32 control's smallest (PERF.md section 2 gives both).
LIMITS = {
    "answers_missing": 0,
    "cells_wrong": 0,
    "readback_wrong": 0,
    "wide_sum_rel_dev": 1e-10,
    "float_rel_dev": 1e-10,
}


class Tally:
    """The numbers compared in one run."""

    def __init__(self):
        self.values = {name: 0 for name in LIMITS}
        self.answers = 0
        self.first_wrong = None  # (statement, detail) for the log

    def worse(self, name: str, value) -> None:
        if value > self.values[name]:
            self.values[name] = value

    def add(self, name: str, count: int = 1, detail=None) -> None:
        self.values[name] += count
        if count and self.first_wrong is None:
            self.first_wrong = detail

    def correct(self) -> bool:
        return self.answers > 0 and all(
            self.values[name] <= limit for name, limit in LIMITS.items()
        )

    def report(self) -> dict:
        """name -> [number, limit], in LIMITS' order."""
        return {name: [self.values[name], LIMITS[name]] for name in LIMITS}


def _scaled(text, scale: int) -> int:
    return int(Decimal(text).scaleb(scale).to_integral_value())


def _rel(got, want) -> float:
    return abs(got - want) / max(abs(want), 1e-300) if got != want else 0.0


def judge_cell(kind: str, got, want, tally: Tally, wrong: str = "cells_wrong") -> None:
    """One received text cell against the reference's value. A cell
    that does not parse as its kind is wrong."""
    base = kind.rstrip("0123456789")
    if base not in ("str", "int", "dec", "wide", "float"):
        raise ValueError(f"unknown kind {kind!r}")
    ok = True
    try:
        if got is None or want is None:
            ok = got is None and want is None
        elif base == "str":
            ok = got == want
        elif base == "int":
            ok = int(got) == int(want)
        elif base == "dec":
            ok = _scaled(got, int(kind[3:])) == int(want)
        elif base == "wide":
            tally.worse("wide_sum_rel_dev", _rel(_scaled(got, int(kind[4:])), int(want)))
        else:
            tally.worse("float_rel_dev", _rel(float(got), float(want)))
    except (InvalidOperation, ValueError, TypeError):
        ok = False
    if not ok:
        tally.add(wrong, 1, (kind, got, want))


def judge_rows(kinds, got_rows, want_rows, tally: Tally, wrong: str = "cells_wrong") -> None:
    """Rows in the reference's order, cell for cell. A missing or an
    extra row counts one wrong cell for each of its columns."""
    width = len(kinds)
    if len(got_rows) != len(want_rows):
        tally.add(wrong, width * abs(len(got_rows) - len(want_rows)),
                  ("rows", len(got_rows), len(want_rows)))
    for got, want in zip(got_rows, want_rows):
        if len(got) != width:
            tally.add(wrong, width, ("columns", len(got), width))
            continue
        for kind, g, w in zip(kinds, got, want):
            judge_cell(kind, g, w, tally, wrong)


def render_rows(kinds, rows) -> list:
    """Reference rows as the text protocol would carry them: what stands
    in the program's place when a control is judged."""

    def text(kind, v):
        if v is None:
            return None
        if kind == "str":
            return str(v)
        if kind == "int":
            return str(int(v))
        if kind == "float":
            return repr(float(v))
        scale = int(kind[3:] if kind.startswith("dec") else kind[4:])
        return str(Decimal(int(v)).scaleb(-scale))

    return [tuple(text(k, v) for k, v in zip(kinds, row)) for row in rows]
