#!/usr/bin/env python3
"""The controls of `correct` for TPC-H Q21: control.py's run and
judging, with the controls that can fail here. Run by hand on the chip
when a limit is set, and at a small size by the tests.

    python3 benchmarks/control_q21.py --workload tpch_sf1_q21.q21 --seed <n> --seconds <s>

control.py's `float32` reads as correct for Q21 (it counts rows and
sums nothing: reference/q21.py) and is reported, not required. So is

- `exists_any_line`: the EXISTS without its residual, so any other
  line of the order satisfies it, whatever its supplier. It differs
  only where an order's lines all come from one supplier, which is
  rare: on four of six seeds at SF 1 the first 100 rows are the same.

Each of these has to come out as not correct:

- `not_exists_any_line`: the NOT EXISTS without l3's lateness filter,
  so any line of another supplier defeats it;
- `stale_read`: control.py's, the read-back answered from the snapshot
  before the acknowledged write.

Prints one JSON line: the program's result line, and each control's
numbers beside their limits with whether it came out correct."""

from __future__ import annotations

import json
import sys

import control
import run as harness

REQUIRED = ("not_exists_any_line", "stale_read")


def judge_controls(judged: dict) -> dict:
    import checks

    out = control.judge_controls(judged)
    for precision in ("exists_any_line", "not_exists_any_line"):
        tally = checks.Tally()
        for name, st in judged["statements"].items():
            tally.answers += 1
            want = st.reference.expected(judged["data"], precision=precision)
            st.judge(checks.render_rows(st.reference.KINDS, want), judged["expected"][name], tally)
        out[precision] = {"correct": tally.correct(), "checks": tally.report()}
    return out


def main(argv=None) -> int:
    args = harness.parse_args(argv)
    try:
        result, judged = harness.run_cell(args)
    except harness.Refused as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 2
    out = {
        "workload": args.workload, "seed": args.seed, "program": result,
        "controls": judge_controls(judged),
    }
    print(json.dumps(out), flush=True)
    failed_as_they_must = not any(out["controls"][name]["correct"] for name in REQUIRED)
    return 0 if result["correct"] and failed_as_they_must else 1


if __name__ == "__main__":
    sys.exit(main())
