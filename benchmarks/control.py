#!/usr/bin/env python3
"""The controls of `correct`: a run of a cell, then the same judging
with the control put in the program's place. Not part of a benchmark
run; run by hand on the chip when a limit is set, and at a small size
by tests/test_controls.py.

    python3 benchmarks/control.py --workload <cell> --seed <n> --seconds <s>

Two controls, each of which has to come out as not correct:

- `float32`: every reference computed in float32, the nearest
  precision below the float64 the configuration states for its wide
  sums, rendered as the wire would carry it;
- `stale_read`: the read-back statement answered from the snapshot
  before the acknowledged write, which breaks the configuration's
  read-your-writes guarantee.

Prints one JSON line: the program's result line, and each control's
numbers beside their limits with whether it came out correct."""

from __future__ import annotations

import json
import sys

import run as harness


def judge_controls(judged: dict) -> dict:
    import checks

    def render(st, want):
        return checks.render_rows(st.reference.KINDS, want)

    low = checks.Tally()
    for name, st in judged["statements"].items():
        low.answers += 1
        st.judge(render(st, st.reference.expected(judged["data"], precision="float32")),
                 judged["expected"][name], low)
    stale = checks.Tally()
    stale.answers += 1
    reader = judged["reader"]
    reader.judge(render(reader, reader.reference.expected(judged["data"])),
                 reader.reference.expected(judged["data"], extra=judged["write"]["extra"]),
                 stale, wrong="readback_wrong")
    return {
        "float32": {"correct": low.correct(), "checks": low.report()},
        "stale_read": {"correct": stale.correct(), "checks": stale.report()},
    }


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
    failed_as_they_must = not any(c["correct"] for c in out["controls"].values())
    return 0 if result["correct"] and failed_as_they_must else 1


if __name__ == "__main__":
    sys.exit(main())
