#!/usr/bin/env python
"""Device self time of one ``.xplane.pb`` summed by operator scope.

``PlanCompiler._build`` wraps every plan node in
``jax.named_scope("<EXPLAIN label>#<nid>")`` (planner/physical.py
``scope_name``), so each HLO op's ``op_name`` holds the stack of the
operators that emitted it. The profiler keeps it as the ``tf_op`` stat
of the op's *event metadata*, which ``jax.profiler.ProfileData`` does
not show: this reads the protobuf itself (TensorFlow's copy of
``xplane_pb2``; nothing else of TensorFlow is used).

Prints, for the window the device ops span (seconds are the mean over
the devices that ran an op: the chips of a mesh run one program):

- the modules launched, by name (``jit_<kind>``, obs/engine_watch.py);
- self time by innermost ``label#nid`` with each scope's largest ops
  and the source line that emitted them, how much of a scope lies in
  a named sub-scope an operator opens (``SUB_SCOPES``: a dense
  aggregate's ``/contract``, executor/aggregate.py; a join's
  ``/compact``, ``/lookup`` and ``/expand/search | gather``,
  executor/join.py; a sorted aggregate's ``/group/sort | reduce``,
  executor/sortops.py; on a mesh an exchange's
  ``/exchange/sort | pack | all-to-all`` and ``/broadcast/all-gather``,
  parallel/exchange.py), and who owns the custom
  fusions (``hlo_category`` "custom fusion": XLA's scatter-shaped
  kCustom) and the ``custom-call``s;
- how many module launches lie outside the ``execute/dispatch`` start
  to ``execute/device-wait`` end of a statement (the ``tidbtpu/``
  annotations of obs/flight.py), exactly and within ``--tolerance-ms``:
  host and device events are stamped by different clocks.

    python scripts/trace_by_scope.py <file.xplane.pb | dir> [--top 4]

PERF.md section 5 pastes its output; ROADMAP S0 queues the
``benchmark`` PR that moves the sum into ``benchmarks/trace_reduce.py``.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import sys

SCOPE = re.compile(r"([^/]*#\d+)(?=/|$)")
#: ``jax.named_scope``s operators open inside their own scope
SUB_SCOPES = (
    # a dense aggregate's digit contraction (executor/aggregate.py)
    "contract",
    "compact",  # a join's output compaction (executor/join.py)
    "lookup",  # a sorted unique lookup's reads at lo (executor/join.py)
    # an expanding join: lo/hi and the slot-to-probe search, the emit
    "expand", "expand/search", "expand/gather",
    # a sorted group-by: the sort and the boundaries, the stacked gather
    # and the cumulative sums (executor/sortops.py)
    "group", "group/sort", "group/reduce",
    # a repartition and its stages, a broadcast (parallel/exchange.py)
    "exchange", "exchange/sort", "exchange/pack", "exchange/all-to-all",
    "broadcast/all-gather",
)


def load(path: str):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    if os.path.isdir(path):
        found = sorted(
            glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
        )
        if not found:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = found[-1]
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def events(plane, line):
    """(name, start ns, end ns, {stat: value}) of a line's events, the
    event's own stats laid over its metadata's."""
    stat_name = {k: v.name for k, v in plane.stat_metadata.items()}

    def stats(xstats):
        out = {}
        for st in xstats:
            value = getattr(st, st.WhichOneof("value"))
            if st.WhichOneof("value") == "ref_value":
                value = stat_name.get(value, value)
            out[stat_name.get(st.metadata_id)] = value
        return out

    meta = {}
    for e in line.events:
        md = plane.event_metadata[e.metadata_id]
        if e.metadata_id not in meta:
            meta[e.metadata_id] = stats(md.stats)
        start = line.timestamp_ns + e.offset_ps / 1e3
        yield (
            md.name, start, start + e.duration_ps / 1e3,
            {**meta[e.metadata_id], **stats(e.stats)},
        )


def self_times(ops):
    """[(op, own ns)]: an op's time less that of the ops nested in it
    (a while loop's body runs as events inside the loop's)."""
    out, stack = [], []

    def close(upto):
        while stack and stack[-1][1] <= upto:
            op, _end, own = stack.pop()
            out.append((op, max(own, 0.0)))

    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(op[1])
        if stack:
            stack[-1][2] -= op[2] - op[1]
        stack.append([op, op[2], op[2] - op[1]])
    close(float("inf"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=4, help="ops shown per scope")
    ap.add_argument("--tolerance-ms", type=float, default=0.6)
    args = ap.parse_args(argv)
    space = load(args.trace)

    by_device, modules, notes = collections.defaultdict(list), [], []
    for plane in space.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:TPU") and line.name == "XLA Ops":
                by_device[plane.name] += list(events(plane, line))
            elif plane.name.startswith("/device:TPU") and line.name == "XLA Modules":
                modules += list(events(plane, line))
            elif plane.name.startswith("/host:"):
                notes += [
                    e for e in events(plane, line) if e[0].startswith("tidbtpu/")
                ]
    ops = [op for dev_ops in by_device.values() for op in dev_ops]
    if not ops:
        print("no device op in the trace")
        return 1
    lo, hi = min(o[1] for o in ops), max(o[2] for o in ops)
    n_dev = len(by_device)
    print(f"device ops {len(ops)} on {n_dev} device(s) over {(hi - lo) / 1e9:.4f} s; modules:",
          dict(collections.Counter(re.sub(r"\(.*", "", m[0]) for m in modules)))

    by_scope = collections.defaultdict(collections.Counter)
    by_sub = collections.defaultdict(collections.Counter)
    custom = {"custom fusion": collections.Counter(),
              "custom-call": collections.Counter()}
    # ops nest within one device's line, not across devices
    timed = [pair for dev_ops in by_device.values() for pair in self_times(dev_ops)]
    for (name, _s, _t, st), own in timed:
        own /= n_dev
        tf_op = str(st.get("tf_op", ""))
        found = SCOPE.findall(tf_op)
        scope = found[-1] if found else "(no scope)"
        for sub in SUB_SCOPES:
            if f"{scope}/{sub}/" in tf_op:
                by_sub[scope][sub] += own
        short = re.sub(r"\{[^{}]*\}", "", name).lstrip("%").split(" ")[0]
        where = str(st.get("source", "")).rsplit("/tidb_tpu/", 1)[-1]
        by_scope[scope][f"{short} [{st.get('hlo_category', '')}] {where}"] += own
        if st.get("hlo_category") == "custom fusion":
            custom["custom fusion"][scope] += own
        if " custom-call(" in name:
            custom["custom-call"][scope] += own
    total = sum(sum(c.values()) for c in by_scope.values())
    print(f"device self time by operator scope, {total / 1e9:.4f} s in all")
    for scope, per_op in sorted(by_scope.items(), key=lambda kv: -sum(kv[1].values())):
        own = sum(per_op.values())
        print(f"  {own / 1e9:9.4f} s {100 * own / total:5.1f} %  {scope}")
        for sub, ns in by_sub[scope].most_common():
            print(f"      {ns / 1e9:9.4f}  of it under /{sub}")
        for op, ns in per_op.most_common(args.top):
            print(f"      {ns / 1e9:9.4f}  {op}")
    for kind, owners in custom.items():
        print(f"owners of {kind}:", ", ".join(
            f"{scope} {ns / 1e9:.4f} s" for scope, ns in owners.most_common()
        ) or "none")

    spans = collections.defaultdict(dict)  # qid -> path -> [start, end]
    for name, start, end, st in notes:
        if name.endswith(("execute/dispatch", "execute/device-wait")):
            at = spans[st.get("qid")].setdefault(name.rsplit("/", 1)[1], [start, end])
            at[0], at[1] = min(at[0], start), max(at[1], end)
    inside = [
        (s["dispatch"][0], s["device-wait"][1])
        for s in spans.values() if "dispatch" in s and "device-wait" in s
    ]
    for tol in (0.0, args.tolerance_ms * 1e6):
        out = [
            m for m in modules
            if not any(a - tol <= m[1] and m[2] <= b + tol for a, b in inside)
        ]
        worst = max(
            (min(max(a - m[1], m[2] - b) for a, b in inside) for m in out),
            default=0.0,
        ) if inside else 0.0
        print(f"module launches {len(modules)}, statements {len(inside)}: "
              f"{len(out)} outside dispatch..device-wait by more than "
              f"{tol / 1e6:.1f} ms (farthest {worst / 1e6:.3f} ms)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
