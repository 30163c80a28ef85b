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
  executor/sortops.py; a window's ``/window/sort | scan``,
  executor/window.py; a semi or anti join with a residual's
  ``/residual/expand | filter | rejoin``, planner/physical.py; on a
  mesh an exchange's
  ``/exchange/sort | pack | all-to-all`` and ``/broadcast/all-gather``,
  parallel/exchange.py), and who owns the custom
  fusions (``hlo_category`` "custom fusion": XLA's scatter-shaped
  kCustom) and the ``custom-call``s;
- how many module launches lie outside the ``execute/dispatch`` start
  to ``execute/device-wait`` end of a statement (the ``tidbtpu/``
  annotations of obs/flight.py), exactly and within ``--tolerance-ms``:
  host and device events are stamped by different clocks.

    python scripts/trace_by_scope.py <file.xplane.pb | dir> [--top 4]

``--idle`` prints instead where the device waits: every device-idle
interval inside the traced window (the benchmark's ``bench/`` spans,
else the device ops' extent) is cut at the edges of the ``tidbtpu/``
annotations it overlaps, and each piece goes to the innermost program
span that covers it, by overlap and not by midpoint: a collector pass
(``gc/gen2``) before any statement span, a piece under a statement's
root and no deeper span to ``<root>/self``, a piece under no statement
span to ``between statements``. Milliseconds a statement by span path,
their total (the trace's idle), and a bound on how far the host and
device clocks disagree: the most negative (first device op of a module)
less (the start of the ``launch`` span nearest it).

    python scripts/trace_by_scope.py <file.xplane.pb | dir> --idle

PERF.md section 5 pastes its output; ROADMAP B9 queues the
``benchmark`` PR that moves the sum and the apportioning into
``benchmarks/trace_reduce.py``.
"""

from __future__ import annotations

import argparse
import bisect
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
    # a window operator: the sort, the boundaries and the inverse
    # permutation; the scans and the gather back (executor/window.py)
    "window", "window/sort", "window/scan",
    # a semi or anti join with a residual: the build's sort and the
    # expansion of (probe row, build row) pairs, the residual over the
    # pairs, the semi or anti join back on the probe's row id
    # (planner/physical.py)
    "residual", "residual/expand", "residual/filter", "residual/rejoin",
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


BETWEEN = "between statements"
GC = "gc/gen2"


def _label(active) -> str:
    """The label of a moment covered by the annotations ``active``
    ((path, start, end, qid)): a collector pass, else the deepest
    statement span (the latest opened of equal depth), else none."""
    if any(path == GC for path, *_rest in active):
        return GC
    spans = [n for n in active if n[3]]
    if not spans:
        return BETWEEN
    path = max(spans, key=lambda n: (n[0].count("/"), n[1]))[0]
    return path if "/" in path else path + "/self"


def labelled_segments(notes) -> list:
    """[(start, end, label)] laid end to end from the first annotation
    edge to the last, cut at every edge: each segment lies wholly
    inside or outside each annotation."""
    order = sorted(notes, key=lambda n: n[1])
    points = sorted({t for n in notes for t in (n[1], n[2])})
    out, active, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(order) and order[i][1] <= a:
            active.append(order[i])
            i += 1
        active = [n for n in active if n[2] > a]
        out.append((a, b, _label(active)))
    return out


def apportion(idle, notes) -> dict:
    """{label: ns} of the idle intervals ``idle`` ((start, end), sorted,
    disjoint), each cut by the annotations ``notes`` ((path, start, end,
    qid); qid 0 for an annotation of no statement, ``gc/gen2`` for a
    collector pass) it overlaps and every piece given to the innermost
    span covering it. The values sum to the idle."""
    segments = labelled_segments(notes)
    out = collections.Counter()
    j = 0
    for x, y in idle:
        while j < len(segments) and segments[j][1] <= x:
            j += 1
        covered, k = 0.0, j
        while k < len(segments) and segments[k][0] < y:
            a, b, label = segments[k]
            piece = min(b, y) - max(a, x)
            if piece > 0:
                out[label] += piece
                covered += piece
            k += 1
        if y - x - covered > 0:
            out[BETWEEN] += y - x - covered
    return dict(out)


def idle_of(ops, lo: float, hi: float) -> list:
    """The gaps between the union of ``ops`` ((name, start, end, ...))
    inside [lo, hi]."""
    gaps, edge = [], lo
    for _n, start, end, *_rest in sorted(ops, key=lambda o: o[1]):
        if end <= edge:
            continue
        if start >= hi:
            break
        if start > edge:
            gaps.append((edge, start))
        edge = max(edge, end)
    if edge < hi:
        gaps.append((edge, hi))
    return gaps


def idle_report(by_device, modules_by_device, notes, bench) -> None:
    """``--idle``: see the module docstring."""
    ops = [op for dev_ops in by_device.values() for op in dev_ops]
    if bench:
        lo, hi = min(b[1] for b in bench), max(b[2] for b in bench)
    else:
        lo, hi = min(o[1] for o in ops), max(o[2] for o in ops)
    program = [
        (name[len("tidbtpu/"):], start, end, int(st.get("qid") or 0))
        for name, start, end, st in notes
    ]
    n_dev = len(by_device)
    table = collections.Counter()
    for dev_ops in by_device.values():
        table.update(apportion(idle_of(dev_ops, lo, hi), program))
    idle = sum(table.values()) / n_dev
    roots = [n for n in program if n[3] and "/" not in n[0] and lo <= n[1] < hi]
    stmts = max(len(roots), 1)
    busy = (hi - lo) - idle
    print(f"window {(hi - lo) / 1e9:.4f} s on {n_dev} device(s), {len(roots)} statements; "
          f"busy {busy / 1e9:.4f} s, idle {idle / 1e9:.4f} s "
          f"({100 * idle / (hi - lo):.2f} %): {idle / stmts / 1e6:.3f} ms a statement")
    print("idle by the innermost program span (ms a statement, share of the idle):")
    for label, ns in table.most_common():
        ns /= n_dev
        print(f"  {ns / stmts / 1e6:9.3f}  {100 * ns / idle:5.1f} %  {label}")
    launches = sorted(n[1] for n in program if n[0].endswith("/dispatch/launch"))
    leads = []
    for dev, mods in modules_by_device.items():
        starts = sorted(o[1] for o in by_device.get(dev, ()))
        for _n, m_start, m_end, *_rest in mods:
            if not launches or not lo <= m_start < hi:
                continue
            i = bisect.bisect_left(starts, m_start)
            first = starts[i] if i < len(starts) and starts[i] < m_end else m_start
            k = bisect.bisect_left(launches, first)
            near = min(launches[max(k - 1, 0):k + 1], key=lambda t: abs(first - t))
            leads.append(first - near)
    if leads:
        leads.sort()
        print(f"clock bound: (first device op of a module) less (its launch span's start) over "
              f"{len(leads)} module(s): least {leads[0] / 1e6:.3f} ms, median "
              f"{leads[len(leads) // 2] / 1e6:.3f} ms (a negative least bounds the clocks' "
              f"disagreement from below)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=4, help="ops shown per scope")
    ap.add_argument("--tolerance-ms", type=float, default=0.6)
    ap.add_argument("--idle", action="store_true",
                    help="apportion the device's idle over the program's spans")
    args = ap.parse_args(argv)
    space = load(args.trace)

    by_device, modules_by_device = collections.defaultdict(list), collections.defaultdict(list)
    notes, bench = [], []
    for plane in space.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:TPU") and line.name == "XLA Ops":
                by_device[plane.name] += list(events(plane, line))
            elif plane.name.startswith("/device:TPU") and line.name == "XLA Modules":
                modules_by_device[plane.name] += list(events(plane, line))
            elif plane.name.startswith("/host:"):
                for e in events(plane, line):
                    if e[0].startswith("tidbtpu/"):
                        notes.append(e)
                    elif e[0].startswith("bench/"):
                        bench.append(e)
    modules = [m for mods in modules_by_device.values() for m in mods]
    ops = [op for dev_ops in by_device.values() for op in dev_ops]
    if not ops:
        print("no device op in the trace")
        return 1
    if args.idle:
        idle_report(by_device, modules_by_device, notes, bench)
        return 0
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
