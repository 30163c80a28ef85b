"""From a profiler trace (`.xplane.pb`) to the device numbers.

Read with `jax.profiler.ProfileData`, nothing else. A device plane is
one whose name starts with `/device:`; on it the line `XLA Ops` holds
one event per executed HLO op (ops inside a `while` are nested in it)
and the line `XLA Modules` one event per program launch. Host spans are
the events the benchmark wrote with `jax.profiler.TraceAnnotation`
under the prefix `bench/`; the caller may add spans of its own on the
trace's clock (the program's flight phases).

- busy: the union of the op intervals, per device, averaged over the
  devices that ran anything;
- window: first `bench/` span's start to the last one's end (the traced
  part of the measured loop), or the device events' extent without them;
- device_ops: self time (children taken out) summed by op, named as
  XLA printed it and cut to name, opcode, fusion kind and result shape
  (`op_label`), the ten largest;
- idle_gaps: idle time inside the window summed by the innermost host
  span that covers each gap's middle, the ten largest.

`python trace_reduce.py <file-or-dir>` prints the reduction."""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%?(?P<name>\S+) = (?P<shape>\(.*?\)|\S+) (?P<opcode>[\w\-]+)\(")
_KIND = re.compile(r"kind=(k\w+)")


def op_label(text: str) -> str:
    """`%fusion.40 = (u32[8]{0:T(1024)}, ...) fusion(...), kind=kCustom,
    calls=...` becomes `fusion.40 fusion kCustom (u32[8], ...)`: what a
    reader needs to find the op, at most 120 characters."""
    flat = _LAYOUT.sub("", _LAYOUT.sub("", text))
    m = _HLO.match(flat)
    if not m:
        return text.lstrip("%")[:120]
    kind = _KIND.search(flat)
    parts = [m["name"], m["opcode"]] + ([kind[1]] if kind else []) + [m["shape"]]
    return " ".join(parts)[:120]


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str) -> dict:
    """{"devices": {plane: {"ops": [(name, start_ns, end_ns)], "modules":
    [...]}}, "spans": [(name, start_ns, end_ns)]} from the file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for e in line.events
                    ]
            devices[plane.name] = {
                "ops": lines.get(OPS_LINE, []), "modules": lines.get(MODULES_LINE, []),
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def union(intervals) -> list:
    """Sorted, merged (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def self_times(events) -> dict:
    """Self time by op label: an op's duration less that of the ops
    nested inside it."""
    totals: dict = {}
    stack = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(own, 0.0)

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= end - start
        stack.append([op_label(name), end, end - start])
    close(float("inf"))
    return totals


def _label(spans, starts, at: float) -> str:
    """The innermost span covering `at`: the latest started of those
    that cover it. `spans` are sorted by start, `starts` are their starts."""
    i = bisect.bisect_right(starts, at) - 1
    while i >= 0:
        if spans[i][2] > at:
            return spans[i][0]
        i -= 1
    return "no statement in flight"


def reduce_trace(trace: dict, extra_spans=()) -> dict | None:
    """The numbers; None where no op ran on any device."""
    devices = {k: v for k, v in trace["devices"].items() if v["ops"] or v["modules"]}
    if not devices:
        return None
    spans = sorted(list(trace["spans"]) + list(extra_spans), key=lambda s: s[1])
    starts = [s[1] for s in spans]
    own = trace["spans"]
    if own:
        lo, hi = min(s[1] for s in own), max(s[2] for s in own)
    else:
        every = [e for d in devices.values() for e in (d["ops"] or d["modules"])]
        lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    busy_ns, launches, ops_ns, gaps_ns = 0.0, 0, {}, {}
    for dev in devices.values():
        events = dev["ops"] or dev["modules"]
        merged = clip(union((s, e) for _n, s, e in events), lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        launches += sum(1 for _n, s, _e in dev["modules"] if lo <= s < hi)
        for name, ns in self_times([e for e in events if e[2] > lo and e[1] < hi]).items():
            ops_ns[name] = ops_ns.get(name, 0.0) + ns
        edge = lo
        for start, end in merged + [(hi, hi)]:
            if start > edge:
                label = _label(spans, starts, (edge + start) / 2)
                gaps_ns[label] = gaps_ns.get(label, 0.0) + (start - edge)
            edge = max(edge, end)
    n = len(devices)

    def top(table):
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:10]
        return [[name, ns / n / 1e9] for name, ns in ranked]

    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "launches": launches / n,
        "devices": n,
        "device_ops": top(ops_ns),
        "idle_gaps": top(gaps_ns),
    }


if __name__ == "__main__":
    print(json.dumps(reduce_trace(load(sys.argv[1])), indent=1))
