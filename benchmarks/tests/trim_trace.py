"""Cut a recorded `.xplane.pb` down to a test-sized one.

    python benchmarks/tests/trim_trace.py <recorded.xplane.pb> <out.xplane.pb>

Keeps, of the first device plane: every `XLA Modules` event, the `XLA
Ops` events of at least a millisecond and the first forty of the rest
(names cut to 200 characters), and the first five `Async XLA Ops`
events (a line the reduction must ignore); of the host: the `bench/`
spans and five other events. Times are kept as recorded.
testdata/join_q18_q5.xplane.pb was cut this way from the `--trace 1`
run of tpch_sf1.join, seed 11, on a v5e (my chip run, PR 26)."""

import sys

from jax.profiler import ProfileData


def quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def plane_text(plane_id: int, name: str, lines: dict) -> str:
    names, out = {}, [f"planes {{ id: {plane_id} name: {quote(name)}"]
    for line_id, (line_name, events) in enumerate(lines.items(), 1):
        out.append(f"  lines {{ id: {line_id} name: {quote(line_name)} timestamp_ns: 0")
        for ev_name, start_ns, dur_ns in events:
            mid = names.setdefault(ev_name[:200], len(names) + 1)
            out.append(f"    events {{ metadata_id: {mid} offset_ps: {int(start_ns * 1000)} "
                       f"duration_ps: {int(dur_ns * 1000)} }}")
        out.append("  }")
    for ev_name, mid in names.items():
        out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} name: {quote(ev_name)} }} }}")
    out.append("}")
    return "\n".join(out)


def main(src: str, dst: str) -> None:
    data = ProfileData.from_file(src)
    device = next(p for p in data.planes if p.name.startswith("/device:TPU:"))
    lines = {}
    for line in device.lines:
        events = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
        if line.name == "XLA Modules":
            lines[line.name] = events
        elif line.name == "XLA Ops":
            long = [e for e in events if e[2] >= 1e6]
            short = [e for e in events if e[2] < 1e6][:40]
            lines[line.name] = sorted(long + short, key=lambda e: e[1])
        elif line.name == "Async XLA Ops":
            lines[line.name] = events[:5]
    host_events, others = [], []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    row = (e.name, e.start_ns, e.duration_ns)
                    (host_events if e.name.startswith("bench/") else others).append(row)
    text = "\n".join([
        plane_text(1, device.name, lines),
        plane_text(2, "/host:CPU", {"bench-client-0": host_events, "other": others[:5]}),
    ])
    with open(dst, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
