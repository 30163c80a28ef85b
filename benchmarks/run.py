#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json: a new process, once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as `setup_s`): bring the cell's configuration up through
its loader, check the loaded row counts against the configuration's
file, start the server in a thread of this process (one process holds
the chip), connect over a real socket, ANALYZE, then every statement of
the cell's traffic twice (cold: compile or read `.jax_cache`; warm).
Window: the traffic's clients send its statements in a closed loop for
`--seconds`, to the end of a whole cycle; every answer is kept. After
the window, untimed: the peak of device memory is read, every kept
answer is judged against its plain reference (`checks.py`), and one
acknowledged write is read back. The last line of standard output is
the result; the numbers compared stand beside their limits at the end
of standard error and under `checks` in the result.

Everything that belongs to one cell, configuration, traffic mix, query
or per-layer metric is a file found by its name in BENCHMARK.json: this
file names none of them (README.md says how a later PR adds one).

`--rehearse-cpu-sf <sf>` (never passed by the driver) rehearses the
whole command on the CPU at a small scale factor: it names
`"platform": "cpu"`, checks answers, and prints no metric."""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python gives it

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class Refused(Exception):
    """The run cannot be made as the cell asks: no result line."""


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise Refused(f"no {what} named {name!r} in BENCHMARK.json")


class CompileMeter:
    """XLA compile activity from JAX's own monitoring events: seconds
    inside backend compile (reading the persistent cache included),
    compile requests, and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as M

        self.secs, self.requests, self.cache_hits = 0.0, 0, 0
        M.register_event_duration_secs_listener(self._on_duration)
        M.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.requests += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.secs, self.requests, self.cache_hits)

    def since(self, snap) -> dict:
        return {
            "compile_s": self.secs - snap[0],
            "compile_requests": self.requests - snap[1],
            "persistent_cache_hits": self.cache_hits - snap[2],
        }


class Statement:
    """A query by name: its text, the columns it reads, its reference."""

    def __init__(self, name: str):
        with open(os.path.join(HERE, "queries", name + ".sql")) as f:
            self.sql = " ".join(f.read().split())
        self.name = name
        self.reads = read_json(HERE, "queries", name + ".json")["reads"]
        self.reference = load_module(os.path.join(HERE, "reference", name + ".py"))

    def judge(self, rows, want, tally, wrong="cells_wrong") -> None:
        import checks

        checks.judge_rows(self.reference.KINDS, rows, want, tally, wrong)


def read_layer(name: str, run: dict):
    """One per-layer metric by its file: `layers/<name>.py` with a
    `read(run)` of its own, or `layers/<name>.json` naming one of
    readers.py's functions and its parameters."""
    import readers

    path = os.path.join(HERE, "layers", name)
    if os.path.exists(path + ".py"):
        return load_module(path + ".py").read(run)
    params = read_json(path + ".json")
    return getattr(readers, params.pop("reader"))(run, **params)


def find_device(chips: int, rehearsal: bool):
    """The device this run measures on, and its peaks. Anything but the
    TPUs the cell asks for, of a kind in peaks.json, refuses the run."""
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if rehearsal:
        if platform != "cpu":
            raise Refused("a rehearsal runs on the CPU only")
        return devices[0], {"platform": "cpu", "kind": kind, "count": 1}, None
    if platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    peaks = read_json(HERE, "peaks.json")
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in peaks.json")
    return devices[0], {"platform": platform, "kind": kind, "count": chips}, peaks[kind]


def percentile(values, q: float) -> float:
    """Nearest rank: the smallest value with at least q of the sample
    at or below it."""
    ordered = sorted(values)
    return ordered[max(math.ceil(q * len(ordered)) - 1, 0)]


class Tracer:
    """`--trace 1`: the profiler over a short steady part of the window.
    The client turns it on at the start of its second cycle and off at
    the end of the first cycle that makes the part `seconds` long."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.started_at = None
        self.done = False

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation("bench/" + name)

    def cycle_boundary(self, cycle_index: int) -> None:
        import jax

        if self.done:
            return
        if self.started_at is None:
            if cycle_index >= 1:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
                self.started_at = time.perf_counter()
        elif time.perf_counter() - self.started_at >= self.seconds:
            self.stop()

    def active(self) -> bool:
        return self.started_at is not None and not self.done

    def stop(self) -> None:
        import jax

        if self.active():
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t0
            self.done = True


def client_loop(port, prelude, order, statements, deadline, tracer) -> list:
    """The closed-loop client: whole cycles until the deadline has
    passed. Returns one record per statement sent."""
    from mysql_client import MysqlClient, ServerError

    records = []
    client = MysqlClient(port)
    try:
        for sql in prelude:
            client.query(sql)
        for cycle_index, cycle in enumerate(order):
            if time.perf_counter() >= deadline:
                break
            if tracer is not None:
                tracer.cycle_boundary(cycle_index)
            for name in cycle:
                traced = tracer is not None and tracer.active()
                wall, t0 = time.time(), time.perf_counter()
                rows, error = None, None
                try:
                    if traced:
                        with tracer.annotate("stmt/" + name + "/outside_flight"):
                            rows = client.query(statements[name].sql)
                    else:
                        rows = client.query(statements[name].sql)
                except ServerError as e:
                    error = str(e)
                records.append({
                    "name": name, "sent_wall": wall, "sent": t0,
                    "latency_s": time.perf_counter() - t0, "rows": rows, "error": error,
                    "traced": traced, "flight": None,
                })
    finally:
        if tracer is not None:
            tracer.stop()
        client.close()
    return records


def attach_flights(records, flights, statements) -> None:
    """Give each window record the program's flight of the same
    statement text that began while it was in the air."""
    by_sql: dict = {}
    for flight in flights:
        by_sql.setdefault(flight["sql"], []).append(flight)
    cursor = {sql: 0 for sql in by_sql}
    for rec in sorted(records, key=lambda r: r["sent_wall"]):
        sql = statements[rec["name"]].sql[:2048]
        rows = by_sql.get(sql, [])
        i = cursor.get(sql, 0)
        while i < len(rows) and rows[i]["start_ts"] < rec["sent_wall"] - 0.002:
            i += 1
        if i < len(rows) and rows[i]["start_ts"] <= rec["sent_wall"] + rec["latency_s"]:
            rec["flight"] = rows[i]
            i += 1
        cursor[sql] = i


def flight_spans(records, stmt_spans) -> list:
    """The traced statements' flight phases as spans on the trace's
    clock. The client's own `stmt/` spans tie the two clocks: the first
    traced statement was sent where its span starts. A flight gives its
    start and each phase's seconds, so the phases are laid one after
    the other from the start."""
    traced = sorted((r for r in records if r["traced"]), key=lambda r: r["sent_wall"])
    if not traced or not stmt_spans:
        return []
    offset_ns = min(s[1] for s in stmt_spans) - traced[0]["sent_wall"] * 1e9
    spans = []
    for rec in traced:
        flight = rec["flight"]
        if flight is None:
            continue
        at = flight["start_ts"] * 1e9 + offset_ns
        for phase, row in flight["phases"].items():
            ns = row["seconds"] * 1e9
            spans.append((f"stmt/{rec['name']}/flight/{phase}", at, at + ns))
            at += ns
    return spans


def run_cell(args) -> tuple:
    """One run. Returns the result line's object and, for control.py,
    what the judging used: statements, the loaded snapshot, the
    references' answers and the write that was read back."""
    rehearsal = args.rehearse_cpu_sf is not None
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    for path in (os.path.join(HERE, "reference"), HERE, ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import checks
    import roofline
    import trace_reduce
    import traffic
    from mysql_client import MysqlClient

    spec = read_json(ROOT, "BENCHMARK.json")
    cell = find(spec["workloads"], args.workload, "workload")
    config = read_json(ROOT, find(spec["configs"], cell["config"], "config")["file"])
    mix = traffic.load(cell["traffic"])
    statements = {name: Statement(name) for name in dict.fromkeys(mix["statements"])}

    loader = load_module(os.path.join(HERE, "loaders", config["loader"] + ".py"))
    device, device_info, peaks = find_device(int(cell["chips"]), rehearsal)
    import jax

    # Only a cell's first run in a checkout may compile, so nothing may be
    # evicted from the persistent cache: under a size cap (the chip tool's
    # machines set 192 MiB) one mix's programs, which do not fit it
    # together, evicted each other and every run compiled them all anew.
    jax.config.update("jax_compilation_cache_max_size", -1)
    meter = CompileMeter()

    # ---- set-up -----------------------------------------------------
    t_load = time.perf_counter()
    scale = args.rehearse_cpu_sf if rehearsal else config["scale_factor"]
    dep = loader.Deployment(config, args.seed, scale)
    counts = dep.row_counts()
    if not rehearsal and counts != config["row_counts"]:
        raise Refused(f"loaded row counts {counts} are not the configuration's")
    setup = {"load_s": time.perf_counter() - t_load}
    emit(phase="load", seconds=setup["load_s"], row_counts=counts, seed=args.seed)
    port = dep.start()
    loader.keep_flights(1 << 18)
    kept = []  # (statement name, rows, where) of every answer to judge
    client = MysqlClient(port)
    try:
        for sql in dep.prelude():
            client.query(sql)
        snap, t0 = meter.snapshot(), time.perf_counter()
        for sql in dep.analyze_statements():
            client.query(sql)
        setup["analyze_s"] = time.perf_counter() - t0
        emit(phase="analyze", seconds=setup["analyze_s"], **meter.since(snap))
        t_pass = time.perf_counter()
        for passno in ("cold", "warm"):
            for name, st in statements.items():
                snap1, t0 = meter.snapshot(), time.perf_counter()
                rows = client.query(st.sql)
                emit(phase="first_pass", statement=name, passno=passno,
                     seconds=time.perf_counter() - t0, rows=len(rows), **meter.since(snap1))
                kept.append((name, rows, passno))
        setup["first_pass_s"] = time.perf_counter() - t_pass
        setup.update(meter.since((0.0, 0, 0)))
        tracer = Tracer(float(mix.get("trace_seconds", 3))) if args.trace else None

        # ---- window -------------------------------------------------
        compiles_before, snap = loader.compilations(), meter.snapshot()
        t_window, wall_window = time.perf_counter(), time.time()
        setup["total_s"] = t_window - T0
        records = client_loop(port, dep.prelude(), traffic.cycles(mix), statements,
                              t_window + args.seconds, tracer)
        window_s = time.perf_counter() - t_window
        window = {
            "seconds": window_s,
            "compiles": loader.compilations() - compiles_before,
            **{"jax_" + k: v for k, v in meter.since(snap).items()},
        }
        # ---- after the window, untimed ------------------------------
        stats = device.memory_stats() or {}
        device_info["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
        attach_flights(
            records, [f for f in loader.flight_rows() if f["start_ts"] >= wall_window - 0.002],
            statements)
        failed = [r for r in records if r["error"] is not None]
        latencies = [window_s if r["error"] is not None else r["latency_s"] for r in records]
        slowest = sorted(records, key=lambda r: -r["latency_s"])[:5]
        emit(phase="window", statements=len(records), failed=len(failed), **window,
             latency_ms={q: 1e3 * percentile(latencies, q) for q in (0.5, 0.95, 0.99, 1.0)}
             if latencies else None,
             slowest=[[round(r["sent"] - t_window, 3), r["name"], round(1e3 * r["latency_s"], 3)]
                      for r in slowest])

        reduced = None
        if tracer is not None and tracer.done:
            t0 = time.perf_counter()
            trace = trace_reduce.load(TRACE_DIR)
            reduced = trace_reduce.reduce_trace(trace, flight_spans(records, trace["spans"]))
            emit(phase="trace", stop_seconds=tracer.stop_s,
                 reduce_seconds=time.perf_counter() - t0, traced_seconds=reduced and reduced["window_s"])
            shutil.rmtree(TRACE_DIR, ignore_errors=True)

        t_ref = time.perf_counter()
        tally = checks.Tally()
        expected = {name: st.reference.expected(dep.data) for name, st in statements.items()}
        for name, rows, _where in kept + [(r["name"], r["rows"], "window") for r in records]:
            if rows is None:
                tally.add("answers_missing", 1, (name, "failed"))
                continue
            tally.answers += 1
            statements[name].judge(rows, expected[name], tally)
        write = dep.write_for_readback()
        reader = statements.get(write["query"]) or Statement(write["query"])
        client.query(write["sql"])  # returns when the server has acknowledged it
        got = client.query(reader.sql)
        reader.judge(got, reader.reference.expected(dep.data, extra=write["extra"]),
                     tally, wrong="readback_wrong")
        emit(phase="judge", answers=tally.answers, seconds=time.perf_counter() - t_ref,
             first_wrong=repr(tally.first_wrong) if tally.first_wrong else None)
    finally:
        client.close()
        dep.shutdown()

    ok = [r for r in records if r["error"] is None]
    run = {
        "cell": cell, "config": config, "traffic": mix, "setup": setup, "window": window,
        "statements": records, "trace": reduced, "peaks": peaks,
        "bytes": {
            name: roofline.statement_bytes(st.reads, counts, dep.data.width_bytes)
            for name, st in statements.items()
        },
    }
    end_to_end = {
        "setup_s": setup["total_s"],
        "stmts_per_s": len(ok) / window_s,
        "stmt_p95_ms": 1e3 * percentile(latencies, 0.95) if latencies else None,
    }
    metrics = {}
    if args.trace:
        for entry in spec["per_layer"]:
            if "workloads" in entry and cell["name"] not in entry["workloads"]:
                continue
            value = read_layer(entry["name"], run)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        for entry in spec["end_to_end"]:
            if "workloads" in entry and cell["name"] not in entry["workloads"]:
                continue
            metrics[entry["name"]] = {"value": end_to_end[entry["name"]], "unit": entry["unit"]}

    result = {
        "correct": tally.correct() and bool(records),
        "attempted": len(records),
        "failed": len(failed),
        "metrics": metrics,
        "device": device_info,
    }
    if rehearsal:
        # a CPU rehearsal shows that the command runs and judges; its
        # timings are not the device's and are not printed
        result["rehearsed_metrics"] = sorted(metrics)
        result["metrics"] = {}
    elif reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
        }
    result["checks"] = tally.report()
    return result, {"statements": statements, "reader": reader, "data": dep.data,
                    "expected": expected, "write": write}


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu-sf", type=float, default=None,
                    help="rehearse on the CPU at this scale factor; prints no metric")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result, _judged = run_cell(args)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 2
    for name, (value, limit) in result["checks"].items():
        print(f"compared {name} = {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
