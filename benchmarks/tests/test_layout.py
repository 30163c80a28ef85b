"""BENCHMARK.json against the files it names, and the rule that the
harness is driven by data: run.py names no cell, configuration,
traffic mix, query or metric."""

import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)


def test_every_named_file_exists():
    for config in SPEC["configs"]:
        with open(os.path.join(ROOT, config["file"])) as f:
            body = json.load(f)
        assert body["source"] == config["source"] and body["reduced"] == config["reduced"]
        assert os.path.isfile(os.path.join(BENCH, "loaders", body["loader"] + ".py"))
    for cell in SPEC["workloads"]:
        with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
            for query in json.load(f)["statements"]:
                for sub, ext in (("queries", ".sql"), ("queries", ".json"), ("reference", ".py")):
                    assert os.path.isfile(os.path.join(BENCH, sub, query + ext)), (query, sub)
    import readers

    for metric in SPEC["per_layer"]:
        path = os.path.join(BENCH, "layers", metric["name"])
        if not os.path.isfile(path + ".py"):
            with open(path + ".json") as f:
                assert callable(getattr(readers, json.load(f)["reader"])), metric


def test_per_layer_metrics_name_real_cells_and_metrics():
    cells = {c["name"] for c in SPEC["workloads"]}
    end_to_end = {m["name"] for m in SPEC["end_to_end"]}
    for metric in SPEC["per_layer"]:
        assert metric["moves"] in end_to_end
        assert set(metric.get("workloads", cells)) <= cells


def test_run_py_names_nothing_of_one_cell():
    with open(os.path.join(BENCH, "run.py")) as f:
        code = f.read()
    names = {c["name"] for c in SPEC["configs"]} | {c["name"] for c in SPEC["workloads"]}
    names |= {c["traffic"] for c in SPEC["workloads"]}
    names |= {m["name"] for m in SPEC["per_layer"]}
    for cell in SPEC["workloads"]:
        with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
            names |= set(json.load(f)["statements"])
    # the three end-to-end metrics are what the harness itself measures
    for name in names:
        assert not re.search(r"(?<![\w.])" + re.escape(name) + r"(?![\w.])", code), name


def test_peaks_name_their_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        for kind, row in json.load(f).items():
            assert row["source"] and row["hbm_bytes_per_s"] > 0, kind


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_keeps_to_the_contract_s_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
    for config in SPEC["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(config["name"]) and line(config["source"]) and line(config["why"])
        assert config["file"].startswith(SPEC["paths"][0] + "/")
        assert all(NAME.match(k) for k in config["reduced"]) and len(config["reduced"]) <= 16
    seen = set()
    for cell in SPEC["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and line(cell["why"])
        assert cell["chips"] in (1, 4) and (cell["config"], cell["traffic"]) not in seen
        seen.add((cell["config"], cell["traffic"]))
    assert {c["config"] for c in SPEC["workloads"]} == {c["name"] for c in SPEC["configs"]}
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for metric in SPEC["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= metric["bound"] <= 0.25 and metric["source"] in ("host_clock", "device_trace")
    assert "setup_s" in names
    for metric in SPEC["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert metric["source"] in SOURCES and line(metric["layer"])
    for metric in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
    with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024
