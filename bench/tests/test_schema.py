"""BENCHMARK.json against the benchmark's contract, and every file the
harness finds by name."""

import re

from bench import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "e2e": {"name", "unit", "better", "bound", "source"},
    "layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def spec():
    return common.benchmark_spec()


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_names_and_units():
    s = spec()
    assert set(s) == KEYS["top"]
    for c in s["configs"]:
        assert set(c) == KEYS["config"] and NAME.match(c["name"]) and _line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in s["workloads"]:
        assert set(w) == KEYS["workload"] and NAME.match(w["name"]) and _line(w["why"])
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for m in s["end_to_end"]:
        assert set(m) - {"workloads"} == KEYS["e2e"]
        assert m["source"] in ("host_clock", "device_trace")
    for m in s["per_layer"]:
        assert set(m) - {"workloads"} == KEYS["layer"] and _line(m["layer"])
        assert m["source"] in SOURCES
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in s[k]]
    assert len(names) == len(set(names))
    assert len(s["command"]) <= 32 and all(_line(w) for w in s["command"])
    assert common.ROOT.joinpath("BENCHMARK.json").stat().st_size <= 64 * 1024


def test_bounds_and_run_length_fit_the_check():
    s = spec()
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= s["run_seconds"] <= 51
    # a full check of 24 cells: 2 + 14 x 24 runs of run_seconds + 60 s, two
    # 90 s compiles per cell and 1200 s spare, inside 43200 s
    assert (2 + 14 * 24) * (s["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_metric_moves_an_end_to_end_metric_its_cells_report():
    s = spec()
    cells = {w["name"] for w in s["workloads"]}
    for w in cells:
        e2e = {m["name"] for m in common.end_to_end_for(s, w)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = common.per_layer_for(s, w)
        assert layer, w
        for m in layer:
            assert m["moves"] in e2e, (m["name"], w)
    for m in s["end_to_end"] + s["per_layer"]:
        assert set(m.get("workloads", [])) <= cells


def test_every_named_file_exists():
    s = spec()
    assert s["paths"] == ["bench"]
    for c in s["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = common.load_json(common.ROOT / c["file"])
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    used = {w["config"] for w in s["workloads"]}
    assert used == {c["name"] for c in s["configs"]}
    for w in s["workloads"]:
        mix = common.traffic_file(w["traffic"])
        assert (common.BENCH / "cells" / f"{mix['kind']}.py").is_file()
        assert common.limits_file(w["name"])
    for m in s["per_layer"]:
        assert (common.BENCH / "metrics" / f"{m['name']}.py").is_file()
