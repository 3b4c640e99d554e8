"""BENCHMARK.json against the contract's letter, and the data files it
names against each other."""

import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_names_units():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(b["command"]) <= 32 and all(line_ok(w) for w in b["command"])
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line_ok(c["source"]) and line_ok(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        with open(os.path.join(ROOT, c["file"])) as fh:
            f = json.load(fh)
        for key in c["reduced"]:
            assert isinstance(f[key], (int, float)), "a reduced key is a number of the file"
            assert key in f["published"], "and the file states the source's value"
        assert f["source"] == c["source"]
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    names = [c["name"] for c in b["configs"]]
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4) and line_ok(w["why"])
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in b["workloads"]} == set(names)
    cells = {w["name"] for w in b["workloads"]}
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in b["end_to_end"])
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert line_ok(m["layer"])
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells


def test_every_cell_reports_enough():
    b = bench()
    for w in b["workloads"]:
        def has(m):
            return "workloads" not in m or w["name"] in m["workloads"]
        e2e = [m["name"] for m in b["end_to_end"] if has(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(has(m) for m in b["per_layer"])


def test_metric_files_name_a_reader_and_a_reported_end_to_end_metric():
    b = bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = [w["name"] for w in b["workloads"]]
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))}
    assert {m["name"] for m in b["per_layer"]} <= on_disk
    for m in b["per_layer"]:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as fh:
            spec = json.load(fh)
        for key in ("layer", "unit", "source", "moves", "better"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec.get("workloads") == m.get("workloads")
        assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (
                f"{m['name']} moves {m['moves']}, which {cell} does not report")
    layers = {}
    for m in b["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), "one spelling a layer"


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel


def test_peaks_have_a_source():
    with open(os.path.join(BENCH, "peaks.json")) as fh:
        peaks = json.load(fh)
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_gbytes_per_s"] == 819.0 and v5e["bf16_tflops"] == 197.0
    assert "Google Cloud" in v5e["source"]
