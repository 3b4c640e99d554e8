"""The one-chip SF5 cell (ISSUE 41): its configuration, cell and ten
metric files through the harness's own loaders; its tables against the
four-chip twin's, value for value; ``prometheus_after`` on a made-up
context; ``kernels.scan_roofline.sf5`` on a made-up one-plane trace with
a hand-computed answer."""

import os
from types import SimpleNamespace

import pytest

import run as harness
import trace_reduce as tr
import traffic
from conftest import BENCH, ROOT

CELL = "sf5_power"
MS = 1e6
METRICS = [
    "kernels.busy_ms_per_stmt.sf5", "kernels.scan_roofline.sf5",
    "device.idle_share.sf5", "device.peak_hbm_bytes.sf5",
    "executor.dispatches_per_stmt.sf5", "executor.host_sync_ms_per_stmt.sf5",
    "executor.compiles_in_window.sf5", "protocol.rows_out_ms.sf5",
    "device.resident_table_bytes.sf5", "device.resident_bytes_in_use.sf5"]


def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def config_of(name):
    return harness.load_json(os.path.join(BENCH, "configs", name + ".json"))


def test_cell_config_and_mix_load_through_the_harness():
    cell, entry = harness.find_cell(bench(), CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_sf5_coordinator", "power", 1)
    assert entry["reduced"] == ["scale_factor", "query_count"]
    config = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert (config["schema"], config["chips"], config["scale_factor"]) == (
        "sf5", 1, 5)
    assert config["published"]["scale_factor"] == 12.5
    assert (config["query_count"], config["published"]["query_count"]) == (
        4, 22)
    assert "hbm_budget_bytes" not in config
    mix = traffic.load_mix(cell["traffic"])
    ref = harness.Reference(config, config["schema"], mix)
    assert len(ref.request) == len(traffic.all_statements(mix))
    assert ref.stated == config["tables"]


@pytest.mark.parametrize("key", [
    "tables", "reference_tables", "reference_indexes", "guarantees",
    "schema", "scale_factor", "stored_bytes_per_value"])
def test_the_deployment_is_the_mesh_cells_data(key):
    assert config_of("tpch_sf5_coordinator")[key] == config_of(
        "tpch_sf5_mesh4")[key]


def test_the_reference_is_the_mesh_cells_by_its_keys():
    """One database and one set of answers under bench_ref/sf5: both
    SF5 configurations ask for the same keys in the same directory."""
    mix = traffic.load_mix("power")
    one, four = (harness.Reference(config_of(n), "sf5", mix)
                 for n in ("tpch_sf5_coordinator", "tpch_sf5_mesh4"))
    assert one.dir == four.dir and one.by_key == four.by_key


def test_the_child_is_the_sf1_deployments_with_no_mesh():
    (child,) = config_of("tpch_sf5_coordinator")["children"]
    assert child == config_of("tpch_sf1_coordinator")["children"][0]
    assert child["owns_chip"] and child["entry"]
    assert "--mesh" not in child["args"]
    assert child["args"] == ["--schema", "{schema}", "--port", "{port}"]


def test_cell_is_judged_on_the_geomean_and_not_on_the_rate():
    ctx = harness.Context()
    ctx.statements = [
        SimpleNamespace(template=t, sent_s=100.0 + i, done_s=100.5 + i,
                        due_s=0.0, error=None, correct=True)
        for i, t in enumerate(("q06", "q18", "q03", "q01"))]
    ctx.t0 = 100.0
    assert set(harness.end_to_end(bench(), CELL, ctx, setup_s=1.0)) == {
        "query_geomean_ms", "setup_s"}


@pytest.mark.parametrize("name", METRICS)
def test_the_cells_metric_file_loads_and_names_its_reader(name):
    b = bench()
    (entry,) = [m for m in b["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "query_geomean_ms"
    spec = harness.load_json(os.path.join(BENCH, "metrics", name + ".json"))
    for key in ("name", "layer", "unit", "better", "source", "moves",
                "workloads"):
        assert spec[key] == entry[key]
    assert callable(harness.load_reader(spec["reader"]))


def test_no_other_metric_reports_the_cell():
    b = bench()
    mine = [m["name"] for m in b["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert sorted(mine) == sorted(METRICS)
    assert [m["name"] for m in b["end_to_end"]
            if CELL in m.get("workloads", [CELL])] == [
                "query_geomean_ms", "setup_s"]


def servers():
    return SimpleNamespace(uris={"coordinator": "http://c"},
                           chip_uri="http://c", entry_uri="http://c")


def test_prometheus_after_reads_the_level_after_the_window():
    read = harness.load_reader("prometheus_after")
    ctx = SimpleNamespace(
        servers=servers(),
        before={"coordinator": {"trino_scan_cache_resident_bytes": 7.0}},
        after={"coordinator": {"trino_scan_cache_resident_bytes": 2.5e9,
                               "trino_scan_cache_resident_tables": 3.0}})
    assert read(ctx, "trino_scan_cache_resident_bytes") == 2.5e9
    assert read(ctx, "trino_scan_cache_resident_tables", of="all") == 3.0
    assert read(ctx, ["trino_scan_cache_resident_bytes",
                      "trino_scan_cache_resident_tables"], scale=2.0) == (
        2 * (2.5e9 + 3.0))
    # a server that exports no such series (the parent's): nothing
    assert read(ctx, "trino_no_such_series") is None
    ctx.after = {"coordinator": {}}
    assert read(ctx, "trino_scan_cache_resident_bytes") is None


def test_resident_table_bytes_is_read_through_the_harness():
    b = bench()
    ctx = harness.Context()
    ctx.servers = servers()
    ctx.after = {"coordinator": {"trino_scan_cache_resident_bytes": 2.5e9}}
    ctx.info = {"device_kind": "TPU v5 lite", "device_memory": [
        {"bytes_in_use": 3.0e9, "peak_bytes_in_use": 5.0e9}]}
    ctx.statements, ctx.query_list, ctx.trace = [], [], None
    got = harness.per_layer(
        {"per_layer": [m for m in b["per_layer"] if m["name"] in (
            "device.resident_table_bytes.sf5", "device.resident_bytes_in_use.sf5",
            "device.peak_hbm_bytes.sf5", "kernels.scan_roofline.sf5")]},
        CELL, ctx)
    assert {k: v["value"] for k, v in got.items()} == {
        "device.resident_table_bytes.sf5": 2.5e9,
        "device.resident_bytes_in_use.sf5": 3.0e9,
        "device.peak_hbm_bytes.sf5": 5.0e9}   # no trace: no roofline share


def test_scan_roofline_at_sf5_is_the_hand_computed_share():
    """One device busy 500 of 1000 ms while one Q6 and one Q1 ran: they
    must read 4 + 7 lineitem columns x 30,006,807 rows x 8 B =
    2,640,599,016 B, 3.2242 ms at 819 GB/s, 0.64483 % of 500 ms."""
    config = config_of("tpch_sf5_coordinator")
    mix = traffic.load_mix("power")
    trace = {"mark_ns": 0.0, "devices": {"/device:TPU:0": {
        "modules": [("jit_chain_Aggregate_Filter_Project(1)", 0, 400 * MS),
                    ("jit_compact(2)", 600 * MS, 700 * MS)],
        "ops": [("f1", 0, 400 * MS), ("f2", 600 * MS, 700 * MS)]}}}
    r = tr.reduce(trace, 0, 1000 * MS, [])
    assert r["devices"] == 1 and r["busy_s"] == pytest.approx(0.5)
    ctx = SimpleNamespace(
        trace=r, config=config, mix=mix,
        statements=[SimpleNamespace(template="q06"),
                    SimpleNamespace(template="q01")],
        info={"device_kind": "TPU v5 lite"},
        peaks=harness.load_json(os.path.join(BENCH, "peaks.json")))
    spec = harness.load_json(
        os.path.join(BENCH, "metrics", "kernels.scan_roofline.sf5.json"))
    read = harness.load_reader(spec["reader"])
    assert read(ctx, **spec["args"]) == pytest.approx(0.6448349245, rel=1e-9)
    assert read(ctx, "busy_ms_per_stmt") == pytest.approx(250.0)
    assert read(ctx, "idle_share") == pytest.approx(50.0)
    assert read(ctx, "dispatches_per_stmt") == pytest.approx(1.0)
