"""The coordinator-and-worker cell (ISSUE 45): its configuration, cell
and metric files through the harness's own loaders; the files it found
in the tree, byte for byte as PR 24 left them; each metric's reader and
what it reads, on made-up contexts — the fleet's two children, a
parent's rows and series that lack what PR 45 adds; and a whole run of
the cell's control flow at ``tiny`` on the CPU."""

import json
import os
import zlib
from types import SimpleNamespace

import pytest

import run as harness
import traffic
from conftest import BENCH, ROOT

CELL = "sf1_fleet_power"
CONFIG = "tpch_sf1_fleet1"
#: metric -> (reader, what it reads)
METRICS = {
    "exchange.rpc_ms_per_stmt": ("prometheus_delta", "trino_rpc_latency_seconds_sum"),
    "exchange.bytes_per_stmt": ("prometheus_delta", [
        "trino_exchange_direct_bytes_total", "trino_spool_bytes_read_total"]),
    "exchange.spool_write_ms_per_stmt": ("query_list", "spool_write_ms"),
    "exchange.spool_read_ms_per_stmt": ("query_list", "spool_read_ms"),
    "exchange.task_poll_wait_ms_per_stmt": ("query_list", "task_poll_wait_ms"),
    "executor.upload_ms_per_stmt.fleet": ("query_list", "upload_ms"),
    "executor.resident_split_scans_per_stmt": (
        "prometheus_delta", "trino_resident_split_scans_total"),
    "executor.host_sync_ms_per_stmt.fleet": ("query_list", "host_sync_ms"),
    "executor.dispatches_per_stmt.fleet": ("trace_busy", "dispatches_per_stmt"),
    "kernels.busy_ms_per_stmt.fleet": ("trace_busy", "busy_ms_per_stmt"),
    "device.idle_share.fleet": ("trace_busy", "idle_share"),
    "kernels.scan_roofline.fleet": ("trace_busy", "scan_roofline"),
    "executor.compiles_in_window.fleet": (
        "prometheus_delta", "trino_xla_compile_total"),
    "device.peak_hbm_bytes.fleet": ("device_info", "peak_bytes_in_use"),
    "device.resident_table_bytes.fleet": (
        "prometheus_after", "trino_scan_cache_resident_bytes"),
}
#: files this PR found in the tree and may not change: CRC-32 at PR 44
UNCHANGED = {
    "configs/tpch_sf1_fleet1.json": 3094292586,
    "metrics/exchange.rpc_ms_per_stmt.json": 3151308216,
    "metrics/exchange.bytes_per_stmt.json": 3782507621,
}


def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def spec_of(name):
    return harness.load_json(os.path.join(BENCH, "metrics", name + ".json"))


def test_cell_config_and_mix_load_through_the_harness():
    cell, entry = harness.find_cell(bench(), CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "power", 1)
    assert entry["reduced"] == ["scale_factor", "query_count"]
    config = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert (config["schema"], config["chips"], config["scale_factor"],
            config["workers"]) == ("sf1", 1, 1, 1)
    assert config["published"]["scale_factor"] == 12.5
    assert (config["query_count"], config["published"]["query_count"]) == (
        4, 22)
    mix = traffic.load_mix(cell["traffic"])
    assert (mix["loop"], mix["clients"]) == ("closed", 1)
    ref = harness.Reference(config, config["schema"], mix)
    assert len(ref.request) == len(traffic.all_statements(mix))
    assert ref.stated == config["tables"]


@pytest.mark.parametrize("key", [
    "tables", "reference_tables", "reference_indexes", "guarantees",
    "schema", "scale_factor", "stored_bytes_per_value"])
def test_the_deployment_is_the_embedded_cells_data(key):
    """Same rows, same reference, same guarantees as ``sf1_power``'s
    configuration: what differs is what stands between the client and
    the executor."""
    one, fleet = (harness.load_json(os.path.join(
        BENCH, "configs", n + ".json")) for n in (
            "tpch_sf1_coordinator", CONFIG))
    assert fleet[key] == one[key]


def test_the_children_are_a_chip_owning_worker_and_a_host_only_entry():
    worker, coord = harness.load_json(
        os.path.join(BENCH, "configs", CONFIG + ".json"))["children"]
    assert worker["module"] == "trino_tpu.server.worker"
    assert worker["owns_chip"] and not worker.get("entry")
    assert worker["args"] == ["--schema", "{schema}", "--port", "{port}"]
    assert coord["module"] == "trino_tpu.server.coordinator"
    assert coord["entry"] and not coord.get("owns_chip")
    assert coord["args"] == [
        "--schema", "{schema}", "--port", "{port}", "--workers",
        "{uri:worker}", "--spool", "{spool}", "--n-partitions", "1"]


@pytest.mark.parametrize("rel", sorted(UNCHANGED))
def test_the_files_that_were_there_are_byte_for_byte_the_parents(rel):
    with open(os.path.join(BENCH, rel), "rb") as fh:
        assert zlib.crc32(fh.read()) == UNCHANGED[rel]


def test_cell_is_judged_on_the_geomean_and_not_on_the_rate():
    ctx = harness.Context()
    ctx.statements = [
        SimpleNamespace(template=t, sent_s=100.0 + i, done_s=100.5 + i,
                        due_s=0.0, error=None, correct=True)
        for i, t in enumerate(("q06", "q18", "q03", "q01"))]
    ctx.t0 = 100.0
    assert set(harness.end_to_end(bench(), CELL, ctx, setup_s=1.0)) == {
        "query_geomean_ms", "setup_s"}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_cells_metric_file_names_a_reader_and_what_it_reads(name):
    (entry,) = [m for m in bench()["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "query_geomean_ms"
    spec = spec_of(name)
    for key in ("name", "layer", "unit", "better", "source", "moves",
                "workloads"):
        assert spec[key] == entry[key]
    reader, reads = METRICS[name]
    assert spec["reader"] == reader
    assert callable(harness.load_reader(reader))
    assert reads in spec["args"].values()
    if reader == "query_list":
        # a field the fleet coordinator's rows carry on every statement
        import re
        with open(os.path.join(ROOT, "trino_tpu", "server",
                               "coordinator.py")) as fh:
            fields = re.search(r"SPAN_FIELDS = \((.*?)\n    \)", fh.read(),
                               re.S).group(1)
        assert f'"{reads}"' in fields


def test_no_other_metric_reports_the_cell():
    b = bench()
    mine = [m["name"] for m in b["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    assert sorted(mine) == sorted(METRICS)
    assert [m["name"] for m in b["end_to_end"]
            if CELL in m.get("workloads", [CELL])] == [
                "query_geomean_ms", "setup_s"]
    # what was there is there, in its place: the cell's entries are last
    assert b["workloads"][-1]["name"] == CELL
    assert b["configs"][-1]["name"] == CONFIG
    assert [m["name"] for m in b["per_layer"][-len(METRICS):]] == list(METRICS)


def fleet_ctx(rows, before, after):
    """A context as a run of the cell leaves it: two children, the
    worker the chip's owner, the coordinator the entry."""
    ctx = harness.Context()
    ctx.servers = SimpleNamespace(
        uris={"worker": "http://w", "coordinator": "http://c"},
        chip_uri="http://w", entry_uri="http://c")
    ctx.statements = [SimpleNamespace(query_id=r["query_id"], cls="long")
                      for r in rows]
    ctx.query_list = rows
    ctx.before, ctx.after = before, after
    ctx.info = {"device_kind": "TPU v5 lite", "device_memory": [
        {"bytes_in_use": 6.0e8, "peak_bytes_in_use": 9.0e8}]}
    ctx.trace = None
    return ctx


def read_all(ctx):
    b = bench()
    got = harness.per_layer(
        {"per_layer": [m for m in b["per_layer"] if m["name"] in METRICS]},
        CELL, ctx)
    return {k: v["value"] for k, v in got.items()}


def test_the_readers_take_the_fleets_two_children():
    rows = [{"query_id": f"q{i}", "upload_ms": 0.0, "spool_write_ms": 30.0,
             "spool_read_ms": 20.0, "task_poll_wait_ms": 100.0 + i,
             "host_sync_ms": 40.0, "resident_split_scans": 2}
            for i in range(4)]
    before = {
        "worker": {"trino_xla_compile_total": 70.0,
                   "trino_resident_split_scans_total": 18.0,
                   "trino_exchange_direct_bytes_total": 1000.0,
                   "trino_scan_cache_resident_bytes": 5.0e8},
        "coordinator": {"trino_rpc_latency_seconds_sum": 1.0,
                        "trino_spool_bytes_read_total": 500.0}}
    after = {
        "worker": {"trino_xla_compile_total": 70.0,
                   "trino_resident_split_scans_total": 26.0,
                   "trino_exchange_direct_bytes_total": 5000.0,
                   "trino_scan_cache_resident_bytes": 5.0e8},
        "coordinator": {"trino_rpc_latency_seconds_sum": 1.2,
                        "trino_spool_bytes_read_total": 900.0,
                        # the host-only coordinator compiles; the cell's
                        # counter is the worker's
                        "trino_xla_compile_total": 3.0}}
    got = read_all(fleet_ctx(rows, before, after))
    assert got == {
        "exchange.rpc_ms_per_stmt": pytest.approx(50.0),
        "exchange.bytes_per_stmt": pytest.approx(1100.0),
        "exchange.spool_write_ms_per_stmt": 30.0,
        "exchange.spool_read_ms_per_stmt": 20.0,
        "exchange.task_poll_wait_ms_per_stmt": 101.5,
        "executor.upload_ms_per_stmt.fleet": 0.0,
        "executor.resident_split_scans_per_stmt": 2.0,
        "executor.host_sync_ms_per_stmt.fleet": 40.0,
        "executor.compiles_in_window.fleet": 0.0,
        "device.peak_hbm_bytes.fleet": 9.0e8,
        "device.resident_table_bytes.fleet": 5.0e8,
    }   # no trace: none of the four trace_busy metrics


def test_the_parents_program_gives_no_residency_metric_and_does_not_raise():
    """The parent's worker uploads every split scan: its rows carry
    ``upload_ms`` and no ``resident_split_scans``, and it exports
    neither the count's series nor (holding no table) a gauge that
    reads anything but 0."""
    rows = [{"query_id": f"q{i}", "upload_ms": 700.0, "spool_write_ms": 30.0,
             "spool_read_ms": 20.0, "task_poll_wait_ms": 900.0,
             "host_sync_ms": 40.0} for i in range(4)]
    series = {"worker": {"trino_xla_compile_total": 70.0},
              "coordinator": {"trino_rpc_latency_seconds_sum": 1.0}}
    got = read_all(fleet_ctx(rows, series, series))
    assert "executor.resident_split_scans_per_stmt" not in got
    assert "device.resident_table_bytes.fleet" not in got
    assert got["executor.upload_ms_per_stmt.fleet"] == 700.0


def test_rehearsal_runs_the_cells_control_flow_on_the_cpu(capsys):
    args = harness.parse(["--workload", CELL, "--seed", "2147483861",
                          "--seconds", "3", "--trace", "0", "--rehearse"])
    os.environ["JAX_PLATFORMS"] = "cpu"
    assert harness.run(args, {"skip_device_check": True}) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["workload"] == CELL and res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] % 4 == 0
    assert res["attempted"] >= 4, "whole passes"
    assert set(res["metrics"]) == {"query_geomean_ms", "setup_s"}
    assert res["compared"]["result_cache_hits"]["value"] == 0
    assert res["device"]["platform"] == "cpu"   # the worker's, as found
