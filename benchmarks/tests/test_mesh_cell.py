"""The four-chip cell (ISSUE 39): its configuration, cell and metric
files through the harness's own loaders, ``mesh.exchange_roofline`` on a
made-up context with a hand-computed answer, and the one-chip readers'
arithmetic on a four-plane trace (a mean over the devices, not a sum)."""

import json
import os
from types import SimpleNamespace

import pytest

import run as harness
import trace_reduce as tr
import traffic
from conftest import BENCH, ROOT

CELL = "sf5_mesh4_power"
MS = 1e6


def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_cell_config_and_mix_load_through_the_harness():
    b = bench()
    cell, entry = harness.find_cell(b, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_sf5_mesh4", "power", 4)
    assert entry["reduced"] == ["scale_factor", "query_count"]
    config = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert config["schema"] == "sf5" and config["chips"] == 4
    assert config["scale_factor"] == 5
    assert config["published"]["scale_factor"] == 10
    assert config["published"]["chips"] == 8
    (child,) = config["children"]
    assert child["owns_chip"] and child["entry"]
    assert child["args"][-2:] == ["--mesh", "4"]
    # the guarantees of the one-chip deployment, word for word
    sf1 = harness.load_json(
        os.path.join(BENCH, "configs", "tpch_sf1_coordinator.json"))
    assert config["guarantees"] == sf1["guarantees"]
    assert config["reference_tables"] == sf1["reference_tables"]
    mix = traffic.load_mix(cell["traffic"])
    ref = harness.Reference(config, config["schema"], mix)
    assert len(ref.request) == len(traffic.all_statements(mix))
    assert ref.stated == config["tables"]


def test_cell_is_judged_on_the_geomean_and_not_on_the_rate():
    b = bench()
    ctx = harness.Context()
    ctx.statements = [
        SimpleNamespace(template=t, sent_s=100.0 + i, done_s=100.5 + i,
                        due_s=0.0, error=None, correct=True)
        for i, t in enumerate(("q06", "q18", "q03", "q01"))]
    ctx.t0 = 100.0
    assert set(harness.end_to_end(b, CELL, ctx, setup_s=1.0)) == {
        "query_geomean_ms", "setup_s"}


def test_the_cells_metric_files_load_and_name_their_readers():
    b = bench()
    mine = [m for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == sorted([
        "mesh.exchange_ms_per_stmt", "mesh.exchanges_per_stmt",
        "mesh.exchange_bytes_per_stmt", "mesh.gather_ms_per_stmt",
        "mesh.upload_ms_per_stmt", "mesh.exchange_roofline",
        "kernels.busy_ms_per_stmt.mesh4", "device.idle_share.mesh4",
        "device.peak_hbm_bytes.mesh4", "executor.dispatches_per_stmt.mesh4",
        "executor.host_sync_ms_per_stmt.mesh4",
        "executor.compiles_in_window.mesh4"])
    for m in mine:
        assert m["moves"] == "query_geomean_ms"
        spec = harness.load_json(
            os.path.join(BENCH, "metrics", m["name"] + ".json"))
        assert callable(harness.load_reader(spec["reader"]))
    # nothing reports the cell under another cell's metric
    assert all(CELL not in m.get("workloads", [CELL])
               for m in b["per_layer"] if m not in mine)


def four_planes():
    """Four devices, each busy 60 of 100 ms in three program
    executions, 10 ms of them in the exchange's two programs."""
    return {"mark_ns": 0.0, "devices": {
        f"/device:TPU:{d}": {
            "modules": [("jit_mesh_chain_Aggregate(1)", 0, 50 * MS),
                        ("jit_mesh_exchange_dest(2)", 60 * MS, 62 * MS),
                        ("jit_mesh_exchange(3)", 62 * MS, 70 * MS)],
            "ops": [("f1", 0, 50 * MS), ("f2", 60 * MS, 70 * MS)],
        } for d in range(4)}}


def test_four_planes_give_a_mean_and_not_a_sum():
    r = tr.reduce(four_planes(), 0, 100 * MS, [])
    assert r["devices"] == 4 and r["executions"] == 12
    assert r["busy_s"] == pytest.approx(0.060)   # not 0.240
    ctx = SimpleNamespace(trace=r, statements=[object()] * 2)
    read = harness.load_reader("trace_busy")
    assert read(ctx, "busy_ms_per_stmt") == pytest.approx(30.0)
    assert read(ctx, "idle_share") == pytest.approx(40.0)
    assert read(ctx, "dispatches_per_stmt") == pytest.approx(1.5)


def roofline_ctx(tmp_path, rows):
    work = tmp_path / "cell"
    run_dir = work / "trace" / "plugins" / "profile" / "r1"
    run_dir.mkdir(parents=True)
    with open(work / "timeline.json", "w") as fh:
        json.dump({"lo_ns": 0, "hi_ns": 100 * MS}, fh)
    sts = [SimpleNamespace(query_id=f"q{i}") for i in range(len(rows))]
    return SimpleNamespace(
        trace={"devices": 4, "xplane": str(run_dir / "t.xplane.pb")},
        statements=sts,
        query_list=[dict(r, query_id=f"q{i}") for i, r in enumerate(rows)],
        cell={"chips": 4}, info={"device_kind": "TPU v5 lite"},
        peaks=harness.load_json(os.path.join(BENCH, "peaks.json")))


def test_exchange_roofline_is_the_hand_computed_share(tmp_path, monkeypatch):
    read = harness.load_reader("mesh_exchange_roofline")
    monkeypatch.setattr(tr, "load", lambda path: four_planes())
    # 80 MB live in the window: 20 MB a chip over 200 GB/s is 0.1 ms;
    # the exchange programs ran 10 ms a device: 1 %
    ctx = roofline_ctx(tmp_path, [{"mesh_exchange_live_bytes": 50e6},
                                  {"mesh_exchange_live_bytes": 30e6}])
    assert read(ctx) == pytest.approx(1.0)
    # a server without the mesh executor's spans: nothing, no error
    assert read(roofline_ctx(tmp_path / "p", [{"host_sync_ms": 1.0}])) is None
    # no exchange program in the trace: nothing
    bare = four_planes()
    for dev in bare["devices"].values():
        dev["modules"] = dev["modules"][:1]
    monkeypatch.setattr(tr, "load", lambda path: bare)
    assert read(roofline_ctx(tmp_path / "b", [
        {"mesh_exchange_live_bytes": 50e6}])) is None
    # an untraced run
    ctx.trace = None
    assert read(ctx) is None
