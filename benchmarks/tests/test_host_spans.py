"""readers/host_spans.py on plain lists (no trace file), the metric
files over the program's spans against what the server's row of
``GET /v1/query`` really has, and that a row list without a metric's
field, or a marked trace without a span of the program, fails the run
instead of leaving the metric out."""

import importlib.util
import json
import os
import sys
import types

import pytest
from conftest import BENCH, ROOT

import run as bench_run

NEW = (
    "dispatch.runner_wait_ms", "frontend.plan_ms",
    "executor.host_sync_ms_per_stmt", "executor.host_syncs_per_stmt",
    "executor.retrace_ms_per_stmt", "protocol.rows_out_ms",
    "device.idle_in_host_sync_share", "device.idle_unattributed_share",
)
#: PR 26's, a count on the same rows
ROW_FIELDS = NEW + ("executor.direct_groupbys_per_stmt",)


def reader(name):
    path = os.path.join(BENCH, "readers", name + ".py")
    spec = importlib.util.spec_from_file_location("_t_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


hs = reader("host_spans")

#                name          start  end  depth query id
STATEMENT = ("statement", 0.0, 1000.0, 0, "q1")
EXECUTE = ("execute", 100.0, 800.0, 1, "q1")
SYNC_A = ("host_sync", 200.0, 300.0, 2, "q1")
SYNC_B = ("host_sync", 500.0, 600.0, 2, "q1")
DISPATCH = ("dispatch", 300.0, 400.0, 2, "q1")
SPANS = [STATEMENT, EXECUTE, SYNC_A, SYNC_B, DISPATCH]


def test_a_gap_wholly_inside_a_host_sync_is_charged_to_it():
    assert hs.charge([(210.0, 290.0)], SPANS) == {"host_sync": 80.0}


def test_a_gap_straddling_two_spans_is_cut_where_one_ends():
    # 30 ns of the sync, 70 ns of the dispatch: each gets its own
    assert hs.charge([(270.0, 370.0)], SPANS) == {
        "host_sync": 30.0, "dispatch": 70.0}
    # between two spans of one parent, the parent's
    assert hs.charge([(280.0, 420.0)], [
        STATEMENT, EXECUTE, SYNC_A,
        ("dispatch", 400.0, 440.0, 2, "q1")]) == {
        "host_sync": 20.0, "execute": 100.0, "dispatch": 20.0}


def test_a_gap_outside_every_span_is_unattributed():
    got = hs.charge([(1200.0, 1300.0), (900.0, 950.0)], SPANS)
    # under ``statement`` alone counts as no span: the check is that
    # the spans below it are complete
    assert got == {hs.UNATTRIBUTED: 150.0}
    # and the part of a gap that sticks out of a span
    assert hs.charge([(780.0, 820.0)], SPANS) == {
        "execute": 20.0, hs.UNATTRIBUTED: 20.0}


def test_work_is_charged_before_a_wait_and_the_latest_started_wins():
    waiting = ("runner_wait", 250.0, 900.0, 1, "q2")
    other = ("statement", 240.0, 2000.0, 0, "q2")
    # another statement waits for the runner while this one syncs: the
    # wait holds no device, however late it started
    assert hs.charge([(510.0, 590.0)], SPANS + [waiting, other]) == {
        "host_sync": 80.0}
    # only the wait is open: charged to the wait, not lost
    assert hs.charge([(820.0, 880.0)], SPANS + [waiting, other]) == {
        "runner_wait": 60.0}
    # two spans of one depth: the latest started
    later = ("to_rows", 150.0, 790.0, 1, "q3")
    assert hs.charge([(650.0, 700.0)], SPANS + [later]) == {"to_rows": 50.0}


def test_depth_is_the_nesting_on_one_thread():
    line = [("statement", 0.0, 100.0, "q"), ("execute", 10.0, 90.0, "q"),
            ("host_sync", 20.0, 30.0, "q"), ("dispatch", 30.0, 40.0, "q"),
            ("respond", 100.0, 120.0, "q")]
    assert [(n, d) for n, _, _, d, _ in hs.with_depth(line)] == [
        ("statement", 0), ("execute", 1), ("host_sync", 2),
        ("dispatch", 2), ("respond", 0)]


def test_the_two_shares_never_pass_100():
    idle = [(210.0, 290.0), (510.0, 590.0), (310.0, 390.0),
            (900.0, 950.0), (1200.0, 1300.0), (650.0, 700.0)]
    by_span = hs.charge(idle, SPANS)
    assert sum(by_span.values()) == sum(e - s for s, e in idle)
    a = hs.share(by_span, "idle_share_in", "host_sync")
    b = hs.share(by_span, "idle_share_unattributed")
    assert a == pytest.approx(100 * 160 / 440)
    assert b == pytest.approx(100 * 150 / 440)
    assert 0 <= a <= 100 and 0 <= b <= 100 and a + b <= 100
    assert hs.share(by_span, "idle_share_in", "upload") == 0.0
    assert hs.share({}, "idle_share_in", "host_sync") is None
    with pytest.raises(ValueError):
        hs.share(by_span, "busy_share")


def test_nothing_without_a_device_plane():
    ctx = types.SimpleNamespace(trace=None)
    assert hs.read(ctx, "idle_share_in", "host_sync") is None
    ctx.trace = {"devices": 0, "xplane": "/nowhere/x.xplane.pb"}
    assert hs.read(ctx, "idle_share_unattributed") is None


def test_idle_intervals_are_trace_reduces():
    trace = {"devices": {"/device:TPU:0": {
        "modules": [("jit_x", 0.0, 50.0)],
        "ops": [("a", 10.0, 20.0), ("b", 20.0, 30.0), ("c", 40.0, 45.0)]}}}
    assert hs.idle_intervals(trace, 0.0, 60.0) == [
        (0.0, 10.0), (30.0, 40.0), (45.0, 60.0)]


def placed_trace(tmp_path):
    """An (empty) raw trace where a run keeps it, with the window placed
    beside it as ``trace_reduce.for_window`` does once it has found the
    clock mark."""
    run_dir = tmp_path / "trace" / "plugins" / "profile" / "r1"
    run_dir.mkdir(parents=True)
    xplane = run_dir / "h.xplane.pb"
    xplane.write_bytes(b"")
    (tmp_path / "timeline.json").write_text(
        json.dumps({"lo_ns": 0.0, "hi_ns": 1000.0}))
    return xplane


def test_reads_a_run_once_and_finds_the_timeline(tmp_path, monkeypatch):
    xplane = placed_trace(tmp_path)
    assert hs.find_timeline(str(xplane)) == str(tmp_path / "timeline.json")
    calls = []
    monkeypatch.setattr(hs.trace_reduce, "load", lambda p: calls.append(p) or {
        "devices": {"d": {"modules": [], "ops": [("x", 0.0, 200.0),
                                                  ("y", 300.0, 1000.0)]}}})
    monkeypatch.setattr(hs, "host_spans", lambda p: SPANS)
    ctx = types.SimpleNamespace(
        trace={"devices": 1, "xplane": str(xplane)})
    assert hs.read(ctx, "idle_share_in", "host_sync") == 100.0
    assert hs.read(ctx, "idle_share_unattributed") == 0.0
    assert len(calls) == 1


def test_a_marked_trace_without_a_span_of_the_program_fails_the_run(
        tmp_path, monkeypatch):
    """The window is placed and the device worked, but no host event
    carries a query id: the program dropped or renamed its spans."""
    xplane = placed_trace(tmp_path)
    monkeypatch.setattr(hs.trace_reduce, "load", lambda p: {
        "devices": {"d": {"modules": [], "ops": [("x", 0.0, 200.0)]}}})
    monkeypatch.setattr(hs, "host_spans", lambda p: [])
    for quantity, span in (("idle_share_in", "host_sync"),
                           ("idle_share_unattributed", None)):
        ctx = types.SimpleNamespace(
            trace={"devices": 1, "xplane": str(xplane)})
        with pytest.raises(RuntimeError, match="no span of the program"):
            hs.read(ctx, quantity, span)


def stmt(qid, cls="long"):
    return types.SimpleNamespace(query_id=qid, cls=cls)


def test_a_row_list_without_a_metrics_field_fails_the_run():
    assert not os.path.exists(os.path.join(BENCH, "readers", "span_field.py"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    ctx = bench_run.Context()
    ctx.statements = [stmt("a"), stmt("b")]
    ctx.query_list = [{"query_id": "a", "plan_ms": 2.0},
                      {"query_id": "b", "plan_ms": 4.0}]
    only = lambda name: dict(bench, per_layer=[
        m for m in bench["per_layer"] if m["name"] == name])
    assert bench_run.per_layer(only("frontend.plan_ms"), "sf1_power", ctx) == {
        "frontend.plan_ms": {"value": 3.0, "unit": "ms"}}
    # no row carries the field (the program dropped or renamed the
    # span): the run fails, the metric does not vanish from the line
    for name in ROW_FIELDS:
        if not name.startswith("device.") and name != "frontend.plan_ms":
            with pytest.raises(RuntimeError, match="0 of the window's 2"):
                bench_run.per_layer(only(name), "sf1_power", ctx)
    # some rows carry it, too few
    ctx.query_list[1].pop("plan_ms")
    with pytest.raises(RuntimeError, match="1 of the window's 2"):
        bench_run.per_layer(only("frontend.plan_ms"), "sf1_power", ctx)


def test_new_metric_files_name_a_reader_and_a_quantity_it_knows():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    # looked up by name: a later PR appends its metrics after these
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(ROW_FIELDS) <= set(entries)
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == ["sf1_power", "sf1_throughput"]
        assert m["moves"] == "queries_per_s"
        assert m["source"] == "program_span" and m["better"] == "lower"
        with open(os.path.join(BENCH, "metrics", name + ".json")) as fh:
            spec = json.load(fh)
        assert spec["reader"] in ("query_list", "host_spans")
        assert os.path.exists(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        if spec["reader"] == "host_spans":
            assert hs.share({"host_sync": 1.0, hs.UNATTRIBUTED: 3.0},
                            **spec["args"]) in (25.0, 75.0)


def test_new_metric_fields_are_on_the_servers_row(tmp_path):
    """Each metric over a row's field reads one that a statement's row
    of ``GET /v1/query`` really has, as a number (a Coordinator at
    ``tiny`` in a CPU-pinned child: this process imports no jax)."""
    import subprocess

    fields = []
    for name in ROW_FIELDS:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as fh:
            spec = json.load(fh)
        if spec["reader"] == "query_list":
            fields.append(spec["args"]["field"])
    assert len(fields) == 7
    code = (
        "import json, urllib.request\n"
        "from trino_tpu.engine import QueryRunner\n"
        "from trino_tpu.server.coordinator import Coordinator\n"
        "from trino_tpu.server.client import StatementClient\n"
        "c = Coordinator(runner=QueryRunner.tpch('tiny'), port=0).start()\n"
        "StatementClient(c.uri).execute('select count(*) from orders "
        "where o_orderkey < 100')\n"
        "print(json.dumps(json.load(urllib.request.urlopen(c.uri + "
        "'/v1/query'))[-1]))\n"
        "c.stop()\n"
    )
    import supervisor

    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=supervisor.child_env("cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    row = json.loads(p.stdout.strip().splitlines()[-1])
    assert row["state"] == "FINISHED"
    for f in fields:
        assert isinstance(row.get(f), (int, float)), (f, row.get(f))
    # and the benchmark's own loop reports them from such a row
    ctx = bench_run.Context()
    ctx.statements = [stmt(row["query_id"])]
    ctx.query_list = [row]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] in ROW_FIELDS]
    got = bench_run.per_layer(bench, "sf1_power", ctx)
    assert set(got) == {n for n in ROW_FIELDS if not n.startswith("device.")}
