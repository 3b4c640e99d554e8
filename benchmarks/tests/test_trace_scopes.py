"""xplane_meta.py and readers/trace_scopes.py: the decoder and the
reduction on the small recorded trace in testdata/ (a window of
sf1_fleet_power recorded on the chip by PR 24, before the program had
``k:`` and ``s:`` scopes: what the reader gives the parent's side),
the arithmetic on hand-made lines, and the two ways a run without the
scopes ends — nothing without a device plane, a failure where device
planes carry no ``tf_op``."""

import gzip
import importlib.util
import json
import os
import shutil
import types

import pytest
from conftest import BENCH, ROOT

import trace_reduce
import xplane_meta

TESTDATA = os.path.join(BENCH, "testdata")
PLANE = "/device:TPU:0"
NEW = (
    "kernels.gather_ms_per_stmt", "kernels.scatter_ms_per_stmt",
    "kernels.sort_ms_per_stmt", "kernels.scan_ms_per_stmt",
    "kernels.join_ms_per_stmt", "kernels.aggregate_ms_per_stmt",
    "kernels.compact_ms_per_stmt", "kernels.unscoped_share",
)


def reader(name):
    path = os.path.join(BENCH, "readers", name + ".py")
    spec = importlib.util.spec_from_file_location("_t_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ts = reader("trace_scopes")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(path, trace, metadata, expected) of the recorded trace."""
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(os.path.join(TESTDATA, "fleet_short.xplane.pb.gz"),
                   "rb") as fin, open(path, "wb") as fout:
        shutil.copyfileobj(fin, fout)
    with open(os.path.join(TESTDATA, "fleet_short.expected.json")) as fh:
        want = json.load(fh)
    return (str(path), trace_reduce.load(str(path)),
            xplane_meta.device_metadata(str(path)), want)


def test_the_decoder_finds_the_plane_and_every_events_metadata(recorded):
    _, trace, metadata, _ = recorded
    assert list(metadata) == [PLANE] == list(trace["devices"])
    dev, meta = trace["devices"][PLANE], metadata[PLANE]
    assert len(dev["ops"]) == 9448 and len(dev["modules"]) == 67
    modules = sorted((s, e, n) for n, s, e in dev["modules"])
    ids = {xplane_meta.program_id_of(n) for _, _, n in modules}
    assert None not in ids and len(ids) == 43
    without_tf_op = 0
    for name, s, _e in dev["ops"]:
        around = [n for ms, me, n in modules if ms <= s < me]
        assert len(around) == 1, name
        md = meta[(xplane_meta.program_id_of(around[0]), name)]
        # the metadata names the program of the module around the event
        assert md["program_id"] == xplane_meta.program_id_of(around[0])
        assert md["hlo_category"] and md["bytes_accessed"] is not None
        without_tf_op += not md["tf_op"]
    # the file leaves some without one: the halves of async copies and
    # slices, and the instructions XLA made itself (a reduce-window's
    # expansion) — many events, 55.6 ms of the window's 2,912
    assert without_tf_op == 3563
    with_source = [md for md in meta.values() if md["source"]]
    assert with_source and all(
        md["source"].rsplit(":", 1)[1].isdigit() for md in with_source)


def test_self_times_add_up_to_the_busy_union(recorded):
    _, trace, metadata, want = recorded
    out = ts.reduce(trace, metadata, want["lo_ns"], want["hi_ns"],
                    want["timeline"])
    busy_ns = want["busy_s"] * 1e9
    assert out["total_ns"] == pytest.approx(busy_ns, rel=0.01)
    for axis in ts.AXES:
        assert sum(out["axes"][axis].values()) == pytest.approx(
            out["total_ns"], rel=1e-9)
    assert sum(r["self_ms"] for r in out["rows"]) == pytest.approx(
        out["total_ns"] / 1e6, rel=1e-9)


def test_the_recorded_trace_reads_gather_first_by_far(recorded):
    """Read from the file once and pinned: the primitive axis whole,
    the operator axis with ``unscoped`` large (the programs of PR 24
    scoped their chains only), no kernel named."""
    _, trace, metadata, want = recorded
    out = ts.reduce(trace, metadata, want["lo_ns"], want["hi_ns"],
                    want["timeline"])
    ms = {a: {k: v / 1e6 for k, v in by.items()}
          for a, by in out["axes"].items()}
    assert ms["primitive"] == pytest.approx({
        "gather": 2603.920793, "scatter": 141.7497, "sort": 95.295064,
        "other": 69.751905, "scan": 1.371649}, rel=1e-6)
    assert ms["operator"] == pytest.approx({
        "Aggregate": 1784.712916, "unscoped": 1124.369896,
        "TopN": 2.771308, "Filter": 0.220191, "Sort": 0.0148}, rel=1e-6)
    assert list(ms["kernel"]) == ["none"]
    top = out["rows"][0]
    assert top["program"] == "jit_counted_9246779623455092039"
    assert (top["template"], top["operator"], top["primitive"]) == (
        "q18_300", "Aggregate", "gather")
    assert top["source"].endswith("trino_tpu/exec/kernels.py:486")
    assert top["category"] == "custom fusion"
    assert top["executions"] == 2 and top["events"] == 12
    assert top["ms_per_run"] == pytest.approx(162.918598)
    assert top["bytes_per_run"] == 150994944
    # two programs hold an instruction of the same text: joined by the
    # program around the event, their times stay apart
    by_program = {}
    for r in out["rows"]:
        by_program[r["program"]] = by_program.get(r["program"], 0) + r["self_ms"]
    busy = dict(map(tuple, want["device_ops"]))
    for program, secs in busy.items():
        assert by_program[program] <= secs * 1e3 * (1 + 1e-9)


def test_classify_reads_the_grammar():
    c = ts.classify(
        "jit(join_count)/op:Join/jit(join_ranges)/k:join_ranges/"
        "k:searchsorted/k:merge_rank/k:packed_argsort/s:gather_high/gather:")
    assert c == {"operator": "Join", "kernel": "packed_argsort",
                 "site": "gather_high", "primitive": "gather"}
    assert ts.classify("jit(counted)/op1:Aggregate/cumsum:")["primitive"] == "scan"
    assert ts.classify("jit(x)/op0:Sort/scatter-add:")["primitive"] == "scatter"
    assert ts.classify("jit(x)/op0:Sort/reduce_window_sum")["primitive"] == "scan"
    for bare in (None, "", "env['§2'][0]:", "reduce_window_sum:"):
        assert ts.classify(bare)["operator"] == ts.UNSCOPED
    # a mesh chain: the shards' entry under the first operator's scope,
    # the innermost operator wins
    assert ts.classify(
        "jit(mesh_chain_Aggregate_Project)/op0:Aggregate/shard_map/"
        "op1:Project/mul:")["operator"] == "Project"


def test_self_time_takes_the_nested_events_out():
    line = [("while", 0.0, 100.0), ("body.1", 10.0, 40.0),
            ("inner", 20.0, 30.0), ("body.2", 50.0, 90.0),
            ("after", 100.0, 120.0)]
    got = {ev[0]: ns for ns, ev in ts.self_times(line)}
    assert got == {"while": 30.0, "body.1": 20.0, "inner": 10.0,
                   "body.2": 40.0, "after": 20.0}
    assert sum(got.values()) == 120.0


def test_a_device_mean_over_planes_and_same_text_in_two_programs():
    ms = 1e6
    ops = [("%f = fusion()", 0.0, 10 * ms), ("%f = fusion()", 20 * ms, 50 * ms)]
    mods = [("jit_a(1)", 0.0, 10 * ms), ("jit_b(2)", 20 * ms, 50 * ms)]
    trace = {"devices": {"/device:TPU:0": {"modules": mods, "ops": ops},
                         "/device:TPU:1": {"modules": mods, "ops": ops}}}
    meta = {(1, "%f = fusion()"): {"tf_op": "jit(a)/op:Join/k:rows_at/gather:",
                                   "bytes_accessed": 8, "source": "x.py:1"},
            (2, "%f = fusion()"): {"tf_op": "jit(b)/op0:Aggregate/add:",
                                   "bytes_accessed": 4, "source": "x.py:2"}}
    out = ts.reduce(trace, {p: meta for p in trace["devices"]}, 0.0, 60 * ms,
                    [(0.0, 60 * ms, "q03")])
    assert out["total_ns"] == 40 * ms
    assert out["axes"]["operator"] == {"Join": 10 * ms, "Aggregate": 30 * ms}
    assert out["axes"]["kernel"] == {"rows_at": 10 * ms, "none": 30 * ms}
    rows = {r["program"]: r for r in out["rows"]}
    assert rows["jit_a_1"]["self_ms"] == 10 and rows["jit_a_1"]["executions"] == 1
    assert rows["jit_b_2"]["bytes_per_run"] == 4
    assert {r["template"] for r in out["rows"]} == {"q03"}


def placed(tmp_path, recorded):
    """The recorded trace where a run keeps it, the window beside it."""
    path, _, _, want = recorded
    run_dir = tmp_path / "trace" / "plugins" / "profile" / "r1"
    run_dir.mkdir(parents=True)
    xplane = run_dir / "h.xplane.pb"
    shutil.copyfile(path, xplane)
    (tmp_path / "timeline.json").write_text(json.dumps(
        {"lo_ns": want["lo_ns"], "hi_ns": want["hi_ns"],
         "timeline": want["timeline"]}))
    return types.SimpleNamespace(
        trace={"devices": 1, "xplane": str(xplane)},
        statements=[object()] * 4)


def test_the_metrics_read_one_reduction_and_write_scopes_json(
        tmp_path, recorded, monkeypatch):
    import run as bench_run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    ctx = placed(tmp_path, recorded)
    loads = []
    real = trace_reduce.load
    monkeypatch.setattr(ts.trace_reduce, "load",
                        lambda p: loads.append(p) or real(p))
    only = dict(bench, per_layer=[m for m in bench["per_layer"]
                                  if m["name"] in NEW])
    assert [m["name"] for m in only["per_layer"]] == list(NEW)
    assert set(bench_run.per_layer(only, "sf1_power", ctx)) == set(NEW)
    for cell in ("sf1_throughput", "sf5_power", "sf5_mesh4_power"):
        assert bench_run.per_layer(only, cell, ctx) == {}
    got = {k: v["value"] for k, v in
           bench_run.per_layer(only, "sf1_power", ctx).items()}
    assert got["kernels.gather_ms_per_stmt"] == pytest.approx(2603.920793 / 4)
    assert got["kernels.aggregate_ms_per_stmt"] == pytest.approx(1784.712916 / 4)
    assert got["kernels.join_ms_per_stmt"] == 0.0  # no such scope yet
    assert got["kernels.unscoped_share"] == pytest.approx(38.61, abs=0.01)
    # the one-chip sum rule: an axis' classes and its rest are the busy time
    sc = ts.scopes(ctx)
    assert sum(sc["axes"]["primitive"].values()) == pytest.approx(
        recorded[3]["busy_s"] * 1e9, rel=0.01)
    # the reduction ran once a run, however many metrics read it
    assert len(loads) == 1
    with open(tmp_path / "scopes.json") as fh:
        doc = json.load(fh)
    assert doc["statements"] == 4 and doc["devices"] == 1
    assert doc["rows"][0]["program"] == "jit_counted_9246779623455092039"
    assert set(doc["rows"][0]) == {
        "program", "template", "operator", "kernel", "site", "primitive",
        "source", "category", "self_ms", "executions", "ms_per_run",
        "events", "bytes_per_run"}
    assert list(doc["axes_ms"]["primitive"])[0] == "gather"
    with pytest.raises(ValueError):
        ts.read(ctx, "ms_per_stmt", axis="colour", cls="red")
    with pytest.raises(ValueError):
        ts.read(ctx, "share_of_nothing")


def test_device_planes_without_tf_op_fail_the_run(
        tmp_path, recorded, monkeypatch):
    """The device worked and no event's metadata says under which
    scopes: the metrics must not vanish from the line."""
    ctx = placed(tmp_path, recorded)
    stripped = {PLANE: {k: dict(md, tf_op=None)
                        for k, md in recorded[2][PLANE].items()}}
    monkeypatch.setattr(ts.xplane_meta, "device_metadata", lambda p: stripped)
    with pytest.raises(RuntimeError, match="no event metadata carries tf_op"):
        ts.read(ctx, "unscoped_share")


def test_nothing_without_a_device_plane(tmp_path, recorded, monkeypatch):
    ctx = types.SimpleNamespace(trace=None, statements=[])
    assert ts.read(ctx, "unscoped_share") is None
    ctx.trace = {"devices": 0, "xplane": "/nowhere/x.xplane.pb"}
    assert ts.read(ctx, "ms_per_stmt", axis="primitive", cls="gather") is None
    # a marked trace that holds host planes only (a CPU rehearsal whose
    # line was reduced all the same)
    ctx = placed(tmp_path, recorded)
    monkeypatch.setattr(ts.trace_reduce, "load",
                        lambda p: {"devices": {}, "mark_ns": 1.0})
    assert ts.read(ctx, "unscoped_share") is None
    assert not os.path.exists(tmp_path / "scopes.json")


def test_metric_files_name_their_reader_and_their_cell():
    """``sf1_power`` alone lists them. Each of the two SF5 cells has an
    accepted test that pins which metrics may name it
    (``test_sf5_cell.py::test_no_other_metric_reports_the_cell``,
    ``test_mesh_cell.py::test_the_cells_metric_files_load_and_name_
    their_readers``), and a PR that adds metrics may edit no file the
    benchmark has: there the reader is run over the kept trace by hand
    (below)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        declared = {m["name"]: m for m in json.load(fh)["per_layer"]}
    for name in NEW + ("device.idle_in_epilogue_share",):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as fh:
            spec = json.load(fh)
        for key in ("name", "unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert spec[key] == declared[name][key], (name, key)
        assert spec["workloads"] == ["sf1_power"]
        assert spec["moves"] == "query_geomean_ms"
        if name in NEW:
            assert spec["reader"] == "trace_scopes"
            assert (spec["layer"], spec["source"]) == ("kernels", "device_trace")
        else:
            assert spec["reader"] == "idle_under_span"
            assert spec["args"] == {"span": "epilogue"}


def test_a_kept_trace_is_reduced_by_hand(tmp_path, recorded, capsys,
                                         monkeypatch):
    """``python3 benchmarks/readers/trace_scopes.py .bench_work/<cell>``:
    the cells that list none of the metrics get their ``scopes.json``
    and the idle time by span from the run's kept trace."""
    placed(tmp_path, recorded)
    (tmp_path / "statements.jsonl").write_text("{}\n" * 4)
    assert ts.main(str(tmp_path)) == 0
    line = json.loads(capsys.readouterr().out)
    assert line["statements"] == 4
    assert line["busy_ms_per_stmt"] == pytest.approx(
        recorded[3]["busy_s"] * 1e3 / 4)
    assert list(line["ms_per_stmt"]["primitive"])[0] == "gather"
    assert line["unscoped_share"] == pytest.approx(38.61, abs=0.01)
    # the recorded trace is older than the program's spans (PR 25)
    assert line["idle_share_by_span"] == {}
    assert os.path.exists(tmp_path / "scopes.json")
    assert ts.main(str(tmp_path / "nowhere")) == 2


def test_idle_under_a_span_counts_what_is_nested_in_it(tmp_path, monkeypatch):
    """``epilogue`` has one child a recorder, and ``host_spans`` charges
    the innermost span: by its own name the epilogue keeps the residue.
    ``idle_under_span`` gives the whole, a statement at a time."""
    ius = reader("idle_under_span")
    #          name          start   end  depth query id
    spans = [("statement", 0.0, 1000.0, 0, "a"),
             ("execute", 100.0, 600.0, 1, "a"),
             ("epilogue", 700.0, 900.0, 1, "a"),
             ("plan_digest", 720.0, 780.0, 2, "a"),
             ("listeners", 800.0, 850.0, 2, "a"),
             # another statement's span at the same time is not folded
             ("respond", 750.0, 760.0, 0, "b")]
    folded = ius.fold(spans, "epilogue")
    assert [n for n, *_ in folded] == [
        "statement", "execute", "epilogue", "epilogue", "epilogue", "respond"]
    assert ius.fold(spans, "mesh-gather") == spans
    run_dir = tmp_path / "trace" / "plugins" / "profile" / "r1"
    run_dir.mkdir(parents=True)
    xplane = run_dir / "h.xplane.pb"
    xplane.write_bytes(b"")
    (tmp_path / "timeline.json").write_text(
        json.dumps({"lo_ns": 0.0, "hi_ns": 1000.0}))
    monkeypatch.setattr(ius.trace_reduce, "load", lambda p: {
        "devices": {"d": {"modules": [], "ops": [
            ("x", 0.0, 710.0), ("y", 790.0, 1000.0)]}}})
    hs_mod = ius._host_spans()
    monkeypatch.setattr(ius, "_host_spans", lambda: hs_mod)
    monkeypatch.setattr(hs_mod, "host_spans", lambda p: spans)
    ctx = types.SimpleNamespace(trace={"devices": 1, "xplane": str(xplane)})
    # idle 710-790: 10 ns the epilogue's own, 60 plan_digest's, 10 its own
    assert ius.read(ctx, "epilogue") == 100.0
    assert hs_mod.share(hs_mod.charge([(710.0, 790.0)], spans),
                        "idle_share_in", "epilogue") == pytest.approx(25.0)
    # a program without the span reads 0, one without any span fails
    assert ius.read(ctx, "mesh-gather") == 0.0
    monkeypatch.setattr(hs_mod, "host_spans", lambda p: [])
    with pytest.raises(RuntimeError, match="no span of the program"):
        ius.read(ctx, "epilogue")
    assert ius.read(types.SimpleNamespace(trace=None), "epilogue") is None
