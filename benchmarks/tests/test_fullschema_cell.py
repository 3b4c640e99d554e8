"""The full-schema cell (ISSUE 48): Q9, Q17, Q13 and Q16 over seven of
TPC-H's eight tables behind the embedded coordinator. Its configuration,
cell, traffic and metric files through the harness's own loaders; the
rows it shares with ``tpch_sf1_coordinator``; each template's ``scans``
against the columns its statement names; a whole run of the cell at the
rehearsal schema on the CPU (every answer against the reference, through
``reference.py``'s comparison); the float32 control, which has to come
out not correct; each metric's reader and what it reads.

Nothing here pins the cell's entries as the last of their lists: the
next cell is appended after them."""

import json
import os
import re
import zlib
from types import SimpleNamespace

import pytest

import control
import datagen
import run as harness
import traffic
from conftest import BENCH, ROOT

CELL = "sf1_fullschema_power"
CONFIG = "tpch_sf1_fullschema"
MIX = "power_fullschema"
SIBLING = "tpch_sf1_coordinator"
TEMPLATES = ("q09", "q17", "q13", "q16")
SHARED = ("customer", "orders", "lineitem")
#: metric -> (reader, what it reads)
METRICS = {
    "kernels.busy_ms_per_stmt.fullschema": ("trace_busy", "busy_ms_per_stmt"),
    "kernels.scan_roofline.fullschema": ("trace_busy", "scan_roofline"),
    "device.idle_share.fullschema": ("trace_busy", "idle_share"),
    "device.peak_hbm_bytes.fullschema": ("device_info", "peak_bytes_in_use"),
    "device.resident_table_bytes.fullschema": (
        "prometheus_after", "trino_scan_cache_resident_bytes"),
    "executor.dispatches_per_stmt.fullschema": (
        "trace_busy", "dispatches_per_stmt"),
    "executor.host_sync_ms_per_stmt.fullschema": ("query_list", "host_sync_ms"),
    "executor.compiles_in_window.fullschema": (
        "prometheus_delta", "trino_xla_compile_total"),
    "frontend.plan_ms.fullschema": ("query_list", "plan_ms"),
    "executor.retrace_ms_per_stmt.fullschema": ("query_list", "build_trace_ms"),
    "kernels.join_ms_per_stmt.fullschema": ("trace_scopes", "operator"),
    "kernels.sort_ms_per_stmt.fullschema": ("trace_scopes", "sort"),
    "kernels.gather_ms_per_stmt.fullschema": ("trace_scopes", "gather"),
    "kernels.scatter_ms_per_stmt.fullschema": ("trace_scopes", "scatter"),
    "kernels.aggregate_ms_per_stmt.fullschema": ("trace_scopes", "operator"),
    "kernels.compact_ms_per_stmt.fullschema": ("trace_scopes", "operator"),
    "kernels.scan_ms_per_stmt.fullschema": ("trace_scopes", "scan"),
    "kernels.unscoped_share.fullschema": ("trace_scopes", "unscoped_share"),
    "kernels.packed_argsort_ms_per_stmt": ("trace_scopes", "packed_argsort"),
    "kernels.merge_rank_ms_per_stmt": ("trace_scopes", "merge_rank"),
    "executor.wide_key_joins_per_stmt": (
        "prometheus_delta", "trino_wide_key_joins_total"),
    "executor.outer_joins_per_stmt": (
        "prometheus_delta", "trino_outer_joins_total"),
    "executor.anti_joins_per_stmt": (
        "prometheus_delta", "trino_anti_joins_total"),
    "executor.distinct_aggregates_per_stmt": (
        "prometheus_delta", "trino_distinct_aggregates_total"),
    "executor.revoked_joins_per_stmt": (
        "prometheus_delta", "trino_join_revocations_total"),
}
#: a metric that reads as another cell's does is that cell's file but
#: for its name, its cell, what it moves and its words
TWINS = {
    name: name[:-len(".fullschema")]
    for name in METRICS if name.endswith(".fullschema")
}
TWIN_OF = {"device.resident_table_bytes": "device.resident_table_bytes.sf5"}


def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def config_of(name=CONFIG):
    return harness.load_json(os.path.join(BENCH, "configs", name + ".json"))


def spec_of(name):
    return harness.load_json(os.path.join(BENCH, "metrics", name + ".json"))


def program_text(*rel):
    with open(os.path.join(ROOT, "trino_tpu", *rel)) as fh:
        return fh.read()


# ---- the configuration, the cell, the mix ----------------------------------


def test_cell_config_and_mix_load_through_the_harness():
    cell, entry = harness.find_cell(bench(), CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == ["scale_factor", "query_count"]
    config = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert config["name"] == CONFIG
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert config["reduced"] == entry["reduced"]
    assert (config["chips"], config["scale_factor"], config["query_count"]) == (
        1, 1, 4)
    assert config["published"]["scale_factor"] == 12.5
    assert config["published"]["query_count"] == 22
    for key in config["reduced"]:
        assert config["published"][key + "_note"]
    mix = traffic.load_mix(cell["traffic"])
    assert (mix["loop"], mix["clients"]) == ("closed", 1)
    assert mix["streams"] == [list(TEMPLATES)]  # stream 00's order
    assert sorted(mix["templates"]) == sorted(TEMPLATES)
    ref = harness.Reference(config, config["schema"], mix)
    assert len(ref.request) == len(traffic.all_statements(mix)) == 4
    assert ref.stated == config["tables"]


def test_the_source_is_the_configurations_own():
    sources = [c["source"] for c in bench()["configs"]]
    assert sources.count(config_of()["source"]) == 1
    for clause in ("2.4.9", "2.4.17", "2.4.13", "2.4.16", "5.3.3"):
        assert clause in config_of()["source"]


def test_entries_are_in_benchmark_json_after_what_was_there():
    """Membership and relative order, not the last place: what the
    accepted benchmark had comes before this cell's entries, in the
    order it had."""
    b = bench()
    cells = [w["name"] for w in b["workloads"]]
    configs = [c["name"] for c in b["configs"]]
    metrics = [m["name"] for m in b["per_layer"]]
    assert cells.index(CELL) > cells.index("sf1_fleet_power")
    assert configs.index(CONFIG) > configs.index("tpch_sf1_fleet1")
    mine = [metrics.index(n) for n in METRICS]
    assert mine == sorted(mine), "in the order they were added"
    assert min(mine) > metrics.index("device.resident_table_bytes.fleet")
    (geo,) = [m for m in b["end_to_end"] if m["name"] == "query_geomean_ms"]
    assert geo["workloads"].index(CELL) > geo["workloads"].index(
        "sf1_fleet_power")
    (qps,) = [m for m in b["end_to_end"] if m["name"] == "queries_per_s"]
    assert CELL not in qps["workloads"]


def test_cell_is_judged_on_the_geomean_and_not_on_the_rate():
    ctx = harness.Context()
    ctx.statements = [
        SimpleNamespace(template=t, sent_s=100.0 + i, done_s=100.5 + i,
                        due_s=0.0, error=None, correct=True)
        for i, t in enumerate(TEMPLATES)]
    ctx.t0 = 100.0
    assert set(harness.end_to_end(bench(), CELL, ctx, setup_s=1.0)) == {
        "query_geomean_ms", "setup_s"}


def test_the_child_is_the_embedded_coordinators_to_the_letter():
    assert config_of()["children"] == config_of(SIBLING)["children"]
    (child,) = config_of()["children"]
    assert child["module"] == "trino_tpu.server.coordinator"
    assert child["args"] == ["--schema", "{schema}", "--port", "{port}"]
    assert child["owns_chip"] and child["entry"]


@pytest.mark.parametrize("key", [
    "guarantees", "session_properties", "shapes", "deployment",
    "stored_bytes_per_value", "scale_factor", "query_count", "chips",
    "tables_note"])
def test_the_deployment_is_the_embedded_cells_but_for_its_statements(key):
    assert config_of()[key] == config_of(SIBLING)[key]


# ---- the rows are sf1_power's, the reference's database is the cell's own ---


def test_the_schema_is_sf1_under_a_name_of_its_own():
    """``benchmarks/run.py`` keeps one reference database a schema NAME
    (``.tpch_cache/bench_ref/<schema>/ref.db``) and never adds a table
    to one that is there, and the accepted SF1 cells' holds three tables
    of other columns. So this configuration asks for the same scale
    factor under the connector's other spelling of it: the same rows,
    the same column cache, a database of its own. The rehearsal schema
    likewise, beside ``tiny``."""
    from trino_tpu.connectors.tpch.connector import TpchConnector

    config, sibling = config_of(), config_of(SIBLING)
    for mine, theirs in ((config["schema"], sibling["schema"]),
                         (config["rehearsal"]["schema"],
                          sibling["rehearsal"]["schema"])):
        assert mine != theirs
        assert TpchConnector._sf(mine) == TpchConnector._sf(theirs)
        # one column cache: the file names are the scale factor's
        a, b = (TpchConnector().data(s) for s in (mine, theirs))
        assert a.stats_path("orders") == b.stats_path("orders")
    mix = traffic.load_mix(MIX)
    ref = harness.Reference(config, config["schema"], mix)
    theirs = harness.Reference(sibling, sibling["schema"],
                               traffic.load_mix("power"))
    assert ref.dir != theirs.dir


@pytest.mark.parametrize("table", SHARED)
def test_the_shared_tables_are_the_embedded_cells_rows(table):
    """Same rows as ``tpch_sf1_coordinator`` states. A table's checksum
    is chained over the reference's columns of it (``datagen.py``), and
    this cell's reference reads other columns (``o_comment``,
    ``l_partkey``, ``l_suppkey``), so ``tables`` cannot state the
    sibling's value; ``shared_tables`` states the chain over the
    SIBLING's columns — computed from the same column cache when the
    configuration was written — and that is the sibling's, to the
    digit."""
    config, sibling = config_of(), config_of(SIBLING)
    assert config["tables"][table]["rows"] == sibling["tables"][table]["rows"]
    shared = config["shared_tables"]["tables"][table]
    assert shared == sibling["tables"][table]
    assert config["shared_tables"]["columns"][table] == (
        sibling["reference_tables"][table])


def test_the_shared_tables_chain_is_reproduced_at_the_rehearsal_schema(
        tmp_path):
    """The chain itself, where a test can afford it: at the rehearsal
    schema both spellings give the same rows and checksums over the
    sibling's columns, and this cell's own columns check against
    nothing stated (a rehearsal states none)."""
    sibling = config_of(SIBLING)
    found = [
        datagen.build_db(schema, sibling["reference_tables"],
                         str(tmp_path / f"{i}.db"), {})
        for i, schema in enumerate((config_of()["rehearsal"]["schema"],
                                    sibling["rehearsal"]["schema"]))]
    assert found[0] == found[1]


def test_tables_are_stated_for_all_seven_and_loaded_for_what_is_read():
    config = config_of()
    seven = {"customer", "orders", "lineitem", "part", "partsupp",
             "supplier", "nation"}
    assert set(config["tables"]) == set(config["reference_tables"]) == seven
    for t in config["tables"].values():
        assert t["rows"] > 0 and 0 <= t["checksum"] < 2 ** 32
    loaded = {c for cols in config["reference_tables"].values() for c in cols}
    assert {"o_comment", "p_name", "p_type", "p_brand", "p_container",
            "p_size", "s_comment", "n_name", "ps_supplycost"} <= loaded
    assert not loaded & {"l_comment", "ps_comment", "p_comment", "c_comment"}
    # every column a reference statement names is loaded, and no other
    named = set()
    for name in TEMPLATES:
        named |= columns_named(traffic.load_template(name).ref_text)
    assert named == loaded
    for table, cols in config["reference_indexes"].items():
        assert set(cols) <= set(config["reference_tables"][table])


# ---- the templates ----------------------------------------------------------


def tpch_columns() -> dict:
    from trino_tpu.connectors.tpch.generator import SCHEMAS

    return {c: t for t, schema in SCHEMAS.items() for c in schema.column_names}


def columns_named(sql: str) -> set:
    sql = "\n".join(line for line in sql.splitlines()
                    if not line.lstrip().startswith("--"))
    return set(re.findall(r"\b[a-z]{1,2}_[a-z]+\b", sql)) & set(tpch_columns())


@pytest.mark.parametrize("name", TEMPLATES)
def test_a_templates_scans_are_the_columns_its_statement_names(name):
    tpl = traffic.load_template(name)
    owner = tpch_columns()
    named = columns_named(tpl.text)
    assert named == columns_named(tpl.ref_text)
    listed = {c for cols in tpl.scans.values() for c in cols}
    assert listed == named
    for table, cols in tpl.scans.items():
        assert all(owner[c] == table for c in cols)
        # a table scanned twice is listed twice: Q17 reads lineitem in
        # the statement and again in its correlated subquery
        times = 2 if (name, table) == ("q17", "lineitem") else 1
        assert max(cols.count(c) for c in cols) == times, (table, cols)
    assert tpl.cls == "long" and len(tpl.tuples) == 1


def test_the_templates_are_the_programs_own_statements():
    """Each ``.sql.txt`` with its validation values filled in is the
    text of ``trino_tpu/connectors/tpch/queries.py``, word for word."""
    from trino_tpu.connectors.tpch.queries import QUERIES

    for st in traffic.all_statements(traffic.load_mix(MIX)):
        assert st.sql.split() == QUERIES[st.template].split(), st.template


@pytest.mark.parametrize("name,clause", [
    ("q09", "2.4.9"), ("q17", "2.4.17"), ("q13", "2.4.13"), ("q16", "2.4.16")])
def test_a_template_names_its_clause_and_says_what_its_reference_departs_in(
        name, clause):
    with open(os.path.join(BENCH, "templates", name + ".params.json")) as fh:
        params = json.load(fh)
    assert clause in params["source"] and "validation" in params["source"]
    kinds = {c["kind"] for c in params["reference"]["columns"]}
    assert kinds <= {"exact", "date", "decimal", "avg"}
    ref_text = traffic.load_template(name).ref_text
    assert ref_text.startswith("--") and clause in ref_text.split("select")[0]


# ---- a whole run at the rehearsal schema; the control ----------------------


def test_sound_run_of_the_cell_is_correct(capsys):
    """Every answer of a window of Q9 Q17 Q13 Q16 through the served
    coordinator at the rehearsal schema on the CPU, against sqlite over
    the same columns in exact integers: ``decimal_gap_ulp`` 0."""
    args = harness.parse(["--workload", CELL, "--seed", "2147483659",
                          "--seconds", "3", "--trace", "0", "--rehearse"])
    os.environ["JAX_PLATFORMS"] = "cpu"
    rc = harness.run(args, {"skip_device_check": True})
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and out
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % 4 == 0 and res["attempted"] >= 4, "whole passes"
    assert set(res["metrics"]) == {"query_geomean_ms", "setup_s"}
    cmp_ = {k: v["value"] for k, v in res["compared"].items()}
    assert cmp_["decimal_gap_ulp"] == 0.0 and cmp_["statements_wrong"] == 0
    assert cmp_["avg_gap_ulp"] <= 0.5 and cmp_["result_cache_hits"] == 0
    # non-degenerate answers: rows of every template came back
    with open(os.path.join(harness.WORK, CELL, "statements.jsonl")) as fh:
        sts = [json.loads(line) for line in fh]
    assert {s["template"] for s in sts} == set(TEMPLATES)
    assert all(s["correct"] for s in sts)


def test_lower_precision_control_is_not_correct(capsys):
    config = config_of()
    rc = control.main(["--workload", CELL, "--seeds", "3",
                       "--schema", config["rehearsal"]["schema"]])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert rc == 0 and len(lines) == 3
    for line in lines:
        assert line["correct"] is False
        assert line["statements"] == 4 * control.CLOSED_LOOP_PASSES
        assert line["compared"]["decimal_gap_ulp"]["value"] > 1000.0


def test_answers_at_the_rehearsal_schema_are_not_degenerate():
    """The generator writes what the predicates look for; the answers a
    run compares hold rows (SF1's counts are in PERF.md, section 4:
    Q9 175, Q13 42, Q16 18,204, Q17 one non-NULL sum)."""
    config = config_of()
    mix = traffic.load_mix(MIX)
    ref = harness.Reference(config, config["rehearsal"]["schema"], mix)
    assert not ref.missing(), "the sound run above wrote them"
    rows = {st.template: ref.expected(st)
            for st in traffic.all_statements(mix)}
    assert len(rows["q09"]) > 100 and all(r[2] for r in rows["q09"])
    assert len(rows["q13"]) > 1 and rows["q13"][0][0] == 0  # no order: most
    assert len(rows["q16"]) > 100
    assert rows["q17"] == [[rows["q17"][0][0], 7]] and rows["q17"][0][0] > 0


# ---- the metrics ------------------------------------------------------------


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
    args = spec["args"]
    flat = [x for v in args.values() for x in (v if isinstance(v, list) else [v])]
    assert reads in flat
    if reader == "query_list":
        fields = re.search(r"SPAN_FIELDS = \((.*?)\n    \)",
                           program_text("server", "coordinator.py"),
                           re.S).group(1)
        assert f'"{reads}"' in fields
    elif reader in ("prometheus_delta", "prometheus_after"):
        assert f'"{reads}"' in (program_text("telemetry.py")
                                + program_text("exec", "scan_cache.py"))
        if reader == "prometheus_delta":
            assert args.get("of", "chip") == "chip"
    elif reader == "trace_scopes" and args["quantity"] == "ms_per_stmt":
        import importlib

        scopes = importlib.import_module("readers.trace_scopes")
        assert args["axis"] in scopes.AXES
        if args["axis"] == "kernel":
            # a kernel scope the program opens: ``@kernel`` on a function
            # of that name (a leading ``_`` dropped)
            assert re.search(rf"@kernel\ndef _?{args['cls']}\(",
                             program_text("exec", "kernels.py"))


@pytest.mark.parametrize("name", sorted(TWINS))
def test_a_twin_reads_as_the_accepted_metric_reads(name):
    base = TWIN_OF.get(TWINS[name], TWINS[name])
    mine, theirs = spec_of(name), spec_of(base)
    for key in ("layer", "unit", "better", "source", "reader", "args"):
        assert mine[key] == theirs[key], key


def test_no_other_metric_reports_the_cell():
    b = bench()
    mine = [m["name"] for m in b["per_layer"]
            if CELL in m.get("workloads", [CELL])]
    assert sorted(mine) == sorted(METRICS)
    assert [m["name"] for m in b["end_to_end"]
            if CELL in m.get("workloads", [CELL])] == [
                "query_geomean_ms", "setup_s"]


def ctx_of(rows, before, after):
    ctx = harness.Context()
    ctx.servers = SimpleNamespace(uris={"coordinator": "http://c"},
                                  chip_uri="http://c", entry_uri="http://c")
    ctx.statements = [SimpleNamespace(query_id=r["query_id"], cls="long",
                                      template=t)
                      for r, t in zip(rows, TEMPLATES)]
    ctx.query_list = rows
    ctx.before, ctx.after = before, after
    ctx.info = {"device_kind": "TPU v5 lite", "device_memory": [
        {"bytes_in_use": 6.0e8, "peak_bytes_in_use": 1.4e9}]}
    ctx.trace = None
    return ctx


COUNTERS = {
    "executor.wide_key_joins_per_stmt": "trino_wide_key_joins_total",
    "executor.outer_joins_per_stmt": "trino_outer_joins_total",
    "executor.anti_joins_per_stmt": "trino_anti_joins_total",
    "executor.distinct_aggregates_per_stmt": "trino_distinct_aggregates_total",
    "executor.revoked_joins_per_stmt": "trino_join_revocations_total",
}


def test_the_counters_read_per_statement_and_nothing_on_the_parent():
    """One pass: Q9's wide join, Q13's and Q17's outer joins, Q16's anti
    join and distinct count, no revocation. A server that exports no
    such series (the parent's) gives nothing, and the line leaves the
    metric out; the metrics over rows and gauges that the parent has
    still read."""
    rows = [{"query_id": f"q{i}", "host_sync_ms": 10.0 * i, "plan_ms": 4.0,
             "build_trace_ms": 0.0} for i in range(4)]
    series = {"trino_wide_key_joins_total": 1.0, "trino_outer_joins_total": 2.0,
              "trino_anti_joins_total": 1.0,
              "trino_distinct_aggregates_total": 1.0,
              "trino_join_revocations_total": 0.0,
              "trino_xla_compile_total": 40.0,
              "trino_scan_cache_resident_bytes": 5.5e8}
    before = {"coordinator": dict.fromkeys(series, 0.0)}
    before["coordinator"]["trino_xla_compile_total"] = 40.0
    b = {"per_layer": [m for m in bench()["per_layer"] if m["name"] in METRICS]}
    got = {k: v["value"] for k, v in harness.per_layer(
        b, CELL, ctx_of(rows, before, {"coordinator": series})).items()}
    assert {k: got[k] for k in COUNTERS} == {
        "executor.wide_key_joins_per_stmt": 0.25,
        "executor.outer_joins_per_stmt": 0.5,
        "executor.anti_joins_per_stmt": 0.25,
        "executor.distinct_aggregates_per_stmt": 0.25,
        "executor.revoked_joins_per_stmt": 0.0}
    assert got["executor.compiles_in_window.fullschema"] == 0.0
    assert got["device.resident_table_bytes.fullschema"] == 5.5e8
    assert got["device.peak_hbm_bytes.fullschema"] == 1.4e9
    assert got["executor.host_sync_ms_per_stmt.fullschema"] == 15.0
    assert got["frontend.plan_ms.fullschema"] == 4.0
    # no trace: none of the device-trace metrics, and no error
    assert not [k for k in got if k.startswith("kernels.")]
    parent = {k: v for k, v in series.items() if k not in COUNTERS.values()}
    got = harness.per_layer(b, CELL, ctx_of(
        rows, {"coordinator": dict.fromkeys(parent, 0.0)},
        {"coordinator": parent}))
    assert not set(COUNTERS) & set(got)
    assert "device.resident_table_bytes.fullschema" in got


def test_scan_roofline_is_the_hand_computed_share():
    """One device busy 500 of 1000 ms while one pass ran. It must read
    (bytes_model.py, 8 B a value): Q9 2 x 200,000 + 2 x 10,000 +
    6 x 6,000,145 + 3 x 800,000 + 2 x 1,500,000 + 2 x 25 values, Q17
    5 x 6,000,145 + 3 x 200,000, Q13 150,000 + 3 x 1,500,000, Q16
    2 x 800,000 + 4 x 200,000 + 2 x 10,000: 79,491,645 values,
    635,933,160 B, 0.77647 ms at 819 GB/s, 0.155294 % of 500 ms."""
    import trace_reduce as tr

    ms = 1e6
    trace = {"mark_ns": 0.0, "devices": {"/device:TPU:0": {
        "modules": [("jit_join_count(1)", 0, 400 * ms),
                    ("jit_compact(2)", 600 * ms, 700 * ms)],
        "ops": [("f1", 0, 400 * ms), ("f2", 600 * ms, 700 * ms)]}}}
    r = tr.reduce(trace, 0, 1000 * ms, [])
    ctx = SimpleNamespace(
        trace=r, config=config_of(), mix=traffic.load_mix(MIX),
        statements=[SimpleNamespace(template=t) for t in TEMPLATES],
        info={"device_kind": "TPU v5 lite"},
        peaks=harness.load_json(os.path.join(BENCH, "peaks.json")))
    spec = spec_of("kernels.scan_roofline.fullschema")
    read = harness.load_reader(spec["reader"])
    values = (2 * 200_000 + 2 * 10_000 + 6 * 6_000_145 + 3 * 800_000
              + 2 * 1_500_000 + 2 * 25
              + 5 * 6_000_145 + 3 * 200_000
              + 150_000 + 3 * 1_500_000
              + 2 * 800_000 + 4 * 200_000 + 2 * 10_000)
    assert values == 79_491_645
    want = 100.0 * (values * 8 / 819e9) / 0.5
    assert read(ctx, **spec["args"]) == pytest.approx(want, rel=1e-9)
    assert want == pytest.approx(0.155294, rel=1e-5)
