"""The coordinator-and-worker deployment (ISSUE 45): the benchmark's
four templates, every tuple of their closed sets, through a fleet
coordinator and one worker at ``tiny`` — in this process and as the two
command lines of ``benchmarks/configs/tpch_sf1_fleet1.json``'s
``children`` — with rows equal to the benchmark's own reference's; the
worker's split scans read the resident table (the second execution of a
statement uploads nothing and counts ``resident_split_scans``); the span
fields the cell's metrics read are on every row of ``GET /v1/query``;
the worker's ``/v1/metrics`` and ``/v1/info`` show what it holds."""

from __future__ import annotations

import json
import os
import sys
import urllib.request
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import datagen  # noqa: E402
import loadgen  # noqa: E402
import reference  # noqa: E402
import run as harness  # noqa: E402
import supervisor  # noqa: E402
import traffic  # noqa: E402

from trino_tpu.connectors.tpch.connector import TpchConnector  # noqa: E402
from trino_tpu.engine import QueryRunner  # noqa: E402
from trino_tpu.exec import scan_cache  # noqa: E402
from trino_tpu.metadata import Metadata, Session  # noqa: E402
from trino_tpu.server import client as client_mod  # noqa: E402
from trino_tpu.server.coordinator import Coordinator  # noqa: E402
from trino_tpu.server.fleet import FleetRunner  # noqa: E402
from trino_tpu.server.worker import WorkerServer  # noqa: E402

MIX = traffic.load_mix("power")
STATEMENTS = traffic.all_statements(MIX)   # Q6's three years, Q18, Q3, Q1
IDS = [st.template + "-" + "_".join(st.params.values()) for st in STATEMENTS]
CONFIG = harness.load_json(
    os.path.join(BENCH, "configs", "tpch_sf1_fleet1.json"))
TABLES = ("customer", "orders", "lineitem")
#: what the cell's ``query_list`` metrics read, and the fleet's other
#: spans: numbers on every row, 0 where the statement had no such span
ROW_FIELDS = (
    "upload_ms", "spool_read_ms", "spool_write_ms", "rpc_ms",
    "task_poll_wait_ms", "task_queue_wait_ms", "host_sync_ms", "stage_ms",
    "resident_split_scans", "split_scan_ms", "dispatches",
    "narrow_key_joins", "wide_key_joins", "outer_joins", "anti_joins",
    "distinct_aggregates", "revoked_joins",
)
#: the fields of ISSUE 48 a statement's tasks must count at least once
#: (a join that runs in several tasks counts in each), through the
#: fleet as through the embedded runner; the templates of the power
#: mix count none of them
KIND_FIELDS = ("wide_key_joins", "outer_joins", "anti_joins",
               "distinct_aggregates", "revoked_joins")
FULL_SCHEMA = {
    "q09": {"wide_key_joins": 1},
    "q13": {"outer_joins": 1},
    "q16": {"anti_joins": 1, "distinct_aggregates": 1},
}
#: least joins a statement's tasks rank below 64 bits (ISSUE 46: the
#: plan's joins; a join that runs in several tasks counts in each)
NARROW_JOINS = {"q06": 0, "q01": 0, "q03": 2, "q18": 3}
#: split scans a statement: two a scan of the plan (``n_live = max(2,
#: workers)`` splits, server/fleet.py); Q18 scans lineitem twice
SPLIT_SCANS = {"q06": 2, "q01": 2, "q03": 6, "q18": 8}


def get_json(uri: str, path: str):
    with urllib.request.urlopen(uri + path, timeout=30) as r:
        return json.loads(r.read())


def serve_twice(entry_uri: str) -> dict:
    """Every statement text of the mix in the power order, then all of
    them again: ``{key: [(rows, row of GET /v1/query), ...]}``."""
    out: dict = {st.key: [] for st in STATEMENTS}
    for _ in range(2):
        for st in STATEMENTS:
            client = loadgen.timed_client(client_mod, entry_uri, 600.0)
            _, rows = client.execute(st.sql)
            listed = {q["query_id"]: q
                      for q in get_json(entry_uri, "/v1/query")}
            out[st.key].append((rows, listed[client.last["id"]]))
    return out


def span_names(tree: dict) -> list:
    names, todo = [], [tree]
    while todo:
        sp = todo.pop()
        todo += sp.get("children", [])
        names.append(sp["name"].split(" ", 1)[0])
    return names


def assert_equal_to_reference(st, rows, conn):
    tpl = MIX["templates"][st.template]
    expected = reference.expected_rows(
        conn, reference.render(tpl.ref_text, st.params))
    r = reference.compare_statement(
        tpl.compare["columns"], tpl.compare["ordered"], rows, expected)
    assert r["exact_mismatches"] == 0, r["detail"]
    assert r["decimal_gap_ulp"] <= harness.LIMITS["decimal_gap_ulp"], r
    assert r["avg_gap_ulp"] <= harness.LIMITS["avg_gap_ulp"], r


def worker_gauges(uri: str) -> tuple[float, float]:
    series = supervisor.prometheus(supervisor.http_text(uri + "/v1/metrics"))
    return (series["trino_scan_cache_resident_bytes"],
            series["trino_scan_cache_resident_tables"])


@pytest.fixture(scope="module")
def ref_conn(tmp_path_factory):
    db = str(tmp_path_factory.mktemp("fleet_ref") / "ref.db")
    datagen.build_db("tiny", CONFIG["reference_tables"], db, {})
    conn = reference.connect(db)
    reference.create_indexes(conn, CONFIG["reference_indexes"])
    yield conn
    conn.close()


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """The deployment in this process: a worker over its own runner, a
    fleet coordinator with no executor of its own, one partition an
    exchange, the servers' default session properties."""
    scan_cache.SHARED.clear()
    worker = WorkerServer(QueryRunner.tpch("tiny"), port=0).start()
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    runner = FleetRunner(
        [f"http://127.0.0.1:{worker.port}"], md,
        Session(catalog="tpch", schema="tiny"),
        spool_root=str(tmp_path_factory.mktemp("fleet_spool")),
        n_partitions=1)
    coord = Coordinator(runner, port=0).start()
    yield SimpleNamespace(
        entry_uri=coord.uri, worker_uri=f"http://127.0.0.1:{worker.port}")
    coord.stop()
    worker.stop()


@pytest.fixture(scope="module")
def served(fleet):
    return serve_twice(fleet.entry_uri)


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_fleet_rows_equal_the_reference(st, served, ref_conn):
    for rows, _ in served[st.key]:
        assert_equal_to_reference(st, rows, ref_conn)


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_the_second_execution_reads_the_resident_table(st, served, fleet):
    (_, cold), (_, warm) = served[st.key]
    assert warm["upload_ms"] == 0
    assert warm["resident_split_scans"] == SPLIT_SCANS[st.template]
    assert cold["resident_split_scans"] == SPLIT_SCANS[st.template]
    names = span_names(get_json(
        fleet.entry_uri, "/v1/query/" + warm["query_id"])["spans"])
    assert "upload" not in names
    assert names.count("split-scan") == SPLIT_SCANS[st.template]
    # every exchange edge still commits to the spool and is read off it
    assert "spool-write" in names and "spool-read" in names
    assert warm["spool_write_ms"] > 0 and warm["rpc_ms"] > 0


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_the_workers_joins_rank_at_the_width_the_coordinator_planned(
        st, served, fleet):
    """The fleet coordinator plans and annotates, the worker executes
    fragments it reads off the wire: a join's exact key range
    (``Join.key_ranges``) rides ``plan/serde.py``, so the worker's join
    programs are built at the width the plan proves — cold and warm."""
    for _, row in served[st.key]:
        assert row["narrow_key_joins"] >= NARROW_JOINS[st.template], row
        # every join of the four templates is on one integer key whose
        # range both inputs prove: none is left at 64 bits
        joins = row["small_build_joins"] + row["sorted_joins"]
        assert joins == row["narrow_key_joins"], row


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_the_power_mix_counts_no_outer_anti_wide_or_distinct(st, served):
    for _, row in served[st.key]:
        assert [row[f] for f in KIND_FIELDS] == [0] * len(KIND_FIELDS), row


def test_the_first_scan_of_a_table_uploads_it_once(served, fleet):
    # Q6 of 1994 ran first: lineitem's four columns went to the device
    # under its first split scan, with their bytes; its second split
    # scan, and every later statement's, found them there
    _, first = served[STATEMENTS[0].key][0]
    assert first["upload_ms"] > 0
    tree = get_json(fleet.entry_uri, "/v1/query/" + first["query_id"])["spans"]
    uploads, todo = [], [tree]
    while todo:
        sp = todo.pop()
        todo += sp.get("children", [])
        if sp["name"] == "upload":
            uploads.append(sp["attrs"])
    assert [u["table"] for u in uploads] == ["lineitem"]
    assert uploads[0]["bytes"] > 0


@pytest.mark.parametrize("field", ROW_FIELDS)
def test_every_row_carries_the_field(field, served, fleet):
    rows = [row for pair in served.values() for _, row in pair]
    assert len(rows) == 2 * len(STATEMENTS)
    assert all(isinstance(r.get(field), (int, float)) for r in rows), field
    # a statement with no such span reads 0: one that fails analysis
    # reaches no stage, task or scan
    sql = "select no_such_column from orders"
    with pytest.raises(client_mod.QueryError):
        loadgen.timed_client(client_mod, fleet.entry_uri, 600.0).execute(sql)
    last = [q for q in get_json(fleet.entry_uri, "/v1/query")
            if q.get("query") == sql][-1]
    assert last["state"] == "FAILED" and last[field] == 0


def test_the_worker_shows_what_it_holds(served, fleet):
    nbytes, tables = worker_gauges(fleet.worker_uri)
    listed = get_json(fleet.worker_uri, "/v1/info")["resident_tables"]
    assert {t["table"] for t in listed} == set(TABLES)
    assert (nbytes, tables) == (sum(t["bytes"] for t in listed), len(TABLES))
    assert nbytes > 0


# ---- the configuration's own two command lines ------------------------------


@pytest.fixture(scope="module")
def children(tmp_path_factory):
    """``python -m trino_tpu.server.worker`` and ``python -m
    trino_tpu.server.coordinator --workers ... --spool ...
    --n-partitions 1`` as the benchmark's supervisor starts them from
    the configuration's file, both held to the CPU."""
    servers = supervisor.Servers(
        CONFIG, "tiny", str(tmp_path_factory.mktemp("fleet_children")),
        traced=False, own_platform="cpu")
    try:
        servers.start()
        yield servers
    finally:
        servers.stop()


@pytest.fixture(scope="module")
def served_by_children(children):
    return serve_twice(children.entry_uri)


def test_the_children_are_a_worker_and_a_host_only_coordinator(children):
    assert list(children.uris) == ["worker", "coordinator"]
    assert children.chip_uri == children.uris["worker"]
    assert children.entry_uri == children.uris["coordinator"]
    args = CONFIG["children"][1]["args"]
    assert args[args.index("--n-partitions") + 1] == "1"
    assert "--session" not in args and "--session" not in (
        CONFIG["children"][0]["args"])


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_children_rows_equal_the_reference(st, served_by_children, ref_conn):
    for rows, _ in served_by_children[st.key]:
        assert_equal_to_reference(st, rows, ref_conn)


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_children_warm_statement_uploads_nothing(st, served_by_children):
    _, warm = served_by_children[st.key][1]
    assert warm["upload_ms"] == 0
    assert warm["resident_split_scans"] == SPLIT_SCANS[st.template]
    for field in ROW_FIELDS:
        assert isinstance(warm.get(field), (int, float)), field


def test_the_worker_child_shows_residency_and_no_result_cache_hit(
        served_by_children, children):
    nbytes, tables = worker_gauges(children.uris["worker"])
    assert nbytes > 0 and tables == len(TABLES)
    for uri in children.uris.values():
        series = supervisor.prometheus(
            supervisor.http_text(uri + "/v1/metrics"))
        assert series.get("trino_result_cache_hits_total", 0.0) == 0
    # the host-only coordinator scans nothing and holds nothing
    coord = supervisor.prometheus(supervisor.http_text(
        children.entry_uri + "/v1/metrics"))
    assert coord.get("trino_scan_cache_resident_bytes", 0.0) == 0


# (last: these statements load four more tables into the worker's scan
# cache, which the tests above count)
@pytest.mark.parametrize("q", sorted(FULL_SCHEMA))
def test_the_workers_tasks_count_joins_by_kind_and_distinct_aggregates(
        q, fleet):
    """An outer join, an anti join, a join ranked at 64 bits and a
    DISTINCT aggregate run in a worker's task: the ``dispatch`` spans
    that carry them are stitched under the coordinator's tree, so the
    fleet coordinator's row counts them as the embedded runner's does
    (ISSUE 48), and the answer is the embedded runner's."""
    from trino_tpu.connectors.tpch.queries import QUERIES

    client = loadgen.timed_client(client_mod, fleet.entry_uri, 600.0)
    _, rows = client.execute(QUERIES[q])
    row = {r["query_id"]: r
           for r in get_json(fleet.entry_uri, "/v1/query")}[client.last["id"]]
    want = FULL_SCHEMA[q]
    for f in KIND_FIELDS:
        if f in want:
            assert row[f] >= want[f], (f, row)
        else:
            assert row[f] == 0, (f, row)
    embedded = QueryRunner.tpch("tiny").execute(QUERIES[q]).rows
    assert len(rows) == len(embedded) > 0
