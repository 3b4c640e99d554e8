"""What is resident on the device, said by the process that owns it
(ISSUE 41): the benchmark's four templates in the power order through a
served coordinator with no mesh at ``tiny`` — the one-chip twin of
``test_mesh_coordinator_rows_equal_the_reference`` — with rows equal to
the benchmark's own reference's; then ``resident_tables`` of
``GET /v1/info``, the gauges ``trino_scan_cache_resident_bytes`` /
``_tables`` (set where a page is stored or dropped), the ``upload``
span's ``bytes``, and what a ``--mesh`` coordinator's gauges read."""

from __future__ import annotations

import json
import os
import sys
import urllib.request

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

from trino_tpu import telemetry  # noqa: E402
from trino_tpu.engine import QueryRunner  # noqa: E402
from trino_tpu.exec import scan_cache  # noqa: E402
from trino_tpu.page import pad_capacity  # noqa: E402
from trino_tpu.parallel.core import make_mesh  # noqa: E402
from trino_tpu.server import client as client_mod  # noqa: E402
from trino_tpu.server.coordinator import Coordinator  # noqa: E402

MIX = traffic.load_mix("power")
STATEMENTS = traffic.all_statements(MIX)   # Q6's three years, Q18, Q3, Q1
IDS = [st.template + "-" + "_".join(st.params.values()) for st in STATEMENTS]
CONFIG = harness.load_json(
    os.path.join(BENCH, "configs", "tpch_sf5_coordinator.json"))
TABLES = ("customer", "orders", "lineitem")


def get_json(uri: str, path: str):
    with urllib.request.urlopen(uri + path, timeout=30) as r:
        return json.loads(r.read())


def gauges(uri: str) -> tuple[float, float]:
    series = supervisor.prometheus(supervisor.http_text(uri + "/v1/metrics"))
    return (series["trino_scan_cache_resident_bytes"],
            series["trino_scan_cache_resident_tables"])


@pytest.fixture(scope="module")
def ref_conn(tmp_path_factory):
    db = str(tmp_path_factory.mktemp("resident_ref") / "ref.db")
    datagen.build_db("tiny", CONFIG["reference_tables"], db, {})
    conn = reference.connect(db)
    reference.create_indexes(conn, CONFIG["reference_indexes"])
    yield conn
    conn.close()


@pytest.fixture(scope="module")
def coord():
    # the cache is the process's: start from nothing resident
    scan_cache.SHARED.clear()
    c = Coordinator(runner=QueryRunner.tpch("tiny"), port=0).start()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def served(coord):
    """Every statement text of the mix, in the power order, once:
    ``{key: (rows, query id)}``."""
    out = {}
    for st in STATEMENTS:
        client = loadgen.timed_client(client_mod, coord.uri, 600.0)
        _, rows = client.execute(st.sql)
        out[st.key] = (rows, client.last["id"])
    return out


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_one_chip_coordinator_rows_equal_the_reference(st, served, ref_conn):
    rows, _ = served[st.key]
    tpl = MIX["templates"][st.template]
    expected = reference.expected_rows(
        ref_conn, reference.render(tpl.ref_text, st.params))
    r = reference.compare_statement(
        tpl.compare["columns"], tpl.compare["ordered"], rows, expected)
    assert r["exact_mismatches"] == 0, r["detail"]
    assert r["decimal_gap_ulp"] <= harness.LIMITS["decimal_gap_ulp"], r
    assert r["avg_gap_ulp"] <= harness.LIMITS["avg_gap_ulp"], r


@pytest.mark.parametrize("table", TABLES)
def test_info_lists_the_resident_table(table, served, coord):
    listed = {t["table"]: t for t in get_json(coord.uri, "/v1/info")[
        "resident_tables"]}
    assert set(listed) == set(TABLES)
    t = listed[table]
    rows = coord.runner.metadata.connector("tpch").row_count("tiny", table)
    assert (t["schema"], t["rows"], t["capacity"]) == (
        "tiny", rows, pad_capacity(rows))
    # what the four templates scan of it, and no more
    scanned = set()
    for tpl in MIX["templates"].values():
        scanned |= set(tpl.scans.get(table, ()))
    assert t["columns"] == len(scanned)
    # at least the live mask and a byte a row a column; 64-bit lanes at most
    assert (t["capacity"] * (1 + t["columns"]) <= t["bytes"]
            <= t["capacity"] * (1 + 9 * t["columns"]))


def test_the_gauges_are_the_sum_of_what_info_lists(served, coord):
    listed = get_json(coord.uri, "/v1/info")["resident_tables"]
    total = sum(t["bytes"] for t in listed)
    assert total > 0
    assert gauges(coord.uri) == (total, len(TABLES))
    assert scan_cache.SHARED.snapshot() == {
        "entries": len(TABLES), "bytes": total}


def test_the_first_scans_upload_span_carries_its_bytes(served, coord):
    # Q6 of 1994 ran first: it put lineitem's four columns and the live
    # mask on the device, and its repeat with another year put nothing
    def uploads(key):
        tree = get_json(coord.uri, "/v1/query/" + served[key][1])["spans"]
        found, todo = [], [tree]
        while todo:
            sp = todo.pop()
            todo += sp.get("children", [])
            if sp["name"] == "upload":
                found.append(sp["attrs"])
        return found

    (first,) = uploads(STATEMENTS[0].key)
    cap = pad_capacity(
        coord.runner.metadata.connector("tpch").row_count("tiny", "lineitem"))
    assert first["table"] == "lineitem"
    assert first["bytes"] >= cap * (1 + 4)
    assert uploads(STATEMENTS[1].key) == []
    # every upload of the six statements together is what is resident
    total = sum(u["bytes"] for st in STATEMENTS for u in uploads(st.key))
    assert total == scan_cache.SHARED.snapshot()["bytes"]


def test_the_gauge_falls_when_a_table_is_dropped(served, coord):
    before = {t["table"]: t["bytes"] for t in get_json(
        coord.uri, "/v1/info")["resident_tables"]}
    coord.runner.executor.invalidate_scan("tpch", "tiny", "orders")
    assert gauges(coord.uri) == (
        sum(before.values()) - before["orders"], len(TABLES) - 1)
    assert "orders" not in {t["table"] for t in get_json(
        coord.uri, "/v1/info")["resident_tables"]}
    # the next scan of it stores its page, and the gauge, again
    loadgen.timed_client(client_mod, coord.uri, 600.0).execute(
        "select count(*), max(o_totalprice) from orders")
    nbytes, tables = gauges(coord.uri)
    assert tables == len(TABLES)
    assert sum(before.values()) - before["orders"] < nbytes


def test_a_mesh_coordinator_counts_what_the_shared_cache_holds(coord):
    """The mesh executor keeps its sharded pages in its own
    ``_dist_scan_cache``; the gauges and ``resident_tables`` are the
    shared whole-table cache's and do not count them: with every scan
    sharded they read nothing."""
    scan_cache.SHARED.clear()
    assert telemetry.SCAN_CACHE_RESIDENT_BYTES.value() == 0
    runner = QueryRunner.tpch("tiny", mesh=make_mesh(4))
    c = Coordinator(runner=runner, port=0).start()
    try:
        q3 = next(st for st in STATEMENTS if st.template == "q03")
        loadgen.timed_client(client_mod, c.uri, 600.0).execute(q3.sql)
        assert len(runner.executor._dist_scan_cache) == len(TABLES)
        assert get_json(c.uri, "/v1/info")["resident_tables"] == []
        assert gauges(c.uri) == (0, 0)
    finally:
        c.stop()


# ---- a worker's split scan is a row range of the resident table (ISSUE 45) --

from dataclasses import replace as dc_replace  # noqa: E402

import numpy as np  # noqa: E402

from trino_tpu import types as T  # noqa: E402
from trino_tpu.connectors.base import Split  # noqa: E402
from trino_tpu.exec import shapes  # noqa: E402
from trino_tpu.exec.local import LocalExecutor  # noqa: E402
from trino_tpu.plan import nodes as P  # noqa: E402

#: a varchar of each table read hash-coded as well as dictionary-coded
HASHED = {"lineitem": "l_comment", "orders": "o_clerk", "customer": "c_name"}


def scan_of(runner, table, columns="*"):
    """The TableScan the planner makes for ``select <columns> from
    <table>``."""
    todo = [runner.plan_sql(f"select {columns} from {table}")]
    while todo:
        node = todo.pop()
        if isinstance(node, P.TableScan):
            return node
        todo += node.sources
    raise AssertionError("no scan in the plan")


def uploaded_split_page(ex, node):
    """What the split scan was before: the connector's rows of the
    range, uploaded."""
    start, count = node.split
    connector = ex.metadata.connector(node.catalog)
    cols = connector.scan(
        node.schema, node.table, list(node.assignments.values()),
        split=Split(node.table, start, count))
    return ex._scanned_page(node, cols, count)


def assert_same_page(got, want):
    assert got.names == want.names
    assert got.capacity == want.capacity
    assert (got.known_rows, got.packed) == (want.known_rows, want.packed)
    assert got.ordered_on is None and want.ordered_on is None
    np.testing.assert_array_equal(np.asarray(got.mask), np.asarray(want.mask))
    n = want.known_rows
    for name, g, w in zip(got.names, got.columns, want.columns):
        assert g.type == w.type, name
        assert (g.valid is None) == (w.valid is None), name
        if w.valid is not None:
            np.testing.assert_array_equal(
                np.asarray(g.valid), np.asarray(w.valid), err_msg=name)
        gd, wd = np.asarray(g.data), np.asarray(w.data)
        assert gd.dtype == wd.dtype and gd.shape == wd.shape, name
        # behind the live rows both hold zeros
        assert not gd[n:].any() and not wd[n:].any(), name
        if w.dictionary is not None:
            # the resident page's dictionary is the whole table's: the
            # same strings, and the same codes where the split holds
            # every value of the column
            np.testing.assert_array_equal(
                g.dictionary.decode(gd[:n]), w.dictionary.decode(wd[:n]),
                err_msg=name)
            if len(g.dictionary) == len(w.dictionary):
                np.testing.assert_array_equal(gd, wd, err_msg=name)
        elif w.hash_pool is not None:
            np.testing.assert_array_equal(gd[:, 0], wd[:, 0], err_msg=name)
            np.testing.assert_array_equal(
                g.hash_pool.values[gd[:n, 1]], w.hash_pool.values[wd[:n, 1]],
                err_msg=name)
        else:
            assert g.hash_pool is None and g.dictionary is None, name
            np.testing.assert_array_equal(gd, wd, err_msg=name)


@pytest.fixture(scope="module")
def split_runner():
    scan_cache.SHARED.clear()
    return QueryRunner.tpch("tiny")


@pytest.mark.parametrize("n_splits", [2, 3])
@pytest.mark.parametrize("table", TABLES)
def test_resident_split_page_equals_the_connectors_split_scan(
        table, n_splits, split_runner):
    ex = split_runner.executor
    scan = scan_of(split_runner, table)
    hashed = [s for s, c in scan.assignments.items() if c == HASHED[table]]
    connector = ex.metadata.connector("tpch")
    splits = connector.splits("tiny", table, n_splits)
    assert len(splits) == n_splits
    for hash_varchar in (None, hashed):
        for sp in splits:
            node = dc_replace(
                scan, split=(sp.start, sp.count), hash_varchar=hash_varchar)
            got = ex._scan_split(node)
            assert got.capacity == shapes.bucket(sp.count)
            assert_same_page(got, uploaded_split_page(ex, node))
    # one resident copy of the table served every split of it
    assert ("tiny", table) in scan_cache.SHARED.resident_tables(connector)


@pytest.mark.parametrize("start,count", [
    (1100, 400),   # the slice's bucket runs past the table's capacity
    (1400, 200),   # the range runs past the table's last row
    (1500, 50),    # an empty range
    (0, 1500),     # the whole table as one split
], ids=["past-capacity", "past-last-row", "empty", "whole"])
def test_resident_split_at_the_tables_end(start, count, split_runner):
    ex = split_runner.executor
    rows = ex.metadata.connector("tpch").row_count("tiny", "customer")
    assert (rows, pad_capacity(rows)) == (1500, 1536)
    node = dc_replace(scan_of(split_runner, "customer"), split=(start, count))
    got = ex._scan_split(node)
    assert got.known_rows == max(0, min(count, rows - start))
    assert_same_page(got, uploaded_split_page(ex, node))


def test_a_split_with_no_columns_is_its_live_mask(split_runner):
    ex = split_runner.executor
    scan = dc_replace(   # the planner keeps a column; a count needs none
        scan_of(split_runner, "orders"), outputs={}, assignments={})
    got = ex._scan_split(dc_replace(scan, split=(5000, 3000)))
    assert (got.columns, got.known_rows, got.capacity) == ([], 3000, 3072)
    assert int(np.asarray(got.mask).sum()) == 3000


def test_the_whole_table_page_is_what_it_was(split_runner):
    """The embedded path (``_TableScan`` with no split): the page the
    shared residency code builds equals the connector's whole-table
    scan uploaded, column for column, carries the declared order, and
    is the cache's own arrays (no copy)."""
    ex = split_runner.executor
    scan_cache.SHARED.clear()
    scan = scan_of(split_runner, "orders")
    page = ex._TableScan(scan)
    assert page.ordered_on == next(
        s for s, c in scan.assignments.items() if c == "o_orderkey")
    connector = ex.metadata.connector("tpch")
    cols = connector.scan("tiny", "orders", list(scan.assignments.values()))
    want = ex._scanned_page(scan, cols, None)
    page.ordered_on = None
    assert_same_page(page, want)
    cache = scan_cache.SHARED.table(connector, "tiny", "orders")
    assert all(c is cache[n] for c, n in zip(
        page.columns, scan.assignments.values()))
    assert ex._TableScan(scan).columns[0] is page.columns[0]


def spy_on_resident_split(monkeypatch):
    calls = []
    real = LocalExecutor._resident_split

    def spy(self, node, *args):
        calls.append(node.table)
        return real(self, node, *args)

    monkeypatch.setattr(LocalExecutor, "_resident_split", spy)
    return calls


def test_a_live_view_keeps_the_uploading_split_scan(monkeypatch):
    from trino_tpu.connectors.system import SystemConnector

    calls = spy_on_resident_split(monkeypatch)
    r = QueryRunner.tpch("tiny")
    r.metadata.register_catalog("system", SystemConnector(runner=r))
    scan = scan_of(r, "system.runtime.caches")
    assert not r.metadata.connector("system").cacheable
    page = r.executor._scan_split(dc_replace(scan, split=(0, 2)))
    assert page.known_rows == 2 and calls == []


@pytest.fixture()
def parquet_runner(tmp_path):
    pytest.importorskip("pyarrow")
    from trino_tpu.connectors.base import TableSchema
    from trino_tpu.connectors.parquet import (
        ParquetConnector, write_parquet_table)
    from trino_tpu.metadata import Metadata, Session

    write_parquet_table(
        str(tmp_path), "default", "f",
        TableSchema("f", [("k", T.BIGINT), ("v", T.BIGINT)]),
        {"k": np.arange(1000, dtype=np.int64),
         "v": np.arange(1000, dtype=np.int64) * 3},
        row_group_size=100)
    md = Metadata()
    md.register_catalog("hive", ParquetConnector(str(tmp_path)))
    return QueryRunner(md, Session(catalog="hive", schema="default"))


def test_a_domain_pruned_parquet_split_keeps_the_uploading_scan(
        parquet_runner, monkeypatch):
    calls = spy_on_resident_split(monkeypatch)
    ex = parquet_runner.executor
    scan = scan_of(parquet_runner, "f")
    # k < 250 pushed down: of the split's five row groups, three are read
    pruned = ex._scan_split(dc_replace(
        scan, split=(0, 500), domains={"k": (None, 249, False, False)}))
    assert calls == [] and pruned.known_rows == 300
    # the same split with nothing to prune is a range of the resident table
    node = dc_replace(scan, split=(300, 500))
    got = ex._scan_split(node)
    assert calls == ["f"]
    assert_same_page(got, uploaded_split_page(ex, node))


def test_a_table_over_the_cap_keeps_task_sized_split_scans(
        parquet_runner, monkeypatch):
    """What the code can observe, not a property of its own: a table
    whose whole-table estimate is over ``query_max_memory_per_node``
    (as ``enforce_resident_fits`` judges a scan) is not made resident
    for a split of it that fits."""
    calls = spy_on_resident_split(monkeypatch)
    ex = parquet_runner.executor
    ex.session.properties["query_max_memory_per_node"] = "10kB"
    ex.session.properties["streaming_scan_enabled"] = False
    node = dc_replace(scan_of(parquet_runner, "f"), split=(0, 500))
    page = ex._scan_split(node)      # 500 rows x 16 B fit; 1000 do not
    assert calls == [] and page.known_rows == 500
    ex.session.properties["query_max_memory_per_node"] = "1MB"
    assert ex._scan_split(node).known_rows == 500 and calls == ["f"]


def test_an_insert_invalidates_what_split_reads_see():
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.metadata import Metadata, Session

    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.execute("create table t (id bigint, name varchar)")
    r.execute("insert into t values (1, 'a'), (2, 'b'), (3, 'c')")
    scan = scan_of(r, "t")
    connector = md.connector("memory")

    def rows_of(page):
        return [row for row, live in zip(
            zip(*(c.to_numpy()[0].tolist() for c in page.columns)),
            np.asarray(page.mask)) if live]

    first = r.executor._scan_split(dc_replace(scan, split=(1, 10)))
    assert rows_of(first) == [(2, "b"), (3, "c")]
    assert ("default", "t") in scan_cache.SHARED.resident_tables(connector)
    r.execute("insert into t values (4, 'd')")
    # the write dropped the resident columns (invalidate_scan)
    assert ("default", "t") not in scan_cache.SHARED.resident_tables(connector)
    second = r.executor._scan_split(dc_replace(scan, split=(1, 10)))
    assert rows_of(second) == [(2, "b"), (3, "c"), (4, "d")]
