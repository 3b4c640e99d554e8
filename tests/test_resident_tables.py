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
