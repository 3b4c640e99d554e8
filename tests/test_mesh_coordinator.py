"""The mesh executor behind the served coordinator (ISSUE 39): the
benchmark's four templates, every tuple of their closed sets, through a
coordinator over a 4-device mesh at ``tiny`` — in this process and as
``python -m trino_tpu.server.coordinator --mesh 4`` — with rows equal to
the benchmark's own reference's (sqlite3 over the same generated
columns, by the templates' comparison kinds); the six ``mesh_*`` fields
on the rows of ``GET /v1/query``; the mesh programs' names; ``--mesh``'s
start-up check; the ``sf5`` schema."""

from __future__ import annotations

import json
import os
import subprocess
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
import traffic  # noqa: E402

from trino_tpu.connectors.tpch.connector import TpchConnector  # noqa: E402
from trino_tpu.connectors.tpch.generator import SCHEMA_SF  # noqa: E402
from trino_tpu.engine import QueryRunner  # noqa: E402
from trino_tpu.parallel.core import make_mesh  # noqa: E402
from trino_tpu.server import client as client_mod  # noqa: E402
from trino_tpu.server.coordinator import Coordinator  # noqa: E402

MIX = traffic.load_mix("power")
STATEMENTS = traffic.all_statements(MIX)
IDS = [st.template + "-" + "_".join(st.params.values()) for st in STATEMENTS]
MESH_FIELDS = (
    "mesh_exchanges", "mesh_exchange_ms", "mesh_exchange_live_bytes",
    "mesh_exchange_buffer_bytes", "mesh_gather_ms", "mesh_upload_ms",
    "mesh_exchanges_in_place",
)
CONFIG = harness.load_json(
    os.path.join(BENCH, "configs", "tpch_sf5_mesh4.json"))


@pytest.fixture(scope="module")
def ref_conn(tmp_path_factory):
    """The benchmark's reference at ``tiny``: the configuration's
    columns in sqlite, as ``datagen.py`` loads them for a run."""
    db = str(tmp_path_factory.mktemp("mesh_ref") / "ref.db")
    datagen.build_db("tiny", CONFIG["reference_tables"], db, {})
    conn = reference.connect(db)
    reference.create_indexes(conn, CONFIG["reference_indexes"])
    yield conn
    conn.close()


@pytest.fixture(scope="module")
def mesh_coord():
    runner = QueryRunner.tpch("tiny", mesh=make_mesh(4))
    c = Coordinator(runner=runner, port=0).start()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def local_coord():
    c = Coordinator(runner=QueryRunner.tpch("tiny"), port=0).start()
    yield c
    c.stop()


@pytest.fixture(scope="module")
def served(mesh_coord):
    """Every statement text of the mix through the in-process mesh
    coordinator, once: ``{key: (rows, row of GET /v1/query)}``."""
    out = {}
    for st in STATEMENTS:
        out[st.key] = serve(mesh_coord.uri, st.sql)
    return out


def serve(uri: str, sql: str):
    # the benchmark's client: the real one, keeping the last response
    client = loadgen.timed_client(client_mod, uri, 600.0)
    _, rows = client.execute(sql)
    with urllib.request.urlopen(uri + "/v1/query", timeout=30) as r:
        listed = {q["query_id"]: q for q in json.loads(r.read())}
    return rows, listed[client.last["id"]]


def assert_equal_to_reference(st, rows, conn):
    tpl = MIX["templates"][st.template]
    expected = reference.expected_rows(
        conn, reference.render(tpl.ref_text, st.params))
    r = reference.compare_statement(
        tpl.compare["columns"], tpl.compare["ordered"], rows, expected)
    assert r["exact_mismatches"] == 0, r["detail"]
    assert r["decimal_gap_ulp"] <= harness.LIMITS["decimal_gap_ulp"], r
    assert r["avg_gap_ulp"] <= harness.LIMITS["avg_gap_ulp"], r


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_mesh_coordinator_rows_equal_the_reference(st, served, ref_conn):
    rows, _ = served[st.key]
    assert_equal_to_reference(st, rows, ref_conn)


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_mesh_fields_on_the_statement_row(st, served):
    _, row = served[st.key]
    for field in MESH_FIELDS:
        assert isinstance(row.get(field), (int, float)), (field, row)
    assert row["mesh_gather_ms"] > 0  # every plan ends in Exchange(single)
    if st.template in ("q03", "q18"):
        assert row["mesh_exchanges"] >= 1
        assert row["mesh_exchange_ms"] > 0
        assert (0 < row["mesh_exchange_live_bytes"]
                <= row["mesh_exchange_buffer_bytes"])
    if st.template == "q06":
        assert row["mesh_exchanges"] == 0
    # Q18's inner group-by is on the key lineitem's shards are ranged
    # and ordered on (ISSUE 40): that exchange, and no other of the mix
    assert row["mesh_exchanges_in_place"] == (st.template == "q18")
    assert row["streamed_groupbys"] == (2 if st.template == "q18" else 0)


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_mesh_joins_rank_at_the_width_the_plan_proves(st, served):
    """Every mesh program that holds a ``join_ranges`` (join count and
    expansion, semi join, dynamic filter) takes the key width from the
    plan node the local executor takes it from (ISSUE 46): each of
    Q3's and Q18's joins is on one integer key with an exact range, so
    none is left at 64 bits; Q1 and Q6 run none."""
    _, row = served[st.key]
    joins = row["small_build_joins"] + row["sorted_joins"]
    assert row["narrow_key_joins"] == joins
    assert (joins > 0) == (st.template in ("q03", "q18"))


#: the fields of ISSUE 48: what each full-schema statement must count
#: on the mesh (a mesh join counts once, on its ``join_count`` program)
KIND_FIELDS = ("wide_key_joins", "outer_joins", "anti_joins",
               "distinct_aggregates", "revoked_joins")
FULL_SCHEMA = {
    "q09": {"outer_joins": 0, "anti_joins": 0, "distinct_aggregates": 0},
    "q13": {"outer_joins": 1, "anti_joins": 0, "distinct_aggregates": 0},
    "q16": {"outer_joins": 0, "anti_joins": 1, "distinct_aggregates": 1},
}


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_the_power_mix_counts_no_outer_anti_wide_or_distinct(st, served):
    _, row = served[st.key]
    assert [row[f] for f in KIND_FIELDS] == [0] * len(KIND_FIELDS), row


@pytest.mark.parametrize("q", sorted(FULL_SCHEMA))
def test_mesh_rows_count_joins_by_kind_and_distinct_aggregates(
        q, mesh_coord, local_coord):
    """The mesh executor notes what the local one notes (ISSUE 48): an
    outer join's kind on its ``join_count`` program, an anti join on
    the semi join under a Filter that negates its match, a chain's
    DISTINCT aggregate calls, a join ranked at 64 bits — Q9's
    two-column key — and the rows are the one-device runner's."""
    from trino_tpu.connectors.tpch.queries import QUERIES

    rows, row = serve(mesh_coord.uri, QUERIES[q])
    for f, n in FULL_SCHEMA[q].items():
        assert row[f] == n, (f, row)
    assert row["revoked_joins"] == 0
    assert (row["wide_key_joins"] >= 1) == (q == "q09"), row
    local_rows, local = serve(local_coord.uri, QUERIES[q])
    assert sorted(map(repr, rows)) == sorted(map(repr, local_rows))
    for f, n in FULL_SCHEMA[q].items():
        assert local[f] == n, (f, local)


@pytest.mark.parametrize(
    "st", [s for s in STATEMENTS if s.template in ("q03", "q18")],
    ids=["q03", "q18"])
def test_no_mesh_fields_move_without_a_mesh(st, local_coord):
    _, row = serve(local_coord.uri, st.sql)
    assert [row[f] for f in MESH_FIELDS] == [0] * len(MESH_FIELDS)


def test_every_mesh_program_is_named(served, mesh_coord):
    """What the mesh executor compiled for the mix, read off its jit
    cache: XLA's module (and the device trace) is ``jit_<name>``."""
    names = set()
    for hit in mesh_coord.runner.executor._mesh_jit_cache.values():
        prog = hit[0] if isinstance(hit, tuple) else hit
        names.add(prog.__name__)
    assert names and all(n.startswith("mesh_") for n in names), names
    assert {"mesh_exchange", "mesh_exchange_dest", "mesh_exchange_in_place",
            "mesh_join_count", "mesh_join_expand", "mesh_semi_join"} <= names
    assert any(n.startswith("mesh_chain_") for n in names)


def test_mesh_exchange_span_carries_what_moved(served, mesh_coord):
    st = next(s for s in STATEMENTS if s.template == "q18")
    qid = served[st.key][1]["query_id"]
    with urllib.request.urlopen(
            mesh_coord.uri + "/v1/query/" + qid, timeout=30) as r:
        tree = json.loads(r.read())["spans"]

    def walk(sp):
        yield sp
        for ch in sp.get("children", ()):
            yield from walk(ch)

    exchanges = [sp for sp in walk(tree) if sp["name"] == "mesh-exchange"]
    assert exchanges
    for sp in exchanges:
        attrs = sp["attrs"]
        assert attrs["edge"].startswith("mesh-")
        assert attrs["live_bytes"] <= attrs["buffer_bytes"]
        assert attrs["live_rows"] >= 0 and attrs["escalations"] >= 0
        # the wait for the flag and the count is a host_sync inside it
        inner = [c["name"] for c in sp["children"]]
        assert "dispatch" in inner and "host_sync" in inner


# ---- the entry point: python -m trino_tpu.server.coordinator --mesh N ----


def _spawn(*args, stderr=subprocess.PIPE):
    env = dict(os.environ, PYTHONPATH=ROOT, PYTHONUNBUFFERED="1")
    return subprocess.Popen(
        [sys.executable, "-m", "trino_tpu.server.coordinator", *args],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=stderr, text=True)


@pytest.fixture(scope="module")
def cli_uri(served, tmp_path_factory):
    # after ``served``: the child reads this process's programs from the
    # persistent compile cache instead of compiling them again
    port = harness.supervisor.free_port()
    log = tmp_path_factory.mktemp("mesh_cli") / "coordinator.err"
    with open(log, "w") as err:  # a file: a full pipe would block it
        proc = _spawn("--schema", "tiny", "--port", str(port), "--mesh", "4",
                      stderr=err)
    try:
        for line in proc.stdout:
            if line.startswith("coordinator ready on port"):
                break
        else:
            pytest.fail("no ready line: " + log.read_text()[-2000:])
        yield f"http://127.0.0.1:{port}"
    finally:
        proc.kill()
        proc.wait(timeout=30)


@pytest.mark.parametrize("st", STATEMENTS, ids=IDS)
def test_mesh_flag_serves_the_templates(st, cli_uri, ref_conn):
    rows, row = serve(cli_uri, st.sql)
    assert_equal_to_reference(st, rows, ref_conn)
    assert row["mesh_gather_ms"] > 0


def test_mesh_flag_reports_its_devices(cli_uri):
    with urllib.request.urlopen(cli_uri + "/v1/info", timeout=30) as r:
        info = json.loads(r.read())
    assert info["device_count"] >= 4


def test_mesh_larger_than_the_host_fails_before_ready():
    proc = _spawn("--schema", "tiny", "--port", "0", "--mesh", "16")
    out, err = proc.communicate(timeout=120)
    assert proc.returncode not in (0, None)
    assert "coordinator ready" not in out
    assert "--mesh 16" in err and "have 8" in err


# ---- the sf5 schema ------------------------------------------------------


@pytest.mark.parametrize("table,rows", [
    ("customer", 750_000), ("orders", 7_500_000), ("lineitem", 30_006_807)])
def test_sf5_row_counts(table, rows):
    """By formula (lineitem from the per-order counts): no column of
    the table is generated."""
    conn = TpchConnector()
    assert SCHEMA_SF["sf5"] == 5.0 and "sf5" in conn.list_schemas()
    assert conn.row_count("sf5", table) == rows
    assert CONFIG["tables"][table]["rows"] == rows
    assert all(c == "__counts__" for _, c in conn.data("sf5")._cache)
