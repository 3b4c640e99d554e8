"""Distributed write path: INSERT INTO ... SELECT and CTAS through
the TableWriter/TableFinish subsystem, against the sqlite oracle.

Every committed table is read BACK through the engine and compared
row-for-row with the same statement's effect applied to an oracle —
a write path that silently drops, duplicates or reorders rows is the
worst failure mode a database can have. The matrix covers the local
executor, the SPMD mesh, and a real 2-worker fleet (scaled writers,
coordinator-side commit); partitioned CTAS additionally proves the
committed Hive layout is PRUNABLE (the layout is the point of
partitioned writes); the chaos variant proves exactly-once commit
under injected writer faults.

Parquet-backed cases require pyarrow and skip cleanly without it
(CI's write-smoke lane installs it; the default matrix does not).
"""

import os
import tempfile

import pytest

from trino_tpu import fault
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.memory import ExceededMemoryLimitError
from trino_tpu.metadata import Metadata, Session

BASE_PORT = 19760  # write-path suite's own range (chaos owns 19680+)


def _mem_runner(**session_props) -> QueryRunner:
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.session.properties.update(session_props)
    r.execute("create table src (k bigint, v varchar)")
    r.execute(
        "insert into src values (1, 'a'), (2, 'b'), (3, 'c'), "
        "(4, 'd'), (5, null)"
    )
    return r


def _hive_runner(root: str, mesh=None) -> QueryRunner:
    from trino_tpu.connectors.parquet import ParquetConnector

    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    md.register_catalog("hive", ParquetConnector(root))
    r = QueryRunner(
        md, Session(catalog="memory", schema="default"), mesh=mesh
    )
    r.execute("create table src (k bigint, v varchar)")
    r.execute(
        "insert into src values (1, 'a'), (2, 'b'), (3, 'c'), "
        "(4, 'd'), (5, null)"
    )
    return r


SRC_ROWS = [(1, "a"), (2, "b"), (3, "c"), (4, "d"), (5, None)]


# ---------------------------------------------------------------------------
# local executor: memory connector (no pyarrow needed)
# ---------------------------------------------------------------------------


def test_ctas_memory_roundtrip():
    r = _mem_runner()
    res = r.execute("create table dst as select k, v from src")
    assert res.rows == [(5,)]
    assert (
        r.execute("select k, v from dst order by k").rows == SRC_ROWS
    )


def test_insert_select_memory_appends():
    r = _mem_runner()
    r.execute("create table dst as select k, v from src")
    res = r.execute(
        "insert into dst select k + 10, v from src where k <= 2"
    )
    assert res.rows == [(2,)]
    assert r.execute("select k, v from dst order by k").rows == (
        SRC_ROWS + [(11, "a"), (12, "b")]
    )


def test_insert_select_column_list_null_fills():
    r = _mem_runner()
    r.execute("create table dst as select k, v from src")
    r.execute("insert into dst (k) select k + 100 from src where k = 1")
    assert r.execute(
        "select k, v from dst where k = 101"
    ).rows == [(101, None)]


def test_ctas_expressions_and_aliases():
    r = _mem_runner()
    r.execute(
        "create table agg as select v, k * 2 as kk from src "
        "where k <= 3"
    )
    assert r.execute("select v, kk from agg order by kk").rows == [
        ("a", 2), ("b", 4), ("c", 6),
    ]


def test_ctas_if_not_exists_is_noop():
    r = _mem_runner()
    r.execute("create table dst as select k, v from src")
    res = r.execute(
        "create table if not exists dst as select k + 99, v from src"
    )
    assert res.rows == [(0,)]
    assert (
        r.execute("select k, v from dst order by k").rows == SRC_ROWS
    )


def test_ctas_existing_table_fails():
    from trino_tpu.analyzer.analyzer import AnalysisError

    r = _mem_runner()
    with pytest.raises(AnalysisError, match="already exists"):
        r.execute("create table src as select k from src")


def test_insert_arity_mismatch_fails():
    from trino_tpu.analyzer.analyzer import AnalysisError

    r = _mem_runner()
    r.execute("create table dst as select k, v from src")
    with pytest.raises(AnalysisError):
        r.execute("insert into dst select k from src")


# ---------------------------------------------------------------------------
# local executor: partitioned parquet (pyarrow-gated)
# ---------------------------------------------------------------------------


def test_ctas_partitioned_parquet_roundtrip(tmp_path):
    pytest.importorskip("pyarrow")
    r = _hive_runner(str(tmp_path))
    res = r.execute(
        "create table hive.w.t with (partitioned_by = array['k']) as "
        "select k, v from src"
    )
    assert res.rows == [(5,)]
    assert (
        r.execute("select k, v from hive.w.t order by k").rows
        == SRC_ROWS
    )
    # the committed layout is Hive-style key=value directories
    tdir = os.path.join(str(tmp_path), "w", "t")
    assert os.path.isdir(os.path.join(tdir, "k=1"))
    assert os.path.isfile(os.path.join(tdir, "_manifest.json"))


def test_ctas_partitioned_layout_is_prunable(tmp_path):
    pytest.importorskip("pyarrow")
    r = _hive_runner(str(tmp_path))
    r.execute(
        "create table hive.w.t with (partitioned_by = array['k']) as "
        "select k, v from src"
    )
    assert r.execute(
        "select v from hive.w.t where k = 3"
    ).rows == [("c",)]
    entry = r.executor.scan_log[-1]
    assert entry["partitions_pruned"] == 4, entry


def test_insert_partitioned_parquet_new_partition(tmp_path):
    pytest.importorskip("pyarrow")
    r = _hive_runner(str(tmp_path))
    r.execute(
        "create table hive.w.t with (partitioned_by = array['k']) as "
        "select k, v from src"
    )
    # partition columns live LAST in a partitioned table's schema —
    # positional INSERT must name its columns to stay readable
    r.execute(
        "insert into hive.w.t (k, v) select k + 10, v from src "
        "where k = 1"
    )
    assert r.execute(
        "select v from hive.w.t where k = 11"
    ).rows == [("a",)]
    assert os.path.isdir(os.path.join(str(tmp_path), "w", "t", "k=11"))


def test_unpartitioned_parquet_ctas_and_insert(tmp_path):
    pytest.importorskip("pyarrow")
    r = _hive_runner(str(tmp_path))
    r.execute("create table hive.w.flat as select k, v from src")
    r.execute("insert into hive.w.flat select k + 10, v from src")
    assert r.execute(
        "select count(*), sum(k) from hive.w.flat"
    ).rows == [(10, 15 + 15 + 50)]


def test_ctas_empty_source_still_readable(tmp_path):
    pytest.importorskip("pyarrow")
    r = _hive_runner(str(tmp_path))
    res = r.execute(
        "create table hive.w.none as select k, v from src where k > 99"
    )
    assert res.rows == [(0,)]
    assert r.execute("select count(*) from hive.w.none").rows == [(0,)]


def test_explain_analyze_renders_writer_line(tmp_path):
    pytest.importorskip("pyarrow")
    r = _hive_runner(str(tmp_path))
    res = r.execute(
        "explain analyze create table hive.w.ea as "
        "select k, v from src"
    )
    text = "\n".join(str(row[0]) for row in res.rows)
    assert "TableWriter: 5 rows" in text
    assert "commit" in text


# ---------------------------------------------------------------------------
# writer memory accounting
# ---------------------------------------------------------------------------


def test_writer_buffers_are_memory_accounted(tmp_path):
    pytest.importorskip("pyarrow")
    r = _hive_runner(str(tmp_path))
    r.execute(
        "create table big as select k * 1000000 + s as k, v from src, "
        "(select 1 as s union all select 2 union all select 3) n"
    )
    # a cap far below the writer's buffered pages must fail the
    # statement with the semantic memory error, not an OS-level OOM —
    # proof the sink's buffered bytes flow through the task's
    # MemoryContext like any operator allocation
    r.session.properties["query_max_memory_per_node"] = "64B"
    with pytest.raises(ExceededMemoryLimitError):
        r.execute("create table hive.w.oom as select k, v from big")
    r.session.properties["query_max_memory_per_node"] = "2GB"
    # and the failed write left nothing behind: the table neither
    # exists nor has staging residue
    from trino_tpu.analyzer.analyzer import AnalysisError

    with pytest.raises((AnalysisError, FileNotFoundError)):
        r.execute("select * from hive.w.oom")
    assert not [
        d for d in os.listdir(str(tmp_path / "w"))
        if d.startswith("_tmp_")
    ] if os.path.isdir(str(tmp_path / "w")) else True


# ---------------------------------------------------------------------------
# DML invalidates the semantic result cache
# ---------------------------------------------------------------------------


def test_write_statements_bump_cache_generation():
    r = _mem_runner(result_cache_enabled=True)
    r.execute("create table dst as select k, v from src")
    sql = "select count(*) from dst"
    assert r.execute(sql).cache_stats["result"]["hit"] is False
    assert r.execute(sql).cache_stats["result"]["hit"] is True
    r.execute("insert into dst select k + 50, v from src where k = 1")
    stale = r.execute(sql)
    assert stale.cache_stats["result"]["hit"] is False, (
        "INSERT SELECT did not invalidate the cached read"
    )
    assert stale.rows == [(6,)]


def test_write_results_are_never_cached():
    r = _mem_runner(result_cache_enabled=True)
    r.execute("create table a as select k from src")
    res = r.execute("insert into a select k + 10 from src")
    assert res.cache_stats is None or not res.cache_stats.get(
        "result", {}
    ).get("hit")
    # re-running the same INSERT text must write again, not replay a
    # cached "5 rows" result
    r.execute("insert into a select k + 10 from src")
    assert r.execute("select count(*) from a").rows == [(15,)]


# ---------------------------------------------------------------------------
# SPMD mesh executor
# ---------------------------------------------------------------------------


def test_ctas_and_insert_on_mesh(tmp_path):
    pytest.importorskip("pyarrow")
    from trino_tpu.parallel.core import make_mesh

    r = _hive_runner(str(tmp_path), mesh=make_mesh())
    r.execute(
        "create table hive.w.t with (partitioned_by = array['k']) as "
        "select k, v from src"
    )
    assert (
        r.execute("select k, v from hive.w.t order by k").rows
        == SRC_ROWS
    )
    r.execute(
        "insert into hive.w.t (k, v) select k + 10, v from src "
        "where k <= 2"
    )
    assert r.execute(
        "select count(*) from hive.w.t"
    ).rows == [(7,)]


# ---------------------------------------------------------------------------
# 2-worker fleet: scaled writers + coordinator-side commit
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet_env():
    from trino_tpu.testing.chaos import spawn_workers, stop_workers

    pytest.importorskip("pyarrow")
    hive_root = tempfile.mkdtemp(prefix="write-path-hive")
    spool = tempfile.mkdtemp(prefix="write-path-spool")
    procs, uris = spawn_workers(
        2, base_port=BASE_PORT,
        extra_env={
            "TRINO_TPU_WORKER_EXTRA_PARQUET": f"hive={hive_root}",
        },
        platform="cpu",
    )
    yield {"uris": uris, "hive_root": hive_root, "spool": spool}
    stop_workers(procs)


def _make_fleet(env):
    from trino_tpu.connectors.parquet import ParquetConnector
    from trino_tpu.connectors.tpch.connector import TpchConnector
    from trino_tpu.server.fleet import FleetRunner

    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    md.register_catalog("hive", ParquetConnector(env["hive_root"]))
    return FleetRunner(
        list(env["uris"]), md, Session(catalog="tpch", schema="tiny"),
        spool_root=env["spool"], n_partitions=4,
    )


@pytest.mark.slow
def test_fleet_partitioned_ctas_oracle_roundtrip(fleet_env):
    fleet = _make_fleet(fleet_env)
    res = fleet.execute(
        "create table hive.w.orders_p "
        "with (partitioned_by = array['o_orderpriority']) as "
        "select o_orderkey, o_totalprice, o_orderpriority from orders"
    )
    n = fleet.execute("select count(*) from orders").rows[0][0]
    assert res.rows == [(n,)]
    # full-content read-back through the fleet itself
    assert fleet.execute(
        "select count(*), sum(o_orderkey) from hive.w.orders_p"
    ).rows == fleet.execute(
        "select count(*), sum(o_orderkey) from orders"
    ).rows
    # committed stats surfaced per-stage (system.runtime.tasks view)
    written = [
        st for st in res.stage_stats
        if st.get("rows_written") is not None
    ]
    assert written and written[0]["rows_written"] == n


@pytest.mark.slow
def test_fleet_scaled_writers_and_insert(fleet_env):
    fleet = _make_fleet(fleet_env)
    fleet.session.properties["task_writer_count"] = 3
    res = fleet.execute(
        "create table hive.w.orders_flat as "
        "select o_orderkey, o_totalprice from orders"
    )
    writer_tasks = {
        ts["task_id"] for ts in res.task_stats
        if ts.get("rows_written") is not None
    }
    assert len(writer_tasks) == 3, writer_tasks
    n = fleet.execute("select count(*) from orders").rows[0][0]
    ins = fleet.execute(
        "insert into hive.w.orders_flat "
        "select o_orderkey + 1000000, o_totalprice from orders "
        "where o_orderkey <= 8"
    )
    assert fleet.execute(
        "select count(*) from hive.w.orders_flat"
    ).rows == [(n + ins.rows[0][0],)]


@pytest.mark.slow
def test_fleet_writer_scaling_off_single_task(fleet_env):
    fleet = _make_fleet(fleet_env)
    fleet.session.properties["task_writer_count"] = 3
    fleet.session.properties["writer_scaling"] = False
    res = fleet.execute(
        "create table hive.w.orders_one as "
        "select o_orderkey from orders"
    )
    writer_tasks = {
        ts["task_id"] for ts in res.task_stats
        if ts.get("rows_written") is not None
    }
    assert len(writer_tasks) == 1, writer_tasks


@pytest.mark.slow
def test_fleet_write_chaos_fast(fleet_env):
    """Fast chaos variant: every writer task's attempt 0 fails after
    staging part files; the committed table must match a clean run
    exactly (retried attempts replace, never duplicate)."""
    fleet = _make_fleet(fleet_env)
    clean = fleet.execute(
        "create table hive.w.chaos_clean as "
        "select o_orderkey, o_totalprice from orders"
    )
    fleet = _make_fleet(fleet_env)
    fleet.session.properties["speculation_enabled"] = False
    fleet.session.properties["retry_initial_delay_ms"] = 5
    fleet.session.properties["retry_max_delay_ms"] = 20
    inj = fault.FaultInjector(seed=7, max_attempts=fleet.max_attempts)
    inj.arm("task-exec", times=1)
    fault.activate(inj)
    try:
        res = fleet.execute(
            "create table hive.w.chaos_t as "
            "select o_orderkey, o_totalprice from orders"
        )
    finally:
        fault.deactivate()
    assert res.tasks_retried >= 1
    assert res.rows == clean.rows
    assert fleet.execute(
        "select count(*), sum(o_orderkey) from hive.w.chaos_t"
    ).rows == fleet.execute(
        "select count(*), sum(o_orderkey) from hive.w.chaos_clean"
    ).rows
