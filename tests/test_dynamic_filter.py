"""Dynamic filtering: build-side key domains pruning probe rows before
join work (DynamicFilterService analog,
MAIN/server/DynamicFilterService.java:106; the reference's
TestDynamicFiltering suites assert probe-side row drops the same way
via operator stats)."""

import pytest

from trino_tpu.engine import QueryRunner
from trino_tpu.exec.local import LocalExecutor
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)


@pytest.fixture()
def runner(monkeypatch):
    monkeypatch.setattr(LocalExecutor, "DF_MIN_PROBE", 1024)
    return QueryRunner.tpch("tiny")


@pytest.fixture()
def mesh_runner(monkeypatch):
    from trino_tpu.parallel.core import make_mesh

    monkeypatch.setattr(LocalExecutor, "DF_MIN_PROBE", 1024)
    return QueryRunner.tpch("tiny", mesh=make_mesh())


@pytest.fixture(scope="module")
def oracle():
    data = QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    return load_tpch_sqlite(data)


def check(runner, oracle, sql):
    result = runner.execute(sql)
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(result.rows, expected, ordered=result.ordered)
    return result


def test_local_minmax_prunes_probe(runner, oracle):
    """A build side confined to a narrow key range prunes the probe
    before the join (min/max domain, the local path)."""
    sql = (
        "select count(*), sum(l_quantity) from lineitem, orders "
        "where l_orderkey = o_orderkey and o_orderkey < 500"
    )
    check(runner, oracle, sql)
    log = runner.executor.df_log
    assert log, "dynamic filter did not run"
    last = log[-1]
    assert last["rows_kept"] < 0.3 * last["rows_in"]


def test_local_df_skips_outer_joins(runner, oracle):
    sql = (
        "select count(*) from orders left join lineitem "
        "on o_orderkey = l_orderkey and l_quantity > 49"
    )
    before = len(runner.executor.df_log)
    check(runner, oracle, sql)
    assert len(runner.executor.df_log) == before


def test_mesh_membership_prunes_before_exchange(mesh_runner, oracle):
    """Distributed: exact membership on the build key drops probe rows
    even for uniform dense keys where min/max can't prune."""
    sql = (
        "select o_orderpriority, count(*) from lineitem, orders "
        "where l_orderkey = o_orderkey and o_orderdate >= date '1997-01-01' "
        "group by o_orderpriority"
    )
    check(mesh_runner, oracle, sql)
    log = mesh_runner.executor.df_log
    assert log, "mesh dynamic filter did not run"
    last = log[-1]
    # ~2/7 of orders fall in 1997+; membership must reflect that drop
    assert last["rows_kept"] < 0.6 * last["rows_in"]


def test_mesh_df_correct_when_filter_empty(mesh_runner, oracle):
    """An empty build side empties the probe (inner join: correct)."""
    sql = (
        "select count(*) from lineitem, orders "
        "where l_orderkey = o_orderkey and o_totalprice < 0"
    )
    check(mesh_runner, oracle, sql)


def test_local_df_multi_key(runner, oracle):
    sql = (
        "select count(*) from lineitem l1, lineitem l2 "
        "where l1.l_orderkey = l2.l_orderkey "
        "and l1.l_linenumber = l2.l_linenumber "
        "and l2.l_orderkey < 300"
    )
    check(runner, oracle, sql)


def test_mesh_df_over_a_small_build_counts_and_keeps_the_same_rows(
        monkeypatch, oracle):
    """A dynamic filter whose (replicated) build side is a handful of
    rows ranks each probe shard by compare-and-count (ISSUE 42): its
    ``dispatch`` span says so, and rows, kept count and answer are what
    the search by sort gives."""
    from trino_tpu.exec import kernels as K
    from trino_tpu.parallel.core import make_mesh

    monkeypatch.setattr(LocalExecutor, "DF_MIN_PROBE", 1024)
    sql = (
        "select count(*), sum(l_quantity) from lineitem, orders "
        "where l_orderkey = o_orderkey and o_orderkey < 500"
    )
    seen, small = {}, K.JOIN_SMALL_BUILD
    for search, limit in (("count", small), ("sort", -1)):
        monkeypatch.setattr(K, "JOIN_SMALL_BUILD", limit)
        K.join_ranges.clear_cache()  # the constant is read while tracing
        try:
            runner = QueryRunner.tpch("tiny", mesh=make_mesh(4))
            result = check(runner, oracle, sql)
        finally:
            K.join_ranges.clear_cache()
        (df,) = [
            sp.attrs for sp in result.trace.root.walk()
            if sp.attrs.get("program") == "mesh_dynamic_filter"
        ]
        assert df["join_search"] == search
        assert df["build_rows"] <= small  # the few orders, replicated
        seen[search] = (result.rows, runner.executor.df_log[-1])
    assert seen["count"] == seen["sort"]
    assert seen["count"][1]["rows_kept"] < 0.3 * seen["count"][1]["rows_in"]
