"""Distributed SQL execution over the virtual 8-device mesh vs sqlite.

The analog of the reference's DistributedQueryRunner tier
(TESTING/DistributedQueryRunner.java:98, TestDistributedEngineOnlyQueries):
the same SQL surface the local tests cover, but every plan goes through
distribution planning (plan.distribute) and SPMD execution on the mesh —
hash all_to_all exchanges, partial/final aggregation, partitioned and
broadcast joins — and must produce identical results.
"""

import jax
import pytest

from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.engine import QueryRunner
from trino_tpu.parallel.core import make_mesh
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)


@pytest.fixture(scope="module")
def runner():
    return QueryRunner.tpch("tiny", mesh=make_mesh(8))


@pytest.fixture(scope="module")
def oracle(runner):
    data = runner.metadata.connector("tpch").data("tiny")
    return load_tpch_sqlite(data)


def check(runner, oracle, sql, abs_tol=1e-9):
    result = runner.execute(sql)
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(
        result.rows, expected, ordered=result.ordered, abs_tol=abs_tol
    )
    return result


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_tpch_query_distributed(runner, oracle, name):
    check(runner, oracle, QUERIES[name], abs_tol=0.006)


def test_dist_global_aggregate(runner, oracle):
    check(
        runner, oracle,
        "select count(*), sum(l_quantity), min(l_tax), max(l_discount) "
        "from lineitem",
    )


def test_dist_group_by_varchar(runner, oracle):
    check(
        runner, oracle,
        "select l_shipmode, count(*), avg(l_extendedprice) from lineitem "
        "group by l_shipmode order by l_shipmode",
    )


def test_dist_distinct_aggregate(runner, oracle):
    check(
        runner, oracle,
        "select l_linestatus, count(distinct l_suppkey) from lineitem "
        "group by l_linestatus order by l_linestatus",
    )


def test_dist_variance(runner, oracle):
    # sqlite has no stddev; compare against the local executor instead
    local = QueryRunner.tpch("tiny")
    sql = (
        "select l_returnflag, stddev(l_quantity), variance(l_discount) "
        "from lineitem group by l_returnflag order by l_returnflag"
    )
    got = runner.execute(sql)
    want = local.execute(sql)
    assert_rows_match(got.rows, want.rows, ordered=True, abs_tol=1e-6)
    # absolute sanity: quantities are uniform 1..50, stddev ~ 14.4
    # (guards the DECIMAL-scale regression where it read ~1437)
    assert 13.0 < got.rows[0][1] < 16.0


def test_dist_partitioned_join(runner, oracle):
    check(
        runner, oracle,
        "select count(*), sum(l_extendedprice) from lineitem, orders "
        "where l_orderkey = o_orderkey and o_orderdate < date '1995-01-01'",
    )


def test_dist_broadcast_join(runner, oracle):
    check(
        runner, oracle,
        "select n_name, count(*) from customer, nation "
        "where c_nationkey = n_nationkey group by n_name order by n_name",
    )


def test_dist_left_join(runner, oracle):
    check(
        runner, oracle,
        "select c_custkey, o_orderkey from customer "
        "left join orders on c_custkey = o_custkey and o_totalprice > 200000 "
        "order by c_custkey, o_orderkey limit 50",
    )


def test_dist_semi_join(runner, oracle):
    check(
        runner, oracle,
        "select count(*) from customer where c_custkey in "
        "(select o_custkey from orders where o_totalprice > 100000)",
    )


def test_dist_anti_join(runner, oracle):
    check(
        runner, oracle,
        "select count(*) from customer where c_custkey not in "
        "(select o_custkey from orders)",
    )


def test_dist_cross_join_scalar_subquery(runner, oracle):
    check(
        runner, oracle,
        "select count(*) from lineitem "
        "where l_quantity > (select avg(l_quantity) from lineitem)",
    )


def test_dist_topn_and_limit(runner, oracle):
    check(
        runner, oracle,
        "select o_orderkey, o_totalprice from orders "
        "order by o_totalprice desc limit 10",
    )


def test_explain_analyze_reports_exchange_stats(runner):
    """Distributed EXPLAIN ANALYZE surfaces exchange telemetry:
    all_to_all count, bytes moved, skew-split and escalation counters
    (the per-stage exchange stats of the reference's EXPLAIN ANALYZE)."""
    rows = runner.execute(
        "explain analyze select l_shipmode, count(*) from lineitem "
        "group by l_shipmode"
    ).rows
    text = "\n".join(r[0] for r in rows)
    assert "Exchanges:" in text and "all_to_all" in text, text
    assert "moved" in text and "escalations" in text
