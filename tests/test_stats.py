"""Statistics framework: connector stats SPI, plan-level estimation,
stats-driven distribution choices, and value-range key packing.

The analog of the reference's StatsCalculator tests
(core/trino-main/src/test/java/io/trino/cost/TestFilterStatsCalculator.java,
TestJoinStatsRule.java) plus DetermineJoinDistributionType plan
assertions — scaled to the implemented surface.
"""

import numpy as np
import pytest

from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.metadata import Metadata, Session
from trino_tpu.plan import nodes as P
from trino_tpu.plan.stats import annotate, estimate


@pytest.fixture(scope="module")
def runner():
    return QueryRunner.tpch("tiny")


def _find(node, kind):
    out = []

    def walk(n):
        if isinstance(n, kind):
            out.append(n)
        for s in n.sources:
            walk(s)

    walk(node)
    return out


# ---- connector stats SPI ---------------------------------------------------

def test_tpch_table_stats(runner):
    conn = runner.metadata.connector("tpch")
    ts = conn.table_stats("tiny", "orders")
    assert ts.row_count == conn.row_count("tiny", "orders")
    ok = ts.columns["o_orderkey"]
    assert ok.ndv == ts.row_count  # primary key
    assert ok.lo == 1.0
    assert ok.null_fraction == 0.0
    ck = ts.columns["o_custkey"]
    assert 0 < ck.ndv <= ts.row_count


def test_memory_table_stats():
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.execute("create table t (a bigint, b varchar)")
    r.execute("insert into t values (1, 'x'), (5, 'y'), (5, null)")
    ts = md.connector("memory").table_stats("default", "t")
    assert ts.row_count == 3
    assert ts.columns["a"].lo == 1 and ts.columns["a"].hi == 5
    assert ts.columns["a"].ndv == 2
    assert ts.columns["b"].null_fraction == pytest.approx(1 / 3)


# ---- plan estimation -------------------------------------------------------

def test_filter_selectivity_range(runner):
    full = runner.plan_sql("select o_orderkey from orders")
    half = runner.plan_sql(
        "select o_orderkey from orders where o_orderdate < date '1995-06-01'"
    )
    e_full = estimate(full, runner.metadata).rows
    e_half = estimate(half, runner.metadata).rows
    # the date domain spans 1992..1998; mid-1995 cuts roughly half
    assert 0.3 * e_full < e_half < 0.75 * e_full


def test_filter_selectivity_eq(runner):
    p = runner.plan_sql(
        "select * from orders where o_orderkey = 7"
    )
    est = estimate(p, runner.metadata).rows
    assert est <= 2.0  # primary key equality -> ~1 row


def test_join_cardinality(runner):
    p = runner.plan_sql(
        "select l_orderkey from lineitem, orders where l_orderkey = o_orderkey"
    )
    li = runner.metadata.connector("tpch").row_count("tiny", "lineitem")
    est = estimate(p, runner.metadata).rows
    # fk join: every lineitem matches exactly one order
    assert 0.5 * li < est < 2.0 * li


def test_aggregate_groups_estimate(runner):
    p = runner.plan_sql(
        "select l_orderkey, count(*) from lineitem group by l_orderkey"
    )
    orders = runner.metadata.connector("tpch").row_count("tiny", "orders")
    est = estimate(p, runner.metadata).rows
    assert 0.5 * orders < est < 2.0 * orders


# ---- stats-driven distribution ---------------------------------------------

def _mesh_plan(sql, session=None):
    from trino_tpu.connectors.tpch.connector import TpchConnector
    from trino_tpu.plan.distribute import add_exchanges
    from trino_tpu.plan.optimizer import optimize
    from trino_tpu.analyzer.analyzer import Analyzer
    from trino_tpu.sql.parser import parse_statement

    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    session = session or Session(catalog="tpch", schema="tiny")
    plan = Analyzer(md, session).analyze(parse_statement(sql))
    plan = optimize(plan, md, session)
    plan = add_exchanges(plan, md, n_shards=8, session=session)
    return annotate(plan, md), md


def test_small_build_broadcasts():
    plan, _ = _mesh_plan(
        "select count(*) from lineitem, region "
        "where l_suppkey % 5 = r_regionkey"
    )
    joins = _find(plan, P.Join)
    assert joins and all(j.distribution == "BROADCAST" for j in joins)


def test_large_build_partitions():
    # both sides are the two largest tables: replication would cost
    # ~8x the build; the cost model must repartition instead
    plan, _ = _mesh_plan(
        "select count(*) from lineitem, orders where l_orderkey = o_orderkey"
    )
    joins = _find(plan, P.Join)
    assert joins and joins[0].distribution == "PARTITIONED"


def test_session_forces_distribution():
    s = Session(
        catalog="tpch", schema="tiny",
        properties={"join_distribution_type": "BROADCAST"},
    )
    plan, _ = _mesh_plan(
        "select count(*) from lineitem, orders where l_orderkey = o_orderkey",
        session=s,
    )
    joins = _find(plan, P.Join)
    assert joins[0].distribution == "BROADCAST"


# ---- annotations -----------------------------------------------------------

def test_aggregate_annotations(runner):
    plan = runner.plan_sql(
        "select l_orderkey, sum(l_quantity) from lineitem group by l_orderkey"
    )
    aggs = _find(plan, P.Aggregate)
    assert aggs
    a = aggs[0]
    orders = runner.metadata.connector("tpch").row_count("tiny", "orders")
    assert a.est_groups is not None
    assert 0.5 * orders < a.est_groups < 2.0 * orders
    assert a.key_ranges
    (key, (lo, hi)), = a.key_ranges.items()
    assert key.startswith("l_orderkey")
    assert lo >= 1 and hi > lo


def test_capacity_planned_no_retry(runner):
    """With stats, the group table is sized upfront: no overflow retry
    on a full-table high-cardinality aggregation."""
    ex = runner.executor
    before = dict(ex._jit_cache)
    runner.execute(
        "select l_orderkey, count(*) c from lineitem group by l_orderkey"
    )
    # a retry would have stored a learned 'caps' entry
    new_caps = [
        k for k in ex._jit_cache
        if k not in before
        and isinstance(k, tuple) and k and k[0] == "caps"
    ]
    assert new_caps == []


# ---- value-range key packing correctness -----------------------------------

def test_range_packed_grouping_exact():
    """Grouping on a column whose values live in a narrow window far
    from zero: the executor shifts by lo and packs to bit_length(hi-lo)
    bits — results must be exact."""
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.execute("create table t (k bigint, v bigint)")
    base = 10**15
    rows = ", ".join(
        f"({base + (i % 7)}, {i})" for i in range(50)
    )
    r.execute(f"insert into t values {rows}")
    plan = r.plan_sql("select k, sum(v) from t group by k")
    aggs = _find(plan, P.Aggregate)
    assert aggs[0].key_ranges is not None  # packing actually engaged
    got = sorted(r.execute("select k, sum(v) from t group by k").rows)
    expect = {}
    for i in range(50):
        expect.setdefault(base + (i % 7), 0)
        expect[base + (i % 7)] += i
    assert got == sorted(expect.items())


def test_range_packed_multiword_group():
    """A multi-column group whose packed widths exceed 64 bits takes
    the multi-word lexsort path; results must be exact."""
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.execute("create table t (a bigint, b bigint, c bigint, v bigint)")
    rng = np.random.default_rng(7)
    n = 200
    a = rng.integers(0, 1 << 40, n)
    b = rng.integers(0, 1 << 40, n)
    c = rng.integers(0, 50, n)
    rows = ", ".join(
        f"({a[i]}, {b[i]}, {c[i]}, {i})" for i in range(n)
    )
    r.execute(f"insert into t values {rows}")
    got = sorted(
        r.execute("select a, b, c, count(*), sum(v) from t group by a, b, c").rows
    )
    expect = {}
    for i in range(n):
        k = (int(a[i]), int(b[i]), int(c[i]))
        cnt, sv = expect.get(k, (0, 0))
        expect[k] = (cnt + 1, sv + i)
    assert got == sorted((k + v) for k, v in expect.items())


def test_huge_int_keys_group_exactly():
    """Keys beyond 2^53 must not collapse: integer bounds stay Python
    ints end-to-end (float64 would round lo UP and corrupt range
    packing)."""
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.execute("create table t (k bigint, v bigint)")
    a, b = 2**60 + 200, 2**60 + 300
    r.execute(f"insert into t values ({a}, 1), ({a}, 10), ({b}, 100)")
    got = sorted(r.execute("select k, sum(v) from t group by k").rows)
    assert got == [(a, 11), (b, 100)]


def test_join_on_count_output_plans():
    """A join keyed on a count(*) output (lo=0 without hi) must not
    crash annotation."""
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.execute("create table t (x bigint)")
    r.execute("create table u (k bigint)")
    r.execute("insert into t values (1), (2), (3)")
    r.execute("insert into u values (7), (7), (9)")
    got = sorted(r.execute(
        "select t.x from t, (select k, count(*) c from u group by k) s "
        "where t.x = s.c"
    ).rows)
    assert got == [(1,), (2,)]


def test_outer_join_does_not_narrow_exact_bounds():
    """LEFT JOIN keeps unmatched probe rows, so the probe key's exact
    bounds must NOT intersect with the build side's narrower range
    (would corrupt value-range key packing and merge distinct groups)."""
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.execute("create table t1 (k bigint)")
    r.execute("create table t2 (k bigint, w bigint)")
    rows = ", ".join(f"({i * 1000})" for i in range(20))
    r.execute(f"insert into t1 values {rows}")
    r.execute("insert into t2 values (5000, 1), (6000, 2)")
    got = sorted(r.execute(
        "select t1.k, count(*) from t1 left join t2 on t1.k = t2.k "
        "group by t1.k"
    ).rows)
    assert got == [(i * 1000, 1) for i in range(20)]


def test_inner_join_keeps_the_exact_side_when_the_other_is_an_estimate():
    """An inner join's key with a guarantee on one side and an estimate
    on the other — a count(*) output cut by ``in`` to [2, 3], inside
    the probe's exact [1, 6] — keeps the guarantee alone: intersecting
    the two and calling the result exact would hand the group-by above
    (and a join above, through ``Join.key_ranges``) a range that an
    estimate drew."""
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.execute("create table t (x bigint)")
    r.execute("create table u (k bigint)")
    r.execute("insert into t values (1), (2), (3), (4), (5), (6)")
    r.execute("insert into u values (7), (7), (9), (9), (9), (4)")
    sql = (
        "select t.x, count(*) from t join "
        "(select k, count(*) c from u group by k) s on t.x = s.c "
        "where s.c in (2, 3) group by t.x"
    )
    plan = r.plan_sql(sql)
    (join,) = _find(plan, P.Join)
    ((x, c),) = join.criteria
    left, right = (estimate(src, md).sym(k) for src, k in
                   zip(join.sources, (x, c)))
    assert (left.lo, left.hi, left.exact) == (1, 6, True)
    assert (right.lo, right.hi, right.exact) == (2, 3, False)
    joined = estimate(join, md)
    for k in (x, c):
        st = joined.sym(k)
        assert (st.lo, st.hi, st.exact) == (1, 6, True), k
    # one side is no guarantee: the join itself ranks at 64 bits
    assert join.key_ranges is None
    above = [a for a in _find(plan, P.Aggregate) if x in a.group_keys]
    assert [a.key_ranges for a in above] == [{x: (1, 6)}]
    assert sorted(r.execute(sql).rows) == [(2, 1), (3, 1)]


def test_distinct_agg_dedupes_before_exchange():
    """Distributed DISTINCT aggregation is two-level: a shard-local
    dedupe feeds a (group keys + distinct column) exchange — at most
    NDV rows, spread by the distinct values so a hot group key cannot
    skew it — then the deduped pairs aggregate partial/final across a
    second exchange on the group keys alone."""
    plan, _ = _mesh_plan(
        "select l_orderkey, count(distinct l_suppkey) from lineitem "
        "group by l_orderkey"
    )
    ex = _find(plan, P.Exchange)
    hash_ex = [e for e in ex if e.partitioning == "hash"]
    assert len(hash_ex) == 2
    # inner exchange: (group key, distinct column), pure-dedupe source
    pair_ex = [e for e in hash_ex if len(e.hash_symbols) == 2]
    assert pair_ex and isinstance(pair_ex[0].source, P.Aggregate)
    assert pair_ex[0].source.aggregates == {}  # pure dedupe
    # outer exchange: group keys only, carrying partial counts
    group_ex = [e for e in hash_ex if len(e.hash_symbols) == 1]
    assert group_ex and isinstance(group_ex[0].source, P.Aggregate)
    assert group_ex[0].source.step == "PARTIAL"
    assert group_ex[0].source.aggregates  # partial count over pairs


# ---- a join's exact key range (ISSUE 46) ------------------------------------

def _pair_ranges(node):
    """``key_ranges`` with the symbols' numeric suffixes cut off."""
    cut = lambda s: s.rsplit("_", 1)[0]  # noqa: E731
    return {
        (cut(l), cut(r)): rng for (l, r), rng in (node.key_ranges or {}).items()
    }


@pytest.mark.parametrize("qid", ["q03", "q18"])
def test_join_key_ranges_on_tpch_plans(runner, qid):
    """Every equi criterion of Q3's and Q18's joins has exact integer
    bounds on BOTH sides at ``tiny``, so each carries one range holding
    both — Q18's semi join too: the subquery's ``l_orderkey`` keeps its
    bounds through the Aggregate and the HAVING — and the range rides
    the wire format the fleet's worker plans from."""
    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.plan.serde import plan_from_json, plan_to_json
    import json

    plan = runner.plan_sql(QUERIES[qid])
    joins = {
        pair: rng for j in _find(plan, P.Join)
        for pair, rng in _pair_ranges(j).items()
    }
    assert joins == {
        ("l_orderkey", "o_orderkey"): (1, 59976),
        ("o_custkey", "c_custkey"): (1, 1500),
    }
    semis = _find(plan, P.SemiJoin)
    assert [_pair_ranges(s) for s in semis] == (
        [{("o_orderkey", "l_orderkey"): (1, 59976)}] if qid == "q18" else [])
    back = plan_from_json(json.loads(json.dumps(plan_to_json(plan))))
    for kind in (P.Join, P.SemiJoin):
        sent = [n.key_ranges for n in _find(plan, kind)]
        assert [n.key_ranges for n in _find(back, kind)] == sent
        for ranges in sent:
            for pair, (lo, hi) in ranges.items():
                assert isinstance(pair, tuple) and type(lo) is type(hi) is int


def _two_tables(ddl_t, ddl_u, rows_t, rows_u):
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.execute(f"create table t ({ddl_t})")
    r.execute(f"create table u ({ddl_u})")
    r.execute(f"insert into t values {rows_t}")
    r.execute(f"insert into u values {rows_u}")
    return r


@pytest.mark.parametrize("case,ddl,rows_t,rows_u,on", [
    ("float_key", "k double, v bigint", "(1.5, 1), (2.5, 2)",
     "(1.5, 1), (9.5, 2)", "t.k = u.k"),
    ("varchar_key", "k varchar, v bigint", "('a', 1), ('b', 2)",
     "('a', 1), ('c', 2)", "t.k = u.k"),
    ("long_decimal_key", "k decimal(30, 2), v bigint", "(1.50, 1), (2.50, 2)",
     "(1.50, 1), (9.50, 2)", "t.k = u.k"),
    # a key that is an expression's value has no exact bounds
    ("side_without_exact_stats", "k bigint, v bigint", "(1, 1), (2, 2)",
     "(1, 1), (9, 2)", "t.k = u.v + u.k - u.v"),
])
def test_join_key_ranges_absent(case, ddl, rows_t, rows_u, on):
    """Only exact INTEGER bounds on both sides make a range: a float, a
    varchar or a two-limb decimal key, or a side whose bounds are not a
    guarantee, leaves the criterion out — and the join answers as it
    did, at 64 bits."""
    r = _two_tables(ddl, ddl, rows_t, rows_u)
    sql = f"select count(*) from t join u on {on}"
    for j in _find(r.plan_sql(sql), P.Join):
        assert j.key_ranges is None, (case, j.key_ranges)
    assert r.execute(sql).rows == [(1,)]


def test_multi_column_join_key_stays_64_bits():
    """Each criterion of a two-column join may have its range; the
    combined key is a hash of both, so its width is 64 whatever they
    are (``LocalExecutor._join_key_width``)."""
    from trino_tpu.exec.local import LocalExecutor

    r = _two_tables(
        "a bigint, b bigint", "a bigint, b bigint",
        "(1, 1), (2, 2), (3, 3)", "(1, 1), (2, 5), (3, 3)",
    )
    sql = "select count(*) from t join u on t.a = u.a and t.b = u.b"
    (join,) = _find(r.plan_sql(sql), P.Join)
    assert len(join.key_ranges) == 2
    ex = r.executor
    left, right = (ex._compact(ex.execute(s)) for s in join.sources)
    assert LocalExecutor._join_key_width(
        join.key_ranges, join.criteria, left, right) == (0, 64)
    one = join.criteria[:1]
    assert LocalExecutor._join_key_width(
        join.key_ranges, one, left, right) == (1, 2)
    assert r.execute(sql).rows == [(2,)]


def _sqlite_rows(tables: dict, sql: str):
    import sqlite3

    conn = sqlite3.connect(":memory:")
    for name, (cols, rows) in tables.items():
        conn.execute(f"create table {name} ({', '.join(cols)})")
        conn.executemany(
            f"insert into {name} values ({', '.join('?' * len(cols))})", rows
        )
    return sorted(conn.execute(sql).fetchall(), key=repr)


def _values(rows):
    return ", ".join(
        "(" + ", ".join("null" if v is None else str(v) for v in row) + ")"
        for row in rows
    )


_BASE = 10**15


@pytest.mark.parametrize(
    "kind", ["join", "left join", "right join", "full join"])
@pytest.mark.parametrize("keys", [
    "narrow_window_at_1e15", "negative_keys", "null_keys",
])
def test_range_ranked_join_is_exact(keys, kind):
    """A memory-table join whose keys the plan proves a range of —
    a window of a few values at 10^15, keys below zero, keys that are
    NULL on both sides — ranks at a handful of bits and answers exactly
    what sqlite answers, as INNER, LEFT, RIGHT and FULL join (an outer
    join's unmatched rows are rows of an input, inside the input's
    range; the RIGHT join runs as the LEFT join of its sides exchanged,
    under the range the plan gave the pair as written)."""
    rng = np.random.default_rng(len(keys) * 7 + len(kind))
    lo = {"narrow_window_at_1e15": _BASE, "negative_keys": -40,
          "null_keys": 5}[keys]
    t_rows = [(int(lo + rng.integers(0, 30)), i) for i in range(60)]
    u_rows = [(int(lo + 10 + rng.integers(0, 35)), 100 + i) for i in range(45)]
    if keys == "null_keys":
        t_rows[::7] = [(None, v) for _, v in t_rows[::7]]
        u_rows[::5] = [(None, v) for _, v in u_rows[::5]]
    r = _two_tables("k bigint, v bigint", "k bigint, w bigint",
                    _values(t_rows), _values(u_rows))
    sql = f"select t.k, t.v, u.k, u.w from t {kind} u on t.k = u.k"
    (join,) = _find(r.plan_sql(sql), P.Join)
    (lo_hi,) = join.key_ranges.values()
    live = [k for k, _ in t_rows + u_rows if k is not None]
    assert lo_hi == (min(live), max(live))
    assert (lo_hi[1] - lo_hi[0]).bit_length() <= 6
    res = r.execute(sql)
    built = {k[2:4] for k in r.executor._jit_cache if k[0] == "joinA"}
    assert built == {(lo_hi[0], (lo_hi[1] - lo_hi[0]).bit_length())}
    want = _sqlite_rows(
        {"t": (["k", "v"], t_rows), "u": (["k", "w"], u_rows)}, sql)
    assert sorted(res.rows, key=repr) == want


def test_semi_join_ranked_at_its_range_is_exact():
    rng = np.random.default_rng(46)
    t_rows = [(int(_BASE + rng.integers(0, 50)), i) for i in range(80)]
    u_rows = [(int(_BASE + 20 + rng.integers(0, 50)), i) for i in range(30)]
    r = _two_tables("k bigint, v bigint", "k bigint, w bigint",
                    _values(t_rows), _values(u_rows))
    sql = "select k, v from t where k in (select k from u where w < 25)"
    (semi,) = _find(r.plan_sql(sql), P.SemiJoin)
    assert semi.key_ranges
    want = _sqlite_rows(
        {"t": (["k", "v"], t_rows), "u": (["k", "w"], u_rows)}, sql)
    assert sorted(r.execute(sql).rows, key=repr) == want


def test_insert_that_widens_a_join_key_range_builds_another_program():
    """The range is part of the join program's cache key: after an
    INSERT that widens it the next statement plans another range,
    builds another program and answers exactly — the old program, cut
    to the old width, is not asked."""
    t_rows = [(i % 8, i) for i in range(40)]
    u_rows = [(i % 8, 100 + i) for i in range(16)]
    r = _two_tables("k bigint, v bigint", "k bigint, w bigint",
                    _values(t_rows), _values(u_rows))
    sql = "select t.k, t.v, u.w from t join u on t.k = u.k"

    def check():
        (join,) = _find(r.plan_sql(sql), P.Join)
        want = _sqlite_rows(
            {"t": (["k", "v"], t_rows), "u": (["k", "w"], u_rows)}, sql)
        assert sorted(r.execute(sql).rows, key=repr) == want
        return next(iter(join.key_ranges.values()))

    def join_programs():
        return {k[2:4] for k in r.executor._jit_cache if k[0] == "joinA"}

    assert check() == (0, 7)
    assert join_programs() == {(0, 3)}
    # 8 + 2**40 has the low bits of the live key 8 % 8 == 0
    far = [(8 + 2**40, 1000), (-3, 1001)]
    t_rows += far
    u_rows += [(k, 2000 + i) for i, (k, _) in enumerate(far)]
    r.execute(f"insert into t values {_values(far)}")
    r.execute(f"insert into u values {_values(u_rows[-2:])}")
    assert check() == (-3, 8 + 2**40)
    assert join_programs() == {(0, 3), (-3, 41)}


# ---- a scan's columns are described on first lookup (ISSUE 48) -------------

from trino_tpu.connectors.tpch.connector import TpchConnector  # noqa: E402
from trino_tpu.connectors.tpch.queries import QUERIES  # noqa: E402
from trino_tpu.plan import serde, stats as stats_mod  # noqa: E402


def _plan_text(runner, sql):
    """The optimized, annotated plan as the fleet would ship it (every
    annotation ``plan/serde.py`` carries) beside its EXPLAIN tree."""
    import json

    plan = runner.plan_sql(sql)
    return (json.dumps(serde.plan_to_json(plan), sort_keys=True, default=str),
            P.plan_tree_str(plan))


@pytest.mark.parametrize("q", sorted(QUERIES))
def test_lazy_scan_statistics_plan_what_eager_ones_plan(q, monkeypatch):
    """An estimate describes a scan's column when a symbol over it is
    first looked up. Described all at once where the scan is first met —
    what the planner did until ISSUE 48 — every one of the 22 TPC-H
    statements plans byte for byte the same tree with the same
    annotations."""
    lazy = _plan_text(QueryRunner.tpch("tiny"), QUERIES[q])
    scan_stats = stats_mod._scan_stats

    def eager(node, md):
        out = scan_stats(node, md)
        for sym in list(out.symbols._pending):
            out.symbols[sym]  # a lookup describes the column
        assert not out.symbols._pending
        return out

    monkeypatch.setattr(stats_mod, "_scan_stats", eager)
    assert _plan_text(QueryRunner.tpch("tiny"), QUERIES[q]) == lazy


class _RecordingTpch(TpchConnector):
    """The tpch connector, remembering whose statistics it was asked."""

    def __init__(self):
        super().__init__()
        self.asked: list[tuple[str, str]] = []

    def column_stats(self, schema, table, column):
        self.asked.append((table, column))
        return super().column_stats(schema, table, column)


@pytest.mark.parametrize("q", ["q03", "q09", "q13", "q16", "q17", "q18"])
def test_planning_asks_statistics_of_no_column_the_plan_does_not_read(q):
    """Planning a statement asks the connector for the statistics of
    columns its optimized plan scans and of no other: before pruning a
    scan assigns every column of its table, and a generator connector
    computes a column's statistics from the whole column (the comment
    columns are the dearest). Q13 reads ``o_comment`` and Q16
    ``s_comment``; no other ``*_comment`` column may be asked about."""
    conn = _RecordingTpch()
    md = Metadata()
    md.register_catalog("tpch", conn)
    r = QueryRunner(md, Session(catalog="tpch", schema="tiny"))
    plan = r.plan_sql(QUERIES[q])
    scanned = {
        (scan.table, col)
        for scan in _find(plan, P.TableScan)
        for col in scan.assignments.values()
    }
    asked = set(conn.asked)
    assert asked, "the planner asked for no statistics at all"
    assert asked <= scanned, sorted(asked - scanned)
    allowed = {"q13": {"o_comment"}, "q16": {"s_comment"}}.get(q, set())
    comments = {c for _, c in asked if c.endswith("_comment")}
    assert comments <= allowed, comments


def test_a_pending_column_keeps_the_caps_of_the_filters_it_passed():
    """A column nobody looked up rides copies, merges and aliases
    undescribed; described at last, its ndv is capped at the smallest
    row estimate of the filters it passed — what an eager description
    at the scan would hold by then."""
    from trino_tpu.connectors.base import ColumnStats

    asked = []

    class Conn:
        def column_stats(self, schema, table, column):
            asked.append(column)
            return ColumnStats(ndv=1000.0, lo=0, hi=999, null_fraction=0.0)

    col = stats_mod._ScanColumn(Conn(), "s", "t", "c")
    syms = stats_mod._Symbols(pending={"a": (col, None), "b": (col, None)})
    syms.cap_ndv(500.0)
    other = syms.copy()
    other.cap_ndv(40.0)
    merged = stats_mod._Symbols({"z": stats_mod.SymbolStats(ndv=3.0)}).merged(
        other)
    aliased = stats_mod._Symbols()
    aliased.alias("a2", syms, "a")
    assert asked == [] and set(merged) == {"z"}
    assert merged["a"].ndv == 40.0 and merged["z"].ndv == 3.0
    assert syms["a"].ndv == 500.0 and aliased["a2"].ndv == 500.0
    assert syms.get("missing") is None
    assert asked == ["c"]  # one column, described once
    syms.cap_ndv(7.0)
    assert syms["a"].ndv == 7.0 and syms["b"].ndv == 7.0
