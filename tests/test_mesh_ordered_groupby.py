"""Mesh group-by on the key the shards are ranged and ordered on
(ISSUE 40): the scan's declared order on the ``ShardedPage``, the
streamed partial and final aggregates per shard, and the hash exchange
between them satisfied where the rows lie (``exchange_in_place``) —
on virtual CPU devices, at ``tiny`` and over a small in-memory table
whose shard boundaries cut a key's run by construction."""

from __future__ import annotations

import re
from dataclasses import replace
from decimal import Decimal

import jax
import numpy as np
import pytest

from trino_tpu import telemetry, types as T
from trino_tpu.connectors.base import TableSchema
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.engine import QueryRunner
from trino_tpu.exec import stage
from trino_tpu.exec.mesh import MeshExecutor, ShardedPage
from trino_tpu.expr.ir import InputRef
from trino_tpu.metadata import Metadata, Session
from trino_tpu.parallel.core import make_mesh
from trino_tpu.plan import nodes as P

Q18_INNER = (
    "select l_orderkey, sum(l_quantity) from lineitem group by l_orderkey"
    " having sum(l_quantity) > 200"
)


class _Declared(MemoryConnector):
    """A memory catalog that promises ``k`` ascends, true or not."""

    def sorted_by(self, schema, table):
        return "k"


#: 40 rows, runs of four starting one row early: with four shards of
#: ten rows (and two of twenty) every boundary cuts a key's run. Keys a
#: thousand apart: the connector's exact range must not fit a slot table
N = 40
KEYS = (np.arange(N, dtype=np.int64) + 1) // 4 * 1000


BIG = Decimal("91000000000000000.25")  # four of them overflow an int64 sum


def _values(k):
    """(k, v, d, m) rows: v and d change sign, m needs the second limb."""
    n = len(k)
    v = (np.arange(n, dtype=np.int64) * 7919) % 1000 - 300
    return [
        (int(k[i]), int(v[i]), float(v[i]) * 0.25, BIG + int(v[i]))
        for i in range(n)
    ]


def _runner(k, mesh=None, connector=None, session=None):
    md = Metadata()
    md.register_catalog("mem", connector or _Declared())
    session = session or Session(catalog="mem", schema="default")
    runner = QueryRunner(md, session, mesh=mesh)
    runner.execute(
        "create table t (k bigint, v bigint, d double, m decimal(18,2))")
    runner.execute("insert into t values " + ", ".join(
        f"({a}, {b}, {c!r}, {d})" for a, b, c, d in _values(k)))
    return runner


def _totals(res) -> dict:
    return telemetry.span_totals(res.trace.root)


def _exchange_spans(res) -> list[dict]:
    return [
        sp.attrs for sp in res.trace.root.walk() if sp.name == "mesh-exchange"
    ]


def _programs(res) -> list[str]:
    return [
        sp.attrs["program"] for sp in res.trace.root.walk()
        if sp.name == "dispatch"
    ]


def _walk(node):
    yield node
    for s in node.sources:
        yield from _walk(s)


def _scan(runner, sql="select k, v from t") -> ShardedPage:
    node = next(
        n for n in _walk(runner.plan_sql(sql)) if isinstance(n, P.TableScan)
    )
    return runner.executor.execute_dist(node)


def _shard_rows(sp: ShardedPage, name: str) -> list[list]:
    data = np.asarray(sp.column(name).data).reshape(sp.n_shards, -1)
    mask = np.asarray(sp.mask).reshape(sp.n_shards, -1)
    return [list(d[m]) for d, m in zip(data, mask)]


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(4)


@pytest.fixture(scope="module")
def tiny_mesh(mesh4):
    return QueryRunner.tpch("tiny", mesh=mesh4)


@pytest.fixture(scope="module")
def tiny_local():
    return QueryRunner.tpch("tiny")


# ---- (a), (b): Q18 and its sub-query, rows and counters ---------------------


@pytest.mark.parametrize("shards", [4, 2])
def test_every_boundary_cuts_a_run(shards):
    per = -(-N // shards)
    for b in range(per, N, per):
        assert KEYS[b - 1] == KEYS[b]
    sp = _scan(_runner(KEYS, make_mesh(shards)))
    assert sp.ordered_on is not None and sp.names[0].startswith("k")
    rows = _shard_rows(sp, sp.ordered_on)
    # placement is the configuration's: shard i holds rows [i*per, (i+1)*per)
    assert rows == [list(KEYS[i * per:(i + 1) * per]) for i in range(shards)]


@pytest.mark.parametrize("shards", [4, 2])
def test_sub_query_over_cut_runs_equals_local(shards):
    sql = "select k, sum(v), count(*) from t group by k having sum(v) > -5000"
    res = _runner(KEYS, make_mesh(shards)).execute(sql)
    assert sorted(res.rows) == sorted(_runner(KEYS).execute(sql).rows)
    totals = _totals(res)
    # partial and final both by runs, the exchange between them in place
    assert totals["streamed_groupbys"] == 2
    assert totals.get("sorted_groupbys", 0) == 0
    assert totals["mesh_exchanges"] == totals["mesh_exchanges_in_place"] == 1
    (span,) = _exchange_spans(res)
    # what crosses is one PARTIAL row a boundary
    assert span["in_place"] and span["live_rows"] == shards - 1
    assert 0 < span["live_bytes"] <= span["buffer_bytes"]
    assert "mesh_exchange_in_place" in _programs(res)
    assert "mesh_exchange" not in _programs(res)


def test_q18_inner_at_tiny(tiny_mesh, tiny_local):
    res = tiny_mesh.execute(Q18_INNER)
    assert sorted(res.rows) == sorted(tiny_local.execute(Q18_INNER).rows)
    totals = _totals(res)
    assert totals["streamed_groupbys"] == 2
    assert totals["mesh_exchanges_in_place"] == 1
    (span,) = _exchange_spans(res)
    assert span["live_rows"] <= tiny_mesh.executor.n_shards - 1


def test_q18_whole_at_tiny(tiny_mesh, tiny_local):
    res = tiny_mesh.execute(QUERIES["q18"])
    assert res.rows == tiny_local.execute(QUERIES["q18"]).rows
    totals = _totals(res)
    assert totals["streamed_groupbys"] == 2
    assert totals["mesh_exchanges_in_place"] == 1
    assert totals["mesh_exchanges"] >= 2  # the outer group-by's still moves rows
    in_place = [s for s in _exchange_spans(res) if s.get("in_place")]
    assert len(in_place) == 1 and in_place[0]["live_rows"] <= 3


def test_group_by_o_orderkey_takes_the_path_too(tiny_mesh, tiny_local):
    sql = "select o_orderkey, count(*) from orders group by o_orderkey"
    res = tiny_mesh.execute(sql)
    assert sorted(res.rows) == sorted(tiny_local.execute(sql).rows)
    assert _totals(res)["mesh_exchanges_in_place"] == 1


# ---- (c): every partial / final pair crosses a boundary ---------------------


@pytest.mark.parametrize("agg", [
    "sum(v)", "count(*)", "count(d)", "min(v)", "max(d)", "avg(v)", "avg(d)",
    "sum(m)",
])
def test_each_aggregate_across_a_boundary(agg, mesh4):
    sql = f"select k, {agg} from t group by k"
    res = _runner(KEYS, mesh4).execute(sql)
    assert sorted(res.rows) == sorted(_runner(KEYS).execute(sql).rows)
    assert _totals(res)["mesh_exchanges_in_place"] == 1
    assert _totals(res)["streamed_groupbys"] == 2
    if agg == "sum(m)":  # the sum of a cut run needs both limbs
        assert max(r[1] for r in res.rows) * 100 > 2 ** 63


# ---- (d): a declaration the data breaks -------------------------------------


def test_false_declaration_sorts_and_is_remembered(mesh4):
    k = KEYS.copy()
    k[[3, 17]] = k[[17, 3]]  # two descents, on two shards
    sql = "select k, sum(v), count(*) from t group by k"
    runner = _runner(k, mesh4)
    expected = sorted(_runner(k).execute(sql).rows)  # falls back once itself
    before = telemetry.STREAMED_GROUPBY_FALLBACKS.value()
    res = runner.execute(sql)
    assert sorted(res.rows) == expected
    totals = _totals(res)
    # one wasted streamed attempt, then the parent's path
    assert totals["streamed_groupbys"] == 1 and totals["sorted_groupbys"] == 2
    assert totals.get("mesh_exchanges_in_place", 0) == 0
    assert totals["mesh_exchanges"] == 1
    assert "mesh_exchange" in _programs(res)
    assert telemetry.STREAMED_GROUPBY_FALLBACKS.value() == before + 1
    again = runner.execute(sql)
    assert sorted(again.rows) == sorted(res.rows)
    assert _totals(again).get("streamed_groupbys", 0) == 0
    assert _totals(again)["sorted_groupbys"] == 2
    assert telemetry.STREAMED_GROUPBY_FALLBACKS.value() == before + 1


# ---- (e): what the in-place exchange hands back to the hash exchange --------


def _rows(sp: ShardedPage) -> list[tuple]:
    return sorted(zip(*(sum(_shard_rows(sp, n), []) for n in sp.names)))


def _assert_exchanged(before: ShardedPage, after: ShardedPage):
    """The exchange's contract: rows conserved, a key on one shard."""
    assert _rows(before) == _rows(after)
    shards = [set(r) for r in _shard_rows(after, before.ordered_on)]
    for i, a in enumerate(shards):
        for b in shards[i + 1:]:
            assert not (a & b)


def _exchange(runner, sp):
    """(page, the one mesh-exchange span's attrs) of ``exchange_in_place``."""
    tracer = telemetry.Tracer("q")
    with tracer.span("execute", "execution") as root:
        telemetry.set_active_span(root)
        try:
            out = runner.executor.exchange_in_place(sp)
        finally:
            telemetry.set_active_span(None)
    (span,) = [s.attrs for s in root.walk() if s.name == "mesh-exchange"]
    return out, span


def test_in_place_moves_only_the_cut_runs(mesh4):
    runner = _runner(KEYS, mesh4)
    sp = _scan(runner)
    out, span = _exchange(runner, sp)
    _assert_exchanged(sp, out)
    assert span["in_place"] and out.ordered_on == sp.ordered_on
    # the leading runs of shards 1-3: rows 10, 20-22, 30
    assert span["live_rows"] == 1 + 3 + 1
    before, after = _shard_rows(sp, sp.ordered_on), _shard_rows(out, sp.ordered_on)
    assert [len(r) for r in before] == [10, 10, 10, 10]
    assert [len(r) for r in after] == [11, 12, 8, 9]
    # still an ascending prefix on every shard, shard after shard
    flat = sum(after, [])
    assert flat == sorted(flat)
    mask = np.asarray(out.mask).reshape(4, -1)
    assert all(m[:len(r)].all() and not m[len(r):].any()
               for m, r in zip(mask, after))


@pytest.mark.parametrize("fault", [
    "three_shards", "run_over_bucket", "no_free_slot", "descent_at_a_seam",
    "descent_inside_a_shard", "nullable_key",
])
def test_failed_preconditions_take_the_hash_exchange(fault, mesh4):
    k = KEYS.copy()
    n_rows = N
    if fault == "three_shards":
        k[9:21] = k[9]  # shard 1 is one run, shards 0 and 2 hold its ends
    elif fault == "descent_at_a_seam":
        k[20:30] -= 5000  # shard 2 starts below shard 1's last row
    elif fault == "descent_inside_a_shard":
        k[[12, 16]] = k[[16, 12]]
    elif fault == "no_free_slot":
        # 64 rows: four shards of 16 in a capacity of 16, seams cut
        n_rows = 64
        k = (np.arange(64, dtype=np.int64) + 1) // 4 * 1000
    runner = _runner(k[:n_rows], mesh4)
    sp = _scan(runner)
    if fault == "run_over_bucket":
        runner.executor.IN_PLACE_BUCKET = 2  # shard 2 leads with three rows
    if fault == "no_free_slot":
        assert sp.shard_capacity == 16 == len(_shard_rows(sp, sp.names[0])[0])
    if fault == "nullable_key":
        col = sp.column(sp.ordered_on)
        sp.columns[0] = replace(
            col, valid=jax.device_put(
                np.ones(col.data.shape, np.bool_), col.data.sharding))
    out, span = _exchange(runner, sp)
    _assert_exchanged(sp, out)
    assert "in_place" not in span and out.ordered_on is None
    assert span["live_rows"] == n_rows  # the general exchange counts them all


def test_fallback_through_sql_is_the_right_answer(mesh4):
    """A key on three shards, met by the plan's own exchange: PARTIAL
    leaves one row of it on each."""
    k = KEYS.copy()
    k[9:21] = k[9]
    sql = "select k, sum(v), count(*), avg(d) from t group by k"
    res = _runner(k, mesh4).execute(sql)
    assert sorted(res.rows) == sorted(_runner(k).execute(sql).rows)
    totals = _totals(res)
    assert totals.get("mesh_exchanges_in_place", 0) == 0
    assert totals["mesh_exchanges"] == 1
    # the partial streamed; the final's page came from the hash exchange
    assert totals["streamed_groupbys"] == 1 and totals["sorted_groupbys"] == 1
    assert _programs(res).count("mesh_exchange_in_place") == 1
    assert "mesh_exchange" in _programs(res)


# ---- (f): conservation, asserted by the engine itself -----------------------


def test_coverage_check_passes_in_place(mesh4):
    session = Session(
        catalog="mem", schema="default",
        properties={"check_exchange_coverage": True,
                    "exchange_partition_counters": True})
    runner = _runner(KEYS, mesh4, session=session)
    sql = "select k, sum(v) from t group by k"
    res = runner.execute(sql)
    assert sorted(res.rows) == sorted(_runner(KEYS).execute(sql).rows)
    assert _totals(res)["mesh_exchanges_in_place"] == 1
    sites = [sp.attrs.get("site") for sp in res.trace.root.walk()
             if sp.name == "host_sync"]
    assert "mesh_exchange_coverage" in sites
    # the skew counters read the in-place exchange's own counts
    ((edge, hist),) = runner.executor.exchange_stats["partition_rows"].items()
    assert edge.startswith("mesh-hash(k") and sum(hist.values()) == 11 + 3


def test_coverage_check_catches_a_lossy_in_place_exchange(mesh4, monkeypatch):
    from trino_tpu.plan.validate import ExchangeCoverageError

    session = Session(
        catalog="mem", schema="default",
        properties={"check_exchange_coverage": True})
    runner = _runner(KEYS, mesh4, session=session)
    sp = _scan(runner)
    real = MeshExecutor._run

    def lossy(self, prog, miss, *args, **kw):
        out = real(self, prog, miss, *args, **kw)
        if prog.__name__ == "mesh_exchange_in_place":
            leaves, live, stat = out
            out = leaves, live.at[0].set(False), stat
        return out

    monkeypatch.setattr(MeshExecutor, "_run", lossy)
    with pytest.raises(ExchangeCoverageError):
        runner.executor.exchange_in_place(sp)


# ---- (g): who carries the order, and who does not ---------------------------


def test_only_the_whole_table_scan_declares(tiny_mesh):
    ex = tiny_mesh.executor

    def page_of(sql, kind):
        node = next(n for n in _walk(tiny_mesh.plan_sql(sql))
                    if isinstance(n, kind))
        return ex.execute_dist(node)

    scan = page_of("select l_orderkey, l_quantity from lineitem", P.TableScan)
    assert scan.ordered_on is not None
    assert scan.ordered_on.startswith("l_orderkey")
    # a Project that passes the column through keeps it, a Filter clears it
    kept = ex._run_chain_sharded(
        [P.Project(
            outputs={"x": T.BIGINT}, source=None, assignments={
                "x": InputRef(T.BIGINT, scan.ordered_on)})], scan)
    assert kept.ordered_on == "x"
    filtered = page_of(
        "select l_orderkey from lineitem where l_quantity > 10", P.Filter)
    assert filtered.ordered_on is None
    joined = page_of(
        "select l_orderkey, o_custkey from lineitem, orders"
        " where l_orderkey = o_orderkey", P.Join)
    assert joined.ordered_on is None
    assert ex.scatter(ex.gather(scan)).ordered_on is None
    assert ex.hash_exchange(scan, [scan.ordered_on]).ordered_on is None
    assert ex._concat_sharded(scan, scan).ordered_on is None
    # a fleet split-bound scan covers a row range: scattered, no order
    split = next(n for n in _walk(tiny_mesh.plan_sql(
        "select l_orderkey from lineitem")) if isinstance(n, P.TableScan))
    split = replace(split, split=(0, 1000))
    assert ex.execute_dist(split).ordered_on is None


def test_a_filter_under_the_aggregate_sorts(tiny_mesh, tiny_local):
    sql = ("select l_orderkey, count(*) from lineitem where l_quantity > 10"
           " group by l_orderkey")
    res = tiny_mesh.execute(sql)
    assert sorted(res.rows) == sorted(tiny_local.execute(sql).rows)
    totals = _totals(res)
    assert totals.get("streamed_groupbys", 0) == 0
    assert totals.get("mesh_exchanges_in_place", 0) == 0
    assert "mesh_exchange" in _programs(res)


#: what the parent dispatched for the three templates the mechanism
#: bypasses, at ``tiny`` on four devices (ab98970, the same statements)
PARENT_PROGRAMS = {
    "q01": ["mesh_chain_Filter_Aggregate", "mesh_exchange_dest",
            "mesh_exchange", "mesh_chain_Aggregate_Project",
            "mesh_range_bits", "mesh_range_dest", "mesh_exchange",
            "mesh_chain_Sort"],
    "q03": ["mesh_chain_Filter", "mesh_chain_Filter", "mesh_chain_Filter",
            "mesh_join_count", "mesh_join_expand", "mesh_join_count",
            "mesh_join_expand", "mesh_chain_Aggregate", "mesh_exchange_dest",
            "mesh_exchange", "mesh_chain_Aggregate_Project_TopN",
            "chain_TopN"],
    "q06": ["mesh_chain_Filter_Aggregate", "chain_Aggregate_Project"],
}


@pytest.mark.parametrize("q", sorted(PARENT_PROGRAMS))
def test_bypassed_templates_dispatch_the_parents_programs(
        q, tiny_mesh, tiny_local):
    res = tiny_mesh.execute(QUERIES[q])
    assert res.rows == tiny_local.execute(QUERIES[q]).rows
    assert _programs(res) == PARENT_PROGRAMS[q]
    totals = _totals(res)
    assert totals.get("streamed_groupbys", 0) == 0
    assert totals.get("mesh_exchanges_in_place", 0) == 0


def test_a_ranged_partial_plans_its_range_of_the_keys():
    """``stage.plan_capacities``: a PARTIAL step may see every key on
    every shard, but over a page ranged on its one group key a shard
    holds a share of them, as a FINAL step's does (Q18's partial at
    SF5: 4.19 M slots a shard instead of 12.58 M)."""
    def partial(keys):
        return P.Aggregate(
            outputs={}, source=None, group_keys=keys, aggregates={},
            step="PARTIAL", est_groups=7_500_000.0)

    rows = 8_388_608
    every = stage.plan_capacities([partial(["k"])], rows, n_shards=4)
    ranged = stage.plan_capacities(
        [partial(["k"])], rows, n_shards=4, ordered_on="k")
    final = stage.plan_capacities(
        [replace(partial(["k"]), step="FINAL")], rows, n_shards=4)
    assert every[0][0] == 12_582_912 and ranged[0][0] == 4_194_304
    assert ranged == final
    # another key, two keys, one device, or a Filter first: as before
    for chain, kw in [
        ([partial(["j"])], dict(n_shards=4, ordered_on="k")),
        ([partial(["k", "j"])], dict(n_shards=4, ordered_on="k")),
        ([partial(["k"])], dict(n_shards=1, ordered_on="k")),
        ([P.Filter(outputs={}, source=None, predicate=None), partial(["k"])],
         dict(n_shards=4, ordered_on="k")),
    ]:
        caps = stage.plan_capacities(chain, rows, **kw)
        assert list(caps.values()) == [every[0]], (chain, kw)
    # a Project that renames the column carries it to the Aggregate
    renamed = [
        P.Project(outputs={}, source=None, assignments={
            "x": InputRef(T.BIGINT, "k")}),
        partial(["x"]),
    ]
    caps = stage.plan_capacities(renamed, rows, n_shards=4, ordered_on="k")
    assert caps[1][0] == 4_194_304


# ---- the program: contiguous copies only ------------------------------------


_ROW_MOVERS = re.compile(r"stablehlo\.(sort|gather|scatter|all_to_all)\b")


def test_in_place_program_holds_no_sort_gather_or_scatter(tiny_mesh):
    """The lowered program over ``tiny``'s partial page (a shard of
    thousands of rows) holds no sort, gather, scatter or all_to_all of
    any size, and nothing shaped ``[rows, shards]`` (the general
    exchange's one-hot rank): slices, concatenations, two all_gathers
    of scalars and one neighbour permute a leaf. The general
    exchange's program, read the same way, holds them — the pattern
    bites."""
    ex = tiny_mesh.executor
    tiny_mesh.execute(Q18_INNER)
    tiny_mesh.execute(
        "select l_partkey, count(*) from lineitem group by l_partkey")
    key, prog = max(
        ((k, p) for k, p in ex._mesh_jit_cache.items()
         if k[0] == "mesh-exchange-in-place"),
        key=lambda kp: kp[0][1][-1][1],  # the widest page's
    )
    avals = [jax.ShapeDtypeStruct(shape, np.dtype(dt)) for dt, shape in key[1]]
    rows = avals[-1].shape[0] // ex.n_shards
    assert rows >= 4096
    text = prog.lower(*avals).as_text()
    assert "stablehlo.dynamic_slice" in text
    assert "stablehlo.dynamic_update_slice" in text
    assert text.count("stablehlo.collective_permute") == len(avals) - 1
    assert text.count("stablehlo.all_gather") == 2
    assert _ROW_MOVERS.search(text) is None, _ROW_MOVERS.search(text)
    assert f"tensor<{rows}x{ex.n_shards}x" not in text
    key, prog = next(
        (k, p) for k, p in ex._mesh_jit_cache.items()
        if k[0] == "mesh-exchange")
    general = prog.lower(
        jax.ShapeDtypeStruct(key[1][-1][1], np.int32),
        *(jax.ShapeDtypeStruct(shape, np.dtype(dt)) for dt, shape in key[1]),
    ).as_text()
    assert {"scatter", "all_to_all"} <= set(_ROW_MOVERS.findall(general))
