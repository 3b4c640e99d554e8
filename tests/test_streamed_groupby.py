"""Group-by over input that already arrives in key order: the streamed
path of ``exec/stage.py:_aggregate_step`` (``kernels.run_group``: the
runs are the groups, in place) against the sort path on the same
inputs, the device check of the declared order and its fallback, and
the rule for which pages may carry the declaration at all.

The sort path is the reference: the same step built from a layout with
no ``ordered_on``. Both run the one lowering the executors use
(``stage.build_chain``), so what is compared is what a query gets.
"""

from dataclasses import replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import telemetry
from trino_tpu import types as T
from trino_tpu.connectors.base import TableSchema
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.engine import QueryRunner
from trino_tpu.exec import stage
from trino_tpu.expr.ir import AggCall, Call, InputRef, Literal
from trino_tpu.metadata import Metadata, Session
from trino_tpu.page import Page
from trino_tpu.plan import nodes as P

N = 512
CAP = 256
DEC = T.DecimalType(15, 2)
DEC38 = T.DecimalType(38, 2)


def _limbs(v):
    return np.stack([v >> 32, v & 0xFFFFFFFF], axis=-1)


def _columns(rng):
    """Argument columns of every kind the reducers serve:
    name -> (type, data, valid)."""
    big = rng.integers(-(1 << 62), 1 << 62, N, dtype=np.int64)
    dec = rng.integers(-(10 ** 14), 10 ** 14, N, dtype=np.int64)
    return {
        "big": (T.BIGINT, big, None),
        "small": (T.BIGINT, rng.integers(0, 9, N, dtype=np.int64), None),
        "dec": (DEC, dec, None),
        "decn": (DEC, dec[::-1].copy(), rng.random(N) < 0.7),
        "wide": (DEC38, _limbs(rng.integers(-(1 << 40), 1 << 40, N) << 22),
                 rng.random(N) < 0.9),
        "flag": (T.BOOLEAN, rng.random(N) < 0.5, rng.random(N) < 0.8),
        "dbl": (T.DOUBLE, rng.normal(size=N) * 1e6, None),
    }


def _aggregates(cols):
    r = lambda name: InputRef(cols[name][0], name)  # noqa: E731
    half = Literal(T.DOUBLE, 0.5)
    return {
        # what ``_Reducer`` serves: sums and limb sums, counts, min/max
        # scans, first-value
        "count_all": AggCall("count_all", (), T.BIGINT),
        "count": AggCall("count", (r("decn"),), T.BIGINT),
        "count_if": AggCall("count_if", (r("flag"),), T.BIGINT),
        "sum_int64": AggCall("sum", (r("big"),), T.BIGINT),
        "sum_decimal": AggCall("sum", (r("dec"),), DEC38),
        "sum_decimal_nullable": AggCall("sum", (r("decn"),), DEC38),
        "sum_decimal38_limbs": AggCall("sum", (r("wide"),), DEC38),
        "avg_decimal": AggCall("avg", (r("decn"),), DEC),
        "min_int64": AggCall("min", (r("big"),), T.BIGINT),
        "max_decimal": AggCall("max", (r("decn"),), DEC),
        "min_two_limb": AggCall("min", (r("wide"),), DEC38),
        "max_two_limb": AggCall("max", (r("wide"),), DEC38),
        "bool_and": AggCall("bool_and", (r("flag"),), T.BOOLEAN),
        "bool_or": AggCall("bool_or", (r("flag"),), T.BOOLEAN),
        "any_value": AggCall("any_value", (r("decn"),), DEC),
        "sum_filter": AggCall("sum", (r("dec"),), DEC38, filter=r("flag")),
        "count_filter": AggCall("count_all", (), T.BIGINT, filter=r("flag")),
        "sum_hi32": AggCall("sum_hi32", (r("decn"),), T.BIGINT),
        "count_final": AggCall("count_final", (r("big"),), T.BIGINT),
        "min_double": AggCall("min", (r("dbl"),), T.DOUBLE),
        # what reads the group context itself (``info.group``,
        # ``info.starts``, the sorted order)
        "count_distinct": AggCall("count", (r("small"),), T.BIGINT,
                                  distinct=True),
        "sum_distinct": AggCall("sum", (r("small"),), T.BIGINT,
                                distinct=True),
        "approx_distinct": AggCall("approx_distinct", (r("big"),), T.BIGINT),
        "approx_percentile": AggCall(
            "approx_percentile", (r("big"), half), T.BIGINT),
        "max_by": AggCall("max_by", (r("dec"), r("big")), DEC),
        "min_by": AggCall("min_by", (r("decn"), r("big")), DEC),
    }


def _run_key(rng, n_live=N, max_run=7, lo=3, ranged=True):
    """An ascending bigint key in runs of 1..max_run rows over the first
    ``n_live`` rows (zeros after them: a page's padding), with its
    exact range when ``ranged``: (type, data, valid, range)."""
    lens = rng.integers(1, max_run + 1, n_live)
    steps = rng.integers(1, 40, n_live)  # a domain past SLOT_KEY_BITS
    vals = np.repeat(lo + np.cumsum(steps) - steps[0], lens)[:n_live]
    data = np.concatenate([vals, np.zeros(N - n_live, np.int64)])
    rng_ = (lo, int(vals.max())) if ranged else None
    return (T.BIGINT, data.astype(np.int64), None, rng_)


def _run_step(key, aggs, cols, mask, ordered, cap=CAP, pre=()):
    """One grouped Aggregate on key ``k`` through ``build_chain``, from
    a layout that declares the order or not: (path, live prefix of every
    output as numpy, num_groups, overflow flag, order-check flag)."""
    kt, kd, kv, krange = key
    types = {"k": kt, **{c: t for c, (t, *_r) in cols.items()}}
    node = P.Aggregate(
        outputs={"k": kt, **{s: a.type for s, a in aggs.items()}},
        source=None, group_keys=["k"], aggregates=aggs,
        key_ranges=None if krange is None else {"k": krange},
    )
    layout = stage.ChainLayout(
        names=list(types), types=types, dicts=dict.fromkeys(types),
        capacity=len(mask), ordered_on="k" if ordered else None,
    )
    chain = list(pre) + [node]
    pos = len(pre)
    fn, out = stage.build_chain(chain, layout, {pos: [cap, cap]})
    env = {
        c: (jnp.asarray(d), None if v is None else jnp.asarray(v))
        for c, (_t, d, v, *_r) in {"k": key, **cols}.items()
    }
    env2, out_mask, flags = fn(env, jnp.asarray(mask))
    live = np.asarray(out_mask)
    g = int(live.sum())
    assert live[:g].all(), "occupied groups are a prefix"
    got = {}
    for s, (d, v) in env2.items():
        v = None if v is None else np.asarray(v)[:g]
        d = np.asarray(d)[:g]
        if v is not None:  # data under a NULL is not part of the answer
            d = np.where(v.reshape((-1,) + (1,) * (d.ndim - 1)), d, 0)
        got[s] = (d, v)
    unordered = flags.get(stage.unordered_flag(pos))
    return (out.groupbys[pos], got, g, bool(flags[pos]),
            None if unordered is None else bool(unordered))


def _both(key, aggs, cols, mask, cap=CAP, exact=True):
    """Streamed against sorted on one input: same groups, same flags,
    outputs bit for bit (or to rounding where ``exact`` is False)."""
    path, got, g, over, unordered = _run_step(key, aggs, cols, mask, True, cap)
    ref_path, ref, g_ref, over_ref, no_check = _run_step(
        key, aggs, cols, mask, False, cap)
    assert (path, ref_path) == ("streamed", "sorted")
    assert unordered is False and no_check is None
    assert (g, over) == (g_ref, over_ref)
    for s in ref:
        for a, b in zip(got[s], ref[s]):
            assert (a is None) == (b is None), s
            if a is None:
                continue
            assert a.dtype == b.dtype, s
            if exact or not np.issubdtype(a.dtype, np.floating):
                assert np.array_equal(a, b), s
            else:
                np.testing.assert_allclose(a, b, rtol=1e-12, err_msg=s)
    return got, g


_AGG_NAMES = sorted(_aggregates(_columns(np.random.default_rng(0))))


@pytest.mark.parametrize("agg", _AGG_NAMES)
def test_each_aggregate_bit_for_bit(agg):
    """Every aggregate kind over runs of 1-7 rows with a dead tail: the
    reducers gather nothing and read the same prefix sums and scans."""
    rng = np.random.default_rng(1)
    cols = _columns(rng)
    n_live = 449
    key = _run_key(rng, n_live)
    mask = np.arange(N) < n_live
    _got, g = _both(key, {agg: _aggregates(cols)[agg]}, cols, mask)
    assert g == len(set(key[1][:n_live].tolist()))


def test_double_sums_agree_closely():
    """Floating sums: the same float64 segmented scan over the same
    values in the same order — the suite's tolerance is not needed, but
    is what is promised."""
    rng = np.random.default_rng(8)
    cols = _columns(rng)
    r = InputRef(T.DOUBLE, "dbl")
    aggs = {
        "s": AggCall("sum", (r,), T.DOUBLE),
        "a": AggCall("avg", (r,), T.DOUBLE),
        "v": AggCall("var_samp", (r,), T.DOUBLE),
    }
    _both(_run_key(rng), aggs, cols, np.ones(N, bool), exact=False)


_PICK = ("count_all", "sum_decimal_nullable", "avg_decimal", "min_two_limb",
         "any_value", "sum_filter", "count_distinct", "max_by")


def _pick(cols):
    return {s: a for s, a in _aggregates(cols).items() if s in _PICK}


@pytest.mark.parametrize("shape", [
    "one_row_runs", "page_wide_run", "all_live", "one_live_row",
    "no_live_row", "full_width_key",
])
def test_run_shapes(shape):
    rng = np.random.default_rng(2)
    cols = _columns(rng)
    n_live = {"one_live_row": 1, "no_live_row": 0}.get(shape, 400)
    if shape == "all_live":
        n_live = N
    if shape == "one_row_runs":
        key = _run_key(rng, n_live, max_run=1)
    elif shape == "page_wide_run":
        data = np.full(N, 41, np.int64)
        key = (T.BIGINT, data, None, (41, 41 + 4096))
    elif shape == "full_width_key":
        # no stats: the whole 64-bit word is the key
        key = _run_key(rng, n_live, ranged=False)
    else:
        key = _run_key(rng, max(n_live, 1))
    mask = np.arange(N) < n_live
    got, g = _both(key, _pick(cols), cols, mask, cap=N)
    assert g == len(set(key[1][:n_live].tolist()))
    if shape == "one_row_runs":
        assert g == n_live and got["count_all"][0].tolist() == [1] * g
    if shape == "page_wide_run":
        assert got["count_all"][0].tolist() == [n_live]


def test_nullable_key_in_word_order():
    """A nullable key packs as (value, null flag): the NULL group's word
    sorts just above the least value's, and rows laid out so stream."""
    rng = np.random.default_rng(3)
    cols = _columns(rng)
    t, data, _v, (lo, hi) = _run_key(rng, 400, lo=5)
    data = data.copy()
    first_other = int(np.argmax(data[:400] != lo))
    valid = np.ones(N, bool)
    # NULLs right after the rows of the least key: words 0.., 1, 2..
    nulls = slice(first_other, first_other + 6)
    valid[nulls] = False
    data[nulls] = rng.integers(0, 99, 6)  # whatever lies under a NULL
    data[first_other + 6:400] += 1
    key = (t, data, valid, (lo, hi + 1))
    mask = np.arange(N) < 400
    got, g = _both(key, _pick(cols), cols, mask)
    assert got["k"][1].tolist().count(False) == 1  # one NULL group
    assert got["k"][1][1] == np.False_  # second in key-word order


def test_nullable_key_too_wide_for_one_word_sorts():
    """64 value bits and a null flag do not pack into one word: such a
    key is sorted, whatever was declared."""
    rng = np.random.default_rng(4)
    cols = _columns(rng)
    t, data, _v, _r = _run_key(rng, 400, ranged=False)
    key = (t, data, np.ones(N, bool), None)
    aggs = {"count_all": _aggregates(cols)["count_all"]}
    mask = np.arange(N) < 400
    path, got, g, _o, unordered = _run_step(key, aggs, cols, mask, True)
    assert (path, unordered) == ("sorted", None)
    assert got["count_all"][0].sum() == 400 and g == len(set(data[:400]))


def test_overflow_flag_matches():
    """More groups than the planned capacity: the same overflow flag as
    the sort path's (the caller retries larger), and no order fault."""
    rng = np.random.default_rng(7)
    cols = _columns(rng)
    aggs = {"count_all": _aggregates(cols)["count_all"]}
    key = _run_key(rng, N, max_run=2)
    mask = np.ones(N, bool)
    path, _got, _g, over, unordered = _run_step(
        key, aggs, cols, mask, True, cap=64)
    assert (path, over, unordered) == ("streamed", True, False)
    assert _run_step(key, aggs, cols, mask, False, cap=64)[3] is True


# ---- declared, then verified --------------------------------------------------


@pytest.mark.parametrize("fault", [
    "descends", "dead_row_inside_a_run", "dead_head", "key_returns",
])
def test_kernel_reports_what_it_cannot_group(fault):
    """Live rows that are no prefix, or whose key words descend: the
    order check trips, whatever else the step returned."""
    rng = np.random.default_rng(9)
    cols = _columns(rng)
    t, data, v, r = _run_key(rng, 400)
    data = data.copy()
    mask = np.arange(N) < 400
    if fault == "descends":
        data[200:400] = data[0:200]
    elif fault == "key_returns":
        # no descent at the first border, one at the second
        data[:5] = [3, 3, 5, 5, 3]
    elif fault == "dead_row_inside_a_run":
        mask[123] = False
    elif fault == "dead_head":
        mask[0] = False
    aggs = {"count_all": _aggregates(cols)["count_all"]}
    path, _got, _g, _o, unordered = _run_step(
        (t, data, v, r), aggs, cols, mask, True)
    assert (path, unordered) == ("streamed", True)


def test_key_returns_is_a_descent():
    """3 3 5 5 3 descends at its last border (each key in one run is
    what is checked, not just equal neighbours); 3 3 5 5 6 does not."""
    rng = np.random.default_rng(10)
    cols = _columns(rng)
    data = np.zeros(N, np.int64)
    data[:5] = [3, 3, 5, 5, 3]
    aggs = {"count_all": _aggregates(cols)["count_all"]}
    key = (T.BIGINT, data, None, (0, 4095))
    assert _run_step(key, aggs, cols, np.arange(N) < 5, True)[4] is True
    data[:5] = [3, 3, 5, 5, 6]
    assert _run_step(key, aggs, cols, np.arange(N) < 5, True)[4] is False


class _Declared(MemoryConnector):
    """A memory catalog that promises ``k`` ascends, true or not."""

    def sorted_by(self, schema, table):
        return "k"


def _memory_runner(connector, k, v):
    md = Metadata()
    md.register_catalog("mem", connector)
    connector.create_table("default", "t", TableSchema(
        "t", [("k", T.BIGINT), ("v", T.BIGINT), ("d", T.DOUBLE)]))
    connector.insert("default", "t", {
        "k": (k, None), "v": (v, None), "d": (v * 0.25, None)})
    return QueryRunner(md, Session(catalog="mem", schema="default"))


def _groupbys(result):
    return [
        path for sp in result.trace.root.walk()
        for path in sp.attrs.get("groupbys", ())
    ]


_SQL = ("select k, sum(v), count(*), min(d), max(v) from t group by k"
        " having count(*) < 1000")


@pytest.mark.parametrize("defer", ["synced_read", "deferred_flags"])
def test_violated_declaration_returns_the_sort_paths_rows(defer):
    """A declared order the data breaks: the same rows as with nothing
    declared, by one rerun on the sort path, counted, and remembered —
    the next run of the statement goes straight to the sort. Both reads
    of the flags: the synced one inside a query (Q18's shape: the
    group-by feeds a semi join) and the deferred one that rides with
    the result of a statement's last chain."""
    rng = np.random.default_rng(11)
    k = np.repeat(np.arange(600, dtype=np.int64), rng.integers(1, 6, 600))
    k[1000:1100] = k[100:200]  # keys come back: two runs a key
    v = rng.integers(-1000, 1000, len(k)).astype(np.int64)
    sql = _SQL if defer == "deferred_flags" else (
        "select k, v from t where k in"
        " (select k from t group by k having count(*) = 1)")
    ref = _memory_runner(MemoryConnector(), k, v).execute(sql)
    assert _groupbys(ref) == ["sorted"]
    runner = _memory_runner(_Declared(), k, v)
    before = telemetry.STREAMED_GROUPBY_FALLBACKS.total()
    res = runner.execute(sql)
    assert sorted(res.rows) == sorted(ref.rows)
    assert _groupbys(res)[-1] == "sorted" and "streamed" in _groupbys(res)
    assert telemetry.STREAMED_GROUPBY_FALLBACKS.total() == before + 1
    again = runner.execute(sql)
    assert sorted(again.rows) == sorted(ref.rows)
    assert _groupbys(again) == ["sorted"]
    assert telemetry.STREAMED_GROUPBY_FALLBACKS.total() == before + 1


def test_kept_declaration_streams_through_the_engine():
    """The same catalog with rows that do ascend: streamed, the sort
    path's rows, no fallback; a Project between scan and Aggregate
    passes the order on, a Filter does not (dead rows inside runs take
    the sort path)."""
    rng = np.random.default_rng(12)
    k = np.repeat(np.arange(600, dtype=np.int64) * 3, rng.integers(1, 6, 600))
    v = rng.integers(-1000, 1000, len(k)).astype(np.int64)
    ref = _memory_runner(MemoryConnector(), k, v)
    runner = _memory_runner(_Declared(), k, v)
    before = telemetry.STREAMED_GROUPBY_FALLBACKS.total()
    for sql, paths in [
        (_SQL, ["streamed"]),
        ("select k, sum(v + 1) from t group by k", ["streamed"]),
        ("select k, sum(v) from t where v > 0 group by k", ["sorted"]),
        ("select k, v, count(*) from t group by k, v", ["sorted"]),
        ("select v, count(*) from t group by v", ["sorted"]),
    ]:
        res = runner.execute(sql)
        assert _groupbys(res) == paths, sql
        assert sorted(res.rows) == sorted(ref.execute(sql).rows), sql
    assert telemetry.STREAMED_GROUPBY_FALLBACKS.total() == before


def test_overflow_retry_on_the_streamed_path():
    """More groups than the first table holds: the streamed program's
    overflow flag grows the table and the chain runs again, streamed,
    as the sort path's retry does."""
    rng = np.random.default_rng(13)
    n = 4096
    k = np.repeat(np.arange(n, dtype=np.int64), 2)[:n]  # 2048 groups
    page = Page.from_arrays({
        "k": (T.BIGINT, k),
        "v": (T.BIGINT, rng.integers(0, 100, n).astype(np.int64)),
    })
    node = P.Aggregate(
        outputs={"k": T.BIGINT, "s": T.BIGINT}, source=None,
        group_keys=["k"],
        aggregates={"s": AggCall("sum", (InputRef(T.BIGINT, "v"),), T.BIGINT)},
    )
    runner = QueryRunner.tpch("tiny")
    ex = runner.executor
    assert stage.plan_capacities([node], page.capacity)[0][0] == 1024
    known = set(dict.keys(ex._jit_cache))
    out = ex._run_chain([node], dc_replace(page, ordered_on="k"))
    ref = ex._run_chain([node], page)
    assert out.num_rows() == ref.num_rows() == 2048
    assert out.to_pylist() == ref.to_pylist()
    assert _chain_paths(ex, known) == [
        # (declared order, capacity, path): the streamed run overflowed
        # once, and the sorted run after it found the capacity learned
        (False, 8192, "sorted"),
        (True, 1024, "streamed"), (True, 8192, "streamed"),
    ]


def _chain_paths(ex, known):
    """(page declared an order, table capacity, path) of every chain
    program with one grouped Aggregate the executor built since
    ``known`` (a snapshot of its jit cache's keys)."""
    return sorted(
        (key[-1] is not None, key[2][0][1], layout.groupbys[0])
        for key, (_fn, layout) in (
            (k, v) for k, v in dict.items(ex._jit_cache)
            if k[0] == "chain" and k not in known
        )
    )


# ---- pages that must never carry the property ----------------------------------

_BY_ORDER = "select l_orderkey, sum(l_quantity) from lineitem group by l_orderkey"


@pytest.fixture(scope="module")
def tiny():
    return QueryRunner.tpch("tiny")


@pytest.fixture(scope="module")
def by_order_rows(tiny):
    res = tiny.execute(_BY_ORDER)
    assert _groupbys(res) == ["streamed"]
    return sorted(res.rows)


def test_whole_table_resident_scan_carries_it(tiny, by_order_rows):
    scan = next(
        n for n in _walk(tiny.plan_sql(_BY_ORDER)) if isinstance(n, P.TableScan))
    page = tiny.executor.execute(scan)
    sym = next(s for s, c in scan.assignments.items() if c == "l_orderkey")
    assert page.ordered_on == sym and page.packed
    assert len(by_order_rows) == 15000


def _walk(node):
    yield node
    for s in node.sources:
        yield from _walk(s)


def test_a_split_does_not(tiny):
    """A split is a row range: a run cut at its border is two partial
    groups. Its page says nothing, and its aggregate sorts."""
    plan = tiny.plan_sql(_BY_ORDER)
    agg = next(n for n in _walk(plan) if isinstance(n, P.Aggregate))
    scan = agg.sources[0]
    assert isinstance(scan, P.TableScan)
    split = dc_replace(scan, split=(1000, 20000))
    page = tiny.executor.execute(split)
    assert page.ordered_on is None
    known = set(dict.keys(tiny.executor._jit_cache))
    out = tiny.executor._run_chain([agg], page)
    assert [(o, p) for o, _cap, p in _chain_paths(tiny.executor, known)] \
        == [(False, "sorted")]
    assert out.num_rows() > 0


def test_a_chunk_does_not(by_order_rows):
    """``max_chunk_rows``: partial aggregates over row slices, then a
    FINAL over their concatenation — every one of them sorted."""
    r = QueryRunner.tpch("tiny")
    r.session.properties["max_chunk_rows"] = 16384
    res = r.execute(_BY_ORDER)
    paths = _groupbys(res)
    assert len(paths) > 2 and set(paths) == {"sorted"}
    assert sorted(res.rows) == by_order_rows


def test_the_output_of_a_join_does_not(tiny):
    res = tiny.execute(
        "select l_orderkey, count(*) from lineitem, orders"
        " where l_orderkey = o_orderkey group by l_orderkey")
    assert _groupbys(res) == ["sorted"] and len(res.rows) == 15000


def test_a_sort_or_a_limit_under_the_aggregate_drops_it():
    """Inside one chain: a Project renames the ordered column and keeps
    it, a Filter, a Limit or a Sort before the Aggregate drops it."""
    rng = np.random.default_rng(14)
    cols = _columns(rng)
    key = _run_key(rng, 400)
    mask = np.arange(N) < 400
    aggs = {"count_all": _aggregates(cols)["count_all"]}
    k = InputRef(T.BIGINT, "k")
    keep = Call(T.BOOLEAN, "ge", (k, Literal(T.BIGINT, 0)))
    passing = P.Project(
        outputs={"k": T.BIGINT}, source=None, assignments={"k": k})
    assert _run_step(key, aggs, {}, mask, True, pre=[passing])[0] == "streamed"
    for pre in (
        P.Filter(outputs={}, source=None, predicate=keep),
        P.Limit(outputs={}, source=None, count=100),
        P.Sort(outputs={}, source=None,
               keys=[P.SortKey("k", True, None)]),
        P.Project(outputs={"k": T.BIGINT}, source=None, assignments={
            "k": Call(T.BIGINT, "add", (k, Literal(T.BIGINT, 1)))}),
    ):
        path, got, *_ = _run_step(key, aggs, {}, mask, True, pre=[pre])
        assert path == "sorted", type(pre).__name__
        assert got["count_all"][0].sum() == (
            100 if isinstance(pre, P.Limit) else 400)


def test_a_mesh_shard_does_too(by_order_rows):
    """The mesh executor's sharded chains (virtual CPU mesh): a shard
    is a row range of the table in scan order, and since ISSUE 40 the
    ``ShardedPage`` says so (``tests/test_mesh_ordered_groupby.py``)."""
    from trino_tpu.exec.mesh import make_mesh

    r = QueryRunner.tpch("tiny", mesh=make_mesh(4))
    assert r.executor.n_shards == 4
    res = r.execute(_BY_ORDER)
    assert sorted(res.rows) == by_order_rows
    paths = [
        path for key, hit in r.executor._mesh_jit_cache.items()
        if key[0] == "mesh-chain" for path in hit[1].groupbys.values()
    ]
    assert paths and set(paths) == {"streamed"}
    assert set(_groupbys(res)) == {"streamed"}


def test_q18_tiny_streams_its_inner_group_by(tiny):
    res = tiny.execute(QUERIES["q18"])
    assert _groupbys(res) == ["streamed", "sorted"]


# ---- one walk at the groups' first rows (ISSUE 44) ---------------------------
#
# The step reads every integer sum of its aggregates, and in place its
# key, at one index vector (``kernels.start_walk``). Streamed and sorted
# now share that code, so the reference here is neither: exact integer
# arithmetic over the rows of each group, in Python.


def _wrap64(x: int) -> int:
    return (x + (1 << 63)) % (1 << 64) - (1 << 63)


def _limb_pair(x: int) -> list[int]:
    return [x >> 32, x & 0xFFFFFFFF]


def _avg_half_away(s: int, c: int) -> int:
    q = (2 * abs(s) + c) // (2 * c)
    return q if s >= 0 else -q


def _walk_columns(rng):
    """The columns of ``_columns`` plus the two BIGINT limb states a
    distributed decimal sum's FINAL step re-aggregates (one validity
    lane for both, as the PARTIAL step emits them)."""
    cols = _columns(rng)
    state_valid = rng.random(N) < 0.85
    cols["hi_state"] = (
        T.BIGINT, rng.integers(-(1 << 31), 1 << 31, N, dtype=np.int64),
        state_valid)
    cols["lo_state"] = (
        T.BIGINT, rng.integers(0, 1 << 40, N, dtype=np.int64), state_valid)
    return cols


def _walk_aggregates(cols):
    r = lambda name: InputRef(cols[name][0], name)  # noqa: E731
    return {
        "sum_limbs": AggCall("sum", (r("dec"),), DEC38),
        "sum_states": AggCall("sum", (r("wide"),), DEC38),
        "sum_final": AggCall(
            "decimal_sum_final", (r("hi_state"), r("lo_state")), DEC38),
        "sum_bigint": AggCall("sum", (r("big"),), T.BIGINT),
        "count_filter": AggCall("count_all", (), T.BIGINT, filter=r("flag")),
        "count_nullable": AggCall("count", (r("decn"),), T.BIGINT),
        "avg": AggCall("avg", (r("decn"),), DEC),
    }


def _exact(cols, rows):
    """name -> (value, is NULL) of ``_walk_aggregates`` over ``rows``."""
    col = lambda c: [  # noqa: E731
        (cols[c][1][i], cols[c][2] is None or bool(cols[c][2][i]))
        for i in rows
    ]
    dec = [int(d) for d, _ in col("dec")]
    wide = [(int(d[0]) << 32) + int(d[1]) for d, ok in col("wide") if ok]
    hi = [int(d) for d, ok in col("hi_state") if ok]
    lo = [int(d) for d, ok in col("lo_state") if ok]
    decn = [int(d) for d, ok in col("decn") if ok]
    return {
        "sum_limbs": (_limb_pair(sum(dec)), False),
        "sum_states": (_limb_pair(sum(wide)), not wide),
        "sum_final": (_limb_pair((sum(hi) << 32) + sum(lo)), not hi),
        "sum_bigint": (_wrap64(sum(int(d) for d, _ in col("big"))), False),
        "count_filter": (sum(bool(d) and ok for d, ok in col("flag")), False),
        "count_nullable": (len(decn), False),
        "avg": (_avg_half_away(sum(decn), max(len(decn), 1)), not decn),
    }


def _check_exact(got, g, cols, groups, names=None):
    """``got``'s first ``g`` slots against ``groups`` (key -> rows, in
    output order)."""
    assert g == len(groups)
    for slot, (key, rows) in enumerate(groups.items()):
        if key is None:
            assert not got["k"][1][slot]
        else:
            assert got["k"][0][slot] == key
            assert got["k"][1] is None or got["k"][1][slot]
        for s, (want, null) in _exact(cols, rows).items():
            if names is not None and s not in names:
                continue
            d, v = got[s]
            assert (v is None and not null) or v[slot] == (not null), (s, key)
            if not null:
                assert np.asarray(d[slot]).tolist() == want, (s, key, slot)


def _groups_of(keys, n_live):
    groups: dict = {}
    for i in range(n_live):
        groups.setdefault(keys[i], []).append(i)
    return groups


@pytest.mark.parametrize("ordered", [True, False], ids=["streamed", "sorted"])
@pytest.mark.parametrize("shape", [
    "dead_tail", "all_live", "capacity_above_rows", "no_live_row",
    "one_row_runs", "nullable_key",
])
def test_start_walk_against_exact_arithmetic(shape, ordered):
    """Seven integer-summing aggregates in one step (nine sums and four
    or five counts, each an int64 prefix sum: seven stacks of words)
    over runs whose first starts at row 0 and whose last ends at the last live row — at the
    page's last row where every row is live, the one position an
    exclusive prefix sum cannot be read at."""
    rng = np.random.default_rng(44)
    cols = _walk_columns(rng)
    n_live = {"all_live": N, "no_live_row": 0}.get(shape, 449)
    # (a key of one row would have a domain narrow enough to address)
    key = _run_key(rng, n_live or 400, max_run=1 if shape == "one_row_runs"
                   else 7)
    keys = key[1].tolist()
    if shape == "nullable_key":
        t, data, _v, (lo, hi) = key
        first_other = int(np.argmax(data != lo))
        valid = np.ones(N, bool)
        valid[first_other:first_other + 5] = False  # word order: 0.., 1, 2..
        data = data.copy()
        data[first_other + 5:n_live] += 1
        key = (t, data, valid, (lo, hi + 1))
        keys = [int(d) if ok else None for d, ok in zip(data, valid)]
    cap = 2 * N if shape == "capacity_above_rows" else N
    mask = np.arange(N) < n_live
    path, got, g, over, _u = _run_step(
        key, _walk_aggregates(cols), cols, mask, ordered, cap)
    assert path == ("streamed" if ordered else "sorted") and not over
    _check_exact(got, g, cols, _groups_of(keys, n_live))


@pytest.mark.parametrize("ordered", [True, False], ids=["streamed", "sorted"])
def test_start_walk_under_overflow(ordered):
    """More groups than slots: the flag is set, and every slot but the
    last still holds its own group's exact sums (the caller retries
    larger and reads none of them)."""
    rng = np.random.default_rng(45)
    cols = _walk_columns(rng)
    key = _run_key(rng, N, max_run=2)
    cap = 64
    path, got, g, over, _u = _run_step(
        key, _walk_aggregates(cols), cols, np.ones(N, bool), ordered, cap)
    assert over and g == cap
    groups = dict(list(_groups_of(key[1].tolist(), N).items())[:cap - 1])
    got = {s: (d[:cap - 1], None if v is None else v[:cap - 1])
           for s, (d, v) in got.items()}
    _check_exact(got, cap - 1, cols, groups)


def test_start_walk_two_keys_in_one_word():
    """Two narrow keys pack into one sort word (the sort path; a page
    is ordered on one column): sums stack among themselves, the keys
    are read at ``perm[starts]`` — a second vector — as they are, a
    column a gather."""
    rng = np.random.default_rng(46)
    cols = _walk_columns(rng)
    cols["k2"] = (T.BIGINT, rng.integers(0, 5, N, dtype=np.int64), None)
    k1 = rng.integers(10, 200, N, dtype=np.int64)  # 8 + 3 bits: no slots
    n_live = 470
    aggs = _walk_aggregates(cols)
    node = P.Aggregate(
        outputs={"k": T.BIGINT, "k2": T.BIGINT,
                 **{s: a.type for s, a in aggs.items()}},
        source=None, group_keys=["k", "k2"], aggregates=aggs,
        key_ranges={"k": (10, 199), "k2": (0, 4)},
    )
    types = {"k": T.BIGINT, **{c: t for c, (t, *_r) in cols.items()}}
    layout = stage.ChainLayout(
        names=list(types), types=types, dicts=dict.fromkeys(types),
        capacity=N,
    )
    fn, out = stage.build_chain([node], layout, {0: [N, N]})
    env = {"k": (jnp.asarray(k1), None), **{
        c: (jnp.asarray(d), None if v is None else jnp.asarray(v))
        for c, (_t, d, v) in cols.items()}}
    env2, out_mask, flags = fn(env, jnp.asarray(np.arange(N) < n_live))
    assert out.groupbys == {0: "sorted"} and not bool(flags[0])
    g = int(np.asarray(out_mask).sum())
    pairs = sorted(set(zip(k1[:n_live].tolist(),
                           cols["k2"][1][:n_live].tolist())))
    assert g == len(pairs)
    assert list(zip(np.asarray(env2["k"][0])[:g].tolist(),
                    np.asarray(env2["k2"][0])[:g].tolist())) == pairs
    by_pair = _groups_of(
        list(zip(k1.tolist(), cols["k2"][1].tolist())), n_live)
    for slot, pair in enumerate(pairs):
        for s, (want, null) in _exact(cols, by_pair[pair]).items():
            d, v = env2[s]
            assert (v is None and not null) or bool(v[slot]) == (not null), s
            if not null:
                assert np.asarray(d[slot]).tolist() == want, (s, pair)
    # nine int64 sums and five counts: 28 words, seven stacks; the two
    # keys a gather each
    assert out.start_walks == {0: 28 // 4 + 2}


def test_the_step_counts_its_start_walks():
    """``ChainLayout.start_walks``: what ``kernels.gather_plan`` gives
    for the columns the step reads at its groups' first rows — Q18's
    inner step two int64 limb sums and its int64 key, six words in two
    gathers where three int64 gathers stood; the sort path counts its
    live rows by a prefix sum too (two walks), and its key, at another
    vector, is a gather of its own, as it is."""
    from trino_tpu.exec import kernels as K

    rng = np.random.default_rng(47)
    cols = _walk_columns(rng)
    aggs = {"s": _walk_aggregates(cols)["sum_limbs"]}
    key = _run_key(rng, 449)
    mask = np.arange(N) < 449
    for ordered, want in ((True, 2), (False, 2 + 1)):
        kt, kd, kv, krange = key
        node = P.Aggregate(
            outputs={"k": kt, "s": DEC38}, source=None, group_keys=["k"],
            aggregates=aggs, key_ranges={"k": krange},
        )
        layout = stage.ChainLayout(
            names=["k", "dec"], types={"k": kt, "dec": DEC},
            dicts={"k": None, "dec": None}, capacity=N,
            ordered_on="k" if ordered else None,
        )
        fn, out = stage.build_chain([node], layout, {0: [CAP, CAP]})
        jaxpr = jax.make_jaxpr(fn)(
            {"k": (jnp.asarray(kd), None),
             "dec": (jnp.asarray(cols["dec"][1]), None)}, jnp.asarray(mask))
        assert out.start_walks == {0: want}
        sized = [
            e.outvars[0].aval for e in _equations(jaxpr.jaxpr)
            if e.primitive.name == "gather"
            and e.outvars[0].aval.shape[:1] == (CAP,)
        ]
        # (the sort path also reads ``perm`` at its starts: int32)
        wide = [a for a in sized if a.dtype.itemsize == 8]
        assert [(a.shape, a.dtype) for a in wide] == (
            [] if ordered else [((CAP,), jnp.int64)]), sized  # its key
        walks = [a for a in sized if a.dtype == jnp.uint32 and a.ndim == 2]
        assert len(walks) + len(wide) == want
        assert all(a.shape[1] <= K.GATHER_STACK_WORDS for a in walks)
    i64 = (jnp.int64, (), False)
    assert K.gather_plan([i64] * 3)[1] == 2


def _equations(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)
