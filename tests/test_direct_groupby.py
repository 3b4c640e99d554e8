"""Group-by over a small key domain: the slot-addressed path of
``exec/stage.py:_aggregate_step`` against the sort path on the same
inputs, bit for bit, and the rule that selects between them.

The sort path is the reference: ``kernels.SLOT_KEY_BITS`` patched to -1
makes every step take it. Both run the one lowering the executors use
(``stage.build_chain``), so what is compared is what a query gets.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.engine import QueryRunner
from trino_tpu.exec import kernels as K
from trino_tpu.exec import stage
from trino_tpu.expr.ir import AggCall, InputRef
from trino_tpu.page import StringDictionary
from trino_tpu.plan import nodes as P

N = 512
CAP = 96
DEC = T.DecimalType(15, 2)
DEC38 = T.DecimalType(38, 2)
_B = K.SLOT_KEY_BITS


def _limbs(v):
    return np.stack([v >> 32, v & 0xFFFFFFFF], axis=-1)


def _columns(rng):
    """Argument columns of every kind the dense reducer serves:
    name -> (type, data, valid)."""
    big = rng.integers(-(1 << 62), 1 << 62, N, dtype=np.int64)
    dec = rng.integers(-(10 ** 14), 10 ** 14, N, dtype=np.int64)
    return {
        "big": (T.BIGINT, big, None),
        "dec": (DEC, dec, None),
        "decn": (DEC, dec[::-1].copy(), rng.random(N) < 0.7),
        "wide": (DEC38, _limbs(rng.integers(-(1 << 40), 1 << 40, N) << 22),
                 rng.random(N) < 0.9),
        "flag": (T.BOOLEAN, rng.random(N) < 0.5, rng.random(N) < 0.8),
        "dbl": (T.DOUBLE, rng.normal(size=N) * 1e6, None),
    }


def _ref(cols, name):
    return InputRef(cols[name][0], name)


def _aggregates(cols):
    r = lambda name: _ref(cols, name)  # noqa: E731
    return {
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
    }


def _run_step(keys, aggs, cols, mask, monkeypatch, sorted_path, cap=CAP):
    """One grouped Aggregate through ``build_chain``: (path, live
    prefix of every output as numpy, num_groups)."""
    monkeypatch.setattr(
        K, "SLOT_KEY_BITS", -1 if sorted_path else _B, raising=True
    )
    types = {k: t for k, (t, *_r) in keys.items()}
    types.update({c: t for c, (t, *_r) in cols.items()})
    dicts = {k: d for k, (_t, _d, _v, d, _r) in keys.items()}
    node = P.Aggregate(
        outputs={**{k: types[k] for k in keys},
                 **{s: a.type for s, a in aggs.items()}},
        source=None, group_keys=list(keys), aggregates=aggs,
        key_ranges={k: r for k, (*_x, r) in keys.items() if r is not None},
    )
    layout = stage.ChainLayout(
        names=list(types), types=types,
        dicts={n: dicts.get(n) for n in types}, capacity=len(mask),
    )
    fn, out = stage.build_chain([node], layout, {0: [cap, cap]})
    env = {
        k: (jnp.asarray(d), None if v is None else jnp.asarray(v))
        for k, (_t, d, v, *_r) in {**keys, **cols}.items()
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
    return out.groupbys[0], got, g, bool(flags[0])


def _both(keys, aggs, cols, mask, monkeypatch, cap=CAP, expect="direct"):
    path, got, g, over = _run_step(keys, aggs, cols, mask, monkeypatch, False, cap)
    ref_path, ref, g_ref, over_ref = _run_step(
        keys, aggs, cols, mask, monkeypatch, True, cap)
    assert (path, ref_path) == (expect, "sorted")
    assert (g, over) == (g_ref, over_ref)
    for s in ref:
        for a, b in zip(got[s], ref[s]):
            assert (a is None) == (b is None), s
            if a is not None:
                assert a.dtype == b.dtype and np.array_equal(a, b), s
    return got, g


def _range_key(rng, bits, nullable=False, holes=False):
    """An integer key over an exact range of ``bits`` bits, off zero."""
    lo = -7
    vals = rng.integers(0, 1 << bits, N, dtype=np.int64)
    if holes:
        vals = vals & ~np.int64(1)  # odd offsets: slots no row has
    valid = (rng.random(N) < 0.85) if nullable else None
    return (T.BIGINT, vals + lo, valid, None, (lo, lo + (1 << bits) - 1))


@pytest.mark.parametrize("agg", sorted(_aggregates(_columns(
    np.random.default_rng(0)))))
def test_each_aggregate_bit_for_bit(agg, monkeypatch):
    """Every supported aggregate, nullable two-key grouping (a
    dictionary column and a boolean), dead rows, a NULL-key group."""
    rng = np.random.default_rng(1)
    cols = _columns(rng)
    d, codes = StringDictionary.from_strings(
        [("A", "N", "R")[i] for i in rng.integers(0, 3, N)])
    keys = {
        "k1": (T.VARCHAR, codes, rng.random(N) < 0.9, d, None),
        "k2": (T.BOOLEAN, rng.random(N) < 0.5, None, None, None),
    }
    mask = rng.random(N) < 0.8
    aggs = {agg: _aggregates(cols)[agg]}
    _got, g = _both(keys, aggs, cols, mask, monkeypatch)
    assert g == 8  # (3 values + NULL) x 2


@pytest.mark.parametrize("nullable", [False, True], ids=["notnull", "nullable"])
@pytest.mark.parametrize("bits", sorted({1, 4, 6, _B}))
def test_slot_counts(bits, nullable, monkeypatch):
    """2, 16 and 64 slots and the most ``SLOT_KEY_BITS`` admits; with a
    nullable key the value bits give way to the null flag."""
    rng = np.random.default_rng(bits)
    cols = _columns(rng)
    kbits = bits - 1 if nullable and bits > 1 else bits
    key = _range_key(rng, kbits, nullable=nullable and bits > 1)
    mask = rng.random(N) < 0.9
    pick = ("count_all", "sum_decimal_nullable", "avg_decimal",
            "min_two_limb", "any_value", "sum_filter")
    aggs = {s: a for s, a in _aggregates(cols).items() if s in pick}
    _got, g = _both({"k": key}, aggs, cols, mask, monkeypatch, cap=384)
    live_keys = np.where(True if key[2] is None else key[2], key[1], 1 << 20)
    assert g == len(set(live_keys[mask].tolist()))


def test_slot_without_live_row_and_order(monkeypatch):
    """Slots no live row holds leave no group; ids follow key order."""
    rng = np.random.default_rng(5)
    cols = _columns(rng)
    key = _range_key(rng, 4, holes=True)
    mask = rng.random(N) < 0.9
    aggs = {"count_all": _aggregates(cols)["count_all"]}
    got, g = _both({"k": key}, aggs, cols, mask, monkeypatch)
    assert g == 8
    assert got["k"][0].tolist() == sorted(set(key[1][mask].tolist()))
    assert got["count_all"][0].sum() == mask.sum()


@pytest.mark.parametrize("rows", ["all_filtered", "one_live"])
def test_no_or_one_live_row(rows, monkeypatch):
    rng = np.random.default_rng(6)
    cols = _columns(rng)
    mask = np.zeros(N, dtype=bool)
    if rows == "one_live":
        mask[17] = True
    aggs = _aggregates(cols)
    aggs.pop("min_double")
    _got, g = _both({"k": _range_key(rng, 3, nullable=True)}, aggs, cols,
                    mask, monkeypatch)
    assert g == int(mask.sum())


def test_overflow_flag_matches(monkeypatch):
    """More groups than the planned capacity: the same overflow flag
    (the caller retries larger), whichever path."""
    rng = np.random.default_rng(7)
    cols = _columns(rng)
    aggs = {"count_all": _aggregates(cols)["count_all"]}
    mask = np.ones(N, dtype=bool)
    path, _got, _g, over = _run_step(
        {"k": _range_key(rng, 5)}, aggs, cols, mask, monkeypatch, False, cap=16)
    assert (path, over) == ("direct", True)
    assert _run_step({"k": _range_key(rng, 5)}, aggs, cols, mask,
                     monkeypatch, True, cap=16)[3] is True


def test_double_sums_agree_closely(monkeypatch):
    """Floating sums accumulate in float64 on both paths, in another
    order: equal to rounding, not bit for bit."""
    rng = np.random.default_rng(8)
    cols = _columns(rng)
    r = _ref(cols, "dbl")
    aggs = {
        "s": AggCall("sum", (r,), T.DOUBLE),
        "a": AggCall("avg", (r,), T.DOUBLE),
        "v": AggCall("var_samp", (r,), T.DOUBLE),
    }
    mask = rng.random(N) < 0.9
    key = {"k": _range_key(rng, 3)}
    _p, got, g, _o = _run_step(key, aggs, cols, mask, monkeypatch, False)
    _p, ref, g_ref, _o = _run_step(key, aggs, cols, mask, monkeypatch, True)
    assert g == g_ref == 8
    for s in aggs:
        np.testing.assert_allclose(got[s][0], ref[s][0], rtol=1e-12)


# ---- the selection rule, through the engine ---------------------------------

#: (case, statement over tpch tiny, paths of its grouped aggregates)
SELECTION = [
    ("two_flags", "select l_returnflag, l_linestatus, sum(l_quantity), "
     "avg(l_discount), count(*) from lineitem group by 1, 2", ["direct"]),
    ("eight_bits", "select l_returnflag, l_shipmode, l_shipinstruct, "
     "min(l_shipdate), count(*) from lineitem group by 1, 2, 3", ["direct"]),
    ("nine_bits_just_over", "select l_returnflag, l_shipmode, l_shipinstruct, "
     "l_quantity > 25, count(*) from lineitem group by 1, 2, 3, 4",
     ["sorted"]),
    ("wide_key", "select l_partkey, count(*) from lineitem group by 1",
     ["sorted"]),
    ("hash_pool_key", "select o_comment, count(*) from orders group by 1",
     ["sorted"]),
    ("distinct", "select l_returnflag, count(distinct l_suppkey) "
     "from lineitem group by 1", ["sorted"]),
    ("approx_percentile", "select l_returnflag, "
     "approx_percentile(l_quantity, 0.5) from lineitem group by 1",
     ["sorted"]),
    ("max_by", "select l_returnflag, max_by(l_orderkey, l_extendedprice) "
     "from lineitem group by 1", ["sorted"]),
    ("all_rows_filtered", "select l_returnflag, sum(l_quantity), count(*) "
     "from lineitem where l_quantity < 0 group by 1", ["direct"]),
]


def _groupbys(result):
    return [
        path for sp in result.trace.root.walk()
        for path in sp.attrs.get("groupbys", ())
    ]


@pytest.fixture(scope="module")
def sorted_rows():
    """Each case's rows from a runner whose every program was traced
    with the sort path."""
    mp = pytest.MonkeyPatch()
    r = QueryRunner.tpch("tiny")
    rows = {}
    try:
        mp.setattr(K, "SLOT_KEY_BITS", -1)
        for case, sql, _paths in SELECTION:
            res = r.execute(sql)
            assert set(_groupbys(res)) <= {"sorted"}
            rows[case] = res.rows
    finally:
        mp.undo()
    return rows


@pytest.fixture(scope="module")
def runner():
    return QueryRunner.tpch("tiny")


@pytest.mark.parametrize("case,sql,paths", SELECTION,
                         ids=[c for c, *_ in SELECTION])
def test_selection_rule(case, sql, paths, runner, sorted_rows):
    assert _B == 8, "the cases' key widths are chosen around 8 bits"
    res = runner.execute(sql)
    assert _groupbys(res) == paths
    assert sorted(res.rows, key=repr) == sorted(sorted_rows[case], key=repr)
    # a warm dispatch reports the path too (kept beside the program)
    assert _groupbys(runner.execute(sql)) == paths
