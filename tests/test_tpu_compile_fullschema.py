"""The full-schema cell's programs compile for a TPU v5e at SF1's
capacities (ISSUE 48) — no chip needed: Q9's two-column join at 64
bits, Q17's and Q13's sorted group-bys, Q16's distinct count.

Marked ``slow``, and in a file of their own beside
``test_tpu_compile.py`` (whose fixtures and helpers they use), not for
their own length — 21-31 s of compile each — but for what four
all-core compiles do to the suite's timing-sensitive tests on a shared
host: with them in tier-1, ``test_chaos.py``'s fetch-fault test and
``test_sentry.py``'s end-to-end regression test each failed full runs
here that pass without them (CHANGES.md, PR 48). Run them with
``pytest tests/test_tpu_compile_fullschema.py`` (no ``-m 'not slow'``)
after any change to ``exec/kernels.py`` or the chain builder, as
``tools/aot_probe.py`` is.
"""

import re

import jax.numpy as jnp
import pytest

from trino_tpu.exec import kernels as K

from test_tpu_compile import (  # noqa: F401  (fixtures by name)
    LINEITEM_SF1, _compile, _sorts, no_compile_cache, one_chip, topo,
)

pytestmark = pytest.mark.slow

def _aggregate_step(node, types, n, capacity, nullable=()):
    """``stage.build_chain`` over one Aggregate fed ``types``' columns
    (those of ``nullable`` with a validity lane) and a mask."""
    from trino_tpu.exec import stage

    layout = stage.ChainLayout(
        names=list(types), types=types, dicts=dict.fromkeys(types),
        capacity=n,
    )
    fn, out = stage.build_chain([node], layout, {0: [capacity, capacity]})
    names = list(types)

    def step(*cols):
        data, valid, mask = (
            cols[:len(names)], cols[len(names):-1], cols[-1])
        lanes = dict(zip(nullable, valid))
        return fn({s: (c, lanes.get(s)) for s, c in zip(names, data)}, mask)

    specs = [((n,), jnp.dtype(t.np_dtype)) for t in types.values()]
    specs += [((n,), jnp.bool_)] * (len(nullable) + 1)
    return step, specs, out


def test_q9_two_column_join_at_sf1(one_chip, no_compile_cache):
    """Q9's ``partsupp`` join at SF1: ``(l_partkey, l_suppkey)`` of all
    6,291,456 ``lineitem`` rows ranked in a build of 1,048,576 on ONE
    hashed 64-bit key (``kernels.hash_columns``; no plan proves a range
    of a hash, so ``key_bits`` stays 64): ``packed_argsort``'s radix —
    the build's order and the merged rank, four sorts — every one
    single-operand and unstable, the build's key read back in one
    ``[probe, 3]`` uint32 gather."""
    n, b = LINEITEM_SF1, 1_048_576
    assert K.join_search(b) == "sort"

    def join(pp, ps, plive, bp, bs, blive):
        pk = K.hash_columns([(pp, None), (ps, None)])
        bk = K.hash_columns([(bp, None), (bs, None)])
        order, lo, cnt = K.join_ranges(bk, blive, pk, plive, key_bits=64)
        return order, lo, cnt, K.blocked_sum(cnt)

    lowered, compiled = _compile(
        join, one_chip,
        ((n,), jnp.int64), ((n,), jnp.int64), ((n,), jnp.bool_),
        ((b,), jnp.int64), ((b,), jnp.int64), ((b,), jnp.bool_),
    )
    assert _sorts(lowered) == [(1, False)] * 4
    probe_sized = re.findall(
        rf"stablehlo\.gather.*-> tensor<({n}x[\dx]*\w+)>", lowered.as_text()
    )
    assert probe_sized == [f"{n}x3xui32"]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_q17_group_by_an_unsorted_key_at_lineitem_sf1(
        one_chip, no_compile_cache):
    """Q17's decorrelated ``avg(l_quantity) ... group by l_partkey``:
    all of ``lineitem`` grouped on a key the connector declares no order
    of, so by sort (``kernels.sort_group``), the decimal average a sum
    and a count read at the groups' first rows; every sort
    single-operand and unstable."""
    from trino_tpu import types as T
    from trino_tpu.expr.ir import AggCall, InputRef
    from trino_tpu.plan import nodes as P

    n, capacity = LINEITEM_SF1, 262_144  # 200,000 parts
    dec = T.DecimalType(15, 2)
    node = P.Aggregate(
        outputs={"k": T.BIGINT, "a": dec}, source=None, group_keys=["k"],
        key_ranges={"k": (1, 200_000)},
        aggregates={"a": AggCall("avg", (InputRef(dec, "q"),), dec)},
    )
    step, specs, out = _aggregate_step(
        node, {"k": T.BIGINT, "q": dec}, n, capacity)
    lowered, _ = _compile(step, one_chip, *specs)
    assert out.groupbys == {0: "sorted"}
    sorts = _sorts(lowered)
    assert sorts and all(s == (1, False) for s in sorts)


def test_q13_count_over_an_outer_joins_nulls_at_sf1(
        one_chip, no_compile_cache):
    """Q13's inner group-by: ``count(o_orderkey)`` by ``c_custkey`` over
    the LEFT join's page — 1,500,000 matches and the customers with no
    order, whose ``o_orderkey`` is NULL (a validity lane) — 2,097,152
    rows into 262,144 groups, by sort."""
    from trino_tpu import types as T
    from trino_tpu.expr.ir import AggCall, InputRef
    from trino_tpu.plan import nodes as P

    n, capacity = 2_097_152, 262_144
    node = P.Aggregate(
        outputs={"c": T.BIGINT, "n": T.BIGINT}, source=None,
        group_keys=["c"], key_ranges={"c": (1, 150_000)},
        aggregates={"n": AggCall("count", (InputRef(T.BIGINT, "o"),),
                                 T.BIGINT)},
    )
    step, specs, out = _aggregate_step(
        node, {"c": T.BIGINT, "o": T.BIGINT}, n, capacity, nullable=("o",))
    lowered, _ = _compile(step, one_chip, *specs)
    assert out.groupbys == {0: "sorted"}
    sorts = _sorts(lowered)
    assert sorts and all(s == (1, False) for s in sorts)


def test_q16_distinct_count_at_sf1(one_chip, no_compile_cache):
    """Q16's ``count(distinct ps_suppkey)`` by (brand, type, size): the
    anti join's 131,072-row page, three narrow keys and a DISTINCT
    argument deduplicated by one more sort of (keys, argument)
    (``stage._dedupe``); every sort single-operand and unstable."""
    from trino_tpu import types as T
    from trino_tpu.expr.ir import AggCall, InputRef
    from trino_tpu.plan import nodes as P

    n, capacity = 131_072, 32_768
    types = {"b": T.INTEGER, "t": T.INTEGER, "z": T.INTEGER, "s": T.BIGINT}
    node = P.Aggregate(
        outputs={"b": T.INTEGER, "t": T.INTEGER, "z": T.INTEGER,
                 "n": T.BIGINT},
        source=None, group_keys=["b", "t", "z"],
        key_ranges={"b": (0, 24), "t": (0, 149), "z": (1, 50)},
        aggregates={"n": AggCall("count", (InputRef(T.BIGINT, "s"),),
                                 T.BIGINT, distinct=True)},
    )
    step, specs, out = _aggregate_step(node, types, n, capacity)
    lowered, _ = _compile(step, one_chip, *specs)
    assert out.groupbys == {0: "sorted"}
    sorts = _sorts(lowered)
    assert len(sorts) >= 2 and all(s == (1, False) for s in sorts)
