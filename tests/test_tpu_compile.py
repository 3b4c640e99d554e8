"""The main path's kernels compile for a TPU v5e — no chip needed.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
chip that is described, not attached (``jax.experimental.topologies``).
These are the kernels the four smoke queries (chip_smoke.py: TPC-H Q1,
Q3, Q6, Q18) dispatch, at the SF1 bucket shapes (lineitem's 6,001,215
rows pad to 6,291,456), kept to the ones that compile in a few seconds.
What they guard, besides "the compiler accepts it": every sort below is
a SINGLE-operand unstable sort — the one sort XLA:TPU compiles quickly —
and no int64 ``div`` reaches the compiler (PR 22 findings, CHANGES.md).

A compile that passes is not a chip run: nothing executes here.

The topology is described inside a module-scoped fixture — never at
import, never in conftest.py — so every xdist worker collects the same
tests and only the worker that runs this file loads libtpu. The
compilation cache is off around the compiles (an entry compiled for a
described chip cannot be read back without one, and would warn).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from trino_tpu.exec import kernels as K

LINEITEM_SF1 = 6_291_456  # shapes.bucket(6_001_215)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *specs):
    avals = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in specs
    ]
    lowered = jax.jit(fn).lower(*avals)
    return lowered, lowered.compile()


def _sorts(lowered) -> list[tuple[int, bool]]:
    """(operand count, is_stable) of every sort in the lowered module."""
    txt = lowered.as_text()
    out = []
    for m in re.finditer(r'"?stablehlo\.sort"?\(([^)]*)\)[^\n]*', txt):
        operands = [x for x in m.group(1).split(",") if x.strip()]
        out.append((len(operands), "is_stable = true" in m.group(0)))
    return out


def test_device_is_a_v5e(topo):
    assert topo.devices[0].platform == "tpu"
    assert topo.devices[0].device_kind == "TPU v5 lite"


def test_compaction_at_lineitem_sf1(one_chip, no_compile_cache):
    """``LocalExecutor._compact``'s body (``kernels.compact_rows``) on
    Q3's filtered ``lineitem`` page at SF1 — ``l_orderkey``,
    ``l_extendedprice``, ``l_discount`` (int64), ``l_shipdate`` (int32),
    6,291,456 rows into 4,194,304 (ISSUE 36): one single-operand
    unstable sort, the page read in ``gather_plan``'s gathers, no
    gather of a ``pred`` (a validity lane or the mask), and no more
    memory than the per-column body's output plus the stacked operand
    and its result."""
    n, limit = LINEITEM_SF1, 4_194_304
    cols = [jnp.int64, jnp.int64, jnp.int64, jnp.int32]

    def compact(mask, *data):
        env = {str(i): (d, None) for i, d in enumerate(data)}
        return K.compact_rows(env, mask, limit)

    lowered, compiled = _compile(
        compact, one_chip, ((n,), jnp.bool_), *(((n,), dt) for dt in cols)
    )
    assert _sorts(lowered) == [(1, False)]
    words, gathers = K.gather_plan([(dt, (), False) for dt in cols])
    assert (words, gathers) == (7, -(-7 // K.GATHER_STACK_WORDS))
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+) gather\(",
        compiled.as_text(), re.M,
    )
    assert len(results) == gathers, results
    assert not any(r.startswith("pred") for r in results), results
    ma = compiled.memory_analysis()
    page_out = limit * (3 * 8 + 4 + 1)
    padded = -(-words // 8) * 8 * 4  # the tile's sublanes hold 8 words
    assert ma.output_size_in_bytes <= page_out + (1 << 20)
    assert (
        ma.temp_size_in_bytes + ma.output_size_in_bytes
        <= page_out + (n + limit) * padded + (16 << 20)
    )


def test_group_and_sum_at_lineitem_sf1(one_chip, no_compile_cache):
    """Q1's shape: two narrow keys packed into one word, grouped by one
    sort, int64 sums by blocked prefix sums."""

    def group_sum(k1, k2, live, v):
        info = K.sort_group(
            (k1.astype(jnp.uint64), k2.astype(jnp.uint64)), (None, None),
            live, 1024, widths=(2, 2),
        )
        vs = jnp.where(live[info.perm], v[info.perm], 0)
        return K.seg_sum_ranges(vs, info), info.num_groups

    n = LINEITEM_SF1
    lowered, _ = _compile(
        group_sum, one_chip, ((n,), jnp.int32), ((n,), jnp.int32),
        ((n,), jnp.bool_), ((n,), jnp.int64),
    )
    sorts = _sorts(lowered)
    assert sorts and all(s == (1, False) for s in sorts)


def test_q18_group_by_runs_at_lineitem_sf1(one_chip, no_compile_cache):
    """Q18's inner ``group by l_orderkey`` over a page that arrives in
    key order, as ``stage.build_chain`` lowers it (``kernels.run_group``,
    ISSUE 31): ONE sort, single-operand and of uint32 words (the
    boundary rows' compaction) — no sort keyed by the group key, no
    ``_merge_rank`` — and what it reads at the run starts, the two limb
    sums' prefix sums and the key, is one walk (ISSUE 44): six 32-bit
    words in two ``[capacity, <= 4]`` uint32 gathers, no gather whose
    result is 64-bit but the one-entry reads of the two totals, none a
    row-sized column."""
    from trino_tpu import types as T
    from trino_tpu.exec import stage
    from trino_tpu.expr.ir import AggCall, InputRef
    from trino_tpu.plan import nodes as P

    n, capacity = LINEITEM_SF1, 2_097_152  # shapes.table_bucket(1.5M)
    dec = T.DecimalType(15, 2)
    node = P.Aggregate(
        outputs={"k": T.BIGINT, "s": T.DecimalType(38, 2)}, source=None,
        group_keys=["k"], key_ranges={"k": (1, 6_000_000)},
        aggregates={
            "s": AggCall("sum", (InputRef(dec, "q"),), T.DecimalType(38, 2))
        },
    )
    layout = stage.ChainLayout(
        names=["k", "q"], types={"k": T.BIGINT, "q": dec},
        dicts={"k": None, "q": None}, capacity=n, ordered_on="k",
    )
    fn, out = stage.build_chain([node], layout, {0: [capacity, capacity]})

    def step(k, q, mask):
        return fn({"k": (k, None), "q": (q, None)}, mask)

    lowered, compiled = _compile(
        step, one_chip, ((n,), jnp.int64), ((n,), jnp.int64), ((n,), jnp.bool_)
    )
    assert out.groupbys == {0: "streamed"}  # filled as the step is traced
    assert _sorts(lowered) == [(1, False)]
    txt = lowered.as_text()
    assert re.findall(
        r"^\s*\}\) : \(tensor<(\w+)>\) -> tensor<\w+>$", txt, re.M
    ) == [f"{n}xui32"]  # the sort's one operand
    gathered = re.findall(r"stablehlo\.gather.*-> tensor<([\dx]+)x(\w+)>", txt)
    assert sorted(gathered) == sorted([
        ("1", "i64"), ("1", "i64"),  # the prefix sums' totals
        (f"{capacity}x4", "ui32"), (f"{capacity}x2", "ui32"),
    ])
    assert out.start_walks == {0: 2}
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* gather\(",
        compiled.as_text(), re.M,
    )
    assert sorted(r for r in results if r[1] != "1") == [
        ("u32", f"{capacity},2"), ("u32", f"{capacity},4")], results
    ma = compiled.memory_analysis()
    # 164.0 MiB as compiled for a v5e here (PR 44): the two prefix sums
    # (48 MiB each) and the two stacks of words read at the starts (96
    # and 48 MiB where the three lone gathers read the columns in
    # place), less what the compiler overlays; the sort path's step
    # held more than 195
    assert ma.temp_size_in_bytes < 180 << 20


@pytest.mark.parametrize("key_bits", [64, 23])
def test_join_ranges_at_q3_sf1(one_chip, no_compile_cache, key_bits):
    """Q3's ``lineitem`` join at SF1 — a probe of 4,194,304 rows ranked
    in a build of 262,144 by sort (``join_search``) — reads the build
    key and the end of its run at ``lo`` in ONE gather of ``[probe, 3]``
    uint32 words (ISSUE 44) where a uint64 and an int32 gather stood,
    and every sort is still single-operand and unstable. At the 23 bits
    the plan proves of ``l_orderkey`` at SF1 (ISSUE 46) the sorts are
    two where 64 bits take four, and the key is one word of that read."""
    from functools import partial

    n, b = 4_194_304, 262_144
    assert K.join_search(b) == "sort"
    lowered, compiled = _compile(
        partial(K.join_ranges.__wrapped__, key_bits=key_bits), one_chip,
        ((b,), jnp.uint64), ((b,), jnp.bool_),
        ((n,), jnp.uint64), ((n,), jnp.bool_),
    )
    sorts = _sorts(lowered)
    assert sorts == [(1, False)] * (4 if key_bits == 64 else 2)
    words = 3 if key_bits == 64 else 2
    probe_sized = re.findall(
        rf"stablehlo\.gather.*-> tensor<({n}x[\dx]*\w+)>", lowered.as_text()
    )
    assert probe_sized == [f"{n}x{words}xui32"]
    results = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* gather\(",
        compiled.as_text(), re.M,
    )
    assert ("u32", f"{n},{words}") in results
    assert not any(dt in ("u64", "s64") and dims == str(n)
                   for dt, dims in results), results


def test_decimal_average_division(one_chip, no_compile_cache):
    """avg(decimal) ends in a 96/64 long division per group; XLA:TPU's
    own int64 div costs ~7 s of compile each and three averages in one
    program crashed the compiler."""
    from trino_tpu.exec.aggregates import _limb_div_round

    def avg3(h1, l1, h2, l2, h3, l3, cnt):
        return (
            _limb_div_round(h1, l1, cnt), _limb_div_round(h2, l2, cnt),
            _limb_div_round(h3, l3, cnt),
        )

    lowered, _ = _compile(
        avg3, one_chip, *([((1024,), jnp.int64)] * 7)
    )
    assert "stablehlo.divide" not in lowered.as_text()
    assert "stablehlo.remainder" not in lowered.as_text()


def test_search_by_merged_sort(one_chip, no_compile_cache):
    """kernels.searchsorted with many queries (expand_matches,
    sort_group's run starts): one merged single-operand sort. Kept
    small — the uint64 single-operand sort alone compiles 13-24 s at
    the SF1 buckets, which is the join programs' cost (CHANGES.md)."""

    def starts(gid_sorted):
        return K.searchsorted(
            gid_sorted, jnp.arange(24_576, dtype=jnp.int32), side="left"
        )

    lowered, _ = _compile(starts, one_chip, ((8192,), jnp.int32))
    assert _sorts(lowered) == [(1, False)]


def test_join_ranges_by_count_at_lineitem_sf5(one_chip, no_compile_cache):
    """Q18's ``lineitem`` join at SF5 (ISSUE 42): a 33,554,432-row probe
    against a build of 512 rows ranks by compare-and-count — the only
    sorts are the build's two, the probe is neither sorted nor
    scattered, and the ``[build, probe]`` compare is fused into its
    reduction: the program's temporaries stay a few words a probe row,
    nowhere near the 17 G cells of the product."""
    n, b = 33_554_432, 512
    assert K.join_search(b) == "count"
    lowered, compiled = _compile(
        K.join_ranges.__wrapped__, one_chip, ((b,), jnp.uint64),
        ((b,), jnp.bool_), ((n,), jnp.uint64), ((n,), jnp.bool_),
    )
    assert _sorts(lowered) == [(1, False)] * 2
    assert "stablehlo.scatter" not in lowered.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= n * 16


def test_q6_scan_filter_sum_at_lineitem_sf1(one_chip, no_compile_cache):
    """Q6's whole chain: compare, multiply, one masked global sum."""

    def q6(shipdate, discount, quantity, price, mask):
        keep = (
            mask & (shipdate >= 8766) & (shipdate < 9131)
            & (discount >= 5) & (discount <= 7) & (quantity < 2400)
        )
        return jnp.sum(jnp.where(keep, price * discount, 0)), K.count_true(keep)

    n = LINEITEM_SF1
    _, compiled = _compile(
        q6, one_chip, ((n,), jnp.int32), ((n,), jnp.int64),
        ((n,), jnp.int64), ((n,), jnp.int64), ((n,), jnp.bool_),
    )
    assert compiled.memory_analysis().argument_size_in_bytes > n * 8 * 3


def test_seam_exchange_on_four_chips_at_q18_sf5(topo, no_compile_cache):
    """The mesh exchange satisfied in place (``parallel.exchange.
    seam_exchange``, ISSUE 40) over Q18's partial-aggregate page at SF5
    on the described 2x2 slice — a key, a two-lane partial state with
    its validity lanes, 4,194,304 slots a shard: the compiled program
    holds the neighbour permutes and the exchange of the shards'
    scalars, no sort, gather, scatter or all-to-all of any size —
    contiguous copies only — and next to nothing beside its arguments
    and results."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from trino_tpu.parallel.exchange import seam_exchange

    n, cap, bucket = 4, 4_194_304, 128
    mesh = Mesh(np.asarray(topo.devices[:n]), ("workers",))
    rows = NamedSharding(mesh, PS("workers"))
    dtypes = [jnp.int64, jnp.int64, jnp.bool_, jnp.int64, jnp.bool_, jnp.bool_]

    def body(*ls):
        return seam_exchange(
            ls[0].astype(jnp.uint64), ls[-1], list(ls[:-1]), n, bucket,
            "workers",
        )

    prog = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(PS("workers"),) * len(dtypes),
        out_specs=([PS("workers")] * (len(dtypes) - 1), PS("workers"), PS()),
        check_vma=False,
    ))
    lowered = prog.lower(*(
        jax.ShapeDtypeStruct((n * cap,), dt, sharding=rows) for dt in dtypes
    ))
    assert not re.search(
        r"stablehlo\.(sort|gather|scatter|all_to_all)\b", lowered.as_text())
    compiled = lowered.compile()
    txt = compiled.as_text()
    # the neighbour hand-over, and the shards' scalars (which XLA:TPU
    # gathers by an all-reduce)
    assert "collective-permute-start" in txt
    assert "all-gather" in txt or "all-reduce" in txt
    assert not re.search(r" (sort|gather|scatter|all-to-all)\(", txt)
    ma = compiled.memory_analysis()
    page = cap * (8 + 8 + 1 + 8 + 1 + 1)
    assert ma.argument_size_in_bytes >= page
    assert ma.temp_size_in_bytes < page // 8


def test_q3_partial_group_by_compiles_on_four_chips_at_sf5(
        topo, no_compile_cache):
    """Q3's PARTIAL group-by as the mesh executor runs it at SF5 — three
    keys, the two 32-bit halves of a decimal product summed, 49,152 rows
    and 98,304 slots a shard, by sort — compiles for the described 2x2
    slice. With the keys read through ``gather_rows``' word view beside
    the sums' stacked walk (ISSUE 44's first cut) XLA:TPU's
    ``tpu-reduce-window-rewriter`` died of a SIGSEGV on exactly this
    program and took the served coordinator with it; under a
    permutation the keys are read as they are (``stage.
    _reads_at_first_rows``), and the sums still ride their walks."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from trino_tpu import types as T
    from trino_tpu.exec import stage
    from trino_tpu.expr.ir import AggCall, Call, InputRef, Literal
    from trino_tpu.plan import nodes as P

    shards, n, capacity = 4, 49_152, 98_304
    dec = T.DecimalType(15, 2)
    product = Call(T.DecimalType(18, 4), "multiply", (
        InputRef(dec, "price"),
        Call(T.DecimalType(18, 2), "subtract",
             (Literal(T.BIGINT, 1), InputRef(dec, "discount"))),
    ))
    node = P.Aggregate(
        outputs={"k": T.BIGINT, "d": T.DATE, "p": T.INTEGER,
                 "hi": T.BIGINT, "lo": T.BIGINT},
        source=None, group_keys=["k", "d", "p"],
        aggregates={"hi": AggCall("sum_hi32", (product,), T.BIGINT),
                    "lo": AggCall("sum_lo32", (product,), T.BIGINT)},
        step="PARTIAL", key_ranges={"k": (1, 29_999_976), "p": (0, 0)},
    )
    types = {"k": T.BIGINT, "d": T.DATE, "p": T.INTEGER,
             "price": dec, "discount": dec}
    layout = stage.ChainLayout(
        names=list(types), types=types, dicts=dict.fromkeys(types),
        capacity=n,
    )
    fn, out = stage.build_chain([node], layout, {0: [capacity, capacity]})

    def step(*cols):
        *data, mask = cols
        return fn({s: (c, None) for s, c in zip(types, data)}, mask)

    dtypes = [jnp.int64, jnp.int32, jnp.int32, jnp.int64, jnp.int64,
              jnp.bool_]
    mesh = Mesh(np.asarray(topo.devices[:shards]), ("workers",))
    rows = NamedSharding(mesh, PS("workers"))
    out_specs = jax.tree.map(
        lambda x: PS("workers") if x.ndim else PS(),
        jax.eval_shape(step, *(jax.ShapeDtypeStruct((n,), d) for d in dtypes)),
    )

    def body(*cols):
        env, mask, flags = step(*cols)
        return env, mask, jax.tree.map(
            lambda f: jax.lax.pmax(f.astype(jnp.int32), "workers"), flags)

    lowered = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(PS("workers"),) * len(dtypes),
        out_specs=out_specs, check_vma=False,
    )).lower(*(
        jax.ShapeDtypeStruct((shards * n,), d, sharding=rows) for d in dtypes
    ))
    walks = re.findall(
        rf"stablehlo\.gather.*-> tensor<{capacity}x(\d)xui32>",
        lowered.as_text())
    assert sorted(walks) == ["2", "4", "4"]  # five int64 sums, no key
    assert out.groupbys == {0: "sorted"}
    assert lowered.compile().memory_analysis().temp_size_in_bytes > 0


def test_split_slice_at_lineitem_sf1(one_chip, no_compile_cache):
    """``LocalExecutor._resident_split``'s body (``kernels.slice_rows``)
    on the second half of Q1's ``lineitem`` columns at SF1 (ISSUE 45):
    six int64 lanes, a date, a validity lane; rows 3,000,073 onward of
    6,291,456 into the split's bucket of 3,145,728. A copy of the
    range: no sort, no gather, no scatter, and no temporary beyond the
    page it makes."""
    n, start, rows, cap = LINEITEM_SF1, 3_000_073, 3_000_072, 3_145_728
    dts = [jnp.int64] * 6 + [jnp.int32]

    def split(valid, *data):
        arrays = [(d, None) for d in data[:-1]] + [(data[-1], valid)]
        return K.slice_rows(arrays, start, rows, cap)

    lowered, compiled = _compile(
        split, one_chip, ((n,), jnp.bool_), *(((n,), dt) for dt in dts)
    )
    assert _sorts(lowered) == []
    hlo = compiled.as_text()
    assert " gather(" not in hlo and " scatter(" not in hlo
    ma = compiled.memory_analysis()
    page_out = cap * (6 * 8 + 4 + 1 + 1)
    assert ma.output_size_in_bytes <= page_out + (1 << 20)
    assert ma.temp_size_in_bytes <= (1 << 20)
