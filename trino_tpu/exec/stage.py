"""Pipeline compiler: a chain of row-level operators becomes ONE
jitted XLA program.

The analog of the reference's compiled pipelines: Trino JIT-compiles
query-specific operator internals per pipeline
(ExpressionCompiler/PageFunctionCompiler, MAIN/sql/gen/) and runs them
page-at-a-time through Driver's pull loop (MAIN/operator/Driver.java:367).
On TPU the batch IS the table, so the whole chain
Filter -> Project -> Aggregate -> Sort -> Limit fuses into a single
XLA computation: one device dispatch, one result, no per-operator
round trips (which dominate when the device link has latency).

Chains break at joins/exchanges (data-dependent capacities need a host
decision) — those are the stage boundaries, exactly where the
reference splits pipelines.

Aggregations inside a chain carry a static slot-table capacity; the
program returns per-aggregate overflow flags and the caller re-builds
with an 8x larger table when one trips (FlatHash rehash analog).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.exec import kernels as K
from trino_tpu.exec.aggregates import (
    StartReads,
    VARIANCE_FNS,
    compute_aggregate,
    dense_reducible,
    dev_hash64,
)
from trino_tpu.expr.ir import InputRef
from trino_tpu.expr.compiler import ColumnLayout, compile_expr
from trino_tpu.page import StringDictionary, content_hash64, pad_capacity
from trino_tpu.plan import nodes as P

__all__ = [
    "FUSABLE", "ChainLayout", "plan_capacities", "build_chain",
    "unordered_flag",
]

#: node types that fuse into one program (single-source, static shapes).
#: Exchange is a stage boundary (collective / gather), never fused.
FUSABLE = (P.Filter, P.Project, P.Aggregate, P.Sort, P.TopN, P.Limit)


@dataclass
class ChainLayout:
    """Host-side metadata flowing through the chain builder."""

    names: list[str]
    types: dict[str, T.DataType]
    dicts: dict[str, StringDictionary | None]
    capacity: int
    #: hash-coded varchar pools by symbol (data = [cap,2] hash+id)
    pools: dict = field(default_factory=dict)
    #: ARRAY-column pools by symbol (page.ArrayPool)
    arrays: dict = field(default_factory=dict)
    #: on ``build_chain``'s output layout: chain position -> "direct" |
    #: "streamed" | "sorted", the path of each grouped Aggregate of the
    #: program (filled when the program is traced; kept beside the
    #: cached program so that a warm dispatch reports it too)
    groupbys: dict = field(default_factory=dict)
    #: beside it: chain position -> the ``[capacity]``-sized device
    #: gathers that grouped Aggregate reads its keys and its integer
    #: sums in at the groups' first rows (``kernels.gather_plan``'s
    #: count of what ``_reads_at_first_rows`` read)
    start_walks: dict = field(default_factory=dict)
    #: the symbol the page's live rows ascend on (``Page.ordered_on``),
    #: while that still holds inside the chain: a Project that passes
    #: the column through renames it, every other step drops it
    ordered_on: str | None = None

    def expr_layout(self) -> ColumnLayout:
        return ColumnLayout(
            types=dict(self.types), dictionaries=dict(self.dicts),
            array_pools=dict(self.arrays),
        )


def unordered_flag(pos: int) -> int:
    """Key, in a chain's flags, of the order check of the streamed
    Aggregate at chain position ``pos`` (True: the declared order does
    not hold, group by sort instead). Overflow flags are keyed by the
    position itself, and the keys of one pytree dict must sort
    together, so this one is its complement: negative keys are order
    checks."""
    return ~pos


def _norm_opt(data, valid):
    """normalize_key, with the null flag elided (None) for columns
    that cannot be NULL — saves a sort pass + compare in sort_group."""
    bits, flag = K.normalize_key(data, valid)
    return bits, (None if valid is None else flag)


def _key_width(t: T.DataType, dictionary, value_range=None) -> int:
    """Bit width that injectively covers a key column's values — lets
    sort_group pack several keys into one u64 sort pass. An EXACT
    ``value_range`` (lo, hi) from stats narrows the width to
    bit_length(hi - lo): the caller shifts the column by lo first
    (value-range key packing)."""
    if dictionary is not None:
        return max(1, len(dictionary).bit_length())
    if isinstance(t, T.DecimalType) and t.is_long:
        raise NotImplementedError(
            "GROUP BY / DISTINCT on decimal(38) keys"
        )
    if isinstance(t, T.BooleanType):
        return 1
    dt = np.dtype(t.np_dtype)
    full = min(dt.itemsize * 8, 64)
    if value_range is not None:
        lo, hi = value_range
        return min(max(1, int(hi - lo).bit_length()), full)
    return full


def _shift_key(data, valid, value_range):
    """Shift an integer key column to its range origin so the low
    bit_length(hi-lo) bits are injective. Dead/NULL rows may wrap —
    they are excluded from grouping by liveness/null flags."""
    if value_range is None:
        return data, valid
    lo, _hi = value_range
    if lo == 0:
        return data, valid
    return data - jnp.asarray(lo, dtype=data.dtype), valid


def _bcast(data, valid, capacity):
    if jnp.ndim(data) == 0:
        data = jnp.broadcast_to(data, (capacity,))
    if valid is not None and jnp.ndim(valid) == 0:
        valid = jnp.broadcast_to(valid, (capacity,))
    return data, valid


def plan_capacities(
    chain: list[P.PlanNode], in_capacity: int, n_shards: int = 1,
    ordered_on: str | None = None,
) -> dict[int, list[int]]:
    """Initial [capacity, max_capacity] per Aggregate position.

    With stats (``est_groups`` from plan.stats.annotate) the group
    table starts at the estimated distinct count — overflow retries
    become the exception, not the warm-up path (the reference reserves
    FlatHash capacity from connector stats the same way). FINAL/SINGLE
    steps in a sharded chain see only their hash partition of the key
    space, so the estimate divides by the shard count (×1.5 margin for
    partition imbalance); PARTIAL steps may see every key on every
    shard — unless the sharded page is ranged and ordered on the one
    group key (``ordered_on``: ``ShardedPage.ordered_on``), when a
    shard holds its range of the keys and no more. Estimates being
    wrong is safe: the overflow flag still triggers the retry-larger
    loop."""
    caps: dict[int, list[int]] = {}
    cap = in_capacity
    for i, nd in enumerate(chain):
        if isinstance(nd, P.Aggregate):
            ranged = list(nd.group_keys) == [ordered_on]
            if not nd.group_keys:
                caps[i] = [1, 1]
                cap = 8
            else:
                from trino_tpu.exec import shapes

                max_cap = pad_capacity(max(2 * cap, 8))
                if nd.est_groups is not None:
                    est = nd.est_groups
                    if n_shards > 1 and (
                        ranged or nd.step in ("FINAL", "SINGLE")
                    ):
                        est = est / n_shards * 1.5
                    start = shapes.table_bucket(est, max_cap)
                else:
                    start = min(
                        shapes.bucket(
                            max(cap // 16, 1024), site="agg-table"
                        ),
                        max_cap,
                    )
                caps[i] = [start, max_cap]
                cap = start
            # what build_chain's layouts rule, as far as capacities care
            ordered_on = ordered_on if ranged else None
        elif isinstance(nd, P.Project):
            ordered_on = _passed_through(nd, ordered_on)
        else:
            ordered_on = None
            if isinstance(nd, P.TopN):
                from trino_tpu.exec import shapes

                cap = shapes.bucket(min(nd.count, cap), site="topn")
    return caps


def op_scope(i: int, nd: P.PlanNode) -> str:
    """The scope of a chain's position ``i``: ``op<i>:<NodeType>``."""
    return f"op{i}:{type(nd).__name__}"


def build_chain(chain: list[P.PlanNode], layout: ChainLayout, caps: dict[int, list[int]]):
    """Build (fn, out_layout): ``fn(env, mask) -> (env', mask', flags)``
    is pure and jittable; ``flags`` maps chain position -> overflow
    scalar for each grouped Aggregate."""
    steps = []
    groupbys: dict[int, str] = {}
    start_walks: dict[int, int] = {}
    for i, nd in enumerate(chain):
        # positional scope label: jax.named_scope stamps it into the
        # per-instruction HLO op_name metadata (fusions included), so
        # a captured device profile can attribute time to this plan
        # operator INSIDE the fused program (kernel observatory)
        scope = op_scope(i, nd)
        if isinstance(nd, P.Filter):
            steps.append((scope, _filter_step(nd, layout)))
        elif isinstance(nd, P.Project):
            step, layout = _project_step(nd, layout)
            steps.append((scope, step))
        elif isinstance(nd, P.Aggregate):
            step, layout = _aggregate_step(
                nd, layout, caps[i][0], i, groupbys, start_walks
            )
            steps.append((scope, step))
        elif isinstance(nd, (P.Sort, P.TopN)):
            step, layout = _sort_step(nd, layout)
            steps.append((scope, step))
        elif isinstance(nd, P.Limit):
            steps.append((scope, _limit_step(nd)))
        else:
            raise NotImplementedError(type(nd).__name__)
        if layout.ordered_on is not None and not isinstance(
            nd, (P.Project, P.Aggregate)
        ):
            # a Sort moves rows, a Filter or a Limit leaves dead rows
            # inside runs: those group by sort (an Aggregate's and a
            # Project's output layouts are built anew, with what holds)
            layout = dc_replace(layout, ordered_on=None)

    def fn(env, mask):
        flags = {}
        for scope, step in steps:
            with jax.named_scope(scope):
                env, mask, flags = step(env, mask, flags)
        return env, mask, flags

    return fn, dc_replace(layout, groupbys=groupbys, start_walks=start_walks)


def _passed_through(nd: P.Project, name: str | None) -> str | None:
    """The symbol under which a Project hands column ``name`` on as it
    is, or None."""
    return next(
        (
            s for s, e in nd.assignments.items()
            if isinstance(e, InputRef) and e.name == name
        ),
        None,
    )


def _filter_step(nd: P.Filter, layout: ChainLayout):
    compiled = compile_expr(nd.predicate, layout.expr_layout())

    def step(env, mask, flags):
        data, valid = compiled.fn(env)
        keep = data if valid is None else (data & valid)
        return env, mask & keep, flags

    return step


def _project_step(nd: P.Project, layout: ChainLayout):
    compiled = {
        sym: compile_expr(e, layout.expr_layout())
        for sym, e in nd.assignments.items()
    }
    cap = layout.capacity
    from trino_tpu.expr.ir import InputRef as _Ref

    out_layout = ChainLayout(
        names=list(nd.assignments),
        types={s: e.type for s, e in nd.assignments.items()},
        dicts={s: c.dictionary for s, c in compiled.items()},
        capacity=cap,
        pools={
            s: layout.pools.get(e.name)
            for s, e in nd.assignments.items()
            if isinstance(e, _Ref) and layout.pools.get(e.name) is not None
        },
        arrays={
            s: (
                compiled[s].pool
                if compiled[s].pool is not None
                else layout.arrays.get(e.name)
                if isinstance(e, _Ref) else None
            )
            for s, e in nd.assignments.items()
            if compiled[s].pool is not None
            or (isinstance(e, _Ref) and layout.arrays.get(e.name) is not None)
        },
        # rows stay where they are; the ordered column, if it is passed
        # through as it is, goes on under its new name
        ordered_on=_passed_through(nd, layout.ordered_on),
    )

    def step(env, mask, flags):
        env2 = {
            sym: _bcast(*c.fn(env), cap) for sym, c in compiled.items()
        }
        return env2, mask, flags

    return step, out_layout


def _aggregate_step(
    nd: P.Aggregate, layout: ChainLayout, capacity: int, pos: int,
    groupbys: dict[int, str], start_walks: dict[int, int],
):
    is_global = not nd.group_keys
    expr_layout = layout.expr_layout()
    agg_meta = []
    for sym, call in nd.aggregates.items():
        arg_c = [compile_expr(a, expr_layout) for a in call.args] or None
        filter_c = (
            compile_expr(call.filter, expr_layout)
            if call.filter is not None else None
        )
        agg_meta.append((sym, call, arg_c, filter_c))
    group_keys = list(nd.group_keys)
    # the input's live rows ascend on the one group key (a connector's
    # declared order, carried by the page): its runs are the groups
    in_key_order = group_keys == [layout.ordered_on]
    in_cap = layout.capacity
    out_cap = 8 if is_global else capacity

    # approx_distinct hashes VALUES, not codes: dictionary/pool columns
    # get a compile-time content-hash table (deterministic across
    # processes — fleet partial states must merge consistently), gather
    # replaces codes with hashes inside the traced step.
    hll_tables = {}
    for sym, call, arg_c, _f in agg_meta:
        if call.name not in ("approx_distinct", "approx_distinct_partial"):
            continue
        if not arg_c:
            continue
        d = arg_c[0].dictionary
        if d is not None:
            vals = d.values
            tbl = (
                content_hash64(vals) if len(vals)
                else np.zeros(1, dtype=np.uint64)
            )
            hll_tables[sym] = ("code", jnp.asarray(tbl))
        else:
            a0 = call.args[0]
            pool = (
                layout.pools.get(a0.name)
                if isinstance(a0, InputRef) else None
            )
            if pool is not None:
                tbl = (
                    content_hash64(pool.values) if len(pool.values)
                    else np.zeros(1, dtype=np.uint64)
                )
                hll_tables[sym] = ("id", jnp.asarray(tbl))

    out_layout = ChainLayout(
        names=group_keys + [sym for sym, *_ in agg_meta],
        types={
            **{s: layout.types[s] for s in group_keys},
            **{sym: call.type for sym, call, *_ in agg_meta},
        },
        dicts={
            **{s: layout.dicts[s] for s in group_keys},
            **{
                sym: (arg_c[0].dictionary if isinstance(call.type, T.VarcharType) and arg_c else None)
                for sym, call, arg_c, _ in agg_meta
            },
        },
        capacity=out_cap,
        pools={
            s: layout.pools[s]
            for s in group_keys if layout.pools.get(s) is not None
        },
        # every grouping path emits its groups in key order, live slots
        # a prefix: input ordered on the one key, output ordered on it
        ordered_on=group_keys[0] if in_key_order else None,
    )

    key_ranges = nd.key_ranges or {}
    dense_aggs = all(
        dense_reducible(call.name, call.distinct) for _s, call, *_ in agg_meta
    )

    def step(env, mask, flags):
        if is_global:
            info = None
            widths = ()
            shifted = []
            out_mask = jnp.zeros((8,), dtype=jnp.bool_).at[0].set(True)
            env2 = {}
        else:
            shifted = []
            width_list = []
            for s in group_keys:
                data, valid = env[s]
                if layout.pools.get(s) is not None:
                    # hash-coded varchar: the hash lane IS the key (the
                    # id lane is row identity, not value identity)
                    shifted.append((data[:, 0], valid))
                    width_list.append(64)
                    continue
                shifted.append(_shift_key(data, valid, key_ranges.get(s)))
                width_list.append(
                    _key_width(
                        layout.types[s], layout.dicts.get(s),
                        key_ranges.get(s),
                    )
                )
            norm = [_norm_opt(d, v) for d, v in shifted]
            widths = tuple(width_list)
            null_flags = tuple(fl for _, fl in norm)
            # a key domain of a few bits is addressed, rows already in
            # key order are grouped where they lie, anything else is
            # sorted: the choice reads only what is static under jit
            # (key widths, nullability, aggregate kinds, the page's
            # declared order), so it is part of the program
            key_bits = K.slot_key_bits(widths, null_flags)
            group_args = (
                tuple(b for b, _ in norm), null_flags, mask, capacity, widths,
            )
            if dense_aggs and key_bits <= K.SLOT_KEY_BITS:
                groupbys[pos] = "direct"
                info = K.slot_group(*group_args)
            elif in_key_order and key_bits <= 64:  # packs into one word
                groupbys[pos] = "streamed"
                # declared, then verified: the caller reruns the chain
                # by sort when the flag comes back set
                info, unordered = K.run_group(*group_args)
                flags = {**flags, unordered_flag(pos): unordered}
            else:
                groupbys[pos] = "sorted"
                info = K.sort_group(*group_args)
            flags = {**flags, pos: info.num_groups > capacity}
            out_mask = (
                jnp.arange(capacity, dtype=jnp.int32) < info.num_groups
            )
        cap_seg = 1 if is_global else capacity
        share = {"#mask": mask}  # per-step cache of sorted cols/counts
        prepared = []
        for sym, call, arg_c, filter_c in agg_meta:
            arg = None
            contrib = mask
            if arg_c is not None:
                vals = [_bcast(*c.fn(env), in_cap) for c in arg_c]
                arg = vals[0] if len(vals) == 1 else vals
            if (
                call.name in VARIANCE_FNS
                and isinstance(call.args[0].type, T.DecimalType)
            ):
                # variance of DECIMAL is computed as DOUBLE over true
                # values, not unscaled ints (reference:
                # DoubleVarianceAggregation via implicit cast)
                d, v = arg
                arg = (
                    d.astype(jnp.float64)
                    / (10.0 ** call.args[0].type.scale),
                    v,
                )
            if call.name in ("approx_distinct", "approx_distinct_partial"):
                d, v = arg
                tb = hll_tables.get(sym)
                if tb is not None:
                    kind, table = tb
                    idx = d[:, 1] if kind == "id" else d
                    lane = table[
                        jnp.clip(
                            idx.astype(jnp.int32), 0, table.shape[0] - 1
                        )
                    ]
                elif jnp.ndim(d) == 2 and isinstance(
                    call.args[0].type, T.VarcharType
                ):
                    # pool column without a reachable pool object:
                    # process-local hash lane (correct in-process)
                    lane = d[:, 0].astype(jnp.uint64)
                else:
                    lane = dev_hash64(d)
                arg = (lane, v)
            if filter_c is not None:
                fd, fv = filter_c.fn(env)
                contrib = contrib & (fd if fv is None else (fd & fv))
            if call.distinct:
                d_arg = arg
                if (
                    isinstance(call.args[0].type, T.VarcharType)
                    and jnp.ndim(arg[0]) == 2
                ):
                    # hash-coded varchar: dedupe on the hash lane
                    d_arg = (arg[0][:, 0], arg[1])
                    dwidth = 64
                else:
                    dwidth = _key_width(
                        call.args[0].type, arg_c[0].dictionary
                    )
                # shifted key pairs match the narrowed widths
                contrib = _dedupe(list(shifted), d_arg, contrib, in_cap,
                                  widths + (dwidth,))
            prepared.append((sym, call, arg, contrib))
        if not is_global:
            keys_at, walks = _reads_at_first_rows(
                {s: env[s] for s in group_keys}, prepared, info, cap_seg,
                share,
            )
            start_walks[pos] = walks
            env2 = {
                s: (d, None if v is None else v & out_mask)
                for s, (d, v) in keys_at.items()
            }
        for sym, call, arg, contrib in prepared:
            data, valid = compute_aggregate(
                call.name, call.type, arg, info, cap_seg, contrib,
                share=share,
            )
            if is_global:
                data = _pad_to(data, 8)
                valid = None if valid is None else _pad_to(valid, 8)
            env2[sym] = (data, valid)
        return env2, out_mask, flags

    return step, out_layout


def _reads_at_first_rows(keys, prepared, info, capacity, share):
    """What a grouped step reads at each group's first row, before its
    aggregates are evaluated: ``(keys', gathers)`` — the key columns
    there, and the ``[capacity]``-sized device gathers the step holds
    for them and for its integer sums (``kernels.gather_plan``'s
    count).

    A sorted-segment step (``GroupInfo``) reads its aggregates' integer
    sums at the same rows: they are learnt by a first evaluation of the
    aggregates (``aggregates.StartReads``) and left in ``share`` for
    the caller's. Grouped in place, sums and keys are one walk. Under a
    permutation the keys lie at ``perm[starts]``, another vector, and
    are read there as they are, a column a gather: beside the sums'
    stacked walk a word view of them crashes XLA:TPU's
    ``tpu-reduce-window-rewriter`` (SIGSEGV while compiling Q3's
    PARTIAL step for four chips at SF5; ``tests/test_tpu_compile.py``
    keeps that compile), and such a step's capacity is small."""
    n = next(iter(keys.values()))[0].shape[0]
    own = jnp.clip(info.owner, 0, n - 1)
    if not isinstance(info, K.GroupInfo):
        return K.gather_rows(keys, own), _gathers(keys)
    if info.perm is not None:
        _presort_shared(prepared, info, share)
    reads = StartReads(info)
    learning = {**share, "#starts": reads}
    for _sym, call, arg, contrib in prepared:
        compute_aggregate(
            call.name, call.type, arg, info, capacity, contrib,
            share=learning,
        )
    share["#starts"] = reads
    sums = {i: (c, None) for i, c in enumerate(reads.cols)}
    if info.perm is None:
        return reads.walk(keys), _gathers({**sums, **keys})
    reads.walk()
    keys_at = {s: K.rows_at(d, v, own) for s, (d, v) in keys.items()}
    lone = sum(1 + (v is not None) for _, v in keys.values())
    return keys_at, _gathers(sums) + lone


def _gathers(env: dict) -> int:
    """Device gathers ``kernels.gather_rows`` reads ``env`` in."""
    return K.gather_plan(
        (d.dtype, d.shape[1:], v is not None) for d, v in env.values()
    )[1]


@K.kernel
def _presort_shared(prepared, info, share):
    """Gather every column the step's aggregates need into group-sorted
    order in as few device gathers as possible: same-dtype columns are
    stacked [n, k] and gathered once (a stacked gather costs barely
    more than a single-column one on TPU), then unstacked into the
    ``share`` cache that ``compute_aggregate``'s reducers consult.
    Mirrors the cache keys of aggregates._Reducer exactly."""
    items: dict[int, object] = {}

    def want(x):
        if x is not None and id(x) not in items:
            items[id(x)] = x

    for _sym, _call, arg, contrib in prepared:
        eff = contrib
        if isinstance(arg, list):
            for pair in arg:
                d, v = pair
                if v is not None:
                    key = ("nulled", id(d), id(v))
                    hit = share.get(key)
                    if hit is None:
                        hit = (
                            d, v,
                            jnp.where(v, d, jnp.zeros((), dtype=d.dtype)),
                        )
                        share[key] = hit
                    want(hit[2])
                else:
                    want(d)
        elif arg is not None:
            d, v = arg
            want(d)
            if v is not None:
                key = ("and", id(contrib), id(v))
                hit = share.get(key)
                if hit is None:
                    hit = (contrib, v, contrib & v)
                    share[key] = hit
                eff = hit[2]
        want(eff)

    by_dtype: dict[tuple, list] = {}
    for x in items.values():
        # key on trailing dims too: multi-lane states ([n, k] sketches,
        # [n, 2] decimal limbs) cannot stack with 1-D columns
        by_dtype.setdefault((str(x.dtype), x.shape[1:]), []).append(x)
    for xs in by_dtype.values():
        if len(xs) == 1:
            x = xs[0]
            share[("sorted", id(x))] = (x, x[info.perm])
        else:
            stacked = jnp.stack(xs, axis=1)[info.perm]
            for i, x in enumerate(xs):
                share[("sorted", id(x))] = (x, stacked[:, i])


@K.kernel
def _dedupe(key_cols, arg, live, page_capacity, widths=None):
    """DISTINCT: keep one representative row per (group keys, value).

    Sort-based grouping is exact and dense, so a capacity equal to the
    page capacity can never overflow (num_groups <= live rows)."""
    data, valid = arg
    live_d = live if valid is None else (live & valid)
    norm = [_norm_opt(d, v) for d, v in key_cols]
    # the dedupe key value itself: NULL rows are excluded via live_d,
    # so the flag is never needed
    norm.append((_norm_opt(data, valid)[0], None))
    cap2 = page_capacity
    info2 = K.sort_group(
        tuple(b for b, _ in norm), tuple(fl for _, fl in norm), live_d, cap2,
        widths=widths,
    )
    row_idx = jnp.arange(page_capacity, dtype=jnp.int32)
    rep = live_d & (
        info2.owner[jnp.clip(info2.group, 0, cap2 - 1)] == row_idx
    )
    return rep


def _sort_step(nd, layout: ChainLayout):
    keys = []
    for k in nd.keys:
        nulls_first = k.nulls_first
        if nulls_first is None:
            # reference default: nulls are largest (ASC last, DESC first)
            nulls_first = not k.ascending
        keys.append((k.symbol, k.ascending, nulls_first))
    is_topn = isinstance(nd, P.TopN)
    in_cap = layout.capacity
    out_cap = pad_capacity(min(nd.count, in_cap)) if is_topn else in_cap
    out_layout = dc_replace(layout, capacity=out_cap) if is_topn else layout
    limit = out_cap if out_cap < in_cap else None
    count = nd.count if is_topn else None

    def step(env, mask, flags):
        sort_keys = []
        for s, asc, nf in keys:
            data, valid = env[s]
            if jnp.ndim(data) == 2:
                # two-limb decimal: hi is the major key, lo minor
                # (canonical lo in [0, 2^32) sorts correctly as int64)
                sort_keys.append((data[:, 0], valid, asc, nf))
                sort_keys.append((data[:, 1], valid, asc, nf))
            else:
                sort_keys.append((data, valid, asc, nf))
        perm = K.sort_perm(sort_keys, mask)
        if limit is not None:
            perm = perm[:limit]
        env2 = {s: K.rows_at(d, v, perm) for s, (d, v) in env.items()}
        mask2, _ = K.rows_at(mask, None, perm)
        if count is not None:
            mask2 = mask2 & (jnp.arange(mask2.shape[0]) < count)
        return env2, mask2, flags

    return step, out_layout


@K.kernel
def _live_rank(mask):
    """1-based rank of each live row among the live rows."""
    return jnp.cumsum(mask.astype(jnp.int64))


def _limit_step(nd: P.Limit):
    def step(env, mask, flags):
        rank = _live_rank(mask)
        keep = mask & (rank > nd.offset)
        if nd.count >= 0:
            keep = keep & (rank <= nd.offset + nd.count)
        return env, keep, flags

    return step


def _pad_to(arr: jnp.ndarray, capacity: int) -> jnp.ndarray:
    n = arr.shape[0]
    if n >= capacity:
        return arr[:capacity]
    pad_shape = (capacity - n,) + arr.shape[1:]  # limb columns are 2D
    return jnp.concatenate([arr, jnp.zeros(pad_shape, dtype=arr.dtype)])
