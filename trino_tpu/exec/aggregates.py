"""Aggregate function evaluation, scatter-free, in three modes.

The analog of the reference's accumulator layer
(MAIN/operator/aggregation/, AccumulatorCompiler). ``_Reducer`` holds
the reductions every aggregate is built from, and picks their form by
the group context it is given:

- **global** (``info`` None): no grouping, dense masked reductions.
- **slots** (``kernels.SlotInfo``): a key domain of a few bits — the
  packed key is the group's slot and each reduction is one dense
  masked pass per slot (``kernels.slot_reduce``). ``DENSE_AGGREGATES``
  names the aggregates built on ``_Reducer`` alone, which this mode
  serves.
- **sorted segments** (``kernels.GroupInfo``): ``kernels.sort_group``
  leaves each group as one contiguous run of the sorted row order, so
  an aggregate is a gather (into sorted order) + cumsum +
  boundary-difference, or a segmented associative scan for min/max.
  Any key, any aggregate — at the price of a sort, a gather a column
  and a full-width int64 prefix sum a limb, whatever the number of
  groups (some 500 ms for Q1's ten aggregates at 6.29M rows on a v5e,
  PERF.md PR 26). Rows that already arrive in key order
  (``kernels.run_group``) are their own sorted order: the same
  context with no permutation, so the sort and every gather fall away
  and the prefix sums run over the columns in place.

Work shared between the aggregates of one GROUP BY (the gather of a
column into group order, the per-group row count, contribution masks)
is deduplicated through a per-step ``share`` cache, the analog of the
reference's shared GroupByHash + per-aggregate accumulators split.
The integer sums of a sorted-segment step are all read at the groups'
first rows, so the step reads them in one walk (``StartReads``).

Distinct aggregates dedupe first: a second ``sort_group`` over
(group keys + argument) keeps one representative row per distinct
value, then the plain path aggregates the representatives
(the reference routes this through MarkDistinct / DistinctAccumulator).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.exec import kernels as K
from trino_tpu.expr.compiler import _div_round_half_up

__all__ = [
    "compute_aggregate", "dense_reducible", "StartReads", "VARIANCE_FNS",
]

VARIANCE_FNS = {
    "stddev", "stddev_samp", "stddev_pop", "variance", "var_samp", "var_pop",
}

#: aggregates built on ``_Reducer``'s reductions alone, so that any of
#: its modes serves them. The others read the sorted-group context
#: itself (DISTINCT's dedupe, the percentile and HLL sketches,
#: max_by/min_by) and need ``kernels.sort_group``.
DENSE_AGGREGATES = frozenset({
    "count_all", "count", "count_if", "sum", "avg", "min", "max",
    "bool_and", "bool_or", "any_value", "arbitrary",
    "count_final", "avg_final", "decimal_sum_final", "decimal_avg_final",
    "sum_hi32", "sum_lo32",
}) | VARIANCE_FNS


def dense_reducible(name: str, distinct: bool) -> bool:
    """Whether ``compute_aggregate`` evaluates this call from
    ``_Reducer``'s reductions alone (see ``DENSE_AGGREGATES``)."""
    return not distinct and (
        name in DENSE_AGGREGATES or name.startswith("var_final:")
    )

#: HLL register counts (relative standard error = 1.04/sqrt(m)):
#: global approx_distinct gets 4096 registers (~1.6%); grouped gets 512
#: (~4.6%) to bound per-group state at 512 bytes. The reference's
#: default HLL standard error is 2.3% (ApproximateCountDistinctAggregations).
HLL_GLOBAL_BUCKETS = 4096
HLL_GROUPED_BUCKETS = 512

#: quantile-summary sizes (rank error per shard <= count/points)
QUANT_GLOBAL_POINTS = 1024
QUANT_GROUPED_POINTS = 256


class StartReads:
    """The reads of one sorted-segment step at its groups' first rows:
    every integer sum of its aggregates (``kernels.start_walk``) and,
    grouped in place, its key columns — one index vector, so one walk.

    An aggregate asks for a sum where it needs it and computes on with
    the result, so the step's columns are only known once every
    aggregate has run. The step therefore evaluates its aggregates
    twice under the one trace: a first time to learn the columns
    (``sum`` keeps each and answers zeros; the results are dropped and
    XLA removes what only they used), then, after ``walk``, a second
    time in which ``sum`` hands out the walk's sums in the order they
    were asked for — the same code on the same inputs asks in the same
    order. Kept in the step's ``share`` under ``"#starts"``."""

    def __init__(self, info: K.GroupInfo):
        self.info = info
        self.cols: list = []
        self._sums = None

    def sum(self, vals):
        if self._sums is None:
            self.cols.append(vals)
            return jnp.zeros(self.info.starts.shape, vals.dtype)
        return next(self._sums)

    def walk(self, keys: dict | None = None):
        """Read the columns learnt so far, and ``keys`` with them
        (grouped in place only): the keys at each group's first row."""
        sums, keys_at = K.start_walk(self.info, self.cols, keys)
        self._sums = iter(sums)
        return keys_at


class _Reducer:
    """Per-group reductions for one GROUP BY — sorted segments
    (``info`` a ``GroupInfo``) or slots (a ``SlotInfo``) — or one
    global aggregate (``info`` None -> [1]-shaped dense reductions).

    ``share`` caches device intermediates across the aggregates of one
    step, keyed by object identity (keys hold a reference to the keyed
    array so ids stay valid for the cache's lifetime).
    """

    def __init__(self, info: K.GroupInfo | K.SlotInfo | None, capacity: int,
                 contrib, share: dict | None = None):
        self.info = info
        self.slots = isinstance(info, K.SlotInfo)
        self.capacity = capacity
        self.contrib = contrib
        self.share = share if share is not None else {}
        self.contrib_s = (
            None if info is None or self.slots else self._sorted(contrib)
        )

    def _sorted(self, x):
        key = ("sorted", id(x))
        hit = self.share.get(key)
        if hit is None or hit[0] is not x:
            hit = (x, self.info.in_order(x))
            self.share[key] = hit
        return hit[1]

    def _range_sum(self, vals):
        """Per-group sum of a group-sorted, contribution-masked column:
        an integer one is a column of the step's one walk."""
        starts = self.share.get("#starts")
        if starts is None or jnp.issubdtype(vals.dtype, jnp.floating):
            return K.seg_sum_ranges(vals, self.info)
        return starts.sum(vals)

    def with_valid(self, valid):
        """Reducer whose contribution also requires ``valid`` (cached)."""
        if valid is None:
            return self
        key = ("and", id(self.contrib), id(valid))
        hit = self.share.get(key)
        if hit is None or hit[0] is not self.contrib or hit[1] is not valid:
            hit = (self.contrib, valid, self.contrib & valid)
            self.share[key] = hit
        return _Reducer(self.info, self.capacity, hit[2], self.share)

    def sum(self, data, dtype=None):
        """Masked per-group sum; ``dtype`` casts AFTER the gather so the
        gathered column is shared with other aggregates of the step."""
        if self.info is None:
            x = data if dtype is None else data.astype(dtype)
            zero = jnp.zeros((), dtype=x.dtype)
            return jnp.sum(jnp.where(self.contrib, x, zero))[None]
        if self.slots:
            x = data if dtype is None else data.astype(dtype)
            # floats accumulate in float64, as the sorted mode's scan
            wide = jnp.issubdtype(x.dtype, jnp.floating)
            acc = x.astype(jnp.float64) if wide else x
            return self._slot(acc, 0).astype(x.dtype)
        xs = self._sorted(data)
        if dtype is not None:
            xs = xs.astype(dtype)
        zero = jnp.zeros((), dtype=xs.dtype)
        return self._range_sum(jnp.where(self.contrib_s, xs, zero))

    def sum_limbs(self, data):
        """Exact (hi, lo) limb sums of an int64 column (or an
        already-two-limb [n, 2] column, for re-aggregating decimal(38)
        results) — the limb split happens AFTER the shared sorted
        gather (splitting first would double the dominant [n]-gather
        per decimal aggregate)."""
        if jnp.ndim(data) == 2:
            hi_in, lo_in = data[:, 0], data[:, 1]
        else:
            hi_in = lo_in = None
        if self.info is None:
            if hi_in is not None:
                z = jnp.int64(0)
                hi = jnp.sum(jnp.where(self.contrib, hi_in, z))[None]
                lo = jnp.sum(jnp.where(self.contrib, lo_in, z))[None]
                return _limb_norm(hi, lo)
            masked = jnp.where(self.contrib, data, jnp.int64(0))
            hi = jnp.sum(masked >> jnp.int64(32))[None]
            lo = jnp.sum(masked & jnp.int64(0xFFFFFFFF))[None]
            return _limb_norm(hi, lo)
        if self.slots:
            if hi_in is None:
                hi_in = data >> jnp.int64(32)
                lo_in = data & jnp.int64(0xFFFFFFFF)
            return _limb_norm(self._slot(hi_in, 0), self._slot(lo_in, 0))
        # one pair a (column, contribution): the limb partials of a
        # distributed sum ask twice, and a walk holds each column once
        key = ("limbs", id(data), id(self.contrib))
        hit = self.share.get(key)
        if hit is None or hit[0] is not data or hit[1] is not self.contrib:
            xs = self._sorted(data)
            if hi_in is not None:
                hs, ls = xs[:, 0], xs[:, 1]
            else:
                hs, ls = xs >> jnp.int64(32), xs & jnp.int64(0xFFFFFFFF)
            zero = jnp.int64(0)
            hs = jnp.where(self.contrib_s, hs, zero)
            ls = jnp.where(self.contrib_s, ls, zero)
            hit = (data, self.contrib,
                   _limb_norm(self._range_sum(hs), self._range_sum(ls)))
            self.share[key] = hit
        return hit[2]

    def count(self):
        key = ("count", id(self.contrib))
        hit = self.share.get(key)
        if hit is None or hit[0] is not self.contrib:
            if self.info is None:
                cnt = jnp.sum(self.contrib.astype(jnp.int64))[None]
            elif self.slots:
                # a page holds under 2^31 rows: count in native lanes
                cnt = self._slot(
                    jnp.ones(self.contrib.shape, jnp.int32), 0
                ).astype(jnp.int64)
            elif self.info.perm is None and (
                self.contrib is self.share.get("#mask")
            ):
                # rows grouped in place, counting the live rows
                # themselves: a group's count is its run's length — no
                # prefix sum, no reads at the run starts. (True of the
                # sort path as well, dead rows sorting last; left as it
                # was there: ROADMAP S2.)
                cnt = (self.info.ends - self.info.starts).astype(jnp.int64)
            else:
                cnt = self._range_sum(self.contrib_s.astype(jnp.int64))
            hit = (self.contrib, cnt)
            self.share[key] = hit
        return hit[1]

    def minmax(self, data, fill, is_min: bool):
        if self.info is None:
            masked = jnp.where(self.contrib, data, fill)
            red = jnp.min if is_min else jnp.max
            return red(masked)[None]
        if self.slots:
            return self._slot(data, fill, "min" if is_min else "max")
        masked = jnp.where(self.contrib_s, self._sorted(data), fill)
        return K.seg_minmax_scan(masked, self.info, fill, is_min)

    @K.kernel
    def first_value(self, data):
        """Value of the first contributing row per group."""
        n = data.shape[0]
        if self.info is None:
            idx = jnp.arange(n, dtype=jnp.int32)
            first = jnp.min(jnp.where(self.contrib, idx, n))[None]
            return data[jnp.clip(first, 0, max(n - 1, 0))]
        if self.slots:
            rows = self._slot(jnp.arange(n, dtype=jnp.int32), n, "min")
        else:
            rows, _ = K.seg_first_index(self.contrib_s, self.info)
        return data[jnp.clip(rows, 0, max(n - 1, 0))]

    def _slot(self, vals, identity, op="sum"):
        return K.slot_reduce(vals, self.contrib, self.info, identity, op)

    def group_of_rows(self):
        """Dense group id of every row (``capacity`` for dead rows)."""
        if self.slots:
            return jnp.take(self.info.rank, self.info.slot, mode="fill",
                            fill_value=self.capacity)
        return self.info.group


@K.kernel
def compute_aggregate(
    name: str,
    out_type: T.DataType,
    arg,
    info: K.GroupInfo | None,
    capacity: int,
    contrib: jnp.ndarray,
    share: dict | None = None,
):
    """Evaluate one aggregate.

    ``info`` carries the sorted-group context (None for a global
    aggregate — output shape [1]); ``contrib`` masks the rows that
    feed this aggregate (liveness & FILTER & DISTINCT dedupe), in
    original row order, as are the ``arg`` (data, valid) pairs.
    Returns (data[capacity|1], valid[...] | None).
    """
    red = _Reducer(info, capacity, contrib, share)

    if name in _FINAL_COMBINES:
        return _FINAL_COMBINES[name](out_type, arg, red)
    if isinstance(name, str) and name.startswith("var_final:"):
        return _var_final(name[10:], arg, red)
    if isinstance(arg, list) and len(arg) == 1:
        arg = arg[0]
    if name == "count_all":
        return red.count(), None

    if name == "count_if":
        data, valid = arg
        eff = contrib & data
        if valid is not None:
            eff = eff & valid
        return _Reducer(info, capacity, eff, share).count(), None

    if name in ("approx_distinct", "approx_distinct_partial"):
        # HLL over prepared 64-bit hash lanes (exec.stage builds the
        # lane: content hashes for varchar, splitmix64 for numerics).
        # Constant-size register state -> splittable partial/final with
        # bounded bytes through every exchange (reference:
        # MAIN/operator/aggregation/ApproximateCountDistinctAggregations.java).
        data, valid = arg
        eff = contrib if valid is None else (contrib & valid)
        if isinstance(out_type, T.SketchType):
            m = out_type.lanes
        else:
            m = HLL_GLOBAL_BUCKETS if info is None else HLL_GROUPED_BUCKETS
        reg = _hll_registers(data, eff, m, info, capacity)
        if name == "approx_distinct_partial":
            return reg, None
        return _hll_estimate(reg), None

    if name == "approx_distinct_final":
        data, valid = arg if not isinstance(arg, list) else arg[0]
        eff = contrib if valid is None else (contrib & valid)
        reg = _hll_merge(data, eff, info, capacity)
        return _hll_estimate(reg), None

    if name == "approx_percentile_partial":
        (vd, vv), _q = arg
        if jnp.ndim(vd) == 2:
            # two-limb decimal values flatten to float64 for the
            # summary (the sketch is approximate by contract; float64
            # carries ~15-16 significant digits)
            vd = (
                vd[:, 0].astype(jnp.float64) * 4294967296.0
                + vd[:, 1].astype(jnp.float64)
            )
        eff = contrib if vv is None else (contrib & vv)
        k = out_type.lanes - 1
        state, _nonempty = _quant_summary(vd, eff, info, capacity, k, red)
        return state, None

    if name == "approx_percentile_final":
        (sd, sv), (qd, _qv) = arg
        return _quant_merge(
            sd, qd, contrib, sv, info, capacity, out_type
        )

    if name == "approx_percentile":
        # EXACT sorted-rank percentile (the reference's qdigest sketch
        # approximates, MAIN/operator/aggregation/ApproximateLongPercentileAggregations;
        # a sort-based engine gets the exact answer for the same cost
        # class): rows re-sort (group, contributing-first, value) and
        # each group reads index round(q * (cnt-1)) of its run.
        (vd, vv), (qd, _qv) = arg
        eff = contrib if vv is None else (contrib & vv)
        q = qd.reshape(-1)[0].astype(jnp.float64)
        n = vd.shape[0]
        if jnp.ndim(vd) == 2:
            # two-limb decimal: numeric order is lexicographic
            # (hi signed, lo canonical) — stable two-pass sort; the
            # rank gather below then returns the exact limb row
            p = jnp.argsort(K.order_bits(vd[:, 1]), stable=True)
            p = p[jnp.argsort(K.order_bits(vd[p, 0]), stable=True)]
            p = p.astype(jnp.int32)
        else:
            p = jnp.argsort(K.order_bits(vd), stable=True).astype(jnp.int32)
        p = p[jnp.argsort((~eff)[p], stable=True)]
        er = _Reducer(info, capacity, eff, share)
        cnt2 = er.count()
        if info is None:
            starts = jnp.zeros((1,), dtype=jnp.int64)
        else:
            # group runs occupy the same [start, end) ranges as info's
            # ordering (identical per-group populations, dead rows last)
            p = p[jnp.argsort(info.group[p], stable=True)]
            starts = info.starts.astype(jnp.int64)
        offs = jnp.clip(
            jnp.round(q * (cnt2.astype(jnp.float64) - 1.0)).astype(jnp.int64),
            0, jnp.maximum(cnt2 - 1, 0),
        )
        at = jnp.clip(starts + offs, 0, max(n - 1, 0))
        out = vd[p[at.astype(jnp.int32)]]
        return out, cnt2 > 0

    if name in ("max_by", "min_by"):
        (vd, vv), (kd, kv) = arg
        is_min = name == "min_by"
        eff = contrib if kv is None else (contrib & kv)
        kbits = K.order_bits(kd)
        n = kd.shape[0]
        if info is None:
            worst = (
                jnp.uint64(0xFFFFFFFFFFFFFFFF) if is_min else jnp.uint64(0)
            )
            masked = jnp.where(eff, kbits, worst)
            m = (jnp.min if is_min else jnp.max)(masked)
            # first CONTRIBUTING row at the extreme — a sentinel-valued
            # real key must not lose to an excluded row
            idx = jnp.argmax(eff & (masked == m))[None]
            has = jnp.any(eff)[None]
            out = vd[jnp.clip(idx, 0, max(n - 1, 0))]
            ov = has if vv is None else (has & vv[idx])
            return out, ov
        er = _Reducer(info, capacity, eff, share)
        rows = K.seg_arg_extreme(
            er._sorted(kbits), er.contrib_s, info, is_min
        )
        has = er.count() > 0
        at = jnp.clip(rows, 0, max(n - 1, 0))
        out = vd[at]
        ov = has if vv is None else (has & vv[at])
        return out, ov

    data, valid = arg
    red = red.with_valid(valid)

    if name == "count":
        return red.count(), None

    cnt = red.count()
    nonempty = cnt > 0

    if name == "sum":
        if isinstance(out_type, T.DecimalType) and out_type.is_long:
            # decimal(38) sum: EXACT two-limb int64 accumulation (the
            # Int128 DecimalSumAggregation analog). Each int64 input
            # splits into hi = x >> 32 (sign-extended) and lo 32 bits;
            # both limb sums fit int64 for any page (|hi| <= 2^31,
            # lo < 2^32, rows < 2^31), so no achievable sum overflows.
            hi, lo = red.sum_limbs(data)
            return jnp.stack([hi, lo], axis=-1), nonempty
        cast = (
            out_type.np_dtype
            if isinstance(out_type, (T.DoubleType, T.RealType))
            else None
        )
        return red.sum(data, dtype=cast), nonempty

    if name == "avg":
        if isinstance(out_type, T.DecimalType):
            # exact limb sum, then exact 96/64 long division with
            # round-half-away (reference: DecimalAverageAggregation);
            # the quotient always fits int64 (an average is bounded by
            # the inputs). Long-decimal outputs re-encode as limbs.
            hi, lo = red.sum_limbs(data)
            q = _limb_div_round(hi, lo, jnp.maximum(cnt, 1))
            if out_type.is_long:
                q = _limb_encode(q)
            return q, nonempty
        s = red.sum(data, dtype=jnp.float64)
        return s / jnp.maximum(cnt, 1), nonempty


    if name in ("min", "max"):
        is_min = name == "min"
        if jnp.ndim(data) == 2:
            # two-limb decimal: extreme of hi, then extreme of lo among
            # rows at that hi (lexicographic == numeric order since lo
            # is canonical non-negative)
            hi, lo = data[:, 0], data[:, 1]
            iinfo = jnp.iinfo(jnp.int64)
            fill_hi = jnp.int64(iinfo.max if is_min else iinfo.min)
            m_hi = red.minmax(hi, fill_hi, is_min)
            if info is None:
                at_ext = hi == m_hi[0]
            else:
                at_ext = hi == m_hi[
                    jnp.clip(red.group_of_rows(), 0, capacity - 1)
                ]
            red2 = _Reducer(info, capacity, red.contrib & at_ext, share)
            fill_lo = jnp.int64((1 << 32) if is_min else -1)
            m_lo = red2.minmax(lo, fill_lo, is_min)
            return jnp.stack([m_hi, m_lo], axis=-1), nonempty
        if data.dtype == jnp.bool_:
            fill = jnp.int8(1 if is_min else 0)
            out = red.minmax(data.astype(jnp.int8), fill, is_min)
            return out.astype(jnp.bool_), nonempty
        if jnp.issubdtype(data.dtype, jnp.floating):
            fill = jnp.array(
                np.inf if is_min else -np.inf, dtype=data.dtype
            )
        else:
            iinfo = jnp.iinfo(data.dtype)
            fill = jnp.array(
                iinfo.max if is_min else iinfo.min, dtype=data.dtype
            )
        return red.minmax(data, fill, is_min), nonempty

    if name in ("any_value", "arbitrary"):
        return red.first_value(data), nonempty

    if name in ("bool_and", "bool_or"):
        is_min = name == "bool_and"
        fill = jnp.int8(1 if is_min else 0)
        out = red.minmax(data.astype(jnp.int8), fill, is_min)
        return out.astype(jnp.bool_), nonempty

    if name in VARIANCE_FNS:
        s1 = red.sum(data, dtype=jnp.float64)
        x = data.astype(jnp.float64)
        s2 = red.sum(x * x)
        n = cnt.astype(jnp.float64)
        m2 = s2 - (s1 * s1) / jnp.maximum(n, 1.0)
        m2 = jnp.maximum(m2, 0.0)  # clamp fp cancellation
        pop = name.endswith("_pop")
        denom = n if pop else n - 1.0
        ok = cnt >= (1 if pop else 2)
        var = m2 / jnp.maximum(denom, 1.0)
        if name.startswith("stddev"):
            var = jnp.sqrt(var)
        return var, ok

    raise NotImplementedError(f"aggregate {name}")


# ---- sketches (HLL / quantile summaries) -----------------------------------

def _clz64(x: jnp.ndarray) -> jnp.ndarray:
    """Vectorized count-of-leading-zeros over uint64 (binary descent —
    XLA has no clz primitive)."""
    zero = x == 0
    n = jnp.zeros(x.shape, dtype=jnp.int32)
    for s in (32, 16, 8, 4, 2, 1):
        no_high = (x >> np.uint64(64 - s)) == 0
        n = n + jnp.where(no_high, s, 0)
        x = jnp.where(no_high, x << np.uint64(s), x)
    return jnp.where(zero, 64, n)


def dev_hash64(data: jnp.ndarray) -> jnp.ndarray:
    """Device-side 64-bit value hash (splitmix64 finalizer) for HLL
    lanes over numeric/date/decimal columns. Two-limb decimals combine
    limbs into the unscaled value first; floats canonicalize -0.0."""
    import jax

    if data.ndim == 2:
        with np.errstate(over="ignore"):
            v = (
                data[:, 0].astype(jnp.uint64) << np.uint64(32)
            ) + data[:, 1].astype(jnp.uint64)
    elif jnp.issubdtype(data.dtype, jnp.floating):
        f = jnp.where(data == 0.0, 0.0, data).astype(jnp.float64)
        v = jax.lax.bitcast_convert_type(f, jnp.uint64)
    else:
        v = data.astype(jnp.int64).astype(jnp.uint64)
    v ^= v >> np.uint64(30)
    v *= np.uint64(0xBF58476D1CE4E5B9)
    v ^= v >> np.uint64(27)
    v *= np.uint64(0x94D049BB133111EB)
    v ^= v >> np.uint64(31)
    return v


@K.kernel
def _hll_registers(h, contrib, m, info, capacity):
    """HLL register arrays from 64-bit hash lanes, scatter-free: rows
    sort by (group, bucket, -rho) and each register reads the first
    element of its run via searchsorted (one sort + one dense gather —
    the engine's sort-based analog of the reference's per-row register
    update loop, MAIN/.../aggregation/state/AbstractHyperLogLogState).
    Returns int8[capacity, m] (capacity 1 for global)."""
    b = int(m).bit_length() - 1
    bucket = (h >> np.uint64(64 - b)).astype(jnp.int64)
    rho = jnp.clip(_clz64(h << np.uint64(b)) + 1, 1, 64 - b + 1)
    caps = 1 if info is None else capacity
    if info is None:
        key = bucket
    else:
        key = info.group.astype(jnp.int64) * m + bucket
    dead = jnp.int64(caps * m)
    key = jnp.where(contrib, key, dead)
    combined = key * 64 + (63 - rho.astype(jnp.int64))
    sc = jnp.sort(combined)
    targets = jnp.arange(caps * m, dtype=jnp.int64) * 64
    pos = jnp.searchsorted(sc, targets)
    n = sc.shape[0]
    at = jnp.clip(pos, 0, n - 1)
    found = sc[at]
    hit = (pos < n) & ((found >> 6) == (targets >> 6))
    reg = jnp.where(hit, 63 - (found & 63), 0).astype(jnp.int8)
    return reg.reshape(caps, m)


def _hll_estimate(reg: jnp.ndarray) -> jnp.ndarray:
    """Registers -> cardinality estimate (standard HLL with the
    small-range linear-counting correction; 64-bit hashes make the
    large-range correction unnecessary)."""
    m = reg.shape[-1]
    alpha = 0.7213 / (1.0 + 1.079 / m)
    inv = jnp.sum(2.0 ** (-reg.astype(jnp.float64)), axis=-1)
    raw = alpha * m * m / inv
    v = jnp.sum((reg == 0), axis=-1).astype(jnp.float64)
    lin = m * jnp.log(jnp.where(v > 0, m / jnp.maximum(v, 1.0), 1.0))
    est = jnp.where((raw <= 2.5 * m) & (v > 0), lin, raw)
    return jnp.round(est).astype(jnp.int64)


@K.kernel
def _hll_merge(states, contrib, info, capacity):
    """Element-wise max of member rows' register arrays (the FINAL
    combine; register max is the HLL merge). Inputs are partial-state
    rows — few per group — so a scatter-max is fine here."""
    live = jnp.where(contrib[:, None], states, jnp.zeros((), jnp.int8))
    if info is None:
        return jnp.max(live, axis=0, keepdims=True)
    gid = jnp.clip(info.group, 0, capacity - 1)
    out = jnp.zeros((capacity, states.shape[1]), dtype=jnp.int8)
    return out.at[gid].max(live)


@K.kernel
def _quant_sorted_perm(vd, eff, info):
    """Permutation ordering rows (group asc, contributing-first,
    value asc) — shared by the exact percentile and the summary
    builder."""
    vbits = K.order_bits(vd)
    p = jnp.argsort(vbits, stable=True).astype(jnp.int32)
    p = p[jnp.argsort((~eff)[p], stable=True)]
    if info is not None:
        p = p[jnp.argsort(info.group[p], stable=True)]
    return p


@K.kernel
def _quant_summary(vd, eff, info, capacity, k, red):
    """Mergeable quantile summary: k evenly-spaced order statistics
    per group + a count lane, as float64[cap, k+1] (the qdigest-state
    analog, MAIN/.../aggregation/ApproximateLongPercentileAggregations;
    rank error after merging S shards is <= total/k per shard).
    """
    p = _quant_sorted_perm(vd, eff, info)
    er = _Reducer(red.info, capacity, eff, red.share)
    cnt = er.count()
    caps = 1 if info is None else capacity
    if info is None:
        starts = jnp.zeros((1,), dtype=jnp.int64)
    else:
        starts = info.starts.astype(jnp.int64)
    i = jnp.arange(k, dtype=jnp.float64)
    offs = jnp.clip(
        jnp.round((i[None, :] + 0.5) / k * cnt[:, None].astype(jnp.float64) - 0.5),
        0, jnp.maximum(cnt[:, None] - 1, 0).astype(jnp.float64),
    ).astype(jnp.int64)
    n = vd.shape[0]
    at = jnp.clip(starts[:, None] + offs, 0, max(n - 1, 0))
    pts = vd[p[at.astype(jnp.int32)]].astype(jnp.float64)
    state = jnp.concatenate(
        [pts, cnt[:caps, None].astype(jnp.float64)], axis=1
    )
    return state, cnt > 0


@K.kernel
def _quant_merge(states, q, contrib, valid, info, capacity, out_type):
    """FINAL combine of quantile summaries: member rows' points merge
    as a weighted quantile (each point carries weight count/k). Sort-
    and-searchsorted based; input rows are partial states (few per
    group)."""
    k = states.shape[1] - 1
    pts = states[:, :k]
    cnt = states[:, k]
    eff = contrib if valid is None else (contrib & valid)
    w = jnp.where(eff & (cnt > 0), cnt / k, 0.0)
    n = states.shape[0]
    caps = 1 if info is None else capacity
    # flatten to n*k weighted points
    vals = pts.reshape(-1)
    wts = jnp.repeat(w, k)
    if info is None:
        gid_f = jnp.zeros(n * k, dtype=jnp.int64)
    else:
        gid_f = jnp.repeat(
            jnp.clip(info.group, 0, capacity - 1).astype(jnp.int64), k
        )
    vbits = K.order_bits(vals)
    p = jnp.argsort(vbits, stable=True).astype(jnp.int32)
    p = p[jnp.argsort(gid_f[p], stable=True)]
    gs = gid_f[p]
    ws = wts[p]
    cum = jnp.cumsum(ws)
    starts = jnp.searchsorted(gs, jnp.arange(caps, dtype=jnp.int64))
    base = jnp.where(
        starts > 0, cum[jnp.clip(starts - 1, 0, n * k - 1)], 0.0
    )
    total = jnp.zeros((caps,), dtype=jnp.float64).at[gs].add(ws)
    # within-group cumulative weight; pick the first point reaching
    # q * total (approximate global rank selection)
    adj = cum - base[gs]
    target = q.reshape(-1)[0].astype(jnp.float64) * total
    reached = adj >= target[gs] - 1e-9
    idx = jnp.arange(n * k, dtype=jnp.int64)
    cand = jnp.where(reached & (ws > 0), idx, n * k)
    first = jnp.full((caps,), n * k, dtype=jnp.int64).at[gs].min(cand)
    # groups with no weight fall back to any index (masked by valid)
    at = jnp.clip(first, 0, max(n * k - 1, 0))
    out = vals[p[at.astype(jnp.int32)]]
    has = total > 0
    if isinstance(out_type, (T.DoubleType, T.RealType)):
        return out.astype(out_type.np_dtype.type), has
    if isinstance(out_type, T.DecimalType) and out_type.is_long:
        return _limb_encode(jnp.round(out).astype(jnp.int64)), has
    return jnp.round(out).astype(jnp.int64).astype(out_type.np_dtype.type), has


def _limb_encode(q: jnp.ndarray) -> jnp.ndarray:
    """Re-encode an int64 value as canonical two limbs (hi signed,
    lo in [0, 2^32)) — the storage form of long-decimal columns."""
    return jnp.stack(
        [q >> jnp.int64(32), q & jnp.int64(0xFFFFFFFF)], axis=-1
    )


def _limb_norm(s_hi, s_lo):
    """Canonicalize limb sums: lo into [0, 2^32), carry into hi."""
    carry = s_lo >> jnp.int64(32)
    lo = s_lo & jnp.int64(0xFFFFFFFF)
    return s_hi + carry, lo


def _limb_div_round(hi, lo, cnt):
    """(hi*2^32 + lo) / cnt exactly, rounded half away from zero.

    Schoolbook 96/64 long division in two int64 steps: q1 = hi // cnt
    leaves r1 < cnt <= 2^31, so (r1 << 32) | lo fits int64."""
    q1 = K.floor_div(hi, cnt)
    r1 = hi - q1 * cnt  # in [0, cnt)
    rem = (r1 << jnp.int64(32)) | lo
    q2 = K.floor_div(rem, cnt)
    r2 = rem - q2 * cnt
    q = (q1 << jnp.int64(32)) + q2  # floor((hi*2^32+lo)/cnt)
    # round half away from zero on the floor quotient: positive values
    # bump at >= .5, negative at > .5 (floor already moved them down)
    neg = (hi < 0) | ((hi == 0) & (lo < 0))
    bump = jnp.where(neg, 2 * r2 > cnt, 2 * r2 >= cnt)
    return q + jnp.where(bump, 1, 0)


# ---- FINAL-step combines ---------------------------------------------------
# The distributed split (plan.distribute._split_aggregate) produces
# shard-local PARTIAL states which these combine after the hash
# exchange — the reference's final Accumulator step over serialized
# intermediate state (MAIN/operator/aggregation/ state serializers).


def _state_sum(pair, red: _Reducer):
    data, valid = pair
    if valid is not None:
        key = ("nulled", id(data), id(valid))
        hit = red.share.get(key)
        if hit is None or hit[0] is not data or hit[1] is not valid:
            hit = (
                data, valid,
                jnp.where(valid, data, jnp.zeros((), dtype=data.dtype)),
            )
            red.share[key] = hit
        data = hit[2]
    return red.sum(data)


def _count_final(out_type, args, red: _Reducer):
    """Sum of partial counts; never NULL (COUNT semantics)."""
    pair = args[0] if isinstance(args, list) else args
    return _state_sum(pair, red), None


def _avg_final(out_type, args, red: _Reducer):
    s = _state_sum(args[0], red)
    c = _state_sum(args[1], red)
    nonempty = c > 0
    if isinstance(out_type, T.DecimalType):
        q = _div_round_half_up(s, jnp.maximum(c, 1))
        if out_type.is_long:
            q = _limb_encode(q)
        return q, nonempty
    return s.astype(jnp.float64) / jnp.maximum(c, 1), nonempty


def _var_final(kind, args, red: _Reducer):
    n = _state_sum(args[0], red).astype(jnp.float64)
    s1 = _state_sum(args[1], red)
    s2 = _state_sum(args[2], red)
    m2 = jnp.maximum(s2 - (s1 * s1) / jnp.maximum(n, 1.0), 0.0)
    pop = kind.endswith("_pop")
    denom = n if pop else n - 1.0
    ok = n >= (1 if pop else 2)
    var = m2 / jnp.maximum(denom, 1.0)
    if kind.startswith("stddev"):
        var = jnp.sqrt(var)
    return var, ok


def _decimal_sum_final(out_type, args, red: _Reducer):
    """FINAL combine of distributed long-decimal sums: partial states
    are two BIGINT limb-sum columns (hi32, lo)."""
    s_hi = _state_sum(args[0], red)
    s_lo = _state_sum(args[1], red)
    hi, lo = _limb_norm(s_hi, s_lo)
    _, hv = args[0]
    cred = red.with_valid(hv)
    return jnp.stack([hi, lo], axis=-1), cred.count() > 0


def _decimal_avg_final(out_type, args, red: _Reducer):
    """FINAL combine of distributed decimal averages: exact limb sum
    of partial limb states, divided by the combined count."""
    s_hi = _state_sum(args[0], red)
    s_lo = _state_sum(args[1], red)
    cnt = _state_sum(args[2], red)
    hi, lo = _limb_norm(s_hi, s_lo)
    nonempty = cnt > 0
    q = _limb_div_round(hi, lo, jnp.maximum(cnt, 1))
    if isinstance(out_type, T.DecimalType) and out_type.is_long:
        q = _limb_encode(q)
    return q, nonempty


def _limb_partial_sum(which: str):
    """PARTIAL limb sums over the raw decimal column: 'hi32' sums the
    sign-extended top 32 bits, 'lo32' the low 32 bits (both exact in
    int64 for any page)."""

    def fn(out_type, args, red: _Reducer):
        pair = args[0] if isinstance(args, list) else args
        data, valid = pair
        r = red.with_valid(valid)
        # both limb partials build the same scan graph; XLA CSE merges
        # them inside the fused step program
        hi, lo = r.sum_limbs(data)
        part = hi if which == "hi32" else lo
        # NULL when no row contributed, so the FINAL combine keeps SUM's
        # all-NULL-group semantics
        return part, r.count() > 0

    return fn


_FINAL_COMBINES = {
    "count_final": _count_final,
    "avg_final": _avg_final,
    "decimal_sum_final": _decimal_sum_final,
    "decimal_avg_final": _decimal_avg_final,
    "sum_hi32": _limb_partial_sum("hi32"),
    "sum_lo32": _limb_partial_sum("lo32"),
}
