"""Window function evaluation — sort-based, scatter-free.

The analog of the reference's WindowOperator + window function suite
(MAIN/operator/WindowOperator.java, MAIN/operator/window/): instead of
per-partition pagination and per-row framing loops, one jitted program
computes every window function of a node:

1. rows are permuted to (partition, order-keys) order — the ORDER BY
   keys are sorted first (kernels.sort_perm), then a stable partition
   grouping (kernels.sort_group with pre_perm) leaves each partition
   as one contiguous, ordered run;
2. ranks and frames become position arithmetic + segmented scans over
   that run structure (cumsum differences for ROWS frames, peer-group
   ends for RANGE frames, associative min/max scans for running
   min/max);
3. results gather back to original row order through the inverse
   permutation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from trino_tpu import types as T
from trino_tpu.exec import kernels as K
from trino_tpu.exec.stage import _key_width, _norm_opt
from trino_tpu.expr.compiler import _div_round_half_up
from trino_tpu.plan import nodes as P

__all__ = ["build_window_program"]

#: SQL default frame: RANGE BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW
_DEFAULT_FRAME = ("range", ("unbounded_preceding", None), ("current", None))


def build_window_program(node: P.Window, layout_types, layout_dicts, capacity):
    """(fn, out_meta): ``fn(env, mask) -> env2`` adds one column per
    window function; ``out_meta`` is [(sym, type, dictionary)] for the
    new columns. Pure and jittable."""
    part_syms = list(node.partition_by)
    order_keys = [
        (k.symbol, k.ascending, k.nulls_first) for k in node.order_keys
    ]
    widths = tuple(
        _key_width(layout_types[s], layout_dicts.get(s)) for s in part_syms
    )
    fns = dict(node.functions)
    out_meta = []
    for sym, call in fns.items():
        d = None
        if (
            isinstance(call.type, T.VarcharType)
            and call.args
            and hasattr(call.args[0], "name")
        ):
            d = layout_dicts.get(call.args[0].name)
        out_meta.append((sym, call.type, d))

    @K.kernel
    def window_rows(env, mask):
        n = mask.shape[0]
        # 1. order-by sort first, stable partition grouping on top
        if order_keys:
            sk = []
            for s, asc, nf in order_keys:
                if nf is None:
                    nf = not asc  # reference default: nulls largest
                data, valid = env[s]
                if jnp.ndim(data) == 2:
                    # two-limb decimal key: (hi, lo) lexicographic ==
                    # numeric order (hi signed, lo canonical)
                    sk.append((data[:, 0], valid, asc, nf))
                    sk.append((data[:, 1], None, asc, False))
                else:
                    sk.append((data, valid, asc, nf))
            pre = K.sort_perm(sk, mask)
        else:
            pre = None
        norm = [_norm_opt(*env[s]) for s in part_syms]
        info = K.sort_group(
            tuple(b for b, _ in norm),
            tuple(f for _, f in norm),
            mask, n, widths=widths, pre_perm=pre,
        )
        perm = info.perm
        inv = jnp.argsort(perm, stable=True)
        pos = jnp.arange(n, dtype=jnp.int32)
        live_s = mask[perm]
        # partition start/end per sorted row
        gid_c = jnp.clip(info.gid_sorted, 0, n - 1)
        pstart = info.starts[gid_c]
        pend = info.ends[gid_c]  # exclusive
        # peer groups: order-key ties within a partition
        pboundary = (pos == 0) | (
            info.gid_sorted != jnp.roll(info.gid_sorted, 1)
        )
        same_order = jnp.ones((n,), dtype=jnp.bool_)
        for s, _asc, _nf in order_keys:
            data, valid = env[s]
            parts = (
                [data[:, 0], data[:, 1]] if jnp.ndim(data) == 2
                else [data]
            )
            for i, p in enumerate(parts):
                bits, flag = K.normalize_key(p, valid if i == 0 else None)
                bs = bits[perm]
                same_order = same_order & (bs == jnp.roll(bs, 1))
                if valid is not None and i == 0:
                    fl = flag[perm]
                    same_order = same_order & (fl == jnp.roll(fl, 1))
        peer_b = pboundary | ~same_order
        # peer start: running max of boundary positions
        peer_start = jax.lax.associative_scan(
            jnp.maximum, jnp.where(peer_b, pos, -1)
        )
        # peer end (exclusive): next boundary position, from the right
        nxt = jnp.concatenate(
            [peer_b[1:], jnp.ones((1,), dtype=jnp.bool_)]
        )
        rev = jnp.flip(jnp.where(nxt, pos + 1, n + 1))
        peer_end = jnp.flip(jax.lax.associative_scan(jnp.minimum, rev))
        peer_end = jnp.minimum(peer_end, pend)

        row_number = (pos - pstart + 1).astype(jnp.int64)

        # RANGE-offset support: with exactly one order key, expose the
        # key in sorted, direction-normalized form so offset bounds
        # become segmented binary searches (the reference's
        # RangeValueWindowFrame, MAIN/operator/window/ — here O(log n)
        # vectorized probes instead of per-row cursors)
        range_ctx = None
        if len(order_keys) == 1:
            s, asc, nf = order_keys[0]
            data, valid = env[s]
            t = layout_types.get(s)
            if jnp.ndim(data) != 2 and jnp.issubdtype(
                jnp.asarray(data).dtype, jnp.number
            ):
                scale = (
                    10 ** t.scale if isinstance(t, T.DecimalType) else 1
                )
                w = data[perm]
                if not asc:
                    w = -w
                vp = None
                if valid is not None:
                    vp = valid[perm]
                    nulls_first = nf if nf is not None else (not asc)
                    sent = _fill_for(w.dtype, not nulls_first)
                    w = jnp.where(vp, w, sent)
                range_ctx = (w, vp, scale)

        env2 = dict(env)
        for sym, call in fns.items():
            data_s, valid_s = _eval_call(
                call, env, mask, perm, info, pos, live_s,
                pstart, pend, peer_start, peer_end, peer_b, row_number, n,
                range_ctx,
            )
            # back to original row order
            data = data_s[inv]
            valid = None if valid_s is None else valid_s[inv]
            env2[sym] = (data, valid)
        return env2

    return window_rows, out_meta


@K.kernel
def _eval_call(
    call, env, mask, perm, info, pos, live_s,
    pstart, pend, peer_start, peer_end, peer_b, row_number, n,
    range_ctx=None,
):
    """One window function in sorted space."""
    name = call.name
    if name == "row_number":
        return row_number, None
    if name == "rank":
        return (peer_start - pstart + 1).astype(jnp.int64), None
    if name == "dense_rank":
        c = jnp.cumsum(peer_b.astype(jnp.int64))
        return c - c[jnp.clip(pstart, 0, n - 1)] + 1, None
    if name == "percent_rank":
        # (rank - 1) / (partition rows - 1); 0 for single-row partitions
        size = (pend - pstart).astype(jnp.float64)
        rank = (peer_start - pstart + 1).astype(jnp.float64)
        return (
            jnp.where(size > 1, (rank - 1) / jnp.maximum(size - 1, 1), 0.0),
            None,
        )
    if name == "cume_dist":
        # rows preceding or peer with current / partition rows
        size = (pend - pstart).astype(jnp.float64)
        return (
            (peer_end - pstart).astype(jnp.float64) / jnp.maximum(size, 1),
            None,
        )
    if name == "ntile":
        k = _const_arg(call.args[0])
        size = (pend - pstart).astype(jnp.int64)
        i = row_number - 1
        return (i * k) // jnp.maximum(size, 1) + 1, None
    if name in ("lead", "lag"):
        off = _const_arg(call.args[1]) if len(call.args) > 1 else 1
        step = off if name == "lead" else -off
        src = pos + step
        ok = (src >= pstart) & (src < pend)
        data, valid = _sorted_arg(env, call.args[0], perm)
        at = jnp.clip(src, 0, n - 1)
        out = data[at]
        out_valid = ok if valid is None else (ok & valid[at])
        if len(call.args) > 2:
            dd, dv = _sorted_arg(env, call.args[2], perm)
            out = jnp.where(ok, out, dd)
            dval = (
                jnp.ones((n,), dtype=jnp.bool_) if dv is None else dv
            )
            base = (
                jnp.ones((n,), dtype=jnp.bool_)
                if valid is None else valid[at]
            )
            out_valid = jnp.where(ok, base, dval)
        return out, out_valid
    frame = call.frame or _DEFAULT_FRAME
    mode, start, end = frame
    if name in ("min", "max") and start[0] != "unbounded_preceding":
        # running scans cover prefix frames only (sliding-window
        # min/max needs a deque structure the reference also
        # special-cases, MAIN/operator/window/)
        raise NotImplementedError(
            "min/max window frames must start UNBOUNDED PRECEDING"
        )
    # frame bounds as sorted positions [lo, hi) per row
    if mode == "range" and (
        start[0] in ("preceding", "following")
        or end[0] in ("preceding", "following")
    ):
        if range_ctx is None:
            raise NotImplementedError(
                "RANGE offset frames require exactly one numeric "
                "ORDER BY key"
            )
        w, vp, scale = range_ctx

        def rbound(b, is_lo):
            kind, off = b
            if kind == "unbounded_preceding":
                return pstart
            if kind == "unbounded_following":
                return pend
            if kind == "current":
                return peer_start if is_lo else peer_end
            delta = off * scale * (1 if kind == "following" else -1)
            target = w + jnp.asarray(delta).astype(w.dtype)
            return _seg_searchsorted(w, target, pstart, pend, is_lo, n)

        lo = rbound(start, True)
        hi = rbound(end, False)
        if vp is not None:
            # null-key rows: the frame is the null peer group
            lo = jnp.where(vp, lo, peer_start)
            hi = jnp.where(vp, hi, peer_end)
    else:
        lo = _bound_pos(start, pos, pstart, pend, peer_start, peer_end, mode, True)
        hi = _bound_pos(end, pos, pstart, pend, peer_start, peer_end, mode, False)
    lo = jnp.clip(lo, pstart, pend)
    hi = jnp.clip(hi, pstart, pend)

    if name in ("first_value", "last_value"):
        data, valid = _sorted_arg(env, call.args[0], perm)
        at = jnp.clip(jnp.where(name == "first_value", lo, hi - 1), 0, n - 1)
        ok = hi > lo
        out_valid = ok if valid is None else (ok & valid[at])
        return data[at], out_valid
    if name == "nth_value":
        k = _const_arg(call.args[1])
        data, valid = _sorted_arg(env, call.args[0], perm)
        at = jnp.clip(lo + k - 1, 0, n - 1)
        ok = (k >= 1) & (lo + k - 1 < hi)
        out_valid = ok if valid is None else (ok & valid[at])
        return data[at], out_valid
    # aggregates over the frame
    if name == "count_all":
        contrib = live_s
        data = None
    else:
        data, valid = _sorted_arg(env, call.args[0] if call.args else None, perm)
        contrib = live_s if valid is None else (live_s & valid)
    cnt = _range_sum(contrib.astype(jnp.int64), lo, hi, n)
    if name in ("count", "count_all"):
        return cnt, None
    if name == "sum":
        if isinstance(call.type, T.DecimalType) and call.type.is_long:
            # exact limb window sums (frames bounded by the page, so
            # both 32-bit limb prefix sums stay within int64); the
            # argument may itself already be a two-limb column (e.g.
            # sum(sum(decimal)) OVER in windows-over-aggregates)
            if jnp.ndim(data) == 2:
                hi_in = jnp.where(contrib, data[:, 0], 0)
                lo_in = jnp.where(contrib, data[:, 1], 0)
            else:
                masked = jnp.where(
                    contrib, data, jnp.zeros((), dtype=data.dtype)
                )
                hi_in = masked >> jnp.int64(32)
                lo_in = masked & jnp.int64(0xFFFFFFFF)
            s_hi = _range_sum(hi_in, lo, hi, n)
            s_lo = _range_sum(lo_in, lo, hi, n)
            carry = s_lo >> jnp.int64(32)
            return (
                jnp.stack(
                    [s_hi + carry, s_lo & jnp.int64(0xFFFFFFFF)], axis=-1
                ),
                cnt > 0,
            )
        z = jnp.zeros((), dtype=data.dtype)
        s = _range_sum(
            jnp.where(contrib, data, z), lo, hi, n, gid=info.gid_sorted
        )
        return s.astype(data.dtype), cnt > 0
    if name == "avg":
        if isinstance(call.type, T.DecimalType) and call.type.is_long:
            # limb window sums + exact 96/64 divide (mirrors the sum
            # branch; the argument may be a two-limb column when
            # averaging an exact decimal(38) aggregate in a window)
            from trino_tpu.exec.aggregates import (
                _limb_div_round,
                _limb_encode,
                _limb_norm,
            )

            if jnp.ndim(data) == 2:
                hi_in = jnp.where(contrib, data[:, 0], 0)
                lo_in = jnp.where(contrib, data[:, 1], 0)
            else:
                masked = jnp.where(
                    contrib, data, jnp.zeros((), dtype=data.dtype)
                )
                hi_in = masked >> jnp.int64(32)
                lo_in = masked & jnp.int64(0xFFFFFFFF)
            s_hi = _range_sum(hi_in, lo, hi, n)
            s_lo = _range_sum(lo_in, lo, hi, n)
            h2, l2 = _limb_norm(s_hi, s_lo)
            q = _limb_div_round(h2, l2, jnp.maximum(cnt, 1))
            return _limb_encode(q), cnt > 0
        if isinstance(call.type, T.DecimalType):
            s = _range_sum(jnp.where(contrib, data, 0), lo, hi, n)
            return _div_round_half_up(s, jnp.maximum(cnt, 1)), cnt > 0
        s = _range_sum(
            jnp.where(contrib, data.astype(jnp.float64), 0.0), lo, hi, n,
            gid=info.gid_sorted,
        )
        return s / jnp.maximum(cnt, 1), cnt > 0
    if name in ("min", "max"):
        is_min = name == "min"
        if jnp.ndim(data) == 2:
            return _range_minmax_limbs(
                data, contrib, lo, hi, info, is_min, n
            ), cnt > 0
        return _range_minmax(
            data, contrib, lo, hi, pos, pstart, info, is_min, n
        ), cnt > 0
    raise NotImplementedError(f"window function {name}")


def _const_arg(ref) -> int:
    from trino_tpu.expr.ir import Literal

    if isinstance(ref, Literal):
        return int(ref.value)
    raise NotImplementedError("window offset must be a literal")


@K.kernel
def _sorted_arg(env, ref, perm):
    if ref is None:
        return None, None
    from trino_tpu.expr.ir import Literal

    if isinstance(ref, Literal):
        n = perm.shape[0]
        if ref.value is None:
            return (
                jnp.zeros((n,), dtype=ref.type.np_dtype),
                jnp.zeros((n,), dtype=jnp.bool_),
            )
        if not isinstance(ref.value, (int, float, bool)):
            raise NotImplementedError(
                f"literal window argument {ref.value!r}"
            )
        return jnp.full((n,), ref.value, dtype=ref.type.np_dtype), None
    data, valid = env[ref.name]
    return data[perm], None if valid is None else valid[perm]


def _bound_pos(bound, pos, pstart, pend, peer_start, peer_end, mode, is_lo):
    kind, off = bound
    if kind == "unbounded_preceding":
        return pstart
    if kind == "unbounded_following":
        return pend
    if kind == "current":
        if mode == "range":
            # RANGE CURRENT ROW includes the whole peer group
            return peer_start if is_lo else peer_end
        return pos if is_lo else pos + 1
    if kind == "preceding":
        return pos - off if is_lo else pos - off + 1
    # following
    return pos + off if is_lo else pos + off + 1


@K.kernel
def _seg_searchsorted(w, target, lo0, hi0, left, n):
    """Per-row binary search of ``target[i]`` within the row's own
    sorted segment ``w[lo0[i]:hi0[i])``. ``left`` gives the first
    position with w >= target, else first with w > target. Fixed
    log-depth unrolled loop — jittable, O(n log n) gathers total."""
    lo = lo0.astype(jnp.int64)
    hi = hi0.astype(jnp.int64)
    for _ in range(max(n.bit_length(), 1)):
        cont = lo < hi
        mid = (lo + hi) // 2
        vm = w[jnp.clip(mid, 0, n - 1)]
        go = (vm < target) if left else (vm <= target)
        lo = jnp.where(cont & go, mid + 1, lo)
        hi = jnp.where(cont & ~go, mid, hi)
    return lo.astype(lo0.dtype)


@K.kernel
def _range_sum(vals, lo, hi, n, gid=None):
    """Per-row sum of vals over sorted positions [lo, hi).

    Integer sums use a global cumsum difference (exact in int64).
    Float sums with ``gid`` use a per-partition segmented scan in
    float64: a global-cumsum difference would quantize every frame at
    ulp(global running prefix), so a small partition next to a huge one
    loses all its precision (the same cross-group cancellation
    aggregates.seg_sum_ranges avoids)."""
    if gid is not None and jnp.issubdtype(vals.dtype, jnp.floating):
        acc = vals.astype(jnp.float64)

        def op(a, b):
            ga, va = a
            gb, vb = b
            return gb, jnp.where(ga == gb, va + vb, vb)

        _, cs = jax.lax.associative_scan(op, (gid, acc))
        zero = jnp.zeros((), dtype=jnp.float64)
        hi_at = jnp.clip(hi - 1, 0, n - 1)
        lo_at = jnp.clip(lo - 1, 0, n - 1)
        top = jnp.where(hi > 0, cs[hi_at], zero)
        # lo-1 belongs to the same partition iff the frame doesn't start
        # at the partition boundary (lo is clipped to pstart upstream)
        bot = jnp.where((lo > 0) & (gid[lo_at] == gid), cs[lo_at], zero)
        return jnp.where(hi > lo, top - bot, zero)
    cs = jnp.cumsum(vals)
    zero = jnp.zeros((), dtype=vals.dtype)
    hi_at = jnp.clip(hi - 1, 0, n - 1)
    lo_at = jnp.clip(lo - 1, 0, n - 1)
    top = jnp.where(hi > 0, cs[hi_at], zero)
    bot = jnp.where(lo > 0, cs[lo_at], zero)
    return jnp.where(hi > lo, top - bot, zero)


@K.kernel
def _range_minmax(data, contrib, lo, hi, pos, pstart, info, is_min, n):
    """Running min/max for prefix frames (lo == partition start):
    a segmented scan; the value at hi-1 is the frame's reduction.
    General sliding frames would need a different structure and are
    rejected at plan time by the frame checks above."""
    if is_min:
        fill = _fill_for(data.dtype, True)
    else:
        fill = _fill_for(data.dtype, False)
    masked = jnp.where(contrib, data, fill)
    red = jnp.minimum if is_min else jnp.maximum

    def op(a, b):
        ga, va = a
        gb, vb = b
        return gb, jnp.where(ga == gb, red(va, vb), vb)

    _, scan = jax.lax.associative_scan(op, (info.gid_sorted, masked))
    at = jnp.clip(hi - 1, 0, n - 1)
    return jnp.where(hi > lo, scan[at], fill)


@K.kernel
def _range_minmax_limbs(data, contrib, lo, hi, info, is_min, n):
    """Running min/max over a two-limb decimal column: numeric order is
    lexicographic (hi signed, lo canonical non-negative), so the
    segmented scan carries both limbs and picks per comparison."""
    i64 = jnp.iinfo(jnp.int64)
    fill_hi = jnp.int64(i64.max if is_min else i64.min)
    fill_lo = jnp.int64(0xFFFFFFFF if is_min else 0)
    hi_l = jnp.where(contrib, data[:, 0], fill_hi)
    lo_l = jnp.where(contrib, data[:, 1], fill_lo)

    def op(a, b):
        ga, ha, la = a
        gb, hb, lb = b
        if is_min:
            a_better = (ha < hb) | ((ha == hb) & (la < lb))
        else:
            a_better = (ha > hb) | ((ha == hb) & (la > lb))
        use_a = (ga == gb) & a_better
        return gb, jnp.where(use_a, ha, hb), jnp.where(use_a, la, lb)

    _, sh, sl = jax.lax.associative_scan(
        op, (info.gid_sorted, hi_l, lo_l)
    )
    at = jnp.clip(hi - 1, 0, n - 1)
    ok = hi > lo
    return jnp.stack(
        [jnp.where(ok, sh[at], fill_hi), jnp.where(ok, sl[at], fill_lo)],
        axis=-1,
    )


def _fill_for(dtype, is_min):
    import numpy as np

    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(np.inf if is_min else -np.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(is_min, dtype=jnp.bool_)
    iinfo = jnp.iinfo(dtype)
    return jnp.array(iinfo.max if is_min else iinfo.min, dtype=dtype)
