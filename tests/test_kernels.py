"""Unit tests for the device kernels vs numpy oracles.

The analog of the reference's operator unit tier
(core/trino-main/src/test/.../operator/, e.g. TestHashAggregationOperator):
kernels are driven directly with synthetic arrays and checked against
straightforward numpy computations.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.exec import kernels as K


def _np(x):
    return np.asarray(x)


def test_assign_groups_basic():
    keys = jnp.asarray([5, 7, 5, 9, 7, 5, 11, 9], dtype=jnp.int64)
    live = jnp.ones(8, dtype=jnp.bool_)
    bits, nulls = K.normalize_key(keys, None)
    group, owner = K.assign_groups((bits,), (nulls,), live, 16)
    g = _np(group)
    k = _np(keys)
    # same key -> same slot; different key -> different slot
    for i in range(8):
        for j in range(8):
            assert (g[i] == g[j]) == (k[i] == k[j]), (i, j)
    # every live row's slot is owned by a row with the same key
    own = _np(owner)
    occupied = own < 8
    assert occupied.sum() == len(set(k.tolist()))


def test_assign_groups_nulls_group_together():
    keys = jnp.asarray([1, 2, 1, 3], dtype=jnp.int64)
    valid = jnp.asarray([True, False, True, False])
    live = jnp.ones(4, dtype=jnp.bool_)
    bits, nulls = K.normalize_key(keys, valid)
    group, _ = K.assign_groups((bits,), (nulls,), live, 8)
    g = _np(group)
    assert g[1] == g[3]  # both NULL
    assert g[0] == g[2]
    assert g[0] != g[1]


def test_assign_groups_dead_rows_dropped():
    keys = jnp.asarray([1, 1, 2, 2], dtype=jnp.int64)
    live = jnp.asarray([True, False, True, False])
    bits, nulls = K.normalize_key(keys, None)
    group, owner = K.assign_groups((bits,), (nulls,), live, 8)
    g = _np(group)
    assert g[1] == 8 and g[3] == 8  # dead -> drop segment
    assert (_np(owner) < 4).sum() == 2


def test_sort_perm_multi_key():
    a = jnp.asarray([3, 1, 2, 1, 2], dtype=jnp.int64)
    b = jnp.asarray([9, 8, 7, 6, 5], dtype=jnp.int64)
    live = jnp.ones(5, dtype=jnp.bool_)
    perm = K.sort_perm([(a, None, True, False), (b, None, True, False)], live)
    got = list(zip(_np(a)[_np(perm)].tolist(), _np(b)[_np(perm)].tolist()))
    assert got == sorted(got)


def test_sort_perm_desc_and_nulls():
    a = jnp.asarray([3, 1, 2, 5], dtype=jnp.int64)
    valid = jnp.asarray([True, True, False, True])
    live = jnp.ones(4, dtype=jnp.bool_)
    # DESC with default nulls-first (nulls treated as largest)
    perm = _np(K.sort_perm([(a, valid, False, True)], live))
    assert perm.tolist()[0] == 2  # null first
    assert _np(a)[perm[1:]].tolist() == [5, 3, 1]


def test_sort_perm_dead_rows_last():
    a = jnp.asarray([4, 3, 2, 1], dtype=jnp.int64)
    live = jnp.asarray([True, False, True, False])
    perm = _np(K.sort_perm([(a, None, True, False)], live))
    assert set(perm[:2].tolist()) == {0, 2}
    assert _np(a)[perm[:2]].tolist() == [2, 4]


def test_join_ranges_and_expand():
    build = jnp.asarray([10, 20, 10, 30, 99], dtype=jnp.uint64)
    build_live = jnp.asarray([True, True, True, True, False])
    probe = jnp.asarray([10, 30, 40, 10], dtype=jnp.uint64)
    probe_live = jnp.asarray([True, True, True, False])
    order, lo, cnt = K.join_ranges(build, build_live, probe, probe_live)
    assert _np(cnt).tolist() == [2, 1, 0, 0]
    probe_idx, build_idx, out_live = K.expand_matches(order, lo, cnt, 8)
    pairs = {
        (int(p), int(b))
        for p, b, l in zip(_np(probe_idx), _np(build_idx), _np(out_live))
        if l
    }
    assert pairs == {(0, 0), (0, 2), (1, 3)}


def test_join_ranges_dead_build_key_not_matched():
    # the dead build row's key must not satisfy probes even when it
    # equals a probe key (regression: sorted-tail keys must be pinned)
    build = jnp.asarray([0xFFFFFFFFFFFFFFFF, 5], dtype=jnp.uint64)
    build_live = jnp.asarray([False, True])
    probe = jnp.asarray([0xFFFFFFFFFFFFFFFF, 5], dtype=jnp.uint64)
    probe_live = jnp.asarray([True, True])
    _, _, cnt = K.join_ranges(build, build_live, probe, probe_live)
    assert _np(cnt).tolist() == [0, 1]


def test_hash_columns_null_vs_zero():
    data = jnp.asarray([0, 0], dtype=jnp.int64)
    valid = jnp.asarray([True, False])
    h = _np(K.hash_columns([(data, valid)]))
    assert h[0] != h[1]  # NULL hashes differently from 0


def test_sort_perm_desc_float_nan_first():
    # reference treats NaN as largest: last for ASC, first for DESC
    data = jnp.asarray([1.5, float("nan"), -2.0, 0.0, float("inf")],
                       dtype=jnp.float64)
    live = jnp.ones(5, dtype=jnp.bool_)
    perm = K.sort_perm([(data, None, False, False)], live)
    got = _np(data)[_np(perm)]
    assert np.isnan(got[0])
    assert got[1] == np.inf and got[2] == 1.5 and got[3] == 0.0
    perm_asc = K.sort_perm([(data, None, True, False)], live)
    got_asc = _np(data)[_np(perm_asc)]
    assert np.isnan(got_asc[-1]) and got_asc[0] == -2.0


def test_sort_perm_negative_zero_equals_zero():
    data = jnp.asarray([-0.0, 3.0, 0.0, -1.0], dtype=jnp.float64)
    tie = jnp.asarray([9, 0, 1, 0], dtype=jnp.int64)
    live = jnp.ones(4, dtype=jnp.bool_)
    # primary key has -0.0 == 0.0; secondary breaks the tie
    perm = K.sort_perm(
        [(data, None, True, False), (tie, None, True, False)], live
    )
    got_tie = _np(tie)[_np(perm)]
    assert got_tie.tolist() == [0, 1, 9, 0]


def test_normalize_key_float_canonicalization():
    a = jnp.asarray([-0.0, float("nan")], dtype=jnp.float64)
    b = jnp.asarray([0.0, float("nan")], dtype=jnp.float64)
    ba, _ = K.normalize_key(a, None)
    bb, _ = K.normalize_key(b, None)
    assert _np(ba == bb).all()


# ---- packed single-operand sorts (PR 22) ------------------------------------
# numpy is the loop-free reference: the arithmetic is integer and
# unchanged, so results must be EQUAL, not close.


@pytest.mark.parametrize("n", [7, 1000, 70_000])
@pytest.mark.parametrize("bits,use_last", [
    (0, True), (5, False), (5, True), (31, False), (31, True),
    (40, False), (40, True), (64, False), (64, True),
])
def test_packed_argsort_is_a_stable_argsort(n, bits, use_last):
    rng = np.random.default_rng(n + bits)
    if bits == 0:
        key = None
    elif bits == 64:
        key = rng.integers(
            -(1 << 62), 1 << 62, n, dtype=np.int64
        ).astype(np.uint64)
    else:
        key = rng.integers(0, 1 << min(bits, 62), n, dtype=np.int64).astype(
            np.uint64
        )
    last = rng.random(n) < 0.3 if use_last else None
    got = _np(K.packed_argsort(
        None if key is None else jnp.asarray(key), bits,
        None if last is None else jnp.asarray(last),
    ))
    want = np.lexsort((
        np.arange(n),
        np.zeros(n, np.uint64) if key is None else key,
        np.zeros(n, bool) if last is None else last,
    ))
    assert (got == want).all()


def test_compact_perm_keeps_row_order():
    mask = np.array([0, 1, 1, 0, 1, 0, 0, 1], dtype=bool)
    assert _np(K.compact_perm(jnp.asarray(mask))).tolist() == [
        1, 2, 4, 7, 0, 3, 5, 6,
    ]


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.uint64, np.float64])
@pytest.mark.parametrize("side", ["left", "right"])
def test_searchsorted_merge_rank_matches_numpy(dtype, side):
    rng = np.random.default_rng(3)
    lo = 0 if dtype == np.uint64 else -1000
    a = np.sort(rng.integers(lo, 1000, 50_000).astype(dtype))
    # > 16384 queries takes the merged single-operand sort
    v = rng.integers(lo, 1100, 40_000).astype(dtype)
    got = _np(K.searchsorted(jnp.asarray(a), jnp.asarray(v), side))
    assert (got == np.searchsorted(a, v, side)).all()


@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_blocked_cumsum_is_exact(dtype):
    rng = np.random.default_rng(4)
    x = rng.integers(-(1 << 30), 1 << 30, 2048 * 512).astype(dtype)
    got = _np(K.cumsum(jnp.asarray(x)))
    assert got.dtype == x.dtype and (got == np.cumsum(x, dtype=dtype)).all()


def test_floor_div_matches_python_floor_division():
    rng = np.random.default_rng(5)
    a = rng.integers(-(1 << 62), 1 << 62, 5000, dtype=np.int64)
    b = rng.integers(1, 1 << 31, 5000, dtype=np.int64)
    a[:6] = [0, -1, 1, np.iinfo(np.int64).min, np.iinfo(np.int64).max, -7]
    b[:6] = [1, 1, 3, 1, 2, 7]
    got = _np(K.floor_div(jnp.asarray(a), jnp.asarray(b)))
    assert (got == a // b).all()


def test_join_ranges_brute_force():
    rng = np.random.default_rng(6)
    nb, n_p = 5000, 30_000
    top = np.uint64(0xFFFFFFFFFFFFFFFF)  # a LIVE key may be the max word
    bk = rng.integers(0, 800, nb).astype(np.uint64)
    bk[:5] = top
    bl = rng.random(nb) < 0.8
    pk = rng.integers(0, 900, n_p).astype(np.uint64)
    pk[:7] = top
    pl = rng.random(n_p) < 0.9
    order, lo, cnt = map(_np, K.join_ranges(
        jnp.asarray(bk), jnp.asarray(bl), jnp.asarray(pk), jnp.asarray(pl)
    ))
    n_live = int(bl.sum())
    assert bl[order][:n_live].all() and not bl[order][n_live:].any()
    assert (np.diff(bk[order][:n_live].astype(np.float64)) >= 0).all()
    for i in range(0, n_p, 37):
        want = int(((bk == pk[i]) & bl).sum()) if pl[i] else 0
        assert cnt[i] == want
        assert (bk[order[lo[i]:lo[i] + cnt[i]]] == pk[i]).all()


# ---- join_ranges: the two searches, bit for bit (PR 42) -------------------

_TOP = np.uint64(0xFFFFFFFFFFFFFFFF)


def _join_ranges_ref(bk, bl, pk, pl):
    """``join_ranges`` in numpy: the build argsorted by (dead, key, row),
    its dead tail pinned to the top word, each probe's left and right
    edge clamped to the live prefix."""
    order = np.lexsort((bk, ~bl))
    n_live = int(bl.sum())
    sk = np.where(np.arange(len(bk)) < n_live, bk[order], _TOP)
    lo = np.minimum(np.searchsorted(sk, pk, "left"), n_live)
    hi = np.minimum(np.searchsorted(sk, pk, "right"), n_live)
    return (order.astype(np.int32), lo.astype(np.int32),
            np.where(pl, hi - lo, 0).astype(np.int32))


def _join_case(case: str, nb: int, rng):
    n_p = 20_000 if case == "large_probe" else 700  # > 16,384: _merge_rank
    bk = rng.integers(0, 3 * nb + 3, nb).astype(np.uint64)
    pk = rng.integers(0, 3 * nb + 6, n_p).astype(np.uint64)
    bl, pl = np.ones(nb, bool), np.ones(n_p, bool)
    if case == "duplicate_build_keys":
        bk = rng.integers(0, max(nb // 8, 2), nb).astype(np.uint64)
        pk = rng.integers(0, max(nb // 8, 2) + 2, n_p).astype(np.uint64)
    elif case == "dead_build_rows":
        bl = rng.random(nb) < 0.6
        bk[~bl] = rng.choice(pk, int((~bl).sum()))  # dead keys that "match"
    elif case == "dead_probe_rows":
        pl = rng.random(n_p) < 0.5
    elif case == "no_live_build_row":
        bl[:] = False
    elif case == "keys_0_and_top":
        bk[: nb // 2] = rng.choice([np.uint64(0), _TOP], nb // 2)
        bl = rng.random(nb) < 0.8  # live and dead rows hold the top word
        pk[:40] = rng.choice([np.uint64(0), _TOP], 40)
    else:
        bl, pl = rng.random(nb) < 0.9, rng.random(n_p) < 0.9
    return bk, bl, pk, pl


@pytest.mark.parametrize("cap", [
    pytest.param(lambda limit: 1, id="1"),
    pytest.param(lambda limit: 64, id="64"),
    pytest.param(lambda limit: limit, id="JOIN_SMALL_BUILD"),
    pytest.param(lambda limit: limit + 1, id="JOIN_SMALL_BUILD+1"),
    pytest.param(lambda limit: 2 * limit, id="2xJOIN_SMALL_BUILD"),
])
@pytest.mark.parametrize("case", [
    "duplicate_build_keys", "dead_build_rows", "dead_probe_rows",
    "no_live_build_row", "keys_0_and_top", "large_probe",
])
def test_join_ranges_two_searches_agree_with_numpy(case, cap, monkeypatch):
    nb = cap(K.JOIN_SMALL_BUILD)
    rng = np.random.default_rng(nb * 7 + len(case))
    bk, bl, pk, pl = _join_case(case, nb, rng)
    args = tuple(jnp.asarray(x) for x in (bk, bl, pk, pl))
    want = _join_ranges_ref(bk, bl, pk, pl)
    got = {"chosen": tuple(map(_np, K.join_ranges(*args)))}
    # the choice reads the build's capacity, and it shows in the program
    scatters = "scatter" in str(jax.make_jaxpr(K.join_ranges)(*args))
    assert K.join_search(nb) == (
        "count" if nb <= K.JOIN_SMALL_BUILD else "sort")
    assert scatters == (K.join_search(nb) == "sort" and len(pk) > 16384)
    for search, limit in (("count", 1 << 30), ("sort", -1)):
        monkeypatch.setattr(K, "JOIN_SMALL_BUILD", limit)
        got[search] = tuple(map(_np, jax.jit(K.join_ranges.__wrapped__)(*args)))
    for name, (order, lo, cnt) in got.items():
        for what, a, b in zip(("order", "lo", "cnt"), (order, lo, cnt), want):
            assert a.dtype == b.dtype and np.array_equal(a, b), (name, what)


# ---- join_ranges at the width the plan proves (ISSUE 46) ------------------


def _narrow_join_case(case: str, key_bits: int, nb: int, n_p: int, rng):
    """Keys whose LIVE values fit ``key_bits`` bits; dead rows hold
    whatever the case says."""
    top = (1 << key_bits) - 1
    span = min(top, 3 * nb) + 1
    bk = rng.integers(0, span, nb).astype(np.uint64)
    pk = rng.integers(0, span, n_p).astype(np.uint64)
    bl, pl = rng.random(nb) < 0.9, rng.random(n_p) < 0.9
    if case == "duplicate_build_keys":
        bk = rng.integers(0, min(span, max(nb // 8, 2)), nb).astype(np.uint64)
    elif case == "absent_probe_keys":
        bk &= ~np.uint64(1)  # even build keys: every odd probe is absent
        bk[: nb // 2] = 0
    elif case == "live_key_is_the_top_of_the_width":
        bk[::5], pk[::3] = top, top  # live and dead rows alike
    elif case == "dead_rows_out_of_range":
        # what a Filter's dead rows and a shifted key's wrap leave: real
        # values past the width, whose low bits "match" live keys
        high = np.uint64(1) << np.uint64(min(key_bits, 63))
        bk[~bl] = (rng.choice(pk[pl], int((~bl).sum())) | high) + (
            high if key_bits < 63 else np.uint64(0))
        pk[~pl] = _TOP - rng.integers(0, 9, int((~pl).sum())).astype(np.uint64)
    elif case == "empty_live_build":
        bl[:] = False
    return bk, bl, pk, pl


@pytest.mark.parametrize("key_bits", [1, 23, 31, 32, 33, 40, 63, 64])
@pytest.mark.parametrize("search", ["count", "sort"])
@pytest.mark.parametrize("case", [
    "duplicate_build_keys", "absent_probe_keys",
    "live_key_is_the_top_of_the_width", "dead_rows_out_of_range",
    "empty_live_build",
])
def test_join_ranges_at_a_proven_key_width(case, search, key_bits):
    """``join_ranges(..., key_bits=k)`` on keys whose live values fit
    ``k`` bits returns on live rows the three arrays ``key_bits=64``
    does, bit for bit, under both searches — whatever dead rows hold,
    with a live key equal to ``2**k - 1`` (the dead tail's sentinel at
    that width) and with no live build row at all."""
    nb = 1500 if search == "count" else K.JOIN_SMALL_BUILD + 904
    assert K.join_search(nb) == search
    # above 16,384 probes the sort search is the merged rank
    n_p = 700 if search == "count" else 20_000
    rng = np.random.default_rng(key_bits * 131 + len(case) + nb)
    bk, bl, pk, pl = _narrow_join_case(case, key_bits, nb, n_p, rng)
    args = tuple(jnp.asarray(x) for x in (bk, bl, pk, pl))
    want = tuple(map(_np, K.join_ranges(*args)))
    assert all(np.array_equal(a, b) for a, b in zip(
        want, _join_ranges_ref(bk, bl, pk, pl)))
    order, lo, cnt = map(_np, K.join_ranges(*args, key_bits=key_bits))
    n_live = int(bl.sum())
    assert order.dtype == lo.dtype == cnt.dtype == np.int32
    assert np.array_equal(order[:n_live], want[0][:n_live])
    assert sorted(order[n_live:]) == sorted(want[0][n_live:])
    assert np.array_equal(lo[pl], want[1][pl])
    assert np.array_equal(cnt, want[2])  # a dead probe counts 0 in both


def test_join_ranges_at_q3s_width_sorts_once_a_packed_argsort():
    """Q3's ``lineitem`` join at SF1 — 4,194,304 probe rows merged with
    a build of 262,144, keys of 23 bits: key and row index share one
    word, so the build's sort and the merged rank's are ONE
    single-operand sort each, and the LSD radix's second sort, the
    gather between the two and the composed permutation are not in the
    program; at 64 bits they are."""
    n, b = 4_194_304, 262_144
    avals = [
        jax.ShapeDtypeStruct(shape, dt) for shape, dt in (
            ((b,), jnp.uint64), ((b,), jnp.bool_),
            ((n,), jnp.uint64), ((n,), jnp.bool_))
    ]
    texts = {
        bits: K.join_ranges.lower(*avals, key_bits=bits).as_text(
            debug_info=True)
        for bits in (23, 64)
    }
    sorts = {bits: t.count("stablehlo.sort") for bits, t in texts.items()}
    assert sorts == {23: 2, 64: 4}
    for scope in ("s:gather_high", "s:sort_high", "s:compose", "s:sort_low"):
        assert scope in texts[64]
        assert scope not in texts[23]
    # the merged words: 23 bits of key over 23 of index, one uint64
    assert f"tensor<{n + b}xui64>" in texts[23]
    # and nothing probe-sized is read as two halves any more
    assert f"tensor<{n}x2xui32>" in texts[23]
    assert f"tensor<{n}x3xui32>" in texts[64]


# ---- reads at one index vector ride one walk of words (ISSUE 44) ----------


@pytest.mark.parametrize("case", [
    "probe_above_every_build_key", "probe_below_every_build_key",
    "probe_is_the_top_word", "duplicate_build_keys", "dead_build_tail",
    "empty_live_build", "dead_probe_rows",
])
@pytest.mark.parametrize("n_probe", [700, 20_000], ids=["scan", "merge_rank"])
def test_join_ranges_sort_branch_reads_key_and_run_end_in_one_walk(
        case, n_probe, monkeypatch):
    """The sort branch's ``[at]`` reads — the build key and the end of
    its run — are one gather of ``[probe, 3]`` uint32 words, the key
    compared as halves: ``lo`` and ``cnt`` are numpy's ``searchsorted``
    left and right, clamped to the live build."""
    monkeypatch.setattr(K, "JOIN_SMALL_BUILD", -1)  # every build sorts
    rng = np.random.default_rng(len(case) + n_probe)
    nb = 300
    bk = (rng.integers(1000, 2000, nb) << 33).astype(np.uint64)  # both halves
    bk += rng.integers(0, 3, nb).astype(np.uint64)
    pk = rng.choice(bk, n_probe)
    pk[::3] += np.uint64(1)  # a near miss in the low half
    pk[1::7] ^= np.uint64(1 << 40)  # and one in the high half
    bl, pl = np.ones(nb, bool), np.ones(n_probe, bool)
    if case == "probe_above_every_build_key":
        pk[:50] = bk.max() + np.uint64(5)  # lo == n_build: ``at`` is clipped
    elif case == "probe_below_every_build_key":
        pk[:50] = np.uint64(7)
    elif case == "probe_is_the_top_word":
        pk[:50] = _TOP
        bk[:3] = _TOP  # live
        bl[-40:] = False  # and a dead tail pinned to the same word
    elif case == "duplicate_build_keys":
        bk = rng.choice(bk[:20], nb)
        pk = rng.choice(bk, n_probe)
    elif case == "dead_build_tail":
        bl = rng.random(nb) < 0.5
        pk[:200] = rng.choice(bk[~bl], 200)  # keys only dead rows hold
    elif case == "empty_live_build":
        bl[:] = False
    elif case == "dead_probe_rows":
        pl = rng.random(n_probe) < 0.5
    args = tuple(jnp.asarray(x) for x in (bk, bl, pk, pl))
    fn = jax.jit(K.join_ranges.__wrapped__)
    got = tuple(map(_np, fn(*args)))
    for what, a, b in zip(("order", "lo", "cnt"), got,
                          _join_ranges_ref(bk, bl, pk, pl)):
        assert a.dtype == b.dtype and np.array_equal(a, b), what
    probe_sized = [
        e.outvars[0].aval
        for e in jax.make_jaxpr(K.join_ranges.__wrapped__)(*args).eqns
        if e.primitive.name == "gather"
        and e.outvars[0].aval.shape[:1] == (n_probe,)
    ]
    assert [(a.shape, a.dtype) for a in probe_sized] == [
        ((n_probe, 3), jnp.uint32)]


def _start_walk_case(case: str, rng):
    """(gid_sorted, starts, ends, owner, n_live, n) of contiguous runs."""
    n, cap = 96, 48  # runs of 1-5 rows: at most 71 groups, some 24
    if case == "capacity_above_rows":
        cap = 160
    n_live = {"all_live": n, "no_live_row": 0}.get(case, 71)
    lens = []
    while sum(lens) < n_live:
        lens.append(int(rng.integers(1, 6)))
    if lens:
        lens[-1] -= sum(lens) - n_live
    if case == "overflow":
        lens = [1] * n_live  # 71 groups in 48 slots
    starts_all = np.cumsum([0] + lens[:-1]).astype(np.int32)
    g = len(lens)
    used = min(g, cap)
    starts = np.full(cap, n_live, np.int32)
    starts[:used] = starts_all[:used]
    ends = np.concatenate([starts[1:], [n_live]]).astype(np.int32)
    owner = np.full(cap, n, np.int32)
    owner[:used] = starts_all[:used]
    gid = np.minimum(np.repeat(np.arange(g), lens), cap)
    gid = np.concatenate([gid, np.full(n - n_live, cap)]).astype(np.int32)
    return gid, starts, ends, owner, n_live, g


@pytest.mark.parametrize("case", [
    "dead_tail", "all_live", "capacity_above_rows", "no_live_row",
    "overflow",
])
def test_start_walk_sums_and_keys_at_one_vector(case):
    """``kernels.start_walk`` over rows grouped in place: sums of int64
    and int32 columns as numpy's per-run sums (wrapping like int64),
    the keys — a nullable int64, a bool, an int16 — as ``data[owner]``
    bit for bit, in ``gather_plan``'s gathers and none of them
    64-bit."""
    rng = np.random.default_rng(len(case))
    gid, starts, ends, owner, n_live, g = _start_walk_case(case, rng)
    n, cap = len(gid), len(starts)
    info = K.GroupInfo(
        None, jnp.asarray(gid), jnp.asarray(gid), jnp.asarray(starts),
        jnp.asarray(ends), jnp.asarray(owner), jnp.int32(g))
    live = np.arange(n) < n_live
    sums = [
        np.where(live, rng.integers(-(1 << 62), 1 << 62, n), 0),
        np.where(live, rng.integers(0, 1 << 32, n), 0),
        np.where(live, rng.integers(-9, 9, n), 0).astype(np.int32),
    ]
    keys = {
        "k": (rng.integers(-(1 << 62), 1 << 62, n), rng.random(n) < 0.7),
        "b": (rng.random(n) < 0.5, None),
        "h": (rng.integers(-300, 300, n).astype(np.int16), None),
    }
    dev = lambda x: None if x is None else jnp.asarray(x)  # noqa: E731

    def walk(sums, keys):
        return K.start_walk(info, sums, keys)

    args = ([dev(s) for s in sums],
            {s: (dev(d), dev(v)) for s, (d, v) in keys.items()})
    got_sums, got_keys = jax.jit(walk)(*args)
    for vals, got in zip(sums, got_sums):
        want = np.zeros(cap, vals.dtype)
        with np.errstate(over="ignore"):
            for slot in range(cap):
                if ends[slot] > starts[slot]:
                    want[slot] = vals[starts[slot]:ends[slot]].sum(
                        dtype=vals.dtype)
        assert got.dtype == want.dtype and np.array_equal(_np(got), want)
    at = np.clip(owner, 0, n - 1)
    for s, (d, v) in keys.items():
        gd, gv = got_keys[s]
        assert gd.dtype == d.dtype and np.array_equal(_np(gd), d[at]), s
        assert (gv is None) == (v is None)
        if v is not None:
            assert np.array_equal(_np(gv), v[at]), s
    sized = [
        e.outvars[0].aval for e in jax.make_jaxpr(walk)(*args).eqns
        if e.primitive.name == "gather"
        and e.outvars[0].aval.shape[:1] == (cap,)
    ]
    # 2 + 2 + 1 words of sums, 2 + 1 + 1 of keys, one of validity bits
    assert len(sized) == K.gather_plan(
        [(s.dtype, (), False) for s in sums]
        + [(d.dtype, (), v is not None) for d, v in keys.values()]
    )[1] == 3
    assert all(a.dtype == jnp.uint32 for a in sized), sized


def test_seg_sum_ranges_is_a_walk_of_one_column():
    """The kernel's lone entry point: an integer column is a
    ``start_walk`` of itself — a word view, not a 64-bit gather — and a
    float column keeps its segmented scan."""
    rng = np.random.default_rng(5)
    gid, starts, ends, owner, n_live, g = _start_walk_case("dead_tail", rng)
    info = K.GroupInfo(
        None, jnp.asarray(gid), jnp.asarray(gid), jnp.asarray(starts),
        jnp.asarray(ends), jnp.asarray(owner), jnp.int32(g))
    vals = np.where(np.arange(len(gid)) < n_live,
                    rng.integers(-(1 << 40), 1 << 40, len(gid)), 0)
    want = np.array([vals[a:b].sum() for a, b in zip(starts, ends)])
    got = K.seg_sum_ranges(jnp.asarray(vals), info)
    assert np.array_equal(_np(got), want)
    fgot = K.seg_sum_ranges(jnp.asarray(vals.astype(np.float64)), info)
    np.testing.assert_allclose(_np(fgot), want.astype(np.float64))
    jaxpr = jax.make_jaxpr(lambda v: K.seg_sum_ranges(v, info))(
        jnp.asarray(vals))
    out = [e.outvars[0].aval for e in jaxpr.eqns
           if e.primitive.name == "gather"
           and e.outvars[0].aval.shape[:1] == (len(starts),)]
    assert [(a.shape, a.dtype) for a in out] == [
        ((len(starts), 2), jnp.uint32)]
