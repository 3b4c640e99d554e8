"""Device kernels for relational operators.

The TPU-native replacements for the reference's hot loops
(SURVEY.md §3.3): instead of per-row probe loops (FlatHash.putIfAbsent,
MAIN/operator/FlatHash.java:190; JoinProbe per-row lookup,
MAIN/operator/join/JoinProbe.java:27), everything here is a
whole-column computation XLA can tile onto the MXU/VPU:

- ``sort_group``: group-by key -> dense group ids by one packed sort
  (the FlatHash analog: exact, no probing, no scatter); ``seg_*``
  reduce each group's contiguous run of the sorted order.
- ``slot_group`` / ``slot_reduce``: group-by over a key domain of at
  most ``2**SLOT_KEY_BITS`` packed values needs neither sort nor
  scatter — the packed key word IS the group's slot, and an aggregate
  is one dense masked reduction per slot.
- ``run_group``: group-by over rows that already arrive in key order
  (a connector's declared sort order) — the runs are the groups, in
  place: ``sort_group``'s contract with the identity as the
  permutation, and a device check that the order holds.
- ``join_expand``: equi-join via sort + searchsorted range expansion
  (the PagesHash/LookupSource analog, MAIN/operator/join/PagesHash.java:19).
- ``sort_perm``: multi-key order-by via iterated stable argsort
  (the PagesIndex sort analog, MAIN/operator/OrderByOperator.java).

All shapes are static; data-dependent sizes are carried as masks, and
the only host syncs are capacity decisions at operator boundaries.
"""

from __future__ import annotations

import math
from functools import partial, wraps
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import telemetry

# this module holds the engine's jitted device kernels: make sure every
# backend compile they trigger lands in trino_xla_compile_total before
# the first jit call anywhere in the process
telemetry.install_jax_compile_hook()

# ---- scopes: what a device trace can say of a program's inside --------------
#
# ``jax.named_scope`` writes one component into the ``op_name`` of every
# HLO instruction traced under it, which the device trace hands back on
# each ``XLA Ops`` event's metadata (``tf_op``): nothing is added to a
# program but the name. One grammar, static strings only (a name holds
# node types, kernel names and site names, never a literal, a capacity
# or a hash):
#
#   op<i>:<NodeType>  a chain's position        (stage.build_chain)
#   op:<NodeType>     a program that is one operator (local._named_jit)
#   k:<kernel>        an entry point of this module or of aggregates.py
#   s:<site>          one sort, gather or scatter inside a kernel
#
# ``benchmarks/readers/trace_scopes.py`` and ``kernel_profile.attribute``
# read them: the operator of an instruction is its ``op`` component's
# node type, its kernel the innermost ``k:``, its site the innermost
# ``s:``.


def kernel(fn):
    """``fn`` under the scope ``k:<fn's name>`` (below ``jax.jit``, where
    the kernel is one)."""
    scope = "k:" + fn.__name__.lstrip("_")

    @wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)

    return scoped


def site(name: str):
    """``with site("compose"):`` — the scope ``s:compose``."""
    return jax.named_scope("s:" + name)


__all__ = [
    "kernel",
    "site",
    "hash_columns",
    "packed_argsort",
    "compact_perm",
    "GATHER_STACK_WORDS",
    "GATHER_WIDE_WORDS",
    "gather_plan",
    "gather_rows",
    "rows_at",
    "keys_match",
    "compact_rows",
    "slice_rows",
    "cumsum",
    "floor_div",
    "searchsorted",
    "normalize_key",
    "GroupInfo",
    "sort_group",
    "run_group",
    "SLOT_KEY_BITS",
    "SlotInfo",
    "slot_key_bits",
    "slot_group",
    "slot_reduce",
    "assign_groups",
    "sort_perm",
    "JOIN_SMALL_BUILD",
    "join_search",
    "join_ranges",
    "expand_matches",
    "range_any",
    "scatter_any",
    "start_walk",
    "seg_sum_ranges",
    "seg_minmax_scan",
    "seg_first_index",
]


# ---- hashing ---------------------------------------------------------------

_MIX_1 = np.uint64(0xFF51AFD7ED558CCD)
_MIX_2 = np.uint64(0xC4CEB9FE1A85EC53)
_NULL_SALT = np.uint64(0x9E3779B97F4A7C15)


def _mix64(h: jnp.ndarray) -> jnp.ndarray:
    """splitmix64-style finalizer (wraparound uint64 math)."""
    h = h ^ (h >> 33)
    h = h * _MIX_1
    h = h ^ (h >> 33)
    h = h * _MIX_2
    h = h ^ (h >> 33)
    return h


def _canonical_float(data: jnp.ndarray) -> jnp.ndarray:
    """Canonicalize float values so equal keys have equal bits:
    -0.0 -> +0.0, every NaN payload -> the canonical quiet NaN
    (grouping/join semantics treat NaN as one value, reference
    TypeOperators equality)."""
    data = jnp.where(data == 0.0, jnp.zeros_like(data), data)
    return jnp.where(jnp.isnan(data), jnp.full_like(data, jnp.nan), data)


def _to_bits(data: jnp.ndarray) -> jnp.ndarray:
    """Reinterpret a key column as uint64 bits (floats canonicalized)."""
    if data.dtype == jnp.float64:
        return jax.lax.bitcast_convert_type(_canonical_float(data), jnp.uint64)
    if data.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(
            _canonical_float(data), jnp.uint32
        ).astype(jnp.uint64)
    if data.dtype == jnp.bool_:
        return data.astype(jnp.uint64)
    return data.astype(jnp.uint64)


# ---- sorting primitives ------------------------------------------------------
#
# XLA:TPU has one cheap sort: a SINGLE-operand, unstable ``lax.sort``.
# AOT compiles for a v5e at 6,291,456 rows (PR 22, tools/aot_probe.py):
# one uint32 operand 6 s, one uint64 operand 24 s — against 36-57 s for
# a stable (key, index) pair and 117-147 s for three operands, per sort
# instance, in every program that holds one. So every sort here is a
# single-operand sort of WORDS MADE UNIQUE by packing the row index
# into the low bits: unique words make an unstable sort deterministic,
# the packed index makes it stable, and the permutation falls out of
# the low bits with no payload operand.


def _idx_bits(n: int) -> int:
    return max(1, (max(n, 1) - 1).bit_length())


@kernel
def packed_argsort(
    key: jnp.ndarray | None, key_bits: int, last: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Stable ascending argsort (int32 permutation) of unsigned keys
    whose values fit in ``key_bits`` bits (``key=None``/0 bits: no key).
    Rows flagged in ``last`` sort after every other row, stably.

    One single-operand sort when key, flag and row index fit one word
    (uint32 if they fit 32 bits); otherwise an LSD radix over the two
    32-bit halves of the key — two single-operand uint64 sorts."""
    n = (last if key is None else key).shape[0]
    ib = _idx_bits(n)
    extra = 0 if last is None else 1
    if key_bits + ib + extra <= 64:
        wt = jnp.uint32 if key_bits + ib + extra <= 32 else jnp.uint64
        w = jnp.arange(n, dtype=wt)
        if key_bits:
            k = key.astype(wt) & wt((1 << key_bits) - 1)
            w = w | (k << wt(ib))
        if last is not None:
            w = w | (last.astype(wt) << wt(key_bits + ib))
        ws = jax.lax.sort(w, is_stable=False)
        return (ws & wt((1 << ib) - 1)).astype(jnp.int32)
    if n >= 1 << 31:
        raise ValueError(f"packed_argsort: {n} rows need a wider index")
    key = key.astype(jnp.uint64)
    with site("sort_low"):
        p1 = packed_argsort(key & jnp.uint64(0xFFFFFFFF), 32)
    with site("gather_high"):
        high = (key >> jnp.uint64(32))[p1]
        last1 = None if last is None else last[p1]
    with site("sort_high"):
        p2 = packed_argsort(high, 32, last1)
    with site("compose"):
        return p1[p2]


@kernel
def compact_perm(mask: jnp.ndarray) -> jnp.ndarray:
    """Permutation gathering live rows to the front, in row order (dead
    rows after them, in row order)."""
    return packed_argsort(None, 0, last=~mask)


# ---- row gathers -------------------------------------------------------------
#
# What a gather costs on the chip is its index walk, not the width of
# what an index fetches, and a ``pred`` gather costs more than an int32
# one. So a page is read at one index vector in as few gathers as the
# table below allows: every column viewed as 32-bit words, the words of
# all columns side by side in one ``[rows, W]`` uint32 operand, the
# validity lanes as bits of one more word. Words are moved, never
# converted: the result is ``d[idx]``, ``v[idx]`` bit for bit.

#: most 32-bit words one gather operand holds; a wider page is read in
#: several stacks of at most this many. On a v5e, 4,194,304 positions
#: out of 6,291,456 rows: a stack of 1 / 2 / 4 / 7 / 8 words takes
#: 36.9 / 26.3 / 26.4 / 86.6 / 86.5 ms (an int64 column alone 67.0, a
#: ``pred`` 41.4), and the whole compaction of Q3's seven words 60.3 ms
#: at 4 against 92.7 at 8, 91.4 at 2 and 329.0 a column
#: (tools/groupby_crossover.py --shape q3compact; PERF.md, PR 36): up
#: to four words ride one index walk, two stacks of four beat one of
#: eight. Inside a program a lone int64 read of a row-sized column
#: cost 33-65 ms at 2,097,152 positions of 6.29 M rows and 493-511 ms
#: at 8,388,608 of 33.5 M (Q18's streamed step, PERF.md, PR 43); its
#: three such reads as one walk of six words (stacks of 4 + 2) cost
#: 21.8 ms in the program at the first shape and, timed alone, 24.0
#: and 265.7 ms where the three lone reads took 100.8 and 1,057.3
#: (--shape startwalk; PERF.md, PR 44).
GATHER_STACK_WORDS = 4

#: a column whose row is wider than this many words (an HLL or sketch
#: state, an array pool's wide lanes) is gathered alone, as it is:
#: stacking it would copy megabytes to save one index walk
GATHER_WIDE_WORDS = 8


def _col_words(dtype, lanes) -> int:
    """32-bit words one row of a ``[rows, *lanes]`` column holds."""
    return math.prod(lanes) * max(1, np.dtype(dtype).itemsize // 4)


def gather_plan(sig) -> tuple[int, int]:
    """(words stacked, gathers) of a page whose columns are ``(dtype,
    lanes, nullable)``: what ``gather_rows`` builds, from the layout
    alone."""
    stacked = alone = nullable = 0
    for dtype, lanes, has_valid in sig:
        w = _col_words(dtype, lanes)
        if w > GATHER_WIDE_WORDS:
            alone += 1
        else:
            stacked += w
        nullable += bool(has_valid)
    stacked += -(-nullable // 32)
    return stacked, alone + -(-stacked // GATHER_STACK_WORDS)


def _to_words(d: jnp.ndarray) -> jnp.ndarray:
    """``[rows, *lanes]`` of any fixed-width dtype as ``[rows, W]``
    uint32 words, exactly."""
    if d.dtype == jnp.bool_:
        w = d.astype(jnp.uint32)
    elif d.dtype.itemsize >= 4:
        w = jax.lax.bitcast_convert_type(d, jnp.uint32)
    else:
        narrow = jnp.uint8 if d.dtype.itemsize == 1 else jnp.uint16
        w = jax.lax.bitcast_convert_type(d, narrow).astype(jnp.uint32)
    return w.reshape(d.shape[0], _col_words(d.dtype, d.shape[1:]))


def _from_words(w: jnp.ndarray, dtype, lanes) -> jnp.ndarray:
    """``_to_words`` undone: ``[n, W]`` words as ``[n, *lanes]``."""
    dtype = np.dtype(dtype)
    n = w.shape[0]
    if dtype == np.bool_:
        return (w != 0).reshape((n, *lanes))
    if dtype.itemsize == 8:
        return jax.lax.bitcast_convert_type(w.reshape((n, *lanes, 2)), dtype)
    if dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(w.reshape((n, *lanes)), dtype)
    narrow = jnp.uint8 if dtype.itemsize == 1 else jnp.uint16
    return jax.lax.bitcast_convert_type(
        w.astype(narrow).reshape((n, *lanes)), dtype
    )


@kernel
def gather_rows(env: dict, idx: jnp.ndarray) -> dict:
    """``{name: (d[idx], v[idx])}`` of a page's ``{name: (data,
    valid)}`` in ``gather_plan``'s number of gathers."""
    width = {n: _col_words(d.dtype, d.shape[1:]) for n, (d, _) in env.items()}
    parts = [
        _to_words(d) for n, (d, _) in env.items()
        if width[n] <= GATHER_WIDE_WORDS
    ]
    valid_at = sum(p.shape[1] for p in parts)
    valids = [v for _, v in env.values() if v is not None]
    for i in range(0, len(valids), 32):
        word = jnp.zeros(valids[0].shape, jnp.uint32)
        for bit, v in enumerate(valids[i:i + 32]):
            word = word | (v.astype(jnp.uint32) << jnp.uint32(bit))
        parts.append(word[:, None])
    if parts:
        words = jnp.concatenate(parts, axis=1)
        with site("stacked_walk"):
            words = jnp.concatenate([
                words[:, i:i + GATHER_STACK_WORDS][idx]
                for i in range(0, words.shape[1], GATHER_STACK_WORDS)
            ], axis=1)
    out, at, nth = {}, 0, 0
    for n, (d, v) in env.items():
        if width[n] > GATHER_WIDE_WORDS:
            with site("lone_walk"):
                data = d[idx]
        else:
            data = _from_words(
                words[:, at:at + width[n]], d.dtype, d.shape[1:])
            at += width[n]
        valid = None
        if v is not None:
            word = words[:, valid_at + nth // 32]
            valid = (word >> jnp.uint32(nth % 32)) & jnp.uint32(1) != 0
            nth += 1
        out[n] = (data, valid)
    return out


@kernel
def rows_at(data: jnp.ndarray, valid: jnp.ndarray | None, idx: jnp.ndarray):
    """One column read at ``idx``, as it is: ``(data[idx], valid[idx])``
    (no valid lane: None)."""
    return data[idx], None if valid is None else valid[idx]


@kernel
def keys_match(pb, bb, probe_idx, build_idx) -> jnp.ndarray:
    """Do an expanded pair's key bits agree (the re-verification of a
    hash-combined join key): ``pb[probe_idx] == bb[build_idx]``."""
    return pb[probe_idx] == bb[build_idx]


@kernel
def compact_rows(env: dict, mask: jnp.ndarray, limit: int):
    """The page's live rows first, in row order, in ``limit`` rows:
    ``(env2, mask2)``. One packed sort gives the positions (measured on
    one v5e chip at 6.29M rows, PR 22: 92 ms with its gathers, against
    380 ms for cumsum+searchsorted and 438 ms for cumsum+scatter
    compactions), ``gather_rows`` reads the page there, and the live
    rows being a prefix, the new mask is their count — no gather."""
    env2 = gather_rows(env, compact_perm(mask)[:limit])
    return env2, jnp.arange(limit, dtype=jnp.int32) < count_true(mask)


@kernel
def slice_rows(arrays: list, start: int, n: int, capacity: int):
    """Rows ``[start, start + n)`` of every ``(data, valid)`` pair as
    the first rows of ``capacity``, zeros behind them, and the live
    mask of that prefix: ``(arrays2, mask)``. All three numbers are
    static, so each column is one slice and one pad — a copy of the
    range, no gather. (The rows behind the prefix are zeroed because a
    page uploaded from the host holds zeros there.)"""
    mask = jnp.arange(capacity, dtype=jnp.int32) < n

    def cut(a):
        if a is None:
            return None
        pad = [(0, capacity - n, 0)] + [(0, 0, 0)] * (a.ndim - 1)
        return jax.lax.pad(
            a[start:start + n], jnp.zeros((), a.dtype), pad
        )

    return [(cut(d), cut(v)) for d, v in arrays], mask


def _rank_key(x: jnp.ndarray) -> tuple[jnp.ndarray, int]:
    """(unsigned order-preserving bits, bit width) of a search key."""
    if jnp.issubdtype(x.dtype, jnp.floating) or x.dtype == jnp.bool_:
        return order_bits(x), 64
    if x.dtype.itemsize == 8:
        return order_bits(x) if jnp.issubdtype(
            x.dtype, jnp.signedinteger
        ) else x, 64
    if jnp.issubdtype(x.dtype, jnp.signedinteger):
        return (x.astype(jnp.int64) + jnp.int64(1 << 31)).astype(
            jnp.uint64
        ), 32
    return x.astype(jnp.uint64), 32


@kernel
def _merge_rank(
    a: jnp.ndarray, v: jnp.ndarray, side: str, key_bits: int | None = None
) -> jnp.ndarray:
    """searchsorted by one merged sort: rank every query among the
    haystack by sorting them TOGETHER (ties: queries first for 'left',
    haystack first for 'right'), counting haystack rows ahead of each
    query with a prefix sum, and scattering the counts back to query
    order. ``key_bits``: the caller's word for unsigned keys all below
    ``2**key_bits`` — the merged sort is then one pass wherever key and
    row index fit a word together (``packed_argsort``); without it the
    width is the dtype's."""
    m, q = a.shape[0], v.shape[0]
    dt = jnp.promote_types(a.dtype, v.dtype)
    ka, bits = _rank_key(a.astype(dt))
    kv, _ = _rank_key(v.astype(dt))
    if key_bits is not None:
        bits = key_bits
    if side == "left":
        perm = packed_argsort(jnp.concatenate([kv, ka]), bits)
        is_hay = perm >= q
        dest = jnp.where(is_hay, q, perm)
    else:
        perm = packed_argsort(jnp.concatenate([ka, kv]), bits)
        is_hay = perm < m
        dest = jnp.where(is_hay, q, perm - m)
    with site("hay_prefix"):
        ahead = (
            jnp.cumsum(is_hay.astype(jnp.int32)) - is_hay.astype(jnp.int32)
        )
    with site("scatter_back"):
        return jnp.zeros((q,), jnp.int32).at[dest].set(ahead, mode="drop")


@kernel
def searchsorted(
    a: jnp.ndarray, v: jnp.ndarray, side: str = "left",
    key_bits: int | None = None,
) -> jnp.ndarray:
    """searchsorted with the method chosen by measurement (one v5e
    chip, 6,291,456 uint64 queries into as many keys, PR 22): the
    binary-search 'scan' — ~log2(n) serialized gather rounds over every
    query — ran 3.5 s but compiles in 0.3 s; jnp's 'sort' method ran
    0.22 s but compiled 74 s (two stable argsorts + two scatters per
    call). Few queries take 'scan'; many take one merged single-operand
    sort (``_merge_rank``). Shapes are static under jit, so the choice
    is made at trace time — from the QUERY count alone: the haystack's
    size is not looked at here. The one caller whose haystack can be a
    handful of rows under millions of queries, ``join_ranges``, decides
    before it calls (``join_search``: a build of at most
    ``JOIN_SMALL_BUILD`` rows is ranked by compare-and-count and never
    reaches this function), and hands on the width of its keys where
    the plan proved one (``key_bits``, ``_merge_rank``)."""
    if v.size <= 16384:
        return jnp.searchsorted(a, v, side=side, method="scan")
    return _merge_rank(a, v.ravel(), side, key_bits).reshape(v.shape)


def floor_div(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a // b`` for int64 ``a`` and int64 ``b >= 1``, by 64 rounds of
    restoring shift-subtract. XLA:TPU expands every int64 ``div``/``rem``
    into ~7 s of compile (Q1's three decimal averages: twelve of them,
    and three alone in one program crash the compiler — AOT for v5e,
    PR 22); this loop compiles in 0.4 s and runs on group-sized
    arrays."""
    neg = a < 0
    ua = jnp.where(neg, -a, a).astype(jnp.uint64)
    ub = b.astype(jnp.uint64)
    one = jnp.uint64(1)

    def step(i, st):
        q, r = st
        bit = jnp.uint64(63) - i.astype(jnp.uint64)
        r = (r << one) | ((ua >> bit) & one)
        ge = r >= ub
        return q | (ge.astype(jnp.uint64) << bit), jnp.where(ge, r - ub, r)

    zero = jnp.zeros_like(ua)
    uq, ur = jax.lax.fori_loop(0, 64, step, (zero, zero))
    uq = uq.astype(jnp.int64)
    return jnp.where(neg, -(uq + (ur != 0).astype(jnp.int64)), uq)


@kernel
def cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """1-D inclusive prefix sum. On TPU ``jnp.cumsum`` lowers to a
    log2(n)-level associative scan over the whole column (int64 at
    6.29M rows: 8-11 s of compile per instance, and Q1 holds fifteen);
    two levels over 2048-wide blocks compile in 2 s and add the same
    integers in another order (exact: wraparound addition is
    associative)."""
    n = x.shape[0]
    block = 2048
    if x.ndim != 1 or n % block or n <= block * block // 8 or not (
        jnp.issubdtype(x.dtype, jnp.integer)
    ):
        return jnp.cumsum(x)
    inner = jnp.cumsum(x.reshape(-1, block), axis=1)
    totals = jnp.cumsum(inner[:, -1])
    offsets = jnp.concatenate([jnp.zeros((1,), x.dtype), totals[:-1]])
    return (inner + offsets[:, None]).reshape(-1)


def limb_parts(data: jnp.ndarray) -> list[jnp.ndarray]:
    """A key column as 1D pieces: two-limb decimal columns ([n, 2])
    contribute their hi and lo limbs as separate key parts."""
    if jnp.ndim(data) == 2:
        return [data[:, 0], data[:, 1]]
    return [data]


def normalize_key(data: jnp.ndarray, valid: jnp.ndarray | None):
    """(bits, null_flag) with NULL data zeroed so equal keys have equal
    bits (SQL GROUP BY / join keys treat NULLs as one group)."""
    bits = _to_bits(data)
    if valid is None:
        return bits, jnp.zeros(bits.shape, dtype=jnp.bool_)
    return jnp.where(valid, bits, jnp.uint64(0)), ~valid


@kernel
def hash_columns(cols: list[tuple[jnp.ndarray, jnp.ndarray | None]]) -> jnp.ndarray:
    """Combined 64-bit hash of key columns (nulls hash to a salt)."""
    h = jnp.zeros(cols[0][0].shape, dtype=jnp.uint64)
    for data, valid in cols:
        bits, isnull = normalize_key(data, valid)
        bits = jnp.where(isnull, _NULL_SALT, bits)
        h = _mix64(h ^ _mix64(bits))
    return h


# ---- group-by via sort ------------------------------------------------------
#
# TPU scatters serialize (measured ~115 ms per segment_sum over 1M rows
# on v5e), so the FlatHash-style scatter-race table was replaced by
# sort-based grouping: one packed sort of the key columns, group
# boundaries by adjacent compare, dense group ids by cumsum. This is
# exact (no hash collisions) and needs no scatter — but it is not
# cheap: at 6,291,456 rows the sort, the gathers into sorted order and
# the int64 prefix sums of a Q1-shaped aggregate are some 500 ms on a
# v5e whatever the number of groups (PERF.md, PR 26). It is the path
# for keys whose domain is wide or unknown; a small known domain takes
# ``slot_group`` below.


class GroupInfo(NamedTuple):
    """Sorted-group context shared by every aggregate over one GROUP BY.

    ``perm`` sorts rows so each group is one contiguous run (dead rows
    last) — None where the rows already are in that order
    (``run_group``: sorted position == row, nothing is gathered);
    ``gid_sorted[p]`` is the dense group id at sorted position p
    (== capacity for dead/overflowed rows); ``group[i]`` maps original
    rows to ids; ``starts``/``ends`` delimit each id's run in sorted
    order; ``owner[s]`` is the first (original-index) row of group s or
    n when s is unused; ``num_groups`` is the exact distinct count.
    """

    perm: jnp.ndarray | None
    gid_sorted: jnp.ndarray
    group: jnp.ndarray
    starts: jnp.ndarray
    ends: jnp.ndarray
    owner: jnp.ndarray
    num_groups: jnp.ndarray

    @kernel
    def in_order(self, x: jnp.ndarray) -> jnp.ndarray:
        """A row-ordered column in group-sorted order."""
        return x if self.perm is None else x[self.perm]

    @kernel
    def rows_at(self, pos: jnp.ndarray) -> jnp.ndarray:
        """Original row index of sorted positions ``pos``."""
        return pos if self.perm is None else self.perm[pos]


@partial(jax.jit, static_argnames=("capacity", "widths"))
@kernel
def sort_group(
    norm_bits: tuple[jnp.ndarray, ...],
    null_flags: tuple[jnp.ndarray, ...],
    live: jnp.ndarray,
    capacity: int,
    widths: tuple[int, ...] | None = None,
    pre_perm: jnp.ndarray | None = None,
) -> GroupInfo:
    """Exact multi-key grouping by lexsort + boundary cumsum.

    The TPU-native FlatHash replacement (MAIN/operator/FlatHash.java:42):
    instead of per-row probing, all key columns are stably sorted,
    equal keys become adjacent runs, and a cumsum over run boundaries
    yields dense group ids 0..num_groups-1 in key-sorted order. Groups
    beyond ``capacity`` report via num_groups > capacity (callers retry
    larger, the rehash analog) — the assignment itself never collides.

    When ``widths`` gives a per-key value bit width and everything
    (plus null flags plus one liveness bit) fits in 64 bits, all keys
    pack into ONE u64 — a single packed sort replaces the multi-pass
    lexsort and dead rows fall to the tail for free. Otherwise each key
    costs a stable ``packed_argsort`` pass (plus one for its null flag
    when the column is nullable — pass flag None for non-nullable).
    """
    n = live.shape[0]
    words = _pack_words(norm_bits, null_flags, live, widths)
    if words is not None:
        packed_words, live_folded, total_bits = words
        if pre_perm is None and len(packed_words) == 1 and live_folded:
            # hot path: ONE packed sort yields the permutation, and
            # liveness reads off the folded MSB of the gathered words
            # instead of a second gather
            perm = packed_argsort(packed_words[0], total_bits + 1)
            ps = packed_words[0][perm]
            live_s = (ps >> jnp.uint64(total_bits)) == 0
            same = ps == jnp.roll(ps, 1)
        else:
            # stable sort preserves the caller's row order within each
            # group (window functions: order-by within partition);
            # multi-word packs lexsort least-significant word first
            perm = (
                jnp.arange(n, dtype=jnp.int32)
                if pre_perm is None else pre_perm.astype(jnp.int32)
            )
            for w in reversed(packed_words):
                perm = perm[packed_argsort(w[perm], 64)]
            if not live_folded:
                perm = perm[compact_perm(live[perm])]
            live_s = live[perm]
            same = jnp.ones((n,), dtype=jnp.bool_)
            for w in packed_words:
                ws = w[perm]
                same = same & (ws == jnp.roll(ws, 1))
    else:
        perm = (
            jnp.arange(n, dtype=jnp.int32)
            if pre_perm is None else pre_perm.astype(jnp.int32)
        )
        for bits, flag in reversed(list(zip(norm_bits, null_flags))):
            perm = perm[packed_argsort(bits[perm], 64)]
            if flag is not None:
                perm = perm[packed_argsort(flag[perm], 1)]
        # dead rows last (live is a prefix after this stable pass)
        perm = perm[compact_perm(live[perm])]
        live_s = live[perm]
        same = jnp.ones((n,), dtype=jnp.bool_)
        for bits, flag in zip(norm_bits, null_flags):
            bs = bits[perm]
            same = same & (bs == jnp.roll(bs, 1))
            if flag is not None:
                fs = flag[perm]
                same = same & (fs == jnp.roll(fs, 1))
    pos = jnp.arange(n, dtype=jnp.int32)
    boundary = live_s & ((pos == 0) | ~same)
    gid1 = jnp.cumsum(boundary.astype(jnp.int32))  # 1-based within live
    num_groups = gid1[-1] if n else jnp.int32(0)
    gid_sorted = jnp.where(live_s, gid1 - 1, capacity)
    gid_sorted = jnp.minimum(gid_sorted, capacity)
    inv = packed_argsort(perm, _idx_bits(n))  # inverse permutation
    group = gid_sorted[inv]
    sids = jnp.arange(capacity, dtype=jnp.int32)
    starts = searchsorted(gid_sorted, sids, side="left").astype(jnp.int32)
    # dense contiguous ids: each group's end IS the next group's start
    # (a second searchsorted would cost ~160ms at 6M rows)
    n_live = searchsorted(
        gid_sorted, jnp.asarray(capacity, dtype=gid_sorted.dtype),
        side="left",
    ).astype(jnp.int32)
    ends = jnp.concatenate([starts[1:], n_live.reshape(1)])
    owner = jnp.where(
        sids < num_groups, perm[jnp.clip(starts, 0, max(n - 1, 0))], n
    ).astype(jnp.int32)
    return GroupInfo(perm, gid_sorted, group, starts, ends, owner, num_groups)


def _pack_words(norm_bits, null_flags, live, widths):
    """(packed_words, live_folded, total_bits) — the keys packed into
    as few u64 sort words as possible, or None when no widths are
    known. ``total_bits`` is the key+flag bit count of the (single)
    word when ``live_folded`` (the liveness bit sits at that
    position).

    Each word combines consecutive keys (significance order preserved:
    word list is most-significant first, callers lexsort
    least-significant word first). Equal keys map to equal packed
    values (the low ``w`` bits of each key's normalized bits are
    injective for values of that width). When the whole pack is one
    word with a 65th bit free, liveness folds in as the MSB so dead
    rows sort last with no extra pass (``live`` None: never folded)."""
    if widths is None:
        return None
    keys = list(zip(norm_bits, null_flags, widths))
    # greedy chunking into <=64-bit words, significance order kept
    chunks: list[list] = []
    cur: list = []
    cur_bits = 0
    for bits, flag, w in keys:
        need = w + (0 if flag is None else 1)
        if need > 64:
            return None  # a single over-wide key defeats packing
        if cur and cur_bits + need > 64:
            chunks.append(cur)
            cur, cur_bits = [], 0
        cur.append((bits, flag, w))
        cur_bits += need
    if cur:
        chunks.append(cur)
    one_word = len(chunks) == 1
    live_folded = live is not None and one_word and cur_bits + 1 <= 64
    words = []
    for ci, chunk in enumerate(chunks):
        # start from the liveness bit (or the first key) rather than a
        # zeros << width chain — a shift by the full 64-bit width is
        # undefined in XLA and would corrupt single-wide-key packing
        packed = (
            (~live).astype(jnp.uint64)
            if live_folded and ci == 0 else None
        )
        for bits, flag, w in chunk:
            piece = bits & jnp.uint64((1 << w) - 1) if w < 64 else bits
            packed = (
                piece if packed is None
                else (packed << jnp.uint64(w)) | piece
            )
            if flag is not None:
                packed = (packed << jnp.uint64(1)) | flag.astype(jnp.uint64)
        words.append(packed)
    return words, live_folded, cur_bits


def assign_groups(
    norm_bits: tuple[jnp.ndarray, ...],
    null_flags: tuple[jnp.ndarray, ...],
    live: jnp.ndarray,
    capacity: int,
    widths: tuple[int, ...] | None = None,
):
    """(group, owner) compatibility wrapper over :func:`sort_group`.

    ``group[i]`` = dense id of row i (== capacity for dead rows and for
    overflow rows when more than ``capacity`` distinct keys exist),
    ``owner[s]`` = representative row of group s (== n when unused).
    """
    info = sort_group(norm_bits, null_flags, live, capacity, widths=widths)
    return info.group, info.owner


# ---- group-by over rows already in key order: runs in place ------------------
#
# A table scanned in its connector's declared sort order (TPC-H's
# ``lineitem`` on ``l_orderkey``: dbgen writes it order by order) is
# already what ``sort_group`` sorts it into: each key one contiguous
# run, runs ascending, dead rows last. Then the permutation is the
# identity and nothing has to move — no row sort, no gather of a key or
# an argument column into sorted order, no inverse permutation — and a
# group's start is the position of its boundary row, which one
# single-operand uint32 sort of the boundary mask compacts (where
# ``sort_group`` ranks ``capacity`` ids among the rows by a second
# uint64 sort, a prefix sum and a scatter: ``_merge_rank``). The order
# is a declaration, so it is checked on the way.


@kernel
def run_group(
    norm_bits: tuple[jnp.ndarray, ...],
    null_flags: tuple[jnp.ndarray, ...],
    live: jnp.ndarray,
    capacity: int,
    widths: tuple[int, ...],
) -> tuple[GroupInfo, jnp.ndarray]:
    """(info, unordered): grouping by the runs the rows already form.
    Same arguments as ``sort_group`` for keys that pack into one word,
    and — where ``unordered`` is False — the same groups, ids, starts,
    ends and owners, with ``perm`` None (the identity).

    ``unordered`` is True when the live rows are not a prefix of the
    page or their packed key words descend somewhere (the order the
    sort path would give them): the runs are then not the groups, the
    rest of ``info`` means nothing, and the caller must group by
    ``sort_group`` instead."""
    n = live.shape[0]
    (word,), _folded, bits = _pack_words(norm_bits, null_flags, None, widths)
    if bits <= 32:
        word = word.astype(jnp.uint32)  # compare in native lanes
    prev = jnp.roll(word, 1)
    live_prev = jnp.roll(live, 1)
    first = jnp.arange(n, dtype=jnp.int32) == 0
    unordered = jnp.any(live & ~first & (~live_prev | (word < prev)))
    boundary = live & (first | (word != prev))
    with site("boundary_prefix"):
        gid1 = cumsum(boundary.astype(jnp.int32))  # 1-based within live
    num_groups = gid1[-1] if n else jnp.int32(0)
    gid = jnp.minimum(jnp.where(live, gid1 - 1, capacity), capacity)
    n_live = jnp.sum(live.astype(jnp.int32))
    sids = jnp.arange(capacity, dtype=jnp.int32)
    used = sids < num_groups
    # the g-th boundary row is where group g starts: boundary rows to
    # the front, in row order, by one packed uint32 sort
    with site("boundary_rows"):
        at_boundary = compact_perm(boundary)[:capacity]
    if capacity > n:
        at_boundary = jnp.concatenate(
            [at_boundary, jnp.full((capacity - n,), n, jnp.int32)]
        )
    starts = jnp.where(used, at_boundary, n_live)
    ends = jnp.concatenate([starts[1:], n_live.reshape(1)])
    owner = jnp.where(used, at_boundary, n).astype(jnp.int32)
    info = GroupInfo(None, gid, gid, starts, ends, owner, num_groups)
    return info, unordered


# ---- group-by over a small key domain: slot addressing ----------------------
#
# When the packed key word of ``_pack_words`` has only a few bits (two
# dictionary-coded flags, a boolean, a narrow exact value range), the
# word is the group's address: row i belongs to slot ``word[i]``, slot
# order is the sort path's key order, and every aggregate is a dense
# masked reduction ``reduce(where(slot == s & contrib, x, identity))``
# per slot — one pass over the column on the VPU, no permutation, no
# gather, no prefix sum. Integer sums add the same integers in another
# order, so they equal the sort path's bit for bit.

#: widest packed key (value bits + null flags) grouped by slot; wider
#: keys sort. The dense reductions cost 2**bits passes of the VPU where
#: the sort's cost does not depend on the groups: on a v5e at 6,291,456
#: rows a Q1-shaped aggregate takes 5 / 12 / 43 / 163 ms at 4 / 6 / 8 /
#: 10 bits against 406-462 ms sorted (tools/groupby_crossover.py;
#: PERF.md, PR 26), so they would meet near 11-12 bits; 8 keeps a
#: ninefold margin for aggregate shapes that table did not measure.
SLOT_KEY_BITS = 8


class SlotInfo(NamedTuple):
    """Slot-addressed group context, ``sort_group``'s contract without
    the sort: dense ids 0..num_groups-1 in key order, an occupied
    prefix, ``owner[g]`` the first live row of group g (n when unused).

    ``slot[i]`` is row i's packed key word (>= n_slots for dead rows);
    ``rank[s]`` the dense id of slot s (capacity when no live row has
    it); ``order[g]`` the slot of dense id g (n_slots when unused)."""

    slot: jnp.ndarray
    rank: jnp.ndarray
    order: jnp.ndarray
    owner: jnp.ndarray
    num_groups: jnp.ndarray


def slot_key_bits(widths, null_flags) -> int:
    """Bits of the packed key word: value widths plus one per nullable
    key. At most ``SLOT_KEY_BITS`` -> ``slot_group``; else ``sort_group``."""
    return sum(widths) + sum(fl is not None for fl in null_flags)


_SLOT_REDUCERS = {
    # accumulate in vals' own dtype (jnp.sum would widen int32 counts)
    "sum": partial(jnp.sum, promote_integers=False),
    "min": jnp.min,
    "max": jnp.max,
}


def _per_slot(slot, n_slots: int, vals, identity, op: str):
    """[n_slots] reduction of ``vals`` over the rows of each slot, one
    fused pass: the [n_slots, n] select is never materialized."""
    ids = jnp.arange(n_slots, dtype=jnp.int32)
    return _SLOT_REDUCERS[op](
        jnp.where(slot[None, :] == ids[:, None], vals[None, :], identity),
        axis=1, initial=identity,
    )


@kernel
def slot_group(norm_bits, null_flags, live, capacity: int, widths) -> SlotInfo:
    """Grouping for keys of ``slot_key_bits(...) <= SLOT_KEY_BITS``:
    same arguments and the same groups, ids and owners as
    ``sort_group``."""
    words, _folded, key_bits = _pack_words(norm_bits, null_flags, live, widths)
    # one word with liveness folded in above the key: dead rows hold a
    # word >= n_slots and match no slot
    slot = words[0].astype(jnp.int32)
    n_slots = 1 << key_bits
    n = live.shape[0]
    first = _per_slot(
        slot, n_slots, jnp.arange(n, dtype=jnp.int32), jnp.int32(n), "min"
    )
    occupied = first < n
    num_groups = jnp.sum(occupied.astype(jnp.int32))
    rank = jnp.where(
        occupied, jnp.cumsum(occupied.astype(jnp.int32)) - 1, capacity
    )
    gids = jnp.arange(capacity, dtype=jnp.int32)
    order = jnp.where(
        gids < num_groups,
        jnp.take(compact_perm(occupied), gids, mode="fill",
                 fill_value=n_slots),
        n_slots,
    )
    owner = jnp.take(first, order, mode="fill", fill_value=n)
    return SlotInfo(slot, rank, order, owner, num_groups)


@kernel
def slot_reduce(vals, contrib, info: SlotInfo, identity, op: str = "sum"):
    """Sum / min / max (``op``) of the contributing rows' ``vals`` per
    group, as [capacity] in dense id order; ``identity`` where a group
    is unused or nothing contributes."""
    n_slots = info.rank.shape[0]
    identity = jnp.asarray(identity, dtype=vals.dtype)
    per_slot = _per_slot(
        jnp.where(contrib, info.slot, n_slots), n_slots, vals, identity, op
    )
    return jnp.concatenate([per_slot, identity[None]])[info.order]


# ---- segment reductions over sorted groups ---------------------------------


def _range_gather(cs: jnp.ndarray, idx: jnp.ndarray, zero):
    """cs[idx-1] with 0 for idx==0 (prefix-sum boundary read)."""
    n = cs.shape[0]
    at = jnp.clip(idx - 1, 0, max(n - 1, 0))
    return jnp.where(idx > 0, cs[at], zero)


@kernel
def start_walk(info: GroupInfo, sums: list, keys: dict | None = None):
    """What a grouped step reads at its groups' first rows, in ONE
    ``gather_rows`` walk: ``(sums', keys')``.

    ``sums`` are integer columns, already group-sorted and
    contribution-masked; ``sums'[i][g]`` is column i's sum over group
    g's run — prefix sum + boundary differences, exact and
    scatter-free. The prefix sum is the *exclusive* one (``cs - vals``,
    one fused subtract), so a group's lower bound is read AT its start
    and every column of the step is read at the same index vector.
    ``ends[g] == starts[g + 1]`` (dense contiguous groups), so the
    upper bound is the lower one shifted by one slot, and the last
    live group's is the column's total, a one-entry read.

    ``keys`` (``{name: (data, valid)}``, row order) ride the same walk
    where the rows are grouped in place (``info.perm`` None: a used
    slot's ``owner`` IS its ``starts``; an unused slot's reads are
    masked, so the walk is at ``owner`` and the keys come back as
    ``data[owner]`` bit for bit). Under a permutation the first rows
    lie at ``perm[starts]``, another vector: the caller reads them.
    """
    n = info.gid_sorted.shape[0]
    n_live = info.ends[-1:]
    env, totals = {}, []
    for i, vals in enumerate(sums):
        cs = cumsum(vals)
        env[i] = (cs - vals, None)
        totals.append(_range_gather(cs, n_live, jnp.zeros((), vals.dtype)))
    at = info.starts
    if keys is not None:
        at = info.owner
        env.update(keys)
    read = gather_rows(env, jnp.clip(at, 0, max(n - 1, 0)))
    out = []
    for i, total in enumerate(totals):
        lo = read.pop(i)[0]
        hi = jnp.where(
            info.ends >= n_live, total, jnp.concatenate([lo[1:], total])
        )
        out.append(jnp.where(
            info.ends > info.starts, hi - lo, jnp.zeros((), lo.dtype)
        ))
    return out, (read if keys is not None else None)


@kernel
def seg_sum_ranges(vals_sorted, info: GroupInfo):
    """Per-group sums of an already group-sorted, contribution-masked
    value column — scatter-free.

    Integers are one column of a ``start_walk`` (exact). Floats use a
    segmented associative scan accumulated in float64 so each group's
    rounding error is bounded by its own magnitude, not the whole
    page's running prefix (a cumsum-difference would lose ~ulp(global
    prefix) per group).
    """
    dtype = vals_sorted.dtype
    if not jnp.issubdtype(dtype, jnp.floating):
        return start_walk(info, [vals_sorted])[0][0]
    acc = vals_sorted.astype(jnp.float64)

    def op(a, b):
        ga, va = a
        gb, vb = b
        return gb, jnp.where(ga == gb, va + vb, vb)

    _, s = jax.lax.associative_scan(op, (info.gid_sorted, acc))
    n = s.shape[0]
    at = jnp.clip(info.ends - 1, 0, max(n - 1, 0))
    out = jnp.where(info.ends > info.starts, s[at], 0.0)
    return out.astype(dtype)


@kernel
def seg_minmax_scan(vals_sorted, info: GroupInfo, fill, is_min: bool):
    """Per-group min/max via a segmented associative scan over the
    group-sorted values (positions outside the group reset the run)."""
    red = jnp.minimum if is_min else jnp.maximum

    def op(a, b):
        ga, va = a
        gb, vb = b
        return gb, jnp.where(ga == gb, red(va, vb), vb)

    _, m = jax.lax.associative_scan(op, (info.gid_sorted, vals_sorted))
    n = m.shape[0]
    at = jnp.clip(info.ends - 1, 0, max(n - 1, 0))
    out = m[at]
    return jnp.where(info.ends > info.starts, out, fill)


def order_bits(data: jnp.ndarray) -> jnp.ndarray:
    """Monotone u64 encoding: unsigned compare == value order (for
    argmin/argmax-style reductions over arbitrary key types)."""
    if jnp.issubdtype(data.dtype, jnp.floating):
        return _float_sort_bits(data).astype(jnp.uint64)
    if data.dtype == jnp.bool_:
        return data.astype(jnp.uint64)
    bits = data.astype(jnp.int64)
    return jax.lax.bitcast_convert_type(bits, jnp.uint64) ^ jnp.uint64(
        0x8000000000000000
    )


@kernel
def seg_arg_extreme(
    key_sorted: jnp.ndarray,
    contrib_sorted: jnp.ndarray,
    info: GroupInfo,
    is_min: bool,
):
    """Original row index of each group's key-extremal contributing row
    (segmented argmin/argmax; ties take the first sorted position).
    The contribution flag rides through the comparison — a real key
    equal to a would-be sentinel can never lose to an excluded row.
    Rows are garbage for empty groups — callers mask with count > 0."""
    n = key_sorted.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)

    def op(a, b):
        ga, ca, ka, pa = a
        gb, cb, kb, pb = b
        same = ga == gb
        if is_min:
            key_better = (ka < kb) | ((ka == kb) & (pa < pb))
        else:
            key_better = (ka > kb) | ((ka == kb) & (pa < pb))
        a_better = (ca & ~cb) | ((ca == cb) & key_better)
        take_a = same & a_better
        return (
            gb,
            jnp.where(take_a, ca, cb),
            jnp.where(take_a, ka, kb),
            jnp.where(take_a, pa, pb),
        )

    _, _, _, best = jax.lax.associative_scan(
        op, (info.gid_sorted, contrib_sorted, key_sorted, pos)
    )
    at = jnp.clip(info.ends - 1, 0, max(n - 1, 0))
    bp = best[at]
    return info.rows_at(jnp.clip(bp, 0, max(n - 1, 0)))


@kernel
def seg_first_index(contrib_sorted, info: GroupInfo):
    """Original row index of the first contributing row per group
    (== n when the group has none)."""
    n = contrib_sorted.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    masked = jnp.where(contrib_sorted, pos, n)
    first_pos = seg_minmax_scan(masked, info, jnp.int32(n), is_min=True)
    has = first_pos < n
    rows = info.rows_at(jnp.clip(first_pos, 0, max(n - 1, 0)))
    return jnp.where(has, rows, n), has


def blocked_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Scalar int64 sum."""
    return x.astype(jnp.int64).sum()


def count_true(mask: jnp.ndarray) -> jnp.ndarray:
    """Scalar int32 count of True values."""
    return mask.astype(jnp.int32).sum()


# ---- join-side match marks (scatter-free) ----------------------------------


@kernel
def range_any(cnt: jnp.ndarray, out_live: jnp.ndarray) -> jnp.ndarray:
    """Per-probe 'any live expanded output in my range' — the
    scatter-free form of segment-any over the (sorted) probe_idx that
    ``expand_matches`` emits. ``cnt`` is the per-probe match count that
    produced the expansion; ``out_live`` the expanded liveness."""
    offsets = jnp.cumsum(cnt)
    c = jnp.cumsum(out_live.astype(jnp.int32))
    zero = jnp.int32(0)
    hi = _range_gather(c, jnp.minimum(offsets, out_live.shape[0]), zero)
    lo = _range_gather(c, jnp.minimum(offsets - cnt, out_live.shape[0]), zero)
    return (hi - lo) > 0


@kernel
def scatter_any(idx: jnp.ndarray, flags: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """``any(flags[idx == b])`` per b in [0, capacity) for arbitrary
    (unsorted) idx — sort + membership probe instead of a scatter."""
    key = jnp.where(flags, idx, capacity).astype(jnp.int32)
    ks = jax.lax.sort(key, is_stable=False)
    targets = jnp.arange(capacity, dtype=jnp.int32)
    pos = searchsorted(ks, targets, side="left")
    at = jnp.clip(pos, 0, max(ks.shape[0] - 1, 0))
    return (pos < ks.shape[0]) & (ks[at] == targets)


# ---- sorting ---------------------------------------------------------------

@kernel
def sort_perm(
    keys: list[tuple[jnp.ndarray, jnp.ndarray | None, bool, bool]],
    live: jnp.ndarray,
) -> jnp.ndarray:
    """Permutation ordering rows by the sort keys, dead rows last.

    Each key is (data, valid, ascending, nulls_first). Implemented as
    iterated stable argsorts (lexsort), two passes per key — data then
    null flag — so no in-band sentinels are needed and int64 keys keep
    full precision. The reference default null ordering (nulls treated
    as largest: last for ASC, first for DESC) is resolved by the
    caller into ``nulls_first``.
    """
    n = live.shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    for data, valid, ascending, nulls_first in reversed(keys):
        if jnp.issubdtype(data.dtype, jnp.floating):
            # order-preserving bit transform: NaN sorts as the largest
            # value under both directions (reference treats NaN as
            # largest: last for ASC, first for DESC) — negating would
            # leave NaN last either way
            kd = _float_sort_bits(data)
            if not ascending:
                kd = ~kd
        else:
            kd = data if ascending else _invert(data)
        perm = perm[jnp.argsort(kd[perm], stable=True)]
        if valid is not None:
            flag = (~valid).astype(jnp.int8)  # 1 = null
            if nulls_first:
                flag = -flag
            perm = perm[jnp.argsort(flag[perm], stable=True)]
    dead = (~live).astype(jnp.int8)
    perm = perm[jnp.argsort(dead[perm], stable=True)]
    return perm


def _invert(data: jnp.ndarray) -> jnp.ndarray:
    if data.dtype == jnp.bool_:
        return ~data
    return -data  # int64 min overflow is accepted (reference wraps too)


def _float_sort_bits(data: jnp.ndarray) -> jnp.ndarray:
    """Monotone unsigned encoding of a float column: flips the
    sign-magnitude representation so unsigned compare == float total
    order, with -0.0 == +0.0 and NaN canonicalized above +inf."""
    if data.dtype == jnp.float32:
        bits = jax.lax.bitcast_convert_type(_canonical_float(data), jnp.uint32)
        sign_mask = jnp.uint32(0x80000000)
        full_mask = jnp.uint32(0xFFFFFFFF)
    else:
        bits = jax.lax.bitcast_convert_type(_canonical_float(data), jnp.uint64)
        sign_mask = jnp.uint64(0x8000000000000000)
        full_mask = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    negative = (bits & sign_mask) != 0
    return bits ^ jnp.where(negative, full_mask, sign_mask)


# ---- equi-join -------------------------------------------------------------

#: largest build capacity whose probe is ranked by compare-and-count
#: (``_count_ranges``) instead of by sorting probe and build together
#: (``searchsorted`` -> ``_merge_rank``). The count is linear in probe
#: x build — on a v5e 2.9-3.0 ps a pair: 2.3 / 6.0 / 19 / 73 / 287 ms
#: at builds of 64 / 256 / 1,024 / 4,096 / 16,384 rows under a
#: 6,291,456-row probe, 10 / 31 / 101 / 392 / 1,558 ms under 33,554,432
#: — where the sort path costs 158 ms (a 64-row build) to 287-309 ms
#: (1,024 and up: its two ``[at]`` gathers turn into index walks) at
#: 6.29 M and 1,806 to 2,689-2,814 ms at 33.5 M (its permutation
#: gathers, not its sorts, grow 17-fold), so the two meet near 17,000
#: rows at the smaller probe and near 30,000 at the larger
#: (tools/groupby_crossover.py --shape joinrank; PERF.md, PR 42). 4,096
#: keeps compare-and-count 4.0 and 6.9 times cheaper there, for probes
#: and chips that table did not measure; the crossing moves up with
#: the probe, so the build's capacity alone decides.
JOIN_SMALL_BUILD = 4096


def join_search(n_build: int) -> str:
    """Which search ``join_ranges`` is built with for a build side of
    ``n_build`` rows (its static capacity): ``"count"`` or ``"sort"``."""
    return "count" if n_build <= JOIN_SMALL_BUILD else "sort"


def _halves(x: jnp.ndarray):
    return (x >> jnp.uint64(32)).astype(jnp.uint32), x.astype(jnp.uint32)


def _words(x: jnp.ndarray) -> list:
    """A join key as the 32-bit words it is read and compared in (the
    lanes the chip has), high word first: a uint64's two halves, a
    uint32 (``join_ranges`` at a proven width of at most 32 bits)
    itself."""
    return [x] if x.dtype == jnp.uint32 else list(_halves(x))


def _packed_counts(less: jnp.ndarray, equal: jnp.ndarray):
    """``(lo, hi)`` from the ``[build rows, probe rows]`` compares of a
    sorted build with each probe key: a build key being less than or
    equal to a probe but never both, one int32 sum over the build
    carries both counts (the equal ones above bit 16), and the select
    is fused into it — the product is never materialized (as
    ``_per_slot``)."""
    if less.shape[0] >= 1 << 15:
        raise ValueError("_packed_counts: a count needs its 16 bits")
    both = jnp.sum(
        jnp.where(less, jnp.int32(1),
                  jnp.where(equal, jnp.int32(1 << 16), jnp.int32(0))),
        axis=0, dtype=jnp.int32,
    )
    lo = both & jnp.int32(0xFFFF)
    return lo, lo + (both >> jnp.int32(16))


@kernel
def _count_ranges(sorted_key: jnp.ndarray, probe_key: jnp.ndarray):
    """``(lo, hi)`` of every probe key in a sorted build of a few rows
    by compare-and-count: ``lo[i] = #{j : sorted_key[j] < probe_key[i]}``
    — which is ``searchsorted(sorted_key, probe_key, "left")`` — and
    ``hi[i] = lo[i] + #{j : sorted_key[j] == probe_key[i]}``, uint64
    keys compared as 32-bit halves (the lanes the chip has), uint32
    keys (``join_ranges`` at a proven width of at most 32 bits) as the
    one word they are."""
    if sorted_key.dtype == jnp.uint32:
        return _packed_counts(
            sorted_key[:, None] < probe_key[None, :],
            sorted_key[:, None] == probe_key[None, :],
        )
    sh, sl = _halves(sorted_key)
    ph, pl = _halves(probe_key)
    high_eq = sh[:, None] == ph[None, :]
    return _packed_counts(
        (sh[:, None] < ph[None, :]) | (high_eq & (sl[:, None] < pl[None, :])),
        high_eq & (sl[:, None] == pl[None, :]),
    )


def _run_end_at(sorted_key, run_end, probe_key, lo):
    """``run_end[lo]`` where ``sorted_key[lo] == probe_key``, else
    ``lo``: the build key and its run's end read at one walk of 32-bit
    words, a uint64 key compared as halves (the lanes the chip has), a
    uint32 key as its one word."""
    n_build = sorted_key.shape[0]
    words = jnp.stack(
        [*_words(sorted_key), run_end.astype(jnp.uint32)], axis=1
    )
    with site("at_walk"):
        read = words[jnp.clip(lo, 0, n_build - 1)]
    probe_words = _words(probe_key)
    found = lo < n_build
    for i, word in enumerate(probe_words):
        found = found & (read[:, i] == word)
    return jnp.where(
        found, read[:, len(probe_words)].astype(jnp.int32), lo
    )


@partial(jax.jit, static_argnames=("key_bits",))
@kernel
def join_ranges(
    build_key: jnp.ndarray,
    build_live: jnp.ndarray,
    probe_key: jnp.ndarray,
    probe_live: jnp.ndarray,
    key_bits: int = 64,
):
    """Sorted-range probe: the LookupSource analog.

    ``build_key``/``probe_key`` are combined uint64 keys (exact for a
    single fixed-width column; hashed for multi-column — callers must
    re-verify matches after expansion). Rows with live=False never
    match; the caller has already excluded NULL keys.

    ``key_bits`` (static) is the caller's proof that every LIVE key of
    either side is below ``2**key_bits`` (the plan's exact key range,
    ``Join.key_ranges``, the keys shifted to its origin). A dead or
    NULL row may hold anything — a Filter's dead rows hold real values
    outside a narrowed range — so both sides are cut to the width
    first: a dead row then holds some value inside it, and it ranks
    nothing (the build's sort puts it last by its flag, its sorted key
    is the sentinel, a dead probe's count is zero). Key and row index
    then share one word wherever they fit (``packed_argsort``): one
    single-operand sort for the build and one for the merged rank,
    where 64 bits take the two sorts, the gather between them and the
    composed permutation of an LSD radix; at 32 bits or fewer the keys
    are one uint32 word in every compare and read.

    Returns (order, lo, cnt): ``order`` sorts the build side by key
    (dead rows last), ``lo[i]``/``cnt[i]`` give each probe row's match
    range inside the sorted build side — on live rows the same three
    arrays bit for bit at every ``key_bits`` the keys fit (a dead
    row's place in ``order``'s tail and a dead probe's ``lo`` are
    whatever its cut key gives).

    How the probe is ranked in the sorted build is chosen from the
    build's static capacity (``join_search``): at most
    ``JOIN_SMALL_BUILD`` rows, by compare-and-count — linear in the
    probe, no sort, gather, prefix sum or scatter of probe size; above
    it, by ``searchsorted``. Both give the same three arrays bit for
    bit.
    """
    n_build = build_key.shape[0]
    word = jnp.uint32 if key_bits <= 32 else jnp.uint64
    # the largest value of the keys' width: the dead tail's sentinel
    top = word((1 << key_bits) - 1)
    if key_bits < 64:
        build_key = build_key.astype(word) & top
        probe_key = probe_key.astype(word) & top
    # sort build: dead rows pushed past every live key
    order = packed_argsort(build_key, key_bits, last=~build_live)
    n_build_live = jnp.sum(build_live)
    # dead tail keys are arbitrary; pin them to the width's MAX so the
    # whole array is globally sorted (binary-search precondition), then
    # clamp the ranges to the live prefix. A live key may EQUAL the
    # sentinel: nothing live sorts after it, so a probe for it finds
    # ``lo`` at its first live row (or at the live prefix's end) and a
    # ``hi`` inside the dead tail, which the clamp brings back
    pos = jnp.arange(n_build)
    with site("build_in_order"):
        sorted_key = jnp.where(pos < n_build_live, build_key[order], top)
    if join_search(n_build) == "count":
        lo, hi = _count_ranges(sorted_key, probe_key)
    else:
        lo = searchsorted(
            sorted_key, probe_key, side="left", key_bits=key_bits
        )
        # the right edge without a second search: each build position
        # knows where its run of equal keys ends (suffix-min over the
        # run-last positions), and a probe that found its key at ``lo``
        # takes it
        last_of_run = jnp.concatenate(
            [sorted_key[1:] != sorted_key[:-1], jnp.ones((1,), jnp.bool_)]
        )
        run_end = jax.lax.cummin(
            jnp.where(last_of_run, pos + 1, n_build).astype(jnp.int32),
            reverse=True,
        )
        hi = _run_end_at(sorted_key, run_end, probe_key, lo)
    lo = jnp.minimum(lo, n_build_live)
    hi = jnp.minimum(hi, n_build_live)
    cnt = jnp.where(probe_live, hi - lo, 0)
    return order.astype(jnp.int32), lo.astype(jnp.int32), cnt.astype(jnp.int32)


@partial(jax.jit, static_argnames=("out_capacity",))
@kernel
def expand_matches(
    order: jnp.ndarray,
    lo: jnp.ndarray,
    cnt: jnp.ndarray,
    out_capacity: int,
):
    """Expand per-probe match ranges into (probe_idx, build_idx) pairs.

    Output position j belongs to the probe row whose cumulative match
    count covers j (searchsorted over the prefix sums — the vectorized
    form of JoinProbe's nested emit loop).

    Returns (probe_idx, build_idx, out_live).
    """
    offsets = jnp.cumsum(cnt)  # inclusive
    total = offsets[-1] if cnt.shape[0] else jnp.int32(0)
    j = jnp.arange(out_capacity, dtype=jnp.int32)
    probe_idx = searchsorted(offsets, j, side="right").astype(jnp.int32)
    probe_c = jnp.clip(probe_idx, 0, cnt.shape[0] - 1)
    start = offsets[probe_c] - cnt[probe_c]
    k = j - start
    build_pos = lo[probe_c] + k
    build_pos = jnp.clip(build_pos, 0, order.shape[0] - 1)
    build_idx = order[build_pos]
    out_live = j < total
    return probe_c, build_idx, out_live


# ---- segment aggregation ---------------------------------------------------

def seg_sum(vals, group, num_segments):
    return jax.ops.segment_sum(vals, group, num_segments=num_segments + 1)[
        :num_segments
    ]


def seg_min(vals, group, num_segments):
    return jax.ops.segment_min(vals, group, num_segments=num_segments + 1)[
        :num_segments
    ]


def seg_max(vals, group, num_segments):
    return jax.ops.segment_max(vals, group, num_segments=num_segments + 1)[
        :num_segments
    ]
