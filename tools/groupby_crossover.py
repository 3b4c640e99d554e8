#!/usr/bin/env python
"""Where the crossovers between the group-by paths lie, as device time
of the step ``exec/stage.py:_aggregate_step`` builds.

``--shape q1`` (the default): a Q1-shaped grouped aggregate (four
decimal sums, three decimal averages, a count, over five int64
columns) slot-addressed against sorted at each packed key width.
``kernels.SLOT_KEY_BITS`` is set from this table (PERF.md, PR 26).

``--shape q18``: Q18's inner ``group by l_orderkey`` (one decimal sum;
6,291,456 rows of which 6.0M live, 1.5M groups of 1-7 rows arriving in
key order, the planner's capacity 2,097,152) sorted against streamed
(``kernels.run_group``), then the streamed step with its run starts
ranked as the sort path ranks them (``kernels.searchsorted``) instead
of compacted, and the pieces of both on their own — the table of
PERF.md, PR 31.

``--shape q3compact``: the compaction of Q3's filtered ``lineitem``
page (``LocalExecutor._compact``: three int64 columns and one int32,
6,291,456 rows of which some 3.24M live, 4,194,304 positions out): the
per-column body the executor had until PR 36 against
``kernels.gather_rows`` at stacks of 8, 4 and 2 words, bit for bit,
and the gathers both are made of, each alone — the table of PERF.md,
PR 36, which set ``kernels.GATHER_STACK_WORDS``.

``--shape joinrank``: ``kernels.join_ranges`` for a build side of a
few rows against a probe of millions (Q18's ``lineitem`` join and semi
join: probe capacities 6,291,456 at SF1 and 33,554,432 at SF5, builds
of 64 to 16,384 rows): the search by sort (``searchsorted`` ->
``_merge_rank``) against compare-and-count (``_count_ranges``), bit
for bit, then the pieces of the sort path each alone, the count
kernel's variants, and ``jnp.searchsorted(method="scan")`` over the
small haystack — the table of PERF.md, PR 42, which set
``kernels.JOIN_SMALL_BUILD``. ``--key-bits 23`` (a list) draws the keys
below ``2**23`` and times ``join_ranges`` at that static width too —
what ``Join.key_ranges`` hands it (PR 46) — against the 64-bit answers
on live rows, with its build sort and merged rank alone; Q3's
``lineitem`` join at SF1 is ``--probes 4194304 --builds 262144
--sorted-builds 262144 --key-bits 23``, at SF5 ``--probes 16777216
--builds 1048576 --sorted-builds 1048576 --key-bits 25`` (a build the
count kernel cannot carry is timed by sort alone).

``--shape startwalk``: what a step reads at one index vector, as lone
gathers (the bodies the engine had until PR 44) against one walk of
32-bit words (``kernels.gather_rows``), bit for bit, at SF1's and
SF5's shapes: Q18's streamed inner step (two int64 limb prefix sums
and its int64 key at the run starts, 2,097,152 of 6,291,456 and
8,388,608 of 33,554,432 positions) whole and as its reads alone, and
``join_ranges``' reads at ``lo`` in Q3's ``lineitem`` join (the build
key and its run's end: 4,194,304 probes into 262,144 and 16,777,216
into 1,048,576) — the table of PERF.md, PR 44.

Run it on the chip: ``chiprun -- python tools/groupby_crossover.py``.
On a CPU it checks that the paths agree and prints host times, which
are no device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.exec import kernels as K
from trino_tpu.exec import shapes
from trino_tpu.exec.aggregates import compute_aggregate
from trino_tpu.exec.stage import _reads_at_first_rows

CAPACITY = 1536  # Q1's planned group-table capacity at SF1
AGGS = [  # (name, output type, column)
    ("sum", T.DecimalType(38, 2), 0), ("sum", T.DecimalType(38, 2), 1),
    ("sum", T.DecimalType(38, 4), 2), ("sum", T.DecimalType(38, 6), 3),
    ("avg", T.DecimalType(15, 2), 0), ("avg", T.DecimalType(15, 2), 1),
    ("avg", T.DecimalType(15, 2), 4), ("count_all", T.BIGINT, None),
]


def inputs(rows: int, bits: int, seed: int):
    rng = np.random.default_rng(seed)
    key = rng.integers(0, 1 << bits, rows, dtype=np.int64)
    cols = [
        rng.integers(-(1 << 40), 1 << 40, rows, dtype=np.int64)
        for _ in range(5)
    ]
    mask = rng.random(rows) < 0.98
    return jnp.asarray(key), [jnp.asarray(c) for c in cols], jnp.asarray(mask)


def engine_path(group_fn, bits: int, capacity: int = CAPACITY, aggs=AGGS):
    """The step as ``_aggregate_step`` builds it, by one grouping path:
    (aggregates + the key at each owner, owners, groups, order fault)."""

    def prog(key, cols, mask):
        kbits, _ = K.normalize_key(key, None)
        info = group_fn((kbits,), (None,), mask, capacity, widths=(bits,))
        unordered = jnp.bool_(False)
        if group_fn is K.run_group:
            info, unordered = info
        share = {"#mask": mask}
        prepared = [
            (None, SimpleNamespace(name=name, type=typ),
             None if c is None else (cols[c], None), mask)
            for name, typ, c in aggs
        ]
        keys_at, _walks = _reads_at_first_rows(
            {"k": (key, None)}, prepared, info, capacity, share
        )
        out = [
            compute_aggregate(
                call.name, call.type, arg, info, capacity, mask, share=share)
            for _s, call, arg, _m in prepared
        ]
        return out + [keys_at["k"]], info.owner, info.num_groups, unordered

    return prog


def timed(prog, args, reps: int):
    fn = jax.jit(prog)
    t0 = time.perf_counter()
    try:
        out = jax.block_until_ready(fn(*args))
    except Exception as e:  # a refused compile or an exhausted device
        return None, {"error": f"{type(e).__name__}: {e}"[:300]}
    first = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, {"first_call_s": round(first, 2),
                 "ms_min": min(times), "ms_median": statistics.median(times)}


Q18_CAPACITY = 2_097_152  # shapes.table_bucket(1.5M groups) at SF1
Q18_KEY_BITS = 23  # l_orderkey's exact range at SF1, shifted to 0
Q18_AGGS = [("sum", T.DecimalType(38, 2), 0)]


def q18_inputs(rows: int, seed: int):
    """Orders of 1-7 lines in key order (sparse keys, as dbgen's),
    quantities of decimal(15,2), live rows a prefix."""
    rng = np.random.default_rng(seed)
    n_live = rows * 6_001_215 // 6_291_456
    lines = rng.integers(1, 8, n_live // 3 + 8)  # 4 a key: enough
    key = np.repeat(np.arange(len(lines), dtype=np.int64), lines)[:n_live]
    key = (key // 8) * 32 + key % 8  # dbgen: 8 keys used, 24 skipped
    key = np.concatenate([key, np.zeros(rows - n_live, np.int64)])
    qty = rng.integers(1, 51, rows, dtype=np.int64) * 100
    mask = np.arange(rows) < n_live
    return jnp.asarray(key), [jnp.asarray(qty)], jnp.asarray(mask)


def _ranked_run_group(norm_bits, null_flags, live, capacity, widths):
    """``run_group`` with the run starts ranked among the rows by
    ``searchsorted`` (``_merge_rank`` at this capacity), as
    ``sort_group`` derives them: what that derivation alone costs."""
    info, _unordered = K.run_group(
        norm_bits, null_flags, live, capacity, widths)
    n = live.shape[0]
    sids = jnp.arange(capacity, dtype=jnp.int32)
    starts = K.searchsorted(info.gid_sorted, sids).astype(jnp.int32)
    ends = jnp.concatenate([starts[1:], info.ends[-1:]])
    owner = jnp.where(sids < info.num_groups, starts, n).astype(jnp.int32)
    return info._replace(starts=starts, ends=ends, owner=owner)


def q18_pieces(rows: int, capacity: int):
    """name -> (fn, args): the primitives the two paths are made of,
    each alone at Q18's shape."""
    rng = np.random.default_rng(1)
    u64 = jnp.asarray(rng.integers(0, 1 << 47, rows, dtype=np.uint64))
    i64 = jnp.asarray(rng.integers(0, 5001, rows, dtype=np.int64))
    perm = jnp.asarray(rng.permutation(rows).astype(np.int32))
    gid = jnp.asarray(np.sort(rng.integers(0, capacity, rows)).astype(np.int32))
    flag = jnp.asarray(rng.random(rows) < 0.25)
    at = jnp.asarray(np.sort(rng.integers(0, rows, capacity)).astype(np.int32))
    sids = jnp.arange(capacity, dtype=jnp.int32)
    return {
        "sort_rows_uint64 (sort path: the packed row sort)":
            (lambda w: K.packed_argsort(w, 47), (u64,)),
        "gather_rows_int64 (sort path: one column into sorted order)":
            (lambda x, p: x[p], (i64, perm)),
        "inverse_perm_uint32_sort (sort path: group of rows)":
            (lambda p: K.packed_argsort(p, K._idx_bits(rows)), (perm,)),
        "merge_rank (sort path: starts of capacity ids among the rows)":
            (lambda g: K.searchsorted(g, sids), (gid,)),
        "compact_uint32_sort (streamed: boundary rows to the front)":
            (K.compact_perm, (flag,)),
        "cumsum_int64 (both: one limb's prefix sum)": (K.cumsum, (i64,)),
        "cumsum_int32 (both: dense ids)":
            (lambda f: K.cumsum(f.astype(jnp.int32)), (flag,)),
        "gather_capacity_int64 (both: prefix sums at the run starts)":
            (lambda x, a: x[a], (i64, at)),
        # what the next step could buy: both limbs' prefix sums read in
        # one gather of [rows, 2]; the same read on 32-bit lanes
        "gather_capacity_int64_x2_stacked":
            (lambda x, a: jnp.stack([x, x + 1], axis=1)[a], (i64, at)),
        "gather_capacity_uint32":
            (lambda x, a: x.astype(jnp.uint32)[a], (i64, at)),
    }


def write(rec: dict, out: str, echo: bool = True) -> None:
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as fh:
        json.dump(rec, fh, indent=1)
    if echo:
        print(json.dumps(rec))


def q18_main(a) -> int:
    dev = jax.devices()[0]
    rec = {"platform": dev.platform, "device_kind": dev.device_kind,
           "shape": "q18", "rows": a.rows, "capacity": a.capacity,
           "key_bits": Q18_KEY_BITS, "runs": [], "pieces": []}
    args = q18_inputs(a.rows, a.seed)
    results = {}
    for path, fn in (("sorted", K.sort_group), ("streamed", K.run_group),
                     ("streamed_ranked_starts", _ranked_run_group)):
        out, t = timed(
            engine_path(fn, Q18_KEY_BITS, a.capacity, Q18_AGGS), args, a.reps)
        results[path] = out
        rec["runs"].append({"path": path, **t})
        print(rec["runs"][-1], flush=True)
    ref = results["sorted"]
    ok = ref is not None
    for run in rec["runs"][1:]:
        got = results[run["path"]]
        run["agrees"] = None not in (ref, got) and same(got, ref)
        ok &= run["agrees"]
    if ok:
        rec["groups"] = int(ref[2])
    for name, (fn, fargs) in q18_pieces(a.rows, a.capacity).items():
        _out, t = timed(fn, fargs, a.reps)
        rec["pieces"].append({"piece": name, **t})
        print(rec["pieces"][-1], flush=True)
    write(rec, a.out)
    return 0 if ok else 1


Q3_LIVE_SHARE = 0.54  # l_shipdate > date '1995-03-15'


def q3_inputs(rows: int, seed: int):
    """Q3's page after its Filter: ``l_orderkey``, ``l_extendedprice``,
    ``l_discount`` (int64), ``l_shipdate`` (int32), and a mask that
    keeps rows at random among the table's 6.0M, none of the padding."""
    rng = np.random.default_rng(seed)
    env = {
        str(i): (jnp.asarray(
            rng.integers(-(1 << 62), 1 << 62, rows, dtype=np.int64)), None)
        for i in range(3)
    }
    env["3"] = (jnp.asarray(
        rng.integers(8000, 10600, rows, dtype=np.int32)), None)
    mask = (rng.random(rows) < Q3_LIVE_SHARE) & (
        np.arange(rows) < rows * 6_000_145 // 6_291_456)
    return env, jnp.asarray(mask)


def q3_bodies(limit: int):
    """name -> compaction body: the per-column one ``_compact`` held
    until PR 36, and ``kernels.compact_rows`` (``gather_rows``, the live
    mask made from the count) at each stack width."""

    def per_column(env, mask):
        perm = K.compact_perm(mask)[:limit]
        return {
            s: (d[perm], None if v is None else v[perm])
            for s, (d, v) in env.items()
        }, mask[perm]

    kept = K.GATHER_STACK_WORDS

    def stacked(width):
        def body(env, mask):
            K.GATHER_STACK_WORDS = width  # read while tracing
            try:
                return K.compact_rows(env, mask, limit)
            finally:
                K.GATHER_STACK_WORDS = kept

        return body

    return {"per_column": per_column,
            **{f"gather_rows_w{w}": stacked(w) for w in (8, 4, 2)}}


def q3_pieces(env, mask, limit: int):
    """name -> (fn, args): the compaction's primitives alone, at the
    compaction's own permutation."""
    perm = jax.jit(lambda m: K.compact_perm(m)[:limit])(mask)
    i64, i32 = env["0"][0], env["3"][0]
    u32 = [
        jax.lax.bitcast_convert_type(env[str(i)][0], jnp.uint32)[:, j]
        for i in range(3) for j in range(2)
    ] + [i32.astype(jnp.uint32)] * 2
    pieces = {
        "compact_uint32_sort (both bodies)": (K.compact_perm, (mask,)),
        "gather_mask_pred (per-column body: mask[perm])":
            (lambda m, p: m[p], (mask, perm)),
        "live_mask_from_count (gather_rows body: arange < count)":
            (lambda m: jnp.arange(limit, dtype=jnp.int32) < K.count_true(m),
             (mask,)),
        "gather_int64": (lambda x, p: x[p], (i64, perm)),
        "gather_int32": (lambda x, p: x[p], (i32, perm)),
    }
    for w in (1, 2, 4, 7, 8):
        pieces[f"gather_stack_{w}_uint32_words"] = (
            lambda p, *cols: jnp.stack(cols, axis=1)[p], (perm, *u32[:w]))
    for w in (4, 8):  # the same words laid [W, rows]: the compiler's choice?
        pieces[f"gather_stack_{w}_uint32_words_rows_minor"] = (
            lambda p, *cols: jnp.stack(cols, axis=0)[:, p], (perm, *u32[:w]))
    return pieces


def same_pages(a, b) -> bool:
    """Two compacted pages, bit for bit: every column, every validity
    lane, the mask."""
    (env_a, mask_a), (env_b, mask_b) = a, b
    if not np.array_equal(mask_a, mask_b):
        return False
    for name, (d, v) in env_a.items():
        d2, v2 = env_b[name]
        if d.dtype != d2.dtype or not np.array_equal(
            np.asarray(d).view(np.uint8), np.asarray(d2).view(np.uint8)
        ):
            return False
        if (v is None) != (v2 is None) or (
            v is not None and not np.array_equal(v, v2)
        ):
            return False
    return True


def q3compact_main(a) -> int:
    dev = jax.devices()[0]
    env, mask = q3_inputs(a.rows, a.seed)
    live = int(mask.sum())
    limit = shapes.bucket(live)  # as ``_compact`` sizes its output
    rec = {"platform": dev.platform, "device_kind": dev.device_kind,
           "shape": "q3compact", "rows": a.rows, "live": live,
           "limit": limit, "runs": [], "pieces": []}
    results = {}
    for name, body in q3_bodies(limit).items():
        results[name], t = timed(body, (env, mask), a.reps)
        rec["runs"].append({"body": name, **t})
        print(rec["runs"][-1], flush=True)
    ref = results["per_column"]
    ok = ref is not None
    for run in rec["runs"][1:]:
        got = results[run["body"]]
        run["agrees"] = None not in (ref, got) and same_pages(got, ref)
        ok &= run["agrees"]
    for name, (fn, fargs) in q3_pieces(env, mask, limit).items():
        _out, t = timed(fn, fargs, a.reps)
        rec["pieces"].append({"piece": name, **t})
        print(rec["pieces"][-1], flush=True)
    write(rec, a.out)
    return 0 if ok else 1


JOINRANK_PROBES = (6_291_456, 33_554_432)  # lineitem's capacity, SF1 / SF5
JOINRANK_BUILDS = (64, 256, 1024, 4096, 16384)


def joinrank_inputs(probe: int, build: int, seed: int, key_bits: int = 64):
    """Q18's ``lineitem`` join in shape: order keys of 1-7 lines each
    as the probe (live rows a prefix), and as the build a sample of
    those keys, three quarters of the capacity live, a few twice; every
    key below ``2**key_bits``."""
    rng = np.random.default_rng(seed)
    n_live = probe * 6_001_215 // 6_291_456
    top = min(n_live // 4 * 32, 1 << key_bits)
    pk = np.sort(rng.integers(1, top, n_live, dtype=np.int64))
    pk = np.concatenate([pk, np.zeros(probe - n_live, np.int64)])
    bk = rng.choice(pk[:n_live], build)
    bk[: build // 16] = bk[build // 16: 2 * (build // 16)]
    return (jnp.asarray(bk.astype(np.uint64)),
            jnp.asarray(rng.random(build) < 0.75),
            jnp.asarray(pk.astype(np.uint64)),
            jnp.asarray(np.arange(probe) < n_live))


def _join_ranges_by(search: str, key_bits: int = 64):
    """``join_ranges``' body traced with the search forced, its keys
    ranked at ``key_bits``."""
    kept = K.JOIN_SMALL_BUILD

    def body(bk, bl, pk, pl):
        K.JOIN_SMALL_BUILD = (1 << 30) if search == "count" else -1
        try:
            return K.join_ranges.__wrapped__(
                bk, bl, pk, pl, key_bits=key_bits)
        finally:
            K.JOIN_SMALL_BUILD = kept

    return body


def _count_two_sums(sk, pk):
    """The count kernel as two reductions of the 64-bit compares."""
    lo = jnp.sum(sk[:, None] < pk[None, :], axis=0, dtype=jnp.int32)
    return lo, lo + jnp.sum(
        sk[:, None] == pk[None, :], axis=0, dtype=jnp.int32)


def _count_low_words(sk, pk):
    """What a key known to fit 32 bits would cost: the low words alone
    (right only where every high word is equal)."""
    sl, pl = sk.astype(jnp.uint32), pk.astype(jnp.uint32)
    return K._packed_counts(
        sl[:, None] < pl[None, :], sl[:, None] == pl[None, :])


def joinrank_pieces(bk, bl, pk, pl, widths=()):
    """name -> (fn, args): what the sort path's ``join_ranges`` is made
    of at this shape, each alone and on the data the step before it
    leaves, then the count kernel's variants and the binary scan; for
    each of ``widths`` the build's sort and the merged rank at that
    static key width."""
    n, m = pk.shape[0], bk.shape[0]
    sk = jnp.sort(jnp.where(bl, bk, jnp.uint64(0xFFFFFFFFFFFFFFFF)))
    both = jnp.concatenate([pk, sk])
    low = both & jnp.uint64(0xFFFFFFFF)
    p1 = jax.jit(lambda w: K.packed_argsort(w, 32))(low)
    high = jax.jit(lambda k, p: (k >> jnp.uint64(32))[p])(both, p1)
    p2 = jax.jit(lambda w: K.packed_argsort(w, 32))(high)
    perm = jax.jit(lambda a, b: a[b])(p1, p2)
    is_hay = perm >= n
    dest = jnp.where(is_hay, n, perm)
    lo = jax.jit(lambda a, v: K.searchsorted(a, v))(sk, pk)
    at = jnp.clip(lo, 0, m - 1)
    run_end = jnp.arange(1, m + 1, dtype=jnp.int32)
    narrow = {}
    for bits in widths:
        word = jnp.uint32 if bits <= 32 else jnp.uint64
        sk_w = jnp.minimum(sk, jnp.uint64((1 << bits) - 1)).astype(word)
        narrow[f"build_argsort at {bits} bits"] = (
            lambda k, l, b=bits: K.packed_argsort(k, b, last=~l), (bk, bl))
        narrow[f"merge_rank whole at {bits} bits"] = (
            lambda a, v, b=bits: K.searchsorted(a, v, key_bits=b),
            (sk_w, pk.astype(word)))
    return {
        "build_argsort (packed_argsort of the build, 64 bits)":
            (lambda k, l: K.packed_argsort(k, 64, last=~l), (bk, bl)),
        "merge_rank whole (searchsorted of the probe in the build)":
            (lambda a, v: K.searchsorted(a, v), (sk, pk)),
        "merge_rank: uint64 sort of the low words with the row index":
            (lambda w: K.packed_argsort(w, 32), (low,)),
        "merge_rank: uint64 gather of the high words into that order":
            (lambda k, p: (k >> jnp.uint64(32))[p], (both, p1)),
        "merge_rank: uint64 sort of the high words with the row index":
            (lambda w: K.packed_argsort(w, 32), (high,)),
        "merge_rank: int32 gather composing the two permutations":
            (lambda a, b: a[b], (p1, p2)),
        "merge_rank: int32 cumsum of the haystack flags":
            (lambda f: jnp.cumsum(f.astype(jnp.int32)), (is_hay,)),
        "merge_rank: int32 scatter back to probe order":
            (lambda d, a: jnp.zeros((n,), jnp.int32).at[d].set(
                a, mode="drop"), (dest, perm)),
        "at gather: sorted_key[at] (uint64 out of the build)":
            (lambda t, a: t[a], (sk, at)),
        "at gather: run_end[at] (int32 out of the build)":
            (lambda t, a: t[a], (run_end, at)),
        "count kernel (kept): 32-bit halves, one packed int32 sum":
            (K._count_ranges, (sk, pk)),
        "count kernel: two sums of 64-bit compares":
            (_count_two_sums, (sk, pk)),
        "count kernel: low words alone (a 32-bit key)":
            (_count_low_words, (sk, pk)),
        "binary scan: jnp.searchsorted(method='scan') left and right":
            (lambda a, v: (jnp.searchsorted(a, v, method="scan"),
                           jnp.searchsorted(a, v, side="right",
                                            method="scan")), (sk, pk)),
        **narrow,
    }


def joinrank_main(a) -> int:
    dev = jax.devices()[0]
    rec = {"platform": dev.platform, "device_kind": dev.device_kind,
           "shape": "joinrank", "runs": [], "pieces": []}
    ok = True
    probes = [int(x) for x in a.probes.split(",")]
    builds = [int(x) for x in a.builds.split(",")]
    sorted_at = {int(x) for x in a.sorted_builds.split(",") if x}
    widths = [int(x) for x in a.key_bits.split(",") if int(x) < 64]
    for probe in probes:
        for build in builds:
            args = joinrank_inputs(
                probe, build, a.seed + build, min(widths, default=64))
            live = [np.asarray(args[1]), np.asarray(args[3])]
            # one int32 sum carries the count kernel's two counts
            searches = ["count"] * (build < 1 << 15) + (
                ["sort"] * (build in sorted_at))
            got = {}
            for search in searches:
                got[search], t = timed(_join_ranges_by(search), args, a.reps)
                run = {"probe": probe, "build": build, "search": search, **t}
                if search == "count" and got[search] is not None:
                    run["matches"] = int(jnp.sum(got[search][2]))
                if search == "sort" and "count" in got:
                    run["agrees"] = None not in got.values() and all(
                        np.array_equal(x, y) for x, y in zip(*got.values()))
                    ok &= run["agrees"]
                rec["runs"].append(run)
                print(run, flush=True)
                for bits in widths:
                    out, t = timed(
                        _join_ranges_by(search, bits), args, a.reps)
                    # the same three arrays on live rows (order: the
                    # live prefix; lo: live probes; cnt: every probe)
                    n_live = int(live[0].sum())
                    agree = None not in (out, got[search]) and all((
                        np.array_equal(out[0][:n_live],
                                       got[search][0][:n_live]),
                        np.array_equal(np.asarray(out[1])[live[1]],
                                       np.asarray(got[search][1])[live[1]]),
                        np.array_equal(out[2], got[search][2])))
                    ok &= agree
                    rec["runs"].append({
                        "probe": probe, "build": build, "search": search,
                        "key_bits": bits, "agrees_with_64": agree, **t})
                    print(rec["runs"][-1], flush=True)
                    del out
            del got
            whole = build == builds[0]
            for name, (fn, fargs) in joinrank_pieces(*args, widths).items():
                # the sort path's pieces do not depend on the build's
                # size: once a probe; the count kernel and the scan do
                if not whole and not name.startswith(("count", "binary")):
                    continue
                if build >= 1 << 15 and name.startswith(("count", "binary")):
                    continue
                _out, t = timed(fn, fargs, a.reps)
                rec["pieces"].append(
                    {"probe": probe, "build": build, "piece": name, **t})
                print(rec["pieces"][-1], flush=True)
            write(rec, a.out)  # a later shape may exhaust the call
    return 0 if ok else 1


#: (rows, capacity) of Q18's inner step, (probe, build) of Q3's
#: ``lineitem`` join, at SF1 and SF5
STARTWALK_SHAPES = {
    "sf1": ((6_291_456, 2_097_152), (4_194_304, 262_144)),
    "sf5": ((33_554_432, 8_388_608), (16_777_216, 1_048_576)),
}


def _lone_step(key, cols, mask, capacity: int, bits: int):
    """Q18's streamed step with the reads at the run starts as the
    engine made them until PR 44: the inclusive prefix sum of each limb
    at ``starts - 1`` and the key at ``owner``, three lone int64
    gathers. Same outputs as ``engine_path(K.run_group, ...)``."""
    kbits, _ = K.normalize_key(key, None)
    info, unordered = K.run_group(
        (kbits,), (None,), mask, capacity, widths=(bits,))
    masked = jnp.where(mask, cols[0], jnp.int64(0))

    def ranges(vals):
        cs = K.cumsum(vals)
        lo = K._range_gather(cs, info.starts, jnp.int64(0))
        total = K._range_gather(cs, info.ends[-1:], jnp.int64(0))
        hi = jnp.concatenate([lo[1:], total])
        return jnp.where(info.ends > info.starts, hi - lo, jnp.int64(0))

    s_hi = ranges(masked >> jnp.int64(32))
    s_lo = ranges(masked & jnp.int64(0xFFFFFFFF))
    limbs = jnp.stack(
        [s_hi + (s_lo >> jnp.int64(32)), s_lo & jnp.int64(0xFFFFFFFF)],
        axis=-1)
    own = jnp.clip(info.owner, 0, key.shape[0] - 1)
    nonempty = info.ends > info.starts
    return ([(limbs, nonempty), (key[own], None)], info.owner,
            info.num_groups, unordered)


def _at_inputs(probe: int, build: int, seed: int):
    """``join_ranges``' state where it reads at ``lo``: a sorted build
    of unique keys with a dead tail, the ends of its runs, a probe in
    key order (``lineitem``'s) of which some nine in ten find a key."""
    rng = np.random.default_rng(seed)
    live = build * 7 // 8
    sk = np.sort(rng.choice(np.arange(1, 8 * build, dtype=np.uint64),
                            live, replace=False))
    sk = np.concatenate([sk, np.full(build - live, np.uint64(2**64 - 1))])
    run_end = np.arange(1, build + 1, dtype=np.int32)
    pk = np.sort(rng.choice(sk[:live], probe))
    pk[rng.random(probe) < 0.1] += np.uint64(1)
    lo = np.searchsorted(sk, pk).astype(np.int32)
    return tuple(jnp.asarray(x) for x in (sk, run_end, pk, lo))


def _at_lone(sorted_key, run_end, probe_key, lo):
    """The two reads ``join_ranges`` made until PR 44."""
    n_build = sorted_key.shape[0]
    at = jnp.clip(lo, 0, n_build - 1)
    found = (lo < n_build) & (sorted_key[at] == probe_key)
    return jnp.where(found, run_end[at], lo)


def startwalk_main(a) -> int:
    dev = jax.devices()[0]
    rec = {"platform": dev.platform, "device_kind": dev.device_kind,
           "shape": "startwalk", "runs": []}
    ok = True

    def run(scale, what, body, fn, args, ref=None):
        nonlocal ok
        out, t = timed(fn, args, a.reps)
        row = {"scale": scale, "what": what, "body": body, **t}
        if ref is not None:
            row["agrees"] = out is not None and ref(out)
            ok &= row["agrees"]
        rec["runs"].append(row)
        print(row, flush=True)
        write(rec, a.out, echo=False)  # a later shape may exhaust the call
        return out

    for scale in a.scales.split(","):
        (rows, capacity), (probe, build) = STARTWALK_SHAPES[scale]
        if a.rows_cap:  # a rehearsal's size
            rows, capacity = a.rows_cap, a.rows_cap // 4
            probe, build = a.rows_cap // 2, a.rows_cap // 16
        key, cols, mask = q18_inputs(rows, a.seed)
        bits = int(key.max()).bit_length()
        lone = run(scale, "q18_step", "lone_gathers",
                   lambda k, c, m: _lone_step(k, c, m, capacity, bits),
                   (key, cols, mask))
        run(scale, "q18_step", "start_walk",
            engine_path(K.run_group, bits, capacity, Q18_AGGS),
            (key, cols, mask),
            ref=lambda out: lone is not None and same(out, lone))
        del lone
        # the reads alone, at the step's own starts
        info, _ = jax.jit(lambda k, m: K.run_group(
            (K.normalize_key(k, None)[0],), (None,), m, capacity,
            widths=(bits,)))(key, mask)
        at = jnp.clip(info.owner, 0, rows - 1)
        i64 = [cols[0], cols[0] + 1, key]
        three = run(scale, "q18_reads", "three_lone_int64",
                    lambda x, y, z, i: (x[i], y[i], z[i]), (*i64, at))
        run(scale, "q18_reads", "one_lone_int64", lambda x, i: x[i],
            (i64[0], at))
        run(scale, "q18_reads", "gather_rows_6_words",
            lambda x, y, z, i: tuple(
                d for d, _ in K.gather_rows(
                    {0: (x, None), 1: (y, None), 2: (z, None)}, i).values()),
            (*i64, at),
            ref=lambda out: three is not None and all(
                np.array_equal(p, q) for p, q in zip(out, three)))
        del three, info, at, i64, key, cols, mask
        args = _at_inputs(probe, build, a.seed)
        two = run(scale, "q3_at_reads", "two_lone_gathers", _at_lone, args)
        run(scale, "q3_at_reads", "one_walk_3_words", K._run_end_at, args,
            ref=lambda out: two is not None and np.array_equal(out, two))
        del two, args
    return 0 if ok else 1


def same(a, b) -> bool:
    """Bit for bit on the occupied prefix (values, validity, owners),
    and no order fault on either side."""
    (outs_a, own_a, n_a, bad_a), (outs_b, own_b, n_b, bad_b) = a, b
    g = int(n_a)
    if bool(bad_a) or bool(bad_b) or g != int(n_b) or not np.array_equal(
        own_a[:g], own_b[:g]
    ):
        return False
    for (da, va), (db, vb) in zip(outs_a, outs_b):
        if not np.array_equal(np.asarray(da)[:g], np.asarray(db)[:g]):
            return False
        if (va is None) != (vb is None):
            return False
        if va is not None and not np.array_equal(
            np.asarray(va)[:g], np.asarray(vb)[:g]
        ):
            return False
    return True


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=("q1", "q18", "q3compact", "joinrank",
                                        "startwalk"), default="q1")
    ap.add_argument("--scales", default="sf1,sf5",
                    help="which of STARTWALK_SHAPES --shape startwalk times")
    ap.add_argument("--rows-cap", type=int, default=0,
                    help="--shape startwalk at this many rows instead "
                         "(a rehearsal)")
    ap.add_argument("--probes", default=",".join(map(str, JOINRANK_PROBES)),
                    help="probe capacities of --shape joinrank")
    ap.add_argument("--builds", default=",".join(map(str, JOINRANK_BUILDS)),
                    help="build capacities of --shape joinrank")
    ap.add_argument("--sorted-builds", default="64,1024,16384",
                    help="build capacities at which --shape joinrank "
                         "times the sort path too")
    ap.add_argument("--key-bits", default="64",
                    help="static key widths at which --shape joinrank "
                         "times join_ranges beside 64 (keys are drawn "
                         "below 2**the smallest)")
    ap.add_argument("--capacity", type=int, default=Q18_CAPACITY,
                    help="group-table capacity of --shape q18")
    ap.add_argument("--rows", type=int, default=6_291_456)
    ap.add_argument("--bits", default="2,4,6,8,10")
    ap.add_argument("--sorted-bits", default="4,8",
                    help="key widths at which the sort path is timed too")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=26)
    ap.add_argument("--out", default="chiprun_out/groupby_crossover.json")
    a = ap.parse_args()
    if a.shape == "q18":
        if a.out == ap.get_default("out"):
            a.out = "chiprun_out/groupby_crossover_q18.json"
        return q18_main(a)
    if a.shape == "q3compact":
        if a.out == ap.get_default("out"):
            a.out = "chiprun_out/groupby_crossover_q3compact.json"
        return q3compact_main(a)
    if a.shape == "joinrank":
        if a.out == ap.get_default("out"):
            a.out = "chiprun_out/groupby_crossover_joinrank.json"
        return joinrank_main(a)
    if a.shape == "startwalk":
        if a.out == ap.get_default("out"):
            a.out = "chiprun_out/groupby_crossover_startwalk.json"
        return startwalk_main(a)
    dev = jax.devices()[0]
    rec = {"platform": dev.platform, "device_kind": dev.device_kind,
           "rows": a.rows, "capacity": CAPACITY, "runs": []}
    sorted_bits = {int(b) for b in a.sorted_bits.split(",") if b}
    ok = True
    for bits in (int(b) for b in a.bits.split(",")):
        args = inputs(a.rows, bits, a.seed + bits)
        direct, t = timed(engine_path(K.slot_group, bits), args, a.reps)
        rec["runs"].append({"bits": bits, "path": "direct", **t})
        print(rec["runs"][-1], flush=True)
        if bits in sorted_bits:
            srt, t = timed(engine_path(K.sort_group, bits), args, a.reps)
            agree = None not in (direct, srt) and same(direct, srt)
            ok &= agree
            rec["runs"].append(
                {"bits": bits, "path": "sorted", "agrees": agree, **t})
            print(rec["runs"][-1], flush=True)
    write(rec, a.out)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
