#!/usr/bin/env python
"""Where the crossover between slot-addressed and sorted group-by lies:
device time of a Q1-shaped grouped aggregate (four decimal sums, three
decimal averages, a count, over five int64 columns) by both paths of
``exec/stage.py:_aggregate_step`` at each packed key width.

``kernels.SLOT_KEY_BITS`` is set from this table (PERF.md, PR 26). Run
it on the chip: ``chiprun -- python tools/groupby_crossover.py``. On a
CPU it checks that both paths agree and prints host times, which are
no device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.exec import kernels as K
from trino_tpu.exec.aggregates import compute_aggregate
from trino_tpu.exec.stage import _presort_shared

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


def engine_path(group_fn, bits: int):
    """The step as ``_aggregate_step`` builds it, by one grouping path."""

    def prog(key, cols, mask):
        kbits, _ = K.normalize_key(key, None)
        info = group_fn((kbits,), (None,), mask, CAPACITY, widths=(bits,))
        share = {"#mask": mask}
        prepared = [
            (None, None, None if c is None else (cols[c], None), mask)
            for _n, _t, c in AGGS
        ]
        if isinstance(info, K.GroupInfo):
            _presort_shared(prepared, info, share)
        out = [
            compute_aggregate(name, typ, arg, info, CAPACITY, mask, share=share)
            for (name, typ, _c), (_s, _k, arg, _m) in zip(AGGS, prepared)
        ]
        return out, info.owner, info.num_groups

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


def same(a, b) -> bool:
    """Bit for bit on the occupied prefix (values, validity, owners)."""
    (outs_a, own_a, n_a), (outs_b, own_b, n_b) = a, b
    g = int(n_a)
    if g != int(n_b) or not np.array_equal(own_a[:g], own_b[:g]):
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
    ap.add_argument("--rows", type=int, default=6_291_456)
    ap.add_argument("--bits", default="2,4,6,8,10")
    ap.add_argument("--sorted-bits", default="4,8",
                    help="key widths at which the sort path is timed too")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=26)
    ap.add_argument("--out", default="chiprun_out/groupby_crossover.json")
    a = ap.parse_args()
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
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as fh:
        json.dump(rec, fh, indent=1)
    print(json.dumps(rec))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
