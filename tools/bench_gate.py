"""Bench regression gate: compare a fresh bench.py JSON line against a
baseline bench JSON named on the command line (no default: a gate
against numbers from another machine class is not a gate).

    python tools/bench_gate.py fresh.json --baseline BASE.json
                               [--tolerance 0.25]

Both inputs may be either shape the repo produces:
  * the bare object bench.py prints (``{"metric", "value", "detail"}``)
  * the committed wrapper (``{"n", "cmd", "rc", "tail", "parsed": {...}}``)
The wrapper is unwrapped through ``parsed``; a wrapper whose run died
before emitting JSON (``parsed: null`` — a run that timed out) is
rejected with exit code 2 so CI shows a config error, not a fake pass.

Checked, each with the same fractional tolerance band:
  * headline ``value`` (rows/s, higher is better)
  * per-query wall clock ``detail.q01_ms/q03_ms/q18_ms`` (lower better)
  * ``detail.join_agg_rows_per_sec_chip`` (higher is better)
  * compile counts (``*_warmup_compiles``/``*_warm_compiles``, lower is
    better) — counts get ``max(1, tol*baseline)`` absolute slack since
    a band around 0 or 2 is meaningless

A key missing from EITHER side is reported as SKIP, never a failure:
older trajectories predate the compile-tax split and newer ones may
drop sections, and the gate must stay useful across that drift.
Improvements are reported but never fail. Exit 0 = no regressions,
1 = at least one metric regressed past the band, 2 = unusable input.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

__all__ = ["compare", "load_bench", "main"]

#: (key, higher_is_better) — dotted keys index into detail. The
#: serving keys (BENCH_r08+) SKIP against older baselines that
#: predate ``bench.py --serving`` — SKIP-not-fail is the contract.
_RATE_KEYS = [
    ("value", True),
    ("vs_baseline", True),
    # single-chip floor vs the hand-vectorized numpy baseline
    # (SKIPs against baselines that predate it)
    ("detail.vs_numpy_geomean", True),
    ("detail.q01_ms", False),
    ("detail.q03_ms", False),
    ("detail.q18_ms", False),
    ("detail.join_agg_rows_per_sec_chip", True),
    ("detail.serving_qps", True),
    ("detail.serving_p95_ms", False),
    ("detail.serving_p99_ms", False),
    # storage keys (BENCH_r06+, ``bench.py --storage``): SKIP against
    # baselines that predate the out-of-core streamed scan tier
    ("detail.storage_stream_rows_per_s", True),
    ("detail.storage_pushdown_rows_per_s", True),
    # exchange keys (BENCH_r07+, ``bench.py --exchange``): SKIP against
    # baselines that predate the direct memory-exchange path
    ("detail.fleet_direct_q03_ms", False),
    ("detail.fleet_direct_q05_ms", False),
    ("detail.fleet_direct_q09_ms", False),
    ("detail.fleet_spool_q03_ms", False),
    ("detail.fleet_spool_q05_ms", False),
    ("detail.fleet_spool_q09_ms", False),
    ("detail.exchange_direct_fetch_ratio", True),
    # skew keys (BENCH_r09+, ``bench.py --skew``): SKIP against
    # baselines that predate salted repartition / adaptive growth
    ("detail.skew_hot_unsalted_ms", False),
    ("detail.skew_hot_salted_ms", False),
    ("detail.skew_hot_salted_input_skew", False),
    ("detail.skew_zipf_salted_ms", False),
    ("detail.skew_zipf_salted_input_skew", False),
    ("detail.skew_hot_adaptive_ms", False),
    # elastic keys (BENCH_r10+, diurnal 2->4->2 scale under load):
    # SKIP against baselines that predate the membership layer
    ("detail.serving_diurnal_low1_p99_ms", False),
    ("detail.serving_diurnal_high_p99_ms", False),
    ("detail.serving_diurnal_low2_p99_ms", False),
    # cache keys (BENCH_r10+, ``bench.py --serving`` zipfian twin):
    # SKIP against baselines that predate the cross-query cache tiers
    ("detail.serving_cached_p50_ms", False),
    ("detail.serving_uncached_p50_ms", False),
    ("detail.result_cache_hit_ratio", True),
    ("detail.serving_cache_cold_p99_ms", False),
    # sentry keys (BENCH_r11+, ``bench.py --sentry``): how fast the
    # performance sentry turned an injected regression into a typed
    # verdict; SKIP against baselines that predate the sentry
    ("detail.sentry_detection_latency_ms", False),
    ("detail.sentry_overhead_ms", False),
]
# NOT banded: the per-query ``detail.{q}_time_breakdown`` dicts
# (BENCH_r08+, flight recorder) are informational — dict-valued and
# too machine-sensitive to gate; like every key outside _RATE_KEYS
# they SKIP rather than fail against any baseline.

#: compile-count keys: lower is better, absolute slack not a pure band
_COUNT_KEYS = [
    f"detail.{q}_{kind}"
    for q in ("q01", "q03", "q18")
    for kind in ("warmup_compiles", "warm_compiles")
]


def load_bench(path: str) -> dict:
    """Load a bench JSON file, unwrapping the committed
    ``{"parsed": {...}}`` trajectory shape when present."""
    with open(path) as f:
        doc = json.load(f)
    if "parsed" in doc and "value" not in doc:
        parsed = doc["parsed"]
        if parsed is None:
            raise ValueError(
                f"{path}: wrapper has parsed=null (rc={doc.get('rc')})"
                " — that run never emitted its JSON line"
            )
        doc = parsed
    if "value" not in doc:
        raise ValueError(f"{path}: no 'value' key — not a bench JSON")
    return doc


def _get(doc: dict, dotted: str):
    cur = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def compare(fresh: dict, baseline: dict, tolerance: float) -> list[dict]:
    """One row per metric: {key, status, fresh, baseline, ratio}.
    status in {OK, IMPROVED, REGRESSION, SKIP}."""
    rows = []
    for key, higher_better in _RATE_KEYS:
        f, b = _get(fresh, key), _get(baseline, key)
        if not isinstance(f, (int, float)) or not isinstance(b, (int, float)) or not b:
            rows.append({"key": key, "status": "SKIP",
                         "fresh": f, "baseline": b})
            continue
        ratio = f / b
        if higher_better:
            bad = ratio < 1.0 - tolerance
            improved = ratio > 1.0 + tolerance
        else:
            bad = ratio > 1.0 + tolerance
            improved = ratio < 1.0 - tolerance
        rows.append({
            "key": key,
            "status": ("REGRESSION" if bad
                       else "IMPROVED" if improved else "OK"),
            "fresh": f, "baseline": b, "ratio": round(ratio, 3),
        })
    for key in _COUNT_KEYS:
        f, b = _get(fresh, key), _get(baseline, key)
        if not isinstance(f, (int, float)) or not isinstance(b, (int, float)):
            rows.append({"key": key, "status": "SKIP",
                         "fresh": f, "baseline": b})
            continue
        slack = max(1.0, tolerance * b)
        rows.append({
            "key": key,
            "status": "REGRESSION" if f > b + slack
            else "IMPROVED" if f < b else "OK",
            "fresh": f, "baseline": b,
        })
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0]
    )
    ap.add_argument("fresh", help="fresh bench JSON (bare or wrapped)")
    ap.add_argument(
        "--baseline", required=True,
        help="trajectory to gate against (bare or wrapped bench JSON "
        "from the same machine class as the fresh run)",
    )
    ap.add_argument(
        "--tolerance", type=float, default=0.25,
        help="fractional band; 0.25 = fail on >25%% regression",
    )
    args = ap.parse_args(argv)

    try:
        fresh = load_bench(args.fresh)
        baseline = load_bench(args.baseline)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench-gate: unusable input: {e}", file=sys.stderr)
        return 2

    rows = compare(fresh, baseline, args.tolerance)
    regressions = [r for r in rows if r["status"] == "REGRESSION"]
    for r in rows:
        if r["status"] == "SKIP":
            print(f"  SKIP       {r['key']} (missing on one side)")
        else:
            extra = (
                f" ({r['ratio']}x)" if "ratio" in r else ""
            )
            print(
                f"  {r['status']:<10} {r['key']}: "
                f"{r['fresh']} vs baseline {r['baseline']}{extra}"
            )
    checked = sum(1 for r in rows if r["status"] != "SKIP")
    print(
        f"bench-gate: {checked} checked, "
        f"{len(regressions)} regression(s), "
        f"tolerance ±{args.tolerance:.0%}, "
        f"baseline {os.path.basename(args.baseline)}"
    )
    return 1 if regressions else 0


if __name__ == "__main__":
    raise SystemExit(main())
