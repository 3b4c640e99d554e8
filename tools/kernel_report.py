"""Kernel regression report: diff two compiled-program catalog
snapshots and flag per-bucket compile-time / FLOP / temp-HBM
regressions — the static-analysis-flavored gate that keeps kernel
rewrites honest.

    # capture a fresh snapshot (warmed q01/q03/q18 on the tiny schema)
    python tools/kernel_report.py --capture fresh.json

    # gate it against the committed baseline
    python tools/kernel_report.py fresh.json \
        [--baseline tools/kernel_baseline.json] [--tolerance 0.25] \
        [--compile-tolerance 2.0]

Snapshot inputs accept every shape the repo produces: a bare entry
list (``program_catalog.CATALOG.snapshot()``), the ``{"programs":
[...]}`` wrapper ``GET /v1/programs`` serves, a diagnostics bundle, or
a BENCH JSON whose ``detail.kernel_catalog`` carries per-bucket
summaries.

Programs join on ``program_id`` (the hash of the executor cache key —
stable for identical chain/bucket/layout) with a label fallback for
cross-shape inputs. A program present on only one side reports as
NEW/GONE and SKIPs — buckets drift as queries and canonicalization
evolve, and the gate must stay useful across that drift. Checked per
joined bucket, all lower-is-better:

  * ``flops``       — XLA cost model, fractional ``--tolerance`` band
  * ``temp_bytes``  — memory_analysis HBM scratch, same band
  * ``compile_s``   — wall clock, the loose ``--compile-tolerance``
    band (machine-load noise) plus 50ms absolute slack

Exit 0 = clean, 1 = at least one regression, 2 = unusable input.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

__all__ = ["load_snapshot", "compare", "capture_snapshot", "main"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_DEFAULT_BASELINE = os.path.join(_HERE, "kernel_baseline.json")

#: (field, kind): "band" uses --tolerance, "compile" the loose band
_CHECKS = [
    ("flops", "band"),
    ("temp_bytes", "band"),
    ("compile_s", "compile"),
]
#: absolute compile-seconds slack: sub-50ms jitter is machine noise
_COMPILE_SLACK_S = 0.05


def load_snapshot(path: str) -> list[dict]:
    """Entry list from any snapshot shape the repo produces."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "parsed" in doc and "programs" not in doc:
        doc = doc["parsed"]  # committed BENCH wrapper
        if doc is None:
            raise ValueError(f"{path}: wrapper has parsed=null")
    if isinstance(doc, dict):
        if isinstance(doc.get("programs"), list):
            doc = doc["programs"]
        elif isinstance(
            (doc.get("detail") or {}).get("kernel_catalog"), list
        ):
            doc = doc["detail"]["kernel_catalog"]
        else:
            raise ValueError(
                f"{path}: no program list ('programs' / "
                "'detail.kernel_catalog' / bare list)"
            )
    if not isinstance(doc, list):
        raise ValueError(f"{path}: not a catalog snapshot")
    for e in doc:
        if not isinstance(e, dict) or "program_id" not in e:
            raise ValueError(f"{path}: entry without program_id")
    return doc


def _join(fresh: list[dict], baseline: list[dict]):
    """(pairs, new, gone): join on program_id, then label for
    leftovers that are unique per side."""
    by_id = {e["program_id"]: e for e in baseline}
    used = set()
    pairs, new = [], []
    for f in fresh:
        b = by_id.get(f["program_id"])
        if b is not None:
            pairs.append((f, b))
            used.add(f["program_id"])
        else:
            new.append(f)
    # label fallback: unique labels on both remaining sides
    rem_b = [e for e in baseline if e["program_id"] not in used]

    def uniq(entries):
        seen: dict = {}
        for e in entries:
            seen.setdefault(e.get("label"), []).append(e)
        return {
            lbl: es[0] for lbl, es in seen.items()
            if lbl and len(es) == 1
        }

    bl = uniq(rem_b)
    still_new = []
    for f in new:
        b = bl.pop(f.get("label"), None)
        if b is not None:
            pairs.append((f, b))
        else:
            still_new.append(f)
    gone = [
        e for e in rem_b
        if all(e is not b for _f, b in pairs)
    ]
    return pairs, still_new, gone


def compare(
    fresh: list[dict], baseline: list[dict],
    tolerance: float = 0.25, compile_tolerance: float = 2.0,
) -> list[dict]:
    """One row per (bucket, metric): {program_id, label, metric,
    status, fresh, baseline}; plus NEW/GONE rows per unmatched bucket."""
    pairs, new, gone = _join(fresh, baseline)
    rows = []
    for f, b in pairs:
        ident = {
            "program_id": f["program_id"],
            "label": f.get("label") or "?",
        }
        for metric, kind in _CHECKS:
            fv, bv = f.get(metric), b.get(metric)
            if not isinstance(fv, (int, float)) or not isinstance(
                bv, (int, float)
            ):
                rows.append({**ident, "metric": metric,
                             "status": "SKIP", "fresh": fv,
                             "baseline": bv})
                continue
            if kind == "compile":
                bad = fv > bv * (1.0 + compile_tolerance) + _COMPILE_SLACK_S
                improved = fv < bv / (1.0 + compile_tolerance)
            else:
                slack = max(abs(bv) * tolerance, 1.0)
                bad = fv > bv + slack
                improved = fv < bv - slack
            rows.append({
                **ident, "metric": metric,
                "status": ("REGRESSION" if bad
                           else "IMPROVED" if improved else "OK"),
                "fresh": fv, "baseline": bv,
            })
    for f in new:
        rows.append({"program_id": f["program_id"],
                     "label": f.get("label") or "?",
                     "metric": "-", "status": "NEW",
                     "fresh": None, "baseline": None})
    for b in gone:
        rows.append({"program_id": b["program_id"],
                     "label": b.get("label") or "?",
                     "metric": "-", "status": "GONE",
                     "fresh": None, "baseline": None})
    return rows


def capture_snapshot(out_path: str) -> int:
    """Run the warmed q01/q03/q18 set on the tiny TPC-H schema and
    write the resulting catalog snapshot (the committed-baseline
    generator; also what CI captures fresh)."""
    sys.path.insert(0, os.path.dirname(_HERE))  # repo root
    # real compile wall, not a persistent-cache deserialize: a warm
    # machine would record ~6x-lower compile_s than the cold CI runner
    # and the gate would flag phantom compile regressions.
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from trino_tpu import program_catalog
    from trino_tpu.engine import QueryRunner
    from trino_tpu.connectors.tpch.queries import QUERIES

    program_catalog.CATALOG.clear()
    runner = QueryRunner.tpch()
    for q in ("q01", "q03", "q18"):
        for _warm in range(2):  # second run = warm (hits, no compile)
            runner.execute(QUERIES[q])
    snap = program_catalog.CATALOG.snapshot()
    with open(out_path, "w") as f:
        json.dump({"programs": snap}, f, indent=1, sort_keys=True)
    print(
        f"kernel-report: captured {len(snap)} program(s) -> {out_path}"
    )
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0]
    )
    ap.add_argument(
        "fresh", nargs="?",
        help="fresh catalog snapshot (any repo shape)",
    )
    ap.add_argument(
        "--capture", metavar="OUT",
        help="run warmed q01/q03/q18 and write the catalog snapshot "
        "instead of comparing",
    )
    ap.add_argument(
        "--baseline", default=_DEFAULT_BASELINE,
        help="snapshot to gate against "
        "(default: tools/kernel_baseline.json)",
    )
    ap.add_argument(
        "--tolerance", type=float, default=0.25,
        help="fractional band for flops/temp_bytes (default 0.25)",
    )
    ap.add_argument(
        "--compile-tolerance", type=float, default=2.0,
        help="fractional band for compile seconds (default 2.0 — "
        "compile wall is machine-load noisy)",
    )
    args = ap.parse_args(argv)

    if args.capture:
        return capture_snapshot(args.capture)
    if not args.fresh:
        ap.error("fresh snapshot path required (or --capture OUT)")

    try:
        fresh = load_snapshot(args.fresh)
        baseline = load_snapshot(args.baseline)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"kernel-report: unusable input: {e}", file=sys.stderr)
        return 2

    rows = compare(
        fresh, baseline, args.tolerance, args.compile_tolerance
    )
    regressions = [r for r in rows if r["status"] == "REGRESSION"]
    for r in rows:
        if r["status"] in ("NEW", "GONE"):
            print(
                f"  {r['status']:<10} {r['program_id']} "
                f"[{r['label']}] (unmatched bucket, skipped)"
            )
        elif r["status"] == "SKIP":
            print(
                f"  SKIP       {r['program_id']} [{r['label']}] "
                f"{r['metric']} (missing on one side)"
            )
        else:
            print(
                f"  {r['status']:<10} {r['program_id']} "
                f"[{r['label']}] {r['metric']}: {r['fresh']} vs "
                f"baseline {r['baseline']}"
            )
    checked = sum(
        1 for r in rows
        if r["status"] in ("OK", "IMPROVED", "REGRESSION")
    )
    print(
        f"kernel-report: {checked} checked, "
        f"{len(regressions)} regression(s), "
        f"tolerance ±{args.tolerance:.0%} "
        f"(compile ±{args.compile_tolerance:.0%}), "
        f"baseline {os.path.basename(args.baseline)}"
    )
    return 1 if regressions else 0


if __name__ == "__main__":
    raise SystemExit(main())
