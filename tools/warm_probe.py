"""Fresh-process warmth probe: what does a restart actually pay?

Runs the given TPC-H queries ONCE each in this (new) process and
prints one JSON line of per-query compile accounting:

    {"q01": {"compiles": 0, "compile_s": 0.0, "persistent_hits": 7,
             "jit_hits": 0, "wall_ms": 412.3}, ...}

Against a warm persistent XLA cache (JAX_COMPILATION_CACHE_DIR, default
``.jax_cache/<cpu-fingerprint>-g2`` at the repo root) and the default
``shape_bucketing=ON``, the second-ever execution of an operator mix
should show ``compiles <= 1`` per query — every program deserializes
instead of compiling. bench.py runs this as its cross-process warm
split; CI runs it twice as the warm-cache smoke test.

Usage: python tools/warm_probe.py [q01 q03 ...]   (BENCH_SF sizes data)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    qids = list(argv if argv is not None else sys.argv[1:]) or [
        "q01", "q03", "q18"
    ]
    sf = float(os.environ.get("BENCH_SF", "1"))
    schema = f"sf{sf:g}" if sf != 0.01 else "tiny"

    from trino_tpu import telemetry
    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.engine import QueryRunner

    telemetry.install_jax_compile_hook()
    runner = QueryRunner.tpch(schema)
    report = {}
    for q in qids:
        c0 = telemetry.compile_snapshot()
        t0 = time.perf_counter()
        runner.execute(QUERIES[q])
        wall = time.perf_counter() - t0
        c1 = telemetry.compile_snapshot()
        report[q] = {
            "compiles": int(c1["compiles"] - c0["compiles"]),
            "compile_s": round(
                c1["compile_seconds"] - c0["compile_seconds"], 3
            ),
            "persistent_hits": int(
                c1["persistent_hits"] - c0["persistent_hits"]
            ),
            "jit_hits": int(c1["cache_hits"] - c0["cache_hits"]),
            "wall_ms": round(wall * 1e3, 1),
        }
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
