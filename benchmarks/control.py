#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: it has to
come out as NOT correct.

The configuration states exact decimal arithmetic. The step that would
tempt a later PR is narrower lanes (ROADMAP S2): sums accumulated in
float32. So the control is the reference put in the program's place
with every ``sum`` accumulated in float32 (reference.Float32Sum): for
each seed, the statements a window of the cell would hold are answered
by that control, and the answers go through the same comparison, with
the same limits, as a run's. It needs no server and no chip: both
sides are sqlite on the host, at the cell's own schema.

    python3 benchmarks/control.py --workload sf1_power --seeds 3

Prints one line a seed, the numbers compared beside their limits, and
exits 0 only if every seed came out not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reference  # noqa: E402
import run as harness  # noqa: E402
import traffic  # noqa: E402


#: passes a closed-loop window holds at least (PERF.md, section 2)
CLOSED_LOOP_PASSES = 6


def window_statements(mix: dict, seed: int, seconds: float) -> list:
    """What a window of the mix holds: its schedule (open loop) or as
    many passes as a run of the ledger's speed makes (closed loop)."""
    if mix["loop"] == "open":
        return traffic.open_schedule(mix, seed, seconds)
    out = []
    for i in range(int(mix.get("clients", 1))):
        loop = traffic.ClosedLoop(mix, seed + 7919 * i, i)
        for _ in range(CLOSED_LOOP_PASSES):
            out += loop.next_pass()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147483700)
    ap.add_argument("--schema", default=None,
                    help="another schema than the configuration's (tests)")
    args = ap.parse_args(argv)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell, cfg_entry = harness.find_cell(bench, args.workload)
    config = harness.load_json(os.path.join(harness.ROOT, cfg_entry["file"]))
    mix = traffic.load_mix(cell["traffic"])
    schema = args.schema or config["schema"]
    logdir = os.path.join(harness.WORK, args.workload + "-control")
    os.makedirs(logdir, exist_ok=True)
    sides = {}
    for name, control in (("reference", False), ("control", True)):
        ref = harness.Reference(config, schema, mix, control=control)
        if ref.missing():
            ref.start(logdir)
            try:
                ref.wait("ready", 3000)
            finally:
                ref.stop()
        sides[name] = ref
    failed_every_seed = True
    for i in range(args.seeds):
        seed = args.first_seed + i
        sts = window_statements(mix, seed, float(bench["run_seconds"]))
        for st in sts:
            spec = mix["templates"][st.template].compare
            st.rows = [reference.served_form(spec["columns"], r)
                       for r in sides["control"].expected(st)]
        nums = harness.compare(mix, sides["reference"], sts)["numbers"]
        nums["result_cache_hits"] = 0
        correct = all(nums[k] <= harness.LIMITS[k] for k in harness.LIMITS)
        failed_every_seed &= not correct
        print(json.dumps({
            "control": "float32 sums", "workload": args.workload,
            "seed": seed, "statements": len(sts), "correct": correct,
            "compared": {k: {"value": nums[k], "limit": harness.LIMITS[k]}
                         for k in harness.LIMITS},
        }), flush=True)
    return 0 if failed_every_seed else 1


if __name__ == "__main__":
    sys.exit(main())
