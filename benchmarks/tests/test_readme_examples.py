"""README.md's worked examples, added to a temporary copy of the
benchmark exactly as written there — new files and new entries, no file
that is there edited — and the new cell run end to end at the rehearsal
schema on the CPU."""

import json
import os
import re
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

BLOCK = re.compile(r"```\w+ ([^\n]*)\n(.*?)```", re.S)


def test_readme_examples_run(tmp_path):
    with open(os.path.join(BENCH, "README.md")) as fh:
        readme = fh.read()
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "trino_tpu"), root / "trino_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    added = []
    for head, body in BLOCK.findall(readme):
        attrs = dict(kv.split("=", 1) for kv in head.split())
        if "file" in attrs:
            path = root / attrs["file"]
            assert not path.exists(), f"{attrs['file']} edits a file that is there"
            if "copy" in attrs:
                with open(os.path.join(ROOT, attrs["copy"])) as fh:
                    merged = json.load(fh)
                merged.update(json.loads(body))
                body = json.dumps(merged, indent=2)
            path.write_text(body)
            added.append(attrs["file"])
        elif attrs.get("merge") == "BENCHMARK.json":
            extra = json.loads(body)
            for key in ("configs", "workloads", "per_layer"):
                bench[key] += extra[key]
            for name, cells in extra["end_to_end_workloads"].items():
                for m in bench["end_to_end"]:
                    if m["name"] == name:
                        m["workloads"] = m["workloads"] + cells
    assert len(added) == 6, added
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "sf1_tiles",
         "--seed", "3", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]   # no chip: no result
    assert p.stdout.strip() == ""
    line = [x for x in p.stderr.splitlines()
            if x.startswith("rehearsal result")][-1]
    res = json.loads(line.split(": ", 1)[1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 4 and res["attempted"] % 2 == 0, "whole passes"
    assert "dispatch.queue_wait_ms.tiles" in res["metrics"]
    assert "executor.compiles_in_window" not in res["metrics"], \
        "metrics of other cells stay out of a new cell's line"
    with open(root / ".bench_work" / "sf1_tiles" / "statements.jsonl") as fh:
        sent = [json.loads(x) for x in fh]
    assert {s["template"] for s in sent} == {"q06", "orders_by_status"}
