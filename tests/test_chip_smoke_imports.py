"""chip_smoke.py is a client and a supervisor: it may not touch a JAX
backend (a parent that has, holds the chip its children need), and it
has one supervisor, the benchmark's."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, sys
import chip_smoke
from benchmarks import supervisor
shared = ("Child", "http_json", "free_port", "load_client")
print(json.dumps({
    "loaded": sorted(
        m for m in sys.modules
        if m.split(".")[0] in ("jax", "jaxlib", "trino_tpu")
    ),
    "shared": {n: getattr(chip_smoke, n) is getattr(supervisor, n)
               for n in shared},
}))
"""


def test_chip_smoke_loads_no_jax_and_takes_the_benchmarks_supervisor():
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    seen = json.loads(proc.stdout.strip().splitlines()[-1])
    assert seen["loaded"] == [], seen
    assert all(seen["shared"].values()), seen
