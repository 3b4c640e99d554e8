"""tools/bench_gate.py: regression gate of a fresh bench JSON against
a baseline trajectory.

The gate must exit 0 when a fresh result matches the baseline, 1 on a
regression past the tolerance band, and 2 on unusable input (e.g. a
trajectory wrapper whose run died before printing its JSON line).
Exercised through the CLI exactly as CI invokes it, against a small
synthetic baseline (the wrapper shape and keys of a committed
``BENCH_r0x.json``; the values are made up and measure nothing).
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE = os.path.join(ROOT, "tools", "bench_gate.py")

_SYNTHETIC = {
    "metric": "tpch_sf1_q1_rows_per_sec",
    "value": 1_000_000.0,
    "unit": "rows/s",
    "vs_baseline": 2.0,
    "detail": {
        "q01_ms": 400.0, "q03_ms": 900.0, "q18_ms": 1500.0,
        "join_agg_rows_per_sec_chip": 2_000_000.0,
        "join_agg_ms": 300.0,
        "q01_warmup_compiles": 3, "q01_warm_compiles": 0,
    },
}


@pytest.fixture(autouse=True)
def baseline(tmp_path, monkeypatch):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({
        "n": 0, "cmd": "synthetic", "rc": 0, "tail": "",
        "parsed": _SYNTHETIC,
    }))
    monkeypatch.setattr(sys.modules[__name__], "BASELINE", str(path))
    return str(path)


BASELINE = ""


def _run(*args):
    return subprocess.run(
        [sys.executable, GATE, *args, "--baseline", BASELINE],
        capture_output=True, text=True,
    )


def _baseline_parsed() -> dict:
    return json.loads(json.dumps(_SYNTHETIC))


def test_gate_passes_on_the_baseline_itself():
    p = _run(BASELINE)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "0 regression(s)" in p.stdout


def test_gate_fails_on_synthetic_2x_regression(tmp_path):
    doc = _baseline_parsed()
    doc["value"] /= 2.0
    doc["detail"]["q03_ms"] *= 2.0
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(doc))
    p = _run(str(fresh))
    assert p.returncode == 1, p.stdout + p.stderr
    assert "REGRESSION" in p.stdout
    assert "value" in p.stdout and "q03_ms" in p.stdout


def test_gate_improvement_is_not_a_failure(tmp_path):
    doc = _baseline_parsed()
    doc["value"] *= 2.0
    doc["detail"]["q01_ms"] /= 2.0
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(doc))
    p = _run(str(fresh))
    assert p.returncode == 0, p.stdout + p.stderr
    assert "IMPROVED" in p.stdout


def test_gate_tolerates_missing_keys(tmp_path):
    # a minimal bare bench line: only the headline — everything else
    # must SKIP, not fail
    doc = {"value": _baseline_parsed()["value"], "detail": {}}
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(doc))
    p = _run(str(fresh))
    assert p.returncode == 0, p.stdout + p.stderr
    assert "SKIP" in p.stdout


def test_gate_rejects_dead_wrapper(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"rc": 124, "parsed": None}))
    p = _run(str(bad))
    assert p.returncode == 2
    assert "unusable" in p.stderr


def test_gate_custom_tolerance(tmp_path):
    doc = _baseline_parsed()
    doc["value"] *= 0.9  # -10%: inside ±25%, outside ±5%
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(doc))
    assert _run(str(fresh)).returncode == 0
    assert _run(str(fresh), "--tolerance", "0.05").returncode == 1
