"""The reduction from a trace to busy union, gaps and per-program sums:
on hand-made intervals, and on the small recorded trace in testdata/."""

import gzip
import json
import os
import shutil

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
TESTDATA = os.path.join(os.path.dirname(HERE), "testdata")


def test_union_merges_overlaps_and_clips():
    iv = [(0, 10), (5, 12), (20, 30), (29, 31), (40, 50)]
    assert tr.union(iv, 0, 100) == [(0, 12), (20, 31), (40, 50)]
    assert tr.union(iv, 8, 45) == [(8, 12), (20, 31), (40, 45)]
    assert tr.gaps(tr.union(iv, 0, 60), 0, 60) == [(12, 20), (31, 40), (50, 60)]


def test_reduce_on_hand_made_trace():
    ms = 1e6
    trace = {"mark_ns": 0.0, "devices": {"/device:TPU:0": {
        "modules": [("jit_a(1)", 0, 30 * ms), ("jit_b(2)", 50 * ms, 60 * ms),
                    ("jit_a(3)", 80 * ms, 100 * ms)],
        # two ops overlap: a sum would say 65 ms, the union says 55
        "ops": [("f1", 0, 20 * ms), ("f2", 15 * ms, 30 * ms),
                ("f3", 50 * ms, 60 * ms), ("f4", 80 * ms, 100 * ms)],
    }}}
    flight = [(0, 45 * ms, "q01"), (45 * ms, 100 * ms, "q18")]
    r = tr.reduce(trace, 0, 100 * ms, flight)
    assert r["devices"] == 1 and r["executions"] == 3
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.060)
    assert dict(map(tuple, r["device_ops"])) == pytest.approx(
        {"jit_a_1": 0.030, "jit_b_2": 0.010, "jit_a_3": 0.020})
    gaps = dict(map(tuple, r["idle_gaps"]))
    # (30, 50) ms lies mostly under q01, (60, 80) ms under q18
    assert gaps == pytest.approx({"during_q01": 0.020, "during_q18": 0.020})
    # nothing in flight: the gap is nobody's
    r = tr.reduce(trace, 0, 100 * ms, [])
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"no_statement_in_flight": 0.040})


def test_recorded_trace(tmp_path):
    """A window of sf1_fleet_power recorded on the chip (PR 24): the
    numbers below were read from it once and must not move."""
    src = os.path.join(TESTDATA, "fleet_short.xplane.pb.gz")
    want = json.load(open(os.path.join(TESTDATA, "fleet_short.expected.json")))
    path = tmp_path / "t.xplane.pb"
    with gzip.open(src, "rb") as fin, open(path, "wb") as fout:
        shutil.copyfileobj(fin, fout)
    trace = tr.load(str(path))
    assert sorted(trace["devices"]) == want["devices"]
    assert trace["mark_ns"] == pytest.approx(want["mark_ns"])
    r = tr.reduce(trace, want["lo_ns"], want["hi_ns"],
                  [tuple(x) for x in want["timeline"]])
    assert r["executions"] == want["executions"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    got_ops = dict(map(tuple, r["device_ops"]))
    for name, secs in want["device_ops"]:
        assert got_ops[name] == pytest.approx(secs, rel=1e-9)
    # a sum of op durations never undercuts their union
    ops = next(iter(trace["devices"].values()))["ops"]
    total = sum(min(e, want["hi_ns"]) - max(s, want["lo_ns"])
                for _, s, e in ops if e > want["lo_ns"] and s < want["hi_ns"])
    assert total / 1e9 >= r["busy_s"] * (1 - 1e-9)


def test_window_without_the_clock_mark_fails(monkeypatch, tmp_path):
    """No silent fall-back to "first to last device operation": that
    window leaves out the idle time at both ends."""
    from types import SimpleNamespace

    trace = {"mark_ns": None, "devices": {"/device:TPU:0": {
        "modules": [("jit_a(1)", 10.0, 20.0)], "ops": []}}}
    monkeypatch.setattr(tr, "find_xplane", lambda d: "x.xplane.pb")
    monkeypatch.setattr(tr, "load", lambda p: trace)
    st = SimpleNamespace(sent_s=1.0, done_s=2.0, template="q06",
                         params={}, cls="long")
    ctx = SimpleNamespace(statements=[st], t0=1.0, t0_wall_ns=10**18)
    with pytest.raises(RuntimeError, match="bench_clock_mark"):
        tr.for_window(str(tmp_path), 10**18, ctx)
    trace["mark_ns"] = 5.0
    with pytest.raises(RuntimeError, match="bench_clock_mark"):
        tr.for_window(str(tmp_path), None, ctx)
    out = tr.for_window(str(tmp_path), 10**18, ctx)
    assert out["window_s"] == pytest.approx(1.0)
