"""Performance sentry: durable history, per-plan baselines, live
attributed anomaly detection (trino_tpu/history.py + sentry.py).

Covers the PR's acceptance contract:
  * baseline-model units — warmup min-samples, MAD bands, bounded
    retention, restart-survives-reload;
  * driver attribution per flight-recorder bucket, plus the
    cache-miss-expected-hit class;
  * a live 2-worker fleet e2e — a seeded compile-delay on a warmed
    statement yields exactly one xla_compile verdict, a diagnostics
    bundle, a system.runtime.anomalies row, and a metrics delta,
    while the healthy twin yields none.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from trino_tpu import fault, history, sentry, telemetry, tracker
from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.metadata import Metadata, Session

BASE_PORT = 19900

_AGG_SQL = (
    "select o_orderpriority, count(*) from orders "
    "group by o_orderpriority order by 1"
)


def _entry(wall_ms, *, digest="d0", fingerprint="f0", state="FINISHED",
           buckets=None, tier=None, query_id="q"):
    return {
        "query_id": query_id,
        "ts": 1000.0,
        "state": state,
        "plan_digest": digest,
        "fingerprint": fingerprint,
        "wall_ms": float(wall_ms),
        "buckets": dict(buckets or {}),
        "cache_hit_tier": tier,
    }


@pytest.fixture
def fresh_sentry():
    """Fresh process singletons around each test that touches them."""
    prev_h, prev_s = history.active(), sentry.active()
    store = history.QueryHistory(root=None, max_entries=256)
    sen = sentry.Sentry(min_samples=3, mads=5.0, min_ratio=1.5,
                        min_delta_ms=5.0)
    history.set_active(store)
    sentry.set_active(sen)
    yield store, sen
    history.set_active(prev_h)
    sentry.set_active(prev_s)


# ---------------------------------------------------------------------------
# baseline model units
# ---------------------------------------------------------------------------


def test_baseline_model_robust_stats_and_retention():
    m = sentry.BaselineModel(retention=4)
    for w in (100.0, 102.0, 98.0, 101.0):
        m.observe(w, {"scan": w / 2}, None)
    assert m.samples == 4
    assert m.p50() == pytest.approx(100.5)
    assert m.mad() == pytest.approx(1.0)
    assert m.bucket_median("scan") == pytest.approx(50.25)
    assert m.bucket_median("absent") == 0.0
    # bounded retention: old samples roll off
    for w in (200.0, 200.0, 200.0, 200.0):
        m.observe(w, None, None)
    assert m.samples == 4
    assert m.p50() == 200.0


def test_result_hit_rate():
    m = sentry.BaselineModel()
    for _ in range(4):
        m.observe(1.0, None, "result")
    m.observe(50.0, None, None)
    assert m.result_hit_rate() == pytest.approx(0.8)


def test_warmup_no_verdict_then_detection():
    sen = sentry.Sentry(min_samples=3, min_delta_ms=5.0)
    # two clean samples — below warmup, even a 100x wall is silent
    assert sen.observe(_entry(10.0)) is None
    assert sen.observe(_entry(10.0)) is None
    assert sen.observe(_entry(1000.0)) is None  # still warming (2 < 3)
    # the warmup outlier was FED (warmup samples always feed), so the
    # model now holds 10, 10, 1000 — median 10, huge MAD tolerance is
    # avoided because MAD of (0, 0, 990) is 0
    assert sen.model_for("d0", "f0").samples == 3
    v = sen.observe(_entry(500.0))
    assert v is not None and v.plan_digest == "d0"
    # the anomalous sample was NOT fed into the baseline
    assert sen.model_for("d0", "f0").samples == 3


def test_band_guards_block_micro_regressions():
    sen = sentry.Sentry(min_samples=3, mads=5.0, min_ratio=1.5,
                        min_delta_ms=50.0)
    for w in (100.0, 101.0, 99.0, 100.0):
        assert sen.observe(_entry(w)) is None
    # above the MAD band but under min_ratio (1.4x) -> silent
    assert sen.observe(_entry(140.0)) is None
    # above ratio but under min_delta_ms -> silent
    tight = sentry.Sentry(min_samples=3, mads=5.0, min_ratio=1.5,
                          min_delta_ms=500.0)
    for w in (100.0, 101.0, 99.0):
        tight.observe(_entry(w))
    assert tight.observe(_entry(300.0)) is None


def test_failed_queries_never_fed_never_judged():
    sen = sentry.Sentry(min_samples=2, min_delta_ms=1.0)
    for w in (10.0, 10.0, 10.0):
        sen.observe(_entry(w))
    assert sen.observe(_entry(9999.0, state="FAILED")) is None
    assert sen.model_for("d0", "f0").samples == 3


def test_fingerprint_partitions_baselines():
    sen = sentry.Sentry(min_samples=2, min_delta_ms=1.0)
    for w in (10.0, 10.0, 10.0):
        sen.observe(_entry(w, fingerprint="fast-knobs"))
    # same digest, different knobs: no baseline yet, no verdict
    assert sen.observe(
        _entry(500.0, fingerprint="slow-knobs")
    ) is None
    assert sen.model_for("d0", "slow-knobs").samples == 1


def test_session_fingerprint_tracks_properties():
    s1 = Session(catalog="tpch", schema="tiny")
    s2 = Session(catalog="tpch", schema="tiny")
    assert history.session_fingerprint(s1) == \
        history.session_fingerprint(s2)
    s2.properties["exchange_mode"] = "SPOOL"
    assert history.session_fingerprint(s1) != \
        history.session_fingerprint(s2)


# ---------------------------------------------------------------------------
# driver attribution
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bucket", [
    "xla_compile", "scan", "exchange", "straggler_slack", "queued",
])
def test_driver_attribution_names_the_grown_bucket(bucket):
    sen = sentry.Sentry(min_samples=3, min_delta_ms=5.0)
    base = {"scan": 20.0, "compute": 60.0, "exchange": 15.0}
    for _ in range(4):
        assert sen.observe(_entry(100.0, buckets=base)) is None
    hot = dict(base)
    hot[bucket] = hot.get(bucket, 0.0) + 400.0
    v = sen.observe(_entry(500.0, buckets=hot))
    assert v is not None
    assert v.driver == bucket
    assert v.driver_delta_ms == pytest.approx(400.0, abs=1.0)
    assert bucket in v.message


def test_driver_cache_miss_expected_hit():
    sen = sentry.Sentry(min_samples=3, min_delta_ms=1.0)
    for _ in range(5):
        sen.observe(_entry(2.0, tier="result"))
    v = sen.observe(_entry(200.0, tier=None,
                           buckets={"compute": 150.0}))
    assert v is not None
    assert v.driver == "cache_miss_expected_hit"


def test_attribution_falls_back_to_other():
    sen = sentry.Sentry(min_samples=3, min_delta_ms=1.0)
    for _ in range(4):
        sen.observe(_entry(10.0, buckets={"compute": 8.0}))
    # wall exploded but no bucket grew — the recorder couldn't see it
    v = sen.observe(_entry(500.0, buckets={"compute": 8.0}))
    assert v is not None and v.driver == "other"


# ---------------------------------------------------------------------------
# history store: ring, durability, compaction
# ---------------------------------------------------------------------------


def test_history_ring_bounded_in_memory():
    h = history.QueryHistory(root=None, max_entries=8)
    for i in range(20):
        h.append({"query_id": f"q{i}"})
    assert len(h) == 8
    assert h.entries()[0]["query_id"] == "q12"
    assert h.entries(limit=2)[-1]["query_id"] == "q19"


def test_history_durable_roundtrip_and_torn_tail(tmp_path):
    root = str(tmp_path / "hist")
    h = history.QueryHistory(root=root, max_entries=64)
    for i in range(5):
        h.append({"query_id": f"q{i}", "wall_ms": float(i)})
    # simulate a crash mid-append: torn trailing line
    with open(h.path, "a") as f:
        f.write('{"query_id": "torn')
    h2 = history.QueryHistory(root=root, max_entries=64)
    assert len(h2) == 5
    assert [e["query_id"] for e in h2.entries()] == \
        [f"q{i}" for i in range(5)]


def test_history_compaction_bounds_the_file(tmp_path):
    root = str(tmp_path / "hist")
    h = history.QueryHistory(root=root, max_entries=4)
    for i in range(20):
        h.append({"query_id": f"q{i}"})
    with open(h.path) as f:
        lines = [ln for ln in f if ln.strip()]
    assert len(lines) <= 8  # 2x bound triggers rewrite to the ring
    h2 = history.QueryHistory(root=root, max_entries=4)
    assert [e["query_id"] for e in h2.entries()] == \
        [f"q{i}" for i in range(16, 20)]


def test_restart_survives_reload_and_excludes_anomalies(tmp_path):
    root = str(tmp_path / "hist")
    store = history.QueryHistory(root=root)
    sen = sentry.Sentry(store, min_samples=3, min_delta_ms=5.0)
    for w in (10.0, 11.0, 9.0, 10.0):
        e = _entry(w)
        store.append(e)
        assert sen.observe(e) is None
    bad = _entry(500.0)
    store.append(bad)
    assert sen.observe(bad) is not None
    assert sen.model_for("d0", "f0").samples == 4
    # restart: a fresh store + sentry rebuilt from the JSONL
    store2 = history.QueryHistory(root=root)
    sen2 = sentry.Sentry(store2, min_samples=3, min_delta_ms=5.0)
    m = sen2.model_for("d0", "f0")
    assert m is not None and m.samples == 4  # anomaly re-excluded
    assert m.p50() == pytest.approx(10.0)
    # and the reloaded baseline still detects
    assert sen2.observe(_entry(500.0)) is not None


# ---------------------------------------------------------------------------
# listener plumbing, metrics, process gauges
# ---------------------------------------------------------------------------


def test_ensure_installed_idempotent_and_gated(monkeypatch):
    md = Metadata()
    sentry.ensure_installed(md)
    sentry.ensure_installed(md)
    assert sum(
        isinstance(lst, sentry.SentryListener)
        for lst in md.event_listeners
    ) == 1
    monkeypatch.setenv("TRINO_TPU_SENTRY", "0")
    md2 = Metadata()
    sentry.ensure_installed(md2)
    assert md2.event_listeners == []
    assert not sentry.enabled()


def test_anomaly_metric_counts_by_driver(fresh_sentry):
    _store, sen = fresh_sentry
    before = telemetry.ANOMALIES.value(driver="scan")
    for _ in range(4):
        sen.observe(_entry(100.0, buckets={"scan": 80.0}))
    sen.observe(_entry(900.0, buckets={"scan": 880.0}))
    assert telemetry.ANOMALIES.value(driver="scan") == before + 1


def test_refresh_process_gauges():
    telemetry.refresh_process_gauges(node="unit-test")
    assert telemetry.PROCESS_RSS.value() > 0
    assert telemetry.PROCESS_THREADS.value() >= 1
    assert telemetry.PROCESS_UPTIME.value() > 0
    from trino_tpu import __version__

    assert telemetry.BUILD_INFO.value(
        version=__version__, node="unit-test"
    ) == 1
    text = telemetry.REGISTRY.render()
    for fam in ("trino_process_rss_bytes", "trino_process_open_fds",
                "trino_process_threads", "trino_process_uptime_seconds",
                "trino_build_info"):
        assert fam in text


def test_tracker_journal_gc(tmp_path):
    from trino_tpu import journal as journal_mod
    from trino_tpu.tracker import QueryTracker

    j = journal_mod.QueryJournal(str(tmp_path / "journal"))
    j.begin("q-old", sql="select 1", user="u",
            session_properties={}, retry_policy="NONE")
    j.finish("q-old", state="FINISHED", rows=1, error=None,
             elapsed_ms=1.0)

    class FakeCoord:
        journal = j
        _lock = __import__("threading").Lock()
        _queries = {}

    t = QueryTracker(FakeCoord())
    t.journal_ttl_s = 0.0
    before = telemetry.JOURNAL_GC_REMOVED.value()
    time.sleep(0.01)
    t._maybe_gc_journal(time.time(), force=True)
    assert telemetry.JOURNAL_GC_REMOVED.value() == before + 1
    assert j.scan() == []


# ---------------------------------------------------------------------------
# local end-to-end: injected compile delay on a warmed statement
# ---------------------------------------------------------------------------


@pytest.fixture
def live_sentry():
    """Like fresh_sentry but with real-timing thresholds: a 40ms
    min-delta so scheduler jitter on warmed sub-ms statements can
    never flag, while a 400ms injected delay still lands 10x over."""
    prev_h, prev_s = history.active(), sentry.active()
    store = history.QueryHistory(root=None, max_entries=256)
    sen = sentry.Sentry(min_samples=3, min_delta_ms=40.0)
    history.set_active(store)
    sentry.set_active(sen)
    yield store, sen
    history.set_active(prev_h)
    sentry.set_active(prev_s)


def test_local_injected_compile_delay_detected(live_sentry,
                                               monkeypatch):
    _store, sen = live_sentry
    monkeypatch.setenv("TRINO_TPU_COMPILE_DELAY_S", "0.4")
    runner = QueryRunner.tpch("tiny")
    sql = "select count(*) from region"
    for _ in range(sen.min_samples + 1):
        runner.execute(sql)
    assert sen.anomalies() == []
    inj = fault.FaultInjector(seed=0)
    inj.arm_nth("compile-delay", 1)
    fault.activate(inj)
    try:
        runner.execute(sql)
    finally:
        fault.deactivate()
    verdicts = sen.anomalies()
    assert len(verdicts) == 1
    v = verdicts[0]
    assert v.driver == "xla_compile"
    assert v.ratio >= 1.5
    # the anomalous SUCCESS captured a diagnostics bundle
    bundle = tracker.QUERY_INFO.get_diagnostics(v.query_id)
    assert bundle is not None
    assert bundle["error_class"] == "anomaly"
    assert bundle["anomaly"]["driver"] == "xla_compile"
    assert bundle["state"] == "FINISHED"
    # healthy repeat: no new anomalies
    runner.execute(sql)
    assert len(sen.anomalies()) == 1


def test_injected_delay_flags_only_the_faulted_plan_among_three(
    tmp_path, monkeypatch
):
    """The sentry CI lane's round: three TPC-H plan shapes warm their
    own baselines in one durable history store, a healthy twin pass
    flags nothing, and a seeded compile delay on Q3 alone yields
    exactly one verdict, attributed to xla_compile and naming the
    faulted statement. (Thresholds are wide so that a loaded machine's
    jitter on a warmed statement cannot flag: 400 ms against a 1.2 s
    injected delay.)"""
    from trino_tpu.connectors.tpch.queries import QUERIES

    monkeypatch.setenv("TRINO_TPU_COMPILE_DELAY_S", "1.2")
    prev_h, prev_s = history.active(), sentry.active()
    store = history.QueryHistory(root=str(tmp_path))
    sen = sentry.Sentry(store, min_samples=3, min_delta_ms=400.0)
    history.set_active(store)
    sentry.set_active(sen)
    try:
        runner = QueryRunner.tpch("tiny")
        qids = ("q01", "q03", "q06")
        for _ in range(sen.min_samples + 1):
            for q in qids:
                runner.execute(QUERIES[q])
        assert sen.baseline_count() == len(qids)
        for q in qids:  # the healthy twin: zero false positives
            runner.execute(QUERIES[q])
        assert sen.anomalies() == []
        inj = fault.FaultInjector(seed=0)
        inj.arm_nth("compile-delay", 1)
        fault.activate(inj)
        try:
            runner.execute(QUERIES["q03"])
        finally:
            fault.deactivate()
        verdict, = sen.anomalies()
        assert verdict.driver == "xla_compile", verdict.message
        flagged = store.entries()[-1]
        assert flagged["query_id"] == verdict.query_id
        assert flagged["plan_digest"] == verdict.plan_digest
        # the other two plans' baselines took no sample from it
        assert sen.baseline_count() == len(qids)
        for q in ("q01", "q06"):
            runner.execute(QUERIES[q])
        assert len(sen.anomalies()) == 1
    finally:
        history.set_active(prev_h)
        sentry.set_active(prev_s)


def test_explain_analyze_baseline_footer(live_sentry):
    _store, sen = live_sentry
    runner = QueryRunner.tpch("tiny")
    sql = "explain analyze select count(*) from nation"
    for _ in range(sen.min_samples + 1):
        res = runner.execute(sql)
    text = "\n".join(r[0] for r in res.rows)
    assert "vs baseline:" in text
    assert "p50" in text


# ---------------------------------------------------------------------------
# 2-worker fleet e2e
# ---------------------------------------------------------------------------


def _spawn_worker(port: int) -> subprocess.Popen:
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["TRINO_TPU_COMPILE_DELAY_S"] = "0.6"
    proc = subprocess.Popen(
        [sys.executable, "-m", "trino_tpu.server.worker",
         "--port", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    deadline = time.monotonic() + 120
    while True:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/info", timeout=1
            ) as resp:
                json.loads(resp.read())
                return proc
        except Exception:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"worker died: {proc.stdout.read()[:4000]}"
                )
            if time.monotonic() > deadline:
                proc.kill()
                raise TimeoutError("worker did not come up")
            time.sleep(0.3)


@pytest.fixture(scope="module")
def workers():
    procs = [_spawn_worker(BASE_PORT + i) for i in range(2)]
    yield [f"http://127.0.0.1:{BASE_PORT + i}" for i in range(2)]
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


def test_fleet_injected_regression_end_to_end(workers, tmp_path):
    from trino_tpu.server.fleet import FleetRunner

    prev_h, prev_s = history.active(), sentry.active()
    store = history.QueryHistory(root=str(tmp_path / "hist"))
    sen = sentry.Sentry(store, min_samples=3, min_delta_ms=100.0)
    history.set_active(store)
    sentry.set_active(sen)
    try:
        md = Metadata()
        md.register_catalog("tpch", TpchConnector())
        fleet = FleetRunner(
            workers, md, Session(catalog="tpch", schema="tiny"),
            spool_root=str(tmp_path / "spool"), n_partitions=2,
        )
        # warm the baseline on the fleet path
        for _ in range(sen.min_samples + 1):
            res = fleet.execute(_AGG_SQL)
        healthy_rows = res.rows
        assert sen.anomalies() == []
        anom_before = telemetry.ANOMALIES.value(driver="xla_compile")
        # seeded compile-delay: the spec ships to both workers on the
        # stage-task requests and every task stalls inside a
        # compile-kind span
        inj = fault.FaultInjector(seed=0)
        inj.arm_nth("compile-delay", 1)
        fault.activate(inj)
        try:
            res = fleet.execute(_AGG_SQL)
        finally:
            fault.deactivate()
        assert res.rows == healthy_rows  # delayed, never wrong
        verdicts = sen.anomalies()
        assert len(verdicts) == 1, [v.message for v in verdicts]
        v = verdicts[0]
        assert v.driver == "xla_compile", v.message
        assert telemetry.ANOMALIES.value(
            driver="xla_compile"
        ) == anom_before + 1
        # anomalous SUCCESS bundle, keyed by the PUBLIC query id
        bundle = tracker.QUERY_INFO.get_diagnostics(v.query_id)
        assert bundle is not None
        assert bundle["error_class"] == "anomaly"
        assert bundle["state"] == "FINISHED"
        assert bundle["anomaly"]["ratio"] == v.ratio
        # history recorded the fleet identity fields
        flagged = store.entries()[-1]
        assert flagged["query_id"] == v.query_id
        assert flagged["plan_digest"] == v.plan_digest
        assert flagged["compiles"] >= 1  # the injected compile spans
        # system.runtime.anomalies row (served from the process
        # sentry, same as GET /v1/anomalies)
        from trino_tpu.connectors.system import SystemConnector

        smd = Metadata()
        smd.register_catalog("system", SystemConnector())
        srunner = QueryRunner(
            smd, Session(catalog="system", schema="runtime")
        )
        rows = srunner.execute(
            "select query_id, driver, ratio from anomalies"
        ).rows
        assert (v.query_id, "xla_compile", v.ratio) in rows
        # healthy repeat: zero new anomalies (no false positives)
        fleet.execute(_AGG_SQL)
        assert len(sen.anomalies()) == 1
    finally:
        history.set_active(prev_h)
        sentry.set_active(prev_s)


def test_coordinator_history_and_anomaly_endpoints(fresh_sentry):
    from trino_tpu.server.coordinator import Coordinator

    store, sen = fresh_sentry
    coord = Coordinator(QueryRunner.tpch("tiny")).start()
    try:
        q = coord.submit("select count(*) from nation")
        deadline = time.monotonic() + 60
        while q.state not in ("FINISHED", "FAILED"):
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert q.state == "FINISHED", q.error
        with urllib.request.urlopen(
            f"{coord.uri}/v1/history?limit=5", timeout=10
        ) as r:
            doc = json.loads(r.read())
        assert doc["total"] >= 1
        assert any(
            e["query_id"] == q.query_id for e in doc["entries"]
        )
        with urllib.request.urlopen(
            f"{coord.uri}/v1/anomalies", timeout=10
        ) as r:
            doc = json.loads(r.read())
        assert doc["anomalies"] == []
        assert doc["baselines"] >= 1
        # process-health gauges ride the metrics scrape
        with urllib.request.urlopen(
            f"{coord.uri}/v1/metrics", timeout=10
        ) as r:
            text = r.read().decode()
        assert "trino_process_rss_bytes" in text
        assert 'trino_build_info{' in text
        assert "trino_history_entries" in text
    finally:
        coord.stop()
