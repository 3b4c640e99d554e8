"""Coordinator crash recovery: the durable query journal, journal
replay/resume, the cluster-wide retry budget, the worker orphan
reaper, restart-tolerant clients, and tracker/registry rehydration.

The fast tier exercises every layer in-process (journal unit
semantics, reaper sweeps against a real WorkerServer, fleet resume
against real worker subprocesses with a hand-truncated journal
standing in for the crash). The real kill -9 + restart path — a
coordinator *process* killed mid-FTE-query — lives in
``chaos.run_recovery_chaos`` under the slow tier.

Port discipline: this module owns 19600+ (recovery chaos claims
19520+, cache chaos 19440+).
"""

import json
import os
import threading
import time

import pytest

from trino_tpu import fault, journal as journal_mod, telemetry, tracker
from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.journal import (
    CoordinatorRestartedError,
    QueryJournal,
    RetryBudget,
    RetryBudgetExhaustedError,
)
from trino_tpu.metadata import Metadata, Session
from trino_tpu.server.fleet import FleetRunner
from trino_tpu.testing.chaos import spawn_workers, stop_workers
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)

BASE_PORT = 19600

_JOIN_SQL = (
    "select c_mktsegment, count(*), sum(o_totalprice) "
    "from customer, orders where c_custkey = o_custkey "
    "group by c_mktsegment order by 1"
)


# ---- journal unit semantics -----------------------------------------


def _write_basic(j: QueryJournal, qid: str = "q1") -> None:
    j.begin(qid, sql="select 1", user="u",
            session_properties={"retry_policy": "TASK"},
            retry_policy="TASK")
    j.epoch(qid, "ep1", "digest-a", 4)
    j.stage(qid, "0", {"s0p0": "fp0", "s0p1": "fp1"})
    j.dispatch(qid, "0", "s0p0", 0, "http://w1")
    j.commit(qid, "0", "s0p0", 0)


def test_journal_roundtrip(tmp_path):
    j = QueryJournal(str(tmp_path))
    _write_basic(j)
    e = j.entry("q1")
    assert e is not None
    assert e.sql == "select 1"
    assert e.begin["retry_policy"] == "TASK"
    assert e.epoch["epoch"] == "ep1"
    assert e.epoch["plan_digest"] == "digest-a"
    assert e.stage_fingerprints() == {"s0p0": "fp0", "s0p1": "fp1"}
    assert e.dispatches() == {("s0p0", 0): "http://w1"}
    assert e.commits() == {"s0p0": 0}
    assert e.done is None
    assert e.resumable
    j.finish("q1", state="FINISHED", rows=7, elapsed_ms=12.5)
    e = j.entry("q1")
    assert e.done["state"] == "FINISHED"
    assert e.done["rows"] == 7
    assert not e.resumable


def test_journal_torn_tail_dropped(tmp_path):
    j = QueryJournal(str(tmp_path))
    _write_basic(j)
    with open(j.path("q1"), "a") as f:
        f.write('{"t": "commit", "sid": "0", "tid"')  # crash mid-append
    e = j.entry("q1")
    assert e.commits() == {"s0p0": 0}
    assert len(e.records) == 5


def test_journal_epoch_scoping(tmp_path):
    """A QUERY-tier re-execution journals a fresh epoch; only the last
    epoch's stage/dispatch/commit records are trusted on resume."""
    j = QueryJournal(str(tmp_path))
    _write_basic(j)
    j.epoch("q1", "ep2", "digest-a", 4)
    j.stage("q1", "0", {"x0": "fpx"})
    j.dispatch("q1", "0", "x0", 1, "http://w2")
    e = j.entry("q1")
    assert e.epoch["epoch"] == "ep2"
    assert e.stage_fingerprints() == {"x0": "fpx"}
    assert e.dispatches() == {("x0", 1): "http://w2"}
    assert e.commits() == {}  # ep1's commit is out of scope


def test_journal_resumable_requires_fte(tmp_path):
    j = QueryJournal(str(tmp_path))
    j.begin("q2", sql="select 1", user="u", session_properties={},
            retry_policy="NONE")
    j.epoch("q2", "ep", "d", 4)
    assert not j.entry("q2").resumable
    # an epoch-less journal (crash during planning) is not resumable
    j.begin("q3", sql="select 1", user="u", session_properties={},
            retry_policy="TASK")
    assert not j.entry("q3").resumable


def test_journal_scan_and_gc(tmp_path):
    j = QueryJournal(str(tmp_path))
    _write_basic(j, "qa")
    _write_basic(j, "qb")
    j.finish("qa", state="FINISHED")
    ids = [e.query_id for e in j.scan()]
    assert set(ids) == {"qa", "qb"}
    assert j.gc(max_age_s=0.0) == 1  # terminal qa dropped, live qb kept
    assert [e.query_id for e in j.scan()] == ["qb"]


def test_spec_fingerprint_tracks_work_not_id():
    class Spec:
        def __init__(self, plan_json, partition, salt=None):
            self.plan_json = plan_json
            self.partition = partition
            self.salt = salt

    a = journal_mod.spec_fingerprint(Spec({"op": "scan"}, 0))
    b = journal_mod.spec_fingerprint(Spec({"op": "scan"}, 0))
    c = journal_mod.spec_fingerprint(Spec({"op": "scan"}, 1))
    d = journal_mod.spec_fingerprint(Spec({"op": "scan"}, 0, salt=3))
    assert a == b
    assert len({a, c, d}) == 3


def test_journal_fault_sites_registered_and_fire(tmp_path):
    assert "journal-write" in fault.SITES
    assert "journal-read" in fault.SITES
    inj = fault.FaultInjector(seed=0)
    inj.arm("journal-write", times=1)
    fault.activate(inj)
    try:
        j = QueryJournal(str(tmp_path))
        with pytest.raises(fault.InjectedFault):
            j.begin("q1", sql="s", user="u", session_properties={},
                    retry_policy="TASK")
    finally:
        fault.activate(None)


# ---- retry budget ----------------------------------------------------


def test_retry_budget_sliding_window():
    b = RetryBudget(2, window_s=60.0)
    b.spend(now=100.0)
    b.spend(now=101.0)
    with pytest.raises(RetryBudgetExhaustedError) as ei:
        b.spend(now=102.0)
    assert "non-retryable" in str(ei.value)
    # outside the window the old spends roll off
    b2 = RetryBudget(2, window_s=10.0)
    b2.spend(now=100.0)
    b2.spend(now=101.0)
    b2.spend(now=120.0)  # 100/101 expired — no raise


def test_retry_budget_disabled_by_default():
    b = RetryBudget(0)
    for _ in range(100):
        b.spend()


def test_retry_budget_error_codes_registered():
    from trino_tpu.server import coordinator as coord_mod

    assert coord_mod.ERROR_CODES["CoordinatorRestartedError"] == (
        135, "COORDINATOR_RESTARTED"
    )
    assert coord_mod.ERROR_CODES["RetryBudgetExhaustedError"] == (
        136, "RETRY_BUDGET_EXHAUSTED"
    )
    payload = coord_mod.error_payload(
        "RetryBudgetExhaustedError: retry budget exhausted"
    )
    assert payload["errorName"] == "RETRY_BUDGET_EXHAUSTED"


def test_retry_budget_session_property():
    from trino_tpu import session_properties as sp

    s = Session(catalog="tpch", schema="tiny")
    assert sp.get(s, "retry_budget") == 0
    sp.set_property(s, "retry_budget", "5")
    assert sp.get(s, "retry_budget") == 5
    with pytest.raises(Exception):
        sp.set_property(s, "retry_budget", "-1")


# ---- worker orphan reaper -------------------------------------------


@pytest.fixture(scope="module")
def local_runner():
    return QueryRunner.tpch("tiny")


def test_orphan_reaper_quarantine_then_cancel(local_runner, tmp_path):
    from trino_tpu.server.worker import WorkerServer, _Task

    server = WorkerServer(local_runner, port=0).start()
    try:
        reaped_before = telemetry.ORPHAN_TASKS_REAPED.value()
        evicted_before = (
            telemetry.EXCHANGE_BUFFER_ORPHAN_EVICTIONS.value()
        )

        class Ctx:
            def try_reserve(self, n):
                return True

            def free(self, n):
                pass

        qroot = tmp_path / "spool" / "epoch1"
        qroot.mkdir(parents=True)
        (qroot / "part0.bin.tmp").write_bytes(b"torn write")
        (qroot / "part0.bin").write_bytes(b"committed")

        running = _Task("t1.0")
        running.query_id = "orphanq"
        running.state = "RUNNING"
        finished = _Task("t2.0")
        finished.query_id = "orphanq"
        finished.state = "FINISHED"
        server._tasks["t1.0"] = running
        server._tasks["t2.0"] = finished
        server.exchange_buffer.put(
            ("orphanq", "t2", 0, 0), b"payload", 1, Ctx()
        )
        server._coord_seen["orphanq"] = time.monotonic() - 100.0
        server._query_spools["orphanq"] = str(qroot)
        # a second query whose coordinator is still polling: untouched
        live = _Task("t3.0")
        live.query_id = "liveq"
        live.state = "RUNNING"
        server._tasks["t3.0"] = live
        server._coord_seen["liveq"] = time.monotonic()

        first = server.reap_orphans_once(ttl_s=1.0, grace_s=30.0)
        assert first == {"quarantined": 1, "reaped": 0, "buffers": 0,
                         "scratch": 0}
        assert running.state == "RUNNING"  # grace period: no kill yet
        # collapse the grace period and sweep again
        server._quarantined["orphanq"] -= 60.0
        second = server.reap_orphans_once(ttl_s=1.0, grace_s=30.0)
        assert second["reaped"] == 1  # the RUNNING task, not FINISHED
        assert second["buffers"] == 1
        assert second["scratch"] == 1
        assert running.state == "CANCELED"
        assert live.state == "RUNNING"
        assert server.exchange_buffer.get(("orphanq", "t2", 0, 0)) is None
        assert not (qroot / "part0.bin.tmp").exists()
        assert (qroot / "part0.bin").exists()  # durable data survives
        assert "orphanq" not in server._coord_seen
        assert telemetry.ORPHAN_TASKS_REAPED.value() == reaped_before + 1
        assert (
            telemetry.EXCHANGE_BUFFER_ORPHAN_EVICTIONS.value()
            == evicted_before + 1
        )
    finally:
        server.stop()


# ---- restart-tolerant client ----------------------------------------


def test_client_restart_wait_rides_through_outage(monkeypatch):
    from trino_tpu.server.client import QueryError, StatementClient

    c = StatementClient("http://127.0.0.1:1", restart_wait_s=30.0)
    c.retry_backoff_s = 0.001
    calls = {"n": 0}

    def flaky(method, url, body=None):
        calls["n"] += 1
        if calls["n"] < 4:
            err = QueryError("coordinator is down")
            err.retryable = True
            raise err
        return {"ok": True}

    monkeypatch.setattr(c, "_request_once", flaky)
    assert c._request("GET", "http://x/page") == {"ok": True}
    assert calls["n"] == 4


def test_client_restart_wait_retries_404(monkeypatch):
    from trino_tpu.server.client import QueryError, StatementClient

    c = StatementClient("http://127.0.0.1:1", restart_wait_s=30.0)
    c.retry_backoff_s = 0.001
    calls = {"n": 0}

    def replaying(method, url, body=None):
        calls["n"] += 1
        if calls["n"] == 1:
            err = QueryError("HTTP 404")
            err.http_status = 404
            err.retryable = False
            raise err
        return {"ok": True}

    monkeypatch.setattr(c, "_request_once", replaying)
    assert c._request("GET", "http://x/page") == {"ok": True}


def test_client_without_restart_wait_fails_fast(monkeypatch):
    from trino_tpu.server.client import QueryError, StatementClient

    c = StatementClient("http://127.0.0.1:1")
    c.retry_backoff_s = 0.001
    calls = {"n": 0}

    def always_down(method, url, body=None):
        calls["n"] += 1
        err = QueryError("down")
        err.retryable = True
        raise err

    monkeypatch.setattr(c, "_request_once", always_down)
    with pytest.raises(QueryError):
        c._request("GET", "http://x/page")
    assert calls["n"] == c.get_retries + 1
    # POSTs are never retried, restart-wait or not
    c2 = StatementClient("http://127.0.0.1:1", restart_wait_s=30.0)
    calls["n"] = 0
    monkeypatch.setattr(c2, "_request_once", always_down)
    with pytest.raises(QueryError):
        c2._request("POST", "http://x/statement", b"sql")
    assert calls["n"] == 1


# ---- tracker / registry rehydration ----------------------------------


def test_tracker_rehydrate_and_recovered_flag():
    qid = "rehydrated-q-1"
    tracker.QUERY_INFO.rehydrate(
        qid, state="FINISHED", sql="select 42", user="alice",
        rows=1, elapsed_ms=250.0,
    )
    row = next(
        r for r in tracker.QUERY_INFO.list() if r["query_id"] == qid
    )
    assert row["recovered"] is True
    assert row["state"] == "FINISHED"
    assert row["rows"] == 1
    got = tracker.QUERY_INFO.get(qid)
    assert got["recovered"] is True
    assert got["sql"] == "select 42"
    # mark_recovered flags a live (begin'd) query too
    qid2 = "rehydrated-q-2"
    tracker.QUERY_INFO.begin(qid2, sql="select 1", user="bob")
    tracker.QUERY_INFO.mark_recovered(qid2)
    assert tracker.QUERY_INFO.get(qid2)["recovered"] is True
    # queries that never crossed a restart stay unflagged
    qid3 = "plain-q-3"
    tracker.QUERY_INFO.begin(qid3, sql="select 2", user="bob")
    assert tracker.QUERY_INFO.get(qid3)["recovered"] is False


def test_system_queries_recovered_column():
    from trino_tpu.connectors.system import (
        SystemConnector, _QUERIES_SCHEMA,
    )

    names = [c[0] for c in _QUERIES_SCHEMA.columns]
    assert names[-1] == "recovered"
    qid = "rehydrated-sys-q"
    tracker.QUERY_INFO.rehydrate(
        qid, state="FAILED", sql="select 9", user="u",
        error="CoordinatorRestartedError: restarted",
    )
    rows = SystemConnector()._rows("queries")
    row = next(r for r in rows if r[0] == qid)
    assert len(row) == len(names)
    assert row[-1] is True


def test_coordinator_recover_rehydrates_and_fails_typed(tmp_path):
    """Journal replay without a resumable runner: terminal queries
    rehydrate the registry; non-FTE in-flight queries fail typed
    COORDINATOR_RESTARTED at their old protocol ids."""
    from trino_tpu.server import coordinator as coord_mod

    j = QueryJournal(str(tmp_path))
    j.note_client("doneq", slug="s1", user="u", sql="select 1")
    j.begin("doneq", sql="select 1", user="u", session_properties={},
            retry_policy="NONE")
    j.finish("doneq", state="FINISHED", rows=3, elapsed_ms=10.0)
    j.note_client("lostq", slug="s2", user="u", sql="select 2")
    j.begin("lostq", sql="select 2", user="u", session_properties={},
            retry_policy="NONE")
    coord = coord_mod.Coordinator(
        QueryRunner.tpch("tiny"), port=0, journal=j
    )
    coord.start()
    try:
        counts = coord.recover()
        assert counts["rehydrated"] == 1
        assert counts["unresumable"] == 1
        assert counts["resumed"] == 0
        assert tracker.QUERY_INFO.get("doneq")["recovered"] is True
        q = coord._queries["lostq"]
        assert q.state == "FAILED"
        payload = coord_mod.error_payload(q.error)
        assert payload["errorName"] == "COORDINATOR_RESTARTED"
        assert payload["errorCode"] == 135
        # the journal got a terminal record: a second restart will
        # rehydrate, not re-fail
        assert j.entry("lostq").done is not None
    finally:
        coord.stop()


# ---- fleet resume (in-process crash stand-in) ------------------------


@pytest.fixture(scope="module")
def workers():
    procs, uris = spawn_workers(2, base_port=BASE_PORT, platform="cpu")
    yield uris
    stop_workers(procs)


@pytest.fixture(scope="module")
def oracle():
    data = (
        QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    )
    return load_tpch_sqlite(data)


def _make_fleet(uris, spool_root, journal):
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    fleet = FleetRunner(
        list(uris), md, Session(catalog="tpch", schema="tiny"),
        spool_root=spool_root, n_partitions=4, keep_spool=True,
        journal=journal,
    )
    fleet.session.properties["retry_policy"] = "TASK"
    fleet.session.properties["speculation_enabled"] = False
    return fleet


def _strip_done(j: QueryJournal, qid: str) -> None:
    """Rewrite the journal as a crash would have left it: everything
    up to (not including) the terminal record."""
    records = [r for r in j.load(qid) if r.get("t") != "done"]
    with open(j.path(qid), "w") as f:
        for r in records:
            f.write(json.dumps(r, sort_keys=True, default=str) + "\n")


def test_fleet_resume_inherits_all_committed_work(
    workers, oracle, tmp_path
):
    """Crash after every task committed: resume must inherit the whole
    DAG from the spool and re-execute nothing."""
    root = str(tmp_path)
    j = QueryJournal(root)
    fleet = _make_fleet(workers, root, j)
    res = fleet.execute(_JOIN_SQL, query_id="resumeq1")
    expected = oracle.execute(to_sqlite(_JOIN_SQL)).fetchall()
    assert_rows_match(res.rows, expected, ordered=res.ordered,
                      abs_tol=1e-6)
    _strip_done(j, "resumeq1")
    assert j.entry("resumeq1").resumable

    fleet2 = _make_fleet(workers, root, j)
    res2 = fleet2.resume(j.entry("resumeq1"))
    assert res2.rows == res.rows
    assert fleet2.resume_stats["tasks_recovered_committed"] >= 1
    assert fleet2.resume_stats["tasks_redispatched"] == 0, (
        "resume re-dispatched spool-committed work"
    )
    post = j.entry("resumeq1")
    assert post.done["state"] == "FINISHED"
    resumed = [r for r in post.records if r.get("t") == "resumed"]
    assert resumed and resumed[-1]["tasks_redispatched"] == 0


def test_fleet_resume_redispatches_missing_attempts(
    workers, oracle, tmp_path
):
    """Crash with one task's commit quarantined (as a corrupt/partial
    attempt would be): resume inherits the rest and re-runs only the
    hole — oracle-exact either way."""
    from trino_tpu.exec import spool

    root = str(tmp_path)
    j = QueryJournal(root)
    fleet = _make_fleet(workers, root, j)
    res = fleet.execute(_JOIN_SQL, query_id="resumeq2")
    _strip_done(j, "resumeq2")
    e = j.entry("resumeq2")
    qroot = os.path.join(root, e.epoch["epoch"])
    # knock out one committed attempt: quarantine its spool markers
    # (as corruption detection would) AND cancel the worker-side task
    # so the adoption pre-probe cannot inherit it either
    victim = next(
        r for r in e.records if r.get("t") == "commit"
    )
    assert spool.quarantine_attempt(
        qroot, victim["sid"], victim["tid"], int(victim["a"])
    )
    import urllib.request

    wuri = e.dispatches()[(victim["tid"], int(victim["a"]))]
    req = urllib.request.Request(
        f"{wuri}/v1/stagetask/{victim['tid']}.{victim['a']}",
        method="DELETE",
    )
    with urllib.request.urlopen(req, timeout=5):
        pass

    fleet2 = _make_fleet(workers, root, j)
    res2 = fleet2.resume(j.entry("resumeq2"))
    expected = oracle.execute(to_sqlite(_JOIN_SQL)).fetchall()
    assert_rows_match(res2.rows, expected, ordered=res2.ordered,
                      abs_tol=1e-6)
    assert res2.rows == res.rows
    assert fleet2.resume_stats["tasks_redispatched"] >= 1
    assert fleet2.resume_stats["tasks_recovered_committed"] >= 1


def test_fleet_resume_refuses_terminal_journal(workers, tmp_path):
    root = str(tmp_path)
    j = QueryJournal(root)
    fleet = _make_fleet(workers, root, j)
    fleet.execute("select count(*) from orders", query_id="doneq9")
    with pytest.raises(CoordinatorRestartedError):
        fleet.resume(j.entry("doneq9"))


def test_fleet_retry_budget_exhaustion_is_terminal(workers, tmp_path):
    """With a 1-retry budget and two first-attempt failures, the query
    dies typed RETRY_BUDGET_EXHAUSTED — and does NOT escalate to a
    QUERY-tier re-execution (query_retries stays 0)."""
    root = str(tmp_path)
    fleet = _make_fleet(workers, root, None)
    fleet.session.properties["retry_budget"] = 1
    # fail every task's first attempt across the whole DAG — far more
    # than one retry, so the second spend() must trip the budget
    fleet.inject_failures = {
        f"{s}:{t}" for s in range(8) for t in range(4)
    }
    with pytest.raises(RetryBudgetExhaustedError):
        fleet.execute(_JOIN_SQL)
    assert fleet.stats.get("query_retries", 0) == 0


# ---- full kill -9 chaos (slow tier) ----------------------------------


@pytest.mark.slow
def test_recovery_chaos_kill9_and_orphan_reap(tmp_path):
    """Real coordinator process SIGKILL'd mid-query + restarted; same
    client rides through (asserts live inside run_recovery_chaos)."""
    from trino_tpu.testing import chaos

    record = chaos.run_recovery_chaos(seed=0, spool_root=str(tmp_path), platform="cpu")
    scenarios = {r["scenario"] for r in record["runs"]}
    assert scenarios == {"kill-mid-query", "orphan-reap"}
    kill = next(
        r for r in record["runs"] if r["scenario"] == "kill-mid-query"
    )
    assert kill["recomputed_committed"] == 0
    assert kill["tasks_recovered_committed"] >= 1
