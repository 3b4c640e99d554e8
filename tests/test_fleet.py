"""Multi-worker fleet execution: N separate worker processes, stage
waves, durable spooled exchange, task retry, worker-crash recovery.

The analog of the reference's fault-tolerant-execution test tier
(TESTING/BaseFailureRecoveryTest.java:75 + the FTE runners wiring
trino-exchange-filesystem with local spooling): queries run against
REAL separate worker processes; inter-stage data crosses through
committed spool files (exec.spool); injected task failures and a
kill -9'd worker mid-query must both retry from spool and still
return oracle-exact results.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.metadata import Metadata, Session
from trino_tpu.server.fleet import FleetRunner
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)

BASE_PORT = 18940


def _spawn_worker(port: int) -> subprocess.Popen:
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "trino_tpu.server.worker",
            "--port", str(port),
        ],
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


@pytest.fixture(scope="module")
def spool_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("spool"))


@pytest.fixture()
def fleet(workers, spool_root):
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    return FleetRunner(
        workers, md, Session(catalog="tpch", schema="tiny"),
        spool_root=spool_root, n_partitions=4,
    )


@pytest.fixture(scope="module")
def oracle():
    data = QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    return load_tpch_sqlite(data)


def check(fleet, oracle, sql, abs_tol=1e-9):
    result = fleet.execute(sql)
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(
        result.rows, expected, ordered=result.ordered, abs_tol=abs_tol
    )
    return result


def test_fleet_aggregation(fleet, oracle):
    check(
        fleet, oracle,
        "select l_returnflag, l_linestatus, sum(l_quantity), count(*) "
        "from lineitem group by l_returnflag, l_linestatus order by 1, 2",
    )


def test_fleet_partitioned_join(fleet, oracle):
    # force a hash-partitioned join (both sides exchanged on keys)
    fleet.session.properties["join_distribution_type"] = "PARTITIONED"
    check(
        fleet, oracle,
        "select c_name, sum(o_totalprice) t from customer, orders "
        "where c_custkey = o_custkey group by c_name "
        "order by t desc limit 10",
        abs_tol=1e-6,
    )


def test_fleet_tpch_q3(fleet, oracle):
    from trino_tpu.connectors.tpch.queries import QUERIES

    check(fleet, oracle, QUERIES["q03"], abs_tol=0.006)


def test_fleet_tpch_q18(fleet, oracle):
    from trino_tpu.connectors.tpch.queries import QUERIES

    check(fleet, oracle, QUERIES["q18"], abs_tol=0.006)


def test_fleet_array_column_crosses_exchange(workers, spool_root):
    """ARRAY columns round-trip through both exchange paths.

    ``array_agg`` has no partial form, so the distributed plan routes
    raw rows by group-key hash and aggregates in one step — the
    resulting list column (offsets + flat values in the spool serde)
    then crosses the agg->sort exchange.  Element order within each
    array depends on row routing, so arrays compare as sorted
    multisets per key against the single-runner result — proving
    every element survived the exchange byte-exact, in both DIRECT
    and SPOOL modes.
    """
    local = QueryRunner.tpch("tiny")
    queries = [
        # bigint elements
        "select o_orderpriority, array_agg(o_orderkey) from orders "
        "group by o_orderpriority order by 1",
        # varchar elements
        "select c_mktsegment, array_agg(c_name) from customer "
        "group by c_mktsegment order by 1",
    ]

    def merged(rows):
        out = {}
        for key, arr in rows:
            out.setdefault(key, []).extend(arr)
        return {k: sorted(v) for k, v in out.items()}

    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    for sql in queries:
        expected = merged(local.execute(sql).rows)
        for mode in ("SPOOL", "DIRECT"):
            fl = FleetRunner(
                workers, md, Session(catalog="tpch", schema="tiny"),
                spool_root=spool_root, n_partitions=4,
            )
            fl.session.properties["exchange_mode"] = mode
            res = fl.execute(sql)
            assert len(res.rows) == len(expected), (mode, sql)
            assert merged(res.rows) == expected, (mode, sql)
            direct = sum(
                st.get("direct_bytes", 0) for st in res.stage_stats
            )
            if mode == "DIRECT":
                assert direct > 0, "DIRECT run served no direct bytes"
            else:
                assert direct == 0, "SPOOL run must not fetch direct"


def test_fleet_direct_exchange_carries_the_bytes_and_matches_spool(
    workers, spool_root
):
    """The exchange CI lane's round on a healthy fleet: partitioned
    join statements return the same rows under ``exchange_mode=SPOOL``
    (the reference side) and ``DIRECT``, and under DIRECT at least nine
    tenths of the exchanged bytes are served from producer memory, the
    spool copy being the fallback only."""
    from trino_tpu.connectors.tpch.queries import QUERIES

    statements = {
        "join": "select c_name, sum(o_totalprice) t from customer, orders "
                "where c_custkey = o_custkey group by c_name "
                "order by t desc limit 10",
        "q03": QUERIES["q03"],
    }
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    rows: dict = {}
    direct = spooled = 0
    for mode in ("SPOOL", "DIRECT"):
        fl = FleetRunner(
            workers, md, Session(catalog="tpch", schema="tiny"),
            spool_root=spool_root, n_partitions=4,
        )
        fl.session.properties["exchange_mode"] = mode
        fl.session.properties["join_distribution_type"] = "PARTITIONED"
        for name, sql in statements.items():
            res = fl.execute(sql)
            rows[mode, name] = res.rows
            if mode == "DIRECT":
                direct += sum(
                    st.get("direct_bytes", 0) for st in res.stage_stats
                )
                spooled += sum(
                    st.get("spooled_bytes", 0) for st in res.stage_stats
                )
    for name in statements:
        assert rows["DIRECT", name] == rows["SPOOL", name], name
    assert direct > 0
    assert direct >= 9 * spooled, (direct, spooled)


def test_fleet_task_retry_after_injected_failure(fleet, oracle):
    """First attempt of a scan task fails (FailureInjector analog);
    the retry on another worker must make the query succeed."""
    fleet.inject_failures = {"0:0", "1:1"}
    check(
        fleet, oracle,
        "select o_orderpriority, count(*) from orders "
        "group by o_orderpriority order by 1",
    )


def test_fleet_overlapping_stage_dag(fleet, oracle):
    """Independent stages interleave across the pool: with a
    partitioned join, BOTH child scan stages must have tasks posted
    before EITHER completes (no strict wave barrier between
    independent subtrees — the PipelinedQueryScheduler direction)."""
    fleet.session.properties["join_distribution_type"] = "PARTITIONED"
    fleet.session.properties["fleet_task_delay_ms"] = 150
    log: list[tuple[str, str]] = []  # ("post"|"done", stage_id)
    fleet.post_hook = lambda sid, tid, w: log.append(("post", sid))
    fleet.stage_hook = lambda sid: log.append(("done", sid))
    check(
        fleet, oracle,
        "select c_mktsegment, count(*) from customer, orders "
        "where c_custkey = o_custkey group by c_mktsegment order by 1",
    )
    # tasks from >= 2 distinct stages must be posted BEFORE any stage
    # completes (the old wave barrier would finish stage A entirely
    # before posting anything of stage B)
    stages_posted_before_first_done = set()
    for ev, sid in log:
        if ev == "done":
            break
        stages_posted_before_first_done.add(sid)
    assert len(stages_posted_before_first_done) >= 2, (
        f"no overlap: {log}"
    )


def test_fleet_worker_graceful_drain(workers, spool_root, oracle):
    """POST /v1/drain mid-query: the drained worker finishes its
    in-flight task (its output counts), receives nothing new, and the
    query completes on the survivors
    (GracefulShutdownHandler analog)."""
    victim_port = BASE_PORT + 8
    victim = _spawn_worker(victim_port)
    victim_uri = f"http://127.0.0.1:{victim_port}"
    try:
        md = Metadata()
        md.register_catalog("tpch", TpchConnector())
        fleet = FleetRunner(
            [victim_uri] + list(workers),
            md, Session(catalog="tpch", schema="tiny"),
            spool_root=spool_root, n_partitions=4,
        )
        fleet.session.properties["fleet_task_delay_ms"] = 200
        state = {"drained": False, "posts_after_drain": 0}

        def post_hook(stage_id, task_id, w):
            if state["drained"] and victim_uri in w.uri:
                state["posts_after_drain"] += 1
            if not state["drained"] and victim_uri in w.uri:
                # drain while its first task is still in flight
                urllib.request.urlopen(
                    urllib.request.Request(
                        f"{victim_uri}/v1/drain", data=b"", method="POST"
                    ),
                    timeout=5,
                ).read()
                state["drained"] = True

        fleet.post_hook = post_hook
        sql = (
            "select o_orderpriority, count(*) from orders "
            "group by o_orderpriority order by 1"
        )
        result = fleet.execute(sql)
        assert state["drained"], "victim never received a task"
        assert state["posts_after_drain"] == 0, (
            "a drained worker must not receive new tasks"
        )
        expected = oracle.execute(to_sqlite(sql)).fetchall()
        assert_rows_match(
            result.rows, expected, ordered=result.ordered, abs_tol=1e-9
        )
        # its in-flight work done, the worker reports DRAINED
        with urllib.request.urlopen(
            f"{victim_uri}/v1/info", timeout=5
        ) as r:
            info = json.loads(r.read())
        assert info["state"] in ("DRAINING", "DRAINED")
        mark = [w for w in fleet.workers if victim_uri in w.uri][0]
        assert mark.alive and mark.draining
    finally:
        victim.kill()


def test_fleet_recovers_from_hung_worker_sigstop(workers, spool_root, oracle):
    """SIGSTOP a worker holding an in-flight task: it keeps its
    sockets open but answers nothing — ``max_poll_fails`` consecutive
    short poll timeouts must declare it dead and reschedule, not one
    long RPC timeout (HeartbeatFailureDetector analog). Judged on what
    happened (the eviction, and how many timed-out polls it took), not
    on the statement's wall clock, which follows the machine's load."""
    victim_port = BASE_PORT + 9
    victim = _spawn_worker(victim_port)
    victim_uri = f"http://127.0.0.1:{victim_port}"
    try:
        md = Metadata()
        md.register_catalog("tpch", TpchConnector())
        fleet = FleetRunner(
            [victim_uri] + list(workers),
            md, Session(catalog="tpch", schema="tiny"),
            spool_root=spool_root, n_partitions=4,
            rpc_timeout_s=2.0, max_poll_fails=3,
        )
        # this test exercises the DEATH-DETECTION path specifically:
        # with speculation on, a backup attempt would win first and
        # the hung worker would never accumulate poll failures
        fleet.session.properties["speculation_enabled"] = False
        fleet.session.properties["fleet_task_delay_ms"] = 200
        hung = [w for w in fleet.workers if victim_uri in w.uri][0]
        state = {"stopped": False}
        #: how every poll of the victim ended, while it was still alive
        victim_polls: list[str] = []

        def post_hook(stage_id, task_id, w):
            if not state["stopped"] and w is hung:
                os.kill(victim.pid, signal.SIGSTOP)
                state["stopped"] = True

        poll_task = fleet._poll_task

        def counted_poll(w, task_id, attempt):
            try:
                status = poll_task(w, task_id, attempt)
            except Exception as e:
                if w is hung:
                    victim_polls.append(type(e).__name__)
                raise
            if w is hung:
                victim_polls.append("answered")
            return status

        fleet.post_hook = post_hook
        fleet._poll_task = counted_poll
        sql = (
            "select o_orderpriority, count(*) from orders "
            "group by o_orderpriority order by 1"
        )
        outcome: dict = {}

        def run():
            try:
                outcome["result"] = fleet.execute(sql)
            except Exception as e:  # re-raised on the test's thread
                outcome["error"] = e

        query = threading.Thread(target=run, daemon=True)
        query.start()
        # wait for the eviction itself; the deadline only bounds a hang
        deadline = time.monotonic() + 120
        while hung.alive and query.is_alive() and (
            time.monotonic() < deadline
        ):
            time.sleep(0.05)
        assert state["stopped"], "victim never received a task"
        assert not hung.alive, (
            f"hung worker not evicted; its polls: {victim_polls}"
        )
        # the stopped process never answered, and it took exactly
        # max_poll_fails short timeouts to declare it dead
        assert len(victim_polls) == fleet.max_poll_fails, victim_polls
        assert "answered" not in victim_polls, victim_polls
        assert "ConnectionRefusedError" not in victim_polls, victim_polls
        query.join(timeout=240)
        assert not query.is_alive(), "query did not finish after eviction"
        if "error" in outcome:
            raise outcome["error"]
        result = outcome["result"]
        assert result.tasks_retried >= 1  # the stranded task re-ran
        expected = oracle.execute(to_sqlite(sql)).fetchall()
        assert_rows_match(
            result.rows, expected, ordered=result.ordered, abs_tol=1e-9
        )
        assert not hung.alive  # still stopped: no probe re-admitted it
    finally:
        try:
            os.kill(victim.pid, signal.SIGCONT)
        except OSError:
            pass
        victim.kill()


def test_fleet_survives_worker_kill9(workers, spool_root, oracle):
    """kill -9 a worker while it owns an in-flight task: the
    coordinator must detect the death, exclude the worker, re-run the
    task from its spooled inputs on a survivor, and the query must
    return oracle-exact results (TASK retry policy over durable
    spooled stage outputs)."""
    victim_port = BASE_PORT + 7
    victim = _spawn_worker(victim_port)
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    fleet = FleetRunner(
        [f"http://127.0.0.1:{victim_port}"] + list(workers),
        md, Session(catalog="tpch", schema="tiny"),
        spool_root=spool_root, n_partitions=4,
    )
    # slow tasks widen the in-flight window; kill the victim as soon
    # as a SECOND-wave task lands on it (the first wave's output is
    # already committed to the spool — the retry must read it back).
    # Stage ids are parent-first, so wave order is tracked via
    # stage_hook, not id comparison.
    fleet.session.properties["fleet_task_delay_ms"] = 300
    state = {"killed": False, "waves_done": 0}

    def stage_hook(stage_id):
        state["waves_done"] += 1

    def post_hook(stage_id, task_id, w):
        if (
            state["waves_done"] > 0
            and not state["killed"]
            and str(victim_port) in w.uri
        ):
            os.kill(victim.pid, signal.SIGKILL)
            state["killed"] = True

    fleet.stage_hook = stage_hook
    fleet.post_hook = post_hook
    sql = (
        "select l_returnflag, l_linestatus, sum(l_quantity), "
        "avg(l_extendedprice), count(*) from lineitem "
        "group by l_returnflag, l_linestatus order by 1, 2"
    )
    result = fleet.execute(sql)
    assert state["killed"], "victim worker was never scheduled past wave 1"
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(
        result.rows, expected, ordered=result.ordered, abs_tol=0.006
    )
    assert not fleet.workers[0].alive  # victim excluded
    # the orphaned task went back through the retry path, and the
    # QueryResult reports it
    assert result.tasks_retried >= 1
    victim.wait(timeout=10)


def test_fleet_spool_survives_producer_death(workers, spool_root, oracle):
    """The defining FTE property: a stage's committed output outlives
    the worker that produced it. Run stage 0 partly on a victim, kill
    the victim BEFORE downstream stages consume its output, and the
    consumers must read it from the spool."""
    victim_port = BASE_PORT + 8
    victim = _spawn_worker(victim_port)
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    fleet = FleetRunner(
        [f"http://127.0.0.1:{victim_port}"] + list(workers),
        md, Session(catalog="tpch", schema="tiny"),
        spool_root=spool_root, n_partitions=4,
    )
    state = {"used": False, "killed": False, "first_wave": True}

    def post_hook(stage_id, task_id, w):
        # victim produced part of the FIRST wave's output
        if state["first_wave"] and str(victim_port) in w.uri:
            state["used"] = True

    def stage_hook(stage_id):
        # first wave committed; the victim's output now lives only in
        # the spool — kill it before any consumer stage runs
        if state["first_wave"]:
            state["first_wave"] = False
            if state["used"] and not state["killed"]:
                os.kill(victim.pid, signal.SIGKILL)
                state["killed"] = True

    fleet.post_hook = post_hook
    fleet.stage_hook = stage_hook
    sql = (
        "select o_orderdate, count(*) c from orders "
        "where o_orderkey in (select l_orderkey from lineitem "
        "where l_quantity > 48) group by o_orderdate order by 1 limit 5"
    )
    result = fleet.execute(sql)
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(
        result.rows, expected, ordered=result.ordered, abs_tol=1e-9
    )
    if state["killed"]:
        victim.wait(timeout=10)
    else:
        victim.kill()


def test_fleet_speculative_execution_beats_straggler(
    workers, spool_root, oracle
):
    """SIGSTOP a worker holding a task while death detection is tuned
    SLOW (15 polls x 2 s): the tail-latency hedge must kick in first —
    a backup attempt launched on an idle worker once the task's age
    exceeds speculation_multiplier x the stage's median runtime — and
    the backup's commit must win the query well before the hung worker
    would be declared dead."""
    victim_port = BASE_PORT + 6
    victim = _spawn_worker(victim_port)
    victim_uri = f"http://127.0.0.1:{victim_port}"
    try:
        md = Metadata()
        md.register_catalog("tpch", TpchConnector())
        fleet = FleetRunner(
            [victim_uri] + list(workers),
            md, Session(catalog="tpch", schema="tiny"),
            spool_root=spool_root, n_partitions=4,
            rpc_timeout_s=2.0, max_poll_fails=15,
        )
        fleet.session.properties["fleet_task_delay_ms"] = 200
        fleet.session.properties["speculation_multiplier"] = 1.5
        state = {"stopped": False}

        def post_hook(stage_id, task_id, w):
            if not state["stopped"] and victim_uri in w.uri:
                os.kill(victim.pid, signal.SIGSTOP)
                state["stopped"] = True

        fleet.post_hook = post_hook
        sql = (
            "select o_orderpriority, count(*) from orders "
            "group by o_orderpriority order by 1"
        )
        t0 = time.monotonic()
        result = fleet.execute(sql)
        elapsed = time.monotonic() - t0
        assert state["stopped"], "victim never received a task"
        assert result.tasks_speculated >= 1
        assert result.speculation_wins >= 1
        # far inside the 15 * 2 s death-detection budget: the hedge,
        # not failure detection, is what unblocked the query
        assert elapsed < 25, f"speculation took {elapsed:.1f}s"
        expected = oracle.execute(to_sqlite(sql)).fetchall()
        assert_rows_match(
            result.rows, expected, ordered=result.ordered, abs_tol=1e-9
        )
    finally:
        try:
            os.kill(victim.pid, signal.SIGCONT)
        except OSError:
            pass
        victim.kill()


def test_fleet_retry_backoff_is_jittered_and_seeded(fleet, oracle):
    """Failed-task retries wait an exponential-backoff delay with full
    jitter, drawn from a seedable RNG: bounded by the session knobs,
    observable on the runner, and bit-identical across runs with the
    same seed."""
    fleet.inject_failures = {"0:0"}
    fleet.session.properties["retry_backoff_seed"] = 20260805
    fleet.session.properties["retry_initial_delay_ms"] = 40
    fleet.session.properties["retry_max_delay_ms"] = 160
    sql = (
        "select o_orderpriority, count(*) from orders "
        "group by o_orderpriority order by 1"
    )
    result = check(fleet, oracle, sql)
    first = list(fleet.retry_delays)
    assert result.tasks_retried >= 1
    assert len(first) >= 1
    # full jitter: uniform in [0, initial_delay] for a first failure
    assert all(0.0 <= d <= 0.040 + 1e-9 for d in first), first
    check(fleet, oracle, sql)
    assert fleet.retry_delays == first, (
        "seeded retry jitter must be deterministic across runs"
    )


def test_fleet_nonretryable_error_fails_fast(spool_root):
    """A deterministic semantic error reported by a worker must fail
    the query IMMEDIATELY — burning max_attempts on copies of the same
    error hides the real failure and triples time-to-diagnosis."""
    from trino_tpu.server.fleet import _retryable

    assert _retryable("InjectedTaskFailure: injected failure")
    assert _retryable(
        "SpoolCorruptionError: corrupt spool partition "
        "stage=0 task=s0t0 attempt=0 file=x.npz: body fails CRC32"
    )
    assert _retryable("worker died")
    assert not _retryable("ValueError: bad literal")
    assert not _retryable("NotImplementedError: ARRAY over exchange")
    assert not _retryable("AnalysisError: column not found")

    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    fleet = FleetRunner(
        # nothing listens on this port: placement probes fail fast and
        # the monkeypatched RPCs below never touch the network
        ["http://127.0.0.1:9"],
        md, Session(catalog="tpch", schema="tiny"),
        spool_root=spool_root, n_partitions=2,
    )
    fleet._post_task = lambda *a, **k: None
    fleet._poll_task = lambda w, tid, a: {
        "state": "FAILED", "error": "ValueError: bad literal"
    }
    with pytest.raises(RuntimeError, match="non-retryable"):
        fleet.execute("select count(*) from nation")
    assert fleet.stats["tasks_retried"] == 0


def test_fleet_readmits_recovered_worker(workers, spool_root, oracle):
    """A worker evicted for unresponsiveness is not banned forever:
    once it answers /v1/info again, a backoff-scheduled probe restores
    it to the placement pool (the recovery half of the
    HeartbeatFailureDetector loop). Query 1 loses the victim to
    SIGSTOP; after SIGCONT, query 2 on the same runner must re-admit
    it."""
    victim_port = BASE_PORT + 5
    victim = _spawn_worker(victim_port)
    victim_uri = f"http://127.0.0.1:{victim_port}"
    try:
        md = Metadata()
        md.register_catalog("tpch", TpchConnector())
        fleet = FleetRunner(
            [victim_uri] + list(workers),
            md, Session(catalog="tpch", schema="tiny"),
            spool_root=spool_root, n_partitions=4,
            rpc_timeout_s=1.0, max_poll_fails=3,
            readmit_initial_s=0.2, readmit_max_s=0.5,
            readmit_probe_timeout_s=0.5,
        )
        fleet.session.properties["speculation_enabled"] = False
        fleet.session.properties["fleet_task_delay_ms"] = 200
        state = {"stopped": False}

        def post_hook(stage_id, task_id, w):
            if not state["stopped"] and victim_uri in w.uri:
                os.kill(victim.pid, signal.SIGSTOP)
                state["stopped"] = True

        fleet.post_hook = post_hook
        sql = (
            "select o_orderpriority, count(*) from orders "
            "group by o_orderpriority order by 1"
        )
        r1 = fleet.execute(sql)
        assert state["stopped"], "victim never received a task"
        mark = [w for w in fleet.workers if victim_uri in w.uri][0]
        assert not mark.alive  # evicted during query 1
        assert r1.workers_readmitted == 0
        # the worker recovers; the NEXT query's probe must find it
        os.kill(victim.pid, signal.SIGCONT)
        time.sleep(max(fleet._probe_at.get(mark.uri, 0) -
                       time.monotonic(), 0) + 0.1)
        fleet.post_hook = None
        r2 = fleet.execute(sql)
        assert r2.workers_readmitted >= 1
        assert mark.alive and not mark.draining
        expected = oracle.execute(to_sqlite(sql)).fetchall()
        assert_rows_match(
            r2.rows, expected, ordered=r2.ordered, abs_tol=1e-9
        )
    finally:
        try:
            os.kill(victim.pid, signal.SIGCONT)
        except OSError:
            pass
        victim.kill()
