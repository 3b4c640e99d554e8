"""Unified chaos-injection framework: FaultInjector semantics, seed
determinism, cross-process shipping, and the multi-site soak.

Fast tier: injector unit tests plus one single-scenario fleet smoke
(CI's chaos smoke job runs exactly these via ``-m 'not slow'``).
Slow tier: the full scenario matrix across all six sites under
retry_policy=TASK and QUERY, byte-for-byte schedule determinism, and
a genuine QUERY-tier retry exhaustion.
"""

import json

import pytest

from trino_tpu import fault
from trino_tpu.testing import chaos


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    fault.deactivate()


@pytest.fixture(scope="module")
def chaos_workers():
    procs, uris = chaos.spawn_workers(2, platform="cpu")
    yield uris
    chaos.stop_workers(procs)


@pytest.fixture(scope="module")
def spool_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("chaos-spool"))


# ---- FaultInjector unit semantics ----------------------------------


def test_unknown_site_rejected():
    inj = fault.FaultInjector()
    with pytest.raises(ValueError, match="unknown fault site"):
        inj.arm("disk", times=1)
    with pytest.raises(ValueError, match="probability"):
        inj.arm_probability("rpc", 1.5)
    with pytest.raises(ValueError, match="n must be"):
        inj.arm_nth("rpc", 0)


def test_times_schedule_clears_on_retry():
    """The classic retry shape: attempts 0..times-1 fail, the retry at
    attempt ``times`` succeeds."""
    inj = fault.FaultInjector()
    inj.arm("task-exec", tag="s0t0", times=2)
    for attempt in (0, 1):
        with pytest.raises(fault.InjectedFault) as ei:
            inj.check("task-exec", tag="s0t0", attempt=attempt)
        assert ei.value.site == "task-exec"
        assert ei.value.attempt == attempt
    inj.check("task-exec", tag="s0t0", attempt=2)  # recovered
    assert inj.injected == [("s0t0", 0), ("s0t0", 1)]


def test_nth_schedule_fires_exactly_once():
    inj = fault.FaultInjector()
    inj.arm_nth("rpc", 3, tag="poll:")
    for i in range(6):
        if i == 2:  # the 3rd matching call (1-based)
            with pytest.raises(fault.InjectedFault):
                inj.check("rpc", tag="poll:t1", attempt=0)
        else:
            inj.check("rpc", tag="poll:t1", attempt=0)
    assert len(inj.injected) == 1


def test_tag_prefix_scoping():
    inj = fault.FaultInjector()
    inj.arm("rpc", tag="post:", times=1)
    inj.check("rpc", tag="poll:t1", attempt=0)  # different prefix
    with pytest.raises(fault.InjectedFault):
        inj.check("rpc", tag="post:t1", attempt=0)


def test_probability_schedule_is_seed_deterministic():
    """The coin hashes (seed, site, tag, attempt) — never call order —
    so two injectors with the same seed agree on every operation, and
    repeated polls of one operation get one verdict."""
    domain = [(f"t{i}", a) for i in range(50) for a in range(3)]

    def verdicts(seed):
        inj = fault.FaultInjector(seed=seed)
        inj.arm_probability("task-exec", 0.3)
        out = []
        for tag, attempt in domain:
            try:
                inj.check("task-exec", tag=tag, attempt=attempt)
                out.append(False)
            except fault.InjectedFault:
                out.append(True)
        return out

    a, b = verdicts(11), verdicts(11)
    assert a == b, "same seed must reproduce the same schedule"
    assert any(a), "p=0.3 over 150 ops must fire sometimes"
    assert not all(a), "p=0.3 over 150 ops must also pass sometimes"
    assert verdicts(12) != a, "different seeds must differ"
    # repeated checks of the SAME operation: same verdict every time
    inj = fault.FaultInjector(seed=11)
    inj.arm_probability("task-exec", 0.3)
    first = None
    for _ in range(5):
        try:
            inj.check("task-exec", tag="t0", attempt=0)
            outcome = False
        except fault.InjectedFault:
            outcome = True
        assert outcome == (first if first is not None else outcome)
        first = outcome


def test_probability_extremes():
    inj = fault.FaultInjector(seed=0)
    inj.arm_probability("planner", 0.0)
    for i in range(20):
        inj.check("planner", tag=f"q{i}", attempt=0)
    inj.reset()
    inj.arm_probability("planner", 1.0)
    with pytest.raises(fault.InjectedFault):
        inj.check("planner", tag="q0", attempt=0)


def test_spec_roundtrip_reproduces_schedule():
    """to_spec/from_spec is how the injector rides a stage-task
    request into the worker process: the rebuilt injector must agree
    with the original on every probabilistic verdict, and honor the
    shipped default_attempt for module-level hooks."""
    src = fault.FaultInjector(seed=99)
    src.arm_probability("spool-write", 0.4)
    src.arm("task-exec", tag="s1", times=1)
    dst = fault.FaultInjector.from_spec(src.to_spec(), default_attempt=1)
    assert dst.seed == 99
    for i in range(40):
        tag = f"s0t{i}"
        fired_src = fired_dst = False
        try:
            src.check("spool-write", tag=tag, attempt=0)
        except fault.InjectedFault:
            fired_src = True
        try:
            dst.check("spool-write", tag=tag, attempt=0)
        except fault.InjectedFault:
            fired_dst = True
        assert fired_src == fired_dst
    # default_attempt=1 beats a times=1 rule (attempt 1 >= times)
    dst.check("task-exec", tag="s1")
    # but attempt 0 (a first attempt) still fails
    with pytest.raises(fault.InjectedFault):
        dst.check("task-exec", tag="s1", attempt=0)


def test_module_hooks_noop_without_active_injector():
    fault.deactivate()
    fault.check("rpc", tag="post:x", attempt=0)  # must not raise
    assert fault.active() is None
    inj = fault.FaultInjector()
    inj.arm("rpc", times=1)
    fault.activate(inj)
    with pytest.raises(fault.InjectedFault):
        fault.check("rpc", tag="post:x", attempt=0)
    fault.deactivate()
    fault.check("rpc", tag="post:x", attempt=0)


def test_decisions_log_records_passes_and_fires():
    inj = fault.FaultInjector()
    inj.arm("planner", times=1)
    with pytest.raises(fault.InjectedFault):
        inj.check("planner", tag="Query", attempt=0)
    inj.check("planner", tag="Query", attempt=1)
    assert inj.decisions == [
        ("planner", "Query", 0, "times"),
        ("planner", "Query", 1, None),
    ]


def test_legacy_failure_injector_is_an_adapter():
    """exec/failure.py keeps its public API but now subclasses the
    unified injector, so legacy mesh tests and new chaos rules
    compose."""
    from trino_tpu.exec.failure import FailureInjector, InjectedFailure

    inj = FailureInjector(max_attempts=3)
    assert isinstance(inj, fault.FaultInjector)
    inj.fail_stage("exchange", times=1)
    with pytest.raises(InjectedFailure) as ei:
        inj.check("exchange", 0)
    assert isinstance(ei.value, fault.InjectedFault)
    assert inj.injected == [("exchange", 0)]
    inj.check("exchange", 1)
    assert ("exchange", 1) in inj.attempts


def test_injected_fault_is_retryable_by_both_tiers():
    from trino_tpu.server.fleet import _query_tier_retryable, _retryable

    e = fault.InjectedFault("spool-write", "2:s2t1", 0, "times")
    assert _retryable(f"{type(e).__name__}: {e}")
    assert _query_tier_retryable(e)


# ---- fleet smoke (the CI chaos-smoke tier) -------------------------


def test_chaos_smoke_task_exec(chaos_workers, spool_root):
    """Seeded single-site smoke: every task's first attempt fails in
    the worker, the task tier retries, the answer stays oracle-exact.
    Cheap enough for the tier-1/CI smoke lane."""
    fleet = chaos.make_fleet(chaos_workers, spool_root)
    fleet.session.properties["speculation_enabled"] = False
    fleet.session.properties["retry_initial_delay_ms"] = 5
    fleet.session.properties["retry_max_delay_ms"] = 20
    inj = fault.FaultInjector(seed=3)
    inj.arm("task-exec", times=1)
    fault.activate(inj)
    try:
        result = fleet.execute(chaos._AGG_SQL)
    finally:
        fault.deactivate()
    assert result.tasks_retried >= 1
    assert any("site=task-exec" in line for line in fleet.failure_log)
    import sqlite3

    from trino_tpu.engine import QueryRunner
    from trino_tpu.testing.golden import (
        assert_rows_match,
        load_tpch_sqlite,
        to_sqlite,
    )

    oracle = load_tpch_sqlite(
        QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    )
    expected = oracle.execute(to_sqlite(chaos._AGG_SQL)).fetchall()
    assert_rows_match(
        result.rows, expected, ordered=result.ordered, abs_tol=1e-9
    )


# ---- pipelined admission under chaos (the CI pipelined lane) -------


def _oracle_rows(sql):
    from trino_tpu.engine import QueryRunner
    from trino_tpu.testing.golden import load_tpch_sqlite, to_sqlite

    oracle = load_tpch_sqlite(
        QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    )
    return oracle.execute(to_sqlite(sql)).fetchall()


def _chaos_run(chaos_workers, spool_root, sql, mode, seed, arm, **props):
    """One seeded chaos execution under one stage_admission mode."""
    fleet = chaos.make_fleet(chaos_workers, spool_root)
    fleet.session.properties["stage_admission"] = mode
    fleet.session.properties["speculation_enabled"] = False
    fleet.session.properties["retry_backoff_seed"] = seed
    fleet.session.properties["retry_initial_delay_ms"] = 5
    fleet.session.properties["retry_max_delay_ms"] = 20
    # stretch producer commit tails so pipelined consumers really are
    # admitted mid-stream, not after an instant full commit
    fleet.session.properties["spool_partition_delay_ms"] = 40
    for k, v in props.items():
        fleet.session.properties[k] = v
    inj = fault.FaultInjector(seed=seed, max_attempts=fleet.max_attempts)
    arm(inj)
    fault.activate(inj)
    try:
        return fleet.execute(sql)
    finally:
        fault.deactivate()


def _assert_modes_agree(chaos_workers, spool_root, sql, seed, arm, **props):
    """Same seed, both admission modes: byte-identical rows, and both
    oracle-exact."""
    from trino_tpu.testing.golden import assert_rows_match

    barrier = _chaos_run(
        chaos_workers, spool_root, sql, "BARRIER", seed, arm, **props
    )
    pipelined = _chaos_run(
        chaos_workers, spool_root, sql, "PIPELINED", seed, arm, **props
    )
    assert pipelined.rows == barrier.rows, (
        "pipelined admission changed result bytes under chaos"
    )
    assert_rows_match(
        pipelined.rows, _oracle_rows(sql), ordered=pipelined.ordered,
        abs_tol=1e-6,
    )
    return barrier, pipelined


def test_chaos_pipelined_producer_retry_mid_stream(
    chaos_workers, spool_root
):
    """Every producer's attempt 0 dies AFTER its partition markers
    land but BEFORE the attempt manifest (the spool-write site sits in
    that window): pipelined consumers admitted against those orphaned
    attempt-0 markers keep reading them — durable, CRC-valid, and
    byte-identical to the retry's recommit — while the producers retry
    to full commit."""
    _, pipelined = _assert_modes_agree(
        chaos_workers, spool_root, chaos._AGG_SQL, 11,
        lambda inj: inj.arm("spool-write", times=1),
    )
    assert pipelined.tasks_retried >= 1


def test_chaos_exchange_fetch_fault_falls_back_to_spool(
    chaos_workers, spool_root
):
    """A mid-fetch fault on the direct exchange (every attempt-0
    producer-memory fetch fires) degrades silently to the durable
    spool copy: no task failure, no retry, rows byte-identical across
    admission modes and oracle-exact. The workers' injection counters
    prove the faults really fired (the site is absorbed, so nothing
    reaches failure_log), and zero direct bytes prove every exchange
    read actually took the fallback path."""
    before = chaos._worker_chaos_counts(chaos_workers)
    _, pipelined = _assert_modes_agree(
        chaos_workers, spool_root, chaos._JOIN_SQL, 41,
        lambda inj: inj.arm("exchange-fetch", times=1),
    )
    after = chaos._worker_chaos_counts(chaos_workers)
    assert after.get("exchange-fetch", 0) > before.get(
        "exchange-fetch", 0
    ), "exchange-fetch site never fired in the workers"
    # absorbed, never fatal: invisible to the retry tiers
    assert pipelined.tasks_retried == 0
    assert pipelined.query_retries == 0
    assert all(
        s["direct_bytes"] == 0 for s in pipelined.stage_stats
    ), "a faulted fetch still served direct bytes"
    assert sum(
        s["spooled_bytes"] for s in pipelined.stage_stats
    ) > 0, "fallback reads never touched the spool"


@pytest.mark.slow
def test_chaos_pipelined_spool_read_fault_on_admitted_edge(
    chaos_workers, spool_root
):
    """A consumer admitted mid-stream fails its attempt-0 pinned
    source read (spool-read site): the task tier retries it, the
    re-post re-pins from current commit state, rows stay identical."""
    _, pipelined = _assert_modes_agree(
        chaos_workers, spool_root, chaos._JOIN_SQL, 23,
        lambda inj: inj.arm("spool-read", times=1),
    )
    assert pipelined.tasks_retried >= 1


@pytest.mark.slow
def test_chaos_pipelined_speculative_producer_loses(
    chaos_workers, spool_root
):
    """First-commit-wins composition: SIGSTOP a producer mid-stream
    (after its early partition markers land) so consumers are admitted
    pinned to its attempt 0, then let the speculative hedge's attempt
    win the full commit. The loser's durable markers stay readable —
    the pinned consumers stand, and the rows match a clean BARRIER
    run byte for byte."""
    import os
    import signal
    import threading

    from trino_tpu.testing.golden import assert_rows_match

    sql = chaos._JOIN_SQL
    barrier = _chaos_run(
        chaos_workers, spool_root, sql, "BARRIER", 31, lambda inj: None
    )

    procs, uris = chaos.spawn_workers(
        1, base_port=chaos.CHAOS_BASE_PORT + 10,
        platform="cpu"
    )
    victim = procs[0]
    try:
        fleet = chaos.make_fleet(
            list(chaos_workers) + uris, spool_root,
            rpc_timeout_s=2.0, max_poll_fails=15,
        )
        fleet.session.properties["stage_admission"] = "PIPELINED"
        fleet.session.properties["spool_partition_delay_ms"] = 150
        fleet.session.properties["speculation_multiplier"] = 1.5
        fleet.session.properties["retry_initial_delay_ms"] = 5
        fleet.session.properties["retry_max_delay_ms"] = 20
        state = {"stopped": False}

        def post_hook(stage_id, task_id, w):
            if state["stopped"] or uris[0] not in w.uri:
                return
            state["stopped"] = True
            # stall AFTER the first partition markers commit (~150 ms
            # into the 4-partition write) so a consumer can pin them
            t = threading.Timer(
                0.25, os.kill, (victim.pid, signal.SIGSTOP)
            )
            t.daemon = True
            t.start()

        fleet.post_hook = post_hook
        result = fleet.execute(sql)
        assert state["stopped"], "victim worker never received a task"
        assert result.rows == barrier.rows
        assert_rows_match(
            result.rows, _oracle_rows(sql), ordered=result.ordered,
            abs_tol=1e-6,
        )
    finally:
        try:
            os.kill(victim.pid, signal.SIGCONT)
        except OSError:
            pass
        chaos.stop_workers(procs)


# ---- the full soak (slow tier) -------------------------------------


@pytest.mark.slow
def test_chaos_soak_covers_all_sites(chaos_workers, spool_root):
    """Every fleet-reachable site injects under both retry policies;
    every scenario returns oracle-exact rows (asserted inside the
    soak); the QUERY tier actually re-executes for the faults that
    escape the task tier. Two sites live outside the fleet soak's
    reach and carry their own dedicated chaos coverage: ``scan-read``
    (parquet streamed-storage splits — tests/test_storage_scan.py and
    run_storage_chaos) and ``compile-deserialize`` (the compile
    service's persistent-cache path, which long-lived soak workers
    never re-enter once their in-memory executable caches are warm —
    tests/test_jit_cache.py)."""
    record = chaos.run_chaos_soak(chaos_workers, spool_root, seed=7)
    assert chaos.fired_sites(record) == set(fault.SITES) - {
        "scan-read", "compile-deserialize",
    }
    by_name = {
        run["scenario"]: run for run in record["policies"]["QUERY"]
    }
    assert by_name["planner"]["query_retries"] >= 1
    assert by_name["root-read-exhausted"]["query_retries"] >= 1
    # the task tier absorbed everything it is meant to absorb
    for run in record["policies"]["TASK"]:
        assert run["query_retries"] == 0
    # the absorbed direct-exchange site: fired in the workers, yet
    # caused no retries at any tier
    for runs in record["policies"].values():
        run = next(
            r for r in runs if r["scenario"] == "exchange-fetch"
        )
        assert run["absorbed_sites"] == ["exchange-fetch"]
        assert run["tasks_retried"] == 0
        assert run["query_retries"] == 0


@pytest.mark.slow
def test_chaos_soak_schedule_is_byte_deterministic(
    chaos_workers, spool_root
):
    """Same seed -> byte-identical canonical injection record (fired
    decisions + worker-tier injected failures), across two full soak
    runs in fresh spool epochs."""
    a = chaos.run_chaos_soak(
        chaos_workers, spool_root, seed=20260805, policies=("TASK",)
    )
    b = chaos.run_chaos_soak(
        chaos_workers, spool_root, seed=20260805, policies=("TASK",)
    )
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


@pytest.mark.slow
def test_query_retries_exhausted_for_real(chaos_workers, spool_root):
    """A fault that never clears exhausts the QUERY tier: bounded
    whole-statement re-executions, then the typed exhaustion error
    carrying the last underlying failure."""
    from trino_tpu.tracker import QueryRetriesExhaustedError

    fleet = chaos.make_fleet(chaos_workers, spool_root)
    fleet.session.properties["retry_policy"] = "QUERY"
    fleet.session.properties["query_retry_attempts"] = 1
    fleet.session.properties["speculation_enabled"] = False
    fleet.session.properties["retry_initial_delay_ms"] = 5
    fleet.session.properties["retry_max_delay_ms"] = 20
    inj = fault.FaultInjector(seed=1)
    inj.arm("task-exec", times=99)  # never recovers within max_attempts
    fault.activate(inj)
    try:
        with pytest.raises(QueryRetriesExhaustedError) as ei:
            fleet.execute("select count(*) from nation")
    finally:
        fault.deactivate()
    msg = str(ei.value)
    assert "2 executions" in msg
    assert "last failure" in msg


@pytest.mark.slow
def test_cache_chaos_kill_worker_with_pinned_entries(tmp_path):
    """A worker holding pinned device-cache entries hard-killed
    mid-round: the retried tasks cold-scan on the survivors, rows stay
    oracle-exact, and the retry count matches the uncached twin —
    cache residency neither rescues nor amplifies the failure path
    (asserts live inside run_cache_chaos)."""
    record = chaos.run_cache_chaos(
        seed=0, spool_root=str(tmp_path), platform="cpu"
    )
    by_name = {r["scenario"]: r for r in record["runs"]}
    assert by_name["kill-cached-worker"]["pinned_entries_lost"] > 0
    assert (
        by_name["kill-cached-worker"]["tasks_retried"]
        == by_name["kill-uncached-worker"]["tasks_retried"]
    )
