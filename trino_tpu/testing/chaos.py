"""Seeded chaos soak: drive a real multi-process fleet through every
fault-injection site and assert oracle-exact results.

The harness behind ``tests/test_chaos.py``.
One *scenario* = one query executed with one armed
:class:`trino_tpu.fault.FaultInjector`; the soak runs a fixed scenario
list per retry policy (TASK recovers everything at the task tier;
QUERY additionally exercises whole-statement re-execution for faults
that escape it). Every scenario's result is checked row-for-row
against the sqlite oracle — chaos that silently corrupts answers is a
far worse outcome than chaos that fails queries.

Determinism: the injector's decisions hash (seed, site, tag, attempt)
— never wall-clock or call order — so the *schedule* of fired
injections is a function of the seed alone. ``run_chaos_soak`` returns
a canonical record (fired coordinator decisions + worker-tier injected
failures, each sorted to strip scheduler interleaving noise); two runs
with the same seed must produce byte-identical records, which is
exactly what the determinism test asserts.

Port discipline: chaos workers bind 18960+ (``test_fleet.py`` owns
18940+) so the suites never collide inside one run.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
import urllib.request

from trino_tpu import fault
from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.metadata import Metadata, Session
from trino_tpu.plan.fragment import fragment_plan
from trino_tpu.server.fleet import FleetRunner
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)

__all__ = [
    "CHAOS_BASE_PORT", "spawn_workers", "stop_workers",
    "make_fleet", "make_serving", "run_chaos_soak", "fired_sites",
    "run_storage_chaos", "run_skew_chaos", "run_elastic_chaos",
    "run_cache_chaos", "run_recovery_chaos", "run_write_chaos",
]

CHAOS_BASE_PORT = 18960

#: worker-raised injected faults announce their coordinates in the
#: error string; the soak parses them back out for per-site evidence
_INJECTED_RE = re.compile(
    r"site=(\S+) tag='([^']*)' attempt=(\d+) kind=(\S+)"
)

_AGG_SQL = (
    "select o_orderpriority, count(*) from orders "
    "group by o_orderpriority order by 1"
)
_JOIN_SQL = (
    "select c_mktsegment, count(*), sum(o_totalprice) "
    "from customer, orders where c_custkey = o_custkey "
    "group by c_mktsegment order by 1"
)


def child_env(platform: str | None, extra_env: dict | None = None) -> dict:
    """Environment of a spawned server process. ``platform`` is the
    child's ``JAX_PLATFORMS``; with ``None`` the child gets no such
    variable — not even the parent's — and takes jax's default backend
    (the chip, on a machine that has one: one process per chip, so the
    caller must not hold it). Tests pass ``"cpu"``."""
    env = os.environ.copy()
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    env.update(extra_env or {})
    return env


def spawn_workers(
    n: int = 2, base_port: int = CHAOS_BASE_PORT,
    timeout_s: float = 120, extra_env: dict | None = None,
    platform: str | None = None,
):
    """Start ``n`` worker processes; returns (procs, uris).
    ``extra_env`` overlays the inherited environment (e.g.
    ``TRINO_TPU_ORPHAN_TTL_S`` to arm the orphan reaper);
    ``platform`` as in :func:`child_env`."""
    env = child_env(platform, extra_env)
    procs, uris = [], []
    for i in range(n):
        port = base_port + i
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "trino_tpu.server.worker",
             "--port", str(port)],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        ))
        uris.append(f"http://127.0.0.1:{port}")
    deadline = time.monotonic() + timeout_s
    for proc, uri in zip(procs, uris):
        while True:
            try:
                with urllib.request.urlopen(
                    f"{uri}/v1/info", timeout=1
                ) as resp:
                    json.loads(resp.read())
                    break
            except Exception:
                if proc.poll() is not None:
                    stop_workers(procs)
                    raise RuntimeError(
                        f"chaos worker died: {proc.stdout.read()[:4000]}"
                    )
                if time.monotonic() > deadline:
                    stop_workers(procs)
                    raise TimeoutError("chaos worker did not come up")
                time.sleep(0.3)
    return procs, uris


def stop_workers(procs) -> None:
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


def make_fleet(
    worker_uris, spool_root: str, schema: str = "tiny", **kwargs
) -> FleetRunner:
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    return FleetRunner(
        list(worker_uris), md, Session(catalog="tpch", schema=schema),
        spool_root=spool_root, n_partitions=4, **kwargs
    )


def make_serving(worker_uris, spool_root: str, **kwargs):
    """A ServingRunner over TPC-H tiny (the multi-query counterpart of
    :func:`make_fleet` — shared slot pool, fair-share admission)."""
    from trino_tpu.dispatcher import ServingRunner

    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    return ServingRunner(
        list(worker_uris), md, Session(catalog="tpch", schema="tiny"),
        spool_root=spool_root, n_partitions=4, **kwargs
    )


def _root_stage_id(fleet: FleetRunner, sql: str) -> str:
    """The root (coordinator-read) stage id of ``sql``'s fragment DAG
    — planning is deterministic, so this matches what execute() will
    schedule. Used to scope spool-read rules to the coordinator's root
    read (worker source reads never touch the root stage's output)."""
    return fragment_plan(fleet._planner.plan_sql(sql))[-1].stage_id


def _scenarios(fleet: FleetRunner, policy: str):
    """(name, sql, arm(injector)) triples. Worker-shipped rules must be
    attempt-sensitive (``times``/``prob``) — an ``nth`` counter resets
    with each per-task rebuild, so it would re-fire on every retry —
    while coordinator-resident rules may be ``nth`` (the instance, and
    its counters, live across the whole statement)."""
    root_agg = _root_stage_id(fleet, _AGG_SQL)
    scenarios = [
        # rpc post: first submission dies on the wire -> the fleet
        # marks the worker dead, reroutes the attempt, re-admits later
        ("rpc-post", _AGG_SQL,
         lambda inj: inj.arm_nth("rpc", 1, tag="post:")),
        # rpc poll: one status poll times out -> poll-failure counter,
        # not eviction; the next poll succeeds
        ("rpc-poll", _AGG_SQL,
         lambda inj: inj.arm_nth("rpc", 2, tag="poll:")),
        # every task's attempt-0 output commit fails BEFORE the commit
        # marker -> task retry rewrites from scratch
        ("spool-write", _AGG_SQL,
         lambda inj: inj.arm("spool-write", times=1)),
        # every attempt-0 spooled read fails: worker source reads fail
        # the task (task retry), the coordinator root read retries in
        # place at the next read attempt
        ("spool-read", _AGG_SQL,
         lambda inj: inj.arm("spool-read", times=1)),
        # every task fails its attempt-0 execution outright
        ("task-exec", _AGG_SQL,
         lambda inj: inj.arm("task-exec", times=1)),
        # every task's attempt-0 first memory reservation fails (a
        # transient busy-device OOM, not the semantic cap breach);
        # needs the join — reservations guard join working sets
        ("device-oom", _JOIN_SQL,
         lambda inj: inj.arm("device-oom", times=1)),
        # multi-site probabilistic storm on a join: the composability
        # the two legacy injectors could not provide
        ("prob-storm", _JOIN_SQL,
         lambda inj: (
             inj.arm_probability("task-exec", 0.3),
             inj.arm_probability("spool-write", 0.2),
             inj.arm_probability("device-oom", 0.15),
         )),
        # every attempt-0 direct-exchange fetch faults mid-fetch ->
        # the consumer silently falls back to the durable spool copy.
        # The task NEVER fails (the site is absorbed, not fatal), so
        # the only evidence is the workers' chaos-injection counters
        # (absorbed_sites) plus the oracle check proving the fallback
        # read the same bytes
        ("exchange-fetch", _JOIN_SQL,
         lambda inj: inj.arm("exchange-fetch", times=1)),
    ]
    if policy == "QUERY":
        scenarios += [
            # transient planner fault: escapes the task tier by
            # definition (no task exists yet) -> whole-statement retry
            ("planner", _AGG_SQL,
             lambda inj: inj.arm_nth("planner", 1)),
            # the coordinator's root read fails max_attempts times ->
            # the task tier gives up -> QUERY tier re-executes under a
            # fresh spool epoch. Stacked nth=1 rules fire the first
            # max_attempts matching calls (a fired rule breaks the
            # scan, so each call consumes exactly one rule); by the
            # re-execution every counter is spent and the reads succeed
            ("root-read-exhausted", _AGG_SQL,
             lambda inj: [
                 inj.arm_nth("spool-read", 1, tag=f"{root_agg}:")
                 for _ in range(fleet.max_attempts)
             ]),
        ]
    return scenarios


def _worker_chaos_counts(worker_uris) -> dict:
    """Summed per-site chaos-injection counters scraped off every
    worker's /v1/metrics — the evidence channel for ABSORBED faults
    (sites like exchange-fetch whose firing degrades a code path
    instead of failing the task, so nothing reaches failure_log)."""
    totals: dict = {}
    pat = re.compile(
        r'trino_chaos_injections_total\{site="([^"]+)"\}\s+(\d+)'
    )
    for uri in worker_uris:
        with urllib.request.urlopen(
            f"{uri}/v1/metrics", timeout=5
        ) as resp:
            txt = resp.read().decode()
        for m in pat.finditer(txt):
            totals[m.group(1)] = (
                totals.get(m.group(1), 0) + int(m.group(2))
            )
    return totals


def run_chaos_soak(
    worker_uris, spool_root: str, seed: int = 0,
    policies=("TASK", "QUERY"), oracle=None,
) -> dict:
    """Run the scenario matrix; assert oracle-correctness throughout;
    return the canonical (sorted, JSON-safe) injection record."""
    if oracle is None:
        data = (
            QueryRunner.tpch("tiny").metadata.connector("tpch")
            .data("tiny")
        )
        oracle = load_tpch_sqlite(data)
    record = {"seed": seed, "policies": {}}
    for policy in policies:
        fleet = make_fleet(worker_uris, spool_root)
        fleet.session.properties["retry_policy"] = policy
        # hedged duplicate attempts would add timing-dependent
        # (site, tag, attempt) checks — keep the schedule a pure
        # function of the seed
        fleet.session.properties["speculation_enabled"] = False
        fleet.session.properties["retry_backoff_seed"] = seed
        fleet.session.properties["retry_initial_delay_ms"] = 5
        fleet.session.properties["retry_max_delay_ms"] = 20
        runs = []
        for name, sql, arm in _scenarios(fleet, policy):
            inj = fault.FaultInjector(
                seed=seed, max_attempts=fleet.max_attempts
            )
            arm(inj)
            before = _worker_chaos_counts(worker_uris)
            fault.activate(inj)
            try:
                result = fleet.execute(sql)
            finally:
                fault.deactivate()
            after = _worker_chaos_counts(worker_uris)
            expected = oracle.execute(to_sqlite(sql)).fetchall()
            assert_rows_match(
                result.rows, expected, ordered=result.ordered,
                abs_tol=1e-6,
            )
            worker_fired = sorted(
                m.groups() for m in (
                    _INJECTED_RE.search(line)
                    for line in fleet.failure_log
                ) if m
            )
            runs.append({
                "scenario": name,
                "coordinator_fired": sorted(
                    d for d in inj.decisions if d[3] is not None
                ),
                "worker_fired": worker_fired,
                # sites whose worker-side injection counters moved
                # during the scenario: catches absorbed faults (the
                # SET is seed-deterministic; raw counts would carry
                # scheduler interleaving noise, so they stay out of
                # the canonical record)
                "absorbed_sites": sorted(
                    site for site, n in after.items()
                    if n > before.get(site, 0)
                ),
                "tasks_retried": result.tasks_retried,
                "query_retries": result.query_retries,
            })
        record["policies"][policy] = runs
    return record


def run_storage_chaos(seed: int = 0, root: str | None = None) -> dict:
    """Streamed-storage chaos scenario: every split's first TWO read
    attempts fail at the ``scan-read`` site mid-stream, forcing the
    out-of-core scan (exec/stream_scan) to retry at SPLIT granularity
    — one row-group batch re-reads, never the table. The result must
    stay oracle-exact and the stream must still report its batches,
    proving the retries were local. Requires pyarrow (the caller
    gates); returns the canonical fired-injection record."""
    import sqlite3
    import tempfile

    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.connectors.base import TableSchema
    from trino_tpu.connectors.parquet import write_parquet_table

    root = root or tempfile.mkdtemp(prefix="chaos-storage")
    n = 120_000
    rng = np.random.default_rng(seed + 101)
    k = np.arange(n, dtype=np.int64) // 64
    v = rng.integers(0, 997, n, dtype=np.int64)
    p = (np.arange(n, dtype=np.int64) * 7) % 3
    write_parquet_table(
        root, "default", "events",
        TableSchema(
            "events",
            [("k", T.BIGINT), ("v", T.BIGINT), ("p", T.BIGINT)],
        ),
        {"k": k, "v": v, "p": p},
        row_group_size=10_000, partition_by=["p"],
    )
    runner = QueryRunner.parquet(root)
    # a tiny budget forces the streamed path regardless of host RAM
    runner.session.properties["hbm_budget_bytes"] = 1 << 20
    sql = (
        "select p, count(*), sum(v) from events where k >= 500 "
        "group by p order by p"
    )
    db = sqlite3.connect(":memory:")
    db.execute("create table events (k integer, v integer, p integer)")
    db.executemany(
        "insert into events values (?,?,?)",
        zip(k.tolist(), v.tolist(), p.tolist()),
    )
    expected = db.execute(to_sqlite(sql)).fetchall()

    inj = fault.FaultInjector(seed=seed)
    # attempts 0 and 1 of EVERY split read fail; the third in-place
    # retry succeeds — one more armed attempt would exhaust
    # stream_scan.SCAN_READ_ATTEMPTS and fail the query
    inj.arm("scan-read", times=2)
    fault.activate(inj)
    try:
        result = runner.execute(sql)
    finally:
        fault.deactivate()
    assert_rows_match(result.rows, expected, ordered=result.ordered)
    entry = runner.executor.scan_log[-1]
    assert entry["streamed"] and entry["batches"] >= 1, entry
    fired = sorted(
        d for d in inj.decisions
        if d[3] is not None and d[0] == "scan-read"
    )
    assert fired, "scan-read injections never fired"
    return {
        "seed": seed, "scenario": "scan-read", "fired": fired,
        "batches": int(entry["batches"]),
    }


#: zipfian join: ~90% of synthetic order keys collapse onto customer 1
#: (the PR 13 flight-recorder shape) — the probe edge's hash histogram
#: shows one hot partition, which is exactly what salting re-plans
_SKEW_SQL = (
    "SELECT c.c_mktsegment, count(*) AS n, sum(o.o_totalprice) AS rev "
    "FROM (SELECT CASE WHEN o_orderkey % 10 < 9 THEN 1 ELSE o_custkey "
    "END AS k, o_totalprice FROM orders) o "
    "JOIN customer c ON o.k = c.c_custkey "
    "GROUP BY c.c_mktsegment ORDER BY 1"
)


def run_skew_chaos(
    worker_uris, spool_root: str, seed: int = 0, oracle=None,
) -> dict:
    """Skew-robustness chaos (ROADMAP skew item (b)/(c) under faults):
    the salted and adaptive re-plans must survive the same fault model
    as every other exchange shape.

    Scenario ``salted-kill``: a clean pre-run of the zipfian join
    learns the salted plan (planning AND detection are deterministic —
    same data, same histograms, same hot set), then the chaos run
    kills one hot partition's salted sub-task on its first attempt.
    Retry + first-commit-wins must reproduce the oracle rows with the
    SAME task set: salt assignment is a pure function of the plan, so
    the retried attempt re-reads the identical 1-in-K row slice.

    Scenario ``adaptive-race``: adaptive growth re-fragments the
    downstream exchange fabric while ``task-exec`` chaos is retrying
    every attempt-0 task — the re-planned partition count must hold
    across retries (attempt pins keep consumers on committed outputs).

    Both run plan_validation=FULL so every runtime re-fragmentation
    re-passes the structural invariants."""
    if oracle is None:
        data = (
            QueryRunner.tpch("tiny").metadata.connector("tpch")
            .data("tiny")
        )
        oracle = load_tpch_sqlite(data)
    expected = oracle.execute(to_sqlite(_SKEW_SQL)).fetchall()
    record: dict = {"seed": seed, "runs": []}

    def skew_fleet(**props):
        fleet = make_fleet(worker_uris, spool_root)
        p = fleet.session.properties
        p["join_distribution_type"] = "PARTITIONED"
        p["plan_validation"] = "FULL"
        p["speculation_enabled"] = False
        p["retry_backoff_seed"] = seed
        p["retry_initial_delay_ms"] = 5
        p["retry_max_delay_ms"] = 20
        p.update(props)
        return fleet

    # clean pre-run: learn the (deterministic) salted plan and the
    # reference task set, with conservation checked across the salted
    # edge (fanout reads sum exactly; replicate reads price in the
    # (K-1)x re-read of hot partitions)
    fleet = skew_fleet(
        skew_salt_threshold=2.0, skew_salt_factor=4,
        check_exchange_coverage=True,
    )
    clean = fleet.execute(_SKEW_SQL)
    assert clean.salted_edges >= 1, "zipfian join did not salt"
    assert_rows_match(
        clean.rows, expected, ordered=clean.ordered, abs_tol=1e-6
    )
    salted = [
        s for s in fleet._last_stages
        if getattr(s, "salt_plan", None) is not None
    ]
    sid = salted[0].stage_id
    hot = salted[0].salt_plan["hot"][0]
    factor = salted[0].salt_plan["factor"]
    clean_tasks = sorted(
        ts["task_id"] for ts in clean.task_stats
        if ts["stage_id"] == sid and ts.get("state") == "FINISHED"
    )
    assert f"s{sid}p{hot}x{factor - 1}" in clean_tasks, clean_tasks

    # scenario 1: first attempt of one hot sub-task dies mid-stage
    fleet = skew_fleet(skew_salt_threshold=2.0, skew_salt_factor=4)
    fleet.inject_failures = {f"{sid}:{hot}.1"}
    res = fleet.execute(_SKEW_SQL)
    assert res.salted_edges >= 1
    assert res.tasks_retried >= 1, "salted kill never fired"
    assert_rows_match(
        res.rows, expected, ordered=res.ordered, abs_tol=1e-6
    )
    killed_tasks = sorted(
        ts["task_id"] for ts in res.task_stats
        if ts["stage_id"] == sid and ts.get("state") == "FINISHED"
    )
    assert killed_tasks == clean_tasks, (
        "salt assignment drifted across the retry:\n"
        f"  clean: {clean_tasks}\n  chaos: {killed_tasks}"
    )
    record["runs"].append({
        "scenario": "salted-kill", "stage": sid, "hot": int(hot),
        "factor": int(factor), "tasks_retried": res.tasks_retried,
        "salted_edges": res.salted_edges,
    })

    # scenario 2: adaptive re-fragmentation racing task retries
    fleet = skew_fleet(
        adaptive_partition_growth_factor=0.5, adaptive_partition_max=8,
    )
    inj = fault.FaultInjector(seed=seed, max_attempts=fleet.max_attempts)
    inj.arm("task-exec", times=1)
    fault.activate(inj)
    try:
        res = fleet.execute(_SKEW_SQL)
    finally:
        fault.deactivate()
    assert res.adaptive_repartitions >= 1, "growth never triggered"
    assert res.tasks_retried >= 1, "task-exec chaos never fired"
    assert_rows_match(
        res.rows, expected, ordered=res.ordered, abs_tol=1e-6
    )
    record["runs"].append({
        "scenario": "adaptive-race",
        "adaptive_repartitions": res.adaptive_repartitions,
        "tasks_retried": res.tasks_retried,
    })
    return record


def run_elastic_chaos(
    seed: int = 0, base_port: int = 19360, spool_root: str | None = None,
    platform: str | None = None,
) -> dict:
    """Elastic-fleet chaos (scale-down is not a crash): spawns its own
    3-worker fleets at ``base_port``+ so it can drain and kill them.

    Scenario ``drain-mid-query``: the zipfian-free join runs clean on
    3 workers, then re-runs with one worker drained the moment its
    first task lands (``post_hook`` — a deterministic mid-query point,
    guaranteeing a task *spans* the drain). The drained worker must
    finish that task, keep serving its exchange buffers/spool reads to
    every consumer, and the run must come back byte-identical to the
    clean run with ``tasks_retried == 0`` — a graceful drain is
    invisible to the query, which is the whole contract.

    Scenario ``kill-draining``: same drain point, but the DRAINING
    worker is hard-killed immediately after — its in-flight task and
    buffers are gone, and the existing FTE tier (poll eviction,
    rerouted retry, first-commit-wins) must recover to oracle-exact
    rows. Drain never replaces the crash path; it only adds a clean
    one beside it."""
    import tempfile

    data = (
        QueryRunner.tpch("tiny").metadata.connector("tpch")
        .data("tiny")
    )
    oracle = load_tpch_sqlite(data)
    expected = oracle.execute(to_sqlite(_JOIN_SQL)).fetchall()
    record: dict = {"seed": seed, "runs": []}

    def elastic_fleet(worker_uris, root):
        fleet = make_fleet(worker_uris, root)
        p = fleet.session.properties
        p["speculation_enabled"] = False
        p["retry_backoff_seed"] = seed
        p["retry_initial_delay_ms"] = 5
        p["retry_max_delay_ms"] = 20
        return fleet

    def drain(uri: str) -> None:
        req = urllib.request.Request(
            f"{uri}/v1/drain", data=b"", method="POST"
        )
        with urllib.request.urlopen(req, timeout=5) as resp:
            json.loads(resp.read())

    def worker_state(uri: str) -> str:
        with urllib.request.urlopen(f"{uri}/v1/info", timeout=5) as r:
            return json.loads(r.read()).get("state", "?")

    # ---- scenario 1: graceful drain mid-query -----------------------
    procs, uris = spawn_workers(3, base_port=base_port, platform=platform)
    try:
        root = spool_root or tempfile.mkdtemp(prefix="chaos-elastic")
        fleet = elastic_fleet(uris, root)
        clean = fleet.execute(_JOIN_SQL)
        assert_rows_match(
            clean.rows, expected, ordered=clean.ordered, abs_tol=1e-6
        )

        target = uris[-1]
        drained: list = []

        def drain_on_first_post(stage_id, task_id, worker):
            if worker.uri == target and not drained:
                drained.append(task_id)
                drain(target)

        fleet = elastic_fleet(uris, root)
        fleet.post_hook = drain_on_first_post
        res = fleet.execute(_JOIN_SQL)
        assert drained, "no task ever landed on the drain target"
        assert res.rows == clean.rows, (
            "drained run is not byte-identical to the clean run"
        )
        assert_rows_match(
            res.rows, expected, ordered=res.ordered, abs_tol=1e-6
        )
        assert res.tasks_retried == 0, (
            f"graceful drain caused {res.tasks_retried} task retries "
            "(drain is not a failure)"
        )
        final_state = worker_state(target)
        assert final_state in ("DRAINING", "DRAINED"), final_state
        record["runs"].append({
            "scenario": "drain-mid-query",
            "drained_task": drained[0],
            "tasks_retried": res.tasks_retried,
            "direct_bytes": sum(
                int(st.get("direct_bytes", 0) or 0)
                for st in res.stage_stats
            ),
            "drained_worker_state": final_state,
        })
    finally:
        stop_workers(procs)

    # ---- scenario 2: hard-kill a DRAINING worker --------------------
    procs, uris = spawn_workers(
        3, base_port=base_port + 4, platform=platform
    )
    try:
        root = spool_root or tempfile.mkdtemp(prefix="chaos-elastic")
        target = uris[-1]
        target_proc = procs[-1]
        killed: list = []

        def drain_then_kill(stage_id, task_id, worker):
            if worker.uri == target and not killed:
                killed.append(task_id)
                drain(target)
                target_proc.kill()

        fleet = elastic_fleet(uris, root)
        fleet.post_hook = drain_then_kill
        res = fleet.execute(_JOIN_SQL)
        assert killed, "no task ever landed on the kill target"
        assert_rows_match(
            res.rows, expected, ordered=res.ordered, abs_tol=1e-6
        )
        assert res.tasks_retried >= 1, (
            "killing a DRAINING worker mid-task must surface as an "
            "FTE retry"
        )
        record["runs"].append({
            "scenario": "kill-draining",
            "killed_task": killed[0],
            "tasks_retried": res.tasks_retried,
            "workers_readmitted": res.workers_readmitted,
        })
    finally:
        stop_workers(procs)
    return record


def run_cache_chaos(
    seed: int = 0, base_port: int = 19440, spool_root: str | None = None,
    platform: str | None = None,
) -> dict:
    """Cache-tier chaos (a cache is never load-bearing): the same
    kill-mid-query round runs as twins — device cache OFF, then ON
    with the workers' HBM tiers warmed by a clean pass — and a worker
    holding pinned device-cache entries is hard-killed the moment its
    first task lands. The retried tasks fall back to cold scans on the
    survivors; both twins must come back oracle-exact and absorb the
    SAME number of task retries, proving cache residency neither
    rescues nor amplifies the failure path. The result cache stays off
    in both twins so the round actually dispatches tasks to kill.
    Ports ``base_port``+ (elastic owns 19360+)."""
    import tempfile

    data = (
        QueryRunner.tpch("tiny").metadata.connector("tpch")
        .data("tiny")
    )
    oracle = load_tpch_sqlite(data)
    expected = oracle.execute(to_sqlite(_JOIN_SQL)).fetchall()
    record: dict = {"seed": seed, "runs": []}

    def cache_fleet(worker_uris, root, cached: bool):
        fleet = make_fleet(worker_uris, root)
        p = fleet.session.properties
        p["speculation_enabled"] = False
        p["retry_backoff_seed"] = seed
        p["retry_initial_delay_ms"] = 5
        p["retry_max_delay_ms"] = 20
        p["result_cache_enabled"] = False
        p["device_cache_enabled"] = cached
        return fleet

    def device_entries(uri: str) -> int:
        with urllib.request.urlopen(
            f"{uri}/v1/metrics", timeout=5
        ) as resp:
            txt = resp.read().decode()
        for line in txt.splitlines():
            if line.startswith("trino_device_cache_entries"):
                return int(float(line.rsplit(" ", 1)[1]))
        return 0

    for cached in (False, True):
        procs, uris = spawn_workers(
            3, base_port=base_port + (4 if cached else 0),
            platform=platform,
        )
        try:
            root = spool_root or tempfile.mkdtemp(prefix="chaos-cache")
            fleet = cache_fleet(uris, root, cached)
            clean = fleet.execute(_JOIN_SQL)
            assert_rows_match(
                clean.rows, expected, ordered=clean.ordered,
                abs_tol=1e-6,
            )
            target, target_proc = uris[-1], procs[-1]
            pinned = device_entries(target)
            if cached:
                assert pinned > 0, (
                    "warm pass pinned nothing on the kill target — "
                    "the scenario would not exercise cache loss"
                )
            killed: list = []

            def kill_on_first_post(stage_id, task_id, worker):
                if worker.uri == target and not killed:
                    killed.append(task_id)
                    target_proc.kill()

            fleet = cache_fleet(uris, root, cached)
            fleet.post_hook = kill_on_first_post
            res = fleet.execute(_JOIN_SQL)
            assert killed, "no task ever landed on the kill target"
            assert res.rows == clean.rows, (
                "post-kill run is not byte-identical to the clean run"
            )
            assert_rows_match(
                res.rows, expected, ordered=res.ordered, abs_tol=1e-6
            )
            assert res.tasks_retried >= 1, (
                "hard-killing a worker mid-task must surface as an "
                "FTE retry"
            )
            record["runs"].append({
                "scenario": (
                    "kill-cached-worker" if cached
                    else "kill-uncached-worker"
                ),
                "killed_task": killed[0],
                "tasks_retried": res.tasks_retried,
                "pinned_entries_lost": pinned,
            })
        finally:
            stop_workers(procs)

    uncached, cached_run = record["runs"]
    assert uncached["tasks_retried"] == cached_run["tasks_retried"], (
        "cache residency changed the retry count: "
        f"{uncached['tasks_retried']} uncached vs "
        f"{cached_run['tasks_retried']} cached"
    )
    return record


def run_recovery_chaos(
    seed: int = 0, base_port: int = 19520, spool_root: str | None = None,
    platform: str | None = None,
) -> dict:
    """Coordinator crash-recovery chaos: a real coordinator *process*
    is ``kill -9``'d mid-FTE-query and restarted against the same
    spool; the same client must ride through and get oracle-exact
    rows, with every spool-committed attempt inherited rather than
    re-executed.

    Scenario ``kill-mid-query``: submit the join through a
    ``StatementClient`` with ``restart_wait_s`` armed, wait for the
    journal to show the first task commit, SIGKILL the coordinator,
    restart it with the same ``--spool``. The restarted coordinator
    replays the journal, re-serves the query at its old protocol URI,
    adopts/re-dispatches only uncommitted work, and the client's
    pagination GETs — retrying through the connection-refused window —
    deliver the finished result. Asserts: rows oracle-exact; at least
    one attempt was inherited from the spool (``resumed`` journal
    record); no post-kill dispatch re-ran a pre-kill-committed
    attempt.

    Scenario ``orphan-reap``: kill the coordinator and do NOT restart
    it. Workers armed with a short ``TRINO_TPU_ORPHAN_TTL_S`` must
    quarantine then cancel the abandoned query's tasks, release its
    exchange buffers, and GC its spool scratch — asserted off the
    workers' own /v1/metrics (reaped >= 1, reserved bytes back to 0).

    Port discipline: recovery claims 19520+ (cache chaos owns 19440+).
    """
    import signal
    import tempfile

    from trino_tpu.server.client import StatementClient

    data = (
        QueryRunner.tpch("tiny").metadata.connector("tpch")
        .data("tiny")
    )
    oracle = load_tpch_sqlite(data)
    expected = oracle.execute(to_sqlite(_JOIN_SQL)).fetchall()
    record: dict = {"seed": seed, "runs": []}

    def spawn_coordinator(port, worker_uris, root, delay_ms):
        env = child_env(platform)
        proc = subprocess.Popen(
            [sys.executable, "-m", "trino_tpu.server.coordinator",
             "--port", str(port),
             "--workers", ",".join(worker_uris),
             "--spool", root,
             "--session", "retry_policy=TASK",
             "--session", "speculation_enabled=false",
             "--session", f"retry_backoff_seed={seed}",
             "--session", "retry_initial_delay_ms=5",
             "--session", "retry_max_delay_ms=20",
             "--session", f"fleet_task_delay_ms={delay_ms}"],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        uri = f"http://127.0.0.1:{port}"
        deadline = time.monotonic() + 120
        while True:
            try:
                with urllib.request.urlopen(
                    f"{uri}/v1/info", timeout=1
                ) as resp:
                    json.loads(resp.read())
                    return proc, uri
            except Exception:
                if proc.poll() is not None:
                    raise RuntimeError(
                        "coordinator died: "
                        f"{proc.stdout.read()[:4000]}"
                    )
                if time.monotonic() > deadline:
                    proc.kill()
                    raise TimeoutError("coordinator did not come up")
                time.sleep(0.2)

    def journal_records(root):
        jdir = os.path.join(root, "_journal")
        recs = []
        if not os.path.isdir(jdir):
            return recs
        for name in sorted(os.listdir(jdir)):
            if not name.endswith(".wal"):
                continue
            with open(os.path.join(jdir, name)) as f:
                for line in f:
                    try:
                        recs.append(json.loads(line))
                    except ValueError:
                        pass
        return recs

    def wait_for_commit(root, timeout_s=90.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            recs = journal_records(root)
            if any(r.get("t") == "commit" for r in recs):
                return recs
            time.sleep(0.05)
        raise TimeoutError("no journaled task commit before deadline")

    def scrape(uri, name):
        with urllib.request.urlopen(f"{uri}/v1/metrics", timeout=5) as r:
            text = r.read().decode()
        total = 0.0
        for line in text.splitlines():
            if line.startswith(name) and not line.startswith("#"):
                try:
                    total += float(line.rsplit(None, 1)[-1])
                except ValueError:
                    pass
        return total

    # ---- scenario 1: kill -9 mid-query, restart, same client --------
    procs, uris = spawn_workers(2, base_port=base_port, platform=platform)
    coord_proc = None
    try:
        # per-scenario subdirectory: the journal is part of the spool
        # root, and scenario 2's wait-for-dispatch must never match
        # this scenario's records
        root = os.path.join(
            spool_root or tempfile.mkdtemp(prefix="chaos-recovery"),
            "kill9",
        )
        os.makedirs(root, exist_ok=True)
        port = base_port + 8
        coord_proc, coord_uri = spawn_coordinator(
            port, uris, root, delay_ms=250
        )
        client = StatementClient(coord_uri, restart_wait_s=120.0)
        result: dict = {}

        def run_client():
            try:
                cols, rows = client.execute(_JOIN_SQL)
                result["rows"] = rows
            except Exception as e:  # surfaced in the main thread
                result["error"] = e

        import threading

        ct = threading.Thread(target=run_client, daemon=True)
        t0 = time.perf_counter()
        ct.start()
        wait_for_commit(root)
        pre = journal_records(root)
        pre_commits = {
            (r["tid"], r["a"]) for r in pre if r.get("t") == "commit"
        }
        n_pre = len(pre)
        coord_proc.send_signal(signal.SIGKILL)
        coord_proc.wait(timeout=30)
        t_kill = time.perf_counter()
        # restart against the same spool + port: journal replay
        # re-serves the in-flight query at its old URI
        coord_proc, coord_uri = spawn_coordinator(
            port, uris, root, delay_ms=250
        )
        ct.join(timeout=180)
        assert not ct.is_alive(), "client never finished after restart"
        if "error" in result:
            raise AssertionError(
                f"client failed through restart: {result['error']}"
            )
        # protocol JSON carries decimals as strings; the oracle
        # returns floats — coerce before the row comparison
        got = [
            [float(v) if isinstance(v, str)
             and re.fullmatch(r"-?\d+(\.\d+)?", v) else v
             for v in row]
            for row in result["rows"]
        ]
        assert_rows_match(got, expected, ordered=True, abs_tol=1e-6)
        post = journal_records(root)
        resumed = [r for r in post if r.get("t") == "resumed"]
        assert resumed, "restarted coordinator never journaled a resume"
        assert resumed[-1].get("tasks_recovered_committed", 0) >= 1, (
            "resume inherited no spool-committed attempt (the kill "
            "landed after a commit, so at least one must carry over)"
        )
        # the no-recompute contract: nothing dispatched after the kill
        # may target an attempt that had already committed
        post_dispatches = {
            (r["tid"], r["a"])
            for r in post[n_pre:] if r.get("t") == "dispatch"
        }
        recomputed = post_dispatches & pre_commits
        assert not recomputed, (
            f"committed attempts re-executed after restart: {recomputed}"
        )
        done = [r for r in post if r.get("t") == "done"]
        assert done and done[-1]["state"] == "FINISHED", (
            "journal never reached a FINISHED done record"
        )
        record["runs"].append({
            "scenario": "kill-mid-query",
            "rows": len(result["rows"]),
            "pre_kill_commits": len(pre_commits),
            "tasks_recovered_committed": int(
                resumed[-1].get("tasks_recovered_committed", 0)
            ),
            "tasks_redispatched": int(
                resumed[-1].get("tasks_redispatched", 0)
            ),
            "recomputed_committed": len(recomputed),
            "time_to_resume_ms": (time.perf_counter() - t_kill) * 1e3,
            "client_elapsed_ms": (time.perf_counter() - t0) * 1e3,
        })
    finally:
        if coord_proc is not None and coord_proc.poll() is None:
            coord_proc.kill()
        stop_workers(procs)

    # ---- scenario 2: kill the coordinator, let the reaper clean up --
    procs, uris = spawn_workers(
        2, base_port=base_port + 16,
        extra_env={"TRINO_TPU_ORPHAN_TTL_S": "0.5"}, platform=platform,
    )
    coord_proc = None
    try:
        root = os.path.join(
            spool_root or tempfile.mkdtemp(prefix="chaos-orphan"),
            "orphan",
        )
        os.makedirs(root, exist_ok=True)
        port = base_port + 24
        coord_proc, coord_uri = spawn_coordinator(
            port, uris, root, delay_ms=4000
        )
        client = StatementClient(coord_uri, timeout=30.0)
        import threading

        threading.Thread(
            target=lambda: _swallow(client.execute, _JOIN_SQL),
            daemon=True,
        ).start()
        # a task must be RUNNING on a worker before the kill — the
        # journal's dispatch record alone races the actual POST (WAL
        # appends land first), and killing inside that gap leaves the
        # workers nothing to reap
        def active_tasks(uri):
            try:
                with urllib.request.urlopen(
                    f"{uri}/v1/info", timeout=2
                ) as r:
                    return int(json.loads(r.read())["activeTasks"])
            except Exception:
                return 0

        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if any(active_tasks(u) >= 1 for u in uris):
                break
            time.sleep(0.05)
        else:
            raise TimeoutError("no worker task before deadline")
        coord_proc.send_signal(signal.SIGKILL)
        coord_proc.wait(timeout=30)
        coord_proc = None
        # reaper timeline: quarantine at ttl (0.5s), cancel one grace
        # period later; poll past it
        reaped = buffers = 0.0
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            reaped = sum(
                scrape(u, "trino_orphan_tasks_reaped_total")
                for u in uris
            )
            if reaped >= 1:
                break
            time.sleep(0.25)
        assert reaped >= 1, (
            "orphan reaper never cancelled the abandoned query's tasks"
        )
        # buffers drain to zero once the reaper drops the query
        reserved = None
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            reserved = sum(
                scrape(u, "trino_exchange_buffer_reserved_bytes")
                for u in uris
            )
            if reserved == 0:
                break
            time.sleep(0.25)
        assert reserved == 0, (
            f"exchange buffers leaked after orphan GC: {reserved} bytes"
        )
        buffers = sum(
            scrape(u, "trino_exchange_buffer_orphan_evictions_total")
            for u in uris
        )
        record["runs"].append({
            "scenario": "orphan-reap",
            "tasks_reaped": int(reaped),
            "buffer_evictions": int(buffers),
            "reserved_after_gc": int(reserved),
        })
    finally:
        if coord_proc is not None and coord_proc.poll() is None:
            coord_proc.kill()
        stop_workers(procs)
    return record


#: distributed CTAS under chaos: partitioned so the writer stage is
#: hash-distributed (every worker writes), deterministic content so
#: the committed table can be diffed row-for-row against a clean twin
_WRITE_SQL = (
    "create table hive.chaos.{table} "
    "with (partitioned_by = array['o_orderpriority']) as "
    "select o_orderkey, o_totalprice, o_orderpriority from orders"
)


def run_write_chaos(
    seed: int = 0, base_port: int = 19720, spool_root: str | None = None,
    platform: str | None = None,
) -> dict:
    """Write-path chaos: the exactly-once commit contract under the
    same fault model as reads. Spawns its own 2-worker fleets (hive
    catalog shipped via ``TRINO_TPU_WORKER_EXTRA_PARQUET``) at
    ``base_port``+ (recovery chaos owns 19520+, bench recovery
    19680+, tests/test_write_path.py 19760+).

    A clean partitioned CTAS off TPC-H tiny establishes the twin.
    Scenario ``staged-faults`` re-runs it with every writer task's
    attempt-0 failing at ``spool-write`` and ``task-exec``; scenario
    ``worker-kill`` SIGKILLs a worker the moment a writer-stage task
    lands on it, mid-write by construction. Both must commit a table
    that is ROW-IDENTICAL to the clean twin — retried attempts stage
    under their own (epoch, task, attempt) part names, losers never
    reach the manifest, and the commit token makes the coordinator's
    finish_write idempotent. The audit additionally proves zero
    orphans: every committed part file is in the manifest, no
    duplicate manifest paths, and the staging epoch dir is gone.

    Requires pyarrow (the caller gates)."""
    import tempfile

    from trino_tpu.connectors.parquet import ParquetConnector

    hive_root = tempfile.mkdtemp(prefix="chaos-write-hive")
    record: dict = {"seed": seed, "runs": []}

    def write_fleet(worker_uris, root):
        md = Metadata()
        md.register_catalog("tpch", TpchConnector())
        md.register_catalog("hive", ParquetConnector(hive_root))
        fleet = FleetRunner(
            list(worker_uris), md,
            Session(catalog="tpch", schema="tiny"),
            spool_root=root, n_partitions=4,
        )
        p = fleet.session.properties
        p["speculation_enabled"] = False
        p["retry_backoff_seed"] = seed
        p["retry_initial_delay_ms"] = 5
        p["retry_max_delay_ms"] = 20
        return fleet

    def table_rows(table):
        md = Metadata()
        md.register_catalog("hive", ParquetConnector(hive_root))
        local = QueryRunner(md, Session(catalog="hive", schema="chaos"))
        return local.execute(
            f"select o_orderkey, o_totalprice, o_orderpriority "
            f"from {table} order by o_orderkey"
        ).rows

    def audit(table):
        """Exactly-once on disk: manifest == directory tree, no
        duplicate part paths, no staging residue."""
        tdir = os.path.join(hive_root, "chaos", table)
        with open(os.path.join(tdir, "_manifest.json")) as f:
            man = json.load(f)
        listed = [e["path"] for e in man["files"]]
        assert len(listed) == len(set(listed)), (
            f"duplicate part paths committed: {sorted(listed)}"
        )
        on_disk = set()
        for dirpath, _dirs, files in os.walk(tdir):
            for name in files:
                if name.endswith(".parquet"):
                    on_disk.add(os.path.relpath(
                        os.path.join(dirpath, name), tdir
                    ))
        assert on_disk == set(listed), (
            f"orphan/missing part files: disk-only "
            f"{sorted(on_disk - set(listed))}, manifest-only "
            f"{sorted(set(listed) - on_disk)}"
        )
        staging = [
            d for d in os.listdir(os.path.join(hive_root, "chaos"))
            if d.startswith("_tmp_")
        ]
        assert not staging, f"staging dirs survived commit: {staging}"
        return {"files": len(listed), "rows": int(man["rows"])}

    extra_env = {
        "TRINO_TPU_WORKER_EXTRA_PARQUET": f"hive={hive_root}",
    }
    procs, uris = spawn_workers(
        2, base_port=base_port, extra_env=extra_env, platform=platform
    )
    try:
        root = spool_root or tempfile.mkdtemp(prefix="chaos-write")
        fleet = write_fleet(uris, root)
        clean_res = fleet.execute(_WRITE_SQL.format(table="clean"))
        clean = table_rows("clean")
        assert clean_res.rows[0][0] == len(clean)
        audit("clean")

        # scenario 1: every writer attempt-0 dies staged (the staged
        # part files of failed attempts must never reach the manifest)
        fleet = write_fleet(uris, root)
        inj = fault.FaultInjector(
            seed=seed, max_attempts=fleet.max_attempts
        )
        inj.arm("spool-write", times=1)
        inj.arm("task-exec", times=1)
        fault.activate(inj)
        try:
            res = fleet.execute(_WRITE_SQL.format(table="faulted"))
        finally:
            fault.deactivate()
        assert res.tasks_retried >= 1, "write chaos never fired"
        assert table_rows("faulted") == clean, (
            "faulted CTAS committed different rows than the clean twin"
        )
        record["runs"].append({
            "scenario": "staged-faults",
            "tasks_retried": res.tasks_retried,
            **audit("faulted"),
        })
    finally:
        stop_workers(procs)

    # scenario 2: SIGKILL a worker as a writer-stage task lands on it
    procs, uris = spawn_workers(
        2, base_port=base_port + 4, extra_env=extra_env,
        platform=platform,
    )
    try:
        root = spool_root or tempfile.mkdtemp(prefix="chaos-write")
        fleet = write_fleet(uris, root)
        sql = _WRITE_SQL.format(table="killed")
        stages = fragment_plan(fleet._planner.plan_sql(sql))
        writer_sid = stages[-2].stage_id  # stages[-1] is TableFinish
        target, target_proc = uris[-1], procs[-1]
        killed: list = []

        def kill_on_writer_post(stage_id, task_id, worker):
            if (
                stage_id == writer_sid and worker.uri == target
                and not killed
            ):
                killed.append(task_id)
                target_proc.kill()

        fleet.post_hook = kill_on_writer_post
        res = fleet.execute(sql)
        assert killed, "no writer task ever landed on the kill target"
        assert res.tasks_retried >= 1, (
            "killing a worker mid-write must surface as an FTE retry"
        )
        assert table_rows("killed") == clean, (
            "post-kill CTAS committed different rows than the clean "
            "twin (duplicate or lost fragments)"
        )
        record["runs"].append({
            "scenario": "worker-kill",
            "killed_task": killed[0],
            "tasks_retried": res.tasks_retried,
            **audit("killed"),
        })
    finally:
        stop_workers(procs)
    return record


def _swallow(fn, *a):
    try:
        fn(*a)
    except Exception:
        pass


def fired_sites(record: dict) -> set[str]:
    """Every site that actually injected at least once, across both
    the coordinator-resident and the worker-shipped injectors."""
    sites = set()
    for runs in record["policies"].values():
        for run in runs:
            for site, _tag, _attempt, _kind in run["coordinator_fired"]:
                sites.add(site)
            for site, _tag, _attempt, _kind in run["worker_fired"]:
                sites.add(site)
            sites.update(run.get("absorbed_sites") or ())
    return sites
