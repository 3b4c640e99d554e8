"""Fleet execution: stage-wave scheduling across N worker processes
with durable spooled stage outputs.

The analog of the reference's fault-tolerant query scheduler
(MAIN/execution/scheduler/faulttolerant/EventDrivenFaultTolerantQueryScheduler.java:200):
the coordinator plans SQL locally, cuts the plan into stages
(plan.fragment), and schedules them through one event loop. Stage
admission granularity is the ``stage_admission`` session property:
``PIPELINED`` (default) delegates per-task readiness to the
partition-granular EventDrivenScheduler (trino_tpu/scheduler.py) —
a consumer task starts the moment its input partition is committed
across all producer tasks, pinned to the observed attempts;
``BARRIER`` preserves the legacy batch-synchronous waves. Either way
every task's output is committed to the spooled exchange (exec.spool)
before anything reads it, so:

- inter-stage data crosses worker processes through durable
  hash-partitioned files (the DCN/FTE exchange tier, SURVEY.md §5.8) —
  never through worker memory;
- a task failure (or a kill -9'd worker) retries JUST that task on a
  surviving worker, reading identical spooled inputs — the query
  completes with oracle-exact results (TASK retry policy,
  MAIN/execution/QueryManagerConfig.java retry-policy);
- workers that vanish are excluded from further placement (the
  HeartbeatFailureDetector analog collapsed into RPC-failure
  detection, MAIN/failuredetector/HeartbeatFailureDetector.java:76).

Tasks per stage: a stage with aligned (hash) inputs runs one task per
partition; a stage scanning a table splits it into row ranges (one
task per split, SPI/connector/ConnectorSplit.java analog); everything
else runs as one task.
"""

from __future__ import annotations

import json
import os
import random
import re
import statistics
import time
import urllib.error
import urllib.request
import uuid
from collections import deque
from dataclasses import dataclass

from trino_tpu import (
    diagnostics,
    fault,
    journal as journal_mod,
    membership as membership_mod,
    memory,
    profiler,
    telemetry,
    telemetry_analysis,
    tracker,
)
from trino_tpu import session_properties as sp
from trino_tpu.connectors.base import ColumnDomain, Split
from trino_tpu.engine import (
    QueryResult,
    QueryRunner,
    _has_order,
    _stage_stats_line,
)
from trino_tpu.exec import spool
from trino_tpu.exec.local import QueryCancelled
from trino_tpu.metadata import Metadata, Session
from trino_tpu.plan import nodes as P
from trino_tpu.plan import validate
from trino_tpu.plan.fragment import Stage, fragment_plan, salt_stage
from trino_tpu.plan.serde import plan_to_json
from trino_tpu.scheduler import EventDrivenScheduler
from trino_tpu.sql import ast
from trino_tpu.sql.parser import parse_statement
from trino_tpu.tracker import (
    QueryDeadlineExceededError,
    QueryRetriesExhaustedError,
)

__all__ = ["FleetRunner", "FleetWorker"]


#: worker-reported exception names that retrying cannot fix: the plan
#: itself is wrong (semantic/analyzer/unsupported-feature errors are
#: deterministic — every attempt would fail identically, so the query
#: fails NOW instead of burning max_attempts on copies of the same
#: error). Everything else — worker death, InjectedTaskFailure,
#: SpoolCorruptionError, I/O flakes — is retryable (the reference's
#: ErrorType.USER_ERROR vs INTERNAL_ERROR retry split,
#: MAIN/spi/ErrorType.java).
_NONRETRYABLE_ERRORS = frozenset({
    "AnalysisError", "SqlSyntaxError", "NotImplementedError",
    "TypeError", "ValueError", "KeyError", "AttributeError",
    "AssertionError", "ZeroDivisionError", "IndexError",
    # an allocation that breached query_max_memory_per_node can never
    # fit on a retry of the same task either — fail fast instead of
    # hedging/retrying (the reference's EXCEEDED_LOCAL_MEMORY_LIMIT is
    # likewise not retryable under task-level FTE)
    "ExceededMemoryLimitError",
    # more attempts cannot manufacture more wall-clock: deadline and
    # cancellation failures are terminal at BOTH FTE tiers (the
    # reference's EXCEEDED_TIME_LIMIT / USER_CANCELED error types)
    "QueryDeadlineExceededError",
    "QueryCancelled",
})

#: worker-serialized SpoolCorruptionError messages carry the producing
#: task's coordinates (exec/spool.py builds them); this maps the
#: consumer's failure back to the upstream output that must be re-made
_CORRUPTION_RE = re.compile(
    r"SpoolCorruptionError.*?stage=(\S+) task=(\S+) attempt=(\d+)"
)


def _retryable(error: str) -> bool:
    return error.split(":", 1)[0].strip() not in _NONRETRYABLE_ERRORS


def _query_tier_retryable(e: BaseException) -> bool:
    """Should retry_policy=QUERY re-execute the statement after this
    failure escaped the task tier? Deadlines, cancellation, memory
    caps, and the legacy stage timeout are terminal (re-running cannot
    change them); injected faults model transients (retryable by
    construction); RuntimeErrors are the scheduler's own escalations —
    retryable unless they wrap a non-retryable task error. Everything
    else (semantic/analyzer/planner errors) is deterministic and
    fails fast."""
    if isinstance(
        e,
        (
            QueryDeadlineExceededError, QueryCancelled,
            memory.ExceededMemoryLimitError, TimeoutError,
        ),
    ):
        return False
    if isinstance(e, fault.InjectedFault):
        return True
    if isinstance(e, RuntimeError):
        return "non-retryable" not in str(e)
    return False


def _write_finish_of(stages: list[Stage]) -> dict | None:
    """If the fragmented plan ends in a coordinator-side TableFinish
    (Output -> TableFinish -> RemoteSource), return its commit spec.
    The fleet strips that root stage and performs the commit itself:
    worker connector instances are per-process, so only the
    coordinator's connector sees the authoritative catalog state."""
    root = stages[-1].root
    if not isinstance(root, P.Output):
        return None
    fin = root.sources[0]
    if not isinstance(fin, P.TableFinish):
        return None
    return {"handle": fin.handle, "names": list(root.names)}


class _FleetParallelism:
    """Duck-typed mesh stand-in for plan_stmt: the fleet's TOTAL
    parallelism (spool partitions x per-worker device count, the
    latter discovered from each worker's /v1/info). Distribution
    planning sees the real shard count a key space divides into —
    capacity estimates and broadcast thresholds match what actually
    runs (VERDICT r4: the fixed _FakeMesh ignored worker meshes)."""

    #: fleet exchanges serialize pages through the host spool serde,
    #: which carries ARRAY/MAP columns — unlike device-mesh sharding
    host_exchange = True

    def __init__(self, n: int):
        self.devices = _N(n)


class _N:
    def __init__(self, n: int):
        self.size = n


@dataclass
class FleetWorker:
    uri: str
    alive: bool = True
    #: DRAINING per /v1/info or a 409 task rejection: no new tasks,
    #: in-flight ones still polled to completion
    draining: bool = False
    #: consecutive poll timeouts (hung-worker detection: a SIGSTOPped
    #: process holds connections open without answering — N short
    #: timeouts in a row declare it dead, vs one long RPC timeout)
    fails: int = 0


@dataclass
class _TaskSpec:
    task_id: str
    plan_json: dict
    partition: int | None
    fail_first: bool = False
    #: build-side output symbols whose min/max the worker reports on
    #: FINISHED (coordinator-level dynamic filtering: the merged range
    #: becomes a storage domain on held probe-side scan stages)
    report_ranges: list[str] | None = None
    #: salted sub-task index for a hot input partition (None = plain
    #: aligned task). A hot partition of a SALTED stage runs
    #: ``salt_plan["factor"]`` tasks; each reads every 1-in-K row of
    #: the fanout source and the WHOLE partition of replicate sources
    salt: int | None = None


class FleetRunner:
    """QueryRunner-compatible facade scheduling stage waves over a
    fleet of worker processes."""

    def __init__(
        self,
        worker_uris: list[str],
        metadata: Metadata,
        session: Session,
        spool_root: str,
        n_partitions: int = 4,
        poll_s: float = 0.02,
        timeout_s: float = 600.0,
        max_attempts: int = 3,
        rpc_timeout_s: float = 15.0,
        max_poll_fails: int = 4,
        stage_hook=None,
        keep_spool: bool = False,
        readmit_initial_s: float = 0.5,
        readmit_max_s: float = 8.0,
        readmit_probe_timeout_s: float = 1.0,
        dispatcher=None,
        workers: list[FleetWorker] | None = None,
        worker_devices: dict[str, int] | None = None,
        cluster_memory=None,
        serving=None,
        resource_group: str = "global",
        group_weight: int = 1,
        membership=None,
        min_workers: int = 0,
        min_workers_wait_s: float = 8.0,
        journal=None,
    ):
        #: serving mode: a shared trino_tpu.dispatcher.Dispatcher owns
        #: worker slots, fair-share grants and ALL status polling; this
        #: runner is then one query among many on a shared fleet. When
        #: None (the default), the legacy single-query path runs: this
        #: loop owns the fleet, posts and polls inline — byte-identical
        #: behavior to every prior PR (including call-order-sensitive
        #: ``nth`` chaos schedules, which a free-running reactor breaks)
        self.dispatcher = dispatcher
        self._serving = serving
        self.resource_group = resource_group
        self.group_weight = group_weight
        #: cross-query memory kill: another query's dispatch loop (via
        #: ServingRunner.enforce_memory) names this query the victim;
        #: our own loop notices and unwinds with the typed error
        self._kill_error: str | None = None
        #: shared FleetWorker objects make liveness/draining state
        #: fleet-global across concurrent queries
        self.workers = (
            workers if workers is not None
            else [FleetWorker(u.rstrip("/")) for u in worker_uris]
        )
        self.metadata = metadata
        self.session = session
        self.spool_root = spool_root
        self.n_partitions = n_partitions
        self.poll_s = poll_s
        self.timeout_s = timeout_s
        #: constructor default; a per-query session override
        #: (retry_max_attempts) applies for that execute() only
        self._default_max_attempts = max_attempts
        self.max_attempts = max_attempts
        #: per-RPC timeout: hung-worker detection latency is
        #: rpc_timeout_s * max_poll_fails (HeartbeatFailureDetector
        #: analog: liveness from RPC health, MAIN/failuredetector/
        #: HeartbeatFailureDetector.java:76). The defaults tolerate
        #: multi-second GIL stalls while a worker traces/compiles a
        #: stage program — a worker slow to ANSWER is not dead; only
        #: max_poll_fails consecutive timeouts (or a refused
        #: connection) declare it so
        self.rpc_timeout_s = rpc_timeout_s
        self.max_poll_fails = max_poll_fails
        #: test hook called after each stage completes (stage_id) —
        #: deterministic point to kill a worker mid-query
        self.stage_hook = stage_hook
        self.keep_spool = keep_spool
        #: task ids to fail on their first attempt (FailureInjector
        #: analog, keyed "stage:task_index")
        self.inject_failures: set[str] = set()
        #: test hook called after each successful task submission
        #: (stage_id, task_id, worker) — deterministic point to crash
        #: the worker a task just landed on
        self.post_hook = None
        #: dead-worker re-admission (the full HeartbeatFailureDetector
        #: loop, MAIN/failuredetector/HeartbeatFailureDetector.java:76:
        #: eviction AND recovery): evicted workers are probed via
        #: /v1/info on an exponential backoff schedule and restored to
        #: the placement pool when they answer — a bounced worker
        #: process rejoins mid-query instead of staying banned forever
        self.readmit_initial_s = readmit_initial_s
        self.readmit_max_s = readmit_max_s
        self.readmit_probe_timeout_s = readmit_probe_timeout_s
        self._probe_at: dict[str, float] = {}
        self._probe_delay: dict[str, float] = {}
        #: per-query fault-tolerance counters, copied onto QueryResult
        self.stats: dict[str, int] = {}
        #: backoff delays (seconds) actually scheduled by the last
        #: execute() — observability for tests asserting jitter bounds
        self.retry_delays: list[float] = []
        #: error strings of every retried task failure from the last
        #: execute() — the chaos suite asserts per-site injections
        #: actually reached the worker tier from these
        self.failure_log: list[str] = []
        #: coordinator-level dynamic-filter applications from the last
        #: execute(): one entry per probe-side scan stage whose domains
        #: were narrowed by merged build-task ranges (tests/EXPLAIN)
        self.df_scan_log: list[dict] = []
        #: task_id -> (Stage, _TaskSpec) from the last _run_dag, kept
        #: for coordinator-side corruption recovery on the root read
        self._last_specs: dict[str, tuple[Stage, _TaskSpec]] = {}
        #: the admission scheduler of the current/last _run_dag
        #: (exposed for tests/bench: admission waits, overlap seconds)
        self._scheduler: EventDrivenScheduler | None = None
        #: coordinator-side memory governor: aggregates the per-worker
        #: pool snapshots shipped on task-status responses, enforces
        #: query_max_memory, and kills the largest query on breach
        #: (shared across queries in serving mode, so the kill policy
        #: sees every live query's reservations)
        self.cluster_memory = (
            cluster_memory if cluster_memory is not None
            else memory.ClusterMemoryManager()
        )
        #: current query id (stamped on stage-task requests so worker
        #: pools attribute reservations to the right query)
        self._query_id: str | None = None
        #: serving-mode dispatch registration of the attempt in flight
        self._dispatch_handle = None
        #: externally-assigned id (the coordinator's) under which this
        #: statement publishes live QueryInfo; attempt-local
        #: ``_query_id`` values keep naming spool epochs
        self._public_query_id: str | None = None
        #: per-attempt telemetry state (set by _execute_attempt)
        self._tracer = None
        self._stage_spans: dict[str, telemetry.Span] = {}
        self._task_stats: list[dict] = []
        self._retries_by_stage: dict[str, int] = {}
        self._plan_ms = 0.0
        #: per-worker wall-clock offsets, learned from the now_ms
        #: stamp on every task-status response; persistent across
        #: queries (the offset is a property of the worker process)
        self._clock_skew = telemetry_analysis.ClockSkewEstimator()
        #: trace of the last execution attempt, success or failure
        #: (post-mortem bundles need the tree of a FAILED attempt)
        self._last_trace = None
        self._last_stages: list[Stage] | None = None
        #: absolute monotonic deadline / cooperative cancel for the
        #: statement in flight (set per execute())
        self._exec_deadline: float | None = None
        self._cancel_event = None
        self._cluster_cap = 0
        self._planner = QueryRunner(metadata, session)
        #: semantic result cache override (cache.SemanticResultCache):
        #: the serving layer shares ONE instance across its per-query
        #: runners; None = the embedded planner's per-runner cache
        self.result_cache = None
        #: per-worker device counts from /v1/info (1 when unreachable
        #: or mesh-less); the planner's shard count is the fleet total.
        #: ServingRunner passes the probed map in so per-statement
        #: runner construction costs no RPCs.
        self.worker_devices = (
            dict(worker_devices) if worker_devices is not None
            else {
                w.uri: self._probe_devices(w.uri) for w in self.workers
            }
        )
        per_worker = max(self.worker_devices.values(), default=1)
        self._planner.mesh = _FleetParallelism(
            max(n_partitions, 2) * per_worker
        )
        #: live-membership registry (elastic fleet). In serving mode
        #: the ServingRunner owns the wiring (attach_membership); a
        #: legacy single-query runner wires itself: its scheduler pins
        #: gate drain deregistration, leaves mark workers
        #: unschedulable-but-alive, and _sync_membership folds joins
        #: into the placement pool every dispatch iteration
        self.membership = membership
        #: ClusterSizeMonitor gate: execute() parks until this many
        #: schedulable members exist, then fails typed
        #: (INSUFFICIENT_RESOURCES) after min_workers_wait_s
        self.min_workers = int(min_workers)
        self.min_workers_wait_s = float(min_workers_wait_s)
        if membership is not None and serving is None:
            membership.residency_providers.append(self._membership_pins)
            membership.on_leave.append(self._membership_leave)
        #: durable query journal (journal.QueryJournal): when set,
        #: execute() WALs begin/epoch/stage/dispatch/commit/done
        #: records so a restarted coordinator can resume this query
        self.journal = journal
        #: journal.JournalEntry being resumed by the current execute()
        #: (set by resume(); None = normal fresh execution)
        self._resume_entry = None
        #: per-attempt resume books derived from the entry (spec
        #: fingerprints, journaled dispatches, committed attempts);
        #: None once the first resumed attempt has consumed them —
        #: a QUERY-tier retry after a failed resume runs fresh
        self._resume_state = None
        #: recovery counters of the last execute() (kept out of
        #: self.stats because QueryResult's fields are closed)
        self.resume_stats: dict[str, int] = {}
        #: sliding-window cluster-wide retry budget (retry_budget
        #: session property); rebuilt per statement
        self._retry_budget = journal_mod.RetryBudget(0)
        #: sha256 of the current statement's fragmented plan wire form
        #: (journaled per epoch; resume re-derives and must match)
        self._plan_digest: str | None = None
        # performance sentry observes every statement this runner
        # completes (no-op when TRINO_TPU_SENTRY=0)
        from trino_tpu import sentry as _sentry

        _sentry.ensure_installed(self.metadata)

    def request_kill(self, error: str) -> bool:
        """Cross-query memory kill (serving mode): mark this query as
        the cluster memory manager's victim. Its dispatch loop raises
        ExceededMemoryLimitError at the next iteration. Returns False
        when a kill is already pending (kills are counted once)."""
        if self._kill_error is not None:
            return False
        self._kill_error = error
        return True

    @staticmethod
    def _probe_devices(uri: str) -> int:
        try:
            with urllib.request.urlopen(f"{uri}/v1/info", timeout=5) as r:
                return max(int(json.loads(r.read()).get("devices", 1)), 1)
        except Exception:
            return 1

    # ---- query entry -----------------------------------------------------

    # ---- live membership (elastic fleet) ------------------------------

    def _membership_registry(self):
        """The registry governing this runner's fleet: its own in
        legacy mode, the ServingRunner's in serving mode."""
        if self.membership is not None:
            return self.membership
        return getattr(self._serving, "membership", None)

    def _membership_pins(self):
        """Residency provider for the drain gate: worker URIs whose
        exchange buffers some not-yet-finished consumer of THIS query
        may still fetch. Empty between statements — a drained worker
        must not wait on a runner with nothing in flight."""
        sched = self._scheduler
        if sched is None or self._public_query_id is None:
            return set()
        return sched.pinned_workers()

    def _membership_leave(self, member, reason: str) -> None:
        """A member left the schedulable set (drain announce or damped
        heartbeat loss): mark it unschedulable-but-alive. Liveness is
        NOT touched — FTE poll eviction stays the only crash path."""
        uri = member.uri.rstrip("/")
        for w in self.workers:
            if w.uri == uri:
                w.draining = True

    def _sync_membership(self) -> None:
        """Fold the live membership into the placement pool (legacy
        dispatch loop, once per iteration): a worker that announced
        after this query was dispatched joins self.workers and is
        eligible for every not-yet-posted task; a previously-evicted
        member that re-announced becomes postable again."""
        reg = self.membership
        if reg is None:
            return
        known = {w.uri: w for w in self.workers}
        for m in reg.schedulable():
            w = known.get(m.uri)
            if w is None:
                w = FleetWorker(m.uri)
                if m.uri not in self.worker_devices:
                    self.worker_devices[m.uri] = self._probe_devices(
                        m.uri
                    )
                self.workers.append(w)
                self.stats["workers_joined"] = (
                    self.stats.get("workers_joined", 0) + 1
                )
            elif w.alive and w.draining:
                w.draining = False

    def execute(
        self, sql: str, cancel_event=None, query_id: str | None = None,
    ) -> QueryResult:
        stmt = parse_statement(sql)
        if isinstance(stmt, ast.Explain) and not stmt.analyze:
            # plan rendering only; the embedded planner shares the
            # fleet's parallelism stand-in, so the printed tree matches
            # what would run distributed
            return self._planner.execute(sql)
        explain_analyze = isinstance(stmt, ast.Explain)
        if explain_analyze:
            stmt = stmt.statement
        # one public id per statement: query-level retries re-execute
        # under fresh attempt/spool ids but publish live QueryInfo
        # under this one (the id the coordinator hands out, when any)
        public_qid = query_id or uuid.uuid4().hex[:12]
        self._public_query_id = public_qid
        tracker.QUERY_INFO.begin(
            public_qid, sql=sql, user=self.session.user,
            resource_group=(
                self.resource_group if self.dispatcher is not None
                else None
            ),
        )
        if self.journal is not None and self._resume_entry is None:
            # WAL the statement before any work: a crash from here on
            # leaves enough on disk for a restarted coordinator to
            # replay (or to fail the query typed, for non-FTE policies)
            self.journal.begin(
                public_qid, sql=sql, user=self.session.user,
                session_properties=self.session.properties,
                retry_policy=str(
                    sp.get(self.session, "retry_policy")
                ).upper(),
            )
        t0 = time.perf_counter()
        error = None
        result = None
        # a failure before any attempt ran (validation, planning) must
        # not pick up the previous statement's state in its bundle
        self._last_trace = None
        self._last_stages = None
        self._last_plan = None
        self._plan_digest = None
        self._write_finish = None
        self._last_commit_stats = None
        self._task_stats = []
        metrics_before = telemetry.REGISTRY.snapshot()
        try:
            reg = self._membership_registry()
            if reg is not None and self.min_workers > 0:
                # ClusterSizeMonitor gate: park while the fleet forms
                # (or re-forms mid-scale-down), reject typed when the
                # wait is hopeless — never dispatch into a cluster
                # that cannot place the DAG
                membership_mod.ClusterSizeMonitor(
                    reg, self.min_workers
                ).wait_for_minimum(self.min_workers_wait_s)
            result = self._execute_stmt(stmt, cancel_event)
            if explain_analyze:
                result = self._render_fleet_analyze(result)
            return result
        except Exception as e:
            error = f"{type(e).__name__}: {e}"
            raise
        finally:
            state = "FAILED" if error else "FINISHED"
            bundle = None
            if error:
                # post-mortem bundle: everything a "why did this die"
                # needs, assembled while the attempt's state is still
                # on the runner (best-effort — never masks the error)
                bundle = diagnostics.build_bundle(
                    public_qid,
                    error=error,
                    sql=sql,
                    state=state,
                    plan=(
                        P.plan_tree_str(self._last_plan)
                        if getattr(self, "_last_plan", None) is not None
                        else None
                    ),
                    stages=self._stages_summary(),
                    trace=self._last_trace,
                    task_stats=list(self._task_stats),
                    residency=dict(
                        getattr(self._scheduler, "_locations", {}) or {}
                    ) if self._scheduler is not None else None,
                    fault_records=list(self.failure_log),
                    metrics_before=metrics_before,
                    metrics_after=telemetry.REGISTRY.snapshot(),
                    extra=(
                        {"membership": mreg.snapshot()}
                        if (mreg := self._membership_registry())
                        is not None else None
                    ),
                )
                diagnostics.record_bundle(bundle)
            if self.journal is not None:
                # terminal WAL record: the restarted coordinator
                # rehydrates tracker rows (and, on failure, the
                # post-mortem bundle) from this. Best-effort — a
                # journal-write fault here must not mask the query's
                # own outcome
                try:
                    self.journal.finish(
                        public_qid, state=state,
                        rows=len(result.rows) if result else 0,
                        error=error,
                        elapsed_ms=(time.perf_counter() - t0) * 1e3,
                        diagnostics=bundle,
                    )
                except Exception:
                    pass
            tracker.QUERY_INFO.finish(
                public_qid,
                state=state,
                rows=len(result.rows) if result else 0,
                error=error,
                peak_memory_bytes=(
                    result.peak_memory_bytes if result else 0
                ),
            )
            self._maybe_log_slow_query(
                sql, (time.perf_counter() - t0) * 1e3, result, public_qid
            )
            if result is not None:
                # post-hoc profile == the live tree, sealed
                result._query_info = tracker.QUERY_INFO.get(public_qid)
            self._public_query_id = None
            telemetry.QUERIES_TOTAL.inc(state=state)
            listeners = getattr(self.metadata, "event_listeners", ())
            if listeners:
                from trino_tpu.events import (
                    QueryCompletedEvent,
                    fire_query_completed,
                )

                elapsed_ms = (time.perf_counter() - t0) * 1e3
                from trino_tpu import history as history_mod

                _skew = 0.0
                _compiles = 0
                _tier = None
                if result is not None:
                    for _st in result.stage_stats or []:
                        _ps = _st.get("partition_skew") or {}
                        _skew = max(
                            _skew,
                            float(_ps.get("max_mean_ratio", 0.0) or 0.0),
                        )
                    if result.trace is not None:
                        _compiles = sum(
                            1 for _s in result.trace.spans()
                            if _s.kind == "compile"
                        )
                    if result.cache_stats and (
                        result.cache_stats.get("result") or {}
                    ).get("hit"):
                        _tier = "result"
                # the PUBLIC id: it is what the tracker, journal, and
                # GET /v1/query/{id}/... speak — an anomaly bundle
                # keyed by the internal attempt id would be
                # unreachable from the client's side
                fire_query_completed(listeners, QueryCompletedEvent(
                    query_id=public_qid,
                    user=self.session.user,
                    sql=sql,
                    state=state,
                    elapsed_ms=elapsed_ms,
                    rows=len(result.rows) if result else 0,
                    error=error,
                    peak_memory_bytes=(
                        result.peak_memory_bytes if result else 0
                    ),
                    peak_memory_per_node=tuple(sorted(
                        result.peak_memory_per_node.items()
                    )) if result else (),
                    planning_ms=getattr(self, "_plan_ms", 0.0),
                    execution_ms=(
                        result.execution_ms if result else elapsed_ms
                    ),
                    cpu_ms=(
                        result.execution_ms if result else elapsed_ms
                    ),
                    query_retries=(
                        result.query_retries if result else 0
                    ),
                    tasks_retried=self.stats.get("tasks_retried", 0),
                    tasks_speculated=self.stats.get(
                        "tasks_speculated", 0
                    ),
                    speculation_wins=self.stats.get(
                        "speculation_wins", 0
                    ),
                    workers_readmitted=self.stats.get(
                        "workers_readmitted", 0
                    ),
                    plan_digest=self._plan_digest,
                    session_fingerprint=(
                        history_mod.session_fingerprint(self.session)
                    ),
                    cache_hit_tier=_tier,
                    compiles=_compiles,
                    exchange_skew=_skew,
                    time_breakdown=(
                        result.time_breakdown if result else None
                    ),
                    plan_text=(
                        P.plan_tree_str(self._last_plan)
                        if getattr(self, "_last_plan", None) is not None
                        else None
                    ),
                    trace=result.trace if result else self._last_trace,
                    task_stats=tuple(
                        dict(ts) for ts in (self._task_stats or [])
                    ),
                ))

    def _stages_summary(self) -> list[dict] | None:
        """Lightweight fragmented-DAG description for post-mortem
        bundles (stage ids, output partitioning, input edges)."""
        stages = getattr(self, "_last_stages", None)
        if not stages:
            return None
        return [
            {
                "stage_id": s.stage_id,
                "partitioning": s.partitioning,
                "hash_symbols": list(s.hash_symbols),
                "inputs": [
                    {
                        "source_id": i.source_id,
                        "stage_id": i.stage_id,
                        "mode": i.mode,
                    }
                    for i in s.inputs
                ],
            }
            for s in stages
        ]

    def _maybe_log_slow_query(
        self, sql: str, elapsed_ms: float, result, query_id: str,
    ) -> None:
        from trino_tpu.events import maybe_log_slow_query

        flat = [
            row
            for ts in (result.task_stats if result else [])
            for row in ts.get("operator_stats") or []
        ]
        maybe_log_slow_query(
            getattr(self.metadata, "event_listeners", ()),
            self.session, query_id, sql, elapsed_ms, flat,
            time_breakdown=(
                result.time_breakdown if result is not None else None
            ),
        )

    def _render_fleet_analyze(self, res: QueryResult) -> QueryResult:
        """EXPLAIN ANALYZE rendering for distributed runs.

        One line per stage from the same ``stage_stats`` dicts that
        back ``system.runtime.tasks``, so the three views always agree.
        """
        from trino_tpu.engine import _fmt_bytes

        stats = res.stage_stats
        total = {
            "stage_id": "query",
            "tasks": sum(st["tasks"] for st in stats),
            # cumulative operator input across stages (intermediate
            # rows count once per stage boundary, as in the reference's
            # cumulative query stats)
            "rows_in": sum(st["rows_in"] for st in stats),
            "rows_out": len(res.rows),
            "bytes_out": stats[-1]["bytes_out"] if stats else 0,
            "elapsed_ms": res.execution_ms,
            "retries": sum(st.get("retries", 0) for st in stats),
            "peak_memory_bytes": res.peak_memory_bytes,
            "admission_wait_ms": sum(
                st.get("admission_wait_ms", 0.0) for st in stats
            ),
        }
        lines = [_stage_stats_line("Query", total)]
        if res.peak_memory_per_node:
            per_node = ", ".join(
                f"{node}: {_fmt_bytes(b)}"
                for node, b in sorted(res.peak_memory_per_node.items())
            )
            lines.append(
                f"Peak memory: {_fmt_bytes(res.peak_memory_bytes)} "
                f"({per_node})"
            )
        if res.cache_stats is not None:
            from trino_tpu import cache as cache_mod

            cs = cache_mod.CacheStats(
                result_hit=res.cache_stats["result"]["hit"],
                result_bytes=res.cache_stats["result"]["bytes"],
                device_hits=res.cache_stats["device"]["hits"],
                device_misses=res.cache_stats["device"]["misses"],
                device_bytes=res.cache_stats["device"]["bytes"],
            )
            lines.append(cs.explain_line())
        cw = getattr(self, "_last_commit_stats", None)
        if cw is not None:
            lines.append(
                f"TableWriter: {cw['rows']} rows, {cw['files']} files, "
                f"{_fmt_bytes(cw['bytes'])} "
                f"(commit {cw['commit_seconds'] * 1000.0:.1f} ms)"
            )
        ops_by_stage: dict[str, dict] = {}
        for ts in res.task_stats:
            if ts.get("state") != "FINISHED":
                continue
            agg = ops_by_stage.setdefault(ts["stage_id"], {})
            for row in ts.get("operator_stats") or []:
                o = agg.setdefault(row.get("name", "?"), {
                    "self_ms": 0.0, "rows_out": 0, "flops": 0.0,
                    "bytes_accessed": 0.0,
                })
                o["self_ms"] += float(row.get("self_ms", 0.0) or 0)
                o["rows_out"] += int(row.get("rows_out") or 0)
                o["flops"] += float(row.get("flops", 0.0) or 0)
                o["bytes_accessed"] += float(
                    row.get("bytes_accessed", 0.0) or 0
                )
        for st in stats:
            lines.append(_stage_stats_line(f"Stage {st['stage_id']}", st))
            skew = st.get("partition_skew") or {}
            if int(skew.get("partitions", 0) or 0) > 1:
                lines.append(
                    f"  exchange partitions: {skew['partitions']}, "
                    f"max/mean {skew['max_mean_ratio']:.2f}, "
                    f"cv {skew['cv']:.2f} "
                    f"(hottest {int(skew['max'])} rows)"
                )
            salted = st.get("salted")
            if salted:
                noun = (
                    "partition" if len(salted["hot"]) == 1
                    else "partitions"
                )
                hot = ", ".join(str(p) for p in salted["hot"])
                lines.append(
                    f"  exchange input {salted['source']} salted "
                    f"×{salted['factor']}, hot {noun} {hot}"
                )
            if st.get("adaptive_repartitions"):
                lines.append(
                    f"  partitions grown {self.n_partitions}"
                    f"→{st['out_partitions']} (adaptive)"
                )
            for name, o in sorted(
                ops_by_stage.get(st["stage_id"], {}).items(),
                key=lambda kv: kv[1]["self_ms"], reverse=True,
            ):
                line = (
                    f"  {name}: {o['self_ms']:.1f} ms self, "
                    f"out: {o['rows_out']} rows"
                )
                roof = profiler.roofline(
                    o["flops"], o["bytes_accessed"], o["self_ms"]
                )
                if roof.get("achieved_gflops") is not None:
                    line += (
                        f", {roof['achieved_gflops']:.2f} GFLOP/s"
                    )
                    util = roof.get("roofline_utilization")
                    if util is not None:
                        line += f" ({util * 100:.1f}% of roofline)"
                lines.append(line)
        lines.extend(
            telemetry_analysis.format_breakdown(res.time_breakdown)
        )
        # sentry baseline footer — judged against history that does
        # NOT yet include this run (completion fires in execute()'s
        # finally, after this render)
        from trino_tpu import history as history_mod
        from trino_tpu import sentry as sentry_mod

        _bf = sentry_mod.baseline_footer(
            self._plan_digest,
            history_mod.session_fingerprint(self.session),
            (res.execution_ms or 0.0) + (res.planning_ms or 0.0),
            res.time_breakdown,
        )
        if _bf:
            lines.append(_bf)
        plan = getattr(self, "_last_plan", None)
        if plan is not None:
            lines.extend(P.plan_tree_str(plan).splitlines())
        out = QueryResult(["Query Plan"], [(line,) for line in lines])
        out.time_breakdown = res.time_breakdown
        out.stage_stats = res.stage_stats
        out.task_stats = res.task_stats
        out.trace = res.trace
        out.planning_ms = res.planning_ms
        out.execution_ms = res.execution_ms
        out.peak_memory_bytes = res.peak_memory_bytes
        out.peak_memory_per_node = res.peak_memory_per_node
        out.query_retries = res.query_retries
        out.salted_edges = res.salted_edges
        out.adaptive_repartitions = res.adaptive_repartitions
        return out

    def _result_cache_probe(self, plan):
        """``(cache, digest, tokens)`` for a result-cacheable plan, or
        None. Delegates the cacheability decision to the embedded
        planner (same session + metadata); the cache instance is the
        serving layer's shared one when set, else the planner's own."""
        rcache, digest, tokens = self._planner._result_cache_probe(plan)
        if rcache is None:
            return None
        # explicit None check: an EMPTY SemanticResultCache is falsy
        # (__len__), and the serving layer's shared instance starts
        # empty — `or` would silently strand every put on the
        # per-query planner cache that dies with this runner
        shared = self.result_cache
        return (shared if shared is not None else rcache, digest, tokens)

    def _cached_result(self, plan, hit) -> QueryResult:
        """Synthesize the QueryResult for a semantic-cache hit: zero
        tasks dispatched, zero retries — the rows are byte-identical to
        the execution that populated the entry."""
        from trino_tpu import cache as cache_mod

        cs = cache_mod.CacheStats()
        cs.result_hit = True
        cs.result_bytes = hit.nbytes
        res = QueryResult(
            names=hit.names, rows=hit.rows, ordered=hit.ordered,
            plan=plan, planning_ms=self._plan_ms,
        )
        res.cache_stats = cs.as_dict()
        return res

    def resume(self, entry) -> QueryResult:
        """Re-execute a journaled RUNNING query under its old public
        id and spool epoch, inheriting committed task attempts and
        adopting still-running ones. The journaled session snapshot
        is restored for the duration (the query runs under ITS
        properties, not whatever the restarted coordinator defaults
        to), with ``plan_validation=FULL`` forced — a replayed plan is
        exactly the case full validation exists for."""
        if not entry.resumable:
            raise journal_mod.CoordinatorRestartedError(
                f"query {entry.query_id} is not resumable after a "
                f"coordinator restart (retry_policy="
                f"{(entry.begin or {}).get('retry_policy', 'NONE')}, "
                f"terminal={entry.done is not None}); resubmit the "
                f"statement"
            )
        saved = dict(self.session.properties)
        self.session.properties.clear()
        self.session.properties.update(entry.begin.get("session") or {})
        self.session.properties["plan_validation"] = "FULL"
        self._resume_entry = entry
        try:
            return self.execute(entry.sql, query_id=entry.query_id)
        finally:
            self._resume_entry = None
            self.session.properties.clear()
            self.session.properties.update(saved)

    def _execute_stmt(self, stmt, cancel_event=None) -> QueryResult:
        raw = self.session.properties.get("retry_max_attempts")
        self.max_attempts = (
            int(raw) if raw is not None else self._default_max_attempts
        )
        policy = str(sp.get(self.session, "retry_policy")).upper()
        if policy == "NONE":
            # fail fast: one attempt per task, no task-tier hedging
            self.max_attempts = 1
        self.stats = {
            "tasks_retried": 0, "tasks_speculated": 0,
            "speculation_wins": 0, "workers_readmitted": 0,
        }
        self.resume_stats = {
            "tasks_recovered_committed": 0, "tasks_adopted": 0,
            "tasks_redispatched": 0,
        }
        self._resume_state = None
        # cluster-wide retry budget: total task retries per sliding
        # window, across every stage — recovery storms after a
        # coordinator restart burn it down and fail typed instead of
        # melting a small fleet (0 = unlimited, the default)
        self._retry_budget = journal_mod.RetryBudget(
            int(sp.get(self.session, "retry_budget")),
            float(sp.get(self.session, "retry_budget_window_ms"))
            / 1000.0,
        )
        self.retry_delays = []
        self.failure_log = []
        self.df_scan_log = []
        # per-statement (not per-attempt): salted/adaptive re-plans
        # mutate the Stage objects, which are reused across query-level
        # retries — the logs describe the statement's final plan
        self._salt_log = []
        self._adaptive_log = []
        self._stage_estimates = {}
        seed = sp.get(self.session, "retry_backoff_seed")
        self._retry_rng = random.Random(seed or None)
        # inconsistent memory caps fail the statement before any task
        # is scheduled; the cluster cap governs this query's total
        memory.validate_session_limits(self.session)
        self._cluster_cap = sp.parse_data_size(
            sp.get(self.session, "query_max_memory")
        )
        # absolute execution deadline: checked every scheduler-loop
        # iteration (between RPC rounds) — the fleet analog of the
        # local executor's operator-boundary checks
        max_exec_s = sp.parse_duration(
            sp.get(self.session, "query_max_execution_time")
        )
        self._exec_deadline = (
            time.monotonic() + max_exec_s if max_exec_s > 0 else None
        )
        self._cancel_event = cancel_event
        retry_init_ms = float(
            sp.get(self.session, "retry_initial_delay_ms")
        )
        retry_max_ms = float(sp.get(self.session, "retry_max_delay_ms"))
        executions = (
            int(sp.get(self.session, "query_retry_attempts")) + 1
            if policy == "QUERY" else 1
        )
        # QUERY tier: re-execute the whole statement (fresh query id =
        # fresh spool epoch) when a RETRYABLE failure escapes the task
        # tier — spool corruption at the coordinator root read, all
        # workers dead, a transient planner fault. Bounded by
        # query_retry_attempts and the remaining execution-time budget.
        plan = None
        stages = None
        probe = None
        last_exc: BaseException | None = None
        query_retries = 0
        for qa in range(executions):
            if qa:
                if (
                    self._exec_deadline is not None
                    and time.monotonic() >= self._exec_deadline
                ):
                    raise QueryDeadlineExceededError(
                        "Query exceeded maximum execution time limit "
                        "during query-level retry "
                        "[query_max_execution_time]"
                    ) from last_exc
                # jittered backoff between whole-statement attempts,
                # clamped to the remaining execution budget
                cap = min(retry_max_ms, retry_init_ms * (2 ** (qa - 1)))
                delay = self._retry_rng.uniform(0.0, cap) / 1000.0
                if self._exec_deadline is not None:
                    delay = min(
                        delay,
                        max(0.0, self._exec_deadline - time.monotonic()),
                    )
                self.retry_delays.append(delay)
                time.sleep(delay)
                query_retries += 1
                telemetry.QUERY_RETRIES.inc()
            try:
                if plan is None:
                    # planning inside the loop: a transient planner
                    # fault is query-retryable; the successful plan is
                    # reused across attempts (it is deterministic)
                    t_plan = time.perf_counter()
                    plan = self._planner.plan_stmt(stmt)
                    # identity for journal resume AND the sentry
                    # baseline key — computed for every planned
                    # statement (cache hits included: a plan that
                    # usually hits needs a baseline to miss against)
                    self._last_plan = plan
                    try:
                        self._plan_digest = journal_mod.plan_digest(plan)
                    except Exception:
                        self._plan_digest = None
                    # semantic result-cache probe BEFORE fragmentation:
                    # a hit serves byte-identical rows without building
                    # stages or dispatching a single task
                    probe = self._result_cache_probe(plan)
                    if probe is not None:
                        hit = probe[0].get(probe[1], probe[2])
                        if hit is not None:
                            self._plan_ms = (
                                (time.perf_counter() - t_plan) * 1e3
                            )
                            return self._cached_result(plan, hit)
                    stages = fragment_plan(plan)
                    if validate.level(self.session) != "OFF":
                        validate.validate_stages(
                            stages, phase="fragment_plan"
                        )
                    # DML: the TableFinish-rooted output stage never
                    # dispatches to a worker — connector metadata
                    # state lives in THIS process, and exactly-once
                    # wants the single atomic commit to happen after
                    # the coordinator gathers the winning fragments
                    self._write_finish = _write_finish_of(stages)
                    if self._write_finish is not None:
                        stages = stages[:-1]
                        self._scale_writer_stages(stages)
                    self._plan_ms = (
                        (time.perf_counter() - t_plan) * 1e3
                    )
                    self._last_stages = stages
                    ent = self._resume_entry
                    if ent is not None:
                        jd = (ent.epoch or {}).get("plan_digest")
                        if jd != self._plan_digest:
                            # catalog/planner drift since the crash:
                            # the journaled spool epoch describes
                            # different work — never half-trust it.
                            # Fall back to a fresh execution.
                            self.failure_log.append(
                                f"resume: plan digest mismatch "
                                f"(journaled {jd}, replanned "
                                f"{self._plan_digest}); running fresh"
                            )
                            self._resume_entry = None
                    if float(sp.get(
                        self.session,
                        "adaptive_partition_growth_factor",
                    )) > 0:
                        # adaptive growth compares committed rows
                        # against these per-stage CBO estimates
                        self._stage_estimates = (
                            self._estimate_stage_rows(stages)
                        )
                result = self._execute_attempt(plan, stages, query_retries)
                if probe is not None:
                    from trino_tpu import cache as cache_mod

                    probe[0].put(
                        probe[1], result.names, result.rows,
                        result.ordered, probe[2],
                    )
                    cs = cache_mod.CacheStats()
                    cs.result_hit = False
                    result.cache_stats = cs.as_dict()
                return result
            except Exception as e:
                # the failed attempt's spool epoch is its write token:
                # un-stage anything its writers left behind before the
                # retry (or the caller) re-enters under a fresh epoch
                self._abort_write_epoch()
                if policy != "QUERY" or not _query_tier_retryable(e):
                    raise
                last_exc = e
        raise QueryRetriesExhaustedError(
            f"query failed after {executions} executions "
            f"(retry_policy=QUERY, query_retry_attempts="
            f"{executions - 1}); last failure: "
            f"{type(last_exc).__name__}: {last_exc}"
        ) from last_exc

    def _execute_attempt(
        self, plan: P.PlanNode, stages: list[Stage], query_retries: int
    ) -> QueryResult:
        """One whole-statement execution under its own spool epoch."""
        ent = self._resume_entry
        if ent is not None and query_retries == 0:
            # resume: re-enter the journaled spool epoch — its
            # committed `.done` markers are the work we must not redo.
            # A QUERY-tier retry after a failed resume (query_retries
            # > 0) runs a fresh epoch like any other retry.
            query_id = ent.epoch["epoch"]
            self._resume_state = {
                "fps": ent.stage_fingerprints(),
                "dispatches": ent.dispatches(),
                "commits": ent.commits(),
            }
        else:
            query_id = uuid.uuid4().hex[:12]
            self._resume_state = None
        self._query_id = query_id
        if self.journal is not None and self._resume_state is None:
            # WAL the epoch before any dispatch: the epoch record
            # anchors which spool directory a resume may trust
            self.journal.epoch(
                self._public_query_id or query_id, query_id,
                self._plan_digest or "", self.n_partitions,
            )
        # one trace per execution attempt: stage/task/rpc spans hang
        # off this root; worker-side subtrees stitch in via the trace
        # context shipped on /v1/stagetask (self._stage_spans)
        tracer = telemetry.Tracer(query_id)
        self._tracer = tracer
        plan_ms = getattr(self, "_plan_ms", 0.0)
        if plan_ms:
            psp = tracer.start("planning", "planning").finish()
            # planning happened BEFORE this attempt's root opened:
            # backdate the synthetic span so the timeline is truthful
            # and the wall-clock decomposition (which clips children to
            # the root interval and accounts planning via its explicit
            # planning_ms input) never double-counts it against the
            # stage spans it would otherwise overlap
            psp.start_ms -= plan_ms
            psp.duration_ms = plan_ms
        self._stage_spans: dict[str, telemetry.Span] = {}
        self._task_stats: list[dict] = []
        self._retries_by_stage: dict[str, int] = {}
        qroot = os.path.join(self.spool_root, query_id)
        os.makedirs(qroot, exist_ok=True)
        tasks_by_stage: dict[str, list[str]] = {}
        t0 = time.perf_counter()
        try:
            self._run_dag(stages, qroot, tasks_by_stage)
            if self._resume_state is not None and self.journal is not None:
                # recovery accounting, durably: how much of the DAG
                # was inherited vs re-dispatched (the chaos harness
                # bounds re-execution off this record)
                try:
                    self.journal.resumed(
                        self._public_query_id or query_id,
                        dict(self.resume_stats),
                    )
                except Exception:
                    pass
            if sp.get(self.session, "check_exchange_coverage"):
                # debug assertion: every stage-to-stage exchange edge
                # conserved rows (consumer reads sum to producer
                # commits) — a mismatch names the dropping edge
                validate.check_edge_coverage(stages, self._task_stats)
            with tracer.span("read-root", "spool"):
                payload = self._read_root(stages, qroot, tasks_by_stage)
            if getattr(self, "_write_finish", None) is not None:
                # the gathered root is the writer fragment stream;
                # commit it HERE, exactly once, tokened by the spool
                # epoch so a journal-resumed replay is idempotent
                payload = self._commit_write(payload, query_id)
            page = spool.host_to_page(payload)
            rows = page.to_pylist()
            res = QueryResult(
                names=list(page.names), rows=rows,
                ordered=_has_order(plan), plan=plan,
                peak_memory_bytes=self.cluster_memory.query_total(
                    query_id
                ),
                peak_memory_per_node=self.cluster_memory.per_worker(
                    query_id
                ),
                query_retries=query_retries,
                **self.stats,
            )
            res.planning_ms = plan_ms
            res.execution_ms = (time.perf_counter() - t0) * 1e3
            res.task_stats = list(self._task_stats)
            res.stage_stats = self._aggregate_stage_stats(stages)
            # counted off the (mutated) stage list, not the event logs:
            # a query-level retry reuses the already-salted/grown plan
            # without re-detecting, and the counts must still report it
            res.salted_edges = sum(
                1 for s in stages if getattr(s, "salt_plan", None)
            )
            res.adaptive_repartitions = sum(
                1 for s in stages
                if getattr(s, "out_partitions", 0)
                and s.partitioning == "hash"
            )
            trace = tracer.finish()
            for spn in trace.root.walk():
                if spn._open:
                    spn.finish()
            res.trace = trace
            res.time_breakdown = telemetry_analysis.compute_time_breakdown(
                trace,
                plan_ms + res.execution_ms,
                planning_ms=plan_ms,
                task_stats=res.task_stats,
            )
            return res
        finally:
            # seal the trace even when the attempt died mid-flight —
            # the post-mortem bundle wants the tree as far as it got
            # (Span.finish is idempotent, so the success path's own
            # finish above is unaffected)
            if self._tracer is not None:
                try:
                    tr = self._tracer.finish()
                    for spn in tr.root.walk():
                        if spn._open:
                            spn.finish()
                    self._last_trace = tr
                except Exception:
                    pass
            self._tracer = None
            if (
                self.dispatcher is not None
                and self._dispatch_handle is not None
            ):
                # drop pending slot requests AND sweep any slots still
                # pinned by attempts of this query (abnormal unwind:
                # retries exhausted, deadline, memory kill)
                self.dispatcher.unregister_query(self._dispatch_handle)
                self._dispatch_handle = None
            # release the query's direct-exchange buffers on every
            # live worker: once the query is done (or dead) nothing
            # will fetch them again — this is the "all pinned
            # consumers have fetched" eviction point
            for w in self.workers:
                if not w.alive:
                    continue
                try:
                    r = urllib.request.Request(
                        f"{w.uri}/v1/exchange/{query_id}",
                        method="DELETE",
                    )
                    with urllib.request.urlopen(
                        r, timeout=self.rpc_timeout_s
                    ):
                        pass
                except Exception:
                    pass  # best-effort; LRU pressure reclaims later
            if not self.keep_spool:
                import shutil

                shutil.rmtree(qroot, ignore_errors=True)

    def _aggregate_stage_stats(self, stages: list[Stage]) -> list[dict]:
        """Fold per-task stats (off task-status responses) into the
        per-stage aggregates EXPLAIN ANALYZE and system.runtime.tasks
        render from. ``elapsed_ms``/``peak_memory_bytes`` are per-stage
        maxima over tasks (stage wall-clock ~ slowest task); rows and
        bytes are sums over committed attempts."""
        by_stage: dict[str, dict] = {}

        def entry(sid: str) -> dict:
            return by_stage.setdefault(sid, {
                "stage_id": sid, "tasks": 0, "rows_in": 0,
                "rows_out": 0, "bytes_out": 0, "elapsed_ms": 0.0,
                "retries": 0, "peak_memory_bytes": 0,
                "admission_wait_ms": 0.0,
                "direct_bytes": 0, "spooled_bytes": 0,
                "partition_rows": {}, "partition_bytes": {},
                "adaptive_repartitions": 0,
            })

        #: per-stage committed rows_in per task — the post-salt balance
        #: observable (a salted hot partition's rows spread across its
        #: K sub-tasks, which the producer-side output histogram cannot
        #: see because read-side salting never rewrites spool files)
        rows_in_by_stage: dict[str, list] = {}
        for ts in self._task_stats:
            st = entry(ts["stage_id"])
            if ts.get("state") != "FINISHED":
                continue
            rows_in_by_stage.setdefault(ts["stage_id"], []).append(
                int(ts.get("rows_in", 0) or 0)
            )
            st["tasks"] += 1
            st["rows_in"] += int(ts.get("rows_in", 0) or 0)
            st["rows_out"] += int(ts.get("rows_out", 0) or 0)
            st["bytes_out"] += int(ts.get("bytes_out", 0) or 0)
            st["elapsed_ms"] = max(
                st["elapsed_ms"], float(ts.get("elapsed_ms", 0.0) or 0)
            )
            st["peak_memory_bytes"] = max(
                st["peak_memory_bytes"],
                int(ts.get("peak_memory_bytes", 0) or 0),
            )
            st["admission_wait_ms"] += float(
                ts.get("admission_wait_ms", 0.0) or 0
            )
            st["direct_bytes"] += int(ts.get("direct_bytes", 0) or 0)
            st["spooled_bytes"] += int(ts.get("spooled_bytes", 0) or 0)
            if ts.get("rows_written") is not None:
                # TableWriter stages: committed write volume, summed
                # over winning attempts (system.runtime.tasks +
                # EXPLAIN ANALYZE writer line)
                st["rows_written"] = (
                    st.get("rows_written", 0)
                    + int(ts.get("rows_written", 0) or 0)
                )
                st["bytes_written"] = (
                    st.get("bytes_written", 0)
                    + int(ts.get("bytes_written", 0) or 0)
                )
                st["files_written"] = (
                    st.get("files_written", 0)
                    + int(ts.get("files_written", 0) or 0)
                )
            # per-partition exchange histograms: the stage's output
            # edge, summed over its committed tasks (deliverable (a)
            # of the ROADMAP skew item)
            for field, src in (
                ("partition_rows", ts.get("partition_rows")),
                ("partition_bytes", ts.get("partition_bytes")),
            ):
                for p, v in (src or {}).items():
                    st[field][str(p)] = (
                        st[field].get(str(p), 0) + int(v or 0)
                    )
        for sid, n in self._retries_by_stage.items():
            entry(sid)["retries"] = n
        for s in stages:
            st = by_stage.get(s.stage_id)
            if st is None:
                continue
            if getattr(s, "salt_plan", None):
                st["salted"] = dict(s.salt_plan)
            if getattr(s, "out_partitions", 0):
                st["out_partitions"] = int(s.out_partitions)
                # scaled-writer round_robin stages set out_partitions
                # by PLAN (task_writer_count), not by runtime adaption
                if s.partitioning == "hash":
                    st["adaptive_repartitions"] = 1
        for sid, st in by_stage.items():
            st["partition_skew"] = telemetry_analysis.partition_skew(
                st["partition_rows"]
            )
            st["input_skew"] = telemetry_analysis.partition_skew({
                str(i): v
                for i, v in enumerate(rows_in_by_stage.get(sid) or [])
            })
            # fraction of exchange input bytes a stage's tasks pulled
            # straight from producer memory (vs. the durable spool)
            tot = st["direct_bytes"] + st["spooled_bytes"]
            st["direct_fetch_ratio"] = (
                st["direct_bytes"] / tot if tot else 0.0
            )
        order = [s.stage_id for s in stages]
        return [by_stage[sid] for sid in order if sid in by_stage]

    def _scale_writer_stages(self, stages: list[Stage]) -> None:
        """Round-robin writer fan-out: the stage feeding an
        unpartitioned scaled TableWriter spools into
        ``task_writer_count`` partitions, so the aligned writer stage
        runs that many tasks (``writer_scaling=false`` collapses to
        one). Hash-partitioned writes keep the fleet's default
        fan-out."""
        n = (
            int(sp.get(self.session, "task_writer_count"))
            if bool(sp.get(self.session, "writer_scaling")) else 1
        )
        for s in stages:
            if s.partitioning == "round_robin":
                s.out_partitions = max(n, 1)

    def _commit_write(self, payload: dict, epoch: str) -> dict:
        """Coordinator-side TableFinish: fold the gathered writer
        fragments into one atomic ``finish_write`` (tokened by the
        spool epoch — replays after a crash-recovery resume observe
        the committed result, never a double apply). Returns the
        statement's result payload."""
        import numpy as np

        from trino_tpu import types as T
        from trino_tpu.exec import write as W

        wf = self._write_finish
        handle = wf["handle"]
        frags = W.fragment_rows(payload)
        rows, secs = W.commit_write(
            self._planner.metadata, handle, frags, token=epoch,
        )
        self._planner.executor.invalidate_scan(
            handle["catalog"], handle["schema"], handle["table"]
        )
        summary = W.fragments_summary(frags)
        self._last_commit_stats = {
            "rows": rows,
            "bytes": summary["bytes"],
            "files": summary["files"],
            "commit_seconds": secs,
        }
        return {
            "names": list(wf["names"]),
            "types": [T.BIGINT],
            "cols": [(np.asarray([rows], dtype=np.int64), None)],
        }

    def _abort_write_epoch(self) -> None:
        """Discard the failed attempt's staged write artifacts (QUERY
        retry / terminal failure). Best-effort by SPI contract."""
        wf = getattr(self, "_write_finish", None)
        epoch = getattr(self, "_query_id", None)
        if wf is None or not epoch:
            return
        try:
            self._planner.metadata.connector(
                wf["handle"]["catalog"]
            ).abort_write(wf["handle"], token=epoch)
        except Exception:
            pass

    def _read_root(
        self, stages: list[Stage], qroot: str,
        tasks_by_stage: dict[str, list[str]],
    ) -> dict:
        """Read the root stage's output, recovering from spool
        corruption detected at the COORDINATOR (the window between the
        last task commit and this read): quarantine the corrupt
        attempt, synchronously re-run the producing task on a live
        worker, and read again."""
        root = stages[-1]
        # the chaos injector's spool-read site also fires on this read;
        # its attempt level is the injector's default_attempt, which we
        # bump per retry so times-schedules let a retried read succeed
        inj = fault.active()
        prev_da = inj.default_attempt if inj is not None else 0
        try:
            for read_attempt in range(self.max_attempts):
                if inj is not None:
                    inj.default_attempt = read_attempt
                try:
                    return spool.read_partition(
                        qroot, root.stage_id,
                        tasks_by_stage[root.stage_id], None,
                    )
                except fault.InjectedFault:
                    continue  # transient read fault: retry in place
                except spool.SpoolCorruptionError as e:
                    spool.quarantine_attempt(
                        qroot, e.stage_id, e.task_id, e.attempt
                    )
                    # keep the scheduler's commit books consistent with
                    # the spool (quarantine retracted the markers too)
                    if self._scheduler is not None:
                        self._scheduler.retract(
                            e.stage_id, e.task_id, e.attempt
                        )
                    self._rerun_task(
                        qroot, tasks_by_stage, e.stage_id, e.task_id
                    )
        finally:
            if inj is not None:
                inj.default_attempt = prev_da
        raise RuntimeError(
            f"root stage {root.stage_id}: spool read failure persisted "
            f"across {self.max_attempts} recovery attempts"
        )

    def _rerun_task(
        self, qroot: str, tasks_by_stage: dict[str, list[str]],
        stage_id: str, task_id: str,
    ) -> None:
        """Synchronously re-run one already-committed task whose spool
        output was found corrupt after _run_dag returned."""
        stage, spec = self._last_specs[task_id]
        attempt = spool.next_attempt(qroot, stage_id, task_id)
        last_err = "no live worker accepted the re-run"
        deadline = time.monotonic() + self.timeout_s
        for w in self.workers:
            if not w.alive or w.draining:
                continue
            try:
                self._post_task(
                    w, stage, spec, attempt, qroot, tasks_by_stage,
                    pins=(
                        self._scheduler.pins_for(stage, spec)
                        if self._scheduler is not None else None
                    ),
                )
            except Exception:
                continue
            self._retry_budget.spend()
            self.stats["tasks_retried"] += 1
            telemetry.TASKS_RETRIED.inc()
            wait_sp = (
                self._tracer.start("task_poll_wait")
                if self._tracer is not None else None
            )
            try:
                while time.monotonic() < deadline:
                    try:
                        state = self._poll_task(w, spec.task_id, attempt)
                    except Exception as e:
                        last_err = f"worker died during re-run: {e}"
                        break
                    if state["state"] == "FINISHED":
                        return
                    if state["state"] in ("FAILED", "CANCELED"):
                        last_err = state.get("error", "re-run failed")
                        break
                    time.sleep(self.poll_s)
                else:
                    raise TimeoutError(
                        "corruption-recovery re-run timed out"
                    )
            finally:
                if wait_sp is not None:
                    wait_sp.finish()
        raise RuntimeError(
            f"task {task_id} corruption recovery failed: {last_err}"
        )

    # ---- runtime re-planning: salted repartition + adaptive growth -------

    def _stage_partition_hist(self, sid: str) -> dict:
        """Fold a stage's committed per-partition output histogram
        from FINISHED task stats (deliverable (a) of the ROADMAP skew
        item feeds (b): the same counters stage_stats renders)."""
        hist: dict[str, int] = {}
        for ts in self._task_stats:
            if ts.get("stage_id") != sid or ts.get("state") != "FINISHED":
                continue
            for p, v in (ts.get("partition_rows") or {}).items():
                hist[str(p)] = hist.get(str(p), 0) + int(v or 0)
        return hist

    def _stage_actual_rows(self, sid: str) -> int:
        return sum(
            int(ts.get("rows_out", 0) or 0)
            for ts in self._task_stats
            if ts.get("stage_id") == sid and ts.get("state") == "FINISHED"
        )

    def _maybe_salt_stage(
        self, stage: Stage, stages: list[Stage], by_id: dict,
        threshold: float, factor: int,
    ) -> None:
        """Hot-key mitigation at admission (ROADMAP skew item (b), the
        reference's skewed-join salting under FTE): if one aligned
        input's committed histogram shows max/mean above the threshold,
        re-plan this edge SALTED — the hot partitions fan out across
        ``factor`` sub-tasks slicing the skewed source row-wise, while
        the other aligned inputs replicate to every salt. Results stay
        byte-identical: the fragment must pass fragment_saltable (row
        splits distribute over it) and the mutated stage list re-runs
        plan validation before any task exists."""
        if getattr(stage, "salt_plan", None) is not None or factor < 2:
            return
        aligned = [i for i in stage.inputs if i.mode == "aligned"]
        if not aligned:
            return
        # replicate closure needs hash-aligned co-inputs; a gather or
        # single-partition producer cannot be sliced per-partition
        if any(
            by_id[i.stage_id].partitioning != "hash" for i in aligned
        ):
            return
        from trino_tpu.plan.distribute import fragment_saltable

        ok, _reason = fragment_saltable(stage.root)
        if not ok:
            return
        best = None  # (ratio, input, hist, mean)
        for i in aligned:
            hist = self._stage_partition_hist(i.stage_id)
            # pad to the producer's full fabric: partitions that got
            # ZERO rows never appear in committed histograms, and
            # dropping them inflates the mean — an edge where every row
            # hashes into one of four partitions is maximally skewed,
            # not ratio-1.0
            n_fab = int(
                getattr(by_id[i.stage_id], "out_partitions", 0) or 0
            ) or self.n_partitions
            for p in range(n_fab):
                hist.setdefault(str(p), 0)
            skew = telemetry_analysis.partition_skew(hist)
            if (
                skew["partitions"] > 1
                and skew["max_mean_ratio"] > threshold
                and (best is None or skew["max_mean_ratio"] > best[0])
            ):
                best = (skew["max_mean_ratio"], i, hist, skew["mean"])
        if best is None:
            return
        ratio, inp, hist, mean = best
        hot = sorted(
            int(p) for p, v in hist.items()
            if mean > 0 and v > threshold * mean
        )
        if not hot:
            return
        salt_stage(stage, inp.source_id, factor, hot)
        self._salt_log.append({
            "stage_id": stage.stage_id,
            "source": inp.source_id,
            "factor": int(factor),
            "hot": hot,
            "max_mean_ratio": round(float(ratio), 4),
        })
        if validate.level(self.session) != "OFF":
            validate.validate_stages(stages, phase="salted_replan")

    def _maybe_grow_partitions(
        self, stage: Stage, stages: list[Stage], by_id: dict,
        started: set, factor: float, cap: int,
    ) -> None:
        """Runtime-adaptive partition count (ROADMAP skew item (c),
        the reference's faulttolerant runtime-adaptive partitioning):
        when an input edge's committed rows blow past the CBO estimate
        by ``factor``, this un-admitted hash stage grows its OUTPUT
        fan-out — the next exchange fabric — so its consumers run more,
        smaller tasks. Producers that already ran keep their pinned
        fan-out; sibling producers feeding a shared consumer grow as a
        group (a consumer's aligned inputs must agree on partition
        count) or not at all."""
        if getattr(stage, "out_partitions", 0) or cap <= self.n_partitions:
            return
        if stage.partitioning != "hash":
            return
        est = getattr(self, "_stage_estimates", None) or {}
        blowup = 0.0
        for i in stage.inputs:
            e = float(est.get(i.stage_id, 0.0) or 0.0)
            if e <= 0:
                continue
            blowup = max(blowup, self._stage_actual_rows(i.stage_id) / e)
        if blowup <= factor:
            return
        import math

        # double at the trigger point, proportional beyond, power-of-2
        # steps (partition counts stay friendly to the hash fold)
        mult = 2 ** max(1, math.ceil(math.log2(blowup / factor)))
        grown = min(int(cap), self.n_partitions * int(mult))
        if grown <= self.n_partitions:
            return
        # sibling closure: every aligned producer sharing a consumer
        # with this stage must adopt the same fan-out — abort if any is
        # already started (its tasks were posted with the old count)
        group = {stage.stage_id}
        while True:
            grew = False
            for s in stages:
                for i in s.inputs:
                    if i.mode != "aligned" or i.stage_id not in group:
                        continue
                    for j in s.inputs:
                        if (
                            j.mode == "aligned"
                            and j.stage_id not in group
                        ):
                            group.add(j.stage_id)
                            grew = True
            if not grew:
                break
        for sid in group:
            if sid != stage.stage_id and (
                sid in started
                or by_id[sid].partitioning != "hash"
                or getattr(by_id[sid], "out_partitions", 0)
            ):
                return
        for sid in sorted(group):
            by_id[sid].out_partitions = grown
            telemetry.ADAPTIVE_REPARTITIONS.inc()
            self._adaptive_log.append({
                "stage_id": sid,
                "from": self.n_partitions,
                "to": grown,
                "blowup": round(float(blowup), 2),
            })
        if validate.level(self.session) != "OFF":
            validate.validate_stages(stages, phase="adaptive_replan")

    def _estimate_stage_rows(self, stages: list[Stage]) -> dict:
        """Per-stage CBO output-row estimates, children before parents.

        Each fragment's RemoteSource leaves are seeded into the stats
        cache with the producer stage's own estimate (identity-keyed
        entries, plan.stats.estimate consults them before descending),
        so an intermediate stage's estimate composes exactly the way
        the monolithic planner's would."""
        from trino_tpu.plan import stats as plan_stats

        by_source = {
            i.source_id: i.stage_id
            for s in stages for i in s.inputs
        }
        est: dict[str, float] = {}
        for s in stages:
            cache: dict = {}
            seen: set[int] = set()

            def seed(n: P.PlanNode) -> None:
                if id(n) in seen:
                    return
                seen.add(id(n))
                if isinstance(n, P.RemoteSource):
                    rows = est.get(by_source.get(n.source_id, ""), 0.0)
                    cache[id(n)] = (n, plan_stats.PlanStats(float(rows)))
                for src in n.sources:
                    seed(src)

            seed(s.root)
            try:
                est[s.stage_id] = float(
                    plan_stats.estimate(s.root, self.metadata, cache).rows
                )
            except Exception:
                est[s.stage_id] = 0.0
        return est

    # ---- task construction -----------------------------------------------

    def _make_tasks(
        self, stage: Stage, by_id: dict | None = None
    ) -> list[_TaskSpec]:
        sid = stage.stage_id
        # serving mode: workers key live tasks by "task_id.attempt", so
        # concurrent queries sharing a fleet need query-unique task ids
        # — prefix with the attempt-level query id. Single-query mode
        # keeps the bare ids every existing test and trace knows.
        pfx = (
            f"{self._query_id[:6]}." if (
                self.dispatcher is not None and self._query_id
            ) else ""
        )
        if stage.aligned:
            wire = plan_to_json(stage.root)
            # an aligned stage runs one task per INPUT partition — the
            # producers' effective fan-out, which adaptive growth may
            # have raised above the fleet default
            n_in = self.n_partitions
            if by_id is not None:
                for i in stage.inputs:
                    if i.mode != "aligned" or i.stage_id not in by_id:
                        continue
                    op = int(
                        getattr(by_id[i.stage_id], "out_partitions", 0)
                        or 0
                    )
                    if op:
                        n_in = op
                        break
            salt = getattr(stage, "salt_plan", None)
            hot = set(salt["hot"]) if salt else set()
            factor = int(salt["factor"]) if salt else 1
            specs = []
            for p in range(n_in):
                if p in hot:
                    # hot partition: K salted sub-tasks, each reading a
                    # 1-in-K row slice of the fanout source (chaos key
                    # "sid:p.s" targets one salted sub-task)
                    specs.extend(
                        _TaskSpec(
                            f"{pfx}s{sid}p{p}x{s}", wire, p,
                            fail_first=(
                                f"{sid}:{p}.{s}" in self.inject_failures
                            ),
                            salt=s,
                        )
                        for s in range(factor)
                    )
                else:
                    specs.append(
                        _TaskSpec(
                            f"{pfx}s{sid}p{p}", wire, p,
                            fail_first=f"{sid}:{p}" in self.inject_failures,
                        )
                    )
            return specs
        scans = stage.scans()
        if len(scans) == 1 and scans[0].split is None:
            scan = scans[0]
            connector = self.metadata.connector(scan.catalog)
            n_live = max(2, sum(1 for w in self.workers if w.alive))
            # pushdown at split generation: a supports_domains
            # connector prunes partitions/row groups from the scan's
            # domains (static filter conjuncts + any coordinator-level
            # dynamic-filter ranges injected before admission), so
            # pruned storage never even becomes a task. Split footer
            # stats give a second, connector-agnostic pruning pass.
            domains = None
            if scan.domains and getattr(connector, "supports_domains", False):
                domains = {
                    c: ColumnDomain(*d) for c, d in scan.domains.items()
                }
            splits = connector.splits(
                scan.schema, scan.table, n_live, domains=domains
            )
            if domains:
                kept = [s for s in splits if not s.disjoint(domains)]
                splits = kept or [Split(scan.table, 0, 0)]
            specs = []
            for i, spl in enumerate(splits):
                bound = _bind_split(stage.root, scan, (spl.start, spl.count))
                specs.append(
                    _TaskSpec(
                        f"{pfx}s{sid}t{i}", plan_to_json(bound), None,
                        fail_first=f"{sid}:{i}" in self.inject_failures,
                    )
                )
            return specs
        return [
            _TaskSpec(
                f"{pfx}s{sid}t0", plan_to_json(stage.root), None,
                fail_first=f"{sid}:0" in self.inject_failures,
            )
        ]

    # ---- coordinator-level dynamic filtering over storage scans ----------

    def _plan_scan_df(self, stages: list[Stage], by_id: dict):
        """Find inner joins whose probe side bottoms at an unbound
        supports_domains TableScan and whose build side is an upstream
        stage. Returns (hold, inject, report):

        - hold: probe_stage_id -> build stage ids that must complete
          before the probe stage is admitted;
        - inject: probe_stage_id -> [{scan, column, build_stage,
          build_sym}] domain-injection targets resolved at admission;
        - report: build_stage_id -> output symbols whose min/max its
          tasks report.

        The reference's coordinator-side dynamic filtering
        (MAIN/server/DynamicFilterService.java:120) does the same
        collect-then-narrow, with the lazy-blocking split source in
        the role the admission hold plays here."""
        hold: dict[str, set] = {}
        inject: dict[str, list] = {}
        report: dict[str, list] = {}
        if not sp.get(self.session, "dynamic_filtering_enabled"):
            return hold, inject, report
        by_source = {
            i.source_id: i.stage_id for s in stages for i in s.inputs
        }

        def blocked_by(sid: str) -> set:
            out: set = set()
            stack = [sid]
            while stack:
                x = stack.pop()
                deps = {i.stage_id for i in by_id[x].inputs}
                deps |= hold.get(x, set())
                for d in deps:
                    if d not in out:
                        out.add(d)
                        stack.append(d)
            return out

        joins: list[tuple[Stage, P.Join]] = []
        for s in stages:
            def walk(n, _s=s):
                if isinstance(n, P.Join):
                    joins.append((_s, n))
                for c in n.sources:
                    walk(c)
            walk(s.root)
        for s, j in joins:
            if j.kind != "inner" or not j.criteria:
                continue
            # planner hint: a build range expected to keep >70% of
            # probe rows cannot pay for the admission hold (same gate
            # as the in-executor range filter); unknown -> try, the
            # storage-pruning upside dwarfs the collection cost
            if j.df_range_keep is not None and j.df_range_keep > 0.7:
                continue
            for psym, bsym in j.criteria:
                bsid, bout = _df_build_source(j.right, bsym, by_source)
                if bsid is None:
                    continue
                pstage, scan, col = _df_trace(
                    s, j.left, psym, by_id, by_source
                )
                if scan is None or pstage.stage_id == bsid:
                    continue
                try:
                    conn = self.metadata.connector(scan.catalog)
                except KeyError:
                    continue
                if not getattr(conn, "supports_domains", False):
                    continue
                # never create a wait cycle: the build stage must not
                # itself (transitively, through inputs or earlier
                # holds) wait on the probe stage
                if pstage.stage_id in blocked_by(bsid):
                    continue
                hold.setdefault(pstage.stage_id, set()).add(bsid)
                inject.setdefault(pstage.stage_id, []).append({
                    "scan": scan, "column": col,
                    "build_stage": bsid, "build_sym": bout,
                })
                syms = report.setdefault(bsid, [])
                if bout not in syms:
                    syms.append(bout)
        return hold, inject, report

    def _apply_scan_df(
        self, stage: Stage, targets: list[dict], col_ranges: dict
    ) -> None:
        """Narrow the held stage's scan domains with the merged build
        ranges (intersected with any static filter domains), rewriting
        the stage root in place before task construction."""
        upd: dict[int, list] = {}
        for t in targets:
            rng = col_ranges.get(t["build_stage"], {}).get(t["build_sym"])
            if not rng or not rng[2] or rng[0] is None:
                continue  # unreported/uncomputable: no narrowing
            scan = t["scan"]
            ent = upd.setdefault(
                id(scan), [scan, dict(scan.domains or {}), []]
            )
            ent[1][t["column"]] = _merge_domain(
                ent[1].get(t["column"]), int(rng[0]), int(rng[1])
            )
            ent[2].append((t["column"], int(rng[0]), int(rng[1])))
        for scan, domains, applied in upd.values():
            stage.root = _bind_domains(stage.root, scan, domains)
            self.df_scan_log.append({
                "stage_id": stage.stage_id,
                "table": f"{scan.schema}.{scan.table}",
                "columns": {c: [lo, hi] for c, lo, hi in applied},
            })

    # ---- overlapping stage-DAG scheduling with retry ---------------------

    def _run_dag(
        self, stages: list[Stage], qroot: str,
        tasks_by_stage: dict[str, list[str]],
    ) -> None:
        """Schedule ALL stages through one event loop. Readiness is
        the EventDrivenScheduler's call, per the ``stage_admission``
        session property:

        - ``BARRIER``: a stage's tasks queue only once EVERY input
          stage has fully committed — independent subtrees (the two
          scan stages under a partitioned join, UNION branches) still
          interleave across the pool, but a consumer never starts
          while a producer stage is partially committed;
        - ``PIPELINED`` (default): every stage registers up front and
          each TASK dispatches the moment its specific input
          partitions are committed across all producer tasks (fed by
          the committed-partition sets workers report on status
          polls), with the observed producer attempts pinned on the
          stage-task request — producer tails overlap consumer heads.

        The loop also owns the fault-tolerance machinery:
        - retry with exponential backoff + full jitter
          (retry_initial_delay_ms/retry_max_delay_ms), failures
          classified so deterministic semantic errors fail the query
          immediately instead of burning attempts;
        - speculative execution (Dean & Barroso, "The Tail at Scale"):
          a RUNNING task older than speculation_multiplier x the
          median completed-task runtime of its stage gets a backup
          attempt on an idle worker; first committed attempt wins,
          the loser is cancelled (spool attempt-dedup makes a raced
          duplicate commit harmless);
        - spool-corruption recovery: a consumer failing with
          SpoolCorruptionError quarantines the corrupt attempt and
          re-runs the PRODUCING task (exchange-data-loss recovery,
          not just consumer retry);
        - dead-worker re-admission: evicted workers are probed on a
          backoff schedule and rejoin the pool when they answer."""
        by_id = {s.stage_id: s for s in stages}
        # coordinator-level dynamic filtering over storage scans: probe
        # stages whose fragment bottoms at a supports_domains TableScan
        # hold admission until their build stages complete, build tasks
        # report per-symbol min/max, and the merged range lands in the
        # probe scan's domains BEFORE its splits are enumerated — the
        # fact table's pruned row groups are never read anywhere
        df_hold, df_inject, df_report = self._plan_scan_df(stages, by_id)
        #: build_stage_id -> sym -> [lo, hi, complete?] merged across
        #: that stage's committed tasks
        col_ranges: dict[str, dict[str, list]] = {}
        specs_of: dict[str, list[_TaskSpec]] = {}
        spec_by_tid: dict[str, tuple[Stage, _TaskSpec]] = {}
        done_of: dict[str, set] = {s.stage_id: set() for s in stages}
        complete: set[str] = set()
        started: set[str] = set()
        #: per-stage task queues, dispatched round-robin so independent
        #: ready stages make progress TOGETHER (a FIFO would fill the
        #: pool with the first stage's tasks and serialize subtrees)
        queues: dict[str, deque] = {}
        rr: deque[str] = deque()  # round-robin order over queues
        #: (task_id, attempt) -> (worker, stage, spec, posted-at);
        #: keyed per ATTEMPT so an original and its speculative backup
        #: coexist
        inflight: dict[
            tuple[str, int], tuple[FleetWorker, Stage, _TaskSpec, float]
        ] = {}
        next_attempt_no: dict[str, int] = {}
        failures: dict[str, int] = {}
        #: earliest monotonic time a task may be re-dispatched (retry
        #: backoff); absent = immediately
        eligible_at: dict[str, float] = {}
        #: completed-task wall-clock runtimes per stage (speculation's
        #: straggler threshold)
        runtimes: dict[str, list[float]] = {}
        speculative: set[tuple[str, int]] = set()
        speculated_tids: set[str] = set()
        quarantined: set[tuple[str, str, int]] = set()
        deadline = time.monotonic() + self.timeout_s

        mode = str(sp.get(self.session, "stage_admission")).upper()
        pipelined = mode == "PIPELINED"
        sched = EventDrivenScheduler(stages, mode=mode)
        self._scheduler = sched

        # skew-proof exchanges (ROADMAP skew item (b)/(c)): both
        # rewrites decide off COMPLETE producer statistics — the
        # per-partition histograms of (a) for salting, committed
        # rows_out vs the CBO estimate for adaptive growth — so a
        # non-zero threshold holds every aligned consumer until its
        # producers finish (the stage-materialization barrier the
        # reference's faulttolerant AdaptivePlanner replans behind).
        # Both default OFF, leaving pipelined admission untouched.
        salt_thresh = float(sp.get(self.session, "skew_salt_threshold"))
        salt_factor = int(sp.get(self.session, "skew_salt_factor"))
        adapt_factor = float(
            sp.get(self.session, "adaptive_partition_growth_factor")
        )
        adapt_max = int(sp.get(self.session, "adaptive_partition_max"))
        skew_hold = salt_thresh > 0 or adapt_factor > 0

        # serving mode: register with the shared dispatcher — slot
        # grants arrive fair-share across resource groups, and ALL
        # status polling happens on its O(workers) reactor threads.
        # The handle is unregistered in _execute_attempt's finally (it
        # sweeps any slots this query still pins on abnormal unwind).
        handle = None
        if self.dispatcher is not None:
            handle = self.dispatcher.register_query(
                self._query_id or "q",
                self.resource_group,
                self.group_weight,
            )
            self._dispatch_handle = handle

        retry_init_ms = float(sp.get(self.session, "retry_initial_delay_ms"))
        retry_max_ms = float(sp.get(self.session, "retry_max_delay_ms"))
        spec_enabled = (
            bool(sp.get(self.session, "speculation_enabled"))
            # retry_policy=NONE (or retry_max_attempts=1) means fail
            # fast: no hedged attempts either
            and self.max_attempts > 1
        )
        spec_mult = float(sp.get(self.session, "speculation_multiplier"))
        spec_min_age_s = (
            float(sp.get(self.session, "speculation_min_task_age_ms"))
            / 1000.0
        )

        def push(stage: Stage, spec: _TaskSpec) -> None:
            sid = stage.stage_id
            if sid not in queues:
                queues[sid] = deque()
                rr.append(sid)
            queues[sid].append(spec)

        def n_pending() -> int:
            return sum(len(q) for q in queues.values())

        def ready(stage: Stage) -> bool:
            return all(i.stage_id in complete for i in stage.inputs)

        def stage_startable(stage: Stage) -> bool:
            # BARRIER constructs a stage's tasks only once its inputs
            # completed (task construction sees post-barrier worker
            # liveness); PIPELINED registers every stage up front —
            # children-first fragment order means producers register
            # before their consumers, and per-TASK readiness is the
            # scheduler's call at dispatch time. A dynamic-filter hold
            # trumps both modes: a probe-side scan stage waits for its
            # build stages so admission sees the merged key ranges.
            holds = df_hold.get(stage.stage_id)
            if holds and not all(b in complete for b in holds):
                return False
            # skew hold: salting and adaptive growth re-plan a stage AT
            # admission from its producers' final output statistics, so
            # aligned consumers wait for complete inputs even under
            # PIPELINED (scan/leaf stages are unaffected)
            if (
                skew_hold
                and any(i.mode == "aligned" for i in stage.inputs)
                and not ready(stage)
            ):
                return False
            return pipelined or ready(stage)

        def take_next(now: float):
            """Next dispatchable (stage, spec) round-robin across
            non-empty queues, skipping tasks still in retry backoff
            and tasks the scheduler does not admit yet (inputs not
            committed at the required granularity, or regressed —
            corruption recovery de-completes a producer stage, so its
            consumers hold)."""
            for _ in range(len(rr)):
                sid = rr[0]
                rr.rotate(-1)
                q = queues.get(sid)
                if not q:
                    continue
                stage = by_id[sid]
                for _ in range(len(q)):
                    spec = q.popleft()
                    if (
                        now < eligible_at.get(spec.task_id, 0.0)
                        or not sched.task_ready(stage, spec)
                    ):
                        q.append(spec)
                        continue
                    return stage, spec
            return None

        def mark_dead(w: FleetWorker) -> None:
            w.alive = False
            w.fails = 0
            self._probe_delay[w.uri] = self.readmit_initial_s
            self._probe_at[w.uri] = (
                time.monotonic() + self.readmit_initial_s
            )

        def other_attempt_inflight(tid: str) -> bool:
            return any(t == tid for (t, _) in inflight)

        def record_failure(
            stage: Stage, spec: _TaskSpec, error: str
        ) -> None:
            tid = spec.task_id
            if not _retryable(error):
                raise RuntimeError(
                    f"task {tid} failed with non-retryable error "
                    f"(not retried): {error}"
                )
            failures[tid] += 1
            self.failure_log.append(f"{tid}: {error}")
            if failures[tid] >= self.max_attempts:
                raise RuntimeError(
                    f"task {tid} failed after {failures[tid]} "
                    f"attempts: {error}"
                )
            # cluster-wide budget: every retry decision spends one
            # token; exhaustion fails the query typed instead of
            # letting a recovery storm retry-flood the fleet
            self._retry_budget.spend()
            telemetry.TASKS_RETRIED.inc()
            self._retries_by_stage[stage.stage_id] = (
                self._retries_by_stage.get(stage.stage_id, 0) + 1
            )
            # exponential backoff with FULL jitter (delay drawn
            # uniformly from [0, cap]): retries of correlated failures
            # decorrelate instead of stampeding the fleet in sync
            cap = min(
                retry_max_ms, retry_init_ms * (2 ** (failures[tid] - 1))
            )
            delay = self._retry_rng.uniform(0.0, cap) / 1000.0
            eligible_at[tid] = time.monotonic() + delay
            self.retry_delays.append(delay)
            self.stats["tasks_retried"] += 1
            push(stage, spec)

        def handle_corruption(error: str) -> None:
            """A consumer task read corrupt spooled input: the fault
            belongs to the PRODUCING task's committed output. Withdraw
            the corrupt attempt and re-run the producer at the next
            attempt number; consumers retry once it recommits."""
            m = _CORRUPTION_RE.search(error)
            if m is None:
                return
            psid, ptid, pa = m.group(1), m.group(2), int(m.group(3))
            if (psid, ptid, pa) in quarantined:
                return
            quarantined.add((psid, ptid, pa))
            spool.quarantine_attempt(qroot, psid, ptid, pa)
            # rescind pipelined admissions pinned to the quarantined
            # attempt: cancel the in-flight consumer attempts and
            # requeue them (no failure counted — the consumer did
            # nothing wrong). A FINISHED consumer stands: it CRC-
            # verified every byte it read, and producer determinism
            # makes any verified attempt's bytes correct.
            for vtid in sched.retract(psid, ptid, pa):
                ventry = spec_by_tid.get(vtid)
                if ventry is None:
                    continue
                vstage, vspec = ventry
                if vtid in done_of[vstage.stage_id]:
                    continue
                vkeys = [k for k in inflight if k[0] == vtid]
                if not vkeys:
                    continue  # still queued: re-pins at next dispatch
                for k2 in vkeys:
                    (w2, _, _, _) = inflight.pop(k2)
                    cancel_attempt(w2, vtid, k2[1])
                    if self.dispatcher is not None:
                        self.dispatcher.finish(vtid, k2[1])
                sched.rescinds += 1
                telemetry.SCHED_RESCINDS.inc()
                self.failure_log.append(
                    f"{vtid}: admission rescinded (producer "
                    f"{ptid} attempt {pa} quarantined)"
                )
                push(vstage, vspec)
            if psid not in by_id or ptid not in spec_by_tid:
                return
            if ptid not in done_of[psid]:
                return  # already re-queued or re-running
            pstage, pspec = spec_by_tid[ptid]
            done_of[psid].discard(ptid)
            complete.discard(psid)
            failures[ptid] += 1
            if failures[ptid] >= self.max_attempts:
                raise RuntimeError(
                    f"task {ptid} output corrupt after "
                    f"{failures[ptid]} attempts"
                )
            next_attempt_no[ptid] = max(
                next_attempt_no[ptid],
                spool.next_attempt(qroot, psid, ptid),
            )
            self._retry_budget.spend()
            self.stats["tasks_retried"] += 1
            telemetry.TASKS_RETRIED.inc()
            self._retries_by_stage[psid] = (
                self._retries_by_stage.get(psid, 0) + 1
            )
            push(pstage, pspec)

        def cancel_attempt(
            w: FleetWorker, tid: str, attempt: int
        ) -> None:
            # best-effort: a cancel that loses the race to the spool
            # commit is harmless (attempt dedup)
            try:
                req = urllib.request.Request(
                    f"{w.uri}/v1/stagetask/{tid}.{attempt}",
                    method="DELETE",
                )
                with urllib.request.urlopen(
                    req, timeout=self.rpc_timeout_s
                ) as r:
                    r.read()
            except Exception:
                pass

        rs = self._resume_state

        def seed_resumed(stage: Stage, spec: _TaskSpec) -> bool:
            """Resume pre-seeding for one spec: inherit a spool-
            committed attempt (only when the regenerated spec's
            fingerprint matches the journaled one — task ids alone are
            not stable across restarts), adopt a still-RUNNING attempt
            on a live worker, or fall through to a normal dispatch
            with the attempt counter advanced past every on-disk and
            journaled attempt. True = spec fully handled, do not
            queue."""
            sid, tid = stage.stage_id, spec.task_id
            ca = spool.committed_attempt(qroot, sid, tid)
            if (
                ca is not None
                and rs["fps"].get(tid) == journal_mod.spec_fingerprint(spec)
            ):
                # committed before the crash AND provably the same
                # work: inherit the attempt, never re-execute it
                wuri = rs["dispatches"].get((tid, ca))
                for p in spool.committed_partitions(qroot, sid, tid, ca):
                    sched.on_partition_commit(sid, tid, ca, p, worker=wuri)
                sched.on_task_commit(sid, tid, ca, worker=wuri)
                done_of[sid].add(tid)
                next_attempt_no[tid] = spool.next_attempt(qroot, sid, tid)
                self.resume_stats["tasks_recovered_committed"] += 1
                return True
            # never reuse an attempt number the dead coordinator may
            # have left running on a worker (tasks key by tid.attempt)
            journaled = [a for (t, a) in rs["dispatches"] if t == tid]
            next_attempt_no[tid] = max(
                next_attempt_no[tid],
                spool.next_attempt(qroot, sid, tid),
                (max(journaled) + 1) if journaled else 0,
            )
            if journaled and self.dispatcher is None:
                a = max(journaled)
                wuri = rs["dispatches"].get((tid, a))
                w = next(
                    (x for x in self.workers
                     if x.uri == wuri and x.alive and not x.draining),
                    None,
                )
                if w is not None:
                    # adopt only after a status pre-probe: blindly
                    # inheriting a vanished attempt would count its
                    # 404s toward evicting a healthy worker
                    try:
                        st = self._poll_task(w, tid, a)
                    except Exception:
                        st = None
                    if st is not None and st.get("state") in (
                        "RUNNING", "FINISHED"
                    ):
                        inflight[(tid, a)] = (
                            w, stage, spec, time.monotonic()
                        )
                        self.resume_stats["tasks_adopted"] += 1
                        return True
            self.resume_stats["tasks_redispatched"] += 1
            return False

        # the loop's waits, one ``task_poll_wait`` span a waiting
        # period: from the first sleep with nothing to do but poll to
        # the first progress (a task dispatched, finished or failed)
        wait_sp = wait_sig = None
        while len(complete) < len(stages):
            if time.monotonic() > deadline:
                raise TimeoutError("query stages timed out")
            if (
                self._exec_deadline is not None
                and time.monotonic() > self._exec_deadline
            ):
                raise QueryDeadlineExceededError(
                    "Query exceeded maximum execution time limit "
                    "[query_max_execution_time]"
                )
            if (
                self._cancel_event is not None
                and self._cancel_event.is_set()
            ):
                raise QueryCancelled("Query was canceled")
            if self._kill_error is not None:
                # named the victim by the cluster memory manager from
                # ANOTHER query's dispatch loop (serving mode)
                msg, self._kill_error = self._kill_error, None
                raise memory.ExceededMemoryLimitError(msg)
            if self.dispatcher is None:
                # re-admission probes: evicted workers that answer
                # /v1/info again rejoin the placement pool (in serving
                # mode the dispatcher's per-worker reactor probes)
                now = time.monotonic()
                for w in self.workers:
                    if w.alive or now < self._probe_at.get(w.uri, 0.0):
                        continue
                    try:
                        with urllib.request.urlopen(
                            f"{w.uri}/v1/info",
                            timeout=self.readmit_probe_timeout_s,
                        ) as r:
                            info = json.loads(r.read())
                    except Exception:
                        d = min(
                            self._probe_delay.get(
                                w.uri, self.readmit_initial_s
                            ) * 2.0,
                            self.readmit_max_s,
                        )
                        self._probe_delay[w.uri] = d
                        self._probe_at[w.uri] = time.monotonic() + d
                        continue
                    w.alive = True
                    w.fails = 0
                    w.draining = info.get("state") != "ACTIVE"
                    self._probe_delay.pop(w.uri, None)
                    self._probe_at.pop(w.uri, None)
                    self.stats["workers_readmitted"] += 1
                    telemetry.WORKERS_READMITTED.inc()
            # admit newly-startable stages (under BARRIER, task
            # construction sees current worker liveness, so it happens
            # at admission, not upfront)
            for stage in stages:
                if stage.stage_id in started or not stage_startable(stage):
                    continue
                targets = df_inject.pop(stage.stage_id, None)
                if targets:
                    self._apply_scan_df(stage, targets, col_ranges)
                if skew_hold and stage.inputs:
                    # producers are complete (skew hold): fold their
                    # observed stats and re-plan this edge before any
                    # task is constructed
                    if salt_thresh > 0:
                        self._maybe_salt_stage(
                            stage, stages, by_id, salt_thresh,
                            salt_factor,
                        )
                    if adapt_factor > 0:
                        self._maybe_grow_partitions(
                            stage, stages, by_id, started, adapt_factor,
                            adapt_max,
                        )
                specs = self._make_tasks(stage, by_id)
                rep = df_report.get(stage.stage_id)
                if rep:
                    for spec in specs:
                        spec.report_ranges = list(rep)
                specs_of[stage.stage_id] = specs
                sched.register_stage(stage, specs)
                if self.journal is not None:
                    # WAL the stage's task enumeration + per-spec work
                    # fingerprints before any dispatch — what a future
                    # resume checks committed attempts against
                    self.journal.stage(
                        self._public_query_id or self._query_id,
                        stage.stage_id,
                        {
                            s.task_id: journal_mod.spec_fingerprint(s)
                            for s in specs
                        },
                    )
                if (
                    self._tracer is not None
                    and stage.stage_id not in self._stage_spans
                ):
                    # stage span: admission -> full commit; worker task
                    # subtrees stitch in under it via the trace context
                    self._stage_spans[stage.stage_id] = (
                        self._tracer.start(
                            f"stage {stage.stage_id}", "stage",
                            tasks=len(specs),
                        )
                    )
                for spec in specs:
                    next_attempt_no[spec.task_id] = 0
                    failures[spec.task_id] = 0
                    spec_by_tid[spec.task_id] = (stage, spec)
                    if rs is not None and seed_resumed(stage, spec):
                        continue
                    push(stage, spec)
                started.add(stage.stage_id)
                if rs is not None and len(done_of[stage.stage_id]) == len(
                    specs
                ):
                    # every task inherited a committed attempt: no poll
                    # event will ever fire for this stage, so complete
                    # it here (mirrors the FINISHED-branch completion)
                    sid0 = stage.stage_id
                    tasks_by_stage[sid0] = [s.task_id for s in specs]
                    complete.add(sid0)
                    sched.on_stage_complete(sid0)
                    ssp = self._stage_spans.get(sid0)
                    if ssp is not None:
                        ssp.finish()
                    if self.stage_hook is not None:
                        self.stage_hook(sid0)
            if self.dispatcher is None:
                self._sync_membership()
            live = [w for w in self.workers if w.alive]
            if not live:
                raise RuntimeError("no live workers remain")
            postable = [w for w in live if not w.draining]
            if n_pending() and not postable and not inflight:
                raise RuntimeError(
                    "all remaining workers are draining; tasks cannot "
                    "be placed"
                )
            if self.dispatcher is None:
                busy = {id(w) for (w, _, _, _) in inflight.values()}
                for _ in range(n_pending()):
                    # NOTE: no busy-count early-out — `busy` includes
                    # draining/hung workers holding in-flight tasks,
                    # which are not in `postable`; counting them would
                    # idle free workers. The `w is None` probe below is
                    # the real "no free worker" exit.
                    nxt = take_next(time.monotonic())
                    if nxt is None:
                        break
                    stage, spec = nxt
                    w = next(
                        (w for w in postable if id(w) not in busy), None
                    )
                    if w is None:
                        queues[stage.stage_id].appendleft(spec)
                        break
                    a = next_attempt_no[spec.task_id]
                    try:
                        self._post_task(
                            w, stage, spec, a, qroot, tasks_by_stage,
                            pins=sched.admit(stage, spec),
                        )
                        next_attempt_no[spec.task_id] = a + 1
                        inflight[(spec.task_id, a)] = (
                            w, stage, spec, time.monotonic()
                        )
                        busy.add(id(w))
                        if self.post_hook is not None:
                            self.post_hook(
                                stage.stage_id, spec.task_id, w
                            )
                    except urllib.error.HTTPError as e:
                        if e.code == 409:
                            # 409 = draining: alive, just not accepting
                            # — reschedule elsewhere, keep polling its
                            # tasks
                            w.draining = True
                            postable = [x for x in postable if x is not w]
                        else:
                            mark_dead(w)
                            postable = [x for x in postable if x is not w]
                        queues[stage.stage_id].appendleft(spec)
                    except Exception:
                        mark_dead(w)
                        postable = [x for x in postable if x is not w]
                        queues[stage.stage_id].appendleft(spec)
            else:
                # serving mode: keep one slot request outstanding per
                # currently-dispatchable task (ready + past backoff);
                # consume fair-share grants by posting from THIS thread
                # so all RPC error handling stays in the query loop
                n_want = sched.ready_count(
                    queues, by_id, eligible_at, time.monotonic()
                )
                self.dispatcher.want(handle, n_want)
                granted = False
                for grant in self.dispatcher.take_grants(handle):
                    granted = True
                    nxt = take_next(time.monotonic())
                    if nxt is None:
                        # readiness regressed between request and
                        # grant (backoff, retraction): hand it back
                        self.dispatcher.release_grant(grant)
                        continue
                    stage, spec = nxt
                    w = grant.worker
                    if not w.alive or w.draining:
                        self.dispatcher.release_grant(grant)
                        queues[stage.stage_id].appendleft(spec)
                        continue
                    a = next_attempt_no[spec.task_id]
                    try:
                        self._post_task(
                            w, stage, spec, a, qroot, tasks_by_stage,
                            pins=sched.admit(stage, spec),
                        )
                        next_attempt_no[spec.task_id] = a + 1
                        inflight[(spec.task_id, a)] = (
                            w, stage, spec, time.monotonic()
                        )
                        self.dispatcher.bind(grant, spec.task_id, a)
                        if self.post_hook is not None:
                            self.post_hook(
                                stage.stage_id, spec.task_id, w
                            )
                    except urllib.error.HTTPError as e:
                        if e.code == 409:
                            w.draining = True
                        else:
                            self.dispatcher.mark_dead(w)
                        self.dispatcher.release_grant(grant)
                        queues[stage.stage_id].appendleft(spec)
                    except Exception:
                        self.dispatcher.mark_dead(w)
                        self.dispatcher.release_grant(grant)
                        queues[stage.stage_id].appendleft(spec)
            for key, entry in list(inflight.items()):
                if key not in inflight:
                    continue  # removed by a dead-worker sweep below
                (w, stage, spec, t0) = entry
                tid, a = key
                if self.dispatcher is None:
                    try:
                        state = self._poll_task(w, tid, a)
                        w.fails = 0
                        # pool snapshots ride on every task-status
                        # response (the heartbeat surface): aggregate
                        # them and apply the cluster cap + kill policy
                        self.cluster_memory.observe(
                            w.uri, state.get("pool")
                        )
                        self.cluster_memory.enforce(
                            self._cluster_cap, running={self._query_id}
                        )
                    except memory.ExceededMemoryLimitError:
                        raise  # killed by the cluster memory manager
                    except Exception as e:
                        # crash/kill -9 refuses the connection: dead
                        # now. A hung-but-alive worker (SIGSTOP) keeps
                        # the socket open and times out: N consecutive
                        # short timeouts declare it dead — detection
                        # latency rpc_timeout_s * max_poll_fails, not
                        # one long RPC timeout (VERDICT r4 missing #8)
                        refused = isinstance(
                            getattr(e, "reason", None),
                            ConnectionRefusedError,
                        ) or isinstance(e, ConnectionRefusedError)
                        w.fails += 1
                        if not (
                            refused or w.fails >= self.max_poll_fails
                        ):
                            continue  # transient: re-poll next loop
                        mark_dead(w)
                        # sweep EVERY attempt the dead worker held; a
                        # task whose sibling attempt survives elsewhere
                        # is not re-queued (the sibling may still win)
                        for k2, e2 in list(inflight.items()):
                            if e2[0] is not w:
                                continue
                            del inflight[k2]
                            st2, sp2 = e2[1], e2[2]
                            tid2 = sp2.task_id
                            if tid2 in done_of[st2.stage_id]:
                                continue
                            if other_attempt_inflight(tid2):
                                continue
                            record_failure(st2, sp2, "worker died")
                        continue
                else:
                    # serving mode: statuses come from the shared
                    # reactor's cache — no RPC from this thread. Worker
                    # death surfaces as a synthetic LOST status per
                    # stranded attempt (memory observation also rides
                    # the reactor, via Dispatcher.on_pool).
                    state = self.dispatcher.status(tid, a)
                    if state is None:
                        continue  # not polled yet
                    if state.get("state") == "LOST":
                        del inflight[key]
                        self.dispatcher.finish(tid, a)
                        if tid in done_of[stage.stage_id]:
                            continue
                        if other_attempt_inflight(tid):
                            continue
                        record_failure(stage, spec, "worker died")
                        continue
                sid = stage.stage_id
                # committed-partition sets ride on every status
                # response: the event feed of pipelined admission
                # (the worker URI doubles as the direct-exchange
                # buffer-residency hint for consumer admissions; in
                # serving mode the reactor's binding is authoritative)
                wuri = w.uri
                if self.dispatcher is not None:
                    wuri = self.dispatcher.residency(tid, a) or w.uri
                for p in state.get("partitions") or ():
                    sched.on_partition_commit(
                        sid, tid, a, int(p), worker=wuri
                    )
                if state["state"] == "FINISHED":
                    del inflight[key]
                    if self.dispatcher is not None:
                        self.dispatcher.finish(tid, a)
                    if tid in done_of[sid]:
                        continue  # duplicate commit of a raced attempt
                    done_of[sid].add(tid)
                    sched.on_task_commit(sid, tid, a, worker=wuri)
                    if self.journal is not None:
                        # advisory (the spool's .done markers are the
                        # durable truth) — lets recovery bound the
                        # in-flight tail without listing the spool
                        try:
                            self.journal.commit(
                                self._public_query_id or self._query_id,
                                sid, tid, a,
                            )
                        except Exception:
                            pass
                    # per-task stats + worker-side span subtree ride on
                    # the FINISHED status response
                    tstats = state.get("stats") or {}
                    # build-side key ranges for coordinator-level
                    # dynamic filtering: merged across the stage's
                    # committed tasks; a task that could not compute a
                    # requested range (None) poisons the symbol so a
                    # partial range never over-prunes the probe scan
                    if spec.report_ranges:
                        got = tstats.get("col_ranges") or {}
                        store = col_ranges.setdefault(sid, {})
                        for sym in spec.report_ranges:
                            cur = store.setdefault(sym, [None, None, True])
                            rng = got.get(sym)
                            if rng is None:
                                cur[2] = False
                            elif rng:
                                lo, hi = int(rng[0]), int(rng[1])
                                cur[0] = (
                                    lo if cur[0] is None
                                    else min(cur[0], lo)
                                )
                                cur[1] = (
                                    hi if cur[1] is None
                                    else max(cur[1], hi)
                                )
                    task_row = {
                        "query_id": self._query_id,
                        "stage_id": sid, "task_id": tid, "attempt": a,
                        "state": "FINISHED", "worker": w.uri,
                        "rows_in": tstats.get("rows_in", 0),
                        "rows_out": tstats.get("rows_out", 0),
                        "bytes_out": tstats.get("bytes_out", 0),
                        "elapsed_ms": tstats.get("elapsed_ms", 0.0),
                        "peak_memory_bytes": tstats.get(
                            "peak_memory_bytes", 0
                        ),
                        "operator_stats": profiler.attach_roofline(
                            tstats.get("operator_stats") or []
                        ),
                        "admission_wait_ms": sched.admission_wait_ms(
                            tid
                        ),
                        "direct_bytes": tstats.get("direct_bytes", 0),
                        "spooled_bytes": tstats.get(
                            "spooled_bytes", 0
                        ),
                        # writer tasks report their sink totals; the
                        # per-stage aggregate and EXPLAIN ANALYZE's
                        # TableWriter line render from these
                        **(
                            {
                                "rows_written": tstats["rows_written"],
                                "bytes_written": tstats[
                                    "bytes_written"
                                ],
                                "files_written": tstats[
                                    "files_written"
                                ],
                            }
                            if tstats.get("rows_written") is not None
                            else {}
                        ),
                        # per-edge consumer row counts (source_id ->
                        # rows read) — the exchange-coverage debug
                        # assertion sums these against producer commits
                        **(
                            {"edge_rows": tstats["edge_rows"]}
                            if "edge_rows" in tstats else {}
                        ),
                        # per-output-partition histograms off the spool
                        # commit (rows + encoded bytes) — the fleet
                        # folds these into per-edge skew stats
                        **(
                            {
                                "partition_rows":
                                    tstats["partition_rows"],
                                "partition_bytes":
                                    tstats.get("partition_bytes") or {},
                            }
                            if tstats.get("partition_rows") else {}
                        ),
                    }
                    self._task_stats.append(task_row)
                    # live introspection: GET /v1/query/{id} serves
                    # this tree while later stages are still running
                    tracker.QUERY_INFO.update_task(
                        self._public_query_id or self._query_id,
                        task_row,
                    )
                    if self._tracer is not None and state.get("spans"):
                        # worker subtrees carry the WORKER's wall
                        # clock; shift onto the coordinator's timeline
                        # before stitching so Chrome traces and
                        # critical-path math never go negative
                        off = self._clock_skew.offset_ms(w.uri)
                        self._tracer.attach(
                            telemetry_analysis.shift_span_tree(
                                state["spans"], off
                            )
                        )
                    runtimes.setdefault(sid, []).append(
                        time.monotonic() - t0
                    )
                    if key in speculative:
                        self.stats["speculation_wins"] += 1
                        telemetry.SPECULATION_WINS.inc()
                    # first committed attempt wins: cancel the losers
                    for k2 in [k for k in inflight if k[0] == tid]:
                        (w2, _, _, _) = inflight.pop(k2)
                        cancel_attempt(w2, tid, k2[1])
                        if self.dispatcher is not None:
                            self.dispatcher.finish(tid, k2[1])
                    if len(done_of[sid]) == len(specs_of[sid]):
                        tasks_by_stage[sid] = [
                            s.task_id for s in specs_of[sid]
                        ]
                        complete.add(sid)
                        sched.on_stage_complete(sid)
                        ssp = self._stage_spans.get(sid)
                        if ssp is not None:
                            ssp.finish()
                        if self.stage_hook is not None:
                            self.stage_hook(sid)
                elif state["state"] == "FAILED":
                    del inflight[key]
                    if self.dispatcher is not None:
                        self.dispatcher.finish(tid, a)
                    error = state.get("error", "task failed")
                    self._task_stats.append({
                        "query_id": self._query_id,
                        "stage_id": sid, "task_id": tid, "attempt": a,
                        "state": "FAILED", "worker": w.uri,
                        "rows_in": 0, "rows_out": 0, "bytes_out": 0,
                        "elapsed_ms": 0.0, "peak_memory_bytes": 0,
                        "admission_wait_ms": sched.admission_wait_ms(
                            tid
                        ),
                    })
                    handle_corruption(error)
                    if tid in done_of[sid]:
                        continue  # a sibling attempt already committed
                    if other_attempt_inflight(tid):
                        continue  # a sibling attempt may still win
                    record_failure(stage, spec, error)
                elif state["state"] == "CANCELED":
                    # a cancelled losing attempt we no longer track,
                    # or a racing cancel — never a failure
                    del inflight[key]
                    if self.dispatcher is not None:
                        self.dispatcher.finish(tid, a)
            # serving mode: cross-query memory governance — the kill
            # victim is picked among ALL live queries (possibly not
            # this one); legacy mode enforced per poll above
            if self.dispatcher is not None:
                if self._serving is not None:
                    self._serving.enforce_memory(
                        self._cluster_cap, self._query_id
                    )
                else:
                    self.cluster_memory.enforce(
                        self._cluster_cap, running={self._query_id}
                    )
            # speculation: hedge stragglers with a backup attempt on
            # an idle worker (first committed attempt wins). Under a
            # shared fleet, "idle" means a FREE SLOT grabbed outside
            # the fair queue — hedges are opportunistic and only ever
            # consume capacity nobody queued for.
            if spec_enabled and inflight:
                now = time.monotonic()
                if self.dispatcher is None:
                    busy = {
                        id(w) for (w, _, _, _) in inflight.values()
                    }
                    idle = [
                        x for x in self.workers
                        if x.alive and not x.draining
                        and id(x) not in busy
                    ]
                else:
                    idle = None
                for key, (w, stage, spec, t0) in list(inflight.items()):
                    if idle is not None and not idle:
                        break
                    tid = spec.task_id
                    sid = stage.stage_id
                    if tid in speculated_tids or tid in done_of[sid]:
                        continue
                    rts = runtimes.get(sid)
                    if not rts:
                        continue  # no completed sibling to compare to
                    threshold = max(
                        spec_min_age_s,
                        spec_mult * statistics.median(rts),
                    )
                    if now - t0 < threshold:
                        continue
                    grant = None
                    if idle is not None:
                        x = next((c for c in idle if c is not w), None)
                        if x is None:
                            continue
                    else:
                        grant = self.dispatcher.try_grab_idle(
                            exclude=w, handle=handle
                        )
                        if grant is None:
                            continue
                        x = grant.worker
                    a2 = next_attempt_no[tid]
                    try:
                        # the hedge re-pins from current commit state;
                        # either attempt's pins read identical bytes
                        self._post_task(
                            x, stage, spec, a2, qroot, tasks_by_stage,
                            pins=sched.admit(stage, spec),
                        )
                    except urllib.error.HTTPError as e:
                        if e.code == 409:
                            x.draining = True
                        elif grant is not None:
                            self.dispatcher.mark_dead(x)
                        else:
                            mark_dead(x)
                        if grant is not None:
                            self.dispatcher.release_grant(grant)
                        else:
                            idle.remove(x)
                        continue
                    except Exception:
                        if grant is not None:
                            self.dispatcher.mark_dead(x)
                            self.dispatcher.release_grant(grant)
                        else:
                            mark_dead(x)
                            idle.remove(x)
                        continue
                    next_attempt_no[tid] = a2 + 1
                    inflight[(tid, a2)] = (x, stage, spec, now)
                    if grant is not None:
                        self.dispatcher.bind(grant, tid, a2)
                    speculative.add((tid, a2))
                    speculated_tids.add(tid)
                    self.stats["tasks_speculated"] += 1
                    telemetry.TASKS_SPECULATED.inc()
                    if idle is not None:
                        idle.remove(x)
                    if self.post_hook is not None:
                        self.post_hook(sid, tid, x)
            # serving mode must ALSO wait while blocked on slot grants
            # (pending tasks, nothing inflight, no grant this round) —
            # otherwise 8 queries contending for 2 slots busy-spin on
            # want()/take_grants() and starve the reactor threads. The
            # wait is event-driven: the dispatcher sets handle.wake on
            # a grant or a terminal status, so the coarse fallback only
            # paces backoff/speculation checks and N blocked queries
            # cost ~no CPU between events.
            if inflight or not n_pending() or (
                self.dispatcher is not None and not granted
            ):
                sig = (len(inflight), len(complete), n_pending())
                if wait_sp is not None and sig != wait_sig:
                    wait_sp.finish()
                    wait_sp = None
                if wait_sp is None and self._tracer is not None:
                    wait_sp = self._tracer.start("task_poll_wait")
                    wait_sig = sig
                if self.dispatcher is not None:
                    handle.wake.wait(self.poll_s * 5)
                    handle.wake.clear()
                else:
                    time.sleep(self.poll_s)
        if wait_sp is not None:
            wait_sp.finish()
        self._last_specs = dict(spec_by_tid)
        # the pipelining win, as one number: seconds of consumer
        # runtime that overlapped a still-streaming producer stage
        telemetry.SCHED_OVERLAP.set(sched.overlap_seconds())
        assert set(tasks_by_stage) == set(by_id)

    # ---- worker RPC ------------------------------------------------------

    def _post_task(
        self, w: FleetWorker, stage: Stage, spec: _TaskSpec, attempt: int,
        qroot: str, tasks_by_stage: dict[str, list[str]],
        pins: dict | None = None,
    ) -> None:
        # chaos seam: an injected rpc fault on the POST looks like a
        # dead worker to the dispatch loop (evict -> re-admission
        # probes restore it), exactly the failure a dropped connection
        # produces
        fault.check("rpc", tag=f"post:{spec.task_id}", attempt=attempt)
        if self.journal is not None:
            # WAL discipline: journal the dispatch BEFORE the POST — a
            # crash may over-report dispatches (recovery probes, then
            # re-dispatches), but an unjournaled running attempt could
            # collide with a resumed one
            self.journal.dispatch(
                self._public_query_id or self._query_id or "",
                stage.stage_id, spec.task_id, attempt, w.uri,
            )
        inj = fault.active()
        req = {
            "task_id": spec.task_id,
            "attempt": attempt,
            # ship the armed chaos schedule to the worker process: it
            # rebuilds the injector (seed-deterministic) and installs
            # it for this task's duration, so spool/memory/task-exec
            # sites fire there exactly as they would in-process
            "fault_spec": (
                inj.to_spec() if inj is not None and inj.armed else None
            ),
            "plan": spec.plan_json,
            "partition": spec.partition,
            # pipelined admission ships pins per input stage: the
            # producer task list in registered spec order (the stage
            # may not be complete, so tasks_by_stage has no entry yet)
            # and, when available, the exact attempt to read per
            # producer task so a consumer never mixes attempts
            "sources": [
                {
                    "source_id": i.source_id,
                    "stage_id": i.stage_id,
                    "mode": i.mode,
                    "hash_symbols": list(i.hash_symbols),
                    "task_ids": (
                        pins[i.stage_id]["task_ids"]
                        if pins and i.stage_id in pins
                        else tasks_by_stage[i.stage_id]
                    ),
                    **(
                        {"attempts": pins[i.stage_id]["attempts"]}
                        if pins and i.stage_id in pins
                        and "attempts" in pins[i.stage_id]
                        else {}
                    ),
                    # direct-exchange residency hints: which worker's
                    # buffer pool holds each pinned attempt's output
                    # (best-effort — a consumer without hints, or
                    # whose fetch misses, reads the spool)
                    **(
                        {"workers": pins[i.stage_id]["workers"]}
                        if pins and i.stage_id in pins
                        and "workers" in pins[i.stage_id]
                        else {}
                    ),
                    # salted sub-task: the fanout source ships the salt
                    # index + factor (the worker keeps every 1-in-K
                    # row); replicate co-inputs are tagged so telemetry
                    # attributes their re-read rows
                    **(
                        {
                            "salt": spec.salt,
                            "salt_factor": int(
                                stage.salt_plan["factor"]
                            ),
                        }
                        if stage.salt_plan is not None
                        and spec.salt is not None
                        and i.source_id == stage.salt_plan["source"]
                        else {}
                    ),
                    **(
                        {"salt_role": "replicate"}
                        if stage.salt_plan is not None
                        and spec.salt is not None
                        and i.mode == "aligned"
                        and i.source_id != stage.salt_plan["source"]
                        else {}
                    ),
                }
                for i in stage.inputs
            ],
            "output": {
                "stage_id": stage.stage_id,
                "partitioning": stage.partitioning,
                "hash_symbols": stage.hash_symbols,
                # adaptive growth raises a hash stage's fan-out above
                # the fleet default; consumers size their task lists
                # from the same field
                "n_partitions": int(
                    getattr(stage, "out_partitions", 0)
                    or self.n_partitions
                ),
            },
            "spool": qroot,
            "session": dict(self.session.properties),
            **(
                {"report_ranges": list(spec.report_ranges)}
                if spec.report_ranges else {}
            ),
            "fail": bool(spec.fail_first and attempt == 0),
            # worker pools attribute reservations per query; the
            # spool directory name doubles as the query id
            "query_id": self._query_id or os.path.basename(qroot),
        }
        # trace context: the worker roots its task span under this
        # stage's span, so the shipped-back subtree stitches into the
        # coordinator's query trace
        ssp = self._stage_spans.get(stage.stage_id)
        if self._tracer is not None and ssp is not None:
            req["trace"] = {
                "trace_id": self._tracer.trace_id,
                "parent_span_id": ssp.span_id,
            }
        rpc_span = (
            ssp.child(
                f"rpc post {spec.task_id}.{attempt}", "rpc",
                worker=w.uri,
            )
            if ssp is not None else None
        )
        body = json.dumps(req).encode()
        r = urllib.request.Request(
            f"{w.uri}/v1/stagetask", data=body,
            headers={"Content-Type": "application/json"},
        )
        t_rpc = time.perf_counter()
        try:
            with urllib.request.urlopen(
                r, timeout=self.rpc_timeout_s
            ) as resp:
                json.loads(resp.read())
        finally:
            if rpc_span is not None:
                rpc_span.finish()
            telemetry.RPC_LATENCY.observe(
                time.perf_counter() - t_rpc, op="post"
            )

    def _poll_task(self, w: FleetWorker, task_id: str, attempt: int) -> dict:
        # an injected poll fault counts toward the consecutive-timeout
        # eviction threshold, like a real unresponsive worker
        fault.check("rpc", tag=f"poll:{task_id}", attempt=attempt)
        t_rpc = time.perf_counter()
        t_send = time.time() * 1e3
        try:
            with urllib.request.urlopen(
                f"{w.uri}/v1/stagetask/{task_id}.{attempt}",
                timeout=self.rpc_timeout_s,
            ) as resp:
                state = json.loads(resp.read())
            # every status response carries the worker's wall clock:
            # the NTP midpoint estimate keeps a per-worker offset fresh
            # for span stitching
            self._clock_skew.observe(
                w.uri, t_send, time.time() * 1e3, state.get("now_ms")
            )
            return state
        finally:
            telemetry.RPC_LATENCY.observe(
                time.perf_counter() - t_rpc, op="poll"
            )


def _bind_split(
    root: P.PlanNode, scan: P.TableScan, split: tuple[int, int]
) -> P.PlanNode:
    """Rebind the fragment's scan leaf to one split."""
    from dataclasses import replace as dc_replace

    from trino_tpu.plan.optimizer import _replace_sources

    def walk(n: P.PlanNode) -> P.PlanNode:
        if n is scan:
            return dc_replace(n, split=split)
        srcs = n.sources
        if not srcs:
            return n
        return _replace_sources(n, [walk(s) for s in srcs])

    return walk(root)


def _bind_domains(
    root: P.PlanNode, scan: P.TableScan, domains: dict
) -> P.PlanNode:
    """Rebind the fragment's scan leaf with narrowed pushdown domains."""
    from dataclasses import replace as dc_replace

    from trino_tpu.plan.optimizer import _replace_sources

    def walk(n: P.PlanNode) -> P.PlanNode:
        if n is scan:
            return dc_replace(n, domains=domains)
        srcs = n.sources
        if not srcs:
            return n
        return _replace_sources(n, [walk(s) for s in srcs])

    return walk(root)


def _merge_domain(cur, lo: int, hi: int):
    """Intersect an existing (lo, hi, lo_strict, hi_strict) domain with
    a closed [lo, hi] dynamic-filter range."""
    if cur is None:
        return (lo, hi, False, False)
    clo, chi, cls, chs = cur
    if clo is None or lo > clo:
        clo, cls = lo, False
    if chi is None or hi < chi:
        chi, chs = hi, False
    return (clo, chi, cls, chs)


def _df_trace(stage: Stage, node: P.PlanNode, sym: str, by_id, by_source):
    """Follow a probe key symbol down Filter/Project chains — hopping
    across exchanges into producer stages — to a bare column of an
    unbound TableScan. Returns (stage, scan, column) or Nones when the
    chain computes the symbol or crosses a non-streaming operator."""
    from trino_tpu.expr.ir import InputRef

    for _ in range(64):  # fragment DAGs are shallow; bound paranoia
        if isinstance(node, P.TableScan):
            col = node.assignments.get(sym)
            if col is None or node.split is not None:
                return None, None, None
            return stage, node, col
        if isinstance(node, P.RemoteSource):
            sid = by_source.get(node.source_id)
            if sid is None:
                return None, None, None
            stage = by_id[sid]
            node = stage.root
            continue
        if isinstance(node, P.Filter):
            node = node.source
            continue
        if isinstance(node, P.Project):
            e = node.assignments.get(sym)
            if not isinstance(e, InputRef):
                return None, None, None
            sym = e.name
            node = node.source
            continue
        return None, None, None
    return None, None, None


def _df_build_source(node: P.PlanNode, sym: str, by_source):
    """Trace a build key symbol down to the RemoteSource reading the
    build stage's spooled output; a Filter between them only widens the
    reported range (superset rows), which stays correct. Returns
    (build_stage_id, stage_output_symbol) or (None, None)."""
    from trino_tpu.expr.ir import InputRef

    for _ in range(64):
        if isinstance(node, P.RemoteSource):
            sid = by_source.get(node.source_id)
            return (sid, sym) if sid is not None else (None, None)
        if isinstance(node, P.Filter):
            node = node.source
            continue
        if isinstance(node, P.Project):
            e = node.assignments.get(sym)
            if not isinstance(e, InputRef):
                return None, None
            sym = e.name
            node = node.source
            continue
        return None, None
    return None, None
