"""Top-level query engine facade.

The analog of the reference's LocalQueryRunner
(MAIN/testing/LocalQueryRunner.java:263): the full pipeline — parse,
analyze, plan, execute — in one process without the HTTP layers. The
distributed runner builds on the same stages but fragments the plan and
executes over a device mesh.

Statement dispatch mirrors the reference's DataDefinitionExecution vs
SqlQueryExecution split (MAIN/execution/): metadata statements (SHOW,
DESCRIBE, USE, SET SESSION) execute coordinator-side; EXPLAIN renders
the plan; EXPLAIN ANALYZE executes with per-node device timings (the
ExplainAnalyzeOperator analog, MAIN/operator/ExplainAnalyzeOperator.java).
"""

from __future__ import annotations

import threading
import time
import uuid
from contextlib import contextmanager
from dataclasses import dataclass, field

from trino_tpu import telemetry
from trino_tpu.analyzer.analyzer import Analyzer
from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.exec.local import LocalExecutor
from trino_tpu.metadata import Metadata, Session
from trino_tpu.page import Page
from trino_tpu.plan import nodes as P
from trino_tpu.plan.optimizer import optimize
from trino_tpu.sql import ast
from trino_tpu.sql.parser import parse_statement

__all__ = ["QueryRunner", "QueryResult"]


@dataclass
class QueryResult:
    names: list[str]
    rows: list[tuple]
    #: True when the query had a top-level ORDER BY (rows are ordered)
    ordered: bool = False
    plan: P.PlanNode | None = field(default=None, repr=False)
    #: fleet fault-tolerance counters (QueryStats analog): how many
    #: task attempts were re-queued after a failure, how many backup
    #: attempts were hedged against stragglers, how many of those
    #: backups committed first, and how many evicted workers rejoined.
    #: Always 0 outside fleet mode; tests use them to prove a recovery
    #: path actually fired rather than the query quietly sailing past
    tasks_retried: int = 0
    tasks_speculated: int = 0
    speculation_wins: int = 0
    workers_readmitted: int = 0
    #: workers that live-joined the placement pool mid-query after
    #: announcing into the membership registry (elastic fleet)
    workers_joined: int = 0
    #: whole-statement re-executions under retry_policy=QUERY (each
    #: one ran under a fresh spool epoch); 0 when the first execution
    #: succeeded or the policy is NONE/TASK
    query_retries: int = 0
    #: skew mitigation counters (fleet tier): exchange edges the
    #: coordinator re-planned as SALTED after hot-partition detection
    #: (skew_salt_threshold), and stages whose output partition count
    #: was grown at runtime after an input edge blew past its
    #: cardinality estimate (adaptive_partition_growth_factor)
    salted_edges: int = 0
    adaptive_repartitions: int = 0
    #: memory governance (QueryStats peakUserMemoryReservation analog):
    #: the query's peak concurrent reservation, total and per node
    peak_memory_bytes: int = 0
    peak_memory_per_node: dict = field(default_factory=dict)
    #: stitched trace span tree (telemetry.Trace) for the whole query;
    #: None when tracing was not active for this statement
    trace: object = field(default=None, repr=False)
    #: per-stage aggregates (rows/bytes in+out, elapsed, retries, peak
    #: memory) — the single source EXPLAIN ANALYZE's stage lines render
    #: from; one pseudo-stage for local execution
    stage_stats: list = field(default_factory=list)
    #: per-task rows backing system.runtime.tasks
    task_stats: list = field(default_factory=list)
    #: elapsed split (QueryStats analog): wall-clock in the planner vs
    #: everything after it
    planning_ms: float = 0.0
    execution_ms: float = 0.0
    #: wall-clock decomposition into named buckets (queued, planning,
    #: compile, scan, compute, exchange, straggler slack, ...) plus the
    #: critical path — telemetry_analysis.compute_time_breakdown over
    #: the finished trace; None when tracing was not active
    time_breakdown: dict | None = field(default=None, repr=False)
    #: per-HLO-scope device-time attribution from a kernel_profile
    #: capture (kernel_profile.attribute summary); None unless the
    #: session property was ON/AUTO and the capture succeeded
    kernel_profile: dict | None = field(default=None, repr=False)
    #: per-query cache traffic (cache.CacheStats.as_dict()): result-tier
    #: hit/miss + bytes and device-tier hits/misses/bytes; None when
    #: both tiers were disabled for the statement
    cache_stats: dict | None = field(default=None, repr=False)

    @property
    def query_info(self) -> dict | None:
        """Post-hoc QueryInfo tree (stages → tasks → operators), the
        same JSON ``GET /v1/query/{id}`` served live. Operator roofline
        attribution resolves lazily on first access — XLA cost analysis
        runs only for queries whose profile is actually read."""
        info = getattr(self, "_query_info", None)
        if info is None:
            resolver = getattr(self, "_query_info_resolver", None)
            if resolver is not None:
                self._query_info = info = resolver()
                self._query_info_resolver = None
        return info

    def profile_json(self, indent: int | None = None) -> str:
        """The query-info tree (with the time breakdown) as one JSON
        document."""
        import json

        info = dict(self.query_info or {})
        if self.time_breakdown is not None:
            info["time_breakdown"] = self.time_breakdown
        return json.dumps(
            info, indent=indent, default=str, sort_keys=True,
        )


@contextmanager
def _epilogue_span(root):
    """``epilogue``: what ``QueryRunner.execute`` still does after
    ``to_rows`` while it holds the runner's lock and the next statement
    waits — the registry's and the recorders' book-keeping, one child
    span a recorder — as a child of the statement's ``root``."""
    telemetry.set_active_span(root)
    try:
        with telemetry.child_span("epilogue") as sp:
            yield sp
    finally:
        telemetry.set_active_span(None)


class QueryRunner:
    """SQL in, rows out — the LocalQueryRunner analog. With a ``mesh``,
    plans are distribution-planned and executed SPMD over the device
    mesh (the DistributedQueryRunner analog,
    TESTING/DistributedQueryRunner.java:98)."""

    def __init__(
        self,
        metadata: Metadata | None = None,
        session: Session | None = None,
        mesh=None,
    ):
        self.metadata = metadata or Metadata()
        self.session = session or Session()
        self.mesh = mesh
        # statements execute serially per runner: the executor's scan
        # cache, jit cache and the session are shared mutable state
        # (the coordinator's per-query threads all funnel through here)
        self._lock = threading.RLock()
        # one executor across queries: keeps the jit-program cache and
        # device-resident scanned tables warm (a Trino worker's lifetime)
        if mesh is not None:
            from trino_tpu.exec.mesh import MeshExecutor

            self.executor = MeshExecutor(self.metadata, self.session, mesh)
        else:
            self.executor = LocalExecutor(self.metadata, self.session)
        # per-runner semantic result cache (cache.py): repeat statements
        # on one long-lived runner hit; unrelated runners never share.
        # The serving layer overrides this with its own shared instance
        from trino_tpu import cache as _cache
        from trino_tpu import session_properties

        self.result_cache = _cache.register_result_cache(
            _cache.SemanticResultCache(
                int(session_properties.get(
                    self.session, "result_cache_max_bytes"
                ))
            )
        )
        # performance sentry observes every statement this runner
        # completes (no-op when TRINO_TPU_SENTRY=0)
        from trino_tpu import sentry as _sentry

        _sentry.ensure_installed(self.metadata)

    @staticmethod
    def tpch(schema: str = "tiny", mesh=None) -> "QueryRunner":
        """Runner with the TPC-H catalog mounted (TpchQueryRunner analog,
        testing/trino-tests/.../TpchQueryRunner.java:21)."""
        md = Metadata()
        md.register_catalog("tpch", TpchConnector())
        return QueryRunner(md, Session(catalog="tpch", schema=schema), mesh=mesh)

    @staticmethod
    def tpcds(schema: str = "tiny", mesh=None) -> "QueryRunner":
        """Runner with the TPC-DS catalog mounted (the reference's
        TpcdsQueryRunner analog)."""
        from trino_tpu.connectors.tpcds.connector import TpcdsConnector

        md = Metadata()
        md.register_catalog("tpcds", TpcdsConnector())
        return QueryRunner(
            md, Session(catalog="tpcds", schema=schema), mesh=mesh
        )

    @staticmethod
    def parquet(
        root: str, schema: str = "default", mesh=None,
        catalog: str = "hive",
    ) -> "QueryRunner":
        """Runner over a parquet directory tree (the HiveQueryRunner
        analog): ``root/<schema>/<table>.parquet`` files or Hive-style
        ``root/<schema>/<table>/<key>=<value>/`` partition trees."""
        from trino_tpu.connectors.parquet import ParquetConnector

        md = Metadata()
        md.register_catalog(catalog, ParquetConnector(root))
        return QueryRunner(
            md, Session(catalog=catalog, schema=schema), mesh=mesh
        )

    # ---- planning --------------------------------------------------------

    def plan_stmt(self, stmt: ast.Statement, optimized: bool = True) -> P.PlanNode:
        """Analyze + optimize one statement, timed into the active
        statement's ``plan`` span (when ``execute`` opened a tree)."""
        tracer = getattr(self, "_tracer", None)
        if tracer is None:
            return self._plan_stmt_inner(stmt, optimized)
        with tracer.span("plan", "planning", stmt=type(stmt).__name__):
            return self._plan_stmt_inner(stmt, optimized)

    def _plan_stmt_inner(
        self, stmt: ast.Statement, optimized: bool = True
    ) -> P.PlanNode:
        from trino_tpu import fault, session_properties

        t_plan = time.monotonic()
        # chaos seam: an armed `planner` fault models a transient
        # planning-infrastructure failure (retryable at the QUERY tier)
        fault.check("planner", tag=type(stmt).__name__)
        plan_delay = session_properties.get(
            self.session, "planning_delay_ms"
        )
        if plan_delay:
            time.sleep(plan_delay / 1e3)
        analyzer = Analyzer(self.metadata, self.session)
        plan = analyzer.analyze(stmt)
        if optimized:
            plan = optimize(plan, self.metadata, self.session)
        if self.mesh is not None and (
            not _has_arrays(plan)
            or getattr(self.mesh, "host_exchange", False)
        ):
            # ARRAY columns live in host pools whose handles cannot
            # shard over a device mesh yet: array-bearing plans execute
            # on the local paths with a mesh attached. Fleet exchanges
            # move pages through the host spool serde (which carries
            # list columns), so a mesh stand-in that advertises
            # host_exchange distributes them normally.
            from trino_tpu.plan.distribute import add_exchanges
            from trino_tpu.plan import validate as _validate

            plan = add_exchanges(
                plan, self.metadata,
                n_shards=self.mesh.devices.size, session=self.session,
                # writer fan-out needs host-side exchanges (the fleet
                # spool); a real device mesh gathers below the writer
                scaled_writers=bool(
                    getattr(self.mesh, "host_exchange", False)
                ),
            )
            if optimized and _validate.level(self.session) != "OFF":
                _validate.validate_plan(plan, phase="add_exchanges")
        if optimized:
            from trino_tpu.plan.stats import annotate

            plan = annotate(plan, self.metadata, self.session)
        if optimized and session_properties.get(
            self.session, "result_cache_enabled"
        ) and _write_handle(plan) is None:
            # semantic fingerprint of the OPTIMIZED tree (post-annotate,
            # so the hash covers what will actually execute); pure
            # read-side derivation, safe under plan_validation=FULL
            from trino_tpu import cache as _cache

            plan._semantic_hash = _cache.plan_digest(plan, self.session)
        max_plan_s = session_properties.parse_duration(
            session_properties.get(self.session, "query_max_planning_time")
        )
        if max_plan_s > 0 and time.monotonic() - t_plan > max_plan_s:
            from trino_tpu.tracker import QueryDeadlineExceededError

            raise QueryDeadlineExceededError(
                f"Query exceeded maximum planning time limit of "
                f"{max_plan_s:g}s [query_max_planning_time]"
            )
        return plan

    def plan_sql(self, sql: str, optimized: bool = True) -> P.PlanNode:
        return self.plan_stmt(parse_statement(sql), optimized=optimized)

    # ---- execution -------------------------------------------------------

    def execute_page(self, sql: str) -> tuple[P.PlanNode, Page]:
        plan = self.plan_sql(sql)
        return plan, self.executor.execute(plan)

    def execute(
        self, sql: str, cancel_event=None, query_id: str | None = None,
        tracer=None,
    ) -> QueryResult:
        """``tracer``: the statement's span tree where the caller opened
        one (the coordinator does, in ``submit``, so that the tree
        starts before the queue and is sealed by it); without one the
        runner opens and seals its own."""
        query_id = query_id or uuid.uuid4().hex[:12]
        own_tracer = tracer is None
        if own_tracer:
            tracer = telemetry.Tracer(query_id, root_name="statement")
        # statements take the runner in turn (see __init__): the wait
        # for the one ahead is the queue below the resource group's
        wait = tracer.start("runner_wait")
        try:
            with self._lock:
                wait.finish()
                return self._execute_locked(
                    sql, cancel_event, query_id, tracer, own_tracer
                )
        finally:
            if own_tracer:
                tracer.finish()  # a failed statement's tree too

    def _execute_locked(
        self, sql: str, cancel_event, query_id: str, tracer,
        own_tracer: bool,
    ) -> QueryResult:
        from trino_tpu import session_properties

        self.executor.cancel_event = cancel_event
        # absolute execution deadline: boundary checks inside the
        # executor turn it into QueryDeadlineExceededError; the
        # coordinator's QueryTracker reaps queries that wedge
        # between boundaries
        max_exec_s = session_properties.parse_duration(
            session_properties.get(
                self.session, "query_max_execution_time"
            )
        )
        self.executor.deadline = (
            time.monotonic() + max_exec_s if max_exec_s > 0 else None
        )
        # per-query memory context: all executor reservations made
        # by this statement attribute to this query's subtree of
        # the pool (restored afterwards so ad-hoc executor use
        # keeps its default context)
        prev_ctx = self.executor.memory_ctx
        qctx = self.executor.memory_pool.query_context(query_id)
        self.executor.memory_ctx = qctx
        from trino_tpu import tracker
        from trino_tpu.profiler import OperatorProfiler

        prev_tracer = getattr(self, "_tracer", None)
        self._tracer = tracer
        # the spans this call adds to the tree: the flight
        # recorder's window is lock acquired -> here, whoever
        # opened the tree and however long it waited before
        root = tracer.root
        n_before = len(root.children)
        start_ms = time.time() * 1e3
        tracker.QUERY_INFO.begin(
            query_id, sql=sql, user=self.session.user
        )
        prev_prof = self.executor.profiler
        self.executor.profiler = prof = OperatorProfiler()
        from trino_tpu import cache as cache_mod

        prev_cstats = getattr(self.executor, "cache_stats", None)
        prev_self_cstats = getattr(self, "_cache_stats", None)
        cstats = cache_mod.CacheStats()
        self._cache_stats = cstats
        self.executor.cache_stats = cstats
        kp_mode = str(
            session_properties.get(self.session, "kernel_profile")
            or "OFF"
        ).upper()
        t0 = time.perf_counter()
        # compile-counter baseline: the delta attributes THIS
        # statement's backend compiles (hook is process-wide)
        comp0 = telemetry.compile_snapshot()
        error = None
        result = None
        try:
            if kp_mode in ("ON", "AUTO"):
                # device-profile the statement; attribution lands
                # on QueryResult.kernel_profile (and, for AUTO, on
                # the slow-query record when the threshold fires)
                from trino_tpu import kernel_profile

                with kernel_profile.Capture(
                    trigger="session" if kp_mode == "ON" else "auto"
                ) as kp_cap:
                    result = self._execute(sql)
                result.kernel_profile = kp_cap.summary()
            else:
                result = self._execute(sql)
            result.peak_memory_bytes = qctx.peak_bytes
            if qctx.peak_bytes:
                result.peak_memory_per_node = {
                    self.executor.memory_pool.node_id: qctx.peak_bytes
                }
            return result
        except Exception as e:
            error = f"{type(e).__name__}: {e}"
            raise
        finally:
            # the statement's own spans: the flight recorder's window
            # (the epilogue below is not the statement's execution)
            mine = root.children[n_before:]
            with _epilogue_span(root):
                self.executor.cancel_event = None
                self.executor.deadline = None
                self.executor.memory_ctx = prev_ctx
                self.executor.profiler = prev_prof
                self.executor.cache_stats = prev_cstats
                self._cache_stats = prev_self_cstats
                if result is not None and result.cache_stats is None and (
                    cstats.result_hit is not None
                    or cstats.device_hits
                    or cstats.device_misses
                ):
                    result.cache_stats = cstats.as_dict()
                self._tracer = prev_tracer
                plan_ms = sum(
                    sp.duration_ms for top in mine for sp in top.walk()
                    if sp.name == "plan"
                )
                elapsed_ms = (time.perf_counter() - t0) * 1e3
                state = "FAILED" if error else "FINISHED"
                telemetry.QUERIES_TOTAL.inc(state=state)
                node_id = self.executor.memory_pool.node_id
                # timings-only seal for the live registry; the lazy
                # QueryResult.query_info resolver is the path that pays
                # for XLA cost analysis
                with telemetry.child_span("operator_stats"):
                    op_stats = prof.finish(None)
                    for _row in op_stats:
                        telemetry.OPERATOR_SELF_TIME.observe(
                            _row.get("self_ms", 0.0) / 1e3,
                            operator=_row.get("node_type", "?"),
                        )
                    tracker.QUERY_INFO.finish(
                        query_id, state=state,
                        rows=len(result.rows) if result else None,
                        error=error,
                        peak_memory_bytes=qctx.peak_bytes,
                        operator_stats=op_stats,
                    )
                if result is not None:
                    _ex, _prof, _qid = self.executor, prof, query_id
                    result._query_info_resolver = (
                        lambda: _local_query_info(_ex, _prof, _qid)
                    )
                with telemetry.child_span("compile_snapshot"):
                    comp1 = telemetry.compile_snapshot()
                    compiles_delta = int(
                        comp1.get("compiles", 0) - comp0.get("compiles", 0)
                    )
                    compile_ms_delta = max(
                        (
                            comp1.get("compile_seconds", 0.0)
                            - comp0.get("compile_seconds", 0.0)
                        ) * 1e3,
                        0.0,
                    )
                plan_digest = None
                fingerprint = None
                with telemetry.child_span("plan_digest"):
                    if result is not None and result.plan is not None:
                        from trino_tpu import history as history_mod
                        from trino_tpu import journal as journal_mod

                        try:
                            plan_digest = journal_mod.plan_digest(result.plan)
                        except Exception:
                            plan_digest = None
                        fingerprint = history_mod.session_fingerprint(
                            self.session
                        )
                if result is not None:
                    # a tree handed in is sealed by who opened it
                    result.trace = (
                        tracer.finish() if own_tracer
                        else telemetry.Trace(root)
                    )
                    result.planning_ms = plan_ms
                    result.execution_ms = max(elapsed_ms - plan_ms, 0.0)
                    with telemetry.child_span("time_breakdown"):
                        from trino_tpu import telemetry_analysis

                        result.time_breakdown = (
                            telemetry_analysis.compute_time_breakdown(
                                telemetry.Trace(telemetry.Span(
                                    name=root.name, kind=root.kind,
                                    start_ms=start_ms, duration_ms=elapsed_ms,
                                    children=mine, _open=False,
                                )),
                                elapsed_ms, op_stats=op_stats,
                                compile_ms=compile_ms_delta,
                            )
                        )
                    if (
                        result.time_breakdown
                        and result.names == ["Query Plan"]
                        and result.stage_stats
                    ):
                        # local EXPLAIN ANALYZE (stage_stats filled by
                        # _explain; plain EXPLAIN has none yet): the
                        # breakdown footer rides the rendered plan
                        result.rows.extend(
                            (line,)
                            for line in telemetry_analysis
                            .format_breakdown(result.time_breakdown)
                        )
                        # sentry baseline footer — judged against
                        # history that does NOT yet include this run
                        # (completion fires below)
                        from trino_tpu import sentry as sentry_mod

                        _bf = sentry_mod.baseline_footer(
                            plan_digest, fingerprint or "",
                            elapsed_ms, result.time_breakdown,
                        )
                        if _bf:
                            result.rows.append((_bf,))
                    if not result.stage_stats:
                        # local execution is one pseudo-stage; the fleet
                        # runner fills real per-stage aggregates instead
                        result.stage_stats = [{
                            "stage_id": "local",
                            "tasks": 1,
                            "rows_in": 0,
                            "rows_out": len(result.rows),
                            "bytes_out": 0,
                            "elapsed_ms": elapsed_ms,
                            "retries": 0,
                            "peak_memory_bytes": qctx.peak_bytes,
                            "admission_wait_ms": 0.0,
                        }]
                    if not result.task_stats:
                        # mirror the (possibly _explain-provided)
                        # stage aggregate so system.runtime.tasks and
                        # stage_stats always report the same numbers
                        st = result.stage_stats[0]
                        result.task_stats = [{
                            "query_id": query_id,
                            "stage_id": st["stage_id"],
                            "task_id": f"{st['stage_id']}.0",
                            "attempt": 0,
                            "state": state,
                            "worker": node_id,
                            "elapsed_ms": st["elapsed_ms"],
                            "rows_in": st["rows_in"],
                            "rows_out": st["rows_out"],
                            "bytes_out": st["bytes_out"],
                            "peak_memory_bytes": st[
                                "peak_memory_bytes"
                            ],
                        }]
                listeners = getattr(self.metadata, "event_listeners", ())
                with telemetry.child_span("listeners"):
                    if listeners:
                        from trino_tpu.events import (
                            QueryCompletedEvent,
                            fire_query_completed,
                        )

                        fire_query_completed(listeners, QueryCompletedEvent(
                            query_id=query_id,
                            user=self.session.user,
                            sql=sql,
                            state=state,
                            elapsed_ms=elapsed_ms,
                            rows=len(result.rows) if result else 0,
                            error=error,
                            peak_memory_bytes=qctx.peak_bytes,
                            peak_memory_per_node=(
                                (node_id, qctx.peak_bytes),
                            ) if qctx.peak_bytes else (),
                            planning_ms=plan_ms,
                            execution_ms=max(elapsed_ms - plan_ms, 0.0),
                            cpu_ms=max(elapsed_ms - plan_ms, 0.0),
                            query_retries=(
                                result.query_retries if result else 0
                            ),
                            tasks_retried=(
                                result.tasks_retried if result else 0
                            ),
                            tasks_speculated=(
                                result.tasks_speculated if result else 0
                            ),
                            speculation_wins=(
                                result.speculation_wins if result else 0
                            ),
                            workers_readmitted=(
                                result.workers_readmitted if result else 0
                            ),
                            plan_digest=plan_digest,
                            session_fingerprint=fingerprint,
                            cache_hit_tier=(
                                "result"
                                if result is not None
                                and result.cache_stats
                                and (
                                    result.cache_stats.get("result") or {}
                                ).get("hit")
                                else None
                            ),
                            compiles=compiles_delta,
                            time_breakdown=(
                                result.time_breakdown if result else None
                            ),
                            trace=result.trace if result else None,
                            task_stats=tuple(
                                result.task_stats if result else ()
                            ),
                        ))
                with telemetry.child_span("slow_query"):
                    from trino_tpu.events import maybe_log_slow_query

                    maybe_log_slow_query(
                        listeners, self.session, query_id, sql,
                        elapsed_ms, op_stats, state=state,
                        time_breakdown=(
                            result.time_breakdown if result else None
                        ),
                        kernel_profile=(
                            result.kernel_profile if result else None
                        ),
                    )

    def _execute(self, sql: str) -> QueryResult:
        from trino_tpu import session_properties

        with self._tracer.span("parse"):
            stmt = parse_statement(sql)
        if not isinstance(stmt, (ast.SessionSet, ast.SessionReset)):
            # inconsistent memory caps fail fast at statement time
            # (SET SESSION stays allowed so a bad combination can be
            # corrected)
            from trino_tpu.memory import validate_session_limits

            validate_session_limits(self.session)
            delay = session_properties.get(
                self.session, "execution_delay_ms"
            )
            if delay:
                # test wedge: a dead sleep reaches no cooperative
                # boundary — only the QueryTracker reaper (or the
                # post-sleep deadline check) can retire the query
                time.sleep(delay / 1e3)
        return self._execute_stmt(stmt)

    def _execute_stmt(self, stmt: ast.Statement) -> QueryResult:
        if isinstance(stmt, ast.Prepare):
            self.session.prepared[stmt.name.lower()] = stmt.statement
            return QueryResult(["result"], [("PREPARE",)])
        if isinstance(stmt, ast.ExecutePrepared):
            body = self.session.prepared.get(stmt.name.lower())
            if body is None:
                raise ValueError(f"prepared statement {stmt.name!r} not found")
            return self._execute_stmt(_bind_parameters(body, stmt.args))
        if isinstance(stmt, ast.Deallocate):
            self.session.prepared.pop(stmt.name.lower(), None)
            return QueryResult(["result"], [("DEALLOCATE",)])
        if isinstance(stmt, ast.Explain):
            return self._explain(stmt)
        if isinstance(stmt, ast.ShowCatalogs):
            return QueryResult(
                ["Catalog"],
                [(c,) for c in sorted(self.metadata.catalogs())],
            )
        if isinstance(stmt, ast.ShowSchemas):
            cat = stmt.catalog or self.session.catalog
            conn = self.metadata.connector(cat)
            return QueryResult(
                ["Schema"], [(s,) for s in sorted(conn.list_schemas())]
            )
        if isinstance(stmt, ast.ShowTables):
            cat = self.session.catalog
            schema = self.session.schema
            if stmt.schema:
                parts = stmt.schema
                schema = parts[-1]
                if len(parts) > 1:
                    cat = parts[0]
            conn = self.metadata.connector(cat)
            return QueryResult(
                ["Table"], [(t,) for t in sorted(conn.list_tables(schema))]
            )
        if isinstance(stmt, ast.DescribeTable):
            qt, schema = self.metadata.resolve_table(
                self.session, tuple(stmt.table)
            )
            return QueryResult(
                ["Column", "Type"],
                [(c, str(t)) for c, t in schema.columns],
            )
        if isinstance(stmt, ast.Use):
            parts = list(stmt.parts)
            if len(parts) == 2:
                self.session.catalog, self.session.schema = parts
            else:
                self.session.schema = parts[0]
            return QueryResult(["result"], [("USE",)])
        if isinstance(stmt, ast.CreateView):
            qualified = self._qualify(stmt.name)
            self.metadata.access_control.check_can_ddl(
                self.session.user, *qualified
            )
            cat, sch, tab = qualified
            try:
                exists = tab in self.metadata.connector(cat).list_tables(sch)
            except Exception:
                exists = False
            if exists:
                # a view shadowing a table would make SELECT and DML
                # see different objects (and a self-referencing body
                # would recurse at use)
                raise ValueError(
                    f"table {'.'.join(qualified)} already exists; "
                    "a view cannot shadow it"
                )
            # validate now: a view that cannot analyze must not store
            self.plan_stmt(stmt.query)
            self.metadata.create_view(
                qualified, stmt.query, or_replace=stmt.or_replace
            )
            return QueryResult(["result"], [("CREATE VIEW",)])
        if isinstance(stmt, ast.DropView):
            qualified = self._qualify(stmt.name)
            self.metadata.access_control.check_can_ddl(
                self.session.user, *qualified
            )
            if not self.metadata.drop_view(qualified) and not stmt.if_exists:
                raise KeyError(f"view not found: {'.'.join(stmt.name)}")
            return QueryResult(["result"], [("DROP VIEW",)])
        if isinstance(stmt, ast.Delete):
            return self._delete(stmt)
        if isinstance(stmt, ast.Update):
            return self._update(stmt)
        if isinstance(stmt, ast.SessionSet):
            from trino_tpu import session_properties as SP

            v = stmt.value
            val = getattr(v, "value", None)
            if val is None and hasattr(v, "text"):
                val = v.text
            SP.set_property(self.session, stmt.name, val)
            return QueryResult(["result"], [("SET SESSION",)])
        if isinstance(stmt, ast.SessionReset):
            from trino_tpu import session_properties as SP

            if stmt.name not in SP.SESSION_PROPERTIES:
                raise ValueError(
                    f"unknown session property: {stmt.name}"
                )
            self.session.properties.pop(stmt.name, None)
            return QueryResult(["result"], [("RESET SESSION",)])
        if isinstance(stmt, ast.ShowSession):
            from trino_tpu import session_properties as SP

            return QueryResult(
                ["name", "value", "default", "type", "description"],
                SP.show_rows(self.session),
            )
        if isinstance(stmt, ast.CreateTable):
            return self._create_table(stmt)
        if isinstance(stmt, ast.CreateTableAs):
            return self._create_table_as(stmt)
        if isinstance(stmt, ast.InsertInto):
            return self._insert(stmt)
        if isinstance(stmt, ast.DropTable):
            cat, sch, tab = self._qualify(stmt.name)
            self.metadata.access_control.check_can_ddl(
                self.session.user, cat, sch, tab
            )
            conn = self.metadata.connector(cat)
            if stmt.if_exists and tab not in conn.list_tables(sch):
                return QueryResult(["result"], [("DROP TABLE",)])
            conn.drop_table(sch, tab)
            self.executor.invalidate_scan(cat, sch, tab)
            return QueryResult(["result"], [("DROP TABLE",)])
        plan = self.plan_stmt(stmt)
        rcache, digest, tokens = self._result_cache_probe(plan)
        cstats = getattr(self, "_cache_stats", None)
        if rcache is not None:
            hit = rcache.get(digest, tokens)
            if hit is not None:
                if cstats is not None:
                    cstats.result_hit = True
                    cstats.result_bytes = hit.nbytes
                return QueryResult(
                    names=hit.names, rows=hit.rows,
                    ordered=hit.ordered, plan=plan,
                )
            if cstats is not None:
                cstats.result_hit = False
        tracer = self._tracer
        self.executor._defer_ok = True
        try:
            done = False
            for _attempt in range(8):
                with tracer.span("execute", "execution") as _sp:
                    # anchor the executor's dispatches and host syncs,
                    # and compile-kind work (persistent-cache reads,
                    # injected compile delays), under the local exec
                    # span — the worker task loop does the same for
                    # fleet tasks
                    telemetry.set_active_span(_sp)
                    try:
                        page = self.executor.execute(plan)
                        pend = getattr(page, "pending_flags", None)
                        # the wait for the device is a host sync of
                        # the executor's, not the protocol's time
                        page.block_until_ready(
                            None if pend is None else pend[0]
                        )
                    finally:
                        telemetry.set_active_span(None)
                # the last device->host transfer and the Python rows:
                # execution time to the flight recorder
                with tracer.span("to_rows", "execution"):
                    if pend is None:
                        rows = page.to_pylist()
                    else:
                        # deferred final-chain sync: the result
                        # transfer carries the overflow flags; a
                        # tripped capacity re-runs the query with the
                        # bumped (persisted) size
                        rows, flags = page.to_pylist(extra=pend[0])
                if pend is None or not self.executor.note_chain_flags(
                    flags, pend[1], pend[2]
                ):
                    done = True
                    break
            if not done:
                # never return rows from an overflowed execution
                raise RuntimeError(
                    "aggregation table overflow persisted through retries"
                )
        finally:
            self.executor._defer_ok = False
        ordered = _has_order(plan)
        if rcache is not None:
            rcache.put(digest, list(page.names), rows, ordered, tokens)
        return QueryResult(
            names=list(page.names),
            rows=rows,
            ordered=ordered,
            plan=plan,
        )

    def _result_cache_probe(self, plan):
        """``(cache, digest, tokens)`` when this plan is result-
        cacheable under the current session; ``(None, None, None)``
        otherwise (property off, unserializable plan, or a scan over an
        uncacheable live connector)."""
        from trino_tpu import cache as cache_mod, session_properties

        if not session_properties.get(self.session, "result_cache_enabled"):
            return None, None, None
        digest = getattr(plan, "_semantic_hash", None)
        if digest is None:
            return None, None, None
        tokens = cache_mod.table_tokens(plan, self.metadata)
        if tokens is None:
            return None, None, None
        return self.result_cache, digest, tokens

    # ---- DDL / DML (DataDefinitionExecution + TableWriter analog,
    # MAIN/execution/CreateTableTask.java, MAIN/operator/TableWriterOperator.java)

    def _qualify(self, parts) -> tuple[str, str, str]:
        parts = list(parts)
        if len(parts) == 3:
            return parts[0], parts[1], parts[2]
        if len(parts) == 2:
            return self.session.catalog, parts[0], parts[1]
        return self.session.catalog, self.session.schema, parts[0]

    def _create_table(self, stmt: ast.CreateTable) -> QueryResult:
        from trino_tpu import types as T
        from trino_tpu.connectors.base import TableSchema

        cat, sch, tab = self._qualify(stmt.name)
        self.metadata.access_control.check_can_ddl(
            self.session.user, cat, sch, tab
        )
        conn = self.metadata.connector(cat)
        if stmt.if_not_exists and tab in conn.list_tables(sch):
            return QueryResult(["result"], [("CREATE TABLE",)])
        ts = TableSchema(
            tab,
            [(c, T.type_from_name(tn)) for c, tn in stmt.columns],
        )
        conn.create_table(sch, tab, ts)
        return QueryResult(["result"], [("CREATE TABLE",)])

    def _execute_write_stmt(self, stmt: ast.Statement) -> QueryResult:
        """INSERT ... SELECT / CTAS through the TableWriter plan path:
        the analyzer performs target resolution, access checks, and the
        side-effect-free ``begin_*``; all mutation happens in the
        TableFinish commit. The statement epoch tokens the write so a
        replayed commit is idempotent."""
        plan = self.plan_stmt(stmt)
        handle = _write_handle(plan)
        ex = self.executor
        epoch = uuid.uuid4().hex[:12]
        prev_ctx = getattr(ex, "write_ctx", None)
        ex.write_ctx = {"epoch": epoch, "task": "t0", "attempt": 0}
        try:
            page = ex.execute(plan)
            rows = page.to_pylist()
        except BaseException:
            if handle is not None:
                try:
                    self.metadata.connector(handle["catalog"]).abort_write(
                        handle, token=epoch
                    )
                except Exception:
                    pass
            raise
        finally:
            ex.write_ctx = prev_ctx
        return QueryResult(
            names=list(page.names), rows=rows, plan=plan,
        )

    def _create_table_as(self, stmt: ast.CreateTableAs) -> QueryResult:
        return self._execute_write_stmt(stmt)

    def _insert(self, stmt: ast.InsertInto) -> QueryResult:
        if stmt.rows is None:
            return self._execute_write_stmt(stmt)
        # VALUES fast path: literals evaluate host-side, but the
        # mutation still flows begin_insert -> sink -> finish_write so
        # every connector write shares one commit protocol
        from trino_tpu.exec import write as W

        cat, sch, tab = self._qualify(stmt.name)
        self.metadata.access_control.check_can_insert(
            self.session.user, cat, sch, tab
        )
        conn = self.metadata.connector(cat)
        ts = conn.table_schema(sch, tab)
        target_cols = stmt.columns or ts.column_names
        for row in stmt.rows:
            if len(row) != len(target_cols):
                raise ValueError(
                    f"INSERT row has {len(row)} values but "
                    f"{len(target_cols)} target columns"
                )
        rows = [
            tuple(
                _literal_value(e, ts.column_type(c))
                for e, c in zip(row, target_cols)
            )
            for row in stmt.rows
        ]
        # align to the table's column order, NULL-filling the rest
        idx = {c: i for i, c in enumerate(target_cols)}
        full_rows = [
            tuple(
                row[idx[c]] if c in idx else None
                for c, _ in ts.columns
            )
            for row in rows
        ]
        cols = _rows_to_columns(ts, ts.column_names, full_rows)
        handle = conn.begin_insert(sch, tab)
        handle["catalog"] = cat
        epoch = uuid.uuid4().hex[:12]
        sink = conn.write_sink(
            handle, {"epoch": epoch, "task": "t0", "attempt": 0}
        )
        try:
            if full_rows:
                sink.append(cols, len(full_rows))
            res = W.finish_sink(sink)
            n, _secs = W.commit_write(
                self.metadata, handle, res["fragments"], token=epoch
            )
        except BaseException:
            sink.abort()
            try:
                conn.abort_write(handle, token=epoch)
            except Exception:
                pass
            raise
        self.executor.invalidate_scan(cat, sch, tab)
        return QueryResult(["rows"], [(n,)])

    # ---- EXPLAIN ---------------------------------------------------------

    def _dml_rows(self, name, items):
        """Evaluate DML expressions per row IN TABLE ORDER: one
        ``SELECT e1, .., en FROM t`` (Project over the scan — row count
        and order preserved, single scan for predicate AND assignments)
        returning python rows."""
        q = ast.Query(
            select=ast.Select(
                items=[ast.SelectItem(e) for e in items],
                relations=[ast.TableRef(tuple(name))],
            ),
            with_=[],
        )
        plan = self.plan_stmt(q, optimized=False)
        page = self.executor.execute(plan)
        return page.to_pylist()

    def _delete(self, stmt: "ast.Delete") -> QueryResult:
        """Row-level DELETE (the MergeWriter family's delete case): the
        predicate evaluates device-side in table order; the connector
        rewrites its storage to the kept rows, rejecting the write if
        the table version moved underneath (conflict detection)."""
        import numpy as np

        cat, sch, tab = self._qualify(stmt.name)
        self.metadata.access_control.check_can_delete(
            self.session.user, cat, sch, tab
        )
        conn = self.metadata.connector(cat)
        version = conn.table_version(sch, tab)
        if stmt.where is None:
            keep = np.zeros(conn.row_count(sch, tab), dtype=bool)
        else:
            rows = self._dml_rows(stmt.name, [stmt.where])
            keep = ~np.asarray(
                [r[0] is True for r in rows], dtype=bool
            )
        n = conn.delete_rows(sch, tab, keep, expected_version=version)
        self.executor.invalidate_scan(cat, sch, tab)
        return QueryResult(["rows"], [(n,)])

    def _update(self, stmt: "ast.Update") -> QueryResult:
        """Row-level UPDATE: ONE query evaluates the predicate and
        every assignment expression together, then the connector
        overwrites the masked rows' columns in place (version-checked
        against concurrent writers)."""
        import numpy as np

        cat, sch, tab = self._qualify(stmt.name)
        self.metadata.access_control.check_can_update(
            self.session.user, cat, sch, tab
        )
        conn = self.metadata.connector(cat)
        version = conn.table_version(sch, tab)
        ts = conn.table_schema(sch, tab)
        cols = [c for c, _ in stmt.assignments]
        items = [e for _, e in stmt.assignments]
        if stmt.where is not None:
            items = items + [stmt.where]
        rows = self._dml_rows(stmt.name, items)
        if stmt.where is not None:
            mask = np.asarray(
                [r[-1] is True for r in rows], dtype=bool
            )
            rows = [r[:-1] for r in rows]
        else:
            mask = np.ones(len(rows), dtype=bool)
        new_cols = _rows_to_columns(ts, cols, rows)
        n = conn.update_rows(
            sch, tab, new_cols, mask, expected_version=version
        )
        self.executor.invalidate_scan(cat, sch, tab)
        return QueryResult(["rows"], [(n,)])

    def _explain(self, stmt: ast.Explain) -> QueryResult:
        plan = self.plan_stmt(stmt.statement)
        if not stmt.analyze:
            return QueryResult(
                ["Query Plan"],
                [(line,) for line in P.plan_tree_str(plan).splitlines()],
            )
        stats: dict[int, tuple[float, int]] = {}
        ex = self.executor
        orig = type(ex).execute

        def timed(node):
            t0 = time.perf_counter()
            out = orig(ex, node)
            # force completion so the timing covers device work (the
            # reference's operator wall clocks include the same sync
            # bias at pipeline boundaries)
            n_rows = out.num_rows() if hasattr(out, "num_rows") else 0
            stats[id(node)] = (
                (time.perf_counter() - t0) * 1e3, n_rows,
            )
            return out

        # instance-level patch: other executors (and other threads'
        # runners) are untouched
        ex.execute = timed
        xstats = getattr(ex, "exchange_stats", None)
        # snapshot-delta (never reset shared counters); histograms are
        # nested dicts, so deep-copy the edge maps for their delta
        x0 = dict(xstats) if xstats is not None else None
        p0 = {
            e: dict(h)
            for e, h in (
                (xstats or {}).get("partition_rows") or {}
            ).items()
        }
        skew0 = getattr(ex, "skew_joins", 0)
        esc0 = getattr(ex, "exchange_escalations", 0)
        # per-operator XLA cost attribution rides on the profiler the
        # surrounding execute() installed (EXPLAIN ANALYZE called
        # directly on a bare runner installs its own)
        own_prof = None
        if ex.profiler is None:
            from trino_tpu.profiler import OperatorProfiler

            ex.profiler = own_prof = OperatorProfiler()
        scan0 = len(getattr(ex, "scan_log", None) or [])
        # EXPLAIN ANALYZE executes for real; a write plan needs the
        # same commit token scoping (and failure abort) as execute()
        wh = _write_handle(plan)
        w_epoch = None
        if wh is not None:
            w_epoch = uuid.uuid4().hex[:12]
            ex.write_ctx = {"epoch": w_epoch, "task": "t0", "attempt": 0}
            ex.last_write_stats = None
            ex.last_commit_stats = None
        kp_cap = None
        try:
            t0 = time.perf_counter()
            if stmt.verbose:
                # VERBOSE tier: device-profile the run; to_pylist's
                # host sync keeps every dispatch inside the window
                from trino_tpu import kernel_profile

                with kernel_profile.Capture(trigger="explain") as kp_cap:
                    page = ex.execute(plan)
                    rows = page.to_pylist()
            else:
                page = ex.execute(plan)
                rows = page.to_pylist()
            total_ms = (time.perf_counter() - t0) * 1e3
        except BaseException:
            if wh is not None:
                try:
                    self.metadata.connector(wh["catalog"]).abort_write(
                        wh, token=w_epoch
                    )
                except Exception:
                    pass
            raise
        finally:
            del ex.execute
            if wh is not None:
                ex.write_ctx = None
        # seal records now (costs resolve through the persistent XLA
        # cache) and key them by plan node for the annotated tree;
        # EXPLAIN ANALYZE is an explicit profile request, so eager
        # cost analysis is the point, not overhead
        prof = ex.profiler
        profile: dict[int, dict] = {}
        try:
            prof.finish(ex)
            for rec in prof.records:
                profile[rec.plan_node_id] = rec.to_dict()
        finally:
            if own_prof is not None:
                ex.profiler = None
        # fold the per-node timings into the single local pseudo-stage's
        # aggregate: EXPLAIN ANALYZE's stage line, QueryResult.stage_stats
        # and system.runtime.tasks all render from this one dict
        from trino_tpu.exec.spill import row_bytes

        peak = getattr(ex, "memory_ctx", None)
        peak_bytes = peak.peak_bytes if peak is not None else 0
        rows_in = sum(
            stats[id(n)][1]
            for n in _walk_plan(plan)
            if not n.sources and id(n) in stats
        )
        stage_stats = [{
            "stage_id": "local",
            "tasks": 1,
            "rows_in": rows_in,
            "rows_out": len(rows),
            "bytes_out": len(rows) * row_bytes(plan.outputs),
            "elapsed_ms": total_ms,
            "retries": 0,
            "peak_memory_bytes": peak_bytes,
            "admission_wait_ms": 0.0,
        }]
        lines = [_stage_stats_line("Query", stage_stats[0])]
        if peak_bytes:
            # per-node peak reservations (QueryStats
            # peakUserMemoryReservation in EXPLAIN ANALYZE analog)
            lines.append(
                f"Peak memory: {_fmt_bytes(peak_bytes)} "
                f"({ex.memory_pool.node_id}: "
                f"{_fmt_bytes(peak_bytes)})"
            )
        cw = getattr(ex, "last_commit_stats", None)
        if wh is not None and cw is not None:
            # writer summary (rows/files/bytes from the committed
            # fragments; commit latency is the finish_write wall time)
            lines.append(
                f"TableWriter: {cw['rows']} rows, {cw['files']} files, "
                f"{_fmt_bytes(cw['bytes'])} "
                f"(commit {cw['commit_seconds'] * 1000.0:.1f} ms)"
            )
        _cs = getattr(self, "_cache_stats", None)
        if _cs is not None and (
            _cs.result_hit is not None
            or _cs.device_hits or _cs.device_misses
        ):
            # per-query cache traffic (hit/miss + bytes per tier); the
            # result tier never serves EXPLAIN ANALYZE itself (analyze
            # must execute) but its probe state still renders here
            lines.append(_cs.explain_line())
        if xstats is not None and xstats["exchanges"] > x0["exchanges"]:
            # distributed exchange telemetry (the reference surfaces
            # per-stage exchange bytes in EXPLAIN ANALYZE the same way)
            lines.append(
                f"Exchanges: {xstats['exchanges'] - x0['exchanges']} "
                f"all_to_all, "
                f"{_fmt_bytes(xstats['bytes'] - x0['bytes'])} moved, "
                f"skew-split joins: {getattr(ex, 'skew_joins', 0) - skew0}, "
                f"bucket escalations: "
                f"{getattr(ex, 'exchange_escalations', 0) - esc0}"
            )
        if xstats is not None:
            from trino_tpu import telemetry_analysis

            for edge, hist in sorted(
                (xstats.get("partition_rows") or {}).items()
            ):
                base = p0.get(edge, {})
                delta = {
                    p: int(v) - int(base.get(p, 0))
                    for p, v in hist.items()
                    if int(v) - int(base.get(p, 0)) > 0
                }
                skew = telemetry_analysis.partition_skew(delta)
                if skew["partitions"] > 1:
                    # per-edge shard routing skew (only recorded when
                    # the exchange_partition_counters debug sync is on)
                    lines.append(
                        f"Exchange {edge}: "
                        f"{skew['partitions']} partitions, "
                        f"max/mean {skew['max_mean_ratio']:.2f}, "
                        f"cv {skew['cv']:.2f}"
                    )
        for entry in (getattr(ex, "scan_log", None) or [])[scan0:]:
            # storage pushdown effectiveness (the connector-metrics
            # lines Trino's EXPLAIN ANALYZE renders per scan)
            parts = [
                f"Scan {entry.get('table', '?')}: "
                f"{entry.get('rowgroups_pruned', 0)}/"
                f"{entry.get('rowgroups_total', 0)} row groups pruned",
            ]
            if entry.get("partitions_pruned"):
                parts.append(
                    f"{entry['partitions_pruned']} partitions pruned"
                )
            if entry.get("streamed"):
                parts.append(
                    f"streamed in {entry.get('batches', 0)} batches"
                )
            lines.append(", ".join(parts))
        # kernel observatory: the programs this query dispatched, in
        # first-dispatch order (profiler records carry the jit keys)
        from trino_tpu import program_catalog, telemetry

        dispatched: list = []
        for rec in prof.records:
            for key in getattr(rec, "dispatch_keys", ()):
                if key not in dispatched:
                    dispatched.append(key)
        # satellite: memory_analysis() temp+output vs what the
        # MemoryContext actually reserved — the estimate-based
        # governor's error, surfaced per query and as a gauge
        est_bytes = 0
        for key in dispatched:
            m = program_catalog.CATALOG.memory(key)
            if m is not None:
                est_bytes += (m["temp_bytes"] or 0) + (
                    m["output_bytes"] or 0
                )
        if est_bytes and peak_bytes:
            ratio = est_bytes / peak_bytes
            telemetry.MEMORY_ESTIMATE_RATIO.set(ratio)
            lines.append(
                f"Compiled-program HBM: {_fmt_bytes(est_bytes)} "
                f"temp+output across {len(dispatched)} program(s) vs "
                f"{_fmt_bytes(peak_bytes)} reserved "
                f"(ratio {ratio:.2f})"
            )
        lines.extend(
            _annotated_tree(plan, stats, profile=profile).splitlines()
        )
        if stmt.verbose:
            # VERBOSE tier: per-HLO-scope device time inside the fused
            # programs, then each dispatched program's catalog entry
            summary = kp_cap.summary() if kp_cap is not None else None
            lines.append("Kernel profile (device time by HLO scope):")
            if summary and summary.get("scopes"):
                denom = (
                    summary["attributed_us"]
                    + summary["unattributed_us"]
                ) or 1.0
                for scope, us in summary["scopes"].items():
                    lines.append(
                        f"  {scope}: {us / 1e3:.3f} ms "
                        f"({us / denom * 100:.0f}%)"
                    )
                if summary["unattributed_us"]:
                    lines.append(
                        "  (unattributed): "
                        f"{summary['unattributed_us'] / 1e3:.3f} ms"
                    )
                # a chip's trace names kernels and primitives too
                for axis in ("kernels", "primitives"):
                    if summary.get(axis):
                        lines.append(f"  by {axis[:-1]}: " + ", ".join(
                            f"{k} {us / 1e3:.3f} ms"
                            for k, us in list(summary[axis].items())[:8]
                        ))
            else:
                lines.append("  <no attributable device events captured>")
            for key in dispatched:
                e = program_catalog.CATALOG.entry_for(key, resolve=True)
                if e is None:
                    continue
                flops = (
                    f"{e.flops:.0f}" if e.flops is not None else "?"
                )
                temp = (
                    _fmt_bytes(e.temp_bytes)
                    if e.temp_bytes is not None else "?"
                )
                lines.append(
                    f"  Program {e.program_id} [{e.label}] "
                    f"({e.source}): {flops} flops, temp {temp}, "
                    f"compile {e.compile_s * 1e3:.0f} ms, "
                    f"hits {e.hits}"
                )
        out = QueryResult(["Query Plan"], [(line,) for line in lines])
        out.stage_stats = stage_stats
        # EXPLAIN ANALYZE executed the inner statement for real, so it
        # carries the inner plan: the sentry digests it and the footer
        # compares against the plain statement's own baseline (plain
        # EXPLAIN stays plan-less — a planning-only wall clock must
        # never feed an execution baseline)
        out.plan = plan
        if kp_cap is not None:
            out.kernel_profile = kp_cap.summary()
        return out


def _local_query_info(executor, prof, query_id: str) -> dict:
    """Resolve the local engine's post-hoc QueryInfo tree: seal the
    profiler WITH the executor so operator records gain XLA cost /
    roofline attribution (the lazily-paid step), then shape the same
    single-pseudo-stage tree the live registry serves."""
    from trino_tpu import tracker
    from trino_tpu.profiler import tree_from_stats

    stats = prof.finish(executor)
    info = tracker.QUERY_INFO.get(query_id) or {
        "query_id": query_id, "state": "FINISHED", "stages": [],
    }
    info["stages"] = [{
        "stage_id": "local",
        "tasks": [{
            "task_id": "local-0",
            "attempt": 0,
            "state": info.get("state", "FINISHED"),
            "worker": "local",
            "operators": tree_from_stats(stats),
        }],
    }]
    return info


def _walk_plan(node: P.PlanNode):
    yield node
    for s in node.sources:
        yield from _walk_plan(s)


def _stage_stats_line(label: str, st: dict) -> str:
    """One EXPLAIN ANALYZE stage line rendered from a stage_stats dict
    (the single source both the local and fleet paths use)."""
    line = (
        f"{label}: {st['tasks']} task(s), in: {st['rows_in']} rows, "
        f"out: {st['rows_out']} rows ({_fmt_bytes(st['bytes_out'])}), "
        f"{st['elapsed_ms']:.1f} ms total"
    )
    if st.get("retries"):
        line += f", retries: {st['retries']}"
    if st.get("peak_memory_bytes"):
        line += f", peak memory: {_fmt_bytes(st['peak_memory_bytes'])}"
    if st.get("admission_wait_ms"):
        line += f", admission wait: {st['admission_wait_ms']:.1f} ms"
    if st.get("direct_bytes") or st.get("spooled_bytes"):
        line += (
            f", direct fetch ratio: {st.get('direct_fetch_ratio', 0.0):.2f}"
        )
    return line


def _timed_frontier_ms(node: P.PlanNode, stats) -> float:
    """Total time of the nearest timed descendants (fused interior
    nodes never pass through execute(), so the direct sources of a
    chain head are untimed — walk through them)."""
    total = 0.0
    for s in node.sources:
        if id(s) in stats:
            total += stats[id(s)][0]
        else:
            total += _timed_frontier_ms(s, stats)
    return total


def _rows_in(node: P.PlanNode, stats) -> int:
    """Input rows = nearest timed descendants' output rows (the
    OperatorStats inputPositions analog)."""
    total = 0
    for s in node.sources:
        if id(s) in stats:
            total += stats[id(s)][1]
        else:
            total += _rows_in(s, stats)
    return total


def _annotated_tree(
    node: P.PlanNode, stats, indent: int = 0, profile=None,
) -> str:
    from trino_tpu.exec.spill import row_bytes

    own = stats.get(id(node))
    base = P.plan_tree_str(node, indent).splitlines()[0]
    if own is not None:
        ms, n_rows = own
        child_ms = _timed_frontier_ms(node, stats)
        n_in = _rows_in(node, stats)
        out_bytes = n_rows * row_bytes(node.outputs)
        base += (
            f"   [in: {n_in} rows, out: {n_rows} rows"
            f" ({_fmt_bytes(out_bytes)}), "
            f"self: {max(ms - child_ms, 0.0):.1f} ms]"
        )
        prow = (profile or {}).get(id(node))
        if prow and prow.get("achieved_gflops") is not None:
            # the TPU-native column: measured rate vs the XLA cost
            # model's roofline ceiling for this compiled program
            util = prow.get("roofline_utilization")
            base += (
                f" [xla: {prow['flops'] / 1e6:.1f} MFLOPs, "
                f"{prow['achieved_gflops']:.2f} GFLOP/s achieved"
            )
            if util is not None:
                base += (
                    f", {util * 100:.1f}% of "
                    f"{prow['roofline_gflops']:.0f} GFLOP/s roofline"
                )
            base += "]"
    lines = [base]
    for s in node.sources:
        lines.append(_annotated_tree(s, stats, indent + 1, profile))
    return "\n".join(lines)


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if n < 1024 or unit == "GB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n:.1f}{unit}"
        n /= 1024
    return f"{n}B"


def _has_arrays(plan: P.PlanNode) -> bool:
    from trino_tpu import types as T

    pooled = (T.ArrayType, T.MapType, T.RowType)
    if any(isinstance(t, pooled) for t in plan.outputs.values()):
        return True
    return any(_has_arrays(s) for s in plan.sources)


def _bind_parameters(stmt, args: list) -> "ast.Statement":
    """Deep-copy a prepared statement with each positional ? replaced
    by its EXECUTE ... USING argument expression (the reference binds
    in the analyzer; an AST substitution is equivalent for a fully
    constant-folded argument list)."""
    import copy

    def xform(v):
        if isinstance(v, ast.Parameter):
            if v.index >= len(args):
                raise ValueError(
                    f"prepared statement needs {v.index + 1} "
                    f"parameters, got {len(args)}"
                )
            return copy.deepcopy(args[v.index])
        if isinstance(v, ast.Node):
            for k, sub in vars(v).items():
                setattr(v, k, xform(sub))
            return v
        if isinstance(v, list):
            return [xform(x) for x in v]
        if isinstance(v, tuple):
            return tuple(xform(x) for x in v)
        return v

    return xform(copy.deepcopy(stmt))


# the host storage codec moved to connectors.base so the write path
# (exec/write.py, WriteSink implementations) shares one encoder with
# the legacy host-side VALUES path; these aliases keep engine-internal
# call sites and test imports stable
from trino_tpu.connectors.base import (  # noqa: E402
    _elem_storage,
    rows_to_columns as _rows_to_columns,
    to_unscaled as _to_unscaled,
)


def _literal_value(e: ast.Expr, t):
    """Evaluate an INSERT VALUES literal expression host-side."""
    if isinstance(e, ast.NullLit):
        return None
    if isinstance(e, (ast.IntLit, ast.FloatLit, ast.StrLit, ast.BoolLit)):
        return e.value
    if isinstance(e, ast.DecimalLit):
        from decimal import Decimal

        return Decimal(e.text)
    if isinstance(e, (ast.DateLit, ast.TimestampLit)):
        return e.text
    if (
        isinstance(e, ast.Unary)
        and e.op == "-"
        and isinstance(e.arg, (ast.IntLit, ast.FloatLit))
    ):
        return -e.arg.value
    if (
        isinstance(e, ast.Unary)
        and e.op == "-"
        and isinstance(e.arg, ast.DecimalLit)
    ):
        from decimal import Decimal

        return -Decimal(e.arg.text)
    if isinstance(e, ast.ArrayLit):
        from trino_tpu import types as T

        elem = t.element if isinstance(t, T.ArrayType) else None
        return [_literal_value(x, elem) for x in e.items]
    if isinstance(e, ast.FnCall) and e.name.lower() == "map":
        from trino_tpu import types as T

        if not (
            isinstance(t, T.MapType)
            and len(e.args) == 2
            and all(isinstance(a, ast.ArrayLit) for a in e.args)
        ):
            raise NotImplementedError(
                "INSERT map() takes (ARRAY[...], ARRAY[...])"
            )
        ks = [_literal_value(x, t.key) for x in e.args[0].items]
        vs = [_literal_value(x, t.value) for x in e.args[1].items]
        if len(ks) != len(vs):
            raise ValueError("map() key/value arrays differ in length")
        if len(set(ks)) != len(ks):
            # same rule as the analyzer's map constructor — INSERT
            # must not silently keep-first what SELECT rejects
            raise ValueError("Duplicate map keys are not allowed")
        return list(zip(ks, vs))
    if isinstance(e, ast.FnCall) and e.name.lower() == "row":
        from trino_tpu import types as T

        if not isinstance(t, T.RowType) or len(e.args) != len(t.fields):
            raise NotImplementedError(
                "INSERT row() arity must match the ROW type"
            )
        return tuple(
            _literal_value(x, ft) for x, (_fn, ft) in zip(e.args, t.fields)
        )
    raise NotImplementedError(
        f"INSERT VALUES supports literals only, got {type(e).__name__}"
    )


def _has_order(plan: P.PlanNode) -> bool:
    node = plan
    while isinstance(node, (P.Output, P.Limit, P.Project)):
        node = node.sources[0]
    return isinstance(node, (P.Sort, P.TopN))


def _write_handle(plan: P.PlanNode) -> dict | None:
    """The write handle of a TableFinish-rooted (DML) plan, else None.
    Write plans are never result-cached and commit with the statement
    epoch as idempotency token."""
    node = plan
    while isinstance(node, (P.Output, P.Exchange)):
        node = node.sources[0]
    if isinstance(node, P.TableFinish):
        return node.handle
    return None
