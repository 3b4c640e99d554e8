"""Coordinator: HTTP statement protocol + query lifecycle.

The analog of the reference's dispatch/protocol layer:

- ``POST /v1/statement`` submits SQL and returns the first protocol
  response with a ``nextUri`` (QueuedStatementResource.postStatement,
  MAIN/dispatcher/QueuedStatementResource.java:158);
- ``GET /v1/statement/executing/{id}/{slug}/{token}`` pages results
  (ExecutingStatementResource,
  MAIN/server/protocol/ExecutingStatementResource.java:71) — each
  response carries a batch of rows and the next token's URI until the
  query drains;
- ``DELETE`` on the same URI cancels
- ``GET /v1/info`` / ``GET /v1/queries`` expose server/query state
  (QueryResource analog, MAIN/server/QueryResource.java).

The lifecycle mirrors QueryStateMachine's QUEUED -> RUNNING ->
FINISHED/FAILED states (MAIN/execution/QueryStateMachine.java) with a
worker thread per query (dispatch is cheap here: the heavy lifting is
device execution, serialized through the engine's executor).
"""

from __future__ import annotations

import json
import secrets
import threading
import time
import traceback
import uuid
from dataclasses import dataclass, field
from decimal import Decimal
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from trino_tpu import profiler, session_properties as sp, telemetry
from trino_tpu.engine import QueryResult, QueryRunner
from trino_tpu.exec import scan_cache
from trino_tpu.tracker import QueryTracker

__all__ = ["Coordinator"]

#: rows per protocol page (the reference targets bytes; rows are fine
#: for a first protocol cut)
PAGE_ROWS = 4096

#: typed failures surface through /v1/statement with DISTINCT error
#: codes + names (the reference's StandardErrorCode registry,
#: SPI/StandardErrorCode.java) — a client can tell a reaped deadline
#: from an exhausted retry tier from a plain cancel without parsing
#: message prose. Code 1 = GENERIC_INTERNAL_ERROR fallback.
ERROR_CODES = {
    "QueryDeadlineExceededError": (131, "EXCEEDED_TIME_LIMIT"),
    "QueryRetriesExhaustedError": (132, "QUERY_RETRIES_EXHAUSTED"),
    "QueryCancelled": (130, "USER_CANCELED"),
    "ExceededMemoryLimitError": (133, "EXCEEDED_MEMORY_LIMIT"),
    "InsufficientResourcesError": (134, "INSUFFICIENT_RESOURCES"),
    # a restarted coordinator could not resume the query (not
    # journaled as fault-tolerant): the statement was fine, resubmit
    "CoordinatorRestartedError": (135, "COORDINATOR_RESTARTED"),
    # cluster-wide sliding-window retry budget spent (retry_budget)
    "RetryBudgetExhaustedError": (136, "RETRY_BUDGET_EXHAUSTED"),
}


def error_payload(error: str | None) -> dict:
    name = (error or "").split(":", 1)[0].strip()
    code, error_name = ERROR_CODES.get(
        name, (1, "GENERIC_INTERNAL_ERROR")
    )
    return {
        "message": error or "unknown error",
        "errorCode": code,
        "errorName": error_name,
    }


@dataclass
class QueryState:
    query_id: str
    slug: str
    sql: str
    state: str = "QUEUED"  # QUEUED | RUNNING | FINISHED | FAILED
    user: str = "user"
    resource_group: str = "global"
    result: QueryResult | None = None
    error: str | None = None
    error_detail: str | None = None  # server-side traceback
    created_at: float = field(default_factory=time.time)
    #: RUNNING transition time (execution-deadline epoch)
    started_at: float | None = None
    finished_at: float | None = None
    cancelled: bool = False
    #: cooperative cancellation signal checked by the executor
    cancel_event: object = field(default_factory=threading.Event)
    #: deadline limits captured from session properties at submit
    #: (0 = unlimited); the QueryTracker reaper enforces them
    max_queued_s: float = 0.0
    max_exec_s: float = 0.0
    #: the statement's span tree (telemetry.Tracer), opened in submit
    #: and sealed at the terminal state; lives and goes with this entry
    tracer: object = None
    #: the sealed tree's totals by span name (telemetry.span_totals),
    #: flat fields of this statement's ``GET /v1/query`` row
    span_totals: dict | None = None
    #: the sealed tree's planning-kind time (``planningTimeMillis``)
    planning_ms: float | None = None
    #: page tokens delivered once already: a page fetched again adds
    #: no ``respond`` span to the sealed tree
    responded: set = field(default_factory=set)
    #: guards the sealed tree and ``span_totals``: HTTP handler threads
    #: add ``respond`` to them while others read them out
    span_lock: object = field(default_factory=threading.Lock)


class Coordinator:
    """Embedded coordinator server (TestingTrinoServer analog,
    MAIN/server/testing/TestingTrinoServer.java:141)."""

    def __init__(
        self, runner: QueryRunner | None = None, port: int = 0,
        resource_groups=None, journal=None,
    ):
        from trino_tpu.server.resource_groups import ResourceGroupManager

        self.runner = runner or QueryRunner.tpch("tiny")
        #: durable query journal shared with a journal-wired fleet
        #: runner; recover() replays it, submit() WALs client records
        self.journal = journal or getattr(self.runner, "journal", None)
        self._queries: dict[str, QueryState] = {}
        self._lock = threading.Lock()
        #: query-state transitions notify this condition so protocol
        #: threads parked in await_page() wake immediately (the reference's
        #: asyncResponse completion, not a sleep-poll)
        self._state_cond = threading.Condition()
        self._seq = 0
        #: finished queries stay fetchable at least this long
        self.history_grace_s = 60.0
        #: admission control (InternalResourceGroupManager analog).
        #: A serving runner carries its own manager (fair-share weights
        #: feed fleet-slot dispatch) — adopt it so admission and slot
        #: scheduling read one group tree, like the reference where
        #: DispatchManager and the scheduler share one
        #: InternalResourceGroupManager.
        self.resource_groups = (
            resource_groups
            or getattr(self.runner, "resource_groups", None)
            or ResourceGroupManager()
        )
        #: cluster-wide memory view (ClusterMemoryManager analog): in
        #: the embedded single-node shape it observes the local pool
        #: after every statement; a serving/fleet-backed coordinator
        #: shares the runner's manager, which is fed worker snapshots
        from trino_tpu.memory import ClusterMemoryManager

        self.cluster_memory = (
            getattr(self.runner, "cluster_memory", None)
            or ClusterMemoryManager()
        )
        #: deadline governance: background reaper enforcing
        #: query_max_queued_time / query_max_execution_time
        #: (MAIN/execution/QueryTracker.java enforceTimeLimits analog)
        self.query_tracker = QueryTracker(self)
        #: cluster time-series recorder — constructed in start() ONLY
        #: when TRINO_TPU_TIMESERIES_INTERVAL_MS enables it (None =
        #: disabled = no background scrape thread exists at all)
        self.timeseries = None
        #: live cluster membership (elastic fleet): adopt the
        #: serving runner's registry when it wired one in, else own a
        #: fresh one — workers started with --coordinator PUT
        #: /v1/announce here either way
        from trino_tpu.membership import MembershipRegistry

        self.membership = (
            getattr(self.runner, "membership", None)
            or MembershipRegistry()
        )
        # system.runtime tables over live coordinator state
        # (MAIN/connector/system/ analog)
        from trino_tpu.connectors.system import SystemConnector

        self.runner.metadata.register_catalog(
            "system", SystemConnector(coordinator=self, runner=self.runner)
        )
        coordinator = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # quiet
                pass

            def _send(self, code: int, payload: dict | None):
                if code == 204 or payload is None:
                    self.send_response(code)
                    self.end_headers()
                    return
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_PUT(self):
                path, _, _ = self.path.partition("?")
                if path != "/v1/announce":
                    self._send(404, {"error": "not found"})
                    return
                n = int(self.headers.get("Content-Length", "0"))
                try:
                    req = json.loads(self.rfile.read(n).decode())
                except (ValueError, UnicodeDecodeError):
                    self._send(400, {"error": "bad announce body"})
                    return
                node_id = str(req.get("node_id") or "").strip()
                uri = str(req.get("uri") or "").strip()
                if not node_id or not uri:
                    self._send(
                        400, {"error": "node_id and uri required"}
                    )
                    return
                self._send(200, coordinator.membership.announce(
                    node_id,
                    uri,
                    state=str(req.get("state") or "ACTIVE"),
                    active_tasks=int(req.get("active_tasks") or 0),
                ))

            def do_POST(self):
                path, _, query = self.path.partition("?")
                if path == "/v1/profile":
                    # kernel observatory: blocking device-profile
                    # capture on the coordinator process (local/mesh
                    # executors run in-process here)
                    from urllib.parse import parse_qs

                    from trino_tpu import kernel_profile

                    dur = (
                        parse_qs(query).get("duration_ms") or [500]
                    )[0]
                    try:
                        dur = float(dur)
                    except (TypeError, ValueError):
                        self._send(400, {"error": "bad duration_ms"})
                        return
                    out = kernel_profile.capture_for(
                        dur, trigger="endpoint"
                    )
                    self._send(200 if "error" not in out else 409, out)
                    return
                if self.path != "/v1/statement":
                    self._send(404, {"error": "not found"})
                    return
                n = int(self.headers.get("Content-Length", "0"))
                sql = self.rfile.read(n).decode()
                user = self.headers.get("X-Trino-User") or "user"
                q = coordinator.submit(sql, user=user)
                coordinator.respond(
                    q, 0, self._base(), lambda p: self._send(200, p)
                )

            def do_GET(self):
                parts = self.path.strip("/").split("/")
                if self.path == "/v1/metrics":
                    # Prometheus text exposition (the reference's
                    # /v1/status JMX surface, flattened): query states,
                    # retry/speculation counters, memory gauges, RPC
                    # latency histograms
                    telemetry.refresh_process_gauges(node="coordinator")
                    body = telemetry.REGISTRY.render().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if self.path == "/v1/info":
                    self._send(200, {
                        "nodeVersion": {"version": "trino-tpu-0.1"},
                        "coordinator": True,
                        "starting": False,
                        **profiler.device_info(),
                        "resident_tables": scan_cache.SHARED.describe(),
                    })
                    return
                if self.path == "/v1/queries":
                    self._send(200, coordinator.list_queries())
                    return
                if self.path == "/v1/query":
                    # live QueryInfo list (QueryResource analog): one
                    # light row per known query
                    self._send(200, coordinator.query_info_list())
                    return
                if self.path.split("?")[0] == "/v1/history":
                    # the performance sentry's durable query history
                    # (most-recent-last; ?limit=N bounds the tail)
                    from trino_tpu import history as history_mod

                    limit = None
                    if "?" in self.path:
                        from urllib.parse import parse_qs

                        qs = parse_qs(self.path.split("?", 1)[1])
                        if qs.get("limit"):
                            try:
                                limit = int(qs["limit"][0])
                            except ValueError:
                                limit = None
                    store = history_mod.active()
                    self._send(200, {
                        "entries": store.entries(limit=limit),
                        "total": len(store),
                        "durable": store.path is not None,
                    })
                    return
                if self.path == "/v1/anomalies":
                    # typed AnomalyVerdicts the sentry has emitted
                    from trino_tpu import sentry as sentry_mod

                    sen = sentry_mod.active()
                    self._send(200, {
                        "anomalies": [
                            v.to_dict() for v in sen.anomalies()
                        ],
                        "baselines": sen.baseline_count(),
                    })
                    return
                if self.path == "/v1/cluster/timeseries":
                    # the bounded metric ring the background recorder
                    # keeps (404 when time-series is disabled — no
                    # recorder means no thread AND no endpoint)
                    rec = coordinator.timeseries
                    if rec is None:
                        self._send(
                            404, {"error": "time-series disabled"}
                        )
                    else:
                        self._send(200, {
                            "interval_ms": rec.interval_ms,
                            "samples": rec.samples(),
                        })
                    return
                if (
                    len(parts) == 4
                    and parts[:2] == ["v1", "query"]
                    and parts[3] == "diagnostics"
                ):
                    # post-mortem bundle of a failed query (404 while
                    # it runs, succeeds, or after retention sweeps it)
                    from trino_tpu import tracker as _tracker

                    bundle = _tracker.QUERY_INFO.get_diagnostics(
                        parts[2]
                    )
                    if bundle is None:
                        self._send(
                            404, {"error": "no diagnostics bundle"}
                        )
                    else:
                        self._send(200, bundle)
                    return
                if len(parts) == 3 and parts[:2] == ["v1", "query"]:
                    # full stage -> task -> operator tree, served live
                    # while the query is still running
                    info = coordinator.query_info(parts[2])
                    if info is None:
                        self._send(404, {"error": "query not found"})
                    else:
                        self._send(200, info)
                    return
                if parts == ["v1", "programs"]:
                    # compiled-program catalog (kernel observatory):
                    # same payload system.runtime.programs serves
                    from trino_tpu import program_catalog

                    self._send(200, {
                        "programs": program_catalog.CATALOG.snapshot(),
                    })
                    return
                if (
                    len(parts) == 3
                    and parts[:2] == ["v1", "programs"]
                ):
                    from trino_tpu import program_catalog

                    e = program_catalog.CATALOG.get(parts[2])
                    if e is None:
                        self._send(404, {"error": "no such program"})
                    else:
                        self._send(200, e.to_dict(include_hlo=True))
                    return
                if (
                    len(parts) == 6
                    and parts[:3] == ["v1", "statement", "executing"]
                ):
                    _, _, _, qid, slug, token = parts
                    q = coordinator.await_page(qid, slug)
                    if q is None:
                        self._send(404, {"error": "query not found"})
                    else:
                        coordinator.respond(
                            q, int(token), self._base(),
                            lambda p: self._send(200, p),
                        )
                    return
                self._send(404, {"error": "not found"})

            def do_DELETE(self):
                parts = self.path.strip("/").split("/")
                if (
                    len(parts) == 6
                    and parts[:3] == ["v1", "statement", "executing"]
                ):
                    coordinator.cancel(parts[3])
                    self._send(204, None)
                    return
                self._send(404, {"error": "not found"})

            def _base(self) -> str:
                host = self.headers.get("Host") or "localhost"
                return f"http://{host}"

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread: threading.Thread | None = None

    # ---- lifecycle -------------------------------------------------------

    def start(self) -> "Coordinator":
        import os

        if os.environ.get("TRINO_TPU_PREWARM", "") not in ("", "0"):
            # trace-compile the canonical bucket set before serving
            # (persistent-cache-backed: warm machines deserialize
            # instead of compiling; off by default for fast test spins)
            from trino_tpu.exec import shapes

            shapes.prewarm()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        self.query_tracker.start()
        from trino_tpu import telemetry_analysis

        self.timeseries = telemetry_analysis.ClusterTimeseriesRecorder.from_env(
            # live-resolved so fleet worker eviction/readmission is
            # reflected scrape-to-scrape; a local runner has no workers
            lambda: [
                w.uri
                for w in getattr(self.runner, "workers", ()) or ()
                if getattr(w, "alive", True)
            ]
        )
        if self.timeseries is not None:
            self.timeseries.start()
            telemetry_analysis.set_active_recorder(self.timeseries)
        return self

    def stop(self):
        if self.timeseries is not None:
            from trino_tpu import telemetry_analysis

            self.timeseries.stop()
            if telemetry_analysis.active_recorder() is self.timeseries:
                telemetry_analysis.set_active_recorder(None)
            self.timeseries = None
        self.query_tracker.stop()
        self._httpd.shutdown()
        self._httpd.server_close()

    def recover(self) -> dict:
        """Replay the durable query journal after a restart — call
        between construction and :meth:`start` (connections arriving
        in between queue in the listen backlog, so clients never see
        a half-recovered coordinator).

        Per journaled query:

        - terminal (``done`` record): rehydrate its registry row —
          ``system.runtime.queries`` / ``GET /v1/query/{id}`` and any
          failure post-mortem bundle survive the restart, flagged
          ``recovered=true``. Result pages are NOT journaled, so the
          old protocol URI does not come back for finished queries.
        - RUNNING + fault-tolerant (``retry_policy`` TASK/QUERY with a
          spool epoch): re-registered at its OLD qid+slug protocol URI
          and resumed on a background thread — committed spool
          attempts are inherited, live worker attempts adopted, only
          the in-flight tail re-dispatched.
        - RUNNING but not resumable (retry_policy=NONE, or an
          unreadable journal): failed typed COORDINATOR_RESTARTED at
          its old URI; the statement was fine — resubmission is the
          client's remedy.

        Returns ``{"resumed": n, "rehydrated": n, "unresumable": n}``.
        """
        from trino_tpu import telemetry, tracker

        counts = {"resumed": 0, "rehydrated": 0, "unresumable": 0}
        if self.journal is None:
            return counts
        to_resume = []
        for e in self.journal.scan():
            if e.done is not None:
                tracker.QUERY_INFO.rehydrate(
                    e.query_id,
                    state=e.done.get("state", "FINISHED"),
                    sql=e.sql,
                    user=(e.begin or e.client or {}).get("user"),
                    rows=e.done.get("rows"),
                    error=e.done.get("error"),
                    elapsed_ms=e.done.get("elapsed_ms", 0.0),
                    diagnostics=e.done.get("diagnostics"),
                )
                counts["rehydrated"] += 1
                telemetry.QUERIES_RECOVERED.inc(outcome="rehydrated")
                continue
            q = QueryState(
                query_id=e.query_id,
                slug=(e.client or {}).get("slug") or secrets.token_hex(8),
                sql=e.sql or "",
                user=str((e.begin or e.client or {}).get("user") or "user"),
            )
            tracker.QUERY_INFO.mark_recovered(e.query_id)
            if e.resumable and hasattr(self.runner, "resume"):
                with self._lock:
                    self._queries[e.query_id] = q
                to_resume.append((q, e))
                counts["resumed"] += 1
                telemetry.QUERIES_RECOVERED.inc(outcome="resumed")
            else:
                q.state = "FAILED"
                q.error = (
                    "CoordinatorRestartedError: the coordinator "
                    "restarted and cannot resume this query "
                    f"(retry_policy="
                    f"{(e.begin or {}).get('retry_policy', 'NONE')}); "
                    "resubmit the statement"
                )
                q.finished_at = time.time()
                with self._lock:
                    self._queries[e.query_id] = q
                tracker.QUERY_INFO.rehydrate(
                    e.query_id, state="FAILED", sql=q.sql, user=q.user,
                    error=q.error,
                )
                try:
                    # terminal WAL record: the NEXT restart rehydrates
                    # this as history instead of re-failing it
                    self.journal.finish(
                        e.query_id, state="FAILED", error=q.error,
                    )
                except Exception:
                    pass
                counts["unresumable"] += 1
                telemetry.QUERIES_RECOVERED.inc(outcome="unresumable")

        def run_resumes():
            # sequential: the fleet runner executes one statement at a
            # time; clients long-poll their old URIs meanwhile
            for q, e in to_resume:
                q.state = "RUNNING"
                q.started_at = time.time()
                self._signal_state()
                try:
                    result = self.runner.resume(e)
                    q.result = result
                    q.state = "FINISHED"
                except Exception as exc:
                    if q.error is None:
                        q.error = f"{type(exc).__name__}: {exc}"
                        q.error_detail = traceback.format_exc()
                    q.state = "FAILED"
                q.finished_at = time.time()
                self._signal_state()

        if to_resume:
            threading.Thread(
                target=run_resumes, name="journal-resume", daemon=True,
            ).start()
        return counts

    @property
    def uri(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def _signal_state(self) -> None:
        """Wake every protocol thread blocked in ``await_page()``. Called on
        every query-state transition (run(), cancel(), the reaper)."""
        with self._state_cond:
            self._state_cond.notify_all()

    # ---- query management ------------------------------------------------

    def submit(self, sql: str, user: str = "user") -> QueryState:
        from trino_tpu.server.resource_groups import (
            QueryQueueFullError,
            QueryRejectedError,
        )

        with self._lock:
            self._seq += 1
            qid = f"{time.strftime('%Y%m%d_%H%M%S')}_{self._seq:05d}_{uuid.uuid4().hex[:5]}"
        q = QueryState(
            query_id=qid, slug=secrets.token_hex(8), sql=sql, user=user,
        )
        # the statement's span tree starts here, before the queue; the
        # runner adds its spans to it and _seal closes it
        q.tracer = telemetry.Tracer(qid, root_name="statement")
        queued = q.tracer.start("queued")
        if self.journal is not None:
            # WAL the protocol identity (qid + slug) so a restarted
            # coordinator can re-serve this query at its old
            # /v1/statement/executing/{qid}/{slug}/{token} URI.
            # Best-effort: an unjournalable query still runs — it just
            # cannot survive a restart (the fleet's own begin/epoch
            # appends are the hard chaos seam).
            try:
                self.journal.note_client(qid, q.slug, user, sql)
            except Exception:
                pass
        # capture deadline limits at submit time so the reaper enforces
        # the session the query was dispatched under, not whatever the
        # session mutates to later
        q.max_queued_s = sp.parse_duration(
            sp.get(self.runner.session, "query_max_queued_time")
        )
        q.max_exec_s = sp.parse_duration(
            sp.get(self.runner.session, "query_max_execution_time")
        )
        # admission (resource groups): selection + queue-full fail-fast
        # happen BEFORE the dispatch thread exists (DispatchManager ->
        # resource-group queueing, MAIN/dispatcher/DispatchManager.java:146)
        try:
            group = self.resource_groups.select(user)
            q.resource_group = group.name
            admitted = self.resource_groups.enqueue(group, qid)
        except (QueryQueueFullError, QueryRejectedError) as e:
            q.state = "FAILED"
            q.error = f"{type(e).__name__}: {e}"
            q.finished_at = time.time()
            self._seal(q)
            with self._lock:
                self._queries[qid] = q
            return q
        with self._lock:
            self._queries[qid] = q
            # bounded history: release old finished results (the
            # reference's QueryTracker min-age expiration,
            # MAIN/execution/QueryTracker.java). A grace period keeps a
            # finished query alive while a slow client is still
            # paginating its resultset — evicting it mid-pagination
            # would surface a spurious 404.
            if len(self._queries) > 200:
                now = time.time()
                done = [
                    k for k, v in self._queries.items()
                    if v.state in ("FINISHED", "FAILED")
                    and v.finished_at is not None
                    and now - v.finished_at > self.history_grace_s
                ]
                for k in done[: len(self._queries) - 200]:
                    del self._queries[k]
            if len(self._queries) > 2000:
                # hard bound: under burst load the grace period alone
                # would let resultset-holding entries grow unboundedly;
                # evict oldest finished regardless of age
                done = sorted(
                    (
                        k for k, v in self._queries.items()
                        if v.finished_at is not None
                    ),
                    key=lambda k: self._queries[k].finished_at,
                )
                for k in done[: len(self._queries) - 2000]:
                    del self._queries[k]

        def run():
            # wait for a running slot (FIFO within the group; immediate
            # when admission already granted one at submit)
            got_slot = self.resource_groups.acquire(
                group, qid, lambda: q.cancelled, admitted=admitted
            )
            queued.finish()
            if not got_slot:
                # the reaper (queued-deadline) and DELETE both set
                # cancelled — keep whichever typed error got there first
                q.state = "FAILED"
                if q.error is None:
                    q.error = "Query was canceled while queued"
                q.finished_at = time.time()
                self._seal(q)
                self._signal_state()
                return
            try:
                if q.cancelled:
                    q.state = "FAILED"
                    if q.error is None:
                        q.error = "Query was canceled while queued"
                    q.finished_at = time.time()
                    self._seal(q)
                    self._signal_state()
                    return
                q.state = "RUNNING"
                q.started_at = time.time()
                self._signal_state()
                try:
                    # cooperative cancellation: DELETE sets the event
                    # and the executor aborts at its next boundary
                    # the coordinator's id IS the runner's id: live
                    # QueryInfo published under it joins QueryState
                    # (tests substitute runners whose execute() has no
                    # query_id parameter — probe before passing it;
                    # same probe for user=, which a serving runner
                    # consumes for per-identity group selection)
                    kwargs = {"cancel_event": q.cancel_event}
                    try:
                        import inspect

                        params = inspect.signature(
                            self.runner.execute
                        ).parameters
                        if "query_id" in params:
                            kwargs["query_id"] = q.query_id
                        if "user" in params:
                            kwargs["user"] = q.user
                        # this thread already holds a resource-group
                        # running slot (acquired above, same adopted
                        # manager) — a serving runner must not gate a
                        # second time
                        if "admitted" in params:
                            kwargs["admitted"] = True
                        # the embedded runner adds its spans to the
                        # statement's tree; a runner that builds a
                        # tree of its own (the fleet's, per execution
                        # attempt) has it hung under the statement
                        if "tracer" in params:
                            kwargs["tracer"] = q.tracer
                    except (TypeError, ValueError):
                        pass
                    result = self.runner.execute(sql, **kwargs)
                    own = getattr(result, "trace", None)
                    if own is not None and own.root is not q.tracer.root:
                        own.root.parent_id = q.tracer.root.span_id
                        q.tracer.root.children.append(own.root)
                    # the tree is sealed BEFORE the state says so: a
                    # client that sees FINISHED finds the totals
                    self._seal(q)
                    if q.cancelled or q.state == "FAILED":
                        q.state = "FAILED"
                    else:
                        q.result = result
                        q.state = "FINISHED"
                except Exception as e:  # surfaces through the protocol
                    # never clobber a reaper-set typed deadline error
                    # with the generic unwind exception it provoked
                    if q.error is None:
                        q.error = f"{type(e).__name__}: {e}"
                        q.error_detail = traceback.format_exc()
                    self._seal(q)
                    q.state = "FAILED"
                    q.result = None
                # a FleetRunner-backed coordinator has no local
                # executor; its pools arrive via task-status snapshots
                pool = getattr(
                    getattr(self.runner, "executor", None),
                    "memory_pool", None,
                )
                if pool is not None:
                    self.cluster_memory.observe(
                        pool.node_id, pool.snapshot()
                    )
                if q.finished_at is None:
                    q.finished_at = time.time()
            finally:
                self.resource_groups.release(group)
                self._signal_state()

        threading.Thread(target=run, daemon=True).start()
        return q

    #: the flat span fields every sealed statement's row carries, as
    #: numbers, whether or not its tree holds such a span
    SPAN_FIELDS = (
        "runner_wait_ms", "parse_ms", "plan_ms", "execute_ms",
        "build_trace_ms", "host_sync_ms", "host_syncs", "dispatches",
        "upload_ms", "to_rows_ms", "respond_ms", "rows_out_ms",
        "direct_groupbys", "sorted_groupbys", "streamed_groupbys",
        "groupby_start_walks",
        "small_build_joins", "sorted_joins", "narrow_key_joins",
        "wide_key_joins", "outer_joins", "anti_joins",
        "distinct_aggregates", "revoked_joins", "join_revoked_ms",
        "compactions", "compact_gather_ops",
        "mesh_exchanges", "mesh_exchange_ms", "mesh_exchange_live_bytes",
        "mesh_exchange_buffer_bytes", "mesh_gather_ms", "mesh_upload_ms",
        "mesh_exchanges_in_place",
        # a fleet statement's: the stage scheduler's spans and the
        # workers' task subtrees stitched under them (0 where the
        # runner is embedded, or the statement had no such span)
        "stage_ms", "rpc_ms", "task_poll_wait_ms", "task_queue_wait_ms",
        "spool_read_ms", "spool_write_ms", "split_scan_ms",
        "resident_split_scans",
    )

    def _seal(self, q: QueryState) -> None:
        """Close the statement's tree (its terminal state is reached)
        and put its totals by span name on the statement's row."""
        root = q.tracer.finish().root
        for sp_ in root.walk():
            sp_.finish()  # what a failure or a cancel left open
        totals = dict.fromkeys(self.SPAN_FIELDS, 0.0)
        totals.update(telemetry.span_totals(root))
        totals.pop("queued_ms", None)  # served as queued_time_ms
        totals["rows_out_ms"] = totals["to_rows_ms"]
        q.planning_ms = _planning_ms(root.walk())
        q.span_totals = totals

    def respond(self, q: QueryState, token: int, base: str, send) -> None:
        """Build one protocol page and hand it to ``send``. A page that
        carries columns or data is the statement's last layer: its
        ``respond`` span joins the sealed tree (after ``statement``
        closed, on the handler's thread) and the row's totals."""
        sp = None
        if q.state == "FINISHED" and q.result is not None and q.span_totals:
            # (a statement rehydrated from the journal has no tree)
            with q.span_lock:
                if token not in q.responded:  # its first delivery only
                    q.responded.add(token)
                    sp = q.tracer.root.child("respond")
        try:
            send(self.proto_response(q, token, base))
        finally:
            if sp is not None:
                sp.finish()
                with q.span_lock:
                    t = q.span_totals
                    t["respond_ms"] += sp.duration_ms
                    t["rows_out_ms"] = t["to_rows_ms"] + t["respond_ms"]

    def cancel(self, qid: str):
        q = self._queries.get(qid)
        if q is not None:
            q.cancelled = True
            q.cancel_event.set()
            if q.state in ("QUEUED", "RUNNING"):
                q.state = "FAILED"
                if q.error is None:
                    q.error = "QueryCancelled: Query was canceled"
                q.finished_at = time.time()
            # a queued query's dispatch thread is parked on the
            # resource-group condition variable — poke it so the cancel
            # takes effect now, not at the next poll tick
            self.resource_groups.wakeup()
            self._signal_state()

    def query_info_list(self) -> list[dict]:
        """``GET /v1/query``: one light row per known query, joining
        coordinator lifecycle state with the live registry's runtime
        stats (rows, peak memory). Queries executed through a runner
        directly (no QueryState) still appear from the registry."""
        from trino_tpu import tracker

        live = {r["query_id"]: r for r in tracker.QUERY_INFO.list()}
        with self._lock:
            snapshot = list(self._queries.values())
        out = []
        for q in snapshot:
            r = live.pop(q.query_id, None) or {}
            # time spent QUEUED: until the RUNNING transition, or (for
            # queries that died in the queue) until the terminal time;
            # still-QUEUED queries report a live, growing value
            queued_end = q.started_at or q.finished_at or time.time()
            out.append({
                "query_id": q.query_id,
                "state": q.state,
                "user": q.user,
                "query": q.sql,
                "resource_group": q.resource_group,
                "elapsed_ms": round(
                    ((q.finished_at or time.time()) - q.created_at)
                    * 1e3, 3,
                ),
                "queued_time_ms": round(
                    (queued_end - q.created_at) * 1e3, 3
                ),
                "peak_memory_bytes": r.get("peak_memory_bytes", 0),
                "rows": r.get("rows"),
                "error": q.error,
                # the sealed span tree's totals by span name
                **self._span_totals(q),
            })
        out.extend(live.values())
        return out

    def query_info(self, qid: str) -> dict | None:
        """``GET /v1/query/{id}``: the full stage → task → operator
        JSON tree. Coordinator lifecycle state overrides the
        registry's (it is authoritative for QUEUED/cancel races)."""
        from trino_tpu import tracker

        info = tracker.QUERY_INFO.get(qid)
        q = self._queries.get(qid)
        if info is None and q is None:
            return None
        if info is None:
            info = {
                "query_id": qid, "state": q.state, "user": q.user,
                "sql": q.sql, "elapsed_ms": round(
                    ((q.finished_at or time.time()) - q.created_at)
                    * 1e3, 3,
                ),
                "peak_memory_bytes": 0, "rows": None,
                "error": q.error, "stages": [],
            }
        elif q is not None:
            info["state"] = q.state
            info["user"] = q.user
            if q.error:
                info["error"] = q.error
        if q is not None:
            info["resource_group"] = q.resource_group
            info["queued_time_ms"] = round(
                ((q.started_at or q.finished_at or time.time())
                 - q.created_at) * 1e3, 3,
            )
            if q.tracer is not None:
                # the statement's span tree, as far as it has got
                with q.span_lock:
                    info["spans"] = q.tracer.root.to_dict()
                info.update(self._span_totals(q))
        return info

    @staticmethod
    def _span_totals(q: QueryState) -> dict:
        with q.span_lock:
            return dict(q.span_totals or {})

    def list_queries(self) -> list[dict]:
        with self._lock:
            snapshot = list(self._queries.values())
        return [
            {
                "queryId": q.query_id,
                "state": q.state,
                "query": q.sql,
                "user": q.user,
                "resourceGroup": q.resource_group,
                "error": q.error,
                "errorDetail": q.error_detail,
            }
            for q in snapshot
        ]

    # ---- protocol responses ----------------------------------------------

    def await_page(self, qid: str, slug: str) -> QueryState | None:
        """The statement a page request names, once it has left QUEUED
        and RUNNING or a second has passed; None for an unknown one."""
        q = self._queries.get(qid)
        if q is None or q.slug != slug:
            return None
        # long-poll: wait server-side for a state transition like the
        # reference's asyncResponse (ExecutingStatementResource). The
        # condition is notified by run()/cancel()/the reaper, so a
        # finishing query releases its waiting client immediately —
        # under high concurrency the old 10 ms sleep-poll added a
        # half-tick of latency per page to every client.
        deadline = time.time() + 1.0
        with self._state_cond:
            while q.state in ("QUEUED", "RUNNING"):
                remaining = deadline - time.time()
                if remaining <= 0:
                    break
                self._state_cond.wait(timeout=remaining)
        return q

    def proto_response(self, q: QueryState, token: int, base: str) -> dict:
        uri = f"{base}/v1/statement/executing/{q.query_id}/{q.slug}"
        resp = {
            "id": q.query_id,
            "infoUri": f"{base}/v1/queries",
            "stats": {
                "state": q.state,
                "queued": q.state == "QUEUED",
                "elapsedTimeMillis": int(
                    ((q.finished_at or time.time()) - q.created_at) * 1e3
                ),
                "queuedTimeMillis": int(
                    ((q.started_at or q.finished_at or time.time())
                     - q.created_at) * 1e3
                ),
                # the sealed tree's planning-kind spans (``plan``; a
                # fleet attempt's ``planning``); before the seal, those
                # the runner has put under the root so far
                "planningTimeMillis": int(
                    q.planning_ms if q.planning_ms is not None
                    else _planning_ms(
                        list(q.tracer.root.children) if q.tracer else ()
                    )
                ),
            },
        }
        if q.state == "FAILED":
            resp["error"] = error_payload(q.error)
            return resp
        if q.state in ("QUEUED", "RUNNING") or q.result is None:
            resp["nextUri"] = f"{uri}/{token}"
            return resp
        result = q.result
        lo = token * PAGE_ROWS
        hi = lo + PAGE_ROWS
        resp["columns"] = [
            {"name": n, "type": _proto_type(result, i)}
            for i, n in enumerate(result.names)
        ]
        chunk = result.rows[lo:hi]
        if chunk:
            resp["data"] = [[_json_value(v) for v in row] for row in chunk]
        if hi < len(result.rows):
            resp["nextUri"] = f"{uri}/{token + 1}"
        return resp


def _planning_ms(spans) -> float:
    return sum(sp.duration_ms for sp in spans if sp.kind == "planning")


def _proto_type(result: QueryResult, i: int) -> str:
    if result.plan is not None and i < len(result.plan.outputs):
        t = list(result.plan.outputs.values())[i]
        return str(t)
    # metadata statements carry strings/ints only
    for row in result.rows:
        v = row[i]
        if v is not None:
            if isinstance(v, bool):
                return "boolean"
            if isinstance(v, int):
                return "bigint"
            if isinstance(v, float):
                return "double"
            break
    return "varchar"


def _json_value(v):
    if isinstance(v, Decimal):
        return str(v)
    return v


def main():
    """Standalone coordinator daemon (``python -m trino_tpu.server.
    coordinator``): a fleet-backed coordinator with the durable query
    journal wired in. On startup it replays the journal — so a
    ``kill -9`` + restart with the SAME --spool resumes journaled
    FTE queries at their old protocol URIs. The recovery chaos
    harness and the recovery-smoke CI job drive exactly this entry
    point."""
    import argparse
    import os
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=8090)
    ap.add_argument(
        "--workers", default="",
        help="comma-separated worker base URIs (fleet mode; omit for "
             "a local embedded runner)",
    )
    ap.add_argument(
        "--spool", default=None,
        help="spool root directory; fleet mode stores the durable "
             "query journal under it (_journal/)",
    )
    ap.add_argument("--catalog", default="tpch")
    ap.add_argument("--schema", default="tiny")
    ap.add_argument("--n-partitions", type=int, default=4)
    ap.add_argument(
        "--mesh", type=int, default=None, metavar="N",
        help="embedded runner only: one process over a mesh of N "
             "devices, tables range-sharded in scan order over the "
             "mesh axis (default: the single-device executor)",
    )
    ap.add_argument(
        "--session", action="append", default=[], metavar="K=V",
        help="session property override (repeatable)",
    )
    args = ap.parse_args()
    journal = None
    if args.mesh is not None and (args.workers or args.mesh < 1):
        ap.error("--mesh N starts the embedded runner over N >= 1 devices; "
                 "it takes no --workers")
    if args.workers:
        from trino_tpu.connectors.tpch.connector import TpchConnector
        from trino_tpu.journal import QueryJournal
        from trino_tpu.metadata import Metadata, Session
        from trino_tpu.server.fleet import FleetRunner

        md = Metadata()
        if args.catalog == "tpcds":
            from trino_tpu.connectors.tpcds.connector import (
                TpcdsConnector,
            )

            md.register_catalog("tpcds", TpcdsConnector())
        else:
            md.register_catalog("tpch", TpchConnector())
        session = Session(catalog=args.catalog, schema=args.schema)
        for kv in args.session:
            k, _, v = kv.partition("=")
            sp.set_property(session, k.strip(), v.strip())
        spool_root = args.spool or os.path.join(
            os.getcwd(), "trino_tpu_spool"
        )
        os.makedirs(spool_root, exist_ok=True)
        journal = QueryJournal(spool_root)
        runner = FleetRunner(
            [u.strip() for u in args.workers.split(",") if u.strip()],
            md, session, spool_root=spool_root,
            n_partitions=args.n_partitions, journal=journal,
        )
    elif args.mesh is not None:
        from trino_tpu.parallel.core import make_mesh

        try:
            mesh = make_mesh(args.mesh)
        except ValueError as e:  # "need N devices, have M"
            sys.exit(f"--mesh {args.mesh}: {e}")
        runner = QueryRunner.tpch(args.schema, mesh=mesh)
    else:
        runner = QueryRunner.tpch(args.schema)
    coord = Coordinator(runner, port=args.port, journal=journal)
    if journal is not None:
        # replay BEFORE serving: clients connecting during recovery
        # queue in the listen backlog and see a consistent view
        counts = coord.recover()
        print(f"recovery: {counts}", flush=True)
    coord.start()
    print(f"coordinator ready on port {coord.port}", flush=True)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        coord.stop()
        sys.exit(0)


if __name__ == "__main__":
    main()
