"""Query deadline governance: typed lifecycle errors + the reaper.

The analog of the reference's QueryTracker.enforceTimeLimits
(MAIN/execution/QueryTracker.java): a coordinator-side daemon sweeps
the live query set on a short period and *reaps* any query past its
deadline — QUEUED past ``query_max_queued_time`` or RUNNING past
``query_max_execution_time`` — marking it FAILED with a typed
``QueryDeadlineExceededError`` and firing its cancel event. The sweep
is what makes deadlines robust: a cooperative check inside the engine
covers the well-behaved path, but a *wedged* query (stuck in a kernel,
a sleep, a hung RPC) never reaches its next boundary check, and only
an external reaper can retire it. The reaper marks the query FAILED
immediately — the protocol surfaces the deadline error to clients even
while the wedged thread is still unwinding.

Deadline failures are terminal by definition (more attempts cannot
create more time), so both FTE tiers classify
``QueryDeadlineExceededError`` non-retryable, and
``QueryRetriesExhaustedError`` marks the QUERY tier giving up.
"""

from __future__ import annotations

import threading
import time

__all__ = [
    "QueryDeadlineExceededError", "QueryRetriesExhaustedError",
    "QueryTracker", "QueryInfoRegistry", "QUERY_INFO",
]


class QueryDeadlineExceededError(RuntimeError):
    """Query exceeded query_max_execution_time /
    query_max_planning_time / query_max_queued_time
    (EXCEEDED_TIME_LIMIT analog — never retried by either FTE tier)."""


class QueryRetriesExhaustedError(RuntimeError):
    """The QUERY retry tier ran out of attempts (or budget) without a
    successful execution; carries the last underlying failure."""


class QueryTracker:
    """Deadline reaper over a coordinator's live queries.

    Reads each QueryState's ``max_queued_s`` / ``max_exec_s``
    (captured from session properties at submit) against its
    ``created_at`` / ``started_at`` timestamps. Reaping a query:
    state -> FAILED with the typed error string, cancel event set (so
    a cooperative executor aborts at its next boundary), cancelled
    flag set, and the resource-group condition notified so a QUEUED
    query's dispatch thread unblocks promptly instead of waiting for
    an unrelated release.
    """

    def __init__(self, coordinator, period_s: float = 0.05):
        self.coordinator = coordinator
        self.period_s = period_s
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        #: (query_id, reason) log of reaped queries
        self.reaped: list[tuple[str, str]] = []
        import os

        #: journal GC cadence/TTL: entries terminal longer than the
        #: TTL are removed on the next due sweep (PR 18 shipped gc();
        #: this is the caller that keeps _journal/ bounded)
        self.journal_gc_period_s = float(
            os.environ.get("TRINO_TPU_JOURNAL_GC_PERIOD_S", "")
            or 60.0
        )
        self.journal_ttl_s = float(
            os.environ.get("TRINO_TPU_JOURNAL_TTL_S", "")
            or 7 * 24 * 3600.0
        )
        self._journal_gc_due = time.time() + self.journal_gc_period_s

    def start(self) -> "QueryTracker":
        self._thread = threading.Thread(
            target=self._loop, name="query-tracker", daemon=True
        )
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def _loop(self):
        while not self._stop.wait(self.period_s):
            try:
                self.sweep()
            except Exception:
                pass  # the reaper must outlive any one bad sweep

    def sweep(self):
        """One enforcement pass (callable directly from tests)."""
        now = time.time()
        self._maybe_gc_journal(now)
        with self.coordinator._lock:
            queries = list(self.coordinator._queries.values())
        for q in queries:
            if q.state == "QUEUED":
                limit = getattr(q, "max_queued_s", 0.0)
                if limit and now - q.created_at > limit:
                    self._reap(
                        q,
                        f"Query exceeded maximum queued time limit "
                        f"of {limit:g}s",
                        "queued",
                    )
            elif q.state == "RUNNING":
                limit = getattr(q, "max_exec_s", 0.0)
                started = getattr(q, "started_at", None) or q.created_at
                if limit and now - started > limit:
                    self._reap(
                        q,
                        f"Query exceeded maximum execution time limit "
                        f"of {limit:g}s",
                        "execution",
                    )

    def _maybe_gc_journal(self, now: float, force: bool = False):
        """Rate-limited durable-journal GC riding the reaper sweep
        (its thread already exists and already swallows per-sweep
        errors). Terminal entries older than the TTL are dropped and
        counted in ``trino_journal_gc_removed_total``."""
        if not force and now < self._journal_gc_due:
            return
        self._journal_gc_due = now + self.journal_gc_period_s
        journal = getattr(self.coordinator, "journal", None)
        if journal is None:
            return
        removed = journal.gc(self.journal_ttl_s)
        if removed:
            from trino_tpu import telemetry

            telemetry.JOURNAL_GC_REMOVED.inc(removed)

    def _reap(self, q, message: str, reason: str):
        if q.state in ("FINISHED", "FAILED"):
            return
        q.error = f"QueryDeadlineExceededError: {message}"
        q.state = "FAILED"
        q.finished_at = time.time()
        q.cancelled = True
        q.cancel_event.set()
        self.reaped.append((q.query_id, reason))
        # a QUEUED query's dispatch thread is blocked in acquire();
        # poke the condition so it observes cancellation now
        wakeup = getattr(self.coordinator.resource_groups, "wakeup", None)
        if wakeup is not None:
            wakeup()
        # clients long-polling await_page() must see the reap immediately
        signal = getattr(self.coordinator, "_signal_state", None)
        if signal is not None:
            signal()


class QueryInfoRegistry:
    """Live QueryInfo trees: the registry behind ``GET /v1/query``.

    The analog of the reference coordinator's QueryTracker-as-registry
    role (MAIN/execution/QueryTracker.java holds the QueryInfo every
    UI/API surface reads): runners push per-task operator stats as
    FINISHED task-status responses arrive, so ``GET /v1/query/{id}``
    serves the stage → task → operator tree *while later stages are
    still running*. Finished queries stay visible for a retention
    window (``min.query.expire-age`` analog), then sweep.

    Thread-safe: the coordinator's HTTP threads read while runner
    threads write.
    """

    def __init__(self, retention_s: float = 300.0,
                 max_finished: int = 200):
        self.retention_s = retention_s
        self.max_finished = max_finished
        self._lock = threading.Lock()
        self._entries: dict[str, dict] = {}
        #: query ids inherited from a pre-restart coordinator (via the
        #: durable journal); their rows report recovered=True whether
        #: rehydrated terminal or resumed live — checked by _entry so
        #: the flag survives a begin() racing the recovery thread
        self._recovered_ids: set[str] = set()

    def _entry(self, query_id: str) -> dict:
        e = self._entries.get(query_id)
        if e is None:
            e = self._entries[query_id] = {
                "query_id": query_id,
                "state": "RUNNING",
                "user": None,
                "sql": None,
                "resource_group": None,
                "queued_ms": 0.0,
                "created_at": time.time(),
                "finished_at": None,
                "error": None,
                "rows": None,
                "peak_memory_bytes": 0,
                #: (stage_id, task_id, attempt) -> task row (with
                #: operator_stats); latest attempt wins per task
                "tasks": {},
                #: post-mortem diagnostic bundle (failed queries only)
                "diagnostics": None,
                #: True when this row crossed a coordinator restart
                #: (journal-rehydrated or journal-resumed)
                "recovered": query_id in self._recovered_ids,
            }
        return e

    def begin(self, query_id: str, sql: str | None = None,
              user: str | None = None,
              resource_group: str | None = None,
              queued_ms: float | None = None) -> None:
        if not query_id:
            return
        with self._lock:
            e = self._entry(query_id)
            if sql is not None:
                e["sql"] = sql
            if user is not None:
                e["user"] = user
            if resource_group is not None:
                e["resource_group"] = resource_group
            if queued_ms is not None:
                e["queued_ms"] = float(queued_ms)

    def update_task(self, query_id: str, task_row: dict) -> None:
        if not query_id:
            return
        with self._lock:
            e = self._entry(query_id)
            key = (
                str(task_row.get("stage_id")),
                str(task_row.get("task_id")),
                int(task_row.get("attempt", 0) or 0),
            )
            e["tasks"][key] = task_row
            e["peak_memory_bytes"] = max(
                e["peak_memory_bytes"],
                int(task_row.get("peak_memory_bytes", 0) or 0),
            )

    def finish(self, query_id: str, state: str, rows: int | None = None,
               error: str | None = None,
               peak_memory_bytes: int = 0,
               operator_stats: list | None = None) -> None:
        """Seal a query. ``operator_stats`` covers the local engine,
        whose single-process execution reports one synthetic task."""
        if not query_id:
            return
        with self._lock:
            e = self._entry(query_id)
            e["state"] = state
            e["finished_at"] = time.time()
            e["error"] = error
            if rows is not None:
                e["rows"] = int(rows)
            e["peak_memory_bytes"] = max(
                e["peak_memory_bytes"], int(peak_memory_bytes or 0)
            )
            if operator_stats and not e["tasks"]:
                e["tasks"][("local", "local-0", 0)] = {
                    "stage_id": "local", "task_id": "local-0",
                    "attempt": 0, "state": state, "worker": "local",
                    "operator_stats": operator_stats,
                }
            self._sweep_locked()

    def mark_recovered(self, query_id: str) -> None:
        """Flag a query as crossing a coordinator restart. Safe to
        call before its begin(): the id is remembered and the flag
        applied when the entry materializes."""
        if not query_id:
            return
        with self._lock:
            self._recovered_ids.add(query_id)
            e = self._entries.get(query_id)
            if e is not None:
                e["recovered"] = True

    def rehydrate(self, query_id: str, *, state: str,
                  sql: str | None = None, user: str | None = None,
                  rows: int | None = None, error: str | None = None,
                  elapsed_ms: float = 0.0,
                  diagnostics: dict | None = None) -> None:
        """Restore a terminal query's registry row from its journal
        `done` record after a coordinator restart. The row reports
        recovered=True; task trees are not journaled, so the stage
        list comes back empty (the post-mortem bundle, when present,
        preserves the failure's full context)."""
        if not query_id:
            return
        with self._lock:
            self._recovered_ids.add(query_id)
            e = self._entry(query_id)
            e["recovered"] = True
            e["state"] = state
            e["sql"] = sql if sql is not None else e["sql"]
            e["user"] = user if user is not None else e["user"]
            e["rows"] = int(rows) if rows is not None else e["rows"]
            e["error"] = error
            # reconstruct the timeline the elapsed math expects
            e["finished_at"] = time.time()
            e["created_at"] = e["finished_at"] - (
                float(elapsed_ms or 0.0) / 1e3
            )
            if diagnostics is not None:
                e["diagnostics"] = diagnostics
            self._sweep_locked()

    def set_diagnostics(self, query_id: str, bundle: dict) -> None:
        """Retain a post-mortem bundle; served by
        ``GET /v1/query/{id}/diagnostics`` until the entry sweeps."""
        if not query_id:
            return
        with self._lock:
            self._entry(query_id)["diagnostics"] = bundle

    def get_diagnostics(self, query_id: str) -> dict | None:
        with self._lock:
            e = self._entries.get(query_id)
            return e["diagnostics"] if e else None

    # -- read side ------------------------------------------------------

    def _elapsed_ms(self, e: dict) -> float:
        end = e["finished_at"] or time.time()
        return (end - e["created_at"]) * 1e3

    def list(self) -> list[dict]:
        """Light rows for ``GET /v1/query`` / system.runtime.queries."""
        with self._lock:
            return [
                {
                    "query_id": e["query_id"],
                    "state": e["state"],
                    "user": e["user"],
                    "resource_group": e["resource_group"],
                    "elapsed_ms": round(self._elapsed_ms(e), 3),
                    "queued_time_ms": round(e["queued_ms"], 3),
                    "peak_memory_bytes": e["peak_memory_bytes"],
                    "rows": e["rows"],
                    "error": e["error"],
                    "recovered": bool(e.get("recovered")),
                }
                for e in self._entries.values()
            ]

    def get(self, query_id: str) -> dict | None:
        """Full stage → task → operator tree for one query."""
        from trino_tpu.profiler import tree_from_stats

        with self._lock:
            e = self._entries.get(query_id)
            if e is None:
                return None
            stages: dict[str, dict] = {}
            for (sid, tid, att), row in sorted(e["tasks"].items()):
                st = stages.setdefault(sid, {"stage_id": sid, "tasks": []})
                task = {
                    k: v for k, v in row.items()
                    if k not in ("operator_stats", "query_id", "stage_id")
                }
                task["operators"] = tree_from_stats(
                    row.get("operator_stats") or []
                )
                st["tasks"].append(task)
            return {
                "query_id": e["query_id"],
                "state": e["state"],
                "user": e["user"],
                "sql": e["sql"],
                "elapsed_ms": round(self._elapsed_ms(e), 3),
                "peak_memory_bytes": e["peak_memory_bytes"],
                "rows": e["rows"],
                "error": e["error"],
                "recovered": bool(e.get("recovered")),
                "stages": list(stages.values()),
            }

    def _sweep_locked(self) -> None:
        now = time.time()
        finished = [
            qid for qid, e in self._entries.items()
            if e["finished_at"] is not None
        ]
        for qid in finished:
            e = self._entries[qid]
            if now - e["finished_at"] > self.retention_s:
                del self._entries[qid]
        finished = [
            qid for qid in self._entries
            if self._entries[qid]["finished_at"] is not None
        ]
        while len(finished) > self.max_finished:
            del self._entries[finished.pop(0)]


#: process-wide registry: the coordinator, the fleet runner, and the
#: local engine all live in one coordinator process, so one registry
#: serves every entry point (worker stats arrive via the poll channel)
QUERY_INFO = QueryInfoRegistry()
