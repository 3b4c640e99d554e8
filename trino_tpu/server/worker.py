"""Worker process: owns the device mesh, executes plans shipped over
HTTP — the coordinator/worker seam.

The analog of the reference's worker tier RPC
(MAIN/server/TaskResource.java:135-339: POST /v1/task with a plan
fragment, long-poll GET for status/results) standing in for the DCN
boundary (SURVEY.md §5.8): even with both processes on one host, the
plan travels as JSON (plan.serde) and results return as typed JSON
rows — the host-boundary serialization layer a multi-host deployment
needs, forced into existence.

Run: ``python -m trino_tpu.server.worker --port 8091 [--mesh]``.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.request
import uuid
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from trino_tpu import (
    fault, membership as membership_mod, profiler, telemetry,
)
from trino_tpu.engine import QueryRunner
from trino_tpu.exec import scan_cache
from trino_tpu.plan.serde import plan_from_json

__all__ = ["WorkerServer"]


class _Task:
    def __init__(self, task_id: str):
        self.task_id = task_id
        self.state = "RUNNING"
        self.error: str | None = None
        #: host columnar result payload ({names, types, cols}) — rows
        #: serialize lazily per fetched batch, never all at once
        self.payload: dict | None = None
        self.n_rows = 0
        self.cancel = threading.Event()
        #: per-task runtime stats / serialized span subtree (stage
        #: tasks only) — ride back on the FINISHED status response so
        #: the coordinator folds them into QueryResult.stage_stats and
        #: stitches the spans into the query trace
        self.stats: dict | None = None
        self.spans: dict | None = None
        #: partition ids this stage task has durably committed so far
        #: (per-partition spool markers) — reported on every status
        #: poll so the coordinator's pipelined scheduler can admit
        #: consumers before the task finishes
        self.partitions: list[int] = []
        #: owning query — stage-task ids repeat across queries on a
        #: long-lived worker, so direct-exchange lookups must also
        #: match the query before trusting a task record
        self.query_id = ""


class _ExchangeBuffer:
    """Producer-side buffer pool of the direct exchange path.

    Committed output partitions stay resident as raw spool-encoded
    bytes (the exact SPL1 frame + CRC the on-disk file carries), keyed
    by ``(query_id, task_id, attempt, partition)`` so a consumer
    pinned to one attempt can structurally never be served another
    attempt's bytes. Every entry is reserved through the producing
    task's MemoryContext, best-effort: under pressure the pool evicts
    LRU entries, and a partition that still does not fit is simply not
    buffered. The pool is a cache, never a source of truth — the async
    spool commit made the bytes durable before they were offered here,
    so any miss, eviction, or producer death degrades the consumer to
    ``spool.read_partition`` with identical results."""

    def __init__(self, cap_bytes: int | None = None):
        self._lock = threading.Lock()
        #: key -> (raw, crc, memory ctx); insertion order is LRU order
        self._entries: OrderedDict[tuple, tuple] = OrderedDict()
        self._bytes = 0
        self.cap_bytes = int(
            cap_bytes if cap_bytes is not None
            else os.environ.get(
                "TRINO_TPU_EXCHANGE_BUFFER_BYTES", 128 << 20
            )
        )

    def put(self, key: tuple, raw: bytes, crc: int, ctx) -> bool:
        need = len(raw)
        with self._lock:
            if key in self._entries:
                return True
            if need > self.cap_bytes:
                return False
            while (
                self._bytes + need > self.cap_bytes
                or not ctx.try_reserve(need)
            ):
                if not self._entries:
                    return False
                self._evict_locked()
            self._entries[key] = (raw, int(crc), ctx)
            self._bytes += need
            telemetry.EXCHANGE_BUFFER_RESERVED.set(self._bytes)
            return True

    def get(self, key: tuple) -> tuple | None:
        """``(raw, crc)`` for an exact key match, else None."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return None
            self._entries.move_to_end(key)
            return e[0], e[1]

    def drop_task(self, query_id: str, task_id: str, attempt: int):
        """Release a canceled attempt's buffers (losing speculative
        attempts; pinned consumers fall back to the durable spool)."""
        with self._lock:
            for key in [
                k for k in self._entries
                if k[0] == query_id and k[1] == task_id
                and k[2] == attempt
            ]:
                self._release_locked(key)
            telemetry.EXCHANGE_BUFFER_RESERVED.set(self._bytes)

    def drop_query(self, query_id: str) -> int:
        """Release every buffer of a finished query — the 'all pinned
        consumers have fetched' eviction point (a query's exchange has
        no readers once the query is done). Returns the number of
        entries released so the orphan reaper can account evictions."""
        with self._lock:
            keys = [
                k for k in self._entries if k[0] == query_id
            ]
            for key in keys:
                self._release_locked(key)
            telemetry.EXCHANGE_BUFFER_RESERVED.set(self._bytes)
            return len(keys)

    def _evict_locked(self):
        key = next(iter(self._entries))
        self._release_locked(key)
        telemetry.EXCHANGE_BUFFER_EVICTIONS.inc()
        telemetry.EXCHANGE_BUFFER_RESERVED.set(self._bytes)

    def _release_locked(self, key: tuple):
        raw, _crc, ctx = self._entries.pop(key)
        self._bytes -= len(raw)
        try:
            ctx.free(len(raw))
        except Exception:
            pass


class InjectedTaskFailure(fault.InjectedFault):
    """Coordinator-requested failure (FailureInjector analog,
    MAIN/execution/FailureInjector.java:39) — exercises the fleet
    retry path without killing the process. A subtype of the unified
    InjectedFault so chaos tooling classifies the legacy `fail` flag
    and the site-addressable schedules identically."""

    def __init__(self, task_id: str, attempt: int):
        super().__init__("task-exec", task_id, attempt, "legacy-flag")


class WorkerServer:
    """One worker process: a QueryRunner-owned executor behind a task
    RPC. Tasks execute serially (the engine's batch model; the
    reference's TaskExecutor concurrency maps to the mesh instead)."""

    def __init__(self, runner: QueryRunner, port: int = 0):
        self.runner = runner
        self._tasks: dict[str, _Task] = {}
        self._lock = threading.Lock()
        #: lifecycle: ACTIVE -> DRAINING (no new tasks, in-flight
        #: finish) -> DRAINED (the GracefulShutdownHandler states,
        #: MAIN/server/GracefulShutdownHandler.java:42)
        self.state = "ACTIVE"
        self._active_tasks = 0
        #: coordinator-liveness per query: monotonic time of the last
        #: status poll that touched one of the query's tasks. A
        #: coordinator that dies stops polling; the orphan reaper
        #: quarantines then cancels queries silent past the TTL.
        self._coord_seen: dict[str, float] = {}
        #: per-query spool root (from submit_stage) so the reaper can
        #: GC scratch temp files the dead coordinator's tasks left
        self._query_spools: dict[str, str] = {}
        #: queries the reaper has flagged (quarantine start time) but
        #: not yet cancelled — the grace period before the kill
        self._quarantined: dict[str, float] = {}
        self._reaper_thread: threading.Thread | None = None
        self._reaper_stop = threading.Event()
        worker = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def _send(self, code: int, payload: dict):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n)) if n else {}
                if self.path == "/v1/drain":
                    worker.drain()
                    self._send(200, {"state": worker.lifecycle_state()})
                    return
                if self.path in ("/v1/task", "/v1/stagetask"):
                    if worker.state != "ACTIVE":
                        # draining workers accept no new work; the
                        # coordinator reschedules elsewhere (409 =
                        # "not dead, just leaving")
                        self._send(409, {
                            "error": "worker is draining",
                            "state": worker.lifecycle_state(),
                        })
                        return
                if self.path == "/v1/task":
                    task = worker.submit(req)
                    self._send(200, {"taskId": task.task_id})
                    return
                if self.path == "/v1/stagetask":
                    task = worker.submit_stage(req)
                    self._send(200, {"taskId": task.task_id})
                    return
                path, _, query = self.path.partition("?")
                if path == "/v1/profile":
                    # kernel observatory: blocking device-profile
                    # capture over a wall-clock window; whatever task
                    # work dispatches during it gets attributed to
                    # named HLO scopes via the program catalog
                    from urllib.parse import parse_qs

                    from trino_tpu import kernel_profile

                    dur = (
                        parse_qs(query).get("duration_ms")
                        or [req.get("duration_ms", 500)]
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
                self._send(404, {"error": "not found"})

            def _task_status(self, task_id: str, token: int | None):
                t = worker._tasks.get(task_id)
                if t is None:
                    self._send(404, {"error": "no such task"})
                    return
                # every status poll is a coordinator-liveness proof
                # for the task's query: the orphan reaper only reaps
                # queries whose coordinator has stopped polling
                worker._coord_seen[t.query_id] = time.monotonic()
                worker._quarantined.pop(t.query_id, None)
                payload = {"state": t.state}
                if t.state == "FINISHED" and token is not None:
                    payload.update(_encode_batch(
                        t, token, getattr(t, "batch_rows", BATCH_ROWS)
                    ))
                elif t.state in ("FAILED", "CANCELED"):
                    payload.update(error=t.error)
                if t.state == "FINISHED":
                    if t.stats is not None:
                        payload["stats"] = t.stats
                    if t.spans is not None:
                        payload["spans"] = t.spans
                # committed-partition set on every status response:
                # the event feed of the pipelined stage scheduler
                # (list append/copy are atomic under the GIL, so no
                # lock against the run thread is needed)
                payload["partitions"] = list(t.partitions)
                payload["query_id"] = t.query_id
                # pool snapshot on every status response: the
                # coordinator's ClusterMemoryManager aggregates these
                # (the heartbeat memory surface of the reference's
                # MemoryResource/ClusterMemoryManager poll)
                payload["pool"] = (
                    worker.runner.executor.memory_pool.snapshot()
                )
                # this process's wall clock, stamped per response: the
                # coordinator's NTP-style skew estimator turns these
                # into per-worker offsets so stitched span subtrees
                # share one timeline
                payload["now_ms"] = time.time() * 1e3
                self._send(200, payload)

            def _buffer_fetch(self, task_id, attempt, part, query):
                from urllib.parse import parse_qs

                try:
                    a, p = int(attempt), int(part)
                except ValueError:
                    self._send(404, {"error": "bad attempt/partition"})
                    return
                qid = (parse_qs(query).get("query") or [""])[0]
                entry = worker.exchange_buffer.get(
                    (qid, task_id, a, p)
                )
                if entry is not None:
                    raw, crc = entry
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "application/octet-stream"
                    )
                    self.send_header("Content-Length", str(len(raw)))
                    self.send_header("X-Trino-File-CRC", str(crc))
                    self.end_headers()
                    self.wfile.write(raw)
                    return
                t = worker._tasks.get(f"{task_id}.{a}")
                if (
                    t is not None and t.query_id == qid
                    and t.state == "FINISHED"
                    and p not in t.partitions
                ):
                    # definitively absent: the attempt committed and
                    # never wrote this partition (vs. a 404 miss /
                    # eviction, where the consumer must try the spool)
                    self.send_response(204)
                    self.end_headers()
                    return
                self._send(404, {"error": "not buffered"})

            def do_GET(self):
                path, _, query = self.path.partition("?")
                parts = path.strip("/").split("/")
                if (
                    len(parts) == 6
                    and parts[:2] == ["v1", "stagetask"]
                    and parts[3] == "results"
                ):
                    # direct-exchange fetch: raw committed partition
                    # bytes straight out of the producer's buffer
                    # pool. Exact attempt match only — a consumer
                    # pinned to attempt N is never served attempt M.
                    self._buffer_fetch(
                        parts[2], parts[4], parts[5], query
                    )
                    return
                if parts == ["v1", "metrics"]:
                    # Prometheus text exposition of the process-wide
                    # registry (worker-side counters: task states,
                    # spool bytes, chaos injections, XLA compiles)
                    telemetry.refresh_process_gauges(node="worker")
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
                if (
                    len(parts) in (4, 5)
                    and parts[:2] == ["v1", "task"]
                    and parts[3] == "results"
                ):
                    # token-paged columnar result fetch (the paged
                    # GET /v1/task/{id}/results/{token} of the
                    # reference, MAIN/server/TaskResource.java:319)
                    token = int(parts[4]) if len(parts) == 5 else 0
                    self._task_status(parts[2], token)
                    return
                if (
                    len(parts) == 3
                    and parts[:2] == ["v1", "stagetask"]
                ):
                    self._task_status(parts[2], None)
                    return
                if parts == ["v1", "stacks"]:
                    # operator diagnosis: every thread's current stack
                    # (jstack analog — TaskResource has no equivalent;
                    # the JVM gets this from the runtime)
                    import sys as _sys
                    import traceback as _tb

                    frames = {
                        str(tid): _tb.format_stack(frame)
                        for tid, frame in _sys._current_frames().items()
                    }
                    self._send(200, {"stacks": frames})
                    return
                if parts == ["v1", "info"]:
                    mesh = worker.runner.mesh
                    self._send(200, {
                        **profiler.device_info(),
                        "resident_tables": scan_cache.SHARED.describe(),
                        "state": worker.lifecycle_state(),
                        "activeTasks": worker._active_tasks,
                        "mesh": mesh is not None,
                        "devices": (
                            1 if mesh is None else int(mesh.devices.size)
                        ),
                        "pool": (
                            worker.runner.executor.memory_pool.snapshot()
                        ),
                    })
                    return
                if parts == ["v1", "programs"]:
                    # compiled-program catalog: every XLA program this
                    # worker compiled/deserialized, with cost and HBM
                    # footprint analysis
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
                self._send(404, {"error": "not found"})

            def do_DELETE(self):
                parts = self.path.strip("/").split("/")
                if len(parts) == 3 and parts[:2] == ["v1", "task"]:
                    ok = worker.cancel_task(parts[2])
                    self._send(200 if ok else 404, {"canceled": ok})
                    return
                if len(parts) == 3 and parts[:2] == ["v1", "stagetask"]:
                    # losing speculative attempts are cancelled here;
                    # a cancel that loses the race to the spool commit
                    # is harmless — readers dedupe committed attempts
                    ok = worker.cancel_task(parts[2])
                    self._send(200 if ok else 404, {"canceled": ok})
                    return
                if len(parts) == 3 and parts[:2] == ["v1", "exchange"]:
                    # query-end buffer release: all pinned consumers
                    # have fetched once the query is done, so the
                    # coordinator drops the query's direct-exchange
                    # buffers on every worker
                    worker.exchange_buffer.drop_query(parts[2])
                    self._send(200, {"released": parts[2]})
                    return
                self._send(404, {"error": "not found"})

        #: direct-exchange buffer pool: committed output partitions of
        #: this worker's stage tasks, served to consumers over
        #: GET /v1/stagetask/{task}/results/{attempt}/{partition}
        self.exchange_buffer = _ExchangeBuffer()
        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._httpd.server_address[1]
        self._self_uri = f"http://127.0.0.1:{self.port}"
        # memory-pool snapshots attribute to this worker's address
        # (the node_id shown in kill-policy errors and
        # system.runtime.memory)
        self.runner.executor.memory_pool.node_id = (
            f"127.0.0.1:{self.port}"
        )
        self._thread: threading.Thread | None = None
        self._announce_thread: threading.Thread | None = None
        self._announce_stop = threading.Event()

    def start(self) -> "WorkerServer":
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        return self

    def stop(self):
        self._announce_stop.set()
        self._reaper_stop.set()
        self._httpd.shutdown()
        self._httpd.server_close()

    def start_announcer(
        self,
        coordinator_uri: str,
        node_id: str | None = None,
        fallback_interval_s: float = 1.0,
    ) -> threading.Thread:
        """Join the live cluster: announce once, then heartbeat at a
        third of the coordinator-advertised TTL, reporting this
        worker's lifecycle state. The loop exits when the coordinator
        answers ``deregister`` — the drain completed (running tasks
        finished AND every dependent consumer committed its exchange
        reads). A failed round — transport error or an armed
        announce-drop/heartbeat-loss fault — is simply skipped; the
        registry's TTL machine absorbs missed heartbeats."""
        node = node_id or f"worker-{self.port}"
        worker = self

        def loop():
            initial = True
            rounds = 0
            interval = fallback_interval_s
            while not worker._announce_stop.is_set():
                try:
                    resp = membership_mod.announce_once(
                        coordinator_uri,
                        node,
                        worker._self_uri,
                        state=worker.lifecycle_state(),
                        active_tasks=worker._active_tasks,
                        initial=initial,
                        attempt=rounds,
                    )
                    initial = False
                    if resp.get("deregister"):
                        return
                    interval = max(
                        float(resp.get("ttl_s", 3.0)) / 3.0, 0.05
                    )
                except Exception:
                    pass  # missed round: the TTL state machine's job
                rounds += 1
                worker._announce_stop.wait(interval)

        t = threading.Thread(
            target=loop, name=f"announce-{self.port}", daemon=True
        )
        t.start()
        self._announce_thread = t
        return t

    # ---- lifecycle (graceful drain) --------------------------------------

    def drain(self) -> None:
        """Enter DRAINING: refuse new tasks, let in-flight ones finish
        (GracefulShutdownHandler.requestShutdown analog — without the
        process exit, which the operator owns)."""
        with self._lock:
            if self.state == "ACTIVE":
                self.state = "DRAINING"

    def lifecycle_state(self) -> str:
        if self.state == "DRAINING" and self._active_tasks == 0:
            return "DRAINED"
        return self.state

    def _task_started(self):
        with self._lock:
            self._active_tasks += 1

    def _task_finished(self):
        with self._lock:
            self._active_tasks -= 1

    # ---- task execution --------------------------------------------------

    def submit(self, req: dict) -> _Task:
        task = _Task(uuid.uuid4().hex[:12])
        with self._lock:
            self._tasks[task.task_id] = task
            if len(self._tasks) > 200:
                # bounded history: results are large; evict oldest done
                done = [
                    k for k, t in self._tasks.items()
                    if t.state in ("FINISHED", "FAILED", "CANCELED")
                ]
                for k in done[: len(self._tasks) - 200]:
                    del self._tasks[k]

        session = req.get("session") or {}
        task.batch_rows = int(
            session.get("result_batch_rows", BATCH_ROWS) or BATCH_ROWS
        )

        def run():
            self._task_started()
            try:
                from trino_tpu.exec.spool import page_to_host

                delay = float(session.get("task_delay_ms", 0) or 0)
                if delay:
                    # test hook: widen the cancel window
                    import time as _time

                    _time.sleep(delay / 1000.0)
                if task.cancel.is_set():
                    raise RuntimeError("Query was canceled")
                plan = plan_from_json(req["plan"])
                with self.runner._lock:
                    # session overrides apply under the execute lock and
                    # restore afterwards: concurrent tasks must not see
                    # (or inherit) each other's settings. The host
                    # materialization stays under the lock too — XLA
                    # must never run from two worker threads at once
                    # (see submit_stage)
                    saved = dict(self.runner.session.properties)
                    self.runner.session.properties.update(
                        req.get("session") or {}
                    )
                    ex = self.runner.executor
                    ex.cancel_event = task.cancel
                    qid = str(req.get("query_id") or task.task_id)
                    prev_ctx = ex.memory_ctx
                    ex.memory_ctx = ex.memory_pool.query_context(
                        qid
                    ).child(task.task_id)
                    try:
                        page = ex.execute(plan)
                        # materialize ONCE to packed host columns;
                        # batches JSON-encode windows of these arrays
                        # on demand (the previous whole-result
                        # json.dumps was the OOM the round-3 VERDICT
                        # flagged, weak #4)
                        payload = page_to_host(page)
                    finally:
                        ex.cancel_event = None
                        ex.memory_ctx = prev_ctx
                        self.runner.session.properties = saved
                with self._lock:
                    # a DELETE that raced past the last executor cancel
                    # checkpoint must still win: never commit a result
                    # for a canceled task
                    if task.cancel.is_set():
                        task.state = "CANCELED"
                        task.payload = None
                    else:
                        task.payload = payload
                        task.n_rows = (
                            len(payload["cols"][0][0])
                            if payload["cols"] else 0
                        )
                        task.state = "FINISHED"
            except Exception as e:
                task.error = f"{type(e).__name__}: {e}"
                task.state = (
                    "CANCELED" if task.cancel.is_set() else "FAILED"
                )
                task.payload = None
            finally:
                self._task_finished()

        threading.Thread(target=run, daemon=True).start()
        return task

    def cancel_task(self, task_id: str) -> bool:
        """DELETE /v1/task/{id}: cooperative cancel + free the result
        (TaskResource.deleteTask analog, MAIN/server/TaskResource.java).
        Serialized with the run thread's commit so a racing finish can
        never resurrect a canceled task's result."""
        t = self._tasks.get(task_id)
        if t is None:
            return False
        with self._lock:
            t.cancel.set()
            if t.state in ("RUNNING", "FINISHED", "FAILED"):
                t.state = "CANCELED"
            t.payload = None
        # a canceled stage attempt keeps no exchange buffers; pinned
        # consumers fall back to whatever it durably committed
        tid, _, a = task_id.rpartition(".")
        if tid and a.isdigit():
            self.exchange_buffer.drop_task(t.query_id, tid, int(a))
        return True

    # ---- orphan reaping --------------------------------------------------

    def reap_orphans_once(
        self, ttl_s: float, grace_s: float | None = None
    ) -> dict:
        """One reaper sweep: queries whose coordinator has gone silent
        (no status poll or dispatch) past ``ttl_s`` are quarantined on
        the first sweep, then — one grace period later — their RUNNING
        tasks are cancelled, their direct-exchange buffers released,
        and any ``*.tmp`` scratch the dead coordinator's tasks left in
        the spool is deleted. The quarantine step means a coordinator
        that was merely paused (GC, restart-in-progress) gets a full
        extra window to resume polling before anything is killed.
        Returns counts for tests/telemetry."""
        if grace_s is None:
            grace_s = ttl_s
        now = time.monotonic()
        out = {"quarantined": 0, "reaped": 0, "buffers": 0,
               "scratch": 0}
        for qid, seen in list(self._coord_seen.items()):
            if now - seen < ttl_s:
                continue
            if qid not in self._quarantined:
                # first sweep past the TTL: quarantine only. The
                # cancel fires a full grace period later if the
                # coordinator stays silent.
                self._quarantined[qid] = now
                out["quarantined"] += 1
                continue
            if now - self._quarantined[qid] < grace_s:
                continue
            # past quarantine: the coordinator is gone for real
            reaped = 0
            for tkey, t in list(self._tasks.items()):
                if t.query_id == qid and t.state in (
                    "PENDING", "RUNNING"
                ):
                    self.cancel_task(tkey)
                    reaped += 1
            if reaped:
                telemetry.ORPHAN_TASKS_REAPED.inc(reaped)
            released = self.exchange_buffer.drop_query(qid)
            if released:
                telemetry.EXCHANGE_BUFFER_ORPHAN_EVICTIONS.inc(
                    released
                )
            out["reaped"] += reaped
            out["buffers"] += released
            out["scratch"] += self._gc_spool_scratch(
                self._query_spools.pop(qid, None)
            )
            self._coord_seen.pop(qid, None)
            self._quarantined.pop(qid, None)
        return out

    @staticmethod
    def _gc_spool_scratch(qroot: str | None) -> int:
        """Delete orphaned ``*.tmp`` spool scratch (writes that never
        reached their atomic rename because the writer died). Committed
        files — the renamed targets — are never touched: a restarted
        coordinator resumes from them."""
        if not qroot or not os.path.isdir(qroot):
            return 0
        n = 0
        for dirpath, _dirs, files in os.walk(qroot):
            for name in files:
                if name.endswith(".tmp"):
                    try:
                        os.unlink(os.path.join(dirpath, name))
                        n += 1
                    except OSError:
                        pass
        return n

    def start_orphan_reaper(
        self,
        ttl_s: float,
        grace_s: float | None = None,
        interval_s: float | None = None,
    ) -> threading.Thread:
        """Background reaper loop (daemon). ``interval_s`` defaults to
        a quarter of the TTL so a silent coordinator is noticed well
        inside one extra TTL."""
        if interval_s is None:
            interval_s = max(0.05, ttl_s / 4.0)

        def loop():
            while not self._reaper_stop.wait(interval_s):
                try:
                    self.reap_orphans_once(ttl_s, grace_s)
                except Exception:
                    pass

        t = threading.Thread(
            target=loop, name="orphan-reaper", daemon=True
        )
        self._reaper_thread = t
        t.start()
        return t

    # ---- direct exchange (consumer side) ---------------------------------

    #: sentinel: the producer attempt committed WITHOUT this partition
    _ABSENT = object()

    def _fetch_buffer(self, uri: str, qid: str, tid: str,
                      attempt: int, part: int):
        """One partition's ``(raw, crc)`` from a producer's buffer
        pool, ``_ABSENT`` when the attempt definitively never wrote
        the partition, or an exception on miss/eviction/unreachable
        producer (the caller falls back to the spool)."""
        if uri.rstrip("/") == self._self_uri:
            entry = self.exchange_buffer.get((qid, tid, attempt, part))
            if entry is not None:
                return entry
            t = self._tasks.get(f"{tid}.{attempt}")
            if (
                t is not None and t.query_id == qid
                and t.state == "FINISHED"
                and part not in t.partitions
            ):
                return WorkerServer._ABSENT
            raise LookupError(f"{tid}.{attempt} p{part} not buffered")
        url = (
            f"{uri}/v1/stagetask/{tid}/results/{attempt}/{part}"
            f"?query={qid}"
        )
        with urllib.request.urlopen(url, timeout=10.0) as resp:
            if resp.status == 204:
                return WorkerServer._ABSENT
            raw = resp.read()
            crc = resp.headers.get("X-Trino-File-CRC")
            return raw, (int(crc) if crc else None)

    def _producer_partitions(self, uri: str, qid: str, tid: str,
                             attempt: int) -> list[int]:
        """Committed partition ids of a FINISHED producer attempt —
        the fetch list for gather/broadcast edges, which read the
        producer's whole output."""
        if uri.rstrip("/") == self._self_uri:
            t = self._tasks.get(f"{tid}.{attempt}")
            if (
                t is None or t.query_id != qid
                or t.state != "FINISHED"
            ):
                raise LookupError(f"{tid}.{attempt} not finished here")
            return sorted(set(t.partitions))
        url = f"{uri}/v1/stagetask/{tid}.{attempt}"
        with urllib.request.urlopen(url, timeout=10.0) as resp:
            state = json.loads(resp.read())
        if (
            state.get("state") != "FINISHED"
            or state.get("query_id") != qid
        ):
            raise LookupError(f"{tid}.{attempt} not finished at {uri}")
        return sorted({int(p) for p in state.get("partitions") or ()})

    def _direct_read(self, src: dict, part: int | None, qid: str):
        """Serve one RemoteSource edge from producer memory: returns
        ``(payload, direct_bytes)``, or ``(None, 0)`` to fall back to
        the spool. Mirrors ``spool.read_partition`` exactly — same
        task_ids concatenation order, same ascending partition order
        within a producer, same per-producer spool-read fault seam (an
        armed spool-read schedule fails the task identically in both
        exchange modes) — so DIRECT results are byte-identical to
        SPOOL. Only the exchange-fetch site is absorbed here: a fired
        fetch fault, like any miss/eviction/producer-death/integrity
        failure, silently degrades the edge to the durable spool copy
        and never fails the task."""
        from trino_tpu.exec import spool

        attempts = src.get("attempts") or {}
        hints = src.get("workers") or {}
        if not attempts or not hints:
            return None, 0
        sid = src["stage_id"]
        payloads: list[dict] = []
        total = 0
        for tid in src["task_ids"]:
            # the same read seam the spool path runs per producer task
            fault.check("spool-read", tag=f"{sid}:{tid}")
            uri = hints.get(tid)
            a = attempts.get(tid)
            if uri is None or a is None:
                return None, 0
            try:
                fault.check("exchange-fetch", tag=f"{sid}:{tid}")
                if part is not None:
                    wanted = [int(part)]
                else:
                    wanted = self._producer_partitions(
                        uri, qid, tid, int(a)
                    )
                for p in wanted:
                    got = self._fetch_buffer(
                        uri, qid, tid, int(a), p
                    )
                    if got is WorkerServer._ABSENT:
                        continue
                    raw, crc = got
                    payloads.append(
                        spool.payload_from_bytes(raw, expect_crc=crc)
                    )
                    total += len(raw)
            except fault.InjectedFault as e:
                if e.site != "exchange-fetch":
                    raise
                return None, 0
            except Exception:
                return None, 0
        if not payloads:
            # no producer had data (empty edge): let the spool path
            # rebuild the typed zero-row payload from its schema files
            return None, 0
        return spool._concat_payloads(payloads), total

    def submit_stage(self, req: dict) -> "_Task":
        """Execute one fleet stage task: a plan fragment whose
        RemoteSource leaves resolve from the spooled exchange, output
        hash-partitioned back into the spool (the worker half of the
        FTE tier — TaskResource.createOrUpdateTask + spooled output,
        MAIN/server/TaskResource.java:139,
        plugin/trino-exchange-filesystem/.../FileSystemExchangeManager.java:38)."""
        from trino_tpu.exec import spool

        tkey = f"{req['task_id']}.{req['attempt']}"
        task = _Task(tkey)
        task.query_id = str(req.get("query_id") or req["task_id"])
        with self._lock:
            self._tasks[tkey] = task
        # admission counts as liveness (the dispatching coordinator is
        # clearly alive); remember the spool root for orphan scratch GC
        self._coord_seen[task.query_id] = time.monotonic()
        if req.get("spool"):
            self._query_spools[task.query_id] = str(req["spool"])

        def run():
            self._task_started()
            import time as _time

            t_task = _time.perf_counter()
            # worker half of the stitched trace: the task span roots
            # under the coordinator's stage span (parent_id from the
            # shipped trace context) and goes back serialized on the
            # FINISHED status response
            trace_ctx = req.get("trace") or {}
            tspan = telemetry.Span(
                name=f"task {tkey}", kind="task",
                parent_id=trace_ctx.get("parent_span_id"),
                trace_id=str(trace_ctx.get("trace_id") or ""),
                node=f"127.0.0.1:{self.port}",
                attrs={
                    "task_id": req["task_id"],
                    "attempt": int(req["attempt"]),
                },
                query_id=task.query_id,
            )
            rows_in = 0
            out_stats = {"rows": 0, "bytes": 0}
            write_stats = None
            peak_bytes = 0
            op_stats: list = []
            col_ranges: dict = {}
            edge_rows: dict = {}
            direct_bytes = 0
            spooled_bytes = 0
            try:
                if req.get("fail"):
                    raise InjectedTaskFailure(
                        req["task_id"], int(req["attempt"])
                    )
                delay = float(
                    (req.get("session") or {}).get("fleet_task_delay_ms", 0)
                    or 0
                )
                if delay:
                    # test hook: widens the window in which a crash can
                    # interrupt a RUNNING task (BaseFailureRecoveryTest
                    # injects timeouts the same way)
                    import time as _time

                    _time.sleep(delay / 1000.0)
                if task.cancel.is_set():
                    raise RuntimeError("task was canceled")
                plan = plan_from_json(req["plan"])
                root = req["spool"]
                partition = req.get("partition")
                out = req["output"]
                # ALL device/XLA work — input page builds, execution,
                # output device_get — stays under the runner lock: a
                # worker process must never drive XLA:CPU from two
                # threads at once (a concurrent compile +
                # deserialize_executable wedges inside the backend;
                # observed as a permanently stuck task thread) — so
                # tasks take the worker in turn, and the wait for the
                # one ahead is ``task_queue_wait``
                queue_sp = tspan.child("task_queue_wait")
                with self.runner._lock:
                    queue_sp.finish()
                    # install the shipped chaos schedule for this
                    # task's duration: tasks serialize under the
                    # runner lock, so the process-global injector
                    # never crosses tasks. Its default attempt is the
                    # task attempt, so times-schedules on spool sites
                    # resolve against the task's retry level and a
                    # retried task eventually clears them.
                    inj = None
                    if req.get("fault_spec"):
                        inj = fault.FaultInjector.from_spec(
                            req["fault_spec"],
                            default_attempt=int(req["attempt"]),
                        )
                        fault.activate(inj)
                    try:
                        fault.check(
                            "task-exec",
                            tag=f"{out['stage_id']}:{req['task_id']}",
                            attempt=int(req["attempt"]),
                        )
                        qid = str(
                            req.get("query_id") or req["task_id"]
                        )
                        sess = req.get("session") or {}
                        use_direct = str(
                            sess.get("exchange_mode") or "DIRECT"
                        ).upper() != "SPOOL"
                        pages = {}
                        read_sp = tspan.child("spool-read", "spool")
                        for src in req["sources"]:
                            part = (
                                partition if src["mode"] == "aligned"
                                else None
                            )
                            payload = None
                            if use_direct:
                                # producer-memory first; any miss or
                                # fault falls back to the spool below
                                payload, nb = self._direct_read(
                                    src, part, qid
                                )
                                direct_bytes += nb
                            if payload is None:
                                nb: list = []
                                payload = spool.read_partition(
                                    root, src["stage_id"],
                                    src["task_ids"], part,
                                    attempts=src.get("attempts"),
                                    on_bytes=nb.append,
                                )
                                spooled_bytes += sum(nb)
                            # SALTED exchange, fan-out half: this salt
                            # task keeps its disjoint 1/K row slice of
                            # the hot partition (applied after the
                            # direct/spool read so both paths stay
                            # byte-identical); replicate sources read
                            # the partition whole on every salt task
                            sfac = int(src.get("salt_factor") or 0)
                            salted = sfac > 1 and src.get("salt") is not None
                            if salted:
                                payload = spool.salt_filter(
                                    payload, int(src["salt"]), sfac
                                )
                            src_rows = 0
                            if payload.get("cols"):
                                src_rows = len(payload["cols"][0][0])
                            if salted:
                                telemetry.EXCHANGE_SALTED_ROWS.inc(
                                    src_rows, role="fanout"
                                )
                            elif src.get("salt_role") == "replicate":
                                telemetry.EXCHANGE_SALTED_ROWS.inc(
                                    src_rows, role="replicate"
                                )
                            rows_in += src_rows
                            # per-edge accounting for the coordinator's
                            # exchange-coverage debug assertion
                            edge_rows[src["source_id"]] = src_rows
                            pages[src["source_id"]] = spool.host_to_page(
                                payload
                            )
                        read_sp.finish()
                        read_sp.attrs["rows"] = rows_in
                        read_sp.attrs["direct_bytes"] = direct_bytes
                        if direct_bytes:
                            telemetry.EXCHANGE_DIRECT_BYTES.inc(
                                direct_bytes
                            )
                        if spooled_bytes:
                            telemetry.EXCHANGE_SPOOLED_BYTES.inc(
                                spooled_bytes
                            )
                        saved = dict(self.runner.session.properties)
                        self.runner.session.properties.update(
                            req.get("session") or {}
                        )
                        ex = self.runner.executor
                        ex.remote_pages = pages
                        ex.remote_hash_keys = {
                            src["source_id"]: src.get("hash_symbols") or []
                            for src in req["sources"]
                        }
                        ex.cancel_event = task.cancel
                        # query -> task context: reservations made by
                        # this fragment attribute to the owning query in
                        # the pool snapshot the coordinator aggregates
                        prev_ctx = ex.memory_ctx
                        task_ctx = ex.memory_pool.query_context(
                            qid
                        ).child(tkey)
                        ex.memory_ctx = task_ctx
                        # writer-task identity: the spool epoch + task
                        # + attempt key staged write artifacts so
                        # speculated attempts never collide on part
                        # file names
                        ex.write_ctx = {
                            "epoch": os.path.basename(root),
                            "task": req["task_id"],
                            "attempt": int(req["attempt"]),
                        }
                        ex.last_write_stats = None
                        from trino_tpu.profiler import OperatorProfiler

                        ex.profiler = prof = OperatorProfiler()
                        try:
                            exec_sp = tspan.child("execute", "execution")
                            # compile/deserialize hops to the
                            # CompileService thread attach here, not
                            # to a detached root (trace anchor is
                            # read on THIS thread by the reroute)
                            telemetry.set_active_span(exec_sp)
                            if self.runner.mesh is not None:
                                # fleet x mesh: the fragment runs SPMD
                                # over this worker's device mesh
                                # (scatter inputs, local collectives,
                                # gather to spool)
                                try:
                                    page = ex.gather(
                                        ex.execute_dist(plan)
                                    )
                                except NotImplementedError:
                                    page = ex.execute(plan)
                            else:
                                page = ex.execute(plan)
                            exec_sp.finish()
                            # seal operator records while the runner
                            # lock is still held: cost resolution may
                            # lower+compile through the persistent
                            # cache, which is XLA work
                            telemetry.set_active_span(tspan)
                            op_stats = prof.finish(ex)
                            # coordinator-level dynamic filtering:
                            # min/max of the requested build-key
                            # output symbols ride back on FINISHED
                            # (still under the runner lock — the
                            # device fetch is XLA work)
                            rep = req.get("report_ranges") or []
                            if rep:
                                col_ranges = _page_col_ranges(page, rep)
                            # a cancelled speculative loser should not
                            # burn spool writes; a cancel arriving after
                            # this check commits anyway, which
                            # attempt-dedup makes safe
                            if not task.cancel.is_set():
                                write_sp = tspan.child(
                                    "spool-write", "spool"
                                )
                                # keep each committed partition's raw
                                # bytes resident for direct-exchange
                                # consumers, reserved on the task's
                                # memory context (best-effort — an
                                # unbuffered partition is served from
                                # the spool)
                                buf_ctx = task_ctx.child(
                                    "exchange-buffer"
                                )

                                def _stash(p, raw, crc):
                                    self.exchange_buffer.put(
                                        (
                                            qid, req["task_id"],
                                            int(req["attempt"]),
                                            int(p),
                                        ),
                                        raw, crc, buf_ctx,
                                    )

                                out_stats = spool.write_task_output(
                                    root, out["stage_id"],
                                    req["task_id"],
                                    int(req["attempt"]), page,
                                    out["partitioning"],
                                    out["hash_symbols"],
                                    int(out["n_partitions"]),
                                    partition_delay_ms=float(
                                        (req.get("session") or {}).get(
                                            "spool_partition_delay_ms", 0
                                        ) or 0
                                    ),
                                    on_partition=task.partitions.append,
                                    on_partition_bytes=(
                                        _stash if use_direct else None
                                    ),
                                ) or out_stats
                                write_sp.finish()
                                write_sp.attrs.update({
                                    k: out_stats[k]
                                    for k in ("rows", "bytes")
                                    if k in out_stats
                                })
                        finally:
                            telemetry.set_active_span(None)
                            ex.profiler = None
                            peak_bytes = task_ctx.peak_bytes
                            write_stats = getattr(
                                ex, "last_write_stats", None
                            )
                            ex.write_ctx = None
                            ex.cancel_event = None
                            ex.remote_pages = {}
                            ex.remote_hash_keys = {}
                            ex.memory_ctx = prev_ctx
                            self.runner.session.properties = saved
                    finally:
                        if inj is not None:
                            fault.deactivate()
                # the root record's rows_out can be unknown when the
                # final chain deferred its count sync — the spool
                # write already resolved it
                if op_stats and op_stats[0].get("rows_out") is None:
                    op_stats[0]["rows_out"] = int(out_stats.get("rows", 0))
                for row in op_stats:
                    telemetry.OPERATOR_SELF_TIME.observe(
                        row.get("self_ms", 0.0) / 1e3,
                        operator=row.get("node_type", "?"),
                    )
                with self._lock:
                    if not task.cancel.is_set():
                        task.stats = {
                            "rows_in": int(rows_in),
                            "rows_out": int(out_stats.get("rows", 0)),
                            "bytes_out": int(out_stats.get("bytes", 0)),
                            "elapsed_ms": (
                                (_time.perf_counter() - t_task) * 1e3
                            ),
                            "peak_memory_bytes": int(peak_bytes),
                            "operator_stats": op_stats,
                            "direct_bytes": int(direct_bytes),
                            "spooled_bytes": int(spooled_bytes),
                            "edge_rows": edge_rows,
                            **(
                                {
                                    "partition_rows": {
                                        str(p): r for p, r in
                                        out_stats["partition_rows"].items()
                                    },
                                    "partition_bytes": {
                                        str(p): b for p, b in
                                        out_stats.get(
                                            "partition_bytes", {}
                                        ).items()
                                    },
                                }
                                if out_stats.get("partition_rows")
                                else {}
                            ),
                            **(
                                {"col_ranges": col_ranges}
                                if col_ranges else {}
                            ),
                            **(
                                {
                                    "rows_written": int(
                                        write_stats["rows_written"]
                                    ),
                                    "bytes_written": int(
                                        write_stats["bytes_written"]
                                    ),
                                    "files_written": int(
                                        write_stats["files"]
                                    ),
                                }
                                if write_stats else {}
                            ),
                        }
                        task.spans = tspan.finish().to_dict()
                        task.state = "FINISHED"
            except Exception as e:
                task.error = f"{type(e).__name__}: {e}"
                task.state = (
                    "CANCELED" if task.cancel.is_set() else "FAILED"
                )
            finally:
                telemetry.WORKER_TASKS.inc(state=task.state)
                self._task_finished()

        threading.Thread(target=run, daemon=True).start()
        return task


def _page_col_ranges(page, symbols: list) -> dict:
    """Min/max of live non-null values per requested output symbol —
    the build-side summary behind coordinator-level dynamic filtering.
    ``[lo, hi]`` when computable, ``[]`` when the task produced no
    usable rows, ``None`` when the column's domain cannot prune
    (dictionary/hash codes carry no storage order, two-limb decimals
    and pooled types have no 1-D integer domain)."""
    import numpy as np

    out: dict = {}
    mask = np.asarray(page.mask)
    for sym in symbols:
        if sym not in page.names:
            out[sym] = None
            continue
        col = page.column(sym)
        if (
            col.dictionary is not None
            or col.hash_pool is not None
            or col.array_pool is not None
        ):
            out[sym] = None
            continue
        data = np.asarray(col.data)
        if data.ndim != 1 or np.dtype(data.dtype).kind != "i":
            out[sym] = None
            continue
        keep = mask.copy()
        if col.valid is not None:
            keep &= np.asarray(col.valid)
        vals = data[keep]
        if vals.size == 0:
            out[sym] = []
        else:
            out[sym] = [int(vals.min()), int(vals.max())]
    return out


def _json_element(t, x):
    from trino_tpu import types as T

    if isinstance(t, T.VarcharType):
        return str(x)
    if isinstance(t, (T.DoubleType, T.RealType)):
        return float(x)
    return int(x)


#: rows per result batch (bounds every HTTP response body regardless
#: of result size — the reference targets bytes per page the same way,
#: MAIN/server/TaskResource.java DEFAULT_MAX_SIZE)
BATCH_ROWS = 65536


def _encode_batch(task: _Task, token: int, batch_rows: int) -> dict:
    """JSON-encode one columnar window of a finished task's host
    payload (typed-JSON column encoding: decimals as strings, dates
    ISO; NULLs as a parallel mask). Only the window serializes — a
    100M-row result never materializes as one JSON body."""
    from trino_tpu import types as T

    payload = task.payload
    if payload is None:
        return {"columns": [], "cols": [], "nulls": [],
                "types": [], "token": token, "nextToken": None}
    lo = token * batch_rows
    hi = min(lo + batch_rows, task.n_rows)
    cols_out, nulls_out, types_out = [], [], []
    for t, (values, valid) in zip(payload["types"], payload["cols"]):
        v = values[lo:hi]
        if isinstance(t, T.ArrayType):
            el = t.element
            out = [
                None if row is None else [
                    _json_element(el, x) for x in row
                ]
                for row in v
            ]
        elif isinstance(t, T.DecimalType):
            import decimal as _d

            if v.ndim == 2:
                out = [
                    str(_d.Decimal(
                        int(x[0]) * (1 << 32) + int(x[1])
                    ).scaleb(-t.scale))
                    for x in v
                ]
            else:
                out = [
                    str(_d.Decimal(int(x)).scaleb(-t.scale)) for x in v
                ]
        elif isinstance(t, T.DateType):
            out = [T.format_date(int(x)) for x in v]
        elif isinstance(t, T.TimestampType):
            out = [T.format_timestamp(int(x)) for x in v]
        elif isinstance(t, T.BooleanType):
            out = [bool(x) for x in v]
        elif isinstance(t, (T.DoubleType, T.RealType)):
            out = [float(x) for x in v]
        elif isinstance(t, (T.VarcharType,)):
            out = [str(x) for x in v]
        else:
            out = [int(x) for x in v]
        cols_out.append(out)
        nulls_out.append(
            None if valid is None else [not bool(x) for x in valid[lo:hi]]
        )
        types_out.append(str(t))
    return {
        "columns": list(payload["names"]),
        "types": types_out,
        "cols": cols_out,
        "nulls": nulls_out,
        "token": token,
        "nextToken": token + 1 if hi < task.n_rows else None,
    }


def main():
    import argparse
    import os
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=8091)
    ap.add_argument("--catalog", default="tpch")
    ap.add_argument("--schema", default="tiny")
    ap.add_argument("--mesh", action="store_true")
    ap.add_argument(
        "--parquet-root", default=None,
        help="mount a parquet directory tree as the worker catalog "
             "(--catalog names the catalog, --schema the schema)",
    )
    ap.add_argument(
        "--coordinator", default=None,
        help="coordinator base URI to announce/heartbeat against "
             "(joins the live cluster; omit for fixed-list fleets)",
    )
    ap.add_argument(
        "--node-id", default=None,
        help="stable membership identity (default worker-<port>)",
    )
    args = ap.parse_args()
    # Persistent compile cache stays ON in workers — but only behind
    # the compile service: backend.deserialize_executable wedges
    # permanently when driven from worker task threads (observed
    # repeatedly — even single-threaded, even against a cache
    # directory this same process just wrote). install() reroutes
    # exactly the cache-read/deserialize onto the service's one
    # dedicated thread with a deadline watchdog — task threads keep
    # compiling and executing in parallel; a wedged deserialize
    # degrades this process to in-memory-only compilation (the old
    # always-off behavior, now the fallback instead of the default)
    # rather than hanging the task. See trino_tpu/jit_cache.py.
    from trino_tpu import jit_cache

    jit_cache.install()
    mesh = None
    if args.mesh:
        from trino_tpu.parallel.core import make_mesh

        mesh = make_mesh()
    if args.parquet_root:
        catalog = "hive" if args.catalog == "tpch" else args.catalog
        runner = QueryRunner.parquet(
            args.parquet_root, schema=args.schema, mesh=mesh,
            catalog=catalog,
        )
    else:
        factory = (
            QueryRunner.tpcds if args.catalog == "tpcds"
            else QueryRunner.tpch
        )
        runner = factory(args.schema, mesh=mesh)
    if "memory" not in runner.metadata.catalogs():
        # memory-table writer fragments only BUFFER on workers (all
        # mutation happens in the coordinator-side TableFinish), but
        # the fragment's write handle still resolves its catalog here
        from trino_tpu.connectors.memory import MemoryConnector

        runner.metadata.register_catalog("memory", MemoryConnector())
    extra_pq = os.environ.get("TRINO_TPU_WORKER_EXTRA_PARQUET", "")
    if extra_pq:
        # writable lakehouse catalog on a shared filesystem: mount
        # "name=/path" (default name "hive") so writer tasks stage
        # part files into the SAME tree the coordinator commits
        from trino_tpu.connectors.parquet import ParquetConnector

        name, _, proot = extra_pq.rpartition("=")
        name = name or "hive"
        runner.metadata.register_catalog(name, ParquetConnector(proot))
    if os.environ.get("TRINO_TPU_PREWARM", "") not in ("", "0"):
        # trace-compile the canonical bucket set before accepting
        # tasks (cheap against a warm persistent cache; off by default
        # so test fleets spawn fast)
        from trino_tpu.exec import shapes

        info = shapes.prewarm()
        print(f"prewarm: {info}", flush=True)
    server = WorkerServer(runner, port=args.port)
    server.start()
    if args.coordinator:
        server.start_announcer(args.coordinator, args.node_id)
    ttl_env = os.environ.get("TRINO_TPU_ORPHAN_TTL_S", "")
    if ttl_env:
        # orphan reaper: cancel tasks + GC exchange buffers and spool
        # scratch of queries whose coordinator stops polling for more
        # than TTL (quarantine) + TTL (grace)
        server.start_orphan_reaper(float(ttl_env))
        print(f"orphan reaper on (ttl {ttl_env}s)", flush=True)
    print(f"worker ready on port {server.port}", flush=True)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        server.stop()
        sys.exit(0)


if __name__ == "__main__":
    main()
