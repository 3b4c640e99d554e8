"""Cache-backed compilation behind a watchdog: one compile thread per
process, deadline-guarded, degrading to in-memory-only.

Why this exists: the persistent XLA compilation cache is the engine's
cross-process warmth (a query-shaped program compiles once per machine,
not once per process — the analog of the reference's long-lived JVM
keeping its generated bytecode). But XLA:CPU's
``backend.deserialize_executable`` can wedge *permanently* when driven
from worker task threads — observed repeatedly: even single-threaded,
even against a cache directory the same process just wrote. The old
answer was to disable the on-disk cache in workers entirely
(cross-restart warmth lost, every worker restart re-paid the full
compile tax).

The answer here: route exactly the wedge-prone operation — the
persistent-cache read (``compilation_cache.get_executable_and_time``,
which calls ``deserialize_executable``) — onto ONE dedicated daemon
thread (``CompileService``) and watch it. ``install()`` wraps the jax
function so every cache lookup hops to the service thread; task
threads keep compiling (and executing) in parallel, they only wait on
the hop. If a lookup exceeds the deadline
(``TRINO_TPU_COMPILE_DEADLINE_S``, default 60s — a wedge is
indefinite, a legitimate deserialize is not), the process *degrades*:
the persistent cache turns off (including jax's memoized enablement),
the lookup reports a miss so the task thread compiles from scratch —
no deserialize, no wedge — and every later compilation stays
in-memory-only. Degraded mode is the old always-off behavior, now the
fallback instead of the default, and it is visible:
``trino_persistent_cache_degraded`` = 1 and
``trino_compile_deserialize_fallbacks_total`` counts the trips in
``/v1/metrics``.

The ``compile-deserialize`` fault site models the wedge for tests: an
armed injector makes the service thread block forever on the next job,
so the watchdog path is deterministically exercisable end-to-end.
"""

from __future__ import annotations

import os
import queue
import threading

from trino_tpu import fault, telemetry

__all__ = [
    "CompileService", "install", "get", "guarded", "DEADLINE_ENV",
]

DEADLINE_ENV = "TRINO_TPU_COMPILE_DEADLINE_S"
DEFAULT_DEADLINE_S = 60.0

class _Job:
    __slots__ = ("fn", "tag", "done", "result", "error")

    def __init__(self, fn, tag: str):
        self.fn = fn
        self.tag = tag
        self.done = threading.Event()
        self.result = None
        self.error: BaseException | None = None


class CompileService:
    """Single dedicated compile thread with a deadline watchdog.

    ``submit(fn)`` runs ``fn`` on the service thread and blocks for the
    result. Reentrant submits (from the service thread itself) and all
    submits after a degrade run inline. One deadline wait maximum per
    process: the first trip flips ``degraded`` and everything after
    short-circuits.
    """

    def __init__(self, deadline_s: float | None = None):
        if deadline_s is None:
            deadline_s = float(
                os.environ.get(DEADLINE_ENV, "") or DEFAULT_DEADLINE_S
            )
        self.deadline_s = deadline_s
        self.degraded = False
        self._q: queue.Queue[_Job] = queue.Queue()
        self._thread = threading.Thread(
            target=self._worker, name="compile-service", daemon=True
        )
        self._thread.start()

    def _worker(self):
        while True:
            job = self._q.get()
            try:
                fault.check("compile-deserialize", tag=job.tag)
            except fault.InjectedFault as e:
                # model the real failure: a wedged deserialize never
                # returns, so neither does this thread — recovery is
                # the caller's watchdog, exactly as in production
                job.error = e  # recorded for post-mortem, never raised
                threading.Event().wait()
            try:
                job.result = job.fn()
            except BaseException as e:  # noqa: BLE001 — relayed to caller
                job.error = e
            job.done.set()

    def _degrade(self, tag: str):
        """Abandon the service thread: persistent cache off, this and
        every later compilation inline (the pre-service worker
        behavior, now a visible degraded mode instead of the default)."""
        self.degraded = True
        import jax

        from jax._src import compilation_cache as _cc

        # the cache directory is placed from outside (trino_tpu/__init__)
        # and never reassigned in code: degrading flips the enable flag
        jax.config.update("jax_enable_compilation_cache", False)
        # jax memoizes cache-enablement on first use; without the
        # reset, the flag would not stop deserializes — and inline
        # compilation on task threads would hit the very wedge this
        # fallback exists to avoid
        _cc.reset_cache()
        telemetry.PERSISTENT_CACHE_DEGRADED.set(1)
        telemetry.COMPILE_DESERIALIZE_FALLBACKS.inc()

    def submit(self, fn, tag: str = "", fallback=None):
        """Run ``fn`` on the service thread; block for the result. On a
        deadline trip, degrade and return ``fallback()`` (default: run
        ``fn`` inline — callers for whom inline is itself the hazard,
        like the deserialize hop, pass an explicit miss sentinel)."""
        if self.degraded or threading.current_thread() is self._thread:
            return fn()
        job = _Job(fn, tag)
        self._q.put(job)
        if not job.done.wait(self.deadline_s):
            self._degrade(tag)
            return (fallback or fn)()
        if job.error is not None:
            raise job.error
        return job.result


_service: CompileService | None = None


def install(deadline_s: float | None = None) -> CompileService:
    """Create (or return) the process compile service and reroute
    jax's persistent-cache reads through it. Workers call this at
    startup; single-process engines never need to (their main thread
    does not exhibit the wedge)."""
    global _service
    if _service is None:
        _service = CompileService(deadline_s)
        _wrap_cache_read(_service)
    return _service


def _wrap_cache_read(svc: CompileService) -> None:
    """Monkeypatch ``compilation_cache.get_executable_and_time`` (the
    function that deserializes cached executables) so it runs on the
    service thread. A deadline trip degrades the process and reports a
    cache miss — the caller compiles from scratch on its own thread."""
    from jax._src import compilation_cache as cc

    orig = cc.get_executable_and_time

    # the signature of jax 0.9.0's compiler._cache_read call, which
    # turns any exception raised here into a warning and a cache miss
    def routed(cache_key, compile_options, backend, executable_devices):
        if svc.degraded:
            return None, None
        # span anchoring happens HERE, on the calling (task) thread:
        # the read hops to the service thread, where the thread anchor
        # (telemetry.set_active_span) would read nothing
        parent = telemetry.active_span()
        sp = (
            parent.child(
                "persistent-cache-read", "compile",
                key=str(cache_key)[:16],
            )
            if parent is not None else None
        )
        hit = False
        try:
            out = svc.submit(
                lambda: orig(
                    cache_key, compile_options, backend, executable_devices
                ),
                tag=f"deserialize:{str(cache_key)[:16]}",
                fallback=lambda: (None, None),
            )
            hit = out[0] is not None
            if hit:
                # catalog the deserialized executable: out[0] is a raw
                # LoadedExecutable, introspectable the same way the
                # executors' jitted programs are
                try:
                    from trino_tpu import program_catalog

                    loaded = out[0]
                    program_catalog.CATALOG.register(
                        ("persistent-cache", str(cache_key)),
                        source="persistent-cache",
                        label=str(cache_key)[:16],
                        resolver=lambda _l=loaded: _l,
                    )
                except Exception:
                    pass
            return out
        finally:
            if sp is not None:
                sp.finish()
                sp.attrs["hit"] = hit

    cc.get_executable_and_time = routed


def get() -> CompileService | None:
    return _service


def guarded(fn, tag: str = ""):
    """Run ``fn`` through the compile service when one is installed
    (worker processes), else inline (single-process engines never hit
    the task-thread deserialize wedge)."""
    svc = _service
    if svc is None:
        return fn()
    return svc.submit(fn, tag)
