"""CompileService: the single compile thread + deserialize watchdog.

The persistent XLA cache wedges when ``deserialize_executable`` runs
from worker task threads, so workers route compilation through one
dedicated thread with a deadline (trino_tpu.jit_cache). These tests
pin the watchdog contract: a wedge (modeled by the
``compile-deserialize`` fault site) must degrade the process to
in-memory-only compilation WITHOUT failing the task, and degraded mode
must be visible in ``/v1/metrics``.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import jax
import pytest

from trino_tpu import fault, jit_cache, telemetry
from trino_tpu.testing import chaos

BASE_PORT = 18910


@pytest.fixture(autouse=True)
def _isolate():
    from jax._src import compilation_cache as cc

    prev_enabled = jax.config.jax_enable_compilation_cache
    yield
    fault.deactivate()
    # a degrade flips process-global state; undo it for later modules
    # (reset_cache clears jax's memoized enablement so the restored
    # flag actually takes effect on the next compile)
    jax.config.update("jax_enable_compilation_cache", prev_enabled)
    cc.reset_cache()
    telemetry.PERSISTENT_CACHE_DEGRADED.set(0)


# ---------------------------------------------------------------------------
# CompileService unit
# ---------------------------------------------------------------------------


def test_submit_runs_on_service_thread_and_returns():
    svc = jit_cache.CompileService(deadline_s=10)
    assert svc.submit(lambda: 41 + 1) == 42
    assert not svc.degraded


def test_submit_relays_exceptions():
    svc = jit_cache.CompileService(deadline_s=10)
    with pytest.raises(ZeroDivisionError):
        svc.submit(lambda: 1 / 0)
    # an exception is a normal outcome, not a wedge
    assert not svc.degraded
    assert svc.submit(lambda: "still alive") == "still alive"


def test_reentrant_submit_runs_inline():
    # a compile that itself reaches guarded code must not deadlock the
    # single service thread
    svc = jit_cache.CompileService(deadline_s=5)
    assert svc.submit(lambda: svc.submit(lambda: 7)) == 7


def test_guarded_is_inline_without_a_service():
    prev = jit_cache._service
    jit_cache._service = None
    try:
        assert jit_cache.get() is None
        assert jit_cache.guarded(lambda: "inline") == "inline"
    finally:
        jit_cache._service = prev


def test_wedged_deserialize_trips_watchdog_and_degrades():
    inj = fault.FaultInjector()
    inj.arm("compile-deserialize", times=1)
    fault.activate(inj)
    svc = jit_cache.CompileService(deadline_s=0.8)
    f0 = telemetry.COMPILE_DESERIALIZE_FALLBACKS.total()
    t0 = time.monotonic()
    # the service thread blocks forever; the caller waits out the
    # deadline, degrades, and still gets its result inline
    assert svc.submit(lambda: "ok", tag="wedge-me") == "ok"
    assert time.monotonic() - t0 >= 0.8
    assert svc.degraded
    assert telemetry.COMPILE_DESERIALIZE_FALLBACKS.total() - f0 == 1
    assert telemetry.PERSISTENT_CACHE_DEGRADED.value() == 1
    # degraded means in-memory-only: the persistent cache is off, by
    # the enable flag — the directory is placed from outside and stays
    assert not jax.config.jax_enable_compilation_cache
    # and every later submit short-circuits inline, no deadline wait
    t1 = time.monotonic()
    assert svc.submit(lambda: 2) == 2
    assert time.monotonic() - t1 < 0.5


def test_wedged_submit_returns_explicit_fallback():
    # the deserialize hop cannot fall back to running inline (inline
    # IS the hazard) — it passes a miss sentinel instead
    inj = fault.FaultInjector()
    inj.arm("compile-deserialize", times=1)
    fault.activate(inj)
    svc = jit_cache.CompileService(deadline_s=0.5)
    out = svc.submit(
        lambda: "deserialized", tag="d", fallback=lambda: (None, None)
    )
    assert out == (None, None)
    assert svc.degraded


# ---------------------------------------------------------------------------
# the reroute itself, called the way jax 0.9.0 calls it
# ---------------------------------------------------------------------------


def test_routed_cache_read_takes_jax_090_call(tmp_path):
    """``compiler._cache_read`` passes (cache_key, compile_options,
    backend, executable_devices) and swallows any exception into a
    warning + miss — so a wrong signature here is a silently dead
    cache. Write an entry, drop the in-memory executables, read it
    back through the installed reroute with errors raised."""
    import warnings

    import jax.numpy as jnp
    from jax._src import compilation_cache as cc

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_raise = jax.config.jax_raise_persistent_cache_errors
    jit_cache.install()
    assert cc.get_executable_and_time.__name__ == "routed"
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_raise_persistent_cache_errors", True)
    cc.reset_cache()
    try:
        def f(x):
            return jnp.cumsum(x * 3 + 1)

        x = jnp.arange(4099)
        want = jax.jit(f)(x).block_until_ready()
        assert list(tmp_path.iterdir()), "no cache entry written"
        jax.clear_caches()
        hits0 = telemetry.compile_snapshot()["persistent_hits"]
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            got = jax.jit(f)(x).block_until_ready()
        assert (got == want).all()
        assert telemetry.compile_snapshot()["persistent_hits"] - hits0 >= 1
        assert not jit_cache.get().degraded
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_raise_persistent_cache_errors", prev_raise)
        cc.reset_cache()


# ---------------------------------------------------------------------------
# end-to-end: a real worker process survives the wedge
# ---------------------------------------------------------------------------


def _metric_value(text: str, name: str):
    for line in text.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            return float(line.rsplit(" ", 1)[1])
    return None


def test_worker_wedge_degrades_without_failing_the_task(tmp_path_factory):
    # short watchdog deadline so the trip costs ~2s, not 60
    os.environ[jit_cache.DEADLINE_ENV] = "2"
    try:
        procs, uris = chaos.spawn_workers(1, base_port=BASE_PORT, platform="cpu")
    finally:
        os.environ.pop(jit_cache.DEADLINE_ENV, None)
    try:
        fleet = chaos.make_fleet(
            uris, str(tmp_path_factory.mktemp("spool"))
        )
        inj = fault.FaultInjector()
        inj.arm("compile-deserialize", times=1)
        fault.activate(inj)
        try:
            # the spec rides the stage-task request into the worker;
            # its compile service wedges on the first job, the
            # watchdog degrades it, and the task must still FINISH
            result = fleet.execute(
                "select l_returnflag, sum(l_quantity) from lineitem"
                " group by l_returnflag"
            )
        finally:
            fault.deactivate()
        assert len(result.rows) == 3  # A/N/R — the query completed
        with urllib.request.urlopen(
            f"{uris[0]}/v1/metrics", timeout=5
        ) as resp:
            text = resp.read().decode()
        assert _metric_value(text, "trino_persistent_cache_degraded") == 1.0
        assert (
            _metric_value(text, "trino_compile_deserialize_fallbacks_total")
            >= 1.0
        )
    finally:
        chaos.stop_workers(procs)


# ---------------------------------------------------------------------------
# a fresh process against a warm persistent cache
# ---------------------------------------------------------------------------

_PROBE = """
import json, sys
from trino_tpu import telemetry
from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.engine import QueryRunner

telemetry.install_jax_compile_hook()
runner = QueryRunner.tpch("tiny")
report = {}
for q in sys.argv[1:]:
    c0 = telemetry.compile_snapshot()
    rows = runner.execute(QUERIES[q]).rows
    c1 = telemetry.compile_snapshot()
    report[q] = {
        "compiles": int(c1["compiles"] - c0["compiles"]),
        "persistent_hits": int(
            c1["persistent_hits"] - c0["persistent_hits"]
        ),
        "rows": json.dumps(rows, default=str),
    }
print("PROBE " + json.dumps(report))
"""


def _probe(cache_dir, qids) -> dict:
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE, *qids],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("PROBE ")]
    return json.loads(line[-1][len("PROBE "):])


def test_fresh_process_compiles_at_most_one_program_a_query(tmp_path):
    """What a restart pays (the warm-cache bar of the compile-tax work):
    a second process, against the persistent cache the first one
    filled, deserializes its programs — at most one real compile a
    query — and answers the same rows."""
    qids = ("q01", "q03")
    cold = _probe(tmp_path, qids)
    assert any(cold[q]["compiles"] > 1 for q in qids), cold
    assert any(tmp_path.iterdir()), "the first process cached nothing"
    warm = _probe(tmp_path, qids)
    for q in qids:
        assert warm[q]["compiles"] <= 1, (q, warm[q])
        assert warm[q]["persistent_hits"] >= 1, (q, warm[q])
        assert warm[q]["rows"] == cold[q]["rows"], q
