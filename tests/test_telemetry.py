"""End-to-end query telemetry: stitched trace spans, Prometheus
metrics, per-stage/per-task stats.

The analog of the reference's observability tier (io.airlift.tracing
OpenTelemetry spans on the dispatcher/scheduler/worker paths, the JMX
/v1/status metric surface, and QueryStats behind EXPLAIN ANALYZE +
system.runtime.tasks): a query through a live 2-worker fleet must
yield ONE trace whose worker-side task spans stitch under the
coordinator's stage spans, /v1/metrics must serve Prometheus text on
every node, and the per-stage stats must agree across EXPLAIN
ANALYZE, QueryResult.stage_stats and system.runtime.tasks.
"""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from trino_tpu import fault, telemetry
from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.events import QueryCompletedEvent, StructuredLogListener
from trino_tpu.metadata import Metadata, Session
from trino_tpu.server.fleet import FleetRunner

BASE_PORT = 19000


# ---------------------------------------------------------------------------
# metrics registry units
# ---------------------------------------------------------------------------


def test_counter_labels_and_render():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("t_requests_total", "requests")
    c.inc(state="ok")
    c.inc(2, state="ok")
    c.inc(state="err")
    assert c.value(state="ok") == 3
    assert c.total() == 4
    text = reg.render()
    assert "# HELP t_requests_total requests" in text
    assert "# TYPE t_requests_total counter" in text
    assert 't_requests_total{state="ok"} 3' in text
    assert 't_requests_total{state="err"} 1' in text


def test_gauge_and_histogram_render():
    reg = telemetry.MetricsRegistry()
    g = reg.gauge("t_pool_bytes", "pool")
    g.set(100, pool="a")
    g.add(-25, pool="a")
    assert g.value(pool="a") == 75
    h = reg.histogram("t_latency_seconds", "lat", buckets=(0.1, 1.0))
    h.observe(0.05, op="x")
    h.observe(0.5, op="x")
    h.observe(5.0, op="x")
    assert h.count(op="x") == 3
    text = reg.render()
    assert 't_pool_bytes{pool="a"} 75' in text
    assert 't_latency_seconds_bucket{le="0.1",op="x"} 1' in text
    assert 't_latency_seconds_bucket{le="+Inf",op="x"} 3' in text
    assert 't_latency_seconds_count{op="x"} 3' in text


def test_unused_family_renders_zero_sample():
    reg = telemetry.MetricsRegistry()
    reg.counter("t_never_incremented_total", "zero")
    assert "t_never_incremented_total 0" in reg.render()


def test_counting_cache_hit_miss_accounting():
    cache = telemetry.CountingCache("t_unit")
    h0 = telemetry.JIT_CACHE_HITS.value(cache="t_unit")
    m0 = telemetry.JIT_CACHE_MISSES.value(cache="t_unit")
    assert cache.get("k") is None
    cache["k"] = 1
    assert cache.get("k") == 1
    assert telemetry.JIT_CACHE_HITS.value(cache="t_unit") == h0 + 1
    assert telemetry.JIT_CACHE_MISSES.value(cache="t_unit") == m0 + 1


# ---------------------------------------------------------------------------
# span trees
# ---------------------------------------------------------------------------


def test_tracer_span_hierarchy_and_chrome_json():
    tracer = telemetry.Tracer("q1")
    with tracer.span("planning", "planning"):
        pass
    with tracer.span("execute", "execution") as ex:
        ex.child("operator scan", "operator").finish()
    trace = tracer.finish()
    kinds = {s.kind for s in trace.spans()}
    assert {"query", "planning", "execution", "operator"} <= kinds
    root = trace.root
    assert all(s.trace_id == root.trace_id for s in trace.spans())
    doc = json.loads(trace.to_chrome_json())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == len(trace.spans())
    for e in xs:
        assert e["ts"] > 0 and e["dur"] >= 0


def test_attach_stitches_worker_subtree():
    tracer = telemetry.Tracer("q2")
    stage = tracer.start("stage 0", "stage")
    # worker side: detached task span rooted at the shipped parent id
    wspan = telemetry.Span(
        name="task s0t0.0", kind="task", parent_id=stage.span_id,
        trace_id=tracer.trace_id, node="w1",
    )
    wspan.child("execute", "execution").finish()
    wspan.finish()
    attached = tracer.attach(wspan.to_dict())
    assert attached is not None
    stage.finish()
    trace = tracer.finish()
    tasks = trace.find(kind="task")
    assert len(tasks) == 1 and tasks[0].node == "w1"
    assert tasks[0] in stage.children


# ---------------------------------------------------------------------------
# chaos + listener counters
# ---------------------------------------------------------------------------


def test_chaos_injection_counter_tracks_seeded_schedule():
    inj = fault.FaultInjector(seed=7)
    inj.arm("spool-read", times=2)
    fault.activate(inj)
    try:
        before = telemetry.CHAOS_INJECTIONS.value(site="spool-read")
        fired = 0
        for attempt in range(4):
            try:
                fault.check("spool-read", tag="t", attempt=attempt)
            except fault.InjectedFault:
                fired += 1
        assert fired == 2
        after = telemetry.CHAOS_INJECTIONS.value(site="spool-read")
        assert after - before == fired
    finally:
        fault.deactivate()


def test_structured_log_listener_and_failure_counter(tmp_path):
    path = tmp_path / "queries.jsonl"
    lst = StructuredLogListener(path=str(path))
    ev = QueryCompletedEvent(
        query_id="q9", user="u", sql="select 1", state="FINISHED",
        elapsed_ms=4.2, rows=1, error=None, peak_memory_bytes=0,
        planning_ms=1.0, execution_ms=3.0, tasks_retried=1,
    )
    lst.query_completed(ev)
    rec = json.loads(path.read_text().splitlines()[0])
    assert rec["query_id"] == "q9"
    assert rec["tasks_retried"] == 1
    assert rec["planning_ms"] == 1.0

    class Exploding:
        def query_completed(self, event):
            raise RuntimeError("boom")

    from trino_tpu.events import fire_query_completed

    before = telemetry.LISTENER_FAILURES.value(listener="Exploding")
    fire_query_completed([Exploding()], ev)  # must not raise
    assert telemetry.LISTENER_FAILURES.value(
        listener="Exploding"
    ) == before + 1


def test_structured_log_listener_requires_one_sink(tmp_path):
    with pytest.raises(ValueError):
        StructuredLogListener()
    with pytest.raises(ValueError):
        StructuredLogListener(path=str(tmp_path / "x"), stream=sys.stderr)


# ---------------------------------------------------------------------------
# local engine: stage_stats + EXPLAIN ANALYZE + system.runtime.tasks
# ---------------------------------------------------------------------------


def test_local_query_result_carries_trace_and_stats():
    runner = QueryRunner.tpch("tiny")
    res = runner.execute("select count(*) from region")
    assert res.trace is not None
    kinds = {s.kind for s in res.trace.spans()}
    assert "query" in kinds and "planning" in kinds
    assert len(res.stage_stats) == 1
    st = res.stage_stats[0]
    assert st["rows_out"] == 1
    assert res.task_stats[0]["state"] == "FINISHED"
    assert res.planning_ms >= 0 and res.execution_ms >= 0


def test_local_explain_analyze_agrees_with_runtime_tasks():
    from trino_tpu.server.coordinator import Coordinator

    coord = Coordinator().start()
    try:

        def run(sql):
            q = coord.submit(sql)
            deadline = time.monotonic() + 60
            while q.state not in ("FINISHED", "FAILED"):
                assert time.monotonic() < deadline
                time.sleep(0.02)
            assert q.state == "FINISHED", q.error
            return q.result

        res = run("explain analyze select count(*) from nation")
        text = "\n".join(r[0] for r in res.rows)
        st = res.stage_stats[0]
        # the rendered stage line and the machine-readable stats are
        # the same numbers
        assert f"out: {st['rows_out']} rows" in text
        tasks = run(
            "select query_id, rows_out from system.runtime.tasks"
        ).rows
        by_query = {r[0]: r[1] for r in tasks}
        assert by_query[res.task_stats[0]["query_id"]] == st["rows_out"]
    finally:
        coord.stop()


# ---------------------------------------------------------------------------
# live 2-worker fleet: stitching, scrapes, stats agreement
# ---------------------------------------------------------------------------


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
def fleet(workers, tmp_path_factory):
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    return FleetRunner(
        workers, md, Session(catalog="tpch", schema="tiny"),
        spool_root=str(tmp_path_factory.mktemp("spool")),
        n_partitions=4,
    )


def _scrape(uri: str) -> str:
    with urllib.request.urlopen(f"{uri}/v1/metrics", timeout=10) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        return r.read().decode()


def _parse_sample(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            metric = line.split(" ")[0]
            if metric == name or metric.startswith(name + "{"):
                total += float(line.rsplit(" ", 1)[1])
    return total


def test_fleet_trace_stitches_across_workers(fleet, workers):
    res = fleet.execute(
        "select o_orderpriority, count(*) c from orders "
        "group by o_orderpriority order by c desc"
    )
    trace = res.trace
    assert trace is not None
    root = trace.root
    assert root.kind == "query"
    stages = trace.find(kind="stage")
    tasks = trace.find(kind="task")
    assert stages and tasks
    # every worker executed at least one stitched task span
    nodes = {s.node for s in tasks}
    assert len(nodes) == 2
    stage_ids = {s.span_id for s in stages}
    assert all(t.parent_id in stage_ids for t in tasks)
    # worker spans nest spool reads/writes and execution
    kinds = {s.kind for s in trace.spans()}
    assert {"planning", "rpc", "spool", "execution"} <= kinds
    # the whole tree shares one trace id
    assert all(s.trace_id == root.trace_id for s in trace.spans())
    # exportable as valid Chrome trace-event JSON
    doc = json.loads(trace.to_chrome_json())
    names = {
        e["args"]["name"]
        for e in doc["traceEvents"] if e["ph"] == "M"
    }
    assert "coordinator" in names and len(names) == 3


def test_fleet_stage_stats_agree_with_task_stats(fleet):
    res = fleet.execute("select count(*) from lineitem")
    assert res.rows[0][0] > 0
    assert res.stage_stats and res.task_stats
    by_stage: dict = {}
    for t in res.task_stats:
        if t["state"] != "FINISHED":
            continue
        agg = by_stage.setdefault(t["stage_id"], [0, 0])
        agg[0] += t["rows_out"]
        agg[1] += t["bytes_out"]
    for st in res.stage_stats:
        rows, bytes_ = by_stage[st["stage_id"]]
        assert st["rows_out"] == rows
        assert st["bytes_out"] == bytes_
    # the root stage feeds the client result
    assert res.stage_stats[-1]["rows_out"] == len(res.rows)


def test_fleet_explain_analyze_renders_stage_stats(fleet):
    res = fleet.execute(
        "explain analyze select count(*) from orders"
    )
    text = "\n".join(r[0] for r in res.rows)
    assert "ms total" in text and "rows," in text
    for st in res.stage_stats:
        assert f"Stage {st['stage_id']}:" in text
        assert f"out: {st['rows_out']} rows" in text


def test_worker_metrics_scrape_counts_tasks(fleet, workers):
    before = [_parse_sample(
        _scrape(w), "trino_worker_tasks_total"
    ) for w in workers]
    fleet.execute("select count(*) from region")
    after = [_parse_sample(
        _scrape(w), "trino_worker_tasks_total"
    ) for w in workers]
    assert sum(after) > sum(before)
    text = _scrape(workers[0])
    for family in (
        "trino_worker_tasks_total",
        "trino_spool_bytes_written_total",
        "trino_spool_bytes_read_total",
        "trino_exchange_rows_total",
        "trino_xla_compile_total",
        "trino_memory_pool_reserved_bytes",
    ):
        assert family in text, family


def test_coordinator_metrics_endpoint():
    from trino_tpu.server.coordinator import Coordinator

    coord = Coordinator().start()
    try:
        q = coord.submit("select 1")
        deadline = time.monotonic() + 60
        while q.state not in ("FINISHED", "FAILED"):
            assert time.monotonic() < deadline
            time.sleep(0.02)
        text = _scrape(f"http://127.0.0.1:{coord.port}")
        for family in (
            "trino_queries_total",
            "trino_query_retries_total",
            "trino_tasks_retried_total",
            "trino_chaos_injections_total",
            "trino_rpc_latency_seconds",
            "trino_event_listener_failures_total",
        ):
            assert family in text, family
        assert _parse_sample(text, "trino_queries_total") >= 1
    finally:
        coord.stop()


# ---------------------------------------------------------------------------
# exposition-format compliance (parse with the official client)
# ---------------------------------------------------------------------------


def test_metrics_prometheus_client_round_trip():
    pytest.importorskip("prometheus_client")
    from prometheus_client.parser import text_string_to_metric_families

    reg = telemetry.MetricsRegistry()
    c = reg.counter(
        "t_rt_requests_total", 'help with "quotes", a \\ and\na newline'
    )
    c.inc(3, state='o"k', path="a\\b\nc")
    h = reg.histogram("t_rt_latency_seconds", "lat", buckets=(0.1, 1.0))
    h.observe(0.05, op="x")
    reg.gauge("t_rt_pool_bytes", "pool").set(7)
    text = reg.render()
    assert text.endswith("\n")
    samples = [
        s
        for fam in text_string_to_metric_families(text)
        for s in fam.samples
    ]
    by_name = {}
    for s in samples:
        by_name.setdefault(s.name, []).append(s)
    # escaped label values come back verbatim
    (req,) = by_name["t_rt_requests_total"]
    assert req.value == 3
    assert req.labels == {"state": 'o"k', "path": "a\\b\nc"}
    buckets = {
        s.labels["le"]: s.value
        for s in by_name["t_rt_latency_seconds_bucket"]
    }
    assert buckets["0.1"] == 1 and buckets["+Inf"] == 1
    assert by_name["t_rt_latency_seconds_count"][0].value == 1
    assert by_name["t_rt_pool_bytes"][0].value == 7

    # the REAL process registry — every live family must parse too
    fams = list(
        text_string_to_metric_families(telemetry.REGISTRY.render())
    )
    assert fams


def test_rpc_latency_histogram_has_submillisecond_buckets():
    # the poll path sits well under 10ms; the default bucket ladder
    # started at 1ms and lumped everything below it together
    assert 0.0005 in telemetry.RPC_LATENCY.buckets
    assert 0.0025 in telemetry.RPC_LATENCY.buckets
    assert 0.0005 in telemetry.OPERATOR_SELF_TIME.buckets


# ---------------------------------------------------------------------------
# per-operator attribution: local engine
# ---------------------------------------------------------------------------


def _walk_ops(ops):
    for op in ops:
        yield op
        yield from _walk_ops(op.get("children") or [])


def test_local_query_info_operator_tree_and_roofline():
    runner = QueryRunner.tpch("tiny")
    res = runner.execute(
        "select sum(l_extendedprice * (1 - l_discount)) from lineitem"
    )
    info = res.query_info
    assert info["state"] == "FINISHED"
    assert info["query_id"]
    (stage,) = info["stages"]
    (task,) = stage["tasks"]
    flat = list(_walk_ops(task["operators"]))
    assert flat
    assert all(op["wall_ms"] >= 0 for op in flat)
    assert any(op["wall_ms"] > 0 for op in flat)
    # the lazy XLA cost join ran: some operator carries flops and the
    # derived roofline attribution
    costed = [op for op in flat if op.get("flops")]
    assert costed, flat
    assert any("achieved_gflops" in op for op in costed)
    # profile_json is the same tree, serialized
    doc = json.loads(res.profile_json())
    assert doc["query_id"] == info["query_id"]


def test_local_explain_analyze_prints_roofline():
    runner = QueryRunner.tpch("tiny")
    res = runner.execute(
        "explain analyze select sum(l_extendedprice * (1 - l_discount)) "
        "from lineitem"
    )
    text = "\n".join(r[0] for r in res.rows)
    assert "self" in text
    assert "xla:" in text, text
    assert "GFLOP/s achieved" in text
    assert "% of" in text and "roofline" in text


def test_slow_query_log_writes_profile_summary(tmp_path):
    runner = QueryRunner.tpch("tiny")
    path = tmp_path / "slow.jsonl"
    runner.metadata.event_listeners = [
        StructuredLogListener(path=str(path))
    ]
    # default: disabled — nothing written
    runner.execute("select count(*) from region")
    recs = [
        json.loads(line)
        for line in path.read_text().splitlines()
        if line
    ] if path.exists() else []
    assert not [r for r in recs if r.get("event") == "slow_query"]
    # threshold below any real run: one slow_query line with the top-3
    runner.session.properties["slow_query_log_threshold"] = "1ms"
    runner.execute("select count(*) from nation")
    recs = [
        json.loads(line)
        for line in path.read_text().splitlines()
        if line
    ]
    slow = [r for r in recs if r.get("event") == "slow_query"]
    assert len(slow) == 1
    rec = slow[0]
    assert rec["query_id"] and rec["sql"].startswith("select count")
    assert rec["elapsed_ms"] > 1e-3
    assert rec["top_operators"]
    assert all("self_ms" in t for t in rec["top_operators"])


# ---------------------------------------------------------------------------
# per-operator attribution: live 2-worker fleet + QueryInfo API
# ---------------------------------------------------------------------------


def test_fleet_operator_stats_sum_consistently_q3(fleet):
    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.testing.golden import load_tpch_sqlite, to_sqlite

    res = fleet.execute(QUERIES["q03"])
    oracle = load_tpch_sqlite(TpchConnector().data("tiny"))
    expect = oracle.execute(to_sqlite(QUERIES["q03"])).fetchall()
    # query level agrees with the sqlite oracle
    assert len(res.rows) == len(expect)

    finished = [t for t in res.task_stats if t["state"] == "FINISHED"]
    assert finished
    tasks_with_ops = 0
    for t in finished:
        ops = t.get("operator_stats") or []
        if not ops:
            continue
        tasks_with_ops += 1
        # operator -> task: exactly one root, and its output IS the
        # task's spooled output
        roots = [o for o in ops if o.get("parent_id") is None]
        assert len(roots) == 1
        assert roots[0]["rows_out"] == t["rows_out"], (roots, t)
        # non-zero host wall clock on every operator record
        assert all(o["wall_ms"] >= 0 for o in ops)
        assert any(o["wall_ms"] > 0 for o in ops)
    assert tasks_with_ops > 0
    # task -> stage -> query: already asserted by
    # test_fleet_stage_stats_agree_with_task_stats; re-check the root
    assert res.stage_stats[-1]["rows_out"] == len(res.rows)


def test_fleet_query_info_tree(fleet):
    res = fleet.execute(
        "select o_orderpriority, count(*) from orders "
        "group by o_orderpriority"
    )
    info = res.query_info
    assert info is not None and info["state"] == "FINISHED"
    assert info["stages"], info
    ops_seen = 0
    for st in info["stages"]:
        assert st["tasks"]
        for task in st["tasks"]:
            for op in _walk_ops(task.get("operators") or []):
                ops_seen += 1
                assert "self_ms" in op
    assert ops_seen > 0
    doc = json.loads(res.profile_json())
    assert doc["query_id"] == info["query_id"]


def test_worker_scrape_mid_query_has_operator_families(fleet, workers):
    import threading

    saved = dict(fleet.session.properties)
    fleet.session.properties["fleet_task_delay_ms"] = 150
    try:
        done = threading.Event()
        results = {}

        def run():
            try:
                results["res"] = fleet.execute(
                    "select count(*) from customer"
                )
            finally:
                done.set()

        th = threading.Thread(target=run, daemon=True)
        th.start()
        time.sleep(0.2)  # inside the delayed task window
        mid = [_scrape(w) for w in workers]  # must answer mid-query
        done.wait(timeout=120)
        th.join(timeout=10)
    finally:
        fleet.session.properties = saved
    assert results["res"].rows[0][0] > 0
    for text in mid:
        assert "trino_operator_self_time_seconds" in text
    # after at least one profiled task, the histogram has samples
    post = [_scrape(w) for w in workers]
    assert sum(
        _parse_sample(t, "trino_operator_self_time_seconds_count")
        for t in post
    ) > 0


def test_coordinator_query_info_endpoints():
    from trino_tpu.server.coordinator import Coordinator

    coord = Coordinator().start()
    try:
        q = coord.submit(
            "select sum(l_extendedprice) from lineitem"
        )
        deadline = time.monotonic() + 60
        while q.state not in ("FINISHED", "FAILED"):
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert q.state == "FINISHED", q.error
        base = f"http://127.0.0.1:{coord.port}"
        with urllib.request.urlopen(f"{base}/v1/query", timeout=10) as r:
            listing = json.loads(r.read())
        mine = [x for x in listing if x["query_id"] == q.query_id]
        assert mine and mine[0]["state"] == "FINISHED"
        assert "elapsed_ms" in mine[0]
        with urllib.request.urlopen(
            f"{base}/v1/query/{q.query_id}", timeout=10
        ) as r:
            info = json.loads(r.read())
        assert info["query_id"] == q.query_id
        assert info["state"] == "FINISHED"
        ops = [
            op
            for st in info.get("stages") or []
            for task in st["tasks"]
            for op in _walk_ops(task.get("operators") or [])
        ]
        assert ops, info
        assert any(op["wall_ms"] > 0 for op in ops)
        # unknown id -> 404
        try:
            urllib.request.urlopen(
                f"{base}/v1/query/nope", timeout=10
            )
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404

        # system.runtime.queries grew user + peak_memory_bytes
        q2 = coord.submit(
            "select query_id, user, peak_memory_bytes, state "
            "from system.runtime.queries"
        )
        deadline = time.monotonic() + 60
        while q2.state not in ("FINISHED", "FAILED"):
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert q2.state == "FINISHED", q2.error
        ids = [r[0] for r in q2.result.rows]
        assert q.query_id in ids
    finally:
        coord.stop()


# ---- device identity: peaks keyed by device_kind, /v1/info (PR 22) ---------


class _FakeDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_peak_rates_v5e_row(monkeypatch):
    import jax

    from trino_tpu import profiler

    monkeypatch.delenv("TRINO_TPU_PEAK_GFLOPS", raising=False)
    monkeypatch.delenv("TRINO_TPU_PEAK_GBPS", raising=False)
    # the kind string a v5e chip reports (seen on the chip, PR 22)
    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeDevice("TPU v5 lite")]
    )
    assert profiler.peak_rates() == (197_000.0, 819.0)


def test_peak_rates_unknown_device_raises(monkeypatch):
    import jax

    from trino_tpu import profiler

    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeDevice("TPU v9 imaginary")]
    )
    with pytest.raises(LookupError, match="TPU v9 imaginary"):
        profiler.peak_rates()


def test_device_info_does_not_initialise_a_backend():
    # a fresh interpreter: importing the engine must not take a device,
    # and device_info() must not either (host-only roles answer
    # /v1/info without one)
    import subprocess
    import sys

    code = (
        "import trino_tpu\n"
        "from trino_tpu import profiler\n"
        "from jax._src import xla_bridge\n"
        "i = profiler.device_info()\n"
        "assert i['platform'] is None and i['device_count'] == 0, i\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "import jax; jax.devices()\n"
        "i = profiler.device_info()\n"
        "assert i['platform'] == 'cpu' and i['device_kind'] == 'cpu', i\n"
        "assert len(i['device_memory']) == i['device_count'] >= 1\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True,
    )
    assert p.returncode == 0, p.stderr[-2000:]


def test_v1_info_names_the_device_on_both_node_types():
    from trino_tpu.server.coordinator import Coordinator
    from trino_tpu.server.worker import WorkerServer

    runner = QueryRunner.tpch("tiny")
    runner.execute("select count(*) from region")  # a backend exists
    coord = Coordinator(runner=runner, port=0).start()
    worker = WorkerServer(QueryRunner.tpch("tiny"), port=0)
    worker.start()
    try:
        for uri in (coord.uri, f"http://127.0.0.1:{worker.port}"):
            with urllib.request.urlopen(f"{uri}/v1/info", timeout=5) as r:
                info = json.loads(r.read())
            assert info["platform"] == "cpu"
            assert info["device_kind"] == "cpu"
            assert info["device_count"] >= 1
            assert len(info["device_memory"]) == info["device_count"]
    finally:
        coord.stop()
        worker.stop()


def test_spawned_children_inherit_no_platform(monkeypatch):
    from trino_tpu.testing import chaos

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    env = chaos.child_env(None)
    assert "JAX_PLATFORMS" not in env and "XLA_FLAGS" not in env
    assert chaos.child_env("cpu", {"A": "b"})["JAX_PLATFORMS"] == "cpu"
    assert chaos.child_env("cpu", {"A": "b"})["A"] == "b"
