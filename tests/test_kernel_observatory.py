"""Kernel observatory: compiled-program catalog and HLO-scope
device-time attribution.

The device tier's observability stack (the layer below PR 7's operator
roofline): every canonical-bucket compile registers a catalog entry
(XLA cost model + memory_analysis HBM footprint + the HLO
instruction→named-scope map), ``jax.profiler`` captures attribute
device time to named plan operators INSIDE a fused program.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from trino_tpu import program_catalog
from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.engine import QueryRunner
from trino_tpu.metadata import Metadata, Session
from trino_tpu.server.fleet import FleetRunner

BASE_PORT = 19210


# ---------------------------------------------------------------------------
# catalog units: registration, hits, retention/eviction
# ---------------------------------------------------------------------------


def test_catalog_register_hits_and_idempotence():
    cat = program_catalog.ProgramCatalog(max_entries=8)
    e = cat.register(("k", 1), source="local", label="Filter")
    assert e.program_id == program_catalog.ProgramCatalog.program_id(
        ("k", 1)
    )
    assert e.hits == 0 and e.source == "local"
    cat.note_hit(("k", 1))
    cat.note_hit(("k", 1))
    # re-registration refreshes, never resets the hit history
    e2 = cat.register(("k", 1), source="local", label="Filter",
                      compile_s=0.5)
    assert e2 is e and e.hits == 2 and e.compile_s == 0.5
    assert len(cat) == 1
    cat.note_compile_seconds(("k", 1), 1.25)
    assert e.compile_s == 1.25


def test_catalog_lru_eviction_past_cap():
    cat = program_catalog.ProgramCatalog(max_entries=3)
    for i in range(3):
        cat.register(("k", i), source="local", label=f"c{i}")
    # touch k0 so k1 becomes the least-recently-used entry
    cat.note_hit(("k", 0))
    cat.register(("k", 99), source="mesh", label="new")
    assert len(cat) == 3 and cat.evictions == 1
    assert cat.entry_for(("k", 1)) is None  # LRU victim
    assert cat.entry_for(("k", 0)) is not None
    assert cat.entry_for(("k", 99)) is not None


def test_catalog_resolver_failure_is_cached_not_retried():
    cat = program_catalog.ProgramCatalog(max_entries=4)
    calls = []

    def bad_resolver():
        calls.append(1)
        raise RuntimeError("backend gone")

    cat.register(("k",), source="local", label="x",
                 resolver=bad_resolver)
    assert cat.cost(("k",)) is None
    assert cat.cost(("k",)) is None  # one attempt only
    assert len(calls) == 1
    snap = cat.snapshot()
    assert snap[0]["resolve_error"].startswith("RuntimeError")


def test_scope_map_from_hlo_extracts_named_scopes():
    hlo = """
HloModule jit_f
%fused_computation {
  ROOT %mul.1 = f32[8]{0} multiply(a, b), metadata={op_name="jit(f)/jit(main)/op0:Filter/mul" source_file="x.py"}
}
ENTRY %main {
  %broadcast_multiply_fusion = f32[8]{0} fusion(...), kind=kLoop, metadata={op_name="jit(f)/jit(main)/op1:Aggregate/reduce"}
  %add.2 = f32[8]{0} add(c, d), metadata={op_name="jit(f)/jit(main)/transpose"}
}
"""
    scopes = program_catalog.scope_map_from_hlo(hlo)
    assert scopes["mul.1"] == "op0:Filter"
    assert scopes["broadcast_multiply_fusion"] == "op1:Aggregate"
    assert "add.2" not in scopes  # no opN: component in its op_name


def test_scope_map_reads_a_one_operator_programs_scope():
    """``op:<NodeType>`` (``local._named_jit``) beside the chains'
    ``op<i>:<NodeType>``; kernel and site scopes are not operators."""
    hlo = """
HloModule jit_join_count, is_scheduled=true
ENTRY %main {
  %gather.3 = s32[8]{0} gather(a, b), metadata={op_name="jit(join_count)/op:Join/jit(join_ranges)/k:join_ranges/s:key_at/gather"}
  %sort.1 = u64[8]{0} sort(c), metadata={op_name="jit(join_count)/k:packed_argsort/sort"}
  %mul.2 = s64[8]{0} multiply(a, a), metadata={op_name="jit(mesh_chain_Aggregate_Project)/op0:Aggregate/shard_map/op1:Project/mul"}
}
"""
    # the innermost operator wins, as a chip's trace is read
    assert program_catalog.scope_map_from_hlo(hlo) == {
        "gather.3": "op:Join", "mul.2": "op1:Project"}
    assert program_catalog._MODULE_RE.match(hlo).group(1) == "jit_join_count"


def test_scope_maps_join_by_module_not_across_programs():
    """Two programs hold an instruction of one name under different
    operators: joined by the module the event names, neither takes the
    other's scope (the union over all programs did)."""
    cat = program_catalog.ProgramCatalog(max_entries=4)

    class _Compiled:
        def __init__(self, module, scope):
            self.text = (
                f"HloModule {module}\nENTRY %main {{\n  %fusion.8 = f32[8]{{0}} "
                f'fusion(a), kind=kLoop, metadata={{op_name="jit(x)/{scope}/add"}}\n}}\n'
            )

        def as_text(self):
            return self.text

    cat.register(("a",), source="local", label="Join",
                 resolver=lambda: _Compiled("jit_join_count", "op:Join"))
    cat.register(("b",), source="local", label="Aggregate",
                 resolver=lambda: _Compiled("jit_chain_Aggregate", "op0:Aggregate"))
    maps = cat.scope_maps()
    assert maps == {"jit_join_count": {"fusion.8": "op:Join"},
                    "jit_chain_Aggregate": {"fusion.8": "op0:Aggregate"}}
    from trino_tpu import kernel_profile

    events = [
        {"name": "fusion.8", "dur_us": 3.0, "hlo_op": "fusion.8",
         "hlo_module": "jit_join_count"},
        {"name": "fusion.8", "dur_us": 5.0, "hlo_op": "fusion.8",
         "hlo_module": "jit_chain_Aggregate"},
        {"name": "fusion.8", "dur_us": 7.0, "hlo_op": "fusion.8",
         "hlo_module": "jit_somebody_elses"},
    ]
    out = kernel_profile.attribute(events, maps)
    assert out["scopes"] == {"op0:Aggregate": 5.0, "op:Join": 3.0}
    assert out["operators"] == {"unscoped": 7.0, "Aggregate": 5.0, "Join": 3.0}
    assert out["unscoped_us"] == out["unattributed_us"] == 7.0
    assert out["matched_events"] == 2 and out["events"] == 3
    assert cat.snapshot()[0]["module"] in maps


# ---------------------------------------------------------------------------
# end-to-end: query -> catalog entry -> system table / EXPLAIN VERBOSE
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def runner():
    from trino_tpu.connectors.system import SystemConnector

    r = QueryRunner.tpch("tiny")
    r.metadata.register_catalog("system", SystemConnector(runner=r))
    return r


def test_query_registers_catalog_entry_with_cost_and_memory(runner):
    program_catalog.CATALOG.clear()
    runner.execute(
        "select l_returnflag, sum(l_quantity) from lineitem "
        "where l_quantity > 25 group by l_returnflag"
    )
    snap = program_catalog.CATALOG.snapshot()
    assert snap, "no catalog entry registered for the fused chain"
    chains = [e for e in snap if "Aggregate" in e["label"]]
    assert chains, snap
    e = chains[0]
    # cost_analysis + memory_analysis populated via the lazy resolver
    assert e["flops"] and e["flops"] > 0
    assert e["bytes_accessed"] and e["bytes_accessed"] > 0
    assert e["temp_bytes"] is not None and e["temp_bytes"] > 0
    assert e["argument_bytes"] > 0
    assert e["hlo_hash"] and e["hlo_lines"] > 0
    # named scopes extracted from the compiled HLO (fusions included)
    assert e["scope_count"] > 0
    assert e["compile_s"] > 0
    assert e["source"] == "local"


def test_repeat_query_counts_hits_not_new_entries(runner):
    program_catalog.CATALOG.clear()
    sql = "select count(*) from orders where o_totalprice > 1000"
    runner.execute(sql)
    n1 = len(program_catalog.CATALOG)
    snap1 = {
        e["program_id"]: e["hits"]
        for e in program_catalog.CATALOG.snapshot(resolve=False)
    }
    runner.execute(sql)
    assert len(program_catalog.CATALOG) == n1
    snap2 = {
        e["program_id"]: e["hits"]
        for e in program_catalog.CATALOG.snapshot(resolve=False)
    }
    assert any(snap2[p] > snap1[p] for p in snap1), (snap1, snap2)


def test_system_runtime_programs_table(runner):
    program_catalog.CATALOG.clear()
    runner.execute("select count(*) from lineitem where l_tax > 0.02")
    res = runner.execute(
        "select program_id, source, operators, flops, temp_bytes, "
        "bytes_accessed, compile_ms from system.runtime.programs"
    )
    assert res.rows, "system.runtime.programs is empty"
    by_label = {r[2]: r for r in res.rows}
    chain = next(
        (r for lbl, r in by_label.items() if "Filter" in lbl), None
    )
    assert chain is not None, res.rows
    assert chain[3] > 0  # flops
    assert chain[5] > 0  # bytes_accessed


def test_chain_cost_reads_through_catalog(runner):
    program_catalog.CATALOG.clear()
    runner.execute("select count(*) from customer where c_acctbal > 0")
    ex = runner.executor
    keys = [k for k in ex._chain_avals if k[0] == "chain"]
    assert keys
    cost = ex.chain_cost(keys[-1])
    assert cost is not None and cost["flops"] > 0
    # the catalog entry served it (or was re-registered on the fly)
    assert program_catalog.CATALOG.cost(keys[-1]) == cost
    # memoized per executor: second read returns the same dict
    assert ex.chain_cost(keys[-1]) is cost


def test_chain_cost_survives_catalog_eviction(runner):
    program_catalog.CATALOG.clear()
    runner.execute("select count(*) from part where p_size > 20")
    ex = runner.executor
    keys = [k for k in ex._chain_avals if k[0] == "chain"]
    assert keys
    key = keys[-1]
    ex._chain_costs.pop(key, None)
    program_catalog.CATALOG.clear()  # simulate eviction
    cost = ex.chain_cost(key)
    assert cost is not None and cost["flops"] > 0
    # the fallback re-registered the program
    assert program_catalog.CATALOG.entry_for(key) is not None


def test_explain_analyze_verbose_attributes_hlo_scopes(runner):
    sql = (
        "select l_returnflag, l_linestatus, sum(l_quantity), "
        "avg(l_extendedprice) from lineitem "
        "where l_shipdate <= date '1998-09-02' "
        "group by l_returnflag, l_linestatus order by l_returnflag"
    )
    runner.execute(sql)  # warm: compiles happen outside the capture
    res = runner.execute("explain analyze verbose " + sql)
    text = "\n".join(r[0] for r in res.rows)
    assert "Kernel profile (device time by HLO scope):" in text
    # named plan-operator scopes INSIDE the fused program, with time
    assert "op" in text
    scope_lines = [
        line for line in text.splitlines()
        if line.strip().startswith("op") and " ms " in line
    ]
    assert scope_lines, text
    # the dispatched programs' catalog entries render too
    assert "Program " in text and "flops" in text
    # the attribution also lands on the result object
    assert res.kernel_profile and res.kernel_profile["scopes"]
    assert any(
        k.split(":")[1] in ("Aggregate", "Filter", "Sort", "Project")
        for k in res.kernel_profile["scopes"]
    )


def test_plain_explain_analyze_unchanged(runner):
    res = runner.execute(
        "explain analyze select count(*) from region"
    )
    text = "\n".join(r[0] for r in res.rows)
    assert "Kernel profile" not in text
    assert res.kernel_profile is None


def test_kernel_profile_session_property(runner):
    sql = "select count(*) from lineitem where l_discount > 0.05"
    runner.execute(sql)  # warm
    saved = dict(runner.session.properties)
    try:
        runner.session.properties["kernel_profile"] = "ON"
        res = runner.execute(sql)
        assert res.kernel_profile is not None
        assert res.kernel_profile["trigger"] == "session"
        # warm dispatch still produces attributable device events
        assert res.kernel_profile["scopes"], res.kernel_profile
    finally:
        runner.session.properties.clear()
        runner.session.properties.update(saved)
    # OFF by default: no capture
    res = runner.execute(sql)
    assert res.kernel_profile is None


def test_kernel_profile_auto_attaches_to_slow_query_log(
    runner, tmp_path
):
    from trino_tpu.events import StructuredLogListener

    sql = "select count(*) from orders where o_shippriority = 0"
    runner.execute(sql)  # warm
    path = tmp_path / "slow.jsonl"
    saved = dict(runner.session.properties)
    runner.metadata.event_listeners = [
        StructuredLogListener(path=str(path))
    ]
    try:
        runner.session.properties["kernel_profile"] = "AUTO"
        runner.session.properties["slow_query_log_threshold"] = "1ms"
        runner.execute(sql)
    finally:
        runner.session.properties.clear()
        runner.session.properties.update(saved)
        runner.metadata.event_listeners = []
    recs = [
        json.loads(line)
        for line in path.read_text().splitlines() if line
    ]
    slow = [r for r in recs if r.get("event") == "slow_query"]
    assert slow and "kernel_profile" in slow[0], slow
    assert "scopes" in slow[0]["kernel_profile"]


def test_nested_capture_is_noop():
    from trino_tpu import kernel_profile

    with kernel_profile.Capture(trigger="outer") as outer:
        assert outer.active
        with kernel_profile.Capture(trigger="inner") as inner:
            assert not inner.active
        assert inner.summary() is None
    assert not outer.active


def test_diagnostics_bundle_snapshots_programs(runner):
    from trino_tpu import diagnostics

    program_catalog.CATALOG.clear()
    runner.execute("select count(*) from nation")
    bundle = diagnostics.build_bundle("q-test", error="Boom: x")
    assert isinstance(bundle["programs"], list)
    assert bundle["programs"], "catalog snapshot missing from bundle"
    assert "program_id" in bundle["programs"][0]


# ---------------------------------------------------------------------------
# fleet: POST /v1/profile on workers + sum-consistency vs PR 7 stats
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


def test_worker_programs_endpoint_after_query(fleet, workers):
    fleet.execute(QUERIES["q03"])
    listed = 0
    for uri in workers:
        with urllib.request.urlopen(
            f"{uri}/v1/programs", timeout=30
        ) as r:
            doc = json.loads(r.read())
        progs = doc["programs"]
        if not progs:
            continue
        listed += len(progs)
        with_cost = [p for p in progs if p.get("flops")]
        assert with_cost, progs
        assert any(
            p.get("temp_bytes") is not None for p in progs
        ), progs
        # detail endpoint serves the HLO text + scope map
        pid = with_cost[0]["program_id"]
        with urllib.request.urlopen(
            f"{uri}/v1/programs/{pid}", timeout=30
        ) as r:
            one = json.loads(r.read())
        assert one["program_id"] == pid
        assert one.get("hlo_text"), "detail endpoint missing HLO"
    assert listed > 0, "no worker registered any compiled program"


def test_fleet_profile_capture_sums_consistently_q03(fleet, workers):
    # warm: every worker compiles its q03 task programs before the
    # capture window, so the profile sees pure dispatch
    fleet.execute(QUERIES["q03"])

    out: dict[str, dict] = {}

    def capture(uri):
        req = urllib.request.Request(
            f"{uri}/v1/profile?duration_ms=6000", method="POST",
            data=b"",
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            out[uri] = json.loads(r.read())

    threads = [
        threading.Thread(target=capture, args=(uri,))
        for uri in workers
    ]
    for t in threads:
        t.start()
    time.sleep(0.5)  # both captures open before work starts
    res = fleet.execute(QUERIES["q03"])
    for t in threads:
        t.join(timeout=90)
    assert out, "no worker returned a profile"

    scope_us = 0.0
    scoped_ops = set()
    for uri, prof in out.items():
        assert "error" not in prof, (uri, prof)
        for scope, us in (prof.get("scopes") or {}).items():
            assert scope.startswith("op"), scope
            scoped_ops.add(scope.split(":", 1)[1])
            scope_us += us
    # named scopes attributed on at least one worker
    assert scope_us > 0, out
    assert scoped_ops & {"Filter", "Aggregate", "Project", "Sort",
                         "TopN", "Limit"}, scoped_ops

    # sum-consistency vs the operator self-times PR 7 reports: device
    # time attributed inside the window cannot exceed the workers'
    # total operator self time by more than a generous bound (host
    # bookkeeping dominates self_ms on CPU, so device <= self; the
    # slack absorbs profiler overhead and unrelated dispatches that
    # landed in the window)
    self_ms = sum(
        op.get("self_ms", 0.0)
        for t in res.task_stats if t["state"] == "FINISHED"
        for op in (t.get("operator_stats") or [])
    )
    assert self_ms > 0
    assert scope_us / 1e3 <= self_ms * 3.0 + 250.0, (
        scope_us, self_ms, out,
    )
